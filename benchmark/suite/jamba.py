"""What the per-layer metrics of the ``jamba2-3b`` cell share: the scan and
flash launches a step runs, COUNTED in the trace by their HLO instruction
names (a model that recomputes its blocks launches a forward kernel twice a
recomputed layer), against ``roofline_hybrid.scan_bytes`` and
``roofline_jamba.py``'s attention counts; device time of the recomputed
forwards by the scope ``jax.checkpoint`` gives them
(``.../rematted_computation/block<i>/...``); the bytes of the float32 logits
as the system counted them; and the whole step against the bf16 peak. Every
function returns ``None`` where the trace or the program has nothing to
read (a program without the kernels, the scope or the counters, as a parent
tree)."""

from __future__ import annotations

import functools
import re

import hybrid
import roofline
import roofline_hybrid
import roofline_jamba
import scopes
import xplane

# what the program said when the step was traced (``systems/jamba.py`` puts
# them here): ``profiler.get_launch_stats("ssm_scan")``,
# ``profiler.get_remat_stats()`` and the head's logits by shape
SCAN_STATS, REMAT_STATS, HEAD_STATS = {}, {}, {}
REMAT_SCOPE = "rematted_computation"


def _mine(view: dict) -> bool:
    return "attn_layer_period" in view["config"]


def kernel_launches(view: dict, which: str):
    """``(device seconds per profiled step, launches per step)`` of a kernel
    group of ``hybrid.KERNELS``. A launch site is one HLO instruction
    (``tpu_custom_call/ssm_scan_fwd.3``)."""
    if "profiled_steps" not in view or "trace" not in view:
        return None
    rx = re.compile(hybrid.KERNELS[which])
    found = [t for n, t in view["trace"]["op_s"].items() if rx.search(n)]
    if not found:
        return None
    return sum(found) / view["profiled_steps"], len(found)


def scan_roofline_pct(view: dict, which: str):
    """Bytes ONE launch of the scan cannot avoid
    (``roofline_hybrid.scan_bytes``) times the launches the trace shows a
    step, over the HBM peak, over the kernel's device time (``which``:
    ``"fwd"`` or ``"bwd"``). By launches seen and not by mamba layers: the
    recomputed forward is a launch like the first, and its time is in the
    denominator."""
    found = kernel_launches(view, "ssm_" + which)
    if found is None or not _mine(view):
        return None
    seconds, launches = found
    rows = view["batch"] // view["chips"]
    nbytes = roofline_hybrid.scan_bytes(view["config"], rows,
                                        view["seq_len"], 2)[which]
    return 100.0 * launches * nbytes / view["peaks"]["hbm_bytes_per_s"] \
        / seconds


def attn_roofline_pct(view: dict):
    """The least time the chip could take for the flash launches the trace
    shows a step (``flash_fwd``, the recomputed one too, and
    ``flash_bwd_fused``; the larger of operations over the bf16 peak and
    bytes over the HBM peak, each direction) at 20-on-1 heads of 128 by
    visible pairs, over their device time."""
    fwd, bwd = (kernel_launches(view, w) for w in ("full_fwd", "full_bwd"))
    if fwd is None or bwd is None or not _mine(view):
        return None
    cfg, rows = view["config"], view["batch"] // view["chips"]
    fl = roofline_jamba.attention_flops(cfg, rows, view["seq_len"])
    by = roofline_jamba.attention_bytes(cfg, rows, view["seq_len"], 2)
    least = {k: roofline.roofline_seconds(fl[k], by[k], view["peaks"])[0]
             for k in ("fwd", "bwd")}
    return 100.0 * (least["fwd"] * fwd[1] + least["bwd"] * bwd[1]) \
        / (fwd[0] + bwd[0])


def rematted(op_name: str) -> bool:
    """Whether the operation was traced inside a recomputed block:
    ``jit(step)/transpose(jvp(HybridDecoderLM))/checkpoint/
    rematted_computation/block3/mamba/...``."""
    path = scopes.WRAPPERS.sub(
        "", op_name.split(";")[0].rstrip(":")).split("/")[:-1]
    return REMAT_SCOPE in path \
        and any(hybrid.BLOCK.fullmatch(p)
                for p in path[path.index(REMAT_SCOPE):])


def remat_ms(view: dict):
    """Device ms per profiled step of the second forwards of the recomputed
    blocks, their kernels INCLUDED (the scan's and the flash forward's
    second launches are most of it), per device used."""
    reduced = view.get("trace")
    if not reduced or "profiled_steps" not in view or not _mine(view):
        return None
    path = scopes._trace_file(view)
    window = [iv for n, ivs in reduced["annotations"].items()
              if n.startswith(xplane.BENCH_SPAN) for iv in ivs]
    if path is None or not window:
        return None
    try:
        seconds = _rematted_in(path, min(a for a, _ in window) * 1e9,
                               max(b for _, b in window) * 1e9,
                               view.get("chips", 1))
    except ImportError:
        return None
    return None if seconds is None else seconds / view["profiled_steps"] * 1e3


@functools.lru_cache(maxsize=1)
def _rematted_in(path: str, w0: float, w1: float, chips: int):
    devices = scopes.read_ops(path)
    used = [devices[n] for n in sorted(devices)[:chips] if devices[n]]
    total, found = 0.0, False
    for ops in used:
        for hlo, op_name, s, e in ops:
            if e <= w0 or s >= w1:
                continue
            if xplane.base_name(xplane.short_name(hlo)) in xplane.CONTAINERS:
                continue
            if rematted(op_name):
                found = True
                total += (min(e, w1) - max(s, w0)) / 1e9 / len(used)
    return total if found else None


def logits_gb(view: dict):
    """GB of the head's float32 logits, as the system counted them from the
    model's vocabulary and the placed batch."""
    nbytes = HEAD_STATS.get("logits_bytes")
    return nbytes / 1e9 if nbytes and _mine(view) else None


def mfu_pct(view: dict):
    if "tokens" not in view or not _mine(view):
        return None
    per_token = roofline_jamba.train_flops_per_token(view["config"],
                                                     view["seq_len"])
    rate = view["tokens"] / view["window_s"]
    return 100.0 * rate * per_token / (
        view["chips"] * view["peaks"]["bf16_flops_per_s"])
