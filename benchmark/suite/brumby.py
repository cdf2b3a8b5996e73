"""What the per-layer metrics of the ``brumby-14b-base`` cell share: device
time of the retention kernels by the names their ``pallas_call`` carries
(``retention_fwd``, ``retention_bwd*``), device time under the mixer's scope
outside them (``block<i>/retention``: projections, q/k norm, rotary
positions, the gate, the copies), the bytes of chunk-start state the
program says it keeps, and the rooflines of ``roofline_retention.py``. Every
function returns ``None`` where the trace or the program has nothing to
read (a program without the kernels, the scope or the counter, as a parent
tree)."""

from __future__ import annotations

import functools
import re

import hybrid
import roofline
import roofline_retention
import scopes
import xplane

M = "^" + xplane.MOSAIC_PREFIX
KERNELS = {"fwd": M + r"retention_fwd(\.\d+)?$",
           "bwd": M + r"retention_bwd\w*(\.\d+)?$"}
# what the program's op reported when the step was traced
# (``profiler.get_retention_stats()``; ``systems/brumby.py`` puts it here)
RETENTION_STATS = {}


def _mine(view: dict) -> bool:
    return "retention_degree" in view["config"]


def kernel_launches(view: dict, which: str):
    """``(device seconds per profiled step, launches per step)`` of the
    forward or the backward kernel. A launch site is one HLO instruction
    (``tpu_custom_call/retention_fwd.3``); a model that runs a block again
    in its backward launches the forward twice a layer."""
    if "profiled_steps" not in view or "trace" not in view:
        return None
    rx = re.compile(KERNELS[which])
    found = [t for n, t in view["trace"]["op_s"].items() if rx.search(n)]
    if not found:
        return None
    return sum(found) / view["profiled_steps"], len(found)


def kernel_ms(view: dict, which: str):
    found = kernel_launches(view, which)
    return None if found is None else found[0] * 1e3


def kernel_roofline_pct(view: dict, which: str):
    """The least time the chip could take for ONE launch (the larger of the
    layer's operations over the bf16 peak and its bytes over the HBM peak)
    over the mean device time of a launch. Per launch, because the
    recomputed forward is a launch like the first: its time is the
    kernel's, though its operations count in no MFU."""
    found = kernel_launches(view, which)
    if found is None or not _mine(view):
        return None
    seconds, launches = found
    cfg, rows = view["config"], view["batch"] // view["chips"]
    fl = roofline_retention.retention_flops(cfg, rows, view["seq_len"])
    by = roofline_retention.retention_bytes(cfg, rows, view["seq_len"], 2)
    least = roofline.roofline_seconds(fl[which], by[which], view["peaks"])[0]
    return 100.0 * least * launches / seconds


def mixer_scope(op_name: str) -> bool:
    """Whether the operation was traced under ``block<i>/retention``."""
    path = scopes.WRAPPERS.sub(
        "", op_name.split(";")[0].rstrip(":")).split("/")
    for at, part in enumerate(path[:-2]):
        if hybrid.BLOCK.fullmatch(part):
            return path[at + 1] == "retention"
    return False


def mixer_ms(view: dict):
    """Device ms per profiled step under ``block<i>/retention`` OUTSIDE the
    Mosaic kernels, per device used."""
    reduced = view.get("trace")
    if not reduced or "profiled_steps" not in view:
        return None
    path = scopes._trace_file(view)
    window = [iv for n, ivs in reduced["annotations"].items()
              if n.startswith(xplane.BENCH_SPAN) for iv in ivs]
    if path is None or not window:
        return None
    try:
        seconds = _mixer_in(path, min(a for a, _ in window) * 1e9,
                            max(b for _, b in window) * 1e9,
                            view.get("chips", 1))
    except ImportError:
        return None
    return None if seconds is None else seconds / view["profiled_steps"] * 1e3


@functools.lru_cache(maxsize=1)
def _mixer_in(path: str, w0: float, w1: float, chips: int):
    devices = scopes.read_ops(path)
    used = [devices[n] for n in sorted(devices)[:chips] if devices[n]]
    total, found = 0.0, False
    for ops in used:
        for hlo, op_name, s, e in ops:
            if e <= w0 or s >= w1:
                continue
            short = xplane.short_name(hlo)
            if xplane.base_name(short) in xplane.CONTAINERS \
                    or short.startswith(xplane.MOSAIC_PREFIX):
                continue
            if mixer_scope(op_name):
                found = True
                total += (min(e, w1) - max(s, w0)) / 1e9 / len(used)
    return total if found else None


def state_gb(view: dict):
    """GB of chunk-start state ONE layer's forward keeps for its backward:
    the op's own count of its newest launch where the step was traced (from
    the launch's shapes; no reading of device memory). A model that
    recomputes a block at a time holds one layer's at a time, so this is
    what is live beside the parameters; a model that did not would hold it
    once a layer."""
    kept = RETENTION_STATS.get("state_bytes_kept")
    if not kept or not _mine(view):
        return None
    return kept / 1e9


def mfu_pct(view: dict):
    if "tokens" not in view or not _mine(view):
        return None
    per_token = roofline_retention.train_flops_per_token(view["config"],
                                                         view["seq_len"])
    rate = view["tokens"] / view["window_s"]
    return 100.0 * rate * per_token / (
        view["chips"] * view["peaks"]["bf16_flops_per_s"])
