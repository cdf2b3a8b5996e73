"""What the per-layer metrics of the ``ling-3.0-flash`` cell share: device
time of the delta-rule kernels by the names their ``pallas_call`` carries
(``kda_fwd``, ``kda_bwd*``), device time under the two mixers' scopes outside
any kernel (``block<i>/kda``, ``block<i>/mla``) and under the router's group
choice (``block<i>/moe/route/groups``), the flash launches (in this model
the latent-attention layer's alone), the bytes of chunk-start state the
program says it keeps, and the rooflines of ``roofline_kda.py``. Every
function returns ``None`` where the trace or the program has nothing to
read (a program without the kernels, the scopes or the counter, as a parent
tree)."""

from __future__ import annotations

import functools
import re

import hybrid
import moe
import roofline
import roofline_kda
import scopes
import xplane

M = "^" + xplane.MOSAIC_PREFIX
KERNELS = {"fwd": M + r"kda_fwd(\.\d+)?$", "bwd": M + r"kda_bwd\w*(\.\d+)?$"}
# what the program's op reported when the step was traced
# (``profiler.get_kda_stats()``; ``systems/ling.py`` puts it here)
KDA_STATS = {}


def _mine(view: dict) -> bool:
    return "kda_lower_bound" in view["config"]


def kernel_launches(view: dict, which: str):
    """``(device seconds per profiled step, launches per step)`` of the
    forward or the backward kernel; a launch site is one HLO instruction
    (``tpu_custom_call/kda_fwd.3``)."""
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
    recurrence's operations over the bf16 peak and its bytes over the HBM
    peak) over the mean device time of a launch."""
    found = kernel_launches(view, which)
    if found is None or not _mine(view):
        return None
    seconds, launches = found
    cfg, rows = view["config"], view["batch"] // view["chips"]
    fl = roofline_kda.kda_flops(cfg, rows, view["seq_len"])
    by = roofline_kda.kda_bytes(cfg, rows, view["seq_len"], 2)
    least = roofline.roofline_seconds(fl[which], by[which], view["peaks"])[0]
    return 100.0 * least * launches / seconds


def scope_of(op_name: str):
    """``"kda"`` / ``"mla"`` for an operation traced under ``block<i>/kda``
    or ``block<i>/mla``, ``"groups"`` under ``moe/route/groups``; ``None``
    otherwise."""
    path = scopes.WRAPPERS.sub(
        "", op_name.split(";")[0].rstrip(":")).split("/")
    for at, part in enumerate(path[:-2]):
        if hybrid.BLOCK.fullmatch(part):
            if path[at + 1] in ("kda", "mla"):
                return path[at + 1]
            if path[at + 1:at + 4] == ["moe", "route", "groups"]:
                return "groups"
    return None


def scope_ms(view: dict, which: str):
    """Device ms per profiled step under the scope OUTSIDE the Mosaic
    kernels, per device used."""
    reduced = view.get("trace")
    if not reduced or "profiled_steps" not in view:
        return None
    path = scopes._trace_file(view)
    window = [iv for n, ivs in reduced["annotations"].items()
              if n.startswith(xplane.BENCH_SPAN) for iv in ivs]
    if path is None or not window:
        return None
    try:
        seconds = _scopes_in(path, min(a for a, _ in window) * 1e9,
                             max(b for _, b in window) * 1e9,
                             view.get("chips", 1))
    except ImportError:
        return None
    if seconds is None or which not in seconds:
        return None
    return seconds[which] / view["profiled_steps"] * 1e3


@functools.lru_cache(maxsize=1)       # one trace a run, several readers
def _scopes_in(path: str, w0: float, w1: float, chips: int):
    devices = scopes.read_ops(path)
    used = [devices[n] for n in sorted(devices)[:chips] if devices[n]]
    out = {}
    for ops in used:
        for hlo, op_name, s, e in ops:
            if e <= w0 or s >= w1:
                continue
            short = xplane.short_name(hlo)
            if xplane.base_name(short) in xplane.CONTAINERS \
                    or short.startswith(xplane.MOSAIC_PREFIX):
                continue
            scope = scope_of(op_name)
            if scope is not None:
                out[scope] = out.get(scope, 0.0) \
                    + (min(e, w1) - max(s, w0)) / 1e9 / len(used)
    return out or None


def attn_roofline_pct(view: dict):
    measured = hybrid.kernel_seconds(view, "full_fwd", "full_bwd")
    if measured is None or not _mine(view):
        return None
    cfg, rows = view["config"], view["batch"] // view["chips"]
    fl = roofline_kda.mla_flops(cfg, rows, view["seq_len"])
    by = roofline_kda.mla_bytes(cfg, rows, view["seq_len"], 2)
    least = sum(roofline.roofline_seconds(fl[k], by[k], view["peaks"])[0]
                for k in ("fwd", "bwd"))
    return 100.0 * least * roofline_kda.layers(cfg, "mla") / measured


def state_gb(view: dict):
    """GB of chunk-start state the ``kda`` layers' forwards keep for their
    backwards: the op's own count of its newest launch where the step was
    traced (from the launch's shapes; no reading of device memory) times the
    ``kda`` layers, all of which a step that recomputes nothing holds at
    once."""
    kept = KDA_STATS.get("state_bytes_kept")
    if not kept or not _mine(view):
        return None
    return kept * roofline_kda.layers(view["config"], "kda") / 1e9


def mfu_pct(view: dict):
    if "tokens" not in view or not _mine(view):
        return None
    per_token = roofline_kda.train_flops_per_token(
        view["config"], view["seq_len"], moe.held_per_token(view))
    rate = view["tokens"] / view["window_s"]
    return 100.0 * rate * per_token / (
        view["chips"] * view["peaks"]["bf16_flops_per_s"])
