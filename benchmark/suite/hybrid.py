"""What the per-layer metrics of a decoder-hybrid-decoder cell share: device
time of the kernels by the names their ``pallas_call`` carries, device time
by the kind-named scope inside ``block<i>`` (``block3/attn_window/...``),
and the rooflines of ``roofline_hybrid.py``. Every function returns ``None``
where the trace or the program has nothing to read."""

from __future__ import annotations

import functools
import re

import roofline
import roofline_hybrid
import scopes
import xplane

M = "^" + xplane.MOSAIC_PREFIX
# HLO instruction names: tpu_custom_call/<pallas name>[.<n>]
KERNELS = {
    "ssm_fwd": M + r"ssm_scan_fwd(\.\d+)?$",
    "ssm_bwd": M + r"ssm_scan_bwd(\.\d+)?$",
    "window_fwd": M + r"flash_fwd_window(\.\d+)?$",
    "window_bwd": M + r"flash_bwd_(dq|dkv)_window(\.\d+)?$",
    "full_fwd": M + r"flash_fwd(\.\d+)?$",
    "full_bwd": M + r"flash_bwd_(dq|dkv|fused)(\.\d+)?$",
}
BLOCK = re.compile(r"block\d+")
KINDS = ("mamba", "attn_window", "attn_full", "attn_cross", "gmu", "mlp")


def kernel_seconds(view: dict, *which: str):
    """Device seconds per profiled step of the named kernel groups."""
    if "profiled_steps" not in view or "trace" not in view:
        return None
    total = sum(xplane.kernel_seconds(view["trace"], KERNELS[w])
                for w in which)
    return total / view["profiled_steps"] if total else None


def kernel_ms(view: dict, *which: str):
    seconds = kernel_seconds(view, *which)
    return None if seconds is None else seconds * 1e3


def kind_of(op_name: str):
    """``"attn_window"`` for ``jit(step)/transpose(jvp(HybridDecoderLM))/
    block1/attn_window/qkv/dot_general``: the component after ``block<i>``
    where it is a kind; ``None`` otherwise (norms, residual adds)."""
    path = scopes.WRAPPERS.sub(
        "", op_name.split(";")[0].rstrip(":")).split("/")
    for at, part in enumerate(path[:-2]):
        if BLOCK.fullmatch(part):
            return path[at + 1] if path[at + 1] in KINDS else None
    return None


def kind_seconds(view: dict):
    """``{kind: device seconds per profiled step}`` of the operations traced
    under ``block<i>/<kind>``, Mosaic kernels left out (they are rows of
    their own), per device used."""
    reduced = view.get("trace")
    if not reduced or "profiled_steps" not in view:
        return None
    path = scopes._trace_file(view)
    window = [iv for n, ivs in reduced["annotations"].items()
              if n.startswith(xplane.BENCH_SPAN) for iv in ivs]
    if path is None or not window:
        return None
    try:
        out = _kinds_in(path, min(a for a, _ in window) * 1e9,
                        max(b for _, b in window) * 1e9,
                        view.get("chips", 1))
    except ImportError:
        return None
    if out is None:
        return None
    return {k: v / view["profiled_steps"] for k, v in out.items()}


@functools.lru_cache(maxsize=1)       # one trace a run, several readers
def _kinds_in(path: str, w0: float, w1: float, chips: int):
    devices = scopes.read_ops(path)
    used = [devices[n] for n in sorted(devices)[:chips] if devices[n]]
    if not used:
        return None
    out = dict.fromkeys(KINDS, 0.0)
    found = False
    for ops in used:
        for hlo, op_name, s, e in ops:
            if e <= w0 or s >= w1:
                continue
            short = xplane.short_name(hlo)
            if xplane.base_name(short) in xplane.CONTAINERS \
                    or short.startswith(xplane.MOSAIC_PREFIX):
                continue
            kind = kind_of(op_name)
            if kind is not None:
                found = True
                out[kind] += (min(e, w1) - max(s, w0)) / 1e9 / len(used)
    return out if found else None


def kind_ms(view: dict, *kinds: str):
    seconds = kind_seconds(view)
    return None if seconds is None else sum(seconds[k] for k in kinds) * 1e3


def _layers(cfg: dict, *kinds: str) -> int:
    return sum(k in kinds for k in cfg.get("layer_kinds", ()))


def attention_roofline_pct(view: dict, windowed: bool):
    """The least time the chip could take for one step's attention of that
    sort, forward and backward over its layers (the larger of operations
    over the bf16 peak and bytes over the HBM peak, each direction), over
    its kernels' device time."""
    measured = kernel_seconds(view, *(("window_fwd", "window_bwd") if windowed
                                      else ("full_fwd", "full_bwd")))
    cfg = view["config"]
    layers = _layers(cfg, "attn_window") if windowed \
        else _layers(cfg, "attn_full", "attn_cross")
    if measured is None or not layers:
        return None
    rows = view["batch"] // view["chips"]
    fl = roofline_hybrid.attention_flops(
        cfg, rows, view["seq_len"],
        cfg["sliding_window"] if windowed else None)
    by = roofline_hybrid.attention_bytes(cfg, rows, view["seq_len"], 2)
    least = sum(roofline.roofline_seconds(fl[k], by[k], view["peaks"])[0]
                for k in ("fwd", "bwd"))
    return 100.0 * least * layers / measured


def scan_roofline_pct(view: dict, which: str):
    """Bytes the scan cannot avoid over the HBM peak, over its kernel's
    device time (``which``: ``"fwd"`` or ``"bwd"``)."""
    measured = kernel_seconds(view, "ssm_" + which)
    cfg = view["config"]
    layers = _layers(cfg, "mamba")
    if measured is None or not layers:
        return None
    rows = view["batch"] // view["chips"]
    nbytes = roofline_hybrid.scan_bytes(cfg, rows, view["seq_len"], 2)[which]
    return 100.0 * layers * nbytes / view["peaks"]["hbm_bytes_per_s"] \
        / measured
