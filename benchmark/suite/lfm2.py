"""What the per-layer metrics of the ``lfm2-8b-a1b`` cell share: device time
under the scope of the convolution mixer (``block<i>/conv``; its gate's share,
``block<i>/conv/gate``, kept apart for whoever reads a trace), the busiest expert by the program's own counts
(``moe.STEP_COUNTS``) and the rooflines of ``roofline_lfm2.py``. Every
function returns ``None`` where the trace or the program has nothing to read
(a program without the scope or the counts, as a parent tree)."""

from __future__ import annotations

import functools

import hybrid
import moe
import roofline
import roofline_lfm2
import scopes
import xplane


def conv_scope(op_name: str):
    """``"gate"`` for ``.../block2/conv/gate/mul``, ``"proj"`` for anything
    else under ``block<i>/conv``, ``None`` outside it."""
    path = scopes.WRAPPERS.sub(
        "", op_name.split(";")[0].rstrip(":")).split("/")
    for at, part in enumerate(path[:-2]):
        if hybrid.BLOCK.fullmatch(part):
            if path[at + 1] != "conv":
                return None
            return "gate" if "gate" in path[at + 2:-1] else "proj"
    return None


def conv_seconds(view: dict):
    """``{"gate", "proj"}``: device seconds per profiled step under the conv
    mixers' scopes, per device used."""
    reduced = view.get("trace")
    if not reduced or "profiled_steps" not in view:
        return None
    path = scopes._trace_file(view)
    window = [iv for n, ivs in reduced["annotations"].items()
              if n.startswith(xplane.BENCH_SPAN) for iv in ivs]
    if path is None or not window:
        return None
    try:
        out = _conv_in(path, min(a for a, _ in window) * 1e9,
                       max(b for _, b in window) * 1e9, view.get("chips", 1))
    except ImportError:
        return None
    if out is None:
        return None
    return {k: v / view["profiled_steps"] for k, v in out.items()}


@functools.lru_cache(maxsize=1)       # one trace a run, several readers
def _conv_in(path: str, w0: float, w1: float, chips: int):
    devices = scopes.read_ops(path)
    used = [devices[n] for n in sorted(devices)[:chips] if devices[n]]
    if not used:
        return None
    out, found = {"gate": 0.0, "proj": 0.0}, False
    for ops in used:
        for hlo, op_name, s, e in ops:
            if e <= w0 or s >= w1:
                continue
            if xplane.base_name(xplane.short_name(hlo)) in xplane.CONTAINERS:
                continue
            scope = conv_scope(op_name)
            if scope is not None:
                found = True
                out[scope] += (min(e, w1) - max(s, w0)) / 1e9 / len(used)
    return out if found else None


def conv_mixer_ms(view: dict):
    seconds = conv_seconds(view)
    return None if seconds is None else sum(seconds.values()) * 1e3


def mixer_roofline_pct(view: dict):
    """The least time the chip could take for every conv layer's mixer,
    forward and backward (the larger of the projections' operations over
    the bf16 peak and the bytes no schedule avoids over the HBM peak, each
    direction), over the device time under ``block<i>/conv``. The region is
    closed: XLA fuses the gate's products across the ``gate`` scope into the
    projections' matmuls, never out of the mixer."""
    seconds, cfg = conv_seconds(view), view["config"]
    if seconds is None or "conv_L_cache" not in cfg:
        return None
    rows = view["batch"] // view["chips"]
    fl = roofline_lfm2.conv_flops(cfg, rows, view["seq_len"])
    by = roofline_lfm2.conv_bytes(cfg, rows, view["seq_len"], 2)
    least = sum(roofline.roofline_seconds(fl[k], by[k], view["peaks"])[0]
                for k in ("fwd", "bwd"))
    return 100.0 * roofline_lfm2.layers(cfg, "conv") * least \
        / sum(seconds.values())


def attention_roofline_pct(view: dict):
    """The causal flash launches against their roofline at this
    configuration's heads, over the ``full_attention`` layers."""
    cfg = view["config"]
    if "conv_L_cache" not in cfg:
        return None
    measured = hybrid.kernel_seconds(view, "full_fwd", "full_bwd")
    n = roofline_lfm2.layers(cfg, "full_attention")
    if measured is None or not n:
        return None
    rows = view["batch"] // view["chips"]
    fl = roofline_lfm2.attention_flops(cfg, rows, view["seq_len"])
    by = roofline_lfm2.attention_bytes(cfg, rows, view["seq_len"], 2)
    least = sum(roofline.roofline_seconds(fl[k], by[k], view["peaks"])[0]
                for k in ("fwd", "bwd"))
    return 100.0 * least * n / measured


def expert_load_max(view: dict):
    """Over the profiled steps and the expert layers, the largest count of
    tokens that chose one expert, over the even share ``tokens * k / E``:
    the straggler among the groups of the grouped products (1 under a
    perfectly even routing)."""
    import numpy as np
    n, cfg = view.get("profiled_steps"), view["config"]
    if not n or len(moe.STEP_COUNTS) < n or "conv_L_cache" not in cfg:
        return None
    even = view["batch"] * view["seq_len"] * cfg["num_experts_per_tok"] \
        / cfg["num_experts"]
    return max(float(np.asarray(count).max())
               for step in list(moe.STEP_COUNTS)[-n:] for count in step) / even


def mfu_pct(view: dict):
    cfg = view["config"]
    if "tokens" not in view or "conv_L_cache" not in cfg:
        return None
    per_token = roofline_lfm2.train_flops_per_token(cfg, view["seq_len"])
    rate = view["tokens"] / view["window_s"]
    return 100.0 * rate * per_token / (
        view["chips"] * view["peaks"]["bf16_flops_per_s"])
