"""What the per-layer metrics of the ``joyai-llm-flash`` cell share: the flash
launches of its six latent-attention layers against ``roofline.py``'s counts
at their two widths, device time under ``block<i>/mla`` outside any kernel
(``ling.scope_ms``: the prediction block's layer is ``mtp0/block<L>/mla``
and counts as a layer), device time under the prediction block's scopes
(``mtp0/...`` and the loss's ``mtp``), the bytes of its logits as the program
counted them, and the whole step against the bf16 peak by
``roofline_joyai.py``. Every function returns ``None`` where the trace or the
program has nothing to read (a program without the block, the scopes or the
counter, as a parent tree)."""

from __future__ import annotations

import functools

import hybrid
import ling
import moe
import roofline
import roofline_joyai
import roofline_kda
import scopes
import xplane

# what the program's model said of its prediction block when the step was
# traced (``profiler.get_launch_stats("mtp")``; ``systems/joyai.py`` puts it
# here)
MTP_STATS = {}


def _mine(view: dict) -> bool:
    return "mtp_loss_weight" in view["config"]


def attn_roofline_pct(view: dict):
    """The least time the chip could take for every layer's flash launches,
    forward and backward (the larger of operations over the bf16 peak and
    bytes over the HBM peak, each direction), over their device time."""
    measured = hybrid.kernel_seconds(view, "full_fwd", "full_bwd")
    if measured is None or not _mine(view):
        return None
    cfg, rows = view["config"], view["batch"] // view["chips"]
    fl = roofline_kda.mla_flops(cfg, rows, view["seq_len"])
    by = roofline_kda.mla_bytes(cfg, rows, view["seq_len"], 2)
    least = sum(roofline.roofline_seconds(fl[k], by[k], view["peaks"])[0]
                for k in ("fwd", "bwd"))
    return 100.0 * least * roofline_joyai.attention_layers(cfg) / measured


def proj_ms(view: dict):
    return ling.scope_ms(view, "mla") if _mine(view) else None


def scope_of(op_name: str):
    """``"mtp_head_loss"`` for an operation traced under ``mtp0/head`` or
    under the loss's ``mtp`` scope, ``"mtp"`` for the rest of ``mtp0``;
    ``None`` otherwise."""
    path = scopes.WRAPPERS.sub(
        "", op_name.split(";")[0].rstrip(":")).split("/")[:-1]
    if "mtp0" in path:
        at = path.index("mtp0")
        return "mtp_head_loss" if path[at + 1:at + 2] == ["head"] else "mtp"
    if "loss" in path and "mtp" in path[path.index("loss"):]:
        return "mtp_head_loss"
    return None


def scope_ms(view: dict, *which: str):
    """Device ms per profiled step under the scopes ``which``, kernels
    INCLUDED (the block's flash launches and grouped products are its
    work), per device used."""
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
    if seconds is None:
        return None
    return sum(seconds.get(w, 0.0) for w in which) \
        / view["profiled_steps"] * 1e3


@functools.lru_cache(maxsize=1)       # one trace a run, several readers
def _scopes_in(path: str, w0: float, w1: float, chips: int):
    devices = scopes.read_ops(path)
    used = [devices[n] for n in sorted(devices)[:chips] if devices[n]]
    out = {}
    for ops in used:
        for hlo, op_name, s, e in ops:
            if e <= w0 or s >= w1:
                continue
            if xplane.base_name(xplane.short_name(hlo)) in xplane.CONTAINERS:
                continue
            scope = scope_of(op_name)
            if scope is not None:
                out[scope] = out.get(scope, 0.0) \
                    + (min(e, w1) - max(s, w0)) / 1e9 / len(used)
    return out or None


def logits_gb(view: dict):
    """GB of the prediction block's float32 logits, as the model counted
    them from their shape where the step was traced."""
    nbytes = MTP_STATS.get("logits_bytes")
    return nbytes / 1e9 if nbytes and _mine(view) else None


def mfu_pct(view: dict):
    if "tokens" not in view or not _mine(view):
        return None
    per_token = roofline_joyai.train_flops_per_token(
        view["config"], view["seq_len"], moe.held_per_token(view))
    rate = view["tokens"] / view["window_s"]
    return 100.0 * rate * per_token / (
        view["chips"] * view["peaks"]["bf16_flops_per_s"])
