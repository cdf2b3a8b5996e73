"""What the per-layer metrics of a sparse grouped-query decoder's cell share:
device time by the scopes the program opens inside an expert layer
(``block<i>/moe/route|dispatch|experts|combine|shared|balance``), the
program's own count of the tokens that chose each expert in each step (the
expert layers' ``count`` state, handed here by the cell's trainer), and the
rooflines of ``roofline_moe.py``. Every function returns ``None`` where the
trace or the program has nothing to read (a program without the scopes or
the counter, as a parent tree)."""

from __future__ import annotations

import collections
import functools

import hybrid
import roofline
import roofline_moe
import scopes
import xplane

MOE = ("route", "dispatch", "experts", "combine", "shared", "balance")
# One entry a training step, newest last: every expert layer's ``count``
# state after the step (``(num_experts,)`` device arrays, the tokens that
# chose each expert), as ``systems/kexaone.py``'s trainer appends them. They
# are handles: nothing is fetched from the device before a reader asks.
STEP_COUNTS = collections.deque(maxlen=4096)


def scope_of(op_name: str):
    """``"experts"`` for ``.../block3/moe/.../block3/moe/experts/moe_tgmm/
    pallas_call``, ``"shared"`` for ``.../block1/moe/shared/down/dot_general``:
    the component after the LAST ``moe`` of the path, where it is one of the
    layer's scopes; ``None`` otherwise."""
    path = scopes.WRAPPERS.sub(
        "", op_name.split(";")[0].rstrip(":")).split("/")
    for at in range(len(path) - 2, -1, -1):
        if path[at] == "moe":
            return path[at + 1] if path[at + 1] in MOE else None
    return None


def scope_seconds(view: dict):
    """``{scope: device seconds per profiled step}`` of the operations traced
    under the scopes above, Mosaic kernels INCLUDED (the grouped products are
    the ``experts`` scope's work), per device used."""
    reduced = view.get("trace")
    if not reduced or "profiled_steps" not in view:
        return None
    path = scopes._trace_file(view)
    window = [iv for n, ivs in reduced["annotations"].items()
              if n.startswith(xplane.BENCH_SPAN) for iv in ivs]
    if path is None or not window:
        return None
    try:
        out = _scopes_in(path, min(a for a, _ in window) * 1e9,
                         max(b for _, b in window) * 1e9,
                         view.get("chips", 1))
    except ImportError:
        return None
    if out is None:
        return None
    return {k: v / view["profiled_steps"] for k, v in out.items()}


@functools.lru_cache(maxsize=1)       # one trace a run, several readers
def _scopes_in(path: str, w0: float, w1: float, chips: int):
    devices = scopes.read_ops(path)
    used = [devices[n] for n in sorted(devices)[:chips] if devices[n]]
    if not used:
        return None
    out = dict.fromkeys(MOE, 0.0)
    found = False
    for ops in used:
        for hlo, op_name, s, e in ops:
            if e <= w0 or s >= w1:
                continue
            if xplane.base_name(xplane.short_name(hlo)) in xplane.CONTAINERS:
                continue
            scope = scope_of(op_name)
            if scope is not None:
                found = True
                out[scope] += (min(e, w1) - max(s, w0)) / 1e9 / len(used)
    return out if found else None


def scope_ms(view: dict, *names: str):
    seconds = scope_seconds(view)
    return None if seconds is None else sum(seconds[n] for n in names) * 1e3


def newest_steps(view: dict):
    """``[[(pairs, active experts) of each step] of each expert layer]``:
    the (token, expert) pairs the held experts got, and how many of them got
    one, as the program counted them in the steps it ran last: the profiled
    ones in a traced run (nothing steps between them and the readers).
    ``None`` where no step handed its counts in, or none was profiled."""
    import numpy as np
    n = view.get("profiled_steps")
    held = view["config"].get("held_experts")
    if not n or not held or len(STEP_COUNTS) < n:
        return None
    steps = [[np.asarray(count)[held] for count in step]
             for step in list(STEP_COUNTS)[-n:]]
    return [[(float(step[at].sum()), int((step[at] > 0).sum()))
             for step in steps] for at in range(len(steps[0]))]


def held_per_token(view: dict):
    """Held experts a token and expert layer, the mean of ``newest_steps``."""
    layers = newest_steps(view)
    if not layers:
        return None
    pairs = [p for steps in layers for p, _ in steps]
    return sum(pairs) / len(pairs) / (view["batch"] * view["seq_len"])


def held_load_ratio(view: dict):
    """The (token, expert) pairs the held experts got in the profiled steps
    over their even share ``tokens * k * held / E``: 1 under a balanced
    routing, the work of the expert layers against what the cell's
    description promises."""
    per_token = held_per_token(view)
    if per_token is None or "mlp_layer_types" not in view["config"]:
        return None
    return per_token / roofline_moe.even_share(view["config"])


def held_load_gap(view: dict):
    """How far ``held_load_ratio`` lies from 1, either way: held experts
    that starve are as far from the cell's description as held experts that
    are swamped, and a step is the faster for the first."""
    ratio = held_load_ratio(view)
    return None if ratio is None else abs(ratio - 1.0)


def experts_roofline_pct(view: dict):
    """The least time the chip could take for the grouped products of every
    expert layer (the larger of operations over the bf16 peak and bytes over
    the HBM peak, a layer and step), by the pairs the program counted in
    the profiled steps, over the ``experts`` scopes' device time of those
    steps."""
    seconds, layers = scope_seconds(view), newest_steps(view)
    if seconds is None or not layers or not seconds["experts"]:
        return None
    cfg = view["config"]
    least = sum(roofline.roofline_seconds(
        roofline_moe.grouped_flops(cfg, pairs),
        roofline_moe.grouped_bytes(cfg, pairs, active, 2),
        view["peaks"])[0] for steps in layers for pairs, active in steps)
    return 100.0 * least / len(layers[0]) / seconds["experts"]


def attention_roofline_pct(view: dict, windowed: bool):
    """As ``hybrid.attention_roofline_pct`` for plain grouped-query heads:
    the held query heads on the held key/value heads, all of ``head_dim``."""
    cfg = view["config"]
    if "layer_types" not in cfg:
        return None
    measured = hybrid.kernel_seconds(view, *(
        ("window_fwd", "window_bwd") if windowed
        else ("full_fwd", "full_bwd")))
    n = roofline_moe.layers(cfg, windowed)
    if measured is None or not n:
        return None
    rows = view["batch"] // view["chips"]
    fl = roofline_moe.attention_flops(
        cfg, rows, view["seq_len"],
        cfg["sliding_window"] if windowed else None)
    by = roofline_moe.attention_bytes(cfg, rows, view["seq_len"], 2)
    least = sum(roofline.roofline_seconds(fl[k], by[k], view["peaks"])[0]
                for k in ("fwd", "bwd"))
    return 100.0 * least * n / measured
