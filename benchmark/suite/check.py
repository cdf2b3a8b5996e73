"""The comparison that decides ``correct``: the timed path's numbers against
the plain reference's, each number with a limit of its own from the cell's
file. Every number is printed beside its limit in every run."""

from __future__ import annotations

import math
import statistics


ZERO_GRADIENT_SHARE = 1e-3   # of the median leaf's gradient norm


def worst_leaf_gap(got: dict, ref: dict) -> tuple:
    """Over leaves, the largest gap between two norms of the same leaf,
    measured against the reference's norm of that leaf or of its median
    leaf, whichever is larger (some gradients are all but zero). Returns
    ``(gap, leaf)``."""
    if set(got) != set(ref):
        missing = sorted(set(ref) ^ set(got))[:4]
        raise ValueError(f"leaf sets differ, e.g. {missing}")
    floor = statistics.median(ref.values())
    worst, where = -1.0, None
    for leaf, r in ref.items():
        gap = abs(got[leaf] - r) / max(r, floor)
        if not math.isfinite(gap):
            return math.inf, leaf
        if gap > worst:
            worst, where = gap, leaf
    return worst, where


def training_numbers(got: dict, ref: dict) -> dict:
    """``ref`` holds ``loss`` (per checked step) and ``grad_norm`` and
    ``delta_norm`` (per leaf); ``got`` holds ``loss``, ``grad_norm`` (ONE
    number: the whole first gradient, see ``system.Trainer``), ``delta_norm``
    (per leaf) and, but for the control, ``window_loss`` (every step of the
    window). Returns
    ``{number: (value, detail)}``."""
    loss_gap = max(abs(a - b) / abs(b) if math.isfinite(a) else math.inf
                   for a, b in zip(got["loss"], ref["loss"]))
    ref_g = whole_norm(ref["grad_norm"])
    g = abs(got["grad_norm"] - ref_g) / ref_g
    # Adam divides by the gradient's own size, so a leaf whose true gradient
    # is zero (the key bias: softmax does not see it) moves by whatever
    # rounding noise its gradient holds. Such leaves are left out of the
    # comparison of changes.
    floor = ZERO_GRADIENT_SHARE * statistics.median(ref["grad_norm"].values())
    live = [k for k, v in ref["grad_norm"].items() if v > floor]
    d, d_leaf = worst_leaf_gap({k: got["delta_norm"][k] for k in live},
                               {k: ref["delta_norm"][k] for k in live})
    out = {"loss_gap": (loss_gap, f"steps {len(ref['loss'])}"),
           "grad_norm_gap": (g if math.isfinite(g) else math.inf,
                             f"whole gradient, reference {ref_g:.6g}"),
           "delta_norm_gap": (d, d_leaf)}
    if got.get("window_loss") is not None:   # the control has no window
        out["window_loss_ratio"] = window_loss_ratio(got["window_loss"])
    return out


def whole_norm(leaf_norms: dict) -> float:
    """The norm of all leaves together from the norm of each."""
    return math.sqrt(sum(v * v for v in leaf_norms.values()))


LOSS_ENDS = 4    # steps at each end of the window whose losses are averaged


def window_loss_ratio(losses: list) -> tuple:
    """The loss falls over the window: the mean of its last steps over the
    mean of its first. A step that leaves its state as it was reads 1."""
    k = min(LOSS_ENDS, len(losses) // 2)
    if k == 0 or not all(math.isfinite(v) for v in losses):
        return math.inf, f"{len(losses)} steps"
    first, last = sum(losses[:k]) / k, sum(losses[-k:]) / k
    return last / first, (f"{first:.4f} -> {last:.4f}, {k} steps at each "
                          f"end of {len(losses)}")


def served_gaps(ref_logits, tokens, first: int):
    """For the tokens at positions ``first..`` of one sequence: how far each
    token's logit lies below the reference's best at the position that
    predicted it, in units of that position's spread of logits over the
    vocabulary. ``ref_logits`` is ``(T, vocab)``, row ``p`` predicting the
    token at ``p + 1``."""
    import numpy as np
    pos = np.arange(first - 1, len(tokens) - 1)
    rows = ref_logits[pos]
    picked = rows[np.arange(len(pos)), np.asarray(tokens[first:])]
    return (rows.max(axis=-1) - picked) / rows.std(axis=-1)


def serving_numbers(sample: list, ref_forward, n_positions: int,
                    control_forward=None) -> dict:
    """``sample`` holds finished requests (``prompt``, ``handle``);
    ``ref_forward(tokens (1, n_positions)) -> logits (1, n_positions, V)``
    is the plain reference. Each prompt with its served tokens goes through
    the reference once, right-padded to the one shape (causal: padding is
    inert). Two numbers are compared: the widest gap over every served token
    of the sample, which a single wrong token moves, and the mean gap, which
    swings less from seed to seed and moves when many tokens are a little
    off. With ``control_forward``
    (the reference in a lower precision) the tokens judged are not the
    served ones but those the control puts first at the same positions of
    the same sequences: the control need not decode."""
    import numpy as np
    gaps = []
    for rec in sample:
        served = rec["handle"].tokens()
        seq = list(rec["prompt"]) + served
        toks = np.zeros((1, n_positions), np.int32)
        toks[0, :len(seq)] = seq
        logits = ref_forward(toks)[0]
        if control_forward is not None:
            first = control_forward(toks)[0].argmax(axis=-1)
            t0 = len(rec["prompt"])
            seq = seq[:t0] + [int(t) for t in first[t0 - 1:len(seq) - 1]]
        gaps.append(served_gaps(logits, seq, len(rec["prompt"])))
    if not gaps:
        none = (float("inf"), "no finished request")
        return {"served_gap_sigma": none, "served_gap_mean_sigma": none}
    allg = np.concatenate(gaps)
    detail = (f"{len(allg)} served tokens of {len(sample)} requests, first "
              f"choice agrees on {float((allg == 0).mean()):.4f}")
    return {"served_gap_sigma": (float(allg.max()), detail),
            "served_gap_mean_sigma": (float(allg.mean()), detail)}


CONTROL_PRECISION = "int8"   # the nearest precision below bfloat16


def judge(numbers: dict, limits: dict, say, what: str = "check") -> bool:
    """Print every number beside its limit; True when all are inside."""
    ok = True
    for name, (value, detail) in numbers.items():
        if name not in limits:
            raise SystemExit(f"benchmark: the cell's file gives no limit "
                             f"for {name}")
        inside = math.isfinite(value) and value <= limits[name]
        ok = ok and inside
        say(f"{what} {name} = {value:.6g} (limit {limits[name]:g}, "
            f"{detail}) {'ok' if inside else 'OVER'}")
    return ok
