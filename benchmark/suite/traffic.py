"""The one general traffic generator. A traffic mix is a data file of
parameters; this reads it and makes the run's inputs from ``--seed``.

Every seed offers the same WORK in another order: the lengths are the
distribution's quantiles at evenly spaced probabilities and the gaps between
arrivals likewise, so every seed has the same set of sizes and the same set
of gaps; ``--seed`` draws which length meets which gap (and the token ids,
and elsewhere the weights). Two runs of one seed offer the identical
schedule; two seeds differ by the realization and not by the amount of work.

``kind: token_batches``  a pool of (tokens, next-token targets) batches
``kind: requests``       arrivals with prompt and output lengths
"""

from __future__ import annotations

import math
import random
from statistics import NormalDist


def _rng(seed: int, salt: str) -> random.Random:
    # str seeds hash through SHA-512 inside random.Random: the same in every
    # process, unlike hash()
    return random.Random(f"{int(seed)}|{salt}")


def quantile_lengths(dist: dict, n: int) -> list:
    """``n`` lengths: the quantiles of ``dist`` at (i + 0.5) / n, clipped."""
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "fixed":
        return [int(dist["value"])] * n
    if dist["dist"] == "lognormal":
        nd = NormalDist()
        mu, sigma = math.log(dist["median"]), dist["sigma"]
        vals = [math.exp(mu + sigma * nd.inv_cdf((i + 0.5) / n))
                for i in range(n)]
    elif dist["dist"] == "uniform":
        vals = [lo + (hi - lo) * (i + 0.5) / n for i in range(n)]
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return [int(min(hi, max(lo, round(v)))) for v in vals]


def arrival_times(traffic: dict, n: int, seconds: float, rng) -> list:
    """``n`` send times inside [0, seconds)."""
    kind = traffic["arrivals"]
    if kind == "at_once":
        return [0.0] * n
    if kind == "uniform":
        return [seconds * i / n for i in range(n)]
    if kind == "poisson":
        # exponential gaps at their quantiles, in the seed's order
        gaps = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
        rng.shuffle(gaps)
        scale = seconds * (n / (n + 1.0)) / sum(gaps)
        t, out = 0.0, []
        for g in gaps:
            t += g * scale
            out.append(t)
        return out
    raise ValueError(f"unknown arrival process {kind!r}")


def requests(traffic: dict, seed: int, seconds: float, vocab: int) -> list:
    """``[{"t", "prompt", "max_new", "greedy"}]`` sorted by send time."""
    n = max(1, round(traffic["rate_per_s"] * seconds))
    order = _rng(seed, "schedule")
    times = arrival_times(traffic, n, seconds, order)
    plens = quantile_lengths(traffic["prompt_tokens"], n)
    olens = quantile_lengths(traffic["output_tokens"], n)
    order.shuffle(plens)
    order.shuffle(olens)
    rng = _rng(seed, "requests")
    shared = traffic.get("shared_prefix", {})
    sessions = int(shared.get("sessions", 0))
    prefixes = [[rng.randrange(1, vocab) for _ in range(int(shared["tokens"]))]
                for _ in range(sessions)]
    out = []
    for i, (t, pl, ol) in enumerate(zip(times, plens, olens)):
        head = prefixes[i % sessions] if sessions else []
        body = [rng.randrange(1, vocab) for _ in range(max(1, pl - len(head)))]
        out.append({"t": t, "prompt": head + body, "max_new": ol,
                    "greedy": True})
    return out


def token_batches(traffic: dict, seed: int, vocab: int):
    """``pool`` batches of int32 ``(batch, seq_len)`` tokens with their
    next-token targets, as numpy arrays. Every row is drawn afresh, so all
    rows of all batches differ."""
    import numpy as np
    rs = np.random.RandomState(_rng(seed, "tokens").randrange(2 ** 32))
    B, T, pool = traffic["batch"], traffic["seq_len"], traffic["pool"]
    out = []
    for _ in range(pool):
        seq = rs.randint(0, vocab, (B, T + 1)).astype(np.int32)
        out.append((seq[:, :-1].copy(), seq[:, 1:].copy()))
    return out
