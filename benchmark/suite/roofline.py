"""Operations and bytes that the algorithms need, from shapes alone. Kept
with the benchmark so that no PR that claims a gain can move the count.
Recomputed operations never count."""

from __future__ import annotations

import weights


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward per trained token: 6 per matmul parameter (the
    tied head counted once) and causal attention's two matmuls, 12 L T d for
    the full square, halved because half of it is masked."""
    d, L = cfg["n_embd"], cfg["n_layer"]
    return 6.0 * weights.matmul_params(cfg) + 6.0 * L * seq_len * d


def flash_flops(batch: int, heads: int, seq: int, head_dim: int) -> dict:
    """Causal flash attention over ``(batch, heads, seq, head_dim)``. The
    forward has two matmuls (QK^T, PV) of 2 T^2 D each, half masked. The
    backward needs five (recomputing S is the algorithm's own, not a remat of
    the model: dV, dP, S, dQ, dK); the split kernels do S and dP twice, which
    is NOT counted: the least work is counted."""
    sq = batch * heads * seq * seq * head_dim
    return {"fwd": 2 * 2 * sq / 2, "bwd": 5 * 2 * sq / 2}


def flash_bytes(batch: int, heads: int, seq: int, head_dim: int,
                itemsize: int) -> dict:
    """Least HBM traffic: forward reads Q, K, V and writes O (and one f32
    row statistic); backward reads Q, K, V, O, dO and the statistic and
    writes dQ, dK, dV."""
    t = batch * heads * seq * head_dim * itemsize
    row = batch * heads * seq * 4
    return {"fwd": 4 * t + row, "bwd": 8 * t + 2 * row}


def roofline_seconds(flops: float, nbytes: float, peaks: dict,
                     chips: int = 1) -> tuple:
    """``(least seconds, which bound)`` on ``chips`` chips."""
    by_compute = flops / (peaks["bf16_flops_per_s"] * chips)
    by_memory = nbytes / (peaks["hbm_bytes_per_s"] * chips)
    return (by_compute, "compute") if by_compute >= by_memory \
        else (by_memory, "memory")


def live_positions(records: list, t: float) -> float:
    """Cache positions that hold a live request's keys and values at time
    ``t`` of the window: for every request decoding then, its prompt and the
    tokens it had (taken as evenly spaced between its first and its last).
    ``records`` hold ``prompt_len``, ``first``, ``last``, ``n``."""
    total = 0.0
    for r in records:
        if r["first"] is None or not r["first"] <= t <= r["last"]:
            continue
        span = r["last"] - r["first"]
        total += r["prompt_len"] + (r["n"] * (t - r["first"]) / span
                                    if span > 0 else r["n"])
    return total


def decode_step_bytes(cfg: dict, weight_itemsize: int, positions: float,
                      kv_itemsize: int) -> float:
    """One decode step reads every weight once and the keys and values of
    the live positions, in every layer. Empty slots and the unused tail of a
    slot's bucket are no work that the algorithm needs."""
    kv = positions * 2 * cfg["n_layer"] * cfg["n_embd"] * kv_itemsize
    return weights.matmul_params(cfg) * weight_itemsize + kv
