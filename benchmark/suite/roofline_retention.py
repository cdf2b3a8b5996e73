"""Operations and bytes that the algorithms of a dense decoder whose mixers
are power-retention layers of degree 2 need (configurations with
``retention_degree``: ``brumby-14b-base``), from shapes alone. The LEAST work
is counted, so that no share of a roofline can pass 100%: retention by the
lesser, at the cell's length, of its two exact forms (the causal pairs, as
attention is counted, and the state), its bytes by the operands once.
Recomputed operations never count: not the block a model runs again in its
backward, not a chunk's weights made twice."""

from __future__ import annotations

from roofline_hybrid import visible_pairs


def _widths(cfg: dict) -> dict:
    return {"d": cfg["hidden_size"], "H": cfg["num_attention_heads"],
            "Hkv": cfg["num_key_value_heads"], "D": cfg["head_dim"],
            "F": cfg["intermediate_size"]}


def state_shape(cfg: dict) -> tuple:
    """A key/value head's state: the ``D (D + 1) / 2`` products of two key
    dimensions, by ``D`` value columns and one that sums the weights."""
    D = cfg["head_dim"]
    return D * (D + 1) // 2, D + 1


def matmul_params_per_token(cfg: dict) -> int:
    """Parameters that a token's forward pass multiplies by: every layer's
    fused q/k/v, output projection, decay gate and SwiGLU, and the untied
    head's slice once (the token table is a lookup)."""
    z = _widths(cfg)
    d = z["d"]
    layer = (z["H"] + 2 * z["Hkv"]) * z["D"] * d + d * z["H"] * z["D"] \
        + z["Hkv"] * d + 3 * d * z["F"]
    return cfg["num_hidden_layers"] * layer + cfg["vocab_size"] * d


def retention_flops(cfg: dict, batch: int, seq: int) -> dict:
    """One layer, the lesser of two exact forms. Pairs: a visible (query,
    key) pair costs ``4 D`` operations a query head (the score and its
    value). State: a token updates its key/value head's state and every
    query head reads one, ``2 x rows x columns`` each. The backward is 2.5
    times the forward, as attention's is counted (five matmuls for two).
    ``{"fwd", "bwd", "form"}``."""
    z = _widths(cfg)
    rows, cols = state_shape(cfg)
    pairs = batch * z["H"] * visible_pairs(seq) * 4 * z["D"]
    state = batch * seq * (z["H"] + z["Hkv"]) * 2 * rows * cols
    fwd = min(pairs, state)
    return {"fwd": fwd, "bwd": 2.5 * fwd,
            "form": "pairs" if pairs <= state else "state"}


def retention_bytes(cfg: dict, batch: int, seq: int, itemsize: int) -> dict:
    """Least HBM traffic of one layer. Forward: q, k, v and the float32
    log-decays read, y written, and the states a sequential schedule cannot
    but keep: ONE a key/value head in float32 (how many chunk starts a
    program keeps beyond that is its choice, and is not counted). Backward:
    those and dy read, dq, dk, dv and the decays' gradient written."""
    z = _widths(cfg)
    rows, cols = state_shape(cfg)
    q = y = batch * seq * z["H"] * z["D"] * itemsize
    k = v = batch * seq * z["Hkv"] * z["D"] * itemsize
    g = batch * seq * z["Hkv"] * 4
    state = batch * z["Hkv"] * rows * cols * 4
    return {"fwd": q + k + v + g + y + state,
            "bwd": 2 * (q + k + v + g + y) + state}


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward per trained token: 6 per matmul parameter (the
    head's slice once) plus every layer's retention, forward and backward;
    the block that is run again in the backward is not counted."""
    fl = retention_flops(cfg, 1, seq_len)
    return 6.0 * matmul_params_per_token(cfg) \
        + cfg["num_hidden_layers"] * (fl["fwd"] + fl["bwd"]) / seq_len
