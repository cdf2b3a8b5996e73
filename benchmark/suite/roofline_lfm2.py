"""Operations and bytes that the algorithms of a gated-short-convolution /
grouped-query-attention decoder with expert layers that hold ALL their
experts need (configurations with ``conv_L_cache``: ``lfm2-8b-a1b``), from
shapes alone: with every expert held a token has exactly
``num_experts_per_tok`` (token, expert) pairs whatever the routing. The
LEAST work is counted, so that no share of a roofline can pass 100%:
attention by the (query, key) pairs the causal mask lets through, on the
``full_attention`` layers only (``roofline_moe`` would count a ``conv``
layer as attention); the conv mixer by its projections' operations and the
bytes no schedule avoids.
Recomputed operations never count."""

from __future__ import annotations

from roofline_hybrid import visible_pairs


def _widths(cfg: dict) -> dict:
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"d": d, "H": H, "Hkv": cfg["num_key_value_heads"], "D": d // H,
            "F": cfg["intermediate_size"],
            "Fe": cfg["moe_intermediate_size"], "E": cfg["num_experts"],
            "k": cfg["num_experts_per_tok"]}


def layers(cfg: dict, kind: str) -> int:
    """Layers whose mixer is ``kind`` (``conv`` or ``full_attention``)."""
    return sum(t == kind for t in cfg["layer_types"])


def matmul_params_per_token(cfg: dict) -> int:
    """Parameters that a token's forward pass multiplies by: a conv mixer's
    two projections (its depthwise taps are no matmul), an attention
    layer's four, the dense FFN or the router and ``num_experts_per_tok``
    experts, and the tied token table once (as the head)."""
    z = _widths(cfg)
    d = z["d"]
    mixer = {"conv": 3 * d * d + d * d,
             "full_attention": (z["H"] + 2 * z["Hkv"]) * z["D"] * d
             + d * z["H"] * z["D"]}
    total = cfg["vocab_size"] * d
    for i, kind in enumerate(cfg["layer_types"]):
        total += mixer[kind]
        total += 3 * d * z["F"] if i < cfg["num_dense_layers"] \
            else d * z["E"] + z["k"] * 3 * d * z["Fe"]
    return total


def attention_flops(cfg: dict, batch: int, seq: int) -> dict:
    """One ``full_attention`` layer: a visible pair costs ``4 D`` operations
    forward (QK^T and PV over D) and 2.5 times that backward (dV, dP, S
    again, dQ, dK: five matmuls for two), for each query head."""
    z = _widths(cfg)
    fwd = batch * z["H"] * visible_pairs(seq) * 4 * z["D"]
    return {"fwd": fwd, "bwd": 2.5 * fwd}


def attention_bytes(cfg: dict, batch: int, seq: int, itemsize: int) -> dict:
    """Least HBM traffic of one layer, as ``roofline.flash_bytes`` counts
    it: forward reads Q, K and V and writes O and one float32 row statistic;
    backward reads Q, K, V, O, dO and the statistic and writes dQ, dK, dV.
    K and V count once per key/value head."""
    z = _widths(cfg)
    q = o = batch * z["H"] * seq * z["D"] * itemsize
    k = v = batch * z["Hkv"] * seq * z["D"] * itemsize
    row = batch * z["H"] * seq * 4
    return {"fwd": q + k + v + o + row,
            "bwd": 2 * (q + k + v + o) + 2 * row}


def conv_flops(cfg: dict, batch: int, seq: int) -> dict:
    """One conv mixer's two projections (``d -> 3 d`` and ``d -> d``): 2 a
    parameter and token forward, twice that backward (dx and dw). The gate's
    dozen operations a value are not counted."""
    fwd = 2 * 4 * cfg["hidden_size"] ** 2 * batch * seq
    return {"fwd": fwd, "bwd": 2 * fwd}


def conv_bytes(cfg: dict, batch: int, seq: int, itemsize: int) -> dict:
    """HBM traffic of one conv mixer that no schedule with its two matmuls
    apart avoids. Forward: x read, B, C and u written by ``in_proj`` and read
    by the gate, the result written (``y = C * conv(B * u)`` may be fused
    into ``out_proj`` as its operand, so y is not counted), both matrices
    read. Backward: dy, x, B, C and u read, dB, dC and du made and read
    (counted once: they may be fused into ``in_proj``'s transposes), dx
    written, both matrices read and their gradients written."""
    wide = batch * seq * cfg["hidden_size"] * itemsize
    mats = 4 * cfg["hidden_size"] ** 2 * itemsize
    return {"fwd": 8 * wide + mats, "bwd": 9 * wide + 2 * mats}


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward per trained token: 6 per matmul parameter (the
    experts at ``num_experts_per_tok`` a token, the tied table once),
    attention by its visible pairs on the ``full_attention`` layers."""
    fl = attention_flops(cfg, 1, seq_len)
    return 6.0 * matmul_params_per_token(cfg) \
        + layers(cfg, "full_attention") * (fl["fwd"] + fl["bwd"]) / seq_len
