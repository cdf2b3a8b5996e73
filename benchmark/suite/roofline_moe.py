"""Operations and bytes that a sparse grouped-query decoder's algorithms need
(configurations with ``layer_types`` and ``mlp_layer_types``:
``k-exaone-236b``), from shapes and from the (token, expert) pairs a step
computed. The LEAST work is counted, so that no share of a roofline can pass
100%: attention by the (query, key) pairs a mask lets through, the grouped
products by the rows there are and not by the buffer. Recomputed operations
never count."""

from __future__ import annotations

from roofline_hybrid import visible_pairs


def _widths(cfg: dict) -> dict:
    return {"d": cfg["hidden_size"], "H": cfg["num_attention_heads"],
            "Hkv": cfg["num_key_value_heads"], "D": cfg["head_dim"],
            "F": cfg["intermediate_size"], "Fe": cfg["moe_intermediate_size"],
            "Fs": cfg["num_shared_experts"] * cfg["moe_intermediate_size"],
            "E": cfg["published_num_experts"],
            "k": cfg["num_experts_per_tok"],
            "held": len(cfg["held_experts"])}


def layers(cfg: dict, windowed: bool) -> int:
    return sum((t == "sliding_attention") == windowed
               for t in cfg["layer_types"])


def expert_params(cfg: dict) -> int:
    """One expert's three matrices."""
    z = _widths(cfg)
    return 3 * z["d"] * z["Fe"]


def even_share(cfg: dict) -> float:
    """Held experts a token and expert layer under a balanced routing."""
    z = _widths(cfg)
    return z["k"] * z["held"] / z["E"]


def matmul_params_per_token(cfg: dict, held_per_token=None) -> float:
    """Parameters that a token's forward pass multiplies by: every layer's
    attention projections over the heads held, the dense MLP or the router,
    the shared expert and ``held_per_token`` held experts (the (token,
    expert) pairs an expert layer computed over its tokens; ``even_share``
    where nothing was counted), and the untied head's slice."""
    z = _widths(cfg)
    d = z["d"]
    if held_per_token is None:
        held_per_token = even_share(cfg)
    attention = (z["H"] + 2 * z["Hkv"]) * z["D"] * d + d * z["H"] * z["D"]
    total = cfg["vocab_size"] * d
    for kind in cfg["mlp_layer_types"]:
        total += attention
        total += (d * z["E"] + 3 * d * z["Fs"]
                  + held_per_token * expert_params(cfg)) \
            if kind == "sparse" else 3 * d * z["F"]
    return total


def attention_flops(cfg: dict, batch: int, seq: int, window=None) -> dict:
    """One layer: a visible pair costs ``4 D`` operations forward (QK^T and
    PV over D) and 2.5 times that backward (dV, dP, S again, dQ, dK: five
    matmuls for two), for each query head held."""
    z = _widths(cfg)
    fwd = batch * z["H"] * visible_pairs(seq, window) * 4 * z["D"]
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


def grouped_flops(cfg: dict, pairs: float) -> float:
    """One expert layer's six grouped products (gate/up and down: forward,
    dx, dw) over ``pairs`` rows: ``2 * 3 d Fe`` a row and product kind."""
    return 3 * 2.0 * expert_params(cfg) * pairs


def grouped_bytes(cfg: dict, pairs: float, active: float,
                  itemsize: int) -> float:
    """Least HBM traffic of those six products: each reads its row operand
    and writes its row result once, the forward and dx products read the
    matrices of the ``active`` experts (those with a row) once each, and dw
    is written for every held expert (zeros for an idle one)."""
    z = _widths(cfg)
    d, Fe = z["d"], z["Fe"]
    rows = pairs * itemsize * (
        (d + 2 * Fe) + (Fe + d)             # forward: x -> gate_up, act -> y
        + (d + Fe) + (2 * Fe + d)           # dx: dy -> dact, dgate_up -> dx
        + (Fe + d) + (d + 2 * Fe))          # dw: act, dy; x, dgate_up
    weights = (2 * active + z["held"]) * expert_params(cfg) * itemsize
    return rows + weights


def train_flops_per_token(cfg: dict, seq_len: int,
                          held_per_token=None) -> float:
    """Forward and backward per trained token: 6 per matmul parameter (the
    experts by the pairs counted, else at their even share), attention by
    its visible pairs."""
    total = 6.0 * matmul_params_per_token(cfg, held_per_token)
    for kind in cfg["layer_types"]:
        fl = attention_flops(cfg, 1, seq_len, cfg["sliding_window"]
                             if kind == "sliding_attention" else None)
        total += (fl["fwd"] + fl["bwd"]) / seq_len
    return total
