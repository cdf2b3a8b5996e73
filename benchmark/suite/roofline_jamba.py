"""Operations and bytes that the algorithms of a Jamba-family decoder need
(configurations with ``attn_layer_period``: ``jamba2-3b``), from shapes
alone. The matrices, the scan's bytes and its multiply-adds are
``roofline_hybrid.py``'s counts, which read the same keys (``layer_kinds``,
``mamba_*``); what differs is the attention layer, plain grouped-query
softmax where that file counts differential attention's wider value. The
LEAST work is counted, so that no share of a roofline or of a peak can pass
100%: attention by the (query, key) pairs the causal mask lets through.
Recomputed operations never count."""

from __future__ import annotations

import roofline_hybrid


def _heads(cfg: dict) -> tuple:
    H = cfg["num_attention_heads"]
    return H, cfg["num_key_value_heads"], cfg["hidden_size"] // H


def attention_flops(cfg: dict, batch: int, seq: int) -> dict:
    """One layer: a visible pair costs ``4 D`` operations forward (QK^T and
    PV over D) and 2.5 times that backward (dV, dP, S again, dQ, dK: five
    matmuls for two), for each query head."""
    H, _, D = _heads(cfg)
    fwd = batch * H * roofline_hybrid.visible_pairs(seq) * 4 * D
    return {"fwd": fwd, "bwd": 2.5 * fwd}


def attention_bytes(cfg: dict, batch: int, seq: int, itemsize: int) -> dict:
    """Least HBM traffic of one layer, as ``roofline.flash_bytes`` counts
    it: forward reads Q, K and V and writes O and one float32 row statistic;
    backward reads Q, K, V, O, dO and the statistic and writes dQ, dK, dV.
    K and V count once per key/value head, however many query heads read
    them."""
    H, Hkv, D = _heads(cfg)
    q = o = batch * H * seq * D * itemsize
    k = v = batch * Hkv * seq * D * itemsize
    row = batch * H * seq * 4
    return {"fwd": q + k + v + o + row,
            "bwd": 2 * (q + k + v + o) + 2 * row}


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward per trained token: 6 per matmul parameter (every
    matrix once forward and twice backward; the tied table's slice once, as
    the head), the attention layers by their visible pairs, the scans'
    multiply-adds as they are."""
    total = 6.0 * roofline_hybrid.matmul_params(cfg)
    for kind in cfg["layer_kinds"]:
        if kind == "attn_full":
            fl = attention_flops(cfg, 1, seq_len)
            total += (fl["fwd"] + fl["bwd"]) / seq_len
        elif kind == "mamba":
            total += roofline_hybrid.SCAN_FLOPS_PER_STATE \
                * cfg["mamba_expand"] * cfg["hidden_size"] \
                * cfg["mamba_d_state"]
    return total
