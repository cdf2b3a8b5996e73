"""Operations and bytes that a decoder-hybrid-decoder model's algorithms
need (configurations with ``layer_kinds``: ``mamba``, ``attn_window``,
``attn_full``, ``attn_cross``, ``gmu``), from shapes alone. The LEAST work is
counted, so that no share of a roofline can pass 100%: attention by the
(query, key) pairs a mask lets through, the scan by the bytes it cannot
avoid moving. Recomputed operations never count."""

from __future__ import annotations

ATTENTION = ("attn_window", "attn_full", "attn_cross")


def visible_pairs(seq: int, window=None) -> int:
    """(query, key) pairs a causal mask lets through: ``T (T + 1) / 2``, and
    through a window of ``W`` keys ``W T - W (W - 1) / 2``."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * seq - window * (window - 1) // 2


def _widths(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    return {"d": d, "F": cfg["intermediate_size"],
            "H": cfg["num_attention_heads"],
            "Hkv": cfg["num_key_value_heads"],
            "D": d // cfg["num_attention_heads"],
            "Di": cfg["mamba_expand"] * d, "N": cfg["mamba_d_state"],
            "R": cfg["mamba_dt_rank"]}


def matmul_params(cfg: dict) -> int:
    """Parameters that a token's forward pass multiplies by: every layer's
    matrices by its kind and the tied head (the token table's slice, once).
    The depthwise convolution and the scan are not matmuls."""
    z = _widths(cfg)
    d, Di = z["d"], z["Di"]
    q, kv = z["H"] * z["D"], 2 * z["Hkv"] * z["D"]
    mixer = {"mamba": 2 * Di * d + (z["R"] + 2 * z["N"]) * Di + Di * z["R"]
             + d * Di,
             "gmu": 2 * Di * d,
             "attn_window": (q + kv) * d + d * q,
             "attn_full": (q + kv) * d + d * q,
             "attn_cross": q * d + d * q}
    return sum(mixer[k] + 3 * d * z["F"] for k in cfg["layer_kinds"]) \
        + cfg["vocab_size"] * d


def attention_flops(cfg: dict, batch: int, seq: int, window=None) -> dict:
    """One layer of differential attention: every query head holds one
    score map over a value twice as wide as the keys, so a visible pair
    costs ``2 (D + 2 D)`` operations forward (QK^T and PV) and 2.5 times
    that backward (dV, dP, S again, dQ, dK: five matmuls for two)."""
    z = _widths(cfg)
    fwd = batch * z["H"] * visible_pairs(seq, window) * 2 * 3 * z["D"]
    return {"fwd": fwd, "bwd": 2.5 * fwd}


def attention_bytes(cfg: dict, batch: int, seq: int, itemsize: int) -> dict:
    """Least HBM traffic of one layer, as ``roofline.flash_bytes`` counts
    it: forward reads Q, K and V and writes O and one float32 row statistic;
    backward reads Q, K, V, O, dO and the statistic and writes dQ, dK, dV.
    K and V count once per key/value pair, however many query heads and
    whichever of the two maps read them."""
    z = _widths(cfg)
    q = batch * z["H"] * seq * z["D"] * itemsize
    k = v = batch * z["Hkv"] * seq * z["D"] * itemsize
    o = 2 * q                                    # a value is 2 D wide
    row = batch * z["H"] * seq * 4
    return {"fwd": q + k + v + o + row,
            "bwd": 2 * (q + k + v + o) + 2 * row}


def scan_bytes(cfg: dict, batch: int, seq: int, itemsize: int) -> dict:
    """One selective scan, by bytes alone (it is bound by memory: six
    multiply-adds a state against 16 states a byte read): forward reads u,
    dt, B and C once and writes y once; backward reads those and dy once
    and writes du, ddt, dB and dC once. A and D are small."""
    z = _widths(cfg)
    wide = batch * seq * z["Di"] * itemsize       # u, dt, y, dy, du, ddt
    thin = batch * seq * z["N"] * itemsize        # B, C, dB, dC
    return {"fwd": 3 * wide + 2 * thin, "bwd": 5 * wide + 4 * thin}


SCAN_FLOPS_PER_STATE = 18   # forward 6 (dt A, decay s, dt u B, +, C s, sum)
                            # and twice that backward; exp not counted


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward per trained token: 6 per matmul parameter (the
    tied slice once), attention by its visible pairs, the scan's
    multiply-adds as they are."""
    z = _widths(cfg)
    window = cfg["sliding_window"]
    total = 6.0 * matmul_params(cfg)
    for kind in cfg["layer_kinds"]:
        if kind in ATTENTION:
            fl = attention_flops(cfg, 1, seq_len,
                                 window if kind == "attn_window" else None)
            total += (fl["fwd"] + fl["bwd"]) / seq_len
        elif kind == "mamba":
            total += SCAN_FLOPS_PER_STATE * z["Di"] * z["N"]
    return total
