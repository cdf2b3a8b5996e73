"""Operations and bytes that the algorithms of a sparse decoder whose mixers
are Kimi-Delta-Attention layers beside latent attention need (configurations
with ``kda_lower_bound``: ``ling-3.0-flash``), from shapes alone. The LEAST
work is counted, so that no share of a roofline can pass 100%: the delta rule
by its RECURRENCE (three products of a ``D x D`` state with a vector a token a
head), which is the same whatever implements it: a chunked program's
triangular system, its score matrices and the chunk it makes again in its
backward are its own and are not counted; latent attention by its visible
pairs at its two widths through ``roofline.py``'s counts; the experts by
``roofline_moe``'s."""

from __future__ import annotations

import roofline
import roofline_moe


def layers(cfg: dict, kind: str) -> int:
    return sum(k == kind for k in cfg["layer_types"])


def kda_flops(cfg: dict, batch: int, seq: int) -> dict:
    """One layer's recurrence. Forward, a token a head: what the state holds
    under the key (``S'^T k``), the rank-one write (``k u^T``) and the read
    (``S^T q``), ``2 D^2`` each; the decay's ``D^2`` products and the vectors'
    are left out. Backward: each product's two transposes."""
    H, D = cfg["num_attention_heads"], cfg["head_dim"]
    fwd = batch * seq * H * 3 * 2 * D * D
    return {"fwd": fwd, "bwd": 2 * fwd}


def kda_bytes(cfg: dict, batch: int, seq: int, itemsize: int) -> dict:
    """Least HBM traffic of one layer. Forward: q, k, v read and o written in
    the model's type, the log-decays a channel (float32, as the gate makes
    them) and beta read, and the states a sequential schedule cannot but
    keep: ONE a head in float32 (how many chunk starts a program keeps is
    its choice). Backward: those and do read, dq, dk, dv, the decays'
    gradient and beta's written."""
    H, D = cfg["num_attention_heads"], cfg["head_dim"]
    x = batch * seq * H * D * itemsize
    a = batch * seq * H * D * 4
    beta = batch * seq * H * 4
    state = batch * H * D * D * 4
    return {"fwd": 4 * x + a + beta + state,
            "bwd": 8 * x + 2 * a + 2 * beta + state}


def mla_flops(cfg: dict, batch: int, seq: int) -> dict:
    """One latent-attention layer, expanded: scores over ``qk_nope_head_dim +
    qk_rope_head_dim``, values over ``v_head_dim``. Forward one product at
    each width; backward three at the first (S again, dQ, dK) and two at the
    second (dV, dP)."""
    H = cfg["num_attention_heads"]
    wide = roofline.flash_flops(
        batch, H, seq, cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"])
    narrow = roofline.flash_flops(batch, H, seq, cfg["v_head_dim"])
    return {"fwd": (wide["fwd"] + narrow["fwd"]) / 2,
            "bwd": 0.6 * wide["bwd"] + 0.4 * narrow["bwd"]}


def mla_bytes(cfg: dict, batch: int, seq: int, itemsize: int) -> dict:
    """Q and the expanded K at the first width, V and O at the second."""
    H = cfg["num_attention_heads"]
    wide = roofline.flash_bytes(
        batch, H, seq, cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
        itemsize)
    narrow = roofline.flash_bytes(batch, H, seq, cfg["v_head_dim"], itemsize)
    return {k: (wide[k] + narrow[k]) / 2 for k in ("fwd", "bwd")}


def mixer_params(cfg: dict, kind: str) -> int:
    """Parameters of one mixer that a token's forward pass multiplies by."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    if kind == "kda":       # q, k, v, the output gate, the decay, W_o; beta
        return 6 * H * cfg["head_dim"] * d + H * d
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rank, v = cfg["kv_lora_rank"], cfg["v_head_dim"]
    return (H * (nope + rope) * d + (rank + rope) * d
            + H * (nope + v) * rank + H * d + H * v * d)


def matmul_params_per_token(cfg: dict, held_per_token=None) -> float:
    """Every layer's mixer, its dense MLP or its router, shared expert and
    ``held_per_token`` held experts (the pairs an expert layer computed over
    its tokens; the even share where nothing was counted), and the untied
    head's slice (the token table is a lookup)."""
    d = cfg["hidden_size"]
    if held_per_token is None:
        held_per_token = roofline_moe.even_share(cfg)
    shared = cfg["num_shared_experts"] \
        * cfg["moe_shared_expert_intermediate_size"]
    total = cfg["vocab_size"] * d
    for kind, mlp in zip(cfg["layer_types"], cfg["mlp_layer_types"]):
        total += mixer_params(cfg, kind)
        total += (d * cfg["published_num_experts"] + 3 * d * shared
                  + held_per_token * roofline_moe.expert_params(cfg)) \
            if mlp == "sparse" else 3 * d * cfg["intermediate_size"]
    return total


def train_flops_per_token(cfg: dict, seq_len: int,
                          held_per_token=None) -> float:
    """Forward and backward per trained token: 6 per matmul parameter (the
    experts by the pairs counted, else at their even share), the delta rule
    by its recurrence, latent attention by its visible pairs."""
    total = 6.0 * matmul_params_per_token(cfg, held_per_token)
    for kind, count in (("kda", kda_flops), ("mla", mla_flops)):
        fl = count(cfg, 1, seq_len)
        total += layers(cfg, kind) * (fl["fwd"] + fl["bwd"]) / seq_len
    return total
