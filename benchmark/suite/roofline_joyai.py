"""Operations that the algorithms of a sparse decoder whose every mixer is a
latent attention with a query latent, trained with a multi-token-prediction
block beside its head, need (configurations with ``q_lora_rank`` and
``mtp_loss_weight``: ``joyai-llm-flash``), from shapes alone. The LEAST work
is counted, so that no share of a peak can pass 100%: latent attention by
its visible pairs at its two widths (``roofline_kda.mla_flops`` /
``mla_bytes``, which read the same keys), the experts by the pairs counted
(``roofline_moe``), the prediction block as the one more layer it is, its
joining matrix, and the head a second time over the positions its loss
counts (all but the last). Both token tables' lookups are no products."""

from __future__ import annotations

import roofline_kda
import roofline_moe


def attention_layers(cfg: dict) -> int:
    """Latent-attention layers a step runs: the trunk's and the prediction
    block's."""
    return len(cfg["layer_types"]) + cfg["num_nextn_predict_layers"]


def mixer_params(cfg: dict) -> int:
    """Parameters of one mixer that a token's forward pass multiplies by:
    the query's two matrices, the latent's two, the output's."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    q_rank, rank, v = cfg["q_lora_rank"], cfg["kv_lora_rank"], \
        cfg["v_head_dim"]
    return (d * q_rank + q_rank * H * (nope + rope) + d * (rank + rope)
            + rank * H * (nope + v) + H * v * d)


def mlp_params(cfg: dict, mlp: str, held_per_token: float) -> float:
    d = cfg["hidden_size"]
    if mlp != "sparse":
        return 3 * d * cfg["intermediate_size"]
    shared = cfg["n_shared_experts"] * cfg["moe_intermediate_size"]
    return (d * cfg["published_num_experts"] + 3 * d * shared
            + held_per_token * roofline_moe.expert_params(cfg))


def matmul_params_per_token(cfg: dict, seq_len: int,
                            held_per_token=None) -> float:
    """Every layer's mixer and MLP (the experts by ``held_per_token``, the
    pairs an expert layer computed over its tokens; the even share where
    nothing was counted), the untied head's slice, and with a prediction
    block one more layer of the last layer's kinds, its joining matrix and
    the head again over ``seq_len - 1`` of ``seq_len`` positions."""
    d = cfg["hidden_size"]
    if held_per_token is None:
        held_per_token = roofline_moe.even_share(cfg)
    mlps = list(cfg["mlp_layer_types"])
    mlps += mlps[-1:] * cfg["num_nextn_predict_layers"]
    total = sum(mixer_params(cfg) + mlp_params(cfg, mlp, held_per_token)
                for mlp in mlps)
    head = cfg["vocab_size"] * d
    return total + head + cfg["num_nextn_predict_layers"] * (
        2 * d * d + head * (seq_len - 1) / seq_len)


def train_flops_per_token(cfg: dict, seq_len: int,
                          held_per_token=None) -> float:
    """Forward and backward per trained token: 6 per matmul parameter,
    latent attention by its visible pairs in every layer the step runs."""
    fl = roofline_kda.mla_flops(cfg, 1, seq_len)
    return 6.0 * matmul_params_per_token(cfg, seq_len, held_per_token) \
        + attention_layers(cfg) * (fl["fwd"] + fl["bwd"]) / seq_len
