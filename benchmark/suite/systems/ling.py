"""The system under test for the ``ling-3.0-flash`` configuration, as its
users call it: ``HybridDecoderLM`` built from the configuration file's
numbers (the program has no preset), layers of kind ``kda`` (Kimi Delta
Attention) and ``mla`` (latent attention) beside a dense SwiGLU or a sparse
expert layer that holds this chip's share of the experts under a
group-limited router, pre-norm RMSNorm, an untied head, holding the seed's
weights, trained by ``system.Trainer`` (``DataParallelTrainer`` + Adam on
``data_parallel_mesh()``). What the cell shares with ``kexaone_train_t4096``
comes from ``systems/kexaone.py`` as it is: the parameters read back in
their stored type, and the trainer that hands the expert layers' counts to
the per-layer readers.
"""

from __future__ import annotations

import os

import numpy as np

import ling as readers
import system
from manifest import load_module
# imported here and not inside build_net: a tree without the two mixers
# fails when the job loads this module, before anything is built
from mxtpu.gluon.model_zoo.hybrid_decoder import (  # noqa: F401
    HybridDecoderLM, KimiDeltaAttention, LatentAttention)

_K = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "kexaone.py"), "suite_systems_kexaone_shared")

# reference leaf -> path below a block: (child, ..., parameter attribute)
LEAVES = {"ln1_g": ("ln1", "gamma"), "ln2_g": ("ln2", "gamma"),
          "in_w": ("kda", "in_proj", "weight"),
          "conv_w": ("kda", "conv_weight"), "f_w": ("kda", "f_proj"),
          "b_w": ("kda", "b_proj"), "a_log": ("kda", "A_log"),
          "dt_bias": ("kda", "dt_bias"), "o_norm_g": ("kda", "o_norm"),
          "q_w": ("mla", "q_proj", "weight"),
          "kva_w": ("mla", "kva_proj", "weight"),
          "kv_norm_g": ("mla", "kv_norm", "gamma"),
          "kvb_w": ("mla", "kvb_proj", "weight"),
          "gate_w": ("mla", "gate_proj", "weight"),
          "q_norm_g": ("mla", "q_norm"), "k_norm_g": ("mla", "k_norm"),
          "o_w": ("<mixer>", "out_proj", "weight"),
          "gate_up_w": ("mlp", "gate_up", "weight"),
          "down_w": ("mlp", "down", "weight"),
          "router_w": ("moe", "router"), "router_b": ("moe", "select_bias"),
          "experts_gate_up_w": ("moe", "gate_up"),
          "experts_down_w": ("moe", "down"),
          "shared_gate_up_w": ("moe", "shared", "gate_up", "weight"),
          "shared_down_w": ("moe", "shared", "down", "weight")}
MLP_KINDS = {"dense": "mlp", "sparse": "moe"}


def build_net(cfg: dict, weights: dict, dtype: str):
    """``HybridDecoderLM`` at the configuration's sizes holding ``weights``
    (the reference's flat tree). Parameters are made in ``dtype`` (the cast
    comes first), so no float32 copy of the model ever exists."""
    net = HybridDecoderLM(
        cfg["vocab_size"], cfg["layer_types"], units=cfg["hidden_size"],
        ffn_units=cfg["intermediate_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_conv=cfg["short_conv_kernel_size"],
        layer_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        norm="rms", norm_position="pre",
        tie_head=cfg["tie_word_embeddings"],
        kda_lower_bound=cfg["kda_lower_bound"],
        mla=dict(latent_dim=cfg["kv_lora_rank"],
                 nope_dim=cfg["qk_nope_head_dim"],
                 rope_dim=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"],
                 interleave=cfg["rope_interleave"]),
        mlp_kinds=[MLP_KINDS[k] for k in cfg["mlp_layer_types"]],
        moe=dict(ffn_units=cfg["moe_intermediate_size"],
                 num_experts=cfg["published_num_experts"],
                 top_k=cfg["num_experts_per_tok"], held=cfg["held_experts"],
                 shared_ffn_units=cfg["num_shared_experts"]
                 * cfg["moe_shared_expert_intermediate_size"],
                 routed_scale=cfg["routed_scaling_factor"],
                 bias_update_rate=cfg["router_bias_update_rate"],
                 n_group=cfg["n_group"], topk_group=cfg["topk_group"]))
    net.cast(dtype)
    # zeros, not a random draw of every shape (the seed's weights follow),
    # for the parameters that name an initializer of their own too
    net.collect_params().setattr("init", "zeros")
    net.initialize()
    leaves = param_leaves(net)
    if {leaf for _, leaf in leaves} != set(weights):
        raise SystemExit("benchmark: the program's parameters and the "
                         "reference's leaves differ: "
                         f"{sorted({l for _, l in leaves} ^ set(weights))[:6]}")
    for param, leaf in leaves:
        param.set_data(weights[leaf])
    return net


def param_leaves(net) -> list:
    """``[(Parameter, "layers/in_w/0" | "embed" | ...)]``: the program's
    parameters by the reference's leaf names, the routers' selection bias (a
    state the step moves by rule) among them."""
    out = [(net.embedding.weight, "embed"), (net.head.weight, "head"),
           (net.ln_f.gamma, "ln_f_g")]
    for i, blk in enumerate(net.blocks):
        for leaf, path in LEAVES.items():
            obj = blk
            for attr in path:
                obj = getattr(obj, blk.kind if attr == "<mixer>" else attr,
                              None)
                if obj is None:
                    break
            if obj is not None:
                out.append((obj, f"layers/{leaf}/{i}"))
    return out


def param_arrays(net) -> dict:
    """Every parameter by leaf name as ``systems/kexaone.py``'s ``Stored``
    (host, in the stored type, float32 when an array is asked of it), read
    back leaf by leaf from the CALLING thread. ``systems/kexaone.py`` reads
    its leaves back on four threads; a process that has done so issues every
    later step in 13 ms where it took 5, or does not, by how the threads
    fell (PERF.md, section 6, PR 41: one process, the same step, before and
    after one such read-back), and a cell whose runs draw one of two step
    times cannot resolve its bound."""
    return {leaf: _K.Stored(np.asarray(p.data().data))
            for p, leaf in param_leaves(net)}


class Trainer(_K.Trainer):
    """``systems/kexaone.py``'s trainer (its steps hand the expert layers'
    counts to ``moe.STEP_COUNTS``) whose parameters are read back by this
    configuration's leaf names."""

    def param_arrays(self) -> dict:
        return param_arrays(self.net)


def kernel_path_counts() -> dict:
    """The program's count of call sites by kernel path; what its delta-rule
    op said of its newest launch goes to the per-layer readers
    (``ling.KDA_STATS``) and is printed beside it, with the expert layers'
    busiest and idlest experts (``systems/kexaone.py``)."""
    from mxtpu import profiler
    readers.KDA_STATS.clear()
    readers.KDA_STATS.update(profiler.get_kda_stats())
    print(f"[system] kda launches traced: {readers.KDA_STATS}", flush=True)
    return _K.kernel_path_counts()
