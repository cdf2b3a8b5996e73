"""The system under test for the ``joyai-llm-flash`` configuration, as its
users call it: ``HybridDecoderLM`` built from the configuration file's
numbers (the program has no preset), every layer's mixer a latent attention
(kind ``mla``) with a query latent and neither q/k norms nor a head gate,
beside a dense SwiGLU or a sparse expert layer that holds this chip's share
of the experts, pre-norm RMSNorm, an untied head, and a multi-token-prediction
block (``mtp_layers``) trained beside the head through
``gluon.loss.NextTokenLoss``, holding the seed's weights, trained by
``system.Trainer`` (``DataParallelTrainer`` + Adam on
``data_parallel_mesh()``). What the cell shares with ``kexaone_train_t4096``
comes from ``systems/kexaone.py`` as it is: the parameters in their stored
type, and the trainer that hands the expert layers' counts to the per-layer
readers.
"""

from __future__ import annotations

import os

import numpy as np

import joyai as readers
import moe as moe_readers
from manifest import load_module
# imported here and not inside build_net: a tree without the prediction
# block or its loss fails when the job loads this module, before anything is
# built
from mxtpu.gluon.loss import NextTokenLoss
from mxtpu.gluon.model_zoo.hybrid_decoder import (  # noqa: F401
    HybridDecoderLM, LatentAttention, MultiTokenPrediction)

_K = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "kexaone.py"), "suite_systems_kexaone_shared")

# reference leaf -> path below a block: (child, ..., parameter attribute)
LEAVES = {"ln1_g": ("ln1", "gamma"), "ln2_g": ("ln2", "gamma"),
          "qa_w": ("mla", "qa_proj", "weight"),
          "qa_norm_g": ("mla", "qa_norm", "gamma"),
          "qb_w": ("mla", "qb_proj", "weight"),
          "kva_w": ("mla", "kva_proj", "weight"),
          "kv_norm_g": ("mla", "kv_norm", "gamma"),
          "kvb_w": ("mla", "kvb_proj", "weight"),
          "o_w": ("mla", "out_proj", "weight"),
          "gate_up_w": ("mlp", "gate_up", "weight"),
          "down_w": ("mlp", "down", "weight"),
          "router_w": ("moe", "router"), "router_b": ("moe", "select_bias"),
          "experts_gate_up_w": ("moe", "gate_up"),
          "experts_down_w": ("moe", "down"),
          "shared_gate_up_w": ("moe", "shared", "gate_up", "weight"),
          "shared_down_w": ("moe", "shared", "down", "weight")}
MLP_KINDS = {"dense": "mlp", "sparse": "moe"}
# the configuration's ``mtp_loss_weight`` as ``build_net`` read it: the job
# hands ``Trainer`` the net and Adam's numbers, not the configuration
_LOSS_WEIGHT = []


def build_net(cfg: dict, weights: dict, dtype: str):
    """``HybridDecoderLM`` at the configuration's sizes holding ``weights``
    (the reference's flat tree). Parameters are made in ``dtype`` (the cast
    comes first), so no float32 copy of the model ever exists."""
    net = HybridDecoderLM(
        cfg["vocab_size"], cfg["layer_types"], units=cfg["hidden_size"],
        ffn_units=cfg["intermediate_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        layer_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        norm="rms", norm_position="pre",
        tie_head=cfg["tie_word_embeddings"],
        mla=dict(latent_dim=cfg["kv_lora_rank"],
                 nope_dim=cfg["qk_nope_head_dim"],
                 rope_dim=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"],
                 interleave=cfg["rope_interleave"],
                 q_latent_dim=cfg["q_lora_rank"], qk_norm=False,
                 head_gate=False),
        mlp_kinds=[MLP_KINDS[k] for k in cfg["mlp_layer_types"]],
        moe=dict(ffn_units=cfg["moe_intermediate_size"],
                 num_experts=cfg["published_num_experts"],
                 top_k=cfg["num_experts_per_tok"], held=cfg["held_experts"],
                 shared_ffn_units=cfg["n_shared_experts"]
                 * cfg["moe_intermediate_size"],
                 routed_scale=cfg["routed_scaling_factor"],
                 bias_update_rate=cfg["router_bias_update_rate"],
                 n_group=cfg["n_group"], topk_group=cfg["topk_group"]),
        mtp_layers=cfg["num_nextn_predict_layers"])
    _LOSS_WEIGHT.append(cfg["mtp_loss_weight"])
    net.cast(dtype)
    # zeros, not a random draw of every shape (the seed's weights follow)
    net.collect_params().setattr("init", "zeros")
    net.initialize()
    leaves = param_leaves(net)
    odd = {leaf for _, leaf in leaves} ^ set(weights)
    if odd:
        raise SystemExit("benchmark: the program's parameters and the "
                         f"reference's leaves differ: {sorted(odd)[:6]}")
    for param, leaf in leaves:
        param.set_data(weights[leaf])
    return net


def layers(net) -> list:
    """The trunk's blocks and, last, the prediction block's layer."""
    blocks = list(net.blocks)
    if net.mtp0 is not None:
        blocks.append(getattr(net.mtp0, f"block{len(blocks)}"))
    return blocks


def param_leaves(net) -> list:
    """``[(Parameter, "layers/qa_w/0" | "embed" | ...)]``: the program's
    parameters by the reference's leaf names, ``embed`` and ``head`` ONCE
    each (the prediction block uses the trunk's), the routers' selection
    bias (a state the step moves by rule) among them; the prediction block's
    layer is layer ``len(net.blocks)``."""
    out = [(net.embedding.weight, "embed"), (net.head.weight, "head"),
           (net.ln_f.gamma, "ln_f_g")]
    if net.mtp0 is not None:
        mtp = net.mtp0
        out += [(mtp.enorm.gamma, "mtp_enorm_g"),
                (mtp.hnorm.gamma, "mtp_hnorm_g"),
                (mtp.eh_proj.weight, "mtp_eh_w"),
                (mtp.norm.gamma, "mtp_norm_g")]
    for i, blk in enumerate(layers(net)):
        for leaf, path in LEAVES.items():
            obj = blk
            for attr in path:
                obj = getattr(obj, attr, None)
                if obj is None:
                    break
            if obj is not None:
                out.append((obj, f"layers/{leaf}/{i}"))
    return out


def param_arrays(net) -> dict:
    """Every parameter by leaf name as ``systems/kexaone.py``'s ``Stored``
    (host, in the stored type, float32 when an array is asked of it), read
    back leaf by leaf from the CALLING thread, as ``systems/ling.py`` does
    and for its reason (PERF.md, section 6, PR 41: a read-back on threads
    drew one of two step times for the rest of the process)."""
    return {leaf: _K.Stored(np.asarray(p.data().data))
            for p, leaf in param_leaves(net)}


class Trainer(_K.Trainer):
    """``systems/kexaone.py``'s trainer (its steps hand the expert layers'
    counts to ``moe.STEP_COUNTS``; here the prediction block's layer's
    last), built as ``system.Trainer`` builds its own but with the loss of
    both heads: ``NextTokenLoss`` weighted by the configuration's
    ``mtp_loss_weight``."""

    def __init__(self, net, opt: dict):
        from mxtpu import optimizer
        from mxtpu.parallel import DataParallelTrainer
        from mxtpu.parallel.mesh import data_parallel_mesh
        self.net, self.mesh = net, data_parallel_mesh()
        self.dpt = DataParallelTrainer(
            net, NextTokenLoss(weight=_LOSS_WEIGHT[-1]),
            optimizer.Adam(learning_rate=opt["lr"], beta1=opt["beta1"],
                           beta2=opt["beta2"], epsilon=opt["epsilon"]),
            self.mesh)
        self.beta1 = opt["beta1"]
        self._counts = [blk.moe.count for blk in layers(net)
                        if blk.mlp_kind == "moe"]
        moe_readers.STEP_COUNTS.clear()

    def param_arrays(self) -> dict:
        return param_arrays(self.net)


def kernel_path_counts() -> dict:
    """The program's count of call sites by kernel path; what the model said
    of its prediction block when the step was traced goes to the per-layer
    readers (``joyai.MTP_STATS``) and is printed beside it, with the expert
    layers' busiest and idlest experts (``systems/kexaone.py``)."""
    from mxtpu import profiler
    readers.MTP_STATS.clear()
    readers.MTP_STATS.update(profiler.get_launch_stats("mtp"))
    print(f"[system] prediction blocks traced: {readers.MTP_STATS}",
          flush=True)
    return _K.kernel_path_counts()
