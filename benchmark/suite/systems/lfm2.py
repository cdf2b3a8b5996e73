"""The system under test for the ``lfm2-8b-a1b`` configuration, as its users
call it: ``HybridDecoderLM`` built from the configuration file's numbers
(the program has no preset) with gated short convolutions beside
grouped-query attention (q/k norm, rotary positions), pre-norm RMSNorm, a
tied head and sparse expert layers that hold ALL their experts and no shared
one, holding the seed's weights, trained by ``system.Trainer``
(``DataParallelTrainer`` + Adam on ``data_parallel_mesh()``). What the cell
shares with ``kexaone_train_t4096`` comes from ``systems/kexaone.py`` as it
is: the parameters read back in their stored type, and the trainer that
hands every step's expert counts to ``moe.STEP_COUNTS``.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import moe as readers
from manifest import load_module
# imported here and not inside build_net: a tree without the convolution
# mixer fails when the job loads this module, before anything is built
from mxtpu.gluon.model_zoo.hybrid_decoder import (  # noqa: F401
    HybridDecoderLM, ShortConv)

_K = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "kexaone.py"), "suite_systems_kexaone_shared")
system = _K.system

# reference leaf -> path below a block: (child, ..., parameter attribute)
LEAVES = {"ln1_g": ("ln1", "gamma"), "ln2_g": ("ln2", "gamma"),
          "conv_in_w": ("conv", "in_proj", "weight"),
          "conv_w": ("conv", "conv_weight"),
          "conv_out_w": ("conv", "out_proj", "weight"),
          "qkv_w": ("attn_full", "qkv", "weight"),
          "o_w": ("attn_full", "out_proj", "weight"),
          "q_norm_g": ("attn_full", "q_norm"),
          "k_norm_g": ("attn_full", "k_norm"),
          "gate_up_w": ("mlp", "gate_up", "weight"),
          "down_w": ("mlp", "down", "weight"),
          "router_w": ("moe", "router"), "router_b": ("moe", "select_bias"),
          "experts_gate_up_w": ("moe", "gate_up"),
          "experts_down_w": ("moe", "down")}
KINDS = {"conv": "conv", "full_attention": "attn_full"}


def build_net(cfg: dict, weights: dict, dtype: str):
    """``HybridDecoderLM`` at the configuration's sizes holding ``weights``
    (the reference's flat tree). Parameters are made in ``dtype`` (the cast
    comes first), so no float32 copy of the model ever exists."""
    layers = cfg["num_hidden_layers"]
    net = HybridDecoderLM(
        cfg["vocab_size"], [KINDS[k] for k in cfg["layer_types"]],
        units=cfg["hidden_size"], ffn_units=cfg["intermediate_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_conv=cfg["conv_L_cache"], layer_norm_eps=cfg["norm_eps"],
        attention="gqa", qk_norm=True, rope_kinds=("attn_full",),
        rope_theta=cfg["rope_theta"], norm="rms", norm_position="pre",
        tie_head=cfg["tie_embedding"],
        float32_logits=cfg["float32_logits"],
        mlp_kinds=["mlp" if i < cfg["num_dense_layers"] else "moe"
                   for i in range(layers)],
        moe=dict(ffn_units=cfg["moe_intermediate_size"],
                 num_experts=cfg["num_experts"],
                 top_k=cfg["num_experts_per_tok"], held=None,
                 shared_ffn_units=0,
                 routed_scale=cfg["routed_scaling_factor"],
                 bias_update_rate=cfg["router_bias_update_rate"],
                 weight_eps=cfg["router_weight_eps"]))
    net.cast(dtype)
    # zeros, not a random draw of every shape (the seed's weights follow)
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
    """``[(Parameter, "layers/conv_w/0" | "embed" | ...)]``: the program's
    parameters by the reference's leaf names, the routers' selection bias (a
    state the step moves by rule) among them; the token table once (it is
    the head)."""
    out = [(net.embedding.weight, "embed"), (net.ln_f.gamma, "ln_f_g")]
    for i, blk in enumerate(net.blocks):
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
    (host, in the stored type, float32 when an array is asked of it)."""
    leaves = param_leaves(net)
    with ThreadPoolExecutor(4) as pool:
        arrays = list(pool.map(
            lambda pl: np.asarray(pl[0].data().data), leaves))
    return {leaf: _K.Stored(a) for (_, leaf), a in zip(leaves, arrays)}


class Trainer(_K.Trainer):
    """``systems/kexaone.py``'s trainer (every step's expert counts go to
    ``moe.STEP_COUNTS`` as device arrays) over this configuration's leaf
    names."""

    def param_arrays(self) -> dict:
        """Read back, after checking that every expert layer moved
        ``tokens * top_k`` pairs in every step so far: all experts are
        held, so a step's work does not depend on its routing. That number
        is the layer's row buffer (``stats()["buffer_rows"]``: the worst
        case, one pass)."""
        from mxtpu import profiler
        want = [float(r["buffer_rows"])
                for r in profiler.get_moe_stats(self.net)]
        for step, counts in enumerate(readers.STEP_COUNTS):
            sums = [float(np.asarray(c).sum()) for c in counts]
            if sums != want:
                raise SystemExit(f"benchmark: step {step}: the expert layers "
                                 f"moved {sums} pairs, not {want}")
        print(f"[system] held pairs a layer in each of the "
              f"{len(readers.STEP_COUNTS)} steps so far: {want}", flush=True)
        return param_arrays(self.net)


kernel_path_counts = _K.kernel_path_counts
