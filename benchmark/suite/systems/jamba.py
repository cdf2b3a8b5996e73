"""The system under test for the ``jamba2-3b`` configuration, as its users
call it: ``HybridDecoderLM`` built from the configuration file's numbers
(the program has no preset): Mamba-1 layers with the Jamba family's inner
norms around one grouped-query attention layer without positions, a dense
SwiGLU after each, pre-norm RMSNorm, the tied head with float32 logits,
every block but the last recomputed in the backward, holding the seed's
weights, trained by ``system.Trainer`` (``DataParallelTrainer`` + Adam on
``data_parallel_mesh()``). What the cell shares with ``kexaone_train_t4096``
comes from ``systems/kexaone.py`` as it is: the parameters read back in
their stored type.
"""

from __future__ import annotations

import os

import numpy as np

import jamba as readers
import system
from manifest import load_module
# imported here and not inside build_net: a tree without the family's Mamba
# layer fails when the job loads this module, before anything is built
from mxtpu.gluon.model_zoo.hybrid_decoder import (  # noqa: F401
    HybridDecoderLM, Mamba)

_K = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "kexaone.py"), "suite_systems_kexaone_shared")

# reference leaf -> path below a block: (child, ..., parameter attribute)
COMMON = {"ln1_g": ("ln1", "gamma"), "ln2_g": ("ln2", "gamma"),
          "gate_up_w": ("mlp", "gate_up", "weight"),
          "down_w": ("mlp", "down", "weight")}
MIXER = {
    "mamba": {"in_w": ("in_proj", "weight"), "conv_w": ("conv_weight",),
              "conv_b": ("conv_bias",), "x_w": ("x_proj", "weight"),
              "dt_norm_g": ("dt_norm", "gamma"),
              "b_norm_g": ("b_norm", "gamma"),
              "c_norm_g": ("c_norm", "gamma"),
              "dt_w": ("dt_proj", "weight"), "dt_b": ("dt_proj", "bias"),
              "A_log": ("A_log",), "D": ("D",),
              "out_w": ("out_proj", "weight")},
    "attn_full": {"qkv_w": ("qkv", "weight"),
                  "o_w": ("out_proj", "weight")},
}


def build_net(cfg: dict, weights: dict, dtype: str):
    """``HybridDecoderLM`` at the configuration's sizes holding ``weights``
    (the reference's flat tree). Parameters are made in ``dtype`` (the cast
    comes first), so no float32 copy of the model ever exists."""
    d = cfg["hidden_size"]
    net = HybridDecoderLM(
        cfg["vocab_size"], cfg["layer_kinds"], units=d,
        ffn_units=cfg["intermediate_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=d // cfg["num_attention_heads"],
        d_inner=cfg["mamba_expand"] * d, d_state=cfg["mamba_d_state"],
        d_conv=cfg["mamba_d_conv"], dt_rank=cfg["mamba_dt_rank"],
        layer_norm_eps=cfg["rms_norm_eps"], attention="gqa", qk_norm=False,
        rope_kinds=(), norm="rms", norm_position="pre",
        tie_head=cfg["tie_word_embeddings"],
        float32_logits=cfg["float32_logits"],
        mamba_inner_norm=cfg["mamba_inner_norm"],
        remat=cfg["recompute_blocks"])
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
    """``[(Parameter, "layers/in_w/0" | "embed" | ...)]``: the program's
    parameters by the reference's leaf names; the token table ONCE (it is
    the head)."""
    out = [(net.embedding.weight, "embed"), (net.ln_f.gamma, "ln_f_g")]
    for i, blk in enumerate(net.blocks):
        mixer = {leaf: (blk.kind,) + path
                 for leaf, path in MIXER[blk.kind].items()}
        for leaf, path in {**COMMON, **mixer}.items():
            obj = blk
            for attr in path:
                obj = getattr(obj, attr)
            out.append((obj, f"layers/{leaf}/{i}"))
    return out


def param_arrays(net) -> dict:
    """Every parameter by leaf name as ``systems/kexaone.py``'s ``Stored``
    (host, in the stored type, float32 when an array is asked of it: a
    float32 copy of 1.6e9 parameters made on the device would not fit
    beside the 9.6 GB resident), read back leaf by leaf from the CALLING
    thread (PERF.md, section 7, PR 41: a read-back on threads drew one of
    two step times for the rest of the process)."""
    return {leaf: _K.Stored(np.asarray(p.data().data))
            for p, leaf in param_leaves(net)}


class Trainer(system.Trainer):
    """``system.Trainer`` whose parameters are read back by this
    configuration's leaf names; placing a batch counts the bytes of the
    head's float32 logits for the per-layer readers
    (``jamba.HEAD_STATS``)."""

    def place(self, tokens, targets):
        net = self.net
        readers.HEAD_STATS.clear()
        if net.head is not None or net._float32_logits:
            readers.HEAD_STATS["logits_bytes"] = \
                int(tokens.size) * net._vocab * 4
        return super().place(tokens, targets)

    def param_arrays(self) -> dict:
        return param_arrays(self.net)


def kernel_path_counts() -> dict:
    """The program's count of call sites by kernel path; what its scan op
    said of its newest launch, what the model recomputes and the bytes of
    its float32 logits go to the per-layer readers (``jamba.SCAN_STATS``,
    ``jamba.REMAT_STATS``, ``jamba.HEAD_STATS``) and are printed beside it."""
    from mxtpu import profiler
    readers.SCAN_STATS.clear()
    readers.REMAT_STATS.clear()
    readers.SCAN_STATS.update(profiler.get_launch_stats("ssm_scan"))
    readers.REMAT_STATS.update(profiler.get_remat_stats())
    print(f"[system] scan launches traced: {readers.SCAN_STATS}; "
          f"recomputed: {readers.REMAT_STATS}; float32 logits: "
          f"{readers.HEAD_STATS}", flush=True)
    return profiler.get_kernel_path_counts()
