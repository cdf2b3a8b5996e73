"""The system under test for the ``brumby-14b-base`` configuration, as its
users call it: ``HybridDecoderLM`` built from the configuration file's
numbers (the program has no preset), every layer of kind ``retention``
(power-retention layers on grouped heads with q/k norm and rotary positions)
beside a dense SwiGLU, pre-norm RMSNorm, an untied head, a block recomputed
at a time in the backward, holding the seed's weights, trained by
``system.Trainer`` (``DataParallelTrainer`` + Adam on
``data_parallel_mesh()``). What the cell shares with ``kexaone_train_t4096``
comes from ``systems/kexaone.py`` as it is: the parameters read back in
their stored type.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import brumby as readers
import system
from manifest import load_module
# imported here and not inside build_net: a tree without the retention
# mixer fails when the job loads this module, before anything is built
from mxtpu.gluon.model_zoo.hybrid_decoder import (  # noqa: F401
    HybridDecoderLM, PowerRetention)

_K = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "kexaone.py"), "suite_systems_kexaone_shared")

# reference leaf -> path below a block: (child, ..., parameter attribute)
LEAVES = {"ln1_g": ("ln1", "gamma"), "ln2_g": ("ln2", "gamma"),
          "qkv_w": ("retention", "qkv", "weight"),
          "o_w": ("retention", "out_proj", "weight"),
          "q_norm_g": ("retention", "q_norm"),
          "k_norm_g": ("retention", "k_norm"),
          "gate_w": ("retention", "gate", "weight"),
          "gate_b": ("retention", "gate", "bias"),
          "gate_up_w": ("mlp", "gate_up", "weight"),
          "down_w": ("mlp", "down", "weight")}


def build_net(cfg: dict, weights: dict, dtype: str):
    """``HybridDecoderLM`` at the configuration's sizes holding ``weights``
    (the reference's flat tree). Parameters are made in ``dtype`` (the cast
    comes first), so no float32 copy of the model ever exists."""
    net = HybridDecoderLM(
        cfg["vocab_size"], ["retention"] * cfg["num_hidden_layers"],
        units=cfg["hidden_size"], ffn_units=cfg["intermediate_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        layer_norm_eps=cfg["rms_norm_eps"], attention="gqa", qk_norm=True,
        rope_kinds=("retention",), rope_theta=cfg["rope_theta"], norm="rms",
        norm_position="pre", tie_head=cfg["tie_word_embeddings"],
        remat=cfg["recompute_blocks"], retention_eps=cfg["retention_eps"])
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
    """``[(Parameter, "layers/qkv_w/0" | "embed" | ...)]``: the program's
    parameters by the reference's leaf names."""
    out = [(net.embedding.weight, "embed"), (net.head.weight, "head"),
           (net.ln_f.gamma, "ln_f_g")]
    for i, blk in enumerate(net.blocks):
        for leaf, path in LEAVES.items():
            obj = blk
            for attr in path:
                obj = getattr(obj, attr)
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


class Trainer(system.Trainer):
    """``system.Trainer`` whose parameters are read back by this
    configuration's leaf names."""

    def param_arrays(self) -> dict:
        return param_arrays(self.net)


def kernel_path_counts() -> dict:
    """The program's count of call sites by kernel path; what its retention
    op said of its newest launch goes to the per-layer readers
    (``brumby.RETENTION_STATS``) and is printed beside it."""
    from mxtpu import profiler
    readers.RETENTION_STATS.clear()
    readers.RETENTION_STATS.update(profiler.get_retention_stats())
    print(f"[system] retention launches traced: {readers.RETENTION_STATS}",
          flush=True)
    return profiler.get_kernel_path_counts()
