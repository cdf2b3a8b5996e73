"""The system under test for the ``phi4-mini-flash`` configuration, as its
users call it: ``HybridDecoderLM`` built from the configuration file's
numbers (the program has no preset), holding the seed's weights, trained by
``system.Trainer`` (``DataParallelTrainer`` + Adam on
``data_parallel_mesh()``), the same way ``system.py`` drives
``TransformerLM``. With ``system.py`` and ``scopes.py`` this is a file of
the benchmark that imports the program.
"""

from __future__ import annotations

import system
# imported here and not inside build_net: a tree without the family fails
# when the job loads this module, before anything is built
from mxtpu.gluon.model_zoo.hybrid_decoder import HybridDecoderLM

# reference leaf -> path below a block: (child, ..., parameter attribute)
COMMON = {"ln1_g": ("ln1", "gamma"), "ln1_b": ("ln1", "beta"),
          "ln2_g": ("ln2", "gamma"), "ln2_b": ("ln2", "beta"),
          "gate_up_w": ("mlp", "gate_up", "weight"),
          "down_w": ("mlp", "down", "weight")}
ATTENTION = {"qkv_w": ("qkv", "weight"), "qkv_b": ("qkv", "bias"),
             "o_w": ("out_proj", "weight"), "o_b": ("out_proj", "bias"),
             "lq1": ("lambda_q1",), "lk1": ("lambda_k1",),
             "lq2": ("lambda_q2",), "lk2": ("lambda_k2",),
             "subln": ("subln",)}
MIXER = {
    "mamba": {"in_w": ("in_proj", "weight"), "conv_w": ("conv_weight",),
              "conv_b": ("conv_bias",), "x_w": ("x_proj", "weight"),
              "dt_w": ("dt_proj", "weight"), "dt_b": ("dt_proj", "bias"),
              "A_log": ("A_log",), "D": ("D",),
              "out_w": ("out_proj", "weight")},
    "gmu": {"in_w": ("in_proj", "weight"), "out_w": ("out_proj", "weight")},
    "attn_window": ATTENTION, "attn_full": ATTENTION, "attn_cross": ATTENTION,
}


def build_net(cfg: dict, weights: dict, dtype: str):
    """``HybridDecoderLM`` at the configuration's sizes holding ``weights``
    (the reference's flat tree)."""
    d = cfg["hidden_size"]
    net = HybridDecoderLM(
        cfg["vocab_size"], cfg["layer_kinds"], units=d,
        ffn_units=cfg["intermediate_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=d // cfg["num_attention_heads"],
        window=cfg["sliding_window"], d_inner=cfg["mamba_expand"] * d,
        d_state=cfg["mamba_d_state"], d_conv=cfg["mamba_d_conv"],
        dt_rank=cfg["mamba_dt_rank"], layer_norm_eps=cfg["layer_norm_eps"])
    # zeros, not a random draw of every shape: the seed's weights follow
    net.initialize(init="zeros")
    net.cast(dtype)
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
    parameters by the reference's leaf names."""
    out = [(net.embedding.weight, "embed"), (net.ln_f.gamma, "ln_f_g"),
           (net.ln_f.beta, "ln_f_b")]
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
    """Every parameter by leaf name, as host float32 arrays; widened on the
    device in ONE program (a program per shape, as ``system.param_arrays``
    has it, is forty compiles in a cold run of this model)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    leaves = param_leaves(net)
    wide = jax.jit(lambda xs: [x.astype(jnp.float32) for x in xs])(
        [p.data().data for p, _ in leaves])
    return {leaf: np.asarray(x) for (_, leaf), x in zip(leaves, wide)}


class Trainer(system.Trainer):
    """``system.Trainer`` whose parameters are read back by this
    configuration's leaf names."""

    def param_arrays(self) -> dict:
        return param_arrays(self.net)


def kernel_path_counts() -> dict:
    """The program's count of call sites by kernel path."""
    from mxtpu import profiler
    return profiler.get_kernel_path_counts()
