"""The system under test for the ``k-exaone-236b`` configuration, as its
users call it: ``HybridDecoderLM`` built from the configuration file's
numbers (the program has no preset) with grouped-query window / full
attention, RMSNorm on each sub-layer's output, an untied head and sparse
expert layers that hold this chip's share of the experts, holding the seed's
weights, trained by ``system.Trainer`` (``DataParallelTrainer`` + Adam on
``data_parallel_mesh()``). With ``system.py``, ``scopes.py`` and
``systems/phi4flash.py`` this is a file of the benchmark that imports the
program.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

import moe as readers
import system
# imported here and not inside build_net: a tree without the family's
# attention and expert layer fails when the job loads this module, before
# anything is built
from mxtpu.gluon.model_zoo.hybrid_decoder import (  # noqa: F401
    GroupedQueryAttention, HybridDecoderLM)
from mxtpu.parallel.moe import SparseExperts  # noqa: F401

# reference leaf -> path below a block: (child, ..., parameter attribute);
# "<attn>" is the block's attention kind
LEAVES = {"ln1_g": ("ln1", "gamma"), "ln2_g": ("ln2", "gamma"),
          "qkv_w": ("<attn>", "qkv", "weight"),
          "o_w": ("<attn>", "out_proj", "weight"),
          "q_norm_g": ("<attn>", "q_norm"), "k_norm_g": ("<attn>", "k_norm"),
          "gate_up_w": ("mlp", "gate_up", "weight"),
          "down_w": ("mlp", "down", "weight"),
          "router_w": ("moe", "router"), "router_b": ("moe", "select_bias"),
          "experts_gate_up_w": ("moe", "gate_up"),
          "experts_down_w": ("moe", "down"),
          "shared_gate_up_w": ("moe", "shared", "gate_up", "weight"),
          "shared_down_w": ("moe", "shared", "down", "weight")}
KINDS = {"sliding_attention": "attn_window", "full_attention": "attn_full"}
MLP_KINDS = {"dense": "mlp", "sparse": "moe"}


def build_net(cfg: dict, weights: dict, dtype: str):
    """``HybridDecoderLM`` at the configuration's sizes holding ``weights``
    (the reference's flat tree). Parameters are made in ``dtype`` (the cast
    comes first), so no float32 copy of the model ever exists."""
    net = HybridDecoderLM(
        cfg["vocab_size"], [KINDS[k] for k in cfg["layer_types"]],
        units=cfg["hidden_size"], ffn_units=cfg["intermediate_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        window=cfg["sliding_window"], layer_norm_eps=cfg["rms_norm_eps"],
        attention="gqa", qk_norm=True, rope_kinds=("attn_window",),
        rope_theta=cfg["rope_parameters"]["rope_theta"], norm="rms",
        norm_position="post", tie_head=cfg["tie_word_embeddings"],
        mlp_kinds=[MLP_KINDS[k] for k in cfg["mlp_layer_types"]],
        moe=dict(ffn_units=cfg["moe_intermediate_size"],
                 num_experts=cfg["published_num_experts"],
                 top_k=cfg["num_experts_per_tok"], held=cfg["held_experts"],
                 shared_ffn_units=cfg["num_shared_experts"]
                 * cfg["moe_intermediate_size"],
                 routed_scale=cfg["routed_scaling_factor"],
                 bias_update_rate=cfg["router_bias_update_rate"]))
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
    """``[(Parameter, "layers/qkv_w/0" | "embed" | ...)]``: the program's
    parameters by the reference's leaf names, the routers' selection bias (a
    state the step moves by rule) among them."""
    out = [(net.embedding.weight, "embed"), (net.head.weight, "head"),
           (net.ln_f.gamma, "ln_f_g")]
    for i, blk in enumerate(net.blocks):
        for leaf, path in LEAVES.items():
            obj = blk
            for attr in path:
                obj = getattr(obj, blk.kind if attr == "<attn>" else attr,
                              None)
                if obj is None:
                    break
            if obj is not None:
                out.append((obj, f"layers/{leaf}/{i}"))
    return out


def _widen(a: np.ndarray) -> np.ndarray:
    """A host array as float32: bfloat16 is the top half of a float32."""
    if a.dtype == np.float32:
        return a
    wide = a.view(np.uint16).astype(np.uint32)
    wide <<= 16
    return wide.view(np.float32)


class Stored:
    """A parameter as read back, on the host in the type it is STORED in
    (2 bytes a number for bfloat16). ``later - earlier`` is their float32
    difference, made when an array is asked of it (``np.asarray``,
    ``np.linalg.norm``) and not before: the job subtracts every leaf of two
    read-backs and then takes the norms one by one, and three float32 copies
    of 2.0e9 parameters beside the step's compile do not fit the machine's
    40 GiB."""

    def __init__(self, stored: np.ndarray):
        self.stored = stored

    def __array__(self, dtype=None, copy=None):
        return _widen(self.stored).astype(dtype or np.float32, copy=False)

    def __sub__(self, earlier: "Stored"):
        return _Change(self, earlier)


class _Change:
    def __init__(self, later: Stored, earlier: Stored):
        self.later, self.earlier = later, earlier

    def __array__(self, dtype=None, copy=None):
        a, b = self.later.stored.reshape(-1), self.earlier.stored.reshape(-1)
        out = np.empty(a.shape, np.float32)

        def part(lo):       # numpy lets go of the interpreter in each pass
            np.subtract(_widen(a[lo:lo + _CHUNK]), _widen(b[lo:lo + _CHUNK]),
                        out=out[lo:lo + _CHUNK])

        with ThreadPoolExecutor(8) as pool:
            list(pool.map(part, range(0, a.size, _CHUNK)))
        return out.reshape(self.later.stored.shape).astype(
            dtype or np.float32, copy=False)


_CHUNK = 1 << 24        # numbers a thread widens and subtracts at a time


def param_arrays(net) -> dict:
    """Every parameter by leaf name as ``Stored`` (host, float32 when an
    array is asked of it), read back leaf by leaf in its stored type
    (widening 2.0e9 parameters in one device program takes 8 GB beside the
    12 resident), a few leaves at a time on threads."""
    leaves = param_leaves(net)
    with ThreadPoolExecutor(4) as pool:
        arrays = list(pool.map(
            lambda pl: np.asarray(pl[0].data().data), leaves))
    return {leaf: Stored(a) for (_, leaf), a in zip(leaves, arrays)}


class Trainer(system.Trainer):
    """``system.Trainer`` whose parameters are read back by this
    configuration's leaf names, and whose steps hand the expert layers'
    counts to the per-layer readers (``moe.STEP_COUNTS``): the device arrays
    as the step left them, which costs a step four attribute reads and no
    transfer."""

    def __init__(self, net, opt: dict):
        super().__init__(net, opt)
        self._counts = [blk.moe.count for blk in net.blocks
                        if blk.mlp_kind == "moe"]
        readers.STEP_COUNTS.clear()

    def step(self, x, y) -> float:
        loss = super().step(x, y)
        readers.STEP_COUNTS.append([p.data().data for p in self._counts])
        return loss

    def param_arrays(self) -> dict:
        return param_arrays(self.net)


def kernel_path_counts() -> dict:
    """The program's count of call sites by kernel path; the tokens that
    chose the busiest and the idlest expert of each expert layer in the
    newest step are printed beside it (the job asks once, after the checked
    steps)."""
    from mxtpu import profiler
    for at, count in enumerate(readers.STEP_COUNTS[-1]
                               if readers.STEP_COUNTS else ()):
        count = np.asarray(count)
        print(f"[system] expert layer {at}: tokens that chose an expert, "
              f"most {count.max():g}, fewest {count.min():g}", flush=True)
    return profiler.get_kernel_path_counts()
