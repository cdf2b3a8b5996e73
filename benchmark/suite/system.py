"""The system under test, as its users call it. This is the only file of the
benchmark that imports the program; everything else here is the yardstick.

It builds the one ``TransformerLM`` from a configuration file's numbers (no
preset of the program is used), installs the seed's weights, and hands out a
``DataParallelTrainer`` or a ``ServingEngine`` built the way ``chip_smoke.py``
and the examples build them.
"""

from __future__ import annotations

import numpy as np


def place_compile_cache() -> str:
    """The program's one site decides: ``JAX_COMPILATION_CACHE_DIR`` where
    set, else ``<checkout>/.jax_cache``; every program is kept, however
    quickly it compiled."""
    from mxtpu import compile_cache
    return compile_cache.place()


def build_net(cfg: dict, weights: dict, dtype: str):
    """``TransformerLM`` at the configuration's sizes holding ``weights``."""
    from mxtpu.gluon.model_zoo.transformer import TransformerLM
    net = TransformerLM(
        cfg["vocab_size"], units=cfg["n_embd"], num_layers=cfg["n_layer"],
        num_heads=cfg["n_head"], max_len=cfg["n_positions"],
        ffn_units=cfg["n_inner"] or 4 * cfg["n_embd"], dropout=0.0,
        tie_weights=True)
    net.initialize()
    net.cast(dtype)
    for param, leaf in param_leaves(net):
        param.set_data(_leaf(weights, leaf))
    return net


def param_leaves(net) -> list:
    """``[(Parameter, "layers/qw/3" | "embed" | ...)]``: the program's
    parameters by the benchmark's leaf names."""
    out = [(net.embedding.weight, "embed"), (net.pos_embed, "pos"),
           (net.ln_f.gamma, "ln_f_g"), (net.ln_f.beta, "ln_f_b")]
    for i, blk in enumerate(net.blocks):
        at = blk.attn
        for name, p in (
                ("ln1_g", blk.ln1.gamma), ("ln1_b", blk.ln1.beta),
                ("qw", at.q_proj.weight), ("qb", at.q_proj.bias),
                ("kw", at.k_proj.weight), ("kb", at.k_proj.bias),
                ("vw", at.v_proj.weight), ("vb", at.v_proj.bias),
                ("ow", at.out_proj.weight), ("ob", at.out_proj.bias),
                ("ln2_g", blk.ln2.gamma), ("ln2_b", blk.ln2.beta),
                ("f1w", blk.ffn1.weight), ("f1b", blk.ffn1.bias),
                ("f2w", blk.ffn2.weight), ("f2b", blk.ffn2.bias)):
            out.append((p, f"layers/{name}/{i}"))
    return out


def _leaf(weights: dict, leaf: str):
    parts = leaf.split("/")
    if parts[0] == "layers":
        return weights["layers"][int(parts[2])][parts[1]]
    return weights[leaf]


def seq_loss(logits, y):
    """Mean next-token cross entropy, as ``chip_smoke.py`` trains."""
    from mxtpu.gluon.loss import SoftmaxCrossEntropyLoss
    b, t, v = logits.shape
    return SoftmaxCrossEntropyLoss()(
        logits.reshape((b * t, v)), y.reshape((b * t,)))


class Trainer:
    """``DataParallelTrainer`` + Adam over ``data_parallel_mesh()``."""

    def __init__(self, net, opt: dict):
        from mxtpu import optimizer
        from mxtpu.parallel import DataParallelTrainer
        from mxtpu.parallel.mesh import data_parallel_mesh
        self.net = net
        self.mesh = data_parallel_mesh()
        self.dpt = DataParallelTrainer(
            net, seq_loss,
            optimizer.Adam(learning_rate=opt["lr"], beta1=opt["beta1"],
                           beta2=opt["beta2"], epsilon=opt["epsilon"]),
            self.mesh)
        self.beta1 = opt["beta1"]

    def place(self, tokens, targets):
        """A host batch onto the mesh, the way ``dpt.step`` would place it;
        placed once, a batch rides every later step with no transfer."""
        from mxtpu import nd
        from mxtpu.parallel import shard_batch
        x = shard_batch(nd.array(tokens), self.mesh)
        y = shard_batch(nd.array(targets.astype(np.float32)), self.mesh)
        return x, y

    def step(self, x, y) -> float:
        """One training step, ending in the loss readback."""
        return self.dpt.step(x, y)

    def first_gradient_norm(self) -> float:
        """The L2 norm of the whole gradient as the optimizer got it, worked
        out from Adam's state after ONE step: the first moment is then
        ``(1 - beta1) * g``. Read through ``optimizer_slots()``, the
        trainer's public accessor, which hands out the slots as placed and
        names no leaf (ZeRO packs leaves into zero-padded buckets), so the
        norm is of all leaves together. Of Adam's two moments only the first
        has negative entries; that is how its slots are told."""
        import jax
        import jax.numpy as jnp

        def reduce(slots):
            return [(jnp.sum(jnp.square(s.astype(jnp.float32))),
                     jnp.any(s < 0)) for s in slots]

        parts = jax.jit(reduce)(self.dpt.optimizer_slots())
        squares = [float(sq) for sq, signed in parts if bool(signed)]
        if not squares:
            raise SystemExit("benchmark: no first-moment slot found among "
                             f"the trainer's {len(parts)} optimizer slots")
        return float(np.sqrt(sum(squares))) / (1.0 - self.beta1)

    def param_arrays(self) -> dict:
        return param_arrays(self.net)


def param_arrays(net) -> dict:
    """Every parameter by leaf name, as host float32 arrays."""
    return {leaf: _host_f32(p.data().data) for p, leaf in param_leaves(net)}


def _host_f32(x) -> np.ndarray:
    """A device array on the host as float32; widened on the device, where
    it costs nothing (numpy widens bfloat16 slowly)."""
    import jax.numpy as jnp
    return np.asarray(x.astype(jnp.float32))


def make_engine(net, engine_args: dict):
    from mxtpu import profiler
    from mxtpu.serving import ServingEngine
    profiler.reset_serving_stats()
    return ServingEngine(net, **engine_args)


def reset_serving_stats() -> None:
    from mxtpu import profiler
    profiler.reset_serving_stats()


def serving_stats() -> dict:
    from mxtpu import profiler
    return profiler.get_serving_stats()


def record_program_spans(on: bool) -> None:
    """Arm (from empty) or stop the program's own host spans
    (``observability/tracer``)."""
    from mxtpu.observability import tracer
    if on:
        tracer.reset()
        tracer.start()
    else:
        tracer.stop()


def program_spans() -> list:
    """What the program's tracer recorded:
    ``[{"name", "ph", "ts" (us), "dur" (us), "args"}]``."""
    from mxtpu.observability import tracer
    return [ev for _, _, events, _ in tracer.snapshot_buffers()
            for ev in events]
