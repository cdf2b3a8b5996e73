"""Weights from ``--seed``: one jitted call, in the type they are served in.

The benchmark makes the weights itself, so that the plain reference can make
the same ones from the same seed and takes nothing from the program. The tree
is the GPT-2 parameter set by its published names' roles; ``system.py`` maps
it onto the program's parameters, ``reference/gpt2.py`` reads it as it is.

Scheme (GPT-2's ``initializer_range``): every matrix and the token table
N(0, 0.02), the position table N(0, 0.01), biases 0, LayerNorm gains 1. The
residual projections are not scaled by 1/sqrt(2L): at seeded weights that only
changes the scale of the residual stream, not the work.
"""

from __future__ import annotations

import math

LAYER_MATRICES = ("qw", "kw", "vw", "ow", "f1w", "f2w")
LAYER_VECTORS = ("qb", "kb", "vb", "ob", "f1b", "f2b", "ln1_b", "ln2_b")
LAYER_GAINS = ("ln1_g", "ln2_g")


def shapes(cfg: dict) -> dict:
    """Leaf name -> shape for one layer, and for the tables. Matrices are
    (out, in), as ``y = x @ W.T + b``."""
    d, f = cfg["n_embd"], cfg["n_inner"] or 4 * cfg["n_embd"]
    layer = {"qw": (d, d), "kw": (d, d), "vw": (d, d), "ow": (d, d),
             "f1w": (f, d), "f2w": (d, f),
             "qb": (d,), "kb": (d,), "vb": (d,), "ob": (d,),
             "f1b": (f,), "f2b": (d,),
             "ln1_g": (d,), "ln1_b": (d,), "ln2_g": (d,), "ln2_b": (d,)}
    top = {"embed": (cfg["vocab_size"], d), "pos": (cfg["n_positions"], d),
           "ln_f_g": (d,), "ln_f_b": (d,)}
    return {"layer": layer, "top": top}


def matmul_params(cfg: dict) -> int:
    """Parameters that a token's forward pass multiplies by: the six
    matrices of every layer and the tied head (the token table, once)."""
    d, f = cfg["n_embd"], cfg["n_inner"] or 4 * cfg["n_embd"]
    return cfg["n_layer"] * (4 * d * d + 2 * d * f) + cfg["vocab_size"] * d


def make_weights(cfg: dict, seed: int, dtype: str):
    """``{"embed", "pos", "ln_f_g", "ln_f_b", "layers": [ {...} x L ]}`` on
    the default device, from one jitted program."""
    import jax
    import jax.numpy as jnp

    sh = shapes(cfg)
    L = cfg["n_layer"]
    dt = jnp.dtype(dtype)
    std = float(cfg.get("initializer_range", 0.02))

    drawn = [("embed", sh["top"]["embed"], std),
             ("pos", sh["top"]["pos"], 0.5 * std)] + \
        [(n, (L,) + sh["layer"][n], std) for n in LAYER_MATRICES]
    sizes = [math.prod(shape) for _, shape, _ in drawn]

    def build(key):
        # one normal draw for everything, then slices: a draw per shape
        # compiles twice as long (11.5 s against 5.6 s for gpt2-medium,
        # compiled for a described v5e, PR 23)
        flat = jax.random.normal(key, (sum(sizes),), jnp.float32)
        out, off = {}, 0
        for (name, shape, scale), k in zip(drawn, sizes):
            out[name] = (scale * flat[off:off + k].reshape(shape)).astype(dt)
            off += k
        stacked = {n: out.pop(n) for n in LAYER_MATRICES}
        out["ln_f_g"] = jnp.ones(sh["top"]["ln_f_g"], dt)
        out["ln_f_b"] = jnp.zeros(sh["top"]["ln_f_b"], dt)
        layers = []
        for i in range(L):
            lp = {name: stacked[name][i] for name in LAYER_MATRICES}
            lp.update({n: jnp.zeros(sh["layer"][n], dt) for n in LAYER_VECTORS})
            lp.update({n: jnp.ones(sh["layer"][n], dt) for n in LAYER_GAINS})
            layers.append(lp)
        out["layers"] = layers
        return out

    # the driver's seeds pass 2**31: fold the two halves in, PRNGKey takes
    # no more than 32 bits on a default install
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    return jax.jit(build)(key)
