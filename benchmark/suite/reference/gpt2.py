"""Plain reference: GPT-2's equations in ``jax.numpy``, float32, matmuls at
``highest`` precision. No kernels, no cache, no batching tricks; it imports
nothing of the program and is given only the seed's weights and tokens.

    x   = E[tok] + P[0:T]
    x  += Wo . softmax(causal(q k^T / sqrt(D))) v + bo      q,k,v from LN1(x)
    x  += W2 . act(W1 . LN2(x) + b1) + b2
    out = LN_f(x) . E^T                                      (tied head)

``act`` is the source's ``activation_function``: ``gelu_new`` is the tanh
form (GPT-2), ``gelu`` the erf form (Cerebras-GPT). Layers run under
``lax.scan`` over stacked weights, so the reference compiles in seconds at
any depth, and each layer is rematerialised in the backward pass, so that it
fits beside nothing else at the timed sizes.

``precision`` is the control's lever (see ``check.py``): ``None`` is this
reference; ``"fp8"`` / ``"int8"`` round both operands of every matmul to that
type first, which is what a lower-precision path of the program would do.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
MATRICES = ("qw", "kw", "vw", "ow", "f1w", "f2w")


def stack_layers(weights: dict, dtype=jnp.float32) -> dict:
    """The weights tree with its list of layers stacked along a new first
    axis and every leaf cast to ``dtype``."""
    layers = weights["layers"]
    stacked = {k: jnp.stack([lp[k] for lp in layers]).astype(dtype)
               for k in layers[0]}
    out = {k: v.astype(dtype) for k, v in weights.items() if k != "layers"}
    out["layers"] = stacked
    return out


def _round_to(x, precision):
    """``x`` rounded to the control's type, as float32."""
    if precision == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    if precision == "int8":
        # symmetric, one scale per row: per token for activations and
        # cotangents, per output channel for an (out, in) weight
        s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
        s = jnp.where(s == 0, 1.0, s)
        return jnp.round(x / s).clip(-127, 127) * s
    raise ValueError(f"unknown control precision {precision!r}")


def _fake_quant(x, precision):
    """An operand as the lower precision sees it. The gradient passes
    straight through the rounding, as a quantised training path's does."""
    if precision is None:
        return x
    return x + lax.stop_gradient(_round_to(x, precision) - x)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _quant_cotangent(y, precision):
    """Identity whose cotangent is rounded: the backward matmuls of a
    lower-precision path take their incoming gradient in that type too."""
    return y


_quant_cotangent.defvjp(lambda y, precision: (y, None),
                        lambda precision, _, g: (_round_to(g, precision),))


def _mm(x, w, precision):
    """``x @ w.T`` for a (out, in) weight."""
    y = jnp.einsum("...i,oi->...o", _fake_quant(x, precision),
                   _fake_quant(w, precision), precision=HIGHEST)
    return y if precision is None else _quant_cotangent(y, precision)


def _ln(x, g, b, eps):
    m = jnp.mean(x, axis=-1, keepdims=True)
    v = jnp.mean(jnp.square(x - m), axis=-1, keepdims=True)
    return (x - m) * lax.rsqrt(v + eps) * g + b


def _act(x, name):
    if name == "gelu_new":
        return 0.5 * x * (1.0 + jnp.tanh(
            math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))
    if name == "gelu":
        return 0.5 * x * (1.0 + lax.erf(x / math.sqrt(2.0)))
    raise ValueError(f"unknown activation_function {name!r}")


def hidden(cfg: dict, w: dict, tokens, precision=None):
    """The final LayerNorm's output ``(B, T, d)``: everything but the head."""
    B, T = tokens.shape
    H = cfg["n_head"]
    D = cfg["n_embd"] // H
    eps = cfg["layer_norm_epsilon"]
    act = cfg["activation_function"]
    causal = jnp.tril(jnp.ones((T, T), bool))

    def layer(x, lp):
        h = _ln(x, lp["ln1_g"], lp["ln1_b"], eps)
        q = (_mm(h, lp["qw"], precision) + lp["qb"]).reshape(B, T, H, D)
        k = (_mm(h, lp["kw"], precision) + lp["kb"]).reshape(B, T, H, D)
        v = (_mm(h, lp["vw"], precision) + lp["vb"]).reshape(B, T, H, D)
        s = jnp.einsum("bqhd,bkhd->bhqk", _fake_quant(q, precision),
                       _fake_quant(k, precision),
                       precision=HIGHEST) / math.sqrt(D)
        s = jnp.where(causal[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", _fake_quant(p, precision),
                         _fake_quant(v, precision), precision=HIGHEST)
        x = x + _mm(ctx.reshape(B, T, H * D), lp["ow"], precision) + lp["ob"]
        g = _ln(x, lp["ln2_g"], lp["ln2_b"], eps)
        g = _act(_mm(g, lp["f1w"], precision) + lp["f1b"], act)
        return x + _mm(g, lp["f2w"], precision) + lp["f2b"], None

    x = w["embed"][tokens] + w["pos"][:T][None]
    x, _ = lax.scan(jax.checkpoint(layer), x, w["layers"])
    return _ln(x, w["ln_f_g"], w["ln_f_b"], eps)


def forward(cfg: dict, w: dict, tokens, precision=None):
    """Logits ``(B, T, vocab)`` in float32 for int tokens ``(B, T)``; ``w``
    is a :func:`stack_layers` tree."""
    return _mm(hidden(cfg, w, tokens, precision), w["embed"], precision)


HEAD_CHUNK = 1024   # positions whose logits exist at one time in loss_fn


def loss_fn(cfg: dict, w: dict, tokens, targets, precision=None):
    """Mean next-token cross entropy over every position of every row. The
    head runs over ``HEAD_CHUNK`` positions at a time, rematerialised, so
    that the (positions, vocab) logits never exist whole."""
    x = hidden(cfg, w, tokens, precision)
    n = x.shape[0] * x.shape[1]
    chunk = math.gcd(n, HEAD_CHUNK)

    @jax.checkpoint
    def chunk_loss(xy):
        xc, yc = xy
        logits = _mm(xc, w["embed"], precision)
        picked = jnp.take_along_axis(logits, yc[:, None], axis=-1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked)

    sums = lax.map(chunk_loss, (x.reshape(n // chunk, chunk, -1),
                                targets.reshape(n // chunk, chunk)))
    return jnp.sum(sums) / n


def stack_norms(tree: dict) -> dict:
    """L2 norms of a stacked tree's leaves: a scalar for each table, one per
    layer, ``(L,)``, for each stacked leaf. Traceable."""
    def norm(a, axes):
        return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)), axis=axes))
    out = {k: norm(v, None) for k, v in tree.items() if k != "layers"}
    out["layers"] = {k: norm(v, tuple(range(1, v.ndim)))
                     for k, v in tree["layers"].items()}
    return out


def flat_leaf_norms(norms: dict) -> dict:
    """``{"embed": n, ..., "layers/qw/3": n}`` from :func:`stack_norms`'
    output: one entry per leaf as the program counts leaves (one per layer,
    not one per stack)."""
    norms = jax.device_get(norms)
    out = {k: float(v) for k, v in norms.items() if k != "layers"}
    for name, per_layer in norms["layers"].items():
        for i, n in enumerate(per_layer):
            out[f"layers/{name}/{i}"] = float(n)
    return out


def train_steps(cfg: dict, weights: dict, batches, opt: dict, store_dtype,
                row_block: int, precision=None) -> dict:
    """Follow the first ``len(batches)`` Adam steps in float32.

    ``batches`` is a list of ``(tokens, targets)`` int arrays ``(B, T)``.
    Loss and gradient of ``row_block`` rows are one program on the
    accelerator over float32 weights. Weights, Adam's moments and the sum of
    the blocks' gradients live on the host, and the update is one program of
    JAX's CPU backend, so that the accelerator holds no more than weights,
    one gradient and one block's activations, and the reference's peak stays
    under the program's. Between steps the parameters are rounded to
    ``store_dtype``, the type the configuration trains in: a float32 master
    copy would be a different job. Adam's moments stay float32.

    Returns host numbers: ``loss`` per step, ``grad_norm`` of the first
    step's gradient per leaf, ``delta_norm`` of the parameters' change over
    all the steps per leaf.
    """
    b1, b2, eps, lr = opt["beta1"], opt["beta2"], opt["epsilon"], opt["lr"]
    host = jax.devices("cpu")[0]
    accel = jax.devices()[0]
    grad_fn = jax.jit(jax.value_and_grad(
        lambda w, x, y: loss_fn(cfg, w, x, y, precision)))
    B = batches[0][0].shape[0]
    if B % row_block:
        raise ValueError(f"row_block {row_block} does not divide batch {B}")
    n_blocks = B // row_block
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=0)

    @functools.partial(jax.jit, donate_argnums=0)
    def mean_and_norms(total):
        g = jax.tree.map(lambda a: a / n_blocks, total)
        return g, stack_norms(g)

    def gradient(w, tokens, targets):
        """Mean loss and gradient over the batch, block by block; the sum
        is kept on the host."""
        w_dev = jax.device_put(w, accel)
        loss, total = 0.0, None
        for i in range(0, B, row_block):
            lv, g = grad_fn(w_dev, tokens[i:i + row_block],
                            targets[i:i + row_block])
            loss += float(lv) / n_blocks
            g = jax.device_put(g, host)
            total = g if total is None else add(total, g)
        return (loss,) + mean_and_norms(total)

    def adam(w, m, v, g, coef):
        m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
        v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
        w = jax.tree.map(
            lambda p, mm, vv: (p - coef * mm / (jnp.sqrt(vv) + eps))
            .astype(store_dtype).astype(jnp.float32), w, m, v)
        return w, m, v

    adam = jax.jit(adam, donate_argnums=(0, 1, 2))
    w0 = jax.device_put(jax.jit(stack_layers)(weights), host)
    w = jax.tree.map(jnp.copy, w0)
    m = jax.tree.map(jnp.zeros_like, w0)
    v = jax.tree.map(jnp.zeros_like, w0)
    losses, grad_norm = [], None
    for t, (tokens, targets) in enumerate(batches, start=1):
        loss, g, gn = gradient(w, tokens, targets)
        losses.append(loss)
        if t == 1:
            grad_norm = flat_leaf_norms(gn)
        coef = jnp.float32(lr * math.sqrt(1 - b2 ** t) / (1 - b1 ** t))
        w, m, v = adam(w, m, v, g, jax.device_put(coef, host))
        del g
    delta = jax.jit(lambda a, b: stack_norms(
        jax.tree.map(jnp.subtract, a, b)))(w, w0)
    return {"loss": losses, "grad_norm": grad_norm,
            "delta_norm": flat_leaf_norms(delta)}
