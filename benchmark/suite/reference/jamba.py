"""Plain reference: the decoder of the Jamba family (``jamba``: AI21's
hybrid of Mamba-1 and attention layers) as AI21-Jamba2-3B configures it, in
``jax.numpy``, float32, matmuls at ``highest`` precision. No kernels, no
cache, no chunked scan algorithm: the recurrence is one ``lax.scan`` over
``T``, a row at a time. It imports nothing of the program and nothing of
the other references, makes its own weights from the seed and is given only
tokens.

``RMS(x; g) = x / sqrt(mean(x^2) + rms_norm_eps) * g``. Layer ``i``: ``h =
x + Mixer_i(RMS(x)); out = h + SwiGLU(RMS'(h))``; ``Mixer_i`` is attention
where ``i % attn_layer_period == attn_layer_offset`` and Mamba elsewhere
(``kinds``). After the last layer an RMS and the TIED head, ``logits = h
E^T`` with the token table ``E``; mean cross entropy of the next token. No
bias anywhere but the convolution's and ``dt_proj``'s; no positional
encoding of any kind.

Mamba       ``[u, z] = W_in a``; ``u = silu(conv_causal_depthwise(u) +
            b_c)`` (``mamba_d_conv`` taps, zeros before the first row);
            ``[dt_r, B, C] = W_x u``; ``dt_r = RMS(dt_r; g_dt)``, ``B =
            RMS(B; g_B)``, ``C = RMS(C; g_C)`` (the family's inner norms);
            ``dt = softplus(W_dt dt_r + b_dt)``; ``A = -exp(A_log)``; ``s_t =
            exp(dt_t A) s_{t-1} + dt_t B_t u_t``, ``y_t = C_t . s_t + D
            u_t``, ``s_0 = 0``; out ``W_out (y * silu(z))``.
Attention   ``[q, k, v] = W_qkv a`` laid out as H query heads of D, then Hkv
            key heads, then Hkv value heads (20, 1 and 1 of 128 here); query
            head h reads key/value head ``h // (H / Hkv)``; causal softmax
            of ``q k^T / sqrt(D)``; ``W_o``. No rotary, no q/k norm, no
            window.
SwiGLU      ``W_down (up * silu(gate))``, ``[gate, up] = W_gu a``
            (``num_experts`` 1: every layer's MLP is this one, no router).

Departures from the published description, which gives sizes and not
equations (the configuration file's ``assumed`` has each): the order of the
layer types by the family's index rule; head size ``hidden_size /
num_attention_heads``; where the three inner norms sit and that each has a
gain; the biases; the seeded weights (``make_weights``); float32 logits.

``train_steps`` takes the gradient HALF A LAYER AT A TIME (a mixer with its
norm, a SwiGLU with its norm: three kinds of half, a forward and a backward
program each, and the head's): the weights (in the type they are stored in:
every step ends by rounding them to it) and Adam's float32 moments (for two
steps: the first step's gradient in their place) live on the host; a forward
sweep puts one half's weights on the accelerator at a time, widens them
there and keeps each half's input; a backward sweep runs one ``jax.vjp`` a
half, whose float32 gradient goes to the host and through Adam there. The
token table's gradient is the head's plus the lookup's.

``precision`` is the control's lever (``check.py``): ``None`` is this
reference; ``"int8"`` / ``"fp8"`` round both operands of every matmul and the
incoming gradient to that type first.
"""

from __future__ import annotations

import functools
import math
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
ROW_BLOCK = 512      # query rows whose scores exist at one time
SCAN_CHUNK = 128     # rows of T between two kept states of the scan
HEAD_CHUNK = 1024    # positions whose logits exist at one time
MAMBA, ATTENTION = "mamba", "attention"


# ---------------------------------------------------------------------------
# sizes and weights
# ---------------------------------------------------------------------------


def sizes(cfg: dict) -> dict:
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"d": d, "F": cfg["intermediate_size"], "H": H,
            "Hkv": cfg["num_key_value_heads"], "D": d // H,
            "Di": cfg["mamba_expand"] * d, "N": cfg["mamba_d_state"],
            "K": cfg["mamba_d_conv"], "R": cfg["mamba_dt_rank"],
            "V": cfg["vocab_size"], "L": cfg["num_hidden_layers"],
            "eps": cfg["rms_norm_eps"]}


def kinds(cfg: dict) -> list:
    """Each layer's mixer by the family's index rule."""
    period, offset = cfg["attn_layer_period"], cfg["attn_layer_offset"]
    return [ATTENTION if i % period == offset else MAMBA
            for i in range(cfg["num_hidden_layers"])]


def layer_shapes(cfg: dict, kind: str) -> dict:
    """Leaf name -> shape for one layer of ``kind``; matrices are (out, in),
    as ``y = x @ W.T``."""
    z = sizes(cfg)
    d, F, Di, N, R = z["d"], z["F"], z["Di"], z["N"], z["R"]
    out = {"ln1_g": (d,)}
    if kind == MAMBA:
        out.update(in_w=(2 * Di, d), conv_w=(Di, z["K"]), conv_b=(Di,),
                   x_w=(R + 2 * N, Di), dt_norm_g=(R,), b_norm_g=(N,),
                   c_norm_g=(N,), dt_w=(Di, R), dt_b=(Di,), A_log=(Di, N),
                   D=(Di,), out_w=(d, Di))
    elif kind == ATTENTION:
        out.update(qkv_w=((z["H"] + 2 * z["Hkv"]) * z["D"], d),
                   o_w=(d, z["H"] * z["D"]))
    else:
        raise ValueError(f"unknown layer kind {kind!r}")
    out.update(ln2_g=(d,), gate_up_w=(2 * F, d), down_w=(d, F))
    return out


ONES = ("ln1_g", "ln2_g", "ln_f_g", "dt_norm_g", "b_norm_g", "c_norm_g", "D")
ZEROS = ("conv_b",)
FFN = ("ln2_g", "gate_up_w", "down_w")      # a layer's SwiGLU half
TOP = 1 << 20                               # the token table's draw


def shapes(cfg: dict) -> dict:
    """Every leaf by its flat name: ``embed`` (the token table, which is the
    head too), ``ln_f_g`` and ``layers/<leaf>/<i>``."""
    z = sizes(cfg)
    out = {"embed": (z["V"], z["d"]), "ln_f_g": (z["d"],)}
    for i, kind in enumerate(kinds(cfg)):
        for leaf, shape in layer_shapes(cfg, kind).items():
            out[f"layers/{leaf}/{i}"] = shape
    return out


def parameter_count(cfg: dict) -> int:
    return sum(math.prod(s) for s in shapes(cfg).values())


def leaf_of(name: str) -> str:
    return name.split("/")[1] if "/" in name else name


def make_weights(cfg: dict, seed: int, dtype: str) -> dict:
    """``{flat leaf name: array}`` on the default device. Scheme (the
    configuration's ``assumed``; ``phi4-mini-flash``'s): matrices and the
    token table N(0, ``initializer_range`` = 0.02); the depthwise
    convolution U(-1/2, 1/2) (fan-in 4); ``A_log = log(1..N)`` per channel;
    ``D`` 1; ``dt_b`` the inverse softplus of a step log-uniform in [1e-3,
    1e-1]; the convolution's bias 0, gains 1. One normal draw a LAYER, keyed
    by the layer's own index, so that layer ``i`` of a deeper model of the
    same widths holds the same numbers; layers of one kind share the
    program."""
    dt = jnp.dtype(dtype)
    std = float(cfg.get("initializer_range", 0.02))

    @functools.partial(jax.jit, static_argnums=1)
    def draw(key, leaves):
        drawn = [(leaf, shape) for leaf, shape in leaves
                 if leaf not in ONES + ZEROS + ("A_log",)]
        flat = jax.random.normal(
            key, (sum(math.prod(s) for _, s in drawn),), jnp.float32)
        out, off = {}, 0
        for leaf, shape in drawn:
            x = flat[off:off + math.prod(shape)].reshape(shape)
            off += math.prod(shape)
            if leaf == "conv_w":          # a normal's CDF is uniform
                x = jax.scipy.stats.norm.cdf(x) - 0.5
            elif leaf == "dt_b":
                step = jnp.exp(jax.scipy.stats.norm.cdf(x)
                               * math.log(1e-1 / 1e-3) + math.log(1e-3))
                x = step + jnp.log(-jnp.expm1(-step))
            else:
                x = std * x
            out[leaf] = x.astype(dt)
        for leaf, shape in leaves:
            if leaf in ONES:
                out[leaf] = jnp.ones(shape, dt)
            elif leaf in ZEROS:
                out[leaf] = jnp.zeros(shape, dt)
            elif leaf == "A_log":
                out[leaf] = jnp.broadcast_to(jnp.log(jnp.arange(
                    1, shape[1] + 1, dtype=jnp.float32)), shape).astype(dt)
        return out

    # the driver's seeds pass 2**31: fold the two halves in
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    z = sizes(cfg)
    out = dict(draw(jax.random.fold_in(key, TOP),
                    (("embed", (z["V"], z["d"])), ("ln_f_g", (z["d"],)))))
    for i, kind in enumerate(kinds(cfg)):
        layer = draw(jax.random.fold_in(key, i),
                     tuple(layer_shapes(cfg, kind).items()))
        out.update({f"layers/{leaf}/{i}": x for leaf, x in layer.items()})
    return out


def layer_weights(w: dict, i: int) -> dict:
    """Layer ``i``'s leaves by their short names."""
    tail = f"/{i}"
    return {n.split("/")[1]: v for n, v in w.items()
            if n.startswith("layers/") and n.endswith(tail)}


# ---------------------------------------------------------------------------
# the control's rounding
# ---------------------------------------------------------------------------


def _round_to(x, precision):
    """``x`` rounded to the control's type, as float32."""
    if precision == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    if precision == "int8":
        # symmetric, one scale per row
        s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
        s = jnp.where(s == 0, 1.0, s)
        return jnp.round(x / s).clip(-127, 127) * s
    raise ValueError(f"unknown control precision {precision!r}")


def _fake_quant(x, precision):
    """An operand as the lower precision sees it; the gradient passes
    straight through the rounding."""
    if precision is None:
        return x
    return x + lax.stop_gradient(_round_to(x, precision) - x)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _quant_cotangent(y, precision):
    """Identity whose cotangent is rounded."""
    return y


_quant_cotangent.defvjp(lambda y, precision: (y, None),
                        lambda precision, _, g: (_round_to(g, precision),))


def _mm(x, w, precision):
    """``x @ w.T`` for a (out, in) weight."""
    y = jnp.einsum("...i,oi->...o", _fake_quant(x, precision),
                   _fake_quant(w, precision), precision=HIGHEST)
    return y if precision is None else _quant_cotangent(y, precision)


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * g


def _silu(x):
    return x * jax.nn.sigmoid(x)


def selective_scan(u, dt, A, B, C, D):
    """``u``, ``dt``: (Bt, T, C); ``A``: (C, N); ``B``, ``C``: (Bt, T, N);
    ``D``: (C,). One row of T at a time; ``SCAN_CHUNK`` rows are a chunk
    that the backward runs again, so that one state a chunk is kept."""
    Bt, T, Cd = u.shape
    chunk = math.gcd(T, SCAN_CHUNK)

    def step(s, x):
        u_t, dt_t, b_t, c_t = x
        s = jnp.exp(dt_t[..., None] * A) * s \
            + (dt_t * u_t)[..., None] * b_t[:, None, :]
        return s, jnp.sum(s * c_t[:, None, :], axis=-1)

    @jax.checkpoint
    def run_chunk(s, xs):
        return lax.scan(step, s, xs)

    def chunks(a):       # (Bt, T, X) -> (T / chunk, chunk, Bt, X)
        return jnp.swapaxes(a, 0, 1).reshape(T // chunk, chunk, Bt, -1)

    s0 = jnp.zeros((Bt, Cd, A.shape[1]), jnp.float32)
    _, y = lax.scan(run_chunk, s0, tuple(chunks(a) for a in (u, dt, B, C)))
    return jnp.swapaxes(y.reshape(T, Bt, Cd), 0, 1) + D * u


def mamba(cfg: dict, lp: dict, x, precision=None, inner_norm: bool = True):
    """The Mamba mixer on ``x`` (B, T, d), already normed.
    ``inner_norm=False`` is the tests' planted fault: the three norms left
    out, as a program of the plain Mamba-1 layer would."""
    z = sizes(cfg)
    Di, N, R, K, eps = z["Di"], z["N"], z["R"], z["K"], z["eps"]
    T = x.shape[1]
    uz = _mm(x, lp["in_w"], precision)
    u, gate = uz[..., :Di], uz[..., Di:]
    padded = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    u = _silu(lp["conv_b"] + sum(padded[:, k:k + T] * lp["conv_w"][:, k]
                                 for k in range(K)))
    xp = _mm(u, lp["x_w"], precision)
    dt_r, B, C = xp[..., :R], xp[..., R:R + N], xp[..., R + N:]
    if inner_norm:
        dt_r = _rms(dt_r, lp["dt_norm_g"], eps)
        B, C = _rms(B, lp["b_norm_g"], eps), _rms(C, lp["c_norm_g"], eps)
    dt = jax.nn.softplus(_mm(dt_r, lp["dt_w"], precision) + lp["dt_b"])
    y = selective_scan(u, dt, -jnp.exp(lp["A_log"]), B, C, lp["D"])
    return _mm(y * _silu(gate), lp["out_w"], precision)


def attention(cfg: dict, lp: dict, x, precision=None):
    """The attention mixer on ``x`` (B, T, d), already normed. Query rows
    in blocks of ``ROW_BLOCK``, each against every key at or before it."""
    z = sizes(cfg)
    B, T, _ = x.shape
    H, Hkv, D = z["H"], z["Hkv"], z["D"]
    G = H // Hkv
    qkv = _mm(x, lp["qkv_w"], precision)
    q = qkv[..., :H * D].reshape(B, T, Hkv, G, D)
    k = _fake_quant(qkv[..., H * D:(H + Hkv) * D].reshape(B, T, Hkv, D),
                    precision)
    v = _fake_quant(qkv[..., (H + Hkv) * D:].reshape(B, T, Hkv, D),
                    precision)
    rows = math.gcd(T, ROW_BLOCK)
    cols = jnp.arange(T)[None, :]

    @jax.checkpoint
    def block(args):
        qb, r0 = args                               # (B, rows, Hkv, G, D)
        seen = r0 + jnp.arange(rows)[:, None] >= cols
        s = jnp.einsum("bqhgd,bkhd->bhgqk", _fake_quant(qb, precision), k,
                       precision=HIGHEST) / math.sqrt(D)
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhgqk,bkhe->bqhge", _fake_quant(p, precision), v,
                          precision=HIGHEST)        # (B, rows, Hkv, G, D)

    qb = jnp.moveaxis(q.reshape(B, T // rows, rows, Hkv, G, D), 1, 0)
    out = lax.map(block, (qb, jnp.arange(T // rows) * rows))
    return _mm(jnp.moveaxis(out, 0, 1).reshape(B, T, H * D), lp["o_w"],
               precision)


def mixer_half(cfg: dict, kind: str, lp: dict, x, precision=None,
               inner_norm: bool = True):
    """``h = x + Mixer(RMS(x))``."""
    a = _rms(x, lp["ln1_g"], cfg["rms_norm_eps"])
    if kind == MAMBA:
        return x + mamba(cfg, lp, a, precision, inner_norm)
    return x + attention(cfg, lp, a, precision)


def ffn_half(cfg: dict, lp: dict, h, precision=None):
    """``h + SwiGLU(RMS'(h))``."""
    gu = _mm(_rms(h, lp["ln2_g"], cfg["rms_norm_eps"]), lp["gate_up_w"],
             precision)
    F = lp["down_w"].shape[1]
    return h + _mm(gu[..., F:] * _silu(gu[..., :F]), lp["down_w"], precision)


def layer(cfg: dict, kind: str, lp: dict, x, precision=None,
          inner_norm: bool = True):
    return ffn_half(cfg, lp, mixer_half(cfg, kind, lp, x, precision,
                                        inner_norm), precision)


def hidden(cfg: dict, w: dict, tokens, precision=None,
           inner_norm: bool = True, layers=None):
    """The output of layer ``layers - 1`` (default: the last) ``(B, T, d)``,
    before the final RMS."""
    x = w["embed"][tokens]
    for i, kind in enumerate(kinds(cfg)[:layers]):
        x = jax.checkpoint(functools.partial(
            layer, cfg, kind, precision=precision, inner_norm=inner_norm))(
                layer_weights(w, i), x)
    return x


def head_loss(cfg: dict, top: dict, x, targets, precision=None):
    """Mean next-token cross entropy of the final RMS and the tied head
    over ``x`` (B, T, d); ``HEAD_CHUNK`` positions at a time."""
    x = _rms(x, top["ln_f_g"], cfg["rms_norm_eps"])
    n = x.shape[0] * x.shape[1]
    chunk = math.gcd(n, HEAD_CHUNK)

    @jax.checkpoint
    def chunk_loss(xy):
        xc, yc = xy
        logits = _mm(xc, top["embed"], precision)
        picked = jnp.take_along_axis(logits, yc[:, None], axis=-1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked)

    sums = lax.map(chunk_loss, (x.reshape(n // chunk, chunk, -1),
                                targets.reshape(n // chunk, chunk)))
    return jnp.sum(sums) / n


def forward(cfg: dict, w: dict, tokens, precision=None,
            inner_norm: bool = True):
    """Logits (B, T, vocab) in float32; ``w`` a flat tree of float32
    leaves."""
    x = _rms(hidden(cfg, w, tokens, precision, inner_norm), w["ln_f_g"],
             cfg["rms_norm_eps"])
    return _mm(x, w["embed"], precision)


def loss_fn(cfg: dict, w: dict, tokens, targets, precision=None,
            inner_norm: bool = True):
    """The whole model's loss under one autodiff (small sizes)."""
    return head_loss(cfg, w, hidden(cfg, w, tokens, precision, inner_norm),
                     targets, precision)


# ---------------------------------------------------------------------------
# the checked steps
# ---------------------------------------------------------------------------


def leaf_norms(tree: dict) -> dict:
    """L2 norm of every leaf. Traceable."""
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def _widen(tree: dict) -> dict:
    return {k: v.astype(jnp.float32) for k, v in tree.items()}


def train_steps(cfg: dict, weights: dict, batches, opt: dict, store_dtype,
                row_block: int, precision=None) -> dict:
    """Follow the first ``len(batches)`` Adam steps in float32.

    ``batches`` is a list of ``(tokens, targets)`` int arrays ``(B, T)``;
    the whole batch is one block (``row_block`` is the job's argument and is
    only checked). Between steps every parameter is rounded to the type its
    leaf of ``weights`` came in, so the weights are KEPT in that type, on
    the host, with Adam's float32 moments; ``weights`` itself stays where it
    is, for the change at the end. After the FIRST step both moments are
    functions of its gradient, so that gradient is kept in their place (4
    bytes a parameter, not 8); after the LAST step nobody reads them, so
    none are kept.

    Returns host numbers: ``loss`` per step, ``grad_norm`` of the first
    step's gradient per leaf, ``delta_norm`` of the change over all the
    steps per leaf."""
    b1, b2, eps, lr = opt["beta1"], opt["beta2"], opt["epsilon"], opt["lr"]
    host, accel = jax.devices("cpu")[0], jax.devices()[0]
    if batches[0][0].shape[0] % row_block:
        raise ValueError(f"row_block {row_block} does not divide the batch")
    if {a.dtype for a in weights.values()} != {jnp.dtype(store_dtype)}:
        raise ValueError(f"weights are not stored in {store_dtype}")
    # everything placed on the host is computed there (committed inputs);
    # a copy of its own where ``weights`` is on the host already: Adam
    # writes in place
    w = {k: jnp.array(a, copy=True) if host in a.devices()
         else jax.device_put(a, host) for k, a in weights.items()}
    first, m, v = {}, {}, {}     # step 1's gradient; Adam's moments

    @functools.partial(jax.jit, static_argnums=0)
    def mixer_fwd(kind, lp, x):
        return mixer_half(cfg, kind, _widen(lp), x, precision)

    @functools.partial(jax.jit, static_argnums=0)
    def mixer_bwd(kind, lp, x, dy):
        _, vjp = jax.vjp(lambda p, x_: mixer_half(cfg, kind, p, x_,
                                                  precision), _widen(lp), x)
        g, dx = vjp(dy)
        return g, dx, leaf_norms(g)

    @jax.jit
    def ffn_fwd(lp, h):
        return ffn_half(cfg, _widen(lp), h, precision)

    @jax.jit
    def ffn_bwd(lp, h, dy):
        _, vjp = jax.vjp(lambda p, h_: ffn_half(cfg, p, h_, precision),
                         _widen(lp), h)
        g, dx = vjp(dy)
        return g, dx, leaf_norms(g)

    @jax.jit
    def top_bwd(top, x, targets):
        loss, (g, dx) = jax.value_and_grad(
            lambda t, x_: head_loss(cfg, t, x_, targets, precision),
            argnums=(0, 1))(_widen(top), x)
        return loss, g, dx

    @jax.jit
    def embed_fwd(embed, tokens):
        return embed.astype(jnp.float32)[tokens]

    @jax.jit
    def embed_bwd(g_head, tokens, dx):
        g = g_head.at[tokens].add(dx)
        return g, jnp.sqrt(jnp.sum(jnp.square(g)))

    steps = len(batches)

    def flat(k, i=None):
        """A leaf's flat name: layer ``i``'s by its short name, or as it is."""
        return k if i is None else f"layers/{k}/{i}"

    def moved(wg, mg, vg, g, coef, keep):
        mg = {k: b1 * mg[k] + (1 - b1) * g[k] for k in g}
        vg = {k: b2 * vg[k] + (1 - b2) * g[k] * g[k] for k in g}
        wg = {k: (wg[k].astype(jnp.float32) - coef * mg[k]
                  / (jnp.sqrt(vg[k]) + eps)).astype(wg[k].dtype) for k in g}
        return (wg, mg, vg) if keep else (wg, {}, {})

    def zeros(g):
        return {k: jnp.zeros_like(a) for k, a in g.items()}

    @functools.partial(jax.jit, donate_argnums=(0,))
    def adam_first(wg, g, coef):
        return moved(wg, zeros(g), zeros(g), g, coef, False)[0]

    def second(keep, wg, g1, g, coef):
        return moved(wg, *moved(wg, zeros(g), zeros(g), g1, coef, True)[1:],
                     g, coef, keep)

    def later(keep, wg, mg, vg, g, coef):
        return moved(wg, mg, vg, g, coef, keep)

    # a step that keeps its moments writes them over what it was given
    adam_second = {keep: jax.jit(functools.partial(second, keep),
                                 donate_argnums=(0, 1) if keep else (0,))
                   for keep in (True, False)}
    adam = {keep: jax.jit(functools.partial(later, keep),
                          donate_argnums=(0, 1, 2) if keep else (0,))
            for keep in (True, False)}

    def update(g: dict, coef, t: int, i=None):
        """Adam's step ``t`` on the host for the leaves of ``g``: layer
        ``i``'s by their short names (halves of one kind share the
        program), or flat names."""
        g = jax.device_put(g, host)
        wg, keep = {k: w[flat(k, i)] for k in g}, t < steps
        if t == 1:
            wg, mg, vg = adam_first(wg, g, coef), {}, {}
            if keep:
                first.update({flat(k, i): a for k, a in g.items()})
        elif t == 2:
            wg, mg, vg = adam_second[keep](
                wg, {k: first.pop(flat(k, i)) for k in g}, g, coef)
        else:
            wg, mg, vg = adam[keep](wg, {k: m.pop(flat(k, i)) for k in g},
                                    {k: v.pop(flat(k, i)) for k in g}, g,
                                    coef)
        for tree, part in zip((w, m, v), (wg, mg, vg)):
            tree.update({flat(k, i): a for k, a in part.items()})

    def on_chip(names, i=None):
        return jax.device_put({k: w[flat(k, i)] for k in names}, accel)

    layer_kinds = kinds(cfg)
    halves = [([k for k in layer_shapes(cfg, kind) if k not in FFN],
               [k for k in layer_shapes(cfg, kind) if k in FFN])
              for kind in layer_kinds]

    # The seven large programs (a forward and a backward for each kind of
    # half, and the head's) are compiled AHEAD and at once, a thread each
    # (the compiler lets go of the interpreter).
    def like(names, i=None):
        return {k: jax.ShapeDtypeStruct(w[flat(k, i)].shape,
                                        w[flat(k, i)].dtype) for k in names}

    tokens0 = batches[0][0]
    x0 = jax.ShapeDtypeStruct(tokens0.shape + (cfg["hidden_size"],),
                              jnp.float32)
    jobs = {("top",): (top_bwd, like(["ln_f_g", "embed"]), x0,
                       jax.ShapeDtypeStruct(tokens0.shape, tokens0.dtype)),
            ("ffn_fwd",): (ffn_fwd, like(halves[0][1], 0), x0),
            ("ffn_bwd",): (ffn_bwd, like(halves[0][1], 0), x0, x0)}
    for i, kind in enumerate(layer_kinds):
        jobs.setdefault(("mixer_fwd", kind),
                        (mixer_fwd, kind, like(halves[i][0], i), x0))
        jobs.setdefault(("mixer_bwd", kind),
                        (mixer_bwd, kind, like(halves[i][0], i), x0, x0))
    with ThreadPoolExecutor(len(jobs)) as pool:
        run = dict(zip(jobs, pool.map(
            lambda job: job[0].lower(*job[1:]).compile(), jobs.values())))

    losses, grad_norm = [], {}
    for t, (tokens, targets) in enumerate(batches, start=1):
        coef = jax.device_put(
            jnp.float32(lr * math.sqrt(1 - b2 ** t) / (1 - b1 ** t)), host)
        tokens, targets = jnp.asarray(tokens), jnp.asarray(targets)
        xs = [embed_fwd(on_chip(["embed"])["embed"], tokens)]
        for i, (mixer, ffn) in enumerate(halves):
            xs.append(run["mixer_fwd", layer_kinds[i]](on_chip(mixer, i),
                                                       xs[-1]))
            xs.append(run["ffn_fwd",](on_chip(ffn, i), xs[-1]))
        loss, g_top, dx = run["top",](on_chip(["ln_f_g", "embed"]),
                                      xs.pop(), targets)
        losses.append(float(loss))
        norms = leaf_norms({"ln_f_g": g_top["ln_f_g"]})
        update({"ln_f_g": g_top.pop("ln_f_g")}, coef, t)
        for i, (mixer, ffn) in reversed(list(enumerate(halves))):
            g, dx, gn = run["ffn_bwd",](on_chip(ffn, i), xs.pop(), dx)
            norms.update({f"layers/{k}/{i}": n for k, n in gn.items()})
            update(g, coef, t, i)
            g, dx, gn = run["mixer_bwd", layer_kinds[i]](
                on_chip(mixer, i), xs.pop(), dx)
            norms.update({f"layers/{k}/{i}": n for k, n in gn.items()})
            update(g, coef, t, i)
        # the table's two gradients: the head's, kept on the accelerator
        # through the sweep, and the lookup's
        g, norms["embed"] = embed_bwd(g_top.pop("embed"), tokens, dx)
        update({"embed": g}, coef, t)
        del g, dx, g_top
        if t == 1:
            grad_norm = {k: float(n) for k, n in norms.items()}

    # the change, on the host, half a layer at a time (by short names, so
    # that halves of one kind share the program)
    change = jax.jit(lambda a, b: leaf_norms(
        {k: a[k].astype(jnp.float32) - b[k].astype(jnp.float32) for k in a}))
    delta = {}
    for i, names in [(i, half) for i in range(len(halves))
                     for half in halves[i]] + [(None, ["embed", "ln_f_g"])]:
        norms = change({k: w[flat(k, i)] for k in names}, jax.device_put(
            {k: weights[flat(k, i)] for k in names}, host))
        delta.update({flat(k, i): n for k, n in norms.items()})
    return {"loss": losses, "grad_norm": grad_norm,
            "delta_norm": {k: float(n) for k, n in delta.items()}}
