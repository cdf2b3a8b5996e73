"""Plain reference: the decoder-hybrid-decoder block of
Phi-4-mini-flash-reasoning (SambaY: Mamba-1, differential attention over a
window / over everything / across layers, gated memory units) in
``jax.numpy``, float32, matmuls at ``highest`` precision. No kernels, no
cache; it imports nothing of the program, makes its own weights from the
seed and is given only tokens.

Every layer is ``h = x + Mixer(LN(x)); out = h + MLP(LN'(h))``, LayerNorm
with gain and bias, ``MLP(u) = W_down (up * silu(gate))``, ``[gate, up] =
W_gu u``; after the last layer a LayerNorm and ``logits = h E^T``. No
positional encoding of any kind. The configuration lists the layers' kinds
(``layer_kinds``); the mixers:

``mamba``        ``[u, z] = W_in x``; ``u = silu(conv_causal_depthwise(u) +
                 b_c)``; ``[dt_r, B, C] = W_x u``; ``dt = softplus(W_dt dt_r +
                 b_dt)``; ``A = -exp(A_log)``; ``s_t = exp(dt_t A) s_{t-1} +
                 dt_t B_t u_t``; ``y_t = C_t . s_t + D u_t``; out ``W_out (y *
                 silu(z))``. Its ``y`` is the memory ``m`` of later ``gmu``s.
``attn_*``       differential attention: heads pair up as (2p, 2p + 1), key
                 and value heads likewise, query pair p reads key/value pair
                 p // group; ``o_p = (1 - l0) RMSNorm_2D(S1 V - lam S2 V) *
                 gain`` with ``S1 = softmax(mask(q_2p k_2p'^T / sqrt(D)))``,
                 ``S2`` on the odd heads, ``V = [v_2p', v_2p'+1]``, ``lam =
                 exp(lq1 . lk1) - exp(lq2 . lk2) + l0``, ``l0 = 0.8 - 0.6
                 exp(-0.3 i)`` for layer index i; out ``W_o concat_p(o_p) +
                 b_o``. ``attn_window``: key j visible to query i iff ``0 <=
                 i - j < window``; ``attn_full``: causal, and its k and v are
                 kept; ``attn_cross``: only ``q = W_q x + b_q`` is made, k and
                 v are the kept ones.
``gmu``          ``W_out (m * silu(W_in x))``.

Each layer is rematerialised in the backward pass, attention runs over
``ROW_BLOCK`` query rows at a time and the scan is a ``lax.scan`` over ``T``
in rematerialised chunks, so that the reference fits at the timed sizes.

``precision`` is the control's lever (``check.py``): ``None`` is this
reference; ``"int8"`` / ``"fp8"`` round both operands of every matmul and the
incoming gradient to that type first.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
ROW_BLOCK = 512      # query rows whose scores exist at one time
SCAN_CHUNK = 128     # rows of T between two kept states of the scan
HEAD_CHUNK = 1024    # positions whose logits exist at one time in loss_fn
KINDS = ("mamba", "attn_window", "attn_full", "attn_cross", "gmu")


# ---------------------------------------------------------------------------
# sizes and weights
# ---------------------------------------------------------------------------


def sizes(cfg: dict) -> dict:
    """The widths by the names used below, from the configuration's keys
    (the source's) and its ``assumed`` Mamba sizes."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"d": d, "F": cfg["intermediate_size"], "H": H,
            "Hkv": cfg["num_key_value_heads"], "D": d // H,
            "Di": cfg["mamba_expand"] * d, "N": cfg["mamba_d_state"],
            "K": cfg["mamba_d_conv"], "R": cfg["mamba_dt_rank"],
            "V": cfg["vocab_size"]}


def layer_shapes(cfg: dict, kind: str) -> dict:
    """Leaf name -> shape for one layer of ``kind``; matrices are (out, in),
    as ``y = x @ W.T + b``."""
    z = sizes(cfg)
    d, F, D, Di = z["d"], z["F"], z["D"], z["Di"]
    out = {"ln1_g": (d,), "ln1_b": (d,), "ln2_g": (d,), "ln2_b": (d,),
           "gate_up_w": (2 * F, d), "down_w": (d, F)}
    if kind == "mamba":
        out.update(in_w=(2 * Di, d), conv_w=(Di, z["K"]), conv_b=(Di,),
                   x_w=(z["R"] + 2 * z["N"], Di), dt_w=(Di, z["R"]),
                   dt_b=(Di,), A_log=(Di, z["N"]), D=(Di,), out_w=(d, Di))
    elif kind == "gmu":
        out.update(in_w=(Di, d), out_w=(d, Di))
    elif kind in KINDS:
        q = z["H"] * D
        kv = 0 if kind == "attn_cross" else 2 * z["Hkv"] * D
        out.update(qkv_w=(q + kv, d), qkv_b=(q + kv,), o_w=(d, q), o_b=(d,),
                   lq1=(D,), lk1=(D,), lq2=(D,), lk2=(D,), subln=(2 * D,))
    else:
        raise ValueError(f"unknown layer kind {kind!r}")
    return out


# leaves that are not N(0, initializer_range): name -> how they are made
ONES = ("ln1_g", "ln2_g", "subln", "D")
ZEROS = ("ln1_b", "ln2_b", "conv_b", "qkv_b", "o_b")
LAMBDAS = ("lq1", "lk1", "lq2", "lk2")


def shapes(cfg: dict) -> dict:
    """Every leaf by its flat name: ``embed``, ``ln_f_g``, ``ln_f_b`` and
    ``layers/<leaf>/<i>``."""
    z = sizes(cfg)
    out = {"embed": (z["V"], z["d"]), "ln_f_g": (z["d"],),
           "ln_f_b": (z["d"],)}
    for i, kind in enumerate(cfg["layer_kinds"]):
        for leaf, shape in layer_shapes(cfg, kind).items():
            out[f"layers/{leaf}/{i}"] = shape
    return out


def leaf_of(name: str) -> str:
    return name.split("/")[1] if "/" in name else name


def make_weights(cfg: dict, seed: int, dtype: str) -> dict:
    """``{flat leaf name: array}`` on the default device from one jitted
    program. Scheme (the configuration's ``assumed``): matrices and the
    token table N(0, 0.02); the depthwise convolution U(-1/2, 1/2) (fan-in
    4); lambda vectors N(0, 0.1); ``A_log = log(1..N)`` per channel; ``D``
    1; ``dt_b`` the inverse softplus of a step log-uniform in [1e-3, 1e-1];
    other biases 0, gains 1."""
    sh = shapes(cfg)
    dt = jnp.dtype(dtype)
    std = float(cfg.get("initializer_range", 0.02))
    drawn = [n for n in sh if leaf_of(n) not in ONES + ZEROS + ("A_log",)]
    counts = [math.prod(sh[n]) for n in drawn]

    def build(key):
        # one draw for everything, then slices: a draw per shape compiles
        # several times as long
        flat = jax.random.normal(key, (sum(counts),), jnp.float32)
        out, off = {}, 0
        for name, k in zip(drawn, counts):
            x, leaf = flat[off:off + k].reshape(sh[name]), leaf_of(name)
            off += k
            if leaf == "conv_w":          # a normal's CDF is uniform
                x = jax.scipy.stats.norm.cdf(x) - 0.5
            elif leaf == "dt_b":
                step = jnp.exp(jax.scipy.stats.norm.cdf(x)
                               * math.log(1e-1 / 1e-3) + math.log(1e-3))
                x = step + jnp.log(-jnp.expm1(-step))
            else:
                x = (0.1 if leaf in LAMBDAS else std) * x
            # the lambda vectors stay float32 (the published implementation
            # keeps them so; see the configuration's ``assumed``)
            out[name] = x.astype(jnp.float32 if leaf in LAMBDAS else dt)
        for name, shape in sh.items():
            leaf = leaf_of(name)
            if leaf in ONES:
                out[name] = jnp.ones(shape, dt)
            elif leaf in ZEROS:
                out[name] = jnp.zeros(shape, dt)
            elif leaf == "A_log":
                out[name] = jnp.broadcast_to(jnp.log(jnp.arange(
                    1, shape[1] + 1, dtype=jnp.float32)), shape).astype(dt)
        return out

    # the driver's seeds pass 2**31: fold the two halves in
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    return jax.jit(build)(key)


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


def _ln(x, g, b, eps):
    m = jnp.mean(x, axis=-1, keepdims=True)
    v = jnp.mean(jnp.square(x - m), axis=-1, keepdims=True)
    return (x - m) * lax.rsqrt(v + eps) * g + b


def _silu(x):
    return x * jax.nn.sigmoid(x)


def selective_scan(u, dt, A, B, C, D):
    """``u``, ``dt``: (Bt, T, C); ``A``: (C, N); ``B``, ``C``: (Bt, T, N);
    ``D``: (C,). One step of T at a time, ``SCAN_CHUNK`` steps a
    rematerialised chunk."""
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


def mamba(z: dict, lp: dict, x, precision):
    """``(output, y before the gate)``."""
    Di, N, R, K = z["Di"], z["N"], z["R"], z["K"]
    T = x.shape[1]
    uz = _mm(x, lp["in_w"], precision)
    u, gate = uz[..., :Di], uz[..., Di:]
    padded = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    u = _silu(lp["conv_b"] + sum(padded[:, k:k + T] * lp["conv_w"][:, k]
                                 for k in range(K)))
    xp = _mm(u, lp["x_w"], precision)
    dt = jax.nn.softplus(_mm(xp[..., :R], lp["dt_w"], precision)
                         + lp["dt_b"])
    y = selective_scan(u, dt, -jnp.exp(lp["A_log"]), xp[..., R:R + N],
                       xp[..., R + N:], lp["D"])
    return _mm(y * _silu(gate), lp["out_w"], precision), y


def diff_attention(z: dict, lp: dict, q, k, v, index: int, window,
                   precision, eps: float):
    """``q``: (B, T, H, D); ``k``, ``v``: (B, T, Hkv, D); returns
    (B, T, H * D). Query rows in blocks of ``ROW_BLOCK``; the key/value
    pair of every query pair is written out (a repeat: K and V are small
    beside the scores)."""
    B, T, H, D = q.shape
    G = H // k.shape[2]                 # query pairs on one key/value pair
    l0 = 0.8 - 0.6 * math.exp(-0.3 * index)
    lam = jnp.exp(jnp.sum(lp["lq1"] * lp["lk1"])) \
        - jnp.exp(jnp.sum(lp["lq2"] * lp["lk2"])) + l0
    # even heads make the first map, odd heads the second; a pair's value
    # is its two heads' values side by side
    k1, k2 = (_fake_quant(jnp.repeat(k[:, :, i::2], G, axis=2), precision)
              for i in (0, 1))
    vv = _fake_quant(jnp.repeat(v.reshape(B, T, -1, 2 * D), G, axis=2),
                     precision)
    rows = math.gcd(T, ROW_BLOCK)
    cols = jnp.arange(T)[None, :]

    def attend(qb, kx, seen):                       # qb: (B, rows, H/2, D)
        s = jnp.einsum("bqhd,bkhd->bhqk", _fake_quant(qb, precision), kx,
                       precision=HIGHEST) / math.sqrt(D)
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhe->bqhe", _fake_quant(p, precision), vv,
                          precision=HIGHEST)        # (B, rows, H/2, 2D)

    @jax.checkpoint
    def block(args):
        qb, r0 = args                               # (B, rows, H, D)
        at = r0 + jnp.arange(rows)[:, None]
        seen = at >= cols
        if window is not None:
            seen = seen & (at - cols < window)
        o = attend(qb[:, :, 0::2], k1, seen) \
            - lam * attend(qb[:, :, 1::2], k2, seen)
        o = o * lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                          + eps)
        return (1.0 - l0) * o * lp["subln"]

    qb = jnp.moveaxis(q.reshape(B, T // rows, rows, H, D), 1, 0)
    out = lax.map(block, (qb, jnp.arange(T // rows) * rows))
    return jnp.moveaxis(out, 0, 1).reshape(B, T, H * D)


def hidden(cfg: dict, w: dict, tokens, precision=None):
    """The final LayerNorm's output ``(B, T, d)``: everything but the head."""
    z = sizes(cfg)
    eps = cfg["layer_norm_eps"]
    window = cfg["sliding_window"]
    H, Hkv, D = z["H"], z["Hkv"], z["D"]
    B, T = tokens.shape

    def layer(kind, index, lp, x, memory, kv):
        h = _ln(x, lp["ln1_g"], lp["ln1_b"], eps)
        if kind == "mamba":
            mixed, memory = mamba(z, lp, h, precision)
        elif kind == "gmu":
            mixed = _mm(memory * _silu(_mm(h, lp["in_w"], precision)),
                        lp["out_w"], precision)
        else:
            qkv = _mm(h, lp["qkv_w"], precision) + lp["qkv_b"]
            q = qkv[..., :H * D].reshape(B, T, H, D)
            if kind != "attn_cross":
                k = qkv[..., H * D:(H + Hkv) * D].reshape(B, T, Hkv, D)
                v = qkv[..., (H + Hkv) * D:].reshape(B, T, Hkv, D)
                if kind == "attn_full":
                    kv = (k, v)
            else:
                k, v = kv
            att = diff_attention(
                z, lp, q, k, v, index,
                window if kind == "attn_window" else None, precision, eps)
            mixed = _mm(att, lp["o_w"], precision) + lp["o_b"]
        x = x + mixed
        g = _ln(x, lp["ln2_g"], lp["ln2_b"], eps)
        gu = _mm(g, lp["gate_up_w"], precision)
        F = z["F"]
        x = x + _mm(gu[..., F:] * _silu(gu[..., :F]), lp["down_w"],
                    precision)
        return x, memory, kv

    x = w["embed"][tokens]
    # what is handed on before its producer ran is zeros the right shape:
    # a checkpointed function needs arrays, and no consumer comes first
    memory = jnp.zeros((B, T, z["Di"]), jnp.float32)
    kv = (jnp.zeros((B, T, Hkv, D), jnp.float32),) * 2
    for i, kind in enumerate(cfg["layer_kinds"]):
        x, memory, kv = jax.checkpoint(functools.partial(layer, kind, i))(
            layer_weights(w, i), x, memory, kv)
    return _ln(x, w["ln_f_g"], w["ln_f_b"], eps)


def forward(cfg: dict, w: dict, tokens, precision=None):
    """Logits ``(B, T, vocab)`` in float32 for int tokens ``(B, T)``; ``w``
    is a flat tree of float32 leaves."""
    return _mm(hidden(cfg, w, tokens, precision), w["embed"], precision)


def loss_fn(cfg: dict, w: dict, tokens, targets, precision=None):
    """Mean next-token cross entropy over every position of every row; the
    head runs over ``HEAD_CHUNK`` positions at a time, rematerialised."""
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


# ---------------------------------------------------------------------------
# the checked steps
# ---------------------------------------------------------------------------


def leaf_norms(tree: dict) -> dict:
    """L2 norm of every leaf. Traceable."""
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def train_steps(cfg: dict, weights: dict, batches, opt: dict, store_dtype,
                row_block: int, precision=None) -> dict:
    """Follow the first ``len(batches)`` Adam steps in float32.

    ``batches`` is a list of ``(tokens, targets)`` int arrays ``(B, T)``.
    Loss and gradient of ``row_block`` rows are one program on the
    accelerator over float32 weights; weights, Adam's moments and the sum of
    the blocks' gradients live on the host and the update is one program of
    JAX's CPU backend, so that the accelerator holds no more than weights,
    one gradient and one layer's activations. Between steps every parameter
    is rounded to the type its leaf of ``weights`` came in (``store_dtype``,
    the type the configuration trains in, for all but the float32 lambda
    vectors); Adam's moments stay float32.

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
        return g, leaf_norms(g)

    def gradient(w, tokens, targets):
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
        w = {k: (w[k] - coef * m[k] / (jnp.sqrt(v[k]) + eps))
             .astype(stored[k]).astype(jnp.float32) for k in w}
        return w, m, v

    stored = {k: a.dtype for k, a in weights.items()}
    if {d for k, d in stored.items() if leaf_of(k) not in LAMBDAS} \
            != {jnp.dtype(store_dtype)}:
        raise ValueError(f"weights are not stored in {store_dtype}")
    adam = jax.jit(adam, donate_argnums=(0, 1, 2))
    w0 = jax.device_put(jax.jit(lambda t: jax.tree.map(
        lambda a: a.astype(jnp.float32), t))(weights), host)
    w = jax.tree.map(jnp.copy, w0)
    m = jax.tree.map(jnp.zeros_like, w0)
    v = jax.tree.map(jnp.zeros_like, w0)
    losses, grad_norm = [], None
    for t, (tokens, targets) in enumerate(batches, start=1):
        loss, g, gn = gradient(w, tokens, targets)
        losses.append(loss)
        if t == 1:
            grad_norm = {k: float(n) for k, n in jax.device_get(gn).items()}
        coef = jnp.float32(lr * math.sqrt(1 - b2 ** t) / (1 - b1 ** t))
        w, m, v = adam(w, m, v, g, jax.device_put(coef, host))
        del g
    delta = jax.jit(lambda a, b: leaf_norms(
        jax.tree.map(jnp.subtract, a, b)))(w, w0)
    return {"loss": losses, "grad_norm": grad_norm,
            "delta_norm": {k: float(n)
                           for k, n in jax.device_get(delta).items()}}
