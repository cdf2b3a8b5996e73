"""Plain reference: the language model of Brumby-14B-Base (``brumby``:
Qwen3-14B's block with every layer's attention replaced by a power-retention
layer) in ``jax.numpy``, float32, matmuls at ``highest`` precision. No
kernels, no state, no chunks: retention is computed by its QUADRATIC form,
every query row against every earlier key, in blocks of ``ROW_BLOCK`` query
rows, so that it shares no algorithm with the chunked state code it judges.
It imports nothing of the program, makes its own weights from the seed and
is given only tokens. The helpers it shares with ``reference/kexaone.py``
(the rounding control, ``RMS``, rotary positions, the dense SwiGLU, the
head's loss in chunks) are imported from that file as they are.

``RMS(x) = x / sqrt(mean(x^2) + rms_norm_eps) * g``. Every layer is pre-norm:
``h = x + Mixer(RMS(x)); out = h + SwiGLU(RMS'(h))``, no bias anywhere but
the decay gate's; after the last layer an RMS and an UNTIED head, cross
entropy over the vocabulary slice. Every layer is the same kind.

Mixer       ``[q, k, v] = W_qkv x`` (no bias), laid out as H query heads of
            D, then Hkv key heads, then Hkv value heads. RMS over D on every
            query and key head (gains ``q_norm_g``, ``k_norm_g``) BEFORE the
            positions; rotary positions (rotate-half, base ``rope_theta``,
            all D dimensions) on q and k. One decay a KEY/VALUE head a token:
            ``log g_t[j] = log_sigmoid(w_g[j] . x_t + b_g[j])``. For query
            head h in group ``j = h // (H / Hkv)``, over ``s <= t``:
            ``a[t, s] = (q_t[h] . k_s[j] / sqrt(D))^2 * exp(sum_{r = s+1..t}
            log g_r[j])``; ``y_t[h] = sum_s a[t, s] v_s[j] / (sum_s a[t, s] +
            retention_eps)``; output ``W_o concat_h y_t[h]`` (no bias).
FFN         ``W_down (up * silu(gate))``, ``[gate, up] = W_gu x``.

Departures from the source, which publishes sizes and not equations (the
configuration file's ``assumed`` has each): degree 2; the gate's shape and
its ``log_sigmoid``; the normalisation by the sum of the weights and its
``retention_eps``; q/k norm and rotary positions kept from Qwen3's block;
the gate's bias, seeded ``b_g[j] = logit(g0[j])`` with half-lives ``ln 2 /
-ln g0`` spaced log-uniformly from ``gate_half_life_min`` to
``gate_half_life_max`` tokens over the key/value heads and kept in float32.

``train_steps`` takes the gradient HALF A LAYER AT A TIME as
``reference/kexaone.py`` does and for its reasons (float32 weights and a
whole float32 gradient do not fit the chip together): weights in their
stored type and Adam's state on the host, one half's weights widened on the
accelerator at a time.

``precision`` is the control's lever (``check.py``): ``None`` is this
reference; ``"int8"`` / ``"fp8"`` round both operands of every matmul and the
incoming gradient to that type first.
"""

from __future__ import annotations

import functools
import importlib.util
import math
import os
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
from jax import lax


def _beside(name: str):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name)
    spec = importlib.util.spec_from_file_location(
        "suite_reference_" + name.removesuffix(".py") + "_helpers", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_K = _beside("kexaone.py")
_mm, _rms, rope, swiglu = _K._mm, _K._rms, _K.rope, _K.swiglu
leaf_norms, leaf_of, _group, _widen = (_K.leaf_norms, _K.leaf_of, _K._group,
                                       _K._widen)
_fake_quant, head_loss, HIGHEST = _K._fake_quant, _K.head_loss, _K.HIGHEST

ROW_BLOCK = 512        # query rows whose weights exist at one time


# ---------------------------------------------------------------------------
# sizes and weights
# ---------------------------------------------------------------------------


def sizes(cfg: dict) -> dict:
    return {"d": cfg["hidden_size"], "H": cfg["num_attention_heads"],
            "Hkv": cfg["num_key_value_heads"], "D": cfg["head_dim"],
            "F": cfg["intermediate_size"], "V": cfg["vocab_size"],
            "L": cfg["num_hidden_layers"], "eps": cfg["rms_norm_eps"]}


def layer_shapes(cfg: dict) -> dict:
    """Leaf name -> shape of a layer. Matrices are (out, in), as ``y = x @
    W.T``."""
    z = sizes(cfg)
    d, H, Hkv, D = z["d"], z["H"], z["Hkv"], z["D"]
    return {"ln1_g": (d,), "q_norm_g": (D,), "k_norm_g": (D,),
            "qkv_w": ((H + 2 * Hkv) * D, d), "o_w": (d, H * D),
            "gate_w": (Hkv, d), "gate_b": (Hkv,),
            "ln2_g": (d,), "gate_up_w": (2 * z["F"], d),
            "down_w": (d, z["F"])}


GAINS = ("ln1_g", "ln2_g", "ln_f_g", "q_norm_g", "k_norm_g")
FLOAT32 = ("gate_b",)                   # kept float32 whatever the dtype
# a layer's leaves that its mixer half reads (the rest are its FFN's)
MIXER = ("ln1_g", "q_norm_g", "k_norm_g", "qkv_w", "o_w", "gate_w", "gate_b")


def shapes(cfg: dict) -> dict:
    """Every leaf by its flat name: ``embed``, ``head``, ``ln_f_g`` and
    ``layers/<leaf>/<i>``."""
    z = sizes(cfg)
    out = {"embed": (z["V"], z["d"]), "head": (z["V"], z["d"]),
           "ln_f_g": (z["d"],)}
    for i in range(z["L"]):
        for leaf, shape in layer_shapes(cfg).items():
            out[f"layers/{leaf}/{i}"] = shape
    return out


def parameter_count(cfg: dict) -> int:
    return sum(math.prod(s) for s in shapes(cfg).values())


def gate_bias(cfg: dict):
    """``logit(g0[j])``, float32: half-lives ``ln 2 / -ln g0`` spaced
    log-uniformly from ``gate_half_life_min`` to ``gate_half_life_max``
    tokens over the key/value heads."""
    half = jnp.exp(jnp.linspace(math.log(cfg["gate_half_life_min"]),
                                math.log(cfg["gate_half_life_max"]),
                                cfg["num_key_value_heads"],
                                dtype=jnp.float32))
    log_g0 = -math.log(2.0) / half
    return log_g0 - jnp.log(-jnp.expm1(log_g0))


def make_weights(cfg: dict, seed: int, dtype: str) -> dict:
    """``{flat leaf name: array}`` on the default device. Matrices (the
    gate's among them) and both token tables N(0, ``initializer_range``);
    gains 1; the gate's bias as ``gate_bias`` says, float32. One normal draw
    a layer (and one for the tables), each as long as the largest of them so
    that one program makes them all, then slices."""
    sh = shapes(cfg)
    dt = jnp.dtype(dtype)
    std = float(cfg.get("initializer_range", 0.02))
    groups = {}       # draw -> [(shape, flat name)], in a fixed order
    for name, shape in sh.items():
        if leaf_of(name) not in GAINS + FLOAT32:
            groups.setdefault(_group(name), []).append((shape, name))
    size = max(sum(math.prod(shape) for shape, _ in rows)
               for rows in groups.values())

    @jax.jit
    def draw(key):                # one program for every group's numbers
        return jax.random.normal(key, (size,), jnp.float32)

    @functools.partial(jax.jit, static_argnums=1)
    def cut(flat, leaves):        # the layers share the program
        out, off = [], 0
        for shape in leaves:
            c = math.prod(shape)
            out.append((std * flat[off:off + c].reshape(shape)).astype(dt))
            off += c
        return out

    # the driver's seeds pass 2**31: fold the two halves in
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    out = {n: jnp.ones(s, dt) for n, s in sh.items() if leaf_of(n) in GAINS}
    out.update({n: gate_bias(cfg) for n in sh if leaf_of(n) == "gate_b"})
    for j, (_, rows) in enumerate(sorted(groups.items())):
        drawn = cut(draw(jax.random.fold_in(key, j)),
                    tuple(shape for shape, _ in rows))
        out.update({name: x for (_, name), x in zip(rows, drawn)})
    return out


def layer_weights(w: dict, i: int) -> dict:
    """Layer ``i``'s leaves by their short names."""
    tail = f"/{i}"
    return {n.split("/")[1]: v for n, v in w.items()
            if n.startswith("layers/") and n.endswith(tail)}


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------


def retention(q, k, v, log_g, eps: float, precision=None,
              carry: bool = True):
    """``q``: (B, T, H, D); ``k``, ``v``: (B, T, Hkv, D); ``log_g``: (B, T,
    Hkv). Returns (B, T, H * D). Query rows in blocks of ``ROW_BLOCK``, each
    against every key at or before it. ``carry=False`` is the tests'
    negative control: a row sees only the keys of its own ``ROW_BLOCK``, as
    a chunked program that dropped its state would."""
    B, T, H, D = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    rows = math.gcd(T, ROW_BLOCK)
    cols = jnp.arange(T)[None, :]
    cum = jnp.cumsum(log_g, axis=1)                          # (B, T, Hkv)
    kq, vq = _fake_quant(k, precision), _fake_quant(v, precision)

    @jax.checkpoint
    def block(args):
        qb, cb, r0 = args                  # (B, rows, Hkv, G, D), (B, rows, Hkv)
        at = r0 + jnp.arange(rows)[:, None]
        seen = at >= cols
        if not carry:
            seen = seen & (cols >= r0)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", _fake_quant(qb, precision), kq,
                       precision=HIGHEST) / math.sqrt(D)
        decay = jnp.einsum("bqh->bhq", cb)[..., None] \
            - jnp.einsum("bkh->bhk", cum)[:, :, None, :]     # (B, Hkv, q, k)
        decay = jnp.where(seen, jnp.exp(jnp.where(seen, decay, 0.0)), 0.0)
        a = s * s * decay[:, :, None]
        num = jnp.einsum("bhgqk,bkhe->bqhge", _fake_quant(a, precision), vq,
                         precision=HIGHEST)
        den = jnp.einsum("bhgqk->bqhg", a)[..., None]
        return num / (den + eps)                     # (B, rows, Hkv, G, D)

    qb = jnp.moveaxis(q.reshape(B, T // rows, rows, Hkv, G, D), 1, 0)
    cb = jnp.moveaxis(cum.reshape(B, T // rows, rows, Hkv), 1, 0)
    out = lax.map(block, (qb, cb, jnp.arange(T // rows) * rows))
    return jnp.moveaxis(out, 0, 1).reshape(B, T, H * D)


def retention_sublayer(cfg: dict, lp: dict, x, precision=None,
                       carry: bool = True):
    """``W_o Retention(x)`` on ``x`` (B, T, d), already normed."""
    z = sizes(cfg)
    B, T, _ = x.shape
    H, Hkv, D, eps = z["H"], z["Hkv"], z["D"], z["eps"]
    qkv = _mm(x, lp["qkv_w"], precision)
    q = _rms(qkv[..., :H * D].reshape(B, T, H, D), lp["q_norm_g"], eps)
    k = _rms(qkv[..., H * D:(H + Hkv) * D].reshape(B, T, Hkv, D),
             lp["k_norm_g"], eps)
    v = qkv[..., (H + Hkv) * D:].reshape(B, T, Hkv, D)
    theta = cfg["rope_theta"]
    log_g = jax.nn.log_sigmoid(_mm(x, lp["gate_w"], precision)
                               + lp["gate_b"])
    return _mm(retention(rope(q, theta), rope(k, theta), v, log_g,
                         cfg["retention_eps"], precision, carry), lp["o_w"],
               precision)


def mixer_half(cfg: dict, lp: dict, x, precision=None, carry: bool = True):
    """``h = x + Mixer(RMS(x))``."""
    return x + retention_sublayer(
        cfg, lp, _rms(x, lp["ln1_g"], cfg["rms_norm_eps"]), precision, carry)


def ffn_half(cfg: dict, lp: dict, h, precision=None):
    """``h + SwiGLU(RMS'(h))``."""
    return h + swiglu(_rms(h, lp["ln2_g"], cfg["rms_norm_eps"]),
                      lp["gate_up_w"], lp["down_w"], precision)


def layer(cfg: dict, lp: dict, x, precision=None, carry: bool = True):
    return ffn_half(cfg, lp, mixer_half(cfg, lp, x, precision, carry),
                    precision)


def hidden(cfg: dict, w: dict, tokens, precision=None, carry: bool = True):
    """The last layer's output (B, T, d), before the final RMS."""
    x = w["embed"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(functools.partial(layer, cfg, precision=precision,
                                             carry=carry))(
            layer_weights(w, i), x)
    return x


def forward(cfg: dict, w: dict, tokens, precision=None):
    """Logits (B, T, vocab) in float32; ``w`` a flat tree of float32
    leaves."""
    x = _rms(hidden(cfg, w, tokens, precision), w["ln_f_g"],
             cfg["rms_norm_eps"])
    return _mm(x, w["head"], precision)


def loss_fn(cfg: dict, w: dict, tokens, targets, precision=None,
            carry: bool = True):
    """The whole model's loss under one autodiff (small sizes)."""
    return head_loss(cfg, w, hidden(cfg, w, tokens, precision, carry),
                     targets, precision)


# ---------------------------------------------------------------------------
# the checked steps
# ---------------------------------------------------------------------------


def train_steps(cfg: dict, weights: dict, batches, opt: dict, store_dtype,
                row_block: int, precision=None) -> dict:
    """Follow the first ``len(batches)`` Adam steps in float32, as
    ``reference/kexaone.py::train_steps`` does (the same split between the
    host and the accelerator, the same Adam with step 1's gradient kept in
    the moments' place; ``row_block`` is rows of the BATCH and is only
    checked). A half of a layer is its mixer or its FFN; every layer is the
    same kind, so there are five large programs, compiled ahead and at once
    on threads as ``reference/lfm2.py`` does.

    Returns host numbers: ``loss`` per step, ``grad_norm`` of the first
    step's gradient per leaf, ``delta_norm`` of the change over all the
    steps per leaf."""
    b1, b2, eps, lr = opt["beta1"], opt["beta2"], opt["epsilon"], opt["lr"]
    host, accel = jax.devices("cpu")[0], jax.devices()[0]
    L = cfg["num_hidden_layers"]
    if batches[0][0].shape[0] % row_block:
        raise ValueError(f"row_block {row_block} does not divide the batch")
    if {a.dtype for k, a in weights.items() if leaf_of(k) not in FLOAT32} \
            != {jnp.dtype(store_dtype)}:
        raise ValueError(f"weights are not stored in {store_dtype}")
    w = {k: jnp.array(a, copy=True) if host in a.devices()
         else jax.device_put(a, host) for k, a in weights.items()}
    first, m, v = {}, {}, {}     # step 1's gradient; Adam's moments

    def half_bwd(fn):
        def bwd(lp, x, dy):
            _, vjp = jax.vjp(lambda p, x_: fn(cfg, p, x_, precision),
                             _widen(lp), x)
            g, dx = vjp(dy)
            return g, dx, leaf_norms(g)
        return jax.jit(bwd)

    mixer_fwd = jax.jit(lambda lp, x: mixer_half(cfg, _widen(lp), x,
                                                 precision))
    ffn_fwd = jax.jit(lambda lp, h: ffn_half(cfg, _widen(lp), h, precision))
    mixer_bwd, ffn_bwd = half_bwd(mixer_half), half_bwd(ffn_half)

    @jax.jit
    def top_bwd(top, x, targets):
        loss, (g, dx) = jax.value_and_grad(
            lambda t, x_: head_loss(cfg, t, x_, targets, precision),
            argnums=(0, 1))(_widen(top), x)
        return loss, g, dx, leaf_norms(g)

    @jax.jit
    def embed_fwd(embed, tokens):
        return embed.astype(jnp.float32)[tokens]

    @jax.jit
    def embed_bwd(tokens, dx):
        g = jnp.zeros(shapes(cfg)["embed"], jnp.float32).at[tokens].add(dx)
        return g, jnp.sqrt(jnp.sum(jnp.square(g)))

    steps = len(batches)

    def flat(k, i=None):
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
        ``i``'s by their short names (the layers share the program), or
        flat names."""
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

    mixer = [k for k in layer_shapes(cfg) if k in MIXER]
    ffn = [k for k in layer_shapes(cfg) if k not in MIXER]
    top = ["ln_f_g", "head"]

    def like(names, i=None):
        return {k: jax.ShapeDtypeStruct(w[flat(k, i)].shape,
                                        w[flat(k, i)].dtype) for k in names}

    tokens0 = batches[0][0]
    x0 = jax.ShapeDtypeStruct(tokens0.shape + (cfg["hidden_size"],),
                              jnp.float32)
    jobs = {"top": (top_bwd, like(top), x0,
                    jax.ShapeDtypeStruct(tokens0.shape, tokens0.dtype)),
            "mixer_fwd": (mixer_fwd, like(mixer, 0), x0),
            "mixer_bwd": (mixer_bwd, like(mixer, 0), x0, x0),
            "ffn_fwd": (ffn_fwd, like(ffn, 0), x0),
            "ffn_bwd": (ffn_bwd, like(ffn, 0), x0, x0)}
    with ThreadPoolExecutor(len(jobs)) as pool:
        run = dict(zip(jobs, pool.map(
            lambda job: job[0].lower(*job[1:]).compile(), jobs.values())))

    losses, grad_norm = [], {}
    for t, (tokens, targets) in enumerate(batches, start=1):
        coef = jax.device_put(
            jnp.float32(lr * math.sqrt(1 - b2 ** t) / (1 - b1 ** t)), host)
        tokens, targets = jnp.asarray(tokens), jnp.asarray(targets)
        xs = [embed_fwd(on_chip(["embed"])["embed"], tokens)]
        for i in range(L):
            xs.append(run["mixer_fwd"](on_chip(mixer, i), xs[-1]))
            xs.append(run["ffn_fwd"](on_chip(ffn, i), xs[-1]))
        loss, g, dx, norms = run["top"](on_chip(top), xs.pop(), targets)
        losses.append(float(loss))
        update(g, coef, t)
        for i in reversed(range(L)):
            for names, bwd in ((ffn, "ffn_bwd"), (mixer, "mixer_bwd")):
                g, dx, gn = run[bwd](on_chip(names, i), xs.pop(), dx)
                norms.update({f"layers/{k}/{i}": n for k, n in gn.items()})
                update(g, coef, t, i)
        g, norms["embed"] = embed_bwd(tokens, dx)
        update({"embed": g}, coef, t)
        del g, dx
        if t == 1:
            grad_norm = {k: float(n) for k, n in norms.items()}

    # the change, on the host, half a layer at a time
    change = jax.jit(lambda a, b: leaf_norms(
        {k: a[k].astype(jnp.float32) - b[k].astype(jnp.float32) for k in a}))
    delta = {}
    for i, names in [(i, half) for i in range(L) for half in (mixer, ffn)] \
            + [(None, ["embed", "head", "ln_f_g"])]:
        norms = change({k: w[flat(k, i)] for k in names}, jax.device_put(
            {k: weights[flat(k, i)] for k in names}, host))
        delta.update({flat(k, i): n for k, n in norms.items()})
    return {"loss": losses, "grad_norm": grad_norm,
            "delta_norm": {k: float(n) for k, n in delta.items()}}
