"""Plain reference: the language model of LFM2-8B-A1B (``lfm2_moe``: gated
short convolutions beside grouped-query attention, a leading dense SwiGLU
layer, then sparse expert layers that hold ALL their experts) in
``jax.numpy``, float32, matmuls at ``highest`` precision. No kernels, no
cache, no sorting of tokens; it imports nothing of the program, makes its
own weights from the seed and is given only tokens. The helpers it shares
with ``reference/kexaone.py`` (the rounding control, ``RMS``, rotary
positions, blocked attention, the dense SwiGLU, the balancing rule) are
imported from that file as they are.

``RMS(x) = x / sqrt(mean(x^2) + norm_eps) * g``. Every layer is pre-norm:
``h = x + Op(RMS(x)); out = h + FFN(RMS'(h))``; after the last layer an RMS
(the family's ``embedding_norm``) and the TIED head, ``logits = h E^T`` with
``E`` the token table, cross entropy over all ``vocab_size`` rows.
``layer_types[l]``: ``conv`` or ``full_attention``; layers ``l <
num_dense_layers`` have the dense FFN, the others the sparse one.

Conv        ``[B, C, u] = W_in x`` (three equal chunks of ``hidden_size`` in
            that order, no bias); ``v_t = sum_{j < L} w[:, j] (B * u)_{t - (L
            - 1) + j}`` per channel, zeros before the first row (a depthwise
            causal convolution of ``L = conv_L_cache`` taps, no bias); ``y =
            C * v``; output ``W_out y``. Both gates are plain products.
Attention   ``[q, k, v] = W_qkv x`` (no bias), laid out as H query heads of
            D, then Hkv key heads, then Hkv value heads; query head h reads
            key/value head h // (H / Hkv). RMS over D on every query and key
            head (gains ``q_norm_g``, ``k_norm_g``) BEFORE the positions;
            rotary positions (rotate-half, base ``rope_theta``, all D
            dimensions) on q and k in every attention layer; scores ``q k^T
            / sqrt(D)``, causal over everything; ``W_o`` (no bias).
Dense FFN   ``W_down (up * silu(gate))``, ``[gate, up] = W_gu x``.
Sparse FFN  ``s = sigmoid(x W_r^T)`` over the ``num_experts``; a token's
            experts are the ``num_experts_per_tok`` largest of ``s + b`` (b
            selects only); weights ``w_e = routed_scaling_factor * s_e /
            (sum of the chosen s + 1e-6)`` (``ROUTE_EPS``: the source's
            ``norm_topk_prob`` branch adds it); ``y = sum over chosen e of
            w_e E_e(x)``, every ``E`` a SwiGLU of ``moe_intermediate_size``,
            no shared expert. Every expert is applied to every token and
            weighted (zero where not chosen): the dense definition.
Balance     ``kexaone.balance``: after a training step ``b += r * sign(N k /
            E - c)``, c_e the tokens of the step that chose expert e; the
            step itself, backward included, uses the b it began with.

``train_steps`` takes the gradient HALF A LAYER AT A TIME as
``reference/kexaone.py`` does and for its reasons (float32 weights and a
whole float32 gradient do not fit the chip together, nor three float32
copies of the model the machine): weights in their stored type and Adam's
state on the host, one half's weights widened on the accelerator at a time.
The token table is one leaf with two gradients (the head's and the
lookup's), summed before its Adam step.

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
_mm, _dot, _rms, _silu = _K._mm, _K._dot, _K._rms, _K._silu
rope, attention, swiglu, balance = _K.rope, _K.attention, _K.swiglu, _K.balance
leaf_norms, leaf_of, _group, _widen = (_K.leaf_norms, _K.leaf_of, _K._group,
                                       _K._widen)
_fake_quant = _K._fake_quant

ROUTE_EPS = 1e-6     # added to the sum of a token's chosen scores


# ---------------------------------------------------------------------------
# sizes and weights
# ---------------------------------------------------------------------------


def sizes(cfg: dict) -> dict:
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"d": d, "H": H, "Hkv": cfg["num_key_value_heads"], "D": d // H,
            "F": cfg["intermediate_size"],
            "Fe": cfg["moe_intermediate_size"], "E": cfg["num_experts"],
            "k": cfg["num_experts_per_tok"], "taps": cfg["conv_L_cache"],
            "scale": float(cfg["routed_scaling_factor"]),
            "V": cfg["vocab_size"], "L": cfg["num_hidden_layers"],
            "eps": cfg["norm_eps"]}


def sparse(cfg: dict, i: int) -> bool:
    return i >= cfg["num_dense_layers"]


def layer_shapes(cfg: dict, i: int) -> dict:
    """Leaf name -> shape for layer ``i``. Dense matrices are (out, in), as
    ``y = x @ W.T``; the convolution's taps (channel, tap); the stacked
    expert matrices (expert, in, out)."""
    z = sizes(cfg)
    d, H, Hkv, D = z["d"], z["H"], z["Hkv"], z["D"]
    out = {"ln1_g": (d,), "ln2_g": (d,)}
    if cfg["layer_types"][i] == "conv":
        out.update(conv_in_w=(3 * d, d), conv_w=(d, z["taps"]),
                   conv_out_w=(d, d))
    else:
        out.update(q_norm_g=(D,), k_norm_g=(D,),
                   qkv_w=((H + 2 * Hkv) * D, d), o_w=(d, H * D))
    if sparse(cfg, i):
        out.update(router_w=(z["E"], d), router_b=(z["E"],),
                   experts_gate_up_w=(z["E"], d, 2 * z["Fe"]),
                   experts_down_w=(z["E"], z["Fe"], d))
    else:
        out.update(gate_up_w=(2 * z["F"], d), down_w=(d, z["F"]))
    return out


GAINS = ("ln1_g", "ln2_g", "ln_f_g", "q_norm_g", "k_norm_g")
FLOAT32 = ("router_b",)                 # kept float32 whatever the dtype
STATES = ("router_b",)                  # no gradient; ``balance`` moves it
# a layer's leaves that its operator half reads (the rest are its FFN's)
OPERATOR = ("ln1_g", "conv_in_w", "conv_w", "conv_out_w", "q_norm_g",
            "k_norm_g", "qkv_w", "o_w")


def shapes(cfg: dict) -> dict:
    """Every leaf by its flat name: ``embed`` (the token table, which is the
    head too), ``ln_f_g`` and ``layers/<leaf>/<i>``."""
    z = sizes(cfg)
    out = {"embed": (z["V"], z["d"]), "ln_f_g": (z["d"],)}
    for i in range(z["L"]):
        for leaf, shape in layer_shapes(cfg, i).items():
            out[f"layers/{leaf}/{i}"] = shape
    return out


def trained(tree: dict) -> dict:
    return {k: v for k, v in tree.items() if leaf_of(k) not in STATES}


def make_weights(cfg: dict, seed: int, dtype: str) -> dict:
    """``{flat leaf name: array}`` on the default device. Matrices, the
    convolutions' taps and the token table N(0, ``initializer_range``); the
    routers' selection bias, float32, N(0, ``router_bias_init_std``): ZERO
    in the benchmark's configuration, as a training run starts it, and a
    draw in the tests, so that a program that ignores it differs; gains 1.
    One normal draw a layer (and one for the table), each as long as the
    largest of them so that one program makes them all, then slices."""
    sh = shapes(cfg)
    dt = jnp.dtype(dtype)
    std = float(cfg.get("initializer_range", 0.02))
    bias_std = float(cfg.get("router_bias_init_std", 0.0))
    groups = {}       # draw -> [(leaf, shape, flat name)], in a fixed order
    for name, shape in sh.items():
        if leaf_of(name) not in GAINS:
            groups.setdefault(_group(name), []).append(
                (leaf_of(name), shape, name))
    size = max(sum(math.prod(shape) for _, shape, _ in rows)
               for rows in groups.values())

    @jax.jit
    def draw(key):                # one program for every group's numbers
        return jax.random.normal(key, (size,), jnp.float32)

    @functools.partial(jax.jit, static_argnums=1)
    def cut(flat, leaves):        # layers of one kind share the program
        out, off = [], 0
        for leaf, shape in leaves:
            c = math.prod(shape)
            scale = bias_std if leaf == "router_b" else std
            out.append((scale * flat[off:off + c].reshape(shape)).astype(
                jnp.float32 if leaf in FLOAT32 else dt))
            off += c
        return out

    # the driver's seeds pass 2**31: fold the two halves in
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    out = {n: jnp.ones(s, dt) for n, s in sh.items() if leaf_of(n) in GAINS}
    for j, (_, rows) in enumerate(sorted(groups.items())):
        drawn = cut(draw(jax.random.fold_in(key, j)),
                    tuple((leaf, shape) for leaf, shape, _ in rows))
        out.update({name: x for (_, _, name), x in zip(rows, drawn)})
    return out


def layer_weights(w: dict, i: int) -> dict:
    """Layer ``i``'s leaves by their short names."""
    tail = f"/{i}"
    return {n.split("/")[1]: v for n, v in w.items()
            if n.startswith("layers/") and n.endswith(tail)}


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------


def short_conv(x, w):
    """``y_t = sum_j w[:, j] x_{t - (L - 1) + j}`` per channel, zeros before
    the first row. ``x``: (B, T, d); ``w``: (d, L)."""
    T, taps = x.shape[1], w.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(padded[:, j:j + T] * w[:, j] for j in range(taps))


def conv_sublayer(cfg: dict, lp: dict, x, precision=None):
    """The gated short convolution on ``x`` (B, T, d), already normed."""
    d = cfg["hidden_size"]
    bcu = _mm(x, lp["conv_in_w"], precision)
    B, C, u = bcu[..., :d], bcu[..., d:2 * d], bcu[..., 2 * d:]
    return _mm(C * short_conv(B * u, lp["conv_w"]), lp["conv_out_w"],
               precision)


def attention_sublayer(cfg: dict, lp: dict, x, precision=None):
    """``W_o Attn(x)`` on ``x`` (B, T, d), already normed: q/k norm, then
    rotary positions, then causal softmax over everything."""
    z = sizes(cfg)
    B, T, _ = x.shape
    H, Hkv, D, eps = z["H"], z["Hkv"], z["D"], z["eps"]
    qkv = _mm(x, lp["qkv_w"], precision)
    q = _rms(qkv[..., :H * D].reshape(B, T, H, D), lp["q_norm_g"], eps)
    k = _rms(qkv[..., H * D:(H + Hkv) * D].reshape(B, T, Hkv, D),
             lp["k_norm_g"], eps)
    v = qkv[..., (H + Hkv) * D:].reshape(B, T, Hkv, D)
    theta = cfg["rope_theta"]
    return _mm(attention(rope(q, theta), rope(k, theta), v, None, precision),
               lp["o_w"], precision)


def route(z: dict, lp: dict, x, precision):
    """``(chosen (N, k) expert ids, weights (N, k))`` for rows ``x``."""
    s = jax.nn.sigmoid(_mm(x, lp["router_w"], precision))
    _, chosen = lax.top_k(s + lax.stop_gradient(lp["router_b"]), z["k"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    return chosen, z["scale"] * picked / (
        jnp.sum(picked, axis=-1, keepdims=True) + ROUTE_EPS)


def experts(z: dict, lp: dict, x, precision):
    """The sparse FFN on rows ``x`` (N, d): one expert at a time over every
    row, weighted by the row's weight for it (zero where not chosen).
    Returns ``(y, the tokens that chose each expert (E,) float32)``."""
    chosen, weights = route(z, lp, x, precision)
    Fe = z["Fe"]
    # (E, N): a row's weight for each expert, zero where not chosen
    w_all = jnp.sum(jnp.where(chosen[..., None] == jnp.arange(z["E"]),
                              weights[..., None], 0.0), axis=1).T

    @jax.checkpoint
    def one(y, expert):
        w_gu, w_down, w_e = expert
        gu = _dot("ni,io->no", x, w_gu, precision)
        return y + _dot("ni,io->no", gu[:, Fe:] * _silu(gu[:, :Fe]), w_down,
                        precision) * w_e[:, None], None

    y = lax.scan(one, jnp.zeros_like(x), (lp["experts_gate_up_w"],
                                          lp["experts_down_w"], w_all))[0]
    count = jnp.sum(chosen.reshape(-1, 1) == jnp.arange(z["E"]),
                    axis=0).astype(jnp.float32)
    return y, lax.stop_gradient(count)


def operator_half(cfg: dict, kind: str, lp: dict, x, precision=None):
    """``h = x + Op(RMS(x))`` for a layer of ``kind`` (an entry of
    ``layer_types``)."""
    normed = _rms(x, lp["ln1_g"], cfg["norm_eps"])
    op = conv_sublayer if kind == "conv" else attention_sublayer
    return x + op(cfg, lp, normed, precision)


def ffn_half(cfg: dict, is_sparse: bool, lp: dict, h, precision=None):
    """``(h + FFN(RMS'(h)), the experts' counts or None)``."""
    normed = _rms(h, lp["ln2_g"], cfg["norm_eps"])
    if not is_sparse:
        return h + swiglu(normed, lp["gate_up_w"], lp["down_w"],
                          precision), None
    y, count = experts(sizes(cfg), lp, normed.reshape(-1, h.shape[-1]),
                       precision)
    return h + y.reshape(h.shape), count


def layer(cfg: dict, i: int, lp: dict, x, precision=None,
          counts: bool = False):
    """Layer ``i`` on ``x`` (B, T, d); with ``counts`` also the tokens that
    chose each expert (None in a dense layer)."""
    h = operator_half(cfg, cfg["layer_types"][i], lp, x, precision)
    out, n = ffn_half(cfg, sparse(cfg, i), lp, h, precision)
    return (out, n) if counts else out


def head_loss(cfg: dict, top: dict, x, targets, precision=None):
    """Mean next-token cross entropy of the final RMS and the tied head
    over ``x`` (B, T, d); ``HEAD_CHUNK`` positions at a time."""
    x = _rms(x, top["ln_f_g"], cfg["norm_eps"])
    n = x.shape[0] * x.shape[1]
    chunk = math.gcd(n, _K.HEAD_CHUNK)

    @jax.checkpoint
    def chunk_loss(xy):
        xc, yc = xy
        logits = _mm(xc, top["embed"], precision)
        picked = jnp.take_along_axis(logits, yc[:, None], axis=-1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked)

    sums = lax.map(chunk_loss, (x.reshape(n // chunk, chunk, -1),
                                targets.reshape(n // chunk, chunk)))
    return jnp.sum(sums) / n


def hidden(cfg: dict, w: dict, tokens, precision=None):
    """The last layer's output (B, T, d), before the final RMS."""
    x = w["embed"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(functools.partial(layer, cfg, i,
                                             precision=precision))(
            layer_weights(w, i), x)
    return x


def forward(cfg: dict, w: dict, tokens, precision=None):
    """Logits (B, T, vocab) in float32; ``w`` a flat tree of float32
    leaves."""
    x = _rms(hidden(cfg, w, tokens, precision), w["ln_f_g"], cfg["norm_eps"])
    return _mm(x, w["embed"], precision)


def loss_fn(cfg: dict, w: dict, tokens, targets, precision=None):
    """The whole model's loss under one autodiff (small sizes)."""
    return head_loss(cfg, w, hidden(cfg, w, tokens, precision), targets,
                     precision)


# ---------------------------------------------------------------------------
# the checked steps
# ---------------------------------------------------------------------------


def train_steps(cfg: dict, weights: dict, batches, opt: dict, store_dtype,
                row_block: int, precision=None) -> dict:
    """Follow the first ``len(batches)`` Adam steps in float32, as
    ``reference/kexaone.py::train_steps`` does (the same split between the
    host and the accelerator, the same Adam with step 1's gradient kept in
    the moments' place; ``row_block`` is only checked). What differs: a
    half of a layer is its operator (one program a kind, ``conv`` or
    ``full_attention``) or its FFN (dense or sparse); the token table's
    gradient is the head's plus the lookup's.

    Returns host numbers: ``loss`` per step, ``grad_norm`` of the first
    step's gradient per trained leaf, ``delta_norm`` of the change over all
    the steps per leaf, the selection biases among them, and ``states``, the
    selection bias of each expert layer after the last step."""
    b1, b2, eps, lr = opt["beta1"], opt["beta2"], opt["epsilon"], opt["lr"]
    host, accel = jax.devices("cpu")[0], jax.devices()[0]
    L = cfg["num_hidden_layers"]
    if batches[0][0].shape[0] % row_block:
        raise ValueError(f"row_block {row_block} does not divide the batch")
    if {a.dtype for k, a in weights.items() if leaf_of(k) not in FLOAT32} \
            != {jnp.dtype(store_dtype)}:
        raise ValueError(f"weights are not stored in {store_dtype}")
    # everything placed on the host is computed there (committed inputs);
    # a copy of its own where ``weights`` is on the host already: Adam
    # writes in place
    w = {k: jnp.array(a, copy=True) if host in a.devices()
         else jax.device_put(a, host) for k, a in weights.items()}
    first, m, v = {}, {}, {}     # step 1's gradient; Adam's moments

    @functools.partial(jax.jit, static_argnums=0)
    def op_fwd(kind, lp, x):
        return operator_half(cfg, kind, _widen(lp), x, precision)

    @functools.partial(jax.jit, static_argnums=0)
    def op_bwd(kind, lp, x, dy):
        _, vjp = jax.vjp(
            lambda p, x_: operator_half(cfg, kind, p, x_, precision),
            _widen(lp), x)
        g, dx = vjp(dy)
        return g, dx, leaf_norms(g)

    @functools.partial(jax.jit, static_argnums=0)
    def ffn_fwd(is_sparse, lp, h):
        return ffn_half(cfg, is_sparse, _widen(lp), h, precision)

    @functools.partial(jax.jit, static_argnums=0)
    def ffn_bwd(is_sparse, lp, h, dy):
        lp = _widen(lp)
        state = {k: a for k, a in lp.items() if k in STATES}
        _, vjp = jax.vjp(
            lambda p, h_: ffn_half(cfg, is_sparse, {**p, **state}, h_,
                                   precision)[0],
            {k: a for k, a in lp.items() if k not in STATES}, h)
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

    kinds = cfg["layer_types"]
    halves = [([k for k in layer_shapes(cfg, i) if k in OPERATOR],
               [k for k in layer_shapes(cfg, i) if k not in OPERATOR])
              for i in range(L)]

    # The nine large programs (a forward and a backward for each kind of
    # half, and the head's) are compiled AHEAD and at once, a thread each
    # (the compiler lets go of the interpreter): one after another they take
    # 88 s of a cold run on the chip's machine, which has 13 cores and a
    # deadline.
    def like(names, i=None):
        return {k: jax.ShapeDtypeStruct(w[flat(k, i)].shape,
                                        w[flat(k, i)].dtype) for k in names}

    tokens0 = batches[0][0]
    x0 = jax.ShapeDtypeStruct(tokens0.shape + (cfg["hidden_size"],),
                              jnp.float32)
    jobs = {("top",): (top_bwd, like(["ln_f_g", "embed"]), x0,
                       jax.ShapeDtypeStruct(tokens0.shape, tokens0.dtype))}
    for i, (op, ffn) in enumerate(halves):
        jobs.setdefault(("op_fwd", kinds[i]),
                        (op_fwd, kinds[i], like(op, i), x0))
        jobs.setdefault(("op_bwd", kinds[i]),
                        (op_bwd, kinds[i], like(op, i), x0, x0))
        jobs.setdefault(("ffn_fwd", sparse(cfg, i)),
                        (ffn_fwd, sparse(cfg, i), like(ffn, i), x0))
        jobs.setdefault(("ffn_bwd", sparse(cfg, i)),
                        (ffn_bwd, sparse(cfg, i), like(ffn, i), x0, x0))
    with ThreadPoolExecutor(len(jobs)) as pool:
        run = dict(zip(jobs, pool.map(
            lambda job: job[0].lower(*job[1:]).compile(), jobs.values())))

    losses, grad_norm = [], {}
    for t, (tokens, targets) in enumerate(batches, start=1):
        coef = jax.device_put(
            jnp.float32(lr * math.sqrt(1 - b2 ** t) / (1 - b1 ** t)), host)
        tokens, targets = jnp.asarray(tokens), jnp.asarray(targets)
        xs = [embed_fwd(on_chip(["embed"])["embed"], tokens)]
        counts = {}
        for i, (op, ffn) in enumerate(halves):
            xs.append(run["op_fwd", kinds[i]](on_chip(op, i), xs[-1]))
            y, counts[f"layers/router_b/{i}"] = run[
                "ffn_fwd", sparse(cfg, i)](on_chip(ffn, i), xs[-1])
            xs.append(y)
        loss, g_top, dx = run["top",](on_chip(["ln_f_g", "embed"]),
                                      xs.pop(), targets)
        losses.append(float(loss))
        norms = leaf_norms({"ln_f_g": g_top["ln_f_g"]})
        update({"ln_f_g": g_top.pop("ln_f_g")}, coef, t)
        for i, (op, ffn) in reversed(list(enumerate(halves))):
            g, dx, gn = run["ffn_bwd", sparse(cfg, i)](on_chip(ffn, i),
                                                       xs.pop(), dx)
            norms.update({f"layers/{k}/{i}": n for k, n in gn.items()})
            update(g, coef, t, i)
            g, dx, gn = run["op_bwd", kinds[i]](on_chip(op, i), xs.pop(),
                                                dx)
            norms.update({f"layers/{k}/{i}": n for k, n in gn.items()})
            update(g, coef, t, i)
        # the table's two gradients: the head's, kept on the accelerator
        # through the sweep, and the lookup's
        g, norms["embed"] = embed_bwd(g_top.pop("embed"), tokens, dx)
        update({"embed": g}, coef, t)
        del g, dx, g_top
        for name, count in counts.items():      # the backward used the old b
            if count is not None:
                w[name] = balance(cfg, w[name], jax.device_put(count, host))
        if t == 1:
            grad_norm = {k: float(n) for k, n in norms.items()}

    # the change, on the host, half a layer at a time (by short names, so
    # that halves of one kind share the program)
    change = jax.jit(lambda a, b: leaf_norms(
        {k: a[k].astype(jnp.float32) - b[k].astype(jnp.float32) for k in a}))
    delta = {}
    for i, names in [(i, half) for i in range(L) for half in halves[i]] \
            + [(None, ["embed", "ln_f_g"])]:
        norms = change({k: w[flat(k, i)] for k in names}, jax.device_put(
            {k: weights[flat(k, i)] for k in names}, host))
        delta.update({flat(k, i): n for k, n in norms.items()})
    return {"loss": losses, "grad_norm": grad_norm,
            "delta_norm": {k: float(n) for k, n in delta.items()},
            "states": {k: jax.device_get(a) for k, a in w.items()
                       if leaf_of(k) in STATES}}
