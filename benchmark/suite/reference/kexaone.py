"""Plain reference: the language model of K-EXAONE-236B-A23B (``exaone_moe``:
the EXAONE 4.0 block with sparse expert layers) in ``jax.numpy``, float32,
matmuls at ``highest`` precision, as ONE CHIP of a deployment holds it: its
share of the query and key/value heads, of the experts and of the
vocabulary. No kernels, no cache, no sorting of tokens; it imports nothing of
the program, makes its own weights from the seed and is given only tokens.

``RMS(x) = x / sqrt(mean(x^2) + eps) * g``. Every layer norms each
sub-layer's OUTPUT (the family's convention): ``h = x + RMS(Attn(x)); out = h
+ RMS'(MLP(h))``; after the last layer an RMS and an UNTIED head, cross
entropy over the vocabulary slice. ``layer_types[l]``: ``sliding_attention``
or ``full_attention``; ``mlp_layer_types[l]``: ``dense`` or ``sparse``.

Attention   ``[q, k, v] = W_qkv x`` (no bias), laid out as H query heads of
            D, then Hkv key heads, then Hkv value heads; query head h reads
            key/value head h // (H / Hkv). RMS over D on every query and key
            head (gains ``q_norm_g``, ``k_norm_g``, shared by the heads).
            Rotary positions (rotate-half: dimension i pairs with i + D / 2,
            base ``rope_parameters.rope_theta``, all D dimensions) on q and
            k in ``sliding_attention`` layers and NONE in ``full_attention``
            layers. Scores ``q k^T / sqrt(D)``. Full: causal. Window: key j
            visible to query i iff ``0 <= i - j < sliding_window``. ``W_o``
            (no bias) over the H heads held.
Dense MLP   ``W_down (up * silu(gate))``, ``[gate, up] = W_gu x``.
Experts     ``s = sigmoid(x W_r^T)`` over all ``published_num_experts``; a
            token's experts are the ``num_experts_per_tok`` largest of ``s +
            b`` (b selects only; ``n_group = topk_group = 1``: no group
            limit); weights ``w_e = routed_scaling_factor * s_e / sum of the
            chosen s``. This chip holds the experts ``held_experts``: ``y =
            sum over chosen e that are held of w_e E_e(x) + E_shared(x)``,
            every ``E`` a SwiGLU of ``moe_intermediate_size``. What the
            absent experts would add is left out. Every held expert is
            applied to every token and weighted (zero where not chosen).
Balance     ``router_bias_update_rate`` r: after a training step, ``b += r *
            sign(N k / E - c)``, c_e the tokens of the step that chose
            expert e among all E, N the step's tokens. The step itself,
            backward included, uses the b it began with.

``train_steps`` takes the gradient HALF A LAYER AT A TIME (attention with
its norm, MLP with its norm): the weights (in the type they are stored in:
every step ends by rounding them to it) and Adam's float32 moments (for two
steps: the first step's gradient in their place) live on the host; a forward
sweep puts one half's weights on the accelerator at a time, widens them
there and keeps each half's input, and a backward sweep runs one ``jax.vjp``
a half, whose float32 gradient goes to the host (and through Adam there)
before the next one's is made. Float32 weights and a whole float32 gradient
do not fit the chip together, and three float32 copies of the model beside
the run's own do not fit the machine's 40 GiB.

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
HEAD_CHUNK = 1024    # positions whose logits exist at one time


# ---------------------------------------------------------------------------
# sizes and weights
# ---------------------------------------------------------------------------


def sizes(cfg: dict) -> dict:
    Fe = cfg["moe_intermediate_size"]
    return {"d": cfg["hidden_size"], "H": cfg["num_attention_heads"],
            "Hkv": cfg["num_key_value_heads"], "D": cfg["head_dim"],
            "F": cfg["intermediate_size"], "Fe": Fe,
            "Fs": cfg["num_shared_experts"] * Fe,
            "E": cfg["published_num_experts"],
            "k": cfg["num_experts_per_tok"],
            "scale": float(cfg["routed_scaling_factor"]),
            "held": list(cfg["held_experts"]), "V": cfg["vocab_size"],
            "L": cfg["num_hidden_layers"], "eps": cfg["rms_norm_eps"]}


def windowed(cfg: dict, i: int) -> bool:
    return cfg["layer_types"][i] == "sliding_attention"


def sparse(cfg: dict, i: int) -> bool:
    return cfg["mlp_layer_types"][i] == "sparse"


def layer_shapes(cfg: dict, i: int) -> dict:
    """Leaf name -> shape for layer ``i``. Dense matrices are (out, in), as
    ``y = x @ W.T``; the stacked expert matrices are (expert, in, out)."""
    z = sizes(cfg)
    d, H, Hkv, D = z["d"], z["H"], z["Hkv"], z["D"]
    out = {"ln1_g": (d,), "ln2_g": (d,), "q_norm_g": (D,), "k_norm_g": (D,),
           "qkv_w": ((H + 2 * Hkv) * D, d), "o_w": (d, H * D)}
    if sparse(cfg, i):
        n = len(z["held"])
        out.update(router_w=(z["E"], d), router_b=(z["E"],),
                   experts_gate_up_w=(n, d, 2 * z["Fe"]),
                   experts_down_w=(n, z["Fe"], d),
                   shared_gate_up_w=(2 * z["Fs"], d),
                   shared_down_w=(d, z["Fs"]))
    else:
        out.update(gate_up_w=(2 * z["F"], d), down_w=(d, z["F"]))
    return out


GAINS = ("ln1_g", "ln2_g", "ln_f_g", "q_norm_g", "k_norm_g")
FLOAT32 = ("router_b",)                 # kept float32 whatever the dtype
STATES = ("router_b",)                  # no gradient; ``balance`` moves it
# a layer's leaves that its attention half reads (the rest are its MLP's)
ATTENTION = ("ln1_g", "q_norm_g", "k_norm_g", "qkv_w", "o_w")


def shapes(cfg: dict) -> dict:
    """Every leaf by its flat name: ``embed``, ``head``, ``ln_f_g`` and
    ``layers/<leaf>/<i>``."""
    z = sizes(cfg)
    out = {"embed": (z["V"], z["d"]), "head": (z["V"], z["d"]),
           "ln_f_g": (z["d"],)}
    for i in range(z["L"]):
        for leaf, shape in layer_shapes(cfg, i).items():
            out[f"layers/{leaf}/{i}"] = shape
    return out


def leaf_of(name: str) -> str:
    return name.split("/")[1] if "/" in name else name


def _group(name: str) -> str:
    """Leaves that are drawn, and updated, together: a layer's, or the
    two token tables and the final gain (``top``)."""
    return name.split("/")[-1] if "/" in name else "top"


def trained(tree: dict) -> dict:
    return {k: v for k, v in tree.items() if leaf_of(k) not in STATES}


def make_weights(cfg: dict, seed: int, dtype: str) -> dict:
    """``{flat leaf name: array}`` on the default device. Matrices and both
    token tables N(0, ``initializer_range``); the routers' selection bias,
    float32, N(0, ``router_bias_init_std``): ZERO in the benchmark's
    configuration, as a training run starts it, and a draw in the tests, so
    that a program that ignores it differs; gains 1. One normal draw a
    layer (and one for the tables), each as long as the largest layer so
    that one program makes them all, then slices: the float32 draw of all
    2.0e9 numbers at once would not leave room."""
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


def _dot(spec: str, a, b, precision):
    y = jnp.einsum(spec, _fake_quant(a, precision), _fake_quant(b, precision),
                   precision=HIGHEST)
    return y if precision is None else _quant_cotangent(y, precision)


def _mm(x, w, precision):
    """``x @ w.T`` for a (out, in) weight."""
    return _dot("...i,oi->...o", x, w, precision)


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * g


def _silu(x):
    return x * jax.nn.sigmoid(x)


def rope(x, theta: float):
    """``x``: (B, T, heads, D); positions 0..T-1 turn dimension i with
    dimension i + D / 2."""
    T, half = x.shape[1], x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(q, k, v, window, precision):
    """``q``: (B, T, H, D); ``k``, ``v``: (B, T, Hkv, D). Returns (B, T, H *
    D). Query rows in blocks of ``ROW_BLOCK``, each against every key under
    its mask."""
    B, T, H, D = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    rows = math.gcd(T, ROW_BLOCK)
    cols = jnp.arange(T)[None, :]
    kq, vq = _fake_quant(k, precision), _fake_quant(v, precision)

    @jax.checkpoint
    def block(args):
        qb, r0 = args                               # (B, rows, Hkv, G, D)
        at = r0 + jnp.arange(rows)[:, None]
        seen = at >= cols
        if window is not None:
            seen = seen & (at - cols < window)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", _fake_quant(qb, precision), kq,
                       precision=HIGHEST) / math.sqrt(D)
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhgqk,bkhe->bqhge", _fake_quant(p, precision), vq,
                          precision=HIGHEST)        # (B, rows, Hkv, G, D)

    qb = jnp.moveaxis(q.reshape(B, T // rows, rows, Hkv, G, D), 1, 0)
    out = lax.map(block, (qb, jnp.arange(T // rows) * rows))
    return jnp.moveaxis(out, 0, 1).reshape(B, T, H * D)


def route(z: dict, lp: dict, x, precision):
    """``(chosen (N, k) expert ids, weights (N, k))`` for rows ``x``."""
    s = jax.nn.sigmoid(_mm(x, lp["router_w"], precision))
    _, chosen = lax.top_k(s + lax.stop_gradient(lp["router_b"]), z["k"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    return chosen, z["scale"] * picked / jnp.sum(picked, axis=-1,
                                                 keepdims=True)


def experts(z: dict, lp: dict, x, precision, held=None):
    """The part of the expert layer's output that the experts ``held``
    (default the configuration's) give, for rows ``x`` (N, d): one held
    expert at a time over every row (``lp``'s stacked matrices are theirs,
    in order)."""
    held = z["held"] if held is None else held
    chosen, weights = route(z, lp, x, precision)
    Fe = z["Fe"]
    # (held, N): a row's weight for each held expert, zero where not chosen
    w_held = jnp.stack([jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)
                        for e in held])

    @jax.checkpoint
    def one(y, expert):
        w_gu, w_down, w_e = expert
        gu = _dot("ni,io->no", x, w_gu, precision)
        return y + _dot("ni,io->no", gu[:, Fe:] * _silu(gu[:, :Fe]), w_down,
                        precision) * w_e[:, None], None

    return lax.scan(one, jnp.zeros_like(x),
                    (lp["experts_gate_up_w"], lp["experts_down_w"],
                     w_held))[0]


def balance(cfg: dict, b, count):
    """The selection bias after a step in which ``count[e]`` of the tokens
    chose expert ``e``."""
    even = jnp.sum(count) / count.shape[0]
    return b + jnp.float32(cfg["router_bias_update_rate"]) \
        * jnp.sign(even - count)


def swiglu(x, w_gate_up, w_down, precision):
    """``W_down (up * silu(gate))`` for (out, in) matrices."""
    gu = _mm(x, w_gate_up, precision)
    F = w_down.shape[1]
    return _mm(gu[..., F:] * _silu(gu[..., :F]), w_down, precision)


def attention_sublayer(cfg: dict, i: int, lp: dict, x, precision=None,
                       win=None):
    """``W_o Attn(x)`` of layer ``i`` over the heads held, before its norm:
    the chip's partial sum of the output projection. ``win``: whether the
    layer is a ``sliding_attention`` one (default: what ``layer_types[i]``
    says); it may be a traced boolean, so that ``train_steps`` compiles one
    program for both kinds."""
    z = sizes(cfg)
    B, T, _ = x.shape
    H, Hkv, D, eps = z["H"], z["Hkv"], z["D"], z["eps"]
    qkv = _mm(x, lp["qkv_w"], precision)
    q = _rms(qkv[..., :H * D].reshape(B, T, H, D), lp["q_norm_g"], eps)
    k = _rms(qkv[..., H * D:(H + Hkv) * D].reshape(B, T, Hkv, D),
             lp["k_norm_g"], eps)
    v = qkv[..., (H + Hkv) * D:].reshape(B, T, Hkv, D)
    win = windowed(cfg, i) if win is None else win
    theta = cfg["rope_parameters"]["rope_theta"]
    q, k = jnp.where(win, rope(q, theta), q), jnp.where(win, rope(k, theta), k)
    # a window of T lets every earlier key through: the full layers' mask
    att = attention(q, k, v, jnp.where(win, cfg["sliding_window"], T),
                    precision)
    return _mm(att, lp["o_w"], precision)


def mlp_sublayer(cfg: dict, i: int, lp: dict, x, precision=None,
                 counts: bool = False):
    """Layer ``i``'s MLP on ``x`` (B, T, d), before its norm: the dense
    SwiGLU, or the held experts' terms plus the shared expert. With
    ``counts`` also the tokens that chose each expert, ``(E,)`` float32
    (None in a dense layer)."""
    z = sizes(cfg)
    if not sparse(cfg, i):
        y = swiglu(x, lp["gate_up_w"], lp["down_w"], precision)
        return (y, None) if counts else y
    rows = x.reshape(-1, z["d"])
    y = (experts(z, lp, rows, precision)
         + swiglu(rows, lp["shared_gate_up_w"], lp["shared_down_w"],
                  precision)).reshape(x.shape)
    if not counts:
        return y
    chosen, _ = route(z, lp, rows, precision)
    return y, jnp.sum(chosen.reshape(-1, 1) == jnp.arange(z["E"]),
                      axis=0).astype(jnp.float32)


def attention_half(cfg: dict, i: int, lp: dict, x, precision=None, win=None):
    """``h = x + RMS(Attn(x))`` of layer ``i``."""
    return x + _rms(attention_sublayer(cfg, i, lp, x, precision, win),
                    lp["ln1_g"], cfg["rms_norm_eps"])


def mlp_half(cfg: dict, i: int, lp: dict, h, precision=None):
    """``(h + RMS'(MLP(h)), mlp_sublayer's counts)`` of layer ``i``."""
    y, n = mlp_sublayer(cfg, i, lp, h, precision, counts=True)
    return h + _rms(y, lp["ln2_g"], cfg["rms_norm_eps"]), n


def layer(cfg: dict, i: int, lp: dict, x, precision=None,
          counts: bool = False):
    """Layer ``i`` on ``x`` (B, T, d); with ``counts`` also
    ``mlp_sublayer``'s."""
    out, n = mlp_half(cfg, i, lp, attention_half(cfg, i, lp, x, precision),
                      precision)
    return (out, n) if counts else out


def head_loss(cfg: dict, top: dict, x, targets, precision=None):
    """Mean next-token cross entropy of the final RMS and the untied head
    over ``x`` (B, T, d); ``HEAD_CHUNK`` positions at a time."""
    x = _rms(x, top["ln_f_g"], cfg["rms_norm_eps"])
    n = x.shape[0] * x.shape[1]
    chunk = math.gcd(n, HEAD_CHUNK)

    @jax.checkpoint
    def chunk_loss(xy):
        xc, yc = xy
        logits = _mm(xc, top["head"], precision)
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
    x = _rms(hidden(cfg, w, tokens, precision), w["ln_f_g"],
             cfg["rms_norm_eps"])
    return _mm(x, w["head"], precision)


def loss_fn(cfg: dict, w: dict, tokens, targets, precision=None):
    """The whole model's loss under one autodiff (small sizes)."""
    return head_loss(cfg, w, hidden(cfg, w, tokens, precision), targets,
                     precision)


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
    is, for the change at the end. A sweep puts one layer's weights on the
    accelerator at a time and widens them there; the forward sweep keeps
    each layer's input, the backward sweep makes one layer's float32
    gradient at a time (its attention half's, then its MLP half's), which
    goes to the host and through Adam (a program of JAX's CPU backend)
    while the accelerator works on the half before. The routers' selection
    bias is a state: no gradient, moved after each step by ``balance``.

    Returns host numbers: ``loss`` per step, ``grad_norm`` of the first
    step's gradient per trained leaf (a selection bias has no gradient and
    no entry), ``delta_norm`` of the change over all the steps per leaf,
    the selection biases among them, and ``states``, the selection bias of
    each expert layer after the last step. ``check.training_numbers``
    compares the change of the leaves that have a gradient, so it does not
    judge the selection bias: ``states`` is there for a comparison entry by
    entry (the tests make it).
    """
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

    def split(lp):
        """A layer's leaves as float32: (trained, states)."""
        lp = _widen(lp)
        return ({k: a for k, a in lp.items() if k not in STATES},
                {k: a for k, a in lp.items() if k in STATES})

    # One program a HALF of a layer and kind. Every layer's attention half
    # is one program (whether it is a window layer is an argument), the MLP
    # halves are one a kind, dense or sparse, called by the index of the
    # first layer of the kind. Compiling them is most of this reference's
    # first run.
    kinds = [sparse(cfg, i) for i in range(L)]
    like = [kinds.index(kind) for kind in kinds]
    wins = [jnp.asarray(windowed(cfg, i)) for i in range(L)]

    @jax.jit
    def attn_fwd(win, lp, x):
        return attention_half(cfg, 0, _widen(lp), x, precision, win)

    @jax.jit
    def attn_bwd(win, lp, x, dy):
        _, vjp = jax.vjp(
            lambda p, x_: attention_half(cfg, 0, p, x_, precision, win),
            _widen(lp), x)
        g, dx = vjp(dy)
        return g, dx, leaf_norms(g)

    @functools.partial(jax.jit, static_argnums=0)
    def mlp_fwd(i, lp, h):
        return mlp_half(cfg, i, _widen(lp), h, precision)

    @functools.partial(jax.jit, static_argnums=0)
    def mlp_bwd(i, lp, h, dy):
        live, state = split(lp)
        _, vjp = jax.vjp(
            lambda p, h_: mlp_half(cfg, i, {**p, **state}, h_, precision)[0],
            live, h)
        g, dx = vjp(dy)
        return g, dx, leaf_norms(g)

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

    # Adam's moments are float32 on the host, 8 bytes a parameter, which the
    # machine does not have beside the run's own. After the FIRST step both
    # are functions of its gradient (``m = (1 - b1) g``, ``v = (1 - b2) g
    # g``, to the last bit), so that gradient is kept in their place, 4
    # bytes a parameter; after the LAST step nobody reads them, so none are
    # kept. Two checked steps then hold one float32 copy, not two.
    steps = len(batches)

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
        ``i``'s by their short names (layers of one kind share the program),
        or flat names."""
        def flat(k):
            return k if i is None else f"layers/{k}/{i}"
        g = jax.device_put(g, host)
        wg, keep = {k: w[flat(k)] for k in g}, t < steps
        if t == 1:
            wg, mg, vg = adam_first(wg, g, coef), {}, {}
            if keep:
                first.update({flat(k): a for k, a in g.items()})
        elif t == 2:
            wg, mg, vg = adam_second[keep](
                wg, {k: first.pop(flat(k)) for k in g}, g, coef)
        else:
            wg, mg, vg = adam[keep](wg, {k: m.pop(flat(k)) for k in g},
                                    {k: v.pop(flat(k)) for k in g}, g, coef)
        for tree, part in zip((w, m, v), (wg, mg, vg)):
            tree.update({flat(k): a for k, a in part.items()})

    def on_chip(names, i=None):
        """The named leaves of ``w`` (layer ``i``'s, or flat names) on the
        accelerator, by short name."""
        return jax.device_put(
            {k: w[k if i is None else f"layers/{k}/{i}"] for k in names},
            accel)

    halves = [([k for k in layer_shapes(cfg, i) if k in ATTENTION],
               [k for k in layer_shapes(cfg, i) if k not in ATTENTION])
              for i in range(L)]
    losses, grad_norm = [], {}
    for t, (tokens, targets) in enumerate(batches, start=1):
        coef = jax.device_put(
            jnp.float32(lr * math.sqrt(1 - b2 ** t) / (1 - b1 ** t)), host)
        tokens, targets = jnp.asarray(tokens), jnp.asarray(targets)
        xs = [embed_fwd(on_chip(["embed"])["embed"], tokens)]
        counts = {}
        for i, (att, mlp) in enumerate(halves):
            xs.append(attn_fwd(wins[i], on_chip(att, i), xs[-1]))
            y, counts[f"layers/router_b/{i}"] = mlp_fwd(
                like[i], on_chip(mlp, i), xs[-1])
            xs.append(y)
        loss, g, dx, norms = top_bwd(on_chip(["ln_f_g", "head"]), xs.pop(),
                                     targets)
        losses.append(float(loss))
        update(g, coef, t)
        for i, (att, mlp) in reversed(list(enumerate(halves))):
            g, dx, gn = mlp_bwd(like[i], on_chip(mlp, i), xs.pop(), dx)
            norms.update({f"layers/{k}/{i}": n for k, n in gn.items()})
            update(g, coef, t, i)
            g, dx, gn = attn_bwd(wins[i], on_chip(att, i), xs.pop(), dx)
            norms.update({f"layers/{k}/{i}": n for k, n in gn.items()})
            update(g, coef, t, i)
        g, norms["embed"] = embed_bwd(tokens, dx)
        update({"embed": g}, coef, t)
        del g, dx
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
            + [(None, ["embed", "head", "ln_f_g"])]:
        flat = {k: k if i is None else f"layers/{k}/{i}" for k in names}
        norms = change({k: w[f] for k, f in flat.items()}, jax.device_put(
            {k: weights[f] for k, f in flat.items()}, host))
        delta.update({flat[k]: n for k, n in norms.items()})
    return {"loss": losses, "grad_norm": grad_norm,
            "delta_norm": {k: float(n) for k, n in delta.items()},
            "states": {k: jax.device_get(a) for k, a in w.items()
                       if leaf_of(k) in STATES}}
