"""Plain reference: the language model of Ling-3.0-flash (``bailing_hybrid``:
Kimi-Delta-Attention layers five to one beside multi-head latent attention, two
leading dense SwiGLU layers, then sparse expert layers under a group-limited
sigmoid router) in ``jax.numpy``, float32, matmuls at ``highest`` precision,
as ONE CHIP of a deployment holds it: its share of the experts and of the
vocabulary. No kernels, no chunks, no cache, no sorting of tokens; it imports
nothing of the program, makes its own weights from the seed and is given only
tokens. The helpers it shares with ``reference/kexaone.py`` (the rounding
control, ``RMS``, the dense SwiGLU, the head's loss, the balancing rule) are
imported from that file as they are.

``RMS(x) = x / sqrt(mean(x^2) + rms_norm_eps) * g``. Every layer is pre-norm:
``h = x + Mixer(RMS(x)); out = h + MLP(RMS'(h))``; after the last layer an RMS
and an UNTIED head, cross entropy over the vocabulary slice.
``layer_types[l]``: ``kda`` or ``mla``; ``mlp_layer_types[l]``: ``dense`` or
``sparse``. No bias anywhere.

KDA         ``H`` heads of ``D`` = ``head_dim``. ``[q, k, v, g] = W_in x``
            (four times ``H D``); ``q, k, v = silu(conv(.))``, ``conv_t =
            sum_{j < L} w[:, j] u_{t - (L - 1) + j}`` per channel, zeros
            before the first row (``L = short_conv_kernel_size``; one array
            of taps for the three); ``q_t <- q_t / sqrt(|q_t|^2 + 1e-6) *
            D^-0.5``, ``k_t <- k_t / sqrt(|k_t|^2 + 1e-6)`` over each head;
            ``a_t[h, c] = kda_lower_bound * sigmoid(exp(A_log[h]) * ((W_f
            x)_t[h, c] + dt_bias[h, c]))``; ``beta_t[h] = sigmoid((W_beta
            x)_t[h])``. TOKEN BY TOKEN, a state ``S`` of ``D x D`` a head
            from zero: ``S' = Diag(exp(a_t)) S_{t-1}``; ``S_t = S' + beta_t k_t
            (v_t - S'^T k_t)^T``; ``o_t = S_t^T q_t``. Output ``W_o (RMS_D(o_t;
            gain) * sigmoid(g_t))``, the norm over each head's ``D`` with one
            gain vector.
MLA         ``q = W_q x`` (``H`` heads of ``nope + rope``); ``[c | k_r] =
            W_kva x`` (``kv_lora_rank + rope``); ``c <- RMS(c)``; ``[k_nope |
            v] = W_kvb c`` a head. RMS with a gain over each query head's
            ``nope + rope`` and over ``k_nope``; rotary positions on the last
            ``rope`` dimensions of q and on the ONE ``k_r`` (pairs ``(2i, 2i
            + 1)``, base ``rope_theta``), which every head appends to its
            ``k_nope``; ``softmax(q k^T / sqrt(nope + rope))``, causal,
            expanded and quadratic, ``ROW_BLOCK`` query rows at a time; a
            head's output times ``sigmoid((W_a x)[head])``; ``W_o``.
Dense MLP   ``W_down (up * silu(gate))``, ``[gate, up] = W_gu x``.
Experts     ``s = sigmoid(x W_r^T)`` over all ``published_num_experts``; ``z =
            s + b`` (b selects only); the experts in ``n_group`` equal groups
            of neighbouring ids, a group's score the sum of its two largest
            ``z``, the ``topk_group`` best groups kept; a token's experts are
            the ``num_experts_per_tok`` largest ``z`` inside them; weights
            ``w_e = routed_scaling_factor * s_e / sum of the chosen s``. This
            chip holds ``held_experts``: ``y = sum over chosen e that are
            held of w_e E_e(x) + E_shared(x)``. Every held expert is applied
            to every token and weighted (zero where not chosen).
Balance     ``kexaone.balance``: after a training step ``b += r * sign(N k /
            E - c)``; the step itself, backward included, uses the b it
            began with.

``train_steps`` takes the gradient HALF A LAYER AT A TIME as
``reference/lfm2.py`` does and for its reasons: weights in their stored type
and Adam's state on the host, one half's weights widened on the accelerator
at a time. The delta rule's backward keeps a state every ``SEGMENT`` tokens
and runs a segment again (``jax.checkpoint``): 4096 states of 32 x 128 x 128
float32 would be 8.6 GB.

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
_fake_quant, swiglu, balance = _K._fake_quant, _K.swiglu, _K.balance
head_loss, leaf_norms, leaf_of = _K.head_loss, _K.leaf_norms, _K.leaf_of
_widen, _group = _K._widen, _K._group

HIGHEST = lax.Precision.HIGHEST
ROW_BLOCK = 512     # query rows whose scores exist at one time
SEGMENT = 64        # tokens of the delta rule between two kept states
NORM_EPS = 1e-6     # inside the square root of q's and k's L2 norm


# ---------------------------------------------------------------------------
# sizes and weights
# ---------------------------------------------------------------------------


def sizes(cfg: dict) -> dict:
    Fe = cfg["moe_intermediate_size"]
    return {"d": cfg["hidden_size"], "H": cfg["num_attention_heads"],
            "D": cfg["head_dim"], "taps": cfg["short_conv_kernel_size"],
            "bound": float(cfg["kda_lower_bound"]),
            "rank": cfg["kv_lora_rank"], "nope": cfg["qk_nope_head_dim"],
            "rope": cfg["qk_rope_head_dim"], "Dv": cfg["v_head_dim"],
            "theta": float(cfg["rope_theta"]),
            "F": cfg["intermediate_size"], "Fe": Fe,
            "Fs": cfg["num_shared_experts"]
            * cfg["moe_shared_expert_intermediate_size"],
            "E": cfg["published_num_experts"],
            "k": cfg["num_experts_per_tok"], "groups": cfg["n_group"],
            "kept": cfg["topk_group"],
            "scale": float(cfg["routed_scaling_factor"]),
            "held": list(cfg["held_experts"]), "V": cfg["vocab_size"],
            "L": cfg["num_hidden_layers"], "eps": cfg["rms_norm_eps"]}


def sparse(cfg: dict, i: int) -> bool:
    return cfg["mlp_layer_types"][i] == "sparse"


def layer_shapes(cfg: dict, i: int) -> dict:
    """Leaf name -> shape for layer ``i``. Dense matrices are (out, in), as
    ``y = x @ W.T``; the convolutions' taps (channel, tap); the stacked
    expert matrices (expert, in, out)."""
    z = sizes(cfg)
    d, H, D = z["d"], z["H"], z["D"]
    out = {"ln1_g": (d,), "ln2_g": (d,)}
    if cfg["layer_types"][i] == "kda":
        W = H * D
        out.update(in_w=(4 * W, d), conv_w=(3 * W, z["taps"]), f_w=(W, d),
                   b_w=(H, d), a_log=(H,), dt_bias=(W,), o_norm_g=(D,),
                   o_w=(d, W))
    else:
        out.update(q_w=(H * (z["nope"] + z["rope"]), d),
                   kva_w=(z["rank"] + z["rope"], d), kv_norm_g=(z["rank"],),
                   kvb_w=(H * (z["nope"] + z["Dv"]), z["rank"]),
                   gate_w=(H, d), q_norm_g=(z["nope"] + z["rope"],),
                   k_norm_g=(z["nope"],), o_w=(d, H * z["Dv"]))
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


GAINS = ("ln1_g", "ln2_g", "ln_f_g", "o_norm_g", "kv_norm_g", "q_norm_g",
         "k_norm_g")
ZEROS = ("a_log",)
FLOAT32 = ("router_b", "a_log", "dt_bias")   # float32 whatever the dtype
STATES = ("router_b",)                  # no gradient; ``balance`` moves it
# a layer's leaves that its mixer half reads (the rest are its MLP's)
OPERATOR = ("ln1_g", "in_w", "conv_w", "f_w", "b_w", "a_log", "dt_bias",
            "o_norm_g", "q_w", "kva_w", "kv_norm_g", "kvb_w", "gate_w",
            "q_norm_g", "k_norm_g", "o_w")


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


def trained(tree: dict) -> dict:
    return {k: v for k, v in tree.items() if leaf_of(k) not in STATES}


def make_weights(cfg: dict, seed: int, dtype: str) -> dict:
    """``{flat leaf name: array}`` on the default device. Matrices, the
    convolutions' taps and both token tables N(0, ``initializer_range``);
    gains 1; ``a_log`` 0; the routers' selection bias, float32, N(0,
    ``router_bias_init_std``): ZERO in the benchmark's configuration; and
    ``dt_bias``, float32, ``logit(ln 2 / (-kda_lower_bound * tau))`` with the
    half-lives ``tau`` drawn log-uniformly between ``kda_half_life_tokens``
    over a layer's channels (a uniform draw through the normal one's
    distribution function). One normal draw a layer (and one for the
    tables), each as long as the largest of them so that one program makes
    them all, then slices."""
    sh = shapes(cfg)
    dt = jnp.dtype(dtype)
    std = float(cfg.get("initializer_range", 0.02))
    bias_std = float(cfg.get("router_bias_init_std", 0.0))
    lo, hi = (math.log(t) for t in cfg.get("kda_half_life_tokens",
                                           (16, 4096)))
    bound = -float(cfg["kda_lower_bound"])
    groups = {}       # draw -> [(leaf, shape, flat name)], in a fixed order
    for name, shape in sh.items():
        if leaf_of(name) not in GAINS + ZEROS:
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
            # (the barrier keeps the slice before the reshape: moved after
            # it, the taps' (channel, 4) would be the WHOLE draw as (n, 4),
            # which a TPU pads to 128 columns: 21 GB)
            x = lax.optimization_barrier(flat[off:off + c]).reshape(shape)
            if leaf == "dt_bias":
                p = math.log(2.0) / (bound * jnp.exp(
                    lo + (hi - lo) * jax.scipy.stats.norm.cdf(x)))
                x = jnp.log(p / (1.0 - p))
            else:
                x = (bias_std if leaf == "router_b" else std) * x
            out.append(x.astype(jnp.float32 if leaf in FLOAT32 else dt))
            off += c
        return out

    # the driver's seeds pass 2**31: fold the two halves in
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    out = {n: jnp.ones(s, dt) for n, s in sh.items() if leaf_of(n) in GAINS}
    out.update({n: jnp.zeros(s, jnp.float32) for n, s in sh.items()
                if leaf_of(n) in ZEROS})
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
    """``x``: (B, T, C); ``w``: (C, L). Depthwise, causal, no bias."""
    T, taps = x.shape[1], w.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(padded[:, j:j + T] * w[:, j] for j in range(taps))


def delta_rule(q, k, v, a, beta):
    """The gated delta rule TOKEN BY TOKEN. ``q``, ``k``, ``v``, ``a``: (B,
    T, H, D); ``beta``: (B, T, H). Returns (B, T, H, D). A state every
    ``SEGMENT`` tokens is kept for the backward, which runs a segment
    again."""
    B, T, H, D = q.shape

    def step(S, x):
        q_t, k_t, v_t, a_t, b_t = x
        S = jnp.exp(a_t)[..., None] * S
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t,
                                               precision=HIGHEST))
        S = S + k_t[..., :, None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t, precision=HIGHEST)

    @jax.checkpoint
    def segment(S, xs):
        return lax.scan(step, S, xs)

    seg = math.gcd(T, SEGMENT)
    xs = tuple(jnp.moveaxis(x, 1, 0).reshape((T // seg, seg) + x.shape[:1]
                                              + x.shape[2:])
               for x in (q, k, v, a, beta))
    _, o = lax.scan(segment, jnp.zeros((B, H, D, D), jnp.float32), xs)
    return jnp.moveaxis(o.reshape(T, B, H, D), 0, 1)


def kda_sublayer(cfg: dict, lp: dict, x, precision=None):
    """``W_o (RMS(o) * sigmoid(g))`` of a KDA layer on normed ``x`` (B, T,
    d)."""
    z = sizes(cfg)
    B, T, _ = x.shape
    H, D = z["H"], z["D"]
    W = H * D
    mixed = _mm(x, lp["in_w"], precision)
    qkv = _silu(short_conv(mixed[..., :3 * W], lp["conv_w"]))
    q, k, v = (qkv[..., j * W:(j + 1) * W].reshape(B, T, H, D)
               for j in range(3))
    q = q * lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + NORM_EPS) \
        * D ** -0.5
    k = k * lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + NORM_EPS)
    logits = (_mm(x, lp["f_w"], precision) + lp["dt_bias"]).reshape(B, T, H,
                                                                     D)
    a = z["bound"] * jax.nn.sigmoid(jnp.exp(lp["a_log"])[:, None] * logits)
    beta = jax.nn.sigmoid(_mm(x, lp["b_w"], precision))
    o = delta_rule(_fake_quant(q, precision), _fake_quant(k, precision),
                   _fake_quant(v, precision), a, beta)
    o = _rms(o, lp["o_norm_g"], z["eps"]).reshape(B, T, W) \
        * jax.nn.sigmoid(mixed[..., 3 * W:])
    return _mm(o, lp["o_w"], precision)


def rope_pairs(x, theta: float):
    """``x``: (B, T, heads, R); positions 0..T-1 turn dimension 2i with
    dimension 2i + 1."""
    T, half = x.shape[1], x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def attention(q, k, v, precision):
    """Causal softmax attention, expanded. ``q``, ``k``: (B, T, H, Dk);
    ``v``: (B, T, H, Dv). Returns (B, T, H, Dv). Query rows in blocks of
    ``ROW_BLOCK``, each against every key under its mask."""
    B, T, H, Dk = q.shape
    rows = math.gcd(T, ROW_BLOCK)
    cols = jnp.arange(T)[None, :]
    kq, vq = _fake_quant(k, precision), _fake_quant(v, precision)

    @jax.checkpoint
    def block(args):
        qb, r0 = args                               # (B, rows, H, Dk)
        seen = (r0 + jnp.arange(rows)[:, None]) >= cols
        s = jnp.einsum("bqhd,bkhd->bhqk", _fake_quant(qb, precision), kq,
                       precision=HIGHEST) / math.sqrt(Dk)
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhe->bqhe", _fake_quant(p, precision), vq,
                          precision=HIGHEST)

    qb = jnp.moveaxis(q.reshape(B, T // rows, rows, H, Dk), 1, 0)
    out = lax.map(block, (qb, jnp.arange(T // rows) * rows))
    return jnp.moveaxis(out, 0, 1).reshape(B, T, H, v.shape[-1])


def mla_sublayer(cfg: dict, lp: dict, x, precision=None):
    """``W_o (Attn * sigmoid(gate))`` of a latent-attention layer on normed
    ``x`` (B, T, d)."""
    z = sizes(cfg)
    B, T, _ = x.shape
    H, nope, rope, eps = z["H"], z["nope"], z["rope"], z["eps"]
    q = _rms(_mm(x, lp["q_w"], precision).reshape(B, T, H, nope + rope),
             lp["q_norm_g"], eps)
    kva = _mm(x, lp["kva_w"], precision)
    latent = _rms(kva[..., :z["rank"]], lp["kv_norm_g"], eps)
    kv = _mm(latent, lp["kvb_w"], precision).reshape(B, T, H,
                                                     nope + z["Dv"])
    k_nope = _rms(kv[..., :nope], lp["k_norm_g"], eps)
    k_rope = rope_pairs(kva[..., None, z["rank"]:], z["theta"])
    q = jnp.concatenate([q[..., :nope],
                         rope_pairs(q[..., nope:], z["theta"])], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (B, T, H, rope))],
                        axis=-1)
    out = attention(q, k, kv[..., nope:], precision)
    gate = jax.nn.sigmoid(_mm(x, lp["gate_w"], precision))
    return _mm((out * gate[..., None]).reshape(B, T, H * z["Dv"]),
               lp["o_w"], precision)


def kept_groups(z: dict, select):
    """(N, n_group) bool: each row's ``topk_group`` best groups, a group's
    score the sum of its two largest selection scores."""
    N = select.shape[0]
    best, _ = lax.top_k(select.reshape(N, z["groups"], -1), 2)
    _, groups = lax.top_k(jnp.sum(best, axis=-1), z["kept"])
    return jnp.sum(jax.nn.one_hot(groups, z["groups"]), axis=1) > 0


def route(z: dict, lp: dict, x, precision):
    """``(chosen (N, k) expert ids, weights (N, k))`` for rows ``x``."""
    s = jax.nn.sigmoid(_mm(x, lp["router_w"], precision))
    select = s + lax.stop_gradient(lp["router_b"])
    kept = jnp.repeat(kept_groups(z, select), z["E"] // z["groups"], axis=1)
    _, chosen = lax.top_k(jnp.where(kept, select, -jnp.inf), z["k"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    return chosen, z["scale"] * picked / jnp.sum(picked, axis=-1,
                                                 keepdims=True)


def experts(z: dict, lp: dict, x, precision, held=None):
    """``(the part of the expert layer's output that the experts ``held``
    (default the configuration's) give for rows ``x`` (N, d), the tokens
    that chose each of the E experts)``: one held expert at a time over
    every row (``lp``'s stacked matrices are theirs, in order)."""
    held = z["held"] if held is None else held
    chosen, weights = route(z, lp, x, precision)
    Fe = z["Fe"]
    w_held = jnp.stack([jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)
                        for e in held])

    @jax.checkpoint
    def one(y, expert):
        w_gu, w_down, w_e = expert
        gu = _dot("ni,io->no", x, w_gu, precision)
        return y + _dot("ni,io->no", gu[:, Fe:] * _silu(gu[:, :Fe]), w_down,
                        precision) * w_e[:, None], None

    y = lax.scan(one, jnp.zeros_like(x), (lp["experts_gate_up_w"],
                                          lp["experts_down_w"], w_held))[0]
    count = jnp.sum(chosen.reshape(-1, 1) == jnp.arange(z["E"]),
                    axis=0).astype(jnp.float32)
    return y, lax.stop_gradient(count)


def operator_half(cfg: dict, kind: str, lp: dict, x, precision=None):
    """``h = x + Mixer(RMS(x))`` for a layer of ``kind`` (an entry of
    ``layer_types``)."""
    normed = _rms(x, lp["ln1_g"], cfg["rms_norm_eps"])
    op = kda_sublayer if kind == "kda" else mla_sublayer
    return x + op(cfg, lp, normed, precision)


def ffn_half(cfg: dict, is_sparse: bool, lp: dict, h, precision=None):
    """``(h + MLP(RMS'(h)), the experts' counts or None)``."""
    normed = _rms(h, lp["ln2_g"], cfg["rms_norm_eps"])
    if not is_sparse:
        return h + swiglu(normed, lp["gate_up_w"], lp["down_w"],
                          precision), None
    rows = normed.reshape(-1, h.shape[-1])
    y, count = experts(sizes(cfg), lp, rows, precision)
    y = y + swiglu(rows, lp["shared_gate_up_w"], lp["shared_down_w"],
                   precision)
    return h + y.reshape(h.shape), count


def layer(cfg: dict, i: int, lp: dict, x, precision=None,
          counts: bool = False):
    """Layer ``i`` on ``x`` (B, T, d); with ``counts`` also the tokens that
    chose each expert (None in a dense layer)."""
    h = operator_half(cfg, cfg["layer_types"][i], lp, x, precision)
    out, n = ffn_half(cfg, sparse(cfg, i), lp, h, precision)
    return (out, n) if counts else out


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


def train_steps(cfg: dict, weights: dict, batches, opt: dict, store_dtype,
                row_block: int, precision=None) -> dict:
    """Follow the first ``len(batches)`` Adam steps in float32, as
    ``reference/lfm2.py::train_steps`` does (the same split between the host
    and the accelerator, the same Adam with step 1's gradient kept in the
    moments' place, the large programs compiled ahead on threads;
    ``row_block`` is only checked). A half of a layer is its mixer (one
    program a kind, ``kda`` or ``mla``) or its MLP (dense or sparse); the
    head is a matrix of its own.

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

    # the large programs (a forward and a backward for each kind of half,
    # and the head's), compiled AHEAD and at once, a thread each
    def like(names, i=None):
        return {k: jax.ShapeDtypeStruct(w[flat(k, i)].shape,
                                        w[flat(k, i)].dtype) for k in names}

    tokens0 = batches[0][0]
    x0 = jax.ShapeDtypeStruct(tokens0.shape + (cfg["hidden_size"],),
                              jnp.float32)
    jobs = {("top",): (top_bwd, like(["ln_f_g", "head"]), x0,
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
        loss, g, dx, norms = run["top",](on_chip(["ln_f_g", "head"]),
                                         xs.pop(), targets)
        losses.append(float(loss))
        update(g, coef, t)
        for i, (op, ffn) in reversed(list(enumerate(halves))):
            g, dx, gn = run["ffn_bwd", sparse(cfg, i)](on_chip(ffn, i),
                                                       xs.pop(), dx)
            norms.update({f"layers/{k}/{i}": n for k, n in gn.items()})
            update(g, coef, t, i)
            g, dx, gn = run["op_bwd", kinds[i]](on_chip(op, i), xs.pop(),
                                                dx)
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
        norms = change({k: w[flat(k, i)] for k in names}, jax.device_put(
            {k: weights[flat(k, i)] for k in names}, host))
        delta.update({flat(k, i): n for k, n in norms.items()})
    return {"loss": losses, "grad_norm": grad_norm,
            "delta_norm": {k: float(n) for k, n in delta.items()},
            "states": {k: jax.device_get(a) for k, a in w.items()
                       if leaf_of(k) in STATES}}
