"""Plain reference: the language model of JoyAI-LLM-Flash (``joyai_llm_flash``,
whose configuration keys are DeepSeek-V3's, arXiv:2412.19437: multi-head
latent attention with a low-rank QUERY in every layer, one leading dense
SwiGLU layer, then sparse expert layers under a sigmoid router with a
selection bias, and a multi-token-prediction block of depth 1 trained beside
the head) in ``jax.numpy``, float32, matmuls at ``highest`` precision, as ONE
CHIP of a deployment holds it: its share of the experts and of the
vocabulary. No kernels, no cache, no sorting of tokens; it imports nothing of
the program, makes its own weights from the seed and is given only tokens.
The helpers it shares with ``reference/kexaone.py`` (the rounding control,
``RMS``, the dense SwiGLU, the router, the balancing rule) and with
``reference/ling.py`` (the rotary pairs, the expanded quadratic attention in
query-row blocks) are imported from those files as they are.

``RMS(x) = x / sqrt(mean(x^2) + rms_norm_eps) * g``. Every layer is pre-norm:
``h = x + MLA(RMS(x)); out = h + MLP(RMS'(h))``; after the last layer an RMS
(``ln_f``) and an UNTIED head, cross entropy over the vocabulary slice.
``mlp_layer_types[l]``: ``dense`` or ``sparse``. No bias anywhere.

MLA         ``c_q = RMS(W_qa x)`` (``q_lora_rank``, a gain); ``q = W_qb c_q``
            (``H`` heads of ``nope + rope``); ``[c | k_r] = W_kva x``
            (``kv_lora_rank + rope``); ``c <- RMS(c)``; ``[k_nope | v] = W_kvb
            c`` a head. Rotary positions on the last ``rope`` dimensions of q
            and on the ONE ``k_r`` (pairs ``(2i, 2i + 1)``, base
            ``rope_theta``), which every head appends to its ``k_nope``; no
            other norm, no gate; ``softmax(q k^T / sqrt(nope + rope))``,
            causal, expanded and quadratic, ``ROW_BLOCK`` query rows at a
            time; ``W_o``.
Dense MLP   ``W_down (up * silu(gate))``, ``[gate, up] = W_gu x``.
Experts     ``s = sigmoid(x W_r^T)`` over all ``published_num_experts``; a
            token's experts are the ``num_experts_per_tok`` largest of ``s +
            b`` (b selects only; ``n_group = topk_group = 1``); weights ``w_e
            = routed_scaling_factor * s_e / sum of the chosen s``. This chip
            holds ``held_experts``: ``y = sum over chosen e that are held of
            w_e E_e(x) + E_shared(x)``. Every held expert is applied to every
            token and weighted (zero where not chosen).
Balance     ``kexaone.balance``: after a training step ``b += r * sign(N k /
            E - c)``; the step itself, backward included, uses the b it
            began with.
Prediction  depth 1 (``num_nextn_predict_layers``). ``g = RMS(trunk;
            ln_f)``, the rows the head reads. ``e_i = RMS(Emb(x_{i+1});
            enorm)`` for ``i < T - 1`` and the zero row at ``T - 1``; ``u =
            W_eh [e ; RMS(g; hnorm)]``; ``w = Layer(u)``: one more sparse
            layer (leaves ``layers/<leaf>/<L>``, ``L`` the trunk's depth);
            ``logits2 = W_head RMS(w; norm)``. ``Emb`` and ``W_head`` are the
            trunk's. ``loss = mean_i CE(logits_i, y_i) + mtp_loss_weight *
            mean_{i < T - 1} CE(logits2_i, y_{i+1})``; no stop-gradient.

``train_steps`` takes the gradient HALF A LAYER AT A TIME as
``reference/ling.py`` does and for its reasons (weights in their stored type
and Adam's state on the host, one half's weights widened on the accelerator
at a time). The two tables' gradients are the SUMS of both uses: the
prediction block's backward runs first and hands its share of the head's
gradient and the cotangent of ``g`` to the head's own backward; the
embedding's two scatters are added at the end.

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


_K, _L = _beside("kexaone.py"), _beside("ling.py")
_mm, _dot, _rms, _silu = _K._mm, _K._dot, _K._rms, _K._silu
swiglu, balance, route = _K.swiglu, _K.balance, _K.route
leaf_norms, leaf_of, _widen, _group = (_K.leaf_norms, _K.leaf_of, _K._widen,
                                       _K._group)
rope_pairs, attention = _L.rope_pairs, _L.attention

HEAD_CHUNK = 1024    # positions whose logits exist at one time


# ---------------------------------------------------------------------------
# sizes and weights
# ---------------------------------------------------------------------------


def sizes(cfg: dict) -> dict:
    Fe = cfg["moe_intermediate_size"]
    return {"d": cfg["hidden_size"], "H": cfg["num_attention_heads"],
            "q_rank": cfg["q_lora_rank"], "rank": cfg["kv_lora_rank"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "Dv": cfg["v_head_dim"], "theta": float(cfg["rope_theta"]),
            "F": cfg["intermediate_size"], "Fe": Fe,
            "Fs": cfg["n_shared_experts"] * Fe,
            "E": cfg["published_num_experts"],
            "k": cfg["num_experts_per_tok"],
            "scale": float(cfg["routed_scaling_factor"]),
            "held": list(cfg["held_experts"]), "V": cfg["vocab_size"],
            "eps": cfg["rms_norm_eps"],
            "mtp": cfg["num_nextn_predict_layers"]}


def sparse(cfg: dict, i: int) -> bool:
    """Layer ``i``'s MLP; the prediction block's layer (``i`` = the trunk's
    depth) is of the last layer's kind."""
    return cfg["mlp_layer_types"][min(i, len(cfg["mlp_layer_types"]) - 1)] \
        == "sparse"


def depth(cfg: dict) -> int:
    """Layers with weights: the trunk's and the prediction block's."""
    return cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"]


def layer_shapes(cfg: dict, i: int) -> dict:
    """Leaf name -> shape for layer ``i``. Dense matrices are (out, in), as
    ``y = x @ W.T``; the stacked expert matrices (expert, in, out)."""
    z = sizes(cfg)
    d, H = z["d"], z["H"]
    out = {"ln1_g": (d,), "ln2_g": (d,), "qa_w": (z["q_rank"], d),
           "qa_norm_g": (z["q_rank"],),
           "qb_w": (H * (z["nope"] + z["rope"]), z["q_rank"]),
           "kva_w": (z["rank"] + z["rope"], d), "kv_norm_g": (z["rank"],),
           "kvb_w": (H * (z["nope"] + z["Dv"]), z["rank"]),
           "o_w": (d, H * z["Dv"])}
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


GAINS = ("ln1_g", "ln2_g", "ln_f_g", "qa_norm_g", "kv_norm_g", "mtp_enorm_g",
         "mtp_hnorm_g", "mtp_norm_g")
FLOAT32 = ("router_b",)                 # kept float32 whatever the dtype
STATES = ("router_b",)                  # no gradient; ``balance`` moves it
# a layer's leaves that its mixer half reads (the rest are its MLP's)
OPERATOR = ("ln1_g", "qa_w", "qa_norm_g", "qb_w", "kva_w", "kv_norm_g",
            "kvb_w", "o_w")
# the prediction block's own leaves outside its layer: what joins the two
# inputs, and the norm before the head
MTP_IN = ("mtp_enorm_g", "mtp_hnorm_g", "mtp_eh_w")
TOP = ("ln_f_g", "head")


def shapes(cfg: dict) -> dict:
    """Every leaf by its flat name: ``embed``, ``head``, ``ln_f_g``, with a
    prediction block ``mtp_enorm_g``, ``mtp_hnorm_g``, ``mtp_eh_w``,
    ``mtp_norm_g``, and ``layers/<leaf>/<i>`` (the block's layer last)."""
    z = sizes(cfg)
    d = z["d"]
    out = {"embed": (z["V"], d), "head": (z["V"], d), "ln_f_g": (d,)}
    if z["mtp"]:
        out.update(mtp_enorm_g=(d,), mtp_hnorm_g=(d,), mtp_eh_w=(d, 2 * d),
                   mtp_norm_g=(d,))
    for i in range(depth(cfg)):
        for leaf, shape in layer_shapes(cfg, i).items():
            out[f"layers/{leaf}/{i}"] = shape
    return out


def trained(tree: dict) -> dict:
    return {k: v for k, v in tree.items() if leaf_of(k) not in STATES}


def make_weights(cfg: dict, seed: int, dtype: str) -> dict:
    """``{flat leaf name: array}`` on the default device, as
    ``reference/kexaone.py`` makes them: matrices and both token tables N(0,
    ``initializer_range``); gains 1; the routers' selection bias, float32,
    N(0, ``router_bias_init_std``): ZERO in the benchmark's configuration.
    One normal draw a layer (and one for the tables and the prediction
    block's joining matrix), each as long as the largest of them so that one
    program makes them all, then slices."""
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


def mla_sublayer(cfg: dict, lp: dict, x, precision=None):
    """``W_o Attn`` of a latent-attention layer on normed ``x`` (B, T, d)."""
    z = sizes(cfg)
    B, T, _ = x.shape
    H, nope, rope, eps = z["H"], z["nope"], z["rope"], z["eps"]
    c_q = _rms(_mm(x, lp["qa_w"], precision), lp["qa_norm_g"], eps)
    q = _mm(c_q, lp["qb_w"], precision).reshape(B, T, H, nope + rope)
    kva = _mm(x, lp["kva_w"], precision)
    latent = _rms(kva[..., :z["rank"]], lp["kv_norm_g"], eps)
    kv = _mm(latent, lp["kvb_w"], precision).reshape(B, T, H,
                                                     nope + z["Dv"])
    k_rope = rope_pairs(kva[..., None, z["rank"]:], z["theta"])
    q = jnp.concatenate([q[..., :nope],
                         rope_pairs(q[..., nope:], z["theta"])], axis=-1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_rope, (B, T, H, rope))], axis=-1)
    out = attention(q, k, kv[..., nope:], precision)
    return _mm(out.reshape(B, T, H * z["Dv"]), lp["o_w"], precision)


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


def operator_half(cfg: dict, lp: dict, x, precision=None):
    """``h = x + MLA(RMS(x))``."""
    return x + mla_sublayer(cfg, lp, _rms(x, lp["ln1_g"],
                                          cfg["rms_norm_eps"]), precision)


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
    out, n = ffn_half(cfg, sparse(cfg, i), lp,
                      operator_half(cfg, lp, x, precision), precision)
    return (out, n) if counts else out


def hidden(cfg: dict, w: dict, tokens, precision=None):
    """The trunk's last layer's output (B, T, d), before the final RMS."""
    x = w["embed"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(functools.partial(layer, cfg, i,
                                             precision=precision))(
            layer_weights(w, i), x)
    return x


def mtp_input(cfg: dict, p: dict, rows, g, precision=None):
    """``u = W_eh [RMS(e; enorm) ; RMS(g; hnorm)]``. ``rows`` (B, T - 1, d):
    the embedding rows of tokens 1..T-1, which a zero row follows; ``g`` (B,
    T, d): the trunk's hidden state after ``ln_f``."""
    eps = cfg["rms_norm_eps"]
    e = _rms(jnp.pad(rows, ((0, 0), (0, 1), (0, 0))), p["mtp_enorm_g"], eps)
    return _mm(jnp.concatenate([e, _rms(g, p["mtp_hnorm_g"], eps)], axis=-1),
               p["mtp_eh_w"], precision)


def mtp_hidden(cfg: dict, w: dict, tokens, g, precision=None):
    """The prediction block's layer's output (B, T, d), before its norm."""
    L = cfg["num_hidden_layers"]
    u = mtp_input(cfg, w, w["embed"][tokens[:, 1:]], g, precision)
    return jax.checkpoint(functools.partial(layer, cfg, L,
                                            precision=precision))(
        layer_weights(w, L), u)


def forward(cfg: dict, w: dict, tokens, precision=None, mtp: bool = False):
    """Logits (B, T, vocab) in float32, and with ``mtp`` the prediction
    block's beside them; ``w`` a flat tree of float32 leaves."""
    eps = cfg["rms_norm_eps"]
    g = _rms(hidden(cfg, w, tokens, precision), w["ln_f_g"], eps)
    logits = _mm(g, w["head"], precision)
    if not mtp:
        return logits
    further = _rms(mtp_hidden(cfg, w, tokens, g, precision), w["mtp_norm_g"],
                   eps)
    return logits, _mm(further, w["head"], precision)


def cross_entropy(head, x, targets, seen, precision=None):
    """Sum over the positions ``seen`` (B, T) of the cross entropy of ``x
    head^T`` (x: (B, T, d) normed rows) against ``targets``; ``HEAD_CHUNK``
    positions at a time."""
    n = x.shape[0] * x.shape[1]
    chunk = math.gcd(n, HEAD_CHUNK)

    @jax.checkpoint
    def chunk_loss(xym):
        xc, yc, mc = xym
        logits = _mm(xc, head, precision)
        picked = jnp.take_along_axis(logits, yc[:, None], axis=-1)[:, 0]
        return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - picked) * mc)

    return jnp.sum(lax.map(chunk_loss, (
        x.reshape(n // chunk, chunk, -1), targets.reshape(n // chunk, chunk),
        seen.reshape(n // chunk, chunk).astype(jnp.float32))))


def main_loss(cfg: dict, top: dict, x, targets, precision=None):
    """``(mean next-token cross entropy of the head over RMS(x; ln_f), the
    rows g the head read)``."""
    g = _rms(x, top["ln_f_g"], cfg["rms_norm_eps"])
    return cross_entropy(top["head"], g, targets,
                         jnp.ones(targets.shape, bool),
                         precision) / targets.size, g


def mtp_loss(cfg: dict, p: dict, w_out, targets, precision=None):
    """Mean over ``i < T - 1`` of the cross entropy of the head over
    ``RMS(w_out; norm)`` against ``targets[:, i + 1]``; ``p`` holds
    ``mtp_norm_g`` and ``head``."""
    B, T = targets.shape
    x = _rms(w_out, p["mtp_norm_g"], cfg["rms_norm_eps"])
    seen = jnp.broadcast_to(jnp.arange(T) < T - 1, (B, T))
    return cross_entropy(p["head"], x, jnp.roll(targets, -1, axis=1), seen,
                         precision) / (B * (T - 1))


def losses(cfg: dict, w: dict, tokens, targets, precision=None):
    """``(the head's loss, the prediction block's)``, unweighted."""
    main, g = main_loss(cfg, w, hidden(cfg, w, tokens, precision), targets,
                        precision)
    if not cfg["num_nextn_predict_layers"]:
        return main, jnp.float32(0.0)
    return main, mtp_loss(cfg, w, mtp_hidden(cfg, w, tokens, g, precision),
                          targets, precision)


def loss_fn(cfg: dict, w: dict, tokens, targets, precision=None):
    """The whole model's loss under one autodiff (small sizes)."""
    main, further = losses(cfg, w, tokens, targets, precision)
    return main + cfg["mtp_loss_weight"] * further


# ---------------------------------------------------------------------------
# the checked steps
# ---------------------------------------------------------------------------


def train_steps(cfg: dict, weights: dict, batches, opt: dict, store_dtype,
                row_block: int, precision=None) -> dict:
    """Follow the first ``len(batches)`` Adam steps in float32, as
    ``reference/ling.py::train_steps`` does (the same split between the host
    and the accelerator, the same Adam with step 1's gradient kept in the
    moments' place, the large programs compiled ahead on threads;
    ``row_block`` is only checked). A half of a layer is its mixer or its
    MLP (dense or sparse); the prediction block's layer is layer ``L`` and
    shares the sparse layers' programs.

    Returns host numbers: ``loss`` per step (both terms, weighted),
    ``grad_norm`` of the first step's gradient per trained leaf (``embed``
    and ``head`` ONCE each, the sums of their two uses), ``delta_norm`` of
    the change over all the steps per leaf, the selection biases among them,
    and ``states``, the selection bias of each expert layer after the last
    step."""
    b1, b2, eps, lr = opt["beta1"], opt["beta2"], opt["epsilon"], opt["lr"]
    host, accel = jax.devices("cpu")[0], jax.devices()[0]
    L, n_layers, lam = cfg["num_hidden_layers"], depth(cfg), \
        float(cfg["mtp_loss_weight"])
    with_mtp = n_layers > L
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

    @jax.jit
    def op_fwd(lp, x):
        return operator_half(cfg, _widen(lp), x, precision)

    @jax.jit
    def op_bwd(lp, x, dy):
        _, vjp = jax.vjp(lambda p, x_: operator_half(cfg, p, x_, precision),
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
    def top_fwd(top, x):
        return _rms(x, top["ln_f_g"].astype(jnp.float32),
                    cfg["rms_norm_eps"])

    @jax.jit
    def top_bwd(top, x, targets, dg, head_g):
        """The head's loss over ``x``; its gradient with what the
        prediction block sends back added: ``dg`` onto the rows the head
        read, ``head_g`` onto the head's own."""
        (loss, _), vjp = jax.vjp(
            lambda t, x_: main_loss(cfg, t, x_, targets, precision),
            _widen(top), x)
        g, dx = vjp((jnp.float32(1.0), dg))
        g = dict(g, head=g["head"] + head_g)
        return loss, g, dx, leaf_norms(g)

    @jax.jit
    def mtp_in_fwd(p, rows, g):
        return mtp_input(cfg, _widen(p), rows, g, precision)

    @jax.jit
    def mtp_in_bwd(p, rows, g, du):
        _, vjp = jax.vjp(
            lambda p_, r_, g_: mtp_input(cfg, p_, r_, g_, precision),
            _widen(p), rows, g)
        gp, drows, dg = vjp(du)
        return gp, drows, dg, leaf_norms(gp)

    @jax.jit
    def mtp_out_bwd(p, w_out, targets):
        """``lam`` times the prediction loss: value, the gradients of the
        block's last norm and of the HEAD (its second use), and the
        cotangent of the block's output."""
        loss, (g, dw) = jax.value_and_grad(
            lambda p_, w_: lam * mtp_loss(cfg, p_, w_, targets, precision),
            argnums=(0, 1))(_widen(p), w_out)
        return loss, g, dw

    @jax.jit
    def embed_fwd(embed, tokens):
        return embed.astype(jnp.float32)[tokens]

    @jax.jit
    def embed_bwd(tokens, dx, drows):
        g = jnp.zeros(shapes(cfg)["embed"], jnp.float32).at[tokens].add(dx)
        if drows is not None:       # the prediction block's use of the table
            g = g.at[tokens[:, 1:]].add(drows)
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

    halves = [([k for k in layer_shapes(cfg, i) if k in OPERATOR],
               [k for k in layer_shapes(cfg, i) if k not in OPERATOR])
              for i in range(n_layers)]

    # the large programs (a forward and a backward for the mixer and for
    # each kind of MLP, and the two heads'), compiled AHEAD and at once, a
    # thread each
    def like(names, i=None):
        return {k: jax.ShapeDtypeStruct(w[flat(k, i)].shape,
                                        w[flat(k, i)].dtype) for k in names}

    tokens0 = batches[0][0]
    x0 = jax.ShapeDtypeStruct(tokens0.shape + (cfg["hidden_size"],),
                              jnp.float32)
    y0 = jax.ShapeDtypeStruct(tokens0.shape, tokens0.dtype)
    head0 = jax.ShapeDtypeStruct(w["head"].shape, jnp.float32)
    jobs = {("top",): (top_bwd, like(TOP), x0, y0, x0, head0),
            ("op_fwd",): (op_fwd, like(halves[0][0], 0), x0),
            ("op_bwd",): (op_bwd, like(halves[0][0], 0), x0, x0)}
    if with_mtp:
        jobs["mtp_out",] = (mtp_out_bwd, like(["mtp_norm_g", "head"]), x0, y0)
    for i, (_, ffn) in enumerate(halves):
        jobs.setdefault(("ffn_fwd", sparse(cfg, i)),
                        (ffn_fwd, sparse(cfg, i), like(ffn, i), x0))
        jobs.setdefault(("ffn_bwd", sparse(cfg, i)),
                        (ffn_bwd, sparse(cfg, i), like(ffn, i), x0, x0))
    with ThreadPoolExecutor(len(jobs)) as pool:
        run = dict(zip(jobs, pool.map(
            lambda job: job[0].lower(*job[1:]).compile(), jobs.values())))

    def sweep_forward(i, x, xs, counts):
        op, ffn = halves[i]
        xs.append(x)
        xs.append(run["op_fwd",](on_chip(op, i), x))
        y, counts[f"layers/router_b/{i}"] = run["ffn_fwd", sparse(cfg, i)](
            on_chip(ffn, i), xs[-1])
        return y

    def sweep_backward(i, dx, xs, norms, coef, t):
        op, ffn = halves[i]
        g, dx, gn = run["ffn_bwd", sparse(cfg, i)](on_chip(ffn, i), xs.pop(),
                                                   dx)
        norms.update({f"layers/{k}/{i}": n for k, n in gn.items()})
        update(g, coef, t, i)
        g, dx, gn = run["op_bwd",](on_chip(op, i), xs.pop(), dx)
        norms.update({f"layers/{k}/{i}": n for k, n in gn.items()})
        update(g, coef, t, i)
        return dx

    losses_, grad_norm = [], {}
    for t, (tokens, targets) in enumerate(batches, start=1):
        coef = jax.device_put(
            jnp.float32(lr * math.sqrt(1 - b2 ** t) / (1 - b1 ** t)), host)
        tokens, targets = jnp.asarray(tokens), jnp.asarray(targets)
        embed = on_chip(["embed"])["embed"]
        xs, counts, norms = [], {}, {}
        x = embed_fwd(embed, tokens)
        for i in range(L):
            x = sweep_forward(i, x, xs, counts)
        dg, head_g, drows, extra = jnp.zeros_like(x), jnp.zeros(
            w["head"].shape, jnp.float32), None, 0.0
        if with_mtp:
            # the prediction block, forward and backward, before the head's
            # own backward: that one needs what this sends back
            g_rows = top_fwd(on_chip(["ln_f_g"]), x)
            rows = embed_fwd(embed, tokens[:, 1:])
            ys = []
            u = mtp_in_fwd(on_chip(MTP_IN), rows, g_rows)
            w_out = sweep_forward(L, u, ys, counts)
            extra, g, dw = run["mtp_out",](on_chip(["mtp_norm_g", "head"]),
                                           w_out, targets)
            head_g = g.pop("head")
            norms.update(leaf_norms(g))
            update(g, coef, t)
            du = sweep_backward(L, dw, ys, norms, coef, t)
            g, drows, dg, gn = mtp_in_bwd(on_chip(MTP_IN), rows, g_rows, du)
            norms.update(gn)
            update(g, coef, t)
            del g_rows, rows, u, w_out, dw, du
        del embed
        loss, g, dx, gn = run["top",](on_chip(TOP), x, targets, dg, head_g)
        norms.update(gn)
        losses_.append(float(loss) + float(extra))
        update(g, coef, t)
        del dg, head_g
        for i in reversed(range(L)):
            dx = sweep_backward(i, dx, xs, norms, coef, t)
        g, norms["embed"] = embed_bwd(tokens, dx, drows)
        update({"embed": g}, coef, t)
        del g, dx, drows
        for name, count in counts.items():      # the backward used the old b
            if count is not None:
                w[name] = balance(cfg, w[name], jax.device_put(count, host))
        if t == 1:
            grad_norm = {k: float(n) for k, n in norms.items()}

    # the change, on the host, half a layer at a time (by short names, so
    # that halves of one kind share the program)
    change = jax.jit(lambda a, b: leaf_norms(
        {k: a[k].astype(jnp.float32) - b[k].astype(jnp.float32) for k in a}))
    tops = ["embed", "head", "ln_f_g"] + (
        list(MTP_IN) + ["mtp_norm_g"] if with_mtp else [])
    delta = {}
    for i, names in [(i, half) for i in range(n_layers)
                     for half in halves[i]] + [(None, tops)]:
        norms = change({k: w[flat(k, i)] for k in names}, jax.device_put(
            {k: weights[flat(k, i)] for k in names}, host))
        delta.update({flat(k, i): n for k, n in norms.items()})
    return {"loss": losses_, "grad_norm": grad_norm,
            "delta_norm": {k: float(n) for k, n in delta.items()},
            "states": {k: jax.device_get(a) for k, a in w.items()
                       if leaf_of(k) in STATES}}
