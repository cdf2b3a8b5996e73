"""The fifth family of ``HybridDecoderLM`` (Ling-3.0-flash's block:
Kimi-Delta-Attention layers beside latent attention, sparse expert layers
under a group-limited router) and its op ``contrib.kda`` (``ops/kda.py``):
the op's chunked ``lax`` form and the Pallas kernels under ``interpret=True``,
on raw operands (q and k un-normed, the gate's logits, ``A_log`` and
``dt_bias``), against the norms and the gate written plainly and the
token-by-token recurrence, values and all seven gradients, at whole and ragged
chunks, with the log-decay at both ends of ``(-5, 0)`` and AT them; the
group-limited choice against a plain top-k over masked groups; the shares of
a layer against the uncut reference; latent attention against the expanded
quadratic form; then the model against the plain float32 reference the
benchmark keeps (``benchmark/suite/reference/ling.py``, which imports
nothing of the program) at a tiny size on seeded weights: logits, loss,
every leaf's gradient, two Adam steps through ``DataParallelTrainer``.
"""

import functools
import importlib.util
import math
import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from mxtpu import autograd, nd, profiler
from mxtpu.ops import kda as K

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SUITE = os.path.join(ROOT, "benchmark", "suite")

# the cell's block at toy widths: 4 heads of 16, a latent of 24 with 16 + 8
# wide keys, 16 experts in 4 groups of which 2 are kept, 2 a token, 2 held;
# the source's layer 1 and one whole group: seven layers
CFG = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
       "head_dim": 16, "short_conv_kernel_size": 4, "kda_lower_bound": -5,
       "kv_lora_rank": 24, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
       "v_head_dim": 16, "rope_theta": 6e6, "rope_interleave": True,
       "intermediate_size": 96, "moe_intermediate_size": 32,
       "moe_shared_expert_intermediate_size": 32, "num_shared_experts": 1,
       "published_num_experts": 16, "num_experts": 2,
       "num_experts_per_tok": 2, "n_group": 4, "topk_group": 2,
       "routed_scaling_factor": 2.5, "held_experts": [0, 1],
       "rms_norm_eps": 1e-6, "vocab_size": 96, "num_hidden_layers": 7,
       "layer_types": ["kda"] * 6 + ["mla"],
       "mlp_layer_types": ["dense"] + ["sparse"] * 6,
       "tie_word_embeddings": False,
       # not 0.02 as in the benchmark's file: at a width of 64 the mixers
       # would hardly reach the logits
       "initializer_range": 0.1, "router_bias_init_std": 0.1,
       "router_bias_update_rate": 0.03, "kda_half_life_tokens": [2, 64]}
ADAM = {"lr": 3e-4, "beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8}
# float32 on both sides: what is left is the order of additions (the program
# solves a chunk's triangular system, the reference walks the tokens)
TOL_LOGITS = 2e-5       # of the largest logit
TOL_LOSS = 1e-5         # relative
TOL_GRAD = 5e-4         # a leaf's gradient, of that leaf's norm
TOL_DELTA = 2e-3        # a leaf's change over two steps, relative
T = 32
CHUNK = 16              # the op's chunk in the model tests: two chunks of T
GATE = (-5.0, 1e-6)     # the op's lower bound and the norms' eps


def _load(path, name):
    if SUITE not in sys.path:
        sys.path.insert(0, SUITE)
    spec = importlib.util.spec_from_file_location(name,
                                                  os.path.join(SUITE, path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("reference/ling.py", "t_reference_ling")


@pytest.fixture(scope="module")
def system():
    return _load("systems/ling.py", "t_system_ling")


@pytest.fixture
def small_chunks(monkeypatch):
    monkeypatch.setattr(K, "CHUNK", CHUNK)


@pytest.fixture(scope="module")
def batch():
    # 8 rows: the test session has 8 virtual devices and the trainer
    # spreads the batch over all of them
    seq = np.random.RandomState(0).randint(0, 96, (8, T + 1)).astype(np.int32)
    return seq[:, :-1], seq[:, 1:]


@pytest.fixture(scope="module")
def weights(ref):
    return ref.make_weights(CFG, 7, "float32")


def test_reference_imports_nothing_of_the_program():
    src = open(os.path.join(SUITE, "reference", "ling.py")).read()
    assert "mxtpu" not in src and "import system" not in src
    assert "lax.scan(step" in src       # the delta rule, token by token


# ---------------------------------------------------------------------------
# the op
# ---------------------------------------------------------------------------


def recurrent(q, k, v, a, beta):
    """The delta rule a token at a time: decay a channel, erase, write."""
    B, T_, H, D = q.shape

    def step(S, x):
        q_t, k_t, v_t, a_t, b_t = x
        S = jnp.exp(a_t)[..., None] * S
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t))
        S = S + k_t[..., :, None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, a, beta))
    _, o = lax.scan(step, jnp.zeros((B, H, D, D), jnp.float32), xs)
    return jnp.moveaxis(o, 0, 1).reshape(B, T_, H * D)


def plain(q, k, v, z, beta, a_log, dt_bias):
    """What the op makes of its raw operands before the recurrence, written
    plainly (no cumulative sum: the recurrence needs none), then the
    recurrence."""
    H, D = q.shape[2:]
    bound, eps = GATE

    def unit(x):
        return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)

    a = bound * jax.nn.sigmoid(jnp.exp(a_log)[:, None]
                               * (z + dt_bias.reshape(H, D)))
    return recurrent(unit(q) * D ** -0.5, unit(k), v, a, beta)


ENDS = {"slow": (1e-4, 0.05), "fast": (4.5, 5.0), "mixed": (0.0, 5.0)}
NAMES = ("value", "dq", "dk", "dv", "dz", "dbeta", "dA_log", "ddt_bias")


def _data(T_, D, end, seed=0):
    """Raw operands whose log-decays lie in the end's range."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    q, k, v = (jax.random.normal(key, (2, T_, 2, D)) for key in ks[:3])
    lo, hi = ENDS[end]
    a = -jax.random.uniform(ks[3], (2, T_, 2, D), minval=lo, maxval=hi)
    a_log = 0.3 * jax.random.normal(ks[6], (2,))
    dt_bias = jax.random.normal(ks[7], (2 * D,))
    share = jnp.clip(a / -5.0, 1e-6, 1.0 - 1e-6)
    z = jnp.log(share / (1.0 - share)) / jnp.exp(a_log)[:, None] \
        - dt_bias.reshape(2, D)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (2, T_, 2)))
    w = jax.random.normal(ks[5], (2, T_, 2 * D))
    return (q, k, v, z, beta, a_log, dt_bias), w


def _value_and_grads(fn, args, w):
    value = fn(*args)
    grads = jax.grad(lambda *x: jnp.sum(fn(*x) * w),
                     argnums=tuple(range(7)))(*args)
    return dict(zip(NAMES, (value,) + grads))


def _lax(chunk):
    def op(q, k, v, z, beta, a_log, dt_bias):
        return K._kda_lax(q, k, v, z, beta, *K._gate_rows(a_log, dt_bias),
                          *GATE, chunk)
    return op


@functools.lru_cache(maxsize=None)
def _forms(T_, D, end, chunk):
    args, w = _data(T_, D, end)
    return (_value_and_grads(plain, args, w),
            _value_and_grads(_lax(chunk), args, w))


def _close(got, want, what, terms=1):
    scale = float(jnp.max(jnp.abs(want)))
    assert scale > 0, what
    # (1e-7: where every channel forgets in a token the decay's gradient is
    # of the size of float32's rounding of the values it is made from; a
    # parameter of the gate sums ``terms`` such gradients, and their rounding
    # as the root of their number)
    assert float(jnp.max(jnp.abs(got.reshape(want.shape) - want))) \
        <= 2e-5 * scale + 1e-7 * math.sqrt(terms), what


def _summed(want, what):
    """How many logits' gradients one entry of ``what`` adds up."""
    return want["dz"].size // want[what].size \
        if what in ("dA_log", "ddt_bias") else 1


@pytest.mark.parametrize("T_", [64, 40])        # whole chunks of 32, and not
@pytest.mark.parametrize("end", list(ENDS))
@pytest.mark.parametrize("what", NAMES)
def test_chunked_lax_form_against_the_recurrence(T_, end, what):
    want, got = _forms(T_, 32, end, 32)
    _close(got[what], want[what], (T_, end, what), _summed(want, what))


def _launch(args, w, kept=lambda tinv: tinv, dtype=jnp.float32, chunk=32):
    """The two kernels interpreted on ``_data``'s operands, chunks of
    ``chunk``, q, k and v of ``dtype``; ``kept`` stands between the forward's
    inverses and the backward. Returns the value and the gradients by name,
    the kept states and the kept inverses."""
    q, k, v, z, beta, a_log, dt_bias = args
    B, T_, H, D = q.shape
    flat = [x.reshape(B, T_, H * D).astype(dtype) for x in (q, k, v)] \
        + [z.reshape(B, T_, H * D)]
    rows, leaves = jax.vjp(K._gate_rows, a_log, dt_bias)
    o, s0, tinv = K._forward_pallas(*flat, beta, *rows, *GATE,
                                    interpret=True, chunk=chunk)
    *grads, dbias, drate = K._backward_pallas(
        *flat, beta, *rows, s0, kept(tinv), w.astype(dtype), *GATE,
        interpret=True, chunk=chunk)
    return dict(zip(NAMES, (o, *grads, *leaves((dbias, drate))))), s0, tinv


@functools.lru_cache(maxsize=None)
def _kernels(end):
    """The two kernels interpreted, heads of 128, two chunks of 32."""
    args, w = _data(64, 128, end)
    return (_value_and_grads(plain, args, w),) + _launch(args, w)


@pytest.mark.parametrize("end", list(ENDS))
@pytest.mark.parametrize("what", NAMES)
def test_pallas_kernels_interpreted_against_the_recurrence(end, what):
    want, got, s0, tinv = _kernels(end)
    _close(got[what], want[what], (end, what), _summed(want, what))
    assert s0.shape == (2, 2, 2, 128, 128) and s0.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(s0[:, :, 0]))) == 0.0      # from zero
    assert tinv.shape == (2, 2, 2, 32, 32) and tinv.dtype == jnp.float32


@pytest.mark.parametrize("T_,C", [(64, 32), (96, 32), (48, 16)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_kept_inverse_is_the_chunks_solve_rounded_once(dtype, T_, C):
    """What ``kda_fwd`` keeps of a chunk is ``_solve(beta * A)`` in the type
    the products take it in, bit for bit: made here from the same tiles by
    the same functions, outside the kernel. Chunks of two sub-chunks, and of
    one (the diagonal phase alone)."""
    dt = jnp.dtype(dtype)
    args, w = _data(T_, 128, "mixed", seed=3)
    _, _, tinv = _launch(args, w, dtype=dt, chunk=C)
    assert tinv.shape == (2, 2, T_ // C, C, C) and tinv.dtype == dt
    q, k, _, z, beta, a_log, dt_bias = args
    bias, rate = K._gate_rows(a_log, dt_bias)

    @jax.jit
    def one(q, k, z, beta, bias, rate):
        q, k, g = K._chunk_operands(q.astype(dt), k.astype(dt), z, bias,
                                    rate, *GATE)
        return K._solve(beta[:, None] * K._chunk_parts(q, k, g, dt)["A"]) \
            .astype(dt)

    for b, h, c in np.ndindex(*tinv.shape[:3]):
        rows = slice(C * c, C * c + C)
        want = one(q[b, rows, h], k[b, rows, h], z[b, rows, h],
                   beta[b, rows, h], bias[h:h + 1], rate[h:h + 1])
        assert np.array_equal(np.asarray(tinv[b, h, c], np.float32),
                              np.asarray(want, np.float32)), (b, h, c)
        assert float(jnp.max(jnp.abs(jnp.triu(want.astype(jnp.float32), 1)))) \
            == 0.0 and bool(jnp.all(jnp.diagonal(want) == 1))


@functools.lru_cache(maxsize=None)
def _kept_and_solved_again(dtype):
    """``kda_bwd`` on the kept inverse, and the same kernel with the system
    solved again inside it from the chunk's own matrices, as it was before
    the forward kept anything."""
    args, w = _data(64, 128, "mixed", seed=4)
    kept, _, _ = _launch(args, w, dtype=jnp.dtype(dtype))
    b = K._chunk_backward

    def solves(q, k, v, g, beta, s0, tinv, do, ds1, dt):
        # (behind a barrier: the CPU compiler then fuses the rest of the
        # body as it does without the solve, and rounds its sums alike)
        q_, k_, g_, beta_ = lax.optimization_barrier((q, k, g, beta))
        again = K._solve(beta_ * K._chunk_parts(q_, k_, g_, dt)["A"])
        return b(q, k, v, g, beta, s0, again, do, ds1, dt)

    K._chunk_backward = solves
    try:
        again, _, _ = _launch(args, w, kept=jnp.zeros_like,
                              dtype=jnp.dtype(dtype))
    finally:
        K._chunk_backward = b
    return kept, again


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("what", NAMES[1:])
def test_the_backward_on_the_kept_inverse_is_the_one_that_solved(what, dtype):
    kept, again = _kept_and_solved_again(dtype)
    assert float(jnp.max(jnp.abs(kept[what].astype(jnp.float32)))) > 0
    assert np.array_equal(np.asarray(kept[what], np.float32),
                          np.asarray(again[what], np.float32))


def test_the_gate_at_its_bounds_stays_finite():
    """Logits of +-1e4: the log-decay within rounding of ``lower_bound`` (15
    rows of it in a sub-chunk) and of 0. Value and every gradient finite, and
    the value the recurrence's."""
    (q, k, v, z, *rest), w = _data(64, 32, "mixed")
    z = 1e4 * jnp.sign(z)
    z = z.at[:, 16:32].set(1e4).at[:, 32:48].set(-1e4)
    args = (q, k, v, z, *rest)
    got = _value_and_grads(_lax(32), args, w)
    for what in NAMES:
        assert bool(jnp.all(jnp.isfinite(got[what]))), what
    _close(got["value"], plain(*args), "value")
    assert float(jnp.max(jnp.abs(got["dz"]))) == 0.0    # the gate is flat


def _strictly_lower(C, kind):
    """A chunk's ``Diag(beta) A``, ``(C, C)`` float32, to stress the solve's
    diagonal phase: seeded noise; the matrix the op makes where every key of
    a sub-chunk is the same unit vector and ``beta`` is 1 (entries near 1 on
    every diagonal block); -0.5 everywhere below the diagonal, whose
    inverse's entries GROW by 1.5 a row (to 1e22 over 128 rows); nothing."""
    rs = np.random.RandomState(3)
    if kind == "random":
        return np.tril(rs.randn(C, C), -1).astype(np.float32) * 0.3
    if kind == "equal_keys":
        k = rs.randn(C // K.SUB, 1, 32).repeat(K.SUB, 1).reshape(C, 32)
        k = jnp.asarray(k / np.linalg.norm(k, axis=1, keepdims=True),
                        jnp.float32)
        g = jnp.cumsum(jnp.full((C, 32), -0.05, jnp.float32), axis=0)
        return np.asarray(K._chunk_parts(k, k, g, jnp.float32)["A"])
    return np.tril(np.full((C, C), {"grows": -0.5, "zero": 0.0}[kind],
                           np.float32), -1)


@pytest.mark.parametrize("C", [16, 32, 48, 128])
@pytest.mark.parametrize("kind", ["random", "equal_keys", "grows", "zero"])
def test_the_triangular_solve_by_blocks_is_the_inverse(kind, C):
    """Against numpy's float64 inverse, to a float32 bound: the diagonal
    blocks by substitution alone (``C == SUB``), with the blocks below them
    at two, three and eight blocks a chunk; jitted and under ``vmap``, as the
    ``lax`` form runs it."""
    n = _strictly_lower(C, kind)
    want = np.linalg.inv(np.eye(C) + n.astype(np.float64))
    for got in (jax.jit(K._solve)(jnp.asarray(n)),
                jax.vmap(K._solve)(jnp.asarray(n)[None])[0]):
        np.testing.assert_allclose(np.asarray(got, np.float64), want, rtol=0,
                                   atol=2e-6 * np.abs(want).max())
        assert float(jnp.max(jnp.abs(jnp.triu(got, 1)))) == 0.0
        if kind == "zero":
            assert np.array_equal(np.asarray(got), np.eye(C))
    assert kind != "grows" or np.abs(want).max() > 100


def test_op_counts_its_path_and_its_kept_states():
    profiler.reset_kernel_path_counts()
    profiler.reset_launch_stats("kda")
    args, _ = _data(40, 32, "slow")
    out = nd.contrib.kda(*(nd.NDArray(x) for x in args))
    assert out.shape == (2, 40, 64)
    assert profiler.get_kernel_path_counts()["kda"] == {"pallas": 0, "xla": 1}
    # 40 rows are one chunk of 48 (whole sub-chunks of 16)
    # ... and the lax form keeps no inverse: its transpose solves again
    assert profiler.get_kda_stats() == {
        "launches": 1, "chunk": 48, "chunks": 1,
        "state_bytes_kept": 2 * 2 * 32 * 32 * 4, "inverse_bytes_kept": 0}
    for dtype, itemsize in ((None, 0), (jnp.bfloat16, 2), (jnp.float32, 4)):
        assert K.kda_stats(4096, 32, 128, kernel_dtype=dtype) == {
            "chunk": 128, "chunks": 32, "state_bytes_kept": 32 * 32 * 65536,
            "inverse_bytes_kept": 32 * 32 * 128 * 128 * itemsize}
    profiler.reset_launch_stats("kda")
    assert profiler.get_kda_stats()["inverse_bytes_kept"] == 0


# ---------------------------------------------------------------------------
# the router's groups, the shares, latent attention
# ---------------------------------------------------------------------------


def _plain_choice(s, bias, top_k, n_group, topk_group):
    """Top-k over masked groups, in numpy, a token at a time."""
    z = s + bias
    chosen = []
    for row in z:
        groups = row.reshape(n_group, -1)
        score = np.sort(groups, axis=1)[:, -2:].sum(axis=1)
        kept = np.argsort(-score, kind="stable")[:topk_group]
        masked = np.full_like(row, -np.inf).reshape(n_group, -1)
        masked[kept] = groups[kept]
        chosen.append(np.argsort(-masked.reshape(-1),
                                 kind="stable")[:top_k])
    return np.asarray(chosen)


@pytest.mark.parametrize("n_group,topk_group", [(8, 4), (4, 1), (2, 2)])
def test_group_limited_choice_against_a_plain_top_k(n_group, topk_group):
    from mxtpu.parallel import moe
    rs = np.random.RandomState(5)
    x = jnp.asarray(rs.randn(64, 16), jnp.float32)
    router = jnp.asarray(rs.randn(32, 16), jnp.float32)
    bias = jnp.asarray(0.2 * rs.randn(32), jnp.float32)
    chosen, weights = moe._route(x, router, bias, 4, 2.5, 0.0, n_group,
                                 topk_group)
    s = np.asarray(jax.nn.sigmoid(x @ router.T))
    kept = moe._kept_groups(jnp.asarray(s) + bias, n_group, topk_group)
    want = _plain_choice(s, np.asarray(bias), 4, n_group, topk_group)
    assert np.array_equal(np.sort(np.asarray(chosen), 1), np.sort(want, 1))
    picked = np.take_along_axis(s, np.asarray(chosen), 1)
    np.testing.assert_allclose(
        np.asarray(weights), 2.5 * picked / picked.sum(1, keepdims=True),
        rtol=1e-6)
    assert kept.shape == (64, n_group) \
        and np.all(np.asarray(kept).sum(1) == topk_group)
    groups = np.asarray(chosen) // (32 // n_group)
    assert all(np.asarray(kept)[t, g] for t in range(64) for g in groups[t])


def test_one_group_is_todays_choice_bit_for_bit():
    """``n_group=1`` (the default) takes the ungrouped path; every group kept
    is the same choice by another road; and the default layer's traced
    program carries no trace of groups."""
    from mxtpu.parallel import moe
    from mxtpu.parallel.moe import SparseExperts
    rs = np.random.RandomState(6)
    x = jnp.asarray(rs.randn(64, 16), jnp.float32)
    router = jnp.asarray(rs.randn(32, 16), jnp.float32)
    bias = jnp.asarray(0.2 * rs.randn(32), jnp.float32)
    one = moe._route(x, router, bias, 4, 2.5)
    every = moe._route(x, router, bias, 4, 2.5, 0.0, 4, 4)
    assert np.array_equal(np.asarray(one[0]), np.asarray(every[0]))
    assert np.array_equal(np.asarray(one[1]), np.asarray(every[1]))
    plain = SparseExperts(16, 8, 32, 4, held=[0, 1])
    grouped = SparseExperts(16, 8, 32, 4, held=[0, 1], n_group=1,
                            topk_group=1)
    assert set(plain.collect_params().keys()) \
        == {k.replace(grouped.prefix, plain.prefix)
            for k in grouped.collect_params().keys()}
    text = jax.jit(lambda *a: moe.sparse_experts(
        *a, held=(0, 1), top_k=4, scale=2.5)).lower(
        jnp.zeros((8, 16)), router, bias, jnp.zeros((2, 16, 16)),
        jnp.zeros((2, 8, 16))).as_text(debug_info=True)
    assert "groups" not in text
    with pytest.raises(ValueError, match="groups"):
        SparseExperts(16, 8, 32, 4, n_group=5)
    with pytest.raises(ValueError, match="groups"):
        SparseExperts(16, 8, 32, 4, n_group=16, topk_group=1)  # 4 of 2


def test_the_shares_add_up_to_the_uncut_layer(ref):
    """The guide's share test: 16 experts in 4 groups (2 kept, 2 a token)
    over 8 chips of 2. The routed terms of all 8 shares, every share routing
    over all 16 under the same groups, with the shared expert, which every
    chip computes alike, counted once, add up to the uncut reference's MLP
    sub-layer."""
    from mxtpu.parallel.moe import SparseExperts
    uncut = dict(CFG, num_experts=16, held_experts=list(range(16)))
    w = ref.make_weights(uncut, 11, "float32")
    lp = ref.layer_weights(w, 1)
    x = jnp.asarray(np.random.RandomState(2).randn(2, T, 64), jnp.float32)
    z = ref.sizes(uncut)
    rows = x.reshape(-1, 64)
    whole = ref.experts(z, lp, rows, None)[0] + ref.swiglu(
        rows, lp["shared_gate_up_w"], lp["shared_down_w"], None)
    total, pairs = 0.0, 0.0
    for share in range(8):
        held = [2 * share, 2 * share + 1]
        blk = SparseExperts(64, 32, 16, 2, held=held, shared_ffn_units=32,
                            routed_scale=2.5, n_group=4, topk_group=2)
        blk.initialize()
        for p, a in ((blk.router, lp["router_w"]),
                     (blk.select_bias, lp["router_b"]),
                     (blk.gate_up, lp["experts_gate_up_w"][jnp.asarray(held)]),
                     (blk.down, lp["experts_down_w"][jnp.asarray(held)]),
                     (blk.shared.gate_up.weight, lp["shared_gate_up_w"]),
                     (blk.shared.down.weight, lp["shared_down_w"])):
            p.set_data(nd.NDArray(a))
        total = total + blk(nd.NDArray(x)).data
        pairs += blk.stats()["pairs"]
    once = blk.shared(nd.NDArray(x)).data
    assert pairs == 2 * T * 2                      # every pair is somewhere
    np.testing.assert_allclose(
        np.asarray(total - 7 * once).reshape(-1, 64), np.asarray(whole),
        rtol=1e-4, atol=2e-5)


def test_latent_attention_against_the_expanded_quadratic_form(ref, weights):
    """One rotary key for all heads, neighbouring pairs turned, the norms
    before the positions, a gate a head: the program's layer on the
    reference's weights."""
    from mxtpu.gluon.model_zoo.hybrid_decoder import LatentAttention, _rope
    lp = ref.layer_weights(weights, 6)
    att = LatentAttention(64, 4, 24, 16, 8, 16, rope_theta=6e6,
                          interleave=True, norm_eps=1e-6)
    att.initialize()
    for p, leaf in ((att.q_proj.weight, "q_w"), (att.kva_proj.weight, "kva_w"),
                    (att.kv_norm.gamma, "kv_norm_g"),
                    (att.kvb_proj.weight, "kvb_w"),
                    (att.gate_proj.weight, "gate_w"), (att.q_norm, "q_norm_g"),
                    (att.k_norm, "k_norm_g"), (att.out_proj.weight, "o_w")):
        p.set_data(nd.NDArray(lp[leaf]))
    x = jnp.asarray(np.random.RandomState(4).randn(2, T, 64), jnp.float32)
    want = ref.mla_sublayer(CFG, lp, x)
    got = att(nd.NDArray(x), {}).data
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=1e-5 * float(jnp.abs(want).max()))
    # the positional part alone: the last 8 of 24 turn, by neighbours
    y = jnp.asarray(np.random.RandomState(5).randn(1, 4, 2, 24), jnp.float32)
    turned = _rope(y, 6e6, True, 8)
    assert np.array_equal(np.asarray(turned[..., :16]), np.asarray(y[..., :16]))
    np.testing.assert_allclose(np.asarray(turned[..., 16:]),
                               np.asarray(ref.rope_pairs(y[..., 16:], 6e6)),
                               rtol=1e-6, atol=1e-6)
    assert np.array_equal(np.asarray(turned[:, 0]), np.asarray(y[:, 0]))


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


def test_logits_loss_and_every_gradient_leaf(ref, system, weights, batch,
                                             small_chunks):
    """Six delta-rule layers over two chunks and one latent-attention layer,
    the dense MLP once and six expert layers under the group-limited choice:
    logits, loss and every leaf's gradient; int8 operands fail the
    tolerances."""
    x, y = batch
    net = system.build_net(CFG, weights, "float32")
    assert net.head is not None and net.layer_kinds == ("kda",) * 6 + ("mla",)
    assert net.mlp_kinds == ("mlp",) + ("moe",) * 6
    assert float(jnp.abs(weights["layers/router_b/1"]).max()) > 0.05
    # the seeded gates: half-lives of 2 to 64 tokens, not one token
    a0 = -5 * jax.nn.sigmoid(weights["layers/dt_bias/0"])
    assert -0.36 < float(a0.min()) and float(a0.max()) < -0.01
    logits = net(nd.array(x)).data
    want = ref.forward(CFG, weights, jnp.asarray(x))
    top = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(logits - want))) <= TOL_LOGITS * top
    low = ref.forward(CFG, weights, jnp.asarray(x), "int8")
    assert float(jnp.max(jnp.abs(low - want))) > 20 * TOL_LOGITS * top

    leaves = [(p, leaf) for p, leaf in system.param_leaves(net)
              if p.grad_req != "null"]
    assert {leaf for _, leaf in leaves} == set(ref.trained(weights))
    for p, _ in leaves:
        p.data().attach_grad()
    with autograd.record():
        loss = nd.mean(system.system.seq_loss(
            net(nd.array(x)), nd.array(y.astype(np.float32))))
    loss.backward()
    want_loss, want_g = jax.value_and_grad(lambda w: ref.loss_fn(
        CFG, w, jnp.asarray(x), jnp.asarray(y)))(weights)
    assert abs(float(loss.asscalar()) - float(want_loss)) \
        <= TOL_LOSS * float(want_loss)
    for p, leaf in leaves:
        norm = float(jnp.linalg.norm(want_g[leaf]))
        assert norm > 0, leaf
        gap = float(jnp.linalg.norm(p.data().grad.data - want_g[leaf])) / norm
        assert gap <= TOL_GRAD, (leaf, gap)


def _drop_the_carried_state(monkeypatch):
    """The fault the cell's limits are set against (PERF.md, section 2), as
    the chip control plants it: the delta rule's state zeroed at every chunk
    start, forward and backward."""
    f, b = K._chunk_forward, K._chunk_backward
    monkeypatch.setattr(K, "_chunk_forward", lambda q, k, v, g, beta, s0, dt:
                        f(q, k, v, g, beta, s0 * 0.0, dt))
    monkeypatch.setattr(
        K, "_chunk_backward", lambda q, k, v, g, beta, s0, tinv, do, ds1, dt:
        b(q, k, v, g, beta, s0 * 0.0, tinv, do, ds1 * 0.0, dt))


@functools.lru_cache(maxsize=None)
def _zeroed_inverse():
    args, w = _data(64, 128, "slow")
    return _launch(args, w, kept=jnp.zeros_like)[0]


# the kept inverse reaches the backward alone: the value is the sound one's
@pytest.mark.parametrize("fault,what", [("state", what) for what in NAMES]
                         + [("inverse", what) for what in NAMES[1:]])
def test_a_dropped_carried_state_fails_the_op(fault, what, monkeypatch):
    """Two chunks of 32 under slow decays: the value and all seven gradients
    of the faulty op are outside what the sound one is held to. And so are
    the seven gradients of the kernels whose kept INVERSE is zeroed between
    ``kda_fwd`` and ``kda_bwd``: the backward reads the residual, it does
    not make its own."""
    if fault == "state":
        _drop_the_carried_state(monkeypatch)
        args, w = _data(64, 32, "slow")
        got = _value_and_grads(_lax(32), args, w)[what]
        want = _forms(64, 32, "slow", 32)[0][what]
    else:
        got, want = _zeroed_inverse()[what], _kernels("slow")[0][what]
    assert float(jnp.max(jnp.abs(got.reshape(want.shape) - want))) \
        > 100 * 2e-5 * float(jnp.max(jnp.abs(want)))


def test_a_dropped_carried_state_fails_the_model(ref, system, weights, batch,
                                                 small_chunks, monkeypatch):
    """And the model's logits and loss against the reference's, by twenty
    times the tolerances the sound model passes."""
    _drop_the_carried_state(monkeypatch)
    x, y = batch
    net = system.build_net(CFG, weights, "float32")
    logits = net(nd.array(x))
    loss = float(nd.mean(system.system.seq_loss(
        logits, nd.array(y.astype(np.float32)))).asscalar())
    want = ref.forward(CFG, weights, jnp.asarray(x))
    top = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(logits.data - want))) > 20 * TOL_LOGITS * top
    want_loss = float(ref.loss_fn(CFG, weights, jnp.asarray(x),
                                  jnp.asarray(y)))
    assert abs(loss - want_loss) > 20 * TOL_LOSS * want_loss


def test_two_adam_steps_through_the_trainer(ref, system, weights, batch,
                                            small_chunks):
    """The reference's half-layer-at-a-time gradient and host Adam against
    the trainer's one program; the selection bias rides the step as an
    auxiliary state and equals the reference's after two steps."""
    x, y = batch
    # counted for the whole process: whatever ran before in this worker
    # (a TPU-platform lowering, say) must not be read as this step's
    profiler.reset_kernel_path_counts()
    net = system.build_net(CFG, weights, "float32")
    w0 = system.param_arrays(net)
    trainer = system.Trainer(net, ADAM)
    losses = []
    for i in range(2):
        losses.append(float(trainer.step(*trainer.place(x, y))))
        if i == 0:
            grad_norm = trainer.first_gradient_norm()
    now = trainer.param_arrays()
    steps = [(jnp.asarray(x), jnp.asarray(y))] * 2
    want = ref.train_steps(CFG, ref.make_weights(CFG, 7, "float32"), steps,
                           ADAM, "float32", row_block=8)
    whole = math.sqrt(sum(v * v for v in want["grad_norm"].values()))
    for a, b in zip(losses, want["loss"]):
        assert abs(a - b) <= TOL_LOSS * b
    assert abs(grad_norm - whole) <= TOL_GRAD * whole
    floor = np.median(list(want["delta_norm"].values()))
    assert set(want["delta_norm"]) == set(w0) == set(want["grad_norm"]) \
        | set(want["states"]) and len(want["states"]) == 6
    for leaf, r in want["delta_norm"].items():
        got = float(np.linalg.norm(now[leaf] - w0[leaf]))
        assert abs(got - r) <= TOL_DELTA * max(r, floor), leaf
    assert losses[1] < losses[0]
    for i in range(1, 7):
        b = net.blocks[i].moe.select_bias.data().asnumpy()
        np.testing.assert_allclose(b, want["states"][f"layers/router_b/{i}"],
                                   rtol=0, atol=1e-6)
    rows = profiler.get_moe_stats(net)
    assert len(rows) == 6 and all(r["passes"] == 1 for r in rows)
    import moe as readers
    assert len(readers.STEP_COUNTS) == 2
    paths = system.kernel_path_counts()
    assert paths["kda"]["xla"] > 0 and paths["flash"]["xla"] > 0 \
        and paths["kda"]["pallas"] == paths["flash"]["pallas"] == 0
    import ling as ling_readers
    assert ling_readers.KDA_STATS["chunk"] == CHUNK \
        and ling_readers.KDA_STATS["chunks"] == 2 \
        and ling_readers.KDA_STATS["state_bytes_kept"] == 8 * 4 * 2 * 1024


def test_step_carries_scopes_and_kernel_names(ref, system, weights, batch,
                                              monkeypatch):
    x, y = batch
    net = system.build_net(CFG, weights, "float32")
    trainer = system.Trainer(net, ADAM)
    trainer.step(*trainer.place(x, y))
    text = trainer.dpt.lowered().as_text(debug_info=True)
    for scope in ("block0/kda/proj", "block0/kda/conv", "block3/kda/gate",
                  "block5/kda/scan", "block0/kda/out", "block6/mla/proj",
                  "block6/mla/rope", "block6/mla/attn", "block6/mla/out",
                  "block0/mlp/gate_up", "block1/moe/route/groups",
                  "block2/moe/dispatch", "block6/moe/experts",
                  "block1/moe/shared", "block1/moe/balance", "ln_f", "head",
                  "loss"):
        assert scope in text, scope
    assert "block0/moe" not in text and "block6/kda" not in text
    # on the TPU platform at heads of 128 and whole chunks: the launches by
    # name (a forward alone: the backward's names are in the compile test)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    wide = dict(CFG, hidden_size=128, head_dim=128, num_attention_heads=1,
                num_key_value_heads=1, qk_nope_head_dim=128,
                qk_rope_head_dim=64, v_head_dim=128, kv_lora_rank=128,
                intermediate_size=256, moe_intermediate_size=128,
                moe_shared_expert_intermediate_size=128, vocab_size=128,
                num_hidden_layers=2, layer_types=["kda", "mla"],
                mlp_layer_types=["dense", "sparse"])
    net2 = system.build_net(wide, ref.make_weights(wide, 1, "bfloat16"),
                            "bfloat16")
    profiler.reset_kernel_path_counts()

    def loss(tokens):
        with autograd.pause(train_mode=True):
            return jnp.sum(net2(nd.NDArray(tokens)).data.astype(jnp.float32))

    hlo = jax.jit(loss).trace(jnp.zeros((1, 128), jnp.int32)).lower(
        lowering_platforms=("tpu",)).as_text()
    assert set(re.findall(r'kernel_name = "([^"]+)"', hlo)) == {
        "kda_fwd", "flash_fwd", "moe_gmm"}
    paths = profiler.get_kernel_path_counts()
    assert paths["kda"] == {"pallas": 1, "xla": 0} \
        and paths["flash"] == {"pallas": 1, "xla": 0}


def test_decoding_raises_and_names_both_states(system, weights):
    from mxtpu.gluon.model_zoo.hybrid_decoder import (HybridDecoderLM, KINDS,
                                                      MIXERS)
    assert {"kda", "mla"} <= set(KINDS) and tuple(MIXERS) == KINDS
    assert all(row.decode_state for row in MIXERS.values())
    net = system.build_net(CFG, weights, "float32")
    with pytest.raises(NotImplementedError, match="trains only") as err:
        net.generate(nd.array(np.zeros((1, 4))), 4)
    said = str(err.value)
    assert "kda: a head_dim x head_dim float32 matrix a head" in said \
        and "mla: a latent row and one rotary key a token" in said
    assert "8256 x 129" not in said and "mamba" not in said
    with pytest.raises(NotImplementedError, match="trains only"):
        net.serving_step()
    with pytest.raises(ValueError, match="give mla="):
        HybridDecoderLM(96, ["mla"], units=64, ffn_units=96, num_heads=4,
                        num_kv_heads=4)


# The mixer protocol and the table of kinds (PR 45) are shared by every
# family: this family's step has to trace to the program it traced to before
# them (hash of the printed jaxpr of loss and gradient at CFG, taken on
# the parent tree, commit a1cb520).
LING_STEP = "42aaee5b909bb9cc"


def test_ling_step_traces_to_the_same_jaxpr(ref, system, weights, batch,
                                            step_jaxpr_hash,
                                            small_chunks):
    net = system.build_net(CFG, weights, "float32")
    assert step_jaxpr_hash(net, system, *batch) == LING_STEP


# The parameters by attribute path, saved name and shape (tests/conftest.py:
# _param_names_hash): the benchmark's systems/ling.py loads the reference's
# weights by these paths, and a renamed child would show first as a cell
# without a result on the chip. Taken at commit a1cb520 (PR 44).
LING_NAMES = "f256c388516d4f73"


def test_ling_parameters_keep_their_names_and_shapes(system, weights,
                                                     param_names_hash):
    net = system.build_net(CFG, weights, "float32")
    got, listing = param_names_hash(net)
    assert got == LING_NAMES, f"{got}\n{listing}"
