"""The fourth family of ``HybridDecoderLM`` (Brumby-14B's block: Qwen3's
dense block with every attention replaced by a power-retention layer, gated
degree-2 linear attention on grouped heads) and its op
``contrib.power_retention`` (``ops/retention.py``): the op's three forms
(a token-by-token recurrence over the expanded state, the quadratic form,
the chunked ``lax`` form the CPU runs) and the Pallas kernels under
``interpret=True`` against each other, then the model against the plain
float32 reference the benchmark keeps
(``benchmark/suite/reference/brumby.py``, quadratic, which imports nothing
of the program) at a tiny size on seeded weights: logits, loss, every
leaf's gradient, two Adam steps through ``DataParallelTrainer``, the
vocabulary slice, the zeroed-state control and the per-block recomputation.
"""

import importlib.util
import json
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
from mxtpu.ops import retention as R

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SUITE = os.path.join(ROOT, "benchmark", "suite")

# the cell's block at toy widths: 4 query heads on 2 key/value heads of 16,
# two layers; the gates seeded over the cell's own range of half-lives
CFG = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
       "head_dim": 16, "intermediate_size": 128, "rms_norm_eps": 1e-6,
       "vocab_size": 96, "num_hidden_layers": 2, "rope_theta": 1e6,
       "tie_word_embeddings": False, "recompute_blocks": False,
       "retention_eps": 1.0,
       # not 0.02 as in the benchmark's file: at a width of 64 the mixers
       # would hardly reach the logits, and a dropped state would not show
       "initializer_range": 0.1,
       "gate_half_life_min": 64, "gate_half_life_max": 8192}
ADAM = {"lr": 3e-4, "beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8}
# float32 on both sides: what is left is the order of additions (the program
# sums over chunks and a state, the reference over every key of a row)
TOL_LOGITS = 2e-5       # of the largest logit
TOL_LOSS = 1e-5         # relative
TOL_GRAD = 5e-4         # a leaf's gradient, of that leaf's norm
TOL_DELTA = 2e-3        # a leaf's change over two steps, relative
T = 32
CHUNK = 8               # the op's chunk in these tests: four chunks of T


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
    return _load("reference/brumby.py", "t_reference_brumby")


@pytest.fixture(scope="module")
def system():
    return _load("systems/brumby.py", "t_system_brumby")


@pytest.fixture(autouse=True)
def small_chunks(monkeypatch):
    monkeypatch.setattr(R, "CHUNK", CHUNK)


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
    src = open(os.path.join(SUITE, "reference", "brumby.py")).read()
    assert "mxtpu" not in src and "import system" not in src


# ---------------------------------------------------------------------------
# the op
# ---------------------------------------------------------------------------


def recurrent(q, k, v, log_g, eps=R.EPS):
    """The state form, a token at a time: a key/value head carries ``S_t =
    g_t S_{t-1} + phi(k_t) [v_t, 1]^T / D`` over the whole outer product
    ``phi``, and every query head of its group reads it."""
    B, T_, H, D = q.shape
    Hkv = k.shape[2]
    G = H // Hkv

    def phi(x):
        return (x[..., :, None] * x[..., None, :]).reshape(x.shape[:-1]
                                                           + (D * D,))

    def step(S, x):
        q_t, k_t, v_t, lg_t = x          # (B, H, D) (B, Hkv, D) .. (B, Hkv)
        v1 = jnp.concatenate([v_t, jnp.ones_like(v_t[..., :1])], -1)
        S = jnp.exp(lg_t)[..., None, None] * S \
            + phi(k_t)[..., :, None] * v1[..., None, :] / D
        out = jnp.einsum("bjgf,bjfe->bjge", phi(q_t.reshape(B, Hkv, G, D)), S)
        return S, (out[..., :D] / (out[..., D:] + eps)).reshape(B, H * D)

    xs = tuple(jnp.swapaxes(a, 0, 1) for a in (q, k, v, log_g))
    _, y = lax.scan(step, jnp.zeros((B, Hkv, D * D, D + 1), q.dtype), xs)
    return jnp.swapaxes(y, 0, 1)


def quadratic(q, k, v, log_g, eps=R.EPS):
    """The quadratic form in float32, a ``T x T`` map a head: the oracle of
    the op's tests. ``q``: ``(B, T, H, D)``; ``k``, ``v``: ``(B, T, Hkv,
    D)``; ``log_g``: ``(B, T, Hkv)``. Returns ``(B, T, H * D)``."""
    B, T, H, D = q.shape
    G = H // k.shape[2]
    f32 = jnp.float32
    cum = jnp.cumsum(log_g.astype(f32), axis=1)                # (B, T, Hkv)
    decay = cum[:, :, None, :] - cum[:, None, :, :]            # [b, t, s, j]
    mask = jnp.tril(jnp.ones((T, T), bool))[None, :, :, None]
    decay = jnp.where(mask, jnp.exp(jnp.where(mask, decay, 0.0)), 0.0)
    qg = q.astype(f32).reshape(B, T, H // G, G, D)
    score = jnp.einsum("btjgd,bsjd->btsjg", qg, k.astype(f32))
    a = score * score / D * decay[..., None]
    num = jnp.einsum("btsjg,bsjd->btjgd", a, v.astype(f32))
    den = jnp.sum(a, axis=2)[..., None]
    return (num / (den + eps)).reshape(B, T, H * D).astype(q.dtype)


def _operands(T_, D=16, H=4, Hkv=2, B=2, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    # a common component: no row's weights sum to nearly nothing, where
    # the division by (sum + eps) would magnify float32's rounding
    q = 0.5 * jax.random.normal(ks[0], (B, T_, H, D)) + 0.5
    k = 0.5 * jax.random.normal(ks[1], (B, T_, Hkv, D)) + 0.5
    v = jax.random.normal(ks[2], (B, T_, Hkv, D))
    log_g = jax.nn.log_sigmoid(jax.random.normal(ks[3], (B, T_, Hkv)) + 2.0)
    dy = jax.random.normal(ks[4], (B, T_, H * D))
    return (q, k, v, log_g), dy


FORMS = {"recurrent": recurrent, "quadratic": quadratic,
         "chunked": R.power_retention}


@pytest.mark.parametrize("T_", [32, 29])     # whole chunks of 8, and not
@pytest.mark.parametrize("what", ["value", "dq", "dk", "dv", "dlog_g"])
def test_three_forms_of_the_op_agree(T_, what):
    """The recurrence over the expanded state, the quadratic form and the
    chunked form (what ``contrib.power_retention`` runs off the TPU) give
    the same output and the same gradient of every operand."""
    args, dy = _operands(T_)
    with jax.default_matmul_precision("highest"):
        if what == "value":
            got = {n: f(*args) for n, f in FORMS.items()}
        else:
            at = ("dq", "dk", "dv", "dlog_g").index(what)
            got = {n: jax.grad(lambda *a, f=f: jnp.sum(f(*a) * dy),
                               argnums=at)(*args) for n, f in FORMS.items()}
    top = float(jnp.abs(got["quadratic"]).max())
    assert top > 0.1
    # the recurrence adds a token at a time into a float32 state
    for name, tol in (("recurrent", 1e-3), ("chunked", 1e-4)):
        assert float(jnp.abs(got[name] - got["quadratic"]).max()) \
            <= tol * top, name
    assert profiler.get_kernel_path_counts()["retention"]["xla"] >= 1


def test_pallas_kernels_interpreted_against_the_quadratic_form():
    """``retention_fwd`` / ``retention_bwd`` under ``interpret=True`` at
    heads of 128 (the kernels' only width), three query heads a group, four
    chunks of 16, feature blocks 5 a matmul: output, the kept chunk starts'
    shapes and the four gradients. The kernels round their matmul operands
    to bfloat16, so the tolerance is bfloat16's."""
    (q, k, v, log_g), dy = _operands(64, D=128, H=6, Hkv=2, B=1, seed=3)
    B, T_, H, D = q.shape
    flat = (q.reshape(B, T_, -1), k.reshape(B, T_, -1), v.reshape(B, T_, -1))
    y, s0, n0 = R._forward_pallas(*flat, log_g, R.EPS, interpret=True,
                                  chunk=16, r_block=5)
    assert s0.shape == (B, 2, 4, 65 * 128, 128) and s0.dtype == jnp.bfloat16
    assert n0.shape == (B, 2, 4, 128, 128)
    assert not np.asarray(s0[:, :, 0], np.float32).any() \
        and np.asarray(s0[:, :, 1], np.float32).any()
    with jax.default_matmul_precision("highest"):
        want = quadratic(q, k, v, log_g)
        want_g = jax.grad(
            lambda *a: jnp.sum(quadratic(*a) * dy),
            argnums=(0, 1, 2, 3))(q, k, v, log_g)
    assert float(jnp.abs(y - want).max()) <= 0.01 * float(jnp.abs(want).max())
    got_g = R._backward_pallas(*flat, log_g, y, s0, n0, dy, R.EPS,
                               interpret=True, chunk=16, r_block=5)
    for name, a, b in zip(("dq", "dk", "dv", "dlog_g"), got_g, want_g):
        gap = float(jnp.linalg.norm(a.reshape(b.shape) - b)
                    / jnp.linalg.norm(b))
        assert gap <= (0.03 if name == "dlog_g" else 0.01), (name, gap)


def test_the_query_heads_of_a_group_read_one_state():
    """Query head ``h`` reads key/value head ``h // 2``: permuting the heads
    INSIDE a group permutes the outputs and nothing else; moving one
    key/value head's keys moves its group alone."""
    (q, k, v, log_g), _ = _operands(T)
    got = R.power_retention(q, k, v, log_g).reshape(2, T, 4, 16)
    perm = jnp.array([1, 0, 3, 2])
    swapped = R.power_retention(q[:, :, perm], k, v, log_g).reshape(
        2, T, 4, 16)
    np.testing.assert_allclose(np.asarray(swapped),
                               np.asarray(got[:, :, perm]), atol=1e-6)
    moved = jnp.abs(R.power_retention(q, k.at[:, :, 1].add(1.0), v, log_g)
                    .reshape(2, T, 4, 16) - got).max(axis=(0, 1, 3))
    assert not moved[:2].any() and moved[2:].all()
    # causal: a later row moves no earlier one
    later = R.power_retention(q, k, v.at[:, 20:].add(1.0), log_g).reshape(
        2, T, 4, 16)
    assert not np.abs(np.asarray(later - got))[:, :20].any()


def test_retention_stats_counts_the_kept_states(monkeypatch):
    monkeypatch.setattr(R, "CHUNK", 256)        # the shipped constant
    stats = R.retention_stats(8192, 8)
    assert stats == {"chunk": 256, "chunks": 32,
                     "state_bytes_kept": 8 * 32 * (65 * 128 * 128 * 2
                                                   + 128 * 128 * 4)}


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


def test_logits_loss_and_every_gradient_leaf(ref, system, weights, batch):
    """Retention mixers on grouped heads with q/k norm, rotary positions and
    the seeded decay gates, pre-norm, an untied head in float32: logits,
    loss and every leaf's gradient; int8 operands fail the tolerances."""
    x, y = batch
    net = system.build_net(CFG, weights, "float32")
    assert net.head is not None and net.layer_kinds == ("retention",) * 2
    gate = net.blocks[0].retention.gate
    assert gate.weight.shape == (2, 64) and gate.bias.shape == (2,)
    np.testing.assert_allclose(
        np.asarray(jax.nn.sigmoid(weights["layers/gate_b/0"])),
        [2 ** (-1 / 64), 2 ** (-1 / 8192)], rtol=1e-6)
    logits = net(nd.array(x)).data
    assert logits.dtype == jnp.float32
    want = ref.forward(CFG, weights, jnp.asarray(x))
    top = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(logits - want))) <= TOL_LOGITS * top
    low = ref.forward(CFG, weights, jnp.asarray(x), "int8")
    assert float(jnp.max(jnp.abs(low - want))) > 20 * TOL_LOGITS * top

    leaves = system.param_leaves(net)
    assert {leaf for _, leaf in leaves} == set(weights)
    for p, _ in leaves:
        p.data().attach_grad()
    with autograd.record():
        loss = nd.mean(system.system.seq_loss(
            net(nd.array(x)), nd.array(y.astype(np.float32))))
    loss.backward()

    def loss_of(precision):
        return jax.value_and_grad(lambda w: ref.loss_fn(
            CFG, w, jnp.asarray(x), jnp.asarray(y), precision))(weights)

    (want_loss, want_g), (_, low_g) = loss_of(None), loss_of("int8")
    assert abs(float(loss.asscalar()) - float(want_loss)) \
        <= TOL_LOSS * float(want_loss)
    failed = 0
    for p, leaf in leaves:
        norm = float(jnp.linalg.norm(want_g[leaf]))
        assert norm > 0, leaf
        gap = float(jnp.linalg.norm(p.data().grad.data - want_g[leaf])) / norm
        assert gap <= TOL_GRAD, (leaf, gap)
        failed += float(jnp.linalg.norm(low_g[leaf] - want_g[leaf])) \
            > 20 * TOL_GRAD * norm
    assert failed >= len(leaves) - 8, failed


def test_two_adam_steps_through_the_trainer(ref, system, weights, batch):
    """The reference's half-layer-at-a-time gradient and host Adam against
    the trainer's one program, the gate's weight and float32 bias among the
    leaves."""
    x, y = batch
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
    assert set(want["delta_norm"]) == set(w0) == set(want["grad_norm"])
    for leaf, r in want["delta_norm"].items():
        got = float(np.linalg.norm(now[leaf] - w0[leaf]))
        assert abs(got - r) <= TOL_DELTA * max(r, floor), leaf
    assert want["delta_norm"]["layers/gate_b/0"] > 0
    assert losses[1] < losses[0]
    low = ref.train_steps(CFG, ref.make_weights(CFG, 7, "float32"), steps,
                          ADAM, "float32", row_block=8, precision="int8")
    worst = max(abs(low["delta_norm"][k] - r) / max(r, floor)
                for k, r in want["delta_norm"].items())
    assert worst > TOL_DELTA


def test_a_vocabulary_slice_is_the_slice_of_the_uncut_models_logits(
        ref, system, weights, batch):
    """Rows 0..47 of both tables: the cut model's logits are the first 48
    columns of the uncut model's wherever the ids lie in the slice."""
    x = batch[0] % 48
    cut = dict(CFG, vocab_size=48)
    w_cut = {k: (v[:48] if k in ("embed", "head") else v)
             for k, v in weights.items()}
    whole = system.build_net(CFG, weights, "float32")(nd.array(x)).data
    part = system.build_net(cut, w_cut, "float32")(nd.array(x)).data
    assert part.shape == (8, T, 48)
    np.testing.assert_allclose(np.asarray(part), np.asarray(whole[..., :48]),
                               rtol=1e-5, atol=1e-6)


def test_zeroing_the_carried_state_moves_the_loss_past_the_cells_limit(
        ref, weights, batch, monkeypatch):
    """At the seeded gates (half-lives of 64 tokens and more) a row's state
    is most of what it sees: a program whose chunks start from nothing
    reads a loss that the cell's ``loss_gap`` refuses."""
    limits = json.load(open(os.path.join(
        SUITE, "cells", "brumby_train_t8192.json")))["limits"]
    x, y = jnp.asarray(batch[0]), jnp.asarray(batch[1])
    monkeypatch.setattr(ref, "ROW_BLOCK", CHUNK)
    whole = float(ref.loss_fn(CFG, weights, x, y))
    local = float(ref.loss_fn(CFG, weights, x, y, carry=False))
    assert abs(local - whole) / whole > 2 * limits["loss_gap"]


@pytest.mark.parametrize("layers", [1, 2, 3])
def test_recomputing_a_block_at_a_time_gives_the_plain_steps_gradients(
        ref, system, batch, layers):
    """``remat=True``: every block but the last under ``jax.checkpoint``
    where the step is traced (the last one's backward comes first, so
    recomputing it would free nothing); the first step's loss and gradient
    are the plain step's, and the counter says what was recomputed."""
    x, y = batch
    cfg = dict(CFG, num_hidden_layers=layers)
    weights = ref.make_weights(cfg, 7, "float32")
    got = {}
    for remat in (False, True):
        profiler.reset_remat_stats()
        net = system.build_net(dict(cfg, recompute_blocks=remat), weights,
                               "float32")
        trainer = system.Trainer(net, ADAM)
        loss = float(trainer.step(*trainer.place(x, y)))
        got[remat] = (loss, trainer.first_gradient_norm(),
                      trainer.dpt.lowered().as_text(debug_info=True),
                      profiler.get_remat_stats())
    plain, again = got[False], got[True]
    assert again[0] == pytest.approx(plain[0], rel=1e-6)
    assert again[1] == pytest.approx(plain[1], rel=1e-5)
    assert "rematted_computation/block" not in plain[2]
    for i in range(layers):
        assert (f"rematted_computation/block{i}/" in again[2]) \
            == (i < layers - 1), i
    if layers == 1:     # nothing to free: the plain program
        assert again[2] == plain[2]
    none = {"blocks": 0, "recomputed": 0, "kinds": {}}
    assert plain[3] == none
    assert again[3] == {"blocks": layers, "recomputed": layers - 1,
                        "kinds": {"retention": layers - 1} if layers > 1
                        else {}}
    profiler.reset_remat_stats()
    assert profiler.get_remat_stats() == none


def test_recomputation_leaves_the_tape_alone_and_refuses_other_kinds(
        system, weights, batch):
    from mxtpu.gluon.model_zoo.hybrid_decoder import HybridDecoderLM
    x, _ = batch
    # on the imperative tape a block runs as it is
    profiler.reset_remat_stats()
    net = system.build_net(dict(CFG, recompute_blocks=True), weights,
                           "float32")
    with autograd.record():
        out = net(nd.array(x))
    assert out.shape == (8, T, 96)
    assert profiler.get_remat_stats() == {"blocks": 0, "recomputed": 0,
                                          "kinds": {}}
    # a mamba layer whose memory a gmu reads hands it on: refused, by layer
    with pytest.raises(ValueError, match=r"hand nothing on.*Not layer 0 "
                       r"\(mamba, mlp\) hands on memory; layer 2 \(gmu, "
                       r"mlp\) reads memory$"):
        HybridDecoderLM(32, ["mamba", "retention", "gmu"], 64, 128, 4, 2,
                        remat=True)
    # beside layers that read nothing it hands nothing on and may be
    HybridDecoderLM(32, ["mamba", "retention"], 64, 128, 4, 2, remat=True)


def test_step_carries_scopes_and_kernel_names(ref, system, weights, batch,
                                              monkeypatch):
    x, y = batch
    net = system.build_net(CFG, weights, "float32")
    trainer = system.Trainer(net, ADAM)
    trainer.step(*trainer.place(x, y))
    text = trainer.dpt.lowered().as_text(debug_info=True)
    for scope in ("block0/retention/qkv", "block0/retention/qk_norm",
                  "block1/retention/rope", "block1/retention/gate",
                  "block0/retention/scan", "block1/retention/out_proj",
                  "block0/mlp/gate_up", "ln_f", "head", "loss"):
        assert scope in text, scope
    # on the TPU platform at heads of 128 and whole chunks: the launches by
    # name, forward and (under grad) backward
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(R, "CHUNK", 256)
    wide = dict(CFG, hidden_size=256, head_dim=128, num_attention_heads=4,
                num_key_value_heads=2, intermediate_size=256, vocab_size=128)
    net2 = system.build_net(wide, ref.make_weights(wide, 1, "bfloat16"),
                            "bfloat16")
    params = [p for p, _ in system.param_leaves(net2)]

    def loss(values, tokens):
        for p, v in zip(params, values):
            p._data._data = v
        with autograd.pause(train_mode=True):
            return jnp.sum(net2(nd.NDArray(tokens)).data)

    values = [p.data().data for p in params]
    try:
        lowered = jax.jit(jax.grad(loss)).trace(
            values, jnp.zeros((1, 256), jnp.int32)).lower(
            lowering_platforms=("tpu",))
    finally:
        for p, v in zip(params, values):
            p._data._data = v
    assert set(re.findall(r'kernel_name = "([^"]+)"', lowered.as_text())) \
        == {"retention_fwd", "retention_bwd"}
    assert profiler.get_kernel_path_counts()["retention"]["pallas"] >= 2


def test_decoding_raises_and_names_the_retention_state(system, weights):
    from mxtpu.gluon.model_zoo.hybrid_decoder import KINDS
    assert "retention" in KINDS
    net = system.build_net(CFG, weights, "float32")
    with pytest.raises(NotImplementedError, match="trains only") as err:
        net.generate(nd.array(np.zeros((1, 4))), 4)
    assert "8256 x 129" in str(err.value)
    with pytest.raises(NotImplementedError, match="trains only"):
        net.serving_step()


# The mixer protocol and the table of kinds (PR 45) are shared by every
# family: this family's step has to trace to the program it traced to before
# them (hash of the printed jaxpr of loss and gradient at dict(CFG, recompute_blocks=True), taken on
# the parent tree, commit a1cb520).
BRUMBY_STEP = "8311347cb39cd7fc"


def test_brumby_step_traces_to_the_same_jaxpr(ref, system, weights, batch,
                                              step_jaxpr_hash):
    net = system.build_net(dict(CFG, recompute_blocks=True), weights, "float32")
    assert step_jaxpr_hash(net, system, *batch) == BRUMBY_STEP


# The parameters by attribute path, saved name and shape (tests/conftest.py:
# _param_names_hash): the benchmark's systems/brumby.py loads the reference's
# weights by these paths, and a renamed child would show first as a cell
# without a result on the chip. Taken at commit a1cb520 (PR 44).
BRUMBY_NAMES = "5cc380559231316a"


def test_brumby_parameters_keep_their_names_and_shapes(system, weights,
                                                       param_names_hash):
    net = system.build_net(dict(CFG, recompute_blocks=True), weights, "float32")
    got, listing = param_names_hash(net)
    assert got == BRUMBY_NAMES, f"{got}\n{listing}"
