"""The sixth family of ``HybridDecoderLM`` (JoyAI-LLM-Flash's block, which is
DeepSeek-V3's: latent attention with a query latent in every layer, a sparse
MLP under a sigmoid router, and a multi-token-prediction block trained beside
the head through the same two tables) against the plain float32 reference
the benchmark keeps (``benchmark/suite/reference/joyai.py``, which imports
nothing of the program), at a tiny size on seeded weights: the ``mla`` kind's
three arguments, ``gluon.loss.NextTokenLoss`` against a hand-written
two-term loss, the tables' gradients as sums of both uses with three planted
faults, the shares of a layer against the uncut reference, logits, both
losses, every leaf's gradient and two Adam steps through
``DataParallelTrainer``, the scopes and the ``mtp`` counter."""

import hashlib
import importlib.util
import math
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxtpu import autograd, nd, profiler
from mxtpu.gluon.loss import NextTokenLoss

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SUITE = os.path.join(ROOT, "benchmark", "suite")

# the cell's block at toy widths: 4 heads, a query latent of 40, a key/value
# latent of 24 with 16 + 8 wide keys, 16 experts of which 2 are held, 2 a
# token; a dense layer, a sparse one and the prediction block: 2 + 1 layers
CFG = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
       "head_dim": 8, "q_lora_rank": 40, "kv_lora_rank": 24,
       "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
       "rope_theta": 32e6, "rope_interleave": True, "intermediate_size": 96,
       "moe_intermediate_size": 32, "n_shared_experts": 1,
       "num_shared_experts": 1, "published_num_experts": 16,
       "n_routed_experts": 2, "num_experts_per_tok": 2, "n_group": 1,
       "topk_group": 1, "routed_scaling_factor": 2.5, "held_experts": [0, 1],
       "rms_norm_eps": 1e-6, "vocab_size": 96, "num_hidden_layers": 2,
       "layer_types": ["mla", "mla"], "mlp_layer_types": ["dense", "sparse"],
       "tie_word_embeddings": False, "num_nextn_predict_layers": 1,
       "mtp_loss_weight": 0.3,
       # not 0.02 and 0 as in the benchmark's file: at a width of 64 the
       # mixers would hardly reach the logits, and a program that ignores
       # the selection bias must differ
       "initializer_range": 0.1, "router_bias_init_std": 0.1,
       "router_bias_update_rate": 0.03}
ADAM = {"lr": 3e-4, "beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8}
# float32 on both sides: what is left is the order of additions
TOL_LOGITS = 2e-5       # of the largest logit
TOL_LOSS = 1e-5         # relative
TOL_GRAD = 5e-4         # a leaf's gradient, of that leaf's norm
TOL_DELTA = 2e-3        # a leaf's change over two steps, relative
T, L = 32, 2


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
    return _load("reference/joyai.py", "t_reference_joyai")


@pytest.fixture(scope="module")
def system():
    return _load("systems/joyai.py", "t_system_joyai")


@pytest.fixture(scope="module")
def batch():
    # 8 rows: the test session has 8 virtual devices and the trainer
    # spreads the batch over all of them
    seq = np.random.RandomState(0).randint(0, 96, (8, T + 1)).astype(np.int32)
    return seq[:, :-1], seq[:, 1:]


@pytest.fixture(scope="module")
def weights(ref):
    return ref.make_weights(CFG, 7, "float32")


def _leaves():
    """The reference's trained leaves at ``CFG`` (named without it, so that
    the cases below can be listed at collection)."""
    layer = ["ln1_g", "ln2_g", "qa_w", "qa_norm_g", "qb_w", "kva_w",
             "kv_norm_g", "kvb_w", "o_w"]
    sparse = ["router_w", "experts_gate_up_w", "experts_down_w",
              "shared_gate_up_w", "shared_down_w"]
    out = ["embed", "head", "ln_f_g", "mtp_enorm_g", "mtp_hnorm_g",
           "mtp_eh_w", "mtp_norm_g"]
    for i, mlp in enumerate((["gate_up_w", "down_w"], sparse, sparse)):
        out += [f"layers/{leaf}/{i}" for leaf in layer + mlp]
    return out


LEAVES = _leaves()


def test_reference_imports_nothing_of_the_program(ref, weights):
    src = open(os.path.join(SUITE, "reference", "joyai.py")).read()
    assert "mxtpu" not in src and "import system" not in src
    assert "mtp_loss" in src and "jnp.roll(targets, -1" in src
    assert set(ref.trained(weights)) == set(LEAVES)


# ---------------------------------------------------------------------------
# the mla kind's three arguments
# ---------------------------------------------------------------------------


def _mixer(**kw):
    from mxtpu.gluon.model_zoo.hybrid_decoder import LatentAttention
    att = LatentAttention(64, 4, 24, 16, 8, 16, rope_theta=32e6,
                          interleave=True, norm_eps=1e-6, **kw)
    att.initialize()
    return att


def test_query_latent_against_the_expanded_quadratic_form(ref, weights):
    """A query latent with its norm, no q/k norm, no gate: the program's
    layer on the reference's weights."""
    lp = ref.layer_weights(weights, 1)
    att = _mixer(q_latent_dim=40, qk_norm=False, head_gate=False)
    assert att.q_proj is None and att.gate_proj is None \
        and att.q_norm is None and att.k_norm is None
    for p, leaf in ((att.qa_proj.weight, "qa_w"),
                    (att.qa_norm.gamma, "qa_norm_g"),
                    (att.qb_proj.weight, "qb_w"),
                    (att.kva_proj.weight, "kva_w"),
                    (att.kv_norm.gamma, "kv_norm_g"),
                    (att.kvb_proj.weight, "kvb_w"),
                    (att.out_proj.weight, "o_w")):
        p.set_data(nd.NDArray(lp[leaf]))
    x = jnp.asarray(np.random.RandomState(4).randn(2, T, 64), jnp.float32)
    want = ref.mla_sublayer(CFG, lp, x)
    got = att(nd.NDArray(x), {}).data
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=1e-5 * float(jnp.abs(want).max()))


def test_the_defaults_are_todays_layer_bit_for_bit():
    """Neither argument given is each at its default, and the defaults build
    the parameters the kind had before it took them (PR 41's names, which
    ``systems/ling.py`` walks)."""
    from mxtpu import rng
    rng.seed(3)
    plain = _mixer()
    rng.seed(3)
    spelt = _mixer(q_latent_dim=0, qk_norm=True, head_gate=True)
    names = sorted(n.split("_", 1)[1] for n in plain.collect_params())
    assert names == sorted(n.split("_", 1)[1] for n in spelt.collect_params())
    assert [n for n in plain._children] == [
        "q_proj", "kva_proj", "kv_norm", "kvb_proj", "gate_proj", "out_proj"]
    assert plain.q_norm.shape == (24,) and plain.k_norm.shape == (16,)
    x = nd.NDArray(jnp.asarray(np.random.RandomState(5).randn(2, T, 64),
                               jnp.float32))
    assert np.array_equal(np.asarray(plain(x, {}).data),
                          np.asarray(spelt(x, {}).data))


@pytest.mark.parametrize("qk_norm,head_gate", [(True, False), (False, True)])
def test_each_argument_alone_takes_out_what_it_names(qk_norm, head_gate):
    """The norms and the gate go one at a time: a layer with one of them
    equals the full layer whose other is made the identity (gains of one
    ARE a norm, so the norm is compared through the op's own arguments)."""
    from mxtpu.gluon.model_zoo.hybrid_decoder import latent_attention
    r = np.random.RandomState(6)
    q, kv = (jnp.asarray(r.randn(2, T, 4, n), jnp.float32) for n in (24, 32))
    k_rope = jnp.asarray(r.randn(2, T, 8), jnp.float32)
    gains = (jnp.asarray(1 + 0.1 * r.randn(24), jnp.float32),
             jnp.asarray(1 + 0.1 * r.randn(16), jnp.float32))
    gate = jnp.asarray(r.randn(2, T, 4), jnp.float32)
    got = latent_attention(q, kv, k_rope, *(gains if qk_norm else (None,) * 2),
                           gate if head_gate else None, nope_dim=16,
                           rope_theta=32e6)
    full = latent_attention(q, kv, k_rope, *gains, gate, nope_dim=16,
                            rope_theta=32e6)
    assert float(jnp.abs(got - full).max()) > 1e-3
    if head_gate:       # the norms by hand, then the op without them
        from mxtpu.ops.nn import rms_norm
        normed = latent_attention(
            rms_norm(q, gains[0], 1e-6),
            jnp.concatenate([rms_norm(kv[..., :16], gains[1], 1e-6),
                             kv[..., 16:]], axis=-1),
            k_rope, None, None, gate, nope_dim=16, rope_theta=32e6)
        np.testing.assert_allclose(np.asarray(normed), np.asarray(full),
                                   rtol=1e-5, atol=1e-6)
    else:               # the gate by hand, after the op without it
        gated = got.reshape(2, T, 4, 16) * jax.nn.sigmoid(gate)[..., None]
        np.testing.assert_allclose(np.asarray(gated.reshape(2, T, 64)),
                                   np.asarray(full), rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# the loss block
# ---------------------------------------------------------------------------


def _logits(seed, shape=(3, 8, 11)):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape), jnp.float32)


def _ce(logits, targets):
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jax.nn.logsumexp(logits, axis=-1) - picked


def test_the_loss_on_one_array_is_seq_loss(system):
    """Given one array the block IS the cross entropy of the flattened
    rows, as the other cells' ``system.seq_loss`` computes it: bit for
    bit."""
    import system as base
    logits = nd.NDArray(_logits(0))
    y = nd.array(np.random.RandomState(1).randint(0, 11, (3, 8))
                 .astype(np.float32))
    got = NextTokenLoss()(logits, y)
    assert got.shape == (24,)
    assert np.array_equal(np.asarray(got.data),
                          np.asarray(base.seq_loss(logits, y).data))


@pytest.mark.parametrize("weight", [0.3, 1.0])
@pytest.mark.parametrize("depths", [1, 2])
def test_the_loss_against_two_terms_written_by_hand(weight, depths):
    """Roll, mask, weight: depth k's targets are the labels rolled left by
    k, its last k positions are out of its mean."""
    y = np.random.RandomState(2).randint(0, 11, (3, 8))
    pred = [_logits(10 + k) for k in range(depths + 1)]
    want = float(jnp.mean(_ce(pred[0], jnp.asarray(y))))
    for k in range(1, depths + 1):
        rolled = jnp.asarray(np.roll(y, -k, axis=1))
        want += weight * float(jnp.mean(_ce(pred[k], rolled)[:, :8 - k]))
    got = NextTokenLoss(weight=weight)(
        tuple(nd.NDArray(p) for p in pred), nd.array(y.astype(np.float32)))
    assert got.shape == ()
    assert abs(float(got.asscalar()) - want) <= 1e-6 * want
    # the masked tail carries no gradient, whatever its logits are
    wild = pred[1].at[:, -1].set(50.0)
    again = NextTokenLoss(weight=weight)(
        (nd.NDArray(pred[0]), nd.NDArray(wild))
        + tuple(nd.NDArray(p) for p in pred[2:]),
        nd.array(y.astype(np.float32)))
    assert float(again.asscalar()) == float(got.asscalar())


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


def test_train_mode_returns_two_logits_predict_mode_one(ref, system, weights,
                                                        batch, monkeypatch):
    x, _ = batch
    net = system.build_net(CFG, weights, "float32")
    assert net.mtp0 is not None and net.layer_kinds == ("mla", "mla") \
        and net.mlp_kinds == ("mlp", "moe")
    assert float(jnp.abs(weights["layers/router_b/2"]).max()) > 0.05
    want, want2 = ref.forward(CFG, weights, jnp.asarray(x), mtp=True)
    top = float(jnp.max(jnp.abs(want)))
    with autograd.pause(train_mode=True):
        out = net(nd.array(x))
    assert isinstance(out, tuple) and len(out) == 2
    for got, w in zip(out, (want, want2)):
        assert got.shape == (8, T, 96) and got.data.dtype == jnp.float32
        assert float(jnp.max(jnp.abs(got.data - w))) <= TOL_LOGITS * top
    low = ref.forward(CFG, weights, jnp.asarray(x), "int8", mtp=True)
    assert all(float(jnp.max(jnp.abs(a - b))) > 20 * TOL_LOGITS * top
               for a, b in zip(low, (want, want2)))
    # outside training the block is not run at all
    profiler.reset_launch_stats("mtp")

    def boom(*args):
        raise AssertionError("the prediction block ran in predict mode")

    monkeypatch.setattr(net.mtp0, "forward", boom)
    one = net(nd.array(x))
    assert isinstance(one, nd.NDArray) and one.shape == (8, T, 96)
    assert profiler.get_launch_stats("mtp")["launches"] == 0


@pytest.fixture(scope="module")
def gradients(ref, system, weights, batch):
    """``{leaf: (the program's gradient, the reference's)}`` of the two-term
    loss on the imperative tape, with both sides' losses."""
    x, y = batch
    net = system.build_net(CFG, weights, "float32")
    leaves = [(p, leaf) for p, leaf in system.param_leaves(net)
              if p.grad_req != "null"]
    for p, _ in leaves:
        p.data().attach_grad()
    with autograd.record():
        loss = NextTokenLoss(CFG["mtp_loss_weight"])(
            net(nd.array(x)), nd.array(y.astype(np.float32)))
    loss.backward()
    want_loss, want = jax.value_and_grad(lambda w: ref.loss_fn(
        CFG, w, jnp.asarray(x), jnp.asarray(y)))(weights)
    return {"loss": (float(loss.asscalar()), float(want_loss)),
            "leaves": {leaf: (p.data().grad.data, want[leaf])
                       for p, leaf in leaves}}


def test_both_losses(ref, weights, batch, gradients):
    x, y = batch
    got, want = gradients["loss"]
    assert abs(got - want) <= TOL_LOSS * want
    main, further = ref.losses(CFG, weights, jnp.asarray(x), jnp.asarray(y))
    assert abs(float(main) + 0.3 * float(further) - want) <= 1e-6 * want
    assert float(further) > 1.0     # a term, not a rounding
    assert set(gradients["leaves"]) == set(LEAVES)


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_gradient_leaf(leaf, gradients):
    got, want = gradients["leaves"][leaf]
    norm = float(jnp.linalg.norm(want))
    assert norm > 0
    assert float(jnp.linalg.norm(got - want)) / norm <= TOL_GRAD


def _table_gradients(net, system, batch, weight=0.3):
    x, y = batch
    tables = {"embed": net.embedding.weight, "head": net.head.weight}
    for p in tables.values():
        p.data().attach_grad()
    with autograd.record():
        loss = NextTokenLoss(weight)(net(nd.array(x)),
                                     nd.array(y.astype(np.float32)))
    loss.backward()
    return {k: p.data().grad.data for k, p in tables.items()}


@pytest.fixture(scope="module")
def table_terms(ref, weights, batch):
    """The reference's gradient of each table from each of its uses: the
    head's loss alone, and lambda times the prediction block's alone."""
    x, y = (jnp.asarray(a) for a in batch)
    tables = {k: weights[k] for k in ("embed", "head")}

    def term(which):
        return jax.grad(lambda t: ref.losses(
            CFG, {**weights, **t}, x, y)[which])(tables)

    main, further = term(0), term(1)
    return main, {k: 0.3 * v for k, v in further.items()}


@pytest.mark.parametrize("table", ["embed", "head"])
def test_a_tables_gradient_is_the_sum_of_both_uses(table, system, weights,
                                                   batch, table_terms):
    main, further = table_terms
    got = _table_gradients(system.build_net(CFG, weights, "float32"), system,
                           batch)[table]
    want = main[table] + further[table]
    norm = float(jnp.linalg.norm(want))
    assert float(jnp.linalg.norm(got - want)) / norm <= TOL_GRAD
    # each use is a real share of it: neither alone passes
    for part in (main[table], further[table]):
        assert float(jnp.linalg.norm(got - part)) / norm > 100 * TOL_GRAD


def _cut_the_embedding(net, monkeypatch):
    run = net.mtp0.forward
    monkeypatch.setattr(net.mtp0, "forward", lambda tokens, g, emb, logits:
                        run(tokens, g, lambda t: nd.NDArray(emb(t).data),
                            logits))
    return 0.3


def _cut_the_head(net, monkeypatch):
    run = net.mtp0.forward

    def logits(h):      # the head's numbers, off the tape
        w = nd.NDArray(net.head.weight.data().data)
        return nd.dot(h.reshape((-1, 64)), w, transpose_b=True).reshape(
            (8, T, 96))

    monkeypatch.setattr(net.mtp0, "forward", lambda tokens, g, emb, _:
                        run(tokens, g, emb, logits))
    return 0.3


def _drop_the_second_loss(net, monkeypatch):
    return 0.0


@pytest.mark.parametrize("fault,moved", [
    (_cut_the_embedding, ("embed",)), (_cut_the_head, ("head",)),
    (_drop_the_second_loss, ("embed", "head"))],
    ids=["embedding_cut_off", "head_cut_off", "second_loss_dropped"])
def test_a_planted_fault_fails_the_tables_gradients(fault, moved, system,
                                                    weights, batch,
                                                    table_terms, monkeypatch):
    """The two faults of ISSUE 46 (and the head's twin): the block's use of a
    table cut off from its gradient, or the second loss dropped, leaves that
    table's gradient short by the block's share, far outside the tolerance;
    a table the fault does not touch still passes."""
    main, further = table_terms
    net = system.build_net(CFG, weights, "float32")
    got = _table_gradients(net, system, batch, fault(net, monkeypatch))
    for table in ("embed", "head"):
        want = main[table] + further[table]
        gap = float(jnp.linalg.norm(got[table] - want)) \
            / float(jnp.linalg.norm(want))
        assert (gap > 100 * TOL_GRAD) == (table in moved), (table, gap)


def test_the_shares_add_up_to_the_uncut_layer(ref):
    """The guide's share test: 16 experts, 2 a token, over 8 chips of 2. The
    routed terms of all 8 shares, every share routing over all 16, with the
    shared expert, which every chip computes alike, counted once, add up to
    the uncut reference's MLP sub-layer."""
    from mxtpu.parallel.moe import SparseExperts
    uncut = dict(CFG, n_routed_experts=16, held_experts=list(range(16)))
    w = ref.make_weights(uncut, 11, "float32")
    lp = ref.layer_weights(w, 1)
    x = jnp.asarray(np.random.RandomState(2).randn(2, T, 64), jnp.float32)
    rows = x.reshape(-1, 64)
    whole = ref.experts(ref.sizes(uncut), lp, rows, None)[0] + ref.swiglu(
        rows, lp["shared_gate_up_w"], lp["shared_down_w"], None)
    total, pairs = 0.0, 0.0
    for share in range(8):
        held = [2 * share, 2 * share + 1]
        blk = SparseExperts(64, 32, 16, 2, held=held, shared_ffn_units=32,
                            routed_scale=2.5)
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


@pytest.fixture(scope="module")
def two_steps(ref, system, weights, batch):
    """Two Adam steps through ``DataParallelTrainer`` beside the reference's
    half-layer-at-a-time gradient and host Adam."""
    x, y = batch
    profiler.reset_kernel_path_counts()
    profiler.reset_launch_stats("mtp")
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
    return {"net": net, "trainer": trainer, "losses": losses,
            "grad_norm": grad_norm, "want": want,
            "delta": {k: float(np.linalg.norm(now[k] - w0[k])) for k in w0},
            "paths": system.kernel_path_counts(),
            # before ``lowered()`` traces the step once more
            "mtp_row": profiler.get_launch_stats("mtp"),
            "text": trainer.dpt.lowered().as_text(debug_info=True)}


def test_two_adam_steps_losses_gradient_and_states(two_steps):
    got, want = two_steps, two_steps["want"]
    for a, b in zip(got["losses"], want["loss"]):
        assert abs(a - b) <= TOL_LOSS * b
    assert got["losses"][1] < got["losses"][0]
    whole = math.sqrt(sum(v * v for v in want["grad_norm"].values()))
    assert abs(got["grad_norm"] - whole) <= TOL_GRAD * whole
    # embed and head ONCE each, and both expert layers' biases as states
    assert set(want["grad_norm"]) == set(LEAVES)
    assert set(want["delta_norm"]) == set(got["delta"]) \
        == set(LEAVES) | set(want["states"])
    assert sorted(want["states"]) == ["layers/router_b/1", "layers/router_b/2"]
    net = got["net"]
    for i, blk in ((1, net.blocks[1]), (L, getattr(net.mtp0, f"block{L}"))):
        np.testing.assert_allclose(
            blk.moe.select_bias.data().asnumpy(),
            want["states"][f"layers/router_b/{i}"], rtol=0, atol=1e-6)
    # the trainer hands both expert layers' counts to the readers
    import moe as readers
    assert len(readers.STEP_COUNTS) == 2 and len(readers.STEP_COUNTS[-1]) == 2
    rows = profiler.get_moe_stats(net)
    assert len(rows) == 2 and all(r["passes"] == 1 for r in rows)
    assert got["paths"]["flash"]["xla"] > 0 \
        and got["paths"]["flash"]["pallas"] == 0
    assert "mtp" not in got["paths"]        # a counter's row, not a kernel


@pytest.mark.parametrize("leaf", LEAVES)
def test_two_adam_steps_every_leafs_change(leaf, two_steps):
    want = two_steps["want"]["delta_norm"]
    floor = np.median(list(want.values()))
    assert abs(two_steps["delta"][leaf] - want[leaf]) \
        <= TOL_DELTA * max(want[leaf], floor)


@pytest.mark.parametrize("scope", [
    "block0/mla/proj/qa_proj", "block0/mla/proj/qa_norm",
    "block1/mla/proj/qb_proj", "block0/mla/rope", "block1/mla/attn",
    "block0/mla/out", "block0/mlp/gate_up", "block1/moe/route",
    "block1/moe/experts", "mtp0/embed/embedding", "mtp0/embed/enorm",
    "mtp0/proj/hnorm", "mtp0/proj/eh_proj", f"mtp0/block{L}/mla/proj",
    f"mtp0/block{L}/mla/attn", f"mtp0/block{L}/moe/experts",
    f"mtp0/block{L}/moe/balance", "mtp0/head/norm", "mtp0/head/head",
    "NextTokenLoss/main", "NextTokenLoss/mtp", "ln_f", "optimizer"])
def test_the_step_carries_the_scopes(scope, two_steps):
    text = two_steps["text"]
    assert scope in text
    assert f"block{L}/mla" not in text.replace(f"mtp0/block{L}", "") \
        and "block0/moe" not in text and "q_norm" not in text \
        and "gate_proj" not in text


def test_the_benchmarks_readers_name_the_blocks_scopes():
    """``mtp0/block<L>/...`` counts as a layer for the readers that look for
    ``block<i>``, and the block's own readers tell its head and loss from
    the rest."""
    if SUITE not in sys.path:
        sys.path.insert(0, SUITE)
    import joyai
    import ling
    import moe
    import scopes
    base = "jit(step)/transpose(jvp(HybridDecoderLM))/"
    proj = base + "mtp0/block5/mla/proj/qa_proj/dot_general:"
    assert scopes.layer_of(proj) == "blocks" and ling.scope_of(proj) == "mla"
    assert scopes.layer_of(base + "mtp0/head/head/dot_general:") \
        == "head_loss"
    assert moe.scope_of(
        base + "mtp0/block5/moe/experts/moe_gmm/pallas_call:") == "experts"
    for name, want in (
            ("mtp0/block5/moe/experts/moe_gmm/pallas_call:", "mtp"),
            ("mtp0/proj/eh_proj/dot_general:", "mtp"),
            ("mtp0/head/head/dot_general:", "mtp_head_loss"),
            ("block4/mla/proj/qa_proj/dot_general:", None),
            ("head/dot_general:", None)):
        assert joyai.scope_of(base + name) == want, name
    loss = "jit(step)/jvp(loss)/NextTokenLoss/"
    assert joyai.scope_of(loss + "mtp/rows/reduce_sum:") == "mtp_head_loss"
    assert joyai.scope_of(loss + "main/rows/reduce_sum:") is None


def test_the_mtp_counters_row(two_steps):
    row = two_steps["mtp_row"]
    assert row["launches"] == 1 and row["depth"] == 1
    assert row["positions"] == 8 * (T - 1)
    assert row["logits_bytes"] == 8 * T * 96 * 4
    import joyai as readers
    assert readers.MTP_STATS == row
    assert readers.logits_gb({"config": CFG}) == row["logits_bytes"] / 1e9
    assert readers.logits_gb({"config": {}}) is None
    profiler.reset_launch_stats("mtp")
    assert profiler.get_launch_stats("mtp") == dict.fromkeys(row, 0)
    assert "mtp" not in profiler.get_kernel_path_counts()


def test_decoding_raises_and_names_the_drafter(system, weights):
    from mxtpu.gluon.model_zoo.hybrid_decoder import HybridDecoderLM
    net = system.build_net(CFG, weights, "float32")
    with pytest.raises(NotImplementedError, match="trains only") as err:
        net.generate(nd.array(np.zeros((1, 4))), 4)
    said = str(err.value)
    assert "mla: a latent row and one rotary key a token" in said \
        and "nothing of the query side" in said
    assert "mtp0" in said and "could draft" in said and "M7" in said
    with pytest.raises(NotImplementedError, match="trains only"):
        net.serving_step()
    spec = dict(units=64, ffn_units=96, num_heads=4, num_kv_heads=4,
                mla=dict(latent_dim=24, nope_dim=16, rope_dim=8, v_dim=16))
    plain = HybridDecoderLM(96, ["mla"], **spec)
    assert plain.mtp0 is None
    with pytest.raises(NotImplementedError) as err:
        plain.generate(nd.array(np.zeros((1, 4))), 4)
    assert "mtp0" not in str(err.value)
    with pytest.raises(ValueError, match="mtp_layers 2"):
        HybridDecoderLM(96, ["mla"], mtp_layers=2, **spec)
    with pytest.raises(ValueError, match="hand-over"):
        HybridDecoderLM(96, ["attn_full"], mtp_layers=1, **spec)


# This family's step pinned as the other five are (hash of the printed jaxpr
# of the two-term loss and its gradient at CFG, taken on this PR's tree):
# a later change to the stack that means to leave the cell alone leaves it.
JOYAI_STEP = "3cf0d1104c5c31cd"


def test_joyai_step_traces_to_the_same_jaxpr(system, weights, batch):
    x, y = batch
    net = system.build_net(CFG, weights, "float32")
    handles = [p for p, _ in system.param_leaves(net)]
    saved = [p._data._data for p in handles]
    loss_fn = NextTokenLoss(CFG["mtp_loss_weight"])

    def loss_of(ps):
        try:
            for p, v in zip(handles, ps):
                p._data._data = v
            with autograd.pause(train_mode=True):
                loss = loss_fn(net(nd.NDArray(jnp.asarray(x))),
                               nd.NDArray(jnp.asarray(y, jnp.float32)))
            return jnp.mean(loss.data)
        finally:
            for p, v in zip(handles, saved):
                p._data._data = v

    text = str(jax.make_jaxpr(jax.value_and_grad(loss_of))(saved))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == JOYAI_STEP


# The parameters by attribute path, saved name and shape (tests/conftest.py:
# _param_names_hash): ``systems/joyai.py`` loads the reference's weights by
# these paths.
JOYAI_NAMES = "f6b327ab05e41f29"


def test_joyai_parameters_keep_their_names_and_shapes(system, weights,
                                                      param_names_hash):
    net = system.build_net(CFG, weights, "float32")
    got, listing = param_names_hash(net)
    assert got == JOYAI_NAMES, f"{got}\n{listing}"
    assert "mtp0/block2/mla/qa_proj/weight" in listing \
        and "mtp0/eh_proj/weight" in listing
