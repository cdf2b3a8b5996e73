"""The seventh family of ``HybridDecoderLM`` (the Jamba family's decoder as
AI21-Jamba2-3B configures it: Mamba-1 mixers with an RMSNorm on each of
``dt``, ``B`` and ``C`` around grouped-query attention layers without
positions, a dense SwiGLU after every mixer, a tied head with float32
logits, every block but the last recomputed) against the plain float32
reference the benchmark keeps (``benchmark/suite/reference/jamba.py``, which
imports nothing of the program or of the other references) at a tiny size on
seeded weights: the mixer alone, logits, loss, every leaf's gradient, two
Adam steps through ``DataParallelTrainer``; the hand-over rule (a mixer
hands on only what a later layer of its stack reads), recomputation of the
kinds that then hand nothing on, bit for bit; the two planted faults; the
cut (layers 0-13 of the 28-layer model ARE the model the cell trains).
"""

import importlib.util
import math
import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxtpu import autograd, nd, profiler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SUITE = os.path.join(ROOT, "benchmark", "suite")

# the cell's block at toy widths: 4 query heads on ONE key/value head of 16,
# d_inner 128 with 8 states and a dt rank of 6; four layers, attention third
KINDS = ["mamba", "mamba", "attn_full", "mamba"]
CFG = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 1,
       "intermediate_size": 96, "rms_norm_eps": 1e-6, "vocab_size": 96,
       "num_hidden_layers": 4, "attn_layer_period": 4, "attn_layer_offset": 2,
       "layer_kinds": KINDS, "mamba_expand": 2, "mamba_d_state": 8,
       "mamba_d_conv": 4, "mamba_dt_rank": 6, "tie_word_embeddings": True,
       "float32_logits": True, "mamba_inner_norm": True,
       "recompute_blocks": False,
       # not 0.02 as in the benchmark's file: at a width of 64 the mixers
       # would hardly reach the logits, and a left-out norm would not show
       "initializer_range": 0.1}
ADAM = {"lr": 3e-4, "beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8}
# float32 on both sides: what is left is the order of additions (XLA fuses
# the program's projections and norms otherwise than the reference's) and
# the scan's exponentials, which the two sides compute from the same float32
# operands
TOL_LOGITS = 2e-5       # of the largest logit
TOL_LOSS = 1e-5         # relative
TOL_GRAD = 5e-4         # a leaf's gradient, of that leaf's norm
TOL_DELTA = 2e-3        # a leaf's change over two steps, relative: Adam's
                        # first steps are lr * sign(g), so a leaf moves by
                        # lr * sqrt(size) whatever its gradient's error
T = 32


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
    return _load("reference/jamba.py", "t_reference_jamba")


@pytest.fixture(scope="module")
def system():
    return _load("systems/jamba.py", "t_system_jamba")


@pytest.fixture(scope="module")
def batch():
    # 8 rows: the test session has 8 virtual devices and the trainer
    # spreads the batch over all of them
    seq = np.random.RandomState(0).randint(0, 96, (8, T + 1)).astype(np.int32)
    return seq[:, :-1], seq[:, 1:]


@pytest.fixture(scope="module")
def weights(ref):
    """Seeded, with the gains moved off 1: a program that skipped a gain
    would differ."""
    w = dict(ref.make_weights(CFG, 7, "float32"))
    rs = np.random.RandomState(1)
    for name in w:
        if ref.leaf_of(name).endswith("_g"):
            w[name] = jnp.asarray(
                1.0 + 0.3 * rs.randn(*w[name].shape).astype(np.float32))
    return w


def test_reference_imports_nothing_of_the_program_or_the_other_references():
    src = open(os.path.join(SUITE, "reference", "jamba.py")).read()
    assert "mxtpu" not in src and "import system" not in src
    assert "importlib" not in src and "_beside" not in src
    for other in ("kexaone", "phi4flash", "brumby", "lfm2", "ling", "joyai"):
        assert f"import {other}" not in src and f"{other}.py\"" not in src


def test_the_reference_follows_the_index_rule_and_counts_the_cells_leaves(
        ref):
    import json
    full = json.load(open(os.path.join(SUITE, "configs", "jamba2-3b.json")))
    kinds = ref.kinds(full)
    assert kinds == ["mamba"] * 7 + ["attention"] + ["mamba"] * 6
    assert [{"attention": "attn_full"}.get(k, k) for k in kinds] \
        == full["layer_kinds"]
    assert ref.parameter_count(full) == 1_598_556_096
    whole = dict(full, num_hidden_layers=28)
    assert [i for i, k in enumerate(ref.kinds(whole)) if k == "attention"] \
        == [7, 21]
    assert ref.parameter_count(whole) == 3_029_337_472


# ---------------------------------------------------------------------------
# the mixer
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("inner_norm", [True, False])
def test_mamba_against_the_plain_recurrence(ref, system, weights, inner_norm):
    """The mixer alone, with the three inner norms (gains off 1) and
    without them: the reference's ``lax.scan`` a row at a time."""
    cfg = dict(CFG, mamba_inner_norm=inner_norm)
    w = {k: v for k, v in weights.items()
         if inner_norm or not re.search(r"/(dt|b|c)_norm_g/", k)}
    net = system.build_net(cfg, w, "float32") if inner_norm \
        else _without_inner_norms(system, cfg, w)
    mixer = net.blocks[0].mamba
    assert (mixer.dt_norm is not None) == inner_norm
    x = nd.array(np.random.RandomState(2).randn(2, T, 64).astype(np.float32))
    shared = {}
    got = mixer(x, shared).data
    assert shared == {}             # nothing in this stack reads its memory
    want = ref.mamba(CFG, ref.layer_weights(weights, 0), x.data,
                     inner_norm=inner_norm)
    top = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) <= TOL_LOGITS * top
    # the other form is far off: the norms are not a rounding
    other = ref.mamba(CFG, ref.layer_weights(weights, 0), x.data,
                      inner_norm=not inner_norm)
    assert float(jnp.max(jnp.abs(got - other))) > 1e3 * TOL_LOGITS * top


def _without_inner_norms(system, cfg, w):
    """``system.build_net`` for a model whose Mamba layers have no inner
    norms: the system's leaf table without the three gains."""
    table = dict(system.MIXER)
    system.MIXER = dict(table, mamba={
        k: v for k, v in table["mamba"].items() if not k.endswith("norm_g")})
    try:
        return system.build_net(cfg, w, "float32")
    finally:
        system.MIXER = table


def test_the_default_mamba_is_the_layer_it_was_bit_for_bit():
    """Without ``inner_norm`` (the default, ``phi4-mini-flash``'s layer):
    the parameters it had, and the forward it had, written out here as it
    stood before the norms."""
    from mxtpu.gluon.model_zoo.hybrid_decoder import Mamba, _silu, _split
    mixer = Mamba(32, 64, 4, 3, 2)
    mixer.initialize()
    assert mixer.dt_norm is None and mixer.b_norm is None \
        and mixer.c_norm is None
    assert sorted(k.split("_", 1)[1] for k in mixer.collect_params()) \
        == sorted(["conv_weight", "conv_bias", "A_log", "D", "dense0_weight",
                   "dense1_weight", "dense2_weight", "dense2_bias",
                   "dense3_weight"])
    x = nd.array(np.random.RandomState(3).randn(2, 16, 32).astype(np.float32))
    shared = {}
    got = mixer(x, shared)
    u, z = _split(mixer.in_proj(x), (64, 64))
    u = _silu(nd.contrib.causal_conv1d(u, mixer.conv_weight.data(),
                                       mixer.conv_bias.data()))
    dt_r, B, C = _split(mixer.x_proj(u), (2, 4, 4))
    dt = nd.Activation(mixer.dt_proj(dt_r), act_type="softrelu")
    y = nd.contrib.selective_scan(u, dt, mixer.A_log.data(), B, C,
                                  mixer.D.data(), log_A=True)
    want = mixer.out_proj(y * _silu(z))
    assert bool(jnp.all(got.data == want.data))
    # built alone it hands its memory on, as it did
    assert bool(jnp.all(shared["memory"].data == y.data))


# ---------------------------------------------------------------------------
# the hand-over follows the stack
# ---------------------------------------------------------------------------

SPEC = dict(vocab_size=32, units=64, ffn_units=64, num_heads=4,
            num_kv_heads=2, head_dim=16, window=4, d_inner=32, d_state=4,
            d_conv=3, dt_rank=2)


@pytest.mark.parametrize("kinds,writes", [
    (["mamba"], [()]),
    (["mamba", "gmu"], [("memory",), ()]),
    # the newest producer before the reader hands on; the one before it is
    # overwritten unread
    (["mamba", "mamba", "gmu"], [(), ("memory",), ()]),
    (["mamba", "gmu", "mamba"], [("memory",), (), ()]),
    (["attn_full"], [()]),
    (["attn_full", "attn_cross"], [("kv",), ()]),
    (["attn_full", "attn_window", "attn_full", "attn_cross", "attn_full"],
     [(), (), ("kv",), (), ()]),
    # phi4-mini-flash's order: the producers next to the readers hand on
    (["mamba", "attn_window", "mamba", "attn_window", "mamba", "attn_full",
      "gmu", "attn_cross"],
     [(), (), (), (), ("memory",), ("kv",), (), ()]),
])
def test_a_mixer_hands_on_only_what_a_later_layer_reads(kinds, writes):
    from mxtpu.gluon.model_zoo.hybrid_decoder import HybridDecoderLM
    net = HybridDecoderLM(layer_kinds=kinds, **SPEC)
    net.initialize()
    assert [blk.mixer.writes for blk in net.blocks] == writes
    assert [blk.may_remat for blk in net.blocks] \
        == [not (blk.mixer.reads or w) for blk, w in zip(net.blocks, writes)]
    h = nd.array(np.random.RandomState(4).randn(2, 8, 64).astype(np.float32))
    shared = {}
    for blk, w in zip(net.blocks, writes):
        before = dict(shared)
        h = blk(h, shared)
        changed = tuple(k for k in shared if shared[k] is not before.get(k))
        assert changed == w, (blk.kind, changed)
    assert bool(jnp.isfinite(h.data).all())
    if all(blk.may_remat for blk in net.blocks):
        HybridDecoderLM(layer_kinds=kinds, remat=True, **SPEC)
    else:
        with pytest.raises(ValueError, match="hand nothing on") as err:
            HybridDecoderLM(layer_kinds=kinds, remat=True, **SPEC)
        for i, (blk, w) in enumerate(zip(net.blocks, writes)):
            said = f"layer {i} ({blk.kind}, mlp)" in str(err.value)
            assert said == bool(w or blk.mixer.reads), (i, str(err.value))


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------


def _loss_and_grads(net, system, x, y, lower: bool = True):
    """Loss and every leaf's gradient of a TRACED step (where ``remat``
    applies), jitted: ``(loss, {leaf: gradient}, lowered text)``."""
    leaves = system.param_leaves(net)
    handles = [p for p, _ in leaves]
    saved = [p._data._data for p in handles]

    def loss_of(ps):
        try:
            for p, v in zip(handles, ps):
                p._data._data = v
            with autograd.pause(train_mode=True):
                out = net(nd.NDArray(jnp.asarray(x)))
                loss = system.system.seq_loss(
                    out, nd.NDArray(jnp.asarray(y, jnp.float32)))
            return jnp.mean(loss.data)
        finally:
            for p, v in zip(handles, saved):
                p._data._data = v

    fn = jax.jit(jax.value_and_grad(loss_of))
    loss, grads = fn(saved)
    text = fn.lower(saved).as_text(debug_info=True) if lower else ""
    return loss, {leaf: g for (_, leaf), g in zip(leaves, grads)}, text


def test_logits_loss_and_every_gradient_leaf(ref, system, weights, batch):
    """Mamba mixers with the inner norms around one 4-on-1 attention layer
    without positions, pre-norm RMSNorm, the tied head in float32: logits,
    loss and every leaf's gradient; int8 operands fail the tolerances, and
    so does the FIRST PLANTED FAULT, a program (here: the reference)
    without the inner norms."""
    x, y = batch
    net = system.build_net(CFG, weights, "float32")
    assert net.head is None and net.layer_kinds == tuple(KINDS)
    assert net.blocks[2].attn_full.qkv.weight.shape == ((4 + 2) * 16, 64)
    assert net.blocks[2].attn_full.q_norm is None
    logits = net(nd.array(x)).data
    assert logits.dtype == jnp.float32
    want = ref.forward(CFG, weights, jnp.asarray(x))
    top = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(logits - want))) <= TOL_LOGITS * top
    low = ref.forward(CFG, weights, jnp.asarray(x), "int8")
    assert float(jnp.max(jnp.abs(low - want))) > 20 * TOL_LOGITS * top
    bare = ref.forward(CFG, weights, jnp.asarray(x), inner_norm=False)
    assert float(jnp.max(jnp.abs(bare - want))) > 20 * TOL_LOGITS * top

    leaves = system.param_leaves(net)
    assert {leaf for _, leaf in leaves} == set(weights)
    assert [leaf for _, leaf in leaves].count("embed") == 1
    for p, _ in leaves:
        p.data().attach_grad()
    with autograd.record():
        loss = nd.mean(system.system.seq_loss(
            net(nd.array(x)), nd.array(y.astype(np.float32))))
    loss.backward()

    def loss_of(precision=None, inner_norm=True):
        return jax.value_and_grad(lambda w: ref.loss_fn(
            CFG, w, jnp.asarray(x), jnp.asarray(y), precision,
            inner_norm))(weights)

    (want_loss, want_g), (_, low_g) = loss_of(), loss_of("int8")
    bare_loss, bare_g = loss_of(inner_norm=False)
    assert abs(float(loss.asscalar()) - float(want_loss)) \
        <= TOL_LOSS * float(want_loss)
    assert abs(float(bare_loss) - float(want_loss)) \
        > 20 * TOL_LOSS * float(want_loss)
    failed = bare = 0
    for p, leaf in leaves:
        norm = float(jnp.linalg.norm(want_g[leaf]))
        assert norm > 0, leaf
        gap = float(jnp.linalg.norm(p.data().grad.data - want_g[leaf])) / norm
        assert gap <= TOL_GRAD, (leaf, gap)
        failed += float(jnp.linalg.norm(low_g[leaf] - want_g[leaf])) \
            > 20 * TOL_GRAD * norm
        bare += float(jnp.linalg.norm(bare_g[leaf] - want_g[leaf])) \
            > 20 * TOL_GRAD * norm
    assert failed >= len(leaves) - 8, failed
    assert bare >= len(leaves) - 8, bare


def test_two_adam_steps_through_the_trainer(ref, system, weights, batch):
    """The reference's half-layer-at-a-time gradient (three kinds of half)
    and host Adam against the trainer's one program; the token table moves
    by the head's gradient plus the lookup's."""
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
    want = ref.train_steps(CFG, dict(weights), steps, ADAM, "float32",
                           row_block=8)
    whole = math.sqrt(sum(v * v for v in want["grad_norm"].values()))
    for a, b in zip(losses, want["loss"]):
        assert abs(a - b) <= TOL_LOSS * b
    assert abs(grad_norm - whole) <= TOL_GRAD * whole
    floor = np.median(list(want["delta_norm"].values()))
    assert set(want["delta_norm"]) == set(w0) == set(want["grad_norm"])
    for leaf, r in want["delta_norm"].items():
        got = float(np.linalg.norm(now[leaf] - w0[leaf]))
        assert abs(got - r) <= TOL_DELTA * max(r, floor), leaf
    for leaf in ("embed", "layers/dt_norm_g/0", "layers/A_log/3",
                 "layers/qkv_w/2"):
        assert want["delta_norm"][leaf] > 0, leaf
    assert losses[1] < losses[0]
    low = ref.train_steps(CFG, dict(weights), steps, ADAM, "float32",
                          row_block=8, precision="int8")
    worst = max(abs(low["delta_norm"][k] - r) / max(r, floor)
                for k, r in want["delta_norm"].items())
    assert worst > TOL_DELTA
    # the system counted the head's float32 logits when it placed the batch
    readers = sys.modules["jamba"]
    assert readers.HEAD_STATS == {"logits_bytes": 8 * T * 96 * 4}


# ---------------------------------------------------------------------------
# recomputation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("jitted", [False, True])
def test_the_recomputed_stack_is_the_kept_stack_bit_for_bit(system, weights,
                                                            batch, jitted):
    """``remat=True`` on (mamba, mamba, attn_full, mamba): blocks 0-2 under
    ``jax.checkpoint`` (the scan's custom backward and the attention's run
    their forwards again), block 3 kept: run operation by operation
    (``jax.disable_jit``) the loss and EVERY gradient leaf are the kept
    stack's to the last bit. Jitted, XLA's CPU backend fuses the two
    programs differently (the loss itself moves by one unit in the last
    place), so there they agree to float32 rounding; the lowered text shows
    which blocks run again and the counter says their kinds."""
    x, y = batch
    if not jitted:
        x, y = x[:2, :8], y[:2, :8]     # eager steps of the scan, one by one
    got = {}
    for remat in (False, True):
        profiler.reset_remat_stats()
        net = system.build_net(dict(CFG, recompute_blocks=remat), weights,
                               "float32")
        if jitted:
            got[remat] = _loss_and_grads(net, system, x, y)
        else:
            with jax.disable_jit():
                got[remat] = _loss_and_grads(net, system, x, y, lower=False)
        got[remat] += (profiler.get_remat_stats(),)
    kept, again = got[False], got[True]
    assert set(kept[1]) == set(again[1]) == set(weights)
    if jitted:
        assert float(again[0]) == pytest.approx(float(kept[0]), rel=1e-6)
        for leaf, g in kept[1].items():
            norm = float(jnp.linalg.norm(g))
            assert norm > 0, leaf
            assert float(jnp.linalg.norm(g - again[1][leaf])) \
                <= 1e-5 * norm, leaf
        assert "rematted_computation/block" not in kept[2]
        for i in range(4):
            assert (f"rematted_computation/block{i}/" in again[2]) \
                == (i < 3), i
    else:
        assert float(kept[0]) == float(again[0])
        for leaf, g in kept[1].items():
            assert bool(jnp.all(g == again[1][leaf])), leaf
            assert float(jnp.linalg.norm(g)) > 0, leaf
    assert kept[3] == {"blocks": 0, "recomputed": 0, "kinds": {}}
    assert again[3] == {"blocks": 4, "recomputed": 3,
                        "kinds": {"mamba": 2, "attn_full": 1}}


def test_a_recomputed_mixer_cut_off_from_its_gradient_is_caught(
        ref, system, weights, batch, monkeypatch):
    """The SECOND PLANTED FAULT: block 1 (recomputed) with its mixer's
    output behind a stop-gradient. The loss is the sound one's; the
    mixer's leaves get no gradient and every earlier leaf a wrong one, far
    outside the tolerance the sound program meets."""
    from mxtpu.gluon.model_zoo.hybrid_decoder import Mamba
    x, y = batch
    net = system.build_net(dict(CFG, recompute_blocks=True), weights,
                           "float32")
    sound = _loss_and_grads(net, system, x, y)
    want = jax.grad(lambda w: ref.loss_fn(CFG, w, jnp.asarray(x),
                                          jnp.asarray(y)))(weights)
    for leaf, g in sound[1].items():
        norm = float(jnp.linalg.norm(want[leaf]))
        assert float(jnp.linalg.norm(g - want[leaf])) <= TOL_GRAD * norm, leaf
    faulty = net.blocks[1].mamba
    forward = Mamba.forward

    def cut(self, x_, shared):
        out = forward(self, x_, shared)
        return nd.NDArray(jax.lax.stop_gradient(out.data)) \
            if self is faulty else out

    monkeypatch.setattr(Mamba, "forward", cut)
    loss, grads, _ = _loss_and_grads(net, system, x, y)
    assert float(loss) == float(sound[0])
    wrong = [leaf for leaf, g in grads.items()
             if float(jnp.linalg.norm(g - want[leaf]))
             > 20 * TOL_GRAD * float(jnp.linalg.norm(want[leaf]))]
    for leaf in ("in_w", "conv_w", "x_w", "dt_norm_g", "dt_w", "A_log",
                 "out_w"):
        assert float(jnp.linalg.norm(grads[f"layers/{leaf}/1"])) == 0.0, leaf
        assert f"layers/{leaf}/1" in wrong
    # what lies before the cut sees a wrong gradient too; what lies after
    # (blocks 2 and 3, the head) does not
    assert "layers/in_w/0" in wrong and "layers/gate_up_w/0" in wrong
    assert not any(leaf.endswith(("/2", "/3")) for leaf in wrong)
    whole = math.sqrt(sum(float(jnp.sum(jnp.square(g)))
                          for g in grads.values()))
    true = math.sqrt(sum(float(jnp.sum(jnp.square(g)))
                         for g in want.values()))
    # the benchmark's one number for the first gradient moves past the
    # cell's limit (0.0018 as shipped)
    assert abs(whole - true) / true > 0.0018


def test_the_step_carries_scopes_launch_rows_and_kernel_names(
        ref, system, weights, batch, monkeypatch):
    x, y = batch
    profiler.reset_launch_stats("ssm_scan")
    profiler.reset_kernel_path_counts()
    net = system.build_net(dict(CFG, recompute_blocks=True), weights,
                           "float32")
    trainer = system.Trainer(net, ADAM)
    trainer.step(*trainer.place(x, y))
    # one call site a mamba layer (a recomputed block is traced once), and
    # off the TPU the row holds the sizes alone
    assert profiler.get_kernel_path_counts()["ssm_scan"] \
        == {"pallas": 0, "xla": 3}
    assert profiler.get_launch_stats("ssm_scan") == {
        "launches": 3, "t_pad": T, "channels": 128, "states": 8, "chunk": 0,
        "block_d": 0, "chunk_start_bytes": 0}
    text = trainer.dpt.lowered().as_text(debug_info=True)
    for scope in ("block0/mamba/in_proj", "block0/mamba/inner_norm/dt_norm",
                  "block1/mamba/inner_norm/b_norm",
                  "block3/mamba/inner_norm/c_norm", "block0/mamba/ssm_scan",
                  "block2/attn_full/qkv", "block2/attn_full/out_proj",
                  "rematted_computation/block0/mamba/inner_norm",
                  "rematted_computation/block2/attn_full/qkv",
                  "rematted_computation/block1/mlp/gate_up",
                  "block3/mlp/gate_up", "ln_f", "head", "loss"):
        assert scope in text, scope
    assert "rematted_computation/block3" not in text
    assert profiler.get_remat_stats() == {
        "blocks": 4, "recomputed": 3, "kinds": {"mamba": 2, "attn_full": 1}}
    # on the TPU platform at whole lane tiles: the launches by name. Three
    # scans forward and the two recomputed blocks' again, three backward;
    # the attention layer's forward twice, its fused backward once
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    wide = dict(CFG, hidden_size=256, num_attention_heads=2,
                intermediate_size=256, vocab_size=128, recompute_blocks=True)
    net2 = system.build_net(wide, ref.make_weights(wide, 1, "bfloat16"),
                            "bfloat16")
    params = [p for p, _ in system.param_leaves(net2)]

    def loss(values, tokens):
        for p, v in zip(params, values):
            p._data._data = v
        with autograd.pause(train_mode=True):
            return jnp.sum(net2(nd.NDArray(tokens)).data)

    values = [p.data().data for p in params]
    profiler.reset_launch_stats("ssm_scan")
    profiler.reset_kernel_path_counts()
    try:
        lowered = jax.jit(jax.grad(loss)).trace(
            values, jnp.zeros((1, 256), jnp.int32)).lower(
            lowering_platforms=("tpu",))
    finally:
        for p, v in zip(params, values):
            p._data._data = v
    names = re.findall(r'kernel_name = "([^"]+)"', lowered.as_text())
    assert {k: names.count(k) for k in set(names)} == {
        "ssm_scan_fwd": 5, "ssm_scan_bwd": 3, "flash_fwd": 2,
        "flash_bwd_fused": 1}
    paths = profiler.get_kernel_path_counts()
    assert paths["ssm_scan"] == {"pallas": 3, "xla": 0}
    assert paths["flash"]["pallas"] >= 1 and paths["flash"]["xla"] == 0
    # the newest scan's geometry, from the program: 256 rows are four
    # chunks of 64, whose float32 starts are kept for the backward
    assert profiler.get_launch_stats("ssm_scan") == {
        "launches": 3, "t_pad": 256, "channels": 512, "states": 8,
        "chunk": 64, "block_d": 512, "chunk_start_bytes": 4 * 8 * 512 * 4}


def test_decoding_raises_and_names_what_a_cache_would_hold(system, weights):
    net = system.build_net(CFG, weights, "float32")
    with pytest.raises(NotImplementedError, match="trains only") as err:
        net.generate(nd.array(np.zeros((1, 4))), 4)
    said = str(err.value)
    assert "mamba: scan and convolution states" in said
    assert "attn_full: every key and value" in said
    assert "gmu" not in said.split("Layer kinds")[0]
    with pytest.raises(NotImplementedError, match="trains only"):
        net.serving_step()


# ---------------------------------------------------------------------------
# the cut
# ---------------------------------------------------------------------------


def test_layers_0_to_13_of_the_28_layer_model_are_the_cut_model(ref, system):
    """No expert, head or vocabulary row is absent, so there is no share to
    add up: the cut is depth alone. The 28-layer reference (period 14,
    offset 7: attention at 7 and 21) and the 14-layer one hold the SAME
    leaves for layers 0-13, and the 14-layer PROGRAM's output after its
    last block is the 28-layer reference's after layer 13."""
    small = dict(CFG, hidden_size=32, intermediate_size=48,
                 num_attention_heads=2, mamba_d_state=4, mamba_dt_rank=3,
                 attn_layer_period=14, attn_layer_offset=7)
    whole = dict(small, num_hidden_layers=28)
    cut = dict(small, num_hidden_layers=14,
               layer_kinds=["mamba"] * 7 + ["attn_full"] + ["mamba"] * 6)
    w28, w14 = (ref.make_weights(c, 11, "float32") for c in (whole, cut))
    assert set(w14) < set(w28)
    assert {n for n in w28 if n not in w14} \
        == {n for n in w28 if n.startswith("layers/")
            and int(n.rsplit("/", 1)[1]) >= 14}
    for name, a in w14.items():
        assert bool(jnp.all(a == w28[name])), name
    x = np.random.RandomState(5).randint(0, 96, (2, 16)).astype(np.int32)
    want = ref.hidden(whole, w28, jnp.asarray(x), layers=14)
    assert bool(jnp.all(want == ref.hidden(cut, w14, jnp.asarray(x))))
    deeper = ref.hidden(whole, w28, jnp.asarray(x))
    assert float(jnp.max(jnp.abs(deeper - want))) > 1e-3
    net = system.build_net(cut, w14, "float32")
    assert net.layer_kinds == tuple(cut["layer_kinds"])
    h = net.embedding(nd.array(x))
    for blk in net.blocks:
        h = blk(h, {})
    top = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(h.data - want))) <= TOL_LOGITS * top


# This family's parameters by attribute path, saved name and shape
# (tests/conftest.py: _param_names_hash): the benchmark's systems/jamba.py
# loads the reference's weights by these paths, and a renamed child would
# show first as a cell without a result on the chip. Taken in PR 49.
JAMBA_NAMES = "38639f9c41b8c455"


def test_jamba_parameters_keep_their_names_and_shapes(system, weights,
                                                      param_names_hash):
    net = system.build_net(CFG, weights, "float32")
    got, listing = param_names_hash(net)
    assert got == JAMBA_NAMES, f"{got}\n{listing}"
