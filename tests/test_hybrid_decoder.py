"""``HybridDecoderLM`` (``gluon/model_zoo/hybrid_decoder.py``) against the
plain float32 reference the benchmark keeps
(``benchmark/suite/reference/phi4flash.py``, which imports nothing of the
program), at a tiny size on seeded weights: logits, loss, every leaf's
gradient, three Adam steps through ``DataParallelTrainer``; the int8 control
has to fail the tolerances; the vocabulary slice is a share of the model."""

import importlib.util
import math
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxtpu import autograd, nd

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SUITE = os.path.join(ROOT, "benchmark", "suite")

CFG = {"hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
       "num_key_value_heads": 2, "sliding_window": 8, "layer_norm_eps": 1e-5,
       "vocab_size": 96, "mamba_expand": 2, "mamba_d_state": 8,
       "mamba_d_conv": 4, "mamba_dt_rank": 4,
       "layer_kinds": ["mamba", "attn_window", "mamba", "attn_full", "gmu",
                       "attn_cross"]}
ADAM = {"lr": 3e-4, "beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8}
# float32 on both sides: what is left is the order of additions. int8 moves
# each of these numbers a hundred times as far (asserted below).
TOL_LOGITS = 2e-5       # of the largest logit
TOL_LOSS = 1e-5         # relative
TOL_GRAD = 5e-4         # a leaf's gradient, of that leaf's norm
TOL_DELTA = 2e-3        # a leaf's change over three steps, relative


def _load(path, name):
    if SUITE not in sys.path:
        sys.path.insert(0, SUITE)
    spec = importlib.util.spec_from_file_location(name, os.path.join(SUITE, path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("reference/phi4flash.py", "t_reference_phi4flash")


@pytest.fixture(scope="module")
def system():
    return _load("systems/phi4flash.py", "t_system_phi4flash")


@pytest.fixture(scope="module")
def batch():
    # 8 rows: the test session has 8 virtual devices and the trainer
    # spreads the batch over all of them
    seq = np.random.RandomState(0).randint(0, 96, (8, 33)).astype(np.int32)
    return seq[:, :-1], seq[:, 1:]


@pytest.fixture(scope="module")
def weights(ref):
    return ref.make_weights(CFG, 7, "float32")


def test_reference_imports_nothing_of_the_program(ref):
    src = open(os.path.join(SUITE, "reference", "phi4flash.py")).read()
    assert "mxtpu" not in src and "import system" not in src


def test_logits_and_loss_agree_with_the_reference(ref, system, weights, batch):
    x, y = batch
    net = system.build_net(CFG, weights, "float32")
    logits = net(nd.array(x)).data
    want = ref.forward(CFG, weights, jnp.asarray(x))
    assert float(jnp.max(jnp.abs(logits - want))) \
        <= TOL_LOGITS * float(jnp.max(jnp.abs(want)))
    loss = float(jnp.mean(system.system.seq_loss(
        nd.array(logits), nd.array(y.astype(np.float32))).data))
    want_loss = float(ref.loss_fn(CFG, weights, jnp.asarray(x),
                                  jnp.asarray(y)))
    assert abs(loss - want_loss) <= TOL_LOSS * want_loss
    # the control: int8 operands move the logits past the tolerance
    low = ref.forward(CFG, weights, jnp.asarray(x), "int8")
    assert float(jnp.max(jnp.abs(low - want))) \
        > 20 * TOL_LOGITS * float(jnp.max(jnp.abs(want)))


def test_every_leafs_gradient_agrees_with_the_reference(ref, system, weights,
                                                        batch):
    """Through the imperative tape: every registered op's backward, the
    scan's and the differential attention's among them."""
    x, y = batch
    net = system.build_net(CFG, weights, "float32")
    leaves = system.param_leaves(net)
    for p, _ in leaves:
        p.data().attach_grad()
    with autograd.record():
        loss = nd.mean(system.system.seq_loss(
            net(nd.array(x)), nd.array(y.astype(np.float32))))
    loss.backward()
    want = jax.grad(lambda w: ref.loss_fn(CFG, w, jnp.asarray(x),
                                          jnp.asarray(y)))(weights)
    low = jax.grad(lambda w: ref.loss_fn(CFG, w, jnp.asarray(x),
                                         jnp.asarray(y), "int8"))(weights)
    worst, worst_low = 0.0, 0.0
    for p, leaf in leaves:
        g = p.data().grad.data
        norm = float(jnp.linalg.norm(want[leaf]))
        if norm == 0.0:
            continue
        gap = float(jnp.linalg.norm(g - want[leaf])) / norm
        assert gap <= TOL_GRAD, (leaf, gap)
        worst = max(worst, gap)
        worst_low = max(worst_low, float(
            jnp.linalg.norm(low[leaf] - want[leaf])) / norm)
    assert worst_low > 20 * TOL_GRAD, (worst, worst_low)


def test_three_adam_steps_through_the_trainer_follow_the_reference(
        ref, system, weights, batch):
    x, y = batch
    net = system.build_net(CFG, weights, "float32")
    w0 = system.param_arrays(net)
    trainer = system.Trainer(net, ADAM)
    losses = []
    for i in range(3):
        losses.append(float(trainer.step(*trainer.place(x, y))))
        if i == 0:
            grad_norm = trainer.first_gradient_norm()
    now = trainer.param_arrays()
    steps = [(jnp.asarray(x), jnp.asarray(y))] * 3
    want = ref.train_steps(CFG, weights, steps, ADAM, "float32", row_block=8)
    low = ref.train_steps(CFG, weights, steps, ADAM, "float32", row_block=8,
                          precision="int8")

    def whole(norms):
        return math.sqrt(sum(v * v for v in norms.values()))

    for a, b in zip(losses, want["loss"]):
        assert abs(a - b) <= TOL_LOSS * b
    assert abs(grad_norm - whole(want["grad_norm"])) \
        <= TOL_GRAD * whole(want["grad_norm"])
    assert abs(whole(low["grad_norm"]) - whole(want["grad_norm"])) \
        > 20 * TOL_GRAD * whole(want["grad_norm"])
    floor = np.median(list(want["delta_norm"].values()))
    for leaf, r in want["delta_norm"].items():
        got = float(np.linalg.norm(now[leaf] - w0[leaf]))
        assert abs(got - r) <= TOL_DELTA * max(r, floor), leaf
    assert losses[2] < losses[0]


def test_the_vocabulary_slice_is_a_share_of_the_model(ref, system, weights):
    """The sliced model's logits are rows 0..V/8 of the uncut model's, and
    its loss is the loss over the slice."""
    V8 = CFG["vocab_size"] // 8
    cut = dict(CFG, vocab_size=V8)
    cut_w = dict(weights, embed=weights["embed"][:V8])
    seq = np.random.RandomState(1).randint(0, V8, (2, 17)).astype(np.int32)
    x, y = seq[:, :-1], seq[:, 1:]
    whole = system.build_net(CFG, weights, "float32")(nd.array(x)).data
    part = system.build_net(cut, cut_w, "float32")(nd.array(x)).data
    assert part.shape[-1] == V8
    np.testing.assert_allclose(np.asarray(part), np.asarray(whole[..., :V8]),
                               rtol=1e-5, atol=1e-7)
    sliced = whole[..., :V8].reshape(-1, V8)
    over_slice = float(jnp.mean(
        jax.nn.logsumexp(sliced, axis=-1)
        - jnp.take_along_axis(sliced, y.reshape(-1, 1), axis=-1)[:, 0]))
    got = float(ref.loss_fn(cut, cut_w, jnp.asarray(x), jnp.asarray(y)))
    assert abs(got - over_slice) <= 1e-5 * over_slice


def test_the_step_carries_kind_scopes_and_kernel_names(system, weights,
                                                       batch, monkeypatch):
    """``block<i>/<kind>`` on the step's operations, and on the TPU platform
    the kernels by name, window launches apart from the others."""
    import re
    from mxtpu.ops import attention, ssm
    x, y = batch
    net = system.build_net(CFG, weights, "float32")
    trainer = system.Trainer(net, ADAM)
    trainer.step(*trainer.place(x, y))
    text = trainer.dpt.lowered().as_text(debug_info=True)
    for scope in ("block0/mamba/in_proj", "block1/attn_window/qkv",
                  "block3/attn_full/out_proj", "block4/gmu/in_proj",
                  "block5/attn_cross/qkv", "block2/mlp/gate_up", "ln_f",
                  "head", "loss", "ssm_scan"):
        assert scope in text, scope
    assert "block5/attn_cross/qkv" in text
    # the kernels, where the platform is the TPU: T = 128 engages them
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    seq = np.zeros((1, 129), np.int32)
    net2 = system.build_net(CFG, weights, "bfloat16")

    def loss(tokens):
        with autograd.pause(train_mode=True):
            return jnp.sum(net2(nd.NDArray(tokens)).data.astype(jnp.float32))

    hlo = jax.jit(loss).trace(jnp.asarray(seq[:, :-1])).lower(
        lowering_platforms=("tpu",)).as_text()
    assert sorted(set(re.findall(r'kernel_name = "([^"]+)"', hlo))) == [
        "flash_fwd", "flash_fwd_window", "ssm_scan_fwd"]


def test_decoding_raises_and_says_why(system, weights):
    net = system.build_net(CFG, weights, "float32")
    for call in (lambda: net.generate(nd.array(np.zeros((1, 4))), 4),
                 lambda: net.serving_step(1, 64),
                 lambda: net.serving_verify_step(1, 64, 2),
                 lambda: net._gen_params()):
        with pytest.raises(NotImplementedError, match="trains only"):
            call()
    from mxtpu.gluon.model_zoo.hybrid_decoder import HybridDecoderLM
    with pytest.raises(ValueError, match="unknown layer kind"):
        HybridDecoderLM(32, ["mamba", "attention"], 64, 128, 4, 2)
    lone = HybridDecoderLM(32, ["gmu"], 64, 128, 4, 2)
    lone.initialize()
    with pytest.raises(ValueError, match="memory of an earlier mamba"):
        lone(nd.array(np.zeros((1, 8), np.int32)))


def test_import_mxtpu_imports_none_of_the_family():
    code = ("import sys, mxtpu, mxtpu.gluon.model_zoo as z; "
            "assert 'mxtpu.gluon.model_zoo.hybrid_decoder' not in sys.modules; "
            "assert not any('pallas' in m for m in sys.modules); "
            "assert z.HybridDecoderLM.__name__ == 'HybridDecoderLM'; "
            "assert 'mxtpu.gluon.model_zoo.hybrid_decoder' in sys.modules")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]


# The sparse family (tests/test_kexaone.py) shares the block and the stack
# with this one: this family's step has to trace to the program it traced to
# before the layer spec grew an attention kind, a norm kind and position, an
# untied head and expert layers (hash of the printed jaxpr of loss and
# gradient at CFG, taken on the parent tree, commit 55b792c).
PHI4_STEP = "cb95fae9884848c5"


def test_phi4_step_traces_to_the_same_jaxpr(ref, system, weights, batch,
                                            step_jaxpr_hash):
    net = system.build_net(CFG, weights, "float32")
    assert step_jaxpr_hash(net, system, *batch) == PHI4_STEP


# The parameters by attribute path, saved name and shape (tests/conftest.py:
# _param_names_hash): the benchmark's systems/phi4flash.py loads the reference's
# weights by these paths, and a renamed child would show first as a cell
# without a result on the chip. Taken at commit a1cb520 (PR 44).
PHI4_NAMES = "e949b3f393c0edbf"


def test_phi4_parameters_keep_their_names_and_shapes(system, weights,
                                                     param_names_hash):
    net = system.build_net(CFG, weights, "float32")
    got, listing = param_names_hash(net)
    assert got == PHI4_NAMES, f"{got}\n{listing}"


# The hand-over contract (PR 45), one case a row of ``MIXERS``: a mixer is
# called ``mixer(x, shared)``, takes what its ``reads`` names from ``shared``
# and puts what its ``writes`` names there itself; the block compares no kind.
SPEC = dict(vocab_size=32, units=64, ffn_units=64, num_heads=4,
            num_kv_heads=2, head_dim=16, window=4, d_inner=32, d_state=4,
            d_conv=3, dt_rank=2,
            mla=dict(latent_dim=8, nope_dim=8, rope_dim=4, v_dim=8))
NEEDS = {"attn_cross": "attn_cross needs the keys and values of an earlier "
                       "attn_full layer",
         "gmu": "gmu needs the memory of an earlier mamba layer"}


def _kinds():
    from mxtpu.gluon.model_zoo.hybrid_decoder import MIXERS
    return [(kind, "diff") for kind in MIXERS] \
        + [("attn_window", "gqa"), ("attn_full", "gqa")]


@pytest.mark.parametrize("kind,attention", _kinds())
def test_a_mixer_takes_and_hands_on_what_it_declares(kind, attention):
    from mxtpu.gluon.model_zoo.hybrid_decoder import (HybridDecoderLM, KINDS,
                                                      MIXERS, Mixer)
    assert KINDS == tuple(MIXERS)
    net = HybridDecoderLM(layer_kinds=[kind], attention=attention, **SPEC)
    net.initialize()
    blk = net.blocks[0]
    mixer = getattr(blk, kind)
    assert isinstance(mixer, Mixer)
    rs = np.random.RandomState(3)
    x = nd.array(rs.randn(2, 8, 64).astype(np.float32))
    given = {"memory": nd.array(rs.randn(2, 8, 32).astype(np.float32)),
             "kv": tuple(nd.array(rs.randn(2, 8, 2, 16).astype(np.float32))
                         for _ in range(2))}
    assert set(mixer.reads) | set(mixer.writes) <= set(given)
    shared = {key: given[key] for key in mixer.reads}
    out = blk(x, shared)
    assert out.shape == x.shape and bool(jnp.isfinite(out.data).all())
    assert sorted(shared) == sorted(mixer.reads + mixer.writes)
    for key in mixer.reads:                 # read, not replaced
        assert shared[key] is given[key]
    if mixer.reads:
        assert kind in NEEDS
        with pytest.raises(ValueError, match=NEEDS[kind]):
            blk(x, {})
    else:
        assert kind not in NEEDS
        alone = {}
        blk(x, alone)
        assert sorted(alone) == sorted(mixer.writes)
    # the row's other two columns: the decode cache's text and recomputation
    with pytest.raises(NotImplementedError, match="trains only") as err:
        net.generate()
    assert f"{kind}: {MIXERS[kind].decode_state}; the engine" \
        in str(err.value)
    assert blk.may_remat == (MIXERS[kind].remat
                             and not (mixer.reads or mixer.writes))
    if blk.may_remat:
        # what a cell runs so, or runs beside one that is; alone in a stack
        # a mamba or a full-attention layer hands nothing on
        assert kind in ("retention", "mamba", "attn_full", "attn_window")
        assert mixer.writes == ()
        HybridDecoderLM(layer_kinds=[kind] * 2, attention=attention,
                        remat=True, **SPEC)
        with pytest.raises(ValueError, match=r"Not layer 0 \(\w+, moe\)$"):
            HybridDecoderLM(layer_kinds=[kind], attention=attention,
                            remat=True, mlp_kinds=["moe"],
                            moe=dict(ffn_units=16, num_experts=4, top_k=2),
                            **SPEC)
    else:
        reads = f" reads {', '.join(mixer.reads)}" if mixer.reads else ""
        with pytest.raises(ValueError, match=r"remat=True recomputes blocks "
                           r"that read nothing, hand nothing on and hold no "
                           r"state: kinds \('mamba', 'attn_window', "
                           r"'attn_full', 'retention'\) beside MLP kinds "
                           rf"\('mlp',\). Not layer 0 \({kind}, mlp\)"
                           rf"{reads}$"):
            HybridDecoderLM(layer_kinds=[kind], attention=attention,
                            remat=True, **SPEC)
