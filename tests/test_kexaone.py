"""The sparse family of ``HybridDecoderLM`` (K-EXAONE's block: grouped-query
window / full attention with q/k norm, rotary positions on the window layers
only, RMSNorm on each sub-layer's output, an untied head, sparse expert
layers with a shared expert and a selection bias that balances the load)
against the plain float32 reference the benchmark keeps
(``benchmark/suite/reference/kexaone.py``, which imports nothing of the
program), at a tiny size on seeded weights with a NONZERO bias draw: logits,
loss, every leaf's gradient, three Adam steps through
``DataParallelTrainer`` with the bias moving equally on both sides; the int8
control has to fail the tolerances; the shares of experts and heads add up
to the uncut layer."""

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

# one chip's share: experts 4..7 of 16, 4 query heads on 1 key/value head
CFG = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 1,
       "head_dim": 16, "sliding_window": 8, "intermediate_size": 128,
       "moe_intermediate_size": 32, "published_num_experts": 16,
       "num_experts": 4, "held_experts": [4, 5, 6, 7],
       "num_experts_per_tok": 4, "num_shared_experts": 1,
       "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-5, "vocab_size": 96,
       "num_hidden_layers": 4,
       "layer_types": ["sliding_attention", "sliding_attention",
                       "full_attention", "sliding_attention"],
       "mlp_layer_types": ["dense", "sparse", "sparse", "sparse"],
       "rope_parameters": {"rope_theta": 1e6}, "tie_word_embeddings": False,
       # NOT zero as in the benchmark's file: a program that ignores the
       # bias, or lets it into the weights, must differ from the reference
       "router_bias_init_std": 0.1, "router_bias_update_rate": 0.03}
ADAM = {"lr": 3e-4, "beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8}
# float32 on both sides: what is left is the order of additions (the program
# adds an expert's rows up by a scatter, the reference a dense product at a
# time). int8 moves each of these numbers far past them (asserted below).
TOL_LOGITS = 2e-5       # of the largest logit
TOL_LOSS = 1e-5         # relative
TOL_GRAD = 5e-4         # a leaf's gradient, of that leaf's norm
TOL_DELTA = 2e-3        # a leaf's change over three steps, relative
T = 32                  # four windows long


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
    return _load("reference/kexaone.py", "t_reference_kexaone")


@pytest.fixture(scope="module")
def system():
    return _load("systems/kexaone.py", "t_system_kexaone")


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
    src = open(os.path.join(SUITE, "reference", "kexaone.py")).read()
    assert "mxtpu" not in src and "import system" not in src


def test_logits_loss_and_every_gradient_leaf(ref, system, weights, batch):
    """Rotary positions on the window layers alone, 4 query heads on 1 key
    head with q/k norm, norms on the sub-layers' outputs, the untied head,
    routing by score + bias with weights that leave the bias out, the held
    experts' grouped products and the shared expert: logits, loss and every
    leaf's gradient; int8 operands fail each tolerance."""
    x, y = batch
    net = system.build_net(CFG, weights, "float32")
    assert net.head is not None
    assert net.mlp_kinds == ("mlp", "moe", "moe", "moe")
    assert float(jnp.abs(weights["layers/router_b/1"]).max()) > 0.05
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

    def loss_of(precision):
        return jax.value_and_grad(lambda w: ref.loss_fn(
            CFG, w, jnp.asarray(x), jnp.asarray(y), precision))(weights)

    (want_loss, want_g), (_, low_g) = loss_of(None), loss_of("int8")
    assert abs(float(loss.asscalar()) - float(want_loss)) \
        <= TOL_LOSS * float(want_loss)
    # (int8 hardly moves the loss itself, at any size: PERF.md section 2)
    for p, leaf in leaves:
        norm = float(jnp.linalg.norm(want_g[leaf]))
        assert norm > 0, leaf
        gap = float(jnp.linalg.norm(p.data().grad.data - want_g[leaf])) / norm
        assert gap <= TOL_GRAD, (leaf, gap)
        if leaf.split("/")[-2:-1] != ["router_w"]:
            # (the routers' gradient is small and int8 moves it least)
            assert float(jnp.linalg.norm(low_g[leaf] - want_g[leaf])) \
                > 20 * TOL_GRAD * norm, leaf


def test_three_adam_steps_with_the_bias_moving_on_both_sides(
        ref, system, weights, batch):
    """The reference's layer-at-a-time gradient and per-layer host Adam
    against the trainer's one program. The selection bias rides the step as
    an auxiliary state: after three steps it equals the reference's, moved
    by the balancing rule from a nonzero draw; its change is a leaf of the
    comparison like any other."""
    x, y = batch
    net = system.build_net(CFG, weights, "float32")
    w0 = system.param_arrays(net)
    trainer = system.Trainer(net, ADAM)
    losses = []
    for i in range(3):
        losses.append(float(trainer.step(*trainer.place(x, y))))
        if i == 0:
            grad_norm = trainer.first_gradient_norm()
    assert len(trainer.dpt._aux_handles) == 6       # bias and count, x 3
    now = trainer.param_arrays()
    steps = [(jnp.asarray(x), jnp.asarray(y))] * 3
    want = ref.train_steps(CFG, ref.make_weights(CFG, 7, "float32"), steps,
                           ADAM, "float32", row_block=8)
    whole = math.sqrt(sum(v * v for v in want["grad_norm"].values()))
    for a, b in zip(losses, want["loss"]):
        assert abs(a - b) <= TOL_LOSS * b
    assert abs(grad_norm - whole) <= TOL_GRAD * whole
    floor = np.median(list(want["delta_norm"].values()))
    # a selection bias has no gradient, and the reference reports none
    assert set(want["delta_norm"]) == set(w0) == set(want["grad_norm"]) \
        | set(want["states"]) and len(want["states"]) == 3
    for leaf, r in want["delta_norm"].items():
        got = float(np.linalg.norm(now[leaf] - w0[leaf]))
        assert abs(got - r) <= TOL_DELTA * max(r, floor), leaf
    assert losses[2] < losses[0]
    for i in (1, 2, 3):
        leaf = f"layers/router_b/{i}"
        b = net.blocks[i].moe.select_bias.data().asnumpy()
        np.testing.assert_allclose(b, want["states"][leaf], rtol=0, atol=1e-6)
        moved = np.abs(b - np.asarray(weights[leaf]))
        # three steps of 0.03 each way: 0.03 or 0.09 where no step met the
        # even share exactly
        assert 0.029 < moved.max() <= 0.0901 and want["delta_norm"][leaf] > 0
    # the layers' own count of the newest step, through the model, and the
    # handles this cell's trainer gave the per-layer readers after each step
    rows = profiler.get_moe_stats(net)
    assert [r["name"] for r in rows] == [net.blocks[i].moe.name
                                         for i in (1, 2, 3)]
    assert all(r["pairs"] > 0 and r["passes"] == 1 for r in rows)
    import moe as readers
    assert len(readers.STEP_COUNTS) == 3
    held = CFG["held_experts"]
    assert [float(np.asarray(c)[held].sum())
            for c in readers.STEP_COUNTS[-1]] == [r["pairs"] for r in rows]
    assert system.kernel_path_counts()["grouped_matmul"]["xla"] > 0


# the deployment the shares are cut from: 32 experts over 16 chips, 16 query
# heads on 8 key/value heads over 8 chips
UNCUT = dict(CFG, num_attention_heads=16, num_key_value_heads=8, head_dim=8,
             published_num_experts=32, num_experts=32,
             held_experts=list(range(32)))


def test_the_shares_add_up_to_the_uncut_layer(ref):
    """The guide's share test, for everything the configuration cuts by
    share. The routed terms of all 16 expert shares (2 experts each, every
    share routing over all 32) with the shared expert, which every chip
    computes alike, counted once, add up to the uncut reference's MLP
    sub-layer; the ``W_o`` partial sums of the 8 head shares (2 query heads
    on 1 key/value head each) add up to its attention sub-layer, in a
    window layer (rotary) and in the full layer (none)."""
    from mxtpu.gluon.model_zoo.hybrid_decoder import GroupedQueryAttention
    from mxtpu.parallel.moe import SparseExperts
    w = ref.make_weights(UNCUT, 11, "float32")
    rs = np.random.RandomState(2)
    x = jnp.asarray(rs.randn(2, T, 64), jnp.float32)
    d, D, Fe = 64, 8, 32

    lp = ref.layer_weights(w, 1)
    whole = ref.mlp_sublayer(UNCUT, 1, lp, x)
    total, pairs = 0.0, 0.0
    for share in range(16):
        held = [2 * share, 2 * share + 1]
        blk = SparseExperts(d, Fe, 32, 4, held=held, shared_ffn_units=Fe,
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
    assert pairs == 2 * T * 4                      # every pair is somewhere
    np.testing.assert_allclose(np.asarray(total - 15 * once),
                               np.asarray(whole), rtol=1e-4, atol=2e-5)

    for i in (0, 2):                               # window, full
        lp = ref.layer_weights(w, i)
        whole = ref.attention_sublayer(UNCUT, i, lp, x)
        qkv, o = lp["qkv_w"], lp["o_w"]
        total = 0.0
        for share in range(8):
            rows = np.r_[2 * share * D:(2 * share + 2) * D,
                         (16 + share) * D:(17 + share) * D,
                         (24 + share) * D:(25 + share) * D]
            att = GroupedQueryAttention(
                d, 2, 1, D, window=8 if i == 0 else None,
                rope_theta=1e6 if i == 0 else 0.0, qk_norm=True)
            att.initialize()
            att.qkv.weight.set_data(nd.NDArray(qkv[rows]))
            att.out_proj.weight.set_data(
                nd.NDArray(o[:, 2 * share * D:(2 * share + 2) * D]))
            att.q_norm.set_data(nd.NDArray(lp["q_norm_g"]))
            att.k_norm.set_data(nd.NDArray(lp["k_norm_g"]))
            total = total + att(nd.NDArray(x), {}).data
        np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                                   rtol=1e-4, atol=1e-5)


def test_window_mask_and_rotary_on_the_window_layers_only(ref):
    """At ``T`` four windows long: a key 8 or more positions back has no
    say in a window layer and has one in the full layer; positions turn q
    and k in the window layer (against the reference's rope) and not in the
    full layer, which without a mask would not know the order of its keys."""
    from mxtpu.gluon.model_zoo.hybrid_decoder import gq_attention
    rs = np.random.RandomState(3)
    q = jnp.asarray(rs.randn(2, T, 4, 16), jnp.float32)
    k = jnp.asarray(rs.randn(2, T, 1, 16), jnp.float32)
    v = jnp.asarray(rs.randn(2, T, 1, 16), jnp.float32)
    window = gq_attention(q, k, v, rope_theta=1e6, window=8)
    want = ref.attention(ref.rope(q, 1e6), ref.rope(k, 1e6), v, 8, None)
    np.testing.assert_allclose(np.asarray(window), np.asarray(want),
                               atol=2e-6)
    full = gq_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(full),
                               np.asarray(ref.attention(q, k, v, None, None)),
                               atol=2e-6)
    k2, v2 = k.at[:, 3].add(1.0), v.at[:, 3].add(1.0)     # an old key moves
    moved_w = jnp.abs(gq_attention(q, k2, v2, rope_theta=1e6, window=8)
                      - window).reshape(2, T, -1).max(axis=(0, 2))
    moved_f = jnp.abs(gq_attention(q, k2, v2) - full).reshape(
        2, T, -1).max(axis=(0, 2))
    assert not moved_w[:3].any() and moved_w[3:11].all() \
        and not moved_w[11:].any()
    assert not moved_f[:3].any() and moved_f[3:].all()
    # rotary somewhere it does not belong is another function
    assert float(jnp.abs(gq_attention(q, k, v, rope_theta=1e6)
                         - full).max()) > 1e-2


def test_step_carries_scopes_and_kernel_names(ref, system, weights, batch,
                                              monkeypatch):
    x, y = batch
    net = system.build_net(CFG, weights, "float32")
    trainer = system.Trainer(net, ADAM)
    trainer.step(*trainer.place(x, y))
    text = trainer.dpt.lowered().as_text(debug_info=True)
    for scope in ("block0/attn_window/qkv", "block0/attn_window/rope",
                  "block2/attn_full/qk_norm", "block0/mlp/gate_up",
                  "block1/moe/route", "block2/moe/dispatch",
                  "block2/moe/experts", "block1/moe/combine",
                  "block3/moe/shared", "block1/moe/balance", "ln_f", "head",
                  "loss"):
        assert scope in text, scope
    assert "block2/attn_full/rope" not in text
    assert profiler.get_kernel_path_counts()["grouped_matmul"]["xla"] > 0
    # on the TPU platform at widths in whole 128s: the grouped-matmul and
    # flash launches by name
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    wide = dict(CFG, hidden_size=128, head_dim=128, intermediate_size=256,
                moe_intermediate_size=128, vocab_size=128)
    net2 = system.build_net(wide, ref.make_weights(wide, 1, "bfloat16"),
                            "bfloat16")

    def loss(tokens):
        with autograd.pause(train_mode=True):
            return jnp.sum(net2(nd.NDArray(tokens)).data.astype(jnp.float32))

    hlo = jax.jit(loss).trace(jnp.zeros((1, 128), jnp.int32)).lower(
        lowering_platforms=("tpu",)).as_text()
    assert set(re.findall(r'kernel_name = "([^"]+)"', hlo)) == {
        "flash_fwd", "flash_fwd_window", "moe_gmm"}


def test_decoding_and_bad_specs_raise(system, weights):
    from mxtpu.gluon.model_zoo.hybrid_decoder import HybridDecoderLM
    net = system.build_net(CFG, weights, "float32")
    with pytest.raises(NotImplementedError, match="trains only"):
        net.generate(nd.array(np.zeros((1, 4))), 4)
    with pytest.raises(NotImplementedError, match="trains only"):
        net.serving_step()
    with pytest.raises(ValueError, match="unknown MLP kind"):
        HybridDecoderLM(32, ["attn_full"], 64, 128, 4, 2, mlp_kinds=["ffn"])
    with pytest.raises(ValueError, match="give moe="):
        HybridDecoderLM(32, ["attn_full"], 64, 128, 4, 2, mlp_kinds=["moe"])
    with pytest.raises(ValueError, match="no attn_cross"):
        HybridDecoderLM(32, ["attn_cross"], 64, 128, 4, 2, head_dim=16,
                        attention="gqa")
    with pytest.raises(ValueError, match="norm_position"):
        HybridDecoderLM(32, ["attn_full"], 64, 128, 4, 2,
                        norm_position="sandwich")


# The conv / all-held family (tests/test_lfm2.py) shares the block, the stack
# and the expert layer with this one: this family's step has to trace to the
# program it traced to before the layer spec grew a mixer kind, float32
# logits for a tied head and the router's ``weight_eps`` (hash of the printed
# jaxpr of loss and gradient at CFG, taken on the parent tree, commit
# 8e34eb5).
KEXAONE_STEP = "a2a6f78a7040f5b2"


def test_kexaone_step_traces_to_the_same_jaxpr(ref, system, weights, batch,
                                               step_jaxpr_hash):
    net = system.build_net(CFG, weights, "float32")
    assert step_jaxpr_hash(net, system, *batch) == KEXAONE_STEP


# The parameters by attribute path, saved name and shape (tests/conftest.py:
# _param_names_hash): the benchmark's systems/kexaone.py loads the reference's
# weights by these paths, and a renamed child would show first as a cell
# without a result on the chip. Taken at commit a1cb520 (PR 44).
KEXAONE_NAMES = "af19681b6a77e24f"


def test_kexaone_parameters_keep_their_names_and_shapes(system, weights,
                                                        param_names_hash):
    net = system.build_net(CFG, weights, "float32")
    got, listing = param_names_hash(net)
    assert got == KEXAONE_NAMES, f"{got}\n{listing}"
