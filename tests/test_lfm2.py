"""The third family of ``HybridDecoderLM`` (LFM2's ``lfm2_moe`` block: gated
short convolutions beside grouped-query attention with q/k norm and rotary
positions, pre-norm RMSNorm, a tied head with float32 logits, sparse expert
layers that hold ALL their experts, no shared one, a selection bias that
balances the load) against the plain float32 reference the benchmark keeps
(``benchmark/suite/reference/lfm2.py``, which imports nothing of the
program), at a tiny size on seeded weights with a NONZERO bias draw: the conv
mixer alone, logits, loss, every leaf's gradient, three Adam steps through
``DataParallelTrainer`` with the bias moving equally on both sides; the int8
control has to fail the tolerances."""

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

# the cell's cut at toy widths: conv + dense, then attention + sparse and
# conv + sparse x 2; every one of the 8 experts held, 2 a token
CFG = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
       "head_dim": 16, "intermediate_size": 128, "moe_intermediate_size": 32,
       "num_experts": 8, "published_num_experts": 8,
       "held_experts": list(range(8)), "num_experts_per_tok": 2,
       "num_shared_experts": 0, "conv_L_cache": 3,
       "routed_scaling_factor": 1, "norm_eps": 1e-5, "vocab_size": 96,
       "num_hidden_layers": 4, "num_dense_layers": 1,
       "layer_types": ["conv", "full_attention", "conv", "conv"],
       "rope_theta": 1e6, "tie_embedding": True, "float32_logits": True,
       # NOT zero as in the benchmark's file: a program that ignores the
       # bias, or lets it into the weights, must differ from the reference
       "router_bias_init_std": 0.1, "router_bias_update_rate": 0.03,
       "router_weight_eps": 1e-6}
ADAM = {"lr": 3e-4, "beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8}
# float32 on both sides: what is left is the order of additions (the program
# sums an expert's rows by gathers and repeated-index adds, the reference a
# dense product at a time; the program's convolution adds its taps in one
# order, the reference's in the same). int8 moves each of these numbers far
# past them (asserted below).
TOL_LOGITS = 2e-5       # of the largest logit
TOL_LOSS = 1e-5         # relative
TOL_GRAD = 5e-4         # a leaf's gradient, of that leaf's norm
TOL_DELTA = 2e-3        # a leaf's change over three steps, relative
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
    return _load("reference/lfm2.py", "t_reference_lfm2")


@pytest.fixture(scope="module")
def system():
    return _load("systems/lfm2.py", "t_system_lfm2")


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
    src = open(os.path.join(SUITE, "reference", "lfm2.py")).read()
    assert "mxtpu" not in src and "import system" not in src


@pytest.mark.parametrize("with_bias", [True, False])
def test_causal_conv1d_with_and_without_bias(with_bias):
    """Taps at ``t - 2, t - 1, t``, zeros before row 0; the bias is optional
    (Mamba's layers pass one, the conv mixer none)."""
    rs = np.random.RandomState(6)
    x = rs.randn(2, 9, 5).astype(np.float32)
    w = rs.randn(5, 3).astype(np.float32)
    b = rs.randn(5).astype(np.float32)
    want = np.zeros_like(x)
    for t in range(9):
        for j in range(3):
            if t - 2 + j >= 0:
                want[:, t] += x[:, t - 2 + j] * w[:, j]
    args = (nd.array(x), nd.array(w)) + ((nd.array(b),) if with_bias else ())
    got = nd.contrib.causal_conv1d(*args).asnumpy()
    np.testing.assert_allclose(got, want + (b if with_bias else 0.0),
                               rtol=1e-5, atol=1e-5)
    x2 = x.copy()
    x2[:, 5:] += 1.0        # a later row does not move an earlier one
    got2 = nd.contrib.causal_conv1d(nd.array(x2), *args[1:]).asnumpy()
    np.testing.assert_array_equal(got2[:, :5], got[:, :5])
    assert np.abs(got2[:, 5:] - got[:, 5:]).min() > 0


def test_conv_mixer_forward_gradients_and_causality(ref):
    """``W_out (C * conv(B * u))`` with ``[B, C, u] = W_in x``: the forward
    and every leaf's gradient against the reference, chunks in the order B,
    C, u, and a change at position ``t`` moves nothing before ``t``."""
    from mxtpu.gluon.model_zoo.hybrid_decoder import ShortConv
    rs = np.random.RandomState(4)
    d = 16
    lp = {"conv_in_w": jnp.asarray(rs.randn(3 * d, d) * 0.3, jnp.float32),
          "conv_w": jnp.asarray(rs.randn(d, 3), jnp.float32),
          "conv_out_w": jnp.asarray(rs.randn(d, d) * 0.3, jnp.float32)}
    x = jnp.asarray(rs.randn(2, 12, d), jnp.float32)
    dy = jnp.asarray(rs.randn(2, 12, d), jnp.float32)
    cfg = {"hidden_size": d}
    mixer = ShortConv(d, 3)
    mixer.initialize()
    leaves = {"conv_in_w": mixer.in_proj.weight, "conv_w": mixer.conv_weight,
              "conv_out_w": mixer.out_proj.weight}
    assert not any("bias" in name for name in mixer.collect_params())
    for leaf, p in leaves.items():
        p.set_data(nd.NDArray(lp[leaf]))
        p.data().attach_grad()
    xin = nd.NDArray(x)
    xin.attach_grad()
    with autograd.record():
        out = mixer(xin, {})
        loss = nd.sum(out * nd.NDArray(dy))
    loss.backward()
    want, vjp = jax.vjp(lambda p, x_: ref.conv_sublayer(cfg, p, x_), lp, x)
    want_g, want_dx = vjp(dy)
    np.testing.assert_allclose(np.asarray(out.data), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # the definition itself, by hand: rows t-2, t-1, t of B * u, then C
    bcu = np.asarray(x) @ np.asarray(lp["conv_in_w"]).T
    B, C, u = bcu[..., :d], bcu[..., d:2 * d], bcu[..., 2 * d:]
    taps, bu = np.asarray(lp["conv_w"]), B * u
    v = np.zeros_like(bu)
    for t in range(12):
        for j in range(3):
            if t - 2 + j >= 0:
                v[:, t] += taps[:, j] * bu[:, t - 2 + j]
    np.testing.assert_allclose(np.asarray(want),
                               (C * v) @ np.asarray(lp["conv_out_w"]).T,
                               rtol=1e-4, atol=1e-4)
    for leaf, p in leaves.items():
        np.testing.assert_allclose(np.asarray(p.data().grad.data),
                                   np.asarray(want_g[leaf]), rtol=1e-4,
                                   atol=1e-4, err_msg=leaf)
    np.testing.assert_allclose(np.asarray(xin.grad.data),
                               np.asarray(want_dx), rtol=1e-4, atol=1e-4)
    moved = np.abs(np.asarray(mixer(nd.NDArray(x.at[:, 7].add(1.0)), {}).data
                              - out.data)).max(axis=(0, 2))
    assert not moved[:7].any() and moved[7:10].all() and not moved[10:].any()


def test_logits_loss_and_every_gradient_leaf(ref, system, weights, batch):
    """Conv and attention mixers, pre-norm, the tied head in float32, routing
    by score + bias with weights that leave the bias out and carry the
    source's 1e-6, all 8 experts' grouped products: logits, loss and every
    leaf's gradient; int8 operands fail each tolerance."""
    x, y = batch
    net = system.build_net(CFG, weights, "float32")
    assert net.head is None and net.layer_kinds == ("conv", "attn_full",
                                                    "conv", "conv")
    assert net.mlp_kinds == ("mlp", "moe", "moe", "moe")
    assert net.blocks[1].moe.held == tuple(range(8)) \
        and net.blocks[1].moe.shared is None
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
    failed = 0
    for p, leaf in leaves:
        norm = float(jnp.linalg.norm(want_g[leaf]))
        assert norm > 0, leaf
        gap = float(jnp.linalg.norm(p.data().grad.data - want_g[leaf])) / norm
        assert gap <= TOL_GRAD, (leaf, gap)
        failed += float(jnp.linalg.norm(low_g[leaf] - want_g[leaf])) \
            > 20 * TOL_GRAD * norm
    # int8 moves all but a few small leaves (routers, gains) 20 times past
    # the tolerance
    assert failed >= len(leaves) - 8, failed


def test_three_adam_steps_with_the_bias_moving_on_both_sides(
        ref, system, weights, batch):
    """The reference's half-layer-at-a-time gradient and host Adam (the
    token table's two gradients summed first) against the trainer's one
    program. The selection bias rides the step as an auxiliary state: after
    three steps it equals the reference's, moved by the balancing rule from a
    nonzero draw."""
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
    now = trainer.param_arrays()    # checks 8 * 32 * 2 pairs a layer and step
    steps = [(jnp.asarray(x), jnp.asarray(y))] * 3
    want = ref.train_steps(CFG, ref.make_weights(CFG, 7, "float32"), steps,
                           ADAM, "float32", row_block=8)
    whole = math.sqrt(sum(v * v for v in want["grad_norm"].values()))
    for a, b in zip(losses, want["loss"]):
        assert abs(a - b) <= TOL_LOSS * b
    assert abs(grad_norm - whole) <= TOL_GRAD * whole
    floor = np.median(list(want["delta_norm"].values()))
    assert set(want["delta_norm"]) == set(w0) == set(want["grad_norm"]) \
        | set(want["states"]) and len(want["states"]) == 3
    assert "head" not in w0 and "embed" in w0       # the table once
    for leaf, r in want["delta_norm"].items():
        got = float(np.linalg.norm(now[leaf] - w0[leaf]))
        assert abs(got - r) <= TOL_DELTA * max(r, floor), leaf
    assert losses[2] < losses[0]
    for i in (1, 2, 3):
        leaf = f"layers/router_b/{i}"
        b = net.blocks[i].moe.select_bias.data().asnumpy()
        np.testing.assert_allclose(b, want["states"][leaf], rtol=0, atol=1e-6)
        moved = np.abs(b - np.asarray(weights[leaf]))
        assert 0.029 < moved.max() <= 0.0901 and want["delta_norm"][leaf] > 0
    rows = profiler.get_moe_stats(net)
    assert all(r["pairs"] == 8 * T * 2 and r["passes"] == 1
               and r["held"] == 8 for r in rows) and len(rows) == 3
    import moe as readers
    assert len(readers.STEP_COUNTS) == 3
    assert [float(np.asarray(c).sum()) for c in readers.STEP_COUNTS[-1]] \
        == [8 * T * 2.0] * 3
    # the int8 control fails the three steps' comparison (the whole
    # gradient's norm averages its rounding down to six times the tolerance;
    # the per-leaf gradients of the test above are where it reads 20 times)
    low = ref.train_steps(CFG, ref.make_weights(CFG, 7, "float32"), steps,
                          ADAM, "float32", row_block=8, precision="int8")
    low_whole = math.sqrt(sum(v * v for v in low["grad_norm"].values()))
    worst = max(abs(low["delta_norm"][k] - r) / max(r, floor)
                for k, r in want["delta_norm"].items() if k in
                want["grad_norm"])
    assert abs(low_whole - whole) > 4 * TOL_GRAD * whole \
        and worst > TOL_DELTA


@pytest.mark.parametrize("eps", [0.0, 1e-6, 0.5])
def test_all_held_layer_without_a_shared_expert_is_the_dense_definition(
        ref, eps, monkeypatch):
    """``SparseExperts(held=None)``: every chosen expert is held, so a token
    has exactly ``top_k`` pairs, the buffer is the worst case, one pass; the
    output is the dense definition (every expert over every row, weighted,
    zero where not chosen), the weights over the chosen scores' sum plus
    ``weight_eps``."""
    from mxtpu.parallel.moe import SparseExperts, expert_rows
    monkeypatch.setattr(ref, "ROUTE_EPS", eps)
    rs = np.random.RandomState(9)
    d, Fe, E, k = 32, 16, 8, 4
    z = {"k": k, "E": E, "Fe": Fe, "scale": 1.0}
    lp = {"router_w": jnp.asarray(rs.randn(E, d), jnp.float32),
          "router_b": jnp.asarray(rs.randn(E) * 0.3, jnp.float32),
          "experts_gate_up_w": jnp.asarray(rs.randn(E, d, 2 * Fe) * 0.2,
                                           jnp.float32),
          "experts_down_w": jnp.asarray(rs.randn(E, Fe, d) * 0.2,
                                        jnp.float32)}
    x = jnp.asarray(rs.randn(2, 24, d), jnp.float32)
    blk = SparseExperts(d, Fe, E, k, weight_eps=eps)
    blk.initialize()
    for p, a in ((blk.router, lp["router_w"]),
                 (blk.select_bias, lp["router_b"]),
                 (blk.gate_up, lp["experts_gate_up_w"]),
                 (blk.down, lp["experts_down_w"])):
        p.set_data(nd.NDArray(a))
    assert blk.shared is None and blk.held == tuple(range(E))
    got = blk(nd.NDArray(x)).data
    want, count = ref.experts(z, lp, x.reshape(-1, d), None)
    np.testing.assert_allclose(np.asarray(got).reshape(-1, d),
                               np.asarray(want), rtol=1e-4, atol=2e-5)
    stats = blk.stats()
    assert stats["pairs"] == 48 * k == float(count.sum())
    assert stats["passes"] == 1 and stats["active"] <= E
    assert stats["rows_added"] == 0 and stats["rows_moved"] == 48 * k
    assert stats["load_max"] == float(count.max()) / (48 * k / E) > 1
    assert stats["buffer_rows"] == expert_rows(48, E, k, E) == 48 * k
    np.testing.assert_array_equal(blk.count.data().asnumpy(),
                                  np.asarray(count))
    if eps == 0.5:      # the term is in the weights, not lost in rounding
        monkeypatch.setattr(ref, "ROUTE_EPS", 0.0)
        other, _ = ref.experts(z, lp, x.reshape(-1, d), None)
        assert float(jnp.abs(other - want).max()) > 1e-2


def test_rotary_on_the_attention_layers_and_qk_norm_before_it(ref):
    """Heads of 64, 4 query heads on 1 key/value head: q/k norm with gains
    that are not 1, THEN rotary positions over all 64 dimensions, causal over
    everything. Norm after the positions is another function (the gain
    would scale turned dimensions)."""
    from mxtpu.gluon.model_zoo.hybrid_decoder import gq_attention
    rs = np.random.RandomState(3)
    q = jnp.asarray(rs.randn(2, T, 8, 64), jnp.float32)
    k = jnp.asarray(rs.randn(2, T, 2, 64), jnp.float32)
    v = jnp.asarray(rs.randn(2, T, 2, 64), jnp.float32)
    gq = jnp.asarray(1.0 + 0.5 * rs.randn(64), jnp.float32)
    gk = jnp.asarray(1.0 + 0.5 * rs.randn(64), jnp.float32)
    got = gq_attention(q, k, v, gq, gk, rope_theta=1e6, eps=1e-5)
    rms = ref._rms
    want = ref.attention(ref.rope(rms(q, gq, 1e-5), 1e6),
                         ref.rope(rms(k, gk, 1e-5), 1e6), v, None, None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-6)
    after = ref.attention(rms(ref.rope(q, 1e6), gq, 1e-5),
                          rms(ref.rope(k, 1e6), gk, 1e-5), v, None, None)
    assert float(jnp.abs(after - want).max()) > 1e-2
    # query head h reads key/value head h // 4: moving head 1's keys moves
    # query heads 4..7 alone
    moved = jnp.abs(gq_attention(q, k.at[:, :, 1].add(1.0), v, gq, gk,
                                 rope_theta=1e6)
                    - got).reshape(2, T, 8, 64).max(axis=(0, 1, 3))
    assert not moved[:4].any() and moved[4:].all()


def test_step_carries_scopes_and_kernel_names(ref, system, weights, batch,
                                              monkeypatch):
    x, y = batch
    net = system.build_net(CFG, weights, "float32")
    trainer = system.Trainer(net, ADAM)
    trainer.step(*trainer.place(x, y))
    text = trainer.dpt.lowered().as_text(debug_info=True)
    for scope in ("block0/conv/in_proj", "block0/conv/gate",
                  "block3/conv/out_proj", "block1/attn_full/qkv",
                  "block1/attn_full/qk_norm", "block1/attn_full/rope",
                  "block0/mlp/gate_up", "block1/moe/route",
                  "block2/moe/dispatch", "block2/moe/experts",
                  "block1/moe/combine", "block1/moe/balance", "ln_f", "head",
                  "loss"):
        assert scope in text, scope
    assert "moe/shared" not in text and "attn_window" not in text
    # the tied head's logits are widened before the loss
    logits = net(nd.array(x))
    assert logits.dtype == np.float32
    # on the TPU platform at widths in whole 128s: the grouped-matmul and
    # flash launches by name (heads of 64 are padded to the lanes)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    wide = dict(CFG, hidden_size=256, head_dim=64, intermediate_size=256,
                moe_intermediate_size=128, vocab_size=128)
    net2 = system.build_net(wide, ref.make_weights(wide, 1, "bfloat16"),
                            "bfloat16")

    def loss(tokens):
        with autograd.pause(train_mode=True):
            return jnp.sum(net2(nd.NDArray(tokens)).data)

    lowered = jax.jit(loss).trace(jnp.zeros((1, 128), jnp.int32)).lower(
        lowering_platforms=("tpu",))
    assert set(re.findall(r'kernel_name = "([^"]+)"', lowered.as_text())) \
        == {"flash_fwd", "moe_gmm"}
    assert lowered.out_info.dtype == jnp.float32


def test_the_tied_head_keeps_its_type_unless_the_spec_widens_it():
    """``float32_logits`` is the layer spec's: the default leaves the tied
    dot's logits in the model's type (``phi4-mini-flash``'s step is the
    program it was: ``tests/test_hybrid_decoder.py`` holds its jaxpr)."""
    from mxtpu.gluon.model_zoo.hybrid_decoder import HybridDecoderLM
    tokens = nd.array(np.zeros((1, 8), np.int32))
    for widen, want in ((False, jnp.bfloat16), (True, jnp.float32)):
        net = HybridDecoderLM(32, ["conv"], 16, 32, 2, 1, d_conv=3,
                              norm="rms", float32_logits=widen)
        net.initialize()
        net.cast("bfloat16")
        assert net(tokens).data.dtype == want


def test_decoding_raises_and_names_the_convolution_state(system, weights):
    from mxtpu.gluon.model_zoo.hybrid_decoder import HybridDecoderLM, KINDS
    assert "conv" in KINDS
    net = system.build_net(CFG, weights, "float32")
    with pytest.raises(NotImplementedError, match="trains only") as err:
        net.generate(nd.array(np.zeros((1, 4))), 4)
    assert "conv layer's last d_conv - 1 rows" in str(err.value)
    with pytest.raises(NotImplementedError, match="trains only"):
        net.serving_step()
    with pytest.raises(ValueError, match="unknown layer kind"):
        HybridDecoderLM(32, ["convolution"], 64, 128, 4, 2)


# The mixer protocol and the table of kinds (PR 45) are shared by every
# family: this family's step has to trace to the program it traced to before
# them (hash of the printed jaxpr of loss and gradient at CFG, taken on
# the parent tree, commit a1cb520).
LFM2_STEP = "c93bcad41b2e4138"


def test_lfm2_step_traces_to_the_same_jaxpr(ref, system, weights, batch,
                                            step_jaxpr_hash):
    net = system.build_net(CFG, weights, "float32")
    assert step_jaxpr_hash(net, system, *batch) == LFM2_STEP


# The parameters by attribute path, saved name and shape (tests/conftest.py:
# _param_names_hash): the benchmark's systems/lfm2.py loads the reference's
# weights by these paths, and a renamed child would show first as a cell
# without a result on the chip. Taken at commit a1cb520 (PR 44).
LFM2_NAMES = "6bcee65500ad987a"


def test_lfm2_parameters_keep_their_names_and_shapes(system, weights,
                                                     param_names_hash):
    net = system.build_net(CFG, weights, "float32")
    got, listing = param_names_hash(net)
    assert got == LFM2_NAMES, f"{got}\n{listing}"
