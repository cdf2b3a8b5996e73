"""ZeRO-1 sharded-optimizer data parallelism (mxtpu/parallel/zero.py).

Parity contract: the ZeRO path (bucketed reduce-scatter → 1/N-sharded
optimizer slots → all-gather) must match the replicated-psum path on the same
model/optimizer/batch — through ``DataParallelTrainer`` AND the fused
``Module.fit`` step (kvstore ``device``), with the device feed on, on 1 and 8
(spoofed) devices, including resume-from-checkpoint mid-run. Plus the
observability (``profiler.get_comm_stats``), state-sharding, compression, and
bucket-layout contracts."""

import os

import numpy as np
import pytest

import jax

import mxtpu as mx
from mxtpu import gluon, nd, optimizer, parallel, profiler
from mxtpu.gluon import nn
from mxtpu.gluon.block import HybridBlock
from mxtpu.io import DataBatch, DataDesc, NDArrayIter
from mxtpu.parallel import zero as zero_mod


def _mlp(seed=0, in_units=10, hidden=32, classes=3):
    mx.rng.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(hidden, activation="tanh", in_units=in_units),
            nn.Dense(classes, in_units=hidden))
    net.initialize(init=mx.initializer.Xavier())
    return net


def _sorted_params(net_or_mod):
    if hasattr(net_or_mod, "collect_params"):
        # in registration order: the prefixes count instances per process,
        # and "dense10_" sorts before "dense9_"
        return [p.data().asnumpy()
                for p in net_or_mod.collect_params().values()]
    return [v.asnumpy()
            for _, v in sorted(net_or_mod.get_params()[0].items())]


# ---------------------------------------------------------------------------
# DataParallelTrainer parity
# ---------------------------------------------------------------------------


@pytest.mark.multi_device(8)
@pytest.mark.parametrize("opt_name", ["sgd_momentum", "adam"])
def test_dpt_zero_matches_replicated(dp_mesh, opt_name):
    rs = np.random.RandomState(0)
    X = rs.randn(32, 10).astype(np.float32)
    y = rs.randint(0, 3, 32).astype(np.float32)
    results = {}
    for zero in (False, True):
        net = _mlp()
        opt = (optimizer.SGD(learning_rate=0.1, momentum=0.9)
               if opt_name == "sgd_momentum"
               else optimizer.Adam(learning_rate=0.01))
        dpt = parallel.DataParallelTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), opt, dp_mesh,
            zero=zero)
        losses = [dpt.step(nd.array(X), nd.array(y)) for _ in range(4)]
        results[zero] = (losses, _sorted_params(net))
    np.testing.assert_allclose(results[False][0], results[True][0], rtol=1e-5)
    for a, b in zip(results[False][1], results[True][1]):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


@pytest.mark.multi_device(8)
def test_zero_optimizer_state_is_dp_sharded(dp_mesh):
    from jax.sharding import PartitionSpec as P
    net = _mlp(seed=1)
    dpt = parallel.DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(),
        optimizer.SGD(learning_rate=0.1, momentum=0.9), dp_mesh, zero=True)
    rs = np.random.RandomState(1)
    dpt.step(nd.array(rs.randn(16, 10).astype(np.float32)),
             nd.array(rs.randint(0, 3, 16).astype(np.float32)))
    assert dpt._zero_layout is not None and dpt._zero_states
    for b, st in zip(dpt._zero_layout.buckets, dpt._zero_states):
        for s in st:
            assert s.shape == (b.padded,)
            assert s.sharding.spec == P("dp")
            # each device holds exactly 1/8 of the flat slot
            assert s.sharding.shard_shape(s.shape) == (b.padded // 8,)
    # the headline: per-device state bytes shrink ~N× vs replicated
    net_r = _mlp(seed=1)
    dpt_r = parallel.DataParallelTrainer(
        net_r, gluon.loss.SoftmaxCrossEntropyLoss(),
        optimizer.SGD(learning_rate=0.1, momentum=0.9), dp_mesh, zero=False)
    dpt_r.step(nd.array(rs.randn(16, 10).astype(np.float32)),
               nd.array(rs.randint(0, 3, 16).astype(np.float32)))
    shrink = dpt_r.optimizer_state_bytes() / dpt.optimizer_state_bytes()
    assert shrink > 6.0, shrink     # 8x minus padding slack


@pytest.mark.multi_device(8)
def test_zero_comm_stats_counters(dp_mesh):
    profiler.reset_comm_stats()
    net = _mlp(seed=2)
    dpt = parallel.DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(),
        optimizer.SGD(learning_rate=0.1), dp_mesh, zero=True)
    rs = np.random.RandomState(2)
    X, y = rs.randn(16, 10).astype(np.float32), \
        rs.randint(0, 3, 16).astype(np.float32)
    for _ in range(3):
        dpt.step(nd.array(X), nd.array(y))
    c = profiler.get_comm_stats()
    assert c["zero_steps"] == 3 and c["steps"] == 3 and c["dp"] == 8
    # analytic consistency: 3 steps x (N-1)/N of the bucket bytes, both legs
    per_step = sum(b.nbytes for b in dpt._zero_layout.buckets) * 7 // 8
    assert c["bytes_reduced"] == 3 * per_step
    assert c["bytes_gathered"] == 3 * per_step
    assert c["bucket_count"] == len(dpt._zero_layout.buckets)
    assert c["allreduce_bytes"] == 0
    # replicated leg records the full-allreduce equivalent instead
    net_r = _mlp(seed=2)
    dpt_r = parallel.DataParallelTrainer(
        net_r, gluon.loss.SoftmaxCrossEntropyLoss(),
        optimizer.SGD(learning_rate=0.1), dp_mesh, zero=False)
    profiler.reset_comm_stats()
    dpt_r.step(nd.array(X), nd.array(y))
    cr = profiler.get_comm_stats()
    assert cr["zero_steps"] == 0 and cr["allreduce_bytes"] > 0
    # ZeRO ships ~half the allreduce bytes (RS + AG vs 2x(N-1)/N full grad)
    assert 2 * per_step <= cr["allreduce_bytes"] + 8  # equal modulo padding
    profiler.reset_comm_stats()


@pytest.mark.multi_device(8)
def test_zero_small_buckets_parity(dp_mesh, monkeypatch):
    """A tiny MXTPU_ZERO_BUCKET_MB forces multiple buckets; math unchanged."""
    monkeypatch.setenv("MXTPU_ZERO_BUCKET_MB", "0.0005")   # ~512 bytes
    rs = np.random.RandomState(3)
    X = rs.randn(16, 10).astype(np.float32)
    y = rs.randint(0, 3, 16).astype(np.float32)
    results = {}
    for zero in (False, True):
        net = _mlp(seed=3)
        dpt = parallel.DataParallelTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(),
            optimizer.SGD(learning_rate=0.1, momentum=0.9), dp_mesh,
            zero=zero)
        losses = [dpt.step(nd.array(X), nd.array(y)) for _ in range(3)]
        if zero:
            assert len(dpt._zero_layout.buckets) > 1
        results[zero] = (losses, _sorted_params(net))
    np.testing.assert_allclose(results[False][0], results[True][0], rtol=1e-5)
    for a, b in zip(results[False][1], results[True][1]):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_zero_multi_axis_mesh_engages_and_matches():
    """ZeRO now ENGAGES on a (dp×tp) mesh — the replicated fallback is gone.

    The regression this guards: resolving the gradient reduction on the
    CONCATENATED bucket (``concat`` of partial-sum grads → one sharding
    constraint) mis-reduces on multi-axis meshes (an extra factor-of-tp
    reduction; see ``test_concat_of_partial_sums_misreduces`` in
    test_fsdp.py). The per-param named-axis resolution + shard_map local
    pack must produce params that match an eager single-device run."""
    from jax.sharding import PartitionSpec as P
    mesh = parallel.make_mesh((4, 2), ("dp", "tp"))

    rs = np.random.RandomState(4)
    X = rs.randn(16, 8).astype(np.float32)
    y = rs.randint(0, 2, 16).astype(np.float32)

    def build():
        mx.rng.seed(4)
        net = nn.HybridSequential()
        net.add(nn.Dense(16, activation="relu", in_units=8),
                nn.Dense(2, in_units=16))
        net.initialize(init=mx.initializer.Xavier())
        return net

    net_a = build()
    trainer = gluon.Trainer(net_a.collect_params(), "sgd",
                            {"learning_rate": 0.1}, kvstore=None)
    from mxtpu import autograd
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    for _ in range(2):
        with autograd.record():
            total = nd.mean(loss_fn(net_a(nd.array(X)), nd.array(y)))
        total.backward()
        trainer.step(1)

    net_b = build()
    # key the tp shardings off the actual param names (gluon name counters
    # advance across builds, so hardcoded dense0_/dense1_ suffixes miss)
    tp_specs = {}
    for n, p in net_b.collect_params().items():
        tp_specs[n] = {(16, 8): P("tp", None), (16,): P("tp"),
                       (2, 16): P(None, "tp")}.get(tuple(p.shape))
    dpt = parallel.DataParallelTrainer(
        net_b, gluon.loss.SoftmaxCrossEntropyLoss(),
        optimizer.SGD(learning_rate=0.1), mesh, zero=True,
        param_shardings=lambda n: tp_specs.get(n))
    for _ in range(2):
        dpt.step(nd.array(X), nd.array(y))
    assert dpt.zero                          # engaged, no fallback
    assert dpt._zero_layout is not None
    # the tp-sharded params stay per-param (passthrough); the replicated
    # leftovers (dense1_bias) are bucketed and reduce over BOTH named axes
    assert dpt._zero_layout.buckets and dpt._zero_layout.passthrough
    for a, b in zip(_sorted_params(net_a), _sorted_params(net_b)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_unsupported_optimizer_falls_back():
    """Norm-coupled/noise optimizers must NOT take the bucketed path."""
    assert not zero_mod.supports_zero(optimizer.LBSGD(learning_rate=0.1))
    assert not zero_mod.supports_zero(optimizer.SGLD(learning_rate=0.1))
    assert zero_mod.supports_zero(optimizer.SGD(learning_rate=0.1))
    mesh = parallel.make_mesh((2,), ("dp",))
    net = _mlp(seed=5)
    dpt = parallel.DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(),
        optimizer.LBSGD(learning_rate=0.1), mesh, zero=True)
    assert not dpt.zero                      # silently replicated, not broken
    rs = np.random.RandomState(5)
    l = dpt.step(nd.array(rs.randn(8, 10).astype(np.float32)),
                 nd.array(rs.randint(0, 3, 8).astype(np.float32)))
    assert np.isfinite(l)


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------


def test_unknown_compression_kind_rejected():
    kv = mx.kvstore.create("local")
    with pytest.raises(ValueError, match="supported kinds"):
        kv.set_gradient_compression({"type": "1bit"})
    with pytest.raises(ValueError, match="supported kinds"):
        parallel.DataParallelTrainer(
            _mlp(seed=6), gluon.loss.SoftmaxCrossEntropyLoss(),
            optimizer.SGD(learning_rate=0.1),
            parallel.make_mesh((1,), ("dp",)),
            compression_params={"type": "terngrad"})
    for ok in ("2bit", "fp16", "bf16"):
        mx.kvstore.create("local").set_gradient_compression({"type": ok})


@pytest.mark.multi_device(8)
@pytest.mark.parametrize("kind", ["fp16", "2bit"])
def test_compressed_sync_converges_like_uncompressed(dp_mesh, kind):
    """Error-feedback residual parity: a 2-layer MLP trained with compressed
    gradient sync lands within tolerance of the uncompressed run (the
    residual re-injects the quantization error, so the bias cancels across
    steps — gradient_compression.h's correctness argument)."""
    rs = np.random.RandomState(7)
    X = rs.randn(64, 10).astype(np.float32)
    y = (X.sum(1) > 0).astype(np.float32)
    finals = {}
    for comp in (None, {"type": kind, "threshold": 0.01}):
        net = _mlp(seed=7, classes=2)
        dpt = parallel.DataParallelTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(),
            optimizer.SGD(learning_rate=0.1, momentum=0.9), dp_mesh,
            zero=True, compression_params=comp)
        losses = [dpt.step(nd.array(X), nd.array(y)) for _ in range(25)]
        finals["plain" if comp is None else kind] = losses
        if comp is not None:
            assert all(r is not None for r in dpt._zero_residuals)
    plain = finals["plain"][-1]
    comp_final = finals[kind][-1]
    assert comp_final < finals[kind][0] * 0.7        # it actually converges
    if kind == "fp16":
        # dtype lowering + residual: within tight tolerance of uncompressed
        assert abs(comp_final - plain) < 0.25 * max(plain, 0.05) + 0.05, \
            (plain, comp_final)
    else:
        # 2bit is sign-SGD-like: magnitudes differ, but error feedback keeps
        # it converging toward the same fixpoint region
        assert comp_final < finals[kind][0] * 0.5, (finals[kind][0],
                                                    comp_final)


def test_kvstore_compressed_push_roundtrip():
    """fp16 codes are what crosses _transport; decode + residual keep the
    running sum faithful."""
    kv = mx.kvstore.create("device")
    kv.set_gradient_compression({"type": "fp16"})
    kv.init("w", nd.zeros((4,)))
    seen = {}
    orig = kv._transport

    def spy(payload):
        seen["dtype"] = str(payload.dtype)
        return orig(payload)

    kv._transport = spy
    g = np.array([1.0002441, -2.0, 0.5, 0.25], np.float32)
    kv.push("w", nd.array(g))
    assert seen["dtype"] == "float16"
    out = nd.zeros((4,))
    kv.pull("w", out)
    np.testing.assert_allclose(out.asnumpy(), g.astype(np.float16), rtol=1e-3)
    # residual holds what fp16 dropped
    res = np.asarray(kv._residuals["w"])
    np.testing.assert_allclose(res, g - g.astype(np.float16).astype(np.float32),
                               atol=1e-7)


# ---------------------------------------------------------------------------
# Module.fit (fused StepExecutor) parity — feed on, 1 and 8 devices
# ---------------------------------------------------------------------------


def _fit_once(ndev, zero_env, monkeypatch, epochs=3, resume_dir=None,
              save_dir=None, save_epoch=None):
    monkeypatch.setenv("MXTPU_ZERO", zero_env)
    parallel.set_default_mesh(parallel.make_mesh((ndev,), ("dp",)))
    try:
        rs = np.random.RandomState(11)
        X = rs.randn(64, 10).astype(np.float32)
        y = rs.randint(0, 3, 64).astype(np.float32)
        mx.rng.seed(11)
        mod = mx.Module(_mlp(seed=11), data_names=("data",),
                        label_names=("softmax_label",))
        cbs = []
        if save_dir is not None:
            from mxtpu.callback import do_checkpoint
            from mxtpu.checkpoint import CheckpointManager
            mgr = CheckpointManager(save_dir)
            cbs.append(do_checkpoint(mgr, module=mod, trainer=None))
        it = NDArrayIter(X, y, batch_size=16, shuffle=False)
        mod.fit(it, num_epoch=epochs, kvstore="device",
                optimizer="sgd",
                optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
                eval_metric="ce",
                epoch_end_callback=cbs or None,
                resume_from=resume_dir)
        if save_dir is not None:
            mgr.close()
        return mod, _sorted_params(mod)
    finally:
        parallel.set_default_mesh(None)


@pytest.mark.multi_device(8)
@pytest.mark.parametrize("ndev", [1, 8])
def test_fit_zero_matches_replicated(ndev, monkeypatch, dp_mesh):
    _, pz = _fit_once(ndev, "1", monkeypatch)
    _, pr = _fit_once(ndev, "0", monkeypatch)
    for a, b in zip(pz, pr):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


@pytest.mark.multi_device(8)
def test_fit_zero_resume_midrun_matches_uninterrupted(tmp_path, monkeypatch,
                                                      dp_mesh):
    """Preemption drill with ZeRO on: save at each epoch end, restart from the
    epoch-2 checkpoint, finish — final params match the uninterrupted run
    (sharded slots round-trip through the snapshot)."""
    d = str(tmp_path / "ckpt")
    _, p_full = _fit_once(8, "1", monkeypatch, epochs=4, save_dir=d)
    # the "preempted" restart: same module recipe, resumes at saved epoch
    _, p_resumed = _fit_once(8, "1", monkeypatch, epochs=4, resume_dir=d)
    for a, b in zip(p_full, p_resumed):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


@pytest.mark.multi_device(8)
def test_fit_zero_uses_sharded_slots(monkeypatch, dp_mesh):
    from jax.sharding import PartitionSpec as P
    mod, _ = _fit_once(8, "1", monkeypatch, epochs=1)
    tr = mod._trainer
    assert tr._zero_layout is not None and tr._zero_states
    for b, st in zip(tr._zero_layout.buckets, tr._zero_states):
        for s in st:
            assert s.sharding.spec == P("dp")
    # per-param slots stay empty — state lives ONLY in the shards
    assert all(st is None or st == () for st in tr._states)


# ---------------------------------------------------------------------------
# checkpoint re-shard (dp size change)
# ---------------------------------------------------------------------------


@pytest.mark.multi_device(8)
def test_zero_slots_restore_onto_different_dp_size(tmp_path, monkeypatch,
                                                   dp_mesh):
    from mxtpu.checkpoint import CheckpointManager

    monkeypatch.setenv("MXTPU_ZERO", "1")
    rs = np.random.RandomState(13)
    X = nd.array(rs.randn(16, 10).astype(np.float32))
    y = nd.array(rs.randint(0, 3, 16).astype(np.float32))
    b = DataBatch(data=[X], label=[y])

    def make(ndev):
        parallel.set_default_mesh(parallel.make_mesh((ndev,), ("dp",)))
        mx.rng.seed(13)
        mod = mx.Module(_mlp(seed=13), data_names=("data",),
                        label_names=("softmax_label",))
        mod.bind(data_shapes=[DataDesc("data", (16, 10))],
                 label_shapes=[DataDesc("softmax_label", (16,))])
        mod.init_params()
        mod.init_optimizer(kvstore="device", optimizer="sgd",
                           optimizer_params={"learning_rate": 0.1,
                                             "momentum": 0.9})
        return mod

    d = str(tmp_path / "ckpt")
    try:
        mod8 = make(8)
        for _ in range(3):
            mod8.forward_backward(b)
            mod8.update()
        lay8 = mod8._trainer._zero_layout
        mom8 = np.asarray(jax.device_get(mod8._trainer._zero_states[0][0]))
        mgr = CheckpointManager(d)
        mgr.save(3, module=mod8, trainer=mod8._trainer, blocking=True)
        mgr.close()

        mod4 = make(4)
        CheckpointManager(d).restore(module=mod4, trainer=mod4._trainer)
        assert mod4._trainer._zero_restore is not None
        mod4.forward_backward(b)         # layout builds + adopts the slots
        lay4 = mod4._trainer._zero_layout
        assert lay4.dp == 4 and lay8.dp == 8
        # momentum content survives the re-shard: compare one pre-update
        # unpadded prefix against the freshly-adopted (pre-step) slots? the
        # step above already advanced them once — instead verify via a
        # fresh restore-without-step below
        mod4b = make(4)
        CheckpointManager(d).restore(module=mod4b, trainer=mod4b._trainer)
        exec_ = __import__("mxtpu.step_cache", fromlist=["StepExecutor"])
        se = exec_.StepExecutor(mod4b._block, mod4b._loss, mod4b._trainer)
        se._ensure_placed()
        se._ensure_zero_states()
        mom4 = np.asarray(jax.device_get(mod4b._trainer._zero_states[0][0]))
        # the packed layout interleaves differently per dp degree — compare
        # de-interleaved per-param content, not the raw flat prefix
        b8, b4 = lay8.buckets[0], lay4.buckets[0]
        f8 = np.concatenate(zero_mod._unpack_flat_host(
            mom8, b8.sizes, b8.psizes, lay8.dp))
        f4 = np.concatenate(zero_mod._unpack_flat_host(
            mom4, b4.sizes, b4.psizes, lay4.dp))
        np.testing.assert_allclose(f4, f8, rtol=1e-6)
        mod4.update()                     # and training continues fine
    finally:
        parallel.set_default_mesh(None)


# ---------------------------------------------------------------------------
# at a data degree of 1 the matrices leave the buckets
# (tests/test_one_chip_update.py); a degree above 1 keeps them all
# ---------------------------------------------------------------------------


@pytest.mark.multi_device(8)
def test_degree_eight_step_keeps_its_buckets(dp_mesh):
    profiler.reset_comm_stats()
    net = _mlp(seed=9)
    dpt = parallel.DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(),
        optimizer.Adam(learning_rate=0.01), dp_mesh)
    rs = np.random.RandomState(9)
    X, y = rs.randn(16, 10).astype(np.float32), \
        rs.randint(0, 3, 16).astype(np.float32)
    for _ in range(3):
        dpt.step(nd.array(X), nd.array(y))
    layout = dpt._zero_layout
    assert dpt.zero and len(layout.buckets) == 1 and not layout.passthrough
    assert all(st == () for st in dpt._states)
    assert [s.shape for s in dpt._zero_states[0]] == \
        [(layout.buckets[0].padded,)] * 2
    c = profiler.get_comm_stats()
    assert c["zero_steps"] == c["steps"] == 3 and c["dp"] == 8
    assert c["bucket_count"] == 1 and c["bytes_reduced"] > 0
    text = dpt.lowered().as_text(debug_info=True)
    assert "optimizer/zero" in text
    assert "stablehlo.concatenate" in text
