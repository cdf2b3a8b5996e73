"""FSDP / ZeRO-3 staged sharding (mxtpu.parallel.fsdp + zero) — the
MXTPU_ZERO_STAGE ladder, multi-axis grad reduction, memory-stats accounting,
and fsdp-elastic checkpoint resume.

The multi-axis regression test pins the root cause that used to force a
replicated fallback on ``dp×tp`` meshes: asking the partitioner to reduce a
CONCATENATION of pending-psum gradients over-reduces (each param's partial
sums get summed once per mesh axis), while resolving each param's reduction
per named axis BEFORE the local concat (``with_sharding_constraint`` per
param + a ``shard_map`` local concat — what ``zero.build_grad_pack`` ships)
is exact. With the reduction expressed correctly, the fallback is deleted
and ZeRO engages on every mesh.

NOTE: this module is imported by multiprocessing *spawn* children (the
elastic-resume test pickles its fit fn by reference), so it must not force
device counts at module level — the supervisor controls the child's XLA
flags via ``dp_schedule``.
"""

import os
import signal

import numpy as np
import pytest

import mxtpu as mx
from mxtpu import nd, parallel, profiler
from mxtpu.callback import do_checkpoint
from mxtpu.checkpoint import CheckpointManager
from mxtpu.gluon import nn
from mxtpu.gluon.block import HybridBlock
from mxtpu.io import DataBatch, DataDesc, NDArrayIter
from mxtpu.parallel import fsdp as fsdp_mod
from mxtpu.parallel import zero as zero_mod
from mxtpu.parallel.mesh import P
from mxtpu.resilience import faults, supervise


# ---------------------------------------------------------------------------
# compose_spec unit rules
# ---------------------------------------------------------------------------


@pytest.mark.multi_device(8)
def test_compose_spec_rules(dp_mesh):
    # dim 0 divisible by the fsdp degree -> sharded there
    assert fsdp_mod.compose_spec((64, 16), None, dp_mesh) == P("dp")
    assert fsdp_mod.compose_spec((8,), None, dp_mesh) == P("dp")
    # dim 0 indivisible or too small -> ineligible (replicated, bucketed)
    assert fsdp_mod.compose_spec((4, 32), None, dp_mesh) is None
    assert fsdp_mod.compose_spec((12, 64), None, dp_mesh) is None
    assert fsdp_mod.compose_spec((), None, dp_mesh) is None
    # dim 0 already tp-sharded -> ineligible (dim-0-only rule: never shard a
    # second dim, that would change the matmul reduction order)
    mesh2 = parallel.make_mesh((4, 2), ("dp", "tp"))
    assert fsdp_mod.compose_spec((16, 64), P("tp", None), mesh2) is None
    # unsharded dim 0 composes WITH a tp spec on another dim
    assert fsdp_mod.compose_spec((16, 8), P(None, "tp"), mesh2) \
        == P("dp", "tp")
    # an axis literally named fsdp wins over the last data axis
    mesh3 = parallel.make_mesh((2, 2, 2), ("dp", "fsdp", "tp"))
    assert fsdp_mod.compose_spec((16, 8), None, mesh3) == P("fsdp")


def test_zero_stage_env_clamped(monkeypatch):
    monkeypatch.delenv("MXTPU_ZERO_STAGE", raising=False)
    assert fsdp_mod.zero_stage() == 1
    for raw, want in (("2", 2), ("3", 3), ("0", 1), ("7", 3), ("x", 1)):
        monkeypatch.setenv("MXTPU_ZERO_STAGE", raw)
        assert fsdp_mod.zero_stage() == want


# ---------------------------------------------------------------------------
# multi-axis grad reduction: named-axis packing against one device
# ---------------------------------------------------------------------------


@pytest.mark.multi_device(8)
def test_concat_misreduction_regression_multi_axis():
    """On a (dp, tp) mesh the SHIPPED packing of pending-psum gradients
    (per-param with_sharding_constraint, then a shard_map LOCAL concat over
    the data axes) matches the single-device gradient.

    Until this PR the test first ran the formulation that packing replaced
    (concatenate the pending gradients, then constrain the concat) and
    asserted that it over-reduced 2x, "to document the failure". That was a
    fault of an earlier jaxlib's partitioner, which summed each partial once
    per mesh axis; on the one installation this repo supports (jax / jaxlib
    0.9.0) the old formulation returns ratio 1.0, so the assertion failed
    before the half that guards our code ran. A test of this repo does not
    pin another release's compiler bug: that half is gone."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from mxtpu.parallel.collectives import shard_map_compat

    mesh = parallel.make_mesh((4, 2), ("dp", "tp"))
    rs = np.random.RandomState(0)
    W = jnp.asarray(rs.randn(16, 4).astype(np.float32))
    b = jnp.asarray(rs.randn(4).astype(np.float32))
    X = jnp.asarray(rs.randn(16, 16).astype(np.float32))

    repl = NamedSharding(mesh, P())
    batch = NamedSharding(mesh, P("dp"))

    def loss(params, x):
        return jnp.sum(jnp.tanh(x @ params[0] + params[1]))

    gt = jax.grad(loss)((W, b), X)

    shard1d = NamedSharding(mesh, P("dp"))

    def step_new(params, x):
        g = jax.grad(loss)(params, x)
        parts = [jax.lax.with_sharding_constraint(jnp.ravel(p), shard1d)
                 for p in g]
        cat = shard_map_compat(
            lambda *locs: jnp.concatenate(locs), mesh,
            in_specs=tuple(P("dp") for _ in parts), out_specs=P("dp"),
            check=False)(*parts)
        return jax.lax.with_sharding_constraint(cat, repl)

    out_new = np.asarray(jax.jit(
        step_new, in_shardings=((repl, repl), batch),
        out_shardings=repl)((W, b), jax.device_put(X, batch)))
    # the local concat yields the dp-INTERLEAVED layout (device d owns
    # [W_chunk_d, b_chunk_d]) — same values, bucket order; build the
    # matching ground truth
    dp = 4
    chunks = [np.split(np.ravel(np.asarray(g)), dp) for g in gt]
    gt_interleaved = np.concatenate(
        [np.concatenate([c[d] for c in chunks]) for d in range(dp)])
    np.testing.assert_allclose(out_new, gt_interleaved, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# stage ladder on the fused Module path: bit parity + residency shrink
# ---------------------------------------------------------------------------


class _ParityMLP(HybridBlock):
    def __init__(self):
        super().__init__()
        self.fc1 = nn.Dense(32, activation="tanh", in_units=16)
        self.fc2 = nn.Dense(4, in_units=32)

    def forward(self, x):
        return self.fc2(self.fc1(x))


def _fit_stage_epochs(stage, monkeypatch, epochs=3):
    """Fresh Module fit at the given ZeRO stage; returns (per-epoch param
    byte snapshots, per-batch loss bytes, memory stats)."""
    monkeypatch.setenv("MXTPU_ZERO_STAGE", str(stage))
    profiler.reset_memory_stats()
    mx.rng.seed(0)
    mod = mx.Module(_ParityMLP(), data_names=("data",),
                    label_names=("softmax_label",))
    mod.bind(data_shapes=[DataDesc("data", (32, 16))],
             label_shapes=[DataDesc("softmax_label", (32,))])
    mod.init_params()
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05,
                                         "momentum": 0.9},
                       kvstore="device")
    rs = np.random.RandomState(1)
    batches = [DataBatch(
        data=[nd.array(rs.rand(32, 16).astype(np.float32))],
        label=[nd.array(rs.randint(0, 4, 32).astype(np.float32))])
        for _ in range(2)]
    snaps, losses = [], []
    for _ in range(epochs):
        for b in batches:
            mod.forward_backward(b)
            losses.append(mod._loss_val.asnumpy().tobytes())
            mod.update()
        arg, aux = mod.get_params()
        # construction-order, not name-keyed: gluon name counters are
        # process-global, so each fresh net renames its params
        snaps.append([v.asnumpy() for v in
                      list(arg.values()) + list(aux.values())])
    return snaps, losses, dict(profiler.get_memory_stats())


@pytest.mark.multi_device(8)
def test_stage_ladder_fit_bit_parity_and_shrink(dp_mesh, monkeypatch):
    """The SAME 3-epoch fused fit at stages 1, 2 and 3: stages 1 and 2 are
    BIT-identical in every loss and every parameter at every epoch boundary;
    stage 3 agrees with them to a few float32 ulp, while its per-device
    param+slot residency is >=4x below the replicated figures from
    get_memory_stats().

    Where the arithmetic order is unchanged the comparison is ``==``: at
    micro_batches=1 stage 2 is stage 1's program. Stage 3 is another
    program: it keeps the eligible parameters sharded, so their gradients
    are reduce-scattered parameter by parameter in the parameter's own
    layout instead of inside stage 1's packed bucket, and the eight devices'
    partial sums are associated in another order. On jax / jaxlib 0.9.0
    that moves the last bit of a gradient from the first update on (it did
    not on the jaxlib this test was written against, which asserted
    ``l1 == l2 == l3``). Measured here over the 3 epochs with momentum 0.9:
    at most 1 ulp in a loss, at most 1 ulp of a tensor's largest element in
    a parameter. The bound is 4 ulp, set by the dtype: a few roundings of
    float32 and nothing a real divergence (a lost shard, a double
    reduction) could hide behind."""
    parallel.set_default_mesh(dp_mesh)
    try:
        s1, l1, m1 = _fit_stage_epochs(1, monkeypatch)
        s2, l2, m2 = _fit_stage_epochs(2, monkeypatch)
        s3, l3, m3 = _fit_stage_epochs(3, monkeypatch)
    finally:
        parallel.set_default_mesh(None)
    tol = 4 * float(np.finfo(np.float32).eps)
    assert l1 == l2
    for step, (a, c) in enumerate(zip(l1, l3)):
        np.testing.assert_allclose(
            np.frombuffer(c, np.float32), np.frombuffer(a, np.float32),
            rtol=tol, atol=0, err_msg=f"stage 3 loss, step {step}")
    for epoch, (a, b, c) in enumerate(zip(s1, s2, s3)):
        assert [x.tobytes() for x in a] == [x.tobytes() for x in b], \
            f"stage 2 diverged from stage 1 at epoch {epoch}"
        for x, z in zip(a, c):
            np.testing.assert_allclose(
                z, x, rtol=0, atol=tol * float(np.abs(x).max()),
                err_msg=f"stage 3 parameter, epoch {epoch}")
    assert m1["stage"] == 1 and m2["stage"] == 2 and m3["stage"] == 3
    assert m3["fsdp_degree"] == 8 and m3["data_degree"] == 8
    # stage 3 holds the eligible params 1/N resident
    assert m3["param_bytes_per_device"] < m1["param_bytes_per_device"]
    # stage 2+ holds grads reduce-scattered
    assert m2["grad_bytes_per_device"] * 7 < m1["grad_bytes_per_device"] * 8
    repl = m3["replicated_param_bytes"] + m3["replicated_slot_bytes"]
    dev = m3["param_bytes_per_device"] + m3["slot_bytes_per_device"]
    assert repl >= 4 * dev, (repl, dev, m3)


@pytest.mark.multi_device(8)
def test_stage3_memory_line_in_profiler_surfaces(dp_mesh, monkeypatch):
    """get_memory_stats flows into compile_cache_summary() and dumps()."""
    import json

    parallel.set_default_mesh(dp_mesh)
    try:
        _fit_stage_epochs(3, monkeypatch, epochs=1)
    finally:
        parallel.set_default_mesh(None)
    summary = profiler.compile_cache_summary()
    assert "memory: zero-stage=3" in summary
    doc = json.loads(profiler.dumps())
    assert doc["memory"]["stage"] == 3
    assert doc["memory"]["param_bytes_per_device"] > 0


# ---------------------------------------------------------------------------
# dp x fsdp composition: batch over both data axes, params on fsdp only
# ---------------------------------------------------------------------------


@pytest.mark.multi_device(8)
def test_stage3_on_dp_fsdp_mesh(monkeypatch):
    """HSDP layout on a ('dp', 'fsdp') 2D mesh: the batch shards over BOTH
    data axes (degree 8) while stage-3 params shard over the fsdp axis only
    (degree 2, replicated across dp) — and training still matches the
    eager single-device reference."""
    from mxtpu import autograd, gluon, optimizer
    from mxtpu.gluon.loss import SoftmaxCrossEntropyLoss

    monkeypatch.setenv("MXTPU_ZERO_STAGE", "3")
    mesh = parallel.make_mesh((4, 2), ("dp", "fsdp"))
    rs = np.random.RandomState(0)
    X = rs.randn(32, 16).astype(np.float32)
    y = rs.randint(0, 4, 32).astype(np.float32)

    def build():
        mx.rng.seed(4)
        net = nn.HybridSequential()
        net.add(nn.Dense(32, activation="tanh", in_units=16),
                nn.Dense(4, in_units=32))
        net.initialize(init=mx.initializer.Xavier())
        return net

    # eager single-device reference
    net_ref = build()
    trainer = gluon.Trainer(net_ref.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9},
                            kvstore="local")
    loss_fn = SoftmaxCrossEntropyLoss()
    for _ in range(3):
        with autograd.record():
            total = nd.mean(loss_fn(net_ref(nd.array(X)), nd.array(y)))
        total.backward()
        trainer.step(1, ignore_stale_grad=True)

    profiler.reset_memory_stats()
    net = build()
    dpt = parallel.DataParallelTrainer(
        net, SoftmaxCrossEntropyLoss(),
        optimizer.SGD(learning_rate=0.1, momentum=0.9), mesh, zero=True)
    for _ in range(3):
        dpt.step(nd.array(X), nd.array(y))

    assert dpt.zero and dpt.stage == 3
    m = profiler.get_memory_stats()
    assert m["data_degree"] == 8 and m["fsdp_degree"] == 2
    # params replicate across dp, shard across fsdp -> 1/2 resident (plus
    # the ineligible fc2 tail)
    assert m["param_bytes_per_device"] < m["replicated_param_bytes"]
    # batch must shard over BOTH data axes
    sharded = parallel.shard_batch(nd.array(X), mesh).data
    assert sharded.sharding.shard_shape(sharded.shape)[0] == X.shape[0] // 8
    # in the order the blocks registered them, the same in both nets:
    # sorted by name, "dense9_" and "dense10_" change places with however
    # many layers the worker's earlier tests built
    for pr, pn in zip(net_ref.collect_params().values(),
                      net.collect_params().values()):
        np.testing.assert_allclose(pr.data().asnumpy(),
                                   pn.data().asnumpy(),
                                   rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# fsdp-elastic resume: stage-3 fit killed at 8 devices, resumed at 4
# ---------------------------------------------------------------------------

_EPOCHS = 2


def _fsdp_train(save_dir):
    """Stage-3 fit (env set by the caller / inherited by spawn children) on
    a ('dp',) mesh over however many devices this process has."""
    import jax
    ndev = len(jax.devices())
    parallel.set_default_mesh(parallel.make_mesh((ndev,), ("dp",)))
    try:
        rs = np.random.RandomState(11)
        X = rs.randn(64, 16).astype(np.float32)
        y = rs.randint(0, 4, 64).astype(np.float32)
        mx.rng.seed(11)
        mod = mx.Module(_ParityMLP(), data_names=("data",),
                        label_names=("softmax_label",))
        mgr = CheckpointManager(save_dir)
        try:
            it = NDArrayIter(X, y, batch_size=16, shuffle=False)
            mod.fit(it, num_epoch=_EPOCHS, kvstore="device",
                    optimizer="sgd",
                    optimizer_params={"learning_rate": 0.1,
                                      "momentum": 0.9},
                    eval_metric="ce",
                    epoch_end_callback=do_checkpoint(mgr, module=mod),
                    resume_from=mgr)
            mgr.wait_until_finished()
        finally:
            mgr.close()
        arg, aux = mod.get_params()
        return [v.asnumpy() for v in list(arg.values()) + list(aux.values())]
    finally:
        parallel.set_default_mesh(None)


def _fsdp_supervised_fit(ctx):
    """Process-mode attempt body (module-level: spawn pickles by ref)."""
    os.environ["MXTPU_ZERO_STAGE"] = "3"
    params = _fsdp_train(ctx.directory)
    np.savez(os.path.join(ctx.directory, "result.npz"), *params)


@pytest.mark.multi_device(8)
def test_fsdp_elastic_resume_8_to_4(tmp_path, monkeypatch):
    """A stage-3 (FSDP) fit is SIGKILLed mid-run on 8 devices; the elastic
    supervisor respawns it on 4 (dp_schedule rewrites the device-count
    flag). Restore re-places fsdp8-sharded params/slots onto the fsdp4 mesh
    (snapshot specs re-resolved; bucket slots de-interleaved and re-packed
    by adopt_states) and the resumed run lands on the uninterrupted
    8-device result within the documented cross-degree tolerance."""
    monkeypatch.setenv("MXTPU_ZERO_STAGE", "3")
    monkeypatch.setenv("MXTPU_RETRY_BACKOFF_S", "0.01")
    baseline = _fsdp_train(str(tmp_path / "base"))

    monkeypatch.setenv(faults.ENV_PLAN, "site=step:at=2:kind=kill:attempt=1")
    faults.reset_fault_plan()
    try:
        res = supervise(_fsdp_supervised_fit, directory=str(tmp_path),
                        mode="process", dp_schedule=[8, 4],
                        restart_backoff_s=0.05, attempt_timeout_s=300)
    finally:
        faults.reset_fault_plan()
    assert res.restarts == 1
    assert -signal.SIGKILL in res.exit_codes and res.exit_codes[-1] == 0
    data = np.load(os.path.join(str(tmp_path), "result.npz"))
    got = [data[k] for k in data.files]
    assert len(got) == len(baseline)
    for g, w in zip(got, baseline):
        # dp8 -> dp4 changes the reduction degree: documented tolerance,
        # same contract as the ZeRO dp-elastic crash-matrix cells
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# checkpoint spec filtering for vanished mesh axes
# ---------------------------------------------------------------------------


def test_restored_array_drops_unknown_axes(tmp_path):
    from mxtpu.checkpoint import snapshot as snap_mod

    assert snap_mod._filter_spec_for_mesh(
        ["fsdp", None], parallel.make_mesh((1,), ("dp",))) == [None, None]
    assert snap_mod._filter_spec_for_mesh(
        [["dp", "fsdp"], None],
        parallel.make_mesh((1,), ("dp",))) == [["dp"], None]
    assert snap_mod._filter_spec_for_mesh(
        ["dp", "tp"],
        parallel.make_mesh((1, 1), ("dp", "tp"))) == ["dp", "tp"]
