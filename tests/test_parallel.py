"""Parallelism tests on the 8-virtual-device CPU mesh (the reference's multi-process
"local launcher" tier, SURVEY.md §4, reimagined as sharding tests)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxtpu as mx
from mxtpu import autograd, gluon, nd, optimizer, parallel
from mxtpu.gluon import nn


def test_eight_devices_present():
    assert len(jax.devices()) == 8


@pytest.mark.multi_device(8)
def test_allreduce_array(dp_mesh):
    x = jnp.ones((4,))
    out = parallel.allreduce_array(x, dp_mesh)
    np.testing.assert_allclose(np.asarray(out), 8.0)
    out_mean = parallel.allreduce_array(x, dp_mesh, op="mean")
    np.testing.assert_allclose(np.asarray(out_mean), 1.0)


@pytest.mark.multi_device(8)
def test_allgather_and_reduce_scatter(dp_mesh):
    x = jnp.arange(16.0).reshape(16, 1)
    sharded = parallel.shard_batch(nd.array(np.arange(16, dtype=np.float32)
                                            .reshape(16, 1)), dp_mesh)
    gathered = parallel.allgather_array(sharded.data, dp_mesh)
    np.testing.assert_allclose(np.asarray(gathered), np.asarray(x))
    rs = parallel.reduce_scatter_array(jnp.ones((16, 1)), dp_mesh)
    np.testing.assert_allclose(np.asarray(rs), 8.0)


@pytest.mark.multi_device(8)
def test_barrier(dp_mesh):
    assert parallel.barrier(dp_mesh) == 8.0


@pytest.mark.multi_device(8)
def test_shard_batch_layout(dp_mesh):
    mesh = dp_mesh
    x = nd.array(np.random.rand(16, 3).astype(np.float32))
    sx = parallel.shard_batch(x, mesh)
    assert sx.shape == (16, 3)
    np.testing.assert_allclose(sx.asnumpy(), x.asnumpy())
    # sharded over dp: addressable shard is 2 rows
    shards = sx.data.addressable_shards
    assert len(shards) == 8 and shards[0].data.shape == (2, 3)


@pytest.mark.multi_device(8)
def test_data_parallel_trainer_matches_serial(dp_mesh):
    """DP-sharded step ≈ serial large-batch step (the dist_sync consistency check,
    tests/nightly/dist_sync_kvstore.py re-imagined)."""
    mesh = dp_mesh

    def build():
        mx.rng.seed(0)
        net = nn.HybridSequential()
        net.add(nn.Dense(16, activation="tanh", in_units=8), nn.Dense(2, in_units=16))
        net.initialize(init=mx.initializer.Xavier())
        return net

    rs = np.random.RandomState(0)
    X = rs.randn(32, 8).astype(np.float32)
    y = rs.randint(0, 2, 32).astype(np.float32)

    # serial reference
    net_a = build()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net_a.collect_params(), "sgd",
                            {"learning_rate": 0.1}, kvstore=None)
    for _ in range(3):
        with autograd.record():
            l = loss_fn(net_a(nd.array(X)), nd.array(y))
            total = nd.mean(l)
        total.backward()
        # match DataParallelTrainer's mean-loss gradient scaling
        trainer.step(1)

    # sharded
    net_b = build()
    dpt = parallel.DataParallelTrainer(net_b, gluon.loss.SoftmaxCrossEntropyLoss(),
                                       optimizer.SGD(learning_rate=0.1), mesh)
    for _ in range(3):
        dpt.step(nd.array(X), nd.array(y))

    pa = {k.split("_", 1)[-1]: p for k, p in net_a.collect_params().items()}
    pb = {k.split("_", 1)[-1]: p for k, p in net_b.collect_params().items()}
    for k in pa:
        np.testing.assert_allclose(pa[k].data().asnumpy(), pb[k].data().asnumpy(),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.multi_device(8)
def test_dp_trainer_loss_decreases(dp_mesh):
    mesh = dp_mesh
    mx.rng.seed(1)
    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu", in_units=10), nn.Dense(2, in_units=32))
    net.initialize(init=mx.initializer.Xavier())
    rs = np.random.RandomState(1)
    X = rs.randn(64, 10).astype(np.float32)
    y = (X.sum(1) > 0).astype(np.float32)
    dpt = parallel.DataParallelTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                                       optimizer.Adam(learning_rate=0.01), mesh)
    losses = [dpt.step(nd.array(X), nd.array(y)) for _ in range(30)]
    assert losses[-1] < losses[0] * 0.5, losses[::10]


def test_kvstore_tpu_type_reduce():
    kv = mx.kvstore.create("device")  # → tpu alias
    kv.init("x", nd.zeros((2,)))
    kv.push("x", [nd.ones((2,))] * 4)
    out = nd.zeros((2,))
    kv.pull("x", out)
    np.testing.assert_allclose(out.asnumpy(), 4.0)


def test_mesh_2d():
    mesh = parallel.make_mesh((4, 2), ("dp", "tp"))
    assert mesh.shape == {"dp": 4, "tp": 2}


def test_dp_tp_trainer_matches_serial():
    """(dp×tp) mesh with gluon-integrated tensor-parallel param shardings must match
    the serial step numerically (GSPMD inserts the tp psum; ctx_group-equivalent)."""
    from jax.sharding import PartitionSpec as P
    mesh = parallel.make_mesh((4, 2), ("dp", "tp"))

    def build():
        mx.rng.seed(3)
        net = nn.HybridSequential()
        net.add(nn.Dense(16, activation="relu", in_units=8), nn.Dense(2, in_units=16))
        net.initialize(init=mx.initializer.Xavier())
        return net

    rs = np.random.RandomState(3)
    X = rs.randn(16, 8).astype(np.float32)
    y = rs.randint(0, 2, 16).astype(np.float32)

    net_a = build()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net_a.collect_params(), "sgd",
                            {"learning_rate": 0.1}, kvstore=None)
    for _ in range(2):
        with autograd.record():
            total = nd.mean(loss_fn(net_a(nd.array(X)), nd.array(y)))
        total.backward()
        trainer.step(1)

    net_b = build()
    dpt = parallel.DataParallelTrainer(
        net_b, gluon.loss.SoftmaxCrossEntropyLoss(),
        optimizer.SGD(learning_rate=0.1), mesh,
        param_shardings={"dense0_weight": P("tp", None), "dense0_bias": P("tp"),
                         "dense1_weight": P(None, "tp")})
    for _ in range(2):
        dpt.step(nd.array(X), nd.array(y))

    pa = {k.split("_", 1)[-1]: p for k, p in net_a.collect_params().items()}
    pb = {k.split("_", 1)[-1]: p for k, p in net_b.collect_params().items()}
    for k in pa:
        np.testing.assert_allclose(pa[k].data().asnumpy(), pb[k].data().asnumpy(),
                                   rtol=1e-4, atol=1e-5)


def test_micro_batch_accumulation_matches_full_batch():
    """micro_batches=k: the optimizer sees the mean full-batch gradient, so a
    BN-free net must train identically (up to fp tolerance) to the k=1 step;
    activation memory shrinks k-fold (the large-batch HBM-capacity cure)."""
    import numpy as np

    import mxtpu as mx
    from mxtpu import gluon, nd, optimizer, parallel
    from mxtpu.gluon import nn

    rs = np.random.RandomState(0)
    X = rs.randn(16, 6).astype(np.float32)
    y = rs.randint(0, 3, 16).astype(np.float32)

    def make():
        mx.rng.seed(7)
        net = nn.HybridSequential()
        net.add(nn.Dense(8, activation="tanh", in_units=6),
                nn.Dense(3, in_units=8))
        net.initialize(init=mx.initializer.Xavier())
        return net

    mesh = parallel.make_mesh((1,), ("dp",))
    losses = {}
    params = {}
    for k in (1, 4):
        net = make()
        dpt = parallel.DataParallelTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(),
            optimizer.SGD(learning_rate=0.5), mesh, micro_batches=k)
        ls = [dpt.step(nd.array(X), nd.array(y)) for _ in range(3)]
        losses[k] = ls
        # auto-naming differs between the two nets — compare in the order
        # the layers registered them (sorted names swap "dense9_" / "dense10_")
        params[k] = [p.data().asnumpy()
                     for p in net.collect_params().values()]
    np.testing.assert_allclose(losses[1], losses[4], rtol=1e-5)
    for a, b in zip(params[1], params[4]):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_micro_batch_with_remat_compiles():
    import numpy as np

    import mxtpu as mx
    from mxtpu import gluon, nd, optimizer, parallel
    from mxtpu.gluon import nn

    net = nn.HybridSequential()
    net.add(nn.Dense(4, in_units=5))
    net.initialize()
    mesh = parallel.make_mesh((1,), ("dp",))
    dpt = parallel.DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(),
        optimizer.SGD(learning_rate=0.1), mesh, micro_batches=2, remat=True)
    rs = np.random.RandomState(1)
    l1 = dpt.step(nd.array(rs.randn(8, 5).astype(np.float32)),
                  nd.array(rs.randint(0, 4, 8).astype(np.float32)))
    assert np.isfinite(l1)


def test_ulysses_matches_single_device_and_ring():
    """All-to-all sequence parallelism (parallel/ulysses.py): output over an
    8-way sp mesh matches the single-device oracle AND ring attention, plain
    and causal."""
    import numpy as np

    from mxtpu import nd, parallel
    from mxtpu.ops.attention import flash_chunk

    n = 8
    mesh = parallel.make_mesh((n,), ("sp",))
    rs = np.random.RandomState(0)
    B, H, T, D = 2, 8, 64, 16
    q = rs.randn(B, H, T, D).astype(np.float32) * 0.5
    k = rs.randn(B, H, T, D).astype(np.float32) * 0.5
    v = rs.randn(B, H, T, D).astype(np.float32) * 0.5

    for causal in (False, True):
        oracle = np.asarray(flash_chunk(q, k, v, causal, 1.0 / D ** 0.5)[0])
        out_u = parallel.ulysses_self_attention(
            nd.array(q), nd.array(k), nd.array(v), mesh=mesh, causal=causal)
        np.testing.assert_allclose(out_u.asnumpy(), oracle, rtol=2e-4,
                                   atol=2e-5)
        out_r = parallel.ring_self_attention(
            nd.array(q), nd.array(k), nd.array(v), mesh=mesh, causal=causal)
        np.testing.assert_allclose(out_u.asnumpy(), out_r.asnumpy(),
                                   rtol=2e-4, atol=2e-5)


def test_ulysses_rejects_head_scarce():
    import numpy as np
    import pytest as _pytest

    from mxtpu import parallel

    mesh = parallel.make_mesh((8,), ("sp",))
    q = np.zeros((1, 4, 64, 8), np.float32)     # 4 heads < 8 devices
    with _pytest.raises(ValueError, match="divisible"):
        parallel.ulysses_self_attention(q, q, q, mesh=mesh)


def test_ulysses_gradients_flow():
    import numpy as np

    from mxtpu import autograd, nd, parallel

    mesh = parallel.make_mesh((8,), ("sp",))
    rs = np.random.RandomState(1)
    q = nd.array(rs.randn(1, 8, 32, 8).astype(np.float32) * 0.5)
    k = nd.array(rs.randn(1, 8, 32, 8).astype(np.float32) * 0.5)
    v = nd.array(rs.randn(1, 8, 32, 8).astype(np.float32) * 0.5)
    for h in (q, k, v):
        h.attach_grad()
    with autograd.record():
        out = parallel.ulysses_self_attention(q, k, v, mesh=mesh)
        loss = nd.sum(nd.square(out))
    loss.backward()
    for h in (q, k, v):
        g = h.grad.asnumpy()
        assert np.isfinite(g).all() and np.abs(g).max() > 0
