"""One chip shards nothing (PR 25): at a data degree of 1
``DataParallelTrainer`` packs no matrix into a ZeRO bucket. Every matrix
takes the per-parameter update in its own shape and layout; only leaves that
are flat already (biases, norm scales) stay bucketed, because their packing
is a plain copy and one buffer of many spares the runtime an allocation a
leaf a step. The numbers are the ones the buckets gave, bit for bit.
Gradient compression keeps every bucket: its residual lives on them."""

import re

import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec as P

import mxtpu as mx
from mxtpu import nd, optimizer, parallel, profiler
from mxtpu.gluon.loss import SoftmaxCrossEntropyLoss
from mxtpu.gluon.model_zoo.transformer import TransformerLM
from mxtpu.parallel import DataParallelTrainer
from mxtpu.parallel import zero as zero_mod
from mxtpu.parallel.mesh import data_parallel_mesh

OPTIMIZERS = {
    "sgd_momentum": lambda: optimizer.SGD(learning_rate=0.05, momentum=0.9,
                                          wd=1e-3),
    "adam": lambda: optimizer.Adam(learning_rate=1e-3),
    # a scalar slot beside the two moments
    "nadam": lambda: optimizer.Nadam(learning_rate=1e-3),
}
# slots a parameter, of them in the parameter's own shape
SLOTS = {"sgd_momentum": (1, 1), "adam": (2, 2), "nadam": (3, 2)}


def _seq_loss(logits, y):
    b, t, v = logits.shape
    return SoftmaxCrossEntropyLoss()(logits.reshape((b * t, v)),
                                     y.reshape((b * t,)))


def _trainer(opt_name, mesh=None, seed=0, **kwargs):
    rs = np.random.RandomState(seed)
    mx.rng.seed(seed)
    net = TransformerLM(50, units=32, num_layers=2, num_heads=2, max_len=16,
                        ffn_units=64)
    net.initialize()
    dpt = DataParallelTrainer(net, _seq_loss, OPTIMIZERS[opt_name](),
                              mesh or data_parallel_mesh(1), **kwargs)
    x = nd.array(rs.randint(0, 50, (8, 16)))
    y = nd.array(rs.randint(0, 50, (8, 16)).astype(np.float32))
    return dpt, x, y


def _force_buckets(dpt):
    """Put ``dpt`` onto the bucket layout whatever its mesh: the reference
    the per-parameter update is held against, built from ``ZeroLayout`` and
    ``init_zero_states`` as ``_collect`` builds it at a degree above 1."""
    collect = dpt._collect

    def collect_into_buckets(x):
        collect(x)
        handles = dpt._param_handles
        raws = [p.data().data for p in handles]
        layout = zero_mod.ZeroLayout(
            raws, [getattr(p, "lr_mult", 1.0) for p in handles],
            [getattr(p, "wd_mult", 1.0) for p in handles], 1)
        dpt._zero_layout = layout
        dpt._zero_states, dpt._zero_residuals = zero_mod.init_zero_states(
            dpt.optimizer, layout, raws, dpt.mesh)
        dpt._zero_state_sh = zero_mod.state_shardings(
            layout, dpt._zero_states, dpt.mesh)
        dpt._states = [()] * len(handles)
        dpt._state_sh = [()] * len(handles)

    dpt._collect = collect_into_buckets
    return dpt


def _after_three_steps(dpt, x, y):
    losses = [dpt.step(x, y) for _ in range(3)]
    params = [np.asarray(p.data().data) for p in dpt._param_handles]
    state = dpt.optimizer_state_by_param()
    slots = [[np.asarray(s) for s in state[n]] for n in dpt._param_names]
    return losses, params, slots


@pytest.fixture(scope="module")
def one_chip():
    """``{optimizer: (trainer, x, y, losses, params, slots)}`` of the default
    trainer on a one-device mesh after three steps, made on first use."""
    made = {}

    def get(opt_name):
        if opt_name not in made:
            profiler.reset_comm_stats()
            dpt, x, y = _trainer(opt_name)
            made[opt_name] = (dpt, x, y) + _after_three_steps(dpt, x, y) \
                + (profiler.get_comm_stats(),)
        return made[opt_name]

    return get


@pytest.mark.parametrize("opt_name", sorted(OPTIMIZERS))
def test_one_chip_update_is_bit_identical_to_the_buckets(one_chip, opt_name):
    _, _, _, losses, params, slots, _ = one_chip(opt_name)
    ref, x, y = _trainer(opt_name)
    _force_buckets(ref)
    ref_losses, ref_params, ref_slots = _after_three_steps(ref, x, y)
    assert ref._zero_layout.buckets and not ref._zero_layout.passthrough
    assert ref.optimizer_slots()[0].ndim <= 1        # flat bucket slots
    assert losses == ref_losses
    assert any(np.abs(s).max() > 0 for st in slots for s in st)
    for name, a, b in zip(ref._param_names, params, ref_params):
        np.testing.assert_array_equal(a, b, err_msg=name)
    for name, sa, sb in zip(ref._param_names, slots, ref_slots):
        assert len(sa) == len(sb) == SLOTS[opt_name][0]
        for a, b in zip(sa, sb):
            np.testing.assert_array_equal(a, b, err_msg=name)


@pytest.mark.parametrize("opt_name", sorted(OPTIMIZERS))
def test_one_chip_update_is_bit_identical_to_zero_off(one_chip, opt_name):
    _, _, _, losses, params, slots, _ = one_chip(opt_name)
    off, x, y = _trainer(opt_name, zero=False)
    off_losses, off_params, off_slots = _after_three_steps(off, x, y)
    assert off._zero_layout is None
    assert losses == off_losses
    for name, a, b in zip(off._param_names, params, off_params):
        np.testing.assert_array_equal(a, b, err_msg=name)
    for name, sa, sb in zip(off._param_names, slots, off_slots):
        for a, b in zip(sa, sb):
            np.testing.assert_array_equal(a, b, err_msg=name)


@pytest.mark.parametrize("opt_name", sorted(OPTIMIZERS))
def test_one_chip_layout_buckets_only_flat_leaves(one_chip, opt_name):
    dpt, _, _, _, _, _, comm = one_chip(opt_name)
    assert dpt.zero
    layout = dpt._zero_layout
    shapes = [p.data().shape for p in dpt._param_handles]
    flat = [i for i, sh in enumerate(shapes) if len(sh) <= 1]
    matrices = [i for i, sh in enumerate(shapes) if len(sh) > 1]
    assert flat and matrices
    assert layout.passthrough == matrices
    assert sorted(i for b in layout.buckets for i in b.indices) == flat
    assert len(layout.buckets) == 1 and dpt._zero_residuals == [None]
    per_param, shaped = SLOTS[opt_name]
    for i, st in enumerate(dpt._states):
        if i in flat:
            assert st == ()
            continue
        # a matrix's slots are its own, in its own shape
        assert len(st) == per_param
        assert [s.shape for s in st[:shaped]] == [shapes[i]] * shaped
        assert all(s.shape == () for s in st[shaped:])
    bucket = layout.buckets[0]
    assert [s.shape for s in dpt._zero_states[0]] == \
        [(bucket.padded,)] * shaped + [()] * (per_param - shaped)
    assert len(dpt.optimizer_slots()) == per_param * (len(matrices) + 1)
    by_param = dpt.optimizer_state_by_param()
    for i, name in enumerate(dpt._param_names):
        assert [s.shape for s in by_param[name][:shaped]] == \
            [shapes[i]] * shaped
        if i in matrices:           # handed out as carried, not unpacked
            assert all(a is b for a, b in zip(by_param[name],
                                              dpt._states[i])), name
    assert dpt.optimizer_state_bytes() == sum(
        int(np.prod(s.shape)) * s.dtype.itemsize
        for s in dpt.optimizer_slots())
    # the counter that says the mechanism engaged: the bytes that go through
    # buckets are the flat leaves' alone
    itemsize = np.dtype(str(dpt._param_handles[0].data().dtype)).itemsize
    flat_bytes = sum(int(np.prod(shapes[i])) for i in flat) * itemsize
    all_bytes = sum(int(np.prod(sh)) for sh in shapes) * itemsize
    assert comm["shard_bytes_per_device"] == flat_bytes < all_bytes // 10
    assert comm["steps"] == comm["zero_steps"] == 3
    assert comm["bucket_count"] == 1 and comm["dp"] == 1
    for k in ("bytes_reduced", "bytes_gathered", "allreduce_bytes"):
        assert comm[k] == 0, k


def _optimizer_ops(dpt):
    """``[(operation, scope path, line)]`` of the lowered step's operations
    under the ``optimizer`` scope."""
    text = dpt.lowered().as_text(debug_info=True)
    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    ops = []
    for line in text.splitlines():
        m = re.search(r'= "?(stablehlo\.[\w.]+|[\w.]+)"?.* loc\((#loc\d+)\)$',
                      line)
        if m and "/optimizer/" in locs.get(m.group(2), ""):
            ops.append((m.group(1), locs[m.group(2)], line))
    return ops


_PACKING = ("concatenate", "dynamic_update_slice", "reshape", "slice", "pad")


def _has_matrix(line):
    """Whether an operation touches a tensor with two or more dimensions
    above 1 (the bucket views a flat leaf as ``[1, n]``: still flat)."""
    return any(sum(int(d) > 1 for d in dims.split("x")[:-1]) > 1
               for dims in re.findall(r"tensor<((?:\d+x)+)", line))


def test_one_chip_step_packs_no_matrix_under_the_optimizer_scope(one_chip):
    dpt = one_chip("adam")[0]
    ops = _optimizer_ops(dpt)
    own = [(op, line) for op, scope, line in ops
           if "/optimizer/zero" not in scope]
    zero = [(op, line) for op, scope, line in ops
            if "/optimizer/zero" in scope]
    matrices = sum(p.data().ndim > 1 for p in dpt._param_handles)
    assert len(own) > 10 * matrices             # Adam on every matrix
    assert any(_has_matrix(line) for _, line in own)
    # a matrix is never ravelled, padded, concatenated or sliced ...
    assert not {op for op, _ in own if any(k in op for k in _PACKING)}
    # ... and what the bucket packs is flat before it gets there
    assert any("concatenate" in op for op, _ in zero)
    assert not [line for _, line in zero if _has_matrix(line)]
    # the control: every leaf in buckets at the same degree packs matrices
    ref, x, y = _trainer("adam")
    _force_buckets(ref).step(x, y)
    ref_ops = _optimizer_ops(ref)
    assert all("/optimizer/zero" in scope for _, scope, _ in ref_ops)
    assert [line for op, _, line in ref_ops
            if "reshape" in op and _has_matrix(line)]


@pytest.mark.parametrize("kind", ["bf16", "2bit"])
def test_compression_keeps_buckets_and_residual_at_degree_one(kind):
    profiler.reset_comm_stats()
    dpt, x, y = _trainer(
        "adam", compression_params={"type": kind, "threshold": 0.01})
    losses = [dpt.step(x, y) for _ in range(2)]
    assert np.all(np.isfinite(losses))
    layout = dpt._zero_layout
    assert layout.buckets and not layout.passthrough
    assert len(dpt._zero_residuals) == len(layout.buckets)
    for b, r, st in zip(layout.buckets, dpt._zero_residuals,
                        dpt._zero_states):
        assert r.shape == (b.padded,) and r.dtype == np.float32
        assert float(np.abs(np.asarray(r)).max()) > 0   # error fed back
        assert all(s.shape == (b.padded,) for s in st)
    assert all(st == () for st in dpt._states)
    comm = profiler.get_comm_stats()
    assert comm["zero_steps"] == comm["steps"] == 2
    assert comm["bucket_count"] == len(layout.buckets)
    assert "optimizer/zero" in dpt.lowered().as_text(debug_info=True)


def test_micro_batches_at_degree_one_accumulate_per_parameter():
    """Gradient accumulation over the mixed layout (stage 1: per-parameter
    f32 accumulators feed buckets and matrices alike) gives ``zero=False``'s
    numbers."""
    got = {}
    for zero in (True, False):
        dpt, x, y = _trainer("adam", zero=zero, micro_batches=2, seed=1)
        got[zero] = _after_three_steps(dpt, x, y)
        matrix = next(i for i, p in enumerate(dpt._param_handles)
                      if p.data().ndim > 1)
        assert dpt._states[matrix][0].shape == \
            dpt._param_handles[matrix].data().shape
    assert got[True][0] == got[False][0]
    for a, b in zip(got[True][1], got[False][1]):
        np.testing.assert_array_equal(a, b)


@pytest.mark.multi_device(2)
def test_tensor_parallel_mesh_of_data_degree_one_buckets_only_flat_leaves():
    """The degree that counts is the DATA degree: on a ``(dp=1, tp=2)`` mesh
    the replicated matrices leave the buckets too, and the tp-sharded ones
    keep the per-parameter update they always had."""
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    # the feed-forward pair, column- then row-parallel
    shardings = {r"block\d+_dense0_weight$": P("tp", None),
                 r"block\d+_dense1_weight$": P(None, "tp")}
    got = {}
    for zero in (True, False):
        dpt, x, y = _trainer(
            "adam", mesh=parallel.make_mesh((1, 2), ("dp", "tp")), zero=zero,
            param_shardings=lambda n: next(
                (s for k, s in shardings.items() if re.search(k, n)), None),
            seed=2)
        got[zero] = _after_three_steps(dpt, x, y)
        assert sum(sh.spec != P() for sh in dpt._param_sh) == 4
        if zero:
            bucketed = {i for b in dpt._zero_layout.buckets
                        for i in b.indices}
            assert bucketed == {i for i, p in enumerate(dpt._param_handles)
                                if p.data().ndim <= 1}
    # over two devices the bucket's constraints move the partitioner's
    # reductions: the same numbers to rounding, not to the bit
    np.testing.assert_allclose(got[True][0], got[False][0], rtol=1e-5)
    for a, b in zip(got[True][1], got[False][1]):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
