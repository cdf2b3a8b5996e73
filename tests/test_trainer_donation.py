"""``DataParallelTrainer`` donates what it replaces (PR 27).

Where the backend donates (``step_cache.donation_supported()``: every one
but the CPU) the jitted step takes the weights, the per-parameter slots,
ZeRO's bucket slots and the compression residuals as donated arguments and
writes each new value into the old one's buffer; the batch, the scalars, the
key and the auxiliary states are never donated. ``_collect`` makes every
slot a buffer of its own in one program, and a parameter under two names
reaches the step once. The numbers are the same to the bit either way.

The CPU backend of this installation honours ``donate_argnums`` when asked,
so with ``donation_supported`` patched true these tests run the real thing:
a donated argument is deleted after the call.
"""

import re

import numpy as np
import pytest

import jax

import mxtpu as mx
from mxtpu import nd, optimizer, parallel, profiler
from mxtpu.analysis import DonationError, sanitize
from mxtpu.gluon import nn
from mxtpu.gluon.loss import SoftmaxCrossEntropyLoss
from mxtpu.gluon.model_zoo.transformer import TransformerLM
from mxtpu.observability import tracer
from mxtpu.parallel import DataParallelTrainer
from mxtpu.parallel import data_parallel as dp_mod

OPTIMIZERS = {
    "sgd_momentum": lambda: optimizer.SGD(learning_rate=0.05, momentum=0.9,
                                          wd=1e-3),
    # one zero array handed out for both moments
    "adam": lambda: optimizer.Adam(learning_rate=1e-3),
    # a scalar slot beside the two moments
    "nadam": lambda: optimizer.Nadam(learning_rate=1e-3),
}

# name -> (mesh shape, axis names, MXTPU_ZERO_STAGE, trainer keywords)
LAYOUTS = {
    # per-parameter slots for every matrix, one bucket of the flat leaves
    "one_device": ((1,), ("dp",), "1", {}),
    "dp8_zero1": ((8,), ("dp",), "1", {}),
    "dp8_zero3": ((4, 2), ("dp", "fsdp"), "3", {}),
    "dp8_micro2": ((8,), ("dp",), "2", {"micro_batches": 2}),
    # bucket slots and an error-feedback residual a bucket
    "dp8_compression": ((8,), ("dp",), "1",
                        {"compression_params": {"type": "2bit",
                                                "threshold": 0.01}}),
    "dp8_replicated": ((8,), ("dp",), "1", {"zero": False}),
}


def _seq_loss(logits, y):
    b, t, v = logits.shape
    return SoftmaxCrossEntropyLoss()(logits.reshape((b * t, v)),
                                     y.reshape((b * t,)))


def _gpt2_toy():
    return TransformerLM(50, units=32, num_layers=1, num_heads=2, max_len=16,
                         ffn_units=64)


def _hybrid_toy():
    from mxtpu.gluon.model_zoo.hybrid_decoder import HybridDecoderLM
    return HybridDecoderLM(50, ["mamba", "attn_window", "mamba", "attn_full",
                                "gmu", "attn_cross"],
                           units=32, ffn_units=64, num_heads=4,
                           num_kv_heads=2, window=8, d_state=4, dt_rank=2)


def _trainer(monkeypatch, layout="one_device", opt_name="adam", donate=None,
             make=_gpt2_toy):
    shape, axes, stage, kwargs = LAYOUTS[layout]
    monkeypatch.setenv("MXTPU_ZERO_STAGE", stage)
    if donate is not None:
        monkeypatch.setattr(dp_mod, "donation_supported", lambda: donate)
    mx.rng.seed(0)
    net = make()
    net.initialize()
    dpt = DataParallelTrainer(net, _seq_loss, OPTIMIZERS[opt_name](),
                              parallel.make_mesh(shape, axes), **kwargs)
    rs = np.random.RandomState(0)
    x = nd.array(rs.randint(0, 50, (8, 16)))
    y = nd.array(rs.randint(0, 50, (8, 16)).astype(np.float32))
    return dpt, x, y


def _donated_leaves(dpt):
    """What the step takes at its donated positions, leaf by leaf."""
    return jax.tree.leaves((
        [p.data().data for p in dpt._param_handles], dpt._states,
        dpt._zero_states, dpt._zero_residuals))


def _main_arguments(dpt):
    """The step program's arguments as the lowering wrote them."""
    sig = re.search(r"func\.func public @main\((.*?)\) -> ",
                    dpt.lowered().as_text(), re.S).group(1)
    return re.split(r", (?=%arg\d+:)", sig)


# ---------------------------------------------------------------------------
# which arguments the lowered step marks as donors
# ---------------------------------------------------------------------------


def _with_aux():
    net = nn.HybridSequential()
    # tokens (8, 16) -> (8, 16, 16): BatchNorm over the positions
    net.add(nn.Embedding(50, 16), nn.BatchNorm(in_channels=16),
            nn.Dense(50, flatten=False, in_units=16))
    return net


@pytest.mark.parametrize("layout,make", [
    ("one_device", _gpt2_toy), ("dp8_compression", _gpt2_toy),
    ("dp8_zero3", _gpt2_toy), ("one_device", _with_aux)],
    ids=["one_device", "dp8_compression", "dp8_zero3", "aux_states"])
def test_lowered_step_donates_what_it_replaces_and_nothing_else(
        monkeypatch, layout, make):
    dpt, x, y = _trainer(monkeypatch, layout, donate=True, make=make)
    dpt.step(x, y)
    n_params, n_aux = len(dpt._param_handles), len(dpt._aux_handles)
    n_slots = len(jax.tree.leaves(
        (dpt._states, dpt._zero_states, dpt._zero_residuals)))
    assert n_slots >= 2
    if make is _with_aux:
        assert n_aux == 2                 # BatchNorm's running statistics
    if layout == "dp8_compression":
        assert all(r is not None for r in dpt._zero_residuals)
    args = _main_arguments(dpt)
    # params, auxs, slots, then x, y, lr, wd, rescale, clip, key, t: of
    # the scalars the lowering keeps only those the program reads
    n_rest = len(args) - (n_params + n_aux + n_slots)
    assert 4 <= n_rest <= 8
    assert [re.search(r"tensor<([^>]*)>", a).group(1)
            for a in args[-n_rest:-n_rest + 2]] == ["8x16xi32", "8x16xf32"]
    donors = {i for i, a in enumerate(args)
              if "tf.aliasing_output" in a or "jax.buffer_donor" in a}
    assert donors == set(range(n_params)) | set(
        range(n_params + n_aux, n_params + n_aux + n_slots))
    # each donor names the output it is written into, and no two the same
    aliased = [int(re.search(r"tf\.aliasing_output = (\d+)", args[i]).group(1))
               for i in sorted(donors)]
    assert len(set(aliased)) == len(donors)


def test_the_cpu_backend_is_asked_for_no_donation(monkeypatch):
    dpt, x, y = _trainer(monkeypatch)
    dpt.step(x, y)
    assert not any("tf.aliasing_output" in a or "jax.buffer_donor" in a
                   for a in _main_arguments(dpt))
    assert dpt._step_buffers["donated"] == 0


# ---------------------------------------------------------------------------
# after _collect no two donated leaves share a buffer
# ---------------------------------------------------------------------------


def _buffers(a):
    return [(s.device.id, s.data.unsafe_buffer_pointer())
            for s in a.addressable_shards]


def _tied(net):
    # the embedding under a second name, as a block that ties two layers by
    # hand registers it
    net._params._params["head_weight_alias"] = net.embedding.weight
    return net


@pytest.mark.parametrize("opt_name", sorted(OPTIMIZERS))
@pytest.mark.parametrize("layout", ["one_device", "dp8_compression",
                                    "dp8_replicated"])
def test_collect_leaves_no_two_donated_leaves_on_one_buffer(
        monkeypatch, layout, opt_name):
    dpt, x, _ = _trainer(monkeypatch, layout, opt_name,
                         make=lambda: _tied(_gpt2_toy()))
    names = list(dpt.block.collect_params())
    assert "head_weight_alias" in names
    dpt._collect(x)
    # the tied weight reaches the step once, under its first name
    assert len(dpt._param_handles) == len(names) - 1
    assert len({id(p) for p in dpt._param_handles}) == len(dpt._param_handles)
    if layout == "one_device":
        # PR 25's rule: the flat leaves' bucket beside per-parameter slots
        assert dpt._zero_layout.buckets and dpt._zero_layout.passthrough
    leaves = _donated_leaves(dpt)
    slots_a_param = {"sgd_momentum": 1, "adam": 2, "nadam": 3}[opt_name]
    assert len(leaves) >= len(dpt._zero_layout.passthrough
                              if dpt.zero else dpt._param_handles) \
        * (1 + slots_a_param)
    seen = [b for a in leaves for b in _buffers(a)]
    assert len(set(seen)) == len(seen)


def test_a_tied_weight_trains_under_donation(monkeypatch):
    # one after the other: parameters still deferred draw from the global
    # generator at the first step
    losses = []
    for donate in (True, False):
        dpt, x, y = _trainer(monkeypatch, donate=donate,
                             make=lambda: _tied(_gpt2_toy()))
        losses.append([dpt.step(x, y) for _ in range(3)])
        assert dpt._step_buffers["donated"] == (
            dpt._step_buffers["outputs"] - 1 if donate else 0)
    assert losses[0] == losses[1] and losses[0][-1] < losses[0][0]


def test_collect_releases_the_eager_gradient_buffers(monkeypatch):
    """``Parameter.initialize`` attaches a zero gradient buffer to every
    parameter; the trainer's step never writes them, so ``_collect`` lets
    them go. The parameters stay marked: an eager backward afterwards has
    its gradients as before."""
    from mxtpu import autograd
    dpt, x, y = _trainer(monkeypatch)
    params = list(dpt.block.collect_params().values())
    assert all(p._data is None or p._data._grad is not None for p in params)
    dpt.step(x, y)
    assert all(p._data._grad is None for p in dpt._param_handles)
    with autograd.record():
        loss = nd.mean(_seq_loss(dpt.block(x), y))
    loss.backward()
    grads = [p.grad().asnumpy() for p in dpt._param_handles]
    assert all(np.isfinite(g).all() for g in grads)
    assert sum(float(np.abs(g).sum()) for g in grads) > 0
    dpt.step(x, y)                       # and the trainer goes on


# ---------------------------------------------------------------------------
# the same numbers, to the bit, with donation on and off
# ---------------------------------------------------------------------------


def _five_steps(dpt, x, y):
    """Losses, weights and slots after five steps, and whether the second
    step consumed the weights it was given."""
    losses = [dpt.step(x, y)]
    given = [p.data().data for p in dpt._param_handles]
    losses += [dpt.step(x, y) for _ in range(4)]
    params = [np.asarray(p.data().data) for p in dpt._param_handles]
    slots = [np.asarray(s) for s in dpt.optimizer_slots()]
    return losses, params, slots, [a.is_deleted() for a in given]


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_five_steps_are_bit_identical_with_donation_on_and_off(
        monkeypatch, layout):
    on, x, y = _trainer(monkeypatch, layout, donate=True)
    losses, params, slots, consumed = _five_steps(on, x, y)
    assert all(consumed)                 # it was the real thing
    off, x, y = _trainer(monkeypatch, layout, donate=False)
    ref_losses, ref_params, ref_slots, consumed = _five_steps(off, x, y)
    assert not any(consumed)
    assert losses == ref_losses and losses[-1] < losses[0]
    assert len(slots) == len(ref_slots) and any(
        np.abs(s).max() > 0 for s in slots)
    for name, a, b in zip(on._param_names, params, ref_params):
        np.testing.assert_array_equal(a, b, err_msg=name)
    for a, b in zip(slots, ref_slots):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# the scalars and the key are not made between a read-back and a dispatch
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("donate", [True, False])
def test_scalars_are_made_once_and_the_key_a_step_ahead(monkeypatch, donate):
    """The learning rate, the decay, the rescale and the clip go to the
    device when their value changes and not every step (never donated, so
    the array of the step before is whole); the key of step ``t + 1`` is
    made after step ``t`` is dispatched and is ``jax.random.key(t + 1)``;
    a learning rate set between two steps is the one the next step uses."""
    dpt, x, y = _trainer(monkeypatch, "one_device", "adam", donate=donate)
    dpt.step(x, y)
    first = {k: v[1] for k, v in dpt._scalars.items()}
    assert set(first) == {"lr", "wd", "rescale", "clip"}
    dpt.step(x, y)
    assert all(dpt._scalars[k][1] is a and not a.is_deleted()
               for k, a in first.items())
    t, key = dpt._key_ahead
    assert t == 3 and np.array_equal(jax.random.key_data(key),
                                     jax.random.key_data(jax.random.key(3)))
    # Adam at a rate of 0 moves nothing
    before = [np.asarray(p.data().data) for p in dpt._param_handles]
    dpt.optimizer.lr = 0.0
    dpt.step(x, y)
    assert dpt._scalars["lr"][1] is not first["lr"] \
        and float(dpt._scalars["lr"][1]) == 0.0 \
        and dpt._scalars["rescale"][1] is first["rescale"]
    assert all(np.array_equal(np.asarray(p.data().data), b)
               for p, b in zip(dpt._param_handles, before))
    dpt.optimizer.lr = 0.05
    dpt.step(x, y)
    assert not any(np.array_equal(np.asarray(p.data().data), b)
                   for p, b in zip(dpt._param_handles, before))
    # a key asked for out of turn (a restored step count) is made afresh
    dpt._t = 10
    dpt.step(x, y)
    assert dpt._key_ahead[0] == 12


# ---------------------------------------------------------------------------
# a stale read is named on the CPU, where nothing was donated
# ---------------------------------------------------------------------------


def test_sanitizer_names_a_slot_kept_across_a_step(monkeypatch):
    profiler.reset_sanitizer_stats()
    dpt, x, y = _trainer(monkeypatch)
    with sanitize.scope("donation"):
        dpt.step(x, y)
        kept_slot = nd.NDArray(dpt.optimizer_slots()[0])
        kept_weight = nd.NDArray(dpt._param_handles[0].data().data)
        by_param = dpt.optimizer_state_by_param()
        kept_moment = nd.NDArray(by_param[dpt._param_names[0]][0])
        dpt.step(x, y)
        for stale in (kept_slot, kept_weight, kept_moment):
            with pytest.raises(DonationError, match="DataParallelTrainer"):
                stale.asnumpy()
        # the accessors, called afresh, hand out what is current
        for s in dpt.optimizer_slots():
            nd.NDArray(s).asnumpy()
        for st in dpt.optimizer_state_by_param().values():
            for s in st:
                nd.NDArray(s).asnumpy()
        for p in dpt._param_handles:
            p.data().asnumpy()
        assert dpt.optimizer_state_bytes() > 0
        dpt._record_memory()
        dpt.cost_analysis()
        dpt.step(x, y)
    stats = profiler.get_sanitizer_stats()
    assert stats["donation_trips"] == 3
    assert stats["donation_poisons_armed"] >= 3 * len(dpt._param_handles)


# ---------------------------------------------------------------------------
# the counter that says it engaged
# ---------------------------------------------------------------------------


@pytest.fixture
def _ring():
    tracer.stop()
    profiler.reset_trace()
    yield lambda: [e for _, _, evs, _ in tracer.snapshot_buffers()
                   for e in evs]
    tracer.stop()
    profiler.reset_trace()


@pytest.mark.parametrize("make", [_gpt2_toy, _hybrid_toy],
                         ids=["gpt2", "hybrid_decoder"])
@pytest.mark.parametrize("donate", [True, False], ids=["donating", "cpu"])
def test_span_arguments_and_profiler_count_the_donated_outputs(
        monkeypatch, _ring, make, donate):
    profiler.reset_memory_stats()
    dpt, x, y = _trainer(monkeypatch, donate=donate, make=make)
    tracer.start()
    dpt.step(x, y)
    dpt.step(x, y)
    tracer.stop()
    issued = {e["name"]: e["args"] for e in _ring()
              if e["name"] in ("train/compile", "train/dispatch")}
    assert set(issued) == {"train/compile", "train/dispatch"}
    mem = profiler.get_memory_stats()
    for args in issued.values():
        assert args["outputs"] == mem["step_outputs"]
        assert args["donated"] == mem["step_donated"]
    assert issued["train/compile"]["step"] == 1
    assert issued["train/dispatch"]["step"] == 2
    # the loss is the one fresh buffer of a donating step
    assert mem["step_outputs"] == len(_donated_leaves(dpt)) + 1
    assert mem["step_donated"] == (mem["step_outputs"] - 1 if donate else 0)
    # and the lowered program says the same
    assert mem["step_donated"] == sum(
        "tf.aliasing_output" in a for a in _main_arguments(dpt))
