"""What a train step is made of, by name (PR 24).

* ``tracer.span`` has three sinks: the profiler's trace and the totals by
  name always, the ring only when armed; ring events carry ``id``/``parent``;
* JAX's own compile phases arrive as ``jax/trace``/``jax/lower``/
  ``jax/compile`` under the span open on the calling thread;
* ``DataParallelTrainer.step`` opens ``train/step`` and its children, every
  one with the step's number;
* the step program's operations carry device scopes (``block0/attn``,
  ``head``, ``loss``, ``optimizer/zero``), forward and backward;
* the step's bookkeeping (``_last_avals``, the comm record) is worked out
  once, and ``optimizer_state_by_param`` unpacks ZeRO's buckets.
"""

import re
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxtpu as mx
from mxtpu import nd, optimizer, profiler
from mxtpu.gluon import nn
from mxtpu.gluon.loss import SoftmaxCrossEntropyLoss
from mxtpu.gluon.model_zoo.transformer import TransformerLM
from mxtpu.observability import export, tracer
from mxtpu.parallel import DataParallelTrainer
from mxtpu.parallel.mesh import data_parallel_mesh

STEP_CHILDREN = ("train/place", "train/prepare", "train/adopt",
                 "train/readback")


@pytest.fixture(autouse=True)
def _clean_tracer():
    tracer.stop()
    profiler.reset_trace()
    yield
    tracer.stop()
    profiler.reset_trace()


def _ring():
    return [e for _, _, evs, _ in tracer.snapshot_buffers() for e in evs]


# ---------------------------------------------------------------------------
# the tracer's three sinks
# ---------------------------------------------------------------------------


def test_ring_records_only_when_armed():
    with tracer.span("a/off"):
        pass
    tracer.instant("a/mark")
    tracer.record_span("a/measured", time.perf_counter_ns(), 1000)
    assert _ring() == []
    tracer.start()
    with tracer.span("a/on", args={"k": 1}):
        pass
    assert [(e["name"], e["args"]) for e in _ring()] == [("a/on", {"k": 1})]


def test_totals_count_unarmed_and_split_by_parent():
    assert not tracer.enabled()
    for _ in range(3):
        with tracer.span("t/outer"):
            with tracer.span("t/inner"):
                time.sleep(0.001)
    with tracer.span("t/inner"):
        pass
    tracer.instant("t/mark")
    tot = profiler.get_span_totals()
    assert tot["t/outer"]["count"] == 3 and tot["t/inner"]["count"] == 4
    assert set(tot["t/inner"]["by_parent"]) == {"t/outer", ""}
    assert tot["t/inner"]["by_parent"]["t/outer"] >= 0.003
    assert tot["t/outer"]["seconds"] >= tot["t/inner"]["by_parent"]["t/outer"]
    assert tot["t/inner"]["seconds"] == pytest.approx(
        sum(tot["t/inner"]["by_parent"].values()))
    assert tot["t/mark"] == {"count": 1, "seconds": 0.0, "min_s": 0.0,
                             "max_s": 0.0, "by_parent": {"": 0.0},
                             "count_by_parent": {"": 1}}
    assert "t/outer" in profiler.get_summary()
    profiler.reset_trace()
    assert profiler.get_span_totals() == {}


def test_span_opens_its_annotation_unarmed(monkeypatch):
    seen = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, *exc):
            seen.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    assert not tracer.enabled()
    with tracer.span("p/outer"):
        with tracer.span("p/inner"):
            pass
    assert seen == [("enter", "p/outer"), ("enter", "p/inner"),
                    ("exit", "p/inner"), ("exit", "p/outer")]


def test_ring_events_carry_id_and_parent():
    tracer.start()
    with tracer.span("r/outer"):
        with tracer.span("r/inner"):
            tracer.instant("r/mark")
        tracer.record_span("r/measured", time.perf_counter_ns(), 500)
    with tracer.span("r/next"):
        pass
    by = {e["name"]: e for e in _ring()}
    assert by["r/outer"]["parent"] == 0 and by["r/next"]["parent"] == 0
    assert by["r/inner"]["parent"] == by["r/outer"]["id"]
    assert by["r/mark"]["parent"] == by["r/inner"]["id"]
    assert by["r/measured"]["parent"] == by["r/outer"]["id"]
    ids = [e["id"] for e in _ring()]
    assert len(set(ids)) == len(ids) and all(i > 0 for i in ids)
    # the export carries both in args; a request's ``args.id`` stays its own
    tracer.instant("serving/submit", args={"id": 41})
    out = {e["name"]: e for e in export.collect_events() if "ts" in e}
    assert out["r/inner"]["args"] == {"span_id": by["r/inner"]["id"],
                                      "parent_id": by["r/outer"]["id"]}
    assert "id" not in out["r/inner"]
    assert out["serving/submit"]["args"]["id"] == 41
    assert export.request_timeline(41)[0]["name"] == "serving/submit"


def test_jax_compile_phases_count_under_the_open_span():
    @jax.jit
    def inner(x):
        return jnp.sin(x) * 2

    def fresh(x):
        return jnp.mean(inner(x) + jnp.where(x > 0, x, 0.0))

    x = jnp.ones((4, 4))
    jax.block_until_ready(x)
    profiler.reset_trace()
    t0 = time.perf_counter()
    with tracer.span("c/compile"):
        step = jax.jit(fresh)
        step(x)
    wall = time.perf_counter() - t0
    tot = profiler.get_span_totals()
    for name in ("jax/trace", "jax/lower", "jax/compile"):
        assert set(tot[name]["by_parent"]) == {"c/compile"}, name
        assert tot[name]["seconds"] > 0
    # ``inner`` and every jnp function are jits traced INSIDE ``fresh``'s
    # trace and report their own durations: the totals take each once
    assert tot["jax/trace"]["count"] > 1
    assert sum(tot[n]["seconds"] for n in
               ("jax/trace", "jax/lower", "jax/compile")) <= wall
    # a warm call compiles nothing
    profiler.reset_trace()
    with tracer.span("c/warm"):
        step(x)
    assert not any(n.startswith("jax/") for n in profiler.get_span_totals())


# ---------------------------------------------------------------------------
# device scopes of blocks
# ---------------------------------------------------------------------------


def test_block_scope_is_the_name_its_parent_registered():
    net = TransformerLM(50, units=32, num_layers=2, num_heads=2, max_len=16,
                        ffn_units=64)
    assert net._scope_name == "TransformerLM"
    assert net.block1._scope_name == "block1"
    assert net.block1.attn.q_proj._scope_name == "q_proj"
    assert net.ln_f._scope_name == "ln_f"
    seq = nn.HybridSequential()
    seq.add(nn.Dense(4, in_units=4), nn.Dense(2, in_units=4))
    seq.initialize()
    text = jax.jit(lambda a: seq(nd.NDArray(a)).data).lower(
        jnp.ones((1, 4))).as_text(debug_info=True)
    assert "HybridSequential/0/" in text and "HybridSequential/1/" in text


# ---------------------------------------------------------------------------
# one tiny trainer, three traced steps
# ---------------------------------------------------------------------------


def _seq_loss(logits, y):
    b, t, v = logits.shape
    return SoftmaxCrossEntropyLoss()(logits.reshape((b * t, v)),
                                     y.reshape((b * t,)))


def _tiny_trainer(zero=None, seed=0):
    rs = np.random.RandomState(seed)
    mx.rng.seed(seed)
    np.random.seed(seed)
    net = TransformerLM(50, units=32, num_layers=2, num_heads=2, max_len=16,
                        ffn_units=64)
    net.initialize()
    dpt = DataParallelTrainer(net, _seq_loss,
                              optimizer.Adam(learning_rate=1e-3),
                              data_parallel_mesh(), zero=zero)
    x = nd.array(rs.randint(0, 50, (8, 16)))
    y = nd.array(rs.randint(0, 50, (8, 16)).astype(np.float32))
    return dpt, x, y


@pytest.fixture(scope="module")
def traced():
    """``{"dpt", "x", "y", "events" (ring, three steps), "totals",
    "avals1", "comm1"}`` of a two-layer model."""
    tracer.stop()
    profiler.reset_trace()
    profiler.reset_comm_stats()
    dpt, x, y = _tiny_trainer()
    tracer.start()
    dpt.step(x, y)
    avals1, comm1 = dpt._last_avals, profiler.get_comm_stats()
    dpt.step(x, y)
    dpt.step(x, y)
    tracer.stop()
    out = {"dpt": dpt, "x": x, "y": y, "events": _ring(),
           "totals": profiler.get_span_totals(), "avals1": avals1,
           "comm1": comm1, "comm3": profiler.get_comm_stats()}
    profiler.reset_trace()
    return out


def test_every_ring_event_of_a_step_has_id_parent_and_step(traced):
    events = [e for e in traced["events"] if e["name"].startswith("train/")]
    # the memory marks' counter samples are tracks, not spans: no id
    assert all("id" in e and "parent" in e for e in traced["events"]
               if e["ph"] != "C")
    assert all(e["args"]["step"] in (1, 2, 3) for e in events)
    steps = {e["args"]["step"]: e for e in events
             if e["name"] == "train/step"}
    assert sorted(steps) == [1, 2, 3]
    for n, root in steps.items():
        kids = [e for e in events if e["parent"] == root["id"]]
        assert {e["args"]["step"] for e in kids} == {n}
        names = [e["name"] for e in kids]
        run = "train/compile" if n == 1 else "train/dispatch"
        first = ["train/collect", "train/build"] if n == 1 else []
        wait = "train/first_readback" if n == 1 else "train/readback"
        assert names == first + ["train/place", "train/prepare", run,
                                 "train/adopt", wait], names
        assert root["parent"] == 0
        # children lie inside their parent on the clock
        for e in kids:
            assert root["ts"] <= e["ts"]
            assert e["ts"] + e["dur"] <= root["ts"] + root["dur"] + 1e-3


def test_the_step_compile_is_told_from_the_eager_forward(traced):
    tot = traced["totals"]
    assert tot["train/collect"]["count"] == 1
    assert tot["train/compile"]["count"] == 1
    assert tot["train/dispatch"]["count"] == 2
    for phase in ("jax/trace", "jax/lower", "jax/compile"):
        assert tot[phase]["by_parent"]["train/compile"] > 0
    inside = sum(tot[p]["by_parent"]["train/compile"]
                 for p in ("jax/trace", "jax/lower", "jax/compile"))
    assert inside <= tot["train/compile"]["seconds"]
    # the eager forward's small programs count under train/collect
    assert tot["jax/compile"]["by_parent"]["train/collect"] > 0


def test_step_async_ends_before_the_readback(traced):
    tracer.start()
    loss = traced["dpt"].step_async(traced["x"], traced["y"])
    tracer.stop()
    # the trainer's spans alone: an eager conversion that a process first ran
    # under a tracer (``nd.contrib.MultiBoxTarget``'s vmap does) stays off
    # jit's fast path there, and the step's key then adds a ``jax/trace``
    names = [e["name"] for e in _ring() if e["name"].startswith("train/")]
    assert names == ["train/place", "train/prepare", "train/dispatch",
                     "train/adopt", "train/step"]
    assert np.isfinite(float(loss.data))


def test_lowered_step_carries_scopes_forward_and_backward(traced):
    text = traced["dpt"].lowered().as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]+)"', text))
    for scope in ("/block0/attn/", "/block1/ffn1/", "/ln_f/", "/head/",
                  "/embed/", "/embedding/", "(loss)", "/optimizer/zero/"):
        assert any(scope in n for n in names), scope
    back = [n for n in names if "transpose(jvp(" in n and "/block1/ffn1/" in n]
    fwd = [n for n in names if "transpose(" not in n and "/block1/ffn1/" in n]
    assert back and fwd
    # the loss scope is around the loss alone, not the whole backward
    assert not any("loss" in n and "/block0/" in n for n in names)


def test_bookkeeping_is_the_same_after_three_steps_as_after_one(traced):
    dpt = traced["dpt"]
    assert dpt._last_avals is traced["avals1"]
    leaves = jax.tree.leaves(dpt._last_avals)
    assert all(isinstance(a, (jax.ShapeDtypeStruct, int)) for a in leaves)
    one, three = traced["comm1"], traced["comm3"]
    assert three["steps"] == 3 * one["steps"] == 3
    for k in ("bytes_reduced", "bytes_gathered", "allreduce_bytes"):
        assert three[k] == 3 * one[k]
    for k in ("bucket_count", "shard_bytes_per_device", "dp"):
        assert three[k] == one[k]
    # a batch of another shape refreshes the avals
    x2 = nd.array(np.zeros((8, 8)))
    dpt.step(x2, nd.array(np.zeros((8, 8), np.float32)))
    assert dpt._last_avals is not traced["avals1"]
    assert dpt._last_avals[5].shape == (8, 8)


def test_optimizer_state_by_param_is_the_same_with_zero_on_and_off():
    got = {}
    for zero in (True, False):
        dpt, x, y = _tiny_trainer(zero=zero, seed=3)
        dpt.step(x, y)
        assert dpt.zero is zero
        state = dpt.optimizer_state_by_param()
        params = dict(dpt.block.collect_params().items())
        assert sorted(state) == sorted(dpt._param_names)
        for name, slots in state.items():
            assert len(slots) == 2      # Adam: first and second moment
            assert all(s.shape == params[name].shape for s in slots), name
        # prefixes count instances: compare by position
        got[zero] = [state[n] for n in dpt._param_names]
    assert any(float(jnp.abs(s).max()) > 0 for st in got[True] for s in st)
    for a, b in zip(got[True], got[False]):
        for sa, sb in zip(a, b):
            np.testing.assert_allclose(np.asarray(sa), np.asarray(sb),
                                       rtol=1e-5, atol=1e-8)
