"""Tier-1 guards for ``mxtpu.observability`` — the unified step-timeline
tracer, chrome-trace export, and MFU accounting (ISSUE 6).

Contracts future PRs cannot silently break:

* the tracing-OFF path records nothing in the ring (and a traced 2-epoch LeNet fit
  is bit-exact with the untraced one — tracing observes, never perturbs);
* spans nest correctly and land on per-thread rows (feed producer and
  checkpoint writer get their own named tid lanes);
* ``profiler.dump()`` after a traced fit is VALID chrome://tracing JSON —
  every duration event carries ph/ts/dur/pid/tid/name — containing the span
  catalog (step/compile, step/execute, feed/transfer, feed/stall, ckpt/*)
  across ≥ 2 named threads plus counter samples, and repeated
  ``dump(finished=True)`` is idempotent;
* ``get_summary()``/``dumps()`` aggregate from the span store;
* the step-time ring yields sane steps/s + p50/p99 and the FLOP estimators
  (XLA cost analysis, analytic jaxpr fallback) agree on known shapes.
"""

import json
import os
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np
import pytest

import conftest
import mxtpu as mx
from mxtpu import nd, profiler
from mxtpu.gluon import nn
from mxtpu.gluon.block import HybridBlock
from mxtpu.io import NDArrayIter
from mxtpu.observability import export, flops, tracer

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_tracer():
    tracer.stop()
    tracer.reset()
    profiler.reset_trace()
    yield
    tracer.stop()
    tracer.reset()
    profiler.reset_trace()


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------


def test_off_path_records_nothing_in_the_ring():
    assert not tracer.enabled()
    with tracer.span("step/execute"):
        pass
    tracer.counter("feed/queue_depth", 3)
    tracer.instant("marker")
    assert all(not evs for _, _, evs, _ in tracer.snapshot_buffers())
    # unarmed, a span still counts into the totals by name
    assert profiler.get_span_totals()["step/execute"]["count"] == 1


def test_spans_nest_on_one_thread():
    tracer.start()
    with tracer.span("outer", cat="t"):
        time.sleep(0.002)
        with tracer.span("inner", cat="t"):
            time.sleep(0.001)
    bufs = [evs for _, _, evs, _ in tracer.snapshot_buffers() if evs]
    assert len(bufs) == 1
    by_name = {e["name"]: e for e in bufs[0]}
    outer, inner = by_name["outer"], by_name["inner"]
    # chrome-trace nesting invariant: child interval contained in parent's
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    assert inner["dur"] > 0


def test_spans_cross_threads_land_on_own_rows():
    tracer.start()
    with tracer.span("main/work"):
        pass

    def worker():
        with tracer.span("worker/outer"):
            with tracer.span("worker/inner"):
                time.sleep(0.001)

    t = threading.Thread(target=worker, name="obs-test-worker")
    t.start()
    t.join()
    evs = export.collect_events()
    rows = {e["args"]["name"]: e["tid"] for e in evs
            if e.get("ph") == "M" and e["name"] == "thread_name"}
    assert "obs-test-worker" in rows
    spans = [e for e in evs if e.get("ph") == "X"]
    by_name = {e["name"]: e for e in spans}
    # the worker's spans carry the worker's tid, distinct from main's
    assert by_name["worker/outer"]["tid"] == rows["obs-test-worker"]
    assert by_name["worker/inner"]["tid"] == rows["obs-test-worker"]
    assert by_name["main/work"]["tid"] != rows["obs-test-worker"]
    # and still nest within their own row
    assert by_name["worker/outer"]["ts"] <= by_name["worker/inner"]["ts"]


def test_ring_bounded_drop_oldest(monkeypatch):
    monkeypatch.setenv("MXTPU_TRACE_BUFFER", "1024")

    def worker():
        for i in range(1200):
            with tracer.span(f"s{i}"):
                pass

    tracer.start()
    t = threading.Thread(target=worker, name="obs-ring-worker")
    t.start()
    t.join()
    rows = [b for b in tracer.snapshot_buffers() if b[1] == "obs-ring-worker"]
    _, _, evs, dropped = rows[-1]
    assert len(evs) == 1024
    assert dropped == 1200 - 1024
    assert evs[-1]["name"] == "s1199"      # the tail survives


def test_legacy_objects_mirror_into_tracer():
    tracer.start()
    d = profiler.Domain("legacy")
    with d.new_task("legacy_task"):
        pass
    d.new_counter("legacy_counter").set_value(7)
    d.new_marker("legacy_marker").mark()
    evs = export.collect_events()
    phs = {e["name"]: e["ph"] for e in evs if e.get("ph") in ("X", "C", "i")}
    assert phs.get("legacy_task") == "X"
    assert phs.get("legacy_counter") == "C"
    assert phs.get("legacy_marker") == "i"
    # and the aggregate table sees the span store
    assert "legacy_task" in profiler.get_summary()


# ---------------------------------------------------------------------------
# traced LeNet fit: dump validity, span catalog, idempotency, bit-exactness
# ---------------------------------------------------------------------------


class _LeNet(HybridBlock):
    def __init__(self):
        super().__init__()
        self.c1 = nn.Conv2D(6, kernel_size=3, in_channels=1)
        self.p1 = nn.MaxPool2D(pool_size=2)
        self.flat = nn.Flatten()
        self.fc1 = nn.Dense(32, in_units=6 * 5 * 5)
        self.fc2 = nn.Dense(10, in_units=32)

    def forward(self, x):
        return self.fc2(self.fc1(self.flat(self.p1(self.c1(x).relu()))).relu())


def _fit_lenet(epochs=2, batch=16, n=64, ckpt_dir=None):
    rs = np.random.RandomState(42)
    x = rs.rand(n, 1, 12, 12).astype(np.float32)
    y = rs.randint(0, 10, n).astype(np.float32)
    it = NDArrayIter(x, y, batch_size=batch, shuffle=False)
    mx.rng.seed(0)
    np.random.seed(0)
    mod = mx.Module(_LeNet(), data_names=("data",),
                    label_names=("softmax_label",))
    cb = None
    if ckpt_dir is not None:
        from mxtpu.callback import do_checkpoint
        from mxtpu.checkpoint import CheckpointManager
        mgr = CheckpointManager(ckpt_dir)
        cb = do_checkpoint(mgr, module=mod)
    mod.fit(it, num_epoch=epochs, optimizer="sgd",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9},
            epoch_end_callback=cb)
    if ckpt_dir is not None:
        # the writer thread records ckpt/write and ckpt/commit when a save
        # ENDS: on a loaded box the last one is still in flight here
        mgr.close()
    arg, aux = mod.get_params()
    return [v.asnumpy() for v in list(arg.values()) + list(aux.values())]


def test_traced_fit_dump_is_valid_chrome_trace(tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_TRACE", "1")    # the documented knob...
    tracer.start()                            # ...read at import; arm directly
    _fit_lenet(ckpt_dir=str(tmp_path / "ckpt"))
    fname = str(tmp_path / "profile.json")
    profiler.set_config(filename=fname, xplane=False)
    out = profiler.dump()
    assert out == fname
    doc = json.loads(open(fname).read())      # parses: valid JSON
    evs = doc["traceEvents"]
    spans = [e for e in evs if e.get("ph") == "X"]
    for e in spans:
        for k in export.REQUIRED_SPAN_KEYS:
            assert k in e, f"span missing {k!r}: {e}"
        assert e["dur"] >= 0
    names = {e["name"] for e in spans}
    # the span catalog: fused-step compile + execute, feed producer +
    # consumer, checkpoint writer — ≥ 5 distinct span kinds
    assert {"step/compile", "step/execute", "feed/transfer", "feed/stall",
            "ckpt/snapshot", "ckpt/write", "ckpt/commit"} <= names, names
    # counter samples ride along (queue depth)
    assert any(e.get("ph") == "C" for e in evs)
    # ≥ 2 named threads: main + the feed producer (+ ckpt writer)
    tnames = {e["args"]["name"] for e in evs
              if e.get("ph") == "M" and e["name"] == "thread_name"}
    assert "mxtpu-device-feed" in tnames
    assert "mxtpu-ckpt-writer" in tnames
    assert len(tnames) >= 3
    # spans from different subsystems landed on different tid rows
    tid_of = {e["name"]: e["tid"] for e in spans}
    assert tid_of["feed/transfer"] != tid_of["step/execute"]
    assert tid_of["ckpt/write"] != tid_of["step/execute"]


def test_dump_finished_is_idempotent(tmp_path):
    tracer.start()
    with tracer.span("a"):
        pass
    fname = str(tmp_path / "p.json")
    profiler.set_config(filename=fname, xplane=False)
    profiler.dump(finished=True)
    first = open(fname).read()
    # events recorded after the finished dump must NOT leak into a re-dump
    tracer.start()
    with tracer.span("b"):
        pass
    profiler.dump(finished=True)
    assert open(fname).read() == first
    # a fresh run (set_state) unfreezes
    profiler.set_config(xplane=False)
    profiler.set_state("run")
    with tracer.span("c"):
        pass
    profiler.set_state("stop")
    profiler.dump(finished=True)
    names = {e["name"] for e in json.loads(open(fname).read())["traceEvents"]}
    assert "c" in names


def test_traced_fit_bit_exact_with_tracing_off():
    plain = _fit_lenet()
    tracer.start()
    traced = _fit_lenet()
    tracer.stop()
    assert any(evs for _, _, evs, _ in tracer.snapshot_buffers())
    assert len(plain) == len(traced)
    for i, (a, b) in enumerate(zip(plain, traced)):
        assert np.array_equal(a, b), f"param #{i} diverged under MXTPU_TRACE"


def test_dumps_carries_mfu_block():
    blob = json.loads(profiler.dumps())
    assert "mfu" in blob and "traceEvents" in blob
    assert set(blob["mfu"]) >= {"steps", "steps_per_sec", "p50_step_ms",
                                "p99_step_ms", "mfu"}


# ---------------------------------------------------------------------------
# MFU accounting
# ---------------------------------------------------------------------------


def test_step_ring_percentiles_and_rate():
    flops.reset_steps()
    for ms in [1.0] * 98 + [10.0, 10.0]:
        flops.record_step(ms / 1e3)
    s = flops.get_mfu_stats(flops_per_step=None)
    assert s["steps"] == 100
    assert s["p50_step_ms"] == pytest.approx(1.0, rel=0.01)
    assert s["p99_step_ms"] == pytest.approx(10.0, rel=0.15)
    # 100 steps over 0.118 s
    assert s["steps_per_sec"] == pytest.approx(100 / 0.118, rel=0.01)
    flops.reset_steps()
    assert flops.get_mfu_stats()["steps"] == 0


def test_device_without_documented_peak_reports_no_mfu():
    """The CPU is in no peak table: ``mfu`` is None there, never a figure
    against an invented peak, and the step times are reported all the same."""
    kind, peak = flops.device_peak()
    assert kind and peak is None
    flops.reset_steps()
    for _ in range(4):
        flops.record_step(0.01)
    s = flops.get_mfu_stats(flops_per_step=1e7)
    assert s["mfu"] is None and s["peak_tflops"] is None
    assert s["device_kind"] == kind and s["flops_per_step"] == 1e7
    assert s["steps"] == 4
    assert s["steps_per_sec"] == pytest.approx(100.0, rel=0.01)
    assert s["p50_step_ms"] == pytest.approx(10.0, rel=0.01)
    assert s["p99_step_ms"] == pytest.approx(10.0, rel=0.01)
    flops.reset_steps()


def test_analytic_jaxpr_flops_matmul_and_conv():
    import jax
    import jax.numpy as jnp

    def mm(a, b):
        return a @ b

    j = jax.make_jaxpr(mm)(jnp.zeros((4, 8)), jnp.zeros((8, 16)))
    assert flops.jaxpr_flops(j) == 2 * 4 * 16 * 8

    from jax import lax

    def conv(x, k):
        return lax.conv_general_dilated(x, k, (1, 1), "VALID")

    j = jax.make_jaxpr(conv)(jnp.zeros((2, 3, 8, 8)), jnp.zeros((5, 3, 3, 3)))
    # out: (2, 5, 6, 6); MACs/out-elem = 3*3*3
    assert flops.jaxpr_flops(j) == 2 * (2 * 5 * 6 * 6) * 27


def test_scan_bodies_scale_by_trip_count():
    import jax
    import jax.numpy as jnp
    from jax import lax

    def scanned(a, b):
        def body(carry, _):
            return carry @ b, ()
        out, _ = lax.scan(body, a, None, length=7)
        return out

    j = jax.make_jaxpr(scanned)(jnp.zeros((4, 4)), jnp.zeros((4, 4)))
    assert flops.jaxpr_flops(j) == 7 * 2 * 4 * 4 * 4


def test_estimate_step_flops_xla_and_analytic_agree(monkeypatch):
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda a, b: a @ b)
    avals = (jax.ShapeDtypeStruct((32, 64), jnp.float32),
             jax.ShapeDtypeStruct((64, 128), jnp.float32))
    expect = 2 * 32 * 128 * 64
    monkeypatch.setenv("MXTPU_FLOPS_MODE", "analytic")
    assert flops.estimate_step_flops(fn, avals) == (expect, "analytic")
    monkeypatch.setenv("MXTPU_FLOPS_MODE", "xla")
    got, source = flops.estimate_step_flops(fn, avals)
    assert got == pytest.approx(expect, rel=0.01) and source == "xla"
    monkeypatch.setenv("MXTPU_FLOPS_MODE", "off")
    assert flops.estimate_step_flops(fn, avals) == (None, None)


def test_estimate_step_flops_names_the_analytic_source_when_xla_fails(caplog):
    """A failed cost model is logged and the count is labelled analytic —
    never passed off as XLA's."""
    import jax
    import jax.numpy as jnp

    class Broken:
        def __init__(self, fn):
            self._fn = fn

        def lower(self, *avals):
            raise RuntimeError("no AOT here")

        def __call__(self, *args):
            return self._fn(*args)

    avals = (jax.ShapeDtypeStruct((8, 8), jnp.float32),) * 2
    with caplog.at_level("WARNING", logger="mxtpu.observability"):
        got = flops.estimate_step_flops(Broken(lambda a, b: a @ b), avals)
    assert got == (2 * 8 * 8 * 8, "analytic")
    assert "cost analysis failed" in caplog.text


def test_fused_step_program_flops_nonzero():
    from mxtpu.io import DataBatch
    batch = 8
    rs = np.random.RandomState(0)
    x = nd.array(rs.rand(batch, 1, 12, 12).astype(np.float32))
    y = nd.array(rs.randint(0, 10, batch).astype(np.float32))
    mod = mx.Module(_LeNet(), data_names=("data",),
                    label_names=("softmax_label",))
    from mxtpu.io import DataDesc
    mod.bind(data_shapes=[DataDesc("data", (batch, 1, 12, 12))],
             label_shapes=[DataDesc("softmax_label", (batch,))])
    mod.init_params()
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05})
    mod.forward_backward(DataBatch(data=[x], label=[y]))
    mod.update()
    f = mod._program_flops()
    assert f is not None and f > 0
    # cached: second read is a dict hit with the same value
    assert mod._program_flops() == f


# ---------------------------------------------------------------------------
# CI: the package passes its own linter
# ---------------------------------------------------------------------------


def test_observability_self_lint_clean():
    p = subprocess.run(
        [sys.executable, "-m", "mxtpu.analysis", "mxtpu/observability",
         "--stats"],
        cwd=_REPO, env=conftest.subprocess_env(),
        capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, (
        f"tpulint found violations in mxtpu/observability "
        f"(rc={p.returncode}):\n{p.stdout}\n{p.stderr[-1000:]}")
