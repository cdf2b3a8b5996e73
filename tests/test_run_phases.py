"""What a run does outside its steady device step has a name: phase spans
from the import on, memory marks at their ends, the step ring fed by
``DataParallelTrainer.step``, and the benchmark's readers of them."""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import mxtpu as mx
from mxtpu import nd, optimizer, profiler
from mxtpu.gluon import nn
from mxtpu.gluon.model_zoo.transformer import TransformerLM
from mxtpu.observability import flops, metrics, tracer
from mxtpu.parallel import DataParallelTrainer
from mxtpu.parallel.mesh import data_parallel_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SUITE = os.path.join(ROOT, "benchmark", "suite")
CELLS = ["gpt2m_train_t1024", "cgpt13_train_t2048", "phi4flash_train_t8192",
         "kexaone_train_t4096", "lfm2moe_train_t4096",
         "brumby_train_t8192", "lingflash_train_t4096", "joyai_train_t4096",
         "jamba2_train_t8192"]
NEW_METRICS = [
    "import_s.train", "net_build_s.train", "first_run_s.train",
    "step_compiled_in_process.train", "device_reserved_gb.train",
    "device_headroom_gb.train", "host_rss_peak_gb.train",
    "host_issue_window_ms_per_step.train", "slow_steps_pct.train",
    "slow_step_issue_excess_ms_per_step.train",
    "slow_step_readback_excess_ms_per_step.train"]
STEPS, SET_DATA_CALLS = 10, 3       # from step 9 on a batch of a new shape
DEVICE_KEYS = {"bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
               "peak_bytes_reserved", "bytes_limit"}


def _suite(module: str):
    """A module of the benchmark's, by the name its readers import it by."""
    if SUITE not in sys.path:
        sys.path.insert(0, SUITE)
    return __import__(module)


def _reader(metric: str):
    return _suite("manifest").load_module(
        os.path.join(SUITE, "layer_metrics", metric + ".py"),
        "phases_test_" + metric.replace(".", "_"))


# -- the import --------------------------------------------------------------

_IMPORT_PROBE = """
import json, jax
import mxtpu
from jax._src import xla_bridge
from mxtpu import profiler
before = xla_bridge.backends_are_initialized()
profiler.get_memory_stats()
mark = mxtpu.observability.metrics.mark_memory("probe")
print(json.dumps({
    "backend_after_import": before,
    "backend_after_mark": xla_bridge.backends_are_initialized(),
    "totals": {k: v for k, v in profiler.get_span_totals().items()
               if k.startswith("import/")},
    "marks": profiler.get_memory_stats()["marks"]}))
"""


@pytest.fixture(scope="module")
def imported():
    """A fresh interpreter that imports JAX, as the harness does, then the
    package, and takes one more mark."""
    out = subprocess.run(
        [sys.executable, "-c", _IMPORT_PROBE], cwd=ROOT, timeout=120,
        capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_the_import_counts_once_with_jax_inside_it(imported):
    whole, inner = (imported["totals"][n]
                    for n in ("import/mxtpu", "import/jax"))
    assert whole["count"] == 1 and whole["count_by_parent"] == {"": 1}
    assert inner["count"] == 1
    assert inner["count_by_parent"] == {"import/mxtpu": 1}
    # the caller had imported JAX: what is left is the package's own lines
    assert inner["seconds"] < 0.05 < whole["seconds"]


def test_importing_the_package_initialises_no_backend(imported):
    assert imported["backend_after_import"] is False
    assert imported["backend_after_mark"] is False


def test_the_imports_mark_holds_the_hosts_numbers_alone(imported):
    first, probe = imported["marks"]
    assert (first["name"], probe["name"]) == ("import/mxtpu", "probe")
    for mark in (first, probe):
        assert mark["host_rss_bytes"] > 0 < mark["host_peak_rss_bytes"]
        assert not DEVICE_KEYS & set(mark)
    assert first["t_ns"] < probe["t_ns"]


# -- a model's set-up and a trainer's steps ---------------------------------

def _seq_loss(logits, y):
    from mxtpu.gluon.loss import SoftmaxCrossEntropyLoss
    b, t, v = logits.shape
    return SoftmaxCrossEntropyLoss()(logits.reshape((b * t, v)),
                                     y.reshape((b * t,)))


@pytest.fixture(scope="module")
def ran():
    """``{"totals", "marks", "rows", "mfu", "events", "memory"}`` of a small
    block's set-up and of ``STEPS`` steps of a tiny model, from the ninth on
    with a batch of a new shape, NOTHING armed."""
    tracer.stop()
    profiler.reset_trace()
    profiler.reset_memory_stats()
    flops.reset_steps()
    dense = nn.Dense(4, in_units=4)
    dense.initialize()
    dense.cast("float32")
    for _ in range(SET_DATA_CALLS):
        dense.weight.set_data(nd.array(np.ones((4, 4), np.float32)))
    totals_net = profiler.get_span_totals()
    marks_net = profiler.get_memory_stats()["marks"]
    profiler.reset_trace()
    profiler.reset_memory_stats()

    rs = np.random.RandomState(0)
    mx.rng.seed(0)
    net = TransformerLM(50, units=32, num_layers=1, num_heads=2, max_len=16,
                        ffn_units=64)
    net.initialize()
    dpt = DataParallelTrainer(net, _seq_loss,
                              optimizer.Adam(learning_rate=1e-3),
                              data_parallel_mesh())

    def batch(t):
        return (nd.array(rs.randint(0, 50, (8, t))),
                nd.array(rs.randint(0, 50, (8, t)).astype(np.float32)))

    same, other = batch(16), batch(8)
    for n in range(1, STEPS + 1):
        dpt.step(*(same if n < 9 else other))
    out = {"totals_net": totals_net, "marks_net": marks_net,
           "totals": profiler.get_span_totals(),
           "memory": profiler.get_memory_stats(),
           "rows": profiler.get_step_timeline(),
           "mfu": profiler.get_mfu_stats(),
           "events": [ev for _, _, evs, _ in tracer.snapshot_buffers()
                      for ev in evs]}
    out["marks"] = out["memory"]["marks"]
    profiler.reset_trace()
    profiler.reset_memory_stats()
    flops.reset_steps()
    return out


@pytest.mark.parametrize("name,count", [
    ("net/initialize", 1), ("net/cast", 1),
    ("param/set_data", SET_DATA_CALLS)])
def test_a_models_set_up_counts_once_per_call(ran, name, count):
    row = ran["totals_net"][name]
    assert row["count"] == count and row["seconds"] > 0
    assert row["count_by_parent"] == {"": count}
    assert [m["name"] for m in ran["marks_net"]] == ["net/initialize"]


@pytest.mark.parametrize("name,call", [
    ("net/initialize", lambda b: b.initialize(force_reinit=True)),
    ("net/cast", lambda b: b.cast("float32"))])
def test_only_the_outermost_call_opens_the_span(name, call):
    """A block's call made from inside another's (an override that goes
    through its children's) is not counted a second time."""
    profiler.reset_trace()
    dense = nn.Dense(4, in_units=4)
    with tracer.span(name):
        assert tracer.is_open(name)
        call(dense)
    assert not tracer.is_open(name)
    call(dense)
    row = profiler.get_span_totals()[name]
    assert row["count"] == 2 and row["count_by_parent"] == {"": 2}
    profiler.reset_trace()
    profiler.reset_memory_stats()


@pytest.mark.parametrize("name,count", [
    ("train/first_readback", 2),            # steps 1 and 9 traced
    ("train/readback", STEPS - 2),
    ("train/compile", 2), ("train/dispatch", STEPS - 2),
    ("train/step", STEPS)])
def test_a_trainers_spans_count_unarmed_once_per_event(ran, name, count):
    assert ran["events"] == []              # the ring was never armed
    row = ran["totals"][name]
    assert row["count"] == count
    assert row["count_by_parent"] == (
        {"": count} if name == "train/step" else {"train/step": count})


@pytest.mark.parametrize("name", [
    "net/initialize", "train/collect", "train/build", "train/compile",
    "train/first_readback", "train/step/1", "train/step/2", "train/step/4",
    "train/step/8"])
def test_a_mark_at_the_end_of_every_phase_and_power_of_two_step(ran, name):
    marks = [m for m in ran["marks"] if m["name"] == name]
    # the step of a new shape compiles and waits a first time once more
    again = name in ("train/compile", "train/first_readback")
    assert len(marks) == (2 if again else 1)
    for mark in marks:
        assert mark["host_rss_bytes"] > 0 < mark["host_peak_rss_bytes"]
        # the CPU's memory_stats() is None: absent keys, not an error
        assert jax.local_devices()[0].memory_stats() is None
        assert not DEVICE_KEYS & set(mark)


def test_marks_come_in_order_and_no_steady_step_takes_one(ran):
    names = [m["name"] for m in ran["marks"]]
    assert names == [
        "net/initialize", "train/collect", "train/build", "train/compile",
        "train/first_readback", "train/step/1", "train/step/2",
        "train/step/4", "train/step/8", "train/compile",
        "train/first_readback"]
    times = [m["t_ns"] for m in ran["marks"]]
    assert times == sorted(times)


def test_the_trainers_build_records_its_auxiliary_bytes(ran):
    mem = ran["memory"]
    assert mem["aux_bytes_per_device"] == 0     # no running statistics here
    assert mem["param_bytes_per_device"] > 0 < mem["slot_bytes_per_device"]


def test_the_ring_holds_one_row_a_step_whose_parts_fit_the_whole(ran):
    rows = ran["rows"]
    assert [r["step"] for r in rows] == list(range(1, STEPS + 1))
    assert [r["step"] for r in rows if r["traced"]] == [1, 9]
    for r in rows:
        assert set(r) == set(flops.STEP_ROW)
        parts = (r["place_s"] + r["prepare_s"] + r["dispatch_s"]
                 + r["adopt_s"] + r["readback_s"])
        assert 0 < parts <= r["step_s"]
        assert r["nivcsw"] >= 0
    starts = [r["start_ns"] for r in rows]
    assert starts == sorted(starts)
    # the compile is in the traced steps' dispatch part
    assert rows[0]["dispatch_s"] > 10 * rows[1]["dispatch_s"]


def test_get_mfu_stats_counts_a_trainers_steps(ran):
    assert ran["mfu"]["steps"] == STEPS
    assert ran["mfu"]["p50_step_ms"] > 0


def test_cache_hits_and_misses_count_by_the_span_they_fell_under():
    profiler.reset_trace()
    with tracer.span("train/compile"):
        jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
        jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    tot = profiler.get_span_totals()
    assert tot["jax/cache_miss"]["count_by_parent"] == {"train/compile": 1}
    assert tot["jax/cache_hit"]["count_by_parent"] == {"train/compile": 1,
                                                       "": 1}
    profiler.reset_trace()


# -- the benchmark's readers ------------------------------------------------

def _row(step, t0, dispatch=0.002, readback=0.150):
    parts = {"place_s": 0.0005, "prepare_s": 0.003, "dispatch_s": dispatch,
             "adopt_s": 0.0005, "readback_s": readback}
    return dict(parts, step=step, start_ns=int(t0 * 1e9),
                step_s=sum(parts.values()) + 0.0001, traced=False, nivcsw=0)


@pytest.fixture
def synthetic():
    """A ring of 3 warm-up steps, a window of 20 with a stall of 0.5 s
    planted in step 7's ``dispatch`` and one of 1.0 s in step 15's
    ``readback``, and 6 profiled steps; the view a traced run hands its
    readers. Yields ``(view, rows of the window)``."""
    flops.reset_steps()
    rows, t = [], 0.0
    for n in range(1, 30):
        window_step = n - 3
        row = _row(n, t, dispatch=0.502 if window_step == 7 else 0.002,
                   readback=1.150 if window_step == 15 else 0.150)
        flops.record_step(row["step_s"], row=row)
        rows.append(row)
        t += row["step_s"] + 0.0002
    window = rows[3:23]
    # the harness's clock reads a little more than the program's span
    view = {"step_s": [r["step_s"] + 0.0003 for r in window],
            "profiled_steps": 6}
    yield view, window
    flops.reset_steps()


@pytest.mark.parametrize("metric,want", [
    ("host_issue_window_ms_per_step.train", 6.0),
    ("slow_steps_pct.train", 10.0),
    ("slow_step_issue_excess_ms_per_step.train", 25.0),     # 500 ms / 20
    ("slow_step_readback_excess_ms_per_step.train", 50.0)])  # 1000 ms / 20
def test_readers_put_each_planted_stall_on_its_side(synthetic, metric, want):
    view, _ = synthetic
    assert _reader(metric).read(view) == pytest.approx(want, abs=1e-6)


@pytest.mark.parametrize("metric", [
    "host_issue_window_ms_per_step.train", "slow_steps_pct.train",
    "slow_step_issue_excess_ms_per_step.train",
    "slow_step_readback_excess_ms_per_step.train", "host_rss_peak_gb.train"])
def test_readers_return_nothing_where_rows_and_step_s_disagree(
        synthetic, metric):
    view, _ = synthetic
    read = _reader(metric).read
    off = dict(view, step_s=list(view["step_s"]))
    off["step_s"][4] += 0.0015                  # one step, 1.5 ms apart
    assert read(off) is None
    assert read(dict(view, step_s=view["step_s"] * 2)) is None  # too few rows
    assert read({"step_s": view["step_s"]}) is None     # no traced run
    flops.reset_steps()                         # a program with no ring
    assert read(view) is None


def test_memory_readers_read_the_newest_mark(synthetic, monkeypatch):
    view, window = synthetic
    profiler.reset_memory_stats()
    reserved, headroom, rss = (_reader(m).read for m in (
        "device_reserved_gb.train", "device_headroom_gb.train",
        "host_rss_peak_gb.train"))
    assert reserved(view) is None and headroom(view) is None    # no mark
    metrics.mark_memory("on the CPU")
    assert reserved(view) is None and headroom(view) is None    # no keys
    monkeypatch.setattr(metrics, "_fullest_device", lambda: {
        "bytes_in_use": 5_600_000_000, "bytes_reserved": 8_560_000_000,
        "bytes_limit": 16_900_000_000})
    monkeypatch.setattr(metrics, "_host_bytes", lambda: {
        "host_rss_bytes": 1_000_000_000, "host_peak_rss_bytes": 2_500_000_000})
    monkeypatch.setattr(metrics.time, "perf_counter_ns",
                        lambda: window[-1]["start_ns"])
    metrics.mark_memory("inside the window's last step")
    monkeypatch.setattr(metrics, "_host_bytes", lambda: {
        "host_rss_bytes": 1_000_000_000, "host_peak_rss_bytes": 9_000_000_000})
    monkeypatch.setattr(metrics.time, "perf_counter_ns",
                        lambda: window[-1]["start_ns"] + 10 ** 10)
    metrics.mark_memory("after the window: the profiler's, not the run's")
    assert reserved(view) == pytest.approx(8.56)
    assert headroom(view) == pytest.approx(16.9 - 5.6 - 8.56)
    assert rss(view) == pytest.approx(2.5)
    profiler.reset_memory_stats()


def test_set_up_readers_read_the_programs_totals(ran, monkeypatch):
    scopes = _suite("scopes")
    view = {"profiled_steps": 6}
    totals = dict(ran["totals"], **{
        "import/mxtpu": {"seconds": 3.5}, "import/jax": {"seconds": 0.5},
        "net/cast": {"seconds": 0.25}, "param/set_data": {"seconds": 0.5}})
    # a worker that ran an example first has a compile cache placed, and the
    # fixture's step then counts as compiled in process: not this test's
    totals.pop("jax/cache_miss", None)
    monkeypatch.setattr(scopes, "span_totals", lambda: totals)
    assert _reader("import_s.train").read(view) == pytest.approx(3.0)
    assert _reader("net_build_s.train").read(view) == pytest.approx(
        totals["net/initialize"]["seconds"] + 0.75)
    assert _reader("first_run_s.train").read(view) == pytest.approx(
        totals["train/first_readback"]["seconds"])
    compiled = _reader("step_compiled_in_process.train").read
    assert compiled(view) == 0              # no miss, no compile here
    totals["jax/cache_miss"] = {"count_by_parent": {"train/compile": 1,
                                                    "": 40}}
    assert compiled(view) == 1
    # a program without the spans (the parent commit's) reports nothing
    monkeypatch.setattr(scopes, "span_totals", lambda: {
        "train/collect": {"seconds": 1.0}})
    for metric in NEW_METRICS[:4]:
        assert _reader(metric).read(view) is None
        assert _reader(metric).read({}) is None


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_every_new_metric_has_its_entry_reader_and_cells(metric):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [m for m in bench["per_layer"] if m["name"] == metric]
    assert entry["workloads"] == CELLS
    assert entry["moves"] in ("setup_s", "train_tokens_per_s")
    assert entry["name"].endswith(".train")
    assert callable(_reader(metric).read)


# -- names the program already carries --------------------------------------

def test_the_all_held_sums_carry_their_callers_scope_in_the_lowering():
    """Where every expert is held, the ``top_k`` gathers, their weights'
    products and the float32 sums of ``_sum_rows`` are lowered under the
    ``combine`` scope forward and the ``dispatch`` scope backward. (On the
    chip XLA fuses the sums into their consumers, whose names they then
    carry, and what ``unattributed_ms_per_step.train`` gained in PR 35 is
    the compiler's own ``copy-done``s, which carry no name: PERF.md, PR 36.)
    """
    import re
    import jax.numpy as jnp
    from mxtpu.parallel import moe
    T, d, f, E, k = 64, 16, 8, 4, 2

    def loss(h, router, gate_up, down, bias):
        with jax.named_scope("block1"), jax.named_scope("moe"):
            y, _ = moe.sparse_experts(h, router, bias, gate_up, down,
                                      held=tuple(range(E)), top_k=k)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    args = (jnp.ones((1, T, d), jnp.bfloat16), jnp.ones((E, d), jnp.bfloat16),
            jnp.ones((E, d, 2 * f), jnp.bfloat16),
            jnp.ones((E, f, d), jnp.bfloat16), jnp.zeros((E,), jnp.float32))
    text = jax.jit(jax.grad(loss)).lower(*args).as_text(debug_info=True)
    names = set(re.findall(r'"(jit\(loss\)/[^"]*)"', text))
    for scope, ops in (("jvp(block1)/moe/combine", ("gather", "mul", "add")),
                       ("transpose(jvp(block1))/moe/dispatch",
                        ("gather", "mul", "add"))):
        for op in ops:
            assert f"jit(loss)/{scope}/{op}" in names, (scope, op)
