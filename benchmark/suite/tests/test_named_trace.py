"""The readers of PR 24's names against a trace recorded with them.

``data/train_named_1step.xplane.pb`` is the first of two profiled steps of a
two-layer TransformerLM (d256, 2 heads of 128, T256, batch 4, vocabulary
1024) through the benchmark's own ``Trainer`` on a TPU v5e (my chip run,
PR 24), cut to the device's Steps / XLA Modules / XLA Ops lines and the
host's Python line, HLO text cut after 48 characters (the Mosaic marker
kept), of the metadata's stats only ``tf_op``. The expected numbers were
summed straight from the protobuf when the file was cut, not by the code
under test. ``data/train_1step.xplane.pb`` is PR 23's trace, without names.
"""

import os
import shutil
import tempfile

import pytest

import manifest
import scopes
import xplane
from conftest import SUITE

DATA = os.path.join(SUITE, "tests", "data")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CONFIG = {"vocab_size": 1024, "n_embd": 256, "n_layer": 2, "n_head": 2,
          "n_positions": 256, "n_inner": 1024}
NEW = ("flash_fwd_ms_per_step.train", "flash_bwd_ms_per_step.train",
       "flash_fwd_roofline_pct.train", "flash_bwd_roofline_pct.train",
       "blocks_ms_per_step.train", "head_loss_ms_per_step.train",
       "optimizer_ms_per_step.train", "unattributed_ms_per_step.train",
       "host_issue_ms_per_step.train", "idle_in_issue_ms_per_step.train",
       "collect_s.train", "trace_lower_s.train", "compile_or_load_s.train")
# ms inside the bench/train/step annotation (6.732129 ms), by hand
BLOCKS, HEAD_LOSS, OPTIMIZER, EMBED = 0.188455704, 0.028318986, \
    0.185786484, 0.018484844
UNATTRIBUTED = 0.05624062 + 0.000652656     # no tf_op + a tf_op of no scope
FWD, BWD = 0.011348984, 0.017207422 + 0.01387375
ISSUE = 0.04781 + 2.69837 + 2.294909 + 0.026
IDLE_IN_ISSUE = 4.54671955
TOTALS = {
    "train/collect": {"count": 1, "seconds": 13.8,
                      "by_parent": {"train/step": 13.8}},
    "jax/trace": {"count": 9, "seconds": 0.7, "by_parent": {
        "": 0.08, "train/collect": 0.14, "train/compile": 0.47}},
    "jax/lower": {"count": 3, "seconds": 1.17, "by_parent": {
        "": 0.6, "train/collect": 0.34, "train/compile": 0.23}},
    "jax/compile": {"count": 3, "seconds": 41.3, "by_parent": {
        "": 24.0, "train/collect": 11.5, "train/compile": 5.8}},
}


def _view(tmp_path, trace: str, **more) -> dict:
    """A traced train run's view over ``trace``, laid out as the profiler
    lays a trace directory out."""
    where = tmp_path / "plugins" / "profile" / "one"
    where.mkdir(parents=True)
    shutil.copy(os.path.join(DATA, trace), where / trace)
    reduced = xplane.reduce_planes(xplane.read_planes(str(where / trace)),
                                   chips=1)
    return dict({"trace": reduced, "trace_dir": str(tmp_path),
                 "profiled_steps": 1, "batch": 4, "seq_len": 256, "chips": 1,
                 "config": CONFIG, "peaks": PEAKS}, **more)


def _read(view: dict) -> dict:
    cell = manifest.Cell("gpt2m_train_t1024")
    return {name: cell.reader(name).read(view) for name in NEW}


@pytest.fixture
def named(tmp_path, monkeypatch):
    monkeypatch.setattr(scopes, "span_totals", lambda: TOTALS)
    return _view(tmp_path, "train_named_1step.xplane.pb")


def test_every_new_metric_is_in_the_manifest_with_a_reader():
    cell = manifest.Cell("gpt2m_train_t1024")
    names = [m["name"] for m in cell.per_layer()]
    assert names[-len(NEW):] == list(NEW)
    for w in cell.manifest["workloads"]:
        assert [m["name"] for m in manifest.Cell(w["name"]).per_layer()] \
            == names
    # the set-up metrics read the trainer's spans: they list the training
    # cells, so that a serving cell of a later PR does not owe them
    for m in cell.per_layer()[-3:]:
        assert m["moves"] == "setup_s" and m["workloads"] == [
            w["name"] for w in cell.manifest["workloads"]]


def test_scope_readers_against_hand_sums(named):
    got = _read(named)
    assert got["blocks_ms_per_step.train"] == pytest.approx(BLOCKS, rel=1e-4)
    assert got["head_loss_ms_per_step.train"] == pytest.approx(
        HEAD_LOSS, rel=1e-4)
    assert got["optimizer_ms_per_step.train"] == pytest.approx(
        OPTIMIZER, rel=1e-4)
    assert got["unattributed_ms_per_step.train"] == pytest.approx(
        UNATTRIBUTED, rel=1e-4)
    assert scopes.ms_per_step(named, "embed") == pytest.approx(
        EMBED, rel=1e-4)


def test_kernel_readers_tell_forward_from_backward(named):
    got = _read(named)
    assert got["flash_fwd_ms_per_step.train"] == pytest.approx(FWD, rel=1e-4)
    assert got["flash_bwd_ms_per_step.train"] == pytest.approx(BWD, rel=1e-4)
    cell = manifest.Cell("gpt2m_train_t1024")
    whole = cell.reader("flash_ms_per_step.train").read(named)
    assert got["flash_fwd_ms_per_step.train"] \
        + got["flash_bwd_ms_per_step.train"] == pytest.approx(whole, rel=1e-6)
    # 4 x 2 heads of T256 x D128 in bf16, two layers, by hand. So short a
    # sequence is bound by memory: forward moves Q, K, V, O and an f32 row
    # statistic (2 and 5 half-masked matmuls would take 1.4 and 3.4 us)
    t, row = 4 * 2 * 256 * 128 * 2, 4 * 2 * 256 * 4
    assert got["flash_fwd_roofline_pct.train"] == pytest.approx(
        100 * 2 * (4 * t + row) / 819e9 / (FWD / 1e3), rel=1e-4)
    assert got["flash_bwd_roofline_pct.train"] == pytest.approx(
        100 * 2 * (8 * t + 2 * row) / 819e9 / (BWD / 1e3), rel=1e-4)
    both = cell.reader("flash_roofline_pct.train").read(named)
    assert got["flash_bwd_roofline_pct.train"] < both \
        < got["flash_fwd_roofline_pct.train"] < 100


def test_the_scopes_partition_the_step(named):
    step = scopes.step_scopes(named)
    parts = sum(step[k] for k in ("blocks", "head_loss", "optimizer", "embed",
                                  "kernels", "unattributed"))
    assert parts == pytest.approx(step["total"])
    reduced = named["trace"]
    every = sum(t for n, t in reduced["op_s"].items()
                if xplane.base_name(n) not in xplane.CONTAINERS)
    assert step["total"] == pytest.approx(every, rel=1e-3)
    assert step["kernels"] == pytest.approx(
        xplane.kernel_seconds(reduced, "^" + xplane.MOSAIC_PREFIX), rel=1e-3)
    assert xplane.covered(step["busy"]) / 1e9 == pytest.approx(
        reduced["busy_s"], rel=1e-3)


def test_host_readers_against_hand_sums(named):
    got = _read(named)
    assert got["host_issue_ms_per_step.train"] == pytest.approx(
        ISSUE, rel=1e-6)
    assert got["idle_in_issue_ms_per_step.train"] == pytest.approx(
        IDLE_IN_ISSUE, rel=1e-4)
    assert got["idle_in_issue_ms_per_step.train"] \
        < got["host_issue_ms_per_step.train"]
    # the four spans and the readback, once per profiled step
    for name in scopes.ISSUE_SPANS + ("train/readback", "train/step"):
        assert len(named["trace"]["annotations"][name]) == 1, name
    # the longest idle gap is named by the program's span, not a runtime's
    assert named["trace"]["idle_gaps"][0][0].startswith("train/prepare")


def test_set_up_readers_take_the_step_compile_by_its_parent(named):
    got = _read(named)
    assert got["collect_s.train"] == 13.8
    assert got["trace_lower_s.train"] == pytest.approx(0.47 + 0.23)
    assert got["compile_or_load_s.train"] == 5.8


def test_every_reader_returns_nothing_on_a_trace_without_names(
        tmp_path, monkeypatch):
    monkeypatch.setattr(scopes, "span_totals", lambda: {})
    old = _view(tmp_path, "train_1step.xplane.pb", batch=8, seq_len=1024,
                config=manifest.Cell("gpt2m_train_t1024").config)
    assert _read(old) == dict.fromkeys(NEW)
    assert scopes.step_scopes(old) is None


def test_every_reader_returns_nothing_for_another_job_kind(named):
    serve = {k: v for k, v in named.items() if k != "profiled_steps"}
    assert _read(serve) == dict.fromkeys(NEW)
    assert _read({}) == dict.fromkeys(NEW)


def test_a_program_without_totals_reads_as_none(monkeypatch, named):
    """The parent commit's ``mxtpu.profiler`` has no ``get_span_totals``."""
    import mxtpu.profiler
    monkeypatch.undo()
    monkeypatch.delattr(mxtpu.profiler, "get_span_totals")
    assert scopes.span_totals() == {}
    got = _read(named)
    assert got["collect_s.train"] is None
    assert got["trace_lower_s.train"] is None
    assert got["compile_or_load_s.train"] is None


@pytest.mark.parametrize("op_name,layer", [
    ("jit(step)/jvp(TransformerLM)/block0/attn/q_proj/dot_general:", "blocks"),
    ("jit(step)/transpose(jvp(TransformerLM))/block11/ffn1/reduce_sum:",
     "blocks"),
    ("jit(step)/transpose(jvp(block3))/ffn1/dot_general:", "blocks"),
    ("jit(step)/jvp(TransformerLM)/ln_f/mul:", "head_loss"),
    ("jit(step)/transpose(jvp(TransformerLM))/head/dot_general:", "head_loss"),
    ("jit(step)/jvp(loss)/SoftmaxCrossEntropyLoss/jit(log_softmax)/"
     "reduce_sum:", "head_loss"),
    ("jit(step)/optimizer/zero/reshape;jit(step)/optimizer/zero/reshape:",
     "optimizer"),
    ("jit(step)/jvp(TransformerLM)/embedding/gather:", "embed"),
    ("jit(step)/jvp(TransformerLM)/embed/add:", "embed"),
    ("jit(step)/reduce_sum:", None),
    ("concatenate:", None),
    ("jit(step)/loss:", None),       # an operation called loss, no scope
    ("", None),
])
def test_layer_of(op_name, layer):
    assert scopes.layer_of(op_name) == layer


def test_the_trace_is_the_newest_bench_trace_directory(tmp_path,
                                                       monkeypatch):
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    assert scopes._trace_file({}) is None
    for i, name in enumerate(("bench-trace-old", "bench-trace-new")):
        where = tmp_path / name / "plugins" / "profile" / "t"
        where.mkdir(parents=True)
        (where / "host.xplane.pb").write_bytes(b"")
        os.utime(tmp_path / name, (100 + i, 100 + i))
    assert scopes._trace_file({}) == str(
        tmp_path / "bench-trace-new" / "plugins" / "profile" / "t"
        / "host.xplane.pb")
    assert scopes._trace_file({"trace_dir": str(tmp_path / "bench-trace-old")
                               }).startswith(str(tmp_path / "bench-trace-old"))
