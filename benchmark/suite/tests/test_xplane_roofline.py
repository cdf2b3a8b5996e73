"""The trace reducer against a recorded TPU trace, and the roofline
functions by hand on one shape.

``data/train_1step.xplane.pb`` is one profiled step of ``gpt2m_train_t1024``
at batch 8 on a TPU v5e (my chip run, PR 23), cut to the device's Steps /
XLA Modules / XLA Ops lines and the host's Python line, HLO text cut after 48
characters. The expected numbers were summed straight from the protobuf when
the file was cut, not by the code under test."""

import os

import pytest

import roofline
import xplane
from conftest import SUITE

TRACE = os.path.join(SUITE, "tests", "data", "train_1step.xplane.pb")
WINDOW_S = 0.274702242          # the bench/train/step annotation
BUSY_S = 0.244231502188         # union of XLA Ops inside it
MOSAIC_S = 0.048176677498       # the 72 tpu_custom_call ops inside it
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def reduced():
    return xplane.reduce_planes(xplane.read_planes(TRACE), chips=1)


def test_busy_window_and_kernel_time(reduced):
    assert reduced["devices"] == 1
    assert reduced["window_s"] == pytest.approx(WINDOW_S, rel=1e-6)
    assert reduced["busy_s"] == pytest.approx(BUSY_S, rel=1e-4)
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    mosaic = xplane.kernel_seconds(reduced, "^" + xplane.MOSAIC_PREFIX)
    assert mosaic == pytest.approx(MOSAIC_S, rel=1e-4)
    assert sum(1 for n in reduced["op_s"]
               if n.startswith(xplane.MOSAIC_PREFIX)) == 72   # 3 x 24 layers
    assert reduced["collective_exposed_s"] == 0.0
    assert reduced["top_ops"][0][0] == "fusion"
    loops = sum(t for n, t in reduced["op_s"].items()
                if xplane.base_name(n) in xplane.CONTAINERS)
    assert loops > 0
    assert sum(t for _, t in reduced["top_ops"]) == pytest.approx(
        sum(reduced["op_s"].values()) - loops)


def test_idle_gaps_are_named_by_the_host(reduced):
    idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(t for _, t in reduced["idle_gaps"]) == pytest.approx(
        idle, rel=1e-3)
    label, seconds = reduced["idle_gaps"][0]
    assert label.startswith("PjitFunction(step)") and seconds > idle / 2


def test_module_runs_fall_inside_the_annotation(reduced):
    runs = xplane.modules_inside(reduced, "bench/train/step")
    assert len(runs) == 1 and runs[0] == pytest.approx(0.2442, rel=0.02)
    assert xplane.modules_inside(reduced, "serving/decode") == []


def test_layer_metric_readers_on_the_trace(reduced):
    import json
    import manifest
    cell = manifest.Cell("gpt2m_train_t1024")
    view = {"trace": reduced, "profiled_steps": 1, "batch": 8,
            "seq_len": 1024, "chips": 1, "config": cell.config,
            "peaks": PEAKS, "step_s": [0.27, 0.28, 0.26], "tokens": 8192 * 10,
            "window_s": 2.7}
    got = {m["name"]: cell.reader(m["name"]).read(view)
           for m in cell.per_layer()}
    assert got["step_ms.train"] == pytest.approx(270.0)
    assert got["flash_ms_per_step.train"] == pytest.approx(48.1767, rel=1e-4)
    assert got["device_idle_pct.train"] == pytest.approx(
        100 * (1 - BUSY_S / WINDOW_S), rel=1e-3)
    assert 0 < got["flash_roofline_pct.train"] < 100
    assert 0 < got["mfu_pct.train"] < 100
    # a reader that finds nothing to read returns nothing
    assert all(cell.reader(m["name"]).read({}) is None
               for m in cell.per_layer())
    json.dumps(got)


@pytest.mark.parametrize("a,b,want", [
    ([[0, 10]], [[2, 3], [5, 7]], [[0, 2], [3, 5], [7, 10]]),
    ([[0, 4], [6, 9]], [[3, 7]], [[0, 3], [7, 9]]),
    ([[0, 4]], [], [[0, 4]]),
    ([[1, 2]], [[0, 5]], []),
])
def test_interval_subtraction(a, b, want):
    assert xplane.subtract(a, b) == want


def test_interval_union_and_names():
    assert xplane.merge([(5, 6), (0, 2), (1, 3)]) == [[0, 3], [5, 6]]
    assert xplane.covered([[0, 3], [5, 6]]) == 4
    assert xplane.short_name("%fusion.7 = bf16[8,4]{1,0} fusion(...)") \
        == "fusion.7"
    assert xplane.short_name(
        '%jvp__.24 = (bf16[1]) custom-call(), '
        'custom_call_target="tpu_custom_call"') == "tpu_custom_call/jvp__.24"
    assert xplane.base_name("convolution_add_fusion.167") \
        == "convolution_add_fusion"


def test_exposed_collective_time():
    planes = {"devices": {0: {
        "ops": [("fusion.1", 0, 100), ("all-reduce.1", 80, 150),
                ("fusion.2", 200, 300), ("all-gather.3", 220, 260)],
        "modules": [("jit_step(1)", 0, 300)]}},
        "host": [("bench/train/step", 0, 300, True)]}
    r = xplane.reduce_planes(planes, chips=1)
    assert r["collective_exposed_s"] == pytest.approx(50e-9)
    assert r["busy_s"] == pytest.approx(250e-9)
    assert r["window_s"] == pytest.approx(300e-9)


def test_no_device_operation_is_an_error():
    with pytest.raises(SystemExit):
        xplane.reduce_planes({"devices": {0: {"ops": [], "modules": []}},
                              "host": []}, chips=1)


def test_flash_roofline_by_hand():
    # batch 8, 16 heads, T 1024, head dim 64, bf16
    fl = roofline.flash_flops(8, 16, 1024, 64)
    assert fl["fwd"] == 2 * 2 * 8 * 16 * 1024 * 1024 * 64 / 2 == 17179869184
    assert fl["bwd"] == 2.5 * fl["fwd"]
    by = roofline.flash_bytes(8, 16, 1024, 64, 2)
    tensor = 8 * 16 * 1024 * 64 * 2
    assert by["fwd"] == 4 * tensor + 8 * 16 * 1024 * 4
    assert by["bwd"] == 8 * tensor + 2 * 8 * 16 * 1024 * 4
    secs, bound = roofline.roofline_seconds(fl["fwd"], by["fwd"], PEAKS)
    assert bound == "compute"
    assert secs == pytest.approx(17179869184 / 197e12)
    assert roofline.roofline_seconds(1e6, 1e9, PEAKS) == (1e9 / 819e9,
                                                          "memory")


def test_train_flops_and_decode_bytes_by_hand():
    cfg = {"n_embd": 1024, "n_inner": None, "n_layer": 24,
           "vocab_size": 50257}
    params = 24 * 12 * 1024 * 1024 + 50257 * 1024
    assert roofline.train_flops_per_token(cfg, 1024) \
        == 6.0 * params + 6.0 * 24 * 1024 * 1024
    # 100 live positions of f32 keys and values in 24 layers of 1024 wide
    assert roofline.decode_step_bytes(cfg, 2, 100, 4) \
        == params * 2 + 100 * 2 * 24 * 1024 * 4


def test_live_positions_by_hand():
    recs = [{"prompt_len": 10, "first": 1.0, "last": 3.0, "n": 20},   # half
            {"prompt_len": 5, "first": 2.5, "last": 4.0, "n": 8},     # later
            {"prompt_len": 7, "first": None, "last": None, "n": 0},   # queued
            {"prompt_len": 3, "first": 2.0, "last": 2.0, "n": 1}]     # one token
    assert roofline.live_positions(recs, 2.0) == (10 + 10) + (3 + 1)
    assert roofline.live_positions(recs, 5.0) == 0
