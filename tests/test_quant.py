"""mxtpu.quant (ISSUE 14) — end-to-end low-precision execution.

Tier-1 contract of the quant subsystem:

* int8 paged-KV round-trips inside the analytic per-row error bound and
  shrinks resident KV bytes >= 1.9x at identical slot count.
* Quantized serving decode: int8-KV greedy output is TOKEN-EXACT with solo
  ``generate`` on the serving-guard smoke prompts; the quantized step's
  logits stay inside a documented tolerance of fp32 ``serving_step``
  (docs/quantization.md); one compiled program per (slots, bucket, chunk)
  per quant mode — never per dispatch.
* The prefix cache stores/shares QUANTIZED blocks and hits stay greedy-exact;
  ``drain()``/``adopt()`` hand quantized pages across engines and refuse a
  kv-dtype mismatch.
* The quantized fused training step (``MXTPU_QUANT_STEP``) converges with
  fp32-comparable loss (rtol documented below) while tracing exactly once
  per mode.
"""

import os

import numpy as np
import pytest

import jax.numpy as jnp

import mxtpu as mx
from mxtpu import nd, profiler
from mxtpu.gluon.model_zoo import transformer_lm
from mxtpu.io import DataBatch, DataDesc
from mxtpu.quant import kv_quant
from mxtpu.quant.serve import (QuantSpec, build_step, parse_quant,
                               quant_param_specs, quantize_lm)
from mxtpu.quant.train import quant_step_mode
from mxtpu.serving import ServingConfig, ServingEngine, ServingHandoff

VOCAB = 50

# mixed-length smoke trace (prompt_len, max_new) in the style of
# tests/test_serving_guard.py: greedy token-exactness is asserted on these
_TRACE_SHAPES = [(3, 24), (17, 18), (9, 26), (26, 20), (5, 12)]


@pytest.fixture(scope="module")
def net():
    mx.rng.seed(0)
    model = transformer_lm("tiny", vocab_size=VOCAB)
    model.initialize()
    # a completing forward materializes the deferred params so _gen_params()
    # works outside the engine too
    model(nd.array(np.zeros((1, 4), np.int32)))
    return model


@pytest.fixture(scope="module")
def trace():
    rs = np.random.RandomState(3)
    return [(rs.randint(1, VOCAB, size=n).tolist(), new)
            for n, new in _TRACE_SHAPES]


@pytest.fixture(scope="module")
def refs(net, trace):
    out = []
    for p, m in trace:
        o = np.asarray(net.generate(nd.array(np.array([p], np.int32)), m).data)
        out.append(o[0, len(p):].tolist())
    return out


def _decode_traces():
    return profiler.get_compile_stats().get(
        "serving_decode", {}).get("traces", 0)


def _run_engine(net, trace, **kw):
    """Burst ``trace`` through a fresh engine; returns (tokens, stats,
    decode-traces-delta) — the delta doubles as the per-mode trace-once
    compile guard."""
    profiler.reset_serving_stats()
    before = _decode_traces()
    with ServingEngine(net, slots=2, queue_depth=8, chunk=4, **kw) as eng:
        reqs = [eng.submit(p, m) for p, m in trace]
        outs = [r.result(timeout=300) for r in reqs]
        stats = eng.stats()
    return outs, stats, _decode_traces() - before


@pytest.fixture(scope="module")
def fp32_run(net, trace):
    # [:2] keeps the lifecycle cheap; kv_bytes_resident is the allocated
    # cache (slots x TOT), independent of how many requests rode through
    return _run_engine(net, trace[:2])


@pytest.fixture(scope="module")
def int8_run(net, trace):
    return _run_engine(net, trace, quant="int8_kv")


@pytest.fixture(scope="module")
def int8_w_run(net, trace):
    profiler.reset_quant_stats()
    return _run_engine(net, trace[:2], quant="int8_kv,int8_w")


# ---------------------------------------------------------------------------
# kv_quant: round-trip bound, byte math
# ---------------------------------------------------------------------------


def test_int8_roundtrip_within_error_bound():
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(4, 8, 32, 16).astype(np.float32) * 3.0)
    q, scale = kv_quant.quantize_rows(x, "int8")
    assert q.dtype == jnp.int8 and scale.dtype == jnp.float32
    err = jnp.abs(kv_quant.dequantize_rows(q, scale) - x)
    bound = kv_quant.roundtrip_error_bound(x, "int8")
    assert bool(jnp.all(jnp.max(err, axis=-1) <= bound + 1e-7))
    # all-zero rows round-trip exactly (scale pinned to 1.0)
    zq, zs = kv_quant.quantize_rows(jnp.zeros((3, 16)), "int8")
    assert bool(jnp.all(zs == 1.0))
    assert bool(jnp.all(kv_quant.dequantize_rows(zq, zs) == 0.0))


def test_unknown_kv_mode_raises():
    with pytest.raises(ValueError, match="unknown KV quantization mode"):
        kv_quant.quantize_rows(jnp.ones((2, 4)), "int4")


def test_kv_bytes_shrink_exceeds_acceptance_floor():
    # shrink = 4D / (D + 4) per row (1 byte/elem + 4-byte f32 scale); the
    # tiny model's D=32 gives 3.56x, far above the 1.9x acceptance floor —
    # and the floor holds for any head_dim >= 3
    assert kv_quant.shrink_vs_f32(2, 4, 32, 64, "int8") \
        == pytest.approx(128 / 36)
    assert kv_quant.shrink_vs_f32(2, 4, 3, 64, "int8") > 1.5
    assert kv_quant.page_nbytes(2, 4, 32, 64, jnp.float32, "int8") \
        == 2 * 2 * 4 * 64 * (32 + 4)


# ---------------------------------------------------------------------------
# parse / spec surface
# ---------------------------------------------------------------------------


def test_parse_quant_surface():
    assert parse_quant(None) == QuantSpec()
    assert not parse_quant(None).enabled
    assert parse_quant("int8_kv") == QuantSpec(kv="int8")
    spec = parse_quant("int8_kv,int8_w")
    assert spec == QuantSpec(kv="int8", weights="int8")
    assert spec.tag == "int8_kv+int8_w"
    assert parse_quant(spec) is spec            # pass-through
    with pytest.raises(ValueError, match="unknown quantization token"):
        parse_quant("int4_kv")
    with pytest.raises(ValueError, match="conflicting"):
        parse_quant("int8_kv,fp8_kv")


def test_quant_step_mode_parse():
    assert quant_step_mode("") is None
    assert quant_step_mode("off") is None
    assert quant_step_mode("fp32") is None
    assert quant_step_mode("int8") == "int8"
    with pytest.raises(ValueError, match="MXTPU_QUANT_STEP"):
        quant_step_mode("int4")


def test_scale_spec_follows_weight_dim0():
    from jax.sharding import PartitionSpec as P
    from mxtpu.parallel.fsdp import SpecLayout, scale_spec
    lay = SpecLayout()
    assert scale_spec(lay.qkv_projection()) == P("tp")   # column-parallel
    assert scale_spec(lay.attn_out()) == P()             # row-parallel
    assert scale_spec(None) == P()
    specs = quant_param_specs(transformer_lm("tiny", vocab_size=VOCAB))
    lp = specs["layers"][0]
    assert lp["qw_s"] == scale_spec(lp["qw_q"])
    assert set(lp) >= {"f1b", "f2b", "ob", "qb", "kb", "vb"}


# ---------------------------------------------------------------------------
# quantized serving decode
# ---------------------------------------------------------------------------


def test_quant_step_logits_tolerance_vs_fp32(net):
    """One decode step, same state: the int8-KV program's logits stay within
    the documented tolerance of fp32 ``serving_step`` (docs/quantization.md:
    1e-2 for int8-KV, 2e-1 with int8 weights on this tiny model)."""
    import jax
    S, TOT = 2, 64
    params = net._gen_params()
    fp_step = jax.jit(net.serving_step(S, TOT))
    rs = np.random.RandomState(5)
    tok = jnp.asarray(rs.randint(1, VOCAB, S).astype(np.int32))
    p = jnp.asarray(np.zeros(S, np.int32))
    caches_fp = jnp.zeros(_cache_shape(net, S, TOT), jnp.float32)
    for spec, tol in ((parse_quant("int8_kv"), 1e-2),
                      (parse_quant("int8_kv,int8_w"), 2e-1)):
        q_step = jax.jit(build_step(net, S, TOT, spec))
        q_params = quantize_lm(net, spec)
        caches_q = kv_quant.empty(_cache_shape(net, S, TOT), quant=spec.kv)
        cf, tk, pp = caches_fp, tok, p
        cq = caches_q
        for _ in range(6):          # a few compounding-state steps
            cf, lf = fp_step(params, cf, tk, pp)
            cq, lq = q_step(q_params, cq, tk, pp)
            dev = float(jnp.max(jnp.abs(lf - lq)))
            assert dev <= tol, (spec.tag, dev)
            tk = jnp.argmax(lf, axis=-1).astype(jnp.int32)
            pp = pp + 1
        assert isinstance(cq, kv_quant.QuantKV)


def _cache_shape(net, S, TOT):
    L = len(net.blocks)
    H = net.blocks[0].attn._heads
    D = net._units // H
    return (L, 2, S, H, TOT, D)


def test_int8_kv_greedy_token_exact(int8_run, refs):
    outs, stats, _ = int8_run
    assert outs == refs              # acceptance: token-exact greedy decode
    assert stats["kv_dtype"] == "int8"
    assert stats["kv_bytes_resident"] > 0


def test_kv_bytes_resident_shrinks_vs_fp32(fp32_run, int8_run):
    (_, st_fp, _), (_, st_q, _) = fp32_run, int8_run
    assert st_fp["kv_dtype"] == "float32"
    shrink = st_fp["kv_bytes_resident"] / st_q["kv_bytes_resident"]
    assert shrink >= 1.9, shrink     # acceptance floor (measured: 3.56x)


def test_fp32_engine_stays_exact(fp32_run, refs):
    outs, _, _ = fp32_run            # unquantized path regression pin
    assert outs == refs[:2]


@pytest.mark.slow        # numerics are tier-1 via the logits-tolerance test
def test_weight_quant_engine_runs_and_counts_matmuls(int8_w_run, trace):
    outs, stats, delta = int8_w_run
    assert stats["kv_dtype"] == "int8"
    assert delta == 1                # trace-once holds for int8_w mode too
    # compounding-greedy with int8 weights may diverge per request; the
    # per-step logits budget is asserted in the tolerance test above
    assert all(len(o) == m for o, (_, m) in zip(outs, trace[:2]))
    qs = profiler.get_quant_stats()
    assert qs["matmuls"] > 0         # sites recorded at trace time
    assert qs["max_abs_error"]       # per-tensor weight round-trip high-water
    assert max(qs["max_abs_error"].values()) < 1e-2


def test_kv_dtype_plumbs_bf16(net, trace, refs):
    """Satellite: the once-dead ``kv.empty_cache(dtype=)`` is now a real
    engine knob (bf16 storage; tiny-model greedy stays exact)."""
    outs, stats, _ = _run_engine(net, trace[:2], kv_dtype="bfloat16")
    assert stats["kv_dtype"] == "bfloat16"
    assert outs == refs[:2]


def test_integer_kv_dtype_is_refused(net):
    """``kv_dtype="int8"`` would store K/V rows cast to integers; quantized
    storage is ``quant="int8_kv"``."""
    with pytest.raises(ValueError, match="int8_kv"):
        ServingEngine(net, slots=2, kv_dtype="int8")


def test_serving_config_carries_quant(net):
    # config plumbing only (the full decode path under int8_kv is covered
    # by the fixture runs above) — no need to start the engine
    eng = ServingEngine(net, slots=2, config=ServingConfig(quant="int8_kv"))
    try:
        assert eng._kv_dtype_str == "int8"
    finally:
        eng.stop()


def test_env_selects_quant(net):
    os.environ["MXTPU_SERVING_QUANT"] = "int8_kv"
    try:
        eng = ServingEngine(net, slots=2)    # resolution only, no start —
        try:                                 # the decode path is int8_run's
            assert eng._kv_dtype_str == "int8"
        finally:
            eng.stop()
    finally:
        del os.environ["MXTPU_SERVING_QUANT"]


def test_trace_once_per_quant_mode(fp32_run, int8_run):
    """Compile guard: each quant mode traces its own decode program exactly
    once for the whole mixed-length burst — quant params ride as traced
    arrays, so steady-state dispatches never retrace within a mode. (The
    int8_w mode's delta is asserted with its engine run below.)"""
    for name, (_, _, delta) in (("fp32", fp32_run), ("int8_kv", int8_run)):
        assert delta == 1, (name, delta)


def test_prefix_cache_hit_with_quantized_blocks(net):
    pfx = list(range(1, 33)) + [7, 7]
    ref = np.asarray(net.generate(
        nd.array(np.array([pfx], np.int32)), 8).data)[0, len(pfx):].tolist()
    profiler.reset_serving_stats()
    with ServingEngine(net, slots=2, queue_depth=8, chunk=4,
                       quant="int8_kv", prefix_cache_mb=1.0) as eng:
        eng.submit(pfx, 8).result(timeout=300)       # seeds the radix cache
        hit = eng.submit(pfx, 8)
        out = hit.result(timeout=300)
        stats = eng.stats()
    assert stats["prefix_hits"] >= 1
    assert stats["prefix_hit_tokens"] >= 32          # one full quant block
    assert out == ref                                # hit stays greedy-exact


def test_drain_adopt_quantized_engine(net, trace, refs):
    import time
    eng = ServingEngine(net, slots=2, queue_depth=8, chunk=4, quant="int8_kv")
    eng.start()
    reqs = [eng.submit(p, m) for p, m in trace[:3]]
    time.sleep(0.25)                                 # let prefill/decode run
    handoff = eng.drain()
    assert handoff.kv_dtype == "int8"
    # >= 1: how many are still mid-decode at drain is timing-dependent
    assert handoff.in_flight >= 1
    eng2 = ServingEngine(net, slots=2, queue_depth=8, chunk=4,
                         quant="int8_kv")
    eng2.adopt(handoff)
    eng2.start()
    outs = [r.result(timeout=300) for r in reqs]
    eng2.stop()
    assert outs == refs[:3]                          # zero drift across hop


def test_adopt_refuses_kv_dtype_mismatch(net):
    eng = ServingEngine(net, slots=2, queue_depth=8, chunk=4)   # fp32 engine
    try:
        with pytest.raises(ValueError, match="int8.*float32"):
            eng.adopt(ServingHandoff(tot=64, kv_dtype="int8"))
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# quantized fused training step
# ---------------------------------------------------------------------------


def _fit(mode, steps=20):
    prev = os.environ.pop("MXTPU_QUANT_STEP", None)
    if mode:
        os.environ["MXTPU_QUANT_STEP"] = mode
    try:
        profiler.reset_compile_stats()
        mx.rng.seed(0)
        model = transformer_lm("tiny", vocab_size=VOCAB)
        mod = mx.Module(model, data_names=("data",),
                        label_names=("softmax_label",))
        mod.bind(data_shapes=[DataDesc("data", (4, 16))],
                 label_shapes=[DataDesc("softmax_label", (4, 16))])
        mod.init_params()
        mod.init_optimizer(optimizer="adam",
                           optimizer_params={"learning_rate": 3e-3})
        rs = np.random.RandomState(0)
        x = nd.array(rs.randint(0, VOCAB, (4, 16)).astype(np.int32))
        y = nd.array(rs.randint(0, VOCAB, (4, 16)).astype(np.float32))
        b = DataBatch(data=[x], label=[y])
        losses = []
        for _ in range(steps):
            mod.forward_backward(b)
            mod.update()
            losses.append(float(mod._loss_val.mean().data))
        return losses, profiler.get_compile_stats()["module_step"]["traces"]
    finally:
        os.environ.pop("MXTPU_QUANT_STEP", None)
        if prev is not None:
            os.environ["MXTPU_QUANT_STEP"] = prev


@pytest.mark.slow        # tier-1 asserts the same parity via the bench guard
def test_quant_fused_step_converges_with_fp32_parity():
    """Memorize-one-batch parity: the int8 fake-quant STE step must track
    the fp32 loss trajectory (documented rtol: 5e-2 on the final loss after
    20 steps; measured ~7e-3 on this fit) and trace exactly once."""
    fp32, tr_fp = _fit(None)
    int8, tr_q = _fit("int8")
    assert tr_fp == 1 and tr_q == 1
    assert fp32[0] > fp32[-1] + 0.5          # both actually learn
    assert int8[0] > int8[-1] + 0.5
    assert int8[-1] == pytest.approx(fp32[-1], rel=5e-2)


def test_quant_step_mode_flip_retraces_once():
    """The quant mode is a signature component: flipping it retraces exactly
    once per mode, and flipping back is a cache hit."""
    profiler.reset_compile_stats()
    mx.rng.seed(0)
    from mxtpu.gluon import nn
    from mxtpu.gluon.block import HybridBlock

    class Net(HybridBlock):
        def __init__(self):
            super().__init__()
            self.fc1 = nn.Dense(16, in_units=12)
            self.fc2 = nn.Dense(10, in_units=16)

        def forward(self, x):
            return self.fc2(self.fc1(x).relu())

    mod = mx.Module(Net(), data_names=("data",),
                    label_names=("softmax_label",))
    mod.bind(data_shapes=[DataDesc("data", (8, 12))],
             label_shapes=[DataDesc("softmax_label", (8,))])
    mod.init_params()
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05})
    rs = np.random.RandomState(1)
    b = DataBatch(data=[nd.array(rs.rand(8, 12).astype(np.float32))],
                  label=[nd.array(rs.randint(0, 10, 8).astype(np.float32))])

    def traces():
        return profiler.get_compile_stats()["module_step"]["traces"]

    prev = os.environ.pop("MXTPU_QUANT_STEP", None)
    try:
        mod.forward_backward(b); mod.update()
        assert traces() == 1
        os.environ["MXTPU_QUANT_STEP"] = "int8"
        mod.forward_backward(b); mod.update()
        assert traces() == 2
        mod.forward_backward(b); mod.update()
        assert traces() == 2                 # steady state within the mode
        del os.environ["MXTPU_QUANT_STEP"]
        mod.forward_backward(b); mod.update()
        assert traces() == 2                 # fp32 program still cached
    finally:
        os.environ.pop("MXTPU_QUANT_STEP", None)
        if prev is not None:
            os.environ["MXTPU_QUANT_STEP"] = prev


# ---------------------------------------------------------------------------
# calibration + contrib regression pins (satellite 2)
# ---------------------------------------------------------------------------


def test_streaming_calibrator_matches_one_shot():
    from mxtpu.quant.calibrate import (StreamingCalibrator,
                                       _get_optimal_threshold)
    rs = np.random.RandomState(0)
    chunks = [rs.randn(512).astype(np.float32) for _ in range(4)]
    chunks[2] *= 4.0                         # forces a range rebin
    calib = StreamingCalibrator()
    for c in chunks:
        calib.observe("x", c)
    full = np.concatenate(chunks)
    lo, hi = calib.minmax("x")
    assert lo == pytest.approx(full.min()) and hi == pytest.approx(full.max())
    assert calib.absmax("x") == pytest.approx(np.abs(full).max())
    # streamed-histogram KL threshold lands within a few percent of the
    # concatenate-everything baseline (rebinning drifts at most one bin)
    assert calib.threshold("x") == pytest.approx(
        _get_optimal_threshold(full), rel=0.05)


def test_calibrate_feed_records_ranges(net):
    from mxtpu.gluon import nn as gnn
    from mxtpu.quant.calibrate import calibrate_feed

    class Tiny(mx.gluon.block.HybridBlock):
        def __init__(self):
            super().__init__()
            self.fc = gnn.Dense(8, in_units=6)

        def forward(self, x):
            return self.fc(x)

    mx.rng.seed(0)
    m = Tiny()
    m.initialize()
    rs = np.random.RandomState(2)
    feed = [nd.array(rs.rand(4, 6).astype(np.float32)) for _ in range(3)]
    profiler.reset_quant_stats()
    calib = calibrate_feed(m, feed, mode="naive")
    assert calib.names() == ["fc"]
    assert profiler.get_quant_stats()["ranges"]["fc"][1] > 0
    with pytest.raises(ValueError, match="calib_mode"):
        calibrate_feed(m, feed, mode="bogus")


def test_contrib_walk_finds_all_transformer_dense_sites(net):
    """Regression pin: the eligibility walk sees every Dense of the tiny
    TransformerLM (4 per attention x 2 blocks + 2 FFN x 2 = 12 sites)."""
    from mxtpu.contrib.quantization import _walk
    sites = _walk(net)
    assert len(sites) == 12
    names = [n for *_, n in sites]
    assert len(set(names)) == 12             # unique dotted paths


def test_quantize_net_rejects_unknown_dtype():
    from mxtpu.contrib.quantization import quantize_net
    from mxtpu.gluon import nn as gnn
    mx.rng.seed(0)
    m = gnn.Dense(4, in_units=4)
    m.initialize()
    m(nd.array(np.ones((1, 4), np.float32)))
    with pytest.raises(ValueError, match="quantized_dtype"):
        quantize_net(m, quantized_dtype="int4")


def test_scale_of_rejects_unknown_out_type():
    from mxtpu.ops.quantization import _scale_of
    with pytest.raises(ValueError, match="unknown quantized out_type"):
        _scale_of(-1.0, 1.0, out_type="int4")


# ---------------------------------------------------------------------------
# observability
# ---------------------------------------------------------------------------


def test_get_quant_stats_shape():
    profiler.reset_quant_stats()
    qs = profiler.get_quant_stats()
    assert qs == {"matmuls": 0, "max_abs_error": {}, "ranges": {}}
    profiler.record_quant_matmuls(3)
    profiler.record_quant_error("w", 0.5)
    profiler.record_quant_error("w", 0.2)    # high-water: keeps 0.5
    profiler.record_quant_range("w", -1.0, 2.0)
    profiler.record_quant_range("w", -0.5, 3.0)   # widens monotonically
    qs = profiler.get_quant_stats()
    assert qs["matmuls"] == 3
    assert qs["max_abs_error"]["w"] == 0.5
    assert qs["ranges"]["w"] == (-1.0, 3.0)
    profiler.reset_quant_stats()
