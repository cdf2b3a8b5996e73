"""mxtpu.ops.quant_attention (ISSUE 16) — fused dequant-attention decode.

Tier-1 contract of the fused quantized-KV attention read:

* PARITY: the Pallas kernel (interpret mode on CPU — the real kernel body)
  and the folded-scale/int8-dot XLA path both match the unfused reference
  (``dequantize_rows`` then masked softmax) within tolerances derived from
  the quantization ``roundtrip_error_bound``, across KV buckets, prefill
  cursors, and both quant modes.
* The int8 x int8 -> int32 ``dot_general`` weight matmul matches
  dequantize-then-f32-matmul inside the activation-quantization bound.
* ``_pick_block`` raises a clear ValueError naming the Mosaic constraint at
  illegal lengths instead of an opaque lowering error (ISSUE 16 satellite).
* TRACE-ONCE: the decode kernel is resolved at engine build; flipping
  ``MXTPU_DECODE_KERNEL`` between dispatches never retraces a live engine.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxtpu as mx
from mxtpu import nd, profiler
from mxtpu.gluon.model_zoo import transformer_lm
from mxtpu.ops import quant_attention as qa
from mxtpu.ops.attention import _pick_block
from mxtpu.quant import kv_quant
from mxtpu.serving import ServingEngine

VOCAB = 50


def _quantized_case(TOT, mode, seed=0, S=3, H=2, D=16):
    """A written-cache decode case: random K/V rows quantized per-row, a
    per-slot cursor strictly inside the bucket, plus the f32 originals."""
    rs = np.random.RandomState(seed)
    q = jnp.asarray(rs.randn(S, H, D).astype(np.float32))
    k = jnp.asarray(rs.randn(S, H, TOT, D).astype(np.float32))
    v = jnp.asarray(rs.randn(S, H, TOT, D).astype(np.float32))
    pc = jnp.asarray(rs.randint(0, TOT, size=S).astype(np.int32))
    kd, ks = kv_quant.quantize_rows(k, mode)
    vd, vs = kv_quant.quantize_rows(v, mode)
    return q, k, v, kd, ks, vd, vs, pc


def _reference(q, kq_deq, vq_deq, pc, scale):
    """Unfused reference over the DEQUANTIZED cache: exactly the pre-PR16
    serving read (materialize, einsum, masked softmax, einsum)."""
    TOT = kq_deq.shape[2]
    s = jnp.einsum("bhd,bhtd->bht", q, kq_deq) * scale
    mask = jnp.arange(TOT)[None, None, :] <= pc[:, None, None]
    att = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
    return jnp.einsum("bht,bhtd->bhd", att, vq_deq)


MODES = [m for m in ("int8", "fp8") if m in kv_quant.KV_MODES]


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("TOT", [32, 64, 128, 256])
def test_fused_decode_parity_across_buckets(TOT, mode):
    """Both fused paths match the unfused dequantize-then-attend reference.

    The reference consumes the SAME quantized cache (dequantized), so the
    comparison isolates the fused read's own error: the Pallas path
    dequantizes in-register (identical values, different reassociation —
    tight bound); the XLA int8 path additionally quantizes the query and
    attention-weight activations per row (one more half-step of
    ``roundtrip_error_bound`` through each dot — looser bound)."""
    D = 16
    scale = 1.0 / np.sqrt(D)
    q, k, v, kd, ks, vd, vs, pc = _quantized_case(TOT, mode, seed=TOT)
    ref = _reference(q, kv_quant.dequantize_rows(kd, ks),
                     kv_quant.dequantize_rows(vd, vs), pc, scale)
    ref_mag = float(jnp.max(jnp.abs(ref)))

    pallas = qa.dequant_attention_decode(q, kd, ks, vd, vs, pc, scale=scale,
                                         kernel="pallas", interpret=True)
    # same dequantized values, only float reassociation differs
    assert float(jnp.max(jnp.abs(pallas - ref))) < 1e-5 * max(ref_mag, 1.0)

    xla = qa.dequant_attention_decode(q, kd, ks, vd, vs, pc, scale=scale,
                                      kernel="xla")
    if mode == "int8":
        # int8 activation quantization of q and att*vs rides on top: the
        # contexts are convex combinations of rows bounded by the V row
        # magnitudes, so a few quantization half-steps bound the drift
        bound = 3.0 * float(jnp.max(kv_quant.roundtrip_error_bound(v, mode)))
    else:
        bound = 1e-5 * max(ref_mag, 1.0)
    assert float(jnp.max(jnp.abs(xla - ref))) < bound
    # and the two fused paths agree with each other inside the same bound
    assert float(jnp.max(jnp.abs(xla - pallas))) < bound + 1e-5


@pytest.mark.parametrize("cursor", ["fresh", "mid", "full"])
def test_fused_decode_parity_across_cursors(cursor):
    """Prefill-cursor sweep: a just-written slot (pc=0), mid-generation,
    and a full bucket all mask identically across the three paths."""
    TOT, D = 64, 16
    scale = 1.0 / np.sqrt(D)
    q, k, v, kd, ks, vd, vs, _ = _quantized_case(TOT, "int8", seed=7)
    pc = {"fresh": jnp.zeros(3, jnp.int32),
          "mid": jnp.asarray([1, TOT // 2, TOT - 2], jnp.int32),
          "full": jnp.full(3, TOT - 1, jnp.int32)}[cursor]
    ref = _reference(q, kv_quant.dequantize_rows(kd, ks),
                     kv_quant.dequantize_rows(vd, vs), pc, scale)
    pallas = qa.dequant_attention_decode(q, kd, ks, vd, vs, pc, scale=scale,
                                         kernel="pallas", interpret=True)
    xla = qa.dequant_attention_decode(q, kd, ks, vd, vs, pc, scale=scale,
                                      kernel="xla")
    assert float(jnp.max(jnp.abs(pallas - ref))) < 1e-5
    bound = 3.0 * float(jnp.max(kv_quant.roundtrip_error_bound(v, "int8")))
    assert float(jnp.max(jnp.abs(xla - ref))) < bound


def test_unwritten_rows_never_leak():
    """Rows past the cursor must contribute NOTHING, even when the
    quantized storage there holds garbage (stale pages are real: slots are
    reused without zeroing)."""
    TOT, D = 64, 16
    scale = 1.0 / np.sqrt(D)
    q, k, v, kd, ks, vd, vs, _ = _quantized_case(TOT, "int8", seed=11)
    pc = jnp.asarray([3, 10, 40], jnp.int32)
    # poison everything past each cursor with large garbage
    rows = jnp.arange(TOT)[None, None, :, None]
    past = jnp.arange(TOT)[None, None, :] > pc[:, None, None]
    poisoned_kd = jnp.where(rows > pc[:, None, None, None], 127, kd)
    poisoned_vd = jnp.where(rows > pc[:, None, None, None], 127, vd)
    ks_big = jnp.where(past, 1e3, ks)
    vs_big = jnp.where(past, 1e3, vs)
    for kernel in ("pallas", "xla"):
        clean = qa.dequant_attention_decode(
            q, kd, ks, vd, vs, pc, scale=scale, kernel=kernel, interpret=True)
        dirty = qa.dequant_attention_decode(
            q, poisoned_kd, ks_big, poisoned_vd, vs_big, pc, scale=scale,
            kernel=kernel, interpret=True)
        assert float(jnp.max(jnp.abs(clean - dirty))) < 1e-4, kernel


def test_int8_dot_general_matches_dequant_matmul():
    """The int8 x int8 -> int32 weight matmul (``_int8_matmul``) matches
    dequantize-then-f32-matmul within the activation-quantization bound."""
    from mxtpu.quant.serve import _int8_matmul
    rs = np.random.RandomState(0)
    h = jnp.asarray(rs.randn(6, 32).astype(np.float32))
    w = jnp.asarray(rs.randn(24, 32).astype(np.float32))
    w_q, w_s = kv_quant.quantize_rows(w, "int8")
    got = _int8_matmul(h, w_q, w_s)
    ref = h @ kv_quant.dequantize_rows(w_q, w_s).T
    # error source: h's per-row half-step, times sum |w| over the K axis
    bound = float(jnp.max(kv_quant.roundtrip_error_bound(h, "int8"))) \
        * float(jnp.max(jnp.sum(jnp.abs(w), axis=-1)))
    assert float(jnp.max(jnp.abs(got - ref))) <= max(bound, 1e-5)


# ---------------------------------------------------------------------------
# kernel selection + block legality
# ---------------------------------------------------------------------------


def test_decode_kernel_mode_validation(monkeypatch):
    assert qa.decode_kernel_mode("pallas") == "pallas"
    assert qa.decode_kernel_mode("XLA") == "xla"
    assert qa.decode_kernel_mode("") is None
    monkeypatch.delenv("MXTPU_DECODE_KERNEL", raising=False)
    assert qa.decode_kernel_mode() is None
    monkeypatch.setenv("MXTPU_DECODE_KERNEL", "pallas")
    assert qa.decode_kernel_mode() == "pallas"
    with pytest.raises(ValueError, match="MXTPU_DECODE_KERNEL"):
        qa.decode_kernel_mode("cuda")


def test_resolve_decode_kernel_only_auto_chooses(monkeypatch):
    monkeypatch.delenv("MXTPU_DECODE_KERNEL", raising=False)
    on_tpu = jax.default_backend() == "tpu"
    # auto: backend decides, and degrades per bucket where it cannot tile
    assert qa.resolve_decode_kernel() == ("pallas" if on_tpu else "xla")
    assert qa.resolve_decode_kernel(None, TOT=136, D=16) == "xla"
    assert qa.resolve_decode_kernel(None, TOT=256, D=600) == "xla"
    # explicit pallas at a legal bucket sticks
    assert qa.resolve_decode_kernel("pallas", TOT=128, D=16) == "pallas"
    # bucket 96: whole-axis blocks are interpret-legal only — on hardware
    # sub-128 vector loads are Mosaic-illegal
    if on_tpu:
        with pytest.raises(ValueError, match="TOT=96"):
            qa.resolve_decode_kernel("pallas", TOT=96, D=16)
    else:
        assert qa.resolve_decode_kernel("pallas", TOT=96, D=16) == "pallas"
    # an explicit request that cannot be met raises — it never hands back
    # xla under the pallas name (kwarg and env alike)
    with pytest.raises(ValueError, match="TOT=136"):
        qa.resolve_decode_kernel("pallas", TOT=136, D=16)
    with pytest.raises(ValueError, match="D=600"):
        qa.resolve_decode_kernel("pallas", TOT=256, D=600)
    monkeypatch.setenv("MXTPU_DECODE_KERNEL", "pallas")
    with pytest.raises(ValueError, match="TOT=136"):
        qa.resolve_decode_kernel(None, TOT=136, D=16)
    monkeypatch.delenv("MXTPU_DECODE_KERNEL")
    assert qa.resolve_decode_kernel("xla", TOT=256, D=16) == "xla"


def test_pick_block_raises_naming_mosaic_constraint():
    """ISSUE 16 satellite: the old code returned 0 and let Mosaic fail with
    an opaque lowering error; now the constraint is named up front."""
    assert _pick_block(256) == 256
    assert _pick_block(2048, 512) == 512
    assert _pick_block(96) == 96            # whole sub-128 axis, 8-divisible
    assert _pick_block(64, 64) == 64        # sub-128 cap, whole axis
    with pytest.raises(ValueError, match="[Mm]osaic"):
        _pick_block(100)                    # not %128, not 8-divisible
    with pytest.raises(ValueError, match="multiple of 128"):
        _pick_block(136)                    # 8-divisible but >128, not %128
    with pytest.raises(ValueError, match="[Mm]osaic"):
        _pick_block(136, 64)                # sub-128 cap, axis too long


# ---------------------------------------------------------------------------
# trace-once: env flips never retrace a live engine
# ---------------------------------------------------------------------------


def _decode_traces():
    return profiler.get_compile_stats().get(
        "serving_decode", {}).get("traces", 0)


def test_env_flip_never_retraces_live_engine(monkeypatch):
    """The engine resolves its decode kernel ONCE at init; flipping
    ``MXTPU_DECODE_KERNEL`` between dispatches must not retrace (the
    program-cache key stays (slots, bucket, chunk))."""
    monkeypatch.delenv("MXTPU_DECODE_KERNEL", raising=False)
    mx.rng.seed(0)
    net = transformer_lm("tiny", vocab_size=VOCAB)
    net.initialize()
    net(nd.array(np.zeros((1, 4), np.int32)))
    rs = np.random.RandomState(5)
    # long enough to overflow the prompt-only prefill bucket -> real decode
    prompt = rs.randint(1, VOCAB, size=30).tolist()
    with ServingEngine(net, slots=2, queue_depth=8, chunk=4,
                       quant="int8_kv", decode_kernel="xla") as eng:
        first = eng.submit(prompt, 8).result(timeout=300)
        after_first = _decode_traces()
        for flip in ("pallas", "xla", "pallas"):
            monkeypatch.setenv("MXTPU_DECODE_KERNEL", flip)
            again = eng.submit(prompt, 8).result(timeout=300)
            assert again == first           # greedy, same program
        assert _decode_traces() == after_first
        assert eng.stats()["decode_kernel"] == "xla"
