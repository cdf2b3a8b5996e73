"""Fused dequant-attention decode — the quantized-KV hot path (ISSUE 16).

PR 14's int8/fp8 paged KV cache shrinks residency 3.56x but the original
attention read ran ``dequantize_rows`` over the WHOLE per-layer cache as
plain XLA ops before the score einsum — a full-precision KV materialization
per layer per decode step, which is exactly why ``quant_decode_speedup``
ratcheted at 0.78 (quantization paid in bytes and charged in time). This
module makes the dequantize happen *inside* the attention read on both
execution paths:

* **pallas** — a Pallas TPU kernel streams int8/fp8 KV tiles through VMEM
  and dequantizes in-register inside the online-softmax body (same flash
  structure as ``attention.py``'s forward, specialised to the one-query
  decode shape). The per-row f32 scales ride as an 8-sublane broadcast
  (Mosaic's row-block tiling rule, see ``_flash_fwd_kernel``'s lse); block
  legality reuses ``_pick_block``. The full-precision KV
  view never exists anywhere — not in HBM, not in VMEM.
* **xla** — the A/B + CPU/interpret fallback. No Pallas, but the scales
  fold into the einsums as per-row scalars (``q . (data*s) == (q . data)*s``
  and ``att @ (data*s) == (att*s) @ data``), so this path ALSO never
  materializes a dequantized ``(S, H, TOT, D)`` cache — the int8 cache
  feeds the score dot directly.

Selection is ``MXTPU_DECODE_KERNEL=pallas|xla`` (engine kwarg > env; unset
= auto: pallas on TPU, xla elsewhere), resolved ONCE per compiled program
at build time — flipping the env between dispatches can never retrace a
live engine program. Only auto chooses per bucket; an explicit ``pallas`` at
a Mosaic-illegal bucket (TOT not a 128-multiple on hardware) raises rather
than serve from the xla path under the pallas name. Off-TPU the kernel runs
in interpret mode so the parity suite exercises the real kernel body on CPU.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .attention import _NEG_INF, _lanes, _pick_block

__all__ = ["decode_kernel_mode", "resolve_decode_kernel",
           "dequant_attention_decode"]

DECODE_KERNELS = ("pallas", "xla")
_AUTO = ("", "auto")


def decode_kernel_mode(value=None) -> Optional[str]:
    """Resolve the decode-kernel selector: ``value`` if given, else
    ``MXTPU_DECODE_KERNEL``. Returns None (auto), 'pallas', or 'xla';
    anything else raises ``ValueError`` (never a silent fallback)."""
    raw = os.environ.get("MXTPU_DECODE_KERNEL", "") if value is None else value
    raw = str(raw).strip().lower()
    if raw in _AUTO:
        return None
    if raw not in DECODE_KERNELS:
        raise ValueError(
            f"MXTPU_DECODE_KERNEL={raw!r} (choose from {list(DECODE_KERNELS)}, "
            "or unset for auto: pallas on TPU, xla elsewhere)")
    return raw


def _legal_bucket(TOT: int) -> bool:
    """Block legality of the KV bucket under the Mosaic tiling rule
    ``_pick_block`` enforces: 128-multiples tile; sub-128 buckets are only
    legal as the whole axis (engine buckets are 32-multiples, so 32/64/96
    qualify in interpret mode; real Mosaic needs the 128-multiple)."""
    return TOT % 128 == 0 or (TOT <= 128 and TOT % 8 == 0)


def resolve_decode_kernel(mode=None, TOT: Optional[int] = None,
                          D: Optional[int] = None) -> str:
    """Concrete kernel for one compiled decode program, decided at BUILD
    time (the engine resolves its mode once per lifetime, so program-cache
    keys stay (slots, bucket, chunk) and env flips never retrace). Only
    auto chooses: pallas on TPU where the kernel can tile the shape (bucket
    legality per ``_legal_bucket``, head dim <= 512), xla otherwise. An
    EXPLICIT pallas request (``kernel='pallas'`` /
    ``MXTPU_DECODE_KERNEL=pallas``) at a shape the kernel cannot tile raises
    ``ValueError`` — it never runs another kernel under that name."""
    mode = decode_kernel_mode(mode)
    on_tpu = jax.default_backend() == "tpu"
    explicit = mode is not None
    if mode is None:
        mode = "pallas" if on_tpu else "xla"
    if mode == "pallas" and TOT is not None:
        legal = (TOT % 128 == 0) if on_tpu else _legal_bucket(TOT)
        if not legal or (D is not None and D > 512):
            if explicit:
                raise ValueError(
                    f"decode kernel 'pallas' was requested but cannot tile "
                    f"KV bucket TOT={TOT}, head dim D={D}: it needs a "
                    f"128-multiple bucket (interpreted off-TPU, a whole-axis "
                    f"bucket <= 128 also passes) and D <= 512. Leave the "
                    f"kernel unset and auto picks per bucket")
            return "xla"
    return mode


# ---------------------------------------------------------------------------
# Pallas kernel: in-register dequant inside the online-softmax decode body
# ---------------------------------------------------------------------------


def _dequant_decode_kernel(lim_ref, q_ref, kd_ref, ks_ref, vd_ref, vs_ref,
                           o_ref, *, block_t: int, scale: float):
    """One (slot*head) program: stream quantized K/V tiles, dequantize
    in-register, online softmax over positions ``0..lim``. The query rides
    broadcast over 8 sublanes (a bare (1, D) row block is Mosaic-illegal,
    same trick as the flash lse), so every row of the (8, Dp) tiles
    computes the identical result and the wrapper keeps row 0."""
    from jax.experimental import pallas as pl

    q = q_ref[0].astype(jnp.float32) * scale           # (8, Dp)
    lim = lim_ref[0, 0, 0]                             # this slot's position
    tot = kd_ref.shape[1]
    num_tb = tot // block_t

    m0 = jnp.full((8, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((8, 1), jnp.float32)
    o0 = jnp.zeros((8, q.shape[1]), jnp.float32)

    def body(tb, carry):
        m, l, o = carry
        t0 = tb * block_t
        # int8/fp8 tile + per-row f32 scale -> f32 tile, in-register only
        k_blk = kd_ref[0, pl.dslice(t0, block_t), :].astype(jnp.float32) \
            * ks_ref[0, 0, pl.dslice(t0, block_t)][:, None]
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32)  # (8, bt)
        cols = t0 + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols <= lim, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = corr * l + jnp.sum(p, axis=-1, keepdims=True)
        v_blk = vd_ref[0, pl.dslice(t0, block_t), :].astype(jnp.float32) \
            * vs_ref[0, 0, pl.dslice(t0, block_t)][:, None]
        o_new = corr * o + jnp.dot(p, v_blk, preferred_element_type=jnp.float32)
        return m_new, l_new, o_new

    # only tiles at or below the slot's position hold written rows
    num_iter = jnp.minimum(lim // block_t + 1, num_tb)
    m, l, o = lax.fori_loop(0, num_iter, body, (m0, l0, o0))
    l = jnp.maximum(l, 1e-30)
    o_ref[0] = (o / l).astype(o_ref.dtype)


def _pad_last(x, dp: int):
    d = x.shape[-1]
    if dp == d:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (0, dp - d)))


def _decode_pallas(q, kd, ks, vd, vs, pc, scale: float, interpret: bool):
    """Kernel launch for the decode shape: q (S,H,D); kd/vd (S,H,TOT,D)
    quantized storage; ks/vs (S,H,TOT) f32 row scales; pc (S,) positions."""
    from jax.experimental import pallas as pl

    S, H, TOT, D = kd.shape
    BH = S * H
    dp = _lanes(D)
    # one query row: the float32 k and v tiles are (block_t, dp), 2 MiB
    # together at the widest head (512 lanes) and 512 positions, so no
    # width asks for a smaller tile
    block_t = _pick_block(TOT)
    q8 = _pad_last(jnp.broadcast_to(q.reshape(BH, 1, D), (BH, 8, D)), dp)
    kd2 = _pad_last(kd.reshape(BH, TOT, D), dp)
    vd2 = _pad_last(vd.reshape(BH, TOT, D), dp)
    # per-row scales ride 8-sublane broadcast (Mosaic row-block tiling)
    ks2 = jnp.broadcast_to(ks.reshape(BH, 1, TOT), (BH, 8, TOT)) \
        .astype(jnp.float32)
    vs2 = jnp.broadcast_to(vs.reshape(BH, 1, TOT), (BH, 8, TOT)) \
        .astype(jnp.float32)
    lim = jnp.broadcast_to(
        jnp.repeat(pc.astype(jnp.int32), H).reshape(BH, 1, 1), (BH, 8, 128))

    kernel = functools.partial(_dequant_decode_kernel, block_t=block_t,
                               scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=(BH,),
        in_specs=[
            pl.BlockSpec((1, 8, 128), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 8, dp), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, TOT, dp), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 8, TOT), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, TOT, dp), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 8, TOT), lambda b: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 8, dp), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, 8, dp), q.dtype),
        name="decode_attn_quant",
        interpret=interpret,
    )(lim, q8, kd2, ks2, vd2, vs2)
    return out[:, 0, :D].reshape(S, H, D)


def _decode_xla(q, kd, ks, vd, vs, pc, scale: float):
    """XLA path over the quantized storage — no Pallas, but both attention
    dots run int8 x int8 -> int32 ``dot_general`` when the cache is int8
    (the same dynamic per-row activation quantization as
    ``quant.serve._int8_matmul``): the query rows quantize against the int8
    K cache for the scores, and the ``att * vscale`` rows quantize against
    the int8 V cache for the context, with the (activation x row) scales
    folded into the int32 accumulator readout. On TPU that is the MXU's
    2x-peak int8 path; on CPU it reads a quarter of the bytes — either
    way the dequantized (S, H, TOT, D) view is never materialized, which
    was the whole 0.78x regression. An fp8 cache (no int8 accumulator)
    keeps f32 dots with the scales folded in as per-row scalars
    (``q . (data*s) == (q . data)*s`` and ``att @ (data*s) == (att*s) @
    data``)."""
    TOT = kd.shape[2]
    mask = jnp.arange(TOT)[None, None, :] <= pc[:, None, None]
    if kd.dtype == jnp.int8:
        from ..quant import kv_quant
        q_q, q_s = kv_quant.quantize_rows(q, "int8")
        acc = lax.dot_general(q_q, kd, (((2,), (3,)), ((0, 1), (0, 1))),
                              preferred_element_type=jnp.int32)
        s = acc.astype(jnp.float32) * q_s[..., None] * ks * scale
        att = jax.nn.softmax(jnp.where(mask, s, _NEG_INF), axis=-1)
        # masked positions are exactly 0 in att, so they quantize to the
        # exact 0 code — the int8 context read never leaks an unwritten row
        w_q, w_s = kv_quant.quantize_rows(att * vs, "int8")
        acc2 = lax.dot_general(w_q, vd, (((2,), (2,)), ((0, 1), (0, 1))),
                               preferred_element_type=jnp.int32)
        return acc2.astype(jnp.float32) * w_s[..., None]
    s = jnp.einsum("bhd,bhtd->bht", q, kd.astype(jnp.float32)) * ks * scale
    att = jax.nn.softmax(jnp.where(mask, s, _NEG_INF), axis=-1)
    return jnp.einsum("bht,bhtd->bhd", att * vs, vd.astype(jnp.float32))


def dequant_attention_decode(q, kd, ks, vd, vs, pc, *, scale: float,
                             kernel=None, interpret: Optional[bool] = None):
    """One decode-step attention read over a quantized paged KV cache.

    ``q`` (S, H, D) working-precision queries; ``kd``/``vd`` (S, H, TOT, D)
    quantized storage (int8 or fp8); ``ks``/``vs`` (S, H, TOT) per-row f32
    scales; ``pc`` (S,) int32 per-slot positions (position ``t`` attends
    iff ``t <= pc[slot]``). Returns the (S, H, D) context in ``q``'s dtype.

    ``kernel`` picks the path ('pallas' / 'xla' / None = resolve from
    ``MXTPU_DECODE_KERNEL`` + backend); off-TPU the Pallas path runs in
    interpret mode unless ``interpret`` overrides. Both paths compute the
    identical masked softmax over the identical dequantized values — they
    differ only in float reassociation, bounded well inside the
    quantization ``roundtrip_error_bound`` (the parity suite pins this)."""
    kernel = resolve_decode_kernel(kernel, TOT=kd.shape[2], D=kd.shape[3])
    if kernel == "pallas":
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        return _decode_pallas(q, kd, ks, vd, vs, pc, scale, interpret)
    return _decode_xla(q, kd, ks, vd, vs, pc, scale)
