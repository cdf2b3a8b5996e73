"""Attention ops — flash attention as Pallas TPU kernels (fwd + bwd) with an
XLA fallback.

The reference predates fused attention (its transformer support is just
``_contrib_div_sqrt_dim``, contrib/transformer.cc:33); for a TPU-native
framework attention IS the hot op, so it gets the Pallas treatment per the
long-context mandate (SURVEY.md §5): blockwise online-softmax (flash) keeps the
T×T score matrix out of HBM — kernels stream K/V tiles through VMEM.

Production shapes engage the kernel: head dims 64/96/128/... (any D ≤ 512) are
zero-padded to the 128-lane width inside the wrapper (padding columns
contribute nothing to q·kᵀ and produce zero output columns, sliced off
afterwards). Sequence lengths engage when T % 128 == 0 on real hardware
(sub-128 whole-axis blocks pass in interpret mode but real Mosaic rejects
their vector loads — observed on v5e); anything else falls back to the XLA
reference, which is equally fast at those sizes. The backward pass is the standard flash
backward — forward saves the per-row log-sum-exp; two kernels recompute the
probabilities per tile and accumulate dq (grid over q blocks) and dk/dv (grid
over k blocks) without materializing T×T.
"""

from __future__ import annotations

import contextlib
import functools
import math
import os
import threading
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register

__all__ = ["attention_reference", "flash_attention", "flash_chunk",
           "partition_scope"]

_NEG_INF = -1e30


def attention_reference(q, k, v, causal: bool = False, scale: Optional[float] = None,
                        bias=None):
    """Pure-XLA softmax attention. q,k,v: (B, H, T, D). The bias-free path is
    the single shared implementation (``_chunk_reference_lse``)."""
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    if bias is None:
        return _chunk_reference_lse(q, k, v, causal, s)[0]
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * s + bias
    if causal:
        # top-left alignment (row i attends keys 0..i), matching torch is_causal
        # and the Pallas kernel's rows>=cols convention
        tq, tk = logits.shape[-2], logits.shape[-1]
        rows = lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
        cols = lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        logits = jnp.where(rows >= cols, logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


# ---------------------------------------------------------------------------
# Pallas flash kernels
# ---------------------------------------------------------------------------


def _pick_block(t: int, cap: int = 512) -> int:
    """Largest legal q/k block: Mosaic requires the lse/delta row blocks'
    last dim to be 128-divisible or equal to the full axis, so blocks are
    multiples of 128 dividing t, or the whole axis (t <= 128, t % 8 == 0).

    Cap 512 measured fastest on v5e at production shapes (B4 H16 T2048 D64
    fwd+bwd: 15.1 ms @128 → 6.7 ms @512, vs 20.7 ms XLA reference); 1024
    exceeds VMEM and fails to compile. Launch sites scale the cap down with
    the padded head dim (`_block_cap`) so large-D shapes stay inside VMEM.

    Raises :exc:`ValueError` when no Mosaic-legal block exists — launch
    sites gate on ``_use_pallas``/``_legal_bucket`` first, so hitting this
    means a kernel was invoked directly at an unsupported length; the error
    names the constraint instead of surfacing as an opaque Mosaic lowering
    failure deep inside ``pallas_call``."""
    if cap < 128:
        # below 128 only a whole-axis block is Mosaic-legal (the lse/delta
        # row block must be 128-divisible or the full axis)
        if t <= cap and t % 8 == 0:
            return t
        raise ValueError(
            f"no Mosaic-legal flash block for axis length {t} under cap "
            f"{cap}: sub-128 caps admit only a whole-axis block, needing "
            f"t <= {cap} and t % 8 == 0 (Mosaic sublane tiling)")
    if t % 128 == 0:
        b = min(cap - cap % 128, t)
        while b > 128 and t % b != 0:
            b -= 128
        return b
    if t <= 128 and t % 8 == 0:
        return t
    raise ValueError(
        f"no Mosaic-legal flash block for axis length {t}: the lse/delta "
        f"row block's last dim must be a multiple of 128 or the whole "
        f"axis, so t must be a multiple of 128, or t <= 128 with "
        f"t % 8 == 0. Pad the sequence (e.g. to {-(-t // 128) * 128}) or "
        f"take the XLA reference path")


def _block_cap(dp: int) -> int:
    """VMEM-aware block cap: 512 validated at Dp=128; scale down linearly in
    the padded head dim so the per-program tiles stay in the same budget
    (Dp=256 → 256, Dp≥512 → 128, the previously-validated floor)."""
    return max(128, 512 * 128 // max(dp, 128))


def _bwd_mode() -> str:
    """Flash-backward launch shape: ``'split'`` (default — the validated
    two-kernel dq then dk/dv pair) or ``'fused'`` (``MXTPU_FLASH_BWD=fused``
    — one kernel per (batch·head, tile) computing dq for its q-tile AND
    dk/dv for its k-tile, halving launches and re-streaming each opposing
    tile once instead of twice across kernels). Long-context retune knob
    (PR16 tentpole c); read at trace time, so flipping it retraces."""
    return "fused" if os.environ.get(
        "MXTPU_FLASH_BWD", "").strip().lower() == "fused" else "split"


def _lse_store_dtype():
    """Storage dtype for the sublane-broadcast lse/delta rows the backward
    kernels stream: f32 (default, exact) or bf16 (``MXTPU_FLASH_LSE=bf16``)
    which halves that HBM traffic at long T. Kernels accumulate in f32
    either way — only the stored rows round. Softmax weights are exp(s-lse),
    so a bf16 lse (rel err ~2^-8) perturbs weights ~0.4% — fine for
    training steps, not for bit-exactness guards, hence opt-in."""
    return jnp.bfloat16 if os.environ.get(
        "MXTPU_FLASH_LSE", "").strip().lower() == "bf16" else jnp.float32


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k: int,
                      causal: bool, scale: float):
    """One (batch·head, q-block) program: stream K/V tiles, online softmax.
    Also writes the per-row log-sum-exp needed by the backward kernels."""
    from jax.experimental import pallas as pl

    q = q_ref[0].astype(jnp.float32) * scale  # (block_q, d)
    block_q = q.shape[0]
    kv_len = k_ref.shape[1]
    num_kb = kv_len // block_k
    qi = pl.program_id(1)

    m0 = jnp.full((block_q, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    o0 = jnp.zeros((block_q, q.shape[1]), jnp.float32)

    q_start = qi * block_q

    def body(kb, carry):
        m, l, o = carry
        k_blk = k_ref[0, pl.dslice(kb * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.dslice(kb * block_k, block_k), :].astype(jnp.float32)
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32)  # (bq, bk)
        if causal:
            rows = q_start + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = kb * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = corr * l + jnp.sum(p, axis=-1, keepdims=True)
        o_new = corr * o + jnp.dot(p, v_blk, preferred_element_type=jnp.float32)
        return m_new, l_new, o_new

    if causal:
        # only key blocks up to the diagonal contribute
        last_kb = (q_start + block_q - 1) // block_k + 1
        num_iter = jnp.minimum(num_kb, last_kb)
    else:
        num_iter = num_kb
    m, l, o = lax.fori_loop(0, num_iter, body, (m0, l0, o0))
    l = jnp.maximum(l, 1e-30)
    o_ref[0] = (o / l).astype(o_ref.dtype)
    # lse travels broadcast over 8 sublanes — Mosaic requires the block's
    # second-to-last dim to be 8-divisible (a bare (1, block_q) is illegal)
    lse_ref[0] = jnp.broadcast_to((m + jnp.log(l))[:, 0][None, :],
                                  (8, block_q))


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, *, block_k: int, causal: bool, scale: float):
    """dq for one q block: loop K/V tiles, recompute P from the saved lse."""
    from jax.experimental import pallas as pl

    q = q_ref[0].astype(jnp.float32) * scale
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0, 0].astype(jnp.float32)[:, None]
    delta = delta_ref[0, 0].astype(jnp.float32)[:, None]
    block_q = q.shape[0]
    qi = pl.program_id(1)
    q_start = qi * block_q
    kv_len = k_ref.shape[1]
    num_kb = kv_len // block_k

    def body(kb, dq):
        k_blk = k_ref[0, pl.dslice(kb * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.dslice(kb * block_k, block_k), :].astype(jnp.float32)
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32)
        if causal:
            rows = q_start + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = kb * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        p = jnp.exp(s - lse)                       # masked entries underflow to 0
        dp = jnp.dot(do, v_blk.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        return dq + jnp.dot(ds, k_blk, preferred_element_type=jnp.float32)

    if causal:
        last_kb = (q_start + block_q - 1) // block_k + 1
        num_iter = jnp.minimum(num_kb, last_kb)
    else:
        num_iter = num_kb
    dq0 = jnp.zeros((block_q, q.shape[1]), jnp.float32)
    dq = lax.fori_loop(0, num_iter, body, dq0)
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, *, block_q: int, causal: bool,
                          scale: float):
    """dk/dv for one k block: loop q tiles, recompute P from the saved lse."""
    from jax.experimental import pallas as pl

    k_blk = k_ref[0].astype(jnp.float32)           # (block_k, d)
    v_blk = v_ref[0].astype(jnp.float32)
    block_k = k_blk.shape[0]
    kb = pl.program_id(1)
    k_start = kb * block_k
    t = q_ref.shape[1]
    num_qb = t // block_q

    def body(qb, carry):
        dk, dv = carry
        qs = qb * block_q
        q = q_ref[0, pl.dslice(qs, block_q), :].astype(jnp.float32) * scale
        do = do_ref[0, pl.dslice(qs, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, 0, pl.dslice(qs, block_q)].astype(jnp.float32)[:, None]
        delta = delta_ref[0, 0, pl.dslice(qs, block_q)].astype(
            jnp.float32)[:, None]
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32)
        if causal:
            rows = qs + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = k_start + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        p = jnp.exp(s - lse)
        dv_new = dv + jnp.dot(p.T, do, preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v_blk.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_new = dk + jnp.dot(ds.T, q, preferred_element_type=jnp.float32)
        return dk_new, dv_new

    start_qb = (k_start // block_q) if causal else 0
    z = jnp.zeros((block_k, k_blk.shape[1]), jnp.float32)
    dk, dv = lax.fori_loop(start_qb, num_qb, body, (z, z))
    # dk absorbed one factor of scale through q; no extra factor needed
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                            dq_ref, dk_ref, dv_ref, *, block: int,
                            causal: bool, scale: float):
    """One (batch·head, tile i) program producing dq for q-tile i AND dk/dv
    for k-tile i (``MXTPU_FLASH_BWD=fused``). Requires self-attention
    tiling (T == Tk, shared block). The two inner loops walk complementary
    causal wedges — key tiles j <= i for dq, query tiles j >= i for dk/dv —
    so together each program touches one full stripe of the T×T square and
    the grid covers it exactly once, in half the kernel launches of the
    split pair."""
    from jax.experimental import pallas as pl

    i = pl.program_id(1)
    t = q_ref.shape[1]
    num_b = t // block
    i_start = i * block

    q_i = q_ref[0, pl.dslice(i_start, block), :].astype(jnp.float32) * scale
    do_i = do_ref[0, pl.dslice(i_start, block), :].astype(jnp.float32)
    lse_i = lse_ref[0, 0, pl.dslice(i_start, block)].astype(
        jnp.float32)[:, None]
    delta_i = delta_ref[0, 0, pl.dslice(i_start, block)].astype(
        jnp.float32)[:, None]
    k_i = k_ref[0, pl.dslice(i_start, block), :].astype(jnp.float32)
    v_i = v_ref[0, pl.dslice(i_start, block), :].astype(jnp.float32)

    # -- dq for q-tile i: stream key tiles j (j <= i when causal) ----------
    def dq_body(j, dq):
        ks = j * block
        k_blk = k_ref[0, pl.dslice(ks, block), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.dslice(ks, block), :].astype(jnp.float32)
        s = jnp.dot(q_i, k_blk.T, preferred_element_type=jnp.float32)
        if causal:
            rows = i_start + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = ks + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        p = jnp.exp(s - lse_i)
        dp = jnp.dot(do_i, v_blk.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta_i)
        return dq + jnp.dot(ds, k_blk, preferred_element_type=jnp.float32)

    dq0 = jnp.zeros((block, q_i.shape[1]), jnp.float32)
    dq = lax.fori_loop(0, jnp.minimum(num_b, i + 1) if causal else num_b,
                       dq_body, dq0)
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)

    # -- dk/dv for k-tile i: stream query tiles j (j >= i when causal) -----
    def dkv_body(j, carry):
        dk, dv = carry
        qs = j * block
        q_blk = q_ref[0, pl.dslice(qs, block), :].astype(jnp.float32) * scale
        do_blk = do_ref[0, pl.dslice(qs, block), :].astype(jnp.float32)
        lse_blk = lse_ref[0, 0, pl.dslice(qs, block)].astype(
            jnp.float32)[:, None]
        delta_blk = delta_ref[0, 0, pl.dslice(qs, block)].astype(
            jnp.float32)[:, None]
        s = jnp.dot(q_blk, k_i.T, preferred_element_type=jnp.float32)
        if causal:
            rows = qs + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = i_start + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        p = jnp.exp(s - lse_blk)
        dv_new = dv + jnp.dot(p.T, do_blk, preferred_element_type=jnp.float32)
        dp = jnp.dot(do_blk, v_i.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta_blk)
        dk_new = dk + jnp.dot(ds.T, q_blk, preferred_element_type=jnp.float32)
        return dk_new, dv_new

    z = jnp.zeros((block, k_i.shape[1]), jnp.float32)
    dk, dv = lax.fori_loop(i if causal else 0, num_b, dkv_body, (z, z))
    # dk absorbed one factor of scale through q_blk; no extra factor needed
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _pad_d(x):
    d = x.shape[-1]
    dp = -(-d // 128) * 128
    if dp == d:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (0, dp - d)))


def _flash_attention_pallas(q, k, v, causal: bool, scale: float,
                            block_q: int = 512, block_k: int = 512,
                            interpret: bool = False):
    """Forward kernel launch; returns (out, lse). q,k,v: (B, H, T, D)."""
    from jax.experimental import pallas as pl

    B, H, T, D = q.shape
    Tk = k.shape[2]
    qq = _pad_d(q.reshape(B * H, T, D))
    kk = _pad_d(k.reshape(B * H, Tk, D))
    vv = _pad_d(v.reshape(B * H, Tk, D))
    Dp = qq.shape[-1]
    block_q = _pick_block(T, min(block_q, _block_cap(Dp)))
    block_k = _pick_block(Tk, min(block_k, _block_cap(Dp)))
    grid = (B * H, T // block_q)

    kernel = functools.partial(_flash_fwd_kernel, block_k=block_k, causal=causal,
                               scale=scale)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, Dp), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, Tk, Dp), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, Tk, Dp), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, Dp), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 8, block_q), lambda b, i: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, T, Dp), q.dtype),
            jax.ShapeDtypeStruct((B * H, 8, T), jnp.float32),
        ],
        name="flash_fwd",
        interpret=interpret,
    )(qq, kk, vv)
    return out[..., :D].reshape(B, H, T, D), lse[:, 0, :]


def _flash_backward_pallas(q, k, v, o, lse, g, causal: bool, scale: float,
                           block_q: int = 512, block_k: int = 512,
                           interpret: bool = False, lse_cot=None):
    """Flash backward: dq via q-block grid, dk/dv via k-block grid (the
    default 'split' launch), or one fused grid doing both per tile when
    ``MXTPU_FLASH_BWD=fused`` and the shape is self-attention tiling.

    ``lse_cot`` (B,H,T): optional cotangent of the log-sum-exp output (ring
    merges differentiate through lse); it folds into the delta term exactly —
    dS = P∘(dP - (Δ - dlse)) since ∂lse/∂S = P."""
    from jax.experimental import pallas as pl

    B, H, T, D = q.shape
    Tk = k.shape[2]
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    if lse_cot is not None:
        delta = delta - lse_cot.astype(jnp.float32)
    # lse/delta ride (BH, 8, T): sublane-broadcast to satisfy Mosaic tiling;
    # MXTPU_FLASH_LSE=bf16 halves this streamed traffic (kernels re-widen)
    row_dt = _lse_store_dtype()
    delta = jnp.broadcast_to(
        delta.astype(row_dt).reshape(B * H, 1, T), (B * H, 8, T))
    lse = jnp.broadcast_to(
        lse.astype(row_dt).reshape(B * H, 1, T), (B * H, 8, T))
    qq = _pad_d(q.reshape(B * H, T, D))
    kk = _pad_d(k.reshape(B * H, Tk, D))
    vv = _pad_d(v.reshape(B * H, Tk, D))
    gg = _pad_d(g.reshape(B * H, T, D))
    Dp = qq.shape[-1]
    # same padded-D cap as the forward (blocks must match its VMEM budget)
    block_q = _pick_block(T, min(block_q, _block_cap(Dp)))
    block_k = _pick_block(Tk, min(block_k, _block_cap(Dp)))

    if _bwd_mode() == "fused" and T == Tk and block_q == block_k:
        fused = functools.partial(_flash_bwd_fused_kernel, block=block_q,
                                  causal=causal, scale=scale)
        dq, dk, dv = pl.pallas_call(
            fused,
            grid=(B * H, T // block_q),
            in_specs=[
                pl.BlockSpec((1, T, Dp), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((1, Tk, Dp), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((1, Tk, Dp), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((1, T, Dp), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((1, 8, T), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((1, 8, T), lambda b, i: (b, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, Dp), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, block_q, Dp), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, block_q, Dp), lambda b, i: (b, i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((B * H, T, Dp), q.dtype),
                jax.ShapeDtypeStruct((B * H, Tk, Dp), k.dtype),
                jax.ShapeDtypeStruct((B * H, Tk, Dp), v.dtype),
            ],
            name="flash_bwd_fused",
            interpret=interpret,
        )(qq, kk, vv, gg, lse, delta)
        return (dq[..., :D].reshape(B, H, T, D),
                dk[..., :D].reshape(B, H, Tk, D),
                dv[..., :D].reshape(B, H, Tk, D))

    dq_kernel = functools.partial(_flash_bwd_dq_kernel, block_k=block_k,
                                  causal=causal, scale=scale)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(B * H, T // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, Dp), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, Tk, Dp), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, Tk, Dp), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, block_q, Dp), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 8, block_q), lambda b, i: (b, 0, i)),
            pl.BlockSpec((1, 8, block_q), lambda b, i: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_q, Dp), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, T, Dp), q.dtype),
        name="flash_bwd_dq",
        interpret=interpret,
    )(qq, kk, vv, gg, lse, delta)

    dkv_kernel = functools.partial(_flash_bwd_dkv_kernel, block_q=block_q,
                                   causal=causal, scale=scale)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(B * H, Tk // block_k),
        in_specs=[
            pl.BlockSpec((1, T, Dp), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, block_k, Dp), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, Dp), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, T, Dp), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, 8, T), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, 8, T), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, Dp), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, Dp), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Tk, Dp), k.dtype),
            jax.ShapeDtypeStruct((B * H, Tk, Dp), v.dtype),
        ],
        name="flash_bwd_dkv",
        interpret=interpret,
    )(qq, kk, vv, gg, lse, delta)

    return (dq[..., :D].reshape(B, H, T, D),
            dk[..., :D].reshape(B, H, Tk, D),
            dv[..., :D].reshape(B, H, Tk, D))


# ---------------------------------------------------------------------------
# more than one chip: the kernels shard_map themselves
# ---------------------------------------------------------------------------
# GSPMD cannot partition a Mosaic custom call ("Mosaic kernels cannot be
# automatically partitioned", first seen on the four-chip v5e host in PR 21):
# on a multi-device mesh the kernel launch has to sit inside a shard_map.
# Attention is independent over batch and heads, so the launch is split over
# whatever mesh axes carry those two dims and every device runs the kernel on
# its own (B/n, H/m, T, D) block with the FULL sequence.

_partition = threading.local()


@contextlib.contextmanager
def partition_scope(mesh, spec):
    """Tell the flash kernels how ``(B, H, ...)`` activations are split over
    ``mesh`` while a step TRACES under jit (a tracer carries no sharding to
    read it from). ``spec`` is a PartitionSpec whose first two entries name
    the batch and head axes; ``DataParallelTrainer`` opens this around its
    step. Concrete arrays need no scope — their own sharding says it."""
    prev = getattr(_partition, "value", None)
    _partition.value = (mesh, spec)
    try:
        yield
    finally:
        _partition.value = prev


def _partition_for(q):
    """``(mesh, P(batch axes, head axes))`` to shard_map a kernel launch
    over, or None when ``q`` lives on one device."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    scope = getattr(_partition, "value", None)
    sharding = None if isinstance(q, jax.core.Tracer) \
        else getattr(q, "sharding", None)       # host arrays carry none
    if sharding is not None and len(sharding.device_set) == 1:
        return None
    if isinstance(sharding, NamedSharding):
        mesh, spec = sharding.mesh, sharding.spec
    elif scope is not None:
        mesh, spec = scope
    elif sharding is None:
        return None     # traced outside any scope: one device, or jax raises
    else:
        raise NotImplementedError(
            f"flash attention on an array spread over "
            f"{len(sharding.device_set)} devices by a {type(sharding).__name__}"
            f": open mxtpu.ops.attention.partition_scope(mesh, spec) so the "
            f"kernel knows which mesh axes carry batch and heads")
    if mesh.devices.size == 1:
        return None
    from ..parallel.fsdp import filter_spec
    # batch and heads only: the kernel needs the whole sequence on a device
    return mesh, filter_spec(P(*tuple(spec)[:2]), q.shape[:2], mesh)


def _launch(local, args, n_out: int):
    """Run ``local(*args)`` — every operand and result is ``(B, H, ...)`` —
    directly on one device, or shard_mapped over batch and heads."""
    part = _partition_for(args[0])
    if part is None:
        return local(*args)
    from ..parallel.collectives import shard_map_compat
    mesh, spec = part
    return shard_map_compat(local, mesh, (spec,) * len(args),
                            (spec,) * n_out)(*args)


def _use_pallas(q, k) -> bool:
    if jax.default_backend() not in ("tpu",):
        return False
    T, D = q.shape[2], q.shape[3]
    Tk = k.shape[2]
    # hardware gate: 128-multiple sequence only. The T<=128 whole-axis block
    # is legal to *interpret* but real Mosaic rejects its sub-128 vector
    # loads ("index in dimension 2 is a multiple of 128", observed on v5e
    # with T=16, Dp=128) — and at those sizes the XLA path is just as fast.
    return T == Tk and D <= 512 and T % 128 == 0


def _chunk_reference_lse(q, k, v, causal, scale):
    """(normalized out, lse) via plain XLA — the flash_chunk fallback. Rows
    with every key masked produce a very negative lse, which zeroes their
    weight in any downstream lse-merge."""
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        tq, tk = logits.shape[-2], logits.shape[-1]
        rows = lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
        cols = lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        logits = jnp.where(rows >= cols, logits, _NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - m)
    l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bhqk,bhkd->bhqd", p / l, v)
    lse = (m + jnp.log(l))[..., 0]
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_chunk(q, k, v, causal, scale):
    """One self-attention chunk returning (normalized out, lse (B,H,T)) —
    the composable unit ring attention merges across devices. Pallas on TPU
    at eligible shapes, XLA fallback elsewhere; the custom vjp handles BOTH
    cotangents (out and lse), so lse-merges differentiate exactly."""
    if _use_pallas(q, k):
        def local(q, k, v):
            out, lse = _flash_attention_pallas(q, k, v, causal, scale)
            return out, lse.reshape(q.shape[:3])
        return _launch(local, (q, k, v), 2)
    return _chunk_reference_lse(q, k, v, causal, scale)


def _flash_chunk_fwd(q, k, v, causal, scale):
    out, lse = flash_chunk(q, k, v, causal, scale)
    return (out, lse), (q, k, v, out, lse)


def _flash_chunk_bwd(causal, scale, res, cots):
    q, k, v, out, lse = res
    g_o, g_lse = cots
    if _use_pallas(q, k):
        def local(q, k, v, out, lse, g_o, g_lse):
            B, H, T, _ = q.shape
            return _flash_backward_pallas(
                q, k, v, out, lse.reshape(B * H, T), g_o, causal, scale,
                lse_cot=g_lse)
        return _launch(local, (q, k, v, out, lse, g_o, g_lse), 3)
    _, vjp = jax.vjp(lambda q_, k_, v_: _chunk_reference_lse(
        q_, k_, v_, causal, scale), q, k, v)
    return vjp((g_o, g_lse))


flash_chunk.defvjp(_flash_chunk_fwd, _flash_chunk_bwd)


@register("flash_attention", namespace="contrib", aliases=("attention",))
def flash_attention(q, k, v, causal: bool = False, scale: Optional[float] = None):
    """Fused scaled-dot-product attention; q,k,v: (B, H, T, D).

    Pallas fwd+bwd on TPU at production shapes (any head dim ≤512 via lane
    padding; T % 128 == 0), XLA reference otherwise — numerically equivalent
    paths. Thin wrapper over ``flash_chunk`` (the lse output's zero cotangent
    folds away in bwd).
    """
    s = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    return flash_chunk(q, k, v, causal, s)[0]
