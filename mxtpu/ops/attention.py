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
reference, which is equally fast at those sizes. The backward pass is the
standard flash backward — forward saves the per-row log-sum-exp; one kernel
(``flash_bwd_fused``, grid over k blocks) recomputes the probabilities of
each score tile once and accumulates dk/dv for its k block and dq for the
whole query row, the latter in a float32 VMEM scratch, without materializing
T×T.

Beyond plain multi-head attention the launches take: fewer key/value heads
than query heads (query head ``h`` reads key/value head ``h // group``
through the block index, no repeated copy of K or V; the backward makes dk
and dv per query head and XLA adds a group's up), a value width other than
the query/key width, and a causal sliding ``window`` (key ``j`` visible to
query ``i`` iff ``0 <= i - j < window``). With a window the key blocks ride a
third grid axis that only covers the blocks the window can touch, so blocks
wholly outside it are neither fetched nor computed; those launches carry the
names ``flash_fwd_window`` / ``flash_bwd_dq_window`` / ``flash_bwd_dkv_window``.
Which path every call site took is counted
(``profiler.get_kernel_path_counts()``).
"""

from __future__ import annotations

import contextlib
import functools
import math
import threading
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..observability import metrics
from .registry import register

__all__ = ["attention_reference", "diff_attention", "flash_attention",
           "flash_chunk", "partition_scope"]

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
    failed to compile under the 16 MiB of VMEM a program gets when it asks
    for nothing. Launch sites take their tiles from ``_flash_tiles``, which
    lowers the cap only where the VMEM bytes of the shapes ask for it.

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
    o0 = jnp.zeros((block_q, v_ref.shape[2]), jnp.float32)

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


def _zeros_like_kv(k_blk, v_blk):
    """Float32 zeros for dk and dv; one array where the widths agree."""
    z = jnp.zeros(k_blk.shape, jnp.float32)
    return z, (z if v_blk.shape == k_blk.shape
               else jnp.zeros(v_blk.shape, jnp.float32))


def _flash_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, dq_acc, *, block_q: int,
                      causal: bool, scale: float):
    """The whole backward for one (batch·head, key tile) program: loop the
    query tiles this key tile is visible to, make S, P, dP and dS ONCE per
    tile from the saved lse, and feed all three gradients from them: five
    matmuls a tile. dk and dv are carried through the loop; dq is summed
    over the key tiles in ``dq_acc``, a float32 ``(T, Dp)`` scratch that
    lives across the (sequential) key axis and is scaled and stored at the
    last key tile."""
    from jax.experimental import pallas as pl

    k_blk = k_ref[0].astype(jnp.float32)           # (block_k, d)
    v_blk = v_ref[0].astype(jnp.float32)
    block_k = k_blk.shape[0]
    kb = pl.program_id(1)
    k_start = kb * block_k
    num_qb = q_ref.shape[1] // block_q

    @pl.when(kb == 0)
    def _():
        dq_acc[...] = jnp.zeros(dq_acc.shape, jnp.float32)

    def body(qb, carry):
        dk, dv = carry
        qs = qb * block_q
        tile = pl.dslice(qs, block_q)
        q = q_ref[0, tile, :].astype(jnp.float32) * scale
        do = do_ref[0, tile, :].astype(jnp.float32)
        lse = lse_ref[0, 0, tile][:, None]
        delta = delta_ref[0, 0, tile][:, None]
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32)
        if causal:
            rows = qs + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = k_start + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        p = jnp.exp(s - lse)                       # masked entries underflow to 0
        dv_new = dv + jnp.dot(p.T, do, preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v_blk.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_new = dk + jnp.dot(ds.T, q, preferred_element_type=jnp.float32)
        dq_acc[tile, :] += jnp.dot(ds, k_blk,
                                   preferred_element_type=jnp.float32)
        return dk_new, dv_new

    # causal: only query tiles from the diagonal on see this key tile
    start_qb = (k_start // block_q) if causal else 0
    dk, dv = lax.fori_loop(start_qb, num_qb, body, _zeros_like_kv(k_blk, v_blk))
    # dk absorbed one factor of scale through q; no extra factor needed
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)

    @pl.when(kb == pl.num_programs(1) - 1)
    def _():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _lanes(d: int) -> int:
    """``d`` rounded up to whole 128-lane registers."""
    return -(-d // 128) * 128


def _pad_d(x):
    d = x.shape[-1]
    dp = _lanes(d)
    if dp == d:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (0, dp - d)))


def _kv_head(group: int):
    """Row of the flattened ``(batch x key/value heads)`` axis that row ``b``
    of the flattened ``(batch x query heads)`` axis reads: ``group``
    consecutive query heads share one key/value head."""
    return (lambda b: b) if group == 1 else (lambda b: b // group)


def _sum_group(dx, B: int, Hkv: int, group: int, dtype):
    """dk or dv made per QUERY head, ``(B * Hkv * group, Tk, D)``, added up
    over the query heads of each key/value head: ``(B, Hkv, Tk, D)``."""
    if group == 1:
        return dx.reshape((B, Hkv) + dx.shape[1:])
    dx = dx.reshape((B, Hkv, group) + dx.shape[1:])
    return jnp.sum(dx.astype(jnp.float32), axis=2).astype(dtype)


# -- causal sliding window: the key blocks ride a third grid axis -----------


def _window_blocks(block: int, window: int) -> int:
    """Key blocks a query block can see through a causal window: its own
    and those that hold any of the ``window - 1`` keys before its first row."""
    return -(-(window - 1) // block) + 1


def _key_block(i, j, n_w: int):
    """The key block that step ``j`` of query block ``i`` fetches: the one
    it visits, or block 0 where that falls before the first (not computed
    there: the kernels skip it)."""
    return jnp.maximum(i - (n_w - 1) + j, 0)


def _window_scores(q, k_blk, q_start, k_start, window: int):
    s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32)
    rows = q_start + lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = k_start + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where((rows >= cols) & (rows - cols < window), s, _NEG_INF)


def _flash_fwd_window_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref,
                             l_ref, acc_ref, *, block: int, window: int,
                             scale: float, n_w: int):
    """One (batch·head, q-block, step) program: step ``j`` visits key block
    ``qi - (n_w - 1) + j``, the diagonal block last; steps that fall before
    the first key block do nothing. A row that sees no key of an early
    block leaves garbage in ``l`` and ``acc`` there (``exp(-1e30 + 1e30)``);
    the diagonal block, where every row sees itself, scales it to zero."""
    from jax.experimental import pallas as pl

    qi, j = pl.program_id(1), pl.program_id(2)
    kb = qi - (n_w - 1) + j

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(kb >= 0)
    def _():
        q = q_ref[0].astype(jnp.float32) * scale
        s = _window_scores(q, k_ref[0].astype(jnp.float32), qi * block,
                           kb * block, window)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_ref[...] = corr * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = corr * acc_ref[...] + jnp.dot(
            p, v_ref[0].astype(jnp.float32),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == n_w - 1)
    def _():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0] = jnp.broadcast_to(
            (m_ref[...] + jnp.log(l))[:, 0][None, :], (8, block))


def _flash_bwd_dq_window_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                delta_ref, dq_ref, acc_ref, *, block: int,
                                window: int, scale: float, n_w: int):
    """dq for one q block, one visible key block a step."""
    from jax.experimental import pallas as pl

    qi, j = pl.program_id(1), pl.program_id(2)
    kb = qi - (n_w - 1) + j

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(kb >= 0)
    def _():
        q = q_ref[0].astype(jnp.float32) * scale
        k_blk = k_ref[0].astype(jnp.float32)
        s = _window_scores(q, k_blk, qi * block, kb * block, window)
        p = jnp.exp(s - lse_ref[0, 0][:, None])
        dp = jnp.dot(do_ref[0].astype(jnp.float32),
                     v_ref[0].astype(jnp.float32).T,
                     preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0][:, None])
        acc_ref[...] += jnp.dot(ds, k_blk, preferred_element_type=jnp.float32)

    @pl.when(j == n_w - 1)
    def _():
        dq_ref[0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_window_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                 delta_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                                 *, block: int, window: int, scale: float,
                                 n_w: int, num_qb: int):
    """dk/dv for one key block: step ``j`` visits query block ``kb + j``,
    the ``n_w`` query blocks whose window reaches this key block."""
    from jax.experimental import pallas as pl

    kb, j = pl.program_id(1), pl.program_id(2)
    qi = kb + j

    @pl.when(j == 0)
    def _():
        dk_acc[...] = jnp.zeros(dk_acc.shape, jnp.float32)
        dv_acc[...] = jnp.zeros(dv_acc.shape, jnp.float32)

    @pl.when(qi < num_qb)
    def _():
        q = q_ref[0].astype(jnp.float32) * scale
        do = do_ref[0].astype(jnp.float32)
        s = _window_scores(q, k_ref[0].astype(jnp.float32), qi * block,
                           kb * block, window)
        p = jnp.exp(s - lse_ref[0, 0][:, None])
        dv_acc[...] += jnp.dot(p.T, do, preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v_ref[0].astype(jnp.float32).T,
                     preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0][:, None])
        dk_acc[...] += jnp.dot(ds.T, q, preferred_element_type=jnp.float32)

    @pl.when(j == n_w - 1)
    def _():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _window_params(vmem_bytes: int):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=vmem_bytes)


def _flash_window_forward(qq, kk, vv, group: int, window: int, scale: float,
                          block: int, vmem_bytes: int, interpret: bool):
    """Forward launch over flattened, lane-padded ``(B·H, T, Dp)`` q and
    ``(B·Hkv, T, D[v]p)`` k, v; returns padded ``(out, lse (B·H, 8, T))``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, T, Dp = qq.shape
    Dvp = vv.shape[-1]
    n_w = _window_blocks(block, window)
    kv = _kv_head(group)

    def key_block(i, j):
        return _key_block(i, j, n_w)

    return pl.pallas_call(
        functools.partial(_flash_fwd_window_kernel, block=block,
                          window=window, scale=scale, n_w=n_w),
        grid=(BH, T // block, n_w),
        in_specs=[
            pl.BlockSpec((1, block, Dp), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block, Dp),
                         lambda b, i, j: (kv(b), key_block(i, j), 0)),
            pl.BlockSpec((1, block, Dvp),
                         lambda b, i, j: (kv(b), key_block(i, j), 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block, Dvp), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 8, block), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, Dvp), qq.dtype),
            jax.ShapeDtypeStruct((BH, 8, T), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block, 1), jnp.float32),
                        pltpu.VMEM((block, 1), jnp.float32),
                        pltpu.VMEM((block, Dvp), jnp.float32)],
        compiler_params=_window_params(vmem_bytes),
        name="flash_fwd_window",
        interpret=interpret,
    )(qq, kk, vv)


def _flash_window_backward(qq, kk, vv, gg, lse, delta, group: int,
                           window: int, scale: float, block: int,
                           vmem_bytes: int, interpret: bool):
    """Backward launches over the flattened, padded operands; dk and dv
    come out per QUERY head."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, T, Dp = qq.shape
    Dvp = vv.shape[-1]
    n_w = _window_blocks(block, window)
    num_qb = T // block
    kv = _kv_head(group)

    def key_block(i, j):
        return _key_block(i, j, n_w)

    def query_block(i, j):
        return jnp.minimum(i + j, num_qb - 1)

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_window_kernel, block=block,
                          window=window, scale=scale, n_w=n_w),
        grid=(BH, num_qb, n_w),
        in_specs=[
            pl.BlockSpec((1, block, Dp), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block, Dp),
                         lambda b, i, j: (kv(b), key_block(i, j), 0)),
            pl.BlockSpec((1, block, Dvp),
                         lambda b, i, j: (kv(b), key_block(i, j), 0)),
            pl.BlockSpec((1, block, Dvp), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 8, block), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((1, 8, block), lambda b, i, j: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, block, Dp), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, Dp), qq.dtype),
        scratch_shapes=[pltpu.VMEM((block, Dp), jnp.float32)],
        compiler_params=_window_params(vmem_bytes),
        name="flash_bwd_dq_window",
        interpret=interpret,
    )(qq, kk, vv, gg, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_window_kernel, block=block,
                          window=window, scale=scale, n_w=n_w,
                          num_qb=num_qb),
        grid=(BH, num_qb, n_w),
        in_specs=[
            pl.BlockSpec((1, block, Dp),
                         lambda b, i, j: (b, query_block(i, j), 0)),
            pl.BlockSpec((1, block, Dp), lambda b, i, j: (kv(b), i, 0)),
            pl.BlockSpec((1, block, Dvp), lambda b, i, j: (kv(b), i, 0)),
            pl.BlockSpec((1, block, Dvp),
                         lambda b, i, j: (b, query_block(i, j), 0)),
            pl.BlockSpec((1, 8, block),
                         lambda b, i, j: (b, 0, query_block(i, j))),
            pl.BlockSpec((1, 8, block),
                         lambda b, i, j: (b, 0, query_block(i, j))),
        ],
        out_specs=[
            pl.BlockSpec((1, block, Dp), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block, Dvp), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, Dp), kk.dtype),
            jax.ShapeDtypeStruct((BH, T, Dvp), vv.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block, Dp), jnp.float32),
                        pltpu.VMEM((block, Dvp), jnp.float32)],
        compiler_params=_window_params(vmem_bytes),
        name="flash_bwd_dkv_window",
        interpret=interpret,
    )(qq, kk, vv, gg, lse, delta)
    return dq, dk, dv


def _windowed(window, Tk: int) -> bool:
    """A window that reaches every earlier key is plain causal attention
    and takes the causal kernels."""
    return window is not None and window < Tk


# -- tiles: the largest that the VMEM bytes of the shapes allow --------------

_VMEM_BYTES = 128 << 20     # a v5e core's VMEM
_VMEM_DEFAULT = 16 << 20    # what a program gets there when it asks for nothing
# what a flash launch may plan for: three quarters of the chip's, since the
# reckonings below are estimates and Mosaic wants room of its own beside them
_VMEM_BUDGET = _VMEM_BYTES // 4 * 3


def _fwd_vmem_bytes(Tk: int, Dp: int, Dvp: int, block_q: int, block_k: int,
                    itemsize: int) -> int:
    """VMEM the forward program holds, from its shapes: the whole K and V
    rows and the q / o / lse blocks (two buffers each), the float32 q, k
    and v tiles, room for a score tile's float32 temporaries (s, p, the
    mask's iotas) and the carried accumulator with its rescaled copy.
    Never under the default."""
    rows = 2 * Tk * (Dp + Dvp) * itemsize
    blocks = 2 * block_q * (Dp + Dvp) * itemsize + 2 * 8 * block_q * 4
    wide = (block_q * Dp + block_k * (Dp + Dvp)) * 4
    temps = 6 * block_q * block_k * 4
    acc = 2 * block_q * Dvp * 4
    return max(_VMEM_DEFAULT, rows + blocks + wide + temps + acc + (2 << 20))


def _bwd_vmem_bytes(T: int, Dp: int, Dvp: int, block_q: int, block_k: int,
                    itemsize: int) -> int:
    """VMEM the backward program holds, from its shapes: the query row's q,
    dO and dq blocks and the key tile's k, v, dk, dv blocks (two buffers
    each), the lse / delta rows, the float32 dq accumulator, and room for a
    score tile's float32 temporaries (s, p, dp, ds, their transposes, the
    mask's iotas) and the carried dk / dv. Never under the default."""
    row = 2 * (2 * T * Dp + T * Dvp) * itemsize + 2 * 2 * 8 * T * 4
    tile = 2 * 2 * block_k * (Dp + Dvp) * itemsize
    acc = T * Dp * 4
    temps = 10 * block_q * block_k * 4 \
        + 4 * (block_q + block_k) * (Dp + Dvp) * 4
    return max(_VMEM_DEFAULT, row + tile + acc + temps + (2 << 20))


def _window_vmem_bytes(Dp: int, Dvp: int, block: int, itemsize: int):
    """``(forward, backward)`` VMEM of the window launches, which hold
    blocks alone: q, k, v and o (forward) or q, k, v, dO, dk and dv (the
    larger backward launch) in two buffers each, the lse / delta blocks, the
    float32 accumulators, the widened operands and a score tile's
    temporaries as in the causal launches."""
    side = block * (Dp + Dvp)
    rows = 2 * 8 * block * 4
    fwd = 2 * 2 * side * itemsize + rows + block * Dvp * 4 \
        + (side + block * Dp) * 4 + 6 * block * block * 4
    bwd = 2 * 3 * side * itemsize + 2 * rows + side * 4 \
        + 2 * side * 4 + 10 * block * block * 4
    return tuple(max(_VMEM_DEFAULT, b + (2 << 20)) for b in (fwd, bwd))


class FlashTiles(NamedTuple):
    """What a call site launches with; the row ``profiler.get_launch_stats``
    shows under ``flash`` / ``flash_window`` for the newest one."""
    block_q: int
    block_k: int
    dp: int
    dvp: int
    fwd_vmem_bytes: int     # the launches' ``vmem_limit_bytes``
    bwd_vmem_bytes: int


def _flash_tiles(T: int, Tk: int, Dp: int, Dvp: int, itemsize: int,
                 block_q: int = 512, block_k: int = 512,
                 window=None) -> FlashTiles:
    """THE rule every flash launch takes its tiles from: the largest legal
    ``(block_q, block_k)``, both under one cap that starts at the larger of
    the two asked for (512: ``_pick_block``) and comes down by 128 at a
    time, whose forward and backward both fit ``_VMEM_BUDGET`` by the
    reckonings above. A window launch runs square tiles, the smaller of the
    pair. Never under 128 rows (or the whole of a shorter axis): where even
    those pass the budget they are taken as long as the chip's VMEM holds
    them, and a launch that it cannot hold raises (``_takes_kernels`` asks
    first, so ``flash_chunk`` takes the XLA path there). On the v5e 512 x
    512 was the fastest of seven pairs up to 1024 x 1024 at q/k 192, v 128
    (forward + backward 7.2 ms against 9.1 at 256 x 256, B1 H32 T4096) and
    within 1.2% of the fastest at 128 lanes (PERF.md §5, PR 47)."""
    windowed = _windowed(window, Tk)
    for cap in range(max(block_q, block_k, 128), 127, -128):
        bq = _pick_block(T, min(cap, block_q))
        bk = _pick_block(Tk, min(cap, block_k))
        if windowed:
            bq = bk = min(bq, bk)
            fwd, bwd = _window_vmem_bytes(Dp, Dvp, bq, itemsize)
        else:
            fwd = _fwd_vmem_bytes(Tk, Dp, Dvp, bq, bk, itemsize)
            bwd = _bwd_vmem_bytes(T, Dp, Dvp, bq, bk, itemsize)
        need = max(fwd, bwd)
        if need <= _VMEM_BUDGET:
            break
    if need > _VMEM_BYTES:
        raise ValueError(
            f"no flash tiles for T={T}, Tk={Tk} at padded widths {Dp} / "
            f"{Dvp} and {itemsize}-byte operands: the rows a program keeps "
            f"in VMEM take {need} bytes at the smallest tiles, the chip has "
            f"{_VMEM_BYTES}. Take the XLA path")
    return FlashTiles(bq, bk, Dp, Dvp, fwd, bwd)


def _flash_attention_pallas(q, k, v, causal: bool, scale: float,
                            block_q: int = 512, block_k: int = 512,
                            interpret: bool = False, window=None):
    """Forward kernel launch; returns (out, lse). q: (B, H, T, D); k:
    (B, Hkv, Tk, D) with ``H % Hkv == 0``; v: (B, Hkv, Tk, Dv). The tiles
    are ``_flash_tiles``'s; ``block_q`` / ``block_k`` cap them."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, T, D = q.shape
    Hkv, Tk, Dv = k.shape[1], k.shape[2], v.shape[3]
    group = H // Hkv
    qq = _pad_d(q.reshape(B * H, T, D))
    kk = _pad_d(k.reshape(B * Hkv, Tk, D))
    vv = _pad_d(v.reshape(B * Hkv, Tk, Dv))
    tiles = _flash_tiles(T, Tk, qq.shape[-1], vv.shape[-1], q.dtype.itemsize,
                         block_q, block_k, window)
    block_q, block_k, Dp, Dvp = tiles[:4]
    windowed = _windowed(window, Tk)
    # the call site's row: its backward takes the same tiles from the rule
    metrics.record_launch("flash_window" if windowed else "flash",
                          **tiles._asdict())
    if windowed:
        out, lse = _flash_window_forward(
            qq, kk, vv, group, window, scale, block_q,
            tiles.fwd_vmem_bytes, interpret)
        return out[..., :Dv].reshape(B, H, T, Dv), lse[:, 0, :]
    grid = (B * H, T // block_q)
    kv = _kv_head(group)

    kernel = functools.partial(_flash_fwd_kernel, block_k=block_k, causal=causal,
                               scale=scale)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, Dp), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, Tk, Dp), lambda b, i: (kv(b), 0, 0)),
            pl.BlockSpec((1, Tk, Dvp), lambda b, i: (kv(b), 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, Dvp), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 8, block_q), lambda b, i: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, T, Dvp), q.dtype),
            jax.ShapeDtypeStruct((B * H, 8, T), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=tiles.fwd_vmem_bytes),
        name="flash_fwd",
        interpret=interpret,
    )(qq, kk, vv)
    return out[..., :Dv].reshape(B, H, T, Dv), lse[:, 0, :]


def _flash_backward_pallas(q, k, v, o, lse, g, causal: bool, scale: float,
                           block_q: int = 512, block_k: int = 512,
                           interpret: bool = False, lse_cot=None,
                           window=None):
    """Flash backward: ONE launch, ``flash_bwd_fused``, over a (batch·head,
    key tile) grid whose key axis is sequential; each score tile is visited
    once and feeds dq, dk and dv (``_flash_bwd_kernel``). The query row's q
    and dO stay in VMEM beside a float32 dq accumulator, so the launch asks
    for the VMEM its shapes need (``_bwd_vmem_bytes``, through
    ``_flash_tiles``). With a causal window the ``flash_bwd_dq_window`` /
    ``flash_bwd_dkv_window`` pair runs instead. Shapes as in the forward;
    with fewer key/value heads than query heads the kernels make dk and dv
    per query head and a group's are added up here.

    ``lse_cot`` (B,H,T): optional cotangent of the log-sum-exp output (ring
    merges differentiate through lse); it folds into the delta term exactly —
    dS = P∘(dP - (Δ - dlse)) since ∂lse/∂S = P."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, T, D = q.shape
    Hkv, Tk, Dv = k.shape[1], k.shape[2], v.shape[3]
    group = H // Hkv
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    if lse_cot is not None:
        delta = delta - lse_cot.astype(jnp.float32)
    # lse/delta ride (BH, 8, T), float32: sublane-broadcast to satisfy
    # Mosaic tiling
    delta = jnp.broadcast_to(delta.reshape(B * H, 1, T), (B * H, 8, T))
    lse = jnp.broadcast_to(
        lse.astype(jnp.float32).reshape(B * H, 1, T), (B * H, 8, T))
    qq = _pad_d(q.reshape(B * H, T, D))
    kk = _pad_d(k.reshape(B * Hkv, Tk, D))
    vv = _pad_d(v.reshape(B * Hkv, Tk, Dv))
    gg = _pad_d(g.reshape(B * H, T, Dv))
    tiles = _flash_tiles(T, Tk, qq.shape[-1], vv.shape[-1], q.dtype.itemsize,
                         block_q, block_k, window)
    block_q, block_k, Dp, Dvp = tiles[:4]
    kv = _kv_head(group)

    def unflatten(dq, dk, dv):
        return (dq[..., :D].reshape(B, H, T, D),
                _sum_group(dk[..., :D], B, Hkv, group, k.dtype),
                _sum_group(dv[..., :Dv], B, Hkv, group, v.dtype))

    if _windowed(window, Tk):
        return unflatten(*_flash_window_backward(
            qq, kk, vv, gg, lse, delta, group, window, scale, block_q,
            tiles.bwd_vmem_bytes, interpret))

    return unflatten(*pl.pallas_call(
        functools.partial(_flash_bwd_kernel, block_q=block_q, causal=causal,
                          scale=scale),
        grid=(B * H, Tk // block_k),
        in_specs=[
            pl.BlockSpec((1, T, Dp), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, block_k, Dp), lambda b, j: (kv(b), j, 0)),
            pl.BlockSpec((1, block_k, Dvp), lambda b, j: (kv(b), j, 0)),
            pl.BlockSpec((1, T, Dvp), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, 8, T), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, 8, T), lambda b, j: (b, 0, 0)),
        ],
        out_specs=[
            # the whole row: written once, after the last key tile
            pl.BlockSpec((1, T, Dp), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, block_k, Dp), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, Dvp), lambda b, j: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, T, Dp), q.dtype),
            jax.ShapeDtypeStruct((B * H, Tk, Dp), k.dtype),
            jax.ShapeDtypeStruct((B * H, Tk, Dvp), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((T, Dp), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=tiles.bwd_vmem_bytes),
        name="flash_bwd_fused",
        interpret=interpret,
    )(qq, kk, vv, gg, lse, delta))


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
    return (T == Tk and D <= 512 and T % 128 == 0
            and q.shape[1] % k.shape[1] == 0)


def _takes_kernels(q, k, v, window=None) -> bool:
    """The shapes the kernels take, whose rows the chip's VMEM can hold."""
    if not (_use_pallas(q, k) and v.shape[3] <= 512):
        return False
    try:
        _flash_tiles(q.shape[2], k.shape[2], _lanes(q.shape[3]),
                     _lanes(v.shape[3]), q.dtype.itemsize, window=window)
    except ValueError:
        return False
    return True


metrics.register_kernel("flash", FlashTiles._fields)
metrics.register_kernel("flash_window", FlashTiles._fields)


def _count_path(k, window, pallas: bool) -> bool:
    """A forward call site chose its path (its backward follows it); counted
    (``profiler.get_kernel_path_counts()``), so a step that fell back to XLA
    says so instead of only running slower."""
    metrics.record_kernel_path(
        "flash_window" if _windowed(window, k.shape[2]) else "flash", pallas)
    return pallas


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


def _attention_xla(q, k, v, causal, scale, window):
    """(normalized out, lse) via plain XLA for what ``_chunk_reference_lse``
    does not take: a causal window, fewer key/value heads than query heads
    (the query heads of a group ride an axis of their own; K and V are not
    repeated)."""
    if window is None and q.shape[1] == k.shape[1]:
        return _chunk_reference_lse(q, k, v, causal, scale)
    B, H, T, D = q.shape
    Hkv = k.shape[1]
    qg = q.reshape(B, Hkv, H // Hkv, T, D)
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k) * scale
    if causal or window is not None:
        rows = lax.broadcasted_iota(jnp.int32, logits.shape[-2:], 0)
        cols = lax.broadcasted_iota(jnp.int32, logits.shape[-2:], 1)
        seen = rows >= cols
        if window is not None:
            seen = seen & (rows - cols < window)
        logits = jnp.where(seen, logits, _NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - m)
    l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", p / l, v)
    return (out.reshape(B, H, T, v.shape[3]),
            (m + jnp.log(l))[..., 0].reshape(B, H, T))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_chunk(q, k, v, causal, scale, window=None):
    """One self-attention chunk returning (normalized out, lse (B,H,T)) —
    the composable unit ring attention merges across devices. Pallas on TPU
    at eligible shapes, XLA fallback elsewhere; the custom vjp handles BOTH
    cotangents (out and lse), so lse-merges differentiate exactly.
    ``window`` (causal only): key ``j`` is visible to query ``i`` iff
    ``0 <= i - j < window``."""
    if _count_path(k, window, _takes_kernels(q, k, v, window)):
        def local(q, k, v):
            out, lse = _flash_attention_pallas(q, k, v, causal, scale,
                                               window=window)
            return out, lse.reshape(q.shape[:3])
        return _launch(local, (q, k, v), 2)
    return _attention_xla(q, k, v, causal, scale, window)


def _flash_chunk_fwd(q, k, v, causal, scale, window):
    out, lse = flash_chunk(q, k, v, causal, scale, window)
    return (out, lse), (q, k, v, out, lse)


def _flash_chunk_bwd(causal, scale, window, res, cots):
    q, k, v, out, lse = res
    g_o, g_lse = cots
    if _takes_kernels(q, k, v, window):
        def local(q, k, v, out, lse, g_o, g_lse):
            B, H, T, _ = q.shape
            return _flash_backward_pallas(
                q, k, v, out, lse.reshape(B * H, T), g_o, causal, scale,
                lse_cot=g_lse, window=window)
        return _launch(local, (q, k, v, out, lse, g_o, g_lse), 3)
    _, vjp = jax.vjp(lambda q_, k_, v_: _attention_xla(
        q_, k_, v_, causal, scale, window), q, k, v)
    return vjp((g_o, g_lse))


flash_chunk.defvjp(_flash_chunk_fwd, _flash_chunk_bwd)


@register("flash_attention", namespace="contrib", aliases=("attention",))
def flash_attention(q, k, v, causal: bool = False, scale: Optional[float] = None,
                    window: Optional[int] = None):
    """Fused scaled-dot-product attention; q: (B, H, T, D), k: (B, Hkv, T, D)
    with ``H % Hkv == 0`` (query head ``h`` reads key/value head
    ``h // (H // Hkv)``), v: (B, Hkv, T, Dv).

    Pallas fwd+bwd on TPU at production shapes (any head dim ≤512 via lane
    padding; T % 128 == 0), XLA reference otherwise — numerically equivalent
    paths. ``window`` (needs ``causal``) keeps the ``window`` newest keys of
    every query, itself included. Thin wrapper over ``flash_chunk`` (the lse
    output's zero cotangent folds away in bwd).
    """
    if window is not None and not causal:
        raise ValueError("flash_attention: a window needs causal=True")
    s = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    return flash_chunk(q, k, v, causal, s, window)[0]


@register("diff_attention", namespace="contrib")
def diff_attention(q, k, v, lq1, lk1, lq2, lk2, gain, lambda_init: float = 0.8,
                   window: Optional[int] = None, eps: float = 1e-5):
    """Causal differential attention (Ye et al. 2024) over heads that pair
    up. ``q``: ``(B, T, H, D)``; ``k``, ``v``: ``(B, T, Hkv, D)``; query pair
    ``p`` = heads ``(2p, 2p + 1)`` reads key/value pair ``p // (H / Hkv)``::

        o_p = (1 - lambda_init) * RMSNorm(S1 V - lam * S2 V) * gain
        S1  = softmax(mask(q_2p k_2p'^T / sqrt(D))), S2 on the odd heads
        V   = [v_2p', v_2p'+1]                         (2 D wide)
        lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init

    Returns ``(B, T, H * D)``. Two flash launches (even and odd heads), each
    with ``H / 2`` query heads on ``Hkv / 2`` key heads and a value twice as
    wide as the keys; ``window`` as in :func:`flash_attention`."""
    B, T, H, D = q.shape
    Hkv = k.shape[2]
    scale = 1.0 / math.sqrt(D)
    qp = q.reshape(B, T, H // 2, 2, D)
    kp = k.reshape(B, T, Hkv // 2, 2, D)
    vv = v.reshape(B, T, Hkv // 2, 2 * D).transpose(0, 2, 1, 3)

    def side(i):
        return flash_chunk(qp[:, :, :, i].transpose(0, 2, 1, 3),
                           kp[:, :, :, i].transpose(0, 2, 1, 3), vv, True,
                           scale, window)[0].astype(jnp.float32)

    f32 = jnp.float32
    lam = (jnp.exp(jnp.sum(lq1.astype(f32) * lk1.astype(f32)))
           - jnp.exp(jnp.sum(lq2.astype(f32) * lk2.astype(f32))) + lambda_init)
    x = side(0) - lam * side(1)                       # (B, H/2, T, 2D)
    x = x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    x = x * gain.astype(f32) * (1.0 - lambda_init)
    return x.transpose(0, 2, 1, 3).reshape(B, T, H * D).astype(q.dtype)
