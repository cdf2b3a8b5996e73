"""Gated power retention of degree 2 (Manifest AI's power-retention layer:
causal attention whose weight is the SQUARE of the score in place of its
exponential, with a learned scalar decay a key/value head a token) as
chunked Pallas TPU kernels, forward and backward, with the same chunked
algorithm in ``lax`` for every other backend and shape.

Per batch row, query head ``h`` in group ``j = h // (H / Hkv)``, over
``s <= t``::

    a[t, s] = (q_t[h] . k_s[j])^2 / D * exp(sum_{r = s+1..t} log_g_r[j])
    y_t[h]  = sum_s a[t, s] v_s[j] / (sum_s a[t, s] + eps)

Weights are never negative. Because ``(q . k)^2 = phi(q) . phi(k)`` for a
finite feature map ``phi`` (the products ``x_a x_b``), the sum over ``s`` is a
state: a key/value head carries ``S_t = g_t S_{t-1} + phi(k_t) v_t^T / D``
and ``N_t = g_t N_{t-1} + k_t k_t^T / D`` (the sum of the weights is
``q^T N q``), and the ``H / Hkv`` query heads of a group read ONE state. So
the work is linear in ``T``: inside a chunk of ``CHUNK`` rows the quadratic
form (scores squared, a decay mask, times V), across chunks the state.
Neither a ``T x T`` array nor ``phi`` of a whole sequence (``T x D (D + 1) /
2``) is ever held in HBM, forward or backward.

The kernels' feature map: ``phi(x)[r, b] = x_b x_{(b - r) mod D}`` for ``r = 0
.. D / 2``, one lane roll and one product a block of ``D`` features, with the
key side weighted 1, 2, .., 2, 1 over ``r`` (each unordered pair of
dimensions once, the pairs at distance ``D / 2`` twice at half weight):
``(D / 2 + 1) D`` = 8320 features at ``D`` = 128 against the least 8256, all
lane-aligned. The state of one key/value head is then ``D / 2 + 1`` tiles of
``D x D`` float32 (4.26 MB) in VMEM across the sequential grid steps of a
sequence, updated by ``phi(K)^T (w V)`` and read by ``phi(Q) S`` on the MXU
(bf16 operands, float32 accumulation; the read uses a bf16 copy of the
state). One grid step is one (batch row, key/value head, chunk): the group's
query heads are stacked along the rows, so a state tile loaded into the MXU
serves ``(H / Hkv) * CHUNK`` rows.

The forward KEEPS the state at every chunk start (bf16, the copy it read;
``N`` in float32): ``T / CHUNK`` states of 2.13 MB a key/value head, 0.55 GB
a layer at 8192 tokens and 8 heads, which a model that recomputes a block at
a time holds for one layer at a time. Recomputing them instead would cost
the backward one more update pass (a fifth of its matmul work). The backward
walks the chunks from the last to the first as ``ssm_scan_bwd`` does,
carrying the gradient of the state. The gradient of the decay needs no pass
of its own: ``a`` is homogeneous of degree 2 in ``q_t`` and in ``k_s``, so
the gradient of the cumulative log-decay at ``t`` is ``(sum_h q_t . dq_t -
k_t . dk_t) / 2``, and ``dlog_g`` is its reverse cumulative sum (XLA).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..observability import metrics
from .registry import register

__all__ = ["power_retention", "retention_stats"]

_LANES = 128
# v5e, one layer of the cell (T 8192, 40 query heads on 8 of 128, bf16; my chip
# runs, PR 38), ms a launch: forward at CHUNK 256 11.46 / 8.09 / 7.62 with 1 /
# 5 / 13 feature blocks a matmul, at 512 9.61 / 7.98 / 7.73; backward at 256
# 17.12 / 19.77 / 22.07 with 1 / 5 / 13 (its blocks' float32 temporaries
# spill), at 512 19.10 with 1.
CHUNK = 256         # rows of T a grid step. The quadratic part costs 4 x CHUNK
                    # x D operations a row against the state's 2 x 8320 x D:
                    # 6% at 256; the kept chunk starts halve at 512
R_BLOCK = 13        # feature blocks (of 128) a matmul, forward: divides D / 2
                    # + 1 = 65
R_BLOCK_BWD = 1     # and backward
EPS = 1.0           # added to the sum of a row's weights: ONE null key of
                    # average weight (weights are squares of scores of mean
                    # 1), as softmax-plus-one is. A sequence's first rows have
                    # a few weights; where they all come near zero, y = num /
                    # (den + eps) magnifies the scores' rounding by up to 1 /
                    # (2 sqrt(eps)), and bf16 q and k carry 0.004-0.01 of it.
                    # At 1e-6 the first row did that on two seeds of four, at
                    # 1e-12 the second row on one of twenty, at 1e-2 the rows
                    # near eps (magnified fivefold) on the driver's first seed
                    # (PERF.md section 6, PR 38). At 1 nothing is magnified:
                    # the first rows fade in over a few keys, and a row whose
                    # weights sum to hundreds moves by under a part in 100
VMEM_LIMIT = 100 * 2 ** 20   # the state, its bf16 copy, the kept start's two
                             # buffers and the (rows, CHUNK) float32 tiles


# ---------------------------------------------------------------------------
# the chunked algorithm in lax
# ---------------------------------------------------------------------------


def _retention_lax(q, k, v, log_g, eps: float, chunk: int):
    """The kernels' algorithm with ``phi`` as the whole ``D x D`` outer
    product: a ``lax.scan`` over chunks that carries ``(S, N)`` in float32.
    Each chunk is a ``jax.checkpoint``, so JAX's transpose of the scan keeps
    the chunk starts alone and walks them backwards."""
    B, T, H, D = q.shape
    Hkv = k.shape[2]
    G, f32 = H // Hkv, jnp.float32
    c = min(chunk, T)
    pad = -T % c
    if pad:     # rows past T: no key, no value, no decay
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for a in (q, k, v))
        log_g = jnp.pad(log_g, ((0, 0), (0, pad), (0, 0)))
    n = (T + pad) // c
    qc = q.astype(f32).reshape(B, n, c, Hkv, G, D).transpose(1, 0, 3, 4, 2, 5)
    kc, vc = (a.astype(f32).reshape(B, n, c, Hkv, D).transpose(1, 0, 3, 2, 4)
              for a in (k, v))
    lc = jnp.cumsum(log_g.astype(f32).reshape(B, n, c, Hkv), axis=2) \
        .transpose(1, 0, 3, 2)                                 # (n, B, Hkv, c)
    causal = jnp.tril(jnp.ones((c, c), bool))

    def phi(x):
        return (x[..., :, None] * x[..., None, :]).reshape(x.shape[:-1]
                                                           + (D * D,))

    @jax.checkpoint
    def body(carry, xs):
        S, N = carry                       # (B, Hkv, D*D, D), (B, Hkv, D, D)
        qb, kb, vb, lb = xs                # (B, Hkv, G, c, D) ... (B, Hkv, c)
        diff = lb[..., :, None] - lb[..., None, :]
        m = jnp.where(causal, jnp.exp(jnp.where(causal, diff, 0.0)), 0.0) / D
        score = jnp.einsum("bjgtd,bjsd->bjgts", qb, kb)
        a = score * score * m[:, :, None]
        grow = jnp.exp(lb)[:, :, None, :, None]                # e^{L_t}
        num = jnp.einsum("bjgts,bjsd->bjgtd", a, vb) \
            + grow * jnp.einsum("bjgtf,bjfd->bjgtd", phi(qb), S)
        den = jnp.sum(a, axis=-1, keepdims=True) + grow * jnp.sum(
            jnp.einsum("bjgtd,bjde->bjgte", qb, N) * qb, -1, keepdims=True)
        last = lb[..., -1:]
        w = (jnp.exp(last - lb) / D)[..., None]                # (B, Hkv, c, 1)
        dec = jnp.exp(last)[..., None]
        S = dec * S + jnp.einsum("bjsf,bjsd->bjfd", phi(kb), vb * w)
        N = dec * N + jnp.einsum("bjsd,bjse->bjde", kb, kb * w)
        return (S, N), num / (den + eps)

    init = (jnp.zeros((B, Hkv, D * D, D), f32), jnp.zeros((B, Hkv, D, D), f32))
    _, y = lax.scan(body, init, (qc, kc, vc, lc))     # (n, B, Hkv, G, c, D)
    y = y.transpose(1, 0, 4, 2, 3, 5).reshape(B, T + pad, H * D)
    return y[:, :T].astype(q.dtype)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def _chunk_decay(l_row, c: int):
    """From the chunk's cumulative log-decays ``(1, c)``: the same as a
    column ``(c, 1)``, the last one ``(1, 1)`` and the causal decay map
    ``exp(L_t - L_s) / D`` for ``s <= t``, zero above the diagonal."""
    rows = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    cols = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    l_col = jnp.sum(jnp.where(rows == cols, l_row, 0.0), axis=1,
                    keepdims=True)
    last = jnp.sum(jnp.where(cols[:1] == c - 1, l_row, 0.0), axis=1,
                   keepdims=True)
    m = jnp.where(cols <= rows,
                  jnp.exp(jnp.minimum(l_col - l_row, 0.0)), 0.0) \
        * (1.0 / _LANES)
    return l_col, last, m


def _stack_heads(ref, groups: int, dtype=None):
    """``(c, groups * 128)`` -> ``(groups * c, 128)``: the heads of a group,
    which lie side by side along the lanes, stacked along the rows."""
    x = ref[0]
    parts = [x[:, g * _LANES:(g + 1) * _LANES] for g in range(groups)]
    out = parts[0] if groups == 1 else jnp.concatenate(parts, axis=0)
    return out if dtype is None else out.astype(dtype)


def _unstack_heads(ref, x, groups: int, c: int):
    for g in range(groups):
        ref[0, :, g * _LANES:(g + 1) * _LANES] = \
            x[g * c:(g + 1) * c].astype(ref.dtype)


def _tile_rows(x, groups: int):
    return x if groups == 1 else jnp.concatenate([x] * groups, axis=0)


def _key_weight(r, n_r: int):
    """1 for the squares (``r = 0``) and for the pairs at distance ``D / 2``
    (each appears twice), 2 for every other pair."""
    return jnp.where((r == 0) | (r == n_r - 1), 1.0, 2.0).astype(jnp.float32)


def _nt(a, b):
    """``a b^T`` on the MXU, float32 out."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _mm(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _fwd_kernel(q_ref, k_ref, v_ref, l_ref, y_ref, s0_ref, n0_ref,
                s_ref, n_ref, qf_ref, ktf_ref, acc_ref, *, chunk: int,
                groups: int, r_block: int, eps: float):
    """One (batch row, key/value head, chunk) program; the chunks of a head
    run in order and hand ``(S, N)`` on in ``s_ref`` / ``n_ref``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    c, G, D = chunk, groups, _LANES
    n_r = D // 2 + 1
    bf16, f32 = jnp.bfloat16, jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)
        n_ref[...] = jnp.zeros_like(n_ref)

    # the state this chunk starts in: kept for the backward, and read here
    s0_ref[0, 0, 0] = s_ref[...].astype(bf16)
    n0_ref[0, 0, 0] = n_ref[...]

    l_col, last, m = _chunk_decay(l_ref[0, 0, 0], c)
    grow = _tile_rows(jnp.exp(l_col), G)                     # e^{L_t}
    k, v = k_ref[0], v_ref[0]
    q = _stack_heads(q_ref, G)                               # (G c, D) bf16
    qf_ref[...] = q.astype(f32)

    score = _nt(q, k)                                        # (G c, c)
    # the weights as the MXU takes them, and their sum over the SAME
    # values: a row that sees one key then reads that key's value whatever
    # the weight's rounding (its weight cancels)
    a = (score * (score * _tile_rows(m, G))).astype(bf16)
    num = _mm(a, v)
    den = jnp.sum(a.astype(f32), axis=1, keepdims=True)

    # across chunks: phi(Q) S, r_block feature blocks a matmul
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def read(i, _):
        qq = qf_ref[...]
        ph = [(pltpu.roll(qq, i * r_block + j, 1) * qq).astype(bf16)
              for j in range(r_block)]
        ph = ph[0] if r_block == 1 else jnp.concatenate(ph, axis=1)
        at = pl.multiple_of(i * (r_block * D), r_block * D)
        acc_ref[...] += _mm(ph, s0_ref[0, 0, 0, pl.ds(at, r_block * D), :])
        return 0

    lax.fori_loop(0, n_r // r_block, read, 0)
    qq = qf_ref[...]
    num = num + grow * acc_ref[...]
    den = den + grow * jnp.sum(
        _mm(q, n_ref[...].astype(bf16)) * qq, axis=1, keepdims=True)
    _unstack_heads(y_ref, num / (den + eps), G, c)

    # the state after this chunk: S <- e^{L_c} S + phi(K)^T (w V)
    kf = k.astype(f32)
    w = jnp.exp(last - l_col) * (1.0 / D)                    # (c, 1)
    vw = (v.astype(f32) * w).astype(bf16)
    dec = jnp.exp(last)                                      # (1, 1)
    ktf_ref[...] = kf.T
    n_ref[...] = dec * n_ref[...] + _mm(ktf_ref[...].astype(bf16),
                                        (kf * w).astype(bf16))

    def update(i, _):
        kt = ktf_ref[...]
        ph = [(pltpu.roll(kt, i * r_block + j, 0) * kt
               * _key_weight(i * r_block + j, n_r)).astype(bf16)
              for j in range(r_block)]
        ph = ph[0] if r_block == 1 else jnp.concatenate(ph, axis=0)
        at = pl.multiple_of(i * (r_block * D), r_block * D)
        rows = pl.ds(at, r_block * D)
        s_ref[rows, :] = dec * s_ref[rows, :] + _mm(ph, vw)
        return 0

    lax.fori_loop(0, n_r // r_block, update, 0)


def _bwd_kernel(q_ref, k_ref, v_ref, l_ref, y_ref, dy_ref, s0_ref, n0_ref,
                dq_ref, dk_ref, dv_ref,
                ds_ref, dn_ref, dsb_ref, qf_ref, qtf_ref, kf_ref, g1_ref,
                dq_acc, dk_acc, dv_acc, *, chunk: int, groups: int,
                r_block: int, eps: float):
    """One (batch row, key/value head, chunk) program, chunks from the last
    to the first: ``ds_ref`` / ``dn_ref`` carry the gradient of the state
    at the END of the chunk in hand."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    c, G, D = chunk, groups, _LANES
    n_r = D // 2 + 1
    bf16, f32 = jnp.bfloat16, jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)
        dn_ref[...] = jnp.zeros_like(dn_ref)

    dsb_ref[...] = ds_ref[...].astype(bf16)
    l_col, last, m = _chunk_decay(l_ref[0, 0, 0], c)
    grow = _tile_rows(jnp.exp(l_col), G)
    k, v = k_ref[0], v_ref[0]
    q = _stack_heads(q_ref, G)
    qf = q.astype(f32)
    kf = k.astype(f32)
    qf_ref[...] = qf
    qtf_ref[...] = qf.T
    kf_ref[...] = kf
    n0 = n0_ref[0, 0, 0].astype(bf16)

    # the chunk's weights again (rounded as the forward rounded them), and
    # the sum of each row's
    score = _nt(q, k)
    p = score * _tile_rows(m, G)
    a = (score * p).astype(bf16).astype(f32)
    qn = _mm(q, n0)                                          # (G c, D)
    den = jnp.sum(a, axis=1, keepdims=True) + grow * jnp.sum(
        qn * qf, axis=1, keepdims=True)
    inv = 1.0 / (den + eps)
    dy = _stack_heads(dy_ref, G, f32)
    # d a[t, s] = dy_t . (v_s - y_t) / (den_t + eps): the difference is
    # taken in float32 BEFORE the division, from the float32 y the forward
    # kept: where a row's weights sum to next to nothing (a sequence's first
    # row, one key, q . k near 0) v_s - y_t is a small difference of equal
    # numbers that 1 / den magnifies, and bf16's rounding of either side
    # would be the whole of it
    dyy = jnp.sum(dy * _stack_heads(y_ref, G, f32), axis=1, keepdims=True)
    dnum = dy * inv
    dden = -dyy * inv
    dnum_b = dnum.astype(bf16)
    # inside the chunk
    dscore = 2.0 * p * ((_nt(dy.astype(bf16), v) - dyy) * inv)
    dscore_b = dscore.astype(bf16)
    dv_acc[...] = _mm(a.T.astype(bf16), dnum_b)
    dk_acc[...] = _mm(dscore_b.T, q)
    # the normaliser's state: d(e^L q^T N q) and d(k^T dN k) w
    gd = grow * dden
    dq_acc[...] = _mm(dscore_b, k) + 2.0 * gd * qn
    w = jnp.exp(last - l_col) * (1.0 / D)                    # (c, 1)
    dec = jnp.exp(last)
    dn = dn_ref[...]
    dk_acc[...] += 2.0 * w * _mm(k, dn.astype(bf16))
    dn_ref[...] = dec * dn + _mm(qtf_ref[...].astype(bf16),
                                 (qf * gd).astype(bf16))
    g1_ref[...] = (grow * dnum).astype(bf16)
    vw = (v.astype(f32) * w).astype(bf16)

    def block(i, dv_in):
        """``r_block`` feature blocks: one matmul each for the queries'
        ``d phi``, the state's gradient, the keys' ``d phi`` and dV."""
        rows = pl.ds(pl.multiple_of(i * (r_block * D), r_block * D),
                     r_block * D)
        s0 = s0_ref[0, 0, 0, rows, :]                  # (r_block D, D) bf16
        dsr = dsb_ref[rows, :]
        g1 = g1_ref[...]
        qq, qt, kk = qf_ref[...], qtf_ref[...], kf_ref[...]
        shifts = [i * r_block + j for j in range(r_block)]

        def cat(parts, axis):
            return parts[0] if r_block == 1 else jnp.concatenate(parts, axis)

        # the queries' side: d phi(q) = e^L dnum S0^T
        dphi = _nt(g1, s0)                             # (G c, r_block D)
        dq = dq_acc[...]
        for j, r in enumerate(shifts):
            d = dphi[:, j * D:(j + 1) * D]
            dq = dq + d * pltpu.roll(qq, r, 1) \
                + pltpu.roll(d * qq, (D - r) % D, 1)
        dq_acc[...] = dq
        # the state's gradient at the chunk's start
        ds_ref[rows, :] = dec * ds_ref[rows, :] + _mm(
            cat([(pltpu.roll(qt, r, 0) * qt).astype(bf16) for r in shifts],
                0), g1)
        # the keys' side, from the gradient that came in
        dphik = _nt(vw, dsr)                           # (c, r_block D)
        dk, phk = dk_acc[...], []
        for j, r in enumerate(shifts):
            wr = _key_weight(r, n_r)
            rk = pltpu.roll(kk, r, 1)
            d = dphik[:, j * D:(j + 1) * D] * wr
            dk = dk + d * rk + pltpu.roll(d * kk, (D - r) % D, 1)
            phk.append((rk * kk * wr).astype(bf16))
        dk_acc[...] = dk
        return dv_in + _mm(cat(phk, 1), dsr)

    dv_state = lax.fori_loop(0, n_r // r_block, block,
                             jnp.zeros((c, D), f32))
    _unstack_heads(dq_ref, dq_acc[...], G, c)
    dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
    dv_ref[0] = (dv_acc[...] + w * dv_state).astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# launches
# ---------------------------------------------------------------------------


def _chunk_cumsum(log_g, chunk: int):
    """``(B, T, Hkv)`` -> ``(B, Hkv, T / chunk, 1, chunk)`` float32: the
    cumulative log-decay from each chunk's first row on."""
    B, T, Hkv = log_g.shape
    lc = jnp.cumsum(log_g.astype(jnp.float32).reshape(B, T // chunk, chunk,
                                                      Hkv), axis=2)
    return lc.transpose(0, 3, 1, 2)[:, :, :, None, :]


def _specs(H, Hkv, chunk, order):
    """Block specs of the operands as the model holds them: ``q`` / ``y``
    ``(B, T, H * D)`` (a group's heads side by side), ``k`` / ``v`` ``(B, T,
    Hkv * D)``, the decays and the kept states by chunk. ``order`` maps the
    grid's chunk index to the chunk (the backward runs them reversed)."""
    from jax.experimental import pallas as pl
    G, D = H // Hkv, _LANES
    n_r = D // 2 + 1
    return dict(
        q=pl.BlockSpec((1, chunk, G * D), lambda b, j, c: (b, order(c), j)),
        kv=pl.BlockSpec((1, chunk, D), lambda b, j, c: (b, order(c), j)),
        l=pl.BlockSpec((1, 1, 1, 1, chunk),
                       lambda b, j, c: (b, j, order(c), 0, 0)),
        s=pl.BlockSpec((1, 1, 1, n_r * D, D),
                       lambda b, j, c: (b, j, order(c), 0, 0)),
        n=pl.BlockSpec((1, 1, 1, D, D),
                       lambda b, j, c: (b, j, order(c), 0, 0)))


def _forward_pallas(q, k, v, log_g, eps, interpret=False, chunk=None,
                    r_block=None):
    """``q``: ``(B, T, H * D)``; ``k``, ``v``: ``(B, T, Hkv * D)``. Returns
    ``(y, S0, N0)``: the output like ``q`` in FLOAT32 (the backward takes
    ``dy . (v - y)`` from it) and the state at the start of every chunk, ``(B, Hkv, T / chunk, 8320, 128)`` bf16 and ``(.., 128,
    128)`` float32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    chunk, r_block = chunk or CHUNK, r_block or R_BLOCK
    B, T, Hkv = log_g.shape
    D = _LANES
    H = q.shape[2] // D
    G, n_c, n_r = H // Hkv, T // chunk, D // 2 + 1
    sp = _specs(H, Hkv, chunk, lambda c: c)
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, groups=G,
                          r_block=r_block, eps=eps),
        grid=(B, Hkv, n_c),
        in_specs=[sp["q"], sp["kv"], sp["kv"], sp["l"]],
        out_specs=[sp["q"], sp["s"], sp["n"]],
        out_shape=[jax.ShapeDtypeStruct(q.shape, f32),
                   jax.ShapeDtypeStruct((B, Hkv, n_c, n_r * D, D),
                                        jnp.bfloat16),
                   jax.ShapeDtypeStruct((B, Hkv, n_c, D, D), f32)],
        scratch_shapes=[pltpu.VMEM((n_r * D, D), f32),
                        pltpu.VMEM((D, D), f32),
                        pltpu.VMEM((G * chunk, D), f32),
                        pltpu.VMEM((D, chunk), f32),
                        pltpu.VMEM((G * chunk, D), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        name="retention_fwd",
        interpret=interpret,
    )(q, k, v, _chunk_cumsum(log_g, chunk))


def _backward_pallas(q, k, v, log_g, y, s0, n0, dy, eps, interpret=False,
                     chunk=None, r_block=None):
    """``(dq, dk, dv, dlog_g)``; ``dq`` and ``dk`` in float32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    chunk, r_block = chunk or CHUNK, r_block or R_BLOCK_BWD
    B, T, Hkv = log_g.shape
    D = _LANES
    H = q.shape[2] // D
    G, n_c, n_r = H // Hkv, T // chunk, D // 2 + 1
    sp = _specs(H, Hkv, chunk, lambda c: n_c - 1 - c)
    f32, bf16 = jnp.float32, jnp.bfloat16
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, groups=G,
                          r_block=r_block, eps=eps),
        grid=(B, Hkv, n_c),
        in_specs=[sp["q"], sp["kv"], sp["kv"], sp["l"], sp["q"], sp["q"],
                  sp["s"], sp["n"]],
        out_specs=[sp["q"], sp["kv"], sp["kv"]],
        out_shape=[jax.ShapeDtypeStruct(q.shape, f32),
                   jax.ShapeDtypeStruct(k.shape, f32),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((n_r * D, D), f32),
                        pltpu.VMEM((D, D), f32),
                        pltpu.VMEM((n_r * D, D), bf16),
                        pltpu.VMEM((G * chunk, D), f32),
                        pltpu.VMEM((D, G * chunk), f32),
                        pltpu.VMEM((chunk, D), f32),
                        pltpu.VMEM((G * chunk, D), bf16),
                        pltpu.VMEM((G * chunk, D), f32),
                        pltpu.VMEM((chunk, D), f32),
                        pltpu.VMEM((chunk, D), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        name="retention_bwd",
        interpret=interpret,
    )(q, k, v, _chunk_cumsum(log_g, chunk), y, dy, s0, n0)
    # the cumulative log-decay's gradient at t, then log_g's: every later
    # row's decay holds log_g_t
    qdq = jnp.sum((q.astype(f32) * dq).reshape(B, T, Hkv, G * D), axis=-1)
    kdk = jnp.sum((k.astype(f32) * dk).reshape(B, T, Hkv, D), axis=-1)
    dcum = 0.5 * (qdq - kdk)
    dlog_g = jnp.flip(jnp.cumsum(jnp.flip(dcum, 1), axis=1), 1)
    return dq, dk, dv, dlog_g.astype(log_g.dtype)


# ---------------------------------------------------------------------------
# the op
# ---------------------------------------------------------------------------


def _on_one_device(q) -> bool:
    """Whether the launch would run on one device: GSPMD cannot partition a
    Mosaic call, and the kernels have no ``shard_map`` launch of their own
    yet. A tracer carries no sharding: the mesh is the one
    ``ops.attention.partition_scope`` names (``DataParallelTrainer`` opens
    it around its step)."""
    from .attention import _partition
    if isinstance(q, jax.core.Tracer):
        scope = getattr(_partition, "value", None)
        return scope is None or scope[0].devices.size == 1
    sharding = getattr(q, "sharding", None)
    return sharding is None or len(sharding.device_set) == 1


def _use_pallas(q, k) -> bool:
    """The kernels take heads of 128 (a lane tile: the feature map is a lane
    roll), whole chunks and one device; everything else, and every backend
    but the TPU, takes the chunked ``lax`` form (counted:
    ``profiler.get_kernel_path_counts()["retention"]``)."""
    return (jax.default_backend() == "tpu" and q.shape[3] == _LANES
            and q.shape[1] % CHUNK == 0 and q.shape[2] % k.shape[2] == 0
            and _on_one_device(q))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _retention_pallas(q, k, v, log_g, eps):
    return _forward_pallas(q, k, v, log_g, eps)[0].astype(q.dtype)


def _retention_pallas_fwd(q, k, v, log_g, eps):
    y, s0, n0 = _forward_pallas(q, k, v, log_g, eps)
    return y.astype(q.dtype), (q, k, v, log_g, y, s0, n0)


def _retention_pallas_bwd(eps, res, dy):
    q, k, v, log_g, y, s0, n0 = res
    dq, dk, dv, dlog_g = _backward_pallas(q, k, v, log_g, y, s0, n0, dy, eps)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv, dlog_g


_retention_pallas.defvjp(_retention_pallas_fwd, _retention_pallas_bwd)


def retention_stats(T: int, num_kv_heads: int, head_dim: int = _LANES,
                    batch: int = 1, pallas: bool = True) -> dict:
    """What one launch does at these sizes: the chunk length, the chunks a
    sequence, and the bytes of chunk-start state the forward keeps for the
    backward, a key/value head a chunk: the kernels' ``S`` in bf16 over
    their ``(D / 2 + 1) D`` features, the ``lax`` form's in float32 over
    ``D^2``, and ``N`` in float32."""
    chunk = min(CHUNK, T)
    chunks = -(-T // chunk)
    per = ((head_dim // 2 + 1) * 2 if pallas else head_dim * 4) \
        * head_dim * head_dim + head_dim * head_dim * 4
    return {"chunk": chunk, "chunks": chunks,
            "state_bytes_kept": batch * num_kv_heads * chunks * per}


# ``profiler.get_retention_stats()``: the op's call sites and the newest
# one's ``retention_stats`` (ONE layer's: what a recomputing model holds)
metrics.register_kernel("retention",
                        ("chunk", "chunks", "state_bytes_kept"))


@register("power_retention", namespace="contrib")
def power_retention(q, k, v, log_g, eps=None):
    """Gated power retention of degree 2, causal. ``q``: ``(B, T, H, D)``;
    ``k``, ``v``: ``(B, T, Hkv, D)``; query head ``h`` reads the state of
    key/value head ``h // (H / Hkv)``; ``log_g`` ``(B, T, Hkv)``: the log of
    each row's decay (not positive), float32. Returns ``(B, T, H * D)``:
    ``y_t = sum_s a[t, s] v_s / (sum_s a[t, s] + eps)`` with ``a[t, s] = (q_t
    . k_s)^2 / D * exp(log_g_{s+1} + .. + log_g_t)``. Pallas kernels with
    their own backward on the TPU where ``D == 128`` and ``T`` is whole
    chunks of ``CHUNK``; the same chunked algorithm in ``lax`` anywhere
    else. Memory is linear in ``T`` either way. ``eps`` ``None`` is
    ``EPS``."""
    eps = EPS if eps is None else float(eps)
    pallas = _use_pallas(q, k)
    metrics.record_kernel_path("retention", pallas)
    B, T, H, D = q.shape
    metrics.record_launch(
        "retention", **retention_stats(T, k.shape[2], D, B, pallas))
    with jax.named_scope("retention"):
        if pallas:
            return _retention_pallas(
                q.reshape(B, T, H * D), k.reshape(B, T, -1),
                v.reshape(B, T, -1), log_g.astype(jnp.float32), eps)
        return _retention_lax(q, k, v, log_g, eps, CHUNK)
