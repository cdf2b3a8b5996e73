"""Kimi Delta Attention's recurrence (a gated delta rule whose decay is one
factor a key CHANNEL a token) as chunked Pallas TPU kernels, forward and
backward, with the same chunked algorithm in ``lax`` for every other backend
and shape.

Per batch row and head, a state ``S`` of ``D x D`` (key channel by value
channel), zero at a sequence's start; with ``a_t`` ``(D,)`` the log-decays
(not positive) and ``beta_t`` a scalar::

    S' = Diag(exp(a_t)) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

The state is decayed channel by channel, then CORRECTED: what it holds under
``k_t`` is erased by the share ``beta_t`` and ``v_t`` written in its place.
Inside a chunk of ``C`` tokens that is a unit-lower-triangular system. With
``g`` the cumulative log-decay from the chunk's first row on, ``A[t, s] =
sum_c k_t[c] k_s[c] exp(g_t[c] - g_s[c])`` for ``s < t``, ``P[t, s]`` the same
with ``q_t`` for ``s <= t``, and ``S0`` the state the chunk starts in::

    (I + Diag(beta) A) U = Diag(beta) (V - (K exp(g)) S0)
    O  = (Q exp(g)) S0 + P U
    S1 = Diag(exp(g_C)) S0 + (K exp(g_C - g))^T U

``exp(-g_s)`` alone overflows (``g`` reaches -5 a token), so ``A`` and ``P``
are made a SUB-CHUNK of rows at a time (``SUB`` = 16) against that
sub-chunk's first row ``r``: ``k_t exp(g_t - r)`` never passes 1 and ``k_s
exp(r - g_s)`` never passes ``exp(75)`` for the keys the sub-chunk sees: the
model's gate is bounded so (``a > -5``: 15 steps of it). Nothing tighter is
relied on. The system is solved in float32, ONCE a chunk: the 16 x 16
diagonal blocks by fifteen steps of forward substitution on the vector units,
all eight packed side by side in two vregs (``_diagonal_blocks``: nothing for
the MXU, whose six products of block-diagonal matrices cost what dense ones
do), then the blocks below them on the MXU by the nilpotent product ``(I -
M)(I + M^2)(I + M^4)`` (``M^(C / 16) = 0``: six products at full precision).

One grid step of a kernel is one (batch row, head, chunk); a head's chunks
run in order with the state in VMEM. The forward KEEPS the state at every
chunk start (float32, 64 KB a head a chunk: 67 MB a layer at 4096 tokens and
32 heads) and the chunk's INVERSE ``(I + Diag(beta) A)^-1``, rounded to the
type its products take it in (the operands': 32 KB a head a chunk in
bfloat16, 33.5 MB a layer). The backward walks the chunks from the last to
the first, makes the chunk's matrices again (their gradients need them),
READS the inverse and carries the state's gradient: it solves nothing. Both
of its products with the inverse rounded it to that type anyway, as the
forward's one does, so the number fetched is the number it would have made.
The ``lax`` form keeps the chunk starts alone: JAX's transpose of its
checkpointed scan solves each chunk again.

The kernels take their operands RAW and make the rest in VMEM, from the tiles
they load anyway (``_prologue`` and ``_running``, which the ``lax`` form runs
too): q and k as the convolution left them, L2-normed over a head's 128
channels in float32 (q scaled by ``D ** -0.5``) and not rounded again before
the products cast them; the gate's logits ``z`` with a row of ``dt_bias`` and
one of ``exp(A_log)``, from which ``a = lower_bound * sigmoid(exp(A_log) * (z
+ dt_bias))`` and ``g``, its cumulative sum over the chunk's rows (shifted
adds along the sublanes, float32). ``kda_bwd`` makes them
again and sends its gradients back through them before it stores: ``dq``,
``dk``, ``dz`` and the two rows' gradients, summed over a head's chunks in an
output block that stays in VMEM. Between the convolution's output / the
gate's product and the kernels no ``(B, T, H * D)`` array exists, forward or
backward.

The token-by-token recurrence is not here: the benchmark's reference and the
tests hold it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..observability import metrics
from .registry import register
from .retention import _on_one_device

__all__ = ["kda", "kda_stats"]

_LANES = 128
# v5e, one layer of the cell (T 4096, 32 heads of 128, bf16; my chip runs,
# PRs 42 and 44; PR 43's builder's figures): ms a launch, forward / backward,
# beside the constant it was read at
CHUNK = 128         # rows of T a grid step: 2.55 / 1.90 with the diagonal
                    # blocks by substitution and the chunk's inverse kept
                    # (33.5 MB a layer, bf16) for a backward that solves
                    # nothing (PR 44); 3.62 / 1.91 with the inverse kept and
                    # the blocks on the MXU (PR 43); 3.61 / 4.51 when the
                    # backward solved again (PR 42); 3.39 / 4.17 without the
                    # prologue in the kernels (PR 41). Of the forward's 2.55
                    # the six products below the blocks are 1.38 and the
                    # substitution 0.19: with ``_solve`` stubbed the
                    # launches take 0.98 / 1.90. Every matrix of a chunk is
                    # one 128 x 128 tile; the kept chunk starts (67 MB a
                    # layer) halve against 64. No other length was run
SUB = 16            # rows of a sub-chunk: 15 steps of the gate's bound -5
_CLAMP = 80.0       # exp's argument for the keys a sub-chunk does NOT see
                    # (masked to zero afterwards): finite in float32
VMEM_LIMIT = 64 * 2 ** 20

_F32 = jnp.float32
_HIGHEST = lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# one chunk, on 2-D arrays: what the kernels and the lax form both run
# ---------------------------------------------------------------------------


def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _dot(a, b, dims, dt):
    """A matmul with float32 accumulation on operands of ``dt``: float32
    operands at full precision, anything narrower as the MXU takes it."""
    return lax.dot_general(
        a.astype(dt), b.astype(dt), (dims, ((), ())),
        precision=_HIGHEST if dt == _F32 else None,
        preferred_element_type=_F32)


def _mm(a, b, dt=_F32):
    return _dot(a, b, ((1,), (0,)), dt)


def _nt(a, b, dt=_F32):     # a b^T
    return _dot(a, b, ((1,), (1,)), dt)


def _tn(a, b, dt=_F32):     # a^T b
    return _dot(a, b, ((0,), (0,)), dt)


def _column(row):
    """``(1, n)`` -> ``(n, 1)`` without a transpose of a one-row tile."""
    n = row.shape[1]
    return jnp.sum(jnp.where(_iota((n, n), 0) == _iota((n, n), 1), row, 0.0),
                   axis=1, keepdims=True)


def _as_row(col):
    n = col.shape[0]
    return jnp.sum(jnp.where(_iota((n, n), 0) == _iota((n, n), 1), col, 0.0),
                   axis=0, keepdims=True)


def _prologue(q, k, z, bias, rate, bound: float, eps: float):
    """What lies between the convolution's output / the gate's product and
    the chunk's matrices, on rows of a head's ``D`` channels: ``(C, D)``
    tiles with ``bias`` / ``rate`` ``(1, D)`` in a kernel, ``(B, T, H, D)``
    with ``(H, D)`` in the ``lax`` form. Returns ``(q, k, a)``, float32: q
    and k L2-normed over the channels (q scaled by ``D ** -0.5``), and the
    log-decay ``a = bound * sigmoid(rate * (z + bias))``, which lies in
    ``[bound, 0]``: the bound the sub-chunks rely on is made where it is
    used."""
    def unit(x, scale):
        x = x.astype(_F32)
        return x * (scale * lax.rsqrt(
            jnp.sum(x * x, axis=-1, keepdims=True) + eps))

    a = bound * jax.nn.sigmoid(rate * (z.astype(_F32) + bias))
    return unit(q, q.shape[-1] ** -0.5), unit(k, 1.0), a


def _shifted_sum(x, back: bool):
    """``(C, D)`` float32 -> its sum over the rows from the first row on
    (``back``: from the last row back), by doubling shifted adds along the
    rows: seven rounds at 128."""
    C = x.shape[0]
    row = _iota(x.shape, 0)
    shift = 1
    while shift < C:
        if back:
            x = x + jnp.where(row < C - shift, jnp.roll(x, -shift, 0), 0.0)
        else:
            x = x + jnp.where(row >= shift, jnp.roll(x, shift, 0), 0.0)
        shift *= 2
    return x


@jax.custom_vjp
def _running(a):
    """The chunk's cumulative log-decay ``g`` from its rows' ``a``: ``(C,
    D)`` float32, the sum from the chunk's first row on; its transpose sums
    from the last row back. (Shifted adds, not a product with a triangle of
    ones: at full precision that is six MXU passes, and a launch was 0.12
    ms forward and 0.20 backward slower with it; with the addend in three
    bf16 pieces 0.03 / 0.07 slower: my chip run, PR 42.)"""
    return _shifted_sum(a, False)


_running.defvjp(lambda a: (_running(a), None),
                lambda _, dg: (_shifted_sum(dg, True),))


def _chunk_operands(q, k, z, bias, rate, bound: float, eps: float):
    """A kernel's tiles -> what its chunk is computed from: ``(q, k, g)``,
    float32, normed and with the cumulative log-decay."""
    q, k, a = _prologue(q, k, z, bias, rate, bound, eps)
    return q, k, _running(a)


def _chunk_parts(q, k, g, dt):
    """The chunk's decayed operands and its two score matrices. ``q``, ``k``:
    ``(C, D)``; ``g`` ``(C, D)`` float32, the cumulative log-decay from the
    chunk's first row on. Returns a dict: ``kq`` / ``qq`` (rows times
    ``exp(g - r)``, ``r`` their sub-chunk's first row), ``er`` that factor,
    ``kk[i]`` (keys times ``e[i] = exp(r_i - g)``, zero for the keys
    sub-chunk ``i`` does not see), ``A`` (strictly lower) and ``P``
    (lower)."""
    C, D = k.shape
    qf, kf = q.astype(_F32), k.astype(_F32)
    firsts = [g[i:i + 1] for i in range(0, C, SUB)]
    er = jnp.exp(g - jnp.concatenate(
        [jnp.broadcast_to(r, (SUB, D)) for r in firsts], axis=0))
    kq, qq = kf * er, qf * er
    row = _iota((C, 1), 0)
    e, kk, a_rows, p_rows = [], [], [], []
    for i, r in enumerate(firsts):
        ei = jnp.where(row < (i + 1) * SUB,
                       jnp.exp(jnp.minimum(r - g, _CLAMP)), 0.0)
        kki = kf * ei
        both = _nt(jnp.concatenate([kq[i * SUB:(i + 1) * SUB],
                                    qq[i * SUB:(i + 1) * SUB]], axis=0),
                   kki, dt)                                   # (2 SUB, C)
        e.append(ei)
        kk.append(kki)
        a_rows.append(both[:SUB])
        p_rows.append(both[SUB:])
    t, s = _iota((C, C), 0), _iota((C, C), 1)
    return dict(qf=qf, kf=kf, er=er, kq=kq, qq=qq, e=e, kk=kk,
                A=jnp.where(s < t, jnp.concatenate(a_rows, axis=0), 0.0),
                P=jnp.where(s <= t, jnp.concatenate(p_rows, axis=0), 0.0))


def _diagonal_blocks(n, near):
    """The inverse of ``I +`` the ``SUB x SUB`` diagonal blocks of a strictly
    lower triangular ``n`` ``(C, C)`` float32 (``near``: the mask of those
    blocks), zero off the blocks: every block by forward substitution, all of
    them a step together, in float32 on the vector units (the MXU is fed
    nothing). The blocks are PACKED side by side as ``(SUB, C)``: with what
    lies off them zeroed, the tile's row groups just add up. Step ``j`` takes
    column ``j`` of every block, spread over the block's ``SUB`` lanes (a
    lane gather), times row ``j`` of the inverse so far (a sublane
    broadcast), off the rows below it. (``kda_fwd`` at the cell's size, ms a
    launch, my chip run, PR 44: 2.55 so; 2.85 with the column spread by a
    masked copy and four doubling rotates, 2.86 by a masked lane sum a block;
    2.72 on the ``(C, C)`` tile as it stands, 3.46 that with a lane gather;
    3.62 by the MXU's six products ``(I - X)(I + X^2)(I + X^4)(I + X^8)``;
    2.36 with no diagonal phase at all.)"""
    C = n.shape[0]
    n = jnp.where(near, n, 0.0)
    x = functools.reduce(jnp.add, [n[i:i + SUB] for i in range(0, C, SUB)])
    row, lane = _iota((SUB, C), 0), _iota((SUB, C), 1)
    first = jnp.bitwise_and(lane, -SUB)     # of the lane's block
    inv = (row == lane - first).astype(_F32)
    for j in range(SUB - 1):
        col = jnp.take_along_axis(x, first + j, axis=1,
                                  mode="promise_in_bounds")
        inv = inv - col * inv[j:j + 1]
    return jnp.where(near, jnp.concatenate([inv] * (C // SUB), axis=0), 0.0)


def _solve(n):
    """``(I + n)^-1`` for a strictly lower triangular ``n`` ``(C, C)``,
    float32: the diagonal blocks of ``SUB`` first (``_diagonal_blocks``), then
    the blocks below them on the MXU (``M^(C / SUB) = 0``)."""
    C = n.shape[0]
    t, s = _iota((C, C), 0), _iota((C, C), 1)
    eye = (t == s).astype(_F32)
    shift = SUB.bit_length() - 1
    near = jnp.right_shift(t, shift) == jnp.right_shift(s, shift)
    inv = _diagonal_blocks(n, near)
    if C == SUB:
        return inv
    m = _mm(inv, jnp.where(near, 0.0, n))
    out, p, power = eye - m, m, 2
    while power < C // SUB:
        p = _mm(p, p)
        out = _mm(out, eye + p)
        power *= 2
    return _mm(out, inv)


def _chunk_forward(q, k, v, g, beta, s0, dt):
    """``(o (C, D) float32, the state after the chunk, the chunk's inverse
    (C, C) of dt)``; ``beta`` ``(C, 1)`` float32, ``s0`` ``(D, D)`` float32.
    Matmuls on the data take operands of ``dt``; the triangular system is
    float32, and its inverse is rounded to ``dt`` ONCE: the product below
    takes it so, and so does every product of ``_chunk_backward``."""
    C = k.shape[0]
    z = _chunk_parts(q, k, g, dt)
    eg, last = jnp.exp(g), g[C - 1:C]
    tinv = _solve(beta * z["A"]).astype(dt)
    u = _mm(tinv, beta * (v.astype(_F32) - _mm(z["kf"] * eg, s0, dt)), dt)
    o = _mm(z["qf"] * eg, s0, dt) + _mm(z["P"], u, dt)
    s1 = _column(jnp.exp(last)) * s0 \
        + _tn(z["kf"] * jnp.exp(last - g), u, dt)
    return o, s1, tinv


def _chunk_backward(q, k, v, g, beta, s0, tinv, do, ds1, dt):
    """The chunk again, and its transpose: ``(dq, dk, dv, dg, dbeta (C, 1),
    ds0)``, all float32. ``tinv`` ``(C, C)`` is the inverse ``_chunk_forward``
    made of this chunk: the matrices are made again (their gradients need
    them), the system is NOT solved again. ``dg`` is the gradient of the
    chunk's cumulative log-decay ``g`` (the caller sums it back onto ``a``).
    A decayed operand ``x exp(+-g)`` hands ``g`` the product of the operand
    and its gradient, so ``dg`` needs no pass of its own."""
    C = k.shape[0]
    z = _chunk_parts(q, k, g, dt)
    qf, kf, A, P = z["qf"], z["kf"], z["A"], z["P"]
    eg, last = jnp.exp(g), g[C - 1:C]
    elast = jnp.exp(last - g)
    kt, qt, kbar = kf * eg, qf * eg, kf * elast
    t, s = _iota((C, C), 0), _iota((C, C), 1)
    vres = v.astype(_F32) - _mm(kt, s0, dt)
    u = _mm(tinv, beta * vres, dt)
    do = do.astype(_F32)

    du = _tn(P, do, dt) + _mm(kbar, ds1, dt)
    dp = jnp.where(s <= t, _nt(do, u, dt), 0.0)
    dr = _tn(tinv, du, dt)
    dn = jnp.where(s < t, -_nt(dr, u, dt), 0.0)
    da_ = beta * dn
    dbeta = jnp.sum(dn * A, axis=1, keepdims=True) \
        + jnp.sum(dr * vres, axis=1, keepdims=True)
    bdr = beta * dr
    dqt = _nt(do, s0, dt)
    dkt = -_nt(bdr, s0, dt)
    dkbar = _nt(u, ds1, dt)
    decay = _column(jnp.exp(last))
    ds0 = _tn(qt, do, dt) + decay * ds1 - _tn(kt, bdr, dt)

    # through A and P: a sub-chunk of rows at a time, as they were made
    dkq, dqq, dk_col, g_col = [], [], 0.0, 0.0
    for i in range(C // SUB):
        rows = slice(i * SUB, (i + 1) * SUB)
        grad = jnp.concatenate([da_[rows], dp[rows]], axis=0)   # (2 SUB, C)
        side = _mm(grad, z["kk"][i], dt)                        # (2 SUB, D)
        dkq.append(side[:SUB])
        dqq.append(side[SUB:])
        dkk = _tn(grad, jnp.concatenate([z["kq"][rows], z["qq"][rows]],
                                        axis=0), dt)            # (C, D)
        dk_col = dk_col + dkk * z["e"][i]
        g_col = g_col + dkk * z["kk"][i]
    dkq, dqq = jnp.concatenate(dkq, axis=0), jnp.concatenate(dqq, axis=0)

    dq = dqq * z["er"] + dqt * eg
    dk = dkq * z["er"] + dk_col + dkt * eg + dkbar * elast
    dg = z["qq"] * dqq + z["kq"] * dkq - g_col + qt * dqt + kt * dkt \
        - kbar * dkbar
    # the chunk's last row also decays the state and every key's write
    dlast = _as_row(jnp.sum(decay * s0 * ds1, axis=1, keepdims=True)) \
        + jnp.sum(kbar * dkbar, axis=0, keepdims=True)
    dg = dg + jnp.where(_iota((C, 1), 0) == C - 1, dlast, 0.0)
    return dq, dk, bdr, dg, dbeta, ds0


# ---------------------------------------------------------------------------
# the chunked algorithm in lax
# ---------------------------------------------------------------------------


def _kda_lax(q, k, v, z, beta, bias, rate, bound, eps, chunk: int):
    """``_prologue`` on the whole arrays, then ``_running`` and
    ``_chunk_forward`` over ``(B, H)`` in a ``lax.scan`` over chunks that
    carries the states; each chunk is a ``jax.checkpoint``, so JAX's
    transpose keeps the chunk starts alone and walks them backwards."""
    B, T, H, D = q.shape
    dt = q.dtype
    q, k, a = _prologue(q, k, z, bias, rate, bound, eps)
    c = min(chunk, -(-T // SUB) * SUB)
    pad = -T % c
    if pad:     # rows past T: no key, no value, no decay, no write
        q, k, v, a = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                      for x in (q, k, v, a))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    n = (T + pad) // c

    def by_chunk(x):        # (B, T, H, ...) -> (n, B, H, c, ...)
        x = x.reshape((B, n, c) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 1), 2, 0)

    def chunk_of(q, k, v, a, beta, s0):
        return _chunk_forward(q, k, v, _running(a), beta, s0, dt)

    one = jax.vmap(jax.vmap(chunk_of))

    @jax.checkpoint
    def body(s0, xs):
        o, s1, _ = one(*xs, s0)
        return s1, o

    xs = (by_chunk(q), by_chunk(k), by_chunk(v), by_chunk(a),
          by_chunk(beta.astype(_F32))[..., None])
    _, o = lax.scan(body, jnp.zeros((B, H, D, D), _F32), xs)
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 3)       # (B, n, c, H, D)
    return o.reshape(B, T + pad, H * D)[:, :T].astype(dt)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, z_ref, b_ref, bias_ref, rate_ref,
                o_ref, s0_ref, tinv_ref, s_ref, *, bound, eps):
    """One (batch row, head, chunk); a head's chunks run in order and hand
    the state on in ``s_ref``. The chunk's start state and its inverse are
    kept for the backward."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    s0 = s_ref[...]
    s0_ref[0, 0, 0] = s0
    q, k, g = _chunk_operands(q_ref[0], k_ref[0], z_ref[0], bias_ref[...],
                              rate_ref[...], bound, eps)
    o, s1, tinv = _chunk_forward(q, k, v_ref[0], g, _column(b_ref[0, 0, 0]),
                                 s0, q_ref.dtype)
    o_ref[0] = o.astype(o_ref.dtype)
    tinv_ref[0, 0, 0] = tinv
    s_ref[...] = s1


def _bwd_kernel(q_ref, k_ref, v_ref, z_ref, b_ref, bias_ref, rate_ref,
                s0_ref, tinv_ref, do_ref, dq_ref, dk_ref, dv_ref, dz_ref,
                db_ref, dbias_ref, drate_ref, ds_ref, *, bound, eps):
    """One (batch row, head, chunk), chunks from the last to the first:
    ``ds_ref`` carries the gradient of the state at the END of the chunk in
    hand, and the two rows' gradients add up over a head's chunks in their
    output blocks. The prologue runs again, and JAX transposes it here."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)
        dbias_ref[...] = jnp.zeros_like(dbias_ref)
        drate_ref[...] = jnp.zeros_like(drate_ref)

    (q, k, g), back = jax.vjp(
        functools.partial(_chunk_operands, bound=bound, eps=eps),
        q_ref[0], k_ref[0], z_ref[0], bias_ref[...], rate_ref[...])
    dq, dk, dv, dg, dbeta, ds0 = _chunk_backward(
        q, k, v_ref[0], g, _column(b_ref[0, 0, 0]), s0_ref[0, 0, 0],
        tinv_ref[0, 0, 0], do_ref[0], ds_ref[...], q_ref.dtype)
    dq_ref[0], dk_ref[0], dz_ref[0], dbias, drate = back((dq, dk, dg))
    dv_ref[0] = dv.astype(dv_ref.dtype)
    db_ref[0, 0, 0] = _as_row(dbeta)
    dbias_ref[0] += dbias
    drate_ref[0] += drate
    ds_ref[...] = ds0


def _specs(chunk: int, order):
    """Block specs of the operands as the model holds them: ``q`` .. ``z``
    ``(B, T, H * D)`` (a head's channels side by side), ``beta``, the kept
    states and the kept inverses by chunk, the gate's two rows ``(1, H *
    D)`` and their gradients ``(B, 1, H * D)`` by head. ``order`` maps the
    grid's chunk index to the chunk (the backward runs them reversed)."""
    from jax.experimental import pallas as pl
    D = _LANES
    return dict(
        x=pl.BlockSpec((1, chunk, D), lambda b, h, c: (b, order(c), h)),
        beta=pl.BlockSpec((1, 1, 1, 1, chunk),
                          lambda b, h, c: (b, h, order(c), 0, 0)),
        s=pl.BlockSpec((1, 1, 1, D, D),
                       lambda b, h, c: (b, h, order(c), 0, 0)),
        inv=pl.BlockSpec((1, 1, 1, chunk, chunk),
                         lambda b, h, c: (b, h, order(c), 0, 0)),
        row=pl.BlockSpec((1, D), lambda b, h, c: (0, h)),
        drow=pl.BlockSpec((1, 1, D), lambda b, h, c: (b, 0, h)))


def _beta_rows(beta, chunk: int):
    """``(B, T, H)`` -> ``(B, H, T / chunk, 1, chunk)`` float32."""
    B, T, H = beta.shape
    return beta.astype(_F32).reshape(B, T // chunk, chunk, H) \
        .transpose(0, 3, 1, 2)[:, :, :, None, :]


def _params():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT)


def _forward_pallas(q, k, v, z, beta, bias, rate, bound, eps,
                    interpret=False, chunk=None):
    """``q``, ``k``, ``v``: ``(B, T, H * D)``, q and k un-normed; ``z`` like
    them, float32, the gate's logits; ``beta`` ``(B, T, H)``; ``bias`` and
    ``rate`` ``(H, D)`` float32 (``dt_bias`` and ``exp(A_log)`` a channel).
    Returns ``(o, S0, Tinv)``: the output like ``q``, the state at the start
    of every chunk, ``(B, H, T / chunk, D, D)`` float32, and every chunk's
    inverse ``(I + Diag(beta) A)^-1``, ``(B, H, T / chunk, chunk, chunk)`` of
    ``q``'s type: as the products take it."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    chunk = chunk or CHUNK
    B, T, H = beta.shape
    D, n_c = _LANES, T // chunk
    sp = _specs(chunk, lambda c: c)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, bound=bound, eps=eps),
        grid=(B, H, n_c),
        in_specs=[sp["x"]] * 4 + [sp["beta"]] + [sp["row"]] * 2,
        out_specs=[sp["x"], sp["s"], sp["inv"]],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((B, H, n_c, D, D), _F32),
                   jax.ShapeDtypeStruct((B, H, n_c, chunk, chunk), q.dtype)],
        scratch_shapes=[pltpu.VMEM((D, D), _F32)],
        compiler_params=_params(),
        name="kda_fwd",
        interpret=interpret,
    )(q, k, v, z, _beta_rows(beta, chunk), bias.reshape(1, H * D),
      rate.reshape(1, H * D))


def _backward_pallas(q, k, v, z, beta, bias, rate, s0, tinv, do, bound, eps,
                     interpret=False, chunk=None):
    """From the forward's operands, its kept states ``s0`` and its kept
    inverses ``tinv``: ``(dq, dk, dv, dz, dbeta, dbias, drate)``, the first
    three like ``q`` (of the un-normed q and k), ``dz`` float32 like ``z``,
    ``dbeta`` ``(B, T, H)`` float32, the last two ``(H, D)`` float32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    chunk = chunk or CHUNK
    B, T, H = beta.shape
    D, n_c = _LANES, T // chunk
    sp = _specs(chunk, lambda c: n_c - 1 - c)
    row = jax.ShapeDtypeStruct((B, 1, H * D), _F32)
    dq, dk, dv, dz, db, dbias, drate = pl.pallas_call(
        functools.partial(_bwd_kernel, bound=bound, eps=eps),
        grid=(B, H, n_c),
        in_specs=[sp["x"]] * 4 + [sp["beta"]] + [sp["row"]] * 2
        + [sp["s"], sp["inv"], sp["x"]],
        out_specs=[sp["x"]] * 4 + [sp["beta"]] + [sp["drow"]] * 2,
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype)] * 3
        + [jax.ShapeDtypeStruct(q.shape, _F32),
           jax.ShapeDtypeStruct((B, H, n_c, 1, chunk), _F32), row, row],
        scratch_shapes=[pltpu.VMEM((D, D), _F32)],
        compiler_params=_params(),
        name="kda_bwd",
        interpret=interpret,
    )(q, k, v, z, _beta_rows(beta, chunk), bias.reshape(1, H * D),
      rate.reshape(1, H * D), s0, tinv, do)
    return (dq, dk, dv, dz,
            db[:, :, :, 0, :].transpose(0, 2, 3, 1).reshape(B, T, H),
            jnp.sum(dbias, axis=0).reshape(H, D),
            jnp.sum(drate, axis=0).reshape(H, D))


# ---------------------------------------------------------------------------
# the op
# ---------------------------------------------------------------------------


def _use_pallas(q) -> bool:
    """The kernels take heads of 128 (a lane tile), whole chunks and one
    device; everything else, and every backend but the TPU, takes the
    chunked ``lax`` form (counted:
    ``profiler.get_kernel_path_counts()["kda"]``)."""
    return (jax.default_backend() == "tpu" and q.shape[3] == _LANES
            and q.shape[1] % CHUNK == 0 and _on_one_device(q))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _kda_pallas(q, k, v, z, beta, bias, rate, bound, eps):
    return _forward_pallas(q, k, v, z, beta, bias, rate, bound, eps)[0]


def _kda_pallas_fwd(q, k, v, z, beta, bias, rate, bound, eps):
    o, s0, tinv = _forward_pallas(q, k, v, z, beta, bias, rate, bound, eps)
    return o, (q, k, v, z, beta, bias, rate, s0, tinv)


def _kda_pallas_bwd(bound, eps, res, do):
    beta = res[4]
    dq, dk, dv, dz, dbeta, dbias, drate = _backward_pallas(
        *res, do, bound, eps)
    return dq, dk, dv, dz, dbeta.astype(beta.dtype), dbias, drate


_kda_pallas.defvjp(_kda_pallas_fwd, _kda_pallas_bwd)


def _gate_rows(a_log, dt_bias):
    """``dt_bias`` and ``exp(a_log)`` a channel, ``(H, D)`` float32 each."""
    H = a_log.shape[0]
    bias = dt_bias.astype(_F32).reshape(H, -1)
    return bias, jnp.broadcast_to(jnp.exp(a_log.astype(_F32))[:, None],
                                  bias.shape)


def kda_stats(T: int, num_heads: int, head_dim: int = _LANES,
              batch: int = 1, kernel_dtype=None) -> dict:
    """What one launch does at these sizes: the chunk length, the chunks a
    sequence, the bytes of chunk-start state the forward keeps for the
    backward (float32, a head a chunk, in the kernels and in the ``lax``
    form's checkpointed scan alike), and the bytes of chunk inverses the
    KERNELS keep beside them (``chunk x chunk`` of ``kernel_dtype``, the
    operands' type, a head a chunk: 33.5 MB a layer in bfloat16 at 4096
    tokens and 32 heads; the ``lax`` form, ``kernel_dtype=None``, keeps
    none: JAX's transpose of its scan solves each chunk again)."""
    chunk = min(CHUNK, -(-T // SUB) * SUB)
    chunks = -(-T // chunk)
    each = batch * num_heads * chunks
    return {"chunk": chunk, "chunks": chunks,
            "state_bytes_kept": each * head_dim * head_dim * 4,
            "inverse_bytes_kept": 0 if kernel_dtype is None else
            each * chunk * chunk * jnp.dtype(kernel_dtype).itemsize}


# ``profiler.get_kda_stats()``: the op's call sites and the newest one's
# ``kda_stats`` (ONE layer's bytes: a model holds that much a ``kda`` layer)
metrics.register_kernel("kda", ("chunk", "chunks", "state_bytes_kept",
                                "inverse_bytes_kept"))


@register("kda", namespace="contrib")
def kda(q, k, v, z, beta, a_log, dt_bias, lower_bound: float = -5.0,
        eps: float = 1e-6):
    """The gated delta rule with a decay a key channel, causal, from a zero
    state, on RAW operands. ``q``, ``k``, ``v``: ``(B, T, H, D)``, q and k
    un-normed: the op L2-norms them over ``D`` in float32 (``x / sqrt(|x|^2
    + eps)``, q scaled by ``D ** -0.5``). ``z`` ``(B, T, H, D)``: the
    decay's logits; ``a_log`` ``(H,)`` and ``dt_bias`` ``(H * D,)``: each
    channel's log-decay is ``a = lower_bound * sigmoid(exp(a_log[h]) * (z +
    dt_bias))``, float32, in ``[lower_bound, 0]`` (a sub-chunk of 16 rows
    is computed against its first row, which a bound of -5 keeps finite);
    ``beta`` ``(B, T, H)``. Returns ``(B, T, H * D)``: ``o_t = S_t^T q_t``
    with ``S_t = (I - beta_t k_t k_t^T) Diag(exp(a_t)) S_{t-1} + beta_t k_t
    v_t^T``. Pallas kernels with their own backward on the TPU where ``D ==
    128`` and ``T`` is whole chunks of ``CHUNK``: the norms, the gate and
    the chunks' cumulative decays are made inside them, and their gradients
    too; the same mathematics in ``lax`` anywhere else. Memory is linear in
    ``T`` either way."""
    pallas = _use_pallas(q)
    metrics.record_kernel_path("kda", pallas)
    B, T, H, D = q.shape
    metrics.record_launch(
        "kda", **kda_stats(T, H, D, B, q.dtype if pallas else None))
    bias, rate = _gate_rows(a_log, dt_bias)
    with jax.named_scope("kda"):
        if pallas:
            return _kda_pallas(
                *(x.reshape(B, T, H * D) for x in (q, k, v, z)), beta, bias,
                rate, lower_bound, eps)
        return _kda_lax(q, k, v, z, beta, bias, rate, lower_bound, eps,
                        CHUNK)
