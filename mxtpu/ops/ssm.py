"""Selective state-space scan (Mamba-1's recurrence) as Pallas TPU kernels
(forward + backward) with a plain ``lax.scan`` for every other backend and
shape.

Per batch row, channel ``c`` and state ``n``::

    s_t[c, n] = exp(dt_t[c] * A[c, n]) * s_{t-1}[c, n] + dt_t[c] * B_t[n] * u_t[c]
    y_t[c]    = sum_n C_t[n] * s_t[c, n] + D[c] * u_t[c]            s_0 = 0

The decay differs per channel AND state, so no matmul form exists (Mamba-2's
does not apply): the work is elementwise over ``(T, C, N)`` and sequential in
``T``. ``B x T x C x N`` is never held in HBM. The kernels keep one
``(N, block_d)`` tile of state in registers (states on sublanes, channels on
lanes), walk ``T`` in chunks of ``chunk`` rows, and the forward writes the
state at the start of every chunk (``T / chunk`` tiles). The backward walks
the chunks from the last to the first, recomputes the chunk's states into
VMEM from that saved start, and runs the reverse recurrence over them.

What leaves the kernels lane-dense: ``B_t`` and ``C_t`` come in with their
``N`` values on sublanes and copied along the 128 lanes (``(T, N, 128)``
bf16/f32, made by XLA: 32 KB a row whatever ``C`` is), so a step needs no
cross-lane broadcast; ``dB_t`` and ``dC_t`` are summed over the lanes of a
channel block once per chunk by a dot with ones and summed over channel
blocks by XLA.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..observability import metrics
from .registry import register

__all__ = ["causal_conv1d", "selective_scan", "selective_scan_reference"]

_LANES = 128
CHUNK = 64          # rows of T per grid step; the backward holds chunk + 1
                    # states of (N, block_d) float32 in VMEM
BLOCK_D = 1024      # channels per grid step (v5e, T 8192 x 5120 channels: chosen
                    # where a forward launch took 2.3 ms against 3.4 at 512 and 5.8
                    # at 256, a backward 6.7 / 7.8 / 11.8; the ledger's PR 48 line
                    # reads 2.06 / 6.28 ms a launch at 1024, phi4flash_train_t8192)


def selective_scan_reference(u, dt, A, B, C, D):
    """The recurrence as one ``lax.scan`` over ``T`` in float32; JAX's own
    transpose is its backward (it keeps every step's state: small shapes and
    the CPU only). ``u``, ``dt``: ``(Bt, T, C)``; ``A``: ``(C, N)``; ``B``,
    ``C``: ``(Bt, T, N)``; ``D``: ``(C,)``."""
    f32 = jnp.float32
    A32, D32 = A.astype(f32), D.astype(f32)

    def step(s, x):
        u_t, dt_t, b_t, c_t = x                      # (Bt, C) (Bt, C) (Bt, N)
        decay = jnp.exp(dt_t[..., None] * A32)       # (Bt, C, N)
        s = decay * s + (dt_t * u_t)[..., None] * b_t[:, None, :]
        return s, jnp.sum(s * c_t[:, None, :], axis=-1) + D32 * u_t

    xs = tuple(jnp.swapaxes(a.astype(f32), 0, 1) for a in (u, dt, B, C))
    s0 = jnp.zeros((u.shape[0], u.shape[2], A.shape[1]), f32)
    _, y = lax.scan(step, s0, xs)
    return jnp.swapaxes(y, 0, 1).astype(u.dtype)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def _lanes(x, groups: int):
    """A ``(N, 128)`` tile copied ``groups`` times along the lanes."""
    return x if groups == 1 else jnp.concatenate([x] * groups, axis=1)


def _fold_lanes(x, groups: int):
    """``(N, groups * 128)`` -> ``(N, 128)``: the sum of the lane groups."""
    out = x[:, :_LANES]
    for g in range(1, groups):
        out = out + x[:, g * _LANES:(g + 1) * _LANES]
    return out


def _scan_fwd_kernel(u_ref, dt_ref, at_ref, bx_ref, cx_ref, d_ref,
                     y_ref, h_ref, s_ref, uf_ref, dtf_ref, yf_ref, *,
                     chunk: int, groups: int):
    """One (batch row, channel block, chunk) program; the chunks of a
    channel block run in order and hand the state on in ``s_ref``."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    h_ref[0, 0] = s_ref[...]                   # the state this chunk starts in
    uf_ref[...] = u_ref[0].astype(jnp.float32)
    dtf_ref[...] = dt_ref[0].astype(jnp.float32)
    at = at_ref[...]                           # (N, block_d)

    def body(t, s):
        dt_row = dtf_ref[pl.ds(t, 1), :]       # (1, block_d)
        u_row = uf_ref[pl.ds(t, 1), :]
        b = _lanes(bx_ref[0, t].astype(jnp.float32), groups)
        c = _lanes(cx_ref[0, t].astype(jnp.float32), groups)
        s = jnp.exp(dt_row * at) * s + b * (dt_row * u_row)
        yf_ref[pl.ds(t, 1), :] = jnp.sum(c * s, axis=0, keepdims=True)
        return s

    s_ref[...] = lax.fori_loop(0, chunk, body, s_ref[...])
    y_ref[0] = (yf_ref[...] + d_ref[...] * uf_ref[...]).astype(y_ref.dtype)


def _scan_bwd_kernel(u_ref, dt_ref, at_ref, bx_ref, cx_ref, d_ref, h_ref,
                     dy_ref, du_ref, ddt_ref, dat_ref, dd_ref, db_ref, dc_ref,
                     g_ref, st_ref, uf_ref, dtf_ref, dyf_ref, duf_ref,
                     ddtf_ref, dbx_ref, dcx_ref, *, chunk: int, groups: int):
    """One (batch row, channel block, chunk) program, chunks from the last
    to the first: ``g_ref`` carries ``decay_{t+1} * g_{t+1}`` across them and
    ``dat_ref`` / ``dd_ref`` (one block per batch row and channel block)
    collect over them."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        g_ref[...] = jnp.zeros_like(g_ref)
        dat_ref[...] = jnp.zeros_like(dat_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    uf_ref[...] = u_ref[0].astype(jnp.float32)
    dtf_ref[...] = dt_ref[0].astype(jnp.float32)
    dyf_ref[...] = dy_ref[0].astype(jnp.float32)
    at = at_ref[...]

    # the chunk's states again: st_ref[t] is the state BEFORE row t
    def again(t, s):
        st_ref[t] = s
        dt_row = dtf_ref[pl.ds(t, 1), :]
        b = _lanes(bx_ref[0, t].astype(jnp.float32), groups)
        return jnp.exp(dt_row * at) * s + b * (dt_row * uf_ref[pl.ds(t, 1), :])

    st_ref[chunk] = lax.fori_loop(0, chunk, again, h_ref[0, 0])

    def body(i, carry):
        g_next, dat = carry
        t = chunk - 1 - i
        dt_row = dtf_ref[pl.ds(t, 1), :]
        u_row = uf_ref[pl.ds(t, 1), :]
        dy_row = dyf_ref[pl.ds(t, 1), :]
        b = _lanes(bx_ref[0, t].astype(jnp.float32), groups)
        c = _lanes(cx_ref[0, t].astype(jnp.float32), groups)
        decay = jnp.exp(dt_row * at)
        g = c * dy_row + g_next                  # dL/ds_t
        through = g * st_ref[t] * decay          # dL/d(decay) * decay
        gb = jnp.sum(g * b, axis=0, keepdims=True)
        duf_ref[pl.ds(t, 1), :] = gb * dt_row
        ddtf_ref[pl.ds(t, 1), :] = (
            jnp.sum(through * at, axis=0, keepdims=True) + gb * u_row)
        dbx_ref[t] = _fold_lanes(g * (dt_row * u_row), groups)
        dcx_ref[t] = _fold_lanes(st_ref[t + 1] * dy_row, groups)
        return decay * g, dat + through * dt_row

    g_next, dat = lax.fori_loop(
        0, chunk, body, (g_ref[...], jnp.zeros_like(at)))
    g_ref[...] = g_next
    dat_ref[0] += dat
    dd_ref[0] += jnp.sum(dyf_ref[...] * uf_ref[...], axis=0, keepdims=True)
    du_ref[0] = (duf_ref[...] + d_ref[...] * dyf_ref[...]).astype(du_ref.dtype)
    ddt_ref[0] = ddtf_ref[...].astype(ddt_ref.dtype)
    # the lanes of a channel block summed on the MXU: ones . X^T puts row
    # (t, n) of X on lane t * N + n of every result row
    n = at.shape[0]
    ones = jnp.ones((8, _LANES), jnp.float32)
    for src, dst in ((dbx_ref, db_ref), (dcx_ref, dc_ref)):
        x = src[...].reshape(chunk * n, _LANES)
        dst[0, 0, 0] = lax.dot_general(
            ones, x, (((1,), (1,)), ((), ())),
            precision=lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# launches
# ---------------------------------------------------------------------------


def _expand(x, t_pad: int):
    """``(Bt, T, N)`` -> ``(Bt, t_pad, N, 128)``: rows padded with zeros,
    every value copied along the lanes."""
    x = jnp.pad(x, ((0, 0), (0, t_pad - x.shape[1]), (0, 0)))
    return jnp.broadcast_to(x[..., None], x.shape + (_LANES,))


def _pad_rows(x, t_pad: int):
    return jnp.pad(x, ((0, 0), (0, t_pad - x.shape[1]), (0, 0)))


def _geometry(T: int, channels: int):
    block_d = min(BLOCK_D, channels)
    while channels % block_d:
        block_d -= _LANES
    return -(-T // CHUNK) * CHUNK, block_d


def launch_stats(batch: int, T: int, channels: int, states: int,
                 pallas: bool) -> dict:
    """A call site's row of ``profiler.get_launch_stats("ssm_scan")``: the
    kernels' geometry for these shapes (``_geometry``) and
    ``chunk_start_bytes``, the float32 states at the start of every chunk
    that a forward launch keeps for its backward (``(batch, t_pad / chunk,
    states, channels)``). Where the ``lax.scan`` runs there is no launch:
    the sizes alone, the rest zeros."""
    row = {"t_pad": T, "channels": channels, "states": states, "chunk": 0,
           "block_d": 0, "chunk_start_bytes": 0}
    if pallas:
        t_pad, block_d = _geometry(T, channels)
        row.update(t_pad=t_pad, chunk=CHUNK, block_d=block_d,
                   chunk_start_bytes=batch * (t_pad // CHUNK) * states
                   * channels * 4)
    return row


def _scan_forward_pallas(u, dt, A, B, C, D, interpret: bool = False):
    """``(y, h)``: ``h`` is the state at the start of every chunk,
    ``(Bt, T_pad / chunk, N, C)`` float32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    Bt, T, Cd = u.shape
    N = A.shape[1]
    t_pad, block_d = _geometry(T, Cd)
    n_chunks, groups = t_pad // CHUNK, block_d // _LANES
    # rows past T: dt = 0 leaves the state as it is
    up, dtp = _pad_rows(u, t_pad), _pad_rows(dt, t_pad)
    at = A.astype(jnp.float32).T
    d2 = D.astype(jnp.float32).reshape(1, Cd)
    row = pl.BlockSpec((1, CHUNK, block_d), lambda b, d, c: (b, c, d))
    wide = pl.BlockSpec((1, CHUNK, N, _LANES), lambda b, d, c: (b, c, 0, 0))
    y, h = pl.pallas_call(
        functools.partial(_scan_fwd_kernel, chunk=CHUNK, groups=groups),
        grid=(Bt, Cd // block_d, n_chunks),
        in_specs=[row, row,
                  pl.BlockSpec((N, block_d), lambda b, d, c: (0, d)),
                  wide, wide,
                  pl.BlockSpec((1, block_d), lambda b, d, c: (0, d))],
        out_specs=[row, pl.BlockSpec((1, 1, N, block_d),
                                     lambda b, d, c: (b, c, 0, d))],
        out_shape=[jax.ShapeDtypeStruct((Bt, t_pad, Cd), u.dtype),
                   jax.ShapeDtypeStruct((Bt, n_chunks, N, Cd), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((N, block_d), jnp.float32)]
        + [pltpu.VMEM((CHUNK, block_d), jnp.float32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="ssm_scan_fwd",
        interpret=interpret,
    )(up, dtp, at, _expand(B, t_pad), _expand(C, t_pad), d2)
    return y[:, :T], h


def _scan_backward_pallas(u, dt, A, B, C, D, h, dy, interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    Bt, T, Cd = u.shape
    N = A.shape[1]
    t_pad, block_d = _geometry(T, Cd)
    n_chunks, groups, n_d = t_pad // CHUNK, block_d // _LANES, Cd // block_d
    f32 = jnp.float32
    last = n_chunks - 1
    row = pl.BlockSpec((1, CHUNK, block_d), lambda b, d, c: (b, last - c, d))
    wide = pl.BlockSpec((1, CHUNK, N, _LANES),
                        lambda b, d, c: (b, last - c, 0, 0))
    per_block = pl.BlockSpec((1, 1, 1, 8, CHUNK * N),
                             lambda b, d, c: (b, d, last - c, 0, 0))
    folded = jax.ShapeDtypeStruct((Bt, n_d, n_chunks, 8, CHUNK * N), f32)
    du, ddt, dat, dd, db, dc = pl.pallas_call(
        functools.partial(_scan_bwd_kernel, chunk=CHUNK, groups=groups),
        grid=(Bt, n_d, n_chunks),
        in_specs=[row, row,
                  pl.BlockSpec((N, block_d), lambda b, d, c: (0, d)),
                  wide, wide,
                  pl.BlockSpec((1, block_d), lambda b, d, c: (0, d)),
                  pl.BlockSpec((1, 1, N, block_d),
                               lambda b, d, c: (b, last - c, 0, d)),
                  row],
        out_specs=[row, row,
                   pl.BlockSpec((1, N, block_d), lambda b, d, c: (b, 0, d)),
                   pl.BlockSpec((1, 1, block_d), lambda b, d, c: (b, 0, d)),
                   per_block, per_block],
        out_shape=[jax.ShapeDtypeStruct((Bt, t_pad, Cd), u.dtype),
                   jax.ShapeDtypeStruct((Bt, t_pad, Cd), dt.dtype),
                   jax.ShapeDtypeStruct((Bt, N, Cd), f32),
                   jax.ShapeDtypeStruct((Bt, 1, Cd), f32),
                   folded, folded],
        scratch_shapes=[pltpu.VMEM((N, block_d), f32),
                        pltpu.VMEM((CHUNK + 1, N, block_d), f32)]
        + [pltpu.VMEM((CHUNK, block_d), f32)] * 5
        + [pltpu.VMEM((CHUNK, N, _LANES), f32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="ssm_scan_bwd",
        interpret=interpret,
    )(_pad_rows(u, t_pad), _pad_rows(dt, t_pad), A.astype(f32).T,
      _expand(B, t_pad), _expand(C, t_pad), D.astype(f32).reshape(1, Cd), h,
      _pad_rows(dy, t_pad))

    def unfold(x, like):
        x = jnp.sum(x[:, :, :, 0, :], axis=1)          # over channel blocks
        return x.reshape(Bt, t_pad, N)[:, :T].astype(like.dtype)

    return (du[:, :T], ddt[:, :T], jnp.sum(dat, axis=0).T.astype(A.dtype),
            unfold(db, B), unfold(dc, C),
            jnp.sum(dd, axis=(0, 1)).astype(D.dtype))


# ---------------------------------------------------------------------------
# the op
# ---------------------------------------------------------------------------


def _use_pallas(u, A) -> bool:
    """The kernels want whole lane tiles of channels and whole sublane tiles
    of states; everything else, and every backend but the TPU, takes the
    ``lax.scan`` (counted: ``profiler.get_kernel_path_counts()``)."""
    return (jax.default_backend() == "tpu" and u.shape[2] % _LANES == 0
            and A.shape[1] % 8 == 0)


@jax.custom_vjp
def _scan_pallas(u, dt, A, B, C, D):
    return _scan_forward_pallas(u, dt, A, B, C, D)[0]


def _scan_pallas_fwd(u, dt, A, B, C, D):
    y, h = _scan_forward_pallas(u, dt, A, B, C, D)
    return y, (u, dt, A, B, C, D, h)


def _scan_pallas_bwd(res, dy):
    return _scan_backward_pallas(*res, dy)


_scan_pallas.defvjp(_scan_pallas_fwd, _scan_pallas_bwd)


# ``profiler.get_launch_stats("ssm_scan")``: the op's call sites and the
# newest one's ``launch_stats`` (ONE layer's)
metrics.register_kernel("ssm_scan", ("t_pad", "channels", "states", "chunk",
                                     "block_d", "chunk_start_bytes"))


@register("selective_scan", namespace="contrib")
def selective_scan(u, dt, A, B, C, D, log_A: bool = False):
    """Mamba-1's selective scan. ``u``, ``dt`` (after its softplus):
    ``(batch, T, channels)``; ``A`` (negative): ``(channels, states)``;
    ``B``, ``C``: ``(batch, T, states)``; ``D``: ``(channels,)``. Returns
    ``y`` like ``u``. With ``log_A`` the third operand is Mamba's parameter
    ``A_log`` and ``A = -exp(A_log)`` is made here, in float32 whatever the
    parameter is stored in. Pallas kernels with their own backward on the
    TPU where ``channels % 128 == 0`` and ``states % 8 == 0``; a ``lax.scan``
    anywhere else."""
    if log_A:
        A = -jnp.exp(A.astype(jnp.float32))
    pallas = _use_pallas(u, A)
    metrics.record_kernel_path("ssm_scan", pallas)
    metrics.record_launch("ssm_scan", **launch_stats(
        u.shape[0], u.shape[1], u.shape[2], A.shape[1], pallas))
    with jax.named_scope("ssm_scan"):
        if pallas:
            return _scan_pallas(u, dt, A, B, C, D)
        return selective_scan_reference(u, dt, A, B, C, D)


@register("causal_conv1d", namespace="contrib")
def causal_conv1d(x, weight, bias=None):
    """Depthwise causal convolution along ``T``: ``x`` ``(batch, T,
    channels)``, ``weight`` ``(channels, width)``, ``bias`` ``(channels,)``
    or none (optional: Mamba's layers have one, a gated short convolution
    has none); ``y_t = bias + sum_k weight[:, k] * x_{t - (width - 1) + k}``
    with zeros before the first row. ``width`` shifted copies, which XLA
    fuses."""
    T, width = x.shape[1], weight.shape[1]
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    y = None if bias is None else bias.astype(x.dtype)
    for k in range(width):
        term = padded[:, k:k + T] * weight[:, k].astype(x.dtype)
        y = term if y is None else y + term
    return y
