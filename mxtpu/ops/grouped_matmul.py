"""Grouped matrix products: the expert matmuls of a mixture-of-experts layer.

``x`` holds rows sorted by group (an expert's tokens side by side, group 0
first), ``group_sizes[g]`` says how many rows group ``g`` has, and row ``r``
of group ``g`` is multiplied by ``w[g]``::

    out[r] = x[r] @ w[g(r)]                    x: (M, K)   w: (G, K, N)

``M`` is a static buffer; the groups may fill any part of it, unevenly, and
some may be empty. Rows past ``sum(group_sizes)`` belong to no group and
read zero, forward and backward.

The backward needs two more products, and the ``custom_vjp`` makes them the
same way: ``dx`` is the same product with every ``w[g]`` transposed, and
``dw[g] = x_g^T dy_g`` is a product per group over that group's rows.

On the TPU both are Pallas kernels (launches ``moe_gmm`` and ``moe_tgmm``).
The work list is made outside the kernels from ``group_sizes``: one item
for each (row tile, group) pair that shares rows, at most ``M / tile + G``
of them, handed to the kernels as prefetched scalars that steer the block
index maps. Row tiles past the last group get no item, so the time follows
the rows there are and not the buffer; a tile that two groups share is
visited once for each and each visit stores its own rows. Everywhere else,
and for shapes the kernels do not take, the products are ``lax.ragged_dot``
/ ``lax.ragged_dot_general``. Which path a call site took is counted as the
kind ``grouped_matmul`` (``profiler.get_kernel_path_counts()``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..observability import metrics
from .registry import register

__all__ = ["grouped_matmul", "grouped_matmul_grads"]

_ROW_TILE = 256      # rows of x a work item multiplies
_TILE = 2048         # the K and N tiles, where they divide


# ---------------------------------------------------------------------------
# the work list
# ---------------------------------------------------------------------------


def _work_list(group_sizes, m: int, tm: int, visit_empty: bool):
    """``(group_of, tile_of, starts, ends, n_work)``, int32: the (row tile,
    group) pairs that share at least one row, in row order, and how many
    there are, which is the extent of the kernels' grid along the work axis
    (a traced number: the grid is as long as the step's routing makes it).
    ``visit_empty`` gives an empty group one item all the same (its ``dw``
    has to be written as zeros). The lists are ``m // tm + G`` long, the
    most there can be; past ``n_work`` they repeat the last item."""
    G = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    last = jnp.where(sizes > 0, (ends - 1) // tm, first - 1)
    tiles = last - first + 1                       # 0 for an empty group
    if visit_empty:
        tiles = jnp.maximum(tiles, 1)
        first = jnp.minimum(first, m // tm - 1)
    n_work = jnp.sum(tiles)
    length = m // tm + G
    item_end = jnp.cumsum(tiles)
    at = jnp.minimum(jnp.arange(length, dtype=jnp.int32),
                     jnp.maximum(n_work - 1, 0))
    group_of = jnp.minimum(
        jnp.searchsorted(item_end, at, side="right").astype(jnp.int32), G - 1)
    tile_of = first[group_of] + at - (item_end - tiles)[group_of]
    tile_of = jnp.clip(tile_of, 0, m // tm - 1)
    return group_of, tile_of, starts, ends, n_work


def _pick(n: int, cap: int) -> int:
    """The largest multiple of 128 up to ``cap`` that divides ``n``."""
    t = min(cap, n)
    t -= t % 128
    while t > 128 and n % t:
        t -= 128
    return t


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def _gmm_kernel(group_of, tile_of, starts, ends, x_ref, w_ref, o_ref,
                acc_ref, *, tm: int, transpose_rhs: bool):
    """Work item ``i`` of column tile ``n``, ``k`` innermost: row tile
    ``tile_of[i]`` times ``w[group_of[i]]``, stored for the rows of the tile
    that are that group's. The output block stays resident while the tile
    does, so the rows an earlier group stored are still there."""
    from jax.experimental import pallas as pl

    i, k = pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    dims = (((1,), (1,)), ((), ())) if transpose_rhs \
        else (((1,), (0,)), ((), ()))
    acc_ref[...] += lax.dot_general(x_ref[...], w_ref[0], dims,
                                    preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _():
        g = group_of[i]
        rows = tile_of[i] * tm + lax.broadcasted_iota(
            jnp.int32, acc_ref.shape, 0)
        mine = (rows >= starts[g]) & (rows < ends[g])
        o_ref[...] = jnp.where(mine, acc_ref[...].astype(o_ref.dtype),
                               o_ref[...])


def _tgmm_kernel(group_of, tile_of, starts, ends, x_ref, dy_ref, o_ref,
                 acc_ref, *, tm: int):
    """Work item ``i`` (innermost) of the ``(k, n)`` tile of ``dw``: the
    rows of tile ``tile_of[i]`` that are group ``group_of[i]``'s, ``x^T dy``
    added up over the group's items and stored with its last."""
    from jax.experimental import pallas as pl

    i, last_item = pl.program_id(2), pl.num_programs(2) - 1
    g = group_of[i]

    @pl.when((i == 0) | (group_of[jnp.maximum(i - 1, 0)] != g))
    def _():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    x = x_ref[...]
    rows = tile_of[i] * tm + lax.broadcasted_iota(jnp.int32, x.shape, 0)
    x = jnp.where((rows >= starts[g]) & (rows < ends[g]), x,
                  jnp.zeros_like(x))
    acc_ref[...] += jnp.dot(x.T, dy_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when((i == last_item)
             | (group_of[jnp.minimum(i + 1, last_item)] != g))
    def _():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def _params(semantics, vmem_bytes: int):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=semantics,
        vmem_limit_bytes=max(32 << 20, vmem_bytes + (8 << 20)))


def _gmm_pallas(x, w, group_sizes, transpose_rhs: bool = False,
                interpret: bool = False):
    """``x`` (M, K) by ``w`` (G, K, N), or (G, N, K) with
    ``transpose_rhs``; rows of no group are left as they are (garbage)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M, K = x.shape
    N = w.shape[1] if transpose_rhs else w.shape[2]
    tm, tk, tn = _pick(M, _ROW_TILE), _pick(K, _TILE), _pick(N, _TILE)
    *work, n_items = _work_list(group_sizes, M, tm, visit_empty=False)
    if transpose_rhs:
        w_spec = pl.BlockSpec(
            (1, tn, tk), lambda n, i, k, g_of, *_: (g_of[i], n, k))
    else:
        w_spec = pl.BlockSpec(
            (1, tk, tn), lambda n, i, k, g_of, *_: (g_of[i], k, n))
    item = x.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, transpose_rhs=transpose_rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(N // tn, n_items, K // tk),
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda n, i, k, g_of, t_of, *_: (t_of[i], k)),
                w_spec],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda n, i, k, g_of, t_of, *_: (t_of[i], n)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        compiler_params=_params(
            ("parallel", "arbitrary", "arbitrary"),
            2 * (tm * tk + tk * tn + tm * tn) * item + tm * tn * 4),
        name="moe_gmm",
        interpret=interpret,
    )(*work, x, w)


def _tgmm_pallas(x, dy, group_sizes, interpret: bool = False):
    """``dw[g] = x_g^T dy_g``: ``x`` (M, K), ``dy`` (M, N) -> (G, K, N)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (M, K), N, G = x.shape, dy.shape[1], group_sizes.shape[0]
    tm, tk, tn = _pick(M, _ROW_TILE), _pick(K, _TILE), _pick(N, _TILE)
    *work, n_items = _work_list(group_sizes, M, tm, visit_empty=True)
    item = x.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(K // tk, N // tn, n_items),
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda k, n, i, g_of, t_of, *_: (t_of[i], k)),
                pl.BlockSpec((tm, tn),
                             lambda k, n, i, g_of, t_of, *_: (t_of[i], n))],
            out_specs=pl.BlockSpec(
                (1, tk, tn), lambda k, n, i, g_of, *_: (g_of[i], k, n)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((G, K, N), x.dtype),
        compiler_params=_params(
            ("parallel", "parallel", "arbitrary"),
            2 * (tm * tk + tm * tn + tk * tn) * item + tk * tn * 4),
        name="moe_tgmm",
        interpret=interpret,
    )(*work, x, dy)


# ---------------------------------------------------------------------------
# the op
# ---------------------------------------------------------------------------


def _use_pallas(x, w) -> bool:
    """The kernels want whole 128-tiles of rows, ``K`` and ``N``; every
    other shape, and every backend but the TPU, takes ``lax.ragged_dot``."""
    return (jax.default_backend() == "tpu" and x.dtype == w.dtype
            and all(n % 128 == 0 for n in x.shape + w.shape[1:]))


def _past_the_groups(out, group_sizes):
    """Rows of no group read zero (the kernels skip them)."""
    rows = lax.broadcasted_iota(jnp.int32, (out.shape[0], 1), 0)
    return jnp.where(rows < jnp.sum(group_sizes), out, jnp.zeros_like(out))


def _gmm(x, w, group_sizes, transpose_rhs: bool):
    if _use_pallas(x, w):
        return _past_the_groups(
            _gmm_pallas(x, w, group_sizes, transpose_rhs), group_sizes)
    return lax.ragged_dot(x, jnp.swapaxes(w, 1, 2) if transpose_rhs else w,
                          group_sizes.astype(jnp.int32))


_PER_GROUP = lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


def _tgmm(x, dy, w, group_sizes):
    if _use_pallas(x, w):
        return _tgmm_pallas(x, dy, group_sizes)
    return lax.ragged_dot_general(x, dy, group_sizes.astype(jnp.int32),
                                  _PER_GROUP).astype(w.dtype)


@jax.custom_vjp
def _grouped(x, w, group_sizes):
    return _gmm(x, w, group_sizes, False)


def _grouped_fwd(x, w, group_sizes):
    return _gmm(x, w, group_sizes, False), (x, w, group_sizes)


def _grouped_bwd(res, dy):
    x, w, group_sizes = res
    dy = dy.astype(x.dtype)
    return (_gmm(dy, w, group_sizes, True), _tgmm(x, dy, w, group_sizes),
            None)


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


metrics.register_kernel("grouped_matmul")


@register("grouped_matmul", namespace="contrib")
def grouped_matmul(x, w, group_sizes):
    """``out[r] = x[r] @ w[g(r)]`` for rows sorted by group: ``x`` ``(M,
    K)``, ``w`` ``(G, K, N)``, ``group_sizes`` ``(G,)`` integers whose sum
    is at most ``M``; rows past that sum read zero. Differentiable in ``x``
    and ``w``. Pallas kernels ``moe_gmm`` / ``moe_tgmm`` on the TPU where
    ``M``, ``K`` and ``N`` are multiples of 128, ``lax.ragged_dot``
    anywhere else; the choice is counted as ``grouped_matmul``
    (``profiler.get_kernel_path_counts()``)."""
    metrics.record_kernel_path("grouped_matmul", _use_pallas(x, w))
    return _grouped(x, w, group_sizes)


def grouped_matmul_grads(x, w, group_sizes, dy):
    """``(dx, dw)`` of ``grouped_matmul(x, w, group_sizes)`` for its
    output's gradient ``dy``, by the rule its ``custom_vjp`` has: for a
    caller that writes its own backward and kept ``x``. The call site was
    counted when its forward was made; this counts nothing."""
    return _grouped_bwd((x, w, group_sizes), dy)[:2]
