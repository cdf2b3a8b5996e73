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
visited once for each and each visit stores its own rows. What a visit
costs follows the rows of ITS group in the tile: it multiplies the 128-row
blocks of a window that starts at the group's first row there (rounded
down to the sublane packing), not the whole tile, and ``moe_gmm`` holds the
whole of K beside a column tile sized from the VMEM bytes (``_gmm_tiles``),
so a group's weights are fetched once a column tile whatever its visits.
``mxu_rows`` counts the rows one launch multiplies by the same arithmetic
(``SparseExperts.stats()["tile_fill"]`` is the pairs over it). Everywhere
else, and for shapes the kernels do not take, the products are
``lax.ragged_dot`` / ``lax.ragged_dot_general``. Which path a call site took
is counted as the kind ``grouped_matmul``
(``profiler.get_kernel_path_counts()``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..observability import metrics
from .registry import register

__all__ = ["grouped_matmul", "grouped_matmul_grads", "mxu_rows"]

_ROW_TILE = 1024     # rows of x a work item has in VMEM
_SUB = 128           # ... of which it multiplies blocks of so many: the MXU's height
_TILE = 2048         # the most columns of a tile, and of ``moe_tgmm``'s K tile
_VMEM_BUDGET = 48 << 20   # what a launch may plan for of a v5e core's 128 MiB


# ---------------------------------------------------------------------------
# the work list, and what a visit costs
# ---------------------------------------------------------------------------


def _spans(sizes, tm: int, xp):
    """``(starts, ends, first, tiles)`` of every group, with ``xp`` (NumPy
    or ``jnp``): its rows ``[start, end)``, its first row tile and how many
    row tiles hold a row of it, 0 for an empty group."""
    ends = xp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    tiles = xp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    return starts, ends, first, tiles


def _align(itemsize: int) -> int:
    """Rows of one packed sublane tile: 8 float32, 16 bfloat16."""
    return 32 // itemsize


def _blocks(lo, hi, align: int):
    """How many ``_SUB``-row blocks a visit multiplies whose group has rows
    ``[lo, hi)`` of the tile: those of a window that starts at ``lo``
    rounded down to the sublane packing ``align``. Integer arithmetic
    alone, so the kernels (traced scalars) and the counter (NumPy) share
    it."""
    return (hi - lo + lo % align + _SUB - 1) // _SUB


def mxu_rows(group_sizes, m: int, itemsize: int = 2) -> int:
    """Rows the MXU multiplies in ONE ``moe_gmm`` launch over a buffer of
    ``m`` rows whose groups have ``group_sizes`` rows (concrete numbers):
    every visit's blocks, by the arithmetic the work list and the kernels
    run on. Never under ``sum(group_sizes)``; the pairs over it is the
    share of the multiplied rows that are somebody's."""
    sizes = np.asarray(group_sizes, np.int64)
    tm, align = _pick(m, _ROW_TILE), _align(itemsize)
    starts, ends, first, tiles = _spans(sizes, tm, np)
    lo, hi = starts - first * tm, ends - (first + tiles - 1) * tm
    alone = _blocks(lo, hi, align)
    across = (_blocks(lo, tm, align) + _blocks(0, hi, align)
              + (tiles - 2) * (tm // _SUB))
    blocks = np.where(tiles == 1, alone, np.where(tiles > 1, across, 0))
    return int(blocks.sum()) * _SUB


def _work_list(group_sizes, m: int, tm: int, visit_empty: bool):
    """``(group_of, tile_of, starts, ends, n_work)``, int32: the (row tile,
    group) pairs that share at least one row, in row order, and how many
    there are, which is the extent of the kernels' grid along the work axis
    (a traced number: the grid is as long as the step's routing makes it).
    ``visit_empty`` gives an empty group one item all the same (its ``dw``
    has to be written as zeros). The lists are ``m // tm + G`` long, the
    most there can be; past ``n_work`` they repeat the last item."""
    G = group_sizes.shape[0]
    starts, ends, first, tiles = _spans(group_sizes.astype(jnp.int32), tm,
                                        jnp)
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


def _gmm_vmem_bytes(tm: int, tk: int, tn: int, k: int, itemsize: int) -> int:
    """What a ``moe_gmm`` program keeps in VMEM: the row tile, the weight
    block and the output tile, each twice (the pipeline's two buffers),
    an output tile's worth of float32 for the blocks' products before they
    are stored and whatever else Mosaic keeps, and the accumulator where K
    is more than one step."""
    return (2 * (tm * tk + tk * tn + tm * tn) * itemsize + tm * tn * 4
            + (tm * tn * 4 if tk < k else 0))


def _gmm_tiles(m: int, k: int, n: int, itemsize: int):
    """``(tm, tk, tn, vmem bytes)`` of a ``moe_gmm`` launch, from its
    shapes: the K tile is the WHOLE of K wherever a column tile of 512 (or
    all of a narrower N) beside it fits ``_VMEM_BUDGET``, the column tile
    then the widest under ``_TILE`` that does. The weight block's index is
    then ``(g, 0, n)`` in every visit of a group, so the pipeline fetches a
    group's weights once a column tile, not once a visit (at K 6144 in
    three steps each visit fetched its 8.4 MB tiles again). A K too long
    for that is halved until it fits, and accumulated over its steps."""
    tm = _pick(m, _ROW_TILE)
    tk = k
    while True:
        for tn in range(_pick(n, _TILE), 127, -128):
            need = _gmm_vmem_bytes(tm, tk, tn, k, itemsize)
            if n % tn == 0 and (need <= _VMEM_BUDGET or tn <= 512):
                break
        if need <= _VMEM_BUDGET or tk % 256:
            return tm, tk, tn, need
        tk //= 2


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def _visit(i, group_of, tile_of, starts, ends, tm: int, align: int):
    """Of work item ``i``: its group's rows ``[lo, hi)`` within its tile,
    and the window of ``_SUB``-row blocks that holds them as ``(first row,
    blocks)``: from ``lo`` rounded down to the sublane packing, moved up
    where it would pass the tile's end; no block for an empty group's
    item."""
    g = group_of[i]
    base = tile_of[i] * tm
    lo = jnp.maximum(starts[g] - base, 0)
    hi = jnp.minimum(ends[g] - base, tm)
    blocks = jnp.where(hi > lo, _blocks(lo, hi, align), 0)
    return lo, hi, jnp.minimum(lo - lo % align, tm - blocks * _SUB), blocks


def _each_block(first, blocks, align: int, body):
    """``body(rows, at)`` for each block of a visit's window, ``rows`` the
    block as a slice from row ``at`` of the tile. A loop with a traced
    extent: its body is one block's product whatever the tile."""
    from jax.experimental import pallas as pl

    def turn(j, _):
        at = pl.multiple_of(first + j * _SUB, align)
        body(pl.ds(at, _SUB), at)
        return 0

    lax.fori_loop(0, blocks, turn, 0)


def _gmm_kernel(group_of, tile_of, starts, ends, x_ref, w_ref, o_ref,
                *acc_ref, tm: int, align: int, transpose_rhs: bool):
    """Work item ``i`` of column tile ``n``, ``k`` innermost: the blocks of
    row tile ``tile_of[i]`` that hold a row of group ``group_of[i]`` times
    ``w[group_of[i]]``, stored for the rows that are that group's. The
    output block stays resident while the tile does, so the rows an earlier
    group stored are still there."""
    from jax.experimental import pallas as pl

    i, k, last_k = pl.program_id(1), pl.program_id(2), pl.num_programs(2) - 1
    lo, hi, first, blocks = _visit(i, group_of, tile_of, starts, ends, tm,
                                   align)
    dims = (((1,), (1,)), ((), ())) if transpose_rhs \
        else (((1,), (0,)), ((), ()))

    def body(rows, at):
        part = lax.dot_general(x_ref[rows, :], w_ref[0], dims,
                               preferred_element_type=jnp.float32)
        r = at + lax.broadcasted_iota(jnp.int32, part.shape, 0)

        def store(acc):
            o_ref[rows, :] = jnp.where((r >= lo) & (r < hi),
                                       acc.astype(o_ref.dtype),
                                       o_ref[rows, :])

        if not acc_ref:                 # K in one step: nothing to add up
            return store(part)
        acc, = acc_ref

        @pl.when(k == 0)
        def _():
            acc[rows, :] = part

        @pl.when(k > 0)
        def _():
            acc[rows, :] += part

        @pl.when(k == last_k)
        def _():
            store(acc[rows, :])

    _each_block(first, blocks, align, body)


def _tgmm_kernel(group_of, tile_of, starts, ends, x_ref, dy_ref, o_ref,
                 acc_ref, *, tm: int, align: int):
    """Work item ``i`` (innermost) of the ``(k, n)`` tile of ``dw``: the
    rows of tile ``tile_of[i]`` that are group ``group_of[i]``'s, ``x^T dy``
    over the blocks that hold them, added up over the group's items and
    stored with its last."""
    from jax.experimental import pallas as pl

    i, last_item = pl.program_id(2), pl.num_programs(2) - 1
    g = group_of[i]
    lo, hi, first, blocks = _visit(i, group_of, tile_of, starts, ends, tm,
                                   align)

    @pl.when((i == 0) | (group_of[jnp.maximum(i - 1, 0)] != g))
    def _():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def body(rows, at):
        x = x_ref[rows, :]
        r = at + lax.broadcasted_iota(jnp.int32, x.shape, 0)
        x = jnp.where((r >= lo) & (r < hi), x, jnp.zeros_like(x))
        acc_ref[...] += lax.dot_general(
            x, dy_ref[rows, :], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _each_block(first, blocks, align, body)

    @pl.when((i == last_item)
             | (group_of[jnp.minimum(i + 1, last_item)] != g))
    def _():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def _params(semantics, vmem_bytes: int):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=semantics,
        vmem_limit_bytes=max(32 << 20, vmem_bytes + (8 << 20)))


# A launch is jitted so that it is traced and lowered ONCE for its shapes,
# however many layers call it, and ``inline`` so that the step's jaxpr holds
# the launch itself: behind a ``call`` XLA added a copy a call site (40 in
# ``joyai_train_t4096``'s step, 6% of it; PERF.md section 6, PR 48).
@functools.partial(jax.jit, static_argnames=("transpose_rhs", "interpret"),
                   inline=True)
def _gmm_pallas(x, w, group_sizes, transpose_rhs: bool = False,
                interpret: bool = False):
    """``x`` (M, K) by ``w`` (G, K, N), or (G, N, K) with
    ``transpose_rhs``; rows of no group are left as they are (garbage)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M, K = x.shape
    N = w.shape[1] if transpose_rhs else w.shape[2]
    item = x.dtype.itemsize
    tm, tk, tn, vmem = _gmm_tiles(M, K, N, item)
    *work, n_items = _work_list(group_sizes, M, tm, visit_empty=False)
    if transpose_rhs:
        w_spec = pl.BlockSpec(
            (1, tn, tk), lambda n, i, k, g_of, *_: (g_of[i], n, k))
    else:
        w_spec = pl.BlockSpec(
            (1, tk, tn), lambda n, i, k, g_of, *_: (g_of[i], k, n))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, align=_align(item),
                          transpose_rhs=transpose_rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(N // tn, n_items, K // tk),
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda n, i, k, g_of, t_of, *_: (t_of[i], k)),
                w_spec],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda n, i, k, g_of, t_of, *_: (t_of[i], n)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]
            if tk < K else []),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        compiler_params=_params(
            ("parallel", "arbitrary", "arbitrary"), vmem),
        name="moe_gmm",
        interpret=interpret,
    )(*work, x, w)


@functools.partial(jax.jit, static_argnames=("interpret",), inline=True)
def _tgmm_pallas(x, dy, group_sizes, interpret: bool = False):
    """``dw[g] = x_g^T dy_g``: ``x`` (M, K), ``dy`` (M, N) -> (G, K, N)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (M, K), N, G = x.shape, dy.shape[1], group_sizes.shape[0]
    tm, tk, tn = _pick(M, _ROW_TILE), _pick(K, _TILE), _pick(N, _TILE)
    *work, n_items = _work_list(group_sizes, M, tm, visit_empty=True)
    item = x.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm, align=_align(item)),
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
            2 * (tm * tk + tm * tn + tk * tn) * item + 2 * tk * tn * 4),
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
