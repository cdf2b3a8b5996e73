"""Mixtures of experts. Two layers live here, for two different jobs.

``SparseExperts`` is the expert layer of today's sparse models as ONE CHIP of
an expert-parallel deployment holds it, and the one to train with: ``top_k``
of ``num_experts`` SwiGLU experts a token by sigmoid scores, no token dropped
whatever the routing, the chip told which experts it holds (``held``, a list
of expert ids), an optional shared expert beside them and a selection bias
that balances the load without an auxiliary loss. It routes over ALL the
experts, renormalises over all the chosen ones, and computes the terms of
the experts it holds with grouped matmuls (``ops/grouped_matmul.py``); what
the absent experts would add is left out and the partial sum goes on. There
is no exchange here and nothing stands in for one: the layer whose tokens
travel over ``ep`` (ROADMAP M4's other half) is to be built on this one.

``expert_parallel_ffn`` is the older EP hook (task mandate: real
tp/pp/dp/sp/ep shardings; the reference predates MoE entirely): a FUNCTION,
not a layer, that shows the exchange and nothing else. Top-1 routing by
softmax, one ReLU expert a rank of the ``ep`` axis, tokens ep-sharded,
dispatch and return over ``lax.all_to_all``: the program structure of
GShard/Switch. Capacity-bounded: each expert accepts at most ``capacity``
tokens per source device; overflow tokens pass through with a zero expert
contribution (standard capacity-drop semantics). It cannot express k > 1,
several experts a chip, a shared expert or a dropless routing; no model of
the zoo uses it.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from .. import autograd
from ..gluon.block import HybridBlock
from ..gluon.nn.basic_layers import SwiGLU
from ..ops import registry
from ..ops.grouped_matmul import (grouped_matmul, grouped_matmul_grads,
                                  mxu_rows)
from .collectives import all_to_all_array, shard_map_compat
from .mesh import Mesh, get_default_mesh

__all__ = ["SparseExperts", "expert_parallel_ffn", "expert_rows"]


# ---------------------------------------------------------------------------
# the expert layer one chip holds
# ---------------------------------------------------------------------------


def expert_rows(tokens: int, num_experts: int, top_k: int, held: int) -> int:
    """Rows of the static buffer a pass of ``SparseExperts`` works on: four
    times the even share of (token, expert) pairs (``tokens * top_k * held /
    num_experts``), in whole 512s, never more than the worst case (every
    token picks ``min(top_k, held)`` held experts). The buffer is room, not
    work: the gather into it, the grouped kernels and the reading of rows
    out of it (and their transposes) touch the row tiles that hold a pair
    and no other, so their time follows the pairs; only the SwiGLU between
    the two products, and the clearing of the buffer before the gather,
    run over the buffer whatever it holds. A step whose routing sends more
    pairs here than the buffer has rows runs a further pass: four times the
    even share keeps that edge far from any load a balanced router
    visits."""
    worst = tokens * min(top_k, held)
    even = -(-tokens * top_k * held // num_experts)
    return min(worst, -(-4 * even // 512) * 512)


def _kept_groups(z, n_group: int, topk_group: int):
    """``(T, n_group)`` bool: the ``topk_group`` groups a token may choose
    from. The ``E`` selection scores ``z`` lie in ``n_group`` equal groups of
    neighbours; a group's score is the sum of its two largest (DeepSeek-V3's
    ``noaux_tc``)."""
    T, E = z.shape
    best, _ = lax.top_k(z.reshape(T, n_group, E // n_group), 2)
    _, groups = lax.top_k(jnp.sum(best, axis=-1), topk_group)
    return jnp.any(groups[..., None] == jnp.arange(n_group), axis=1)


def _route(x, router_w, bias, top_k: int, scale: float, eps: float = 0.0,
           n_group: int = 1, topk_group: int = 1):
    """``(chosen experts (T, k), their weights (T, k) float32)``: sigmoid
    scores in float32, the ``top_k`` largest of score + ``bias`` (which only
    selects and has no gradient), with ``n_group`` > 1 among the
    ``topk_group`` best groups alone (scope ``groups``); weights ``scale``
    times the chosen scores over their sum (plus ``eps`` where a family's
    router adds one; 0 adds no operation)."""
    z = lax.dot_general(x, router_w, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
    s = jax.nn.sigmoid(z)
    select = s + lax.stop_gradient(bias.astype(jnp.float32))
    if n_group > 1:
        with jax.named_scope("groups"):
            kept = _kept_groups(select, n_group, topk_group)
            select = jnp.where(jnp.repeat(kept, s.shape[1] // n_group,
                                          axis=1), select, -jnp.inf)
    _, chosen = lax.top_k(select, top_k)
    # one chosen score and zeros: exact, and a dense fusion both ways where
    # take_along_axis is a scalar gather and, backward, a scalar scatter-add
    picked = jnp.sum(jnp.where(chosen[..., None] == jnp.arange(s.shape[1]),
                               s[:, None, :], 0.0), axis=-1)
    weighted = scale * picked
    total = jnp.sum(picked, axis=1, keepdims=True)
    return chosen, weighted / (total + eps if eps else total)


_ROW_TILE = 512     # rows a turn of the row loops moves


def _row_tile(rows: int) -> int:
    """The loops' granularity for a buffer of ``rows``: ``_ROW_TILE`` where
    it divides the buffer, as it does every buffer of whole 512s."""
    return math.gcd(rows, _ROW_TILE)


def _pair_index(key, weights, n_held: int, top_k: int, worst: int,
                all_held: bool):
    """Where a step's (token, expert) pairs go and come from, int32 but
    for the weights, from ``key`` ``(T * k,)``: pair ``t * k + j``'s held
    slot, ``n_held`` for an absent expert's. ``(order, w_sorted, sums)``.
    ``order`` ``(worst,)``: the pair ids sorted by slot, a slot's by token,
    absent ones last, and ``w_sorted`` their ``weights`` (T, k) in that
    order, which carry no gradient (``_held_experts`` writes the weights'
    own). ``sums``: what ``_sum_rows`` finds a token's rows by, made of
    ``at`` ``(T, k)``, every pair's sorted position (the sort's inverse).
    ``all_held`` (the layer holds every expert, so every pair is held and a
    token has exactly ``top_k``): ``(at,)`` and nothing else. Otherwise
    nobody knows which tokens have a pair, so ``(place, first, more_at,
    more_pair)``. ``place`` ``(T,)``: the sorted position of each token's
    FIRST held pair, -1 where it has none, and ``first`` ``(T, k)`` bool:
    which of its choices that pair is. ``more_at``, ``more_pair``: the
    sorted positions and the ids of every FURTHER held pair of a token,
    ascending by position; past the last of them ``T * k`` and 0,
    ``_ROW_TILE`` more than there can be. Sorts and dense selections alone:
    a scalar gather or scatter costs the chip more a number than a sort
    does."""
    pairs = key.shape[0]
    T = pairs // top_k
    _, by_slot, w_sorted = lax.sort(
        (key, jnp.arange(pairs, dtype=jnp.int32),
         lax.stop_gradient(weights).reshape(-1)), num_keys=1, is_stable=True)
    room = (0, max(0, worst - pairs))
    at = jnp.argsort(by_slot).astype(jnp.int32).reshape(T, top_k)
    if all_held:
        return (jnp.pad(by_slot[:worst], room),
                jnp.pad(w_sorted[:worst], room), (at,))
    held = (key < n_held).reshape(T, top_k)
    first = held & (jnp.cumsum(held, axis=1) == 1)
    place = jnp.where(jnp.any(first, axis=1),
                      jnp.sum(jnp.where(first, at, 0), axis=1), -1)
    more_at, more_pair = lax.sort(
        (jnp.where(held & ~first, at, pairs).reshape(-1),
         jnp.arange(pairs, dtype=jnp.int32)), num_keys=1)
    most = T * (min(top_k, n_held) - 1)
    return (jnp.pad(by_slot[:worst], room), jnp.pad(w_sorted[:worst], room),
            (place, first,
             jnp.pad(more_at[:most], (0, _ROW_TILE), constant_values=pairs),
             jnp.pad(more_pair[:most], (0, _ROW_TILE))))


def _cleared(shape, dtype, count):
    """Zeros, from a traced scalar that is 0 in every step (``count`` is
    never negative). Constant zeros of all scopes are merged by XLA into
    broadcasts that carry no name, and a device trace then books the
    clearing of this layer's buffer under no scope at all."""
    return jnp.broadcast_to(jnp.minimum(count, 0).astype(dtype), shape)


def _take_rows(src, token, tiles, tile: int, into, weight=None,
               dot: bool = False):
    """``into`` with its first ``tiles`` row tiles replaced by ``src[token]``
    (times ``weight``, in float32), a tile a turn; rows past them stay as
    they came. With ``dot`` also the products of the gathered rows with the
    rows they replace, ``(len(token),)`` float32, zero past those tiles."""
    d = src.shape[1]

    def turn(i, carry):
        buf, dots = carry
        at = i * tile
        got = src[lax.dynamic_slice(token, (at,), (tile,))]
        if dot:
            old = lax.dynamic_slice(buf, (at, 0), (tile, d))
            dots = lax.dynamic_update_slice(
                dots, jnp.sum(got * old.astype(jnp.float32), axis=1), (at,))
        if weight is not None:
            got = got * lax.dynamic_slice(weight, (at,), (tile,))[:, None]
        return (lax.dynamic_update_slice(buf, got.astype(buf.dtype), (at, 0)),
                dots)

    dots = jnp.zeros(token.shape, jnp.float32) if dot else None
    return lax.fori_loop(0, tiles, turn, (into, dots))


def _sum_rows(sums, top_k: int, weights=None):
    """``add(acc, src, lo)`` for a step's ``sums`` (``_pair_index``'s):
    ``acc`` (None: nothing yet) plus, for every token, the sum of the rows
    of ``src`` that its pairs have in this pass, each times its weight in
    ``weights`` ``(T, k)`` (None: 1), ``src`` being rows ``lo ..`` of the
    sorted pairs; float32 ``(T, d)``. One algorithm, a float32 sum of each
    token's held rows by sorted position, in the two forms the layer's
    static shape allows. Where every expert is held a token's pairs are
    known: choice ``j``'s row comes by a gather over the tokens from
    ``at[:, j]`` (weight zero for a pair outside this pass), ``top_k``
    gathers that each write every token's row, so nothing starts from a
    buffer of zeros and nothing is added with repeated indices. Otherwise
    only a token's FIRST pair comes by such a gather (its weight taken as
    zero where that pair is not in this pass or the token has none) and
    its further pairs are added with repeated indices, ``_ROW_TILE`` a turn
    for as many as this pass has: at a fraction of a pair a token ``top_k``
    gathers would move mostly rows that carry nothing (PR 32 measured it
    slower than the whole-buffer add), and at ``top_k`` pairs a token the
    further pairs are three quarters of all rows, each four to eleven
    times the cost of a gathered one (PR 33)."""

    def gathered(acc, src, lo, at, w):
        n = src.shape[0]
        here = (at >= lo) & (at < lo + n)
        rows = src[jnp.clip(at - lo, 0, n - 1)].astype(jnp.float32) \
            * jnp.where(here, w, 0.0)[:, None]
        return rows if acc is None else acc + rows

    if len(sums) == 1:                      # every expert is held
        at, = sums

        def add(acc, src, lo):
            for j in range(top_k):
                acc = gathered(acc, src, lo, at[:, j],
                               1.0 if weights is None else weights[:, j])
            return acc
        return add

    place, first, more_at, more_pair = sums
    w_first = jnp.any(first, axis=1).astype(jnp.float32) if weights is None \
        else jnp.sum(jnp.where(first, weights, 0.0), axis=1)

    def add(acc, src, lo):
        w_pair = None if weights is None else weights.reshape(-1)
        n = src.shape[0]
        acc = gathered(acc, src, lo, place, w_first)
        if more_at.shape[0] == _ROW_TILE:   # a token has one pair at most
            return acc
        a, b = jnp.sum(more_at < lo), jnp.sum(more_at < lo + n)

        def turn(i, acc):
            at = a + i * _ROW_TILE
            pair = lax.dynamic_slice(more_pair, (at,), (_ROW_TILE,))
            row = lax.dynamic_slice(more_at, (at,), (_ROW_TILE,)) - lo
            w = jnp.where(at + jnp.arange(_ROW_TILE) < b,
                          1.0 if w_pair is None else w_pair[pair], 0.0)
            part = src[jnp.clip(row, 0, n - 1)].astype(jnp.float32) \
                * w[:, None]
            return acc.at[pair // top_k].add(part)

        return lax.fori_loop(0, -(-(b - a) // _ROW_TILE), turn, acc)
    return add


def _pass_rows(order, w_sorted, starts, ends, p, rows: int, top_k: int):
    """Pass ``p`` takes rows ``p * rows ..`` of the sorted (token, expert)
    pairs: ``(pairs, their tokens, their weights (zero past the last pair),
    the held experts' rows among them, the row tiles that hold a pair)``."""
    with jax.named_scope("dispatch"):
        lo = p * rows
        pairs = lax.dynamic_slice(order, (lo,), (rows,))
        sizes = jnp.clip(ends, lo, lo + rows) \
            - jnp.clip(starts, lo, lo + rows)
        live = jnp.clip(ends[-1] - lo, 0, rows)
        w_row = jnp.where(jnp.arange(rows) < live,
                          lax.dynamic_slice(w_sorted, (lo,), (rows,)), 0.0)
        return (pairs, pairs // top_k, w_row, sizes,
                -(-live // _row_tile(rows)))


def _one_pass(index, rows: int) -> bool:
    """Whether a step's pairs take ONE pass whatever its routing: a layer
    that holds every expert has exactly ``T * top_k`` pairs, and its buffer
    holds them all (as ``expert_rows`` makes it). A static fact of the
    layer, and what the two things that differ between the regimes follow.
    The loop over further passes is not traced there: one that never turns
    still cost every step of ``lfm2moe_train_t4096`` 1.3% (its carries are
    copied, and the row buffers that pass through them are filled more
    slowly) and traced and compiled the pass a second time. And the pass
    keeps its two products for the backward (``_held_experts``): the one
    pass there is can hand them over, and a layer that holds every expert
    of a model that fits has the room. A share's pairs follow the routing:
    its loop stays, how many passes it takes is a traced number (a loop
    cannot hand each pass's rows to the backward), and it keeps nothing of
    a pass."""
    order, _, sums, _, _ = index
    return len(sums) == 1 and order.shape[0] == rows


def _swiglu(gate_up):
    ffn = gate_up.shape[1] // 2
    return gate_up[:, ffn:] * jax.nn.silu(gate_up[:, :ffn])


def _experts(xs, w_gate_up, w_down, sizes):
    """``(out, gate_up)``: the rows' two grouped products, the second of
    the SwiGLU of the first."""
    with jax.named_scope("experts"):
        gate_up = grouped_matmul(xs, w_gate_up, sizes)
        return grouped_matmul(_swiglu(gate_up), w_down, sizes), gate_up


def _experts_grads(xs, w_gate_up, w_down, sizes, gate_up, d_out):
    """``_experts``' transpose from the ``gate_up`` its forward made, with
    no product taken a second time: the four grouped products of the
    backward (``grouped_matmul``'s own, by its rule) and one element-wise
    pass over ``gate_up`` for the SwiGLU and its transpose. ``(dxs,
    d_w_gate_up, d_w_down)``."""
    with jax.named_scope("experts"):
        act, swiglu_vjp = jax.vjp(_swiglu, gate_up)
        d_act, d_down = grouped_matmul_grads(act, w_down, sizes, d_out)
        d_gate_up, = swiglu_vjp(d_act)
        return (*grouped_matmul_grads(xs, w_gate_up, sizes, d_gate_up),
                d_down)


def _passes(x, weights, w_gate_up, w_down, index, rows: int, top_k: int):
    """``_held_experts``' forward: ``(y, kept)``, ``kept`` the one pass's
    ``(gate_up, out)`` where ``_one_pass`` holds and ``()`` otherwise."""
    order, w_sorted, sums, starts, ends = index
    tile = _row_tile(rows)
    with jax.named_scope("combine"):
        add_rows = _sum_rows(sums, top_k, weights)

    def one(p, y):
        _, token, _, sizes, tiles = _pass_rows(
            order, w_sorted, starts, ends, p, rows, top_k)
        with jax.named_scope("dispatch"):
            xs, _ = _take_rows(x, token, tiles, tile,
                               _cleared((rows, x.shape[1]), x.dtype, tiles))
        out, gate_up = _experts(xs, w_gate_up, w_down, sizes)
        with jax.named_scope("combine"):
            return add_rows(y, out, p * rows), (gate_up, out)

    if _one_pass(index, rows):
        return one(0, None)
    return lax.fori_loop(1, -(-ends[-1] // rows), lambda p, y: one(p, y)[0],
                         one(0, None)[0]), ()


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _held_experts(x, weights, w_gate_up, w_down, index, rows: int,
                  top_k: int):
    """Every pass the step's pairs need, added up: one, unless the routing
    sends more than ``rows`` pairs to the held experts. ``index``:
    ``_pair_index``'s three and the held experts' ``starts`` and ``ends``
    among the sorted pairs. A pass gathers its pairs' tokens into the row
    buffer a live tile a turn, multiplies them by their experts and sums
    each token's weighted rows in float32 (``_sum_rows``, in the form the
    index was made for: gathers alone where every expert is held, a gather
    and a tiled add of the further pairs otherwise): rows past the last
    live tile are neither gathered nor read back. Every loop's length
    is a traced number, so nothing is dropped and nothing is moved for
    pairs that are not there. The backward writes each movement's
    transpose out (the tokens' gradients gathered into the buffer a live
    tile a turn, the rows' gradients summed onto their tokens as the
    forward sums the rows, weights of 1), and gathers ``xs`` again from the
    input in either regime. What the layer keeps between forward and
    backward beside its input and the routing follows ``_one_pass``:

    * one pass, always (every expert held): the pass's ``gate_up`` ``(rows,
      2 ffn)`` and ``out`` ``(rows, d)`` in the layer's dtype
      (``SparseExperts.stats()["kept_bytes"]``). The backward takes the
      SwiGLU and its transpose in one element-wise pass over ``gate_up``,
      turns ``out``'s buffer into its gradient's, and runs the four grouped
      products a backward has: six a layer and step. The rows kept are the
      rows a second forward would make, so the result is the same.
    * a traced number of passes (a share): nothing as wide as (pairs, ffn).
      No row buffer outlives its pass; the backward runs the same passes
      again, each from its inputs, its two forward products a second time
      (eight a layer and step). A loop cannot hand a pass's rows to the
      backward, and where a chip holds a share of a model too large for it
      the room is the scarcer thing (``kexaone_train_t4096``: 0.87 GB free).

    Returns ``(T, d)`` float32."""
    return _passes(x, weights, w_gate_up, w_down, index, rows, top_k)[0]


def _held_experts_fwd(x, weights, w_gate_up, w_down, index, rows, top_k):
    if _one_pass(index, rows):
        y, kept = _passes(x, weights, w_gate_up, w_down, index, rows, top_k)
    else:       # as it was: the passes stay one call in the step's program
        y, kept = _held_experts(x, weights, w_gate_up, w_down, index, rows,
                                top_k), ()
    return y, (x, weights, w_gate_up, w_down, index, kept)


def _held_experts_bwd(rows, top_k, res, dy):
    x, weights, w_gate_up, w_down, index, kept = res
    order, w_sorted, sums, starts, ends = index
    tile = _row_tile(rows)
    with jax.named_scope("dispatch"):
        add_rows = _sum_rows(sums, top_k)

    def one(p, dx, dweights):
        pairs, token, w_row, sizes, tiles = _pass_rows(
            order, w_sorted, starts, ends, p, rows, top_k)
        with jax.named_scope("dispatch"):
            xs, _ = _take_rows(x, token, tiles, tile,
                               _cleared((rows, x.shape[1]), x.dtype, tiles))
        if kept:
            gate_up, out = kept
            experts_vjp = functools.partial(
                _experts_grads, xs, w_gate_up, w_down, sizes, gate_up)
        else:
            out, experts_vjp = jax.vjp(
                lambda *a: _experts(*a, sizes)[0], xs, w_gate_up, w_down)
        with jax.named_scope("combine"):
            # out's buffer becomes its gradient's, a tile after each tile's
            # products with dy are taken (the weights' gradient)
            d_out, dw_row = _take_rows(dy, token, tiles, tile, out,
                                       weight=w_row, dot=True)
            dweights = dweights.at[pairs].add(dw_row)
        dxs, d_gate_up, d_down = experts_vjp(d_out)
        with jax.named_scope("dispatch"):
            dx = add_rows(dx, dxs, p * rows)
        return dx, dweights, d_gate_up, d_down

    def more(p, grads):
        dx, dweights, d_gate_up, d_down = one(p, *grads[:2])
        return dx, dweights, grads[2] + d_gate_up, grads[3] + d_down

    with jax.named_scope("combine"):
        dweights = jnp.zeros((weights.size,), jnp.float32)
    if _one_pass(index, rows):
        dx, dweights, d_gate_up, d_down = one(0, None, dweights)
    else:
        dx, dweights, d_gate_up, d_down = lax.fori_loop(
            1, -(-ends[-1] // rows), more, one(0, None, dweights))
    with jax.named_scope("dispatch"):
        dx = dx.astype(x.dtype)
    return (dx, dweights.reshape(weights.shape), d_gate_up, d_down, None)


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


@registry.register("sparse_experts", namespace="contrib", num_outputs=2)
def sparse_experts(h, router_w, bias, w_gate_up, w_down, held=(),
                   top_k: int = 1, scale: float = 1.0, rows: int = 0,
                   weight_eps: float = 0.0, n_group: int = 1,
                   topk_group: int = 1):
    """The held experts' part of a sparse expert layer. ``h``: ``(..., d)``;
    ``router_w``: ``(E, d)``; ``bias``: ``(E,)``, added to the scores for the
    choice alone; ``w_gate_up``: ``(len(held), d, 2 f)``, ``w_down``:
    ``(len(held), f, d)``, the experts ``held`` (ids among the ``E``) in
    that order. Returns ``(y like h, count (E,) float32)``: ``y[t] = sum
    over the chosen e that are held of w_e(t) down_e(up_e h * silu(gate_e
    h))`` with ``w_e = scale * s_e / (sum of the chosen s + weight_eps)``,
    and the tokens that chose each of the ``E`` experts, held or not
    (``count[held]`` are the rows each held expert got). ``rows``: the
    static row buffer of a pass (0: ``expert_rows``). ``n_group`` > 1: the
    ``E`` experts in that many equal groups of neighbours, a group's score
    the sum of its two largest score + ``bias``, a token's ``top_k`` taken
    inside its ``topk_group`` best groups."""
    d, n_held = h.shape[-1], len(held)
    x = h.reshape(-1, d)
    T, E = x.shape[0], router_w.shape[0]
    with jax.named_scope("route"):
        chosen, weights = _route(x, router_w, bias, top_k, scale,
                                 weight_eps, n_group, topk_group)
        count = jnp.sum(chosen.reshape(-1, 1) == jnp.arange(E), axis=0,
                        dtype=jnp.int32)
    with jax.named_scope("dispatch"):
        # pairs sorted by held expert's slot, pairs of absent experts last
        # (dense: a lookup in a table of slots is 32768 scalar gathers)
        is_slot = chosen.reshape(-1, 1) == np.asarray(held, np.int32)
        key = n_held + jnp.sum(
            jnp.where(is_slot, np.arange(n_held, dtype=np.int32) - n_held, 0),
            axis=1)
        rows = rows or expert_rows(T, E, top_k, n_held)
        worst = -(-T * min(top_k, n_held) // rows) * rows
        load = count[np.asarray(held, np.int32)]
        ends = jnp.cumsum(load)
        index = (*_pair_index(key, weights, n_held, top_k, worst,
                              n_held == E), ends - load, ends)
    y = _held_experts(x, weights, w_gate_up, w_down, index, rows, top_k)
    return (y.astype(h.dtype).reshape(h.shape),
            lax.stop_gradient(count.astype(jnp.float32)))


_SPARSE_EXPERTS = registry.get_op("contrib.sparse_experts")


class SparseExperts(HybridBlock):
    """``top_k`` of ``num_experts`` SwiGLU experts a token, of which this
    chip holds ``held`` (a list of distinct expert ids; default all).
    Sigmoid scores in float32, the choice by score + ``select_bias``,
    weights ``routed_scale`` times the chosen scores over their sum (plus
    ``weight_eps``, 0 unless the family's router adds one), no token dropped
    for any routing. ``n_group`` > 1 limits the choice to groups: the experts
    in ``n_group`` equal groups of neighbouring ids, a group's score the sum
    of its two largest score + ``select_bias``, the ``top_k`` taken inside
    the ``topk_group`` best groups (scope ``route/groups``; 1, the default,
    leaves the traced program as it was). ``shared_ffn_units`` > 0 adds a
    shared expert (child ``shared``, a SwiGLU of that width every token goes
    through, added unweighted): every chip of a deployment computes it
    alike, so the shares' sum counts it once.

    ``bias_update_rate`` > 0 balances the load without an auxiliary loss
    (DeepSeek-V3's rule): every TRAINING forward ends with ``select_bias +=
    rate * sign(tokens * top_k / num_experts - count)``, the tokens that
    chose each of the ``num_experts`` experts against their even share, so
    an expert with more than its share is chosen less in the next step; the
    step itself, backward included, routes by the bias it began with. The
    routing is over all the experts, so a chip that holds a share of them
    counts the same numbers as every other and keeps the same bias with no
    exchange. 0 (the default) leaves the bias as it was set.

    Parameters: ``router`` ``(num_experts, units)``, ``gate_up``
    ``(len(held), units, 2 ffn)``, ``down`` ``(len(held), ffn, units)``, the
    shared expert's two matrices; auxiliary states (``grad_req="null"``,
    float32 whatever the model is cast to, carried through a compiled step
    as running statistics are), both ``(num_experts,)``: ``select_bias``
    and ``count``, the tokens that chose each expert in the newest forward
    (``stats()`` reads it).

    One pass works on a static buffer of ``expert_rows`` (token, expert)
    pairs, four times the even share; a step whose routing sends more pairs
    here than that runs more passes, so memory is the buffer's and time the
    pairs'. What follows the pairs: the dispatch (tokens' rows gathered into
    the buffer, a row tile that holds a pair a turn), the grouped products,
    the combine (each token's weighted rows summed in float32) and the
    transposes of both (the tokens' gradients gathered into the buffer by
    live tiles; the rows' gradients summed onto their tokens as the combine
    sums rows): no row of the buffer past the last live tile is written or
    read (``stats()["rows_moved"]``), and no sum starts from a buffer of
    zeros. What still runs over the whole buffer: the SwiGLU between the
    two products, the clearing of the buffer before the gather, and a
    weight and a token id a row.

    The sum of a token's rows takes one of two forms, chosen by the
    layer's own static shape and by nothing else. A layer that holds EVERY
    expert (``len(held) == num_experts``) knows each token's pairs: exactly
    ``top_k``, choice ``j``'s at a sorted position the dispatch's sort
    already gives, so it sums them by ``top_k`` gathers over the tokens and
    adds nothing with repeated indices (``stats()["rows_added"]`` 0); three
    quarters of its rows would otherwise be further pairs, each several
    times the cost of a gathered row. A layer that holds a SHARE does not
    know which tokens have a pair (a fraction of one a token at 8 of 128):
    a token's first held pair comes by one gather over the tokens and only
    its further pairs are added with repeated indices, a tile of them a
    turn, where ``top_k`` gathers would move mostly rows that carry
    nothing. There is no threshold between the two: a layer one expert
    short of all takes the second. The same fact decides what a training
    forward keeps for its backward: a layer that holds every expert takes
    one pass whatever the routing and keeps that pass's two products
    (``stats()["kept_bytes"]``), so its backward multiplies nothing a
    second time; a share keeps nothing of a pass and runs its passes again
    (``_held_experts``). Scopes in a device trace: ``route``,
    ``dispatch``, ``experts``, ``combine``, ``shared``, ``balance``."""

    def __init__(self, units: int, ffn_units: int, num_experts: int,
                 top_k: int, held=None, shared_ffn_units: int = 0,
                 routed_scale: float = 1.0, bias_update_rate: float = 0.0,
                 weight_eps: float = 0.0, n_group: int = 1,
                 topk_group: int = 1, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        if bias_update_rate < 0:
            raise ValueError(f"bias_update_rate {bias_update_rate} < 0")
        held = tuple(range(num_experts) if held is None else held)
        if not held or len(set(held)) != len(held) \
                or min(held) < 0 or max(held) >= num_experts:
            raise ValueError(f"held experts must be distinct ids inside "
                             f"0..{num_experts - 1}: {list(held)}")
        if not 0 < top_k <= num_experts:
            raise ValueError(f"top_k {top_k} of {num_experts} experts")
        if num_experts % n_group or not 0 < topk_group <= n_group \
                or top_k > topk_group * (num_experts // n_group) \
                or (n_group > 1 and num_experts // n_group < 2):
            raise ValueError(
                f"{num_experts} experts in {n_group} groups of which "
                f"{topk_group} are kept for {top_k} experts a token")
        self.held = held
        self._groups = (n_group, topk_group)
        self._top_k, self._experts = top_k, num_experts
        self._scale, self._bias_rate = float(routed_scale), \
            float(bias_update_rate)
        self._weight_eps = float(weight_eps)
        self._rows = None       # the buffer's rows, once a forward has run
        with self.name_scope():
            self.router = self.params.get(
                "router", shape=(num_experts, units), init="normal")
            self.select_bias = self.params.get(
                "select_bias", shape=(num_experts,), init="zeros",
                grad_req="null", keep_float32=True)
            self.count = self.params.get(
                "count", shape=(num_experts,), init="zeros",
                grad_req="null", keep_float32=True)
            self.gate_up = self.params.get(
                "gate_up", shape=(len(held), units, 2 * ffn_units),
                init="normal")
            self.down = self.params.get(
                "down", shape=(len(held), ffn_units, units), init="normal")
            self.shared = SwiGLU(units, shared_ffn_units) \
                if shared_ffn_units else None

    def forward(self, x):
        tokens = math.prod(x.shape[:-1])
        self._rows = expert_rows(tokens, self._experts, self._top_k,
                                 len(self.held))
        bias = self.select_bias.data()
        y, count = registry.invoke(
            _SPARSE_EXPERTS, x, self.router.data(), bias,
            self.gate_up.data(), self.down.data(), held=self.held,
            top_k=self._top_k, scale=self._scale, rows=self._rows,
            weight_eps=self._weight_eps, n_group=self._groups[0],
            topk_group=self._groups[1])
        with jax.named_scope("balance"):
            if self._bias_rate and autograd.is_training():
                even = tokens * self._top_k / self._experts
                bias._set_data(bias.data + self._bias_rate
                               * jnp.sign(even - count.data))
            self.count.data()._set_data(count.data)
        return y if self.shared is None else y + self.shared(x)

    def stats(self) -> dict:
        """Of the newest forward (zeros before the first): the (token,
        expert) ``pairs`` the held experts got, how many of the ``held``
        were ``active`` (got a row), the ``max_count`` and ``min_count`` of
        tokens that chose any one of ALL the experts, ``load_max`` (the
        busiest HELD expert's rows over an expert's even share ``tokens *
        top_k / num_experts``: the straggler among the groups of the grouped
        products, 1 under an even routing), the ``buffer_rows`` of
        a pass, the ``passes`` the pairs took, the ``rows_moved``: the
        rows of the buffer that the dispatch filled and the combine read
        back, which are the pairs in whole tiles of the row loops and reach
        ``buffer_rows`` times ``passes`` only where the pairs do, and the
        ``rows_added``: the most of those rows that were summed onto their
        tokens with repeated indices, in the combine and again in the
        dispatch's transpose. Those are the FURTHER pairs of tokens that
        hold several: the pairs less the busiest held expert's (a token
        chooses an expert once, so at least that many tokens hold a first
        pair; ``count`` does not say which tokens hold one, so between
        steps only this bound is known), 0 where a token holds one pair at
        most, and 0 where every expert is held, whose layer sums by
        gathers alone; and the ``kept_bytes``: what of a pass the layer
        keeps from a training forward to its backward beyond its input and
        the routing, which is the pass's two products, ``buffer_rows *
        (2 ffn + units)`` numbers of the layer's dtype, where every expert
        is held (its pairs take one pass whatever the routing, and its
        backward multiplies nothing a second time) and 0 for a share (its
        backward runs its passes again: ``_held_experts``); the
        ``mxu_rows``: the rows the MXU multiplies in ONE grouped product
        over those pairs (``ops.grouped_matmul.mxu_rows``: every visit of a
        row tile by a group costs the 128-row blocks that hold the group's
        rows there, so rows of OTHER groups in those blocks are multiplied
        and thrown away), summed over the passes, and ``tile_fill`` =
        ``pairs / mxu_rows``, the share of the multiplied rows that are
        somebody's (1 where every group starts and ends on a block's edge;
        0 with no pairs). All seven None before the first forward. Reads
        ``count`` from the device: ask between steps, not inside a timed
        loop."""
        count = self.count.data().asnumpy()
        load = count[list(self.held)]
        pairs, even = float(load.sum()), float(count.sum()) / count.size
        tile = self._rows and _row_tile(self._rows)
        itemsize = jnp.dtype(self.gate_up.dtype).itemsize
        multiplied = self._rows and self._mxu_rows(load, itemsize)
        all_held = len(self.held) == self._experts
        by_gathers = all_held or min(self._top_k, len(self.held)) == 1
        _, units, ffn2 = self.gate_up.shape
        return {"name": self.name, "held": len(self.held), "pairs": pairs,
                "active": int((load > 0).sum()),
                "max_count": float(count.max()),
                "min_count": float(count.min()),
                "load_max": float(load.max()) / even if even else 0.0,
                "buffer_rows": self._rows,
                "passes": self._rows and max(1, -(-int(pairs) // self._rows)),
                "rows_moved": tile and -(-int(pairs) // tile) * tile,
                "rows_added": tile and (
                    0 if by_gathers else int(pairs - load.max())),
                "kept_bytes": self._rows and (
                    self._rows * (ffn2 + units) * itemsize
                    if all_held else 0),
                "mxu_rows": multiplied,
                "tile_fill": multiplied and pairs / multiplied}

    def _mxu_rows(self, load, itemsize: int) -> int:
        """``mxu_rows`` of the held experts' ``load``, each pass's share of
        the sorted pairs cut out as ``_pass_rows`` cuts it."""
        ends = np.cumsum(load.astype(np.int64))
        starts = np.concatenate([[0], ends[:-1]])
        return sum(
            mxu_rows(np.clip(ends, lo, lo + self._rows)
                     - np.clip(starts, lo, lo + self._rows),
                     self._rows, itemsize)
            for lo in range(0, max(int(ends[-1]), 1), self._rows))


# ---------------------------------------------------------------------------
# the older hook: top-1, capacity drop, one expert a rank, all-to-all
# ---------------------------------------------------------------------------


def expert_parallel_ffn(router_w, w1, w2, x, mesh: Optional[Mesh] = None,
                        axis_name: str = "ep",
                        capacity_factor: float = 1.0):
    """MoE FFN: ``y[t] = gate[t] * FFN_{e(t)}(x[t])`` with expert-sharded
    weights (one expert per ep rank).

    ``router_w``: (d, E) routing matrix (replicated). ``w1``: (E, d, h),
    ``w2``: (E, h, d) expert weights, stacked over the leading expert axis and
    sharded over ``ep`` (one expert per ep rank: E == ep size). ``x``: (N, d)
    tokens, N divisible by E. Returns (N, d).
    """
    mesh = mesh or get_default_mesh()
    E = mesh.shape[axis_name]
    N, d = x.shape
    if router_w.shape[1] != E or w1.shape[0] != E or w2.shape[0] != E:
        raise ValueError(
            f"expert count mismatch: ep axis has {E} ranks but router_w/w1/w2 "
            f"carry {router_w.shape[1]}/{w1.shape[0]}/{w2.shape[0]} experts "
            "(one expert per ep rank)")
    if N % E != 0:
        raise ValueError(f"token count {N} not divisible by ep size {E}")
    n_loc = N // E
    capacity = max(1, int(capacity_factor * n_loc))

    def spmd(router_w, w1_loc, w2_loc, x_loc):
        # x_loc: (n_loc, d); w1_loc/w2_loc: (1, d, h)/(1, h, d) — my expert
        logits = x_loc @ router_w                        # (n_loc, E)
        expert = jnp.argmax(logits, axis=-1)             # (n_loc,)
        gate = jax.nn.softmax(logits, axis=-1)[
            jnp.arange(n_loc), expert]                   # (n_loc,)

        # position of each token within its expert's send buffer
        onehot = jax.nn.one_hot(expert, E, dtype=jnp.int32)   # (n_loc, E)
        pos = jnp.cumsum(onehot, axis=0) * onehot        # 1-based where routed
        pos = jnp.sum(pos, axis=-1) - 1                  # (n_loc,)
        keep = pos < capacity

        send = jnp.zeros((E, capacity, d), x_loc.dtype)
        send = send.at[expert, jnp.where(keep, pos, 0)].add(
            jnp.where(keep[:, None], x_loc, 0.0))

        # exchange: device e receives every device's buffer for expert e
        recv = all_to_all_array(send, axis_name=axis_name, split_axis=0,
                                concat_axis=0, tiled=False)  # (E_src, capacity, d)

        h = recv.reshape(-1, d) @ w1_loc[0]              # my expert's FFN
        h = jax.nn.relu(h)
        out = (h @ w2_loc[0]).reshape(E, capacity, d)

        # return trip + gather each token's result back by its position
        back = all_to_all_array(out, axis_name=axis_name, split_axis=0,
                                concat_axis=0, tiled=False)  # (E_expert, capacity, d)
        y = back[expert, jnp.where(keep, pos, 0)]
        y = jnp.where(keep[:, None], y * gate[:, None], 0.0)
        return y

    fn = shard_map_compat(
        spmd, mesh,
        (P(), P(axis_name), P(axis_name), P(axis_name)),
        P(axis_name))
    return fn(router_w, w1, w2, x)
