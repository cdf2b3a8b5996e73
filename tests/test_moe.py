"""Expert parallelism (MoE over the ep mesh axis): parity vs a dense oracle,
capacity-drop semantics, gradients."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxtpu import parallel
from mxtpu.parallel import moe


def _setup(E=4, d=8, h=16, N=16, seed=0):
    rs = np.random.RandomState(seed)
    router_w = jnp.asarray(rs.randn(d, E).astype(np.float32))
    w1 = jnp.asarray(rs.randn(E, d, h).astype(np.float32) * 0.3)
    w2 = jnp.asarray(rs.randn(E, h, d).astype(np.float32) * 0.3)
    x = jnp.asarray(rs.randn(N, d).astype(np.float32))
    return router_w, w1, w2, x


def _oracle(router_w, w1, w2, x, capacity=None):
    """Dense reference: every token through its argmax expert, gated."""
    logits = np.asarray(x @ router_w)
    expert = logits.argmax(-1)
    gate = np.asarray(jax.nn.softmax(jnp.asarray(logits), axis=-1))[
        np.arange(x.shape[0]), expert]
    E = w1.shape[0]
    N = x.shape[0]
    n_loc = N // E
    out = np.zeros_like(np.asarray(x))
    # capacity accounting mirrors the sharded layout: tokens are ep-sharded in
    # contiguous blocks of n_loc; each (source device, expert) pair holds
    # `capacity` slots filled in token order
    cap = capacity if capacity is not None else n_loc
    for src in range(E):
        counts = {}
        for t in range(src * n_loc, (src + 1) * n_loc):
            e = expert[t]
            k = counts.get(e, 0)
            counts[e] = k + 1
            if k >= cap:
                continue  # dropped
            hdn = np.maximum(np.asarray(x)[t] @ np.asarray(w1)[e], 0)
            out[t] = gate[t] * (hdn @ np.asarray(w2)[e])
    return out


def test_moe_matches_dense_oracle():
    mesh = parallel.make_mesh((4,), ("ep",))
    router_w, w1, w2, x = _setup()
    y = moe.expert_parallel_ffn(router_w, w1, w2, x, mesh)
    ref = _oracle(router_w, w1, w2, x)
    np.testing.assert_allclose(np.asarray(y), ref, rtol=1e-4, atol=1e-5)


def test_moe_capacity_drop():
    mesh = parallel.make_mesh((4,), ("ep",))
    router_w, w1, w2, x = _setup(seed=7)
    # force congestion: route nearly everything to expert 0
    router_w = router_w.at[:, 0].set(10.0)
    y = moe.expert_parallel_ffn(router_w, w1, w2, x, mesh,
                                capacity_factor=0.5)
    ref = _oracle(router_w, w1, w2, x, capacity=2)  # 0.5 * n_loc(=4)
    np.testing.assert_allclose(np.asarray(y), ref, rtol=1e-4, atol=1e-5)
    # overflow rows are exactly zero (dropped)
    dropped = np.all(np.asarray(y) == 0, axis=1)
    assert dropped.any()


def test_moe_grads_flow_to_experts():
    mesh = parallel.make_mesh((4,), ("ep",))
    router_w, w1, w2, x = _setup(seed=3)

    def loss(w1_, w2_):
        return jnp.sum(moe.expert_parallel_ffn(router_w, w1_, w2_, x, mesh) ** 2)

    g1, g2 = jax.grad(loss, argnums=(0, 1))(w1, w2)
    # every expert that received tokens gets nonzero grads
    logits = np.asarray(x @ router_w)
    used = set(logits.argmax(-1).tolist())
    for e in range(4):
        gnorm = float(jnp.abs(g1[e]).sum())
        if e in used:
            assert gnorm > 0, e


# ---------------------------------------------------------------------------
# SparseExperts: the expert layer one chip holds
# ---------------------------------------------------------------------------

SCALE = 2.5


def _sparse_setup(E=16, k=4, d=32, f=48, tokens=96, bias_std=0.3, seed=0):
    rs = np.random.RandomState(seed)
    return dict(
        h=jnp.asarray(rs.randn(2, tokens // 2, d), jnp.float32),
        router=jnp.asarray(rs.randn(E, d), jnp.float32) * 0.3,
        bias=jnp.asarray(rs.randn(E), jnp.float32) * bias_std,
        gate_up=jnp.asarray(rs.randn(E, d, 2 * f), jnp.float32) * 0.2,
        down=jnp.asarray(rs.randn(E, f, d), jnp.float32) * 0.2, k=k)


def _sparse_oracle(s, held, bias_in_weights=False, use_bias=True):
    """The uncut layer's terms of the experts ``held``: every held expert
    on every token, weighted (zero where it was not chosen)."""
    x = s["h"].reshape(-1, s["h"].shape[-1])
    score = jax.nn.sigmoid(x @ s["router"].T)
    biased = score + s["bias"]
    _, chosen = jax.lax.top_k(biased if use_bias else score, s["k"])
    picked = jnp.take_along_axis(biased if bias_in_weights else score,
                                 chosen, axis=1)
    weights = SCALE * picked / picked.sum(axis=1, keepdims=True)
    f = s["down"].shape[1]
    y = jnp.zeros_like(x)
    for e in held:
        gu = x @ s["gate_up"][e]
        out = (gu[:, f:] * jax.nn.silu(gu[:, :f])) @ s["down"][e]
        y = y + out * jnp.sum(jnp.where(chosen == e, weights, 0.0),
                              axis=1)[:, None]
    return y.reshape(s["h"].shape)


def _sparse_layer(s, held, rows=0):
    at = jnp.asarray(list(held))
    y, count = moe.sparse_experts(
        s["h"], s["router"], s["bias"], s["gate_up"][at], s["down"][at],
        held=tuple(held), top_k=s["k"], scale=SCALE, rows=rows)
    # every expert's tokens are counted, held or not
    assert float(count.sum()) == s["h"].shape[0] * s["h"].shape[1] * s["k"]
    return y, count[at]


def test_sparse_selection_uses_the_bias_and_the_weights_do_not():
    s = _sparse_setup()
    held = range(16)
    y, _ = _sparse_layer(s, held)
    np.testing.assert_allclose(np.asarray(y), _sparse_oracle(s, held),
                               rtol=1e-4, atol=1e-5)
    # a layer that ignored the bias, or let it into the weights, is another
    for wrong in (dict(use_bias=False), dict(bias_in_weights=True)):
        gap = np.abs(np.asarray(y) - _sparse_oracle(s, held, **wrong)).max()
        assert gap > 100 * 1e-4, (wrong, gap)


@pytest.mark.parametrize("rows", [0, 16, 64])
def test_sparse_shares_add_up_to_the_uncut_layer(rows):
    """The guide's share test at the layer: 16 experts over 4 shares of 4
    (and 2 of 8, and 4 of scattered ids), each share routing over all 16;
    the shares' partial results add up to the uncut layer's, whatever the
    row buffer (16 rows: many passes)."""
    s = _sparse_setup(seed=3)
    whole = _sparse_oracle(s, range(16))
    for shares in ([range(f, f + 4) for f in range(0, 16, 4)],
                   [range(0, 8), range(8, 16)],
                   [[0, 5, 10, 15], [1, 4, 11, 14], [2, 7, 8, 13],
                    [3, 6, 9, 12]]):
        parts, loads = [], []
        for held in shares:
            y, load = _sparse_layer(s, held, rows)
            np.testing.assert_allclose(np.asarray(y), _sparse_oracle(s, held),
                                       rtol=1e-4, atol=1e-5)
            parts.append(np.asarray(y))
            loads.append(float(load.sum()))
        np.testing.assert_allclose(sum(parts), whole, rtol=1e-4, atol=2e-5)
        assert sum(loads) == 96 * s["k"]           # every pair is somewhere


def _sparse_grads(s, fn):
    def loss(h, router, gate_up, down):
        t = dict(s, h=h, router=router, gate_up=gate_up, down=down)
        return jnp.sum(jnp.sin(fn(t)))
    return jax.grad(loss, argnums=(0, 1, 2, 3))(
        s["h"], s["router"], s["gate_up"], s["down"])


def test_sparse_drops_no_token_when_every_token_picks_held_experts():
    """A bias that makes experts 4..7 every token's choice: 4 x 96 pairs
    land on a share whose even load is a quarter of that. The default
    buffer takes them in one pass (it is capped at the worst case), a small
    one in many; nothing is dropped either way, gradients included."""
    s = _sparse_setup(seed=5)
    s["bias"] = s["bias"].at[4:8].add(10.0)
    held = range(4, 8)
    want = _sparse_oracle(s, held)
    for rows in (0, 32):
        y, load = _sparse_layer(s, held, rows)
        assert float(load.sum()) == 96 * 4
        np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=1e-5)
    ref = _sparse_grads(s, lambda t: _sparse_oracle(t, held))
    # 32 rows: twelve passes; 384: the worst case in one
    for rows in (32, 384):
        got = _sparse_grads(s, lambda t: _sparse_layer(t, held, rows)[0])
        for g, r in zip(got, ref):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                       rtol=2e-3, atol=2e-5)


@pytest.mark.parametrize("rows,passes", [(256, 1), (64, 3)])
def test_sparse_gradients_either_side_of_the_buffer(rows, passes):
    """A buffer under the worst case (384 rows) learns while it runs how
    many passes the pairs take: one where they fit, more where they do not.
    Both give the oracle's gradients."""
    s = _sparse_setup(seed=3)
    held = range(4)
    pairs = float(_sparse_layer(s, held, rows)[1].sum())
    assert -(-pairs // rows) == passes, pairs
    got, ref = (_sparse_grads(s, fn) for fn in (
        lambda t: _sparse_layer(t, held, rows)[0],
        lambda t: _sparse_oracle(t, held)))
    for g, r in zip(got, ref):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=2e-3,
                                   atol=2e-5)


def test_sparse_block_keeps_counts_and_bias_as_states():
    from mxtpu import autograd, nd, profiler
    blk = moe.SparseExperts(32, 48, 16, 4, held=[4, 5, 6, 7],
                            shared_ffn_units=24, routed_scale=SCALE)
    blk.initialize()
    zero = blk.stats()
    assert zero["pairs"] == 0 and zero["buffer_rows"] is None \
        and zero["passes"] is None
    x = nd.array(np.random.RandomState(0).randn(2, 48, 32).astype(np.float32))
    with autograd.record():
        out = blk(x)
        loss = nd.sum(out * out)
    loss.backward()
    for state in (blk.select_bias, blk.count):
        assert state.grad_req == "null" and state.keep_float32 \
            and state.shape == (16,)
    assert float(nd.sum(nd.abs(blk.gate_up.grad())).asscalar()) > 0
    assert float(nd.sum(nd.abs(
        blk.shared.down.weight.grad())).asscalar()) > 0
    assert blk.router.grad().shape == (16, 32)
    # every token chose 4 of the 16 experts, and the layer counted them
    count = blk.count.data().asnumpy()
    assert count.sum() == 96 * 4 and count.dtype == np.float32
    row, = profiler.get_moe_stats(blk)
    assert row == blk.stats() and row["held"] == 4
    assert row["pairs"] == count[4:8].sum() > 0
    assert row["active"] == (count[4:8] > 0).sum()
    assert row["max_count"] == count.max() >= 96 * 4 / 16 >= count.min() \
        == row["min_count"]
    assert row["buffer_rows"] == moe.expert_rows(96, 16, 4, 4) == 384
    assert row["passes"] == 1
    # the state holds the NEWEST forward's counts, training or not; a model
    # cast to a narrower type keeps both states float32
    with autograd.pause():
        blk(x * 2.0)
    assert blk.stats()["pairs"] == blk.count.data().asnumpy()[4:8].sum()
    blk.cast("bfloat16")
    assert blk.count.dtype == blk.select_bias.dtype == "float32"
    assert blk.router.dtype == "bfloat16"
    with pytest.raises(ValueError, match="distinct ids"):
        moe.SparseExperts(32, 48, 16, 4, held=[1, 1])
    with pytest.raises(ValueError, match="distinct ids"):
        moe.SparseExperts(32, 48, 16, 4, held=[16])


@pytest.mark.parametrize("rate", [0.0, 0.02])
def test_sparse_block_balances_the_load_through_the_bias(rate):
    """``bias_update_rate``: every TRAINING forward moves each expert's
    selection bias by the rate against its load's side of the even share
    (all 16 experts, though 4 are held); repeated, the loads even out. A
    rate of 0 and a forward outside training leave the bias alone."""
    from mxtpu import autograd, nd
    rs = np.random.RandomState(1)
    blk = moe.SparseExperts(32, 48, 16, 4, held=range(4, 8),
                            bias_update_rate=rate)
    blk.initialize()
    b0 = (rs.randn(16) * 0.1).astype(np.float32)
    blk.select_bias.set_data(nd.array(b0))
    blk.router.set_data(nd.array(rs.randn(16, 32).astype(np.float32) * 0.3))
    x = nd.array(rs.randn(4, 64, 32).astype(np.float32))
    even = 256 * 4 / 16

    def counts():
        s = jax.nn.sigmoid(jnp.asarray(x.asnumpy()).reshape(-1, 32)
                           @ blk.router.data().data.T)
        _, chosen = jax.lax.top_k(s + blk.select_bias.data().data, 4)
        return np.bincount(np.asarray(chosen).ravel(), minlength=16)

    c0 = counts()
    blk(x)                                   # not training: nothing moves
    np.testing.assert_array_equal(blk.select_bias.data().asnumpy(), b0)
    with autograd.record():
        out = blk(x)
        loss = nd.sum(out * out)
    loss.backward()                          # the step's own bias, not the new
    assert float(nd.sum(nd.abs(blk.router.grad())).asscalar()) > 0
    np.testing.assert_allclose(blk.select_bias.data().asnumpy(),
                               b0 + rate * np.sign(even - c0), rtol=0,
                               atol=1e-7)
    assert blk.stats()["pairs"] == c0[4:8].sum()
    assert blk.stats()["max_count"] == c0.max()
    for _ in range(30):
        with autograd.record():
            blk(x)
    if rate:
        assert np.abs(counts() - even).max() < np.abs(c0 - even).max() / 2
    else:
        np.testing.assert_array_equal(counts(), c0)
    with pytest.raises(ValueError, match="bias_update_rate"):
        moe.SparseExperts(32, 48, 16, 4, bias_update_rate=-1.0)


# ---------------------------------------------------------------------------
# the dispatch / combine pair: rows gathered into the buffer a live tile at
# a time, each token's rows summed by gathers over its ``top_k`` choices
# where every expert is held, by a gather of its first pair and a tiled add
# of its further ones otherwise, both directions' transposes written out;
# against the whole-buffer gather and ``.at[].add`` they replaced
# ---------------------------------------------------------------------------


def _pairs_setup(case, T=40, k=2, E=8, d=16, f=24, seed=0):
    """A routing made by hand: ``chosen`` (T, k) expert ids, so the pairs
    the held experts get are known, and the index set ``sparse_experts``
    would make of it."""
    rs = np.random.RandomState(seed)
    chosen = np.stack([rs.permutation(E)[:k] for _ in range(T)])
    if case == "all_held":
        held = tuple(range(E))
    elif case == "two_of_eight":
        held = (2, 5)
    elif case == "token_without_pair":
        held = (0, 1, 2, 3)
        chosen[::3] = [4, 6]                    # these tokens: absent only
    else:                                       # one_expert
        held = (3, 6)
        chosen[:] = [6, 0]                      # all on held expert 6
    slot = np.full((E,), len(held), np.int32)
    slot[list(held)] = np.arange(len(held))
    key = slot[chosen.reshape(-1)]
    load = np.bincount(key, minlength=len(held) + 1)[:len(held)]
    return dict(
        x=jnp.asarray(rs.randn(T, d), jnp.float32),
        weights=jnp.asarray(rs.rand(T, k) + 0.1, jnp.float32),
        gate_up=jnp.asarray(rs.randn(len(held), d, 2 * f), jnp.float32) * 0.2,
        down=jnp.asarray(rs.randn(len(held), f, d), jnp.float32) * 0.2,
        key=key, order=np.argsort(key, kind="stable").astype(np.int32),
        load=load, k=k, worst=T * min(k, len(held)),
        all_held=len(held) == E)


def _whole_buffer(s, rows):
    """The oracle: every pass gathers its whole buffer's tokens, multiplies
    and adds every row back with ``.at[].add``, differentiated by JAX."""
    from mxtpu.ops.grouped_matmul import grouped_matmul
    ends = np.cumsum(s["load"])
    starts = ends - s["load"]
    worst = -(-s["worst"] // rows) * rows
    order = np.zeros((worst,), np.int32)
    n = min(worst, len(s["order"]))
    order[:n] = s["order"][:n]

    def fn(x, weights, gate_up, down):
        y = jnp.zeros(x.shape, jnp.float32)
        for p in range(max(1, -(-int(ends[-1]) // rows))):
            lo = p * rows
            pairs = order[lo:lo + rows]
            token = pairs // s["k"]
            sizes = jnp.asarray(np.clip(ends, lo, lo + rows)
                                - np.clip(starts, lo, lo + rows))
            xs = x[token]
            f = down.shape[1]
            gu = grouped_matmul(xs, gate_up, sizes)
            out = grouped_matmul(gu[:, f:] * jax.nn.silu(gu[:, :f]), down,
                                 sizes)
            w_row = jnp.where(lo + np.arange(rows) < ends[-1],
                              weights.reshape(-1)[pairs], 0.0)
            y = y.at[token].add(out * w_row[:, None])
        return y

    def ours(x, weights, gate_up, down):
        index = (*moe._pair_index(jnp.asarray(s["key"]), weights,
                                  len(s["load"]), s["k"], worst,
                                  s["all_held"]),
                 jnp.asarray(starts, jnp.int32), jnp.asarray(ends, jnp.int32))
        return moe._held_experts(x, weights, gate_up, down, index, rows,
                                 s["k"])
    return fn, ours


def _equations(fn, *args):
    """Every equation of the jaxpr of ``fn`` and of everything it calls
    (loop bodies, a ``custom_vjp``'s two halves)."""
    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub)

    return walk(jax.make_jaxpr(fn)(*args).jaxpr)


def _wide_row_adds(fn, *args, d):
    """The ``scatter-add``s onto ``(tokens, d)`` operands in ``fn``: the
    adds of whole rows with repeated indices."""
    return [eqn.invars[0].aval.shape for eqn in _equations(fn, *args)
            if eqn.primitive.name == "scatter-add"
            and eqn.invars[0].aval.shape[1:] == (d,)]


def _like_the_whole_buffer(s, passes):
    """Output and all four gradients against the oracle's; returns the adds
    of whole rows with repeated indices that forward + backward hold."""
    pairs = int(s["load"].sum())
    rows = s["worst"] if passes == 1 else -(-pairs // 3)
    assert max(1, -(-pairs // rows)) == passes, (pairs, rows)
    args = (s["x"], s["weights"], s["gate_up"], s["down"])
    g = jnp.asarray(np.random.RandomState(9).randn(*s["x"].shape),
                    jnp.float32)
    oracle, ours = _whole_buffer(s, rows)
    np.testing.assert_allclose(np.asarray(jax.jit(ours)(*args)),
                               np.asarray(oracle(*args)), rtol=1e-5,
                               atol=1e-6)
    got, want = (jax.grad(lambda *a: jnp.sum(fn(*a) * g),
                          argnums=(0, 1, 2, 3))(*args)
                 for fn in (jax.jit(ours), oracle))
    for name, a, b in zip(("x", "weights", "gate_up", "down"), got, want):
        assert float(jnp.abs(b).max()) > 0, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-5, err_msg=name)
    return _wide_row_adds(
        jax.grad(lambda *a: jnp.sum(ours(*a) * g), argnums=(0, 1, 2, 3)),
        *args, d=s["x"].shape[1])


@pytest.mark.parametrize("passes", [1, 3])
@pytest.mark.parametrize("case", ["all_held", "two_of_eight",
                                  "token_without_pair", "one_expert"])
def test_rows_follow_the_pairs_like_the_whole_buffer(case, passes):
    # only a layer that holds every expert adds no row with repeated indices
    adds = _like_the_whole_buffer(_pairs_setup(case), passes)
    assert (adds == []) == (case == "all_held"), adds


@pytest.mark.parametrize("passes", [1, 3])
@pytest.mark.parametrize("k", [1, 2, 4])
def test_all_held_sums_a_tokens_rows_by_gathers_alone(k, passes):
    """A layer that holds every expert knows each token's ``k`` pairs: the
    combine and the dispatch's transpose gather them (a pair outside a pass
    weighs nothing there), the oracle's output and all four gradients come
    out, and nothing as wide as a row is added with repeated indices."""
    s = _pairs_setup("all_held", k=k)
    assert s["all_held"] and int(s["load"].sum()) == 40 * k
    assert _like_the_whole_buffer(s, passes) == []


def _traced_layer(held, rows=0):
    """``sparse_experts`` of a ``_sparse_setup`` as ``_sparse_grads`` wants
    it, with nothing that stops a trace (``_sparse_layer`` checks counts)."""
    held = tuple(held)

    def layer(t):
        at = jnp.asarray(held)
        return moe.sparse_experts(
            t["h"], t["router"], t["bias"], t["gate_up"][at], t["down"][at],
            held=held, top_k=t["k"], scale=SCALE, rows=rows)[0]
    return layer


def _grads_to_trace(s, layer):
    """``(fn, *args)`` for ``_equations``: forward + backward of ``layer``."""
    return (lambda h, router, gate_up, down: _sparse_grads(
        dict(s, h=h, router=router, gate_up=gate_up, down=down), layer),
        s["h"], s["router"], s["gate_up"], s["down"])


@pytest.mark.parametrize("held", [None, [4, 5, 6, 7]])
def test_only_a_share_adds_rows_with_repeated_indices(held):
    """The same fact of the operator itself, forward + backward: the form
    is chosen by ``len(held) == num_experts``, and by nothing else."""
    s = _sparse_setup()
    held = range(16) if held is None else held
    adds = _wide_row_adds(*_grads_to_trace(s, _traced_layer(held)), d=32)
    assert (adds == []) == (len(held) == 16), adds


@pytest.mark.parametrize("held,tokens", [(None, 96), ([4, 5, 6, 7], 96),
                                         ([3], 4096)])
def test_rows_moved_follows_the_pairs_and_not_the_buffer(held, tokens):
    """``stats()["rows_moved"]``: the pairs of the newest forward in whole
    tiles of the loops. A layer that holds every expert moves its whole
    buffer, a share moves its pairs', far under the buffer's four-fold.
    ``rows_added``: of those, the most that were added with repeated
    indices: none where every expert is held (gathers alone) or a token
    has one pair at most, the pairs past the busiest expert's otherwise."""
    from mxtpu import nd
    blk = moe.SparseExperts(32, 48, 16, 4, held=held)
    blk.initialize()
    assert blk.stats()["rows_moved"] is None is blk.stats()["rows_added"]
    blk(nd.array(np.random.RandomState(0).randn(1, tokens, 32)
                 .astype(np.float32)))
    row = blk.stats()
    tile = moe._row_tile(row["buffer_rows"])
    assert row["pairs"] <= row["rows_moved"] < row["pairs"] + tile
    assert row["rows_moved"] % tile == 0
    if held is None:
        assert row["rows_moved"] == row["buffer_rows"] == tokens * 4
        assert row["rows_added"] == 0
    else:
        assert row["rows_moved"] <= row["buffer_rows"] / 2, row
        if len(held) == 1:      # one pair a token at most: nothing further
            assert row["rows_added"] == 0
        else:
            load = blk.count.data().asnumpy()[held]
            assert 0 < row["rows_added"] == load.sum() - load.max() \
                <= row["rows_moved"]


@pytest.mark.parametrize("held,tokens", [(None, 96), ([4, 5, 6, 7], 96),
                                         ([3], 4096), ([2, 9], 1024)])
def test_tile_fill_is_the_pairs_over_the_rows_the_mxu_multiplies(held,
                                                                  tokens):
    """``stats()["mxu_rows"]`` is ``ops.grouped_matmul.mxu_rows`` of the held
    experts' counts over the layer's buffer (a count by the kernels' own
    arithmetic, whichever path the products took), ``tile_fill`` the pairs
    over it; both None before a forward, ``profiler.get_moe_stats`` carries
    them."""
    from mxtpu import nd, profiler
    from mxtpu.ops.grouped_matmul import mxu_rows
    blk = moe.SparseExperts(32, 48, 16, 4, held=held)
    blk.initialize()
    assert blk.stats()["mxu_rows"] is None is blk.stats()["tile_fill"]
    blk(nd.array(np.random.RandomState(0).randn(1, tokens, 32)
                 .astype(np.float32)))
    row, = profiler.get_moe_stats(blk)
    load = blk.count.data().asnumpy()[list(held or range(16))]
    assert row["passes"] == 1 and row["pairs"] == load.sum() > 0
    assert row["mxu_rows"] == mxu_rows(load, row["buffer_rows"], 4) \
        >= row["pairs"]
    assert row["mxu_rows"] % 128 == 0
    assert row["tile_fill"] == row["pairs"] / row["mxu_rows"] <= 1


def test_mxu_rows_are_summed_over_the_passes(monkeypatch):
    """A buffer the pairs do not fit: each pass's share of the sorted pairs
    is a launch of its own."""
    from mxtpu import nd
    from mxtpu.ops.grouped_matmul import mxu_rows
    monkeypatch.setattr(moe, "expert_rows", lambda *a: 512)
    blk = moe.SparseExperts(32, 48, 4, 2, held=[0, 1])
    blk.initialize()
    blk(nd.array(np.random.RandomState(0).randn(1, 2048, 32)
                 .astype(np.float32)))
    load = blk.count.data().asnumpy()[[0, 1]].astype(int)
    row = blk.stats()
    rows = row["buffer_rows"]
    assert rows == 512 and row["passes"] == -(-load.sum() // 512) > 2
    ends = np.cumsum(load)
    want = sum(mxu_rows(np.clip(ends, lo, lo + rows)
                        - np.clip(ends - load, lo, lo + rows), rows, 4)
               for lo in range(0, int(ends[-1]), rows))
    assert row["mxu_rows"] == want >= row["pairs"]


# ---------------------------------------------------------------------------
# what the layer keeps for its backward: where its pairs take one pass
# whatever the routing (every expert held, the buffer their worst case) the
# pass's two products, and no product a second time; otherwise nothing of a
# pass, and the backward runs its passes again
# ---------------------------------------------------------------------------


def _grouped_products(fn, *args):
    """The ``ragged_dot`` / ``ragged_dot_general`` equations in ``fn``: what
    a grouped product is off the chip."""
    return [eqn.primitive.name for eqn in _equations(fn, *args)
            if eqn.primitive.name.startswith("ragged_dot")]


@pytest.mark.parametrize("rows,products", [(0, 6), (96, 8)])
def test_one_pass_multiplies_nothing_a_second_time(rows, products):
    """Forward + backward of a layer that holds every expert: at the default
    buffer (the pairs' worst case, one pass) two products forward and the
    backward's four; a buffer that forces several passes keeps nothing of
    one, so its backward multiplies the two forward products again (its
    jaxpr holds a pass twice: the first, and the body of the loop over the
    further ones)."""
    s = _sparse_setup()
    found = _grouped_products(
        *_grads_to_trace(s, _traced_layer(range(16), rows)))
    traced = 2 if rows else 1           # the first pass and the loop's body
    assert len(found) == products * traced, found


@pytest.mark.parametrize("k", [1, 4])
def test_one_pass_form_gives_the_several_pass_forms_gradients(k):
    """The kept rows are the rows a second forward would make: loss and
    every gradient (the input's, the router's, both expert matrices') of
    the one-pass form are the several-pass form's of the same layer."""
    s = _sparse_setup(k=k, seed=3)
    one, several = (_traced_layer(range(16), rows) for rows in (0, 32 * k))
    np.testing.assert_allclose(float(jnp.sum(jnp.sin(one(s)))),
                               float(jnp.sum(jnp.sin(several(s)))),
                               rtol=1e-5)
    for name, a, b in zip(("h", "router", "gate_up", "down"),
                          _sparse_grads(s, one), _sparse_grads(s, several)):
        assert float(jnp.abs(b).max()) > 0, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                                   atol=2e-5, err_msg=name)


@pytest.mark.parametrize("held,dtype", [(None, "float32"),
                                        (None, "bfloat16"),
                                        ([4, 5, 6, 7], "float32")])
def test_kept_bytes_are_the_one_pass_two_products(held, dtype):
    """``stats()["kept_bytes"]``: ``rows * (2 f + d)`` numbers of the layer's
    dtype where every expert is held at the default buffer, 0 for a share,
    None before a forward; ``profiler.get_moe_stats`` carries it."""
    from mxtpu import nd, profiler
    blk = moe.SparseExperts(32, 48, 16, 4, held=held)
    blk.initialize()
    blk.cast(dtype)
    assert blk.stats()["kept_bytes"] is None
    blk(nd.array(np.random.RandomState(0).randn(2, 48, 32), dtype=dtype))
    row, = profiler.get_moe_stats(blk)
    rows = row["buffer_rows"]
    if held is None:
        assert rows == 96 * 4 and row["passes"] == 1
        assert row["kept_bytes"] \
            == rows * (2 * 48 + 32) * (4 if dtype == "float32" else 2)
    else:
        assert row["kept_bytes"] == 0
