"""``nd.contrib.grouped_matmul`` (``ops/grouped_matmul.py``): values and all
three gradients against a per-group loop, empty groups, one group taking
every row, rows past the groups, and the Pallas kernels in interpret mode
against the same loop."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxtpu import autograd, nd, profiler
from mxtpu.ops import grouped_matmul as G

CASES = {
    "uneven": [100, 0, 300, 57, 0, 0, 200, 11],
    "all_empty": [0] * 8,
    "first_takes_all": [768, 0, 0, 0, 0, 0, 0, 0],
    "last_takes_all": [0, 0, 0, 0, 0, 0, 0, 768],
    "even_and_full": [96] * 8,
    "one_row": [0, 0, 1, 0, 0, 0, 0, 0],
}


def _loop(x, w, sizes):
    out, at = np.zeros((x.shape[0], w.shape[2]), np.float32), 0
    for g, n in enumerate(sizes):
        out[at:at + n] = x[at:at + n] @ w[g]
        at += n
    return out


def _loop_dw(x, dy, sizes, like):
    dw, at = np.zeros_like(like), 0
    for g, n in enumerate(sizes):
        dw[g] = x[at:at + n].T @ dy[at:at + n]
        at += n
    return dw


def _operands(seed=0, M=768, K=256, N=384, groups=8):
    rs = np.random.RandomState(seed)
    return (rs.randn(M, K).astype(np.float32),
            rs.randn(groups, K, N).astype(np.float32) * 0.1,
            rs.randn(M, N).astype(np.float32))


@pytest.mark.parametrize("case", sorted(CASES))
def test_values_and_gradients_follow_a_per_group_loop(case):
    sizes = CASES[case]
    x, w, dy = _operands()
    live = sum(sizes)
    gs = nd.array(np.asarray(sizes, np.int32))
    xs, ws = nd.array(x), nd.array(w)
    xs.attach_grad()
    ws.attach_grad()
    with autograd.record():
        out = nd.contrib.grouped_matmul(xs, ws, gs)
    out.backward(nd.array(dy))
    np.testing.assert_allclose(out.asnumpy(), _loop(x, w, sizes), atol=2e-4)
    assert not out.asnumpy()[live:].any()          # rows of no group: zero
    np.testing.assert_allclose(
        xs.grad.asnumpy(), _loop(dy, np.swapaxes(w, 1, 2), sizes), atol=2e-4)
    np.testing.assert_allclose(ws.grad.asnumpy(),
                               _loop_dw(x, dy, sizes, w), atol=2e-3)


# the kernels hold a row tile of TM of a buffer's ROWS rows (two tiles) and
# multiply the 128-row blocks that hold the visiting group's rows. (sizes,
# K, dtype)
F32, BF16 = "float32", "bfloat16"
ROWS = 2048
TM = G._pick(ROWS, G._ROW_TILE)
KERNEL_CASES = {
    **{name: (sizes, 256, F32) for name, sizes in CASES.items()},
    # a group's edge at, just before and just after a block's edge (128)
    # and a tile's edge (TM)
    "edge_at_a_block": ([128, 256, 0, 0, 100, 0, 0, 0], 256, F32),
    "edge_before_a_block": ([127, 256, 0, 0, 100, 0, 0, 0], 256, F32),
    "edge_after_a_block": ([129, 254, 2, 0, 100, 0, 0, 0], 256, F32),
    "edge_at_a_tile": ([TM, 256, 0, 0, 100, 0, 0, 0], 256, F32),
    "edge_before_a_tile": ([TM - 1, 256, 0, 0, 100, 0, 0, 0], 256, F32),
    "edge_after_a_tile": ([TM + 1, 254, 1, 0, 100, 0, 0, 0], 256, F32),
    # a window of one block that starts off the blocks' edges, and one
    # that would pass the tile's end and is moved up to end with it
    "one_block_off_the_edges": ([70, 120, 60, 120, 0, 0, 0, 0], 256, F32),
    "one_block_at_the_tiles_end": ([TM - 106, 100, 0, 0, 0, 0, 0, 0], 256,
                                   F32),
    "two_blocks_at_the_tiles_end": ([TM - 206, 200, 0, 0, 0, 0, 0, 0], 256,
                                    F32),
    "two_blocks_at_the_tiles_end_bf16": ([TM - 206, 200, 0, 0, 0, 0, 0, 0],
                                         256, BF16),
    # groups under 128 rows, several to a tile
    "several_to_a_tile": ([60, 70, 50, 64, 13, 100, 90, 33], 256, F32),
    "several_to_a_tile_bf16": ([60, 70, 50, 64, 13, 100, 90, 33], 256, BF16),
    # an empty group first, in the middle and last; rows past the groups
    "empty_first_middle_last": ([0, 200, 0, 0, 130, 90, 0, 0], 256, F32),
    "empty_first_middle_last_bf16": ([0, 200, 0, 0, 130, 90, 0, 0], 256,
                                     BF16),
    "rows_past_the_groups": ([5, 0, 0, 3, 0, 0, 0, 0], 256, F32),
    # K of three tiles' worth, held whole (one K step, no accumulator)
    "k_of_three_tiles": ([100, 0, 300, 57, 0, 0, 200, 11], 6144, F32),
    "k_of_three_tiles_bf16": ([100, 0, 300, 57, 0, 0, 200, 11], 6144, BF16),
    "uneven_bf16": (CASES["uneven"], 256, BF16),
}


def _kernels_follow_the_loop(sizes, K, dtype):
    x, w, dy = (jnp.asarray(a, dtype)
                for a in _operands(seed=1, M=ROWS, K=K))
    x32, w32, dy32 = (np.asarray(a, np.float32) for a in (x, w, dy))
    # a bfloat16 result is rounded once, from a float32 sum
    tol = dict(atol=2e-4 * (K / 256) ** 0.5) if dtype == F32 \
        else dict(rtol=1e-2, atol=1e-2 * (K / 256) ** 0.5)
    gs = jnp.asarray(sizes, jnp.int32)
    out = G._past_the_groups(G._gmm_pallas(x, w, gs, interpret=True), gs)
    assert out.dtype == x.dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               _loop(x32, w32, sizes), **tol)
    dx = G._past_the_groups(
        G._gmm_pallas(dy, w, gs, True, interpret=True), gs)
    np.testing.assert_allclose(
        np.asarray(dx, np.float32),
        _loop(dy32, np.swapaxes(w32, 1, 2), sizes), **tol)
    dw = G._tgmm_pallas(x, dy, gs, interpret=True)
    np.testing.assert_allclose(
        np.asarray(dw, np.float32), _loop_dw(x32, dy32, sizes, w32),
        **(dict(atol=2e-3) if dtype == F32 else dict(rtol=2e-2, atol=0.2)))


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_interpreted_kernels_follow_the_loop(case):
    _kernels_follow_the_loop(*KERNEL_CASES[case])


@pytest.mark.parametrize("case", ["uneven", "several_to_a_tile",
                                  "edge_after_a_tile",
                                  "two_blocks_at_the_tiles_end"])
def test_a_k_too_long_to_hold_is_added_up_over_its_steps(case, monkeypatch):
    """With room for a quarter of K beside the tiles the rule halves the K
    tile twice, and the visits' blocks are accumulated over the K steps."""
    sizes, _, dtype = KERNEL_CASES[case]
    whole = G._gmm_tiles(ROWS, 1024, 384, 4)
    monkeypatch.setattr(G, "_VMEM_BUDGET",
                        G._gmm_vmem_bytes(TM, 256, 384, 1024, 4))
    assert whole[1] == 1024 and G._gmm_tiles(ROWS, 1024, 384, 4)[1] == 256
    _kernels_follow_the_loop(sizes, 1024, dtype)


def _brute_mxu_rows(sizes, m, sub=128, align=16):
    """A count over (tile, group): the blocks of the window the kernels
    open on the group's rows in the tile."""
    rows, at, tm = 0, 0, G._pick(m, G._ROW_TILE)
    for n in sizes:
        for t in range(m // tm):
            lo, hi = max(at, t * tm) - t * tm, min(at + n, (t + 1) * tm) - t * tm
            if hi > lo:
                first = lo - lo % align
                rows += sub * len(range(first, hi, sub))
        at += n
    return rows


# the four sparse cells' routing: (groups, pairs, buffer rows, spread)
ROUTINGS = {"kexaone": (8, 2236, 8192, 0.2), "lfm2moe": (32, 16384, 16384, 0.5),
            "joyai": (32, 4096, 16384, 0.4), "lingflash": (16, 1024, 4096, 0.4)}


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("cell", sorted(ROUTINGS))
def test_mxu_rows_is_a_brute_count_over_tiles_groups_and_blocks(cell, seed):
    groups, pairs, m, spread = ROUTINGS[cell]
    rs = np.random.RandomState(seed)
    share = rs.uniform(1 - spread, 1 + spread, groups)
    sizes = rs.multinomial(pairs, share / share.sum())
    if seed == 2:
        sizes[rs.randint(groups)] = 0
    rows = G.mxu_rows(sizes, m)
    assert rows == _brute_mxu_rows(sizes, m) and rows % 128 == 0
    assert sizes.sum() <= rows <= m + 128 * groups
    # every visit of the work list is counted, at a block at least and a
    # whole tile at most
    tm = G._pick(m, G._ROW_TILE)
    *_, items = G._work_list(jnp.asarray(sizes, jnp.int32), m, tm, False)
    assert 128 * int(items) <= rows <= tm * int(items)
    # float32 rows pack by 8: a window may start later, never earlier
    assert G.mxu_rows(sizes, m, 4) == _brute_mxu_rows(sizes, m, align=8) \
        <= rows


def test_mxu_rows_of_edges_on_the_blocks_are_the_rows():
    assert G.mxu_rows([128, 256, 0, 384], 1024) == 768
    assert G.mxu_rows([0, 0, 0], 1024) == 0
    # 129 rows from row 0: two blocks; 100 rows from row 129 (a window
    # from row 128): one block
    assert G.mxu_rows([129, 100], 1024) == 256 + 128
    # 100 rows that lie across a tile's edge: a block of each tile
    tm = G._pick(2048, G._ROW_TILE)
    assert G.mxu_rows([tm - 56, 100], 2048) == tm + 128 + 128


def test_the_work_list_covers_every_shared_tile_once():
    sizes = jnp.asarray(CASES["uneven"], jnp.int32)
    group_of, tile_of, starts, ends, n = G._work_list(sizes, 768, 128, False)
    pairs = list(zip(np.asarray(tile_of)[:int(n)].tolist(),
                     np.asarray(group_of)[:int(n)].tolist()))
    want = [(t, g) for g, (a, b) in enumerate(zip(np.asarray(starts),
                                                  np.asarray(ends)))
            for t in range(6) if b > a and a < (t + 1) * 128 and b > t * 128]
    assert pairs == sorted(want, key=lambda p: (p[1], p[0]))
    # with the empty groups visited, each gets exactly one item more
    *_, n_all = G._work_list(sizes, 768, 128, True)
    assert int(n_all) == int(n) + CASES["uneven"].count(0)


def test_the_path_is_counted_and_bf16_agrees(monkeypatch):
    profiler.reset_kernel_path_counts()
    x, w, _ = _operands(seed=2)
    gs = jnp.asarray(CASES["uneven"], jnp.int32)
    out = G.grouped_matmul(jnp.asarray(x, jnp.bfloat16),
                           jnp.asarray(w, jnp.bfloat16), gs)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               _loop(x, w, CASES["uneven"]), atol=0.08)
    assert profiler.get_kernel_path_counts()["grouped_matmul"] \
        == {"pallas": 0, "xla": 1}
    # on the TPU platform whole 128-tiles take the kernels, other shapes not
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert G._use_pallas(jnp.zeros((256, 128)), jnp.zeros((2, 128, 384)))
    assert not G._use_pallas(jnp.zeros((256, 96)), jnp.zeros((2, 96, 384)))
