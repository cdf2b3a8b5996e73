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


@pytest.mark.parametrize("case", sorted(CASES))
def test_interpreted_kernels_follow_the_loop(case):
    sizes = CASES[case]
    x, w, dy = _operands(seed=1)
    gs = jnp.asarray(sizes, jnp.int32)
    out = G._past_the_groups(
        G._gmm_pallas(jnp.asarray(x), jnp.asarray(w), gs, interpret=True), gs)
    np.testing.assert_allclose(np.asarray(out), _loop(x, w, sizes), atol=2e-4)
    dx = G._past_the_groups(
        G._gmm_pallas(jnp.asarray(dy), jnp.asarray(w), gs, True,
                      interpret=True), gs)
    np.testing.assert_allclose(
        np.asarray(dx), _loop(dy, np.swapaxes(w, 1, 2), sizes), atol=2e-4)
    dw = G._tgmm_pallas(jnp.asarray(x), jnp.asarray(dy), gs, interpret=True)
    np.testing.assert_allclose(np.asarray(dw), _loop_dw(x, dy, sizes, w),
                               atol=2e-3)


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
