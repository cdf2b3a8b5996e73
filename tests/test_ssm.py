"""The selective-scan op (``ops/ssm.py``): the Pallas kernels in interpret
mode and the ``lax.scan`` path against a sequential float32 recurrence,
forward and backward; the depthwise causal convolution; the path counter."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxtpu import nd, profiler
from mxtpu.ops import ssm


def _inputs(Bt, T, Cd, N, seed=0):
    rs = np.random.RandomState(seed)
    u = rs.randn(Bt, T, Cd).astype(np.float32)
    dt = np.log1p(np.exp(rs.randn(Bt, T, Cd) - 2.0)).astype(np.float32)
    A = -np.exp(0.5 * rs.randn(Cd, N)).astype(np.float32)
    B = rs.randn(Bt, T, N).astype(np.float32)
    C = rs.randn(Bt, T, N).astype(np.float32)
    D = rs.randn(Cd).astype(np.float32)
    return u, dt, A, B, C, D


def _sequential(u, dt, A, B, C, D):
    """The recurrence one step, one batch row at a time, in numpy."""
    Bt, T, Cd = u.shape
    y = np.zeros_like(u)
    for b in range(Bt):
        s = np.zeros((Cd, A.shape[1]), np.float32)
        for t in range(T):
            s = np.exp(dt[b, t][:, None] * A) * s \
                + (dt[b, t] * u[b, t])[:, None] * B[b, t][None, :]
            y[b, t] = s @ C[b, t] + D * u[b, t]
    return y


def _numeric_grads(args, dy):
    """Gradients of ``sum(y * dy)`` through the scan written with plain
    ``jax.numpy`` indexing (no ``lax.scan``, none of the op's code)."""
    def loss(u, dt, A, B, C, D):
        s = jnp.zeros((u.shape[0], u.shape[2], A.shape[1]), jnp.float32)
        total = 0.0
        for t in range(u.shape[1]):
            s = jnp.exp(dt[:, t, :, None] * A) * s \
                + (dt[:, t] * u[:, t])[..., None] * B[:, t, None, :]
            y_t = jnp.einsum("bcn,bn->bc", s, C[:, t]) + D * u[:, t]
            total = total + jnp.sum(y_t * dy[:, t])
        return total
    return jax.grad(loss, argnums=tuple(range(6)))(*map(jnp.asarray, args))


# (T, channels): T a multiple of the chunk, and not; one and two lane groups
SHAPES = [(128, 128), (100, 256), (40, 128)]


@pytest.mark.parametrize("T,Cd", SHAPES)
def test_scan_kernel_forward_matches_the_sequential_recurrence(T, Cd):
    args = _inputs(2, T, Cd, 16)
    want = _sequential(*args)
    y, h = ssm._scan_forward_pallas(*map(jnp.asarray, args), interpret=True)
    np.testing.assert_allclose(np.asarray(y), want, rtol=2e-5, atol=2e-5)
    # the state kept at the start of every chunk: none before the first
    assert h.shape == (2, -(-T // ssm.CHUNK), 16, Cd)
    assert not np.asarray(h[:, 0]).any()


@pytest.mark.parametrize("T,Cd", SHAPES)
def test_scan_kernel_backward_matches_plain_autodiff(T, Cd):
    args = _inputs(1, T, Cd, 8, seed=1)
    dy = np.random.RandomState(2).randn(1, T, Cd).astype(np.float32)
    want = _numeric_grads(args, jnp.asarray(dy))
    jargs = tuple(map(jnp.asarray, args))
    _, h = ssm._scan_forward_pallas(*jargs, interpret=True)
    got = ssm._scan_backward_pallas(*jargs, h, jnp.asarray(dy),
                                    interpret=True)
    for name, g, w in zip(("u", "dt", "A", "B", "C", "D"), got, want):
        scale = float(jnp.max(jnp.abs(w)))
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4,
                                   atol=1e-4 * scale, err_msg="d" + name)


def test_scan_lax_path_forward_and_backward():
    args = _inputs(2, 37, 24, 4, seed=3)          # no kernel takes this
    jargs = tuple(map(jnp.asarray, args))
    np.testing.assert_allclose(
        np.asarray(ssm.selective_scan_reference(*jargs)),
        _sequential(*args), rtol=2e-5, atol=2e-5)
    dy = jnp.asarray(np.random.RandomState(4).randn(2, 37, 24), jnp.float32)
    got = jax.vjp(ssm.selective_scan_reference, *jargs)[1](dy)
    for g, w in zip(got, _numeric_grads(args, dy)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4,
                                   atol=1e-4 * float(jnp.max(jnp.abs(w))))


def test_scan_op_counts_its_path_and_records_on_the_tape():
    """Off the TPU the op takes the ``lax.scan`` and says so; through
    ``nd.contrib`` it is differentiable on the imperative tape."""
    from mxtpu import autograd
    profiler.reset_kernel_path_counts()
    args = [nd.array(a) for a in _inputs(1, 16, 128, 8, seed=5)]
    for a in args:
        a.attach_grad()
    with autograd.record():
        y = nd.contrib.selective_scan(*args)
        loss = nd.sum(y * y)
    loss.backward()
    counts = profiler.get_kernel_path_counts()
    assert counts["ssm_scan"]["xla"] >= 1 and counts["ssm_scan"]["pallas"] == 0
    assert all(float(nd.sum(nd.abs(a.grad)).asscalar()) > 0 for a in args)
    profiler.reset_kernel_path_counts()
    assert profiler.get_kernel_path_counts()["ssm_scan"] == {
        "pallas": 0, "xla": 0}


def test_scan_kernels_carry_their_names_in_the_tpu_lowering(monkeypatch):
    """On the TPU platform the op lowers to ``ssm_scan_fwd`` and, under
    ``grad``, ``ssm_scan_bwd``, both inside the scope ``ssm_scan``."""
    import re
    monkeypatch.setattr(ssm, "_use_pallas", lambda u, A: True)
    av = [jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in (
        (1, 256, 256), (1, 256, 256))] + [
        jax.ShapeDtypeStruct((256, 16), jnp.float32),
        jax.ShapeDtypeStruct((1, 256, 16), jnp.bfloat16),
        jax.ShapeDtypeStruct((1, 256, 16), jnp.bfloat16),
        jax.ShapeDtypeStruct((256,), jnp.float32)]
    f = jax.jit(jax.grad(lambda *a: jnp.sum(
        ssm.selective_scan(*a).astype(jnp.float32)), argnums=(0, 1, 2, 3, 4)))
    text = f.trace(*av).lower(lowering_platforms=("tpu",)).as_text(
        debug_info=True)
    assert sorted(set(re.findall(r'kernel_name = "([^"]+)"', text))) == [
        "ssm_scan_bwd", "ssm_scan_fwd"]
    # the scope the op opens, around the kernel's own name
    assert re.search(r'loc\("[^"]*ssm_scan[^"]*/ssm_scan_fwd/pallas_call"', text)


def test_causal_conv1d_matches_numpy():
    rs = np.random.RandomState(6)
    x = rs.randn(2, 9, 5).astype(np.float32)
    w = rs.randn(5, 4).astype(np.float32)
    b = rs.randn(5).astype(np.float32)
    want = np.zeros_like(x)
    for t in range(9):
        for k in range(4):
            src = t - 3 + k
            if src >= 0:
                want[:, t] += x[:, src] * w[:, k]
    want += b
    got = nd.contrib.causal_conv1d(nd.array(x), nd.array(w), nd.array(b))
    np.testing.assert_allclose(got.asnumpy(), want, rtol=1e-5, atol=1e-5)
    # causal: a later row does not move an earlier one
    x2 = x.copy()
    x2[:, 5:] += 1.0
    got2 = nd.contrib.causal_conv1d(nd.array(x2), nd.array(w), nd.array(b))
    np.testing.assert_array_equal(got2.asnumpy()[:, :5], got.asnumpy()[:, :5])
