"""Flash/ring attention tests: XLA reference vs torch; ring vs single-device."""

import numpy as np
import pytest
import torch
import torch.nn.functional as tF

import jax
import jax.numpy as jnp

from mxtpu import nd, parallel
from mxtpu.ops.attention import attention_reference, _flash_attention_pallas


def _qkv(B=2, H=2, T=16, D=8, seed=0):
    rs = np.random.RandomState(seed)
    return [rs.randn(B, H, T, D).astype(np.float32) for _ in range(3)]


def test_attention_reference_vs_torch():
    q, k, v = _qkv()
    out = attention_reference(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    ref = tF.scaled_dot_product_attention(
        torch.from_numpy(q), torch.from_numpy(k), torch.from_numpy(v)).numpy()
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-5)


def test_attention_causal_vs_torch():
    q, k, v = _qkv(T=12)
    out = attention_reference(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                              causal=True)
    ref = tF.scaled_dot_product_attention(
        torch.from_numpy(q), torch.from_numpy(k), torch.from_numpy(v),
        is_causal=True).numpy()
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-5)


def test_flash_pallas_interpret_matches_reference():
    q, k, v = _qkv(B=1, H=2, T=128, D=128)
    qa, ka, va = map(jnp.asarray, (q, k, v))
    ref = attention_reference(qa, ka, va)
    out, lse = _flash_attention_pallas(qa, ka, va, causal=False,
                                       scale=1.0 / np.sqrt(128), interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4,
                               atol=1e-4)
    # lse parity vs explicit logsumexp
    logits = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(128)
    ref_lse = np.log(np.exp(logits - logits.max(-1, keepdims=True)).sum(-1)) \
        + logits.max(-1)
    np.testing.assert_allclose(np.asarray(lse).reshape(1, 2, 128), ref_lse,
                               rtol=1e-4, atol=1e-4)


def test_flash_pallas_interpret_causal():
    q, k, v = _qkv(B=1, H=1, T=256, D=128, seed=2)
    qa, ka, va = map(jnp.asarray, (q, k, v))
    ref = attention_reference(qa, ka, va, causal=True)
    out, _ = _flash_attention_pallas(qa, ka, va, causal=True,
                                     scale=1.0 / np.sqrt(128), block_q=128,
                                     block_k=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("D,T,causal", [(64, 128, False), (96, 128, True),
                                        (128, 120, False)])
def test_flash_pallas_production_shapes(D, T, causal):
    """Head dims 64/96 (lane padding) and non-128 T (block fallback) must run
    through the kernel and match the reference."""
    q, k, v = _qkv(B=1, H=2, T=T, D=D, seed=3)
    qa, ka, va = map(jnp.asarray, (q, k, v))
    ref = attention_reference(qa, ka, va, causal=causal)
    out, _ = _flash_attention_pallas(qa, ka, va, causal=causal,
                                     scale=1.0 / np.sqrt(D), interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("D,causal", [(64, False), (128, True)])
def test_flash_pallas_backward_matches_reference(D, causal):
    """The Pallas backward kernels (dq + dk/dv) against jax.grad of the XLA
    reference."""
    from mxtpu.ops.attention import _flash_backward_pallas
    B, H, T = 1, 2, 128
    q, k, v = _qkv(B=B, H=H, T=T, D=D, seed=4)
    qa, ka, va = map(jnp.asarray, (q, k, v))
    scale = 1.0 / np.sqrt(D)
    g = jnp.asarray(np.random.RandomState(5).randn(B, H, T, D).astype(np.float32))

    out, lse = _flash_attention_pallas(qa, ka, va, causal=causal, scale=scale,
                                       interpret=True)
    dq, dk, dv = _flash_backward_pallas(qa, ka, va, out, lse, g, causal, scale,
                                        interpret=True)
    _, vjp = jax.vjp(lambda q_, k_, v_: attention_reference(
        q_, k_, v_, causal=causal, scale=scale), qa, ka, va)
    rq, rk, rv = vjp(g)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(rq), rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(rk), rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(rv), rtol=1e-3, atol=1e-4)


def _reference_out_lse(q, k, v, causal, scale):
    """``attention_reference`` with grouped key/value heads repeated, and
    the log-sum-exp of the same masked scores beside it."""
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest") * scale
    if causal:
        i = jnp.arange(s.shape[-2])[:, None]
        s = jnp.where(i >= jnp.arange(s.shape[-1])[None, :], s, -jnp.inf)
    return (attention_reference(q, k, v, causal=causal, scale=scale),
            jax.nn.logsumexp(s, axis=-1))


# (H, Hkv, T, Tk, D, Dv, causal, dtype, lse cotangent). Blocks of 128, so
# T = 256 is two query tiles and two key tiles: the dq accumulator is
# revisited by the second key tile, dk / dv are carried over two query tiles
ONE_PASS_CASES = [
    (2, 2, 256, 256, 64, 64, True, "float32", False),
    (2, 2, 256, 256, 64, 64, False, "float32", False),
    (2, 2, 256, 256, 128, 128, True, "float32", False),
    (2, 2, 256, 256, 128, 128, False, "float32", False),
    (2, 2, 256, 384, 64, 64, False, "float32", False),     # T != Tk
    (2, 2, 384, 128, 128, 128, False, "float32", True),    # one key tile
    (4, 2, 256, 256, 64, 128, True, "float32", False),     # 2 : 1, Dv = 2 D
    (4, 2, 256, 256, 64, 128, False, "float32", True),
    (2, 2, 256, 256, 64, 64, True, "float32", True),
    (2, 2, 256, 256, 64, 64, True, "bfloat16", False),
    (4, 2, 256, 256, 64, 128, True, "bfloat16", True),
]


@pytest.mark.parametrize("H,Hkv,T,Tk,D,Dv,causal,dtype,lse_cot",
                         ONE_PASS_CASES)
def test_flash_one_pass_backward_matches_reference(H, Hkv, T, Tk, D, Dv,
                                                   causal, dtype, lse_cot):
    """``flash_bwd_fused`` (every score tile once, dq summed over the key
    tiles in a VMEM scratch) against ``jax.vjp`` of the XLA reference."""
    from mxtpu.ops.attention import _flash_backward_pallas
    rs = np.random.RandomState(T + Tk + D + Dv)
    q, k, v, g = (jnp.asarray(rs.randn(*shape).astype(np.float32)).astype(
        dtype) for shape in ((1, H, T, D), (1, Hkv, Tk, D), (1, Hkv, Tk, Dv),
                             (1, H, T, Dv)))
    g_lse = jnp.asarray(rs.randn(1, H, T).astype(np.float32)) if lse_cot \
        else None
    scale = 1.0 / np.sqrt(D)
    out, lse = _flash_attention_pallas(q, k, v, causal, scale, 128, 128,
                                       interpret=True)
    got = _flash_backward_pallas(q, k, v, out, lse, g, causal, scale, 128,
                                 128, interpret=True, lse_cot=g_lse)
    f32 = [a.astype(jnp.float32) for a in (q, k, v)]
    _, vjp = jax.vjp(lambda *a: _reference_out_lse(*a, causal, scale), *f32)
    want = vjp((g.astype(jnp.float32),
                jnp.zeros((1, H, T)) if g_lse is None else g_lse))
    # bfloat16: the kernel reads the forward's ROUNDED output for delta and
    # rounds what it stores
    tol = 1e-4 if dtype == "float32" else 3e-2
    for name, a, b, like in zip("qkv", got, want, (q, k, v)):
        assert a.shape == like.shape and a.dtype == like.dtype, name
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b), rtol=10 * tol,
            atol=tol * float(jnp.max(jnp.abs(b))), err_msg="d" + name)


MIB = 1 << 20

# (T, Dp, Dvp, itemsize, window) -> (block_q, block_k). The first seven are
# what the benchmark's cells launch (``brumby_train_t8192`` launches none):
# 512 x 512 everywhere, at the latent widths (q/k 192 in 256 lanes, v 128) as
# at 128 lanes. Then float32 chunks (``parallel/ring_attention.py``), 384 and
# 512 lanes, and rows long or wide enough that the bytes bring the tiles down
TILE_CASES = [
    ((1024, 128, 128, 2, None), (512, 512)),    # gpt2m_train_t1024
    ((2048, 128, 128, 2, None), (512, 512)),    # cgpt13_train_t2048
    ((8192, 128, 128, 2, None), (512, 512)),    # phi4flash_train_t8192
    ((8192, 128, 128, 2, 512), (512, 512)),     # ... its window layers
    ((4096, 128, 128, 2, None), (512, 512)),    # kexaone / lfm2moe
    ((4096, 128, 128, 2, 128), (512, 512)),     # kexaone's window layers
    ((4096, 256, 128, 2, None), (512, 512)),    # lingflash / joyai
    ((2048, 128, 128, 4, None), (512, 512)),
    ((4096, 256, 128, 4, None), (512, 512)),
    ((4096, 384, 384, 2, None), (512, 512)),
    ((4096, 512, 512, 2, None), (512, 512)),
    ((4096, 512, 512, 4, None), (512, 512)),
    ((4096, 512, 512, 4, 128), (512, 512)),
    ((8192, 512, 512, 2, None), (256, 256)),
    ((16384, 384, 384, 2, None), (128, 128)),
    ((8192, 512, 512, 4, None), (128, 128)),
    ((1536, 128, 128, 2, None), (512, 512)),
    ((640, 128, 128, 2, None), (128, 128)),     # 128 alone divides 640
    ((64, 128, 128, 4, None), (64, 64)),        # a whole short axis
]


@pytest.mark.parametrize("shape,want", TILE_CASES)
def test_flash_tiles_follow_the_vmem_bytes_of_the_shapes(shape, want):
    """One rule for every launch: the largest legal tiles up to 512 whose
    reckoned VMEM fits the budget; never under 128 rows of a long axis,
    never a limit under the default or over the chip's VMEM."""
    from mxtpu.ops import attention as A
    T, Dp, Dvp, itemsize, window = shape
    tiles = A._flash_tiles(T, T, Dp, Dvp, itemsize, window=window)
    assert (tiles.block_q, tiles.block_k) == want
    assert (tiles.dp, tiles.dvp) == (Dp, Dvp)
    assert T % tiles.block_q == 0 and tiles.block_q >= min(T, 128)
    for limit in (tiles.fwd_vmem_bytes, tiles.bwd_vmem_bytes):
        assert A._VMEM_DEFAULT <= limit <= A._VMEM_BYTES
    if want == (512, 512):
        assert max(tiles[4:]) <= A._VMEM_BUDGET
    # a caller's cap still holds (the tests' 128-row tiles)
    capped = A._flash_tiles(T, T, Dp, Dvp, itemsize, 128, 128, window)
    assert capped.block_q == capped.block_k == min(want[0], 128)


def test_flash_tiles_reckon_todays_backward_bytes_and_refuse_what_no_chip_holds(
        monkeypatch):
    from mxtpu.ops import attention as A
    # the backward's limit at 128 lanes, as the launch asked since PR 29
    for T, want in ((1024, 20054016), (2048, 22282240), (4096, 26738688),
                    (8192, 35651584)):
        tiles = A._flash_tiles(T, T, 128, 128, 2)
        assert tiles.bwd_vmem_bytes == want \
            == A._bwd_vmem_bytes(T, 128, 128, 512, 512, 2)
    # the forward, which asked for nothing and got the default: its bytes
    # grow with the K and V rows
    assert A._flash_tiles(1024, 1024, 128, 128, 2).fwd_vmem_bytes \
        == A._VMEM_DEFAULT
    assert A._fwd_vmem_bytes(8192, 128, 128, 512, 512, 2) \
        > A._fwd_vmem_bytes(4096, 128, 128, 512, 512, 2) > 8 * MIB
    # rows that no tile makes fit: the launch raises, the op takes XLA
    with pytest.raises(ValueError, match="no flash tiles"):
        A._flash_tiles(32768, 32768, 512, 512, 4)
    q = jax.ShapeDtypeStruct((1, 2, 32768, 512), jnp.float32)
    small = jax.ShapeDtypeStruct((1, 2, 4096, 192), jnp.bfloat16)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert not A._takes_kernels(q, q, q)
    assert A._takes_kernels(small, small, small)


@pytest.mark.parametrize("block", [256, 512])
def test_flash_at_the_latent_widths_matches_reference(block):
    """q/k 192 wide (256 lanes), v 128, causal, T = 1024: four or two tiles
    a side, so both sizes cross a tile edge and the diagonal. Forward and
    the three gradients against the XLA reference, and the counter's row."""
    from mxtpu import profiler
    from mxtpu.ops.attention import _flash_backward_pallas
    rs = np.random.RandomState(192 + block)
    q, k, v, g = (jnp.asarray(rs.randn(*shape).astype(np.float32))
                  for shape in ((1, 4, 1024, 192), (1, 4, 1024, 192),
                                (1, 4, 1024, 128), (1, 4, 1024, 128)))
    scale = 1.0 / np.sqrt(192)
    profiler.reset_launch_stats("flash")
    assert set(profiler.get_launch_stats("flash").values()) == {0}
    out, lse = _flash_attention_pallas(q, k, v, True, scale, block, block,
                                       interpret=True)
    row = profiler.get_launch_stats("flash")
    assert (row["launches"], row["block_q"], row["block_k"], row["dp"],
            row["dvp"]) == (1, block, block, 256, 128)
    assert row["bwd_vmem_bytes"] >= row["fwd_vmem_bytes"] >= 16 * MIB
    got = _flash_backward_pallas(q, k, v, out, lse, g, True, scale, block,
                                 block, interpret=True)
    # the backward takes the call site's tiles and records no row of its own
    assert profiler.get_launch_stats("flash") == row
    (want_out, want_lse), vjp = jax.vjp(
        lambda *a: _reference_out_lse(*a, True, scale), q, k, v)
    np.testing.assert_allclose(out, want_out, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(lse.reshape(1, 4, 1024), want_lse, rtol=2e-5,
                               atol=2e-5)
    for name, a, b in zip("qkv", got, vjp((g, jnp.zeros((1, 4, 1024))))):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-3,
            atol=1e-4 * float(jnp.max(jnp.abs(b))), err_msg="d" + name)
    profiler.reset_launch_stats("flash")
    assert set(profiler.get_launch_stats("flash").values()) == {0}


def _count_primitive(jaxpr, name: str) -> int:
    """Equations called ``name`` in ``jaxpr`` and every jaxpr nested in it."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _count_primitive(sub, name)
    return n


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_is_one_launch_of_five_matmuls(causal):
    """S and dP are made once per score tile: the backward is ONE Pallas
    call whose body holds five ``dot_general``s (S, dV, dP, dK, dQ), where
    a dq kernel beside a dk/dv kernel held seven."""
    from mxtpu.ops.attention import _flash_backward_pallas
    av = jax.ShapeDtypeStruct((1, 2, 256, 64), jnp.bfloat16)
    lse = jax.ShapeDtypeStruct((2, 256), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda q, k, v, o, lse, g: _flash_backward_pallas(
        q, k, v, o, lse, g, causal, 0.125, 128, 128, interpret=True))(
            av, av, av, av, lse, av).jaxpr
    calls = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    assert calls[0].params["name"] == "flash_bwd_fused"
    assert _count_primitive(calls[0].params["jaxpr"], "dot_general") == 5


def test_nd_attention_op_and_grad():
    q, k, v = _qkv(T=8)
    qn, kn, vn = nd.array(q), nd.array(k), nd.array(v)
    qn.attach_grad()
    from mxtpu import autograd
    with autograd.record():
        out = nd.contrib.flash_attention(qn, kn, vn)
        loss = nd.sum(out)
    loss.backward()
    # torch grads
    tq = torch.from_numpy(q).requires_grad_(True)
    tF.scaled_dot_product_attention(tq, torch.from_numpy(k),
                                    torch.from_numpy(v)).sum().backward()
    np.testing.assert_allclose(qn.grad.asnumpy(), tq.grad.numpy(), rtol=1e-3,
                               atol=1e-5)


def test_ring_attention_matches_single_device():
    mesh = parallel.make_mesh((8,), ("sp",))
    q, k, v = _qkv(B=1, H=2, T=64, D=16, seed=5)
    qa, ka, va = map(jnp.asarray, (q, k, v))
    ref = attention_reference(qa, ka, va)
    out = parallel.ring_self_attention(qa, ka, va, mesh, axis_name="sp")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4,
                               atol=1e-5)


def test_ring_attention_causal():
    mesh = parallel.make_mesh((8,), ("sp",))
    q, k, v = _qkv(B=1, H=1, T=64, D=16, seed=6)
    qa, ka, va = map(jnp.asarray, (q, k, v))
    ref = attention_reference(qa, ka, va, causal=True)
    out = parallel.ring_self_attention(qa, ka, va, mesh, axis_name="sp",
                                       causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4,
                               atol=1e-5)


def test_ring_attention_2d_mesh_dp_sp():
    mesh = parallel.make_mesh((2, 4), ("dp", "sp"))
    q, k, v = _qkv(B=2, H=2, T=32, D=16, seed=7)
    qa, ka, va = map(jnp.asarray, (q, k, v))
    ref = attention_reference(qa, ka, va)
    out = parallel.ring_self_attention(qa, ka, va, mesh, axis_name="sp")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4,
                               atol=1e-5)


def test_sync_batchnorm_global_stats():
    """dp-sharded input: stats span the global batch (the SyncBatchNorm semantic)."""
    from mxtpu.gluon.contrib import SyncBatchNorm
    from mxtpu import autograd
    net = SyncBatchNorm(in_channels=3)
    net.initialize()
    x = nd.array(np.random.RandomState(0).rand(16, 3, 4, 4).astype(np.float32) * 4)
    with autograd.record():
        out = net(x)
    o = out.asnumpy()
    np.testing.assert_allclose(o.mean(axis=(0, 2, 3)), 0, atol=1e-4)
    # running stats moved toward batch stats
    assert not np.allclose(net.running_mean.data().asnumpy(), 0)


def test_sync_batchnorm_grad_flows():
    from mxtpu.gluon.contrib import SyncBatchNorm
    from mxtpu import autograd, gluon
    net = SyncBatchNorm(in_channels=2)
    net.initialize()
    x = nd.array(np.random.rand(4, 2, 3, 3).astype(np.float32))
    x.attach_grad()
    with autograd.record():
        out = net(x)
        loss = nd.sum(out * out)
    loss.backward()
    assert np.isfinite(x.grad.asnumpy()).all()
    assert np.abs(x.grad.asnumpy()).sum() > 0
    assert net.beta.data()._grad is not None


def test_multihead_attention_block():
    from mxtpu.gluon.contrib.nn import MultiHeadAttention
    mha = MultiHeadAttention(units=32, num_heads=4, causal=True)
    mha.initialize()
    x = nd.random.normal(shape=(2, 10, 32))
    out = mha(x)
    assert out.shape == (2, 10, 32)
    # cross attention
    mem = nd.random.normal(shape=(2, 6, 32))
    out2 = mha(x, mem)
    assert out2.shape == (2, 10, 32)


def test_variational_dropout_cell():
    from mxtpu.gluon.contrib.rnn import VariationalDropoutCell
    from mxtpu import autograd, gluon
    cell = VariationalDropoutCell(gluon.rnn.LSTMCell(8, input_size=4),
                                  drop_inputs=0.5)
    cell.initialize()
    x = nd.ones((2, 6, 4))
    with autograd.record():
        outs, _ = cell.unroll(6, x, merge_outputs=False)
    # same mask across time: masked input positions identical each step
    m1 = cell._mask_in.asnumpy()
    assert (m1 == 0).any()


def test_causal_cross_attention_top_left():
    """Top-left causal alignment: query row 0 attends key 0 even when Tk < Tq."""
    rs = np.random.RandomState(9)
    q = jnp.asarray(rs.randn(1, 1, 10, 8).astype(np.float32))
    k = jnp.asarray(rs.randn(1, 1, 6, 8).astype(np.float32))
    v = jnp.asarray(rs.randn(1, 1, 6, 8).astype(np.float32))
    out = attention_reference(q, k, v, causal=True)
    # row 0 sees only key 0 → output equals v[0]
    np.testing.assert_allclose(np.asarray(out[0, 0, 0]), np.asarray(v[0, 0, 0]),
                               rtol=1e-5)


def test_ring_attention_grad_through_tape():
    from mxtpu import autograd
    mesh = parallel.make_mesh((4,), ("sp",))
    rs = np.random.RandomState(3)
    arrs = [rs.randn(1, 2, 16, 8).astype(np.float32) for _ in range(3)]
    qn, kn, vn = [nd.array(a) for a in arrs]
    qn.attach_grad()
    with autograd.record():
        out = parallel.ring_self_attention(qn, kn, vn, mesh, axis_name="sp")
        loss = nd.sum(out)
    loss.backward()
    g = qn.grad.asnumpy()
    assert np.isfinite(g).all() and np.abs(g).sum() > 0
    # compare against single-device reference grad
    qa = jnp.asarray(arrs[0])
    ref_g = jax.grad(lambda q_: jnp.sum(attention_reference(
        q_, jnp.asarray(arrs[1]), jnp.asarray(arrs[2]))))(qa)
    np.testing.assert_allclose(g, np.asarray(ref_g), rtol=1e-4, atol=1e-5)


def test_variational_dropout_preserves_lstm_cell_state():
    from mxtpu.gluon.contrib.rnn import VariationalDropoutCell
    from mxtpu import autograd, gluon
    cell = VariationalDropoutCell(gluon.rnn.LSTMCell(8, input_size=4),
                                  drop_states=0.9)
    cell.initialize()
    x = nd.ones((2, 4))
    states = cell.begin_state(2)
    states[1]._set_data(np.full((2, 8), 5.0, np.float32))
    with autograd.record():
        out, next_states = cell(x, states)
    # cell memory (states[1]) must not be zeroed by the state mask
    c = next_states[1].asnumpy()
    assert np.isfinite(c).all()


def test_flash_chunk_lse_cotangent_vjp():
    """flash_chunk's custom vjp handles BOTH cotangents (out AND lse) — the
    path ring-attention merges differentiate through. Pallas bwd folds the
    lse cotangent into delta; checked against the reference chunk's autodiff."""
    from mxtpu.ops.attention import (_chunk_reference_lse,
                                     _flash_attention_pallas,
                                     _flash_backward_pallas)
    B, H, T, D = 1, 2, 128, 64
    rs = np.random.RandomState(11)
    q, k, v = [jnp.asarray(rs.randn(B, H, T, D).astype(np.float32))
               for _ in range(3)]
    g_o = jnp.asarray(rs.randn(B, H, T, D).astype(np.float32))
    g_lse = jnp.asarray(rs.randn(B, H, T).astype(np.float32))
    scale = 1.0 / np.sqrt(D)

    # reference vjp with both cotangents
    _, vjp = jax.vjp(lambda a, b, c: _chunk_reference_lse(a, b, c, True, scale),
                     q, k, v)
    rq, rk, rv = vjp((g_o, g_lse))

    # pallas backward with the folded lse cotangent (interpret mode)
    out, lse = _flash_attention_pallas(q, k, v, True, scale, interpret=True)
    dq, dk, dv = _flash_backward_pallas(q, k, v, out, lse, g_o, True, scale,
                                        interpret=True,
                                        lse_cot=g_lse.reshape(B, H, T))
    np.testing.assert_allclose(np.asarray(dq), np.asarray(rq), rtol=1e-3,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(rk), rtol=1e-3,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(rv), rtol=1e-3,
                               atol=1e-4)


def test_ring_attention_causal_grad_parity():
    """Causal ring (diag/below/above cond branches + lse merge) end-to-end
    gradient parity vs single-device reference, through flash_chunk's vjp."""
    mesh = parallel.make_mesh((4,), ("sp",))
    rs = np.random.RandomState(13)
    arrs = [rs.randn(1, 2, 32, 8).astype(np.float32) for _ in range(3)]
    qa, ka, va = map(jnp.asarray, arrs)

    def loss_ring(q_, k_, v_):
        return jnp.sum(parallel.ring_self_attention(q_, k_, v_, mesh,
                                                    causal=True) ** 2)

    def loss_ref(q_, k_, v_):
        return jnp.sum(attention_reference(q_, k_, v_, causal=True) ** 2)

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(qa, ka, va)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(qa, ka, va)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-5)


# ---------------------------------------------------------------------------
# more than one device: GSPMD cannot partition a Mosaic kernel (first met on
# the four-chip v5e host, PR 21), so the launch shard_maps itself over the
# mesh axes that carry batch and heads
# ---------------------------------------------------------------------------


@pytest.fixture
def engage_kernels(monkeypatch):
    """``engage(interpret)`` takes the Pallas path off-TPU: the cross-lowering
    test needs the real launch, the numeric ones interpret mode."""
    import functools
    from mxtpu.ops import attention as A

    def engage(interpret: bool):
        monkeypatch.setattr(A, "_use_pallas", lambda q, k: (
            q.shape[2] == k.shape[2] and q.shape[2] % 128 == 0))
        if interpret:
            for name in ("_flash_attention_pallas", "_flash_backward_pallas"):
                monkeypatch.setattr(A, name, functools.partial(
                    getattr(A, name), interpret=True))
    return engage


def _sq_loss(q, k, v):
    from mxtpu.ops.attention import flash_chunk
    o, lse = flash_chunk(q, k, v, True, 0.2)
    return jnp.sum(o.astype(jnp.float32) ** 2) + jnp.sum(lse)


@pytest.mark.multi_device(4)
def test_flash_lowers_for_tpu_only_inside_shard_map(engage_kernels):
    """The sandbox's view of the four-chip failure: lowering the sharded
    kernel for the TPU platform raises outside a partition scope and yields
    the two Mosaic calls on the per-device (B/4) block inside one."""
    import re
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxtpu.ops import attention as A
    engage_kernels(interpret=False)
    mesh = parallel.make_mesh((4,), ("dp",))
    sh = NamedSharding(mesh, P("dp"))
    av = jax.ShapeDtypeStruct((8, 2, 128, 64), jnp.bfloat16)

    def lower():    # a fresh jit each time: traces are cached per function
        f = jax.jit(jax.grad(lambda q, k, v: _sq_loss(q, k, v),
                             argnums=(0, 1, 2)), in_shardings=(sh, sh, sh))
        return f.trace(av, av, av).lower(lowering_platforms=("tpu",))

    with pytest.raises(NotImplementedError, match="partitioned"):
        lower()
    with A.partition_scope(mesh, P("dp")):
        text = lower().as_text()
    assert sorted(re.findall(r'kernel_name = "([^"]+)"', text)) == [
        "flash_bwd_fused", "flash_fwd"]
    assert "tensor<4x128x128xbf16>" in text     # (8/4 batch x 2 heads, T, Dp)


def test_flash_kernels_carry_their_names_in_the_tpu_lowering(engage_kernels):
    """Each ``pallas_call`` passes ``name=``: the custom call's
    ``kernel_name`` and the ``op_name`` of the HLO instruction (what the
    device trace shows) tell the forward from the backward."""
    import re
    names = ["flash_bwd_fused", "flash_fwd"]
    engage_kernels(interpret=False)
    av = jax.ShapeDtypeStruct((2, 2, 128, 64), jnp.bfloat16)
    f = jax.jit(jax.grad(lambda q, k, v: _sq_loss(q, k, v),
                         argnums=(0, 1, 2)))
    text = f.trace(av, av, av).lower(lowering_platforms=("tpu",)).as_text(
        debug_info=True)
    assert sorted(re.findall(r'kernel_name = "([^"]+)"', text)) == names
    for name in names:
        assert re.search(r'loc\("[^"]*\(%s\)[^"]*pallas_call' % name, text) \
            or re.search(r'loc\("[^"]*%s[^"]*"' % name, text), name


@pytest.mark.multi_device(4)
def test_flash_shard_maps_over_a_mesh_and_matches_the_reference(
        engage_kernels):
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxtpu.ops import attention as A
    engage_kernels(interpret=True)
    mesh = parallel.make_mesh((4,), ("dp",))
    q, k, v = map(jnp.asarray, _qkv(B=4, H=2, T=128, D=16, seed=3))
    ref_o, ref_lse = A._chunk_reference_lse(q, k, v, True, 0.2)
    # concrete arrays: their own sharding says how to split, scope or not
    for spec in (P("dp"), P()):
        sh = NamedSharding(mesh, spec)
        o, lse = A.flash_chunk(*(jax.device_put(a, sh) for a in (q, k, v)),
                               True, 0.2)
        assert o.sharding.spec == spec
        np.testing.assert_allclose(np.asarray(o), np.asarray(ref_o),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                                   rtol=1e-4, atol=1e-5)
    # traced: the scope says it; both cotangents flow through the shard_map
    sh = NamedSharding(mesh, P("dp"))
    g_ref = jax.grad(lambda *a: jnp.sum(A._chunk_reference_lse(
        *a, True, 0.2)[0] ** 2) + jnp.sum(A._chunk_reference_lse(
            *a, True, 0.2)[1]), argnums=(0, 1, 2))(q, k, v)
    with A.partition_scope(mesh, P("dp")):
        g = jax.jit(jax.grad(_sq_loss, argnums=(0, 1, 2)),
                    in_shardings=(sh, sh, sh))(q, k, v)
    for a, b in zip(g, g_ref):
        assert a.sharding.spec == P("dp")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3,
                                   atol=1e-4)


@pytest.mark.multi_device(4)
def test_data_parallel_trainer_runs_the_kernels_on_a_dp_mesh(engage_kernels):
    """The whole step — forward, flash backward, optimizer — on dp=4 with
    the kernels engaged equals the XLA-reference step it replaces."""
    import mxtpu as mx
    from mxtpu import optimizer
    from mxtpu.gluon.loss import SoftmaxCrossEntropyLoss
    from mxtpu.gluon.model_zoo import transformer_lm

    def seq_loss(logits, y):
        b, t, v = logits.shape
        return SoftmaxCrossEntropyLoss()(logits.reshape((b * t, v)),
                                         y.reshape((b * t,)))

    def losses():
        mx.rng.seed(0)
        net = transformer_lm("tiny", vocab_size=50)
        net.initialize()
        dpt = parallel.DataParallelTrainer(
            net, seq_loss, optimizer.Adam(learning_rate=1e-2),
            parallel.make_mesh((4,), ("dp",)))
        rs = np.random.RandomState(0)
        x = nd.array(rs.randint(0, 50, (8, 128)).astype(np.int32))
        y = nd.array(rs.randint(0, 50, (8, 128)).astype(np.float32))
        return [dpt.step(x, y) for _ in range(3)]

    want = losses()                 # off-TPU: the XLA reference path
    engage_kernels(interpret=True)
    np.testing.assert_allclose(losses(), want, rtol=1e-5)
