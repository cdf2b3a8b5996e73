"""Flash attention beyond plain multi-head (``ops/attention.py``): a causal
sliding window, fewer key/value heads than query heads, a value width other
than the query/key width, each against a masked float32 reference, forward
and backward, with the kernels in interpret mode; the launch GPT-2's block
makes, unchanged; the path counter."""

import hashlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxtpu import nd, profiler
from mxtpu.ops import attention as A


def _masked_reference(q, k, v, window):
    """Softmax attention with every mask and repeat written out."""
    B, H, T, D = q.shape
    group = H // k.shape[1]
    kr, vr = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, kr,
                   precision="highest") / np.sqrt(D)
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    seen = i >= j
    if window is not None:
        seen = seen & (i - j < window)
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vr, precision="highest")


def _operands(H, Hkv, D, Dv, T, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (2, H, T, D)),
            jax.random.normal(ks[1], (2, Hkv, T, D)),
            jax.random.normal(ks[2], (2, Hkv, T, Dv)),
            jax.random.normal(ks[3], (2, H, T, Dv)))


# (query heads, key/value heads, D, Dv, T, window): the window a block, less
# than a block, across blocks, one key; grouped; a wider and a narrower value
CASES = [
    (4, 2, 64, 128, 512, 128),
    (4, 4, 64, 64, 512, 200),
    (4, 2, 64, 32, 384, 100),
    (4, 2, 64, 128, 256, None),
    (2, 1, 32, 48, 256, 1),
    (2, 2, 128, 128, 384, 384),      # the window reaches every key
]


@pytest.mark.parametrize("H,Hkv,D,Dv,T,window", CASES)
def test_flash_variants_forward_and_backward(H, Hkv, D, Dv, T, window):
    q, k, v, g = _operands(H, Hkv, D, Dv, T)
    scale = 1.0 / np.sqrt(D)
    want, vjp = jax.vjp(lambda *a: _masked_reference(*a, window), q, k, v)
    out, lse = A._flash_attention_pallas(q, k, v, True, scale, 128, 128,
                                         interpret=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-5,
                               atol=2e-5)
    got = A._flash_backward_pallas(q, k, v, out, lse, g, True, scale, 128,
                                   128, interpret=True, window=window)
    for name, a, b in zip("qkv", got, vjp(g)):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=2e-5, err_msg="d" + name)
    # the XLA formulation every other backend and shape takes
    np.testing.assert_allclose(
        np.asarray(A._attention_xla(q, k, v, True, scale, window)[0]),
        np.asarray(want), rtol=2e-5, atol=2e-5)


def test_a_window_that_reaches_every_key_is_the_causal_kernel_bit_for_bit():
    q, k, v, g = _operands(2, 2, 64, 64, 256, seed=1)
    for window in (256, 300, 10 ** 6):
        a = A._flash_attention_pallas(q, k, v, True, 0.125, 128, 128,
                                      interpret=True, window=window)
        b = A._flash_attention_pallas(q, k, v, True, 0.125, 128, 128,
                                      interpret=True)
        assert all(bool(jnp.all(x == y)) for x, y in zip(a, b))
        ga = A._flash_backward_pallas(q, k, v, *a, g, True, 0.125, 128, 128,
                                      interpret=True, window=window)
        gb = A._flash_backward_pallas(q, k, v, *b, g, True, 0.125, 128, 128,
                                      interpret=True)
        assert all(bool(jnp.all(x == y)) for x, y in zip(ga, gb))


def test_window_blocks_outside_the_window_are_not_visited():
    """The third grid axis covers the key blocks a window can touch and no
    more: 2 at a window of one block, whatever T is."""
    assert A._window_blocks(512, 512) == 2
    assert A._window_blocks(512, 513) == 2
    assert A._window_blocks(512, 514) == 3
    assert A._window_blocks(128, 1) == 1
    assert A._window_blocks(256, 512) == 3


def test_flash_attention_op_takes_window_and_groups_through_nd():
    q, k, v, _ = _operands(4, 2, 16, 32, 24, seed=2)
    out = nd.contrib.flash_attention(nd.array(q), nd.array(k), nd.array(v),
                                     causal=True, window=5)
    np.testing.assert_allclose(out.asnumpy(),
                               np.asarray(_masked_reference(q, k, v, 5)),
                               rtol=2e-5, atol=2e-5)
    with pytest.raises(ValueError, match="causal"):
        A.flash_attention(q, k, v, causal=False, window=5)


def test_the_xla_fall_back_is_counted_by_kind():
    profiler.reset_kernel_path_counts()
    q, k, v, _ = _operands(2, 2, 16, 16, 16, seed=3)
    A.flash_attention(q, k, v, causal=True)
    A.flash_attention(q, k, v, causal=True, window=4)
    A.flash_attention(q, k, v, causal=True, window=16)    # plain causal
    counts = profiler.get_kernel_path_counts()
    assert counts["flash"] == {"pallas": 0, "xla": 2}
    assert counts["flash_window"] == {"pallas": 0, "xla": 1}


def _tpu_text(fn, avals):
    return jax.jit(fn).trace(*avals).lower(
        lowering_platforms=("tpu",)).as_text()


def test_window_launches_carry_names_of_their_own(monkeypatch):
    monkeypatch.setattr(A, "_use_pallas", lambda q, k: True)
    av = (jax.ShapeDtypeStruct((1, 4, 256, 64), jnp.bfloat16),
          jax.ShapeDtypeStruct((1, 2, 256, 64), jnp.bfloat16),
          jax.ShapeDtypeStruct((1, 2, 256, 128), jnp.bfloat16))

    def names(window):
        loss = lambda q, k, v: jnp.sum(A.flash_attention(
            q, k, v, causal=True, window=window).astype(jnp.float32))
        return sorted(re.findall(r'kernel_name = "([^"]+)"', _tpu_text(
            jax.grad(loss, argnums=(0, 1, 2)), av)))

    assert names(128) == ["flash_bwd_dkv_window", "flash_bwd_dq_window",
                          "flash_fwd_window"]
    assert names(None) == ["flash_bwd_fused", "flash_fwd"]


# What GPT-2's block launches (equal heads, equal widths, no window): the
# traced program, kernels' bodies, grids and block maps included, by the hash
# of its text with source positions taken out. Taken on the tree before the
# window, the grouped heads and the value width came in (PR 25); a change to
# the launch or to a kernel that these cells run moves it. PR 29 replaced the
# backward (one launch, every score tile once): the two "grad" hashes are of
# that tree, the two "fwd" hashes still PR 25's. PR 47 made the forward ask
# for its VMEM as the backward does: ``flash_fwd``'s compiler parameters
# (``vmem_limit_bytes`` 16777216 at these shapes, what it got unasked) are
# all that moved in the four texts; tiles, grids, maps and bodies are PR 29's.
GPT2_LAUNCH = {
    ((2, 16, 1024, 64), "fwd"): "a53df5469cd65a48",
    ((2, 16, 1024, 64), "grad"): "cc644f609d0c0ce3",
    ((1, 16, 2048, 128), "fwd"): "9696e2ce3ba7bc27",
    ((1, 16, 2048, 128), "grad"): "03a92ccdddab3162",
}


@pytest.mark.parametrize("shape,which", sorted(GPT2_LAUNCH))
def test_the_gpt2_launch_traces_to_unchanged_text(monkeypatch, shape, which):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def f(q, k, v):
        return jnp.sum(A.flash_attention(q, k, v, causal=True).astype(
            jnp.float32))

    fn = f if which == "fwd" else jax.grad(f, argnums=(0, 1, 2))
    text = str(jax.make_jaxpr(fn)(
        *[jax.ShapeDtypeStruct(shape, jnp.bfloat16)] * 3))
    text = re.sub(r" at \S+:\d+", "", text)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == GPT2_LAUNCH[shape, which]
