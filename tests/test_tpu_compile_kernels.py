"""The kernels of the hybrid family compiled at the benchmark's widths for a
DESCRIBED v5e chip (none is attached: the TPU's compiler is installed here
and raises what the chip's would: a slice not aligned to the tiling, more
VMEM than a kernel may use). Nothing runs, so these say nothing of results or
times. One file, a fixture that skips where no topology can be described: see
the ``on-chip-measurement`` guide, section 2."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

T, CHANNELS, STATES = 8192, 5120, 16


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def quiet_cache():
    """A compile for a described device is written to the persistent cache
    and cannot be read back without a chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)


def _avals(one_chip, *shapes):
    return [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]


def test_scan_kernels_compile_at_the_cells_size(one_chip, quiet_cache):
    from mxtpu.ops import ssm
    bf, f32 = jnp.bfloat16, jnp.float32
    avals = _avals(one_chip, ((1, T, CHANNELS), bf), ((1, T, CHANNELS), bf),
                   ((CHANNELS, STATES), f32), ((1, T, STATES), bf),
                   ((1, T, STATES), bf), ((CHANNELS,), f32))

    def loss(*a):
        return jnp.sum(ssm._scan_pallas(*a).astype(f32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4, 5))).lower(
        *avals).compile().as_text()
    assert "ssm_scan_fwd" in text and "ssm_scan_bwd" in text


@pytest.mark.parametrize("window", [512, None])
def test_flash_kernels_compile_at_the_cells_size(one_chip, quiet_cache,
                                                 window):
    """20 query heads of 64 on 10 key heads, the value 128 wide, T = 8192:
    one of the two launches of a differential-attention layer."""
    from mxtpu.ops import attention as A
    bf = jnp.bfloat16
    q, k, v = _avals(one_chip, ((1, 20, T, 64), bf), ((1, 10, T, 64), bf),
                     ((1, 10, T, 128), bf))

    def both(q, k, v, g):
        out, lse = A._flash_attention_pallas(q, k, v, True, 0.125,
                                             window=window)
        return A._flash_backward_pallas(q, k, v, out, lse, g, True, 0.125,
                                        window=window)

    g, = _avals(one_chip, ((1, 20, T, 128), bf))
    text = jax.jit(both).lower(q, k, v, g).compile().as_text()
    for name in (("flash_fwd_window", "flash_bwd_dq_window",
                  "flash_bwd_dkv_window") if window
                 else ("flash_fwd", "flash_bwd_fused")):
        assert name in text


@pytest.mark.parametrize("window", [128, None])
def test_flash_kernels_compile_at_the_sparse_cells_size(one_chip, quiet_cache,
                                                        window):
    """8 query heads of 128 on 1 key/value head, T = 4096, window 128 or
    none: an attention layer of ``kexaone_train_t4096``."""
    from mxtpu.ops import attention as A
    bf = jnp.bfloat16
    q, k, v, g = _avals(one_chip, ((1, 8, 4096, 128), bf),
                        ((1, 1, 4096, 128), bf), ((1, 1, 4096, 128), bf),
                        ((1, 8, 4096, 128), bf))
    scale = 128 ** -0.5

    def both(q, k, v, g):
        out, lse = A._flash_attention_pallas(q, k, v, True, scale,
                                             window=window)
        return A._flash_backward_pallas(q, k, v, out, lse, g, True, scale,
                                        window=window)

    text = jax.jit(both).lower(q, k, v, g).compile().as_text()
    for name in (("flash_fwd_window", "flash_bwd_dq_window",
                  "flash_bwd_dkv_window") if window
                 else ("flash_fwd", "flash_bwd_fused")):
        assert name in text, name


GROUPED = {   # cell: (buffer rows, groups, [(K, N) of gate/up and of down])
    "kexaone": (8192, 8, [(6144, 4096), (2048, 6144)]),
    "lfm2moe": (16384, 32, [(2048, 3584), (1792, 2048)]),
    "joyai": (16384, 32, [(2048, 1536), (768, 2048)]),
    "lingflash": (4096, 16, [(2560, 1536), (768, 2560)]),
}


@pytest.mark.parametrize("cell,M,G,K,N", [
    (cell, M, G, K, N) for cell, (M, G, widths) in GROUPED.items()
    for K, N in widths])
def test_grouped_matmul_kernels_compile_at_the_sparse_cells_sizes(
        one_chip, quiet_cache, cell, M, G, K, N):
    """The grouped products of every sparse cell at its widths: the row
    buffer of a pass over the experts one chip holds, gate/up (K -> 2 ffn)
    and down (ffn -> K), forward, dx and the per-group dw. The forward and
    dx hold the whole of K beside a column tile that the VMEM rule sizes
    (``_gmm_tiles``): what it asks for is what Mosaic is given here."""
    from mxtpu.ops import grouped_matmul as G_
    bf = jnp.bfloat16
    x, w, gs, dy = _avals(one_chip, ((M, K), bf), ((G, K, N), bf),
                          ((G,), jnp.int32), ((M, N), bf))

    def all_three(x, w, gs, dy):
        return (G_._gmm_pallas(x, w, gs), G_._gmm_pallas(dy, w, gs, True),
                G_._tgmm_pallas(x, dy, gs))

    text = jax.jit(all_three).lower(x, w, gs, dy).compile().as_text()
    assert "moe_gmm" in text and "moe_tgmm" in text
    for k, n in ((K, N), (N, K)):
        tm, tk, tn, need = G_._gmm_tiles(M, k, n, 2)
        assert tk == k and n % tn == 0 and need <= G_._VMEM_BUDGET


def test_flash_kernels_compile_at_the_conv_cells_size(one_chip, quiet_cache):
    """32 query heads of 64 on 8 key/value heads, T = 4096, causal over
    everything: the attention layer of ``lfm2moe_train_t4096``."""
    from mxtpu.ops import attention as A
    bf = jnp.bfloat16
    q, k, v, g = _avals(one_chip, ((1, 32, 4096, 64), bf),
                        ((1, 8, 4096, 64), bf), ((1, 8, 4096, 64), bf),
                        ((1, 32, 4096, 64), bf))

    def both(q, k, v, g):
        out, lse = A._flash_attention_pallas(q, k, v, True, 0.125)
        return A._flash_backward_pallas(q, k, v, out, lse, g, True, 0.125)

    text = jax.jit(both).lower(q, k, v, g).compile().as_text()
    assert "flash_fwd" in text and "flash_bwd_fused" in text


def test_flash_kernels_compile_at_the_latent_cells_size(one_chip, quiet_cache):
    """32 heads, q/k 192 wide (256 lanes) on a value of 128, T = 4096: a
    latent-attention layer of ``joyai_train_t4096`` / ``lingflash_train_t4096``
    on the 512 x 512 tiles its VMEM bytes allow (a rule that read the padded
    key width gave it 256 until PR 47)."""
    from mxtpu import profiler
    from mxtpu.ops import attention as A
    bf = jnp.bfloat16
    q, k, v, g = _avals(one_chip, ((1, 32, 4096, 192), bf),
                        ((1, 32, 4096, 192), bf), ((1, 32, 4096, 128), bf),
                        ((1, 32, 4096, 128), bf))
    scale = 192 ** -0.5

    def both(q, k, v, g):
        out, lse = A._flash_attention_pallas(q, k, v, True, scale)
        return A._flash_backward_pallas(q, k, v, out, lse, g, True, scale)

    profiler.reset_launch_stats("flash")
    text = jax.jit(both).lower(q, k, v, g).compile().as_text()
    assert "flash_fwd" in text and "flash_bwd_fused" in text
    row = profiler.get_launch_stats("flash")
    assert row["block_q"] == row["block_k"] == 512
    assert (row["dp"], row["dvp"]) == (256, 128)


def test_retention_kernels_compile_at_the_retention_cells_size(one_chip,
                                                               quiet_cache):
    """40 query heads of 128 on 8 key/value heads, T = 8192: a mixer of
    ``brumby_train_t8192``, forward and (under grad) backward, the state of
    a key/value head (65 tiles of 128 x 128 float32) in VMEM beside its
    bf16 copy and the kept chunk start's two buffers."""
    from mxtpu.ops import retention as R
    bf, f32 = jnp.bfloat16, jnp.float32
    avals = _avals(one_chip, ((1, T, 40 * 128), bf), ((1, T, 8 * 128), bf),
                   ((1, T, 8 * 128), bf), ((1, T, 8), f32))

    def loss(*a):
        return jnp.sum(R._retention_pallas(*a, R.EPS).astype(f32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        *avals).compile()
    text = compiled.as_text()
    assert "retention_fwd" in text and "retention_bwd" in text
    # linear in T: the chunk starts (0.55 GB) are the largest thing kept,
    # and nothing the size of T x T (40 heads: 5.4 GB) or T x 8256 exists
    assert compiled.memory_analysis().temp_size_in_bytes < 1.2e9


def test_kda_kernels_compile_at_the_ling_cells_size(one_chip, quiet_cache):
    """32 heads of 128, T = 4096: a ``kda`` mixer of
    ``lingflash_train_t4096`` on RAW operands (q and k un-normed, the gate's
    logits, ``A_log``, ``dt_bias``), forward and (under grad) backward: the
    norms, the gate and the chunk's cumulative decays, a chunk's 128 x 128
    matrices, its eight sub-chunks' decayed keys and the triangular system's
    float32 products in VMEM beside the head's state, and in the backward
    the transposes of all of them."""
    import re
    from mxtpu.ops import kda as K
    bf, f32 = jnp.bfloat16, jnp.float32
    wide = (1, 4096, 32 * 128)
    avals = _avals(one_chip, (wide, bf), (wide, bf), (wide, bf), (wide, f32),
                   ((1, 4096, 32), f32), ((32,), f32), ((32 * 128,), f32))

    def loss(q, k, v, z, beta, a_log, dt_bias):
        return jnp.sum(K._kda_pallas(
            q, k, v, z, beta, *K._gate_rows(a_log, dt_bias), -5.0,
            1e-6).astype(f32))

    compiled = jax.jit(jax.grad(loss, argnums=tuple(range(7)))).lower(
        *avals).compile()
    text = compiled.as_text()
    assert "kda_fwd" in text and "kda_bwd" in text
    # linear in T, and nothing of the operands' size but the operands: the
    # chunk starts (67.1 MB, float32) and the chunks' inverses (33.6 MB,
    # bfloat16: the backward reads them and solves nothing) are ALL that is
    # kept (read: 100.8 MB)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert 0.1e9 < temp < 0.11e9, temp
    # ``kda_fwd``'s third result is ``kda_bwd``'s ninth of ten operands
    def call(name):
        return next(line for line in text.splitlines()
                    if "custom-call(" in line and f"({name})" in line)

    results = call("kda_fwd").split(" custom-call(")[0]
    assert results.count("bf16[1,32,32,128,128]") == 1
    operands = re.search(r"operand_layout_constraints=\{(.*?)\}, frontend",
                         call("kda_bwd")).group(1).split("}, ")
    assert len(operands) == 10 \
        and operands[8].startswith("bf16[1,32,32,128,128]")
    # no cumulative sum is XLA's, and XLA makes no float32 array of the
    # operands' size at all: the one there is is the kernel's ``dz``
    assert "reduce-window" not in text and "cumsum" not in text
    made = [line for line in text.splitlines()
            if re.search(r"= f32\[1,4096,4096\]", line)
            and not re.search(r"custom-call|get-tuple-element|parameter\(",
                              line)]
    assert not made, made


def test_flash_kernels_compile_at_the_jamba_cells_size(one_chip, quiet_cache):
    """20 query heads of 128 on ONE key/value head, T = 8192, causal over
    everything, no positions: the attention layer of ``jamba2_train_t8192``
    (``kexaone_train_t4096`` runs 8-on-1 at 4096)."""
    from mxtpu import profiler
    from mxtpu.ops import attention as A
    bf = jnp.bfloat16
    q, k, v, g = _avals(one_chip, ((1, 20, T, 128), bf), ((1, 1, T, 128), bf),
                        ((1, 1, T, 128), bf), ((1, 20, T, 128), bf))
    scale = 128 ** -0.5

    def both(q, k, v, g):
        out, lse = A._flash_attention_pallas(q, k, v, True, scale)
        return A._flash_backward_pallas(q, k, v, out, lse, g, True, scale)

    profiler.reset_launch_stats("flash")
    text = jax.jit(both).lower(q, k, v, g).compile().as_text()
    assert "flash_fwd" in text and "flash_bwd_fused" in text
    row = profiler.get_launch_stats("flash")
    assert row["block_q"] == row["block_k"] == 512
    assert (row["dp"], row["dvp"]) == (128, 128)


def test_the_jamba_stack_launches_its_kernels_at_the_cells_shapes(
        one_chip, monkeypatch):
    """``jamba2_train_t8192``'s 14 layers at its widths (13 ``mamba`` layers
    of 8192 x 5120 x 16 states under the inner norms around one 20-on-1
    attention layer of 128, the 8192-wide SwiGLUs, the whole tied table),
    blocks 0-12 recomputed, LOWERED for the described chip (parameters are
    shapes; nothing is compiled or run): the launches by name, counted in
    the lowered text. Each recomputed block's forward kernel is there a
    second time: 13 + 12 scans forward, 13 backward, the attention layer's
    flash forward twice and its fused backward once; and the scan's launch
    row says what one launch keeps for its backward."""
    import re
    from mxtpu import autograd, nd, profiler
    from mxtpu.gluon.model_zoo.hybrid_decoder import HybridDecoderLM
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    kinds = ["mamba"] * 7 + ["attn_full"] + ["mamba"] * 6
    net = HybridDecoderLM(
        65536, kinds, units=2560, ffn_units=8192, num_heads=20,
        num_kv_heads=1, head_dim=128, d_inner=CHANNELS, d_state=STATES,
        d_conv=4, dt_rank=160, layer_norm_eps=1e-6, attention="gqa",
        norm="rms", tie_head=True, float32_logits=True,
        mamba_inner_norm=True, remat=True)
    net.cast("bfloat16")
    params = list(net.collect_params().values())
    assert sum(int(np.prod(p.shape)) for p in params) == 1_598_556_096
    for p in params:                    # placeholders: the step is only traced
        p._data = nd.NDArray(jnp.zeros((1,), jnp.bfloat16))

    def loss(values, tokens):
        for p, v in zip(params, values):
            p._data._data = v
        with autograd.pause(train_mode=True):
            return jnp.sum(net(nd.NDArray(tokens)).data)

    avals = _avals(one_chip, *((p.shape, jnp.bfloat16) for p in params))
    tokens, = _avals(one_chip, ((1, T), jnp.int32))
    for kind in ("ssm_scan", "flash"):
        profiler.reset_launch_stats(kind)
    profiler.reset_kernel_path_counts()
    profiler.reset_remat_stats()
    text = jax.jit(jax.grad(loss)).lower(avals, tokens).as_text()
    names = re.findall(r'kernel_name = "([^"]+)"', text)
    assert {k: names.count(k) for k in set(names)} == {
        "ssm_scan_fwd": 25, "ssm_scan_bwd": 13, "flash_fwd": 2,
        "flash_bwd_fused": 1}
    paths = profiler.get_kernel_path_counts()
    assert paths["ssm_scan"] == {"pallas": 13, "xla": 0}
    assert paths["flash"]["xla"] == 0 and paths["flash"]["pallas"] >= 1
    assert profiler.get_remat_stats() == {
        "blocks": 14, "recomputed": 13,
        "kinds": {"mamba": 12, "attn_full": 1}}
    # 128 chunks of 64 rows: 128 x 16 x 5120 float32 chunk starts a launch
    assert profiler.get_launch_stats("ssm_scan") == {
        "launches": 13, "t_pad": T, "channels": CHANNELS, "states": STATES,
        "chunk": 64, "block_d": 1024, "chunk_start_bytes": 41_943_040}
    row = profiler.get_launch_stats("flash")
    assert row["block_q"] == row["block_k"] == 512
