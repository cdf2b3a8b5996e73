"""Decoder-hybrid-decoder language models (SambaY; Phi-4-mini-flash-reasoning
is the published instance): Mamba-1 state-space layers, differential
attention over a window, over everything and across layers, and gated
memory units, in one stack with no positional encoding of any kind.

A model is a list of layer kinds and the widths; nothing here is a preset.
Every layer is ``h = x + Mixer(LN(x)); out = h + MLP(LN'(h))`` with a SwiGLU
MLP (``W_down (up * silu(gate))``, no biases), then a LayerNorm and the tied
head ``logits = h E^T``. The mixers, by kind:

``mamba``
    ``[u, z] = W_in x``; ``u = silu(conv1d_causal(u) + b_c)``; ``[dt_r, B, C]
    = W_x u``; ``dt = softplus(W_dt dt_r + b_dt)``; ``A = -exp(A_log)``
    in float32; ``y = selective_scan(u, dt, A, B, C, D)`` (``ops/ssm.py``); output
    ``W_out (y * silu(z))``. Its ``y``, before the gate, is handed on as the
    stack's memory ``m``: a later ``gmu`` reads the newest one.
``attn_window`` / ``attn_full``
    ``[q, k, v] = W_qkv x + b``; differential attention
    (``ops.attention.diff_attention``: heads pair up, two softmax maps over
    a value twice as wide, their difference normalised), ``W_o . + b_o``.
    ``attn_window`` sees the ``window`` newest keys, ``attn_full`` all of
    them; ``attn_full`` hands its ``k`` and ``v`` on.
``attn_cross``
    only ``q = W_q x + b`` is made here; keys and values are those the
    newest ``attn_full`` handed on; own lambdas, norm and ``W_o``.
``gmu``
    ``W_out (m * silu(W_in x))``: no scan, no convolution.

Keys/values and the scan's output are made ONCE and read by every later
layer that wants them: gradients flow back into the one producer from all
its consumers. That is the training path, and the only one: there is no
decode cache for any of these kinds yet (``generate`` and the serving steps
raise), because a cache here has to hold a window's keys, one layer's full
keys for all cross layers, and scan and convolution states side by side.
"""

from __future__ import annotations

import math

import jax

from ... import initializer
from ... import ndarray as nd
from ..block import HybridBlock
from ..nn.basic_layers import Dense, Embedding, LayerNorm

__all__ = ["HybridDecoderBlock", "HybridDecoderLM", "KINDS"]

KINDS = ("mamba", "attn_window", "attn_full", "attn_cross", "gmu")


class _ALog(initializer.Initializer):
    """``A_log[c, n] = log(n + 1)``: Mamba's S4D-real initialisation."""

    def init_array(self, name, arr):
        import jax.numpy as jnp
        row = jnp.log(jnp.arange(1, arr.shape[1] + 1, dtype=jnp.float32))
        arr._set_data(jnp.broadcast_to(row, arr.shape).astype(arr.dtype))


class _DtBias(initializer.Initializer):
    """The inverse softplus of steps drawn log-uniform in ``[lo, hi]``."""

    def __init__(self, lo: float = 1e-3, hi: float = 1e-1):
        super().__init__(lo=lo, hi=hi)
        self.lo, self.hi = lo, hi

    def init_array(self, name, arr):
        import jax.numpy as jnp
        from ... import rng
        u = jax.random.uniform(rng.next_key(), arr.shape, jnp.float32)
        dt = jnp.exp(u * (math.log(self.hi) - math.log(self.lo))
                     + math.log(self.lo))
        arr._set_data((dt + jnp.log(-jnp.expm1(-dt))).astype(arr.dtype))


def _silu(x):
    return nd.Activation(x, act_type="silu")


def _split(x, sizes):
    """``x`` cut along its last axis into pieces of ``sizes``."""
    out, at = [], 0
    for n in sizes:
        out.append(nd.slice_axis(x, axis=-1, begin=at, end=at + n))
        at += n
    return out


class SwiGLU(HybridBlock):
    """``W_down (up * silu(gate))`` with ``[gate, up] = W_gu x``, no biases."""

    def __init__(self, units: int, ffn_units: int, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._ffn = ffn_units
        with self.name_scope():
            self.gate_up = Dense(2 * ffn_units, use_bias=False, flatten=False,
                                 in_units=units)
            self.down = Dense(units, use_bias=False, flatten=False,
                              in_units=ffn_units)

    def forward(self, x):
        gate, up = _split(self.gate_up(x), (self._ffn, self._ffn))
        return self.down(up * _silu(gate))


class Mamba(HybridBlock):
    """Mamba-1 mixer; ``forward`` returns ``(output, y before the gate)``."""

    def __init__(self, units: int, d_inner: int, d_state: int, d_conv: int,
                 dt_rank: int, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._inner, self._state, self._rank = d_inner, d_state, dt_rank
        with self.name_scope():
            self.in_proj = Dense(2 * d_inner, use_bias=False, flatten=False,
                                 in_units=units)
            self.conv_weight = self.params.get(
                "conv_weight", shape=(d_inner, d_conv), init="normal")
            self.conv_bias = self.params.get(
                "conv_bias", shape=(d_inner,), init="zeros")
            self.x_proj = Dense(dt_rank + 2 * d_state, use_bias=False,
                                flatten=False, in_units=d_inner)
            self.dt_proj = Dense(d_inner, flatten=False, in_units=dt_rank,
                                 bias_initializer=_DtBias())
            self.A_log = self.params.get(
                "A_log", shape=(d_inner, d_state), init=_ALog())
            self.D = self.params.get("D", shape=(d_inner,), init="ones")
            self.out_proj = Dense(units, use_bias=False, flatten=False,
                                  in_units=d_inner)

    def forward(self, x):
        u, z = _split(self.in_proj(x), (self._inner, self._inner))
        u = _silu(nd.contrib.causal_conv1d(u, self.conv_weight.data(),
                                           self.conv_bias.data()))
        dt_r, B, C = _split(self.x_proj(u),
                            (self._rank, self._state, self._state))
        dt = nd.Activation(self.dt_proj(dt_r), act_type="softrelu")
        y = nd.contrib.selective_scan(u, dt, self.A_log.data(), B, C,
                                      self.D.data(), log_A=True)
        return self.out_proj(y * _silu(z)), y


class DiffAttention(HybridBlock):
    """Differential attention; ``cross=True`` makes queries only and reads
    the keys and values it is handed. ``forward`` returns ``(output, (k,
    v))``, the keys and values it used, each ``(B, T, kv_heads, head_dim)``."""

    def __init__(self, units: int, num_heads: int, num_kv_heads: int,
                 head_dim: int, layer_index: int, window=None,
                 cross: bool = False, norm_eps: float = 1e-5, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        if num_heads % 2 or num_kv_heads % 2 or num_heads % num_kv_heads:
            raise ValueError(
                f"differential attention pairs heads up: {num_heads} query "
                f"and {num_kv_heads} key/value heads must both be even and "
                f"the first a multiple of the second")
        self._heads, self._kv_heads, self._dim = num_heads, num_kv_heads, \
            head_dim
        self._window, self._cross, self._eps = window, cross, norm_eps
        self._lambda_init = 0.8 - 0.6 * math.exp(-0.3 * layer_index)
        kv = 0 if cross else 2 * num_kv_heads * head_dim
        with self.name_scope():
            self.qkv = Dense(num_heads * head_dim + kv, flatten=False,
                             in_units=units)
            self.out_proj = Dense(units, flatten=False,
                                  in_units=num_heads * head_dim)
            for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
                setattr(self, name, self.params.get(
                    name, shape=(head_dim,), init=initializer.Normal(0.1)))
            self.subln = self.params.get(
                "subln", shape=(2 * head_dim,), init="ones")

    def forward(self, x, kv=None):
        B, T, _ = x.shape
        H, Hkv, D = self._heads, self._kv_heads, self._dim
        if self._cross:
            if kv is None:
                raise ValueError("attn_cross needs the keys and values of an "
                                 "earlier attn_full layer")
            q, (k, v) = self.qkv(x), kv
        else:
            q, k, v = _split(self.qkv(x), (H * D, Hkv * D, Hkv * D))
            k, v = k.reshape((B, T, Hkv, D)), v.reshape((B, T, Hkv, D))
        out = nd.contrib.diff_attention(
            q.reshape((B, T, H, D)), k, v, self.lambda_q1.data(),
            self.lambda_k1.data(), self.lambda_q2.data(),
            self.lambda_k2.data(), self.subln.data(),
            lambda_init=self._lambda_init, window=self._window,
            eps=self._eps)
        return self.out_proj(out), (k, v)


class GatedMemoryUnit(HybridBlock):
    """``W_out (m * silu(W_in x))``: the memory ``m`` gated by this layer."""

    def __init__(self, units: int, d_inner: int, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.in_proj = Dense(d_inner, use_bias=False, flatten=False,
                                 in_units=units)
            self.out_proj = Dense(units, use_bias=False, flatten=False,
                                  in_units=d_inner)

    def forward(self, x, memory):
        if memory is None:
            raise ValueError("gmu needs the memory of an earlier mamba layer")
        return self.out_proj(memory * _silu(self.in_proj(x)))


class HybridDecoderBlock(HybridBlock):
    """One layer of ``kind``; its mixer is the child named by the kind, so a
    device trace reads ``block3/attn_window/...``. ``shared`` is the stack's
    hand-over: ``{"memory": y of the newest mamba, "kv": (k, v) of the
    newest attn_full}``."""

    def __init__(self, kind: str, layer_index: int, units: int,
                 ffn_units: int, num_heads: int, num_kv_heads: int,
                 head_dim: int, window: int, d_inner: int, d_state: int,
                 d_conv: int, dt_rank: int, eps: float, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        if kind not in KINDS:
            raise ValueError(f"unknown layer kind {kind!r}; one of {KINDS}")
        self.kind = kind
        with self.name_scope():
            self.ln1 = LayerNorm(epsilon=eps, in_channels=units)
            if kind == "mamba":
                mixer = Mamba(units, d_inner, d_state, d_conv, dt_rank)
            elif kind == "gmu":
                mixer = GatedMemoryUnit(units, d_inner)
            else:
                mixer = DiffAttention(
                    units, num_heads, num_kv_heads, head_dim, layer_index,
                    window=window if kind == "attn_window" else None,
                    cross=kind == "attn_cross", norm_eps=eps)
            setattr(self, kind, mixer)
            self.ln2 = LayerNorm(epsilon=eps, in_channels=units)
            self.mlp = SwiGLU(units, ffn_units)

    def forward(self, x, shared):
        mixer, h = getattr(self, self.kind), self.ln1(x)
        if self.kind == "mamba":
            mixed, shared["memory"] = mixer(h)
        elif self.kind == "gmu":
            mixed = mixer(h, shared.get("memory"))
        elif self.kind == "attn_cross":
            mixed, _ = mixer(h, shared.get("kv"))
        else:
            mixed, kv = mixer(h)
            if self.kind == "attn_full":
                shared["kv"] = kv
        h = x + mixed
        return h + self.mlp(self.ln2(h))


class HybridDecoderLM(HybridBlock):
    """Decoder LM over token ids from a list of layer kinds (``KINDS``).

    Input ``(B, T)`` int tokens, output ``(B, T, vocab)`` logits; no position
    table, so ``T`` is bounded by memory alone. Trains through
    ``DataParallelTrainer`` like ``TransformerLM``. Multiples of 128 in ``T``
    engage the flash kernels and ``d_inner % 128 == 0`` the scan kernels on
    the TPU (``profiler.get_kernel_path_counts()`` says which ran).

    ``d_inner`` is the state-space layers' width (Mamba's ``expand *
    units``), shared by ``mamba`` and ``gmu`` since the one gates the
    other's output; ``dt_rank`` defaults to ``ceil(units / 16)``.
    """

    def __init__(self, vocab_size: int, layer_kinds, units: int,
                 ffn_units: int, num_heads: int, num_kv_heads: int,
                 head_dim: int = 0, window: int = 512, d_inner: int = 0,
                 d_state: int = 16, d_conv: int = 4, dt_rank: int = 0,
                 layer_norm_eps: float = 1e-5, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._vocab, self._units = vocab_size, units
        self.layer_kinds = tuple(layer_kinds)
        with self.name_scope():
            self.embedding = Embedding(vocab_size, units,
                                       weight_initializer="normal")
            self.blocks = []
            for i, kind in enumerate(self.layer_kinds):
                blk = HybridDecoderBlock(
                    kind, i, units, ffn_units, num_heads, num_kv_heads,
                    head_dim or units // num_heads, window,
                    d_inner or 2 * units, d_state, d_conv,
                    dt_rank or -(-units // 16), layer_norm_eps)
                setattr(self, f"block{i}", blk)   # registers child + params
                self.blocks.append(blk)
            self.ln_f = LayerNorm(epsilon=layer_norm_eps, in_channels=units)

    def forward(self, tokens):
        B, T = tokens.shape
        h = self.embedding(tokens)
        shared = {}
        for blk in self.blocks:
            h = blk(h, shared)
        h = self.ln_f(h)
        with jax.named_scope("head"):
            w = self.embedding.weight.data()
            flat = nd.reshape(h, (B * T, self._units))
            return nd.reshape(nd.dot(flat, w, transpose_b=True),
                              (B, T, self._vocab))

    def cast(self, dtype):
        """Every parameter to ``dtype`` but the lambda vectors, which stay
        float32 as the published implementation keeps them: at their size
        (N(0, 0.1)) an Adam step of 3e-4 is under one bfloat16 step, so in
        bfloat16 rounding alone would decide whether they ever move."""
        for name, p in self.collect_params().items():
            if "lambda_" not in name:
                p.cast(dtype)
        return self

    def _no_decode(self, what: str):
        raise NotImplementedError(
            f"HybridDecoderLM.{what}: this family trains only. Decoding "
            f"needs a cache that holds, side by side, a window of keys for "
            f"attn_window layers, one attn_full layer's keys for every "
            f"attn_cross layer, and scan and convolution states for mamba "
            f"layers; the engine has one cache geometry (ROADMAP D1/D2, "
            f"M3, M6). Layer kinds: {self.layer_kinds}")

    def generate(self, *args, **kwargs):
        self._no_decode("generate")

    def serving_step(self, *args, **kwargs):
        self._no_decode("serving_step")

    def serving_verify_step(self, *args, **kwargs):
        self._no_decode("serving_verify_step")

    def _gen_params(self):
        self._no_decode("_gen_params (the serving engine's first call)")
