"""Decoder language models built from a list of layer kinds: a mixer kind, an
MLP kind and a norm for each layer, and the widths; nothing here is a preset.
Seven published families are instances (``docs/hybrid_decoder.md`` has each
one's spec): SambaY / Phi-4-mini-flash, K-EXAONE, LFM2's ``lfm2_moe``,
Brumby-14B, Ling-3.0's ``bailing_hybrid``, JoyAI-LLM-Flash's (DeepSeek-V3's
block with its multi-token-prediction block, ``MultiTokenPrediction``) and
Jamba's (``mamba`` layers under ``mamba_inner_norm`` around ``attn_full``
layers without positions).

Every layer is ``h = x + Mixer(N(x)); out = h + MLP(N'(h))``, or with
``norm_position="post"`` ``h = x + N(Mixer(x)); out = h + N'(MLP(h))``. ``N``
is a LayerNorm or an RMSNorm; ``MLP`` is a SwiGLU (``W_down (up *
silu(gate))``, no biases) or, where ``mlp_kinds[i] == "moe"``, the held
experts of a sparse expert layer with its shared expert
(``parallel.moe.SparseExperts``: child ``moe``, scopes ``block<i>/moe/route|
dispatch|experts|combine|shared|balance``). After the last layer the norm
and the head: the token table again (``logits = h E^T``), or a matrix of its
own (``tie_head=False``, float32 logits).

A kind is one row of ``MIXERS`` and one class, whose docstring has the
kind's equations:

=============== ===========================================================
``mamba``       ``Mamba``
``attn_window`` ``DiffAttention``, or with ``attention="gqa"``
``attn_full``   ``GroupedQueryAttention`` under the same child names
``attn_cross``  ``DiffAttention(cross=True)``
``gmu``         ``GatedMemoryUnit``
``conv``        ``ShortConv``
``retention``   ``PowerRetention``
``kda``         ``KimiDeltaAttention``
``mla``         ``LatentAttention``
=============== ===========================================================

The hand-over (``Mixer``): keys/values and the scan's output are made ONCE
and read by every later layer that wants them, so gradients flow back into
the one producer from all its consumers. What a layer hands on follows from
the STACK: ``HybridDecoderLM`` tells each mixer which of the keys it could
write a later layer reads before another layer writes them again (``gmu``
reads ``memory``, ``attn_cross`` reads ``kv``), and the mixer puts only
those into the dict. A layer that hands nothing on and reads nothing may be
recomputed (``remat=True``). That is the training path, and the only one:
there is no decode cache for any of these kinds yet (``generate``
and the serving steps raise), because a cache here has to hold side by side
what each row of ``MIXERS`` says a layer of its kind would keep.
"""

from __future__ import annotations

import math
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from ... import autograd, initializer
from ... import ndarray as nd
from ...observability import metrics
from ...ops import registry
from ...ops.attention import flash_chunk
from ...ops.kda import kda as _kda
from ...ops.nn import rms_norm
from ...ops.retention import power_retention
from ...ops.ssm import causal_conv1d
from ...parallel.moe import SparseExperts
from ..block import HybridBlock
from ..nn.basic_layers import (Dense, Embedding, LayerNorm, RMSNorm,
                                SwiGLU)

__all__ = ["HybridDecoderBlock", "HybridDecoderLM", "KINDS", "MLP_KINDS",
           "MIXERS", "MLPS", "Mixer", "MultiTokenPrediction"]


class Mixer(HybridBlock):
    """What the stack asks of a mixer. ``forward(x, shared)`` returns the
    mixed rows ``(B, T, units)`` alone; ``shared`` is the stack's hand-over,
    one dict a forward, and the mixer itself takes from it the keys
    ``reads`` names and puts into it those ``writes`` names. ``writes`` as
    the class or ``__init__`` sets it is what the mixer CAN hand on, and
    what one built alone does; in a stack ``HybridDecoderLM`` cuts it to
    the keys a later layer reads (``hand_on``). The classmethod
    ``from_spec(z, kind, layer_index)`` builds one for a layer of ``kind``
    from the stack's widths ``z``, so a kind's widths are listed with it."""

    reads: tuple = ()
    writes: tuple = ()

    def hand_on(self, wanted) -> None:
        """Keep of ``writes`` the keys in ``wanted``: those a later layer of
        the stack reads before another layer writes them."""
        self.writes = tuple(k for k in self.writes if k in wanted)


class _ALog(initializer.Initializer):
    """``A_log[c, n] = log(n + 1)``: Mamba's S4D-real initialisation."""

    def init_array(self, name, arr):
        row = jnp.log(jnp.arange(1, arr.shape[1] + 1, dtype=jnp.float32))
        arr._set_data(jnp.broadcast_to(row, arr.shape).astype(arr.dtype))


class _DtBias(initializer.Initializer):
    """The inverse softplus of steps drawn log-uniform in ``[lo, hi]``."""

    def __init__(self, lo: float = 1e-3, hi: float = 1e-1):
        super().__init__(lo=lo, hi=hi)
        self.lo, self.hi = lo, hi

    def init_array(self, name, arr):
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


class Mamba(Mixer):
    """Mamba-1 mixer (kind ``mamba``). ``[u, z] = W_in x``; ``u =
    silu(conv1d_causal(u) + b_c)``; ``[dt_r, B, C] = W_x u``; with
    ``inner_norm`` (the Jamba family's) ``dt_r = RMSNorm(dt_r)``, ``B =
    RMSNorm(B)``, ``C = RMSNorm(C)``, each with a gain of its own and
    ``norm_eps`` (children ``dt_norm``, ``b_norm``, ``c_norm``; scope
    ``inner_norm``); ``dt = softplus(W_dt dt_r + b_dt)``; ``A =
    -exp(A_log)`` in float32; ``y = selective_scan(u, dt, A, B, C, D)``
    (``ops/ssm.py``); output ``W_out (y * silu(z))``. Its ``y``, before the
    gate, is the stack's memory where a later ``gmu`` reads it (``writes``:
    the newest producer before the reader hands it on, no other)."""

    writes = ("memory",)

    @classmethod
    def from_spec(cls, z, kind, layer_index):
        return cls(z["units"], z["d_inner"], z["d_state"], z["d_conv"],
                   z["dt_rank"], inner_norm=z["mamba_inner_norm"],
                   norm_eps=z["eps"])

    def __init__(self, units: int, d_inner: int, d_state: int, d_conv: int,
                 dt_rank: int, inner_norm: bool = False,
                 norm_eps: float = 1e-5, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._inner, self._state, self._rank = d_inner, d_state, dt_rank
        self.dt_norm = self.b_norm = self.c_norm = None
        with self.name_scope():
            self.in_proj = Dense(2 * d_inner, use_bias=False, flatten=False,
                                 in_units=units)
            self.conv_weight = self.params.get(
                "conv_weight", shape=(d_inner, d_conv), init="normal")
            self.conv_bias = self.params.get(
                "conv_bias", shape=(d_inner,), init="zeros")
            self.x_proj = Dense(dt_rank + 2 * d_state, use_bias=False,
                                flatten=False, in_units=d_inner)
            if inner_norm:
                self.dt_norm = RMSNorm(epsilon=norm_eps, in_channels=dt_rank)
                self.b_norm = RMSNorm(epsilon=norm_eps, in_channels=d_state)
                self.c_norm = RMSNorm(epsilon=norm_eps, in_channels=d_state)
            self.dt_proj = Dense(d_inner, flatten=False, in_units=dt_rank,
                                 bias_initializer=_DtBias())
            self.A_log = self.params.get(
                "A_log", shape=(d_inner, d_state), init=_ALog())
            self.D = self.params.get("D", shape=(d_inner,), init="ones")
            self.out_proj = Dense(units, use_bias=False, flatten=False,
                                  in_units=d_inner)

    def forward(self, x, shared):
        u, z = _split(self.in_proj(x), (self._inner, self._inner))
        u = _silu(nd.contrib.causal_conv1d(u, self.conv_weight.data(),
                                           self.conv_bias.data()))
        dt_r, B, C = _split(self.x_proj(u),
                            (self._rank, self._state, self._state))
        if self.dt_norm is not None:
            with jax.named_scope("inner_norm"):
                dt_r, B, C = self.dt_norm(dt_r), self.b_norm(B), \
                    self.c_norm(C)
        dt = nd.Activation(self.dt_proj(dt_r), act_type="softrelu")
        y = nd.contrib.selective_scan(u, dt, self.A_log.data(), B, C,
                                      self.D.data(), log_A=True)
        if self.writes:
            shared["memory"] = y
        return self.out_proj(y * _silu(z))


class DiffAttention(Mixer):
    """Differential attention (kinds ``attn_window`` / ``attn_full`` /
    ``attn_cross`` under ``attention="diff"``). ``[q, k, v] = W_qkv x + b``;
    ``ops.attention.diff_attention`` (heads pair up, two softmax maps over a
    value twice as wide, their difference normalised); ``W_o . + b_o``. With
    ``window`` it sees the ``window`` newest keys, without all of them, and
    then hands its ``k`` and ``v`` on (each ``(B, T, kv_heads, head_dim)``)
    where a later ``attn_cross`` reads them (``Mixer.hand_on``).
    ``cross=True`` makes ``q = W_q x + b`` alone and reads those of the
    newest ``attn_full``; own lambdas, norm and ``W_o``."""

    @classmethod
    def from_spec(cls, z, kind, layer_index):
        return cls(z["units"], z["num_heads"], z["num_kv_heads"],
                   z["head_dim"], layer_index,
                   window=z["window"] if kind == "attn_window" else None,
                   cross=kind == "attn_cross", norm_eps=z["eps"])

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
        self._window, self._eps = window, norm_eps
        self.reads = ("kv",) if cross else ()
        self.writes = () if cross or window else ("kv",)
        self._lambda_init = 0.8 - 0.6 * math.exp(-0.3 * layer_index)
        kv = 0 if cross else 2 * num_kv_heads * head_dim
        with self.name_scope():
            self.qkv = Dense(num_heads * head_dim + kv, flatten=False,
                             in_units=units)
            self.out_proj = Dense(units, flatten=False,
                                  in_units=num_heads * head_dim)
            # float32 whatever the model is cast to, as the published
            # implementation keeps them: at their size (N(0, 0.1)) an Adam
            # step of 3e-4 is under one bfloat16 step, so in bfloat16
            # rounding alone would decide whether they ever move
            for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
                setattr(self, name, self.params.get(
                    name, shape=(head_dim,), init=initializer.Normal(0.1),
                    keep_float32=True))
            self.subln = self.params.get(
                "subln", shape=(2 * head_dim,), init="ones")

    def forward(self, x, shared):
        B, T, _ = x.shape
        H, Hkv, D = self._heads, self._kv_heads, self._dim
        if self.reads:                  # cross: queries alone are made here
            if "kv" not in shared:
                raise ValueError("attn_cross needs the keys and values of an "
                                 "earlier attn_full layer")
            q, (k, v) = self.qkv(x), shared["kv"]
        else:
            q, k, v = _split(self.qkv(x), (H * D, Hkv * D, Hkv * D))
            k, v = k.reshape((B, T, Hkv, D)), v.reshape((B, T, Hkv, D))
            if self.writes:
                shared["kv"] = (k, v)
        out = nd.contrib.diff_attention(
            q.reshape((B, T, H, D)), k, v, self.lambda_q1.data(),
            self.lambda_k1.data(), self.lambda_q2.data(),
            self.lambda_k2.data(), self.subln.data(),
            lambda_init=self._lambda_init, window=self._window,
            eps=self._eps)
        return self.out_proj(out)


@registry.register("as_float32", namespace="contrib")
def as_float32(x):
    """``x`` widened to float32, differentiably (``nd.cast`` has the
    reference's semantics: no gradient on the imperative tape)."""
    return x.astype(jnp.float32)


_AS_FLOAT32 = registry.get_op("contrib.as_float32")


def _rope(x, theta: float, interleave: bool = False, rotary_dim: int = 0):
    """Rotary positions 0..T-1 on ``x`` ``(B, T, heads, D)``, angles in
    float32. Rotate-half pairing (dimension ``i`` with ``i + D / 2``), or
    with ``interleave`` neighbours (``2i`` with ``2i + 1``). ``rotary_dim``
    > 0 turns the LAST ``rotary_dim`` dimensions alone (a latent-attention
    head's positional part) and leaves the rest as they are."""
    if rotary_dim and rotary_dim < x.shape[-1]:
        keep = x.shape[-1] - rotary_dim
        return jnp.concatenate(
            [x[..., :keep], _rope(x[..., keep:], theta, interleave)], axis=-1)
    T, half = x.shape[1], x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
    if interleave:
        pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (half, 2))
        a, b = pairs[..., 0], pairs[..., 1]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                         axis=-1).reshape(x.shape).astype(x.dtype)
    a, b = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def _norm_rope(q, k, q_gain, k_gain, rope_theta: float, eps: float):
    """What the grouped-query kinds do to ``q`` ``(B, T, H, D)`` and ``k``
    ``(B, T, Hkv, D)`` before they meet: with gains an RMSNorm over ``D`` on
    every head (scope ``qk_norm``), then with ``rope_theta`` > 0 rotary
    positions (scope ``rope``)."""
    if q_gain is not None:
        with jax.named_scope("qk_norm"):
            q, k = rms_norm(q, q_gain, eps), rms_norm(k, k_gain, eps)
    if rope_theta:
        with jax.named_scope("rope"):
            q, k = _rope(q, rope_theta), _rope(k, rope_theta)
    return q, k


@registry.register("gq_attention", namespace="contrib")
def gq_attention(q, k, v, q_gain=None, k_gain=None, rope_theta: float = 0.0,
                 window=None, eps: float = 1e-5):
    """Causal grouped-query softmax attention. ``q``: ``(B, T, H, D)``;
    ``k``, ``v``: ``(B, T, Hkv, D)``; query head ``h`` reads key/value head
    ``h // (H / Hkv)``. ``q_gain`` / ``k_gain`` ``(D,)``: an RMSNorm over
    ``D`` on every query and key head, before the positions. ``rope_theta``
    > 0: rotary positions (rotate-half, all ``D`` dimensions) on q and k; 0:
    none. Scores ``q k^T / sqrt(D)``, ``window`` as in ``flash_attention``.
    Returns ``(B, T, H * D)``."""
    B, T, H, D = q.shape
    q, k = _norm_rope(q, k, q_gain, k_gain, rope_theta, eps)
    out, _ = flash_chunk(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                         v.transpose(0, 2, 1, 3), True, 1.0 / math.sqrt(D),
                         window)
    return out.transpose(0, 2, 1, 3).reshape(B, T, H * D)


_GQ_ATTENTION = registry.get_op("contrib.gq_attention")


@registry.register("gq_retention", namespace="contrib")
def gq_retention(q, k, v, log_g, q_gain=None, k_gain=None,
                 rope_theta: float = 0.0, eps: float = 1e-5,
                 retention_eps=None):
    """Gated power retention on grouped heads: ``gq_attention``'s norm and
    positions on ``q`` and ``k``, then ``ops.retention.power_retention``
    under the scope ``scan``. ``log_g`` ``(B, T, Hkv)`` float32;
    ``retention_eps`` ``None`` is the op's own (``ops.retention.EPS``).
    Returns ``(B, T, H * D)``."""
    q, k = _norm_rope(q, k, q_gain, k_gain, rope_theta, eps)
    with jax.named_scope("scan"):
        return power_retention(q, k, v, log_g, retention_eps)


@registry.register("decay_gate", namespace="contrib")
def decay_gate(x, weight, bias):
    """``log_sigmoid(x W^T + b)`` in float32 whatever ``x`` and ``W`` are
    stored in: the log of one decay a head a token, ``(B, T, heads)``."""
    logits = jnp.einsum("btd,hd->bth", x, weight,
                        preferred_element_type=jnp.float32)
    return jax.nn.log_sigmoid(logits + bias.astype(jnp.float32))


_GQ_RETENTION = registry.get_op("contrib.gq_retention")
_DECAY_GATE = registry.get_op("contrib.decay_gate")


class GroupedQueryAttention(Mixer):
    """Grouped-query softmax attention (kinds ``attn_window`` /
    ``attn_full`` under ``attention="gqa"``, the child names
    ``DiffAttention`` has): a fused ``qkv`` without bias, with ``qk_norm``
    an RMSNorm over ``head_dim`` on every query and key head (scope
    ``qk_norm``), rotary positions (rotate-half, all of ``head_dim``; scope
    ``rope``) where ``rope_theta`` > 0 (the kinds ``rope_kinds`` names),
    plain softmax through ``flash_chunk`` with query head ``h`` on key/value
    head ``h // (H / Hkv)`` over the ``window`` newest keys or over all of
    them, and ``out_proj`` without bias. Without a window it hands its ``k``
    and ``v`` on, as ``DiffAttention`` does and under the same rule."""

    @classmethod
    def from_spec(cls, z, kind, layer_index):
        if kind == "attn_cross":
            raise ValueError("grouped-query attention has no attn_cross")
        return cls(z["units"], z["num_heads"], z["num_kv_heads"],
                   z["head_dim"],
                   window=z["window"] if kind == "attn_window" else None,
                   rope_theta=z["rope_theta"] if kind in z["rope_kinds"]
                   else 0.0, qk_norm=z["qk_norm"], norm_eps=z["eps"])

    def __init__(self, units: int, num_heads: int, num_kv_heads: int,
                 head_dim: int, window=None, rope_theta: float = 0.0,
                 qk_norm: bool = False, norm_eps: float = 1e-5, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        if num_heads % num_kv_heads or head_dim % 2:
            raise ValueError(
                f"grouped-query attention: {num_heads} query heads on "
                f"{num_kv_heads} key/value heads of {head_dim}")
        self._heads, self._kv_heads, self._dim = num_heads, num_kv_heads, \
            head_dim
        self._attrs = dict(rope_theta=float(rope_theta), window=window,
                           eps=norm_eps)
        self.writes = () if window else ("kv",)
        with self.name_scope():
            self.qkv = Dense((num_heads + 2 * num_kv_heads) * head_dim,
                             use_bias=False, flatten=False, in_units=units)
            self.out_proj = Dense(units, use_bias=False, flatten=False,
                                  in_units=num_heads * head_dim)
            self.q_norm = self.params.get(
                "q_norm", shape=(head_dim,), init="ones") if qk_norm else None
            self.k_norm = self.params.get(
                "k_norm", shape=(head_dim,), init="ones") if qk_norm else None

    def _qkv_heads(self, x):
        """``(q (B, T, H, D), k, v (B, T, Hkv, D))`` of the fused
        projection, and the two gains where q and k are normed."""
        B, T, _ = x.shape
        H, Hkv, D = self._heads, self._kv_heads, self._dim
        q, k, v = _split(self.qkv(x), (H * D, Hkv * D, Hkv * D))
        k, v = k.reshape((B, T, Hkv, D)), v.reshape((B, T, Hkv, D))
        heads = (q.reshape((B, T, H, D)), k, v)
        if self.q_norm is None:
            return heads, ()
        return heads, (self.q_norm.data(), self.k_norm.data())

    def forward(self, x, shared):
        (q, k, v), gains = self._qkv_heads(x)
        if self.writes:
            shared["kv"] = (k, v)
        out = registry.invoke(_GQ_ATTENTION, q, k, v, *gains, **self._attrs)
        return self.out_proj(out)


def _attention(z, kind, layer_index):
    """The three ``attn_*`` kinds' mixer, of the class ``attention`` names."""
    cls = GroupedQueryAttention if z["attention"] == "gqa" else DiffAttention
    return cls.from_spec(z, kind, layer_index)


class _HalfLives(initializer.Initializer):
    """``b[j] = logit(g0[j])`` with the half-lives ``ln 2 / -ln g0`` spaced
    log-uniformly from ``lo`` to ``hi`` tokens over the heads: a trained
    gated model's range of memories."""

    def __init__(self, lo: float = 64.0, hi: float = 8192.0):
        super().__init__(lo=lo, hi=hi)
        self.lo, self.hi = lo, hi

    def init_array(self, name, arr):
        half = jnp.exp(jnp.linspace(math.log(self.lo), math.log(self.hi),
                                    arr.shape[0], dtype=jnp.float32))
        log_g0 = -math.log(2.0) / half
        arr._set_data((log_g0 - jnp.log(-jnp.expm1(log_g0)))
                      .astype(arr.dtype))


class DecayGate(HybridBlock):
    """One log-decay a head a token: ``log_sigmoid(W x + b)``, computed in
    float32. The bias is kept in float32 whatever the model is cast to, as
    the differential-attention lambdas are: at ``logit(g0)`` near 9 an Adam
    step of 3e-4 is a hundredth of one bfloat16 step."""

    def __init__(self, units: int, heads: int, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.weight = self.params.get(
                "weight", shape=(heads, units), init="normal")
            self.bias = self.params.get(
                "bias", shape=(heads,), init=_HalfLives(), keep_float32=True)

    def forward(self, x):
        return registry.invoke(_DECAY_GATE, x, self.weight.data(),
                               self.bias.data())


class PowerRetention(GroupedQueryAttention):
    """A gated power-retention layer of degree 2 on grouped heads (kind
    ``retention``; ``ops/retention.py``): ``GroupedQueryAttention``'s fused
    ``qkv``, q/k RMSNorm, rotary positions and ``out_proj`` whatever
    ``attention`` says (the same code), with the softmax replaced by
    ``contrib.power_retention`` and its learned decay (child ``gate``: one
    logit a KEY/VALUE head a token, float32, so that a group shares a
    state): ``log g_t[j] = log_sigmoid(w_g[j] . x_t + b_g[j])``; for query
    head ``h`` in group ``j = h // (H / Hkv)``, over ``s <= t``: ``a[t, s] =
    (q_t[h] . k_s[j] / sqrt(D))^2 * exp(log g_{s+1}[j] + .. + log g_t[j])``
    and ``y_t[h] = sum_s a[t, s] v_s[j] / (sum_s a[t, s] + retention_eps)``
    (1: one null key of average weight, so the first rows fade in and
    nothing magnifies bf16's rounding); output ``W_o concat_h y_t[h]``.
    Equal to a state (the op's docstring): a key/value head carries ONE
    ``D (D + 1) / 2 x (D + 1)`` matrix (8256 x 129 float32 at ``D`` = 128)
    for the query heads of its group, so the op is linear in ``T``. It
    inherits the projections and not the hand-over. A device trace reads
    ``block<i>/retention/qkv|qk_norm|rope|gate|scan|out_proj``."""

    @classmethod
    def from_spec(cls, z, kind, layer_index):
        return cls(z["units"], z["num_heads"], z["num_kv_heads"],
                   z["head_dim"],
                   rope_theta=z["rope_theta"] if kind in z["rope_kinds"]
                   else 0.0, qk_norm=z["qk_norm"], norm_eps=z["eps"],
                   retention_eps=z["retention_eps"])

    def __init__(self, units: int, num_heads: int, num_kv_heads: int,
                 head_dim: int, rope_theta: float = 0.0,
                 qk_norm: bool = False, norm_eps: float = 1e-5,
                 retention_eps=None, prefix=None, params=None):
        super().__init__(units, num_heads, num_kv_heads, head_dim,
                         rope_theta=rope_theta, qk_norm=qk_norm,
                         norm_eps=norm_eps, prefix=prefix, params=params)
        self._attrs = dict(rope_theta=float(rope_theta), eps=norm_eps,
                           retention_eps=retention_eps)
        self.writes = ()
        with self.name_scope():
            self.gate = DecayGate(units, num_kv_heads)

    def forward(self, x, shared):
        (q, k, v), gains = self._qkv_heads(x)
        out = registry.invoke(_GQ_RETENTION, q, k, v, self.gate(x), *gains,
                              **self._attrs)
        return self.out_proj(out)


@registry.register("kda_gate", namespace="contrib", num_outputs=2)
def kda_gate(x, f_weight, b_weight):
    """The delta rule's two gates as far as their matrix products, in
    float32 whatever ``x`` and the matrices are stored in. ``(z (B, T, H,
    D), beta (B, T, H))``: the decay's logits ``W_f x`` as the product makes
    them (``ops.kda`` adds ``dt_bias`` and makes the bounded log-decay from
    them, inside its kernels on the TPU), and ``sigmoid(W_beta x)``."""
    H = b_weight.shape[0]
    f32 = jnp.float32
    z = jnp.einsum("btd,cd->btc", x, f_weight, preferred_element_type=f32)
    beta = jax.nn.sigmoid(jnp.einsum("btd,hd->bth", x, b_weight,
                                     preferred_element_type=f32))
    return z.reshape(x.shape[:2] + (H, -1)), beta


@registry.register("kda_scan", namespace="contrib")
def kda_scan(qkv, conv_weight, z, beta, a_log, dt_bias,
             lower_bound: float = -5.0, eps: float = 1e-6):
    """``qkv`` ``(B, T, 3 H D)`` through its short convolution and SiLU
    (scope ``conv``), then ``ops.kda`` on what that leaves (scope ``scan``):
    the op L2-norms q and k over each head (q scaled by ``D ** -0.5``) and
    makes the log-decay ``lower_bound * sigmoid(exp(A_log[h]) * (z +
    dt_bias))`` from the logits ``z`` of ``kda_gate`` itself. ``(B, T, H *
    D)``."""
    B, T, _ = qkv.shape
    H, D = z.shape[2:]
    with jax.named_scope("conv"):
        q, k, v = jnp.split(jax.nn.silu(causal_conv1d(qkv, conv_weight)), 3,
                            axis=-1)
    with jax.named_scope("scan"):
        return _kda(*(x.reshape(B, T, H, D) for x in (q, k, v)), z, beta,
                    a_log, dt_bias, lower_bound=lower_bound, eps=eps)


@registry.register("gated_head_norm", namespace="contrib")
def gated_head_norm(o, gate, gain, heads: int, eps: float = 1e-6):
    """``RMSNorm(o; gain) * sigmoid(gate)`` with the norm over each of the
    ``heads`` heads' channels and one gain vector for all of them."""
    B, T, W = o.shape
    normed = rms_norm(o.reshape(B, T, heads, W // heads), gain, eps)
    return normed.reshape(B, T, W) * jax.nn.sigmoid(gate)


_KDA_GATE = registry.get_op("contrib.kda_gate")
_KDA_SCAN = registry.get_op("contrib.kda_scan")
_GATED_HEAD_NORM = registry.get_op("contrib.gated_head_norm")


class _ChannelHalfLives(initializer.Initializer):
    """``b = logit(ln 2 / (-bound * tau))`` with the half-lives ``tau`` spaced
    log-uniformly from ``lo`` to ``hi`` tokens over the channels: under the
    bounded gate ``bound * sigmoid(b)`` a channel then forgets half in
    ``tau`` tokens. (At 0 every channel's gate is ``bound / 2``: a state
    that forgets in one token.)"""

    def __init__(self, bound: float = -5.0, lo: float = 16.0,
                 hi: float = 4096.0):
        super().__init__(bound=bound, lo=lo, hi=hi)
        self.bound, self.lo, self.hi = bound, lo, hi

    def init_array(self, name, arr):
        n = math.prod(arr.shape)
        half = jnp.exp(jnp.linspace(math.log(self.lo), math.log(self.hi), n,
                                    dtype=jnp.float32))
        p = math.log(2.0) / (-self.bound * half)
        arr._set_data(jnp.log(p / (1.0 - p)).reshape(arr.shape)
                      .astype(arr.dtype))


class KimiDeltaAttention(Mixer):
    """A Kimi-Delta-Attention layer (kind ``kda``; ``ops/kda.py``): a gated
    delta rule whose decay is one factor a key channel a token, on ``H`` =
    ``num_heads`` heads of ``D`` = ``head_dim``, keys and values as many as
    queries. ``[q, k, v, g] = W_in x`` (``in_proj``: four times ``H D``, no
    bias); ``q, k, v = silu(conv1d_causal(.))`` (depthwise, ``d_conv`` taps,
    no bias); ``q_t <- q_t / sqrt(|q_t|^2 + 1e-6) * D^-0.5`` and ``k_t <-
    k_t / sqrt(|k_t|^2 + 1e-6)`` over each head; float32
    (``contrib.kda_gate``: full matrices ``f_proj`` / ``b_proj``): ``a_t[h,
    c] = lower_bound * sigmoid(exp(A_log[h]) * ((W_f x)_t[h, c] + dt_bias[h,
    c]))`` (so ``lower_bound < a < 0``; ``A_log`` a head and ``dt_bias`` a
    channel are kept in float32) and ``beta_t[h] = sigmoid((W_beta
    x)_t[h])``; a state ``S`` of ``D x D`` a head, zero at the start: ``S' =
    Diag(exp(a_t)) S_{t-1}``, ``S_t = S' + beta_t k_t (v_t - S'^T k_t)^T``,
    ``o_t = S_t^T q_t``; output ``W_o (RMSNorm_D(o_t; gain) *
    sigmoid(g_t))``, the norm over each head with one gain vector. The layer
    hands ``contrib.kda`` its operands RAW (``kda_scan``): the two norms,
    the gate's sigmoid and bound and the chunks' cumulative decays are made
    inside the op's kernels on the TPU (and their gradients too), so nothing
    of ``(B, T, H D)`` is written between the convolution / the gate's
    product and the kernels. A device trace reads
    ``block<i>/kda/proj|conv|gate|scan|out``."""

    @classmethod
    def from_spec(cls, z, kind, layer_index):
        return cls(z["units"], z["num_heads"], z["head_dim"], z["d_conv"],
                   lower_bound=z["kda_lower_bound"], norm_eps=z["eps"])

    def __init__(self, units: int, num_heads: int, head_dim: int,
                 d_conv: int = 4, lower_bound: float = -5.0,
                 norm_eps: float = 1e-6, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._heads, self._bound, self._eps = num_heads, float(lower_bound), \
            norm_eps
        wide = num_heads * head_dim
        with self.name_scope():
            self.in_proj = Dense(4 * wide, use_bias=False, flatten=False,
                                 in_units=units)
            self.conv_weight = self.params.get(
                "conv_weight", shape=(3 * wide, d_conv), init="normal")
            self.f_proj = self.params.get(
                "f_proj", shape=(wide, units), init="normal")
            self.b_proj = self.params.get(
                "b_proj", shape=(num_heads, units), init="normal")
            self.A_log = self.params.get(
                "A_log", shape=(num_heads,), init="zeros", keep_float32=True)
            self.dt_bias = self.params.get(
                "dt_bias", shape=(wide,), keep_float32=True,
                init=_ChannelHalfLives(lower_bound))
            self.o_norm = self.params.get(
                "o_norm", shape=(head_dim,), init="ones")
            self.out_proj = Dense(units, use_bias=False, flatten=False,
                                  in_units=wide)

    def forward(self, x, shared):
        with jax.named_scope("proj"):
            qkv, g = _split(self.in_proj(x), (self.conv_weight.shape[0],
                                              self.dt_bias.shape[0]))
        with jax.named_scope("gate"):
            z, beta = registry.invoke(
                _KDA_GATE, x, self.f_proj.data(), self.b_proj.data())
        o = registry.invoke(
            _KDA_SCAN, qkv, self.conv_weight.data(), z, beta,
            self.A_log.data(), self.dt_bias.data(), lower_bound=self._bound)
        with jax.named_scope("out"):
            return self.out_proj(registry.invoke(
                _GATED_HEAD_NORM, o, g, self.o_norm.data(),
                heads=self._heads, eps=self._eps))


@registry.register("latent_attention", namespace="contrib")
def latent_attention(q, kv, k_rope, q_gain, k_gain, gate, nope_dim: int,
                     rope_theta: float = 1e4, interleave: bool = True,
                     eps: float = 1e-6):
    """Causal softmax attention with keys and values expanded from a latent
    (DeepSeek-V2's MLA, not absorbed: the training half). ``q`` ``(B, T, H,
    nope + rope)``; ``kv`` ``(B, T, H, nope + v)``: each head's
    position-free key and its value; ``k_rope`` ``(B, T, rope)``: ONE
    rotary key, which every head appends to its own; ``q_gain`` / ``k_gain``:
    an RMSNorm over a query head's whole width and over ``k_nope``, before
    the positions (``None``: not normed); rotary positions on
    the last ``rope`` dimensions of q and on ``k_rope``; scores ``q k^T /
    sqrt(nope + rope)``; ``gate`` ``(B, T, H)`` logits: the head's output
    times their sigmoid (``None``: no gate). Returns ``(B, T, H * v)``.
    Scopes ``rope`` (norms and positions) and ``attn``."""
    B, T, H, W = q.shape
    rope_dim = W - nope_dim
    with jax.named_scope("rope"):
        if q_gain is not None:
            q = rms_norm(q, q_gain, eps)
        q = _rope(q, rope_theta, interleave, rope_dim)
        k_nope = kv[..., :nope_dim]
        if k_gain is not None:
            k_nope = rms_norm(k_nope, k_gain, eps)
        k_rope = _rope(k_rope[:, :, None, :], rope_theta, interleave)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (B, T, H, rope_dim))], axis=-1)
    with jax.named_scope("attn"):
        qh, kh, vh = (x.transpose(0, 2, 1, 3)
                      for x in (q, k, kv[..., nope_dim:]))
        out, _ = flash_chunk(qh, kh, vh, True, 1.0 / math.sqrt(W), None)
        out = out.transpose(0, 2, 1, 3)
        if gate is not None:
            out = out * jax.nn.sigmoid(gate)[..., None].astype(out.dtype)
    return out.reshape(B, T, -1)


_LATENT_ATTENTION = registry.get_op("contrib.latent_attention")


class LatentAttention(Mixer):
    """Multi-head latent attention as it TRAINS (kind ``mla``; the stack's
    keyword ``mla`` holds this class's arguments after ``num_heads``:
    ``latent_dim``, ``nope_dim``, ``rope_dim``, ``v_dim``, ``interleave``,
    ``q_latent_dim``, ``qk_norm``, ``head_gate``). The query: ``q = W_q x``
    on ``H`` heads of ``nope_dim + rope_dim``, or with ``q_latent_dim`` > 0
    through a latent of its own (DeepSeek-V3's ``q_lora_rank``): ``c_q =
    RMSNorm(W_qa x)`` (gain ``qa_norm``), ``q = W_qb c_q``. ``[c | k_r] =
    W_kva x`` (``latent_dim + rope_dim``); ``c <- RMSNorm(c)`` (gain
    ``kv_norm``); ``[k_nope | v] = W_kvb c`` a head; with ``qk_norm`` an
    RMSNorm with a gain over each query head's whole width and over
    ``k_nope``; rotary positions (base ``rope_theta``, neighbouring pairs
    with ``interleave``) on the last ``rope_dim`` dimensions of every query
    head and on the ONE ``k_r``, which every head appends to its ``k_nope``;
    ``softmax(q k^T / sqrt(nope_dim + rope_dim))``, causal, times ``v``;
    with ``head_gate`` each head's output times ``sigmoid((W_a x)[head])``
    (``gate_proj``); ``W_o``. Ling-3.0's variant is the defaults (one query
    matrix, both norms, the gate); DeepSeek-V3's is ``q_latent_dim`` with
    ``qk_norm=False, head_gate=False``. The flash kernels take q/k of
    ``nope_dim + rope_dim`` and v of ``v_dim``. Nothing is handed on. A
    device trace reads ``block<i>/mla/proj|rope|attn|out`` (``proj`` holds
    both query matrices)."""

    @classmethod
    def from_spec(cls, z, kind, layer_index):
        if not z["mla"]:
            raise ValueError("an mla layer: give mla= (latent_dim, "
                             "nope_dim, rope_dim, v_dim)")
        return cls(z["units"], z["num_heads"], rope_theta=z["rope_theta"],
                   norm_eps=z["eps"], **z["mla"])

    def __init__(self, units: int, num_heads: int, latent_dim: int,
                 nope_dim: int, rope_dim: int, v_dim: int,
                 rope_theta: float = 1e4, interleave: bool = True,
                 norm_eps: float = 1e-6, q_latent_dim: int = 0,
                 qk_norm: bool = True, head_gate: bool = True, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        self._heads, self._latent = num_heads, latent_dim
        self._nope, self._rope, self._v = nope_dim, rope_dim, v_dim
        self._attrs = dict(nope_dim=nope_dim, rope_theta=float(rope_theta),
                           interleave=bool(interleave), eps=norm_eps)
        wide = num_heads * (nope_dim + rope_dim)
        self.q_proj = self.gate_proj = self.q_norm = self.k_norm = None
        with self.name_scope():
            if q_latent_dim:
                self.qa_proj = Dense(q_latent_dim, use_bias=False,
                                     flatten=False, in_units=units)
                self.qa_norm = RMSNorm(epsilon=norm_eps,
                                       in_channels=q_latent_dim)
                self.qb_proj = Dense(wide, use_bias=False, flatten=False,
                                     in_units=q_latent_dim)
            else:
                self.q_proj = Dense(wide, use_bias=False, flatten=False,
                                    in_units=units)
            self.kva_proj = Dense(latent_dim + rope_dim, use_bias=False,
                                  flatten=False, in_units=units)
            self.kv_norm = RMSNorm(epsilon=norm_eps, in_channels=latent_dim)
            self.kvb_proj = Dense(num_heads * (nope_dim + v_dim),
                                  use_bias=False, flatten=False,
                                  in_units=latent_dim)
            if head_gate:
                self.gate_proj = Dense(num_heads, use_bias=False,
                                       flatten=False, in_units=units)
            if qk_norm:
                self.q_norm = self.params.get(
                    "q_norm", shape=(nope_dim + rope_dim,), init="ones")
                self.k_norm = self.params.get(
                    "k_norm", shape=(nope_dim,), init="ones")
            self.out_proj = Dense(units, use_bias=False, flatten=False,
                                  in_units=num_heads * v_dim)

    def forward(self, x, shared):
        B, T, _ = x.shape
        H = self._heads
        with jax.named_scope("proj"):
            q = self.q_proj(x) if self.q_proj is not None \
                else self.qb_proj(self.qa_norm(self.qa_proj(x)))
            q = q.reshape((B, T, H, self._nope + self._rope))
            latent, k_rope = _split(self.kva_proj(x),
                                    (self._latent, self._rope))
            kv = self.kvb_proj(self.kv_norm(latent)).reshape(
                (B, T, H, self._nope + self._v))
            gate = None if self.gate_proj is None else self.gate_proj(x)
        q_gain, k_gain = (None, None) if self.q_norm is None \
            else (self.q_norm.data(), self.k_norm.data())
        out = registry.invoke(_LATENT_ATTENTION, q, kv, k_rope, q_gain,
                              k_gain, gate, **self._attrs)
        with jax.named_scope("out"):
            return self.out_proj(out)


class GatedMemoryUnit(Mixer):
    """``W_out (m * silu(W_in x))`` (kind ``gmu``): the memory ``m`` the
    newest ``mamba`` handed on, gated by this layer; no scan, no
    convolution."""

    reads = ("memory",)

    @classmethod
    def from_spec(cls, z, kind, layer_index):
        return cls(z["units"], z["d_inner"])

    def __init__(self, units: int, d_inner: int, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.in_proj = Dense(d_inner, use_bias=False, flatten=False,
                                 in_units=units)
            self.out_proj = Dense(units, use_bias=False, flatten=False,
                                  in_units=d_inner)

    def forward(self, x, shared):
        if "memory" not in shared:
            raise ValueError("gmu needs the memory of an earlier mamba layer")
        return self.out_proj(shared["memory"] * _silu(self.in_proj(x)))


class ShortConv(Mixer):
    """A gated short convolution (kind ``conv``; LFM2's mixer): ``W_out (C *
    conv(B * u))`` with ``[B, C, u] = W_in x`` (three equal chunks of
    ``units``, in that order) and a depthwise causal convolution of
    ``width`` taps (the stack's ``d_conv``; zeros before the first row), no
    bias anywhere. Both gates are plain products: no activation function
    anywhere in it. The two products and the convolution run under the
    scope ``gate`` (a device trace reads
    ``block<i>/conv/in_proj|gate|out_proj``). There is no kernel for it:
    the gate is three elementwise passes over ``(T, units)`` values and
    ``width`` shifted copies that XLA fuses, partly INTO the projections'
    matmuls, so time under ``gate`` is not the gate's own; the benchmark's
    ``conv_mixer_roofline_pct.train`` sets the whole mixer against its
    roofline."""

    @classmethod
    def from_spec(cls, z, kind, layer_index):
        return cls(z["units"], z["d_conv"])

    def __init__(self, units: int, width: int, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._units = units
        with self.name_scope():
            self.in_proj = Dense(3 * units, use_bias=False, flatten=False,
                                 in_units=units)
            self.conv_weight = self.params.get(
                "conv_weight", shape=(units, width), init="normal")
            self.out_proj = Dense(units, use_bias=False, flatten=False,
                                  in_units=units)

    def forward(self, x, shared):
        B, C, u = _split(self.in_proj(x), (self._units,) * 3)
        with jax.named_scope("gate"):
            y = C * nd.contrib.causal_conv1d(B * u, self.conv_weight.data())
        return self.out_proj(y)


class _Kind(NamedTuple):
    """A row of ``MIXERS``: all the stack knows of a mixer kind."""
    build: Callable         # (z, kind, layer_index) -> the kind's ``Mixer``
    decode_state: str       # what a decode cache would hold for a layer, a slot
    # may its block run under jax.checkpoint (``may_remat`` has the rest of
    # the rule: the layer reads nothing and hands nothing on IN ITS STACK,
    # beside a dense MLP). Yes for the kinds a cell runs so, or runs beside
    # one that is (the three ``attn_*`` classes are one mixer); the kinds
    # that only ever read (``attn_cross``, ``gmu``) never can
    remat: bool = False


MIXERS = {
    "mamba": _Kind(Mamba.from_spec, "scan and convolution states",
                   remat=True),
    "attn_window": _Kind(_attention, "a window of keys and values",
                         remat=True),
    "attn_full": _Kind(_attention, "every key and value, which attn_cross "
                                   "layers read too", remat=True),
    "attn_cross": _Kind(_attention, "nothing of its own (an earlier "
                                    "attn_full layer's keys)"),
    "gmu": _Kind(GatedMemoryUnit.from_spec,
                 "nothing of its own (an earlier mamba layer's output)"),
    "conv": _Kind(ShortConv.from_spec,
                  "a conv layer's last d_conv - 1 rows a channel"),
    "retention": _Kind(PowerRetention.from_spec,
                       "a head_dim (head_dim + 1) / 2 x (head_dim + 1) "
                       "matrix a key/value head (8256 x 129 at 128)",
                       remat=True),
    "kda": _Kind(KimiDeltaAttention.from_spec,
                 "a head_dim x head_dim float32 matrix a head and the last "
                 "d_conv - 1 rows of q, k and v"),
    "mla": _Kind(LatentAttention.from_spec,
                 "a latent row and one rotary key a token (latent_dim + "
                 "rope_dim numbers), read through absorbed projections; "
                 "nothing of the query side, whose latent is made anew a "
                 "token"),
}
KINDS = tuple(MIXERS)


def _swiglu(z):
    return SwiGLU(z["units"], z["ffn_units"])


def _experts(z):
    if not z["moe"]:
        raise ValueError("mlp_kinds names an expert layer: give moe=")
    return SparseExperts(z["units"], **z["moe"])


# MLP kind: (how it is built from z, ``remat`` as in ``_Kind``: a dense MLP
# holds no state)
MLPS = {"mlp": (_swiglu, True), "moe": (_experts, False)}
MLP_KINDS = tuple(MLPS)


class HybridDecoderBlock(HybridBlock):
    """One layer of ``kind``; its mixer is the child named by the kind and
    its MLP the child named by ``mlp_kind`` (``mlp`` or ``moe``), so a
    device trace reads ``block3/attn_window/...``, ``block3/moe/experts``.
    ``z`` holds the model's widths and options (``HybridDecoderLM`` builds
    it). ``shared`` is the stack's hand-over (``Mixer``): ``{"memory": y of
    the newest mamba, "kv": (k, v) of the newest attn_full}``, each there
    only where a later layer reads it."""

    def __init__(self, kind: str, layer_index: int, mlp_kind: str, z: dict,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        if kind not in MIXERS:
            raise ValueError(f"unknown layer kind {kind!r}; one of {KINDS}")
        if mlp_kind not in MLPS:
            raise ValueError(f"unknown MLP kind {mlp_kind!r}; one of "
                             f"{MLP_KINDS}")
        self.kind, self.mlp_kind = kind, mlp_kind
        self._post = z["norm_position"] == "post"
        units, eps = z["units"], z["eps"]
        norm = RMSNorm if z["norm"] == "rms" else LayerNorm
        with self.name_scope():
            self.ln1 = norm(epsilon=eps, in_channels=units)
            mixer = MIXERS[kind].build(z, kind, layer_index)
            setattr(self, kind, mixer)
            self.ln2 = norm(epsilon=eps, in_channels=units)
            build_mlp, self._mlp_remat = MLPS[mlp_kind]
            setattr(self, mlp_kind, build_mlp(z))

    @property
    def mixer(self) -> Mixer:
        return getattr(self, self.kind)

    @property
    def may_remat(self) -> bool:
        """Both rows allow it and the mixer neither reads ``shared`` nor
        writes what a later layer reads (``Mixer.hand_on``)."""
        mixer = self.mixer
        return MIXERS[self.kind].remat and self._mlp_remat \
            and not (mixer.reads or mixer.writes)

    def forward(self, x, shared):
        mixer, mlp = self.mixer, getattr(self, self.mlp_kind)
        if self._post:
            h = x + self.ln1(mixer(x, shared))
            return h + self.ln2(mlp(h))
        h = x + mixer(self.ln1(x), shared)
        return h + mlp(self.ln2(h))


# ``profiler.get_launch_stats("mtp")``: the training forwards that ran a
# prediction block, and of the newest one its depth, the positions its loss
# counts and the bytes of its float32 logits
metrics.register_launch("mtp", ("depth", "positions", "logits_bytes"))


class MultiTokenPrediction(HybridBlock):
    """DeepSeek-V3's multi-token-prediction module of depth 1 (the child
    ``mtp0`` of ``HybridDecoderLM(mtp_layers=1)``), which predicts token ``i
    + 2`` at position ``i`` beside the head's ``i + 1``, in training only.
    With ``g`` the hidden state the head reads (after ``ln_f``): ``e_i =
    N_e(Emb(x_{i+1}))`` for ``i < T - 1`` and the zero row at ``T - 1``
    (whose loss is masked), ``Emb`` the TRUNK's table; ``u = W_eh [e ;
    N_h(g)]`` (``eh_proj``, ``2 units -> units``, no bias); ``w =
    Block(u)``: one more whole layer of the stack's last layer's kinds,
    causal over the same positions, with weights, router and selection bias
    of its own; ``logits2 = Head(N_s(w))`` through the TRUNK's head. Both
    tables are the trunk's parameters, used twice a step: their gradients
    are the sums of both uses. Its layer is the child ``block<L>`` (``L`` the
    trunk's depth), so a device trace reads ``mtp0/embed``, ``mtp0/proj``,
    ``mtp0/block<L>/<kind>/...`` and ``mtp0/head``."""

    def __init__(self, kind: str, layer_index: int, mlp_kind: str, z: dict,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        units, eps = z["units"], z["eps"]
        norm = RMSNorm if z["norm"] == "rms" else LayerNorm
        with self.name_scope():
            self.enorm = norm(epsilon=eps, in_channels=units)
            self.hnorm = norm(epsilon=eps, in_channels=units)
            self.eh_proj = Dense(units, use_bias=False, flatten=False,
                                 in_units=2 * units)
            # under the name the trunk would give its next layer
            self._layer = f"block{layer_index}"
            setattr(self, self._layer,
                    HybridDecoderBlock(kind, layer_index, mlp_kind, z))
            self.norm = norm(epsilon=eps, in_channels=units)
        mixer = getattr(self, self._layer).mixer
        if mixer.reads or mixer.writes:
            raise ValueError(f"a prediction block of kind {kind!r} would "
                             f"read or write the trunk's hand-over")

    def forward(self, tokens, g, embedding, logits):
        """``tokens`` ``(B, T)``, ``g`` ``(B, T, units)``; ``embedding`` and
        ``logits`` are the trunk's table and head (``HybridDecoderLM
        ._logits``). Returns ``(B, T, vocab)``."""
        B, T = tokens.shape
        with jax.named_scope("embed"):
            e = embedding(nd.slice_axis(tokens, axis=1, begin=1, end=T))
            zero_row = nd.NDArray(jnp.zeros((B, 1, e.shape[-1]),
                                            e.data.dtype))
            e = self.enorm(nd.concat(e, zero_row, dim=1))
        with jax.named_scope("proj"):
            u = self.eh_proj(nd.concat(e, self.hnorm(g), dim=2))
        w = getattr(self, self._layer)(u, {})
        with jax.named_scope("head"):
            return logits(self.norm(w))


class HybridDecoderLM(HybridBlock):
    """Decoder LM over token ids from a list of layer kinds (``KINDS``, the
    keys of ``MIXERS``).

    Input ``(B, T)`` int tokens, output ``(B, T, vocab)`` logits (and in
    training the prediction block's, with ``mtp_layers``); no position
    table, so ``T`` is bounded by memory alone. Trains through
    ``DataParallelTrainer`` like ``TransformerLM``. Multiples of 128 in ``T``
    engage the flash kernels, ``d_inner % 128 == 0`` the scan kernels and
    widths in whole 128s the grouped-matmul kernels on the TPU
    (``profiler.get_kernel_path_counts()`` says which ran).

    ``d_inner`` is the state-space layers' width (Mamba's ``expand *
    units``), shared by ``mamba`` and ``gmu`` since the one gates the
    other's output; ``dt_rank`` defaults to ``ceil(units / 16)``. ``d_conv``
    is the taps of every depthwise causal convolution: Mamba's, and the
    ``conv`` kind's (LFM2's ``conv_L_cache``). ``mamba_inner_norm=True``
    gives every ``mamba`` layer the Jamba family's three RMSNorms with gains
    (over ``dt_r``, ``B`` and ``C``, between ``x_proj`` and ``dt_proj`` /
    the scan; eps ``layer_norm_eps``; ``Mamba``'s docstring).

    The layer spec beside ``layer_kinds``. ``attention="gqa"`` makes
    ``attn_window`` / ``attn_full`` ``GroupedQueryAttention``s: ``qk_norm``
    norms q and k, ``rope_kinds`` names the kinds whose q and k get rotary
    positions (base ``rope_theta``). ``norm="rms"`` takes RMSNorm
    for LayerNorm; ``norm_position="post"`` puts each norm on its
    sub-layer's OUTPUT (``h = x + N(Mixer(x))``). ``tie_head=False`` gives
    the head a matrix of its own (child ``head``) and float32 logits;
    ``float32_logits=True`` widens the TIED head's logits too (the default
    leaves them in the model's type).
    ``mlp_kinds`` names each layer's MLP, ``"mlp"`` (SwiGLU of
    ``ffn_units``) or ``"moe"`` (``parallel.moe.SparseExperts`` built from
    ``moe``, its keyword arguments after ``units``: ``ffn_units``,
    ``num_experts``, ``top_k``, ``held``, ``shared_ffn_units``,
    ``routed_scale``, ``bias_update_rate``, ``weight_eps``).

    ``retention_eps`` is what the ``retention`` kind adds to the sum of a
    row's weights before it divides by it; ``None`` leaves the op's own
    (``ops.retention.EPS``, the one place that states it).

    ``remat=True`` runs every block BUT THE LAST under ``jax.checkpoint``
    where the step is traced (``DataParallelTrainer``; not on the imperative
    tape): the backward keeps each such block's INPUT and computes the block
    again, a layer at a time, for one more forward's operations. The last
    block runs as it is: its backward begins as soon as the head and the
    loss are through, so recomputing it would rebuild at once what it
    declined to keep, and free nothing (a one-layer model is the plain
    program). ``profiler.get_remat_stats()`` says how many blocks the newest
    traced step had, how many it recomputes and of which kinds. It is for
    models whose kept activations do not fit beside their state (five
    330M-parameter layers at 8192 tokens keep 7.5 GB; fourteen 100M ones
    with their scans' operands 7.1); only layers that read nothing, hand
    nothing on IN THIS STACK and hold no state take it (the rows of
    ``MIXERS`` and ``MLPS`` that say so: ``HybridDecoderBlock.may_remat``).
    A ``mamba`` or ``attn_full`` layer hands on only what a later ``gmu`` /
    ``attn_cross`` reads, so a stack without those readers may be
    recomputed and one with them is refused, by layer.

    ``kda_lower_bound`` bounds the ``kda`` kind's log-decay a channel;
    ``mla`` holds the ``mla`` kind's arguments, those of ``LatentAttention``
    after ``num_heads`` (its docstring lists them; the rotary base is
    ``rope_theta``).

    ``mtp_layers=1`` adds a multi-token-prediction block (child ``mtp0``,
    ``MultiTokenPrediction``: one more layer of the last layer's kinds on
    the final hidden state and the next token's embedding, through the same
    table and head). In train mode (``autograd.is_training()``) ``forward``
    then returns ``(logits, logits2)``, for ``gluon.loss.NextTokenLoss``;
    outside it the block does not run and the output is the logits alone.
    """

    def __init__(self, vocab_size: int, layer_kinds, units: int,
                 ffn_units: int, num_heads: int, num_kv_heads: int,
                 head_dim: int = 0, window: int = 512, d_inner: int = 0,
                 d_state: int = 16, d_conv: int = 4, dt_rank: int = 0,
                 layer_norm_eps: float = 1e-5, attention: str = "diff",
                 qk_norm: bool = False, rope_kinds=(), rope_theta: float = 1e4,
                 norm: str = "layer", norm_position: str = "pre",
                 tie_head: bool = True, mlp_kinds=None, moe=None,
                 float32_logits: bool = False, remat: bool = False,
                 retention_eps=None, kda_lower_bound: float = -5.0,
                 mla=None, mtp_layers: int = 0,
                 mamba_inner_norm: bool = False, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        if mtp_layers not in (0, 1):
            raise ValueError(f"mtp_layers {mtp_layers}: 0 or 1 (deeper "
                             f"predictions chain their blocks: ROADMAP M7)")
        for what, value, known in (
                ("attention", attention, ("diff", "gqa")),
                ("norm", norm, ("layer", "rms")),
                ("norm_position", norm_position, ("pre", "post"))):
            if value not in known:
                raise ValueError(f"{what} {value!r}; one of {known}")
        self._vocab, self._units = vocab_size, units
        self._float32_logits = float32_logits
        self.layer_kinds = tuple(layer_kinds)
        self.mlp_kinds = tuple(mlp_kinds or ("mlp",) * len(self.layer_kinds))
        if len(self.mlp_kinds) != len(self.layer_kinds):
            raise ValueError(f"{len(self.layer_kinds)} layers, "
                             f"{len(self.mlp_kinds)} MLP kinds")
        self._remat = remat
        z = dict(units=units, ffn_units=ffn_units, num_heads=num_heads,
                 num_kv_heads=num_kv_heads,
                 head_dim=head_dim or units // num_heads, window=window,
                 d_inner=d_inner or 2 * units, d_state=d_state,
                 d_conv=d_conv, dt_rank=dt_rank or -(-units // 16),
                 eps=layer_norm_eps, attention=attention, qk_norm=qk_norm,
                 rope_kinds=tuple(rope_kinds), rope_theta=rope_theta,
                 norm=norm, norm_position=norm_position, moe=moe,
                 retention_eps=retention_eps,
                 kda_lower_bound=kda_lower_bound, mla=mla,
                 mamba_inner_norm=mamba_inner_norm)
        with self.name_scope():
            self.embedding = Embedding(vocab_size, units,
                                       weight_initializer="normal")
            self.blocks = []
            for i, (kind, mlp_kind) in enumerate(zip(self.layer_kinds,
                                                     self.mlp_kinds)):
                blk = HybridDecoderBlock(kind, i, mlp_kind, z)
                setattr(self, f"block{i}", blk)   # registers child + params
                self.blocks.append(blk)
            # a layer hands on what a LATER layer reads before another
            # layer writes it again: walked from the last layer back
            wanted = set()
            for blk in reversed(self.blocks):
                could = blk.mixer.writes
                blk.mixer.hand_on(wanted)
                wanted = (wanted - set(could)) | set(blk.mixer.reads)
            if remat and not all(blk.may_remat for blk in self.blocks):
                kinds = tuple(k for k, row in MIXERS.items() if row.remat)
                mlps = tuple(k for k, (_, ok) in MLPS.items() if ok)
                why = "; ".join(
                    f"layer {i} ({blk.kind}, {blk.mlp_kind})"
                    + "".join(f" {verb} {', '.join(keys)}" for verb, keys in
                              (("reads", blk.mixer.reads),
                               ("hands on", blk.mixer.writes)) if keys)
                    for i, blk in enumerate(self.blocks)
                    if not blk.may_remat)
                raise ValueError(
                    f"remat=True recomputes blocks that read nothing, hand "
                    f"nothing on and hold no state: kinds {kinds} beside "
                    f"MLP kinds {mlps}. Not {why}")
            self.ln_f = (RMSNorm if norm == "rms" else LayerNorm)(
                epsilon=layer_norm_eps, in_channels=units)
            self.head = None if tie_head else Dense(
                vocab_size, use_bias=False, flatten=False, in_units=units)
            self.mtp0 = MultiTokenPrediction(
                self.layer_kinds[-1], len(self.blocks), self.mlp_kinds[-1],
                z) if mtp_layers else None

    def forward(self, tokens):
        B, T = tokens.shape
        h = self.embedding(tokens)
        shared = {}
        remat = self._remat and not autograd.is_recording()
        if remat:
            metrics.record_remat(len(self.blocks), self.layer_kinds[:-1])
        for blk in self.blocks:
            # not the last one, whose backward comes first (the docstring)
            if remat and blk is not self.blocks[-1]:
                h = nd.NDArray(jax.checkpoint(
                    lambda x, blk=blk: blk(nd.NDArray(x), {}).data)(h.data))
            else:
                h = blk(h, shared)
        h = self.ln_f(h)
        logits = self._logits(h)
        if self.mtp0 is None or not autograd.is_training():
            return logits
        further = self.mtp0(tokens, h, self.embedding, self._logits)
        metrics.record_launch(
            "mtp", depth=1, positions=B * (T - 1),
            logits_bytes=further.size * further.data.dtype.itemsize)
        return logits, further

    def _logits(self, h):
        """The head on normed rows ``h`` ``(B, T, units)``."""
        B, T, _ = h.shape
        if self.head is not None:
            # float32 logits: in a TPU step with bfloat16 logits XLA adds
            # log_softmax's exponentials (a row of the vocabulary) in a
            # bfloat16 reduce, and the loss reads low
            return registry.invoke(_AS_FLOAT32, self.head(h))
        with jax.named_scope("head"):
            w = self.embedding.weight.data()
            flat = nd.reshape(h, (B * T, self._units))
            logits = nd.reshape(nd.dot(flat, w, transpose_b=True),
                                (B, T, self._vocab))
            if self._float32_logits:    # for the untied head's reason
                logits = registry.invoke(_AS_FLOAT32, logits)
            return logits

    def _no_decode(self, what: str):
        states = "; ".join(f"{kind}: {row.decode_state}"
                           for kind, row in MIXERS.items()
                           if kind in self.layer_kinds)
        raise NotImplementedError(
            f"HybridDecoderLM.{what}: this family trains only. Decoding "
            f"needs a cache that holds, side by side, for this model's "
            f"layers: {states}; the engine has one cache geometry (ROADMAP "
            f"D1/D2, M3, M5, M6). Layer kinds: {self.layer_kinds}"
            + ("" if self.mtp0 is None else
               ". Its prediction block (mtp0) runs in training alone; once "
               "there is a cache it could draft for speculative decoding "
               "(ROADMAP M7)"))

    def generate(self, *args, **kwargs):
        self._no_decode("generate")

    def serving_step(self, *args, **kwargs):
        self._no_decode("serving_step")

    def serving_verify_step(self, *args, **kwargs):
        self._no_decode("serving_verify_step")

    def _gen_params(self):
        self._no_decode("_gen_params (the serving engine's first call)")
