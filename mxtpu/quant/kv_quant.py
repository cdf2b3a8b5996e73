"""Quantized paged KV cache — the int8/fp8 twin of the serving cache arrays.

The serving engine's KV cache is one static ``(L, 2, S, H, TOT, D)`` array
(``mxtpu/serving/kv.py``); at float32 its bytes are the binding constraint on
resident slots per device (ROADMAP item 2). :class:`QuantKV` stores the same
geometry as an int8 (or float8_e4m3fn) ``data`` array plus a float32
``scale`` array of shape ``(L, 2, S, H, TOT)`` — ONE symmetric absmax scale
per (layer, k/v, slot, head, token) row, stored alongside the 32-token blocks
so every slice the paging layer takes (slot rows, prefix blocks, bucket
promotions) slices ``data`` and ``scale`` congruently.

Why per-token-per-head rows:

* **Quantize-on-append** — the decode/prefill step writes exactly one
  ``(S, H, D)`` row per position; a per-row scale is computed from that row
  alone, so appending NEVER re-quantizes a neighbor and a row's bytes are
  immutable once written (the property the radix prefix cache's bit-exact
  sharing rests on).
* **Bounded error** — symmetric round-to-nearest over ``±absmax`` gives a
  per-element round-trip error ``|x - deq(q(x))| <= absmax / 254`` for int8
  (half a quantization step, ``step = absmax/127``); the bound is asserted
  per block by ``tests/test_quant.py``.
* **Capacity math** — per-row overhead is 4 bytes of scale per ``D`` int8
  elements: shrink vs float32 = ``4D / (D + 4)`` — 3.56x at the tiny
  preset's D=32, 3.94x at D=128, always >= 1.9x for D >= 5 (the acceptance
  floor; ``docs/quantization.md`` has the table).

:class:`QuantKV` is a registered jax pytree, so it rides ``lax.scan``
carries, ``jax.jit`` arguments, and ``ServingHandoff`` host round-trips
exactly like the raw array it replaces. Every helper here dispatches on
raw-array vs QuantKV, so ``serving/kv.py`` and the engine call ONE function
(``empty``/``promote``/``merge_page``/...) regardless of cache dtype.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

__all__ = ["QuantKV", "KV_MODES", "quantize_rows", "dequantize_rows",
           "roundtrip_error_bound", "empty", "empty_page", "promote",
           "merge_page", "slot_page", "to_host", "to_device", "install_rows",
           "block_slice", "cache_nbytes", "page_nbytes", "shrink_vs_f32"]

# fp8 support is gated on the installed jax exposing float8_e4m3fn (it does
# from 0.4.x); the int8 path never touches it
_FP8 = getattr(jnp, "float8_e4m3fn", None)

# mode -> (storage dtype, max representable magnitude the scale maps onto)
KV_MODES = {"int8": (jnp.int8, 127.0)}
if _FP8 is not None:
    KV_MODES["fp8"] = (_FP8, 448.0)


@jax.tree_util.register_pytree_node_class
class QuantKV:
    """A quantized KV cache/page: ``data`` (..., D) low-precision values and
    ``scale`` (...,) float32 per-row dequantization factors, with
    ``deq = data.astype(f32) * scale[..., None]``. ``mode`` ('int8'/'fp8')
    is static metadata and participates in trace signatures via the pytree
    aux, so an int8 and an fp8 cache can never silently share a program."""

    __slots__ = ("data", "scale", "mode")

    def __init__(self, data, scale, mode: str = "int8"):
        self.data = data
        self.scale = scale
        self.mode = mode

    def tree_flatten(self):
        return (self.data, self.scale), self.mode

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], aux)

    @property
    def shape(self):
        return self.data.shape

    @property
    def nbytes(self) -> int:
        return int(self.data.nbytes) + int(self.scale.nbytes)

    def dequantize(self):
        """Full-precision view (tests/debugging; the serving step dequantizes
        per layer in-kernel instead of materializing this)."""
        return dequantize_rows(self.data, self.scale)

    def __repr__(self):
        return (f"QuantKV(mode={self.mode!r}, shape={self.data.shape}, "
                f"nbytes={self.nbytes})")


def _mode_of(mode: str) -> Tuple:
    try:
        return KV_MODES[mode]
    except KeyError:
        raise ValueError(
            f"unknown KV quantization mode {mode!r} "
            f"(choose from {sorted(KV_MODES)})") from None


def quantize_rows(x, mode: str = "int8"):
    """Symmetric per-row quantization over the LAST axis.

    Returns ``(q, scale)`` with ``x ~= q.astype(f32) * scale[..., None]``;
    ``scale = absmax / qmax`` (1.0 for all-zero rows, so zeros round-trip
    exactly and freshly-zeroed cache rows are valid)."""
    dtype, qmax = _mode_of(mode)
    absmax = jnp.max(jnp.abs(x), axis=-1)
    scale = jnp.where(absmax > 0, absmax / qmax, 1.0).astype(jnp.float32)
    inv = x / scale[..., None]
    if mode == "int8":
        q = jnp.clip(jnp.round(inv), -qmax, qmax).astype(dtype)
    else:
        q = inv.astype(dtype)
    return q, scale


def dequantize_rows(q, scale):
    return q.astype(jnp.float32) * scale[..., None]


def roundtrip_error_bound(x, mode: str = "int8"):
    """Per-row worst-case |x - deq(q(x))| bound: half a quantization step
    for int8's round-to-nearest; fp8 e4m3 keeps >= 2 mantissa bits over the
    top binade, so half of absmax/2^2 bounds it (loose but sufficient for
    the tests' contract)."""
    _, qmax = _mode_of(mode)
    absmax = jnp.max(jnp.abs(x), axis=-1)
    if mode == "int8":
        return absmax / (2.0 * qmax)
    return absmax / 8.0


# ---------------------------------------------------------------------------
# paging helpers — ONE surface over raw arrays and QuantKV
# ---------------------------------------------------------------------------


def empty(shape: Tuple[int, ...], dtype=jnp.float32,
          quant: Optional[str] = None):
    """An all-zero cache/page of the serving geometry ``(..., TOT, D)``:
    a plain ``dtype`` array, or a :class:`QuantKV` when ``quant`` names a
    mode (zero data + unit scales — a valid round-trip of zeros)."""
    if quant is None:
        return jnp.zeros(shape, dtype)
    qdtype, _ = _mode_of(quant)
    return QuantKV(jnp.zeros(shape, qdtype),
                   jnp.ones(shape[:-1], jnp.float32), quant)


def empty_page(L: int, H: int, D: int, PB: int, dtype=jnp.float32,
               quant: Optional[str] = None):
    """A fresh single-request prefill page ``(L, 2, 1, H, PB, D)``."""
    return empty((L, 2, 1, H, PB, D), dtype, quant)


def promote(caches, TOT_new: int):
    """Zero-pad into a bigger TOT bucket (content-preserving: positions past
    the old TOT are unwritten by definition). Mirrors ``serving.kv.promote``
    for the quantized cache — pad scales with 1.0 so the padded rows stay a
    valid round-trip of zeros."""
    if not isinstance(caches, QuantKV):
        L, two, S, H, TOT_old, D = caches.shape
        if TOT_new <= TOT_old:
            return caches
        return jnp.zeros((L, two, S, H, TOT_new, D), caches.dtype) \
            .at[..., :TOT_old, :].set(caches)
    L, two, S, H, TOT_old, D = caches.data.shape
    if TOT_new <= TOT_old:
        return caches
    data = jnp.zeros((L, two, S, H, TOT_new, D), caches.data.dtype) \
        .at[..., :TOT_old, :].set(caches.data)
    scale = jnp.ones((L, two, S, H, TOT_new), jnp.float32) \
        .at[..., :TOT_old].set(caches.scale)
    return QuantKV(data, scale, caches.mode)


def merge_page(caches, page, slot: int):
    """Install a prefilled ``(L, 2, 1, H, PB, D)`` page as slot row ``slot``,
    zeroing the row's tail past PB (stale K/V from the slot's previous
    tenant must not survive admission) — data and scale congruently."""
    if not isinstance(caches, QuantKV):
        PB = page.shape[4]
        row = jnp.zeros(caches.shape[:2] + caches.shape[3:], caches.dtype) \
            .at[..., :PB, :].set(page[:, :, 0])
        return caches.at[:, :, slot].set(row)
    PB = page.data.shape[4]
    dsh = caches.data.shape
    row = jnp.zeros(dsh[:2] + dsh[3:], caches.data.dtype) \
        .at[..., :PB, :].set(page.data[:, :, 0])
    # scale row shape is (L, 2, H, TOT): the data row minus its D axis
    srow = jnp.ones(dsh[:2] + (dsh[3], dsh[4]), jnp.float32) \
        .at[..., :PB].set(page.scale[:, :, 0])
    return QuantKV(caches.data.at[:, :, slot].set(row),
                   caches.scale.at[:, :, slot].set(srow), caches.mode)


def slot_page(caches, slot: int):
    """One slot's page ``(L, 2, 1, H, TOT, D)`` — the drain() unit."""
    if not isinstance(caches, QuantKV):
        return caches[:, :, slot:slot + 1]
    return QuantKV(caches.data[:, :, slot:slot + 1],
                   caches.scale[:, :, slot:slot + 1], caches.mode)


def to_host(page):
    """Host-land a page for a mesh-independent handoff (numpy leaves)."""
    if not isinstance(page, QuantKV):
        return np.asarray(page)
    return QuantKV(np.asarray(page.data), np.asarray(page.scale), page.mode)


def to_device(page):
    if not isinstance(page, QuantKV):
        return jnp.asarray(page)
    return QuantKV(jnp.asarray(page.data), jnp.asarray(page.scale),
                   page.mode)


def install_rows(page, blocks, m: int):
    """Seed a fresh page's first ``m`` token rows from a list of cached
    prefix blocks (the PrefixCache hit path). Quantized blocks install their
    BYTES — the shared prefix stays bit-identical across requests and never
    pays a second quantization."""
    if not blocks or m == 0:
        return page
    if not isinstance(page, QuantKV):
        return page.at[..., :m, :].set(jnp.concatenate(blocks, axis=4))
    return QuantKV(
        page.data.at[..., :m, :].set(
            jnp.concatenate([b.data for b in blocks], axis=4)),
        page.scale.at[..., :m].set(
            jnp.concatenate([b.scale for b in blocks], axis=4)),
        page.mode)


def block_slice(page, start: int, size: int):
    """Token rows ``[start, start+size)`` of a page — the PrefixCache
    insertion unit (data and scale sliced congruently)."""
    if not isinstance(page, QuantKV):
        return page[..., start:start + size, :]
    return QuantKV(page.data[..., start:start + size, :],
                   page.scale[..., start:start + size], page.mode)


def cache_nbytes(caches) -> int:
    """Resident bytes of a cache/page (data + scales for QuantKV) — the
    ``kv_bytes_resident`` stat."""
    if caches is None:
        return 0
    return int(caches.nbytes)


def page_nbytes(L: int, H: int, D: int, tokens: int, dtype=jnp.float32,
                quant: Optional[str] = None) -> int:
    """Analytic bytes of ``tokens`` KV positions (both K and V) across all
    layers/heads — the PrefixCache block accounting and the fixed-HBM-budget
    slot math behind :func:`shrink_vs_f32`."""
    rows = L * 2 * H * tokens
    if quant is None:
        return rows * D * jnp.dtype(dtype).itemsize
    qdtype, _ = _mode_of(quant)
    return rows * (D * jnp.dtype(qdtype).itemsize + 4)   # +4: f32 scale


def shrink_vs_f32(L: int, H: int, D: int, tokens: int,
                  quant: str = "int8") -> float:
    """KV-bytes shrink factor vs a float32 cache of identical geometry
    (= ``4D / (D + 4)`` for int8; the acceptance floor is 1.9x)."""
    return (page_nbytes(L, H, D, tokens)
            / page_nbytes(L, H, D, tokens, quant=quant))
