"""Quantized serving decode — int8/fp8 KV and int8 per-channel weights.

Two independently selectable axes (``MXTPU_SERVING_QUANT`` tokens, or
``ServingEngine(quant=...)``):

* ``int8_kv`` / ``fp8_kv`` — the paged KV cache is a
  :class:`~mxtpu.quant.kv_quant.QuantKV` (quantize-on-append, per-token-
  per-head scales, dequantize-in-kernel at attention). Composes with the
  radix :class:`~mxtpu.serving.kv.PrefixCache` (cached prefix blocks are
  stored and shared QUANTIZED, so the capacity win multiplies with the hit
  rate) and with ``drain()/adopt()`` handoff.
* ``int8_w`` — :func:`quantize_lm` rewrites the model's ``_gen_params()``
  pytree: every matmul weight becomes an int8 tensor + a per-output-channel
  float32 scale (LLM.int8()/AWQ-style weight-only quantization). Matmuls
  issue ``lax.dot_general`` with int8 operands and
  ``preferred_element_type=int32`` — the MXU's 2x-peak int8 path —
  with a dynamic per-row activation scale folded into the accumulator
  readout. Biases, LayerNorms, and the position table stay float32.

:func:`build_step` mirrors :meth:`TransformerLM.serving_step` exactly —
same einsums, same per-slot scatter, same masking — so the quantized
program keeps every contract the engine relies on (row independence,
one trace per (slots, TOT) bucket; quantized params and scales ride as
traced jit ARGUMENTS, so weight updates or engine restarts never retrace).
The fp32 path through ``serving/kv.py`` is untouched: ``build_decode`` /
``build_prefill_chunk`` select this step fn only when a spec is active.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from . import kv_quant

__all__ = ["QuantSpec", "parse_quant", "quantize_lm", "build_step",
           "build_verify_step", "quant_param_specs"]

# weight tensors of one transformer layer's _gen_params dict that carry a
# matmul (biases/norms excluded); "embed" is handled separately (tied head)
_LAYER_MATMULS = ("qw", "kw", "vw", "ow", "f1w", "f2w")

_VALID_TOKENS = {"int8_kv": ("kv", "int8"), "fp8_kv": ("kv", "fp8"),
                 "int8_w": ("weights", "int8")}


def _constrain_raw(x, entry: str):
    """Activation/cache constraint hook mirroring the fp32 step functions
    (identity outside ``parallel.fsdp.layout_scope``; the sharded serving
    engine opens the scope while the quantized programs trace)."""
    from ..parallel import fsdp as _fsdp
    return _fsdp.constrain(x, entry)


@dataclass(frozen=True)
class QuantSpec:
    """Resolved low-precision configuration for one serving engine.

    ``kv`` is the KV-cache mode (None | 'int8' | 'fp8'); ``weights`` the
    matmul-weight mode (None | 'int8'). Frozen: an engine holds ONE spec
    for its lifetime, so its program caches stay keyed on (slots, bucket,
    chunk) exactly as the fp32 engine — no retrace churn."""
    kv: Optional[str] = None
    weights: Optional[str] = None

    @property
    def enabled(self) -> bool:
        return bool(self.kv or self.weights)

    @property
    def tag(self) -> str:
        """Stable human-readable tag ('fp32', 'int8_kv', 'int8_kv+int8_w',
        ...) — the stats label."""
        parts = []
        if self.kv:
            parts.append(f"{self.kv}_kv")
        if self.weights:
            parts.append(f"{self.weights}_w")
        return "+".join(parts) if parts else "fp32"


def parse_quant(value) -> QuantSpec:
    """Parse ``MXTPU_SERVING_QUANT`` / ``ServingEngine(quant=...)``:
    a :class:`QuantSpec` passes through; a comma-separated token string
    (``int8_kv``, ``fp8_kv``, ``int8_w``) composes one; None/'' disables.
    Unknown tokens raise ``ValueError`` (never silently fp32)."""
    if value is None:
        return QuantSpec()
    if isinstance(value, QuantSpec):
        return value
    fields = {}
    for tok in str(value).split(","):
        tok = tok.strip()
        if not tok:
            continue
        if tok not in _VALID_TOKENS:
            raise ValueError(
                f"unknown quantization token {tok!r} in {value!r} "
                f"(choose from {sorted(_VALID_TOKENS)})")
        field, mode = _VALID_TOKENS[tok]
        if fields.get(field, mode) != mode:
            raise ValueError(f"conflicting quantization tokens in {value!r}")
        fields[field] = mode
    if fields.get("kv") == "fp8" and "fp8" not in kv_quant.KV_MODES:
        raise ValueError("fp8_kv requires a jax with float8_e4m3fn")
    return QuantSpec(**fields)


def _quantize_weight(w):
    """Symmetric per-output-channel int8: ``w (out, in) ~= q * s[:, None]``
    (scale = absmax/127 — kv_quant's row convention over the IN axis)."""
    return kv_quant.quantize_rows(w, "int8")


def quantize_lm(model, spec: QuantSpec = None):
    """The engine-side params pytree for ``spec``.

    With ``weights='int8'`` every matmul weight ``<name>`` in the model's
    ``_gen_params()`` pytree is replaced by ``<name>_q`` (int8) +
    ``<name>_s`` (float32 per-output-channel scales); the embedding table
    becomes ``embed_q``/``embed_s`` with per-VOCAB-ROW scales, which serves
    both the lookup (dequantize one row) and the tied head (the row axis is
    the output axis of ``h @ E^T``). Biases, LayerNorm params, and the
    position table stay float32. Everything returned is a traced jit
    argument downstream — quantizing is a one-time host-side pass.

    Per-tensor max-abs round-trip error is recorded into
    ``profiler.get_quant_stats()`` (the quant-regression observability
    contract)."""
    params = model._gen_params()
    if spec is None or spec.weights != "int8":
        return params
    from .. import profiler

    def q(name, w):
        wq, ws = _quantize_weight(w)
        err = float(jnp.max(jnp.abs(w - kv_quant.dequantize_rows(wq, ws))))
        profiler.record_quant_error(name, err)
        return wq, ws

    out = {k: v for k, v in params.items() if k != "embed"}
    out["embed_q"], out["embed_s"] = q("embed", params["embed"])
    layers = []
    for i, lp in enumerate(params["layers"]):
        nlp = {k: v for k, v in lp.items() if k not in _LAYER_MATMULS}
        for name in _LAYER_MATMULS:
            nlp[name + "_q"], nlp[name + "_s"] = q(f"layers[{i}].{name}",
                                                   lp[name])
        layers.append(nlp)
    out["layers"] = layers
    if "head_w" in params:
        out.pop("head_w")
        out["head_w_q"], out["head_w_s"] = q("head_w", params["head_w"])
    return out


def _int8_matmul(h, w_q, w_s):
    """``h (S, in) @ deq(w_q (out, in)).T`` on the int8 MXU path: dynamic
    per-row activation quantization, int32 accumulation, one fused rescale
    by (activation scale x per-out-channel weight scale)."""
    h_q, h_s = kv_quant.quantize_rows(h, "int8")
    acc = lax.dot_general(h_q, w_q, (((1,), (1,)), ((), ())),
                          preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * h_s[:, None] * w_s[None, :]


def build_step(model, S: int, TOT: int, spec: QuantSpec, decode_kernel=None):
    """The quantized twin of :meth:`TransformerLM.serving_step` — identical
    decode math with (a) KV rows quantized on append and the attention read
    running FUSED over the quantized storage when ``spec.kv`` is set
    (``caches`` is then a :class:`QuantKV`; see
    :mod:`mxtpu.ops.quant_attention` — the full-precision KV view is never
    materialized on either the pallas or the xla path), and (b) weight
    matmuls on the int8 path when ``spec.weights`` is set (``params`` from
    :func:`quantize_lm`).

    ``decode_kernel`` picks the attention-read path ('pallas'/'xla'/None =
    ``MXTPU_DECODE_KERNEL`` + backend auto) and is resolved ONCE here at
    build time, so the compiled program is pinned to one kernel and env
    flips between dispatches cannot retrace.

    Returns ``step(params, caches, tok, p) -> (new_caches, logits)`` with
    the same row-independence property as the fp32 step: slot ``s``'s
    output depends only on its own cache row and position, so the engine's
    continuous-batching semantics carry over unchanged. Records the
    quantized-matmul site count into ``get_quant_stats()`` at build time."""
    H = model.blocks[0].attn._heads
    U = model._units
    D = U // H
    scale = 1.0 / math.sqrt(D)
    wq = spec.weights == "int8"
    kvq = spec.kv
    if kvq:
        from ..ops import quant_attention
        dec_kernel = quant_attention.resolve_decode_kernel(
            decode_kernel, TOT=TOT, D=D)
    if wq or kvq:
        from .. import profiler
        # matmul sites staged per step: 6 per layer + tied/untied head
        n_sites = (6 * len(model.blocks) + 1) if wq else 0
        profiler.record_quant_matmuls(n_sites)

    def ln(x, g, b, eps=1e-5):
        m = jnp.mean(x, axis=-1, keepdims=True)
        v = jnp.var(x, axis=-1, keepdims=True)
        return (x - m) * lax.rsqrt(v + eps) * g + b

    def mm(h, lp, w, b):
        if wq:
            return _int8_matmul(h, lp[w + "_q"], lp[w + "_s"]) + lp[b]
        return h @ lp[w].T + lp[b]

    def step(params, caches, tok, p):
        rows = jnp.arange(S)
        pc = jnp.clip(p, 0, TOT - 1)
        if wq:
            x = kv_quant.dequantize_rows(params["embed_q"][tok],
                                         params["embed_s"][tok]) \
                + params["pos"][pc]
        else:
            x = params["embed"][tok] + params["pos"][pc]       # (S, U)
        x = _constrain_raw(x, "activations")
        mask = jnp.arange(TOT)[None, :] <= pc[:, None]         # (S, TOT)
        new_caches = caches
        for i, lp in enumerate(params["layers"]):
            h = ln(x, lp["ln1_g"], lp["ln1_b"])
            q = mm(h, lp, "qw", "qb").reshape(S, H, D)
            k = mm(h, lp, "kw", "kb").reshape(S, H, D)
            v = mm(h, lp, "vw", "vb").reshape(S, H, D)
            # per-slot scatter, quantize-on-append: slot s writes only its
            # own row at its own position, as one (D,) int8 row + one f32
            # scale — written bytes are immutable, so prefix blocks sliced
            # off this cache are shareable bit-exactly
            if kvq:
                k_q, k_s = kv_quant.quantize_rows(k, kvq)
                v_q, v_s = kv_quant.quantize_rows(v, kvq)
                data = new_caches.data \
                    .at[i, 0, rows, :, pc].set(k_q) \
                    .at[i, 1, rows, :, pc].set(v_q)
                scl = new_caches.scale \
                    .at[i, 0, rows, :, pc].set(k_s) \
                    .at[i, 1, rows, :, pc].set(v_s)
                new_caches = kv_quant.QuantKV(data, scl, kvq)
                # fused dequant-attention: the quantized storage feeds the
                # read directly — no dequantized (S, H, TOT, D) view exists
                # on either path (the 0.78x-regression fix)
                ctx = quant_attention.dequant_attention_decode(
                    q, new_caches.data[i, 0], new_caches.scale[i, 0],
                    new_caches.data[i, 1], new_caches.scale[i, 1],
                    pc, scale=scale, kernel=dec_kernel).reshape(S, U)
            else:
                new_caches = new_caches.at[i, 0, rows, :, pc].set(k)
                new_caches = new_caches.at[i, 1, rows, :, pc].set(v)
                K = new_caches[i, 0]        # (S, H, TOT, D)
                V = new_caches[i, 1]
                s = jnp.einsum("bhd,bhtd->bht", q, K) * scale
                s = jnp.where(mask[:, None, :], s, -1e30)
                att = jax.nn.softmax(s, axis=-1)
                ctx = jnp.einsum("bht,bhtd->bhd", att, V).reshape(S, U)
            # all-gather before each row matmul — replicated ow/f2w under
            # the serving layout keep the contraction a full local dot
            # (the sharded bit-exactness contract; mxtpu/serving/sharded.py)
            ctx = _constrain_raw(ctx, "activations")
            x = x + mm(ctx, lp, "ow", "ob")
            g = ln(x, lp["ln2_g"], lp["ln2_b"])
            g = jax.nn.gelu(mm(g, lp, "f1w", "f1b"), approximate=False)
            g = _constrain_raw(g, "activations")
            x = x + mm(g, lp, "f2w", "f2b")
        h = ln(x, params["ln_f_g"], params["ln_f_b"])
        if wq:
            if "head_w_q" in params:
                logits = _int8_matmul(h, params["head_w_q"],
                                      params["head_w_s"]) + params["head_b"]
            else:
                logits = _int8_matmul(h, params["embed_q"],
                                      params["embed_s"])
        elif "head_w" in params:
            logits = h @ params["head_w"].T + params["head_b"]
        else:
            logits = h @ params["embed"].T                      # (S, vocab)
        # pin the carry sharding to the engine's canonical placement
        if kvq:
            new_caches = kv_quant.QuantKV(
                _constrain_raw(new_caches.data, "kv_cache"),
                _constrain_raw(new_caches.scale, "kv_cache"), kvq)
        else:
            new_caches = _constrain_raw(new_caches, "kv_cache")
        return new_caches, logits

    return step


def build_verify_step(model, S: int, TOT: int, K1: int, spec: QuantSpec,
                      decode_kernel=None):
    """The quantized twin of :meth:`TransformerLM.serving_verify_step`:
    one forward scoring ``K1`` = k + 1 consecutive positions per slot for
    speculative decode, over quantized KV and/or int8 weights.

    Bit-exactness with :func:`build_step` is structural, exactly as the
    fp32 pair: dense matmuls run on the flattened ``(S * K1, in)`` row
    batch (per-row activation scales make each row's int8 dot identical to
    the single-step one), all ``K1`` K/V rows quantize-on-append before
    any query reads, and the attention read loops the drafted positions
    through the SAME :func:`~mxtpu.ops.quant_attention
    .dequant_attention_decode` call the decode step issues — one position
    per call, per-slot read cursor ``p + j`` — on both the pallas and the
    xla kernel. Rejected drafts leave quantized garbage rows (data AND
    per-row scales) above the accept point; both are overwritten
    congruently by the next dispatch before anything attends them, so the
    int8 scales roll back with the write cursor for free."""
    H = model.blocks[0].attn._heads
    U = model._units
    D = U // H
    scale = 1.0 / math.sqrt(D)
    wq = spec.weights == "int8"
    kvq = spec.kv
    if kvq:
        from ..ops import quant_attention
        dec_kernel = quant_attention.resolve_decode_kernel(
            decode_kernel, TOT=TOT, D=D)

    def ln(x, g, b, eps=1e-5):
        m = jnp.mean(x, axis=-1, keepdims=True)
        v = jnp.var(x, axis=-1, keepdims=True)
        return (x - m) * lax.rsqrt(v + eps) * g + b

    def mm(h, lp, w, b):
        if wq:
            return _int8_matmul(h, lp[w + "_q"], lp[w + "_s"]) + lp[b]
        return h @ lp[w].T + lp[b]

    def step(params, caches, toks, p):
        rows = jnp.arange(S)
        pcs = jnp.clip(p[:, None] + jnp.arange(K1)[None, :], 0, TOT - 1)
        if wq:
            x = kv_quant.dequantize_rows(params["embed_q"][toks],
                                         params["embed_s"][toks]) \
                + params["pos"][pcs]
        else:
            x = params["embed"][toks] + params["pos"][pcs]   # (S, K1, U)
        x = _constrain_raw(x, "activations")
        mask = jnp.arange(TOT)[None, None, :] <= pcs[:, :, None]
        new_caches = caches
        for i, lp in enumerate(params["layers"]):
            h = ln(x, lp["ln1_g"], lp["ln1_b"])
            flat = h.reshape(S * K1, U)
            q = mm(flat, lp, "qw", "qb").reshape(S, K1, H, D)
            k = mm(flat, lp, "kw", "kb").reshape(S, K1, H, D)
            v = mm(flat, lp, "vw", "vb").reshape(S, K1, H, D)
            if kvq:
                data, scl = new_caches.data, new_caches.scale
                for j in range(K1):
                    k_q, k_s = kv_quant.quantize_rows(k[:, j], kvq)
                    v_q, v_s = kv_quant.quantize_rows(v[:, j], kvq)
                    data = data.at[i, 0, rows, :, pcs[:, j]].set(k_q) \
                               .at[i, 1, rows, :, pcs[:, j]].set(v_q)
                    scl = scl.at[i, 0, rows, :, pcs[:, j]].set(k_s) \
                             .at[i, 1, rows, :, pcs[:, j]].set(v_s)
                new_caches = kv_quant.QuantKV(data, scl, kvq)
                ctx = jnp.stack([
                    quant_attention.dequant_attention_decode(
                        q[:, j], new_caches.data[i, 0],
                        new_caches.scale[i, 0], new_caches.data[i, 1],
                        new_caches.scale[i, 1], pcs[:, j], scale=scale,
                        kernel=dec_kernel)
                    for j in range(K1)], axis=1).reshape(S, K1, U)
            else:
                for j in range(K1):
                    new_caches = new_caches \
                        .at[i, 0, rows, :, pcs[:, j]].set(k[:, j]) \
                        .at[i, 1, rows, :, pcs[:, j]].set(v[:, j])
                K = new_caches[i, 0]            # (S, H, TOT, D)
                V = new_caches[i, 1]
                ctxs = []
                for j in range(K1):
                    s = jnp.einsum("bhd,bhtd->bht", q[:, j], K) * scale
                    s = jnp.where(mask[:, j][:, None, :], s, -1e30)
                    att = jax.nn.softmax(s, axis=-1)
                    ctxs.append(jnp.einsum("bht,bhtd->bhd", att, V))
                ctx = jnp.stack(ctxs, axis=1).reshape(S, K1, U)
            # all-gather-before-row-matmul, as in build_step
            flatc = _constrain_raw(ctx.reshape(S * K1, U), "activations")
            x = x + mm(flatc, lp, "ow", "ob").reshape(S, K1, U)
            g = ln(x, lp["ln2_g"], lp["ln2_b"])
            g = jax.nn.gelu(mm(g.reshape(S * K1, U), lp, "f1w", "f1b"),
                            approximate=False)
            g = _constrain_raw(g, "activations")
            x = x + mm(g, lp, "f2w", "f2b").reshape(S, K1, U)
        h = ln(x, params["ln_f_g"], params["ln_f_b"])
        hf = h.reshape(S * K1, U)
        if wq:
            if "head_w_q" in params:
                logits = _int8_matmul(hf, params["head_w_q"],
                                      params["head_w_s"]) + params["head_b"]
            else:
                logits = _int8_matmul(hf, params["embed_q"],
                                      params["embed_s"])
        elif "head_w" in params:
            logits = hf @ params["head_w"].T + params["head_b"]
        else:
            logits = hf @ params["embed"].T
        V = logits.shape[-1]
        if kvq:
            new_caches = kv_quant.QuantKV(
                _constrain_raw(new_caches.data, "kv_cache"),
                _constrain_raw(new_caches.scale, "kv_cache"), kvq)
        else:
            new_caches = _constrain_raw(new_caches, "kv_cache")
        return new_caches, logits.reshape(S, K1, V)

    return step


def quant_param_specs(model, layout=None):
    """Partition specs for a :func:`quantize_lm` pytree under the composed
    dp x fsdp x tp flagship mesh: each ``<name>_q`` tensor inherits the
    fp32 weight's :class:`~mxtpu.parallel.fsdp.SpecLayout` entry, and each
    ``<name>_s`` scale vector follows its weight's OUTPUT-channel axis
    (``parallel.fsdp.scale_spec``) — so a tp-sharded column-parallel weight
    carries tp-sharded scales and the rescale stays local to the shard."""
    from ..parallel.fsdp import SpecLayout, scale_spec
    from jax.sharding import PartitionSpec as P
    layout = layout or SpecLayout()
    wspec = {"qw": layout.qkv_projection(), "kw": layout.qkv_projection(),
             "vw": layout.qkv_projection(), "ow": layout.attn_out(),
             "f1w": layout.ffn_up(), "f2w": layout.ffn_down()}
    layers = []
    for _ in model.blocks:
        lp = {}
        for name, sp in wspec.items():
            lp[name + "_q"] = sp
            lp[name + "_s"] = scale_spec(sp)
        for v in ("ln1_g", "ln1_b", "qb", "kb", "vb", "ob",
                  "ln2_g", "ln2_b", "f1b", "f2b"):
            lp[v] = layout.vector()
        layers.append(lp)
    emb = layout.embeddings()
    return {"embed_q": emb, "embed_s": scale_spec(emb),
            "pos": layout.vector(), "ln_f_g": layout.vector(),
            "ln_f_b": layout.vector(), "layers": layers,
            "_replicated": P()}
