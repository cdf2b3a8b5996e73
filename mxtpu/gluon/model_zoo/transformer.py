"""Decoder-only transformer language model — the TPU-native flagship training
workload.

The reference's transformer support is a single helper op
(``_contrib_div_sqrt_dim``, src/operator/contrib/transformer.cc:33) plus the
gluon-nlp ecosystem it fed; a TPU-first framework makes the transformer a
first-class model-zoo family instead, built over the Pallas flash-attention
kernel (ops/attention.py) per the long-context mandate (SURVEY.md §5).

Architecture (GPT-2-style, pre-LN):

    tokens → embed + learned pos-embed
           → N × [LN → causal MHA → +res, LN → FFN(4d, GELU) → +res]
           → LN → logits = h · Eᵀ   (tied softmax head)

The tied head reuses the token-embedding matrix (Press & Wolf 2017 weight
tying) — one fewer V×d parameter and the standard LM configuration.

Every layer is jit-friendly: static shapes, no data-dependent control flow,
registered nd ops throughout so the imperative autograd tape records the same
graph ``DataParallelTrainer`` traces under jit.

This file is GPT-2's block only. Models built from other layer kinds (no
position table, SwiGLU, Mamba, windowed / cross differential attention,
gated memory units) are the sibling family ``hybrid_decoder.HybridDecoderLM``,
which trains through the same trainer and has no serving steps yet.
"""

from __future__ import annotations

import math

import jax

from ... import ndarray as nd
from ..block import HybridBlock
from ..contrib.nn import MultiHeadAttention, _layout_constrain
from ..nn.basic_layers import Dense, Embedding, LayerNorm

__all__ = ["TransformerBlock", "TransformerLM", "transformer_lm"]


def _constrain_raw(x, entry: str):
    """Raw-jnp twin of ``_layout_constrain`` for the serving step functions
    (identity outside ``parallel.fsdp.layout_scope`` — the sharded serving
    engine opens the scope around every program trace)."""
    from ...parallel import fsdp as _fsdp
    return _fsdp.constrain(x, entry)


class TransformerBlock(HybridBlock):
    """One pre-LN decoder block: causal flash MHA + position-wise FFN."""

    def __init__(self, units: int, num_heads: int, ffn_units: int = 0,
                 dropout: float = 0.0, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        ffn_units = ffn_units or 4 * units
        with self.name_scope():
            self.ln1 = LayerNorm(in_channels=units)
            self.attn = MultiHeadAttention(units, num_heads, causal=True,
                                           dropout=dropout)
            self.ln2 = LayerNorm(in_channels=units)
            self.ffn1 = Dense(ffn_units, flatten=False, in_units=units)
            self.ffn2 = Dense(units, flatten=False, in_units=ffn_units)

    def forward(self, x):
        h = x + self.attn(self.ln1(x))
        g = nd.LeakyReLU(self.ffn1(self.ln2(h)), act_type="gelu")
        return h + self.ffn2(g)


class TransformerLM(HybridBlock):
    """Decoder-only LM over token ids.

    Input ``(B, T)`` int tokens, output ``(B, T, vocab)`` logits. ``T`` may be
    anything ≤ ``max_len`` (the learned position table is sliced); multiples
    of 128 engage the Pallas flash kernel on TPU, others fall back to the XLA
    attention reference (ops/attention.py ``_use_pallas``).
    """

    def __init__(self, vocab_size: int, units: int = 512, num_layers: int = 6,
                 num_heads: int = 8, max_len: int = 2048, ffn_units: int = 0,
                 dropout: float = 0.0, tie_weights: bool = True,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._vocab = vocab_size
        self._units = units
        self._max_len = max_len
        self._tie = tie_weights
        with self.name_scope():
            self.embedding = Embedding(vocab_size, units,
                                       weight_initializer="normal")
            self.pos_embed = self.params.get(
                "pos_embed", shape=(max_len, units), init="normal")
            self.blocks = []
            for i in range(num_layers):
                blk = TransformerBlock(units, num_heads, ffn_units, dropout)
                setattr(self, f"block{i}", blk)   # registers child + params
                self.blocks.append(blk)
            self.ln_f = LayerNorm(in_channels=units)
            if not tie_weights:
                self.head = Dense(vocab_size, flatten=False, in_units=units)

    def forward(self, tokens):
        B, T = tokens.shape
        if T > self._max_len:
            raise ValueError(f"sequence length {T} exceeds max_len "
                             f"{self._max_len}")
        h = self.embedding(tokens)
        with jax.named_scope("embed"):
            pos = nd.slice_axis(self.pos_embed.data(), axis=0, begin=0, end=T)
            h = h + nd.reshape(pos, (1, T, self._units))
        # composed-flagship layout: activations ride the SpecLayout table
        # (sequence-sharded through the block stack under a layout_scope,
        # identity otherwise)
        h = _layout_constrain(h, "seq_activations")
        for blk in self.blocks:
            h = _layout_constrain(blk(h), "seq_activations")
        h = self.ln_f(h)
        if not self._tie:
            return self.head(h)
        # tied softmax head: logits = h · Eᵀ over the embedding table
        with jax.named_scope("head"):
            w = self.embedding.weight.data()
            flat = nd.reshape(h, (B * T, self._units))
            return nd.reshape(nd.dot(flat, w, transpose_b=True),
                              (B, T, self._vocab))

    # -- autoregressive decoding (TPU-first: one jitted scan, static KV
    # cache — no per-token dispatch, no dynamic shapes) ---------------------
    def _gen_params(self):
        """Raw weight pytree, passed as a jit ARGUMENT so weight updates
        don't recompile the decode program."""
        def raw(p):
            return p.data().data
        layers = []
        for blk in self.blocks:
            at = blk.attn
            layers.append(dict(
                ln1_g=raw(blk.ln1.gamma), ln1_b=raw(blk.ln1.beta),
                qw=raw(at.q_proj.weight), qb=raw(at.q_proj.bias),
                kw=raw(at.k_proj.weight), kb=raw(at.k_proj.bias),
                vw=raw(at.v_proj.weight), vb=raw(at.v_proj.bias),
                ow=raw(at.out_proj.weight), ob=raw(at.out_proj.bias),
                ln2_g=raw(blk.ln2.gamma), ln2_b=raw(blk.ln2.beta),
                f1w=raw(blk.ffn1.weight), f1b=raw(blk.ffn1.bias),
                f2w=raw(blk.ffn2.weight), f2b=raw(blk.ffn2.bias)))
        out = dict(embed=raw(self.embedding.weight),
                   pos=raw(self.pos_embed), ln_f_g=raw(self.ln_f.gamma),
                   ln_f_b=raw(self.ln_f.beta), layers=layers)
        if not self._tie:
            out["head_w"] = raw(self.head.weight)
            out["head_b"] = raw(self.head.bias)
        return out

    def serving_step(self, S: int, TOT: int):
        """The engine-facing step-callable: one decode step over an
        ``S``-slot batch with PER-SLOT positions.

        Returns ``step(params, caches, tok, p) -> (new_caches, logits)``
        where ``caches`` is the static ``(L, 2, S, H, TOT, D)`` KV cache,
        ``tok`` is the ``(S,)`` int32 token fed at per-slot position ``p``
        (``(S,)`` int32, clipped into the cache), and ``logits`` is
        ``(S, vocab)`` for position ``p + 1``. Every op is row-independent
        (per-slot causal mask, per-slot KV scatter), so one slot's output is
        bit-identical regardless of what the other slots hold — the property
        the continuous-batching engine's bit-exactness contract rests on.
        ``_build_generate`` scans this same callable with ``p`` broadcast to
        a single position, so solo ``generate`` and the serving engine share
        one implementation of the decode math."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        H = self.blocks[0].attn._heads
        U = self._units
        D = U // H
        scale = 1.0 / math.sqrt(D)

        def ln(x, g, b, eps=1e-5):
            m = jnp.mean(x, axis=-1, keepdims=True)
            v = jnp.var(x, axis=-1, keepdims=True)
            return (x - m) * lax.rsqrt(v + eps) * g + b

        def step(params, caches, tok, p):
            rows = jnp.arange(S)
            pc = jnp.clip(p, 0, TOT - 1)
            x = params["embed"][tok] + params["pos"][pc]       # (S, U)
            x = _constrain_raw(x, "activations")
            mask = jnp.arange(TOT)[None, :] <= pc[:, None]     # (S, TOT)
            new_caches = caches
            for i, lp in enumerate(params["layers"]):
                h = ln(x, lp["ln1_g"], lp["ln1_b"])
                q = (h @ lp["qw"].T + lp["qb"]).reshape(S, H, D)
                k = (h @ lp["kw"].T + lp["kb"]).reshape(S, H, D)
                v = (h @ lp["vw"].T + lp["vb"]).reshape(S, H, D)
                # per-slot scatter: slot s writes only its own cache row at
                # its own position — dead/retired slots can't corrupt peers
                kv_dt = new_caches.dtype     # bf16 caches: cast, then store
                new_caches = new_caches.at[i, 0, rows, :, pc].set(
                    k.astype(kv_dt))
                new_caches = new_caches.at[i, 1, rows, :, pc].set(
                    v.astype(kv_dt))
                K = new_caches[i, 0]        # (S, H, TOT, D)
                V = new_caches[i, 1]
                s = jnp.einsum("bhd,bhtd->bht", q, K) * scale
                s = jnp.where(mask[:, None, :], s, -1e30)
                att = jax.nn.softmax(s, axis=-1)
                ctx = jnp.einsum("bht,bhtd->bhd", att, V).reshape(S, U)
                # all-gather the tp-sharded ctx/g before each row matmul:
                # the weight is replicated under the serving layout, so the
                # contraction stays a full local dot — never partial sums +
                # psum (the bit-exactness contract; mxtpu/serving/sharded.py)
                ctx = _constrain_raw(ctx, "activations")
                x = x + ctx @ lp["ow"].T + lp["ob"]
                g = ln(x, lp["ln2_g"], lp["ln2_b"])
                g = jax.nn.gelu(g @ lp["f1w"].T + lp["f1b"],
                                approximate=False)
                g = _constrain_raw(g, "activations")
                x = x + g @ lp["f2w"].T + lp["f2b"]
            h = ln(x, params["ln_f_g"], params["ln_f_b"])
            if self._tie:
                logits = h @ params["embed"].T                  # (S, vocab)
            else:
                logits = h @ params["head_w"].T + params["head_b"]
            # pin the carry sharding so the scanned/returned cache matches
            # the engine's canonical placement (trace-once across dispatches)
            new_caches = _constrain_raw(new_caches, "kv_cache")
            return new_caches, logits

        return step

    def serving_verify_step(self, S: int, TOT: int, K1: int):
        """Speculative-decode verifier: one forward scoring ``K1`` = k + 1
        consecutive positions per slot against the same paged KV cache.

        Returns ``step(params, caches, toks, p) -> (new_caches, logits)``
        where ``toks`` is ``(S, K1)`` int32 — ``toks[s, 0]`` is the slot's
        current token (what plain decode would feed at ``p[s]``) and
        ``toks[s, j]`` for ``j >= 1`` the j-th drafted token, fed at
        position ``p[s] + j`` — and ``logits`` is ``(S, K1, vocab)``:
        row ``j`` is the model's prediction for position ``p[s] + j + 1``.

        Bit-exactness with :meth:`serving_step` is structural, not
        approximate: the dense projections run on the flattened
        ``(S * K1, U)`` row batch (each row the same dot product the
        single-step path computes), all ``K1`` K/V rows are scattered
        before any query attends, and attention runs per drafted position
        ``j`` through the IDENTICAL ``"bhd,bhtd->bht"`` einsum with the
        causal mask ``t <= p + j`` — so query ``j`` sees exactly the rows
        sequential decode would have written by step ``j``. A rejected
        draft leaves garbage K/V rows above the accept point; they sit
        beyond every surviving query's mask and are overwritten in order
        by the next dispatch before anything attends them, so rollback is
        host cursor arithmetic only."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        H = self.blocks[0].attn._heads
        U = self._units
        D = U // H
        scale = 1.0 / math.sqrt(D)

        def ln(x, g, b, eps=1e-5):
            m = jnp.mean(x, axis=-1, keepdims=True)
            v = jnp.var(x, axis=-1, keepdims=True)
            return (x - m) * lax.rsqrt(v + eps) * g + b

        def step(params, caches, toks, p):
            rows = jnp.arange(S)
            # (S, K1) per-slot write positions p..p+K1-1, clipped like the
            # single-step path; clipped duplicates land on row TOT-1, which
            # no live query ever attends (max fed position is limit - 1)
            pcs = jnp.clip(p[:, None] + jnp.arange(K1)[None, :], 0, TOT - 1)
            x = params["embed"][toks] + params["pos"][pcs]     # (S, K1, U)
            x = _constrain_raw(x, "activations")
            # query j may see rows 0..p+j only — the rows sequential decode
            # would have written by its j-th step
            mask = jnp.arange(TOT)[None, None, :] <= pcs[:, :, None]
            new_caches = caches
            for i, lp in enumerate(params["layers"]):
                h = ln(x, lp["ln1_g"], lp["ln1_b"])
                flat = h.reshape(S * K1, U)       # per-row dots == decode's
                q = (flat @ lp["qw"].T + lp["qb"]).reshape(S, K1, H, D)
                k = (flat @ lp["kw"].T + lp["kb"]).reshape(S, K1, H, D)
                v = (flat @ lp["vw"].T + lp["vb"]).reshape(S, K1, H, D)
                kv_dt = new_caches.dtype
                # every position's row lands before any query attends; the
                # j-loop keeps writes ordered so a clipped collision at
                # TOT-1 resolves deterministically (last write wins)
                for j in range(K1):
                    new_caches = new_caches.at[i, 0, rows, :, pcs[:, j]].set(
                        k[:, j].astype(kv_dt))
                    new_caches = new_caches.at[i, 1, rows, :, pcs[:, j]].set(
                        v[:, j].astype(kv_dt))
                K = new_caches[i, 0]              # (S, H, TOT, D)
                V = new_caches[i, 1]
                ctxs = []
                for j in range(K1):
                    s = jnp.einsum("bhd,bhtd->bht", q[:, j], K) * scale
                    s = jnp.where(mask[:, j][:, None, :], s, -1e30)
                    att = jax.nn.softmax(s, axis=-1)
                    ctxs.append(jnp.einsum("bht,bhtd->bhd", att, V))
                ctx = jnp.stack(ctxs, axis=1).reshape(S, K1, U)
                # same all-gather-before-row-matmul contract as serving_step
                # (replicated ow/f2w under the serving layout: no psum)
                flatc = _constrain_raw(ctx.reshape(S * K1, U), "activations")
                x = x + (flatc @ lp["ow"].T + lp["ob"]).reshape(S, K1, U)
                g = ln(x, lp["ln2_g"], lp["ln2_b"])
                g = jax.nn.gelu(g.reshape(S * K1, U) @ lp["f1w"].T
                                + lp["f1b"], approximate=False)
                g = _constrain_raw(g, "activations")
                x = x + (g @ lp["f2w"].T + lp["f2b"]).reshape(S, K1, U)
            h = ln(x, params["ln_f_g"], params["ln_f_b"])
            hf = h.reshape(S * K1, U)
            if self._tie:
                logits = hf @ params["embed"].T
            else:
                logits = hf @ params["head_w"].T + params["head_b"]
            new_caches = _constrain_raw(new_caches, "kv_cache")
            return new_caches, logits.reshape(S, K1, self._vocab)

        return step

    def serving_sample(self):
        """Per-slot next-token selection shared by the serving decode and
        chunked-prefill programs (``serving/kv.py``): returns
        ``sample(logits (S, V), temp (S,), topk (S,), seed (S,), pos (S,))
        -> (S,) int32``.

        Every sampling parameter is a TRACED array, so a mixed batch of
        greedy and sampled slots — or a change in the mix between
        dispatches — reuses one compiled program. ``temp[s] == 0`` selects
        plain argmax, bit-identical to the pre-sampling greedy path (the
        engine's bit-exactness contract vs solo ``generate``);
        ``temp[s] > 0`` samples from the temperature-scaled, top-k-masked
        logits with a key derived as ``fold_in(PRNGKey(seed[s]), pos[s])``.
        Keying on the ABSOLUTE position makes a request's stream a pure
        function of (weights, prompt, temperature, top-k, seed): the same
        request re-submitted under any slot assignment, chunk boundary, or
        prefill/decode split reproduces the same tokens — the
        seed-determinism contract. ``topk[s] <= 0`` means no top-k
        truncation; ties at the k-th logit are all kept (deterministic)."""
        import jax
        import jax.numpy as jnp

        V = self._vocab

        def sample(logits, temp, topk, seed, pos):
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

            def one(lg, tm, k, sd, p):
                kk = jnp.clip(jnp.where(k <= 0, V, k), 1, V)
                thresh = jnp.sort(lg)[V - kk]          # k-th largest logit
                masked = jnp.where(lg >= thresh, lg, -jnp.inf)
                key = jax.random.fold_in(jax.random.PRNGKey(sd), p)
                return jax.random.categorical(
                    key, masked / jnp.maximum(tm, 1e-6)).astype(jnp.int32)

            sampled = jax.vmap(one)(logits, temp, topk, seed, pos)
            return jnp.where(temp > 0, sampled, greedy)

        return sample

    def _build_generate(self, B: int, P: int, TOT: int, greedy: bool):
        """One compiled decode program for (batch B, prompt bucket P, scan
        bucket TOT): the TRUE prompt length arrives as a traced scalar, so
        natural-length prompts share programs per bucket instead of
        recompiling per length. The scan body is :meth:`serving_step` with
        every slot at the same position; the greedy program takes no rng
        key (argmax needs none — dropping it keeps the donation/signature
        surface minimal)."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        H = self.blocks[0].attn._heads
        D = self._units // H
        L = len(self.blocks)
        step = self.serving_step(B, TOT)

        def body_tok(params, caches, prev, prompt, t0, t):
            # prompt positions are FORCED; generated positions feed back
            tok = jnp.where(t < t0, prompt[:, jnp.minimum(t, P - 1)], prev)
            pos = jnp.full((B,), t, jnp.int32)
            return step(params, caches, tok, pos)

        if greedy:
            def run(params, prompt, t0):
                caches0 = jnp.zeros((L, 2, B, H, TOT, D),
                                    params["embed"].dtype)

                def body(carry, t):
                    caches, prev = carry
                    new_caches, logits = body_tok(params, caches, prev,
                                                  prompt, t0, t)
                    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    return (new_caches, nxt), nxt

                init = (caches0, jnp.zeros((B,), jnp.int32))
                _, outs = lax.scan(body, init,
                                   jnp.arange(TOT, dtype=jnp.int32))
                return outs.T                                   # (B, TOT)
        else:
            def run(params, prompt, t0, key):
                caches0 = jnp.zeros((L, 2, B, H, TOT, D),
                                    params["embed"].dtype)

                def body(carry, t):
                    caches, prev, key = carry
                    new_caches, logits = body_tok(params, caches, prev,
                                                  prompt, t0, t)
                    key, sub = jax.random.split(key)
                    nxt = jax.random.categorical(sub, logits, axis=-1) \
                        .astype(jnp.int32)
                    return (new_caches, nxt, key), nxt

                init = (caches0, jnp.zeros((B,), jnp.int32), key)
                _, outs = lax.scan(body, init,
                                   jnp.arange(TOT, dtype=jnp.int32))
                return outs.T                                   # (B, TOT)

        return jax.jit(run)

    def length_bucket(self, n: int) -> int:
        """32-token length bucket (capped at ``max_len``) — programs are
        shared per bucket; the serving KV admission uses the same rounding
        so engine caches and solo ``generate`` key identically."""
        return min(self._max_len, -(-n // 32) * 32)

    @staticmethod
    def batch_bucket(b: int) -> int:
        """Power-of-two batch bucket (1 stays 1): ragged last batches pad up
        instead of compiling a fresh decode program per exact batch size."""
        return 1 if b <= 1 else 1 << (b - 1).bit_length()

    def generate(self, tokens, max_new_tokens: int, greedy: bool = True,
                 seed: int = 0):
        """Autoregressive continuation: returns ``(B, T0 + max_new_tokens)``
        int tokens (prompt + generated). One compiled ``lax.scan`` over a
        static KV cache — the prompt prefills through the same step program,
        so decode costs one dispatch total, not one per token. Programs key
        on (batch bucket, prompt bucket, scan bucket): ragged batches pad to
        the next power of two and masked rows are sliced off the output."""
        import jax
        import jax.numpy as jnp

        from ... import autograd
        from ...ndarray.ndarray import NDArray
        from ...step_cache import ProgramCache
        raw = tokens.data if isinstance(tokens, NDArray) else jnp.asarray(tokens)
        B, T0 = raw.shape
        if T0 < 1:
            raise ValueError("generate needs a non-empty prompt (give a BOS "
                             "token for unconditional generation)")
        if any(p._data is None for p in self.collect_params().values()):
            with autograd.predict_mode():   # materialize deferred params
                self(NDArray(raw))
        total = T0 + int(max_new_tokens)
        if total > self._max_len:
            raise ValueError(f"prompt {T0} + {max_new_tokens} new exceeds "
                             f"max_len {self._max_len}")

        BB = self.batch_bucket(B)
        P, TOT = self.length_bucket(T0), self.length_bucket(total)
        key = (BB, P, TOT, bool(greedy))
        cache = getattr(self, "_gen_fns", None)
        if cache is None:
            cache = self._gen_fns = ProgramCache("generate")
        fn = cache.get_or_build(
            key, lambda: self._build_generate(BB, P, TOT, greedy))
        padded = jnp.zeros((BB, P), jnp.int32).at[:B, :T0].set(
            raw.astype(jnp.int32))
        if greedy:
            outs = fn(self._gen_params(), padded, jnp.int32(T0))
        else:
            outs = fn(self._gen_params(), padded, jnp.int32(T0),
                      jax.random.key(seed))
        # outs[t] is the token sampled AFTER position t; stitch prompt + tail
        gen = outs[:B, T0 - 1:total - 1]
        return NDArray(jnp.concatenate([raw.astype(jnp.int32), gen], axis=1))


_PRESETS = {
    # name: (units, layers, heads, max_len)
    "tiny": (64, 2, 2, 256),            # tests
    "small": (512, 6, 8, 1024),         # ~35M params at 16k vocab
    "base": (768, 12, 12, 1024),        # GPT-2 124M-class
    "flagship": (1024, 8, 16, 2048),    # the bench workload: MXU-dominated
    "wide": (2048, 4, 16, 2048),        # fewer/wider blocks: 2048x8192 FFN
                                        # matmuls saturate the MXU (64.9% MFU
                                        # measured on v5e vs 44% at d1024 L8)
}


def transformer_lm(preset: str = "small", vocab_size: int = 16384, **kwargs):
    """Factory over the preset table (model-zoo surface parity with
    ``vision.get_model``)."""
    try:
        units, layers, heads, max_len = _PRESETS[preset]
    except KeyError:
        raise ValueError(
            f"unknown preset {preset!r}; choose from {sorted(_PRESETS)}")
    cfg = dict(units=units, num_layers=layers, num_heads=heads,
               max_len=max_len)
    cfg.update(kwargs)
    return TransformerLM(vocab_size, **cfg)
