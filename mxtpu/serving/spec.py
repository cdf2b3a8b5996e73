"""Speculative multi-token decode — the draft side of draft-and-verify.

The serving engine's speculative path splits one decode turn into two
halves with an exact greedy contract between them:

* **draft** (this module, host-side) — a :class:`Drafter` proposes up to
  ``k`` continuation tokens per slot from cheap n-gram statistics; a miss
  proposes nothing and the slot runs a plain decode step inside the same
  compiled verify program (``dlen == 0``), so drafting can never stall or
  retrace the engine.
* **verify** (``kv.build_verify``, on-device) — ONE batched target forward
  scores all ``k + 1`` positions per slot; the accepted prefix is exactly
  the run of drafts the target model itself would have produced, plus one
  bonus token, so greedy output is bit-identical to plain decode no matter
  what the drafter proposes.

:class:`NgramDrafter` is the default proposer and needs no second model:
it combines a *self-context* suffix lookup (the request's own
prompt + generated stream — prompt-lookup decoding, exact on the loops
and copy-spans real decodes are full of) with the
:meth:`~mxtpu.serving.kv.PrefixCache.ngram_lookup` side index over the
radix tree's token-id paths (cross-request prompt statistics, LRU with
the tree). The :class:`Drafter` base is the pluggable seam for a small
draft LM from the model zoo later — anything returning token ids fits;
proposals are advisory by construction.

Enable per engine with ``ServingEngine(spec=SpecConfig(k=...))``, the
``ServingConfig.spec`` field, or ``MXTPU_SPEC_DECODE=<k>``; default off
and byte-identical without it. See ``docs/serving.md`` for the turn state
machine and the accept-length diagnosis table.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import List, Optional

__all__ = ["SpecConfig", "parse_spec", "Drafter", "NgramDrafter",
           "ModelDrafter"]


@dataclass(frozen=True)
class SpecConfig:
    """Resolved speculative-decode configuration for one serving engine.

    ``k`` is the draft depth — the verify program scores ``k + 1``
    positions per slot and is keyed on (slots, KV bucket, k), so an engine
    holds ONE ``k`` for its lifetime (no retrace churn). ``ngram`` /
    ``min_ngram`` bound the suffix match the default drafter tries
    (longest first); ``scan`` caps how far back the self-context search
    walks. ``drafter`` swaps in a custom :class:`Drafter` (a draft LM
    seam); None builds an :class:`NgramDrafter` wired to the engine's
    prefix cache."""
    k: int = 4
    ngram: int = 3
    min_ngram: int = 2
    scan: int = 1024
    drafter: Optional["Drafter"] = None

    def __post_init__(self):
        if not 1 <= self.k <= 16:
            raise ValueError(f"spec draft depth k must be in 1..16, "
                             f"got {self.k}")
        if not 1 <= self.min_ngram <= self.ngram:
            raise ValueError(
                f"need 1 <= min_ngram <= ngram, got "
                f"min_ngram={self.min_ngram} ngram={self.ngram}")


def parse_spec(value) -> Optional[SpecConfig]:
    """Parse ``MXTPU_SPEC_DECODE`` / ``ServingEngine(spec=...)``: a
    :class:`SpecConfig` passes through; an int (or int string) is the
    draft depth ``k``; None / '' / 0 disables (the byte-identical
    default). Anything else raises — speculation is never silently off
    when asked for."""
    if value is None or value == "":
        return None
    if isinstance(value, SpecConfig):
        return value
    try:
        k = int(value)
    except (TypeError, ValueError):
        raise ValueError(
            f"spec must be a SpecConfig or an integer draft depth, "
            f"got {value!r}") from None
    return SpecConfig(k=k) if k > 0 else None


def spec_from_env() -> Optional[SpecConfig]:
    """The environment fallback of the engine's knob resolution chain
    (constructor kwarg > ``ServingConfig.spec`` > ``MXTPU_SPEC_DECODE``)."""
    return parse_spec(os.environ.get("MXTPU_SPEC_DECODE"))


class Drafter:
    """The pluggable proposer seam. ``propose(context, k)`` returns up to
    ``k`` token ids predicted to continue ``context`` (the request's full
    prompt + generated stream, oldest first) — an empty list on a miss.
    Called on the engine's scheduler thread between dispatches, for greedy
    slots only; implementations must be cheap and must not touch jax
    state (a draft *model* belongs behind its own compiled program and
    feeds its tokens back through this same interface)."""

    def propose(self, context: List[int], k: int) -> List[int]:
        raise NotImplementedError

    def stats(self) -> dict:
        """Optional counters merged into the engine's serving stats."""
        return {}


class NgramDrafter(Drafter):
    """Model-free n-gram proposer: self-context suffix lookup first, then
    the :class:`~mxtpu.serving.kv.PrefixCache` radix-tree side index.

    The self-context pass finds the most recent earlier occurrence of the
    stream's final ``n``-gram (``n`` from ``ngram`` down to ``min_ngram``,
    longest match wins, searching at most ``scan`` positions back) and
    proposes the tokens that followed it — exact whenever decode revisits
    a span it has produced or read before. On a miss, the tree's
    ``ngram_lookup`` answers from every cached prompt path, so a slot can
    draft from OTHER requests' prompts before its own stream has any
    statistics. Either source may be absent; both missing is a clean
    ``[]`` (the slot decodes plain this turn)."""

    def __init__(self, prefix_cache=None, ngram: int = 3, min_ngram: int = 2,
                 scan: int = 1024):
        self._prefix = prefix_cache
        self.ngram = int(ngram)
        self.min_ngram = int(min_ngram)
        self.scan = int(scan)

    @classmethod
    def from_config(cls, cfg: SpecConfig, prefix_cache=None):
        return cls(prefix_cache=prefix_cache, ngram=cfg.ngram,
                   min_ngram=cfg.min_ngram, scan=cfg.scan)

    def propose(self, context: List[int], k: int) -> List[int]:
        if k <= 0 or not context:
            return []
        got = self._self_lookup(context, k)
        if got:
            return got
        if self._prefix is not None:
            return self._prefix.ngram_lookup(context[-self.ngram:], k)
        return []

    def _self_lookup(self, context: List[int], k: int) -> List[int]:
        L = len(context)
        for n in range(min(self.ngram, L - 1), self.min_ngram - 1, -1):
            pat = context[L - n:]
            lo = max(0, L - n - self.scan)
            for s in range(L - n - 1, lo - 1, -1):
                if context[s:s + n] == pat:
                    cont = context[s + n:s + n + k]
                    if cont:
                        return list(cont)
        return []


class ModelDrafter(Drafter):
    """Draft-LM proposer behind the :class:`Drafter` seam: a small
    ``transformer_lm`` greedily continues the slot's context and its
    tokens ride the SAME advisory verify contract as the n-gram drafter —
    a weak draft model can slow decode down, never corrupt it.

    The draft model runs its OWN cached decode program (the model zoo's
    ``generate`` path), fully separate from the target engine's program
    caches. To keep that cache bounded, the context is left-truncated to
    the largest fitting bucket of ``buckets`` — at most ``len(buckets)``
    compiled draft programs per draft depth, regardless of how long served
    requests grow. Truncation only costs proposal quality (the verify
    step re-scores everything with the full-context target); a context
    shorter than the smallest bucket proposes nothing and the slot decodes
    plain that turn.

    Pair it with the engine via ``SpecConfig(k=..., drafter=
    ModelDrafter(draft_net))``."""

    BUCKETS = (8, 32, 64)

    def __init__(self, model, buckets=BUCKETS):
        self._model = model
        self.buckets = tuple(sorted(int(b) for b in buckets))
        if not self.buckets or self.buckets[0] < 1:
            raise ValueError(f"bad draft buckets {buckets!r}")
        self.calls = 0
        self.proposed = 0

    def propose(self, context: List[int], k: int) -> List[int]:
        if k <= 0:
            return []
        b = 0
        for cand in self.buckets:
            if cand <= len(context):
                b = cand
        if b == 0:
            return []
        if b + k > self._model._max_len:
            return []
        import numpy as np
        from .. import nd
        tail = np.asarray(context[-b:], np.int32)[None, :]
        out = self._model.generate(nd.array(tail), k)
        toks = [int(t) for t in np.asarray(out.data)[0, b:]]
        self.calls += 1
        self.proposed += len(toks)
        return toks

    def stats(self) -> dict:
        return {"draft_lm_calls": self.calls,
                "draft_lm_tokens": self.proposed}
