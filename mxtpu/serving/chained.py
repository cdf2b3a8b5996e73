"""Dispatch-amortized serving — the public form of the chained-forward trick.

Per-call inference pays one jit dispatch per forward, and at small batch
that fixed cost can gate serving below the chip's real rate (ResNet-50 b1:
87 img/s per-call vs 589 chained in rounds 3-5, a retired runtime; not
re-measured on the v5e). The reference has no
equivalent layer; here the amortization belongs IN the framework.

``ChainedPredictor`` compiles ONE program that scans over a stack of n
batches, so a chain of n forwards costs one dispatch + n compute steps.
``Module.predict(..., chain=n)`` uses it transparently.

Use the PLAIN (non-hybridized) block: a hybridized CachedOp draws rng keys at
its own trace time, which leaks tracers when traced inside the outer jit
(``tests/test_serving.py`` chains plain blocks for that reason).
"""

from __future__ import annotations

from typing import Iterable, List, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .. import autograd
from ..ndarray.ndarray import NDArray
from ..step_cache import ProgramCache

__all__ = ["ChainedPredictor"]


class ChainedPredictor:
    """Throughput serving over a single-input block.

    ``chain`` batches are stacked to ``(chain, B, ...)`` and one compiled
    ``lax.scan`` produces all outputs; programs are cached per
    (chain, batch shape, dtype) — a short tail chain compiles once more.
    The cache is a bounded LRU (``MXTPU_SERVING_PROGRAM_CACHE``) counted
    under ``serving_chained`` in ``profiler.get_compile_stats()``, so
    serving-side shape churn neither grows without limit nor hides from the
    retrace forensics.
    """

    def __init__(self, block, chain: int = 8):
        if chain < 1:
            raise ValueError("chain must be >= 1")
        if getattr(block, "_active", False):
            raise ValueError(
                "ChainedPredictor needs the PLAIN block: a hybridized "
                "CachedOp draws rng keys at its own trace time and leaks "
                "tracers inside the chain's jit — call "
                "block.hybridize(False) first")
        self._block = block
        self.chain = int(chain)
        self._fns = ProgramCache("serving_chained")

    def _fn(self, n: int, shape: Tuple[int, ...], dtype):
        key = (n,) + tuple(shape) + (str(dtype),)
        block = self._block

        def build():
            def run(stack):
                def step(carry, xb):
                    with autograd.predict_mode():
                        out = block(NDArray(xb))
                    outs = (tuple(o.data for o in out)
                            if isinstance(out, (tuple, list))
                            else (out.data,))
                    return carry, outs
                _, outs = lax.scan(step, jnp.zeros((), jnp.float32), stack)
                return outs
            return jax.jit(run)

        return self._fns.get_or_build(key, build)

    def predict_stack(self, stack) -> List[NDArray]:
        """(n, B, ...) stacked batches → list over outputs of (n, B, ...)."""
        raw = stack.data if isinstance(stack, NDArray) else jnp.asarray(stack)
        outs = self._fn(raw.shape[0], raw.shape[1:], raw.dtype)(raw)
        return [NDArray(o) for o in outs]

    def predict_batches(self, batches: Iterable) -> List[List[NDArray]]:
        """Consume an iterable of same-shape ``(B, ...)`` arrays; returns one
        ``[outputs...]`` list per input batch, in order. Dispatches once per
        ``chain`` batches (plus once for a shorter tail)."""
        results: List[List[NDArray]] = []
        buf: List = []

        def flush():
            if not buf:
                return
            raws = [b.data if isinstance(b, NDArray) else jnp.asarray(b)
                    for b in buf]
            stacked = jnp.stack(raws)
            outs = self.predict_stack(NDArray(stacked))
            for i in range(len(buf)):
                results.append([NDArray(o.data[i]) for o in outs])
            buf.clear()

        for b in batches:
            shape = tuple(b.shape)
            if buf and tuple(buf[0].shape) != shape:
                flush()                 # odd-shaped batch starts a new chain
            buf.append(b)
            if len(buf) == self.chain:
                flush()
        flush()
        return results
