"""ZeRO sharded data parallelism — the TPU-native re-imagining of the
reference's KVStore server sharding (SURVEY §1 layer 6, ``include/mxnet/kvstore.h``):
ps-lite never holds the full optimizer state on one worker — keys are sharded across
servers, the update runs on the shard owner, and workers pull back only what they
need. Here the same ownership split is expressed in ONE fused XLA program, staged
per ZeRO (Rajbhandari et al., 2020) via ``MXTPU_ZERO_STAGE`` (see
``parallel/fsdp.py``):

* gradients are flattened into a small number of dtype-homogeneous **buckets**
  (``MXTPU_ZERO_BUCKET_MB``, default 32), each param padded to a multiple of the
  data degree N and packed into an N-interleaved flat layout (device d owns the
  d-th chunk of every member param);
* each per-param gradient is constrained to the data-axis sharding right after
  the backward — GSPMD converts the pending per-axis reduction into a
  **reduce-scatter** (the partial-sum → sharded-consumer optimization;
  not timed against an all-reduce on the v5e) — and the
  owned shards are packed with a ``shard_map`` local concat. The per-param
  constraint + explicit local pack is load-bearing: concatenating partial-sum
  gradients BEFORE the constraint trips a partitioner mis-reduction on
  multi-axis meshes (an extra reduction over the idle axis, verified on
  (dp, tp)), which is why PR 4 had to fall back to replicated updates there.
  Per-param resolution over named axes is exact on any mesh, so the fallback
  is gone and ZeRO composes with tensor parallelism;
* optimizer slots live ONLY as data-sharded flat buckets (1/N of the state
  bytes per device, ``NamedSharding`` so checkpoint capture/restore keeps
  working), and the elementwise update runs on the shard;
* the updated packed shard is constrained back to replicated — one
  **all-gather** per bucket — and de-interleaved with static slices into the
  full parameters the next forward consumes. At stage 3 (FSDP) shardable
  params never enter buckets at all: they stay resident 1/N on the ``fsdp``
  axis and take the per-param sharded update (``parallel/fsdp.py``).

Because everything happens inside the jitted step, XLA schedules the per-bucket
collectives against the remaining backward/update compute (the reference's
push/pull priority-overlap trick becomes latency hiding for free) instead of
serializing one monolithic all-reduce at the step boundary.

A data degree of 1 shards nothing: no reduce-scatter, no all-gather, no 1/N
of anything. What is left of a bucket is the packing, and on a TPU a
``[d, 4d]`` matrix in its tiled layout is not a contiguous stretch of a flat
array, so every pack and unpack of a matrix is a real relayout (a fifth of a
1.3B model's step on one v5e, PERF.md PR 25). ``DataParallelTrainer``
therefore passes ``eligible`` False for every matrix there and gives it the
per-parameter update (``step_cache.build_update_all``: the same
``_preprocess_grad`` + ``_kernel`` on the same numbers, so the result is
bit-identical). Leaves that are flat already (biases, norm scales) stay
bucketed: their packing is a plain copy, and one buffer in place of hundreds
spares the runtime an allocation for each of them every step, which is what
the host's dispatch time follows (PERF.md PR 25). Gradient compression is
the exception: its quantisation and error-feedback residual exist only on
the bucket path, so a trainer with ``compression_params`` keeps every
replicated parameter bucketed at every degree. ``StepExecutor``
(``step_cache.py``) still buckets everything at degree 1: its ``zopt:``
checkpoint slots are what a resume onto another degree re-packs (ROADMAP
S10).

Eligibility: the optimizer must be **elementwise** (``Optimizer.elementwise``) —
bucket packing must not change the math (SGD/NAG/Adam/RMSProp/…); norm-based
(LBSGD) and noise-injecting (SGLD) optimizers fall back to the replicated path.
"""

from __future__ import annotations

import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .mesh import Mesh, data_axis_names

__all__ = ["zero_enabled", "zero_bucket_bytes", "supports_zero", "ZeroLayout",
           "build_zero_update", "build_grad_pack", "init_zero_states",
           "state_shardings", "comm_dtype_of"]


def zero_enabled() -> bool:
    """Opt-out env: ``MXTPU_ZERO=0`` restores the replicated-psum path."""
    return os.environ.get("MXTPU_ZERO", "1") != "0"


def zero_bucket_bytes() -> int:
    """Bucket size cap (``MXTPU_ZERO_BUCKET_MB``, default 32 MB): small enough
    that per-bucket collectives interleave with backward compute, large enough
    to amortize collective launch latency."""
    try:
        mb = float(os.environ.get("MXTPU_ZERO_BUCKET_MB", "32"))
    except ValueError:
        mb = 32.0
    return max(1, int(mb * (1 << 20)))


def supports_zero(opt) -> bool:
    """An optimizer qualifies when its update math is elementwise (bucketing
    params into one flat array is then exact) and it uses the standard
    ``_kernel`` protocol (no custom ``update`` override like SGLD's)."""
    from ..optimizer import Optimizer
    return (getattr(opt, "elementwise", False)
            and type(opt).update is Optimizer.update
            and not getattr(opt, "multi_precision", False))


def comm_dtype_of(compression_params: Optional[dict]):
    """Comm-payload dtype selected by ``KVStore.set_gradient_compression``:
    ``fp16``/``bf16`` lower the bucket payload with an error-feedback residual;
    ``2bit`` keeps the reference's sign-threshold semantics. ``None`` → exact."""
    if not compression_params:
        return None
    kind = compression_params.get("type", "2bit")
    table = {"fp16": jnp.float16, "bf16": jnp.bfloat16, "2bit": "2bit"}
    if kind not in table:
        raise ValueError(
            f"unknown gradient compression type {kind!r}; supported kinds: "
            f"{sorted(table)} (reference gradient_compression.h ships 2bit; "
            "fp16/bf16 lower the comm payload dtype with an error-feedback "
            "residual)")
    return table[kind]


class ZeroBucket:
    """One dtype/lr-mult/wd-mult-homogeneous gradient bucket.

    Packed layout: every member param is padded to ``psizes[k]`` (a multiple
    of N) and the bucket is N-INTERLEAVED — viewing the flat bucket as
    ``(N, padded // N)``, row d is the concat of every param's d-th chunk.
    Device d therefore owns a contiguous slice of each param, the pack is a
    shard-local concat (no cross-device data motion), and the layout degrades
    to a plain concatenation at N = 1."""

    __slots__ = ("indices", "sizes", "psizes", "shapes", "dtype", "lr_mult",
                 "wd_mult", "unpadded", "padded")

    def __init__(self, dtype, lr_mult: float, wd_mult: float):
        self.indices: List[int] = []
        self.sizes: List[int] = []
        self.psizes: List[int] = []
        self.shapes: List[tuple] = []
        self.dtype = dtype
        self.lr_mult = float(lr_mult)
        self.wd_mult = float(wd_mult)
        self.unpadded = 0
        self.padded = 0

    @property
    def nbytes(self) -> int:
        return self.unpadded * np.dtype(self.dtype).itemsize

    def describe(self) -> dict:
        return {"indices": list(self.indices), "sizes": list(self.sizes),
                "psizes": list(self.psizes), "dtype": str(np.dtype(self.dtype)),
                "unpadded": self.unpadded,
                "lr_mult": self.lr_mult, "wd_mult": self.wd_mult}


def _pack_flat_host(flats: Sequence[np.ndarray], psizes: Sequence[int],
                    n: int) -> np.ndarray:
    """Host-side interleave: pad each flat to its psize and stack the
    per-device chunks column-wise → the packed global bucket."""
    cols = []
    for a, ps in zip(flats, psizes):
        a = np.ravel(np.asarray(a))
        flat = np.zeros((ps,), a.dtype)
        flat[:a.shape[0]] = a
        cols.append(flat.reshape(n, ps // n))
    mat = np.concatenate(cols, axis=1) if len(cols) > 1 else cols[0]
    return np.ascontiguousarray(mat.reshape(-1))


def _unpack_flat_host(packed: np.ndarray, sizes: Sequence[int],
                      psizes: Sequence[int], n: int) -> List[np.ndarray]:
    """Inverse of ``_pack_flat_host``: per-param unpadded flats."""
    packed = np.ravel(np.asarray(packed))
    mat = packed.reshape(n, packed.shape[0] // n)
    outs, off = [], 0
    for sz, ps in zip(sizes, psizes):
        step = ps // n
        outs.append(np.ascontiguousarray(
            mat[:, off:off + step].reshape(-1)[:sz]))
        off += step
    return outs


class ZeroLayout:
    """Deterministic bucket layout over a parameter list.

    Grouping (by dtype and per-param lr/wd multiplier, chunked at
    ``bucket_bytes``) is independent of the data degree — only the per-param
    PADDING (and hence the interleave) depends on N — so a checkpointed state
    restores onto a different degree by de-interleaving with the saved
    N/psizes and re-packing with the current ones (``adopt_states``).

    ``eligible`` masks params OUT of the buckets (``passthrough``): at
    stages 1/2 that is the tensor-parallel params (their grads reduce over
    the tp axis, not dp); at stage 3 it is additionally every fsdp-shardable
    param, which gets the per-param resident-sharded update instead.
    """

    def __init__(self, params: Sequence, lr_mults: Sequence[float],
                 wd_mults: Sequence[float], dp: int,
                 eligible: Optional[Sequence[bool]] = None,
                 bucket_bytes: Optional[int] = None):
        self.dp = max(1, int(dp))
        bucket_bytes = bucket_bytes or zero_bucket_bytes()
        self.buckets: List[ZeroBucket] = []
        self.passthrough: List[int] = []
        open_buckets: Dict[tuple, ZeroBucket] = {}
        for i, w in enumerate(params):
            if eligible is not None and not eligible[i]:
                self.passthrough.append(i)
                continue
            dt = np.dtype(str(w.dtype))
            key = (str(dt), float(lr_mults[i]), float(wd_mults[i]))
            b = open_buckets.get(key)
            if b is None or b.nbytes >= bucket_bytes:
                b = ZeroBucket(dt, lr_mults[i], wd_mults[i])
                open_buckets[key] = b
                self.buckets.append(b)
            n = int(np.prod(w.shape)) if len(w.shape) else 1
            b.indices.append(i)
            b.sizes.append(n)
            b.shapes.append(tuple(w.shape))
            b.unpadded += n
        for b in self.buckets:
            b.psizes = [-(-s // self.dp) * self.dp for s in b.sizes]
            b.padded = sum(b.psizes)

    # -- identity ----------------------------------------------------------
    def fingerprint(self) -> tuple:
        return (self.dp, tuple(self.passthrough),
                tuple((tuple(b.indices), b.unpadded, str(b.dtype),
                       b.lr_mult, b.wd_mult) for b in self.buckets))

    def describe(self) -> dict:
        """JSON-able layout record for checkpoint meta."""
        return {"dp": self.dp, "passthrough": list(self.passthrough),
                "buckets": [b.describe() for b in self.buckets]}

    def compatible_with(self, desc: dict) -> bool:
        """True when ``desc`` (a saved ``describe()``) has the same grouping —
        dp may differ (the interleave is re-derived from the saved psizes),
        bucket membership may not. Pre-packed-format checkpoints (no psizes
        recorded) are incompatible: their flat layout cannot be de-interleaved."""
        if not desc:
            return False
        saved = desc.get("buckets", [])
        if len(saved) != len(self.buckets):
            return False
        for s, b in zip(saved, self.buckets):
            if (s.get("indices") != list(b.indices)
                    or s.get("sizes") != list(b.sizes)
                    or not s.get("psizes")
                    or np.dtype(s.get("dtype")) != b.dtype):
                return False
        return True

    # -- accounting --------------------------------------------------------
    def step_comm(self) -> dict:
        """Analytic per-device comm bytes for ONE step: ring reduce-scatter
        moves (N-1)/N of each bucket per device, the parameter all-gather the
        same — vs 2·(N-1)/N of the FULL gradient for a ring all-reduce."""
        n = self.dp
        frac = (n - 1) / n if n > 1 else 0.0
        total = sum(b.nbytes for b in self.buckets)
        return {
            "bytes_reduced": int(total * frac),
            "bytes_gathered": int(total * frac),
            "bucket_count": len(self.buckets),
            "shard_bytes": int(sum(-(-b.nbytes // n) for b in self.buckets)),
            "dp": n,
        }

    def state_bytes_per_device(self, states: Sequence[Tuple]) -> int:
        """Actual optimizer-slot bytes resident per device (sharded slots
        count 1/N; scalar/replicated slots count fully)."""
        total = 0
        for b, st in zip(self.buckets, states):
            for s in st:
                nb = int(np.dtype(str(s.dtype)).itemsize
                         * int(np.prod(s.shape))) if hasattr(s, "shape") else 0
                total += nb // self.dp if getattr(s, "shape", ()) == \
                    (b.padded,) else nb
        return total

    # -- state shard/unshard ----------------------------------------------
    def data_spec(self, mesh: Mesh) -> P:
        """1-D PartitionSpec over every data axis of ``mesh`` (dp×fsdp)."""
        axes = data_axis_names(mesh)
        return P(axes if len(axes) > 1 else axes[0])

    def shard_spec(self, mesh: Mesh):
        # dp=1: the data spec and P() are the same layout, but XLA normalizes
        # outputs to P() — use P() up front so the step signature (which
        # includes shardings) stays stable across steps (no retrace)
        if self.dp == 1:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, self.data_spec(mesh))

    def repl_spec(self, mesh: Mesh):
        return NamedSharding(mesh, P())

    def adopt_states(self, saved_arrays: Dict[str, np.ndarray],
                     saved_desc: dict, mesh: Mesh):
        """Re-place checkpointed bucket states onto THIS layout's mesh/dp:
        de-interleave with the SAVED dp/psizes, re-pack with the current ones,
        place sharded. Returns ``(states, residuals)`` or ``None`` when the
        saved layout is incompatible (caller starts fresh)."""
        if not self.compatible_with(saved_desc):
            return None
        from .data_parallel import _place
        old_n = max(1, int(saved_desc.get("dp", 1)))
        saved_buckets = saved_desc.get("buckets", [])
        shard = self.shard_spec(mesh)
        repl = self.repl_spec(mesh)

        def repack(raw: np.ndarray, b: ZeroBucket, old_ps: List[int]):
            flats = _unpack_flat_host(raw, b.sizes, old_ps, old_n)
            return _pack_flat_host(flats, b.psizes, self.dp)

        states: List[Tuple] = []
        residuals: List[Any] = []
        for bi, b in enumerate(self.buckets):
            old_ps = [int(v) for v in saved_buckets[bi]["psizes"]]
            old_padded = sum(old_ps)
            st = []
            j = 0
            while f"zopt:{bi}:{j}" in saved_arrays:
                raw = np.asarray(saved_arrays[f"zopt:{bi}:{j}"])
                if raw.ndim == 1 and raw.shape[0] == old_padded:
                    st.append(_place(repack(raw, b, old_ps), shard))
                else:                       # scalar/replicated slot
                    st.append(_place(raw, repl))
                j += 1
            states.append(tuple(st))
            rk = f"zres:{bi}"
            if rk in saved_arrays:
                raw = np.asarray(saved_arrays[rk])
                if raw.shape[0] == old_padded:
                    residuals.append(_place(repack(raw, b, old_ps), shard))
                else:
                    residuals.append(
                        _place(np.zeros((b.padded,), raw.dtype), shard))
            else:
                residuals.append(None)
        return states, residuals


# ---------------------------------------------------------------------------
# state init
# ---------------------------------------------------------------------------


def _bucket_weight(layout: ZeroLayout, b: ZeroBucket, param_raws):
    """Packed (N-interleaved) bucket weight, traceable. Params carry no
    pending reduction, so reshape/concat are layout-only here — the
    partitioner hazard is specific to partial-sum GRADIENTS."""
    n = layout.dp
    cols = []
    for i, sz, ps in zip(b.indices, b.sizes, b.psizes):
        flat = jnp.ravel(param_raws[i]).astype(b.dtype)
        if ps > sz:
            flat = jnp.pad(flat, (0, ps - sz))
        cols.append(flat.reshape(n, ps // n))
    mat = jnp.concatenate(cols, axis=1) if len(cols) > 1 else cols[0]
    return mat.reshape(-1)


def _unpack_bucket(new_w_full, b: ZeroBucket, n: int):
    """Static-slice de-interleave of a REPLICATED packed bucket back into
    per-param flats (runs after the all-gather, no pending reductions)."""
    mat = new_w_full.reshape(n, b.padded // n)
    outs, off = [], 0
    for sz, ps in zip(b.sizes, b.psizes):
        step = ps // n
        outs.append(mat[:, off:off + step].reshape(-1)[:sz])
        off += step
    return outs


def unpack_states(layout: ZeroLayout,
                  states: Sequence[Tuple]) -> Dict[int, Tuple]:
    """``{parameter index: its optimizer slots in the parameter's own
    shape}`` for every bucketed parameter: each bucket-shaped slot is
    de-interleaved as the updated weights are; a slot of another shape (a
    scalar schedule) belongs to the whole bucket and is handed to each of
    its parameters as it is. Traceable."""
    out: Dict[int, Tuple] = {}
    for b, st in zip(layout.buckets, states):
        per_slot = [
            [flat.reshape(shape) for flat, shape in
             zip(_unpack_bucket(s, b, layout.dp), b.shapes)]
            if getattr(s, "shape", None) == (b.padded,)
            else [s] * len(b.indices) for s in st]
        for k, i in enumerate(b.indices):
            out[i] = tuple(slot[k] for slot in per_slot)
    return out


def init_zero_states(opt, layout: ZeroLayout, param_raws, mesh: Mesh,
                     with_residual: bool = False):
    """Create per-bucket optimizer slots, placed data-sharded (1/N resident
    per device). Slot shapes follow ``create_state`` on the flat bucket
    "weight" (so DCASGD's prev-weight copy, Nadam's scalar schedule, … all
    work); bucket-shaped slots shard over the data axes, scalar slots stay
    replicated. One program makes them all, so every slot and residual is a
    buffer of its own and a step may donate each
    (``step_cache.create_distinct``)."""
    from ..ndarray.ndarray import NDArray
    from ..step_cache import create_distinct
    if not layout.buckets:
        return [], []
    residual_sh = layout.shard_spec(mesh) if with_residual else None

    def create(raws):
        states = [tuple(opt.create_state(
            ("zero", bi), NDArray(_bucket_weight(layout, b, raws))))
            for bi, b in enumerate(layout.buckets)]
        residuals = [jnp.zeros((b.padded,), jnp.float32)
                     if with_residual else None for b in layout.buckets]
        return states, residuals

    return create_distinct(
        create,
        lambda shapes: (state_shardings(layout, shapes[0], mesh),
                        [residual_sh] * len(layout.buckets)),
        list(param_raws))


def state_shardings(layout: ZeroLayout, states, mesh: Mesh):
    """Matching NamedSharding pytree for jit in/out_shardings."""
    shard = layout.shard_spec(mesh)
    repl = layout.repl_spec(mesh)
    return [tuple(shard if getattr(s, "shape", None) == (b.padded,) else repl
                  for s in st)
            for b, st in zip(layout.buckets, states)]


# ---------------------------------------------------------------------------
# the traced update
# ---------------------------------------------------------------------------


def _build_bucket_pack(layout: ZeroLayout, mesh: Mesh):
    """Traceable per-bucket gradient pack: per-param pad → per-param data-axis
    sharding constraint (GSPMD resolves each pending reduction as a
    reduce-scatter over the NAMED axes — exact on any mesh) → shard_map local
    concat into the packed shard. The per-param constraint must come BEFORE
    any concatenation: concat of partial-sum grads is what the partitioner
    mis-reduces on multi-axis meshes."""
    n = layout.dp
    spec1d = layout.data_spec(mesh)
    shard = layout.shard_spec(mesh)

    def pack_bucket(b: ZeroBucket, grads, dt):
        flats = []
        for i, sz, ps in zip(b.indices, b.sizes, b.psizes):
            f = jnp.ravel(grads[i])
            if ps > sz:
                f = jnp.pad(f, (0, ps - sz))
            f = f.astype(dt)
            if n > 1:
                f = jax.lax.with_sharding_constraint(f, shard)
            flats.append(f)
        if len(flats) == 1:
            return flats[0]
        if n == 1:
            return jnp.concatenate(flats)
        from .collectives import shard_map_compat
        local_concat = shard_map_compat(
            lambda *locs: jnp.concatenate(locs), mesh,
            in_specs=tuple(spec1d for _ in flats),
            out_specs=spec1d, check=False)
        return local_concat(*flats)

    return pack_bucket


def build_grad_pack(layout: ZeroLayout, mesh: Mesh):
    """Traceable ``pack_grads(grads) -> [packed f32 bucket shards]`` — the
    ZeRO-2 entry point: micro-batch loops reduce-scatter each micro-gradient
    into the 1/N packed shard and accumulate THAT, so accumulation memory is
    the bucket shard, never the replicated gradient."""
    pack_bucket = _build_bucket_pack(layout, mesh)

    def pack_grads(grads):
        return [pack_bucket(b, grads, jnp.float32) for b in layout.buckets]

    return pack_grads


def build_zero_update(opt, layout: ZeroLayout, mesh: Mesh,
                      comm_dtype=None, compression_params: Optional[dict] = None):
    """One traceable function applying ``opt`` to every bucketed parameter
    through the reduce-scatter → shard-update → all-gather dataflow.

    Returns ``zero_update(params, grads, states, residuals, lr, wd, rescale,
    clip, t, packed_grads=None) -> (new_params, new_states, new_residuals)``.
    ``params`` and ``grads`` are the full per-param lists; passthrough
    (non-bucketed: tensor-parallel, or fsdp-resident at stage 3) parameters
    are NOT updated here — callers compose with ``build_update_all`` for
    those. ``packed_grads`` (from ``build_grad_pack``, stage 2) bypasses the
    gradient pack when the caller already holds reduce-scattered shards.

    The sharding constraints are the whole trick: the per-param constraint
    lands on each gradient while its cross-data-axis reduction is still
    pending, so GSPMD materializes a per-axis reduce-scatter; the final
    constraint forces the updated packed shard back to replicated, an
    all-gather. Per-bucket, so XLA interleaves the collectives with the rest
    of the backward/update instead of fencing the step on one monolithic
    all-reduce.
    """
    shard = layout.shard_spec(mesh)
    repl = layout.repl_spec(mesh)
    n = layout.dp
    pack_bucket = _build_bucket_pack(layout, mesh)
    clipped = opt.clip_gradient is not None
    thr = float((compression_params or {}).get("threshold", 0.5))

    # the bucket update's device operations read "<caller's scope>/zero"
    @jax.named_scope("zero")
    def zero_update(params, grads, states, residuals, lr, wd, rescale, clip, t,
                    packed_grads=None):
        new_params = list(params)
        new_states = []
        new_residuals = []
        for bi, b in enumerate(layout.buckets):
            dt = jnp.dtype(str(b.dtype))
            if packed_grads is not None:
                g_shard = packed_grads[bi].astype(dt)
            else:
                g_shard = pack_bucket(b, grads, dt)
            w_full = _bucket_weight(layout, b, params)
            w_shard = jax.lax.with_sharding_constraint(w_full, shard)
            gg = opt._preprocess_grad(g_shard, rescale.astype(dt),
                                      clip.astype(dt) if clipped else None)
            res = residuals[bi]
            if comm_dtype is not None:
                # error-feedback payload lowering on the owned shard: the
                # quantization error re-enters next step's gradient, so the
                # compressed run converges to the uncompressed fixpoint
                # (gradient_compression.h:37 semantics at ZeRO granularity)
                e = gg.astype(jnp.float32) + res
                if comm_dtype == "2bit":
                    q = (jnp.where(e >= thr, thr, 0.0)
                         + jnp.where(e <= -thr, -thr, 0.0))
                else:
                    q = e.astype(comm_dtype).astype(jnp.float32)
                res = jax.lax.with_sharding_constraint(e - q, shard)
                gg = q.astype(dt)
            out = opt._kernel(w_shard, gg, lr.astype(dt) * b.lr_mult,
                              wd.astype(dt) * b.wd_mult, t, *states[bi])
            if isinstance(out, tuple):
                new_w_shard, new_st = out[0], tuple(out[1:])
            else:
                new_w_shard, new_st = out, ()
            new_states.append(tuple(
                jax.lax.with_sharding_constraint(s, shard)
                if getattr(s, "shape", None) == (b.padded,) else s
                for s in new_st))
            new_residuals.append(res)
            # updated packed shard → replicated: the all-gather; then a
            # static-slice de-interleave rebuilds each full parameter
            new_w_full = jax.lax.with_sharding_constraint(new_w_shard, repl)
            for i, flat in zip(b.indices, _unpack_bucket(new_w_full, b, n)):
                new_params[i] = flat.reshape(
                    params[i].shape).astype(params[i].dtype)
        return new_params, new_states, new_residuals

    return zero_update
