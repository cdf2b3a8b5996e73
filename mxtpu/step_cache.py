"""Fused training-step executor + framework-wide compile-cache registry.

The reference's headline perf design is the dependency engine bulking many
small pushed ops into few engine ops (``MXNET_ENGINE_BULK_SIZE``,
threaded_engine.h:404) plus CachedOp whole-graph execution. The TPU-native
equivalent of "bulk size = everything" is compiling the ENTIRE training step —
forward, loss, backward, gradient scaling, and optimizer update — into one
XLA program with donated parameter/optimizer-state buffers. That is what
:class:`StepExecutor` does; ``mxtpu.module.Module`` routes
``forward_backward``/``update`` through it whenever the step is fusable, and
``engine.bulk(0)`` / ``engine.set_bulk_size(0)`` is the documented opt-out
that forces the eager per-op path (debugging, Monitor spying).

This module also owns the framework-wide **compile-cache registry**: every
signature cache (CachedOp / StepExecutor / symbol Executor backward /
DataParallelTrainer) registers its hits and traces here, exposed through
``mxtpu.profiler.get_compile_stats()`` — the observability story for "did my
loop retrace?" (the reference's equivalent forensic is engine bulk logging).
"""

from __future__ import annotations

import os
import threading
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

__all__ = ["CacheStats", "cache_stats", "snapshot", "reset_stats",
           "ProgramCache", "StepExecutor", "build_update_all",
           "optimizer_fingerprint"]


# ---------------------------------------------------------------------------
# compile-cache registry
# ---------------------------------------------------------------------------

_lock = threading.Lock()
_registry: "Dict[str, CacheStats]" = {}


class CacheStats:
    """Hit/trace counters for one named signature cache.

    ``misses`` counts traces (every compile of a new signature); ``retraces``
    is the number of compiles beyond the first — the "my fixed-shape loop
    recompiled" red flag tests and CI guards key off.
    """

    __slots__ = ("name", "hits", "misses")

    def __init__(self, name: str):
        self.name = name
        self.hits = 0
        self.misses = 0

    def hit(self):
        self.hits += 1

    def miss(self):
        self.misses += 1

    @property
    def traces(self) -> int:
        return self.misses

    @property
    def retraces(self) -> int:
        return max(0, self.misses - 1)

    def as_dict(self) -> dict:
        return {"hits": self.hits, "traces": self.misses,
                "retraces": self.retraces}


def cache_stats(name: str) -> CacheStats:
    """Get-or-create the stats entry for a named cache."""
    with _lock:
        st = _registry.get(name)
        if st is None:
            st = _registry[name] = CacheStats(name)
        return st


def snapshot() -> Dict[str, dict]:
    """All registered caches → {hits, traces, retraces}."""
    with _lock:
        return {name: st.as_dict() for name, st in _registry.items()}


def reset_stats(name: Optional[str] = None):
    """Zero one cache's counters, or all of them (tests, epoch boundaries)."""
    with _lock:
        targets = [_registry[name]] if name in _registry else (
            [] if name is not None else list(_registry.values()))
        for st in targets:
            st.hits = 0
            st.misses = 0


# ---------------------------------------------------------------------------
# bounded signature→program caches (serving-side compile caches)
# ---------------------------------------------------------------------------


def _program_cache_capacity(env: str, default: int) -> int:
    try:
        return max(1, int(os.environ.get(env, str(default))))
    except ValueError:
        return default


class ProgramCache:
    """Bounded LRU signature→compiled-program cache, registered in the
    compile-cache registry above.

    ``ChainedPredictor._fns`` and ``TransformerLM._gen_fns`` used to be bare
    dicts: under serving-side shape churn (a new batch shape / prompt bucket
    per stream) they grew without limit AND were invisible to
    ``profiler.get_compile_stats()``. This wrapper bounds them (LRU eviction,
    capacity from ``MXTPU_SERVING_PROGRAM_CACHE``, default 64) and counts
    every hit/trace in the named registry entry, so a retrace-leaking serving
    loop shows up in the same forensics table as the training step."""

    def __init__(self, name: str, capacity: Optional[int] = None,
                 env: str = "MXTPU_SERVING_PROGRAM_CACHE"):
        self.name = name
        self.capacity = capacity if capacity is not None \
            else _program_cache_capacity(env, 64)
        self.evictions = 0
        self._fns: "OrderedDict[Any, Any]" = OrderedDict()
        self._stats = cache_stats(name)

    def __len__(self) -> int:
        return len(self._fns)

    def __contains__(self, key) -> bool:
        return key in self._fns

    def get(self, key):
        """Cache lookup; counts a hit and refreshes LRU order on success."""
        fn = self._fns.get(key)
        if fn is not None:
            self._fns.move_to_end(key)
            self._stats.hit()
        return fn

    def put(self, key, fn):
        """Insert a freshly traced program (counts a trace); evicts the
        least-recently-used entry beyond capacity."""
        self._stats.miss()
        self._fns[key] = fn
        self._fns.move_to_end(key)
        while len(self._fns) > self.capacity:
            self._fns.popitem(last=False)
            self.evictions += 1
        return fn

    def get_or_build(self, key, build):
        fn = self.get(key)
        if fn is None:
            fn = self.put(key, build())
        return fn


# ---------------------------------------------------------------------------
# shared in-trace optimizer application
# ---------------------------------------------------------------------------


def optimizer_fingerprint(opt) -> tuple:
    """Static hyperparameter identity of an optimizer instance.

    Part of every fused-step cache key: scalar hyperparams (momentum, betas,
    eps, …) are baked into the trace by ``_kernel``, so changing one must
    retrace. Dynamic per-step values (lr, wd, rescale_grad, update counts)
    are traced arguments and deliberately excluded.
    """
    dynamic = {"lr", "wd", "rescale_grad", "num_update"}
    items = tuple(sorted(
        (k, v) for k, v in vars(opt).items()
        if isinstance(v, (int, float, bool, str)) and k not in dynamic))
    return (type(opt).__name__, opt.clip_gradient is not None, items)


def build_update_all(opt, lr_mults: Sequence[float], wd_mults: Sequence[float],
                     shardings: Optional[Sequence] = None):
    """One traceable function applying ``opt`` to every parameter.

    Exactly the ``_preprocess_grad`` + ``_kernel`` composition the eager
    ``Optimizer.update`` path jits per parameter (and that the
    ``mx.nd.*_update`` fused ops in ``ndarray/fused_optimizer.py`` wrap) —
    inlined so the whole multi-parameter update fuses into the enclosing
    step program. Shared by :class:`StepExecutor` and
    ``parallel.data_parallel.DataParallelTrainer``.

    ``shardings`` (optional per-param ``NamedSharding`` or None entries)
    constrains each gradient to its param's sharding BEFORE the kernel: for
    fsdp-resident params GSPMD resolves the pending data-axis reduction as an
    explicit per-axis reduce-scatter onto the shard (never a replicated
    all-reduce), and the updated param is constrained back to the same
    resident sharding.

    Returns ``update_all(params, grads, states, lr, wd, rescale, clip, t)``
    → ``(new_params, new_states)``. ``clip`` is ignored unless the optimizer
    has ``clip_gradient`` set (a static variant, like ``_get_jitted``).
    """
    clipped = opt.clip_gradient is not None

    def update_all(params, grads, states, lr, wd, rescale, clip, t):
        new_params: List[Any] = []
        new_states: List[Tuple] = []
        for i, (w, g, st) in enumerate(zip(params, grads, states)):
            dt = w.dtype
            g = g.astype(dt)
            sh = shardings[i] if shardings is not None else None
            if sh is not None:
                g = jax.lax.with_sharding_constraint(g, sh)
            gg = opt._preprocess_grad(g, rescale.astype(dt),
                                      clip.astype(dt) if clipped else None)
            out = opt._kernel(w, gg, lr.astype(dt) * lr_mults[i],
                              wd.astype(dt) * wd_mults[i], t, *st)
            if isinstance(out, tuple):
                new_w, new_st = out[0], tuple(out[1:])
            else:
                new_w, new_st = out, ()
            if sh is not None:
                new_w = jax.lax.with_sharding_constraint(new_w, sh)
            new_params.append(new_w)
            new_states.append(new_st)
        return new_params, new_states

    return update_all


# component names of the StepExecutor._sig tuple, in order — the retrace
# sanitizer uses them to label its signature diff ("params[0].dtype changed")
_SIG_LABELS = ("data", "label", "params", "aux", "opt_states", "grad_req",
               "opt_hyperparams", "zero", "quant")


def quant_step_mode():
    # lazy: mxtpu.quant.train imports ops.nn, which must finish registering
    # before quant resolves — deferring breaks the import cycle
    from .quant.train import quant_step_mode as _mode
    return _mode()


def quant_scope(mode):
    from .quant.train import quant_scope as _scope
    return _scope(mode)


def _sharding_of(raw):
    # sharding participates in the executable's contract (same rationale as
    # CachedOp._shard_key): re-placed arrays must retrace
    return getattr(raw, "sharding", None)


def _arr_sig(raw) -> tuple:
    return (tuple(raw.shape), str(raw.dtype), _sharding_of(raw))


def donation_supported() -> bool:
    """Buffer donation is a real transfer-of-ownership only on accelerator
    backends; on cpu XLA ignores it with a warning, so we skip it there."""
    try:
        return jax.default_backend() not in ("cpu",)
    except Exception:
        return False


def unique_buffers(state: Tuple) -> Tuple:
    """Deep-copy optimizer-state arrays so no two donated leaves alias one
    buffer (freshly created zeros states can share a constant; XLA rejects
    donating the same buffer twice)."""
    def copy(s):
        if not hasattr(s, "dtype"):
            return s
        sh = getattr(s, "sharding", None)
        if sh is not None and getattr(sh, "num_devices", 1) > 1:
            # sharding-preserving copy: jnp.array(copy=True) would gather a
            # NamedSharding-placed slot onto one device
            return s + jnp.zeros((), s.dtype)
        return jnp.array(s, copy=True)
    return tuple(copy(s) for s in state)


def create_distinct(create: Callable, shardings_of: Callable, *args):
    """``create(*args)`` run as ONE program, each array of its result a
    buffer of its own placed on ``shardings_of(<the result's shapes>)``:
    XLA hands no two results of a program the same buffer, nor an
    argument's, so what ``create`` returns twice (Adam's one zero array for
    both moments) or copies from an argument can be donated leaf by leaf.
    :func:`unique_buffers` reaches the same by one eager copy a slot."""
    shapes = jax.eval_shape(create, *args)
    return jax.jit(create, out_shardings=shardings_of(shapes))(*args)


# ---------------------------------------------------------------------------
# StepExecutor
# ---------------------------------------------------------------------------


class StepExecutor:
    """Compile forward+loss+backward+optimizer-update into ONE cached program.

    Wraps a Gluon-style ``block``, a ``loss_fn`` (callable on
    ``(outputs[0], label)`` returning per-sample losses), and a
    ``gluon.Trainer`` whose optimizer/state it drives. Each ``step()``:

    * looks up the signature (input/param/state shapes+dtypes+shardings,
      grad_req layout, optimizer hyperparam fingerprint) in the cache;
    * on miss, traces the whole step once (``jax.jit`` with
      ``donate_argnums`` on parameters and optimizer state when the backend
      supports donation) and records a trace in the ``module_step`` registry
      entry;
    * runs the compiled program and writes back parameters, aux (BatchNorm
      moving stats), optimizer state, and parameter gradients — so eager
      introspection (``param.grad()``) and eager/fused interleaving stay
      coherent.

    The gradient written back is the UNSCALED sum-gradient (eager-backward
    parity); rescaling by 1/batch_size happens inside the traced update,
    exactly where ``Trainer.step`` applies ``rescale_grad``.
    """

    def __init__(self, block, loss_fn, trainer, cache_name: str = "module_step"):
        self.block = block
        self.loss_fn = loss_fn
        self.trainer = trainer
        self._cache: Dict[tuple, dict] = {}
        self._cache_name = cache_name
        self._last_sig: Optional[tuple] = None
        self._stats = cache_stats(cache_name)
        self._param_handles = list(trainer._params)
        self._aux_handles = [p for p in trainer._all_params
                             if p.grad_req == "null" and p._data is not None]
        # ZeRO engagement, resolved ONCE (kvstore type device/dist_sync +
        # MXTPU_ZERO + elementwise optimizer → trainer.zero_requested()):
        # the batch shards over the data axes, gradients resolve per-param
        # as named-axis reduce-scatters into packed buckets, and optimizer
        # slots live 1/N-sharded. ``MXTPU_ZERO_STAGE=3`` additionally keeps
        # every shardable param RESIDENT 1/N on the fsdp axis. Works on any
        # mesh (the old multi-axis replicated fallback is gone — per-param
        # constraint resolution is exact where the concat formulation
        # mis-reduced).
        self._zero_mesh = None
        self._zero_stage = 0
        self._param_sh = None
        self._strict_adopt = False
        if trainer.zero_requested():
            from .parallel.mesh import get_default_mesh
            from .parallel.fsdp import zero_stage
            self._zero_mesh = get_default_mesh()
            self._zero_stage = zero_stage()

    # -- ZeRO plumbing -----------------------------------------------------
    def _ensure_placed(self):
        """Place params/aux across the mesh (idempotent; the committed
        NamedSharding is part of the signature, so this runs BEFORE _sig).
        Stages 1/2 replicate everything; stage 3 keeps each shardable param
        RESIDENT 1/N on the fsdp axis (XLA all-gathers it just-in-time inside
        the compiled step and frees the gathered copy after use)."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from .parallel.data_parallel import _place
        mesh = self._zero_mesh
        repl = NamedSharding(mesh, P())
        if self._param_sh is None:
            if self._zero_stage >= 3:
                from .parallel import fsdp as fsdp_mod
                composed = fsdp_mod.fsdp_param_specs(
                    [tuple(p._data._data.shape) for p in self._param_handles],
                    [None] * len(self._param_handles), mesh)
                self._param_sh = [
                    NamedSharding(mesh, c) if c is not None else repl
                    for c in composed]
            else:
                self._param_sh = [repl] * len(self._param_handles)
        for p, sh in zip(self._param_handles, self._param_sh):
            raw = p._data._data
            if getattr(raw, "sharding", None) != sh:
                p._data._set_data(_place(raw, sh))
        for p in self._aux_handles:
            raw = p._data._data
            if getattr(raw, "sharding", None) != repl:
                p._data._set_data(_place(raw, repl))

    def _ensure_zero_states(self):
        """Create (or adopt from a checkpoint restore) the per-bucket sharded
        optimizer slots, owned by the Trainer so snapshot capture sees them."""
        from jax.sharding import PartitionSpec as P
        from .parallel import zero as zero_mod
        from .parallel.mesh import data_size
        tr = self.trainer
        opt = tr._optimizer
        if tr._zero_layout is not None:
            if tr._zero_layout.passthrough:
                self._ensure_pt_states()
            return
        raws = [p._data._data for p in self._param_handles]
        comp = getattr(tr._kvstore, "_compression_params", None) \
            if tr._kvstore is not None else None
        # stage 3: fsdp-resident params are NOT bucketed — they keep the
        # per-param sharded update (slots follow the param's sharding)
        layout = zero_mod.ZeroLayout(
            raws,
            [getattr(p, "lr_mult", 1.0) * opt.lr_mult.get(i, 1.0)
             for i, p in enumerate(self._param_handles)],
            [getattr(p, "wd_mult", 1.0) * opt.wd_mult.get(i, 1.0)
             for i, p in enumerate(self._param_handles)],
            data_size(self._zero_mesh),
            eligible=[sh.spec == P() for sh in self._param_sh])
        tr._zero_layout = layout
        adopted = None
        if tr._zero_restore is not None:
            saved_meta, saved_arrays = tr._zero_restore
            adopted = layout.adopt_states(saved_arrays,
                                          saved_meta.get("layout", {}),
                                          self._zero_mesh)
            tr._zero_restore = None
            if adopted is None and self._strict_adopt:
                # live resize: a silent fresh-state fallback would continue
                # training with zeroed momentum — fail so the elastic
                # controller's caller takes the process-restart path instead
                raise RuntimeError(
                    "in-place mesh adoption failed: live ZeRO optimizer "
                    "slots do not match the re-bucketed layout on the new "
                    "mesh")
            if adopted is None:
                import warnings
                warnings.warn(
                    "checkpointed ZeRO optimizer slots do not match the "
                    "current bucket layout (params or MXTPU_ZERO_BUCKET_MB "
                    "changed); starting with fresh optimizer state",
                    stacklevel=3)
        if adopted is not None:
            tr._zero_states, tr._zero_residuals = adopted
        else:
            tr._zero_states, tr._zero_residuals = zero_mod.init_zero_states(
                opt, layout, raws, self._zero_mesh,
                with_residual=comp is not None)
        # normalize residuals to the CURRENT compression setting: fresh zeros
        # where compression wants one and none was saved; dropped when off
        if comp is None:
            tr._zero_residuals = [None] * len(layout.buckets)
        else:
            from .parallel.data_parallel import _place
            shard = layout.shard_spec(self._zero_mesh)
            tr._zero_residuals = [
                r if r is not None
                else _place(jnp.zeros((b.padded,), jnp.float32), shard)
                for b, r in zip(layout.buckets, tr._zero_residuals)]
        if donation_supported():
            tr._zero_states = [unique_buffers(st) for st in tr._zero_states]
        if layout.passthrough:
            self._ensure_pt_states()

    def _ensure_pt_states(self):
        """Per-param optimizer slots for the passthrough set (fsdp-resident
        params at stage 3): each slot is placed with its PARAM's sharding, so
        state is 1/N resident without bucketing — and the checkpoint path
        (``opt:i:j`` keys + recorded specs) re-shards it across fsdp widths
        exactly like a param."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from .parallel.data_parallel import _place
        tr = self.trainer
        opt = tr._optimizer
        repl = NamedSharding(self._zero_mesh, P())
        donate = donation_supported()
        for i in tr._zero_layout.passthrough:
            if tr._states[i] is not None:
                continue
            p = self._param_handles[i]
            shape = tuple(p._data._data.shape)
            st = opt.create_state_multi_precision(i, p.data())
            placed = tuple(
                _place(s, self._param_sh[i]
                       if getattr(s, "shape", None) == shape else repl)
                if hasattr(s, "dtype") else s
                for s in st)
            tr._states[i] = unique_buffers(placed) if donate else placed

    # -- live elasticity ---------------------------------------------------
    def adopt_mesh(self, mesh) -> None:
        """Re-home the fused step onto ``mesh`` IN PLACE, mid-run (live
        elasticity, ROADMAP item 4): the optimizer keeps its exact state —
        bucketed ZeRO slots are host-landed, staged through the same
        ``trainer._zero_restore`` ritual a checkpoint restore uses, and
        re-adopted via ``ZeroLayout.adopt_states`` at the NEW data size;
        per-param (stage-3 passthrough) slots re-place with their param's
        new resident sharding. The program cache is dropped (the next step
        traces once on the new mesh) and update counters / RNG are untouched,
        so the continuation is bit-exact with a cold checkpoint-resume onto
        the same mesh.

        Must be called at a step boundary (no step in flight). A bucket-
        layout mismatch on the new mesh raises — the caller (``ElasticRun``)
        falls back to a process restart rather than continuing with silently
        zeroed momentum."""
        if self._zero_mesh is None:
            raise RuntimeError(
                "adopt_mesh requires a ZeRO/FSDP-engaged step (kvstore "
                "device/dist_sync with an elementwise optimizer); the "
                "replicated eager path has no mesh to resize")
        from jax.sharding import NamedSharding, PartitionSpec as P
        from .checkpoint.snapshot import _to_host
        from .parallel.data_parallel import _place
        tr = self.trainer
        # 1. host-land the bucketed ZeRO slots, keyed exactly like a
        #    checkpoint (zopt:{b}:{j} / zres:{b}) so the adoption below is
        #    the SAME de-interleave/re-pack path a dp-N→dp-M resume takes
        if tr._zero_layout is not None:
            zarrays, zslots = {}, []
            for b, st in enumerate(tr._zero_states):
                zslots.append(len(st))
                for j, s in enumerate(st):
                    zarrays[f"zopt:{b}:{j}"] = _to_host(s)
            for b, r in enumerate(tr._zero_residuals or []):
                if r is not None:
                    zarrays[f"zres:{b}"] = _to_host(r)
            tr._zero_restore = ({"layout": tr._zero_layout.describe(),
                                 "slots": zslots}, zarrays)
            tr._zero_layout = None
            tr._zero_states = []
            tr._zero_residuals = []
        # 2. host-land per-param slots (the stage-3 passthrough set) before
        #    their shardings go stale with the old mesh
        host_states = [
            None if st is None else
            tuple(_to_host(s) if hasattr(s, "dtype") else s for s in st)
            for st in tr._states]
        # 3. re-home: new mesh, recomputed param shardings, cold program
        #    cache (the signature includes shardings, so the first step on
        #    the new mesh must trace — dropping the cache just makes the
        #    old-mesh programs collectable)
        self._zero_mesh = mesh
        self._param_sh = None
        self._cache.clear()
        self._last_sig = None
        self._ensure_placed()
        repl = NamedSharding(mesh, P())
        donate = donation_supported()
        for i, st in enumerate(host_states):
            if st is None:
                continue
            shape = tuple(self._param_handles[i]._data._data.shape)
            placed = tuple(
                _place(s, self._param_sh[i]
                       if getattr(s, "shape", None) == shape else repl)
                if hasattr(s, "dtype") else s
                for s in st)
            tr._states[i] = unique_buffers(placed) if donate else placed
        # 4. adopt the staged slots onto the new layout — strict: a layout
        #    mismatch raises instead of silently resetting optimizer state
        self._strict_adopt = True
        try:
            self._ensure_zero_states()
        finally:
            self._strict_adopt = False

    # -- signature ---------------------------------------------------------
    def _ensure_states(self):
        tr = self.trainer
        opt = tr._optimizer
        donate = donation_supported()
        for i, p in enumerate(self._param_handles):
            if tr._states[i] is None:
                st = opt.create_state_multi_precision(i, p.data())
                tr._states[i] = unique_buffers(st) if donate else tuple(st)

    def _sig(self, data, label) -> tuple:
        tr = self.trainer
        zero_sig = None
        if self._zero_mesh is not None:
            zero_sig = (
                tr._zero_layout.fingerprint(),
                tuple(tuple(_arr_sig(s) for s in st)
                      for st in tr._zero_states),
                tuple(None if r is None else _arr_sig(r)
                      for r in tr._zero_residuals),
            )
        return (
            tuple(_arr_sig(d.data) for d in data),
            _arr_sig(label.data) if label is not None else None,
            tuple(_arr_sig(p._data._data) for p in self._param_handles),
            tuple(_arr_sig(p._data._data) for p in self._aux_handles),
            tuple(tuple(_arr_sig(s) for s in (st or ()))
                  for st in tr._states),
            tuple(p.grad_req for p in self._param_handles),
            optimizer_fingerprint(tr._optimizer),
            zero_sig,
            quant_step_mode(),   # MXTPU_QUANT_STEP: flipping modes retraces
        )

    # -- tracing -----------------------------------------------------------
    def _build(self) -> dict:
        from . import autograd, rng
        from .ndarray.ndarray import NDArray
        from .gluon.loss import SoftmaxCrossEntropyLoss

        block, loss_fn = self.block, self.loss_fn
        opt = self.trainer._optimizer
        param_handles = self._param_handles
        aux_handles = self._aux_handles
        # static per-param multipliers (the _get_lr/_get_wd composition)
        lr_mults = [getattr(p, "lr_mult", 1.0) * opt.lr_mult.get(i, 1.0)
                    for i, p in enumerate(param_handles)]
        wd_mults = [getattr(p, "wd_mult", 1.0) * opt.wd_mult.get(i, 1.0)
                    for i, p in enumerate(param_handles)]
        update_all = build_update_all(opt, lr_mults, wd_mults)
        zero_update = None
        pt: List[int] = []
        pt_update = None
        if self._zero_mesh is not None:
            from .parallel import zero as zero_mod
            comp = getattr(self.trainer._kvstore, "_compression_params", None) \
                if self.trainer._kvstore is not None else None
            zero_update = zero_mod.build_zero_update(
                opt, self.trainer._zero_layout, self._zero_mesh,
                comm_dtype=zero_mod.comm_dtype_of(comp),
                compression_params=comp)
            # fsdp-resident (stage 3) params: per-param update with the
            # gradient constrained to the param's resident sharding — the
            # pending data-axis reduction lowers to an explicit per-axis
            # reduce-scatter onto the 1/N shard
            pt = list(self.trainer._zero_layout.passthrough)
            if pt:
                pt_update = build_update_all(
                    opt, [lr_mults[i] for i in pt], [wd_mults[i] for i in pt],
                    shardings=[self._param_sh[i] for i in pt])
        softmax_expose = isinstance(loss_fn, SoftmaxCrossEntropyLoss)
        struct: dict = {}

        def pure(param_raws, aux_raws, state_raws, zstates, zres, data_raws,
                 label_raw, lr, wd, rescale, clip, t, key):
            provider = rng.push_trace_provider(key)
            saved_p = [p._data._data for p in param_handles]
            saved_a = [p._data._data for p in aux_handles]
            try:
                def loss_on(ps):
                    for p, r in zip(param_handles, ps):
                        p._data._data = r
                        p._data._version += 1
                    for p, r in zip(aux_handles, aux_raws):
                        p._data._data = r
                        p._data._version += 1
                    with autograd.pause(train_mode=True):
                        out = block(*[NDArray(d) for d in data_raws])
                        single = not isinstance(out, (tuple, list))
                        outs = [out] if single else list(out)
                        loss = loss_fn(outs[0], NDArray(label_raw))
                    struct["single"] = single
                    new_aux = [p._data._data for p in aux_handles]
                    # sum-of-loss head: eager backward seeds ones on the
                    # per-sample loss vector, which IS d(sum)/d(.)
                    return (jnp.sum(loss.data.astype(jnp.float32)),
                            (new_aux, [o.data for o in outs], loss.data))

                (_, (new_aux, raw_outs, loss_arr)), grads = \
                    jax.value_and_grad(loss_on, has_aux=True)(list(param_raws))
                if zero_update is not None:
                    # ZeRO: bucketed reduce-scatter → sharded slot update →
                    # all-gather. Grads are NOT returned in this mode: a
                    # replicated grad output would force the very all-reduce
                    # the reduce-scatter exists to avoid.
                    new_params, new_zstates, new_zres = zero_update(
                        list(param_raws), list(grads), zstates, zres,
                        lr, wd, rescale, clip, t)
                    new_states, out_grads = list(state_raws), None
                    if pt:
                        sub_w, sub_st = pt_update(
                            [new_params[i] for i in pt],
                            [grads[i] for i in pt],
                            [state_raws[i] or () for i in pt],
                            lr, wd, rescale, clip, t)
                        for j, i in enumerate(pt):
                            new_params[i] = sub_w[j]
                            new_states[i] = sub_st[j]
                else:
                    new_params, new_states = update_all(
                        param_raws, grads, state_raws, lr, wd, rescale,
                        clip, t)
                    new_zstates, new_zres, out_grads = zstates, zres, \
                        list(grads)
                exposed0 = (jax.nn.softmax(raw_outs[0], axis=-1)
                            if softmax_expose else None)
                return (new_params, new_aux, new_states, new_zstates,
                        new_zres, out_grads, loss_arr, raw_outs, exposed0)
            finally:
                for p, r in zip(param_handles, saved_p):
                    p._data._data = r
                    p._data._version += 1
                for p, r in zip(aux_handles, saved_a):
                    p._data._data = r
                    p._data._version += 1
                rng.pop_trace_provider()

        donate = (0, 2, 3, 4) if donation_supported() else ()
        jitted = jax.jit(pure, donate_argnums=donate)
        return {"jitted": jitted, "struct": struct}

    # -- FLOP accounting ---------------------------------------------------
    def program_flops(self) -> Optional[float]:
        """FLOPs of ONE execution of the current compiled step program
        (``observability.flops.estimate_step_flops``; which source counted
        them is in ``get_mfu_stats()["flops_source"]``). Lazy and cached per
        cache entry: the first call after a trace pays one AOT lower+compile,
        subsequent calls are a dict read — callers (fit epoch logs)
        keep this OFF the step hot path."""
        entry = self._cache.get(self._last_sig)
        if entry is None or "avals" not in entry:
            return None
        if "flops" not in entry:
            from .observability import flops as flops_mod
            entry["flops"], source = flops_mod.estimate_step_flops(
                entry["jitted"], entry["avals"])
            flops_mod.set_step_flops(entry["flops"], source)
        return entry["flops"]

    def audit_entry(self):
        """``(jitted program, abstract args)`` of the most recently
        dispatched fused-step signature — the program auditor's entry point
        (``python -m mxtpu.analysis --audit``).  The avals are the same
        shape/dtype skeleton :meth:`program_flops` lowers against, so the
        auditor re-traces the EXACT program the trainer runs (donation map
        included) without pinning any live buffers.  Raises until one real
        step has populated the cache."""
        entry = self._cache.get(self._last_sig)
        if entry is None or "avals" not in entry:
            raise RuntimeError(
                "audit_entry: no fused step has been dispatched yet — run "
                "one training step before auditing the step program")
        return entry["jitted"], entry["avals"]

    # -- the step ----------------------------------------------------------
    def step(self, data: Sequence, label, batch_size: Optional[int] = None):
        """Run one fused train step. Returns a dict with detached
        ``loss`` (per-sample array), ``outputs``, and ``exposed`` (softmaxed
        outputs when the loss is classification, else None)."""
        from . import rng
        from .analysis import sanitize
        from .ndarray.ndarray import NDArray
        from .observability import tracer
        from .resilience import fault_point
        from .resilience.watchdog import heartbeat

        # resilience seam FIRST — before the RNG advances below — so a fault
        # (or preemption save) fired here leaves per-step RNG state identical
        # to a run that never reached this step; heartbeat feeds the
        # per-step deadline watchdog and the supervisor's progress beacon
        fault_point("step")
        heartbeat("step")

        san = sanitize.active()
        tr = self.trainer
        tr._init_kvstore()
        opt = tr._optimizer
        if self._zero_mesh is not None:
            # ZeRO-1: replicate params over the dp mesh, dp-shard the batch,
            # keep optimizer slots ONLY as 1/N bucket shards (tr._states
            # stays None — snapshot capture reads tr._zero_states instead)
            from .parallel.data_parallel import shard_batch
            self._ensure_placed()
            self._ensure_zero_states()
            data = [shard_batch(d, self._zero_mesh) for d in data]
            if label is not None:
                label = shard_batch(label, self._zero_mesh)
        else:
            self._ensure_states()
        batch_size = batch_size if batch_size is not None else data[0].shape[0]

        sig = self._sig(data, label)
        entry = self._cache.get(sig)
        traced_now = entry is None
        if traced_now:
            if "retrace" in san and self._cache:
                # raises RetraceError with a labeled signature diff BEFORE
                # paying for the compile; the limit defaults to 2 (train +
                # eval — the compile-guard contract)
                sanitize.escalate_retrace(self._cache_name, len(self._cache),
                                          self._last_sig, sig,
                                          labels=_SIG_LABELS)
            self._stats.miss()
            entry = self._cache[sig] = self._build()
        else:
            self._stats.hit()
        self._last_sig = sig

        t = max([opt._index_update_count.get(i, 0)
                 for i in range(len(self._param_handles))] or [0]) + 1
        # eager parity: _update_count precedes _get_lr, so the scheduler sees
        # the post-increment num_update
        lr = jnp.float32(opt.lr_scheduler(max(opt.num_update, t))
                         if opt.lr_scheduler else opt.lr)
        wd = jnp.float32(opt.wd)
        rescale = jnp.float32(tr._scale / batch_size)
        clip = jnp.float32(opt.clip_gradient
                           if opt.clip_gradient is not None else 0.0)
        key = rng.next_key()

        # donated argument groups, held as locals so the donation sanitizer
        # can poison exactly what the compiled program consumed. ``t`` goes
        # in as int32 so the transfer guard sees no per-step host scalar.
        param_raws = [p._data._data for p in self._param_handles]
        aux_raws = [p._data._data for p in self._aux_handles]
        state_raws = list(tr._states)
        zstate_raws = list(tr._zero_states)
        zres_raws = list(tr._zero_residuals)
        data_raws = [d.data for d in data]
        label_raw = label.data if label is not None else None
        t_arr = jnp.int32(t)
        step_args = (param_raws, aux_raws, state_raws, zstate_raws, zres_raws,
                     data_raws, label_raw, lr, wd, rescale, clip, t_arr, key)
        if traced_now:
            # shape/dtype skeleton for the lazy FLOP estimate (program_flops)
            # — holding real arrays would pin donated buffers
            entry["avals"] = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
                if hasattr(a, "shape") else a, step_args)
            if self._zero_mesh is not None:
                # per-device residency accounting, from the placed shardings
                from .parallel import fsdp as fsdp_mod
                slots = [s for st in list(tr._states) + list(tr._zero_states)
                         for s in (st or ()) if hasattr(s, "dtype")]
                slots += [r for r in tr._zero_residuals if r is not None]
                grad_bytes = sum(fsdp_mod.replicated_bytes(a)
                                 for a in param_raws)
                fsdp_mod.measure_memory(self._zero_stage, self._zero_mesh,
                                        param_raws, slots, grad_bytes)
        # one span per dispatch on the unified step timeline: the first call
        # of a signature IS the trace+lower+compile (step/compile, tagged
        # with the signature fingerprint), cache hits are step/execute
        sp = tracer.span("step/compile" if traced_now else "step/execute",
                         cat="step",
                         args={"cache": self._cache_name,
                               "signature":
                               f"{hash(sig) & 0xffffffffffffffff:016x}"}
                         if traced_now else {"cache": self._cache_name})
        with sp, sanitize.step_guard(san, traced_now, where=self._cache_name), \
                quant_scope(sig[-1]):
            # quant_scope swaps the dense/conv contraction for the fake-quant
            # STE path while THIS signature's program traces (no-op when the
            # mode is off or the program is already compiled)
            out = entry["jitted"](*step_args)
        (new_params, new_aux, new_states, new_zstates, new_zres, grads,
         loss_arr, raw_outs, exposed0) = out

        if "donation" in san:
            # the program consumed argnums (0, 2, 3, 4): params, optimizer
            # slots, ZeRO slots/residuals. Poison the old references (minus
            # pass-throughs the program returned unchanged) so a stale read
            # raises a NAMED error here on CPU too — where XLA skips
            # donation and the PR 2 snapshot race was silent.
            donated = list(param_raws)
            for st in state_raws:
                donated.extend(st or ())
            for st in zstate_raws:
                donated.extend(st or ())
            donated.extend(r for r in zres_raws if r is not None)
            returned = {id(v) for v in new_params}
            for group in (new_states, new_zstates):
                for st in group:
                    returned.update(id(s) for s in (st or ()))
            returned.update(id(r) for r in new_zres if r is not None)
            sanitize.poison(
                (a for a in donated if id(a) not in returned),
                origin=f"the fused '{self._cache_name}' step "
                       f"(donate_argnums params/opt-state)")

        # write-back: params/aux/state swap + eager-visible gradients
        for p, v in zip(self._param_handles, new_params):
            p._data._set_data(v)
        for p, v in zip(self._aux_handles, new_aux):
            p._data._set_data(v)
        tr._states = list(new_states)
        tr._zero_states = list(new_zstates)
        tr._zero_residuals = list(new_zres)
        if grads is not None:
            # eager-visible gradients (param.grad()); the ZeRO path skips
            # this — materializing the full grad would force an all-reduce
            for p, g in zip(self._param_handles, grads):
                h = p._data
                if h._grad is not None and getattr(h._grad, "stype",
                                                   "default") == "default":
                    h._grad._set_data(g)
                else:
                    h._grad = NDArray(g)
        for i in range(len(self._param_handles)):
            opt._index_update_count[i] = t
        opt.num_update = max(opt.num_update, t)
        if self._zero_mesh is not None:
            from . import profiler
            profiler.record_comm_step(zero=True,
                                      **tr._zero_layout.step_comm())

        outputs = [NDArray(r) for r in raw_outs]
        return {
            "loss": NDArray(loss_arr),
            "outputs": outputs[0] if entry["struct"].get("single", True)
            and len(outputs) == 1 else outputs,
            "outputs_list": outputs,
            "exposed": ([NDArray(exposed0)] + outputs[1:]
                        if exposed0 is not None else None),
        }
