"""Data-parallel training over a mesh — the TPU-native replacement for the reference's
``DataParallelExecutorGroup`` + KVStore reduce (SURVEY.md §2.3 row "DP, single
machine"): instead of splitting a batch into per-GPU executors and reducing grads
through a Comm tree, the batch is **sharded** over the ``dp`` mesh axis and one jitted
step runs SPMD — XLA inserts the gradient all-reduce over ICI and overlaps it with
backward compute (the reference's priority-overlap trick, for free).

``DataParallelTrainer`` wraps a Gluon block + optimizer into such a step.
"""

from __future__ import annotations

import resource
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import numpy as np

from .. import autograd
from ..analysis import sanitize
from .. import ndarray as nd_mod
from ..ndarray.ndarray import NDArray
from ..observability import flops, metrics, tracer
from ..ops import attention as attention_ops
from ..step_cache import (build_update_all, cache_stats, create_distinct,
                          donation_supported)
from . import fsdp as fsdp_mod
from . import zero as zero_mod
from .mesh import (Mesh, data_axis_names, data_size, dp_size,
                   fsdp_axis_name, fsdp_size, get_default_mesh)

__all__ = ["shard_batch", "replicate", "place", "DataParallelTrainer"]

# positions of the step's arguments that it replaces, and so donates:
# params, per-parameter slots, ZeRO slots, residuals (StepExecutor's four)
_DONATED = (0, 2, 3, 4)

# whose context switches a step's row counts: the calling thread's, where the
# platform tells threads apart
_RUSAGE_WHO = getattr(resource, "RUSAGE_THREAD", resource.RUSAGE_SELF)


def _place(raw, sharding: NamedSharding):
    """Host→mesh placement that works in both single- and multi-process runs.

    Multi-process (jax.distributed): a process can only device_put to its own
    devices, so each rank contributes its LOCAL slice and JAX assembles the global
    array (the SPMD per-host-feed convention; replaces the reference's per-worker
    batch slicing in executor_group.py:281-310)."""
    import jax.numpy as _jnp
    raw = _jnp.asarray(raw)
    if jax.process_count() > 1 and any(
            not d.process_index == jax.process_index()
            for d in sharding.mesh.devices.flat):
        import numpy as np
        return jax.make_array_from_process_local_data(
            sharding, np.asarray(jax.device_get(raw)))
    return jax.device_put(raw, sharding)


# public alias: the checkpoint subsystem restores arrays through the SAME
# placement path the training step feeds through (per-host local slices
# assemble into the global array under jax.distributed)
place = _place


def shard_batch(array, mesh: Optional[Mesh] = None, axis: int = 0) -> NDArray:
    """Place a host batch as a dp-sharded jax.Array (≈ decide_slices/_split_input_slice,
    executor_group.py:281-310 — but one logical array, no per-device copies).

    Multi-process: ``array`` is this rank's LOCAL batch shard.

    An array already committed with the target sharding (e.g. staged by a
    ``device_feed.DeviceFeed`` ahead of the step) is returned as-is — the
    step path never double-``device_put``s resident inputs."""
    mesh = mesh or get_default_mesh()
    spec = [None] * (array.ndim if hasattr(array, "ndim") else len(array.shape))
    axes = data_axis_names(mesh)
    spec[axis] = axes if len(axes) > 1 else axes[0]
    raw = array.data if isinstance(array, NDArray) else jnp.asarray(array)
    target = NamedSharding(mesh, P(*spec))
    if isinstance(raw, jax.Array) and getattr(raw, "committed", False) \
            and raw.sharding == target:
        return array if isinstance(array, NDArray) else NDArray(raw)
    return NDArray(_place(raw, target))


def replicate(array, mesh: Optional[Mesh] = None) -> NDArray:
    mesh = mesh or get_default_mesh()
    raw = array.data if isinstance(array, NDArray) else jnp.asarray(array)
    return NDArray(_place(raw, NamedSharding(mesh, P())))


class DataParallelTrainer:
    """Sharded training step: params replicated, batch dp-sharded, grads psum'd.

    Usage::

        dpt = DataParallelTrainer(net, loss_fn, optimizer, mesh)
        loss = dpt.step(x_batch, y_batch)   # one jitted SPMD step

    The whole fwd+bwd+update is ONE XLA program: gradient all-reduce rides ICI and
    overlaps backward; the optimizer update is fused in.

    **The step donates what it replaces.** On a backend that donates
    (``step_cache.donation_supported()``: every one but the CPU) the weights,
    the per-parameter optimizer slots, ZeRO's bucket slots and the
    compression residuals go into the step as donated arguments, and each new
    value is written into the buffer of the old one: the runtime allocates
    one fresh output a step (the loss) and weights and slots exist once.
    The batch, the scalars and the auxiliary states are never donated. So an
    array taken from ``p.data().data``, :meth:`optimizer_slots` or
    :meth:`optimizer_state_by_param` is DELETED by the next step
    (``StepExecutor``'s contract): read the accessors afresh after a step,
    or copy (``np.asarray``) what has to outlive it. ``MXTPU_SANITIZE=donation``
    names such a stale read on the CPU too, where nothing is donated.

    The eager gradient buffers that ``Parameter.initialize`` attached are
    released when the trainer takes the parameters over (the step never
    writes them): ``p.grad()`` has nothing to hand out until an eager
    backward runs again, which makes the buffer anew.
    """

    def __init__(self, block, loss_fn, optimizer, mesh: Optional[Mesh] = None,
                 param_shardings=None, remat: bool = False,
                 micro_batches: int = 1, zero: Optional[bool] = None,
                 compression_params: Optional[dict] = None):
        """``param_shardings`` is the gluon-integrated model-parallel hook (the
        TPU-native replacement for the reference's ``ctx_group``/``group2ctx`` layer
        placement, graph_executor.cc:408): a dict mapping parameter-name suffixes to
        ``PartitionSpec``s, or a callable ``name -> PartitionSpec | None``. Unlisted
        params are replicated. XLA/GSPMD inserts the tp collectives automatically.

        ``remat=True`` wraps the loss in ``jax.checkpoint`` (rematerialization:
        trade one extra forward's FLOPs for not keeping activations alive
        across fwd→bwd — the reference's mirror/memonger capability). Use when
        activation memory approaches HBM capacity (large batch/sequence).

        ``micro_batches=k`` accumulates gradients over k micro-batches inside
        ONE jitted step (a ``lax.scan``): activation memory is that of
        batch/k while the optimizer sees the full-batch gradient — the
        cure for the large-batch HBM-capacity cliff (rounds 3-5, a retired
        runtime; not re-measured on the v5e). Micro-batches take every
        k-th row so each stays evenly dp-sharded.

        ``zero`` selects the ZeRO gradient/update path (default: the
        ``MXTPU_ZERO`` env, on unless ``=0``), staged by ``MXTPU_ZERO_STAGE``:
        gradients resolve per-param as reduce-scatters over the named data
        axes into packed buckets, optimizer slots live 1/N-sharded, updated
        params are all-gathered back (parallel/zero.py). Works on any mesh —
        tensor-parallel-sharded params keep the per-param update; at stage 3
        shardable params are instead RESIDENT 1/N on the ``fsdp`` axis
        (parallel/fsdp.py). Where the mesh's data degree is 1 there is
        nothing to shard, and packing a matrix into a flat bucket is a
        relayout that buys nothing: only parameters that are flat already
        (biases, norm scales) are bucketed there, every matrix takes the
        per-param update in its own shape and layout, and
        ``profiler.get_comm_stats()["shard_bytes_per_device"]`` shrinks to
        the flat leaves' bytes. ``compression_params`` (KVStore
        ``set_gradient_compression`` dict: type 2bit|fp16|bf16) lowers the
        bucket payload with an error-feedback residual; both live on the
        bucket path, so with compression every replicated parameter stays
        bucketed at every degree, 1 included."""
        self.block = block
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.mesh = mesh or get_default_mesh()
        self.param_shardings = param_shardings
        self.remat = remat
        self.micro_batches = int(micro_batches)
        self.zero = (zero_mod.zero_enabled() if zero is None else bool(zero)) \
            and zero_mod.supports_zero(optimizer)
        self.stage = fsdp_mod.zero_stage() if self.zero else 0
        if compression_params is not None:
            zero_mod.comm_dtype_of(compression_params)  # validate the kind
        self._compression_params = compression_params
        self._step_fn = None
        self._t = 0
        # the step's scalar arguments as they stand on the device: name ->
        # (host value, array), and (t, key) made while step t - 1 ran
        self._scalars: dict = {}
        self._key_ahead = None
        # the thread's count at the last step's row (the first: from here)
        self._nivcsw = resource.getrusage(_RUSAGE_WHO).ru_nivcsw
        self._params: List = []
        self._states: List = []
        self._zero_layout = None
        self._zero_states: List = []
        self._zero_residuals: List = []
        self._stats = cache_stats("data_parallel_step")

    def _spec_for(self, name) -> P:
        if self.param_shardings is None:
            return P()
        if callable(self.param_shardings):
            return self.param_shardings(name) or P()
        for suffix, spec in self.param_shardings.items():
            if name.endswith(suffix):
                return spec
        return P()

    def _kernel_scope(self):
        """Open while the forward runs or the step traces: GSPMD cannot
        partition a Pallas kernel, so on a multi-device mesh the flash
        kernels shard_map themselves over the axes that carry batch and
        heads (``ops.attention.partition_scope``) — the composed layout's
        head-activation spec under a ``layout_scope``, the data axes alone
        otherwise."""
        scope = fsdp_mod.current_layout()
        spec = scope[0].head_activations() if scope is not None \
            else P(data_axis_names(self.mesh))
        return attention_ops.partition_scope(self.mesh, spec)

    def _collect(self, x_example):
        # deferred params materialize in an eager forward; a block whose
        # every parameter holds data already (all widths given) needs none,
        # and at real sizes that forward is dozens of one-operation programs
        # to compile and run before the step's own
        if any(p._data is None
               for p in self.block.collect_params().values()):
            with autograd.predict_mode(), self._kernel_scope():
                self.block(x_example)
        # a parameter that the block yields under two names (tied weights)
        # reaches the step once: one buffer cannot be donated twice
        named, seen = [], set()
        for n, p in self.block.collect_params().items():
            if id(p) not in seen:
                seen.add(id(p))
                named.append((n, p))
        self._param_names = [n for n, p in named
                             if p._data is not None and p.grad_req != "null"]
        self._param_handles = [p for n, p in named
                               if p._data is not None and p.grad_req != "null"]
        self._aux_handles = [p for n, p in named
                             if p._data is not None and p.grad_req == "null"]
        # place across the mesh: replicated unless a tp sharding was
        # requested; at stage 3 the fsdp axis composes into every spec with
        # an eligible (free, divisible) dimension — those params are RESIDENT
        # 1/N and XLA all-gathers them just-in-time per layer
        self._param_sh = [NamedSharding(self.mesh, self._spec_for(n))
                          for n in self._param_names]
        if self.zero and self.stage >= 3:
            composed = fsdp_mod.fsdp_param_specs(
                [p.data().shape for p in self._param_handles],
                [sh.spec for sh in self._param_sh], self.mesh)
            self._param_sh = [
                NamedSharding(self.mesh, c) if c is not None else sh
                for c, sh in zip(composed, self._param_sh)]
        for p, sh in zip(self._param_handles, self._param_sh):
            p._data._set_data(_place(p.data().data, sh))
        for p in self._aux_handles:
            p._data._set_data(_place(p.data().data, NamedSharding(self.mesh, P())))
        # the step works its gradients out inside the program and never
        # writes the eager buffers ``Parameter.initialize`` attached (float32
        # even after a cast to bfloat16: four bytes a parameter of device
        # memory that nothing reads). Released here; the parameters stay
        # marked, and autograd makes a buffer again the first time an eager
        # backward has a gradient to put there.
        for p in self._param_handles:
            p._data._grad = None
        repl = NamedSharding(self.mesh, P())
        raws = [p.data().data for p in self._param_handles]
        if self.zero:
            # replicated params bucket into data-sharded flat slots; tp- and
            # fsdp-sharded params keep the per-param update below (their
            # slots follow the param's sharding, so fsdp slots are 1/N too).
            # At a data degree of 1 a bucket shards nothing and packing a
            # matrix is a relayout, so only leaves that are flat already
            # stay bucketed (one output buffer in place of hundreds: the
            # runtime allocates each one every step) and every matrix takes
            # the per-param update; compression keeps all its buckets, where
            # its residual lives (parallel/zero.py's docstring has the why)
            flat_only = (data_size(self.mesh) == 1
                         and self._compression_params is None)
            eligible = [sh.spec == P()
                        and (p.data().ndim <= 1 or not flat_only)
                        for p, sh in zip(self._param_handles,
                                         self._param_sh)]
            self._zero_layout = zero_mod.ZeroLayout(
                raws,
                [getattr(p, "lr_mult", 1.0) for p in self._param_handles],
                [getattr(p, "wd_mult", 1.0) for p in self._param_handles],
                data_size(self.mesh), eligible=eligible)
            self._zero_states, self._zero_residuals = zero_mod.init_zero_states(
                self.optimizer, self._zero_layout, raws, self.mesh,
                with_residual=self._compression_params is not None)
            self._zero_state_sh = zero_mod.state_shardings(
                self._zero_layout, self._zero_states, self.mesh)
            passthrough = set(self._zero_layout.passthrough)
        else:
            passthrough = set(range(len(self._param_handles)))
        # per-parameter slots in the parameter's shape follow its sharding;
        # one program makes them all, so no two share a buffer (Adam hands
        # out one zero array for both moments) and the step can donate each
        def create(ws):
            return [tuple(self.optimizer.create_state(i, NDArray(w)))
                    if i in passthrough else () for i, w in enumerate(ws)]

        self._states = create_distinct(
            create,
            lambda shapes: [
                tuple(sh if s.shape == p.data().shape else repl for s in st)
                for p, sh, st in zip(self._param_handles, self._param_sh,
                                     shapes)],
            raws)
        self._state_sh = [tuple(s.sharding for s in st)
                          for st in self._states]
        self._record_memory()

    def _record_memory(self):
        """Per-device param/grad/slot byte accounting (profiler
        ``get_memory_stats``), from the actual placed shardings."""
        params = [p.data().data for p in self._param_handles]
        slots = self.optimizer_slots()
        grad_bytes = sum(
            int(np.prod(p.shape)) * np.dtype(str(p.dtype)).itemsize
            for p in params)
        fsdp_mod.measure_memory(self.stage, self.mesh, params, slots,
                                grad_bytes)

    def _build(self):
        block, loss_fn, opt = self.block, self.loss_fn, self.optimizer
        param_handles = self._param_handles
        aux_handles = self._aux_handles
        from .. import rng as rng_mod
        # the per-param optimizer application is the SAME inlined
        # preprocess+kernel composition the fused Module step uses
        # (step_cache.build_update_all) — one shared code path for every
        # whole-step compile in the framework
        lr_mults = [getattr(p, "lr_mult", 1.0) for p in param_handles]
        wd_mults = [getattr(p, "wd_mult", 1.0) for p in param_handles]
        # per-param updates apply only to the passthrough set (everything,
        # when ZeRO is off; the tp-sharded leftovers when it is on)
        pt = list(self._zero_layout.passthrough) if self.zero \
            else list(range(len(param_handles)))
        update_pt = build_update_all(
            opt, [lr_mults[i] for i in pt], [wd_mults[i] for i in pt])
        zero_update = zero_mod.build_zero_update(
            opt, self._zero_layout, self.mesh,
            comm_dtype=zero_mod.comm_dtype_of(self._compression_params),
            compression_params=self._compression_params) if self.zero else None
        # ZeRO-2: micro-batch accumulation holds packed 1/N bucket SHARDS —
        # each micro-gradient reduce-scatters into its shard inside the scan,
        # so no replicated gradient buffer ever materializes for bucketed
        # params
        stage2_acc = (zero_update is not None and self.stage >= 2
                      and self.micro_batches > 1
                      and self._zero_layout.buckets)
        pack_grads = zero_mod.build_grad_pack(self._zero_layout, self.mesh) \
            if stage2_acc else None
        zshard = self._zero_layout.shard_spec(self.mesh) if self.zero else None

        def step(params, auxs, states, zstates, zres, x, y, lr, wd, rescale,
                 clip, key, t):
            provider = rng_mod.push_trace_provider(key)
            saved = [p._data._data for p in param_handles]
            saved_aux = [p._data._data for p in aux_handles]
            try:
                def loss_on(ps, auxs_in, xb, yb):
                    for p, v in zip(param_handles, ps):
                        p._data._data = v
                        p._data._version += 1
                    for p, v in zip(aux_handles, auxs_in):
                        p._data._data = v
                        p._data._version += 1
                    with autograd.pause(train_mode=True):
                        out = block(nd_mod.NDArray(xb))
                        # around the loss alone: around value_and_grad every
                        # backward operation would read "loss"
                        with jax.named_scope("loss"):
                            loss = loss_fn(out, nd_mod.NDArray(yb))
                    new_auxs = [p._data._data for p in aux_handles]
                    return jnp.mean(loss.data), new_auxs

                k = self.micro_batches
                if k > 1:
                    # gradient accumulation: scan over k micro-batches, each
                    # taking every k-th row (stays evenly dp-sharded);
                    # activation working set shrinks k-fold, the optimizer
                    # sees the mean full-batch gradient
                    def loss_of(ps, auxs_in, xb, yb):
                        f = (jax.checkpoint(loss_on) if self.remat
                             else loss_on)
                        return f(ps, auxs_in, xb, yb)

                    xs = jnp.swapaxes(
                        x.reshape((-1, k) + x.shape[1:]), 0, 1)
                    ys = jnp.swapaxes(
                        y.reshape((-1, k) + y.shape[1:]), 0, 1)

                    if pack_grads is not None:
                        # ZeRO-2 carry: packed bucket shards (1/N resident)
                        # plus full f32 grads ONLY for the passthrough set
                        def body(carry, xy):
                            pacc, gpt, lacc, auxs_c = carry
                            xb, yb = xy
                            (lv, new_aux), g = jax.value_and_grad(
                                loss_of, has_aux=True)(list(params), auxs_c,
                                                       xb, yb)
                            pk = pack_grads(g)
                            pacc = [a + q for a, q in zip(pacc, pk)]
                            gpt = [a + g[i].astype(jnp.float32)
                                   for a, i in zip(gpt, pt)]
                            return (pacc, gpt, lacc + lv, new_aux), None

                        init = ([jax.lax.with_sharding_constraint(
                                    jnp.zeros((b.padded,), jnp.float32),
                                    zshard)
                                 for b in self._zero_layout.buckets],
                                [jnp.zeros(params[i].shape, jnp.float32)
                                 for i in pt],
                                jnp.zeros((), jnp.float32), list(auxs))
                        (psum_b, gpt_sum, lsum, new_auxs), _ = jax.lax.scan(
                            body, init, (xs, ys))
                        packed = [p / k for p in psum_b]
                        grads = [None] * len(params)
                        for j, i in enumerate(pt):
                            grads[i] = gpt_sum[j] / k
                        loss_val = lsum / k
                    else:
                        def body(carry, xy):
                            gacc, lacc, auxs_c = carry
                            xb, yb = xy
                            (lv, new_aux), g = jax.value_and_grad(
                                loss_of, has_aux=True)(list(params), auxs_c,
                                                       xb, yb)
                            # accumulate in f32: summing k similar-magnitude
                            # bf16 grads in bf16 would compound rounding vs
                            # the k=1 step
                            gacc = [a + gi.astype(jnp.float32)
                                    for a, gi in zip(gacc, g)]
                            return (gacc, lacc + lv, new_aux), None

                        init = ([jnp.zeros(p.shape, jnp.float32)
                                 for p in params],
                                jnp.zeros((), jnp.float32), list(auxs))
                        (gsum, lsum, new_auxs), _ = jax.lax.scan(
                            body, init, (xs, ys))
                        grads = [g / k for g in gsum]  # f32; cast per param
                        packed = None
                        loss_val = lsum / k
                else:
                    def loss_of(ps):
                        f = (jax.checkpoint(loss_on) if self.remat
                             else loss_on)
                        return f(ps, list(auxs), x, y)

                    (loss_val, new_auxs), grads = jax.value_and_grad(
                        loss_of, has_aux=True)(list(params))
                    packed = None
                new_params = list(params)
                new_zstates, new_zres = zstates, zres
                new_states = [()] * len(param_handles)
                with jax.named_scope("optimizer"):
                    if zero_update is not None:
                        new_params, new_zstates, new_zres = zero_update(
                            new_params, list(grads), zstates, zres,
                            lr, wd, rescale, clip, t, packed_grads=packed)
                    if pt:
                        sub_w, sub_st = update_pt(
                            [new_params[i] for i in pt],
                            [grads[i] for i in pt], [states[i] for i in pt],
                            lr, wd, rescale, clip, t)
                        for j, i in enumerate(pt):
                            new_params[i] = sub_w[j]
                            new_states[i] = sub_st[j]
                return (new_params, new_auxs, new_states, new_zstates,
                        new_zres, loss_val)
            finally:
                for p, v in zip(param_handles, saved):
                    p._data._data = v
                for p, v in zip(aux_handles, saved_aux):
                    p._data._data = v
                rng_mod.pop_trace_provider()

        repl = NamedSharding(self.mesh, P())
        axes = data_axis_names(self.mesh)
        batch = NamedSharding(self.mesh,
                              P(axes if len(axes) > 1 else axes[0]))
        zstate_sh = getattr(self, "_zero_state_sh", []) if self.zero else []
        zres_sh = [self._zero_layout.shard_spec(self.mesh)
                   if r is not None else None
                   for r in self._zero_residuals] if self.zero else []
        # the step donates every argument it replaces, wherever the backend
        # donates at all. In and out shardings are the same trees, so each
        # donated leaf has an output to alias, and _collect made every leaf
        # a buffer of its own. Never the batch (a caller's pool or
        # DeviceFeed may hand it in again), the scalars, the key or the
        # auxiliary states.
        donate = _DONATED if donation_supported() else ()
        self._step_fn = jax.jit(
            step,
            in_shardings=(self._param_sh, repl, self._state_sh, zstate_sh,
                          zres_sh, batch, batch, repl, repl, repl, repl, repl,
                          None),
            out_shardings=(self._param_sh, repl, self._state_sh, zstate_sh,
                           zres_sh, repl),
            donate_argnums=donate)
        # what says it engaged, on every train/compile and train/dispatch
        # span and in profiler.get_memory_stats(): the buffers a step hands
        # back, and how many of them are a donated argument's, written in
        # place (all but the loss and the auxiliary states, or none)
        replaced = len(jax.tree.leaves(
            (self._states, self._zero_states, self._zero_residuals))) \
            + len(param_handles)
        self._step_buffers = {
            "outputs": replaced + len(aux_handles) + 1,
            "donated": replaced if donate else 0}
        metrics.record_memory_stats(
            step_outputs=self._step_buffers["outputs"],
            step_donated=self._step_buffers["donated"],
            aux_bytes_per_device=sum(
                fsdp_mod.per_device_bytes(p.data().data)
                for p in aux_handles))
        self._comm_step = self._comm_record()

    def step_async(self, x, y) -> NDArray:
        """One SPMD train step; returns the loss WITHOUT a host sync, so callers
        can keep the device queue full (JAX async dispatch ≈ the reference
        engine's lazy push; WaitToRead happens when the caller materializes the
        loss)."""
        with tracer.span("train/step", args={"step": self._t + 1}):
            return self._issue(x, y)[0]

    def _issue(self, x, y) -> tuple:
        """Everything of one step up to the handle swap, under the caller's
        ``train/step`` span; every span carries the step's number, and the
        set-up phases end in a memory mark. Returns the loss, whether the
        call traced, and the nanoseconds its four issuing spans measured
        (place, prepare, dispatch or compile, adopt)."""
        x = x if isinstance(x, NDArray) else nd_mod.array(x)
        y = y if isinstance(y, NDArray) else nd_mod.array(y)
        step = {"step": self._t + 1}
        built = self._step_fn is None
        if built:
            self._stats.miss()
            with metrics.marked_span("train/collect", args=step):
                self._collect(x)
            with metrics.marked_span("train/build", args=step):
                self._build()
        else:
            self._stats.hit()
        if self.micro_batches > 1 and x.shape[0] % self.micro_batches:
            raise ValueError(
                f"batch size {x.shape[0]} is not divisible by "
                f"micro_batches={self.micro_batches}; pad or drop the tail "
                f"batch (ImageRecordIter marks it with .pad)")
        with tracer.span("train/place", args=step) as place:
            xs = shard_batch(x, self.mesh).data
            ys = shard_batch(y, self.mesh).data
        with tracer.span("train/prepare", args=step) as prepare:
            self._t += 1
            opt = self.optimizer
            lr = self._scalar("lr", opt.learning_rate)
            wd = self._scalar("wd", opt.wd)
            # grads are mean-loss grads already; rescale stays 1 (clip honors
            # the optimizer's clip_gradient, a static variant inside
            # update_all)
            rescale = self._scalar("rescale", 1.0)
            clip = self._scalar("clip", opt.clip_gradient
                                if opt.clip_gradient is not None else 0.0)
            ahead, self._key_ahead = self._key_ahead, None
            key = ahead[1] if ahead is not None and ahead[0] == self._t \
                else jax.random.key(self._t)
            params = [p.data().data for p in self._param_handles]
            auxs = [p.data().data for p in self._aux_handles]
            args = (params, auxs, self._states, self._zero_states,
                    self._zero_residuals, xs, ys, lr, wd, rescale, clip,
                    key, self._t)
            batch_sig = (xs.shape, xs.dtype, ys.shape, ys.dtype)
            # a new signature: the call below traces and compiles
            traces = built or batch_sig != self._avals_batch
            if traces:
                # keep only avals (shape/dtype) for cost_analysis — holding
                # the real arrays would pin the previous step's buffers in
                # HBM; they change only with the batch's shape
                self._avals_batch = batch_sig
                self._last_avals = jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
                    if hasattr(a, "shape") else a, args)
        call = metrics.marked_span if traces else tracer.span
        with call("train/compile" if traces else "train/dispatch",
                  args=dict(step, **self._step_buffers)) as dispatch, \
                self._kernel_scope():
            (new_params, new_auxs, new_states, new_zstates, new_zres,
             loss) = self._step_fn(*args)
        with tracer.span("train/adopt", args=step) as adopt:
            for p, v in zip(self._param_handles, new_params):
                p._data._data = v
                p._data._version += 1
            for p, v in zip(self._aux_handles, new_auxs):
                p._data._data = v
                p._data._version += 1
            self._states = new_states
            self._zero_states = new_zstates
            self._zero_residuals = new_zres
            self.optimizer.num_update = self._t
            # the next step's key now, behind this step on the device: a
            # caller that reads the loss back every step leaves the chip idle
            # from the read-back to the next dispatch, and whatever is made
            # between the two (a seed program, a transfer a scalar) adds its
            # host round trip to every step
            self._key_ahead = (self._t + 1, jax.random.key(self._t + 1))
            if sanitize.enabled("donation"):
                # the old weights and slots are gone on a backend that
                # donates; poisoned, a stale read raises by name on the CPU
                # too, where nothing was donated and it would read old values
                sanitize.poison(
                    jax.tree.leaves([args[i] for i in _DONATED]),
                    origin="DataParallelTrainer's step (donate_argnums "
                           "params/opt-state)")
            metrics.record_comm_step(**self._comm_step)
        return NDArray(loss), traces, (place.dur_ns, prepare.dur_ns,
                                       dispatch.dur_ns, adopt.dur_ns)

    def _scalar(self, name: str, value):
        """``value`` as a float32 scalar on the device: the array of the
        step before, while the value stands (a constant learning rate is one
        transfer a run, a schedule one a change)."""
        held = self._scalars.get(name)
        if held is None or held[0] != value:
            held = self._scalars[name] = (value,
                                          jnp.asarray(value, jnp.float32))
        return held[1]

    def _comm_record(self) -> dict:
        """One step's comm accounting (profiler.get_comm_stats), worked out
        once when the step is built: analytic per-device ring bytes —
        reduce-scatter + all-gather legs on the ZeRO path, the full-allreduce
        equivalent on the replicated path — so the two paths are directly
        comparable."""
        n = data_size(self.mesh)
        if self.zero and self._zero_layout is not None:
            c = self._zero_layout.step_comm()
            if self.stage >= 2 and self.micro_batches > 1:
                # ZeRO-2 reduce-scatters each micro-gradient into the shard
                # accumulator: k reduce legs per step instead of one
                c["bytes_reduced"] *= self.micro_batches
            if self.stage >= 3 and self._param_sh is not None:
                # stage-3 params live 1/N: the compiler's JIT all-gathers
                # (fwd + bwd) and grad reduce-scatter don't pass through the
                # explicit bucket collectives, so account them analytically
                # with the same per-device ring fractions step_comm() uses
                axis = fsdp_axis_name(self.mesh)
                nf = fsdp_size(self.mesh)
                fsdp_bytes = sum(
                    int(np.prod(p.data().shape))
                    * np.dtype(str(p.data().dtype)).itemsize
                    for p, sh in zip(self._param_handles, self._param_sh)
                    if any(fsdp_mod._mentions(e, axis) for e in sh.spec))
                frac = (nf - 1) / nf if nf > 1 else 0.0
                c["bytes_gathered"] += int(2 * fsdp_bytes * frac)
                c["bytes_reduced"] += int(fsdp_bytes * frac)
            return dict(c, zero=True, allreduce_bytes=0)
        frac = 2.0 * (n - 1) / n if n > 1 else 0.0
        grad_bytes = sum(
            int(np.prod(p.data().shape))
            * np.dtype(str(p.data().dtype)).itemsize
            for p in self._param_handles)
        return {"dp": n, "allreduce_bytes": int(grad_bytes * frac)}

    def optimizer_state_bytes(self) -> int:
        """Optimizer-slot bytes RESIDENT PER DEVICE (the ZeRO-1 headline
        metric: 1/N with sharding on, full with it off). Valid after the
        first step."""
        def per_device(arr):
            sh = getattr(arr, "sharding", None)
            shape = tuple(arr.shape)
            if sh is not None and hasattr(sh, "shard_shape"):
                shape = sh.shard_shape(shape)
            return int(np.prod(shape)) * np.dtype(str(arr.dtype)).itemsize \
                if len(shape) else np.dtype(str(arr.dtype)).itemsize
        return sum(per_device(s) for s in self.optimizer_slots())

    def optimizer_slots(self) -> List:
        """Every optimizer-state array the step carries (per-param slots,
        ZeRO bucket slots, compression residuals), as placed on the mesh.
        Valid after the first step, and until the next: that one donates
        them (see the class docstring)."""
        slots = [s for st in list(self._states) + list(self._zero_states)
                 for s in (st or ()) if hasattr(s, "dtype")]
        return slots + [r for r in self._zero_residuals if r is not None]

    def optimizer_state_by_param(self) -> dict:
        """``{parameter name: tuple of its optimizer slots, each in the
        parameter's own shape}`` (Adam: first and second moment), whichever
        way the step carries them: ZeRO's packed buckets are unpacked through
        the layout. Valid after the first step, and until the next: a slot
        the step carries in the parameter's shape is handed out as it is,
        and the next step donates it."""
        by_index = dict(enumerate(self._states))
        if self._zero_layout is not None:
            by_index.update(jax.jit(
                lambda st: zero_mod.unpack_states(self._zero_layout, st))(
                    self._zero_states))
        return {n: tuple(by_index[i])
                for i, n in enumerate(self._param_names)}

    def step(self, x, y) -> float:
        """One step ending in the loss on the host. The wait is
        ``train/readback``, or ``train/first_readback`` after a call that
        traced: that one loads the executable onto the device and runs it
        for the first time. Every step leaves a row in the step ring
        (``profiler.get_step_timeline()``, ``flops.STEP_ROW``); the first
        wait and the steps numbered 1, 2, 4, 8, ... end in a memory mark
        (inside ``train/step``: about 0.9 ms on a v5e host, so the row's
        whole is what the caller's clock sees), no other step does."""
        with tracer.span("train/step", args={"step": self._t + 1}) as whole:
            loss, traced, (place, prepare, dispatch, adopt) = \
                self._issue(x, y)
            wait = metrics.marked_span if traced else tracer.span
            with wait("train/first_readback" if traced else "train/readback",
                      args={"step": self._t}) as readback:
                value = float(loss.data)
            if self._t & (self._t - 1) == 0:
                metrics.mark_memory(f"train/step/{self._t}")
        nivcsw = resource.getrusage(_RUSAGE_WHO).ru_nivcsw
        flops.record_step(whole.dur_ns / 1e9, row={
            "step": self._t, "start_ns": whole.t0_ns,
            "place_s": place / 1e9, "prepare_s": prepare / 1e9,
            "dispatch_s": dispatch / 1e9, "adopt_s": adopt / 1e9,
            "readback_s": readback.dur_ns / 1e9,
            "step_s": whole.dur_ns / 1e9, "traced": traced,
            "nivcsw": nivcsw - self._nivcsw})
        self._nivcsw = nivcsw
        return value

    def device_feed(self, batches, depth: Optional[int] = None):
        """Wrap an iterable of ``(x, y)`` batches (or ``DataBatch``es) in a
        ``device_feed.DeviceFeed`` committed to this trainer's dp batch
        sharding: a producer thread keeps the next ``depth`` batches resident
        across the mesh, and ``step_async``'s ``shard_batch`` recognizes them
        as placed (no second ``device_put``). Multi-process: each rank feeds
        its LOCAL shard, exactly like ``shard_batch``. ::

            for x, y in dpt.device_feed(loader):
                dpt.step_async(x, y)
        """
        from ..device_feed import DeviceFeed
        return DeviceFeed(batches, depth=depth, placement=self.mesh)

    def lowered(self):
        """The step program AOT-lowered at the last step's signature (a
        ``jax.stages.Lowered``): ``.as_text()`` shows which kernels the step
        really calls, ``.compile()`` feeds :meth:`cost_analysis`. Valid after
        the first step."""
        if self._step_fn is None or not hasattr(self, "_last_avals"):
            raise RuntimeError("run at least one step first")
        with self._kernel_scope():
            return self._step_fn.lower(*self._last_avals)

    def cost_analysis(self) -> dict:
        """XLA's own cost model for the compiled step (flops, bytes accessed).
        Valid after the first step (``tests/test_trainer_donation.py`` calls it).
        The lowering/compile for the analysis is cached (first call only)."""
        if not hasattr(self, "_cost_cache"):
            self._cost_cache = dict(
                self.lowered().compile().cost_analysis() or {})
        return self._cost_cache
