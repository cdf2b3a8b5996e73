"""Collectives — the communication backbone (SURVEY.md §5 "distributed communication
backend"): one layer exposing allreduce/allgather/reducescatter/broadcast/barrier as
XLA collectives over a Mesh, replacing the reference's Comm tree / NCCL / ps-lite
stack (src/kvstore/comm.h, kvstore_nccl.h, kvstore_dist.h).

Two API levels:

* **array level** (used by KVStore dist mode): ``allreduce_array`` etc. operate on a
  replicated/sharded ``jax.Array`` and run a tiny pjit'd program whose collective XLA
  lowers onto ICI (in-slice) or DCN (cross-slice) automatically.
* **in-program level** (used inside shard_map'd training steps): ``psum``/
  ``all_gather``/``reduce_scatter``/``ppermute`` re-exports with the mesh axis name —
  these are what a sharded train step calls so XLA can overlap them with compute
  (the reference's push/pull priority-overlap trick, model.py:141-153, becomes XLA
  latency hiding).
"""

from __future__ import annotations

import os
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from .mesh import Mesh, get_default_mesh

__all__ = ["allreduce", "allreduce_array", "allgather_array", "broadcast_array",
           "reduce_scatter_array", "all_to_all_array", "a2a_impl", "barrier",
           "psum", "pmean", "all_gather", "reduce_scatter", "ppermute",
           "all_to_all", "shard_map_compat"]


def shard_map_compat(fn, mesh, in_specs, out_specs, check: bool = False):
    """The framework's one call to ``jax.shard_map``: every shard_map
    (collectives, ring attention, MoE dispatch, GPipe, ZeRO) routes through
    here, with the varying-manual-axes check off unless asked for."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)

# -- in-program collectives (use inside shard_map/pjit bodies) --------------
psum = lax.psum
pmean = lax.pmean
all_gather = lax.all_gather
ppermute = lax.ppermute
all_to_all = lax.all_to_all


def reduce_scatter(x, axis_name: str, scatter_dimension: int = 0, tiled: bool = True):
    return lax.psum_scatter(x, axis_name, scatter_dimension=scatter_dimension,
                            tiled=tiled)


# -- array-level collectives ------------------------------------------------

def _shard_map_1d(fn, mesh: Mesh, in_spec, out_spec):
    return shard_map_compat(fn, mesh, in_spec, out_spec)


def allreduce_array(x, mesh: Optional[Mesh] = None, op: str = "sum"):
    """All-reduce a (replicated or dp-sharded) array over the mesh's first axis.

    For a fully-replicated single-process array this is the identity for 'sum' over
    ranks=1; in multi-process (jax.distributed) it reduces across processes.
    """
    mesh = mesh or get_default_mesh()
    axis = mesh.axis_names[0]
    if mesh.devices.size == 1:
        return jnp.asarray(x)

    def _psum(v):
        r = lax.psum(v, axis)
        return r / mesh.shape[axis] if op == "mean" else r

    fn = shard_map_compat(_psum, mesh, P(), P())

    # Resilience seam + retry at the array-level entry (the path kvstore and
    # barrier() ride): a transient backend failure here — the "one
    # UNAVAILABLE erased a bench round" incident — is retried; the injected
    # `collective` fault reproduces it on CPU tier-1, where the
    # cross-process short-circuits above never fire.
    from ..resilience import fault_point, retry_transient

    def _run():
        fault_point("collective")
        return fn(jnp.asarray(x))

    return retry_transient(_run, label="collective.allreduce")


allreduce = allreduce_array


def allgather_array(x, mesh: Optional[Mesh] = None, axis: int = 0):
    """Gather dp-sharded rows into the full array on every device."""
    mesh = mesh or get_default_mesh()
    ax_name = mesh.axis_names[0]
    if mesh.devices.size == 1:
        return jnp.asarray(x)
    spec = [None] * jnp.ndim(x)
    spec[axis] = ax_name

    def _ag(v):
        return lax.all_gather(v, ax_name, axis=axis, tiled=True)

    fn = shard_map_compat(_ag, mesh, P(*spec), P())
    return fn(jnp.asarray(x))


def reduce_scatter_array(x, mesh: Optional[Mesh] = None, axis: int = 0):
    mesh = mesh or get_default_mesh()
    ax_name = mesh.axis_names[0]
    if mesh.devices.size == 1:
        return jnp.asarray(x)
    spec = [None] * jnp.ndim(x)
    spec[axis] = ax_name

    def _rs(v):
        return lax.psum_scatter(v, ax_name, scatter_dimension=axis, tiled=True)

    fn = shard_map_compat(_rs, mesh, P(), P(*spec))
    return fn(jnp.asarray(x))


_A2A_IMPLS = ("jit_reshard", "shard_map")
_a2a_programs = None


def a2a_impl() -> str:
    """Active array-level all_to_all lowering, selected by ``MXTPU_A2A_IMPL``.

    * ``jit_reshard`` (default) — the fast path the PR 8 ``all_to_all_probe``
      proved: express the exchange as a sharding-spec flip inside one jitted
      identity and let GSPMD emit the native all-to-all. The explicit
      ``shard_map``+``lax.all_to_all`` lowering was ~12.6× slower for the same
      logical op (round-5 review, 8-virtual-device CPU mesh: 64 MB a2a at
      9,582 ms vs 1,117 ms allreduce).
    * ``shard_map`` — the legacy explicit lowering, kept for A/B comparison.
    """
    impl = os.environ.get("MXTPU_A2A_IMPL", "jit_reshard").strip().lower()
    if impl not in _A2A_IMPLS:
        raise ValueError(f"MXTPU_A2A_IMPL={impl!r}: expected one of {_A2A_IMPLS}")
    return impl


def _a2a_program_cache():
    # lazy: collectives loads very early; step_cache registration can wait
    global _a2a_programs
    if _a2a_programs is None:
        from ..step_cache import ProgramCache
        _a2a_programs = ProgramCache("a2a_reshard")
    return _a2a_programs


def all_to_all_array(x, mesh: Optional[Mesh] = None, split_axis: int = 1,
                     concat_axis: int = 0, *, axis_name: Optional[str] = None,
                     tiled: bool = True, impl: Optional[str] = None):
    """Transpose shard ownership: each device scatters its ``split_axis``
    slices to peers and concatenates what it receives along ``concat_axis``
    (the Ulysses/MoE dispatch primitive). ``x`` is sharded on ``concat_axis``
    in, sharded on ``split_axis`` out.

    Two forms, so every all-to-all in the framework routes through ONE place:

    * **in-program** (``axis_name`` given): call from inside a shard_map body —
      dispatches straight to ``lax.all_to_all`` over that axis (``tiled``
      honored). MoE dispatch and Ulysses head/sequence exchange use this.
    * **array-level** (no ``axis_name``): operates on a global ``jax.Array``
      over the mesh's first axis. The lowering is selected by ``impl`` /
      ``MXTPU_A2A_IMPL`` (see :func:`a2a_impl`): the default ``jit_reshard``
      exploits that the tiled exchange is semantically a pure reshard — the
      global array is unchanged, only its sharding flips from
      ``concat_axis`` to ``split_axis`` — so a jitted spec flip lets GSPMD
      emit the native all-to-all instead of the degenerate shard_map lowering.
      Compiled programs are cached per (mesh, shape, dtype, axes) signature.
    """
    if axis_name is not None:
        return lax.all_to_all(x, axis_name, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=tiled)

    mesh = mesh or get_default_mesh()
    ax_name = mesh.axis_names[0]
    if mesh.devices.size == 1:
        return jnp.asarray(x)
    x = jnp.asarray(x)
    in_spec = [None] * x.ndim
    in_spec[concat_axis] = ax_name
    out_spec = [None] * x.ndim
    out_spec[split_axis] = ax_name

    chosen = impl or a2a_impl()
    if chosen not in _A2A_IMPLS:
        raise ValueError(f"all_to_all_array impl={chosen!r}: expected one of "
                         f"{_A2A_IMPLS}")
    key = (chosen, mesh, x.shape, str(x.dtype), split_axis, concat_axis)

    if chosen == "jit_reshard":
        in_sh = NamedSharding(mesh, P(*in_spec))
        out_sh = NamedSharding(mesh, P(*out_spec))

        def _build_reshard():
            def _flip(v):
                v = lax.with_sharding_constraint(v, in_sh)
                return lax.with_sharding_constraint(v, out_sh)
            return jax.jit(_flip, out_shardings=out_sh)

        fn = _a2a_program_cache().get_or_build(key, _build_reshard)
        return fn(x)

    def _build_shard_map():
        def _a2a(v):
            return lax.all_to_all(v, ax_name, split_axis=split_axis,
                                  concat_axis=concat_axis, tiled=True)
        return shard_map_compat(_a2a, mesh, P(*in_spec), P(*out_spec))

    fn = _a2a_program_cache().get_or_build(key, _build_shard_map)
    return fn(x)


def broadcast_array(x, mesh: Optional[Mesh] = None, root: int = 0):
    """Broadcast root's value to all devices (device_put with replicated sharding)."""
    mesh = mesh or get_default_mesh()
    return jax.device_put(jnp.asarray(x), NamedSharding(mesh, P()))


def barrier(mesh: Optional[Mesh] = None):
    """Block until all devices/processes reach this point (ps::Postoffice barrier
    parity): a 1-element psum everyone must contribute to."""
    mesh = mesh or get_default_mesh()
    out = allreduce_array(jnp.ones(()), mesh)
    jax.block_until_ready(out)
    return float(out)


# -- cross-process collectives (kvstore dist_sync backbone) -----------------
# The reference's worker→server push/pull (kvstore_dist.h, ps-lite/ZMQ) becomes one
# XLA collective over a process-spanning mesh: each process contributes its local
# value on a leading "proc" axis; the reduction rides DCN/ICI.

def _process_mesh() -> Mesh:
    import numpy as np
    devs = jax.devices()
    nproc = jax.process_count()
    per = len(devs) // nproc
    # one device per process is enough for host-value reduction
    picked = [d for d in devs if d.id % per == 0] if per > 1 else devs
    return Mesh(np.array(picked[:nproc]), ("proc",))


def _process_exchange(x, body):
    """Shared cross-process plumbing: stack each rank's host value on a 'proc'
    axis, run `body` replicated, return the host-local result. Both
    allreduce_processes and allgather_processes ride this one path so
    transport fixes land once. Wall time + payload bytes land in the
    profiler's comm counters (``get_comm_stats().collective_*``) — the
    measured half of the comm-accounting story (the in-program ZeRO
    collectives are accounted analytically per step)."""
    import time
    import numpy as np
    from .. import profiler
    from ..observability import tracer
    from ..resilience import fault_point, retry_transient
    t0 = time.perf_counter()
    local = np.asarray(jax.device_get(jnp.asarray(x)))[None]

    def _run():
        # seam + retry around the whole exchange: DCN flakes surface here as
        # backend UNAVAILABLE, and re-running the collective is idempotent
        # (every rank re-contributes the same host value)
        fault_point("exchange")
        with tracer.span("comm/exchange", cat="comm",
                         args={"bytes": int(local.nbytes)}):
            mesh = _process_mesh()
            sh = NamedSharding(mesh, P("proc"))
            arr = jax.make_array_from_process_local_data(sh, local)
            fn = jax.jit(body, out_shardings=NamedSharding(mesh, P()))
            out = fn(arr)
            jax.block_until_ready(out)
            return jnp.asarray(jax.device_get(out))

    res = retry_transient(_run, label="collective.exchange")
    profiler.record_collective((time.perf_counter() - t0) * 1e3, local.nbytes)
    return res


def allreduce_processes(x, op: str = "sum"):
    """Reduce a per-process host value across ALL processes; returns a host-local
    array every rank can read (dist_sync push semantics, kvstore_dist_server.h:283)."""
    nproc = jax.process_count()
    if nproc == 1:
        return jnp.asarray(x)

    def _sum(a):
        s = jnp.sum(a, axis=0)
        return s / nproc if op == "mean" else s

    return _process_exchange(x, _sum)


def allreduce_rowsparse_processes(indices, values, num_rows: int):
    """Cross-process row-sparse sum WITHOUT densifying: returns
    ``(union_rows, summed_values)`` where payload across the wire is
    O(union rows), not O(dense size).

    Reference: ``kvstore_dist.h:436-510`` DataHandleRowSparse /
    EncodeRowSparseKey ship only live rows over ps-lite. Here the exchange is
    three static-shape XLA collectives:

    1. allgather each rank's (count-padded) row ids — O(max_rows × nproc) ints;
    2. every rank deterministically computes the sorted union on host;
    3. allreduce a (union_padded × row_width) value slab — O(union rows).

    The union slab is padded to the next power of two so XLA recompiles
    O(log num_rows) distinct programs, not one per distinct union size
    (the reference's bucketing trick applied to comm shapes).
    """
    import numpy as np
    idx = np.asarray(jax.device_get(jnp.asarray(indices))).astype(np.int64)
    vals = np.asarray(jax.device_get(jnp.asarray(values)))
    if jax.process_count() == 1:
        return jnp.asarray(idx), jnp.asarray(vals)

    # 1) agree on a common padded index length (gather per-rank counts — nproc
    # scalars), then allgather the padded row ids. Pad marker is num_rows (an
    # invalid row id). nmax is pow2-bucketed like the value slab so varying
    # live-row counts reuse compiled programs.
    counts = np.asarray(jax.device_get(allgather_processes(
        jnp.asarray([np.int32(len(idx))]))))
    nmax = 1
    while nmax < max(1, int(counts.max())):
        nmax *= 2
    nmax = min(nmax, num_rows)
    pad = np.full((nmax,), num_rows, np.int32)
    pad[:len(idx)] = idx
    all_idx = np.asarray(jax.device_get(allgather_processes(
        jnp.asarray(pad)))).astype(np.int64)

    # 2) deterministic union on every rank
    union = np.unique(all_idx.reshape(-1))
    union = union[union < num_rows]
    # bucket the slab length: next power of two, so comm programs are reused
    cap = 1
    while cap < max(1, len(union)):
        cap *= 2
    cap = min(cap, num_rows)

    # 3) scatter local rows into the union slab, allreduce the slab
    slab = np.zeros((cap,) + vals.shape[1:], vals.dtype)
    pos = np.searchsorted(union, idx)
    np.add.at(slab, pos, vals)        # accumulate — local dup rows stay correct
    summed = allreduce_processes(jnp.asarray(slab))
    return jnp.asarray(union), jnp.asarray(summed)[:len(union)]


def allgather_processes(x):
    """Concatenate each process's host value along a new leading axis
    (every rank receives all contributions)."""
    if jax.process_count() == 1:
        return jnp.asarray(x)[None]
    return _process_exchange(x, lambda a: a)


def broadcast_processes(x, root: int = 0):
    """Every rank receives root's value (ps-lite init-broadcast parity)."""
    import numpy as np
    if jax.process_count() == 1:
        return jnp.asarray(x)
    xs = np.asarray(jax.device_get(jnp.asarray(x)))
    contrib = xs if jax.process_index() == root else np.zeros_like(xs)
    return allreduce_processes(contrib)


def process_barrier():
    """Block until every process arrives (ps::Postoffice::Barrier parity)."""
    out = allreduce_processes(jnp.ones(()))
    return float(out)
