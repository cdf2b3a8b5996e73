"""mxtpu.observability — unified step-timeline tracing + MFU accounting.

The reference ships profiling as a first-class subsystem (``src/profiler/``:
chrome://tracing export, aggregate stats, Domain/Task/Counter/Marker
objects). This package is that subsystem TPU-natively, unifying every
instrumentation point the framework already had — the fused-step cache, the
DeviceFeed producer, the ZeRO comm path, the async checkpoint writer — into
**spans on one step timeline**:

* :mod:`.tracer` — per-thread span recorder: every span opens its
  ``jax.profiler.TraceAnnotation`` (so spans reach any ``jax.profiler``
  session unarmed) and counts into the totals by name
  (``profiler.get_span_totals()``); ``MXTPU_TRACE=1`` or
  ``profiler.set_state('run')`` arms the lock-free-ish bounded rings, whose
  events carry ``id`` and ``parent``.
* :mod:`.export` — chrome-trace JSON serialization (pid/tid rows per thread,
  metadata names, per-request swim-lanes, the ``profiler.dump()``/
  ``dumps()`` body, ``request_timeline``).
* :mod:`.flops` — MFU accounting (XLA cost-analysis FLOPs with an analytic
  conv/matmul fallback, bounded step ring → steps/s + p50/p99 + MFU; a
  ``DataParallelTrainer`` step's row holds its parts:
  ``profiler.get_step_timeline()``).
* :mod:`.metrics` — the subsystem counter stores (checkpoint / feed / comm /
  sanitizer), moved here from ``profiler.py``; the profiler re-exports them.
  The memory store also keeps the marks taken at the ends of phases
  (``mark_memory``: host and device bytes; ``get_memory_stats()["marks"]``).
* :mod:`.histogram` — bounded log-bucketed streaming histograms backing the
  serving latency percentiles (TTFT/queue-wait/prefill/first-decode/
  per-token) and fused-step times.
* :mod:`.exporter` — pull-based Prometheus/JSON metrics endpoint
  (``MXTPU_METRICS_PORT``; off by default).
* :mod:`.flight` — always-on crash flight recorder; postmortem bundles to
  ``MXTPU_FLIGHT_DIR`` on stalls, resize failures, scheduler-thread
  exceptions, and SIGTERM drains.

``mxtpu.profiler`` remains the user-facing facade — importing this package
directly is for framework internals and tests.

Span catalog (see docs/observability.md):

==========================  =================================================
``import/mxtpu``            the package's import, first line to last
``import/jax``              inside it: the package's first ``import jax``
``net/initialize``          the outermost ``Block.initialize`` (+ mark)
``net/cast``                the outermost ``Block.cast``
``param/set_data``          one ``Parameter.set_data``
``train/step``              one ``DataParallelTrainer.step`` (args: step)
``train/collect``           first call: eager forward, placement, slots
``train/build``             first call: the step function is built
``train/place``             the batch onto the mesh (``shard_batch``)
``train/prepare``           scalars, PRNG key, argument lists
``train/compile``           the step program's call when it traces
``train/dispatch``          the step program's call otherwise
``train/adopt``             handle swap, state swap, comm record
``train/readback``          ``float(loss)``: the host waits for the device
``train/first_readback``    that wait after a call that traced (+ mark)
``jax/trace``               JAX traced a function (args: fun)
``jax/lower``               JAX lowered a program to StableHLO (args: fun)
``jax/compile``             XLA compiled, or the cache loaded (args: fun)
``jax/cache_hit``           instant: persistent compile cache hit
``jax/cache_miss``          instant: compiled here, kept by the cache
``step/compile``            trace+lower+compile of a fused step
``step/execute``            one cache-hit fused-step dispatch
``feed/transfer``           DeviceFeed producer staging one batch
``feed/stall``              consumer blocked waiting on the feed queue
``comm/exchange``           cross-process collective (``_process_exchange``)
``ckpt/snapshot``           device→host state capture (training thread)
``ckpt/write``              serialize+fsync of one step (writer thread)
``ckpt/commit``             atomic rename+COMMIT marker (writer thread)
``feed/queue_depth``        counter: prefetch queue occupancy
``memory/<key>``            counter: a memory mark's numbers (ring armed)
``serving/submit``          instant: request enqueued (args: id)
``serving/admit``           instant: request admitted to a slot (args: id)
``serving/prefix_hit``      instant: radix prefix-cache hit (args: id)
``serving/prefix_miss``     instant: probe found nothing (args: id)
``serving/prefill_chunk``   one chunked-prefill dispatch (args: id)
``serving/first_token``     instant: first generated token (args: id)
``serving/decode``          one slot-batch decode dispatch (args: ids)
``serving/first_decode``    instant: slot's first decode emission (args: id)
``serving/retire``          instant: request left its slot (args: id)
``serving/drain_freeze``    instant: request frozen into a handoff (args: id)
``serving/adopt_resume``    instant: request resumed from a handoff (id)
``serving/drained``         instant: handoff complete (args: ids)
``serving/adopted``         instant: adoption complete (args: ids)
==========================  =================================================

Memory marks (``metrics.mark_memory``) end ``import/mxtpu``,
``net/initialize``, ``train/collect`` / ``build`` / ``compile`` /
``first_readback`` and the steps numbered 1, 2, 4, 8, ... (``train/step/<n>``).
A step's row in the ring (``flops.STEP_ROW``): step, start_ns, place_s,
prepare_s, dispatch_s, adopt_s, readback_s, step_s, traced, nivcsw.

Names on the device (a ``jax.profiler`` trace's operations): Pallas kernels
``flash_fwd``, ``flash_bwd_fused`` (the whole backward), with a window
``flash_fwd_window``, ``flash_bwd_dq_window``, ``flash_bwd_dkv_window``;
``decode_attn_quant``; scopes ``<RootBlock>/block<i>/attn/q_proj`` … from
``Block.__call__`` (the name the parent registered the child under),
``embed``, ``head`` (``TransformerLM``), ``loss``, ``optimizer``,
``optimizer/zero`` (``DataParallelTrainer``; at a data degree of 1 only the
flat leaves' update is under ``optimizer/zero``, a matrix's under
``optimizer`` alone).
"""

from . import (exporter, export, flight, flops, histogram, metrics,  # noqa
               tracer)
from .tracer import counter, enabled, instant, span

__all__ = ["tracer", "export", "flops", "metrics", "histogram",
           "exporter", "flight",
           "span", "instant", "counter", "enabled"]

# MXTPU_METRICS_PORT arms the scrape endpoint at import, mirroring how
# MXTPU_TRACE arms the tracer — off (no socket) when unset
exporter._maybe_start_from_env()
