"""Subsystem counter stores (checkpoint / device-feed / comm / sanitizer).

Moved here from ``mxtpu/profiler.py`` when the profiler became a facade over
``mxtpu.observability`` — the public surface is unchanged and re-exported
from ``mxtpu.profiler`` (``record_*`` / ``get_*_stats`` / ``reset_*``), so
every existing call site and test keeps working.

THE module stats lock: every stat dict here is bumped from more than one
thread — the DeviceFeed producer (``device_feed.py``), the checkpoint writer
(``checkpoint/manager.py``), and the main training thread — and
read-modify-write pairs (total+last) tear without mutual exclusion. One lock,
never held across a call that could re-acquire it (tpulint R004 is the static
guard for this contract).
"""

from __future__ import annotations

import contextlib
import os
import resource
import threading
import time
from typing import Dict, Optional

from . import histogram as _hist
from . import tracer as _tracer

_stats_lock = threading.Lock()


# ---------------------------------------------------------------------------
# checkpoint observability (mxtpu.checkpoint manager counters)
# ---------------------------------------------------------------------------

_CKPT_ZERO = {"saves": 0, "commits": 0, "restores": 0,
              "committed_bytes": 0,
              "blocked_step_ms_total": 0.0, "blocked_step_ms_last": 0.0,
              "save_latency_ms_total": 0.0, "save_latency_ms_last": 0.0,
              "write_ms_last": 0.0,
              "shard_writes": 0, "shard_write_ms_last": 0.0}
_ckpt = dict(_CKPT_ZERO)


def record_checkpoint_save(blocked_ms: float):
    """Training-thread side of an async save: how long the step was blocked
    on the snapshot handoff (device→host DMA start + enqueue)."""
    with _stats_lock:
        _ckpt["saves"] += 1
        _ckpt["blocked_step_ms_last"] = blocked_ms
        _ckpt["blocked_step_ms_total"] += blocked_ms


# Commit observers (resilience's committed-step watermark rides here).
# Registered callables run OUTSIDE the stats lock — a hook may call back
# into any record_*/get_* without self-deadlock.
_commit_hooks: list = []


def add_commit_hook(fn):
    """Register ``fn()`` to run after every checkpoint commit (idempotent)."""
    with _stats_lock:
        if fn not in _commit_hooks:
            _commit_hooks.append(fn)


def record_checkpoint_commit(write_ms: float, latency_ms: float, nbytes: int):
    """Writer-thread side: ``write_ms`` is the serialize+fsync+commit work,
    ``latency_ms`` the enqueue→commit wall time (queueing included),
    ``nbytes`` the committed payload size."""
    with _stats_lock:
        _ckpt["commits"] += 1
        _ckpt["write_ms_last"] = write_ms
        _ckpt["save_latency_ms_last"] = latency_ms
        _ckpt["save_latency_ms_total"] += latency_ms
        _ckpt["committed_bytes"] += int(nbytes)
        hooks = list(_commit_hooks)
    for fn in hooks:
        try:
            fn()
        except Exception as e:
            import logging
            logging.getLogger(__name__).warning("commit hook failed: %s", e)


def record_checkpoint_shard_write(write_ms: float):
    """Writer-thread side on ranks != 0: only this rank's shard write is
    measured — commit stats (count/bytes) belong to rank 0, which owns the
    rename and is the only rank that can see the final dir."""
    with _stats_lock:
        _ckpt["shard_writes"] += 1
        _ckpt["shard_write_ms_last"] = write_ms


def record_checkpoint_restore():
    with _stats_lock:
        _ckpt["restores"] += 1


def get_checkpoint_stats() -> dict:
    """Checkpoint counters (saves/commits/restores, committed bytes, save
    latency, blocked-step time) — the observability contract of the async
    checkpoint subsystem (``tests/test_checkpoint_manager.py`` reads these)."""
    with _stats_lock:
        return dict(_ckpt)


def reset_checkpoint_stats():
    with _stats_lock:
        _ckpt.update(_CKPT_ZERO)


# ---------------------------------------------------------------------------
# device-feed observability (mxtpu.device_feed input-pipeline counters)
# ---------------------------------------------------------------------------

_FEED_ZERO = {"batches_prefetched": 0, "batches_consumed": 0,
              "transfer_count": 0, "resident_skips": 0,
              "transfer_bytes": 0, "transfer_ms_total": 0.0,
              "stall_ms_total": 0.0, "stall_ms_last": 0.0,
              "queue_depth_max": 0, "feed_depth": 0}
_feed = dict(_FEED_ZERO)


def record_feed_transfer(nbytes: int, ms: float):
    """Producer-thread side: one array dispatched through the host→device
    boundary (``ms`` is the non-blocking dispatch wall time)."""
    with _stats_lock:
        _feed["transfer_count"] += 1
        _feed["transfer_bytes"] += int(nbytes)
        _feed["transfer_ms_total"] += ms


def record_feed_resident():
    """Producer-thread side: an array already committed with the target
    sharding was NOT re-transferred — the double-``device_put`` guard
    counter."""
    with _stats_lock:
        _feed["resident_skips"] += 1


def record_feed_prefetch(queue_depth: int):
    """Producer-thread side: one batch staged device-resident; samples the
    queue-depth high-water mark."""
    with _stats_lock:
        _feed["batches_prefetched"] += 1
        if queue_depth > _feed["queue_depth_max"]:
            _feed["queue_depth_max"] = queue_depth


def record_feed_consume(stall_ms: float):
    """Consumer-thread side: one batch taken; ``stall_ms`` is how long the
    step loop was blocked waiting on data (the input-stall metric)."""
    with _stats_lock:
        _feed["batches_consumed"] += 1
        _feed["stall_ms_last"] = stall_ms
        _feed["stall_ms_total"] += stall_ms


def set_feed_depth(depth: int):
    with _stats_lock:
        _feed["feed_depth"] = int(depth)


def get_feed_stats() -> dict:
    """Input-pipeline counters (input-stall ms, transfer bytes/ms, queue-depth
    high-water mark, batches prefetched vs consumed) — the observability
    contract of the device-feed pipeline. ``Speedometer`` prints these and
    ``Module.fit`` logs them per epoch. Counters are monotone until
    :func:`reset_feed_stats`."""
    with _stats_lock:
        return dict(_feed)


def reset_feed_stats():
    """Zero the feed counters (tests, per-epoch accounting)."""
    with _stats_lock:
        _feed.update(_FEED_ZERO)


# ---------------------------------------------------------------------------
# distributed-comm observability (ZeRO-1 / collectives counters)
# ---------------------------------------------------------------------------

_COMM_ZERO = {"steps": 0, "zero_steps": 0,
              "bytes_reduced": 0, "bytes_gathered": 0, "allreduce_bytes": 0,
              "bucket_count": 0, "shard_bytes_per_device": 0, "dp": 1,
              "collectives": 0, "collective_ms_total": 0.0,
              "collective_bytes": 0}
_comm = dict(_COMM_ZERO)


def record_comm_step(bytes_reduced: int = 0, bytes_gathered: int = 0,
                     bucket_count: int = 0, shard_bytes: int = 0,
                     dp: int = 1, allreduce_bytes: int = 0,
                     zero: bool = False):
    """One training step's gradient-exchange accounting (per-device bytes,
    analytic from the bucket layout and dp degree — ring collectives move
    (N-1)/N of the payload per device). The ZeRO path records reduce-scatter
    + all-gather legs; the replicated-psum path records the full all-reduce
    equivalent, so the two are directly comparable (``get_comm_stats()``)."""
    with _stats_lock:
        _comm["steps"] += 1
        if zero:
            _comm["zero_steps"] += 1
        _comm["bytes_reduced"] += int(bytes_reduced)
        _comm["bytes_gathered"] += int(bytes_gathered)
        _comm["allreduce_bytes"] += int(allreduce_bytes)
        _comm["bucket_count"] = int(bucket_count)
        _comm["shard_bytes_per_device"] = int(shard_bytes)
        _comm["dp"] = int(dp)


def record_collective(ms: float, nbytes: int):
    """One host-blocking array-level collective (``parallel.collectives``
    cross-process exchange): measured wall ms + payload bytes."""
    with _stats_lock:
        _comm["collectives"] += 1
        _comm["collective_ms_total"] += ms
        _comm["collective_bytes"] += int(nbytes)


def get_comm_stats() -> dict:
    """Per-step comm counters (bytes reduced/gathered, bucket count, shard
    bytes per device, dp degree, measured collective ms) — the observability
    contract of the ZeRO-1 gradient path. ``Speedometer`` prints the per-step
    deltas; ``Module.fit`` logs them per epoch; ``tests/test_zero_dp.py``
    holds the ZeRO legs against the replicated all-reduce accounting."""
    with _stats_lock:
        return dict(_comm)


def reset_comm_stats():
    with _stats_lock:
        _comm.update(_COMM_ZERO)


# ---------------------------------------------------------------------------
# memory observability (ZeRO/FSDP per-device residency accounting)
# ---------------------------------------------------------------------------

_MEM_ZERO = {"stage": 0, "data_degree": 1, "fsdp_degree": 1,
             "param_bytes_per_device": 0, "grad_bytes_per_device": 0,
             "slot_bytes_per_device": 0, "aux_bytes_per_device": 0,
             "replicated_param_bytes": 0, "replicated_grad_bytes": 0,
             "replicated_slot_bytes": 0,
             "step_outputs": 0, "step_donated": 0}
_mem = dict(_MEM_ZERO)
_marks: list = []            # [mark]: appended and cleared under _stats_lock

# what a device's ``memory_stats()`` is asked for; a backend that reports
# fewer (the CPU reports none) leaves the others out
_DEVICE_KEYS = ("bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
                "peak_bytes_reserved", "bytes_limit")
_PAGE = os.sysconf("SC_PAGE_SIZE")


def _host_bytes() -> dict:
    """This process's resident bytes now (``/proc/self/statm``) and at their
    peak (``getrusage``: kilobytes on Linux)."""
    out = {"host_peak_rss_bytes":
           resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024}
    try:
        with open("/proc/self/statm") as f:
            out["host_rss_bytes"] = int(f.read().split()[1]) * _PAGE
    except (OSError, ValueError, IndexError):
        pass                 # no procfs here: the peak alone
    return out


def _fullest_device() -> dict:
    """``memory_stats()`` of the local device with the most bytes in use, as
    the chip reports it; ``{}`` before a backend exists (asking JAX for its
    devices would make one) and on a backend that reports none."""
    from jax._src import xla_bridge
    if not xla_bridge.backends_are_initialized():
        return {}
    import jax
    rows = [d.memory_stats() or {} for d in jax.local_devices()]
    full = max(rows, key=lambda ms: ms.get("bytes_in_use", 0), default={})
    return {k: int(full[k]) for k in _DEVICE_KEYS if k in full}


def mark_memory(name: str) -> dict:
    """One memory mark, at the END of a phase and never in a steady step
    (``import/mxtpu``, ``net/initialize``, the trainer's ``train/collect`` /
    ``build`` / ``compile`` / ``first_readback``, and ``train/step/<n>`` for
    n = 1, 2, 4, 8, ...): ``{"name", "t_ns" (the tracer's clock),
    "host_rss_bytes", "host_peak_rss_bytes"}`` and, once a backend exists,
    the fullest local device's ``bytes_in_use``, ``peak_bytes_in_use``,
    ``bytes_reserved``, ``peak_bytes_reserved``, ``bytes_limit``, each where
    the backend reports it. ``get_memory_stats()["marks"]`` hands them out in order; with the
    ring armed each number is also a counter track ``memory/<key>``. Beside
    ``param_`` / ``slot_`` / ``aux_bytes_per_device`` a mark reads as owners:
    in use = parameters + slots + auxiliary state + the rest (batches, a
    pool); reserved = the loaded programs' temporaries; limit less both is
    free."""
    sizes = {**_host_bytes(), **_fullest_device()}
    mark = {"name": name, "t_ns": time.perf_counter_ns(), **sizes}
    with _stats_lock:
        _marks.append(mark)
    for key, value in sizes.items():
        _tracer.counter("memory/" + key, value, cat="memory")
    return mark


@contextlib.contextmanager
def marked_span(name: str, args: Optional[dict] = None):
    """``tracer.span(name)`` for a set-up phase: once it has closed without
    an error, a :func:`mark_memory` of its name."""
    with _tracer.span(name, args=args) as span:
        yield span
    mark_memory(name)


def record_memory_stats(**kwargs):
    """Per-device resident-byte accounting for params/grads/optimizer slots
    by ZeRO stage (``parallel.fsdp.measure_memory`` computes the figures from
    the actual placed shardings at trace time). ``replicated_*`` keys carry
    the stage-0 equivalent the shrink ratio is quoted against.
    ``aux_bytes_per_device`` is ``DataParallelTrainer``'s auxiliary state
    (running statistics, routing counts and biases: what rides the step and
    is no parameter's gradient).
    ``step_outputs`` / ``step_donated`` are ``DataParallelTrainer``'s: the
    buffers its step hands back and how many of them are a donated
    argument's, written in place (weights and slots then exist once)."""
    with _stats_lock:
        for k, v in kwargs.items():
            if k in _mem:
                _mem[k] = int(v)


def get_memory_stats() -> dict:
    """Latest memory accounting snapshot — the number that proves ZeRO-2/3
    actually shrinks the footprint. ``compile_cache_summary()`` prints it,
    ``Module.fit`` logs it per epoch, and ``tests/test_fsdp.py`` compares
    the stages with it. ``"marks"``: every :func:`mark_memory` since the
    last reset, in order."""
    with _stats_lock:
        return dict(_mem, marks=[dict(m) for m in _marks])


def reset_memory_stats():
    with _stats_lock:
        _mem.update(_MEM_ZERO)
        _marks.clear()


# ---------------------------------------------------------------------------
# resilience observability (mxtpu.resilience counters)
# ---------------------------------------------------------------------------

_RESIL_ZERO = {"faults_injected": 0,
               "retries": 0, "retries_exhausted": 0, "escalations": 0,
               "watchdog_stalls": 0, "emergency_saves": 0,
               "restarts": 0, "steps_lost": 0,
               "restart_latency_ms_total": 0.0,
               "restart_latency_ms_last": 0.0,
               # live elasticity (mxtpu.resilience.elastic): in-place mesh
               # resizes completed vs process-restart fallbacks taken when an
               # in-place adoption raised
               "live_resizes": 0, "restart_fallbacks": 0,
               "resize_latency_ms_total": 0.0,
               "resize_latency_ms_last": 0.0}
_resil = dict(_RESIL_ZERO)


def record_resilience(key: str, n=1):
    """One resilience event (``mxtpu.resilience``): faults fired, transient
    retries taken/exhausted, non-transient escalations, watchdog stalls,
    emergency saves, supervisor restarts, steps lost since last commit.
    ``*_last`` keys assign; everything else accumulates."""
    with _stats_lock:
        if key.endswith("_last"):
            _resil[key] = n
        else:
            _resil[key] += n


def get_resilience_stats() -> dict:
    """Resilience counters — the observability contract of the fault-
    injection/retry/watchdog/supervisor stack. The exporter serves them;
    the guard tests (``tests/test_resilience_guard.py``) assert injected
    faults left fingerprints here."""
    with _stats_lock:
        return dict(_resil)


def reset_resilience_stats():
    with _stats_lock:
        _resil.update(_RESIL_ZERO)


# ---------------------------------------------------------------------------
# serving observability (mxtpu.serving engine counters)
# ---------------------------------------------------------------------------

_SERVING_ZERO = {"submitted": 0, "admitted": 0, "completed": 0,
                 "cancelled": 0, "rejected": 0, "expired": 0,
                 "prefills": 0, "prefill_chunks": 0,
                 "decode_steps": 0, "tokens_out": 0,
                 "kv_promotions": 0,
                 # shared-prefix radix KV reuse (serving/kv.PrefixCache):
                 # hits/misses count PREFILLED requests with at least one
                 # cache-eligible block (prompt > 32 tokens); hit_tokens is
                 # the positions whose prefill was skipped
                 "prefix_hits": 0, "prefix_misses": 0, "prefix_hit_tokens": 0,
                 # partial-block reuse: hits whose matched length ends inside
                 # a 32-token block (token-granular tail rows copied from a
                 # cached child block); partial_tokens is the sub-block
                 # positions saved, already included in prefix_hit_tokens
                 "prefix_partial_hits": 0, "prefix_partial_tokens": 0,
                 "prefix_inserts": 0, "prefix_evictions": 0,
                 "prefix_cache_bytes": 0,
                 # SLO control plane (mxtpu.sched): requests shed before
                 # their deadline, decode slots preempted for a higher tier,
                 # parked requests resumed
                 "shed": 0, "preempted": 0, "resumed": 0,
                 # batched prefill admissions (mxtpu.sched.admission): one
                 # count per PrefillGroup launched, not per member
                 "prefill_groups": 0,
                 # live elasticity: requests carried across an engine
                 # drain()/adopt() handoff (zero-drop contract)
                 "drained": 0, "adopted": 0,
                 # speculative decode (mxtpu.serving.spec): verify dispatches
                 # taken instead of plain decode turns; tokens the drafter
                 # proposed vs how many the verify forward accepted/rejected
                 # (accepted + rejected == drafted over any window); n-gram
                 # side-index probes on the prefix radix tree. The
                 # accept-length distribution itself is histogram-backed
                 # ("serving/accept_len" -> accept_len_mean + percentiles)
                 "spec_dispatches": 0, "tokens_drafted": 0,
                 "tokens_accepted": 0, "tokens_rejected": 0,
                 "ngram_hits": 0, "ngram_misses": 0,
                 "queue_depth_max": 0, "slots": 0,
                 "slot_occupancy_sum": 0.0, "occupancy_samples": 0,
                 "ttft_ms_total": 0.0, "ttft_ms_last": 0.0,
                 # TTFT decomposition: queue wait (submit -> prefill start)
                 # + prefill (prefill start -> first token); first_decode is
                 # admission-complete -> first decode-chunk token; token_ms is
                 # decode wall time per emitted token (one sample/dispatch)
                 "queue_wait_ms_total": 0.0, "queue_wait_ms_last": 0.0,
                 "prefill_ms_total": 0.0, "prefill_ms_last": 0.0,
                 "first_decode_ms_total": 0.0, "first_decode_ms_last": 0.0,
                 "token_ms_total": 0.0, "token_ms_last": 0.0,
                 # decode-only wall clock and tokens: one decode_ms sample
                 # per decode dispatch (the dispatch's full wall time) plus
                 # the tokens it emitted — decode_tokens / decode_ms_total
                 # is pure decode throughput with prefill, queueing, and
                 # scheduler sleeps excluded (the quant_decode_speedup
                 # methodology; see docs/quantization.md)
                 "decode_ms_total": 0.0, "decode_ms_last": 0.0,
                 "decode_tokens": 0,
                 # KV-cache residency (mxtpu.quant): bytes of the resident
                 # paged cache (data + scales when quantized) and its
                 # storage dtype ('float32' | 'bfloat16' | 'int8' | 'fp8');
                 # decode_kernel is the fused dequant-attention path of a
                 # quantized cache ('pallas' | 'xla'; 'none' when the cache
                 # is full-precision and the fused read never engages)
                 "kv_bytes_resident": 0, "kv_dtype": "float32",
                 "decode_kernel": "none",
                 # identity of the engine that last wrote this store (the
                 # exporter's {engine=...} metric label) — the store is
                 # process-global, so with several in-process engines the
                 # label names the LAST writer; a router reads each
                 # engine.load() for per-replica signals instead
                 "engine": "none"}
_serving = dict(_SERVING_ZERO)

# keys that ASSIGN the latest value instead of accumulating
_SERVING_ASSIGN = ("slots", "prefix_cache_bytes", "kv_bytes_resident")
# string-valued keys (assign verbatim)
_SERVING_STR = ("kv_dtype", "decode_kernel", "engine")
# latency series backed by the histogram store (``histogram.record_value``):
# the compat ``<base>_last``/``<base>_total`` keys AND the ``<base>_p*``
# percentiles in ``get_serving_stats()`` all derive from "serving/<base>"
_SERVING_LATENCY = ("ttft_ms", "queue_wait_ms", "prefill_ms",
                    "first_decode_ms", "token_ms", "decode_ms")
# non-latency histogram series: same "<base>_last" -> "serving/<base>"
# routing and readback as the latency keys (accept_len is the per-slot
# accepted-token count of one speculative verify dispatch)
_SERVING_HIST = ("accept_len",)


def record_serving(key: str, n=1):
    """One serving-engine event (``mxtpu.serving.engine``): request
    lifecycle counts (submitted/admitted/completed/cancelled/rejected/
    expired), prefill and decode-step dispatches, tokens emitted, KV-bucket
    promotions, latency accumulators. ``*_last`` keys assign, ``*_max`` keys
    take the high-water mark, everything else accumulates. Latency
    ``*_ms_last`` keys are routed WHOLE into the histogram store — one
    guarded write per sample instead of the old torn last+total scalar
    pair — and read back (last/total/percentiles) by
    :func:`get_serving_stats`."""
    if key.endswith("_ms_last") or (key.endswith("_last")
                                    and key[:-5] in _SERVING_HIST):
        _hist.record_value("serving/" + key[:-5], float(n))
        return
    with _stats_lock:
        if key.endswith("_last"):
            _serving[key] = n
            base = key[:-5] + "_total"
            if base in _serving:
                _serving[base] += n
        elif key.endswith("_max"):
            if n > _serving[key]:
                _serving[key] = n
        elif key in _SERVING_STR:
            _serving[key] = str(n)
        elif key in _SERVING_ASSIGN:
            _serving[key] = int(n)
        else:
            _serving[key] += n


# per-tenant serving series (mxtpu.sched satellite): counters here, latency
# samples in the histogram store under "serving/tenant/<t>/<base>" (the
# "serving/" prefix keeps them inside reset_serving_stats' blast radius and
# gets them exported as quantile gauges for free). Cardinality is BOUNDED:
# past _TENANT_CAP distinct tenants, everything folds into "__other__" so a
# tenant-id-per-user caller can't grow the store (or the Prometheus page)
# without bound.
_TENANT_CAP = 32
_OTHER_TENANT = "__other__"
_tenants: Dict[str, Dict[str, float]] = {}


def _tenant_key(tenant: str) -> str:
    t = str(tenant)
    if t not in _tenants and len(_tenants) >= _TENANT_CAP:
        return _OTHER_TENANT
    return t


def record_tenant(tenant: str, key: str, n=1):
    """One per-tenant serving sample. ``*_ms_last`` keys are histogram
    samples (``serving/tenant/<t>/<base>``: TTFT, goodput latency);
    everything else accumulates in the tenant's counter row (tokens_out,
    completed, shed, ...)."""
    if key.endswith("_ms_last"):
        with _stats_lock:
            t = _tenant_key(tenant)
            _tenants.setdefault(t, {})
        _hist.record_value(f"serving/tenant/{t}/{key[:-8]}", float(n))
        return
    with _stats_lock:
        row = _tenants.setdefault(_tenant_key(tenant), {})
        row[key] = row.get(key, 0) + n


def record_serving_occupancy(active_slots: int, total_slots: int):
    """One decode-step occupancy sample (active slots / capacity) — the
    utilization series behind ``get_serving_stats()['slot_occupancy']``."""
    with _stats_lock:
        _serving["slots"] = int(total_slots)
        _serving["slot_occupancy_sum"] += \
            active_slots / max(1, total_slots)
        _serving["occupancy_samples"] += 1


def get_serving_stats() -> dict:
    """Serving-engine counters (request lifecycle, decode steps, tokens out,
    TTFT/queue-wait accumulators, mean slot occupancy, KV promotions) — the
    observability contract of :class:`mxtpu.serving.ServingEngine`.
    ``engine.stats()`` reads these; ``docs/serving.md`` has the diagnosis
    guide (e.g. rejected≫0 → raise queue depth; occupancy≈1 with queue
    growth → raise MXTPU_SERVING_SLOTS). Latency keys are histogram-backed:
    the legacy ``<base>_last``/``<base>_total`` scalars stay, and each base
    gains ``_p50/_p90/_p99/_p999`` (log-bucket percentiles, ≤ ~2 % relative
    error — see ``observability/histogram.py``)."""
    with _stats_lock:
        out = dict(_serving)
    samples = out.pop("occupancy_samples")
    occ_sum = out.pop("slot_occupancy_sum")
    out["slot_occupancy"] = (occ_sum / samples) if samples else 0.0
    probes = out["prefix_hits"] + out["prefix_misses"]
    out["prefix_hit_rate"] = (out["prefix_hits"] / probes) if probes else 0.0
    # latency series: read outside _stats_lock (histogram store has its own
    # lock; never nest the two — R004 discipline)
    for base in _SERVING_LATENCY + _SERVING_HIST:
        h = _hist.get_histogram("serving/" + base)
        if h is not None and h.count:
            s = h.summary()
            out[base + "_last"] = s["last"]
            out[base + "_total"] = s["sum"]
            out[base + "_count"] = s["count"]
            for _q, name in _hist.QUANTILES:
                out[f"{base}_{name}"] = s[name]
        else:
            out[base + "_count"] = 0
            for _q, name in _hist.QUANTILES:
                out[f"{base}_{name}"] = 0.0
    # the speculative-decode headline number: mean accepted tokens per live
    # slot per verify dispatch (>= 1.0 always — the bonus token; > 1.0 means
    # drafts are landing and decode is running faster than one token/turn)
    out["accept_len_mean"] = (out.get("accept_len_total", 0.0)
                              / out["accept_len_count"]
                              if out["accept_len_count"] else 0.0)
    # per-tenant series (only when something recorded them — the plain
    # engine's stats dict is unchanged): counters + quantiles of every
    # "serving/tenant/<t>/<base>" histogram (read outside _stats_lock)
    with _stats_lock:
        tenants = {t: dict(row) for t, row in _tenants.items()}
    if tenants:
        for name, s in _hist.get_histogram_stats().items():
            if not name.startswith("serving/tenant/"):
                continue
            _, _, rest = name.partition("serving/tenant/")
            t, _, base = rest.partition("/")
            if t in tenants and base:
                tenants[t][base + "_count"] = s["count"]
                for _q, qname in _hist.QUANTILES:
                    tenants[t][f"{base}_{qname}"] = s[qname]
        out["tenants"] = tenants
    return out


def reset_serving_stats():
    with _stats_lock:
        _serving.update(_SERVING_ZERO)
        _tenants.clear()
    _hist.reset_histograms(prefix="serving/")


# ---------------------------------------------------------------------------
# multi-replica router observability (mxtpu.serving.router)
# ---------------------------------------------------------------------------

_ROUTER_ZERO = {"submitted": 0,
                # routing decisions: prefix-affinity target honored /
                # affinity target over headroom so the request spilled to
                # the least-loaded replica / no affinity (short or
                # cache-opted-out prompt) -> least-loaded
                "routed_affinity": 0, "routed_spill": 0,
                "routed_least_loaded": 0,
                # backpressure: one replica's queue was full and the
                # request moved on to the next candidate (overflow), or
                # EVERY replica was full and submit() raised (rejected)
                "overflow": 0, "rejected": 0,
                # live-rebalance lifecycle: engine swaps via drain/adopt,
                # replicas removed, in-flight requests re-routed to a
                # survivor, and requests LOST in a removal (the zero-drop
                # contract: this stays 0; anything else is a bug a chaos
                # test must catch)
                "rebalanced": 0, "replicas_removed": 0,
                "requests_rebalanced": 0, "requests_dropped": 0,
                "fair_share_syncs": 0,
                "replicas": 0}
_router = dict(_ROUTER_ZERO)
_ROUTER_ASSIGN = ("replicas",)


def record_router(key: str, n=1):
    """One router event (``mxtpu.serving.router.Router``): routing
    decisions, backpressure overflow/rejection, rebalance lifecycle.
    ``replicas`` assigns the current replica count; everything else
    accumulates."""
    with _stats_lock:
        if key in _ROUTER_ASSIGN:
            _router[key] = int(n)
        else:
            _router[key] += n


def get_router_stats() -> dict:
    """Router counters — the observability contract of
    :class:`mxtpu.serving.router.Router` (``tests/test_router_guard.py`` reads
    these; the exporter serves them under the ``router`` block)."""
    with _stats_lock:
        return dict(_router)


def reset_router_stats():
    with _stats_lock:
        _router.update(_ROUTER_ZERO)


# ---------------------------------------------------------------------------
# SLO scheduler observability (mxtpu.sched control plane)
# ---------------------------------------------------------------------------

# assign-style snapshot store: the engine pushes SLOScheduler.stats() (picks/
# sheds/preemptions/resumes, fair-share tenant count, service-rate EWMAs) and
# the autoscaler its latest decision — the exporter serves whatever was
# pushed last, so a scrape never calls back into the scheduler thread
_sched: Dict[str, object] = {}


def record_sched(stats: Dict[str, object]):
    """Replace-merge the scheduler/autoscaler snapshot block served at
    ``collect_snapshot()['sched']``."""
    with _stats_lock:
        _sched.update(stats)


def get_sched_stats() -> dict:
    with _stats_lock:
        return dict(_sched)


def reset_sched_stats():
    with _stats_lock:
        _sched.clear()


# ---------------------------------------------------------------------------
# quantization observability (mxtpu.quant counters)
# ---------------------------------------------------------------------------

_QUANT_ZERO = {"matmuls": 0}
_quant = dict(_QUANT_ZERO)
_quant_err: Dict[str, float] = {}
_quant_ranges: Dict[str, tuple] = {}


def record_quant_matmuls(n: int = 1):
    """``n`` quantized matmul sites staged. Serving records the per-program
    site count at build time; the QAT step hooks record one per Dense/Conv
    site at TRACE time — so the counter reads 'quantized matmuls compiled',
    which is the retrace-stable quantity (per-dispatch counts would need a
    host sync inside jit)."""
    with _stats_lock:
        _quant["matmuls"] += int(n)


def record_quant_error(tensor: str, err: float):
    """Per-tensor max-abs round-trip quantization error, high-water over the
    process (``quantize_lm`` records each weight once; re-quantizing after a
    weight update only raises the mark if the error grew)."""
    with _stats_lock:
        if err > _quant_err.get(tensor, float("-inf")):
            _quant_err[tensor] = float(err)


def record_quant_range(tensor: str, lo: float, hi: float):
    """Calibrated activation range for one site (``quant.calibrate``) —
    widens monotonically so repeated calibration passes compose."""
    with _stats_lock:
        old = _quant_ranges.get(tensor)
        if old is not None:
            lo, hi = min(lo, old[0]), max(hi, old[1])
        _quant_ranges[tensor] = (float(lo), float(hi))


def get_quant_stats() -> dict:
    """Quantization counters: ``matmuls`` (quantized matmul sites staged),
    ``max_abs_error`` (per-tensor weight round-trip error high-water),
    ``ranges`` (per-site calibrated activation (min, max)) — the
    observability contract of ``mxtpu.quant``: a quant regression shows up
    here before it shows up in accuracy."""
    with _stats_lock:
        out = dict(_quant)
        out["max_abs_error"] = dict(_quant_err)
        out["ranges"] = dict(_quant_ranges)
    return out


def reset_quant_stats():
    with _stats_lock:
        _quant.update(_QUANT_ZERO)
        _quant_err.clear()
        _quant_ranges.clear()


# ---------------------------------------------------------------------------
# sanitizer observability (mxtpu.analysis.sanitize counters)
# ---------------------------------------------------------------------------

_SAN_ZERO = {"transfer_guards": 0, "transfer_trips": 0,
             "donation_poisons_armed": 0, "donation_trips": 0,
             "retrace_escalations": 0,
             "ownership_checks": 0, "ownership_trips": 0}
_san = dict(_SAN_ZERO)


def record_sanitizer(key: str, n: int = 1):
    """One sanitizer event (``mxtpu.analysis.sanitize``): guards armed and
    poisons planted count the coverage a sanitized run actually had; trips
    and escalations count violations (a clean run reports zero)."""
    with _stats_lock:
        _san[key] += int(n)


def get_sanitizer_stats() -> dict:
    """Sanitizer counters (transfer-guard arms/trips, donation poisons
    armed/tripped, retrace escalations, ownership assertions checked/
    tripped) — the observability contract of ``MXTPU_SANITIZE``.
    ``compile_cache_summary()`` prints them, ``Module.fit`` logs the
    per-epoch deltas, and ``profiler.dumps()`` carries them as the
    ``"sanitizer"`` block."""
    with _stats_lock:
        return dict(_san)


def sanitizer_violations(stats: Optional[dict] = None) -> int:
    """Total violations in a stats snapshot (0 for a clean sanitized run)."""
    s = stats if stats is not None else get_sanitizer_stats()
    return (s["transfer_trips"] + s["donation_trips"]
            + s["retrace_escalations"] + s["ownership_trips"])


def reset_sanitizer_stats():
    with _stats_lock:
        _san.update(_SAN_ZERO)


# ---------------------------------------------------------------------------
# kernels: a kernel file registers its kinds where its op is defined
# (``register_kernel``); nothing here names one
# ---------------------------------------------------------------------------

_kernel_paths: Dict[str, dict] = {}     # kind -> call sites by path
_launches: Dict[str, dict] = {}         # kind -> its newest call site's row


def register_kernel(kind: str, launch_keys: tuple = ()):
    """A kernel file names a ``kind`` whose call sites it counts by path
    and, with ``launch_keys``, the keys of the row it records for its newest
    call site. At import: a reader finds zeros before any launch."""
    with _stats_lock:
        _kernel_paths[kind] = {"pallas": 0, "xla": 0}
    if launch_keys:
        register_launch(kind, launch_keys)


def register_launch(kind: str, launch_keys: tuple):
    """A row of ``launch_keys`` for ``kind``'s newest call site WITHOUT a
    kernel path: for what is traced into a step and is no kernel (the model
    stack's prediction block, kind ``mtp``)."""
    with _stats_lock:
        _launches[kind] = dict.fromkeys(("launches",) + launch_keys, 0)


def record_kernel_path(kind: str, pallas: bool):
    """One call site of ``kind`` chose its path. Recorded where the choice
    is made, which under ``jit`` is at TRACE time: once for each call site
    of each traced program, and once for each eager call."""
    with _stats_lock:
        _kernel_paths[kind]["pallas" if pallas else "xla"] += 1


def get_kernel_path_counts() -> dict:
    """``{kind: {"pallas": n, "xla": n}}`` for every registered kind since
    the last reset: how many call sites took the Pallas kernels and how many
    the XLA formulation (another backend than the TPU, or a shape the
    kernels do not take). A TPU step that should run kernels reads ``xla ==
    0``."""
    with _stats_lock:
        return {kind: dict(row) for kind, row in _kernel_paths.items()}


def reset_kernel_path_counts():
    with _stats_lock:
        for row in _kernel_paths.values():
            row.update(pallas=0, xla=0)


def record_launch(kind: str, **row):
    """One call site of ``kind``'s op was traced (or run eagerly): ``row``
    holds its registered keys (the op's file says what each counts)."""
    with _stats_lock:
        mine = _launches[kind]
        mine.update(row, launches=mine["launches"] + 1)


def get_launch_stats(kind: str) -> dict:
    """``{"launches", *registered keys}``: call sites of ``kind``'s op
    since the last reset and the NEWEST one's row; zeros before any."""
    with _stats_lock:
        return dict(_launches[kind])


def reset_launch_stats(kind: str):
    with _stats_lock:
        _launches[kind].update(dict.fromkeys(_launches[kind], 0))


_remat = {"blocks": 0, "recomputed": 0, "kinds": {}}


def record_remat(blocks: int, kinds):
    """A model that recomputes its blocks (``HybridDecoderLM(remat=True)``)
    traced a step: how many blocks it has, and the layer kind of each block
    that runs under ``jax.checkpoint`` (one entry a block). Recorded at
    TRACE time, as the kernel paths are; a model that never asks records
    nothing."""
    count = {}
    for kind in kinds:
        count[kind] = count.get(kind, 0) + 1
    with _stats_lock:
        _remat.update(blocks=blocks, recomputed=len(kinds), kinds=count)


def get_remat_stats() -> dict:
    """``{"blocks", "recomputed", "kinds"}`` of the NEWEST traced step that
    asked for recomputation since the last reset: the model's blocks, those
    whose forward runs again in the backward (all but the last, whose
    activations are live at its backward either way) and how many of them
    are of each layer kind (``{"mamba": 12, "attn_full": 1}``: each kind's
    kernels launch that many more forwards a step). Zeros and ``{}`` where
    no such step was traced."""
    with _stats_lock:
        return dict(_remat, kinds=dict(_remat["kinds"]))


def reset_remat_stats():
    with _stats_lock:
        _remat.update(blocks=0, recomputed=0, kinds={})
