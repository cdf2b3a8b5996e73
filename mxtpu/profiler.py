"""Profiler — parity with ``src/profiler/`` + ``python/mxnet/profiler.py``
(SURVEY.md §5): set_config/set_state/dump, pause/resume, Domain/Task/Frame/
Event/Counter/Marker objects, chrome://tracing output.

This module is the user-facing FACADE over :mod:`mxtpu.observability`:

* the span recorder (``observability.tracer``) captures the unified step
  timeline — ``step/compile``, ``step/execute``, ``feed/transfer``,
  ``feed/stall``, ``comm/exchange``, ``ckpt/*`` — on per-thread rings, each
  span mirrored into ``jax.profiler.TraceAnnotation`` so XLA device traces
  (XPlane dirs from ``set_state('run')``, openable in Perfetto) line up with
  the framework spans;
* ``dump()``/``dumps()`` serialize it to valid chrome://tracing JSON
  (``observability.export``), with pid/tid rows per thread (main,
  feed-producer, ckpt-writer) — ``dump(finished=True)`` freezes the snapshot
  so repeated dumps are idempotent rather than accumulating;
* MFU accounting (``observability.flops``) feeds ``get_mfu_stats()`` —
  steps/s, p50/p99 step latency, FLOPs/step, MFU vs the chip's documented
  peak;
* every subsystem counter surface (``record_*`` / ``get_*_stats`` /
  ``reset_*`` for checkpoint, device-feed, comm, sanitizer) is re-exported
  unchanged from ``observability.metrics``.

The span ring is opt-in — ``MXTPU_TRACE=1`` (the ``MXNET_PROFILER_AUTOSTART``
analogue) or ``profiler.set_state('run')``; unarmed, a span still opens its
``TraceAnnotation`` (so any ``jax.profiler`` session carries it) and counts
into ``get_span_totals()``. The legacy Domain/Task/Counter/Marker
objects keep their original always-on local event list (``_state['events']``)
AND emit real spans onto the unified timeline when tracing is armed.
"""

from __future__ import annotations

import functools
import json
import os
import time
from typing import Optional

import jax

from .observability import export as _export
from .observability import flops as _flops
from .observability import histogram as _hist
from .observability import tracer as _tracer
from .observability.metrics import (  # noqa: F401  (re-exported surface)
    _stats_lock,
    add_commit_hook,
    get_checkpoint_stats, get_comm_stats, get_feed_stats,
    get_kernel_path_counts, get_launch_stats,
    get_memory_stats, get_quant_stats, get_remat_stats, get_resilience_stats,
    get_router_stats, get_sanitizer_stats, get_sched_stats,
    get_serving_stats,
    record_checkpoint_commit, record_checkpoint_restore,
    record_checkpoint_save, record_checkpoint_shard_write,
    record_collective, record_comm_step,
    record_feed_consume, record_feed_prefetch, record_feed_resident,
    record_feed_transfer, record_kernel_path,
    record_memory_stats,
    record_quant_error, record_quant_matmuls, record_quant_range,
    record_remat, record_resilience, record_router, record_sanitizer, record_sched,
    record_serving, record_serving_occupancy, record_tenant,
    reset_checkpoint_stats, reset_comm_stats, reset_feed_stats,
    reset_kernel_path_counts, reset_launch_stats,
    reset_memory_stats, reset_quant_stats, reset_remat_stats,
    reset_resilience_stats,
    reset_router_stats, reset_sanitizer_stats, reset_sched_stats,
    reset_serving_stats,
    sanitizer_violations, set_feed_depth,
)

# the two launch rows the benchmark reads by name (systems/brumby.py, ling.py)
get_retention_stats = functools.partial(get_launch_stats, "retention")
get_kda_stats = functools.partial(get_launch_stats, "kda")

# MFU/step-latency surface (observability.flops is the store)
get_mfu_stats = _flops.get_mfu_stats
get_step_timeline = _flops.get_step_timeline
record_step_time = _flops.record_step
reset_step_times = _flops.reset_steps

# streaming latency histograms (observability.histogram is the store)
get_histogram = _hist.get_histogram
get_histogram_stats = _hist.get_histogram_stats
reset_histograms = _hist.reset_histograms

_state = {"config": {"filename": "profile.json", "profile_all": False},
          "running": False, "dir": None, "events": [], "paused": False}

# dump(finished=True) freezes its payload here so repeated finished dumps
# rewrite the SAME file content instead of re-collecting (and duplicating)
# whatever was recorded since — cleared by set_state('run') / reset_trace()
_final = {"payload": None}


def set_config(**kwargs):
    """profiler.set_config parity (filename, profile_{symbolic,imperative,memory,api},
    aggregate_stats…); unknown knobs are accepted and recorded."""
    with _stats_lock:
        _state["config"].update(kwargs)


def set_state(state: str = "stop", profile_process: str = "worker"):
    """'run' arms the unified span recorder AND an XLA device trace
    (``jax.profiler.start_trace`` XPlane dir next to the configured
    filename); 'stop' closes both. ``set_config(xplane=False)`` keeps the
    framework spans without the device-trace dir (cheap mode — what
    ``MXTPU_TRACE=1`` uses)."""
    if state == "run" and not _state["running"]:
        _tracer.start()
        with _stats_lock:
            _final["payload"] = None          # a new run unfreezes the dump
        if not _state["config"].get("xplane", True):
            with _stats_lock:
                _state["running"] = True
            return
        out_dir = os.path.splitext(_state["config"].get("filename", "profile.json"))[0] \
            + "_trace"
        with _stats_lock:
            _state["dir"] = out_dir
        jax.profiler.start_trace(out_dir)
        with _stats_lock:
            _state["running"] = True
    elif state == "stop":
        if _state["running"]:
            if _state["config"].get("xplane", True):
                jax.profiler.stop_trace()
            with _stats_lock:
                _state["running"] = False
        _tracer.stop()
        with _stats_lock:
            # explicit stop cancels pause-resume
            _state.pop("resume_running", None)


def pause(profile_process: str = "worker"):
    """Suspend collection (c_api MXProfilePause parity): custom events and
    framework spans stop recording and the device trace is closed until
    resume()."""
    if _state["paused"]:
        return
    with _stats_lock:
        _state["paused"] = True
    _tracer.pause()
    if _state["running"]:
        if _state["config"].get("xplane", True):
            jax.profiler.stop_trace()
        with _stats_lock:
            _state["running"] = False
            _state["resume_running"] = True


def resume(profile_process: str = "worker"):
    if not _state["paused"]:
        return
    with _stats_lock:
        _state["paused"] = False
        restart = _state.pop("resume_running", False)
        if restart:
            _state["segment"] = _state.get("segment", 0) + 1
            out_dir = f"{_state['dir']}_resume{_state['segment']}"
            _state["dir"] = out_dir  # dump() must point at the live trace dir
    _tracer.resume()
    if restart:
        if _state["config"].get("xplane", True):
            jax.profiler.start_trace(out_dir)
        with _stats_lock:
            _state["running"] = True


def reset_trace():
    """Drop every recorded span/event, zero the span totals and unfreeze a
    finished dump (tests)."""
    _tracer.reset()
    with _stats_lock:
        _state["events"] = []
        _final["payload"] = None


def dump(finished: bool = True, profile_process: str = "worker"):
    """Stop tracing and write the chrome://tracing JSON (one ``pid`` with a
    named ``tid`` row per instrumented thread). ``finished=True`` (the
    reference default) freezes the payload: calling ``dump(finished=True)``
    again rewrites the identical file instead of duplicating events recorded
    since; ``finished=False`` writes a live snapshot without freezing."""
    if _state["running"]:
        set_state("stop")
    with _stats_lock:
        fname = _state["config"].get("filename", "profile.json")
        legacy = list(_state["events"])
        xdir = _state["dir"]
        payload = _final["payload"] if finished else None
    if payload is None:
        payload = _export.chrome_trace(legacy_events=legacy, xplane_dir=xdir)
        if finished:
            with _stats_lock:
                if _final["payload"] is None:
                    _final["payload"] = payload
                else:
                    payload = _final["payload"]   # lost the freeze race
    _export.write_chrome_trace(fname, payload)
    return fname


def get_span_totals() -> dict:
    """Count and summed seconds of every span name since the last
    ``reset_trace()``, kept whether or not the ring is armed:
    ``{name: {"count", "seconds", "min_s", "max_s", "by_parent": {name of the
    span open on the thread when it began, or "": seconds}}}``. The framework's
    spans, the legacy Domain/Task objects and JAX's compile phases
    (``jax/trace``, ``jax/lower``, ``jax/compile``) all count."""
    return _tracer.totals()


def get_summary(sort_by: str = "total") -> str:
    """Aggregate-stats table (MXAggregateProfileStatsPrint / aggregate_stats.cc
    parity): per-name count, total/avg/min/max duration, printed from
    :func:`get_span_totals`."""
    key = {"total": lambda kv: -kv[1]["seconds"],
           "count": lambda kv: -kv[1]["count"],
           "avg": lambda kv: -(kv[1]["seconds"] / kv[1]["count"]),
           "name": lambda kv: kv[0]}[sort_by]
    lines = [f"{'Name':<40s}{'Count':>8s}{'Total(ms)':>12s}{'Avg(ms)':>10s}"
             f"{'Min(ms)':>10s}{'Max(ms)':>10s}"]
    lines.append("-" * len(lines[0]))
    for name, t in sorted(get_span_totals().items(), key=key):
        tot = t["seconds"] * 1e3
        lines.append(f"{name:<40s}{t['count']:>8d}{tot:>12.3f}"
                     f"{tot / t['count']:>10.3f}{t['min_s'] * 1e3:>10.3f}"
                     f"{t['max_s'] * 1e3:>10.3f}")
    return "\n".join(lines)


def dumps(reset: bool = False) -> str:
    """Aggregate table when set_config(aggregate_stats=True) (reference
    profiler.dumps), raw chrome-trace JSON otherwise — traceEvents now
    includes the unified span store alongside every subsystem stats block."""
    if _state["config"].get("aggregate_stats"):
        out = get_summary()
    else:
        with _stats_lock:
            legacy = list(_state["events"])
        out = json.dumps({"traceEvents": _export.collect_events(legacy),
                          "compileCaches": get_compile_stats(),
                          "checkpoint": get_checkpoint_stats(),
                          "deviceFeed": get_feed_stats(),
                          "comm": get_comm_stats(),
                          "memory": get_memory_stats(),
                          "sanitizer": get_sanitizer_stats(),
                          "resilience": get_resilience_stats(),
                          "serving": get_serving_stats(),
                          "histograms": _hist.get_histogram_stats(),
                          "mfu": get_mfu_stats()})
    if reset:
        reset_trace()
    return out


# ---------------------------------------------------------------------------
# compile-cache observability (step_cache registry)
# ---------------------------------------------------------------------------


def get_moe_stats(block) -> list:
    """``stats()`` of every ``parallel.moe.SparseExperts`` layer under
    ``block`` (a model, or the layer itself), in the order the model holds
    them: of its newest forward the (token, expert) ``pairs`` its held
    experts got, how many of the ``held`` were ``active``, the
    ``max_count`` and ``min_count`` of tokens that chose any one of all the
    experts, ``load_max`` (the busiest held expert's rows over an expert's
    even share), the ``buffer_rows`` of a pass, the ``passes`` the pairs took,
    the ``rows_moved`` (the buffer rows the dispatch filled and the
    combine read, which follow the pairs) and the ``rows_added`` (the most of
    them that were added onto their tokens with repeated indices: 0 for a
    layer that holds every expert, which sums by gathers) and the ``kept_bytes`` (what of a pass the layer keeps for its
    backward beyond its input and routing: its two products where every expert is held, 0 for a share, whose backward
    multiplies them again), and ``mxu_rows`` (the rows the MXU multiplies in one grouped product over those pairs: a
    group's visit to a row tile costs the 128-row blocks that hold its rows there) with ``tile_fill`` = ``pairs /
    mxu_rows``, the share of them that are somebody's. The numbers are a state of the layer (``count``, one float an
    expert) that rides the compiled step, so there is nothing to reset;
    reading them fetches it from the device: ask between steps."""
    from .parallel.moe import SparseExperts
    rows = []
    block.apply(lambda b: rows.append(b.stats())
                if isinstance(b, SparseExperts) else None)
    return rows


def get_compile_stats() -> dict:
    """Per-cache {hits, traces, retraces} for every signature cache in the
    framework (fused training step, CachedOp/hybridize, symbol Executor
    backward, DataParallelTrainer step). The TPU-native analogue of the
    reference's engine-bulk forensics: a fixed-shape training loop should
    show exactly one trace and N-1 hits — anything else is a retrace leak."""
    from .step_cache import snapshot
    return snapshot()


def reset_compile_stats(name: Optional[str] = None):
    """Zero one named cache's counters (or all). Tests and epoch-boundary
    accounting use this; the caches themselves are untouched."""
    from .step_cache import reset_stats
    reset_stats(name)


def compile_cache_summary() -> str:
    """Human-readable compile-cache table (pairs with get_summary()), plus
    the sanitizer counter line when a sanitized run recorded anything."""
    stats = get_compile_stats()
    lines = [f"{'Cache':<24s}{'Hits':>10s}{'Traces':>10s}{'Retraces':>10s}"]
    lines.append("-" * len(lines[0]))
    for name in sorted(stats):
        s = stats[name]
        lines.append(f"{name:<24s}{s['hits']:>10d}{s['traces']:>10d}"
                     f"{s['retraces']:>10d}")
    san = get_sanitizer_stats()
    if any(san.values()):
        lines.append(
            f"sanitizer: transfer-guards={san['transfer_guards']} "
            f"(trips {san['transfer_trips']}), "
            f"poisons={san['donation_poisons_armed']} "
            f"(trips {san['donation_trips']}), "
            f"retrace-escalations={san['retrace_escalations']}, "
            f"ownership={san['ownership_checks']} "
            f"(trips {san['ownership_trips']})")
    mem = get_memory_stats()
    if mem["param_bytes_per_device"] or mem["slot_bytes_per_device"]:
        lines.append(
            f"memory: zero-stage={mem['stage']} "
            f"(data×fsdp {mem['data_degree']}×{mem['fsdp_degree']}) "
            f"per-device params={mem['param_bytes_per_device']} "
            f"grads={mem['grad_bytes_per_device']} "
            f"slots={mem['slot_bytes_per_device']} B "
            f"(replicated: {mem['replicated_param_bytes']}/"
            f"{mem['replicated_grad_bytes']}/"
            f"{mem['replicated_slot_bytes']} B)")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# custom profiling objects (Domain/Task/Frame/Event/Counter/Marker)
# ---------------------------------------------------------------------------


class Domain:
    def __init__(self, name: str):
        self.name = name

    def new_task(self, name):
        return Task(self, name)

    def new_counter(self, name, value=None):
        return Counter(self, name, value)

    def new_marker(self, name):
        return Marker(self, name)


class _Scoped:
    def __init__(self, domain: Optional[Domain], name: str):
        self.domain = domain
        self.name = name
        self._ann = None
        self._t0 = None

    def start(self):
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()

    def stop(self):
        if self._ann is not None:
            t1 = time.perf_counter_ns()
            self._ann.__exit__(None, None, None)
            cat = self.domain.name if self.domain else "default"
            if not _state["paused"]:
                with _stats_lock:
                    _state["events"].append({
                        "name": self.name, "ph": "X", "ts": self._t0 / 1000,
                        "dur": (t1 - self._t0) / 1000,
                        "pid": 0, "tid": 0, "cat": cat})
                # mirror onto the unified timeline (real pid/tid row) when
                # the span recorder is armed
                _tracer.record_span(self.name, self._t0, t1 - self._t0,
                                    cat=cat)
            self._ann = None

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()


class Task(_Scoped):
    pass


class Frame(_Scoped):
    pass


class Event(_Scoped):
    pass


class Counter:
    def __init__(self, domain, name, value=None):
        self.domain, self.name = domain, name
        self.value = value or 0

    def set_value(self, value):
        self.value = value
        if not _state["paused"]:
            with _stats_lock:
                _state["events"].append({"name": self.name, "ph": "C",
                                         "ts": time.perf_counter_ns() / 1000,
                                         "pid": 0,
                                         "args": {self.name: value}})
            _tracer.counter(self.name, value,
                            cat=self.domain.name if self.domain
                            else "counters")

    def increment(self, delta=1):
        self.set_value(self.value + delta)

    def decrement(self, delta=1):
        self.set_value(self.value - delta)


class Marker:
    def __init__(self, domain, name):
        self.domain, self.name = domain, name

    def mark(self, scope: str = "process"):
        if not _state["paused"]:
            with _stats_lock:
                _state["events"].append({"name": self.name, "ph": "i",
                                         "ts": time.perf_counter_ns() / 1000,
                                         "pid": 0, "s": scope[0]})
            _tracer.instant(self.name,
                            cat=self.domain.name if self.domain else "marker",
                            scope=scope[0])
