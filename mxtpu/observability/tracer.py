"""Per-thread span recorder — the unified step timeline.

The reference's profiler (``src/profiler/profiler.h:87,437``) records every
engine op into per-device ``ProfileStat`` ring buffers and serializes them to
chrome://tracing JSON. Here the interesting "ops" are framework-level phases —
``step/compile``, ``step/execute``, ``feed/transfer``, ``feed/stall``,
``comm/exchange``, ``ckpt/snapshot``/``write``/``commit`` — each recorded as a
duration span on the thread that ran it, so one trace shows the main step
loop, the DeviceFeed producer, and the checkpoint writer as separate timeline
rows (pid/tid lanes in the viewer).

Design (lock-free-ish): every thread owns a private bounded ring buffer,
created on first use and registered once under the module lock. Appends touch
only the owning thread's buffer (no lock on the hot path); readers
(``export.py``) snapshot the registered buffers under the lock. The only
module-level mutations are the registration list and the enable/pause flags —
all lock-guarded (tpulint R004 contract for thread-spawning modules).

One call site, three sinks:

* the profiler's trace, always: a span opens its
  ``jax.profiler.TraceAnnotation`` whether or not the ring is armed (a no-op
  in C++ while no profiler session runs), so any ``jax.profiler`` session
  carries the framework's spans on the device trace's clock with nothing to
  switch on;
* totals by name, always: count and summed time per span name, split by the
  name of the span open on the thread when it began (:func:`totals`,
  ``profiler.get_span_totals()``) — what lets spans that end long before a
  profiler session starts (set-up) be read at the end of a run;
* the ring, when armed (``MXTPU_TRACE=1``, read at import, or
  ``profiler.set_state('run')``): each event carries ``id``, ``parent`` (the
  id of the span open on that thread when it began) and the caller's ``args``.

JAX's own compile phases arrive as spans too (``jax/trace``, ``jax/lower``,
``jax/compile``, instants ``jax/cache_hit`` and ``jax/cache_miss``) through
one ``jax.monitoring`` listener pair registered at import: JAX traces, lowers
and compiles on the thread that made the call, so they land under whatever
span is open there, instants too (``count_by_parent``). They fire only on a
compile path, never in a warm step.

Cost of ``with span(): pass`` on the v5e host: docs/observability.md.
"""

from __future__ import annotations

import collections
import itertools
import os
import threading
import time
from typing import Optional

import jax

__all__ = ["span", "instant", "counter", "record_span", "enabled", "start",
           "stop", "pause", "resume", "reset", "snapshot_buffers",
           "buffer_capacity", "totals", "is_open"]

# ring capacity per thread (events); a 2-epoch traced fit generates a few
# thousand spans, so the default keeps hours of steps without growing
_DEFAULT_CAP = 65536

_reg_lock = threading.Lock()
_buffers: list = []          # [_ThreadBuf] — append/clear under _reg_lock only
_tls = threading.local()

_enabled = False             # flipped by start()/stop() (scalar rebind: atomic)
_paused = False

_ids = itertools.count(1)    # span ids, process-wide (next() is atomic)
_NO_PARENT = (0, "")         # (id, name) of "no span open on this thread"


def buffer_capacity() -> int:
    try:
        return max(1024, int(os.environ.get("MXTPU_TRACE_BUFFER",
                                            str(_DEFAULT_CAP))))
    except ValueError:
        return _DEFAULT_CAP


class _ThreadBuf:
    """One thread's bounded event ring, its stack of open spans and its
    totals by name. Only the owning thread writes; readers copy via
    :func:`snapshot_buffers` / :func:`totals` (a list or dict copy is atomic
    enough under the GIL for the monotonically-appended prefix)."""

    __slots__ = ("tid", "name", "events", "dropped", "cap", "stack", "totals",
                 "jax_traces")

    def __init__(self, tid: int, name: str, cap: int):
        self.tid = tid
        self.name = name
        self.cap = cap
        self.events = collections.deque(maxlen=cap)
        self.dropped = 0
        self.stack: list = []        # [(id, name)] of the spans open here
        self.totals: dict = {}       # (name, parent name) -> [n, ns, min, max]
        # (start_ns, dur_ns) of the outermost jax/trace spans seen so far:
        # a jit traced inside another reports before the outer one does
        self.jax_traces = collections.deque(maxlen=65536)

    def open_span(self) -> tuple:
        """``(id, name)`` of the innermost span open on this thread."""
        return self.stack[-1] if self.stack else _NO_PARENT

    def count(self, name: str, parent: str, dur_ns: int):
        tot = self.totals.get((name, parent))
        if tot is None:
            self.totals[(name, parent)] = [1, dur_ns, dur_ns, dur_ns]
            return
        tot[0] += 1
        tot[1] += dur_ns
        if dur_ns < tot[2]:
            tot[2] = dur_ns
        elif dur_ns > tot[3]:
            tot[3] = dur_ns

    def append(self, ev: dict):
        if len(self.events) == self.cap:
            # drop-oldest (the deque's own) keeps the tail of a long run (the
            # part a post-mortem dump wants); the dropped count is exported
            # as trace metadata
            self.dropped += 1
        self.events.append(ev)


def _buf() -> _ThreadBuf:
    b = getattr(_tls, "buf", None)
    if b is None:
        t = threading.current_thread()
        b = _ThreadBuf(t.ident or 0, t.name, buffer_capacity())
        _tls.buf = b
        with _reg_lock:
            _buffers.append(b)
    return b


# -- lifecycle ---------------------------------------------------------------

def enabled() -> bool:
    return _enabled and not _paused


def start():
    """Arm span recording (``profiler.set_state('run')`` / ``MXTPU_TRACE``)."""
    global _enabled, _paused
    _enabled = True
    _paused = False


def stop():
    global _enabled
    _enabled = False


def pause():
    global _paused
    _paused = True


def resume():
    global _paused
    _paused = False


def reset():
    """Drop all recorded events and zero the totals (tests, fresh dump
    epochs). Live threads' buffers stay registered (their thread-locals still
    point at them); dead producers' buffers — every traced DeviceFeed
    generation spawns one — are unregistered so back-to-back traced legs
    don't accumulate rows."""
    live = {t.ident for t in threading.enumerate()}
    with _reg_lock:
        _buffers[:] = [b for b in _buffers if b.tid in live]
        for b in _buffers:
            b.events = collections.deque(maxlen=b.cap)
            b.dropped = 0
            b.totals = {}
            b.jax_traces.clear()


def snapshot_buffers():
    """Read-side snapshot: ``[(tid, thread_name, events_copy, dropped)]``."""
    with _reg_lock:
        return [(b.tid, b.name, list(b.events), b.dropped) for b in _buffers]


def totals() -> dict:
    """Every span name seen since the last :func:`reset`, armed or not:
    ``{name: {"count", "seconds", "min_s", "max_s", "by_parent": {name of the
    span open on the thread when it began, or "": seconds},
    "count_by_parent": {the same names: count}}}``. Instants count with no
    seconds; ``jax/trace`` counts the time no nested ``jax/trace`` covers,
    so the seconds of a name add up to wall time."""
    with _reg_lock:
        rows = [kv for b in _buffers for kv in list(b.totals.items())]
    out: dict = {}
    for (name, parent), (n, ns, lo, hi) in rows:
        t = out.get(name)
        if t is None:
            t = out[name] = {"count": 0, "seconds": 0.0, "min_s": lo / 1e9,
                             "max_s": hi / 1e9, "by_parent": {},
                             "count_by_parent": {}}
        t["count"] += n
        t["seconds"] += ns / 1e9
        t["min_s"] = min(t["min_s"], lo / 1e9)
        t["max_s"] = max(t["max_s"], hi / 1e9)
        t["by_parent"][parent] = t["by_parent"].get(parent, 0.0) + ns / 1e9
        t["count_by_parent"][parent] = \
            t["count_by_parent"].get(parent, 0) + n
    return out


def is_open(name: str) -> bool:
    """Whether a span of this name is open on the calling thread: how a
    call that may nest in itself (``Block.initialize`` of a child) knows it
    is not the outermost."""
    return any(n == name for _, n in _buf().stack)


# -- recording ---------------------------------------------------------------


class _Span:
    __slots__ = ("name", "cat", "args", "t0_ns", "dur_ns", "_ann", "_buf",
                 "_id", "_parent")

    def __init__(self, name: str, cat: Optional[str], args: Optional[dict]):
        self.name = name
        self.cat = cat
        self.args = dict(args) if args else None

    def set(self, **kwargs):
        """Attach args discovered mid-span (payload bytes, cache key…)."""
        if self.args is None:
            self.args = {}
        self.args.update(kwargs)
        return self

    def __enter__(self):
        buf = self._buf = _buf()
        self._parent = buf.open_span()
        self._id = next(_ids)
        buf.stack.append((self._id, self.name))
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self.t0_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        # kept on the span: whoever opened it reads what it measured
        dur = self.dur_ns = time.perf_counter_ns() - self.t0_ns
        self._ann.__exit__(None, None, None)
        buf = self._buf
        buf.stack.pop()
        buf.count(self.name, self._parent[1], dur)
        if _enabled and not _paused:
            buf.append(_event(self.name, "X", self.cat, self.t0_ns, self._id,
                              self._parent[0], self.args, dur=dur / 1e3))
        return False


def _event(name, ph, cat, t_ns, span_id, parent_id, args, **more) -> dict:
    ev = {"name": name, "ph": ph, "cat": cat or name.split("/", 1)[0],
          "ts": t_ns / 1e3, "id": span_id, "parent": parent_id, **more}
    if args:
        ev["args"] = args
    return ev


def span(name: str, cat: Optional[str] = None, args: Optional[dict] = None):
    """Context manager recording one duration span on the calling thread:
    into the profiler's trace and the totals always, into the ring when
    armed."""
    return _Span(name, cat, args)


def instant(name: str, cat: Optional[str] = None,
            args: Optional[dict] = None, scope: str = "t"):
    """One instant event (chrome-trace ``ph: 'i'``); counts into the totals
    with no seconds."""
    buf = _buf()
    parent = buf.open_span()
    buf.count(name, parent[1], 0)
    if _enabled and not _paused:
        buf.append(_event(name, "i", cat, time.perf_counter_ns(), next(_ids),
                          parent[0], args and dict(args), s=scope))


def record_span(name: str, t0_ns: int, dur_ns: int,
                cat: Optional[str] = None, args: Optional[dict] = None,
                total_ns: Optional[int] = None,
                parent: Optional[tuple] = None) -> tuple:
    """Record an already-measured span under the span open on the calling
    thread (legacy Domain/Task/Frame objects and JAX's compile phases measure
    their own window). ``total_ns`` is what counts into the totals where that
    is not the whole duration. Returns the span's ``(id, name)``, which a
    caller that measured a span INSIDE this one passes as that one's
    ``parent`` (the package's import times itself before a tracer exists)."""
    buf = _buf()
    if parent is None:
        parent = buf.open_span()
    span_id = next(_ids)
    buf.count(name, parent[1], dur_ns if total_ns is None else total_ns)
    if _enabled and not _paused:
        buf.append(_event(name, "X", cat, t0_ns, span_id, parent[0],
                          args and dict(args), dur=dur_ns / 1e3))
    return span_id, name


def counter(name: str, value, cat: str = "counters"):
    """One counter sample (chrome-trace ``ph: 'C'`` — rendered as a stacked
    area track in the viewer). Used for queue depths and rate gauges."""
    if not _enabled or _paused:
        return
    _buf().append({"name": name, "ph": "C", "cat": cat,
                   "ts": time.perf_counter_ns() / 1e3,
                   "args": {name.rsplit("/", 1)[-1]: value}})


# -- JAX's compile phases ----------------------------------------------------

_JAX_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "jax/trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax/lower",
    "/jax/core/compile/backend_compile_duration": "jax/compile",
}
# a hit: the executable came from the persistent cache; a miss: XLA compiled
# it in this process and the cache kept it
_JAX_CACHE = {"/jax/compilation_cache/cache_hits": "jax/cache_hit",
              "/jax/compilation_cache/cache_misses": "jax/cache_miss"}


def _on_jax_duration(event: str, secs: float, **kwargs):
    name = _JAX_PHASES.get(event)
    if name is None:
        return
    end = time.perf_counter_ns()
    dur = int(secs * 1e9)
    own = dur
    if name == "jax/trace":
        # every jit traced inside this one has reported already: the totals
        # take what is left, so that the name adds up to wall time
        seen = _buf().jax_traces
        while seen and seen[-1][0] >= end - dur:
            own -= seen.pop()[1]
        seen.append((end - dur, dur))
    fun = kwargs.get("fun_name")
    record_span(name, end - dur, dur, cat="jax",
                args={"fun": fun} if fun else None, total_ns=max(own, 0))


def _on_jax_event(event: str, **kwargs):
    name = _JAX_CACHE.get(event)
    if name is not None:
        instant(name, cat="jax")


jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)
jax.monitoring.register_event_listener(_on_jax_event)


# MXTPU_TRACE=1 arms tracing for the whole process at import (the env-var
# analogue of the reference's MXNET_PROFILER_AUTOSTART)
if os.environ.get("MXTPU_TRACE", "").lower() in ("1", "true", "on", "run"):
    start()
