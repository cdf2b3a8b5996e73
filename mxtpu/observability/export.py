"""Chrome-trace serialization of the span recorder (``profiler.dump`` body).

Produces the JSON Trace Event Format that chrome://tracing and Perfetto's
legacy importer open directly (the reference CLI surface:
``mx.profiler.dump()`` writes ``profile.json`` next to the run). Every
registered thread buffer becomes its own ``tid`` row under this process's
``pid``, with ``thread_name`` metadata events so the viewer labels the rows
("MainThread", "mxtpu-device-feed", "mxtpu-ckpt-writer") instead of showing
bare ids.

Events carry the recorder's monotonic ``perf_counter_ns``-derived
microsecond timestamps — a single clock across threads, so producer spans
visibly overlap the consumer's stall spans.
"""

from __future__ import annotations

import json
import os
from typing import List, Optional

from . import tracer

__all__ = ["collect_events", "chrome_trace", "write_chrome_trace",
           "request_timeline", "request_lane_events",
           "REQUIRED_SPAN_KEYS", "REQUEST_LANE_PID"]

# the schema contract tests validate exported "X" events against
REQUIRED_SPAN_KEYS = ("name", "ph", "ts", "dur", "pid", "tid")

# synthetic pid for the per-request lane rows (one tid per request id) —
# far above any real pid so the viewer groups them as their own process
REQUEST_LANE_PID = 1 << 22


def collect_events(legacy_events: Optional[List[dict]] = None) -> List[dict]:
    """Snapshot every thread ring + the legacy Domain/Task/Counter/Marker
    event list into one flat chrome-trace event array (metadata rows first).
    Read-only: repeated calls over an unchanged recorder return identical
    output (the ``dump(finished=True)`` idempotency contract builds on
    this)."""
    pid = os.getpid()
    events: List[dict] = [{"ph": "M", "name": "process_name", "pid": pid,
                           "tid": 0, "args": {"name": "mxtpu"}}]
    for tid, tname, evs, dropped in tracer.snapshot_buffers():
        events.append({"ph": "M", "name": "thread_name", "pid": pid,
                       "tid": tid, "args": {"name": tname}})
        if dropped:
            events.append({"ph": "i", "name": "trace/dropped_events",
                           "cat": "trace", "pid": pid, "tid": tid,
                           "ts": evs[0]["ts"] if evs else 0, "s": "t",
                           "args": {"dropped": dropped}})
        for ev in evs:
            e = dict(ev)
            e["pid"] = pid
            e["tid"] = tid
            if "id" in e:
                # the span's id and its parent's travel in ``args`` (a
                # top-level ``id`` means an async event to the viewers; the
                # serving spans' ``args.id`` is the request's)
                e["args"] = dict(e.get("args") or {}, span_id=e.pop("id"),
                                 parent_id=e.pop("parent"))
            events.append(e)
    for ev in legacy_events or []:
        e = dict(ev)
        e.setdefault("pid", pid)
        e.setdefault("tid", 0)
        events.append(e)
    return events


def _event_request_ids(ev: dict):
    """Request ids an event is tagged with: the serving spans carry
    ``args.id`` (one request) or ``args.ids`` (a decode dispatch over the
    whole slot batch)."""
    args = ev.get("args")
    if not isinstance(args, dict):
        return ()
    rid = args.get("id")
    ids = args.get("ids")
    if rid is not None and not isinstance(ids, (list, tuple)):
        return (rid,)
    if rid is not None:
        return (rid, *ids)
    return tuple(ids) if isinstance(ids, (list, tuple)) else ()


def request_timeline(rid: int,
                     events: Optional[List[dict]] = None) -> List[dict]:
    """Every recorded event tagged with request ``rid``, time-sorted — one
    request's full life (submit → admission → prefill chunks → decode
    dispatches → retire, including the drain/adopt markers when the request
    crossed an engine handoff). ``ServingEngine.request_timeline`` is the
    public face."""
    if events is None:
        events = collect_events()
    out = [e for e in events if rid in _event_request_ids(e)]
    out.sort(key=lambda e: e.get("ts", 0))
    return out


def request_lane_events(events: List[dict]) -> List[dict]:
    """Synthetic per-request chrome-trace lanes: every request-tagged event
    duplicated under ``pid = REQUEST_LANE_PID`` with ``tid = request id``,
    plus naming metadata — so the viewer shows one swim-lane per request
    alongside the real thread rows (a decode span over N active slots lands
    in all N lanes)."""
    lanes: List[dict] = []
    seen: set = set()
    for ev in events:
        for rid in _event_request_ids(ev):
            if rid not in seen:
                seen.add(rid)
                lanes.append({"ph": "M", "name": "thread_name",
                              "pid": REQUEST_LANE_PID, "tid": rid,
                              "args": {"name": f"request {rid}"}})
            e = dict(ev)
            e["pid"] = REQUEST_LANE_PID
            e["tid"] = rid
            lanes.append(e)
    if seen:
        lanes.insert(0, {"ph": "M", "name": "process_name",
                         "pid": REQUEST_LANE_PID, "tid": 0,
                         "args": {"name": "mxtpu-requests"}})
    return lanes


def chrome_trace(legacy_events: Optional[List[dict]] = None,
                 xplane_dir: Optional[str] = None,
                 events: Optional[List[dict]] = None,
                 request_lanes: bool = False) -> dict:
    """The full dump payload. ``events`` short-circuits collection (used by
    the profiler's frozen final snapshot); ``request_lanes=True`` appends
    the synthetic per-request swim-lanes (flight-recorder bundles use it)."""
    if events is None:
        events = collect_events(legacy_events)
    if request_lanes:
        events = list(events) + request_lane_events(events)
    payload = {"traceEvents": events, "displayTimeUnit": "ms"}
    if xplane_dir:
        # the paired XLA device trace (jax.profiler XPlane dir, open in
        # Perfetto/TensorBoard); span names match via TraceAnnotation
        payload["otherData"] = {"xplane_dir": xplane_dir}
    return payload


def write_chrome_trace(fname: str, payload: dict) -> str:
    tmp = f"{fname}.tmp-{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(payload, f)
    os.replace(tmp, fname)   # readers never observe a torn dump
    return fname
