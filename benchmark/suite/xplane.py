"""From the profiler's ``.xplane.pb`` to numbers, in one pass with
``jax.profiler.ProfileData``: device busy time, the operations that took most
of it, kernel time by name, collective time exposed, and the longest idle
gaps named by the host span open at the time.

What the planes hold on a TPU v5e host (looked at by hand, PR 23): one plane
``/device:TPU:<n>`` per chip, whose line ``XLA Ops`` has one event per
executed HLO operation (fusions, custom calls, copies, collectives) and
``XLA Modules`` one per program run; the host plane ``/host:CPU`` has a line
per thread with the ``TraceAnnotation``s. Events of one file share a time
base (``start_ns``).
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute",
    re.I)
CONTAINERS = ("while", "conditional", "call")
BENCH_SPAN = "bench/"
PYTHON_LEVEL = ("PjitFunction", BENCH_SPAN, "serving/")
NAMED_GAPS = 200      # the longest idle gaps get a name, the rest one row


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise SystemExit(f"benchmark: no .xplane.pb under {trace_dir}")
    return found[-1]


def merge(intervals: list) -> list:
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def covered(intervals: list) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: list, b: list) -> list:
    """The parts of the disjoint sorted intervals ``a`` outside ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def read_planes(path: str) -> dict:
    """``{"devices": {n: {"ops": [(name, start, end)], "modules": [...]}},
    "host": [(name, start, end, on a Python thread)]}`` with times in ns;
    ``host`` holds every host event that lasts (annotations, dispatches,
    transfers, waits)."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    key = "ops"
                elif line.name == MODULES_LINE:
                    key = "modules"
                else:
                    continue
                dev[key] = [(short_name(e.name), e.start_ns,
                             e.start_ns + e.duration_ns) for e in line.events]
            devices[int(m.group(1))] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events if e.duration_ns > 0]
                # a Python thread's line: it carries jit dispatches or the
                # benchmark's or the program's annotations
                python = any(n.startswith(PYTHON_LEVEL) for n, _, _ in events)
                host.extend((n, a, b, python) for n, a, b in events)
    return {"devices": devices, "host": host}


MOSAIC = 'custom_call_target="tpu_custom_call"'
MOSAIC_PREFIX = "tpu_custom_call/"


def short_name(text: str) -> str:
    """An ``XLA Ops`` event is named by its whole HLO line, ``%fusion.7 =
    bf16[...] fusion(...)``: keep ``fusion.7``. A Pallas kernel is a custom
    call to ``tpu_custom_call`` and carries no kernel name in the trace (its
    HLO name is the JAX scope it was traced in, ``jvp__``,
    ``transpose_jvp___``): mark it, ``tpu_custom_call/jvp__.24``."""
    m = re.match(r"%(\S+) = ", text)
    name = m.group(1) if m else text
    return MOSAIC_PREFIX + name if MOSAIC in text else name


def base_name(op: str) -> str:
    """``fusion.123`` -> ``fusion``."""
    return re.sub(r"[.\d]+$", "", op) or op


def reduce_planes(planes: dict, chips: int) -> dict:
    """The numbers the layer metrics read. Times in seconds, per-device
    quantities averaged over the ``chips`` devices used."""
    devs = [planes["devices"][n] for n in sorted(planes["devices"])[:chips]]
    devs = [d for d in devs if d["ops"]]
    if not devs:
        raise SystemExit("benchmark: the trace holds no device operation")
    spans = [(n, s, e) for n, s, e, _ in planes["host"]
             if n.startswith(BENCH_SPAN)]
    if spans:
        w0, w1 = min(s for _, s, _ in spans), max(e for _, _, e in spans)
    else:
        w0 = min(s for d in devs for _, s, _ in d["ops"])
        w1 = max(e for d in devs for _, _, e in d["ops"])
    busy, by_name, exposed = 0.0, {}, 0.0
    gaps = []
    for d in devs:
        ops = [(n, max(s, w0), min(e, w1)) for n, s, e in d["ops"]
               if e > w0 and s < w1]
        all_iv = merge([(s, e) for _, s, e in ops])
        busy += covered(all_iv)
        for n, s, e in ops:
            by_name[n] = by_name.get(n, 0.0) + (e - s)
        coll = merge([(s, e) for n, s, e in ops if COLLECTIVE.search(n)])
        comp = merge([(s, e) for n, s, e in ops if not COLLECTIVE.search(n)])
        exposed += covered(subtract(coll, comp))
        if d is devs[0]:
            gaps = subtract([[w0, w1]], all_iv)
    k = len(devs)
    named = name_gaps(gaps, planes["host"])
    annotations = {}
    for n, a, b, py in planes["host"]:
        if py and "/" in n and a >= w0 and b <= w1:
            annotations.setdefault(n, []).append((a / 1e9, b / 1e9))
    grouped = {}
    for n, t in by_name.items():
        # a loop's event spans its body's own events: counting both would
        # count the body twice
        if base_name(n) not in CONTAINERS:
            grouped[base_name(n)] = grouped.get(base_name(n), 0.0) + t
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy / k / 1e9,
        "op_s": {n: t / k / 1e9 for n, t in by_name.items()},
        "top_ops": [[n, t / k / 1e9] for n, t in
                    sorted(grouped.items(), key=lambda kv: -kv[1])],
        "collective_exposed_s": exposed / k / 1e9,
        # the first device's program runs and the Python threads' annotations
        # by name, both as (start_s, end_s) on the trace's clock
        "modules": [(n, a / 1e9, b / 1e9) for n, a, b in devs[0]["modules"]
                    if b > w0 and a < w1],
        "annotations": annotations,
        "idle_gaps": [[n, t / 1e9] for n, t in
                      sorted(named.items(), key=lambda kv: -kv[1])],
        "devices": k,
    }


def name_gaps(gaps: list, host: list) -> dict:
    """Idle time by what the host was doing: each of the longest gaps is
    named by the innermost event of a Python thread open at its middle
    (a dispatch, a readback, an annotation) and, after ``>``, the innermost
    host event of any thread (what inside the runtime)."""
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])
    named = {}
    for s, e in gaps[:NAMED_GAPS]:
        mid = (s + e) / 2
        open_now = [(b - a, n, py) for n, a, b, py in host if a <= mid <= b]
        py = [x for x in open_now if x[2]]
        label = (min(py)[1][:60] if py else "no Python event open") + " > " \
            + (min(open_now)[1][:60] if open_now else "nothing")
        named[label] = named.get(label, 0.0) + (e - s)
    rest = sum(e - s for s, e in gaps[NAMED_GAPS:])
    if rest:
        named[f"gaps beyond the {NAMED_GAPS} longest"] = rest
    return named


def reduce_dir(trace_dir: str, chips: int) -> dict:
    return reduce_planes(read_planes(find_xplane(trace_dir)), chips)


def modules_inside(reduced: dict, annotation: str) -> list:
    """Device durations (s) of the program runs that fall inside a host
    annotation of this name: the engine reads a dispatch's result back
    before its span closes, so the run lies within it. One entry per span
    that holds a run."""
    out = []
    for a, b in reduced["annotations"].get(annotation, []):
        inside = [e - s for _, s, e in reduced["modules"]
                  if a <= (s + e) / 2 <= b]
        if inside:
            out.append(sum(inside))
    return out


def kernel_seconds(reduced: dict, pattern: str) -> float:
    """Device seconds (per device) of the operations whose name matches."""
    rx = re.compile(pattern)
    return sum(t for n, t in reduced["op_s"].items() if rx.search(n))
