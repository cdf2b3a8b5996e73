#!/usr/bin/env python3
"""The benchmark's entry point.

    python3 benchmark/suite/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1> [--rehearsal]

One run of one cell: build the system from the seed, check what the timed
path produces against the plain reference, warm up every shape, measure for
``--seconds``, and print one JSON object as the last line of stdout.
Earlier lines carry the phase clock, compile counts, generator lateness,
sample counts and memory. Without the chips the cell asks for the run exits
non-zero and prints no result; ``--rehearsal`` is the only CPU mode, at tiny
sizes, and prints counts and correctness and never a device metric.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()          # before anything is imported

import argparse
import contextlib
import gc
import json
import os
import shutil
import sys
import tempfile
import threading

SUITE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(SUITE))
sys.path[:0] = [SUITE, ROOT]     # the suite's modules, then the program

# One run is killed from outside at 360 s whatever it is doing. A run that
# is still going at this many seconds by the harness's own clock names the
# phase it is in and exits 4, instead of dying without a word. A cold traced
# run, the longest there is, has to end inside it.
RUN_DEADLINE_S = 300.0


class Run:
    """What a job kind gets: the cell, the arguments, the clock."""

    def __init__(self, cell, args, counter):
        self.cell, self.counter = cell, counter
        self.seed, self.seconds = args.seed, float(args.seconds)
        self.trace, self.rehearsal = bool(args.trace), args.rehearsal
        self.control = bool(args.control)
        self.phases = []              # (name, seconds)
        self.current = "start"
        self.t_window = None
        self.window_compiles = None
        self.trace_dir = None
        self.memory_peak = None
        self.done = False
        self._window_before = None

    def say(self, msg: str) -> None:
        print(f"[bench {time.perf_counter() - T0:7.2f}s] {msg}", flush=True)

    @contextlib.contextmanager
    def phase(self, name: str):
        self.current = name
        before = self.counter.snapshot()
        t = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t
            self.phases.append((name, dt))
            d = self.counter.delta(self.counter.snapshot(), before)
            self.say(f"phase {name}: {dt:.2f}s; compile requests "
                     f"{d['requests']}, cache hits {d['cache_hits']}, "
                     f"compiled {d['compiled']} in {d['compile_s']}s")
            self.current = "after " + name

    def call(self, span: str, fn, *args):
        """A call into the system under a host span of the benchmark's own,
        which the profiler's trace carries (``TraceAnnotation``)."""
        import jax
        with jax.profiler.TraceAnnotation("bench/" + span):
            return fn(*args)

    def _devices(self) -> list:
        import jax
        return jax.local_devices()[:self.cell.chips]

    def keep_memory_peak(self) -> None:
        """The peak on the fullest chip up to now: a job calls this before
        it frees the program's state and runs the reference, so that the
        peak the run reports is the program's."""
        self.memory_peak = max((d.memory_stats() or {}).get(
            "peak_bytes_in_use", 0) for d in self._devices())
        self.say(f"peak_bytes_in_use of the program: {self.memory_peak}")

    def note_memory(self, when: str) -> None:
        for d in self._devices():
            ms = d.memory_stats() or {}
            self.say(f"memory {when}, device {d.id}: " + ", ".join(
                f"{k} {ms.get(k)}" for k in (
                    "bytes_in_use", "peak_bytes_in_use", "largest_alloc_size",
                    "bytes_limit")))

    def _gc_event(self, phase: str, info: dict) -> None:
        """Times the interpreter's collections while the window is open:
        a full collection stops every Python thread of the process."""
        now = time.perf_counter()
        if phase == "start":
            self._gc_t = now
        elif self._gc_t is not None:
            dt = now - self._gc_t
            self._gc_total += dt
            if dt > self._gc_longest[0]:
                self._gc_longest = (dt, info.get("generation"))

    def window_opens(self) -> None:
        self._gc_t, self._gc_total, self._gc_longest = None, 0.0, (0.0, None)
        gc.callbacks.append(self._gc_event)
        self.current = "window"
        self.t_window = time.perf_counter()
        self._window_before = self.counter.snapshot()
        self.say(f"window opens; set-up so far {self.t_window - T0:.2f}s")

    def window_closes(self) -> None:
        gc.callbacks.remove(self._gc_event)
        d = self.counter.delta(self.counter.snapshot(), self._window_before)
        self.window_compiles = d["compiled"]
        self.say(f"window closed; compile requests inside {d['requests']}, "
                 f"compiled inside {d['compiled']}; interpreter collections "
                 f"took {self._gc_total * 1e3:.0f}ms in all, the longest "
                 f"{self._gc_longest[0] * 1e3:.0f}ms (generation "
                 f"{self._gc_longest[1]})")
        self.current = "after window"

    def setup_s(self) -> float:
        """Process start to the start of the window. The jobs run the
        reference after the window, so nothing of it is in here."""
        return self.t_window - T0

    @contextlib.contextmanager
    def profile(self):
        """``jax.profiler`` on, for the few steps or seconds inside: Python
        tracing off, host tracer at the lowest level that keeps
        ``TraceAnnotation``s."""
        import jax
        self.trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        try:
            yield _Profile()
        finally:
            jax.profiler.stop_trace()


class _Profile:
    @contextlib.contextmanager
    def step(self, name: str, i: int):
        import jax
        with jax.profiler.StepTraceAnnotation(name, step_num=i):
            yield


def _watchdog(run: Run) -> None:
    """Ends the process if the run passes its deadline (also from inside a
    long compile, which holds no Python lock)."""
    def watch():
        while not run.done:
            if time.perf_counter() - T0 > RUN_DEADLINE_S:
                sys.stderr.write(
                    f"benchmark: the run passed its deadline of "
                    f"{RUN_DEADLINE_S:.0f}s in phase {run.current!r}; "
                    f"phases so far: "
                    f"{[(p[0], round(p[1], 1)) for p in run.phases]}\n")
                sys.stderr.flush()
                os._exit(4)
            time.sleep(0.5)
    threading.Thread(target=watch, daemon=True, name="bench-deadline").start()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also put the reference, computed in the nearest "
                         "lower precision, in the program's place and print "
                         "its numbers beside the limits (never a "
                         "benchmark run: the driver does not pass it)")
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU walk-through at tiny sizes: counts and "
                         "correctness, no device metric")
    args = ap.parse_args(argv)

    from manifest import Cell, load_peaks
    cell = Cell(args.workload, rehearsal=args.rehearsal)
    import system                       # the program, imported here and not
    import jax                          # before: a missing tree fails first

    devices = jax.devices()
    if args.rehearsal:
        if devices[0].platform != "cpu":
            raise SystemExit("benchmark: --rehearsal is the CPU mode")
    elif devices[0].platform != "tpu" or len(devices) < cell.chips:
        sys.stderr.write(
            f"benchmark: {cell.name} needs {cell.chips} TPU chip(s); JAX "
            f"found {len(devices)} x {devices[0].platform}\n")
        return 3
    cache_dir = system.place_compile_cache()
    from compile_counter import CompileCounter
    run = Run(cell, args, CompileCounter())
    run.phases.append(("import", time.perf_counter() - T0))
    run.say(f"{cell.name} seed {args.seed} seconds {args.seconds} trace "
            f"{args.trace}; {len(devices)} x {devices[0].device_kind}; "
            f"import {run.phases[0][1]:.2f}s; compile cache {cache_dir}")
    _watchdog(run)

    result = cell.job().run(run)

    if run.window_compiles:
        run.say(f"{run.window_compiles} programs compiled inside the window: "
                f"the warm-up missed a shape")
        result["correct"] = False
    if run.memory_peak is None:
        run.keep_memory_peak()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": run.memory_peak}
    metrics = {}
    line = {"correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"], "metrics": metrics, "device": device}
    if args.rehearsal:
        run.say("rehearsal: counts and correctness only, no metric printed")
    elif not args.trace:
        values = dict(result["end_to_end"], setup_s=run.setup_s())
        for m in cell.end_to_end():
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        with run.phase("trace reduction"):
            import xplane
            reduced = xplane.reduce_dir(run.trace_dir, chips=cell.chips)
            view = dict(result["observations"], trace=reduced, cell=cell,
                        config=cell.config,
                        peaks=load_peaks(devices[0].device_kind),
                        spans=system.program_spans(),
                        serving_stats=system.serving_stats())
            for m in cell.per_layer():
                value = cell.reader(m["name"]).read(view)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        line["breakdown"] = {"device_ops": reduced["top_ops"][:10],
                             "idle_gaps": reduced["idle_gaps"][:10]}
    if run.trace_dir:
        shutil.rmtree(run.trace_dir, ignore_errors=True)
    run.done = True
    run.say("phases: " + ", ".join(f"{p[0]} {p[1]:.1f}s" for p in run.phases)
            + f"; total {time.perf_counter() - T0:.1f}s")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
