"""What the program says of a run outside its steady device step: the
memory marks at the ends of its phases and the step ring's rows, one a
``dpt.step``. The two accessors of the program are here (beside ``scopes.py``
and ``system.py``, the other places of the benchmark that import it); the
spans of the phases come through ``scopes.span_totals``. A program that keeps
no marks or no ring gives nothing, and every reader then returns ``None``.
"""

from __future__ import annotations

import scopes
import stats

ISSUE_PARTS = ("place_s", "prepare_s", "dispatch_s", "adopt_s")
SLOW = 1.2              # a slow step: over this many window medians


def memory_marks() -> list:
    """``profiler.get_memory_stats()["marks"]``, oldest first: ``[{"name",
    "t_ns", "host_rss_bytes", "host_peak_rss_bytes", and what the fullest
    device reports of "bytes_in_use", "bytes_reserved", "bytes_limit",
    ...}]``."""
    from mxtpu import profiler
    return profiler.get_memory_stats().get("marks", [])


def step_timeline() -> list:
    """``profiler.get_step_timeline()``: the ring's rows, oldest first."""
    from mxtpu import profiler
    get = getattr(profiler, "get_step_timeline", None)
    return get() if get else []


def span_seconds(view: dict, names: tuple, less: tuple = ()):
    """Seconds under the spans ``names`` less those under ``less``, from the
    program's totals; ``None`` for another job kind or where the first of
    ``names`` was never opened."""
    if "profiled_steps" not in view:
        return None
    totals = scopes.span_totals()
    if names[0] not in totals:
        return None
    return sum(totals[n]["seconds"] for n in names if n in totals) \
        - sum(totals[n]["seconds"] for n in less if n in totals)


def newest_mark(view: dict, before_ns=None):
    """The newest mark (taken before ``before_ns`` on the tracer's clock,
    where given) of a training run, or ``None``."""
    if "profiled_steps" not in view:
        return None
    marks = [m for m in memory_marks()
             if before_ns is None or m.get("t_ns", 0) <= before_ns]
    return marks[-1] if marks else None


def mark_gb(mark, plus: tuple, minus: tuple = ()):
    """Sum of the mark's keys ``plus`` less ``minus``, in GB; ``None``
    where the mark or one of the keys is missing (a backend that does not
    report it)."""
    if mark is None or any(k not in mark for k in plus + minus):
        return None
    return (sum(mark[k] for k in plus) - sum(mark[k] for k in minus)) / 1e9


def window_rows(view: dict):
    """The ring's rows of the window's steps: of the newest ``len(step_s) +
    profiled_steps`` the first ``len(step_s)`` (the profiled steps follow
    the window). ``None`` where the ring holds fewer, or where a row and the
    harness's own time of that step differ by more than 1 ms: the rows are
    then not the window's, and nothing is read from them."""
    step_s, after = view.get("step_s"), view.get("profiled_steps")
    if not step_s or after is None:
        return None
    rows = step_timeline()
    rows = rows[max(0, len(rows) - len(step_s) - after):len(rows) - after]
    if len(rows) != len(step_s) or any(
            abs(r["step_s"] - s) > 1e-3 for r, s in zip(rows, step_s)):
        return None
    return rows


def issue_s(row: dict) -> float:
    return sum(row[k] for k in ISSUE_PARTS)


def slow_rows(rows: list) -> list:
    """The rows whose step took over ``SLOW`` times the rows' median."""
    limit = SLOW * stats.median([r["step_s"] for r in rows])
    return [r for r in rows if r["step_s"] > limit]


def slow_excess_ms_per_step(view: dict, part):
    """Over the window's slow steps, what ``part(row)`` took beyond its
    median over the window, summed and spread over ALL window steps, ms:
    the share of a step's mean time that the stalls cost on that side."""
    rows = window_rows(view)
    if rows is None:
        return None
    typical = stats.median([part(r) for r in rows])
    return sum(part(r) - typical for r in slow_rows(rows)) / len(rows) * 1e3
