"""Model step (train): median over the WINDOW's steps of the host time in
the trainer's four issuing spans (``train/place`` + ``prepare`` + ``dispatch``
+ ``adopt``), from the step ring's rows: ``host_issue_ms_per_step.train`` on
every step of the window instead of six profiled ones."""
import phases
import stats


def read(view):
    rows = phases.window_rows(view)
    if rows is None:
        return None
    return stats.median([phases.issue_s(r) for r in rows]) * 1e3
