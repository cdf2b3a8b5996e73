"""Model step (train): the share of the window's steps that took over 1.2
times the window's median step, from the step ring's rows."""
import phases


def read(view):
    rows = phases.window_rows(view)
    if rows is None:
        return None
    return 100.0 * len(phases.slow_rows(rows)) / len(rows)
