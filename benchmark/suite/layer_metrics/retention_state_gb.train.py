"""Kernels: GB of chunk-start state ONE retention layer's forward keeps for
its backward (the op's own count of a launch,
``profiler.get_retention_stats()``): with a block recomputed at a time, what
is live at once."""
import brumby


def read(view):
    return brumby.state_gb(view)
