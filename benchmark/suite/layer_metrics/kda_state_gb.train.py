"""Kernels: GB of chunk-start state the ``kda`` layers keep from forward to
backward (the op's own count of a launch, ``profiler.get_kda_stats()``,
times the layers: nothing is recomputed, so all are live at once)."""
import ling


def read(view):
    return ling.state_gb(view)
