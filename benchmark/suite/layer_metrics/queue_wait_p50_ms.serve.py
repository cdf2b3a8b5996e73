"""Admission + scheduler: the engine's own TTFT decomposition, median wait
from submit to admission (``profiler.get_serving_stats()``, log-bucket
percentile) over the window."""


def read(view):
    stats = view.get("serving_stats") or {}
    if not stats.get("queue_wait_ms_count"):
        return None
    return stats["queue_wait_ms_p50"]
