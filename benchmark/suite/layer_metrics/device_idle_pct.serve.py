"""Device: share of the profiled seconds of serving in which no operation
ran on the device."""


def read(view):
    if "trace" not in view or "records" not in view:
        return None
    t = view["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
