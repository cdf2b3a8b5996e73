"""Device: share of the profiled steps in which no operation ran, averaged
over the devices used."""


def read(view):
    if "profiled_steps" not in view:
        return None
    t = view["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
