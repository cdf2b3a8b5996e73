"""Device: what the window's slow steps (over 1.2 medians) waited in
``train/readback`` beyond its median, spread over all window steps: the part
of the stalls in which the device or its runtime was late, not the host
thread."""
import phases


def read(view):
    return phases.slow_excess_ms_per_step(view, lambda r: r["readback_s"])
