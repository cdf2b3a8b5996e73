"""Kernels: device time of the retention backward kernels (``retention_bwd``
and whatever carries that prefix) per profiled step, per device."""
import brumby


def read(view):
    return brumby.kernel_ms(view, "bwd")
