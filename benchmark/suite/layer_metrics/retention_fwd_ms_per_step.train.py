"""Kernels: device time of the retention forward kernel (``retention_fwd``)
per profiled step, per device: every launch, the recomputed ones among
them."""
import brumby


def read(view):
    return brumby.kernel_ms(view, "fwd")
