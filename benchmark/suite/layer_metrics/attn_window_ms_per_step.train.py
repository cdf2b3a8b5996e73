"""Kernels: device time of the windowed flash kernels (``flash_fwd_window``,
``flash_bwd_dq_window``, ``flash_bwd_dkv_window``) per profiled step."""
import hybrid


def read(view):
    return hybrid.kernel_ms(view, "window_fwd", "window_bwd")
