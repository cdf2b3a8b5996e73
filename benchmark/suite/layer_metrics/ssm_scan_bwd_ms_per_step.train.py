"""Kernels: device time of the selective scan's backward kernel
(``ssm_scan_bwd``) per profiled step, per device."""
import hybrid


def read(view):
    return hybrid.kernel_ms(view, "ssm_bwd")
