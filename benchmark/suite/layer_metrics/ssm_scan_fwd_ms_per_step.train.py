"""Kernels: device time of the selective scan's forward kernel
(``ssm_scan_fwd``) per profiled step, per device."""
import hybrid


def read(view):
    return hybrid.kernel_ms(view, "ssm_fwd")
