"""Kernels: the scan's backward launches against their roofline
(``roofline_hybrid.scan_bytes(...)["bwd"]`` a launch x the launches the
TRACE shows a step, over the HBM peak, over the kernel's device time per
step)."""
import jamba


def read(view):
    return jamba.scan_roofline_pct(view, "bwd")
