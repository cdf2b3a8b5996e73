"""Kernels: the scan's backward against its roofline
(``roofline_hybrid.scan_bytes(...)["bwd"]`` x mamba layers over the HBM peak,
over its device time per step)."""
import hybrid


def read(view):
    return hybrid.scan_roofline_pct(view, "bwd")
