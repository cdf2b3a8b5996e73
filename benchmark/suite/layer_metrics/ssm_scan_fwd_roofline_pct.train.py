"""Kernels: the scan's forward against its roofline
(``roofline_hybrid.scan_bytes(...)["fwd"]`` x mamba layers over the HBM peak,
over its device time per step). Memory bounds it by the count."""
import hybrid


def read(view):
    return hybrid.scan_roofline_pct(view, "fwd")
