"""Kernels: the scan's forward launches against their roofline
(``roofline_hybrid.scan_bytes(...)["fwd"]`` a launch x the launches the
TRACE shows a step, the recomputed blocks' second ones among them, over the
HBM peak, over the kernel's device time per step)."""
import jamba


def read(view):
    return jamba.scan_roofline_pct(view, "fwd")
