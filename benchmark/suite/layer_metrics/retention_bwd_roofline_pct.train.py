"""Kernels: one launch of the retention backward against its roofline (2.5
times the forward's operations, as attention's backward is counted)."""
import brumby


def read(view):
    return brumby.kernel_roofline_pct(view, "bwd")
