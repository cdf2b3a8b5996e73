"""Kernels: one launch of the delta rule's backward against its roofline
(``roofline_kda``: twice the recurrence's forward products, or its bytes,
whichever bounds), over a launch's mean device time."""
import ling


def read(view):
    return ling.kernel_roofline_pct(view, "bwd")
