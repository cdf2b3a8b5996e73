"""Kernels: one launch of the delta rule's forward against its roofline
(``roofline_kda``: the RECURRENCE's three products a token a head over the
bf16 peak, or its bytes over the HBM peak, whichever is larger), over a
launch's mean device time."""
import ling


def read(view):
    return ling.kernel_roofline_pct(view, "fwd")
