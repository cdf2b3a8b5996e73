"""Kernels: one launch of the retention forward against its roofline
(``roofline_retention``: the lesser of the causal-pairs and the state form
over the bf16 peak, or its bytes over the HBM peak, whichever is larger),
over a launch's mean device time."""
import brumby


def read(view):
    return brumby.kernel_roofline_pct(view, "fwd")
