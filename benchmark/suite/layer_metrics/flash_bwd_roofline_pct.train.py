"""Kernels: the backward flash kernels against their own roofline
(``roofline.flash_flops/bytes(...)["bwd"]`` x layers over their device time
per step; the split kernels' second S and dP are not counted as work)."""
import scopes


def read(view):
    return scopes.flash_roofline_pct(view, "bwd")
