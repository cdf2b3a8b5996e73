"""Kernels: the forward flash kernel against its own roofline
(``roofline.flash_flops/bytes(...)["fwd"]`` x layers over its device time per
step). Compute bounds it at these shapes."""
import scopes


def read(view):
    return scopes.flash_roofline_pct(view, "fwd")
