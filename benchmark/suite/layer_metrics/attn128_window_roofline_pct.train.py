"""Kernels: the windowed flash launches against their roofline at the held
query heads of ``head_dim`` on the held key/value heads, forward and
backward over the window layers, by the pairs the window lets through
(``roofline_moe.attention_flops/bytes``)."""
import moe


def read(view):
    return moe.attention_roofline_pct(view, windowed=True)
