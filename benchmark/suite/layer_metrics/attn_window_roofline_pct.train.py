"""Kernels: windowed differential attention against its roofline, forward
and backward over the window layers, by the pairs the window lets through
(``roofline_hybrid.attention_flops/bytes``)."""
import hybrid


def read(view):
    return hybrid.attention_roofline_pct(view, windowed=True)
