"""Kernels: full and cross differential attention against their roofline,
forward and backward over those layers, by the pairs the causal mask lets
through (``roofline_hybrid.attention_flops/bytes``)."""
import hybrid


def read(view):
    return hybrid.attention_roofline_pct(view, windowed=False)
