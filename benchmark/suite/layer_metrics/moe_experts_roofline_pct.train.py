"""Kernels: the grouped products of every expert layer against their
roofline, by the (token, expert) pairs the program counted in the profiled
steps (``profiler.get_moe_stats()``) and ``roofline_moe.grouped_flops/bytes``,
over the device time under ``block<i>/moe/experts``."""
import moe


def read(view):
    return moe.experts_roofline_pct(view)
