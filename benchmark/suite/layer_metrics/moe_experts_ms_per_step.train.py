"""Kernels: device time per profiled step under ``block<i>/moe/experts``: the
grouped products (``moe_gmm`` / ``moe_tgmm``, or XLA's ragged dot) and the
SwiGLU between them, forward, the backward's second forward and backward."""
import moe


def read(view):
    return moe.scope_ms(view, "experts")
