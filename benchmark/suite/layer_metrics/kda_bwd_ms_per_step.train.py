"""Kernels: device time per profiled step of the delta rule's backward
launches (``tpu_custom_call/kda_bwd*``), one a ``kda`` layer; the chunk's
matrices made again are inside."""
import ling


def read(view):
    return ling.kernel_ms(view, "bwd")
