"""Kernels: device time per profiled step of the delta rule's forward
launches (``tpu_custom_call/kda_fwd*``), one a ``kda`` layer."""
import ling


def read(view):
    return ling.kernel_ms(view, "fwd")
