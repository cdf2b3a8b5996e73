"""Kernels: device time of the forward flash kernel (``flash_fwd``, the
name its ``pallas_call`` carries) per profiled step, per device."""
import scopes


def read(view):
    seconds = scopes.flash_seconds_per_step(view, "fwd")
    return None if seconds is None else seconds * 1e3
