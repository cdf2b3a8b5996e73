"""Kernels: device time of the backward flash kernels (``flash_bwd_dq`` +
``flash_bwd_dkv``, or ``flash_bwd_fused``) per profiled step, per device."""
import scopes


def read(view):
    seconds = scopes.flash_seconds_per_step(view, "bwd")
    return None if seconds is None else seconds * 1e3
