"""Kernels: device time of the causal flash kernels (``flash_fwd``,
``flash_bwd_dq``, ``flash_bwd_dkv``) per profiled step: the full layer and
the cross layers, which run the same kernels."""
import hybrid


def read(view):
    return hybrid.kernel_ms(view, "full_fwd", "full_bwd")
