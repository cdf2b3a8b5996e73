"""Kernels: the causal flash launches (``flash_fwd``, ``flash_bwd_fused``)
against their roofline at 32 query heads of 64 on 8 key/value heads, over
the ``full_attention`` layers (``roofline_lfm2.attention_flops/bytes``)."""
import lfm2


def read(view):
    return lfm2.attention_roofline_pct(view)
