"""Kernels: the causal flash launches a step runs (``flash_fwd`` x the
launches seen in the trace, the recomputed one too, and
``flash_bwd_fused``) against their roofline at 20 query heads on ONE
key/value head of 128 by visible pairs
(``roofline_jamba.attention_flops/bytes``)."""
import jamba


def read(view):
    return jamba.attn_roofline_pct(view)
