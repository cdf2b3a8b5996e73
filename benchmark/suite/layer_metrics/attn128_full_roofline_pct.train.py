"""Kernels: the causal flash launches (``flash_fwd``, ``flash_bwd_fused``)
against their roofline at the held query heads of ``head_dim`` on the held
key/value heads, over the full-attention layers
(``roofline_moe.attention_flops/bytes``)."""
import moe


def read(view):
    return moe.attention_roofline_pct(view, windowed=False)
