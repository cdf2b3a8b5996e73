"""Kernels: the latent-attention layers' flash launches against their
roofline (``roofline_kda.mla_flops/bytes``: ``roofline.py``'s counts at the
score width 192 and the value width 128, by visible pairs), forward and
backward."""
import ling


def read(view):
    return ling.attn_roofline_pct(view)
