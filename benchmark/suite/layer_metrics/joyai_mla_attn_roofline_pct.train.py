"""Kernels: the flash launches of every latent-attention layer a step runs
(the trunk's five and the prediction block's) against their roofline
(``roofline_kda.mla_flops/bytes``: ``roofline.py``'s counts at the score
width 192 and the value width 128, by visible pairs), forward and
backward."""
import joyai


def read(view):
    return joyai.attn_roofline_pct(view)
