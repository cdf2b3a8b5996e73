"""Model step (train): tokens per second of the window times the operations
a token needs (``roofline_lfm2.train_flops_per_token``: 6 per matmul
parameter with ``num_experts_per_tok`` experts a token and the tied table
once; attention by visible pairs on the ``full_attention`` layers; no
recomputation) over chips times the bf16 peak."""
import lfm2


def read(view):
    return lfm2.mfu_pct(view)
