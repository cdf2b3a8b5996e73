"""Model step (train): tokens per second of the window times the operations
a token needs (``roofline_kda.train_flops_per_token``: 6 per matmul
parameter with the experts by the pairs counted, the delta rule by its
recurrence, latent attention by its visible pairs) over chips times the
bf16 peak."""
import ling


def read(view):
    return ling.mfu_pct(view)
