"""Model step (train): tokens per second of the window times the operations
a token needs (``roofline_joyai.train_flops_per_token``: 6 per matmul
parameter with the experts by the pairs counted, the prediction block's
layer, joining matrix and second pass through the head included, latent
attention by its visible pairs in all six layers) over chips times the bf16
peak."""
import joyai


def read(view):
    return joyai.mfu_pct(view)
