"""Model step (train): tokens per second of the window times the operations
a token needs (``roofline_retention.train_flops_per_token``: 6 per matmul
parameter with the head's slice once, plus every layer's retention at the
lesser of its two forms; no recomputation) over chips times the bf16
peak."""
import brumby


def read(view):
    return brumby.mfu_pct(view)
