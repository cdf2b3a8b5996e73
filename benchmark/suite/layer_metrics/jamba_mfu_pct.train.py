"""Model step (train): tokens per second of the window times the operations
a token needs (``roofline_jamba.train_flops_per_token``: 6 per matmul
parameter, the tied table's slice once as the head, the attention layer by
its visible pairs at 20-on-1 heads of 128, the scans' multiply-adds as they
are; the recomputed forwards NOT counted) over chips times the bf16 peak."""
import jamba


def read(view):
    return jamba.mfu_pct(view)
