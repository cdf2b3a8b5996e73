"""Model step (train): tokens per second of the window times the operations
a token needs (``roofline.train_flops_per_token``: 6 per matmul parameter,
tied head once, plus causal attention; no recomputation) over chips times the
bf16 peak."""
import roofline


def read(view):
    if "tokens" not in view:
        return None
    per_token = roofline.train_flops_per_token(view["config"],
                                               view["seq_len"])
    rate = view["tokens"] / view["window_s"]
    return 100.0 * rate * per_token / (
        view["chips"] * view["peaks"]["bf16_flops_per_s"])
