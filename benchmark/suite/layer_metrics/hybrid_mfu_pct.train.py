"""Model step (train): tokens per second of the window times the operations
a token needs (``roofline_hybrid.train_flops_per_token``: 6 per matmul
parameter, the tied slice once, attention by visible pairs, the scan as it
is; no recomputation) over chips times the bf16 peak."""
import roofline_hybrid


def read(view):
    if "tokens" not in view or "layer_kinds" not in view["config"]:
        return None
    per_token = roofline_hybrid.train_flops_per_token(view["config"],
                                                      view["seq_len"])
    rate = view["tokens"] / view["window_s"]
    return 100.0 * rate * per_token / (
        view["chips"] * view["peaks"]["bf16_flops_per_s"])
