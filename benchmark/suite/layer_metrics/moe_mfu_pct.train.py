"""Model step (train): tokens per second of the window times the operations
a token needs (``roofline_moe.train_flops_per_token``: 6 per matmul
parameter with the held experts by the (token, expert) pairs the program
counted in the profiled steps; attention by visible pairs on the heads
held; no recomputation) over chips times the bf16 peak."""
import moe
import roofline_moe


def read(view):
    if "tokens" not in view or "mlp_layer_types" not in view["config"]:
        return None
    per_token = roofline_moe.train_flops_per_token(
        view["config"], view["seq_len"], moe.held_per_token(view))
    rate = view["tokens"] / view["window_s"]
    return 100.0 * rate * per_token / (
        view["chips"] * view["peaks"]["bf16_flops_per_s"])
