"""Model step (train): device time per profiled step under
``block<i>/mlp``: the SwiGLU MLP of every layer, forward and backward (and,
fused into the weight-gradient matmuls, its matrices' Adam)."""
import hybrid


def read(view):
    return hybrid.kind_ms(view, "mlp")
