"""Model step (train): device time per profiled step under
``block<i>/moe/shared``: the shared expert's SwiGLU over every token, forward
and backward (and, fused into the weight-gradient matmuls, its matrices'
Adam)."""
import moe


def read(view):
    return moe.scope_ms(view, "shared")
