"""Model step (train): device time per profiled step under
``block<i>/kda`` OUTSIDE the kernels: the four-wide input projection and the
output projection (with their matrices' Adam fused in), the short
convolutions and SiLU, the float32 gates, the L2 norms, the cumulative sums
around the launches, the gated head norm, forward and backward."""
import ling


def read(view):
    return ling.scope_ms(view, "kda")
