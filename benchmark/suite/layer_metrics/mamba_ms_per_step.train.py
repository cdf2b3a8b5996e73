"""Model step (train): device time per profiled step under
``block<i>/mamba``: projections, convolution, gate and what XLA does around
the scan; the scan kernels are rows of their own."""
import hybrid


def read(view):
    return hybrid.kind_ms(view, "mamba")
