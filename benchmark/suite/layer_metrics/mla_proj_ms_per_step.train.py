"""Model step (train): device time per profiled step under
``block<i>/mla`` OUTSIDE the kernels: the query, latent and expanding
projections, the head gate and the output projection (with their Adam), the
three norms, the rotary positions and the copies around the launches."""
import ling


def read(view):
    return ling.scope_ms(view, "mla")
