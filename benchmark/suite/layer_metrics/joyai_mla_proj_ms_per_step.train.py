"""Model step (train): device time per profiled step under ``block<i>/mla``
OUTSIDE the kernels, the trunk's layers and the prediction block's
(``mtp0/block<L>/mla``): the query's two matrices and its norm, the latent
and expanding projections, the output projection (with their Adam), the
rotary positions and the copies around the launches."""
import joyai


def read(view):
    return joyai.proj_ms(view)
