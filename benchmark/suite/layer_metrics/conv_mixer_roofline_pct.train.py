"""Model step (train): the conv layers' mixers (``block<i>/conv``: the two
projections and the gate between them, XLA fusions, no kernel) against
their roofline, forward and backward: the larger of the projections'
operations over the bf16 peak and the bytes no schedule avoids over the HBM
peak (``roofline_lfm2.conv_flops/bytes``). The gate alone has no share of
its own: XLA fuses its products across the ``gate`` scope into the
projections' matmuls, so time under that scope is not the gate's."""
import lfm2


def read(view):
    return lfm2.mixer_roofline_pct(view)
