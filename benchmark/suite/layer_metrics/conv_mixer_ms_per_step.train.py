"""Model step (train): device time per profiled step under ``block<i>/conv``:
the gated short convolutions' two projections (with their matrices' Adam
fused in) and their gates, forward and backward."""
import lfm2


def read(view):
    return lfm2.conv_mixer_ms(view)
