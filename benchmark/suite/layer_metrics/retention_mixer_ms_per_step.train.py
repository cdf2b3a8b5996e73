"""Model step (train): device time per profiled step under
``block<i>/retention`` OUTSIDE the kernels: the fused q/k/v and output
projections (with their matrices' Adam fused in), q/k norm, rotary
positions, the decay gate and the copies around the launches, forward,
recomputed forward and backward."""
import brumby


def read(view):
    return brumby.mixer_ms(view)
