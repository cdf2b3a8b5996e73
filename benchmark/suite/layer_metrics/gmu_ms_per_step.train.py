"""Model step (train): device time per profiled step under
``block<i>/gmu``: the gated memory unit's two projections and its gate."""
import hybrid


def read(view):
    return hybrid.kind_ms(view, "gmu")
