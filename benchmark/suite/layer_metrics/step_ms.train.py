"""Model step (train): median host-clock time of one ``dpt.step`` call in
the window, loss readback included."""
import stats


def read(view):
    if "step_s" not in view:
        return None
    return stats.median(view["step_s"]) * 1e3
