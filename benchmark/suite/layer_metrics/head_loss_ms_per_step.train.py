"""Model step (train): device time per profiled step under the scopes
``ln_f``, ``head`` and ``loss``, forward and backward: the final norm, the
tied vocabulary head and the cross entropy."""
import scopes


def read(view):
    return scopes.ms_per_step(view, "head_loss")
