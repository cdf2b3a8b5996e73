"""Model step (train): device time per profiled step of the operations
traced under a ``block<i>`` scope, forward and backward; the flash kernels
are their own rows."""
import scopes


def read(view):
    return scopes.ms_per_step(view, "blocks")
