"""Model step (train): device time per profiled step of everything the
multi-token-prediction block adds to a step, forward and backward, kernels
included: the scopes ``mtp0/embed|proj|block<L>|head`` and the loss's
``mtp``."""
import joyai


def read(view):
    return joyai.scope_ms(view, "mtp", "mtp_head_loss")
