"""Model step (train): device time per profiled step under
``block<i>/moe/route``, ``dispatch``, ``combine`` and ``balance``: router
logits, sigmoid and top-k, the sort of the (token, expert) pairs, the gather
of their rows, the weighted add back onto the tokens, and the selection
bias's update with the step's counts, forward and backward."""
import moe


def read(view):
    return moe.scope_ms(view, "route", "dispatch", "combine", "balance")
