"""Model step (train), program counter: how far the (token, expert) pairs
this chip's held experts got in the profiled steps, every expert layer, lie
from their even share ``tokens * k * held / E``: ``|pairs / share - 1|``.
The work of the expert layers follows the pairs, and so does a step's time,
so fewer pairs than the share read as a faster step: this number says that
the step was not the one the cell describes."""
import moe


def read(view):
    return moe.held_load_gap(view)
