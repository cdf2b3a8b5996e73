"""Model step (train), program counter: GB of the prediction block's float32
logits (``profiler.get_launch_stats("mtp")``: counted from their shape where
the step was traced), which exist beside the head's own."""
import joyai


def read(view):
    return joyai.logits_gb(view)
