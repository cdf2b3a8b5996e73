"""Model step (train), program counter: GB of the tied head's float32
logits (rows of the placed batch x the model's vocabulary x 4 bytes, as
``systems/jamba.py`` counted them), which the loss and its backward hold
whole."""
import jamba


def read(view):
    return jamba.logits_gb(view)
