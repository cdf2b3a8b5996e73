"""Model step (train): device time per profiled step under ``mtp0/head`` (the
block's last norm, the head's matrix a second time, float32 logits) and the
loss's ``mtp`` scope (the second cross entropy over rolled targets), forward
and backward."""
import joyai


def read(view):
    return joyai.scope_ms(view, "mtp_head_loss")
