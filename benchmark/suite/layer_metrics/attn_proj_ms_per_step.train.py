"""Model step (train): device time per profiled step under
``block<i>/attn_window|attn_full|attn_cross`` outside the flash kernels: the
q/k/v and output projections, the head transposes, lambda and the RMSNorm of
the difference."""
import hybrid


def read(view):
    return hybrid.kind_ms(view, "attn_window", "attn_full", "attn_cross")
