"""Kernels: the least time the chip could take for one step's causal flash
attention, forward and backward over every layer (the larger of operations
over the bf16 peak and bytes over the HBM peak, ``roofline.py``), over the
kernels' device time per step. Compute bounds it at these shapes (head dim
64..128, T 1024..2048: hundreds of operations per byte)."""
import roofline
import xplane

# every Mosaic call of a train step is a flash kernel; the trace has no
# kernel names yet (PERF.md, for the tracing issue)
FLASH = "^" + xplane.MOSAIC_PREFIX


def read(view):
    if "profiled_steps" not in view:
        return None
    measured = xplane.kernel_seconds(view["trace"], FLASH) \
        / view["profiled_steps"]
    if not measured:
        return None
    cfg = view["config"]
    rows = view["batch"] // view["chips"]          # one device's share
    heads, dim = cfg["n_head"], cfg["n_embd"] // cfg["n_head"]
    fl = roofline.flash_flops(rows, heads, view["seq_len"], dim)
    by = roofline.flash_bytes(rows, heads, view["seq_len"], dim, 2)
    least = sum(roofline.roofline_seconds(fl[k], by[k], view["peaks"])[0]
                for k in ("fwd", "bwd")) * cfg["n_layer"]
    return 100.0 * least / measured
