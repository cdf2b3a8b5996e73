"""Kernels: device time of the Pallas flash-attention kernels (forward, dq,
dk/dv) per profiled step, per device."""
import xplane

# every Mosaic call of a train step is a flash kernel; the trace has no
# kernel names yet (PERF.md, for the tracing issue)
FLASH = "^" + xplane.MOSAIC_PREFIX


def read(view):
    if "profiled_steps" not in view:
        return None
    seconds = xplane.kernel_seconds(view["trace"], FLASH)
    if not seconds:
        return None
    return seconds / view["profiled_steps"] * 1e3
