"""Kernels (the decode program; XLA attention today): the least time one
decode step could take, reading every weight and the live requests' keys and
values once at the HBM peak (memory bounds it: one token per slot against
gigabytes), over the device time of one decode step in the trace. Live
positions are counted from the benchmark's own records at the middle of the
profiled span; slots that are empty, or reserved past a request's length,
count as nothing."""
import roofline
import stats
import xplane

ITEMSIZE = {"bfloat16": 2, "float32": 4, "float16": 2, "int8": 1}


def read(view):
    if "trace" not in view or "profiled" not in view:
        return None
    turns = xplane.modules_inside(view["trace"], "serving/decode")
    at, span = view["profiled"]
    positions = roofline.live_positions(view["records"], at + span / 2)
    if not turns or not positions:
        return None
    chunk = view["engine_args"].get("chunk", 8)      # the engine's default
    step_s = stats.median(turns) / chunk
    kv = ITEMSIZE[view["engine_args"].get("kv_dtype", "float32")]
    least = roofline.decode_step_bytes(
        view["config"], ITEMSIZE[view["dtype"]], positions, kv) \
        / view["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / step_s
