"""What the names this program gives its work let a reader see: device time
by the scope an operation was traced in, and the program's own totals by
span name.

Where the scope sits in a TPU v5e trace (looked at by hand, PR 24): not in an
``XLA Ops`` event's name (the HLO line) and not in the event's own stats
(``device_offset_ps``, ``device_duration_ps``), but in the stat ``tf_op`` of
the event's METADATA, as ``jit(step)/transpose(jvp(TransformerLM))/block1/
attn/k_proj/dot_general:`` (several joined by ``;`` where XLA merged
instructions). ``jax.profiler.ProfileData`` does not hand out metadata stats,
so the file is read once more here, with ``google.protobuf`` and the few
fields of the xplane schema that are needed; copies, the dynamic-update-slices
XLA makes of a concatenate and other instructions of the compiler's own carry
no ``tf_op`` and count as unattributed. A fusion carries one ``tf_op``: its
time goes to the scope of its root.

``run.py`` hands a reader no path: the trace is the newest ``bench-trace-*``
directory under the temporary directory (``run.py`` makes one per traced run
and removes it after the readers ran), unless ``view["trace_dir"]`` says
otherwise. This module and ``system.py`` are the two places of the benchmark
that import the program (``span_totals``).
"""

from __future__ import annotations

import functools
import glob
import os
import re
import tempfile

import xplane

# layer -> the scope names (one component of the path) that belong to it;
# the outermost component that matches decides
LAYERS = (
    ("blocks", re.compile(r"block\d+")),
    ("head_loss", re.compile(r"ln_f|head|loss")),
    ("optimizer", re.compile(r"optimizer")),
    ("embed", re.compile(r"embed|embedding")),
)
WRAPPERS = re.compile(r"\w+\(|\)")      # jit(..), jvp(..), transpose(..)
KERNELS, UNATTRIBUTED = "kernels", "unattributed"
ISSUE_SPANS = ("train/place", "train/prepare", "train/dispatch",
               "train/adopt")
# the flash kernels by the HLO instruction name their ``pallas_call`` name
# gives them: ``tpu_custom_call/flash_fwd.2``, ``.../flash_bwd_dq.7``
FLASH = {"fwd": "^" + xplane.MOSAIC_PREFIX + "flash_fwd",
         "bwd": "^" + xplane.MOSAIC_PREFIX + "flash_bwd_"}


def span_totals() -> dict:
    """The program's totals by span name (``profiler.get_span_totals()``),
    ``{}`` from a program that keeps none."""
    from mxtpu import profiler
    get = getattr(profiler, "get_span_totals", None)
    return get() if get else {}


def layer_of(op_name: str):
    """``"blocks"`` for ``jit(step)/transpose(jvp(TransformerLM))/block1/
    attn/...``; ``None`` where no component of the path is a known scope."""
    path = WRAPPERS.sub("", op_name.split(";")[0].rstrip(":")).split("/")
    for part in path[:-1]:              # the last component is the operation
        for layer, rx in LAYERS:
            if rx.fullmatch(part):
                return layer
    return None


def _schema():
    """The part of the xplane schema that is read, as protobuf classes.
    Maps are declared as the repeated key/value entries they are on the
    wire; fields that are not declared are skipped by the parser."""
    from google.protobuf import descriptor_pb2, descriptor_pool, \
        message_factory
    T = descriptor_pb2.FieldDescriptorProto
    f = descriptor_pb2.FileDescriptorProto(
        name="suite_xplane.proto", package="suite_xplane", syntax="proto3")

    def message(name, *fields):
        m = f.message_type.add(name=name)
        for fname, number, ftype, repeated in fields:
            fd = m.field.add(
                name=fname, number=number,
                label=T.LABEL_REPEATED if repeated else T.LABEL_OPTIONAL,
                type=T.TYPE_MESSAGE if isinstance(ftype, str) else ftype)
            if isinstance(ftype, str):
                fd.type_name = ".suite_xplane." + ftype

    message("XStat", ("metadata_id", 1, T.TYPE_INT64, False),
            ("str_value", 5, T.TYPE_STRING, False),
            ("ref_value", 7, T.TYPE_UINT64, False))
    message("XEventMetadata", ("name", 2, T.TYPE_STRING, False),
            ("stats", 5, "XStat", True))
    message("XStatMetadata", ("name", 2, T.TYPE_STRING, False))
    message("EventEntry", ("key", 1, T.TYPE_INT64, False),
            ("value", 2, "XEventMetadata", False))
    message("StatEntry", ("key", 1, T.TYPE_INT64, False),
            ("value", 2, "XStatMetadata", False))
    message("XEvent", ("metadata_id", 1, T.TYPE_INT64, False),
            ("offset_ps", 2, T.TYPE_INT64, False),
            ("duration_ps", 3, T.TYPE_INT64, False))
    message("XLine", ("name", 2, T.TYPE_STRING, False),
            ("timestamp_ns", 3, T.TYPE_INT64, False),
            ("events", 4, "XEvent", True))
    message("XPlane", ("name", 2, T.TYPE_STRING, False),
            ("lines", 3, "XLine", True),
            ("event_metadata", 4, "EventEntry", True),
            ("stat_metadata", 5, "StatEntry", True))
    message("XSpace", ("planes", 1, "XPlane", True))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("suite_xplane.XSpace"))


def read_ops(path: str) -> dict:
    """``{device number: [(HLO line, op_name, start_ns, end_ns)]}`` of every
    ``XLA Ops`` event, ``op_name`` being ``""`` where the instruction has
    none."""
    space = _schema()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out = {}
    for plane in space.planes:
        m = xplane.DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {}
        for e in plane.event_metadata:
            op_name = ""
            for st in e.value.stats:
                if stat_names.get(st.metadata_id) == "tf_op":
                    op_name = st.str_value or stat_names.get(st.ref_value, "")
            meta[e.key] = (e.value.name, op_name)
        for line in plane.lines:
            if line.name != xplane.OPS_LINE:
                continue
            t0 = line.timestamp_ns * 1000
            out[int(m.group(1))] = [
                meta.get(ev.metadata_id, ("", ""))
                + ((t0 + ev.offset_ps) / 1e3,
                   (t0 + ev.offset_ps + ev.duration_ps) / 1e3)
                for ev in line.events]
    return out


def _trace_file(view: dict):
    trace_dir = view.get("trace_dir")
    if trace_dir is None:
        found = glob.glob(os.path.join(tempfile.gettempdir(),
                                       "bench-trace-*"))
        if not found:
            return None
        trace_dir = max(found, key=os.path.getmtime)
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(files) if files else None


def step_scopes(view: dict):
    """Device seconds inside the ``bench/`` window by layer, per device used:
    ``{"blocks", "head_loss", "optimizer", "embed", "kernels",
    "unattributed", "total", "busy" (device 0's merged intervals, ns)}``, the
    loops' own events left out as ``xplane.reduce_planes`` leaves them out.
    ``None`` without a trace, without ``google.protobuf``, or where no
    operation of the window carries a scope (a program that names none)."""
    reduced = view.get("trace")
    if not reduced or "profiled_steps" not in view:
        return None
    path = _trace_file(view)
    window = [iv for n, ivs in reduced["annotations"].items()
              if n.startswith(xplane.BENCH_SPAN) for iv in ivs]
    if path is None or not window:
        return None
    try:
        return _scopes_in(path, min(a for a, _ in window) * 1e9,
                          max(b for _, b in window) * 1e9,
                          view.get("chips", 1))
    except ImportError:
        return None


@functools.lru_cache(maxsize=1)       # one trace a run, several readers
def _scopes_in(path: str, w0: float, w1: float, chips: int):
    devices = read_ops(path)
    used = [devices[n] for n in sorted(devices)[:chips] if devices[n]]
    if not used:
        return None
    out = dict.fromkeys([layer for layer, _ in LAYERS]
                        + [KERNELS, UNATTRIBUTED], 0.0)
    scoped = False
    for ops in used:
        for hlo, op_name, s, e in ops:
            if e <= w0 or s >= w1:
                continue
            short = xplane.short_name(hlo)
            if xplane.base_name(short) in xplane.CONTAINERS:
                continue
            if short.startswith(xplane.MOSAIC_PREFIX):
                layer = KERNELS
            else:
                layer = layer_of(op_name) or UNATTRIBUTED
                scoped = scoped or layer != UNATTRIBUTED
            out[layer] += (min(e, w1) - max(s, w0)) / 1e9 / len(used)
    if not scoped:
        return None
    out["total"] = sum(out.values())
    out["busy"] = xplane.merge([(max(s, w0), min(e, w1))
                                for _, _, s, e in used[0]
                                if e > w0 and s < w1])
    return out


def ms_per_step(view: dict, layer: str):
    """One layer's device time per profiled step, ms."""
    scopes = step_scopes(view)
    if scopes is None:
        return None
    return scopes[layer] / view["profiled_steps"] * 1e3


def issue_spans(view: dict):
    """The trainer's four issuing spans inside the window, as sorted
    ``(start_ns, end_ns)``; ``None`` where the trace holds none of them."""
    reduced = view.get("trace")
    if not reduced or "profiled_steps" not in view:
        return None
    spans = [(a * 1e9, b * 1e9) for name in ISSUE_SPANS
             for a, b in reduced["annotations"].get(name, [])]
    return sorted(spans) or None


def flash_seconds_per_step(view: dict, which: str):
    """Device seconds per profiled step of the forward (``"fwd"``) or the
    backward (``"bwd"``: dq + dk/dv, or fused) flash kernels; ``None`` where
    the trace names no such kernel."""
    if "profiled_steps" not in view or "trace" not in view:
        return None
    seconds = xplane.kernel_seconds(view["trace"], FLASH[which])
    return seconds / view["profiled_steps"] if seconds else None


def flash_roofline_pct(view: dict, which: str):
    """The least time the chip could take for one step's causal flash
    attention in that direction over every layer (``roofline.py``: the
    larger of operations over the bf16 peak and bytes over the HBM peak),
    over the kernels' device time per step."""
    import roofline
    measured = flash_seconds_per_step(view, which)
    if measured is None:
        return None
    cfg = view["config"]
    rows = view["batch"] // view["chips"]          # one device's share
    heads, dim = cfg["n_head"], cfg["n_embd"] // cfg["n_head"]
    flops = roofline.flash_flops(rows, heads, view["seq_len"], dim)[which]
    nbytes = roofline.flash_bytes(rows, heads, view["seq_len"], dim, 2)[which]
    least = roofline.roofline_seconds(flops, nbytes, view["peaks"])[0]
    return 100.0 * least * cfg["n_layer"] / measured


def compile_seconds(view: dict, phases: tuple):
    """Seconds of JAX's compile ``phases`` (``jax/trace``, ...) spent under
    the trainer's ``train/compile`` span; ``None`` for another job kind or a
    program that reports none."""
    if "profiled_steps" not in view:
        return None
    totals = span_totals()
    rows = [totals[p]["by_parent"].get("train/compile")
            for p in phases if p in totals]
    rows = [r for r in rows if r is not None]
    return sum(rows) if rows else None
