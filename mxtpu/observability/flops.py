"""MFU accounting: per-program FLOP estimates + a bounded step-time ring.

Two halves:

* **FLOPs per compiled program** — :func:`estimate_step_flops` asks XLA's own
  cost model first (``lowered.compile().cost_analysis()['flops']``) and, when
  that fails, logs why and walks the jaxpr counting ``dot_general``/
  ``conv_general_dilated`` MACs (``scan`` bodies × trip count). It returns
  the count WITH its source, and ``get_mfu_stats()["flops_source"]`` carries
  it. The estimate is cached per step-cache entry by the caller; it is never
  computed on the step hot path.
* **Step-time ring** — :func:`record_step` appends one wall-clock step sample
  into a bounded ring (default 4096; ``MXTPU_STEP_RING``), from which
  :func:`get_mfu_stats` derives ``steps_per_sec``, ``p50_step_ms``,
  ``p99_step_ms``, and ``mfu`` against the detected chip's documented peak.
  ``Module.fit`` records every batch and logs the epoch roll-up;
  ``DataParallelTrainer.step`` records every step WITH its parts (a row of
  :data:`STEP_ROW`), which :func:`get_step_timeline` hands out: a slow step
  is then told by the side it waited on, the host's issuing or the device's
  answer; ``Speedometer`` prints the rolling p50/p99;
  ``profiler.get_mfu_stats()`` hands out the same roll-up.

Peak FLOP/s: the documented bf16 peak of the detected TPU generation
(public spec sheets — fp32 convs execute as bf16 MXU passes, so bf16 is the
denominator for both precisions). A device that is not a listed TPU (the CPU
among them) has no documented peak: ``device_peak()`` gives ``None`` for it and
``mfu`` is ``None``, while the step times are reported as on any device.
"""

from __future__ import annotations

import logging
import math
import os
import threading
from collections import deque
from typing import Optional, Tuple

from . import histogram

_log = logging.getLogger("mxtpu.observability")

__all__ = ["device_peak", "estimate_step_flops", "jaxpr_flops",
           "record_step", "set_step_flops", "get_step_flops",
           "get_mfu_stats", "get_step_timeline", "reset_steps", "step_count",
           "PEAK_TFLOPS", "STEP_ROW"]

# documented bf16 peak TFLOP/s per chip, keyed by the exact
# ``jax.devices()[0].device_kind`` string. Both spellings of each generation
# are the ones jax._src.pallas.mosaic.tpu_info accepts; the figures are
# Google Cloud's per-chip numbers ("TPU v4" / "TPU v5e" / "TPU v5p" /
# "TPU v6e" system-architecture pages).
PEAK_TFLOPS = {
    "TPU v5 lite": 197.0,   # v5e; what the chip reports (chip_smoke, PR 21)
    "TPU v5e": 197.0,
    "TPU v5": 459.0,        # v5p
    "TPU v5p": 459.0,
    "TPU v4": 275.0,
    "TPU v6 lite": 918.0,   # v6e (Trillium)
    "TPU v6e": 918.0,
}


def device_peak() -> Tuple[str, Optional[float]]:
    """``(device_kind, peak_tflops_or_None)`` for device 0. A TPU's kind is
    looked up EXACTLY in :data:`PEAK_TFLOPS` and a TPU that is not listed
    raises ``KeyError`` — a near match would put another chip's peak under
    every MFU this process reports. Any other platform, the CPU among them,
    returns ``None`` (MFU undefined)."""
    import jax
    dev = jax.devices()[0]
    kind = dev.device_kind
    if dev.platform == "tpu":
        if kind not in PEAK_TFLOPS:
            raise KeyError(
                f"TPU device_kind {kind!r} has no entry in "
                f"mxtpu.observability.flops.PEAK_TFLOPS "
                f"({sorted(PEAK_TFLOPS)}); add its documented bf16 peak "
                f"with the source")
        return kind, PEAK_TFLOPS[kind]
    return kind, None


# ---------------------------------------------------------------------------
# FLOP estimation
# ---------------------------------------------------------------------------


def _prod(xs) -> int:
    out = 1
    for x in xs:
        out *= int(x)
    return out


def _dot_general_flops(eqn) -> float:
    ((lc, rc), (lb, rb)) = eqn.params["dimension_numbers"]
    lhs = eqn.invars[0].aval.shape
    rhs = eqn.invars[1].aval.shape
    batch = _prod(lhs[i] for i in lb)
    k = _prod(lhs[i] for i in lc)
    m = _prod(lhs[i] for i in range(len(lhs)) if i not in set(lb) | set(lc))
    n = _prod(rhs[i] for i in range(len(rhs)) if i not in set(rb) | set(rc))
    return 2.0 * batch * m * n * k


def _conv_flops(eqn) -> float:
    out = eqn.outvars[0].aval
    rhs = eqn.invars[1].aval
    dn = eqn.params["dimension_numbers"]
    out_features = rhs.shape[dn.rhs_spec[0]]
    # MACs per output element = kernel elements feeding it (in_ch/group ×
    # spatial window) = rhs_elems / out_features — feature groups cancel
    return 2.0 * _prod(out.shape) * (_prod(rhs.shape) / max(out_features, 1))


def jaxpr_flops(jaxpr) -> float:
    """Analytic matmul/conv FLOP count over a (Closed)Jaxpr: 2·MACs for every
    ``dot_general`` and ``conv_general_dilated``, recursing into sub-jaxprs
    (``pjit`` bodies, custom-derivative calls; ``scan`` bodies × trip count).
    Elementwise/reduction work is excluded — on matmul-dominated training
    steps it is noise, and XLA's own model is preferred when available."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    total = 0.0
    for eqn in inner.eqns:
        prim = eqn.primitive.name
        if prim == "dot_general":
            total += _dot_general_flops(eqn)
        elif prim == "conv_general_dilated":
            total += _conv_flops(eqn)
        else:
            mult = int(eqn.params.get("length", 1)) if prim == "scan" else 1
            for v in eqn.params.values():
                if hasattr(v, "eqns") or hasattr(v, "jaxpr"):
                    total += mult * jaxpr_flops(v)
    return total


def estimate_step_flops(jitted, avals) -> Tuple[Optional[float], Optional[str]]:
    """``(flops, source)`` of one execution of ``jitted(*avals)``.

    ``source`` says where the number came from, so an MFU never rests on a
    silently substituted count: ``"xla"`` is XLA's cost analysis of the
    AOT-lowered program (exact, fusion-aware; pays one extra lower+compile
    per unique signature, which is why callers cache the result per
    step-cache entry and compute it OFF the step path); ``"analytic"`` is
    the jaxpr walk, taken when ``MXTPU_FLOPS_MODE=analytic`` or when the
    cost model failed or reported nothing — the failure is logged, not
    swallowed. ``MXTPU_FLOPS_MODE=off`` gives ``(None, None)``."""
    mode = os.environ.get("MXTPU_FLOPS_MODE", "xla").lower()
    if mode in ("off", "0", "none"):
        return None, None
    if mode != "analytic":
        try:
            ca = jitted.lower(*avals).compile().cost_analysis()
            flops = float((ca or {}).get("flops", 0.0))
            if flops > 0:
                return flops, "xla"
            _log.warning("XLA cost analysis reported no flops; counting "
                         "matmuls/convs from the jaxpr instead")
        except Exception:
            _log.warning("XLA cost analysis failed; counting matmuls/convs "
                         "from the jaxpr instead", exc_info=True)
    import jax
    return jaxpr_flops(jax.make_jaxpr(jitted)(*avals)), "analytic"


# ---------------------------------------------------------------------------
# step-time ring
# ---------------------------------------------------------------------------

_ring_lock = threading.Lock()


def _ring_cap() -> int:
    try:
        return max(64, int(os.environ.get("MXTPU_STEP_RING", "4096")))
    except ValueError:
        return 4096


_ring: "deque" = deque(maxlen=_ring_cap())
_state = {"flops_per_step": None, "flops_source": None, "total_steps": 0}

# a step's row, as ``DataParallelTrainer.step`` records it: the step's
# number, its start on the tracer's clock (``time.perf_counter_ns``), the
# seconds of its ``train/place``, ``prepare``, ``dispatch`` (``compile``
# where the call traced: ``traced``), ``adopt`` and ``readback``
# (``first_readback``) spans and of the whole ``train/step``, and the calling
# thread's involuntary context switches since the trainer's last step
STEP_ROW = ("step", "start_ns", "place_s", "prepare_s", "dispatch_s",
            "adopt_s", "readback_s", "step_s", "traced", "nivcsw")


def record_step(seconds: float, flops: Optional[float] = None,
                row: Optional[dict] = None):
    """One training step's wall time (and, optionally, its FLOP count — when
    omitted the last :func:`set_step_flops` value applies at read time —
    and its ``row`` of :data:`STEP_ROW`, which the ring keeps as it is).
    Also lands in the bounded ``step/fused_step_ms`` log-bucket histogram
    (``observability.histogram``) so fused-step tails survive past the
    ring's window and export alongside the serving latency series."""
    with _ring_lock:
        _ring.append((float(seconds), flops, row))
        _state["total_steps"] += 1
    histogram.record_value("step/fused_step_ms", float(seconds) * 1e3)


def get_step_timeline() -> list:
    """The rows (:data:`STEP_ROW`) of the steps the ring still holds, oldest
    first: one a ``DataParallelTrainer.step``; a step recorded without a row
    (``Module.fit``'s) is not among them."""
    with _ring_lock:
        return [dict(row) for _, _, row in _ring if row is not None]


def set_step_flops(flops: Optional[float], source: Optional[str] = None):
    """Register the FLOPs of the CURRENT compiled step program and where the
    count came from (``estimate_step_flops``'s source; called by the fit
    loop once per traced signature, off the hot path)."""
    with _ring_lock:
        _state["flops_per_step"] = flops
        _state["flops_source"] = source


def get_step_flops() -> Optional[float]:
    with _ring_lock:
        return _state["flops_per_step"]


def step_count() -> int:
    with _ring_lock:
        return _state["total_steps"]


def reset_steps():
    """Clear the ring + the fused-step histogram (epoch boundaries, tests)."""
    with _ring_lock:
        _ring.clear()
        _state["total_steps"] = 0
    histogram.reset_histograms(prefix="step/")


def _percentile(sorted_vals, q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = (len(sorted_vals) - 1) * q
    lo = math.floor(idx)
    hi = math.ceil(idx)
    if lo == hi:
        return sorted_vals[lo]
    frac = idx - lo
    return sorted_vals[lo] * (1 - frac) + sorted_vals[hi] * frac


def get_mfu_stats(flops_per_step: Optional[float] = None) -> dict:
    """Roll up the step-time ring: ``steps``, ``steps_per_sec``,
    ``p50_step_ms``/``p99_step_ms``, ``flops_per_step``, and ``mfu`` against
    the detected chip peak (None when FLOPs or peak are unknown)."""
    with _ring_lock:
        samples = list(_ring)
        default_flops = _state["flops_per_step"]
        source = _state["flops_source"]
    if flops_per_step is None:
        flops_per_step = default_flops
    else:
        source = "caller"
    times = sorted(s[0] for s in samples)
    n = len(times)
    wall = sum(times)
    out = {"steps": n,
           "steps_per_sec": round(n / wall, 3) if wall > 0 else 0.0,
           "p50_step_ms": round(_percentile(times, 0.50) * 1e3, 3),
           "p99_step_ms": round(_percentile(times, 0.99) * 1e3, 3),
           "flops_per_step": flops_per_step, "flops_source": source,
           "mfu": None, "device_kind": None, "peak_tflops": None}
    kind, peak = device_peak()
    out["device_kind"], out["peak_tflops"] = kind, peak
    if n and wall > 0 and flops_per_step and peak:
        out["mfu"] = round((n * flops_per_step / wall) / (peak * 1e12), 6)
    return out
