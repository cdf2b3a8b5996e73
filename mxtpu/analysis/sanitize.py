"""Runtime sanitizers — the ASan/TSan-style twin of the tpulint rules.

Opt-in via ``MXTPU_SANITIZE=transfers,donation,retrace,threads`` (or
``all``), or programmatically via :func:`configure` / :func:`scope`.  Each
mode arms one hazard detector at the exact choke points the static rules
reason about, and every check/trip lands in
``profiler.get_sanitizer_stats()``:

* ``transfers`` — wraps the fused step's compiled-program execution in
  ``jax.transfer_guard("disallow")`` so an implicit host transfer per step
  fails loudly (R001's runtime twin), and re-names trace-time
  concretization errors (``.asnumpy()`` on a tracer) as
  :class:`HostSyncError`.
* ``donation`` — poisons the buffer references a ``donate_argnums`` step
  consumed; a later read through an ``NDArray`` handle raises
  :class:`DonationError` naming the donating step, instead of XLA's opaque
  "Array has been deleted" (and instead of silently working on CPU, where
  XLA skips donation — the PR 2 snapshot race was invisible on CPU for
  exactly that reason).
* ``retrace`` — escalates a compile-cache signature miss beyond
  ``MXTPU_SANITIZE_RETRACE_LIMIT`` (default 2: train + eval) into a
  :class:`RetraceError` carrying a structural signature diff — which
  shape/dtype/sharding/hyperparameter changed.
* ``threads`` — asserts ownership transitions: a DeviceFeed batch delivered
  to the consumer is never re-enqueued, checkpoint snapshots are
  host-landed before the next (donating) step can run, and checkpoint
  writes happen on the owning writer thread.
"""

from __future__ import annotations

import os
import threading
import weakref
from contextlib import contextmanager
from typing import Dict, Iterable, Optional, Sequence, Tuple

import numpy as np

__all__ = ["SanitizerError", "HostSyncError", "DonationError", "RetraceError",
           "ThreadOwnershipError", "configure", "active", "enabled", "scope",
           "MODES", "poison", "clear_poison", "step_guard",
           "escalate_retrace", "sig_diff", "assert_fresh_delivery",
           "assert_host_landed", "assert_owner_thread"]

MODES = ("transfers", "donation", "retrace", "threads")

_EMPTY = frozenset()
_active: Optional[frozenset] = None
_retrace_limit = 2
_lock = threading.Lock()


# ---------------------------------------------------------------------------
# named errors (each carries the lint rule it is the runtime twin of)
# ---------------------------------------------------------------------------


class SanitizerError(RuntimeError):
    """Base of all sanitizer trips; ``mode`` and ``rule`` name the detector."""

    mode = "sanitize"
    rule = "R000"

    def __init__(self, msg: str):
        super().__init__(f"mxtpu sanitizer [{self.mode}/{self.rule}]: {msg}")


class HostSyncError(SanitizerError):
    mode = "transfers"
    rule = "R001"


class DonationError(SanitizerError):
    mode = "donation"
    rule = "R002"


class RetraceError(SanitizerError):
    mode = "retrace"
    rule = "retrace"


class ThreadOwnershipError(SanitizerError):
    mode = "threads"
    rule = "R004"


# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------


def configure(spec: Optional[str] = None,
              retrace_limit: Optional[int] = None) -> frozenset:
    """(Re)parse the sanitizer configuration.

    ``spec`` overrides ``MXTPU_SANITIZE`` (comma list of modes, or ``all``);
    ``retrace_limit`` overrides ``MXTPU_SANITIZE_RETRACE_LIMIT`` (max
    distinct signatures one step cache may compile before escalation).
    Unknown modes raise ValueError — a typo must not silently disarm a
    sanitizer run.
    """
    global _active, _retrace_limit
    raw = os.environ.get("MXTPU_SANITIZE", "") if spec is None else spec
    modes = set()
    for tok in str(raw).replace(";", ",").split(","):
        tok = tok.strip().lower()
        if not tok:
            continue
        if tok in ("all", "1", "on", "true"):
            modes.update(MODES)
        elif tok in MODES:
            modes.add(tok)
        else:
            raise ValueError(
                f"MXTPU_SANITIZE: unknown mode {tok!r} (choose from "
                f"{', '.join(MODES)} or 'all')")
    with _lock:
        _active = frozenset(modes)
        if retrace_limit is not None:
            _retrace_limit = max(1, int(retrace_limit))
        else:
            try:
                _retrace_limit = max(1, int(os.environ.get(
                    "MXTPU_SANITIZE_RETRACE_LIMIT", "2")))
            except ValueError:
                _retrace_limit = 2
    _install_hooks()
    return _active


def active() -> frozenset:
    """The armed mode set (lazily parsed from ``MXTPU_SANITIZE`` on first
    use; cheap enough for per-step calls)."""
    if _active is None:
        return configure()
    return _active


def enabled(mode: str) -> bool:
    return mode in active()


def retrace_limit() -> int:
    if _active is None:
        configure()
    return _retrace_limit


@contextmanager
def scope(spec: str, retrace_limit: Optional[int] = None):
    """Temporarily arm a mode set (``tests/test_analysis.py`` and others);
    restores the previous configuration and clears poisons on exit."""
    prev_active, prev_limit = _active, _retrace_limit
    configure(spec, retrace_limit=retrace_limit)
    try:
        yield active()
    finally:
        clear_poison()
        with _lock:
            globals()["_active"] = prev_active
            globals()["_retrace_limit"] = prev_limit
        _install_hooks()


def _install_hooks():
    """Arm/disarm the NDArray read hook (donation poisons)."""
    try:
        from ..ndarray import ndarray as nd_mod
    except ImportError:     # package still importing: step() installs later
        return
    on = _active is not None and "donation" in _active
    nd_mod._sanitize_data_hook = _check_poison if on else None


def _record(key: str, n: int = 1):
    from .. import profiler
    profiler.record_sanitizer(key, n)


# ---------------------------------------------------------------------------
# donation poisoning (R002 runtime twin)
# ---------------------------------------------------------------------------

# id(array) -> (weakref, origin). A weakref (not the array) so poisoning
# never extends buffer lifetime; the finalizer retires the entry, and the
# identity re-check on read makes id reuse harmless.
_poisoned: Dict[int, Tuple[weakref.ref, str]] = {}


def poison(arrays: Iterable, origin: str):
    """Mark buffers a donating program consumed: any later read through an
    NDArray handle raises :class:`DonationError`.  On CPU (where XLA skips
    donation and the stale read would silently 'work') this makes the
    accelerator ownership contract enforceable in CI."""
    armed = 0
    for a in arrays:
        if a is None or not hasattr(a, "dtype"):
            continue
        key = id(a)
        try:
            r = weakref.ref(a, lambda _ref, _key=key: _poisoned.pop(_key, None))
        except TypeError:
            continue
        _poisoned[key] = (r, origin)
        armed += 1
    if armed:
        _record("donation_poisons_armed", armed)


def clear_poison():
    _poisoned.clear()


def _check_poison(raw):
    """NDArray read hook (installed as ``ndarray._sanitize_data_hook``)."""
    ent = _poisoned.get(id(raw))
    if ent is not None and ent[0]() is raw:
        _record("donation_trips")
        raise DonationError(
            f"read of a buffer that was donated to {ent[1]} — on "
            f"accelerators this array is already deleted (XLA would raise "
            f"an opaque 'Array has been deleted'); copy the value before "
            f"the donating step, or read the step's returned arrays")


# ---------------------------------------------------------------------------
# transfer guard (R001 runtime twin)
# ---------------------------------------------------------------------------


def _is_transfer_error(e: BaseException) -> bool:
    s = str(e)
    return "isallowed" in s and "transfer" in s


@contextmanager
def step_guard(san: frozenset, traced_now: bool, where: str = "fused step"):
    """Guard one compiled-step execution.

    On a cache-hit execution, ``jax.transfer_guard("disallow")`` turns any
    implicit host transfer into :class:`HostSyncError`.  On the trace call
    the guard stays off (tracing legitimately ships constants to the
    device); instead, trace-time concretizations (``.asnumpy()`` / ``float``
    on a tracer — the lint rule R001 shapes) are re-raised as
    :class:`HostSyncError` so CI names the bug instead of printing a
    300-line tracer error.
    """
    if "transfers" not in san:
        yield
        return
    import jax
    if traced_now:
        try:
            yield
        except Exception as e:
            if e.__class__.__name__ in ("TracerArrayConversionError",
                                        "ConcretizationTypeError",
                                        "TracerBoolConversionError"):
                _record("transfer_trips")
                raise HostSyncError(
                    f"host sync inside the traced {where}: {e}") from e
            raise
    else:
        _record("transfer_guards")
        try:
            with jax.transfer_guard("disallow"):
                yield
        except Exception as e:
            if _is_transfer_error(e):
                _record("transfer_trips")
                raise HostSyncError(
                    f"implicit host transfer while executing the compiled "
                    f"{where}: {e}") from e
            raise


# ---------------------------------------------------------------------------
# retrace escalation (+ signature diffing)
# ---------------------------------------------------------------------------


def sig_diff(old, new, labels: Optional[Sequence[str]] = None,
             max_entries: int = 8) -> str:
    """Structural diff of two cache signatures → "which key changed".

    Tuples/lists are descended elementwise (``labels`` names the top-level
    components); a 3-tuple ``(shape, dtype, sharding)`` — the framework's
    array signature — gets field names.  Output like
    ``params[0].dtype: 'float32' -> 'float16'``.
    """
    out = []

    def walk(path, a, b):
        if len(out) >= max_entries:
            return
        if type(a) is type(b) and isinstance(a, (tuple, list)):
            if len(a) != len(b):
                out.append(f"{path or 'sig'}: arity {len(a)} -> {len(b)}")
                return
            arr_sig = (len(a) == 3 and isinstance(a[0], tuple)
                       and isinstance(a[1], str))
            for i, (x, y) in enumerate(zip(a, b)):
                if arr_sig:
                    field = ("shape", "dtype", "sharding")[i]
                    walk(f"{path}.{field}" if path else field, x, y)
                elif labels is not None and not path and i < len(labels):
                    walk(labels[i], x, y)
                else:
                    walk(f"{path}[{i}]" if path else f"[{i}]", x, y)
        elif a != b:
            out.append(f"{path or 'sig'}: {a!r} -> {b!r}")

    walk("", old, new)
    return "; ".join(out) if out else "signatures differ structurally"


def escalate_retrace(cache_name: str, n_cached: int, old_sig, new_sig,
                     labels: Optional[Sequence[str]] = None):
    """Raise when a step cache is about to compile one signature too many.

    ``n_cached`` is how many signatures the cache already holds; the limit
    (default 2 — a train + eval pair, the compile-guard contract) comes from
    :func:`configure`.  The error carries the structural diff against the
    most recently used signature: the changed shape/dtype/sharding/
    hyperparameter is named instead of leaving the reader to eyeball two
    500-element tuples.
    """
    if n_cached < retrace_limit():
        return
    _record("retrace_escalations")
    diff = sig_diff(old_sig, new_sig, labels=labels)
    raise RetraceError(
        f"cache '{cache_name}' would compile signature #{n_cached + 1} "
        f"(limit {retrace_limit()}; raise MXTPU_SANITIZE_RETRACE_LIMIT if "
        f"this loop legitimately multi-compiles) — changed vs last step: "
        f"{diff}")


# ---------------------------------------------------------------------------
# thread-ownership assertions (R004 runtime twin)
# ---------------------------------------------------------------------------

# id -> batch, weak so consumed batches don't accumulate; the identity
# re-check makes id reuse after GC harmless
_delivered: "weakref.WeakValueDictionary" = weakref.WeakValueDictionary()


def assert_fresh_delivery(batch, origin: str = "DeviceFeed"):
    """Producer-side: a batch handed to the consumer must never be enqueued
    again — the consumer may donate its buffers the moment it takes it."""
    _record("ownership_checks")
    prev = _delivered.get(id(batch))
    if prev is batch:
        _record("ownership_trips")
        raise ThreadOwnershipError(
            f"{origin}: batch re-enqueued after delivery — the consumer owns "
            f"it (and may have donated its buffers to a fused step)")
    try:
        _delivered[id(batch)] = batch
    except TypeError:
        pass            # not weakref-able: can't track, don't crash


def assert_host_landed(arrays: Dict[str, object], origin: str):
    """Checkpoint-side: every snapshot array must be host-resident before
    ``save()`` returns — the next step's donation deletes device buffers a
    reference-only snapshot would still point at (the PR 2 race)."""
    _record("ownership_checks")
    bad = [k for k, v in arrays.items() if not isinstance(v, np.ndarray)]
    if bad:
        _record("ownership_trips")
        raise ThreadOwnershipError(
            f"{origin}: snapshot entries {bad[:5]} are not host-landed "
            f"numpy arrays — a donating step can delete the device buffers "
            f"they reference before the writer serializes them")


def assert_owner_thread(owner: Optional[threading.Thread], origin: str):
    """Assert the current thread is the declared owner of a transition
    (e.g. checkpoint serialization happens on the writer thread only)."""
    _record("ownership_checks")
    if owner is not None and threading.current_thread() is not owner:
        _record("ownership_trips")
        raise ThreadOwnershipError(
            f"{origin}: ran on thread {threading.current_thread().name!r} "
            f"but is owned by {owner.name!r}")
