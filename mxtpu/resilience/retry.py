"""``retry_transient`` — THE retry policy (one implementation, many callers).

ROADMAP item 4's motivating incident: a single transient backend
``UNAVAILABLE`` erased an entire bench round because nothing between the
raise and the harness knew the difference between "try again in a second"
and "your program is wrong". This module is that knowledge:

* :func:`classify_error` — transient (backend UNAVAILABLE / init races /
  fs hiccups / connection flakes) vs logic errors (TypeError & friends
  escalate immediately; retrying those only buries the traceback).
* :func:`retry_transient` — bounded exponential backoff with deterministic
  jitter around any callable. Adopted by ``dist.initialize`` and the
  checkpoint writer's shard-write/commit path.

Knobs: ``MXTPU_RETRY_MAX`` (retries after the first attempt, default 3),
``MXTPU_RETRY_BACKOFF_S`` (base delay, default 0.5, doubling per retry,
capped at ``MXTPU_RETRY_BACKOFF_MAX_S`` default 30). Jitter is a
deterministic per-process sequence so runs are reproducible.

Every retry lands in ``profiler.get_resilience_stats()`` (``retries`` /
``retries_exhausted`` / ``escalations``) and on the chrome-trace timeline as
a ``resilience/retry`` span covering the backoff sleep.
"""

from __future__ import annotations

import os
import random
import time
from typing import Callable, Optional, Tuple

from .faults import InjectedFault

__all__ = ["classify_error", "is_transient", "retry_transient", "RetryError"]

#: substrings marking a transient backend/transport/init failure (matched
#: case-insensitively against "ExcType: message")
TRANSIENT_MARKERS: Tuple[str, ...] = (
    "unavailable", "deadline exceeded", "deadline_exceeded",
    "resource exhausted", "resource_exhausted", "aborted",
    "temporarily", "connection reset", "connection refused",
    "broken pipe", "socket closed", "handshake",
    "unable to initialize", "failed to initialize",
    "stale file handle", "try again",
)

#: exception families that are never worth retrying — a second attempt runs
#: the same wrong code
_LOGIC_TYPES = (TypeError, ValueError, KeyError, IndexError, AttributeError,
                AssertionError, NotImplementedError, ArithmeticError,
                ImportError, NameError)

#: OS-level families that usually mean "the world hiccuped, not the program"
_TRANSIENT_TYPES = (ConnectionError, TimeoutError, BlockingIOError,
                    InterruptedError)


class RetryError(RuntimeError):
    """Wrapper raised when a *transient* error survives every allowed retry —
    callers distinguishing "gave up retrying" from "logic error" catch this;
    the original failure is ``__cause__``."""

    def __init__(self, label: str, attempts: int, last: BaseException):
        self.label = label
        self.attempts = attempts
        super().__init__(
            f"{label}: transient error persisted through {attempts} attempts: "
            f"{type(last).__name__}: {last}")


def classify_error(exc: BaseException) -> bool:
    """True when ``exc`` looks transient (worth retrying)."""
    if isinstance(exc, InjectedFault):
        return exc.transient
    if isinstance(exc, _LOGIC_TYPES):
        return False
    if isinstance(exc, _TRANSIENT_TYPES):
        return True
    text = f"{type(exc).__name__}: {exc}".lower()
    return any(marker in text for marker in TRANSIENT_MARKERS)


is_transient = classify_error  # alias, reads better at some call sites


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, str(default)))
    except ValueError:
        return default


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, str(default)))
    except ValueError:
        return default


# Deterministic jitter: a fixed-seed stream (overridable for tests) so two
# runs with the same fault plan sleep the same schedule.
_jitter_rng = random.Random(20260804)


def _backoff_s(attempt: int, base: float, cap: float) -> float:
    delay = min(base * (2.0 ** attempt), cap)
    return delay * (1.0 + 0.25 * _jitter_rng.random())


def retry_transient(fn: Callable, *args,
                    label: str = "op",
                    max_retries: Optional[int] = None,
                    base_backoff_s: Optional[float] = None,
                    max_backoff_s: Optional[float] = None,
                    classify: Optional[Callable[[BaseException], bool]] = None,
                    on_retry: Optional[Callable[[BaseException, int], None]] = None,
                    **kwargs):
    """Call ``fn(*args, **kwargs)``; retry transient failures with bounded
    exponential backoff.

    Non-transient errors propagate unchanged on the first occurrence.
    Transient errors are retried up to ``max_retries`` times
    (``MXTPU_RETRY_MAX``, default 3); exhaustion raises :class:`RetryError`
    from the last failure. ``on_retry(exc, attempt)`` runs before each
    backoff sleep (loggers, counters)."""
    retries = _env_int("MXTPU_RETRY_MAX", 3) if max_retries is None \
        else max_retries
    base = _env_float("MXTPU_RETRY_BACKOFF_S", 0.5) if base_backoff_s is None \
        else base_backoff_s
    cap = _env_float("MXTPU_RETRY_BACKOFF_MAX_S", 30.0) if max_backoff_s is None \
        else max_backoff_s
    judge = classify or classify_error

    from ..observability import metrics, tracer
    last: Optional[BaseException] = None
    for attempt in range(retries + 1):
        try:
            return fn(*args, **kwargs)
        except (KeyboardInterrupt, SystemExit):
            raise
        except BaseException as exc:
            last = exc
            if not judge(exc):
                metrics.record_resilience("escalations")
                tracer.instant("resilience/escalate", cat="resilience",
                               args={"label": label,
                                     "error": type(exc).__name__})
                raise
            if attempt >= retries:
                break
            metrics.record_resilience("retries")
            if on_retry is not None:
                on_retry(exc, attempt)
            with tracer.span("resilience/retry", cat="resilience",
                             args={"label": label, "attempt": attempt + 1,
                                   "error": f"{type(exc).__name__}: {exc}"[:200]}):
                time.sleep(_backoff_s(attempt, base, cap))
    metrics.record_resilience("retries_exhausted")
    tracer.instant("resilience/retries_exhausted", cat="resilience",
                   args={"label": label, "attempts": retries + 1})
    assert last is not None
    raise RetryError(label, retries + 1, last) from last
