"""Elastic resume supervisor — restart-from-last-commit as a library call.

``supervise(fit_fn, ...)`` owns the restart loop every elastic trainer
hand-rolls: run training, and when it dies (transient-turned-fatal error,
injected fault, watchdog abort, preemption, SIGKILL) start it again resuming
from the latest *committed* checkpoint step — at whatever dp size is
available for the new attempt. The dp-N→dp-M leg is exactly the
``ZeroLayout.adopt_states`` + DeviceFeed re-bucketing path the checkpoint
subsystem already supports; the supervisor is what exercises it end to end
without a human in the loop (ROADMAP item 4's "elasticity today means a
human restarts at a different dp size").

Two modes:

* ``mode="inline"`` (default) — ``fit_fn`` runs in this process inside the
  restart loop. Survives raised failures (injected faults, writer errors,
  collective flakes) but by nature not process death; cheap enough for
  tier-1.
* ``mode="process"`` — each attempt is a fresh ``multiprocessing``
  *spawn* child (fork after JAX init is hazardous), so SIGKILL / preemption
  / watchdog ``os._exit(87)`` are all survivable. ``fit_fn`` must be a
  module-level (picklable) callable. The child inherits ``os.environ`` at
  spawn time: the supervisor sets ``MXTPU_RESTART_ATTEMPT`` (fault-plan
  ``attempt=`` gating), ``MXTPU_PROGRESS_BEACON`` (steps-lost accounting
  across SIGKILL), and — when a ``dp_schedule`` is given — rewrites the
  ``--xla_force_host_platform_device_count`` flag so the child boots with
  that attempt's device count.

``fit_fn`` receives a :class:`RestartContext` telling it which attempt this
is and where to resume from; the contract is that it passes
``ctx.resume_from()`` to ``Module.fit`` (a no-op fresh start when nothing
is committed yet, per ``fit``'s resume semantics).

Restarts, steps lost since the last commit, and restart latency all land in
``profiler.get_resilience_stats()``; each restart is a ``resilience/restart``
instant on the trace timeline.
"""

from __future__ import annotations

import logging
import os
import signal
import sys
import time
import traceback
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Sequence, Union

from . import faults, watchdog
from .retry import classify_error

__all__ = ["supervise", "RestartContext", "SuperviseResult", "GiveUpError"]

_log = logging.getLogger("mxtpu.resilience")

ENV_MAX_RESTARTS = "MXTPU_MAX_RESTARTS"


class GiveUpError(RuntimeError):
    """The restart budget is spent; the last failure is ``__cause__`` (inline
    mode) or summarized in the message (process mode)."""


@dataclass
class RestartContext:
    """What one attempt needs to know. Picklable (process mode ships it to
    the spawn child), so the manager handle is inline-only — process-mode
    ``fit_fn`` builds its own manager at ``directory``."""
    attempt: int                      # 1-based; attempt 1 is the first run
    directory: Optional[str]          # checkpoint root (shared across attempts)
    resume_step: Optional[int]        # latest committed step at attempt start
    dp: Optional[int] = None          # device count this attempt runs at
    prev_error: Optional[str] = None  # why the previous attempt died
    manager: Optional[object] = None  # inline mode: the live CheckpointManager
    elastic: Optional[object] = None  # inline mode: the live ElasticRun, so
    #   fit_fn can route train_data through it (live resize before restart)

    @property
    def restarts(self) -> int:
        return self.attempt - 1

    def resume_from(self):
        """The value to pass to ``Module.fit(resume_from=...)``: the manager
        (inline) or the directory, or None when there is nothing to resume."""
        if self.resume_step is None:
            return None
        return self.manager if self.manager is not None else self.directory


@dataclass
class SuperviseResult:
    result: object = None             # fit_fn return value (inline mode)
    attempts: int = 0
    restarts: int = 0
    steps_lost: int = 0
    exit_codes: List[int] = field(default_factory=list)  # process mode
    errors: List[str] = field(default_factory=list)


def _latest_committed(manager, directory: Optional[str]) -> Optional[int]:
    if manager is not None:
        return manager.latest_step()
    if directory and os.path.isdir(directory):
        from ..checkpoint import atomic_io
        steps = atomic_io.committed_steps(directory, "step")
        return steps[-1] if steps else None
    return None


def _dp_for_attempt(dp_schedule, attempt: int) -> Optional[int]:
    if dp_schedule is None:
        return None
    if callable(dp_schedule):
        return dp_schedule(attempt)
    seq: Sequence[int] = dp_schedule
    if not seq:
        return None
    return int(seq[min(attempt - 1, len(seq) - 1)])


def _xla_flags_with_device_count(flags: str, n: int) -> str:
    kept = [f for f in flags.split()
            if not f.startswith("--xla_force_host_platform_device_count=")]
    kept.append(f"--xla_force_host_platform_device_count={n}")
    return " ".join(kept)


class _EnvScope:
    """Set env vars for the duration of a with-block, restoring prior values
    (the spawn child snapshots ``os.environ`` at ``Process.start()``)."""

    def __init__(self, updates: dict):
        self.updates = updates
        self._saved: dict = {}

    def __enter__(self):
        for k, v in self.updates.items():
            self._saved[k] = os.environ.get(k)
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = str(v)
        return self

    def __exit__(self, *exc):
        for k, old in self._saved.items():
            if old is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = old
        return False


def _record_restart(reason: str, latency_ms: float, lost: int) -> None:
    from ..observability import metrics, tracer
    metrics.record_resilience("restarts")
    metrics.record_resilience("restart_latency_ms_total", latency_ms)
    metrics.record_resilience("restart_latency_ms_last", latency_ms)
    if lost > 0:
        metrics.record_resilience("steps_lost", lost)
    tracer.instant("resilience/restart", cat="resilience",
                   args={"reason": reason[:200],
                         "latency_ms": round(latency_ms, 3),
                         "steps_lost": lost})


def supervise(fit_fn: Callable[[RestartContext], object],
              manager=None,
              directory: Optional[str] = None,
              mode: str = "inline",
              max_restarts: Optional[int] = None,
              dp_schedule: Union[None, Sequence[int],
                                 Callable[[int], Optional[int]]] = None,
              restart_backoff_s: float = 0.1,
              attempt_timeout_s: Optional[float] = None,
              elastic=None) -> SuperviseResult:
    """Run ``fit_fn`` under the elastic restart loop.

    ``manager``/``directory`` name the checkpoint root resumption reads from
    (one of them is required for resume to mean anything; with neither, every
    restart is a fresh start). ``max_restarts`` bounds restarts beyond the
    first attempt (env ``MXTPU_MAX_RESTARTS``, default 3); exhaustion raises
    :class:`GiveUpError`. ``attempt_timeout_s`` (process mode) kills a child
    that outlives it — a last-resort backstop under the watchdog.

    ``elastic`` (inline mode) is an :class:`~.elastic.ElasticRun` handed to
    each attempt via ``ctx.elastic``: resizes are served live in place, and
    only a :class:`~.elastic.ResizeError` — live adoption failed — falls
    through to this restart loop (counted as a ``restart_fallback``)."""
    if mode not in ("inline", "process"):
        raise ValueError(f"mode must be 'inline' or 'process', got {mode!r}")
    if max_restarts is None:
        try:
            max_restarts = int(os.environ.get(ENV_MAX_RESTARTS, "3"))
        except ValueError:
            max_restarts = 3
    if manager is not None and directory is None:
        directory = manager.directory
    watchdog.ensure_commit_hook()
    if mode == "inline":
        return _supervise_inline(fit_fn, manager, directory, max_restarts,
                                 dp_schedule, restart_backoff_s, elastic)
    if elastic is not None:
        raise ValueError("elastic= is inline-only (an ElasticRun holds live "
                         "module state and cannot ship to a spawn child)")
    return _supervise_process(fit_fn, directory, max_restarts, dp_schedule,
                              restart_backoff_s, attempt_timeout_s)


# -- inline mode -------------------------------------------------------------

def _supervise_inline(fit_fn, manager, directory, max_restarts, dp_schedule,
                      backoff_s, elastic=None) -> SuperviseResult:
    from ..observability import tracer
    res = SuperviseResult()
    prev_error: Optional[str] = None
    # steps-lost baseline: heartbeat counters are process-cumulative, so any
    # steps run BEFORE this supervise() call must not count as "lost"
    base_steps = watchdog.progress_snapshot()["steps"]
    attempt = 0
    while True:
        attempt += 1
        res.attempts = attempt
        ctx = RestartContext(attempt=attempt, directory=directory,
                             resume_step=_latest_committed(manager, directory),
                             dp=_dp_for_attempt(dp_schedule, attempt),
                             prev_error=prev_error, manager=manager,
                             elastic=elastic)
        with _EnvScope({faults.ENV_ATTEMPT: attempt}):
            try:
                with tracer.span("resilience/attempt", cat="resilience",
                                 args={"attempt": attempt, "mode": "inline",
                                       "resume_step": ctx.resume_step}):
                    res.result = fit_fn(ctx)
                return res
            except (KeyboardInterrupt, SystemExit):
                raise
            except BaseException as exc:
                prev_error = f"{type(exc).__name__}: {exc}"
                res.errors.append(prev_error)
                from .elastic import ResizeError
                if isinstance(exc, ResizeError):
                    # live in-place adoption failed — this restart is the
                    # fallback path, and the scoreboard should say so
                    from ..observability import metrics
                    metrics.record_resilience("restart_fallbacks")
                    _log.warning("supervise[inline]: live resize failed "
                                 "(%s) — falling back to restart", exc)
                snap = watchdog.progress_snapshot()
                lost = max(0, snap["steps"]
                           - max(snap["committed_steps"], base_steps))
                if res.restarts >= max_restarts:
                    raise GiveUpError(
                        f"giving up after {attempt} attempts "
                        f"({max_restarts} restarts): {prev_error}") from exc
                res.restarts += 1
                res.steps_lost += lost
                _log.warning(
                    "supervise[inline]: attempt %d died (%s; transient=%s, "
                    "~%d steps since last commit) — restarting from step %s",
                    attempt, prev_error, classify_error(exc), lost,
                    _latest_committed(manager, directory))
        t_death = time.perf_counter()
        time.sleep(backoff_s)
        _record_restart(prev_error, (time.perf_counter() - t_death) * 1e3,
                        lost)


# -- process mode ------------------------------------------------------------

def _child_main(fit_fn, ctx: RestartContext) -> None:
    """Spawn-child entry: arm the watchdog when a deadline is configured,
    run the attempt, exit 0/1. (Beacon + commit hook arm at import via
    ``MXTPU_PROGRESS_BEACON``, which the parent set before spawning.)"""
    wd = None
    if os.environ.get(watchdog.ENV_DEADLINE):
        wd = watchdog.Watchdog().start()
    try:
        fit_fn(ctx)
    except (KeyboardInterrupt, SystemExit):
        raise
    except BaseException:  # mxtpu: ignore[R005] — KI/SE re-raised above; any
        # other death must become a nonzero exit the parent can classify
        traceback.print_exc()
        sys.stderr.flush()
        sys.exit(1)
    finally:
        if wd is not None:
            wd.stop()
    sys.exit(0)


def _describe_exit(code: Optional[int]) -> str:
    if code is None:
        return "still alive?"
    if code == watchdog.WATCHDOG_EXIT_CODE:
        return f"watchdog abort (exit {code})"
    if code < 0:
        try:
            name = signal.Signals(-code).name
        except ValueError:
            name = f"signal {-code}"
        return f"killed by {name}"
    return f"exit {code}"


def _supervise_process(fit_fn, directory, max_restarts, dp_schedule,
                       backoff_s, attempt_timeout_s) -> SuperviseResult:
    import multiprocessing
    from ..observability import tracer
    mp = multiprocessing.get_context("spawn")
    res = SuperviseResult()
    beacon_path = os.path.join(directory, ".progress-beacon") if directory \
        else None
    prev_error: Optional[str] = None
    attempt = 0
    t_death: Optional[float] = None
    while True:
        attempt += 1
        res.attempts = attempt
        dp = _dp_for_attempt(dp_schedule, attempt)
        ctx = RestartContext(attempt=attempt, directory=directory,
                             resume_step=_latest_committed(None, directory),
                             dp=dp, prev_error=prev_error)
        env = {faults.ENV_ATTEMPT: attempt}
        if beacon_path:
            env[watchdog.ENV_BEACON] = beacon_path
        if dp is not None:
            env["XLA_FLAGS"] = _xla_flags_with_device_count(
                os.environ.get("XLA_FLAGS", ""), dp)
        with _EnvScope(env):
            child = mp.Process(target=_child_main, args=(fit_fn, ctx),
                               name=f"mxtpu-supervised-{attempt}")
            child.start()
        if t_death is not None:  # restart latency: death → new child running
            latency_ms = (time.perf_counter() - t_death) * 1e3
            lost = 0
            if beacon_path:
                beacon = watchdog.read_beacon(beacon_path)
                if beacon:
                    lost = max(0, int(beacon.get("steps", 0))
                               - int(beacon.get("committed_steps", 0)))
            res.steps_lost += lost
            _record_restart(prev_error or "?", latency_ms, lost)
        child.join(attempt_timeout_s)
        if child.is_alive():
            _log.error("supervise[process]: attempt %d exceeded %.1fs — "
                       "killing", attempt, attempt_timeout_s)
            child.terminate()
            child.join(10)
            if child.is_alive():
                child.kill()
                child.join(10)
        code = child.exitcode
        res.exit_codes.append(code if code is not None else -255)
        if code == 0:
            return res
        t_death = time.perf_counter()
        prev_error = _describe_exit(code)
        res.errors.append(prev_error)
        tracer.instant("resilience/child_exit", cat="resilience",
                       args={"attempt": attempt, "exit": prev_error})
        if res.restarts >= max_restarts:
            raise GiveUpError(
                f"giving up after {attempt} attempts ({max_restarts} "
                f"restarts): last child death: {prev_error}")
        res.restarts += 1
        _log.warning(
            "supervise[process]: attempt %d died (%s) — restarting from "
            "step %s at dp=%s", attempt, prev_error,
            _latest_committed(None, directory),
            _dp_for_attempt(dp_schedule, attempt + 1))
        time.sleep(backoff_s)
