"""Continuous-batching serving engine (Orca-style step-boundary scheduling,
Sarathi-style decode-overlapped chunked prefill, SGLang-style radix prefix
reuse).

:class:`ServingEngine` is the online front door over a decode-capable model
(anything exposing ``serving_step`` / ``serving_sample`` / ``_gen_params`` —
``TransformerLM`` in the zoo): callers ``submit()`` token prompts from any
thread; one scheduler thread runs the slot batch.

The data path, end to end:

1. **Admission** — ``submit()`` drops the request into a bounded queue
   (full → :exc:`QueueFullError`, the backpressure contract). A
   :class:`DeviceFeed` producer stages each prompt device-resident (padded
   to its 32-token bucket) so admission never pays a host→device transfer
   inside the decode loop; the scheduler drains it with the non-blocking
   ``poll()``.
2. **Chunked prefill** — the prompt runs through a separate B=1 program in
   fixed-budget position chunks (``kv.build_prefill_chunk``, one program per
   (prompt bucket, chunk size)), ONE chunk dispatched between decode chunks:
   a partial-prefill cursor lives on the reserved slot, so a long prompt
   never stalls the in-flight slot batch for more than one chunk's work (the
   decode-stall guard bound). Before the first chunk the radix
   :class:`~mxtpu.serving.kv.PrefixCache` is probed: a prompt extending a
   cached prefix copies the cached K/V rows into its page and prefills only
   the suffix — a shared system prompt costs one prefill, ever. The finished
   page is merged into the slot row; forced-prompt blocks are inserted back
   into the tree.
3. **Decode** — ``kv.build_decode`` runs ``chunk`` steps over ALL slots per
   dispatch; per-slot token/position/active/limit AND sampling params
   (temperature/top-k/seed) are traced inputs, so requests retiring,
   joining, or changing the sampling mix between dispatches reuse the same
   compiled program (ONE trace per (slots, TOT bucket) — the compile-guard
   contract). Greedy slots stay bit-exact with solo ``generate``; sampled
   slots are deterministic per (seed, position). Finished/cancelled/expired
   requests retire at chunk boundaries and their slots are immediately
   re-admissible.

Guardrails: every dispatch heartbeats the resilience watchdog on the
``serving`` source (arm with ``MXTPU_SERVING_STALL_S``), spans land in the
unified trace under ``serving/*`` (``prefill_chunk``, ``decode``,
``prefix_hit``…), and counters — including the TTFT decomposition
queue-wait / prefill / first-decode-token — in
``profiler.get_serving_stats()``.

Live elasticity (ROADMAP item 4, ``docs/resilience.md``): ``drain()`` stops
admission, parks the scheduler at a chunk boundary, and freezes every
in-flight request — its KV page, next-token/position/limit/sampling slot
state, and handle, including a PARTIALLY-PREFILLED request's cursor and
partial page — into a :class:`ServingHandoff`; ``adopt()`` on a fresh engine
(same model, survivor mesh) reinstalls the pages and resumes decoding (or
the suffix prefill) for the SAME request handles bit-exactly, with zero
drops. Queued-but-unprefilled requests ride along and are re-staged on the
adopting engine.

Knobs: ``MXTPU_SERVING_SLOTS`` (slot-batch capacity, default 4),
``MXTPU_SERVING_QUEUE`` (admission queue depth, default 16),
``MXTPU_SERVING_CHUNK`` (decode steps per dispatch, default 8),
``MXTPU_SERVING_PREFILL_CHUNK`` (prefill positions per dispatch, default
64), ``MXTPU_PREFIX_CACHE_MB`` (radix prefix-cache byte cap, default 64; 0
disables), ``MXTPU_SERVING_LOG_S`` (per-interval engine log period, default
off), ``MXTPU_SERVING_PROGRAM_CACHE`` (LRU bound on the program caches),
``MXTPU_SERVING_KV_DTYPE`` (cache storage dtype, e.g. ``bfloat16``),
``MXTPU_SERVING_QUANT`` (low-precision execution: ``int8_kv`` / ``fp8_kv``
/ ``int8_w``, comma-separated — see ``docs/quantization.md``). All knobs
are also settable programmatically via :class:`~mxtpu.serving.api
.ServingConfig` / the constructor kwargs.
"""

from __future__ import annotations

import itertools
import logging
import os
import queue
import threading
import time
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np

import jax.numpy as jnp

from .. import profiler
from ..device_feed import DeviceFeed
from ..ndarray.ndarray import NDArray
from ..observability import tracer
from ..resilience.elastic import elastic_watchdog
from ..resilience.faults import fault_point
from ..ops import quant_attention
from ..quant.serve import parse_quant, quantize_lm
from ..resilience.watchdog import Watchdog, heartbeat
from ..step_cache import ProgramCache
from . import kv
from .api import (CANCELLED, DONE, EXPIRED, PENDING, RUNNING, SHED,
                  HandoffMismatch, QueueFullError, ServingConfig,
                  ServingRequest)
from .spec import NgramDrafter, parse_spec, spec_from_env

__all__ = ["ServingEngine", "ServingHandoff"]

_log = logging.getLogger("mxtpu.serving")

# replica ids minted at construction (satellite of the router work): every
# serving metric series carries this label so N scraped replicas never
# collide on one series name; a fronting Router overrides it per replica
_ENGINE_IDS = itertools.count()


@dataclass
class ServingHandoff:
    """Frozen in-flight serving state from :meth:`ServingEngine.drain`,
    consumable by :meth:`ServingEngine.adopt` on a fresh engine. Everything
    is host-resident (pages are numpy), so the handoff survives the source
    mesh disappearing entirely."""
    tot: int                                  # KV bucket length of each page
    entries: List[dict] = field(default_factory=list)   # per in-flight slot:
    #   req / page (L,2,1,H,tot,D np) / tok / p / limit / left / temp/topk/seed
    partial: List[dict] = field(default_factory=list)   # mid-prefill request:
    #   req / page (L,2,1,H,PB,D np) / t (cursor) / prev / t0 / PB / left —
    #   adopt() resumes the SUFFIX prefill, never re-prefills from scratch
    pending: List[ServingRequest] = field(default_factory=list)  # admitted,
    #   never prefilled — re-staged verbatim by adopt(). The request handles
    #   everywhere in this handoff carry their own scheduling metadata
    #   (tenant / priority / deadline), so SLO state survives the hop
    kv_dtype: str = "float32"                 # page storage: 'float32' /
    #   'bfloat16' / 'int8' / 'fp8' — adopt() refuses a mismatched engine
    #   (quantized pages are QuantKV hosts; reinterpreting them as another
    #   storage would corrupt every resumed request)
    parked: List[dict] = field(default_factory=list)  # preempted decode
    #   slots (mxtpu.sched): same shape as `entries` plus the park-time
    #   "tot" — adopt() re-queues them for resume, sched-enabled engines only
    sched_state: Optional[dict] = None        # SLOScheduler.export_state():
    #   fair-share passes + service-rate EWMAs, so the successor's policy
    #   doesn't restart cold
    spec: Optional[dict] = None               # speculative-decode state of the
    #   source engine ({"k": draft depth}); entries/parked then also carry
    #   per-slot "draft" (proposed tokens) + "dlen" (how many are live). The
    #   verify cursor is the entry's own "p" — drafts are proposed BETWEEN
    #   dispatches, so a drained slot's p is always at a verify boundary and
    #   its in-flight drafts are pure proposals (no K/V written for them
    #   yet). adopt() on a spec-less engine refuses in-flight drafts, the
    #   parked-slots rule's mirror; a spec engine with a different k safely
    #   truncates or re-proposes (drafts are advisory by construction)
    mesh: Optional[tuple] = None              # sharded.mesh_fingerprint() of
    #   the source engine (None = single-device): adopt() refuses a
    #   mismatched successor with HandoffMismatch UP FRONT — single-device
    #   and sharded engines never silently exchange placement assumptions
    kv_geometry: Optional[tuple] = None       # (L, H, D) cache-row geometry
    #   of the source model; page shapes are validated against the adopting
    #   model BEFORE any merge, so a wrong-geometry handoff is a named
    #   error, never a shape crash mid-adopt

    @property
    def in_flight(self) -> int:
        return (len(self.entries) + len(self.partial) + len(self.pending)
                + len(self.parked))


def _env_int(name: str, default: int) -> int:
    try:
        return max(1, int(os.environ.get(name, str(default))))
    except ValueError:
        return default


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, str(default)))
    except ValueError:
        return default


def _req_sampling(req: ServingRequest):
    sp = req.sampling
    if sp is None:
        return 0.0, 0, 0
    return float(sp.temperature), int(sp.top_k), int(sp.seed)


class ServingEngine:
    """Online continuous-batching server over one decode-capable model.

    Greedy decoding is the bit-exact default (argmax vs solo ``generate``);
    per-request :class:`~mxtpu.serving.api.SamplingParams` ride the decode
    program as per-slot traced arrays, seed-deterministic regardless of
    slot assignment or chunk boundaries."""

    def __init__(self, model, slots: Optional[int] = None,
                 queue_depth: Optional[int] = None,
                 chunk: Optional[int] = None,
                 stall_deadline_s: Optional[float] = None,
                 prefill_chunk: Optional[int] = None,
                 prefix_cache_mb: Optional[float] = None,
                 kv_dtype=None, quant=None, decode_kernel=None,
                 sched=None, prefill_batch: Optional[int] = None,
                 spec=None, mesh=None, engine_id: Optional[str] = None,
                 config: Optional[ServingConfig] = None):
        if config is not None:
            slots = slots or config.slots
            queue_depth = queue_depth or config.queue_depth
            chunk = chunk or config.chunk
            prefill_chunk = prefill_chunk or config.prefill_chunk
            if prefix_cache_mb is None:
                prefix_cache_mb = config.prefix_cache_mb
            if stall_deadline_s is None:
                stall_deadline_s = config.stall_deadline_s
            kv_dtype = kv_dtype or config.kv_dtype
            if quant is None:
                quant = config.quant
            if decode_kernel is None:
                decode_kernel = config.decode_kernel
            if sched is None:
                sched = config.sched
            if prefill_batch is None:
                prefill_batch = config.prefill_batch
            if spec is None:
                spec = config.spec
            if mesh is None:
                mesh = config.mesh
            engine_id = engine_id or config.engine_id
        self._model = model
        # per-replica metric label (observability): minted here so every
        # serving series this engine records carries a stable id from the
        # first dispatch; a fronting Router names its replicas through this
        self.engine_id = engine_id or f"engine{next(_ENGINE_IDS)}"
        # speculative multi-token decode (mxtpu.serving.spec): like quant,
        # ONE resolved config per engine lifetime (kwarg > config >
        # MXTPU_SPEC_DECODE env) — the verify program cache stays keyed on
        # (slots, bucket, k); None keeps every path below byte-identical
        self._spec = parse_spec(spec) if spec is not None else spec_from_env()
        self._drafter = (self._spec.drafter
                         if self._spec is not None else None)
        # low-precision execution (mxtpu.quant): ONE spec per engine
        # lifetime, resolved kwarg > config > env — the program caches stay
        # keyed on (slots, bucket, chunk) because the spec never changes
        if quant is None:
            quant = os.environ.get("MXTPU_SERVING_QUANT") or None
        self._quant = parse_quant(quant)
        # fused dequant-attention path of the quantized KV read: like the
        # spec, resolved ONCE per engine lifetime (kwarg > config >
        # MXTPU_DECODE_KERNEL env) — an env flip while serving can never
        # reach a live program, let alone retrace it
        self._decode_kernel = quant_attention.decode_kernel_mode(decode_kernel)
        # model-parallel serving (mxtpu.serving.sharded): ONE mesh per
        # engine lifetime — params, the paged KV, and every compiled
        # program place onto it at materialization, and each dispatch
        # traces under fsdp.layout_scope so the step functions' activation
        # constraints fire. mesh=None keeps every path below byte-identical
        self._mesh = mesh
        self._layout = None
        if mesh is not None:
            from . import sharded
            sharded.validate_mesh(mesh)
            self._layout = sharded.ServingLayout()
            if self._quant.kv:
                # the fused pallas read is refused under a mesh; auto pins
                # the GSPMD-partitionable xla read (named error, up front)
                self._decode_kernel = sharded.pin_decode_kernel(
                    self._decode_kernel)
        self._decode_kernel_str = (
            quant_attention.resolve_decode_kernel(self._decode_kernel)
            if self._quant.kv else None)
        if kv_dtype is None:
            kv_dtype = os.environ.get("MXTPU_SERVING_KV_DTYPE") or None
        self._kv_dtype = jnp.zeros((0,), kv_dtype or jnp.float32).dtype
        if not jnp.issubdtype(self._kv_dtype, jnp.floating):
            # a plain integer cache would store K/V rows CAST to integers
            raise ValueError(
                f"kv_dtype={kv_dtype!r} is not a float dtype; quantized KV "
                f"storage is quant='int8_kv' / 'fp8_kv'")
        # what get_serving_stats()/ServingHandoff report as the page storage
        self._kv_dtype_str = self._quant.kv or self._kv_dtype.name
        self.slots = slots if slots else _env_int("MXTPU_SERVING_SLOTS", 4)
        self.queue_depth = queue_depth if queue_depth \
            else _env_int("MXTPU_SERVING_QUEUE", 16)
        self.chunk = chunk if chunk else _env_int("MXTPU_SERVING_CHUNK", 8)
        self.prefill_chunk = prefill_chunk if prefill_chunk \
            else _env_int("MXTPU_SERVING_PREFILL_CHUNK", 64)
        self.prefix_cache_mb = prefix_cache_mb if prefix_cache_mb is not None \
            else _env_float("MXTPU_PREFIX_CACHE_MB", 64.0)
        if stall_deadline_s is None:
            raw = os.environ.get("MXTPU_SERVING_STALL_S", "")
            stall_deadline_s = float(raw) if raw else None
        self._stall_deadline_s = stall_deadline_s
        self._log_s = _env_float("MXTPU_SERVING_LOG_S", 0.0)
        self._next_log = 0.0
        self._submit_q: "queue.Queue" = queue.Queue(maxsize=self.queue_depth)
        self._start_lock = threading.Lock()
        self._decode_fns = ProgramCache("serving_decode")
        self._prefill_fns = ProgramCache("serving_prefill")
        self._verify_fns = ProgramCache("serving_verify")
        self._stop = threading.Event()
        self._draining = threading.Event()
        self._started = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._feed: Optional[DeviceFeed] = None
        self._wd: Optional[Watchdog] = None
        self._error: Optional[BaseException] = None
        # slot state (scheduler-thread-owned; riders of the decode trace)
        self._params = None
        self._caches = None
        self._TOT: Optional[int] = None
        self._tok = np.zeros(self.slots, np.int32)
        self._p = np.zeros(self.slots, np.int32)
        self._limit = np.zeros(self.slots, np.int32)
        self._active = np.zeros(self.slots, bool)
        self._left = np.zeros(self.slots, np.int64)
        self._temp = np.zeros(self.slots, np.float32)
        self._topk = np.zeros(self.slots, np.int32)
        self._seed = np.zeros(self.slots, np.uint32)
        self._t_admit = np.zeros(self.slots, np.float64)
        self._dec_emitted = np.zeros(self.slots, bool)
        self._reqs: List[Optional[ServingRequest]] = [None] * self.slots
        # per-slot speculative draft buffers (scheduler-thread-owned):
        # proposed at the END of a decode turn, consumed by the next verify
        # dispatch — so a drain() between turns carries genuine in-flight
        # drafts. dlen == 0 means "plain decode this turn" for the slot
        if self._spec is not None:
            self._draft = np.zeros((self.slots, self._spec.k), np.int32)
            self._dlen = np.zeros(self.slots, np.int32)
        self._ngram_hits_seen = 0
        self._ngram_misses_seen = 0
        # partial-prefill cursor (scheduler-thread-owned; at most one
        # request prefills at a time, one CHUNK dispatched per loop turn)
        self._pf: Optional[dict] = None
        self._prefix: Optional[kv.PrefixCache] = None
        self._evict_seen = 0
        # SLO control plane (mxtpu.sched) — strictly opt-in: with sched
        # unset every code path below is byte-identical to the plain FIFO
        # engine (the sched package is imported only when enabled)
        self._sched = None
        if sched:
            from ..sched.policy import SLOPolicy, SLOScheduler
            if sched is True:
                self._sched = SLOScheduler()
            elif isinstance(sched, SLOScheduler):
                self._sched = sched
            elif isinstance(sched, SLOPolicy):
                self._sched = SLOScheduler(sched)
            else:
                raise ValueError(
                    "sched must be True, an SLOPolicy, or an SLOScheduler; "
                    f"got {type(sched).__name__}")
        self._prefill_batch = int(prefill_batch) if prefill_batch else 1
        if self._prefill_batch > 1 and self._sched is None:
            raise ValueError("prefill_batch > 1 requires the SLO scheduler "
                             "(pass sched=True / a policy)")
        # staged (req, prompt) pairs awaiting a fair-share pick; preempted
        # decode slots parked for resume; in-flight batched prefill group
        # (all scheduler-thread-owned, sched mode only)
        self._sched_pending: List[tuple] = []
        self._parked: List[dict] = []
        self._pfg = None

    # -- public surface ------------------------------------------------------
    def start(self) -> "ServingEngine":
        with self._start_lock:
            if self._thread is not None:
                return self
            self._materialize_params()
            profiler.record_serving("slots", self.slots)
            profiler.record_serving("engine", self.engine_id)
            profiler.record_serving("kv_dtype", self._kv_dtype_str)
            if self._decode_kernel_str is not None:
                profiler.record_serving("decode_kernel",
                                        self._decode_kernel_str)
            self._feed = DeviceFeed(self._staging_source(), depth=2)
            if self._stall_deadline_s:
                self._wd = Watchdog(deadline_s=self._stall_deadline_s,
                                    source="serving").start()
            self._thread = threading.Thread(target=self._run, daemon=True,
                                            name="mxtpu-serving-scheduler")
            self._thread.start()
            self._started.set()
        return self

    def submit(self, prompt, max_new_tokens: int,
               deadline_s: Optional[float] = None,
               sampling=None, prefix_cache: bool = True,
               tenant: str = "default",
               priority: str = "standard") -> ServingRequest:
        """Enqueue one generation request; returns its handle immediately.
        ``sampling`` takes :class:`~mxtpu.serving.api.SamplingParams` (or a
        mapping of its fields; omitted = bit-exact greedy);
        ``prefix_cache=False`` opts the request out of shared-prefix KV
        reuse in both directions. ``tenant``/``priority`` are the SLO
        scheduling keys (inert without ``sched=...``; see
        :class:`~mxtpu.serving.api.ServingRequest`). Raises
        :exc:`QueueFullError` when the admission queue is at capacity
        (backpressure, not silent growth) and ``ValueError`` for requests
        the model can't hold."""
        if self._draining.is_set():
            raise RuntimeError(
                "ServingEngine is draining — submit to the adopting engine")
        if self._stop.is_set():
            raise RuntimeError("ServingEngine is stopped")
        req = ServingRequest(prompt, max_new_tokens, deadline_s,
                             sampling=sampling, prefix_cache=prefix_cache,
                             tenant=tenant, priority=priority)
        if req.total > self._model._max_len:
            raise ValueError(
                f"prompt {len(req.prompt)} + {req.max_new} new exceeds "
                f"max_len {self._model._max_len}")
        if self._thread is None:
            self.start()
        try:
            self._submit_q.put_nowait(req)
        except queue.Full:
            profiler.record_serving("rejected")
            tracer.instant("serving/reject", cat="serving",
                           args={"id": req.id})
            raise QueueFullError(
                f"admission queue full ({self.queue_depth}); request "
                f"{req.id} rejected") from None
        profiler.record_serving("submitted")
        profiler.record_serving("queue_depth_max", self._submit_q.qsize())
        tracer.instant("serving/submit", cat="serving",
                       args={"id": req.id, "prompt": len(req.prompt),
                             "max_new": req.max_new})
        return req

    def stats(self) -> dict:
        return profiler.get_serving_stats()

    def load(self) -> dict:
        """Cheap load signal for a fronting :class:`~mxtpu.serving.router
        .Router`: queued admissions plus occupied/reserved work, plus the
        queue bound so the router can reason about headroom. Lock-free
        snapshot reads — safe from any thread, never blocks the scheduler
        (the R010 contract: routers poll, they don't block a decode
        turn)."""
        active = int(self._active.sum())
        waiting = (self._submit_q.qsize()
                   + (1 if self._pf is not None else 0)
                   + (len(self._pfg.members) if self._pfg is not None else 0)
                   + len(self._sched_pending) + len(self._parked))
        return {"engine": self.engine_id, "active": active,
                "queued": waiting, "slots": self.slots,
                "queue_depth": self.queue_depth,
                "in_flight": active + waiting}

    def request_timeline(self, rid: int) -> List[dict]:
        """Every trace event tagged with request ``rid``, time-sorted —
        submit → admit → prefill chunks → decode dispatches → retire,
        including drain/adopt markers when the request crossed an engine
        handoff. Needs tracing on (``profiler.start()`` / ``MXTPU_TRACE``);
        ids also land in the batch ``serving/decode`` spans, so a request's
        lane shows exactly which dispatches computed its tokens."""
        from ..observability import export
        return export.request_timeline(rid)

    def stop(self) -> None:
        """Stop the scheduler; queued and in-flight requests are finished
        as CANCELLED so no caller blocks forever."""
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=30)
        if self._feed is not None:
            self._feed.close()
        if self._wd is not None:
            self._wd.stop()
        if self._error is not None:
            raise self._error

    def drain(self) -> ServingHandoff:
        """Zero-drop handoff, half one: stop admission (``submit`` raises),
        park the scheduler at its chunk boundary, and freeze every live
        request — KV page, slot cursors, sampling params, handle, and a
        mid-prefill request's partial page + cursor — into a host-resident
        :class:`ServingHandoff` for :meth:`adopt` on a successor engine.
        No request is cancelled; callers blocked in ``result()`` simply keep
        waiting across the handoff. Runs under the ``elastic`` heartbeat
        source (``MXTPU_ELASTIC_STALL_S``) and the ``serving.drain`` fault
        seam; on any failure the normal cancel-everything sweep runs before
        the error propagates, so the no-caller-blocks-forever contract holds
        even when the handoff itself dies."""
        if self._thread is None:
            raise RuntimeError("ServingEngine is not started")
        with tracer.span("serving/drain", cat="serving"), elastic_watchdog():
            heartbeat("elastic")
            self._draining.set()      # submit() now raises
            self._stop.set()          # scheduler exits at the chunk boundary
            self._thread.join(timeout=60)
            if self._error is not None:
                raise self._error     # sweep already ran in the scheduler
            try:
                fault_point("serving.drain")
                # an in-flight batched prefill group is finished HERE, one
                # chunk per turn (bounded: the cursor only advances), so its
                # survivors freeze below as ordinary in-slot entries
                while self._pfg is not None:
                    self._prefill_group_chunk()
                now = time.monotonic()
                entries: List[dict] = []
                for slot in np.flatnonzero(self._active):
                    slot = int(slot)
                    req = self._reqs[slot]
                    if req._cancelled():
                        self._retire(slot, CANCELLED, now)
                        continue
                    if req._expired(now):
                        self._retire(slot, EXPIRED, now)
                        continue
                    entry = {
                        "req": req,
                        # one slot row, host-landed: survives the old mesh
                        # (quantized pages keep their data + scale leaves)
                        "page": kv.host_page(
                            kv.slot_page(self._caches, slot)),
                        "tok": int(self._tok[slot]),
                        "p": int(self._p[slot]),
                        "limit": int(self._limit[slot]),
                        "left": int(self._left[slot]),
                        "temp": float(self._temp[slot]),
                        "topk": int(self._topk[slot]),
                        "seed": int(self._seed[slot]),
                    }
                    if self._spec is not None:
                        # the slot's in-flight drafts (proposed at the end
                        # of the last turn, not yet verified) ride along;
                        # "p" doubles as the verify cursor — see the
                        # ServingHandoff.spec field note
                        entry["draft"] = self._draft[slot].tolist()
                        entry["dlen"] = int(self._dlen[slot])
                    entries.append(entry)
                    tracer.instant("serving/drain_freeze", cat="serving",
                                   args={"id": req.id, "slot": slot,
                                         "p": int(self._p[slot])})
                # a partially-prefilled admission carries its cursor +
                # already-computed page rows — adopt() resumes the SUFFIX
                partial: List[dict] = []
                if self._pf is not None:
                    pf, self._pf = self._pf, None
                    req = pf["req"]
                    if req._cancelled():
                        req._finish(CANCELLED, now)
                        profiler.record_serving("cancelled")
                    elif req._expired(now):
                        req._finish(EXPIRED, now)
                        profiler.record_serving("expired")
                    else:
                        partial.append({
                            "req": req,
                            "page": kv.host_page(pf["page"]),
                            "t": pf["t"], "prev": pf["prev"],
                            "t0": pf["t0"], "PB": pf["PB"],
                            "left": pf["left"],
                        })
                        tracer.instant("serving/drain_freeze", cat="serving",
                                       args={"id": req.id, "partial": True,
                                             "t": pf["t"]})
                heartbeat("elastic")
                # staged by the feed but never prefilled: keep the handles,
                # drop the staged arrays (adopt() re-stages them). The
                # producer drains _submit_q before ending, so polling to
                # StopIteration collects every admitted request.
                pending: List[ServingRequest] = []
                deadline = time.monotonic() + 10.0
                while self._feed is not None \
                        and time.monotonic() < deadline:
                    try:
                        item = self._feed.poll(timeout=0.2)
                    except StopIteration:
                        break
                    if item is not None:
                        pending.append(item[0])
                while True:            # belt and braces: producer died early
                    try:
                        pending.append(self._submit_q.get_nowait())
                    except queue.Empty:
                        break
                # sched mode: staged-but-unpicked requests ride as pending;
                # preempted (parked) slots host-land like entries
                pending.extend(r for r, _s in self._sched_pending)
                self._sched_pending = []
                parked = [{**e, "page": kv.host_page(e["page"])}
                          for e in self._parked]
                self._parked = []
                heartbeat("elastic")
            except BaseException:
                self._shutdown_sweep()
                raise
        if self._feed is not None:
            self._feed.close()
        if self._wd is not None:
            self._wd.stop()
        from . import sharded
        handoff = ServingHandoff(
            tot=self._TOT or 0, entries=entries, partial=partial,
            pending=pending, kv_dtype=self._kv_dtype_str, parked=parked,
            sched_state=self._sched.export_state()
            if self._sched is not None else None,
            spec={"k": self._spec.k} if self._spec is not None else None,
            mesh=sharded.mesh_fingerprint(self._mesh),
            kv_geometry=kv.cache_dims(self._model))
        profiler.record_serving("drained", handoff.in_flight)
        tracer.instant("serving/drained", cat="serving",
                       args={"in_slots": len(entries),
                             "partial": len(partial),
                             "pending": len(pending),
                             "parked": len(parked),
                             "ids": [e["req"].id for e in entries]
                             + [e["req"].id for e in partial]
                             + [r.id for r in pending]
                             + [e["req"].id for e in parked]})
        return handoff

    def adopt(self, handoff: ServingHandoff) -> "ServingEngine":
        """Zero-drop handoff, half two: on a FRESH engine (same model,
        survivor mesh), reinstall each drained slot — KV page merged into a
        slot row, cursors and sampling params restored — resume a
        mid-prefill request from its cursor (suffix only, never from
        scratch), then start the scheduler and re-stage the pending
        requests. The adopted :class:`ServingRequest` handles are the
        originals, and ``_emit`` accounting is cumulative, so decode
        resumes exactly where the source engine stopped: greedy output
        stays bit-exact with an uninterrupted solo ``generate``."""
        with self._start_lock:
            if self._thread is not None:
                raise RuntimeError(
                    "adopt() needs a fresh engine (call before start/submit)")
            if len(handoff.entries) + len(handoff.partial) > self.slots:
                raise ValueError(
                    f"handoff carries {len(handoff.entries)} in-flight + "
                    f"{len(handoff.partial)} mid-prefill slots but this "
                    f"engine has {self.slots}")
            if handoff.kv_dtype != self._kv_dtype_str:
                raise ValueError(
                    f"handoff pages are {handoff.kv_dtype} but this engine "
                    f"stores KV as {self._kv_dtype_str} — adopt on an "
                    "engine with the same kv_dtype/quant configuration")
            self._validate_handoff(handoff)
            if handoff.parked and self._sched is None:
                raise ValueError(
                    "handoff carries preempted (parked) requests — adopt on "
                    "an engine with the SLO scheduler enabled (sched=...)")
            # mirror of the parked rule for speculation: in-flight drafts are
            # proposals only (no K/V behind them — "p" is the verify cursor),
            # but a spec-less engine has no verify program to consume them
            # and silently dropping speculative state is how handoffs rot
            in_flight_drafts = sum(
                int(e.get("dlen") or 0)
                for e in list(handoff.entries) + list(handoff.parked))
            if in_flight_drafts and self._spec is None:
                raise ValueError(
                    "handoff carries in-flight speculative drafts — adopt on "
                    "an engine with speculative decode enabled (spec=...)")
            if self._sched is not None:
                if handoff.sched_state:
                    self._sched.load_state(handoff.sched_state)
                # re-register every surviving handle so fair-share charging
                # and R008-shaped inflight tracking pick up where drain left
                for req in ([e["req"] for e in handoff.entries]
                            + [e["req"] for e in handoff.partial]
                            + [e["req"] for e in handoff.parked]):
                    self._sched.register(req)
                self._parked.extend(dict(e) for e in handoff.parked)
            if handoff.entries or handoff.partial:
                self._materialize_params()
            if handoff.entries:
                self._ensure_capacity(handoff.tot)
                for i, e in enumerate(handoff.entries):
                    self._merge_page(kv.device_page(e["page"]), i)
                    self._tok[i] = e["tok"]
                    self._p[i] = e["p"]
                    self._limit[i] = e["limit"]
                    self._left[i] = e["left"]
                    self._temp[i] = e.get("temp", 0.0)
                    self._topk[i] = e.get("topk", 0)
                    self._seed[i] = e.get("seed", 0)
                    self._t_admit[i] = time.monotonic()
                    self._dec_emitted[i] = False
                    if self._spec is not None and e.get("dlen"):
                        # a k mismatch truncates (advisory proposals — the
                        # verify program re-scores whatever survives)
                        n = min(int(e["dlen"]), self._spec.k)
                        self._draft[i, :n] = e["draft"][:n]
                        self._dlen[i] = n
                    self._active[i] = True
                    self._reqs[i] = e["req"]
                    tracer.instant("serving/adopt_resume", cat="serving",
                                   args={"id": e["req"].id, "slot": i,
                                         "p": e["p"]})
            if handoff.partial:
                e = handoff.partial[0]
                req = e["req"]
                padded = np.zeros((1, e["PB"]), np.int32)
                padded[0, :len(req.prompt)] = req.prompt
                temp, topk, seed = _req_sampling(req)
                self._pf = {"req": req, "prompt": jnp.asarray(padded),
                            "page": kv.device_page(e["page"]),
                            "t": e["t"], "prev": e["prev"],
                            "t0": e["t0"], "PB": e["PB"], "left": e["left"],
                            "slot": len(handoff.entries),
                            "t_start": time.monotonic(),
                            "temp": temp, "topk": topk, "seed": seed}
                tracer.instant("serving/adopt_resume", cat="serving",
                               args={"id": req.id, "partial": True,
                                     "t": e["t"]})
        self.start()
        for req in handoff.pending:
            self._submit_q.put(req)     # blocking is fine: consumer is live
        profiler.record_serving("adopted", handoff.in_flight)
        tracer.instant("serving/adopted", cat="serving",
                       args={"in_slots": len(handoff.entries),
                             "partial": len(handoff.partial),
                             "pending": len(handoff.pending),
                             "ids": [e["req"].id for e in handoff.entries]
                             + [e["req"].id for e in handoff.partial]
                             + [r.id for r in handoff.pending]})
        return self

    def _validate_handoff(self, handoff: ServingHandoff) -> None:
        """Up-front handoff compatibility: mesh/sharding fingerprint and KV
        page geometry are checked BEFORE any page merges, so an incompatible
        adopt is a :class:`~mxtpu.serving.api.HandoffMismatch` naming the
        mismatch — never a shape crash halfway through reinstalling slots
        (which would strand the already-merged requests)."""
        from . import sharded
        mine = sharded.mesh_fingerprint(self._mesh)
        if handoff.mesh != mine:
            def _name(fp):
                return ("single-device" if fp is None
                        else "x".join(f"{a}={n}" for a, n in fp))
            raise HandoffMismatch(
                f"handoff was drained from a {_name(handoff.mesh)} engine "
                f"but this engine is {_name(mine)} — drained pages only "
                "re-place onto the same mesh geometry; adopt on a matching "
                "engine (or drain/adopt through a host round-trip tool)")
        geo = kv.cache_dims(self._model)
        if handoff.kv_geometry is not None and \
                tuple(handoff.kv_geometry) != tuple(geo):
            raise HandoffMismatch(
                f"handoff KV rows have (layers, heads, head_dim) = "
                f"{tuple(handoff.kv_geometry)} but this engine's model "
                f"has {tuple(geo)} — same-model adoption only")
        L, H, D = geo

        def _shape(page):
            return tuple(getattr(page, "data", page).shape)

        for kind, tot_of, lst in (
                ("in-flight", lambda e: handoff.tot, handoff.entries),
                ("mid-prefill", lambda e: e["PB"], handoff.partial),
                ("parked", lambda e: e["tot"], handoff.parked)):
            for e in lst:
                page = e.get("page")
                if page is None:     # page-less entry (e.g. a spec-only
                    continue         # probe handoff) — nothing to re-place
                want = (L, 2, 1, H, tot_of(e), D)
                got = _shape(page)
                if got != want:
                    raise HandoffMismatch(
                        f"{kind} page for request {e['req'].id} has shape "
                        f"{got}, expected {want} — the handoff does not "
                        "match this engine's model/bucket geometry")

    def __enter__(self) -> "ServingEngine":
        return self.start()

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc_type is None:
            self.stop()          # a latched scheduler error surfaces here
        else:
            try:
                self.stop()
            except BaseException:   # mxtpu: ignore[R005] — the body's
                pass                # exception wins over teardown's
        return False

    # -- staging (DeviceFeed producer thread) --------------------------------
    def _staging_source(self):
        """Blocking iterator the DeviceFeed producer pulls: pops submitted
        requests and pads their prompt to its 32-token bucket so the feed
        stages a device-resident ``(1, PB)`` int32 array per request."""
        while True:
            try:
                req = self._submit_q.get(timeout=0.1)
            except queue.Empty:
                if self._stop.is_set():
                    return
                continue
            PB = kv.bucket32(len(req.prompt), self._model._max_len)
            padded = np.zeros((1, PB), np.int32)
            padded[0, :len(req.prompt)] = req.prompt
            yield (req, NDArray(padded))

    # -- scheduler thread ----------------------------------------------------
    def _materialize_params(self) -> None:
        pars = self._model.collect_params().values()
        if any(p._data is None for p in pars):
            from .. import autograd
            with autograd.predict_mode():
                self._model(NDArray(np.zeros((1, 1), np.int32)))
        # identity pass-through on the fp32 path; int8 per-channel weights +
        # scales under int8_w (one host-side pass, then everything is traced)
        self._params = quantize_lm(self._model, self._quant)
        if self._mesh is not None:
            # one-time placement onto the SpecLayout table (column-parallel
            # sharded, row-parallel replicated — mxtpu/serving/sharded.py);
            # params ride every program as ALREADY-PLACED jit arguments, so
            # the first trace keys on the canonical shardings
            from . import sharded
            self._params = sharded.place_params(self._params, self._mesh,
                                                self._layout)
        if self._prefix is None and self.prefix_cache_mb > 0:
            block_bytes = kv.block_nbytes(self._model, self._kv_dtype,
                                          self._quant)
            self._prefix = kv.PrefixCache(block_bytes, self.prefix_cache_mb)
        if self._spec is not None and self._drafter is None:
            # default drafter: radix-tree n-grams + self-context lookup;
            # works with the prefix cache disabled too (self-context only)
            self._drafter = NgramDrafter.from_config(self._spec, self._prefix)

    def _run(self) -> None:
        try:
            while not self._stop.is_set():
                heartbeat("serving")
                busy = bool(self._active.any()) or self._pf is not None \
                    or self._pfg is not None
                self._admit(wait_s=0.0 if busy else 0.02)
                if self._pf is not None:
                    self._prefill_chunk()     # ONE chunk, then yield to
                elif self._pfg is not None:   # decode: the stall bound
                    self._prefill_group_chunk()
                if self._active.any():
                    if self._spec is not None:
                        self._spec_decode_turn()
                    else:
                        self._decode_chunk()
                self._maybe_log()
        except BaseException as e:
            self._error = e
            from ..observability import flight
            flight.record("scheduler_error", error=repr(e))
            flight.dump("scheduler_error", extra={"error": repr(e)})
        finally:
            # a clean drain hands its in-flight state to adopt(); anything
            # else (stop, scheduler error) must cancel so nobody blocks
            if self._error is not None or not self._draining.is_set():
                self._shutdown_sweep()

    def _free_slot(self, exclude=()) -> Optional[int]:
        reserved = set(exclude)
        if self._pf is not None:
            reserved.add(self._pf["slot"])
        if self._pfg is not None:
            reserved.update(m["slot"] for m in self._pfg.members)
        for i in range(self.slots):
            if not self._active[i] and i not in reserved:
                return i
        return None

    def _admit(self, wait_s: float) -> None:
        """Start at most one partial prefill per loop turn: pop a staged
        request, probe the prefix cache, reserve a slot, and leave the
        cursor for :meth:`_prefill_chunk` to advance between decodes."""
        if self._sched is not None:
            self._admit_sched(wait_s)
            return
        while self._pf is None:
            slot = self._free_slot()
            if slot is None or self._feed is None:
                return
            try:
                item = self._feed.poll(timeout=wait_s)
            except StopIteration:
                return
            if item is None:
                return
            wait_s = 0.0
            req, staged = item
            now = time.monotonic()
            if req._cancelled():
                req._finish(CANCELLED, now)
                profiler.record_serving("cancelled")
                continue
            if req._expired(now):
                req._finish(EXPIRED, now)
                profiler.record_serving("expired")
                continue
            self._begin_prefill(req, staged, slot, now)

    # -- SLO scheduling (mxtpu.sched; every method below is sched-mode only) --
    def _admit_sched(self, wait_s: float) -> None:
        """Sched-mode admission: pull EVERY staged request into the pending
        pool, then let the policy decide — shed the doomed, resume parked
        requests into free slots, preempt a lower tier for a waiting higher
        one, and start (batched) prefill on the fair-share winner(s)."""
        while self._feed is not None:
            try:
                item = self._feed.poll(timeout=wait_s)
            except StopIteration:
                break
            if item is None:
                break
            wait_s = 0.0
            self._sched.register(item[0])
            self._sched_pending.append(item)
        now = time.monotonic()
        keep = []
        for req, staged in self._sched_pending:
            if req._cancelled():
                self._finish_unslotted(req, CANCELLED, now)
            elif req._expired(now):
                self._finish_unslotted(req, EXPIRED, now)
            else:
                keep.append((req, staged))
        self._sched_pending = keep
        self._resume_parked(now)
        if self._pf is not None or self._pfg is not None \
                or not self._sched_pending:
            return
        choice, shed = self._sched.select(
            [r for r, _ in self._sched_pending], now)
        self._apply_shed(shed, now)
        if choice is None:
            return
        slot = self._free_slot()
        if slot is None:
            slot = self._preempt_for(choice, now)
            if slot is None:
                return                    # saturated; wait for a retire
        self._sched.charge(choice)        # slot secured: commit the pick
        if self._prefill_batch > 1 and len(self._sched_pending) > 1:
            self._begin_group(choice, slot, now)
        else:
            staged = self._pop_pending(choice)
            self._begin_prefill(choice, staged, slot, now)

    def _pop_pending(self, req):
        for i, (r, _s) in enumerate(self._sched_pending):
            if r.id == req.id:
                return self._sched_pending.pop(i)[1]
        raise KeyError(req.id)     # unreachable: select() picked from pending

    def _finish_unslotted(self, req, state: str, now: float) -> None:
        req._finish(state, now)
        profiler.record_serving({CANCELLED: "cancelled",
                                 EXPIRED: "expired"}[state])
        self._sched.forget(req)

    def _apply_shed(self, shed, now: float) -> None:
        for req in shed:
            req._finish(SHED, now, error=self._sched.shed_error(req, now))
            profiler.record_serving("shed")
            profiler.record_tenant(req.tenant, "shed")
            tracer.instant("serving/shed", cat="serving",
                           args={"id": req.id, "tenant": req.tenant,
                                 "priority": req.priority})
            self._sched.forget(req)
        if shed:
            gone = {r.id for r in shed}
            self._sched_pending = [(r, s) for r, s in self._sched_pending
                                   if r.id not in gone]
            profiler.record_sched(self._sched.stats())

    def _preempt_for(self, incoming, now: float) -> Optional[int]:
        """Park a lower-tier running request so ``incoming`` gets its
        decode slot; returns the freed slot (None: nobody preemptible)."""
        running = [self._reqs[int(s)] for s in np.flatnonzero(self._active)]
        victim = self._sched.pick_victim(running, incoming)
        if victim is None:
            return None
        slot = next(i for i, r in enumerate(self._reqs)
                    if r is not None and r.id == victim.id)
        self._park(slot, now)
        return slot

    def _park(self, slot: int, now: float) -> None:
        """Freeze a running request out of its decode slot — exactly the
        state a drain() entry carries (kept device-resident) — and queue
        it for :meth:`_resume_parked`. The page plus (tok, p, limit)
        cursors ARE the decode chain, so resume is bit-exact for the same
        reason adopt() is."""
        req = self._reqs[slot]
        entry = {
            "req": req, "tot": self._TOT,
            "page": kv.slot_page(self._caches, slot),
            "tok": int(self._tok[slot]), "p": int(self._p[slot]),
            "limit": int(self._limit[slot]), "left": int(self._left[slot]),
            "temp": float(self._temp[slot]), "topk": int(self._topk[slot]),
            "seed": int(self._seed[slot]),
            "dec_emitted": bool(self._dec_emitted[slot]),
        }
        if self._spec is not None:
            # in-flight drafts park with the slot (pure proposals — no K/V
            # committed for them yet) and resume where they left off
            entry["draft"] = self._draft[slot].tolist()
            entry["dlen"] = int(self._dlen[slot])
            self._dlen[slot] = 0
        self._parked.append(entry)
        req._set_state(PENDING)
        self._sched.note_preempt()
        profiler.record_serving("preempted")
        profiler.record_tenant(req.tenant, "preempted")
        tracer.instant("serving/preempt", cat="serving",
                       args={"id": req.id, "slot": slot,
                             "p": int(self._p[slot]), "tenant": req.tenant,
                             "priority": req.priority})
        self._reqs[slot] = None
        self._active[slot] = False
        self._tok[slot] = 0
        self._p[slot] = 0
        self._limit[slot] = 0
        self._left[slot] = 0
        self._temp[slot] = 0.0
        self._topk[slot] = 0
        self._seed[slot] = 0
        self._dec_emitted[slot] = False

    def _resume_parked(self, now: float) -> None:
        """Re-slot parked requests (FIFO) while slots are free — unless a
        pending request outranks the parked one, in which case the free
        slot is left for admission (don't hand the slot straight back to
        the tier that just lost it)."""
        while self._parked:
            slot = self._free_slot()
            if slot is None:
                return
            e = self._parked[0]
            req = e["req"]
            if req._cancelled() or req._expired(now):
                self._parked.pop(0)
                self._finish_unslotted(
                    req, CANCELLED if req._cancelled() else EXPIRED, now)
                continue
            my_rank = self._sched.tier(req).rank
            if any(self._sched.tier(r).rank < my_rank
                   for r, _ in self._sched_pending):
                return
            self._parked.pop(0)
            page = kv.device_page(e["page"])
            self._ensure_capacity(e["tot"])
            if e["tot"] < self._TOT:
                page = kv.promote(page, self._TOT)
            self._merge_page(page, slot)
            self._tok[slot] = e["tok"]
            self._p[slot] = e["p"]
            self._limit[slot] = e["limit"]
            self._left[slot] = e["left"]
            self._temp[slot] = e["temp"]
            self._topk[slot] = e["topk"]
            self._seed[slot] = e["seed"]
            self._t_admit[slot] = now
            self._dec_emitted[slot] = e["dec_emitted"]
            if self._spec is not None and e.get("dlen"):
                n = min(int(e["dlen"]), self._spec.k)
                self._draft[slot, :n] = e["draft"][:n]
                self._dlen[slot] = n
            self._active[slot] = True
            self._reqs[slot] = req
            req._set_state(RUNNING)
            self._sched.note_resume()
            profiler.record_serving("resumed")
            tracer.instant("serving/resume", cat="serving",
                           args={"id": req.id, "slot": slot, "p": e["p"],
                                 "tenant": req.tenant})

    def _begin_group(self, first, first_slot: int, now: float) -> None:
        """Collect up to ``prefill_batch`` fair-share winners (bounded by
        free slots) and start ONE batched prefill over their packed
        prompts (``mxtpu.sched.admission``)."""
        picked = [(first, self._pop_pending(first), first_slot)]
        taken = {first_slot}
        while len(picked) < self._prefill_batch and self._sched_pending:
            slot = self._free_slot(exclude=taken)
            if slot is None:
                break
            choice, shed = self._sched.select(
                [r for r, _ in self._sched_pending], now)
            self._apply_shed(shed, now)
            if choice is None:
                break
            self._sched.charge(choice)    # joins the group: slot reserved
            picked.append((choice, self._pop_pending(choice), slot))
            taken.add(slot)
        if len(picked) == 1:
            self._begin_prefill(first, picked[0][1], first_slot, now)
            return
        from ..sched.admission import PrefillGroup
        PB = max(s.shape[1] for _, s, _ in picked)
        members = []
        for req, staged, slot in picked:
            t0 = len(req.prompt)
            req._set_state(RUNNING)
            profiler.record_serving("admitted")
            profiler.record_serving("queue_wait_ms_last",
                                    (now - req.t_submit) * 1e3)
            tracer.instant("serving/admit", cat="serving",
                           args={"id": req.id, "slot": slot,
                                 "tenant": req.tenant,
                                 "queue_wait_ms": round(
                                     (now - req.t_submit) * 1e3, 3)})
            m, blocks = 0, None
            if self._prefix is not None and req.use_prefix_cache \
                    and t0 - 1 >= kv.PrefixCache.BLOCK:
                m, blocks, path = self._prefix.match(req.prompt, t0 - 1)
                # the pins only guard the tree nodes; the block arrays stay
                # alive through `blocks` itself, so release before install
                # is safe here (PrefillGroup installs them immediately)
                self._prefix.release(path)
                self._note_prefix_probe(req, m)
            temp, topk, seed = _req_sampling(req)
            members.append({"req": req, "slot": slot, "t0": t0,
                            "start": m, "blocks": blocks or None,
                            "left": req.max_new, "done": False,
                            "t_start": now, "temp": temp, "topk": topk,
                            "seed": seed})
        self._pfg = PrefillGroup(self._model, members, self._prefill_batch,
                                 PB, self._kv_dtype, self._quant)
        # the group page must join the mesh's device set before the first
        # batched-prefill dispatch (the slot dim shards when divisible,
        # heads on tp — same filter path as the full cache)
        self._pfg.page = self._place_caches(self._pfg.page)
        profiler.record_serving("prefill_groups")
        tracer.instant("serving/prefill_group", cat="serving",
                       args={"ids": [mm["req"].id for mm in members],
                             "bucket": PB, "rows": len(members)})

    def _prefill_group_chunk(self) -> None:
        """Advance the batched prefill by ONE fixed-budget chunk (the same
        stall bound as the scalar path — one chunk's work per turn, shared
        by all members); emit each member's valid tokens, finish members
        that complete at admission, and at scan end merge every survivor
        into its reserved slot."""
        g = self._pfg
        now = time.monotonic()
        for mem in g.members:
            req = mem["req"]
            if mem["done"]:
                continue
            if req._cancelled():
                mem["done"] = True
                self._finish_unslotted(req, CANCELLED, now)
            elif req._expired(now):
                mem["done"] = True
                self._finish_unslotted(req, EXPIRED, now)
        if all(m["done"] for m in g.members):
            self._pfg = None
            return
        csize = min(self.prefill_chunk, g.remaining())
        live_ids = [m["req"].id for m in g.members if not m["done"]]
        with tracer.span("serving/prefill_chunk", cat="serving",
                         args={"ids": live_ids, "start": g.cursor,
                               "chunk": csize, "bucket": g.PB,
                               "batched": len(live_ids)}):
            from ..sched.admission import build_prefill_batch
            with self._scope():
                fn = self._prefill_fns.get_or_build(
                    ("batch", g.N, g.PB, csize),
                    lambda: build_prefill_batch(
                        self._model, g.N, g.PB, csize, quant=self._quant,
                        decode_kernel=self._decode_kernel))
                page, prev, lastfed, outs = fn(
                    self._params,
                    *(inp if i == 0 else self._dev(inp)
                      for i, inp in enumerate(g.chunk_inputs())))
            outs_np = np.asarray(outs)
        profiler.record_serving("prefill_chunks")
        self._sched.observe_prefill(csize * len(live_ids),
                                    time.monotonic() - now)
        for n, mem in enumerate(g.members):
            if mem["done"]:
                continue
            req = mem["req"]
            j_lo, j_hi = g.valid_range(n, csize)
            if j_lo >= j_hi:
                continue
            valid = outs_np[j_lo:j_hi, n]
            done_t = time.monotonic()
            first = req.t_first_token is None
            left = req._emit(valid.tolist(), done_t)
            profiler.record_serving("tokens_out", mem["left"] - left)
            self._sched.charge_tokens(req.tenant, mem["left"] - left)
            mem["left"] = left
            if first:
                self._note_first_token(req, done_t, mem["t_start"])
            if left == 0:
                # short request: completed inside the group, never decodes.
                # NB: slice the chunk's OUTPUT page — g.page is pre-advance
                # here (advance runs after this loop), and inserting the
                # stale rows would seed the prefix tree with blocks the
                # scan hasn't written yet
                mem["done"] = True
                self._insert_prefix(req, kv.slot_page(page, n),
                                    upto=g.cursor + csize)
                req._finish(DONE, done_t)
                profiler.record_serving("prefills")
                profiler.record_serving("completed")
                profiler.record_tenant(req.tenant, "completed")
                profiler.record_tenant(req.tenant, "goodput_tokens",
                                       req.max_new)
                self._sched.forget(req)
                tracer.instant("serving/retire", cat="serving",
                               args={"id": req.id, "state": DONE,
                                     "tenant": req.tenant,
                                     "at_admission": True})
        g.advance(page, prev, lastfed, csize)
        if g.remaining() == 0:
            self._finish_group()
        profiler.record_sched(self._sched.stats())

    def _finish_group(self) -> None:
        """Batched-prefill phase three: every member row is scanned to the
        bucket end — merge each survivor's page row into its reserved slot
        and hand it to the decode batch (the groupwise twin of
        :meth:`_finish_prefill`)."""
        g, self._pfg = self._pfg, None
        prev_np = np.asarray(g.prev)
        now = time.monotonic()
        survivors = [(n, m) for n, m in enumerate(g.members)
                     if not m["done"]]
        if not survivors:
            return
        need = max([g.PB] + [kv.bucket32(m["req"].total,
                                         self._model._max_len)
                             for _n, m in survivors])
        self._ensure_capacity(need)
        for n, mem in survivors:
            req = mem["req"]
            slot = mem["slot"]
            self._insert_prefix(req, g.member_page(n), upto=mem["t0"] - 1)
            self._merge_page(g.member_page(n), slot)
            self._tok[slot] = int(prev_np[n])    # the token at position PB
            self._p[slot] = g.PB                 # next position to feed
            self._limit[slot] = req.total - 1
            self._active[slot] = True
            self._left[slot] = mem["left"]
            self._temp[slot] = mem["temp"]
            self._topk[slot] = mem["topk"]
            self._seed[slot] = mem["seed"]
            self._t_admit[slot] = now
            self._dec_emitted[slot] = False
            self._reqs[slot] = req
            profiler.record_serving("prefills")

    def _note_prefix_probe(self, req, m: int) -> None:
        """Prefix-probe accounting shared by scalar and group admission
        (partial-block hits count the sub-block tail separately)."""
        if m:
            profiler.record_serving("prefix_hits")
            profiler.record_serving("prefix_hit_tokens", m)
            if m % kv.PrefixCache.BLOCK:
                profiler.record_serving("prefix_partial_hits")
                profiler.record_serving("prefix_partial_tokens",
                                        m % kv.PrefixCache.BLOCK)
            tracer.instant("serving/prefix_hit", cat="serving",
                           args={"id": req.id, "tokens": m})
        else:
            profiler.record_serving("prefix_misses")
            tracer.instant("serving/prefix_miss", cat="serving",
                           args={"id": req.id})

    def _note_first_token(self, req, done_t: float,
                          t_start: float) -> None:
        profiler.record_serving("ttft_ms_last",
                                (done_t - req.t_submit) * 1e3)
        profiler.record_serving("prefill_ms_last",
                                (done_t - t_start) * 1e3)
        if self._sched is not None:
            profiler.record_tenant(req.tenant, "ttft_ms_last",
                                   (done_t - req.t_submit) * 1e3)
        tracer.instant("serving/first_token", cat="serving",
                       args={"id": req.id,
                             "ttft_ms": round(
                                 (done_t - req.t_submit) * 1e3, 3)})

    def _begin_prefill(self, req: ServingRequest, staged, slot: int,
                       now: float) -> None:
        """Admission, phase one: probe the radix prefix cache, seed the
        page with any cached rows, and park the partial-prefill cursor at
        the first position that still needs computing."""
        t0 = len(req.prompt)
        PB = staged.shape[1]
        req._set_state(RUNNING)
        profiler.record_serving("admitted")
        profiler.record_serving("queue_wait_ms_last",
                                (now - req.t_submit) * 1e3)
        tracer.instant("serving/admit", cat="serving",
                       args={"id": req.id, "slot": slot,
                             "queue_wait_ms": round(
                                 (now - req.t_submit) * 1e3, 3)})
        page = kv.empty_page(self._model, PB, self._kv_dtype, self._quant)
        m = 0
        # only FORCED prompt positions are reusable (limit = t0 - 1: the
        # last prompt position seeds the feedback chain and is recomputed)
        if self._prefix is not None and req.use_prefix_cache \
                and t0 - 1 >= kv.PrefixCache.BLOCK:
            m, blocks, path = self._prefix.match(req.prompt, t0 - 1)
            if m:
                # COPY the cached rows into this request's page (functional
                # .at[].set — the tree's rows are never aliased mutably;
                # quantized blocks install their bytes, never re-quantize)
                page = kv.install_rows(page, blocks, m)
                self._prefix.release(path)
            self._note_prefix_probe(req, m)
        temp, topk, seed = _req_sampling(req)
        # scan from the last BLOCK boundary, not the raw match length: a
        # partial-block hit (m % 32 != 0) re-feeds its sub-block tail as an
        # identical rewrite (K/V at p is a pure function of tokens 0..p),
        # which keeps the (PB, csize) program-key space bounded — an
        # arbitrary mid-block cursor would mint a fresh multi-second XLA
        # compile per distinct tail length
        t_scan = m - (m % kv.PrefixCache.BLOCK)
        # mesh mode: the fresh page must live on the mesh's device set
        # before it rides a dispatch next to the placed params (jnp-created
        # arrays are committed to the default device)
        page = self._place_caches(page)
        self._pf = {"req": req, "prompt": staged.data, "page": page,
                    "t": t_scan, "prev": 0, "t0": t0, "PB": PB,
                    "left": req.max_new, "slot": slot, "t_start": now,
                    "temp": temp, "topk": topk, "seed": seed}

    def _prefill_chunk(self) -> None:
        """Admission, phase two (repeated): advance the partial prefill by
        ONE fixed-budget chunk, emitting any tokens past ``t0`` as they
        materialize; on reaching the bucket end, merge the page into the
        reserved slot and activate it for decode."""
        pf = self._pf
        req = pf["req"]
        now = time.monotonic()
        if req._cancelled():
            self._pf = None
            req._finish(CANCELLED, now)
            profiler.record_serving("cancelled")
            return
        if req._expired(now):
            self._pf = None
            req._finish(EXPIRED, now)
            profiler.record_serving("expired")
            return
        start = pf["t"]
        csize = min(self.prefill_chunk, pf["PB"] - start)
        with tracer.span("serving/prefill_chunk", cat="serving",
                         args={"id": req.id, "start": start,
                               "chunk": csize, "bucket": pf["PB"]}):
            with self._scope():
                fn = self._prefill_fns.get_or_build(
                    (pf["PB"], csize),
                    lambda: kv.build_prefill_chunk(
                        self._model, pf["PB"], csize, quant=self._quant,
                        decode_kernel=self._decode_kernel))
                page, outs = fn(
                    self._params, pf["page"], self._dev(pf["prompt"]),
                    self._dev(jnp.int32(pf["t0"])),
                    self._dev(jnp.int32(start)),
                    self._dev(jnp.full((1,), pf["prev"], jnp.int32)),
                    self._dev(jnp.full((1,), pf["temp"], jnp.float32)),
                    self._dev(jnp.full((1,), pf["topk"], jnp.int32)),
                    self._dev(jnp.full((1,), pf["seed"], jnp.uint32)))
            outs_np = np.asarray(outs)
        profiler.record_serving("prefill_chunks")
        if self._sched is not None:
            # scalar prefills must feed the rate EWMA too, or a sched-mode
            # engine with prefill_batch=1 never warms its shed estimator
            self._sched.observe_prefill(csize, time.monotonic() - now)
        pf["page"] = page
        pf["t"] = start + csize
        pf["prev"] = int(outs_np[-1])
        # outs[j] is the token FOR position start+j+1; generated tokens are
        # positions >= t0, i.e. indices j >= t0-1-start (see kv.py)
        valid = outs_np[max(pf["t0"] - 1 - start, 0):]
        if valid.size:
            done_t = time.monotonic()
            first = req.t_first_token is None
            left = req._emit(valid.tolist(), done_t)
            profiler.record_serving("tokens_out", pf["left"] - left)
            if self._sched is not None:
                self._sched.charge_tokens(req.tenant, pf["left"] - left)
            pf["left"] = left
            if first:
                self._note_first_token(req, done_t, pf["t_start"])
            if left == 0:
                # short request: completed at admission, never took a slot
                self._pf = None
                self._insert_prefix(req, page, upto=pf["t"])
                req._finish(DONE, done_t)
                profiler.record_serving("prefills")
                profiler.record_serving("completed")
                if self._sched is not None:
                    profiler.record_tenant(req.tenant, "completed")
                    profiler.record_tenant(req.tenant, "goodput_tokens",
                                           req.max_new)
                    self._sched.forget(req)
                # terminal timeline marker: every request's timeline ends in
                # a retire even when it never occupied a decode slot
                tracer.instant("serving/retire", cat="serving",
                               args={"id": req.id, "state": DONE,
                                     "at_admission": True})
                return
        if pf["t"] >= pf["PB"]:
            self._finish_prefill(pf)

    def _finish_prefill(self, pf: dict) -> None:
        """Admission, phase three: the whole bucket is prefilled — merge
        the page into the reserved slot row and hand the request to the
        decode batch."""
        req = pf["req"]
        slot = pf["slot"]
        self._pf = None
        self._insert_prefix(req, pf["page"], upto=pf["t0"] - 1)
        self._ensure_capacity(
            kv.bucket32(req.total, self._model._max_len))
        self._merge_page(pf["page"], slot)
        self._tok[slot] = pf["prev"]         # the token at position PB
        self._p[slot] = pf["PB"]             # next position to feed
        self._limit[slot] = req.total - 1
        self._active[slot] = True
        self._left[slot] = pf["left"]
        self._temp[slot] = pf["temp"]
        self._topk[slot] = pf["topk"]
        self._seed[slot] = pf["seed"]
        self._t_admit[slot] = time.monotonic()
        self._dec_emitted[slot] = False
        self._reqs[slot] = req
        profiler.record_serving("prefills")

    def _insert_prefix(self, req: ServingRequest, page, upto: int) -> None:
        """Seed the radix tree with this request's forced-prompt blocks
        (positions below ``upto``, whole 32-blocks only) so the NEXT
        request sharing the prefix skips their prefill."""
        if self._prefix is None or not req.use_prefix_cache:
            return
        created = self._prefix.insert(req.prompt, page,
                                      min(upto, len(req.prompt) - 1))
        if created:
            profiler.record_serving("prefix_inserts", created)
        if self._prefix.evictions > self._evict_seen:
            profiler.record_serving("prefix_evictions",
                                    self._prefix.evictions - self._evict_seen)
            self._evict_seen = self._prefix.evictions
        profiler.record_serving("prefix_cache_bytes", self._prefix.bytes)

    def _ensure_capacity(self, need: int) -> None:
        if self._TOT is None:
            self._TOT = need
            self._caches = self._place_caches(
                kv.empty_cache(self._model, self.slots, need,
                               self._kv_dtype, self._quant))
        elif need > self._TOT:
            with tracer.span("serving/kv_promote", cat="serving",
                             args={"from": self._TOT, "to": need}):
                self._caches = self._place_caches(
                    kv.promote(self._caches, need))
            self._TOT = need
            profiler.record_serving("kv_promotions")
        else:
            return
        profiler.record_serving("kv_bytes_resident",
                                kv.cache_nbytes(self._caches))

    # -- sharded placement (mesh mode; all identity when mesh is None) -------
    def _place_caches(self, caches):
        """Pin a freshly created / promoted / page-merged cache onto the
        canonical kv_cache sharding so dispatch-input shardings never drift
        from what the first trace keyed on (trace-once over shardings)."""
        if self._mesh is None:
            return caches
        from . import sharded
        return sharded.place_cache(caches, self._mesh, self._layout)

    def _merge_page(self, page, slot: int) -> None:
        """``kv.merge_page`` + re-pin: every eager host-side cache mutation
        funnels through here in mesh mode. The incoming page is placed
        FIRST — a parked/adopted page arrives committed to the default
        device, and an eager merge across mismatched device sets throws."""
        page = self._place_caches(page)
        self._caches = self._place_caches(
            kv.merge_page(self._caches, page, slot))

    def _scope(self):
        """Layout scope for program dispatch: under a mesh every dispatch
        (and therefore every first-call trace) runs with the serving layout
        active, so the step functions' activation constraints fire."""
        if self._mesh is None:
            return nullcontext()
        from ..parallel.fsdp import layout_scope
        return layout_scope(self._layout, self._mesh)

    def _dev(self, x):
        """Replicate a small dispatch input (slot-state vectors, prompt
        block, cursors) onto the mesh's device set. jnp-created arrays are
        committed to the default device, and a jit mixing them with the
        mesh-placed params throws; replicating through ONE NamedSharding
        also keeps the dispatch-input shardings identical across calls
        (trace-once)."""
        if self._mesh is None:
            return x
        import jax
        from ..parallel.mesh import NamedSharding, P
        return jax.device_put(x, NamedSharding(self._mesh, P()))

    def _decode_chunk(self) -> None:
        n_active = int(self._active.sum())
        span_args = {"active": n_active, "tot": self._TOT}
        if tracer.enabled():
            # tag the dispatch with the whole slot batch's request ids so
            # request_timeline()/per-request lanes can claim it (built only
            # under tracing — the off path stays a dict literal)
            span_args["ids"] = [self._reqs[int(s)].id
                                for s in np.flatnonzero(self._active)]
        t_dispatch = time.monotonic()
        with tracer.span("serving/decode", cat="serving", args=span_args):
            key = (self.slots, self._TOT, self.chunk)
            with self._scope():
                fn = self._decode_fns.get_or_build(
                    key, lambda: kv.build_decode(
                        self._model, *key, quant=self._quant,
                        decode_kernel=self._decode_kernel))
                caches, tok, p, toks, lives = fn(
                    self._params, self._caches,
                    self._dev(jnp.asarray(self._tok)),
                    self._dev(jnp.asarray(self._p)),
                    self._dev(jnp.asarray(self._active)),
                    self._dev(jnp.asarray(self._limit)),
                    self._dev(jnp.asarray(self._temp)),
                    self._dev(jnp.asarray(self._topk)),
                    self._dev(jnp.asarray(self._seed)))
            toks_np = np.asarray(toks)
            lives_np = np.asarray(lives)
        self._caches = caches
        self._tok = np.array(tok)   # owned copies: the slot state is
        self._p = np.array(p)       # mutated at retire/admit boundaries
        now = time.monotonic()
        profiler.record_serving("decode_steps")
        # re-assert per dispatch: these are assign-style stats, and callers
        # commonly reset_serving_stats() after warmup (which wiped the values
        # recorded at start()/cache creation)
        profiler.record_serving("engine", self.engine_id)
        profiler.record_serving("kv_dtype", self._kv_dtype_str)
        if self._decode_kernel_str is not None:
            profiler.record_serving("decode_kernel", self._decode_kernel_str)
        profiler.record_serving("kv_bytes_resident",
                                kv.cache_nbytes(self._caches))
        profiler.record_serving_occupancy(n_active, self.slots)
        emitted_total = 0
        for slot in np.flatnonzero(self._active):
            req = self._reqs[slot]
            fresh = toks_np[lives_np[:, slot], slot]
            if fresh.size:
                left = req._emit(fresh.tolist(), now)
                got = int(self._left[slot] - left)
                profiler.record_serving("tokens_out", got)
                emitted_total += got
                self._left[slot] = left
                if self._sched is not None:
                    self._sched.charge_tokens(req.tenant, got)
                if not self._dec_emitted[slot]:
                    self._dec_emitted[slot] = True
                    profiler.record_serving(
                        "first_decode_ms_last",
                        (now - self._t_admit[slot]) * 1e3)
                    tracer.instant("serving/first_decode", cat="serving",
                                   args={"id": req.id})
            if self._left[slot] == 0:
                self._retire(slot, DONE, now)
            elif req._cancelled():
                self._retire(slot, CANCELLED, now)
            elif req._expired(now):
                self._retire(slot, EXPIRED, now)
        if emitted_total:
            # dispatch wall clock amortized per emitted token — one sample
            # per dispatch into the serving/token_ms histogram
            profiler.record_serving(
                "token_ms_last", (now - t_dispatch) * 1e3 / emitted_total)
            # decode-only throughput series: full dispatch wall + its token
            # yield, so decode_tokens / decode_ms_total excludes prefill and
            # scheduler time (the quant_decode_speedup denominator)
            profiler.record_serving("decode_ms_last",
                                    (now - t_dispatch) * 1e3)
            profiler.record_serving("decode_tokens", emitted_total)
        if self._sched is not None:
            if emitted_total:
                self._sched.observe_decode(emitted_total, now - t_dispatch)
            profiler.record_sched(self._sched.stats())

    # -- speculative decode (mxtpu.serving.spec; spec-mode only below) -------
    def _spec_decode_turn(self) -> None:
        """One decode turn under speculation: dispatch the verify program
        when any slot holds drafts (a slot without them runs a plain
        single-position step INSIDE the same program — no retrace), fall
        back to the ordinary decode chunk when nobody does (a cold or
        miss-everywhere turn keeps plain-chunk throughput), then propose
        the NEXT turn's drafts from each survivor's updated stream. The
        end-of-turn proposal order is what makes a drain() between turns
        carry genuine in-flight drafts."""
        if int(self._dlen.sum()) > 0:
            self._verify_chunk()
        else:
            self._decode_chunk()
        self._propose_drafts()

    def _propose_drafts(self) -> None:
        """Refill the per-slot draft buffers for the next dispatch. Greedy
        slots only — a sampled slot's next token is a draw, not an argmax,
        so speculation degrades it to dlen=0 plain decode per slot (the
        verify program re-checks ``temp`` on-device as well). Proposals
        are clipped to the slot's remaining live positions; the final
        token of a request always decodes plain."""
        k = self._spec.k
        for slot in np.flatnonzero(self._active):
            slot = int(slot)
            self._dlen[slot] = 0
            if self._temp[slot] > 0:
                continue
            room = int(self._limit[slot]) - int(self._p[slot]) - 1
            if room <= 0:
                continue
            req = self._reqs[slot]
            prop = self._drafter.propose(req.prompt + req.tokens(),
                                         min(k, room))
            n = min(len(prop), k, room)
            if n > 0:
                self._draft[slot, :n] = prop[:n]
                self._dlen[slot] = n
                profiler.record_serving("tokens_drafted", n)
        self._publish_ngram_stats()

    def _publish_ngram_stats(self) -> None:
        """Mirror the PrefixCache's n-gram lookup counters into the serving
        stats as deltas (same idiom as prefix_evictions)."""
        if self._prefix is None:
            return
        dh = self._prefix.ngram_hits - self._ngram_hits_seen
        dm = self._prefix.ngram_misses - self._ngram_misses_seen
        if dh:
            profiler.record_serving("ngram_hits", dh)
        if dm:
            profiler.record_serving("ngram_misses", dm)
        self._ngram_hits_seen = self._prefix.ngram_hits
        self._ngram_misses_seen = self._prefix.ngram_misses

    def _verify_chunk(self) -> None:
        """Dispatch ONE batched verify: all k+1 positions of every slot
        scored by a single target forward, greedy accept/reject on-device,
        then exactly one host readback of (outs, lives) — the sanctioned
        readback tpulint R009 polices; per-token ``.item()`` loops here
        would serialize a device sync per accepted token."""
        k = self._spec.k
        n_active = int(self._active.sum())
        span_args = {"active": n_active, "tot": self._TOT, "k": k}
        if tracer.enabled():
            span_args["ids"] = [self._reqs[int(s)].id
                                for s in np.flatnonzero(self._active)]
        t_dispatch = time.monotonic()
        with tracer.span("serving/verify", cat="serving", args=span_args):
            key = (self.slots, self._TOT, k)
            with self._scope():
                fn = self._verify_fns.get_or_build(
                    key, lambda: kv.build_verify(
                        self._model, *key, quant=self._quant,
                        decode_kernel=self._decode_kernel))
                caches, tok, p, outs, lives = fn(
                    self._params, self._caches,
                    self._dev(jnp.asarray(self._tok)),
                    self._dev(jnp.asarray(self._p)),
                    self._dev(jnp.asarray(self._active)),
                    self._dev(jnp.asarray(self._limit)),
                    self._dev(jnp.asarray(self._temp)),
                    self._dev(jnp.asarray(self._topk)),
                    self._dev(jnp.asarray(self._seed)),
                    self._dev(jnp.asarray(self._draft)),
                    self._dev(jnp.asarray(self._dlen)))
            outs_np = np.asarray(outs)
            lives_np = np.asarray(lives)
        self._caches = caches
        self._tok = np.array(tok)
        self._p = np.array(p)
        now = time.monotonic()
        profiler.record_serving("decode_steps")
        profiler.record_serving("spec_dispatches")
        profiler.record_serving("engine", self.engine_id)
        profiler.record_serving("kv_dtype", self._kv_dtype_str)
        if self._decode_kernel_str is not None:
            profiler.record_serving("decode_kernel", self._decode_kernel_str)
        profiler.record_serving("kv_bytes_resident",
                                kv.cache_nbytes(self._caches))
        profiler.record_serving_occupancy(n_active, self.slots)
        emitted_total = 0
        for slot in np.flatnonzero(self._active):
            slot = int(slot)
            req = self._reqs[slot]
            fresh = outs_np[slot, lives_np[slot]]
            drafted = int(self._dlen[slot])
            self._dlen[slot] = 0          # consumed, hit or miss
            if fresh.size:
                left = req._emit(fresh.tolist(), now)
                got = int(self._left[slot] - left)
                profiler.record_serving("tokens_out", got)
                emitted_total += got
                self._left[slot] = left
                if self._sched is not None:
                    self._sched.charge_tokens(req.tenant, got)
                # accept-length sample: tokens this slot emitted from one
                # dispatch (1 = no speculation win, k+1 = full accept)
                e = int(fresh.size)
                profiler.record_serving("accept_len_last", e)
                confirmed = min(max(e - 1, 0), drafted)
                if confirmed:
                    profiler.record_serving("tokens_accepted", confirmed)
                if drafted - confirmed:
                    profiler.record_serving("tokens_rejected",
                                            drafted - confirmed)
                if not self._dec_emitted[slot]:
                    self._dec_emitted[slot] = True
                    profiler.record_serving(
                        "first_decode_ms_last",
                        (now - self._t_admit[slot]) * 1e3)
                    tracer.instant("serving/first_decode", cat="serving",
                                   args={"id": req.id})
            if self._left[slot] == 0:
                self._retire(slot, DONE, now)
            elif req._cancelled():
                self._retire(slot, CANCELLED, now)
            elif req._expired(now):
                self._retire(slot, EXPIRED, now)
        if emitted_total:
            profiler.record_serving(
                "token_ms_last", (now - t_dispatch) * 1e3 / emitted_total)
            profiler.record_serving("decode_ms_last",
                                    (now - t_dispatch) * 1e3)
            profiler.record_serving("decode_tokens", emitted_total)
        if self._sched is not None:
            if emitted_total:
                self._sched.observe_decode(emitted_total, now - t_dispatch)
            profiler.record_sched(self._sched.stats())

    def _retire(self, slot: int, state: str, now: float) -> None:
        req = self._reqs[slot]
        req._finish(state, now)
        profiler.record_serving({DONE: "completed", CANCELLED: "cancelled",
                                 EXPIRED: "expired"}[state])
        if self._sched is not None:
            self._sched.forget(req)
            profiler.record_tenant(
                req.tenant, {DONE: "completed", CANCELLED: "cancelled",
                             EXPIRED: "expired"}[state])
            if state == DONE:
                profiler.record_tenant(req.tenant, "goodput_tokens",
                                       len(req.tokens()))
        tracer.instant("serving/retire", cat="serving",
                       args={"id": req.id, "state": state})
        self._reqs[slot] = None
        self._active[slot] = False
        self._tok[slot] = 0
        self._p[slot] = 0
        self._limit[slot] = 0
        self._left[slot] = 0
        self._temp[slot] = 0.0
        self._topk[slot] = 0
        self._seed[slot] = 0
        self._dec_emitted[slot] = False
        if self._spec is not None:
            self._dlen[slot] = 0

    def _maybe_log(self) -> None:
        """Per-interval engine log (``MXTPU_SERVING_LOG_S``): one line with
        the TTFT decomposition and cache/occupancy health."""
        if not self._log_s:
            return
        now = time.monotonic()
        if now < self._next_log:
            return
        self._next_log = now + self._log_s
        s = profiler.get_serving_stats()
        _log.info(
            "serving: %d in-flight / %d done; ttft last %.1f ms "
            "(queue %.1f + prefill %.1f), first-decode %.1f ms; "
            "occupancy %.2f; prefix hit-rate %.2f (%d hits, %.1f MB)",
            int(self._active.sum()) + (1 if self._pf is not None else 0),
            s["completed"], s["ttft_ms_last"], s["queue_wait_ms_last"],
            s["prefill_ms_last"], s["first_decode_ms_last"],
            s["slot_occupancy"], s["prefix_hit_rate"], s["prefix_hits"],
            s["prefix_cache_bytes"] / (1 << 20))

    def _shutdown_sweep(self) -> None:
        """Terminal sweep: nothing submitted may block forever — in-slot,
        mid-prefill, staged, and still-queued requests all finish
        CANCELLED."""
        self._stop.set()     # scheduler may exit via error with stop unset
        now = time.monotonic()
        for slot in np.flatnonzero(self._active):
            self._retire(int(slot), CANCELLED, now)
        if self._pf is not None:
            pf, self._pf = self._pf, None
            pf["req"]._finish(CANCELLED, now)
            profiler.record_serving("cancelled")
        if self._pfg is not None:
            g, self._pfg = self._pfg, None
            for mem in g.members:
                if not mem["done"]:
                    mem["req"]._finish(CANCELLED, now)
                    profiler.record_serving("cancelled")
        for e in self._parked:
            e["req"]._finish(CANCELLED, now)
            profiler.record_serving("cancelled")
        self._parked = []
        for req, _s in self._sched_pending:
            req._finish(CANCELLED, now)
            profiler.record_serving("cancelled")
        self._sched_pending = []
        # staged by the feed but never admitted: drain until the producer's
        # end marker (it sees the stop flag within its 0.1s poll)
        deadline = time.monotonic() + 5.0
        while self._feed is not None and time.monotonic() < deadline:
            try:
                item = self._feed.poll(timeout=0.2)
            except StopIteration:
                break
            except Exception:   # producer died mid-teardown: nothing to drain
                break
            if item is None:
                continue
            item[0]._finish(CANCELLED, now)
            profiler.record_serving("cancelled")
        while True:                    # never even staged
            try:
                req = self._submit_q.get_nowait()
            except queue.Empty:
                break
            req._finish(CANCELLED, now)
            profiler.record_serving("cancelled")


def audit_key_specs(max_len: int, slots: int, chunk: int, prefill_chunk: int,
                    k: int, bucket=None):
    """The live ProgramCache key sites above, as data — the program
    auditor's retrace-closure proof (rule A301).  Each row is ``(name,
    keys_of, component_bounds)``: ``keys_of(prompt_len, total)`` returns
    every program key a request with that geometry can dispatch under
    (prefill returns one key per chunk step), and ``component_bounds[i]``
    caps how many distinct values component ``i`` may take across the
    WHOLE admissible request domain.  The product of the bounds caps the
    program count, which is exactly the trace-once contract: bucketing is
    what closes the key set, so a raw length leaking into a key (the
    seeded ``--expect-fail`` case passes ``bucket=lambda n: n``) blows a
    component's bound and the audit fails before the recompile storm
    ships.  Keep these in lockstep with the ``get_or_build`` tuples in
    ``_dispatch_decode`` / ``_dispatch_verify`` / the two prefill sites."""
    b = bucket or (lambda n: kv.bucket32(n, max_len))
    nb = (max_len + 31) // 32          # distinct 32-token bucket values

    def decode_keys(plen, total):
        return [(slots, b(total), chunk)]

    def verify_keys(plen, total):
        return [(slots, b(total), k)]

    def prefill_keys(plen, total):
        PB = b(plen)
        return [(PB, min(prefill_chunk, PB - s))
                for s in range(0, PB, prefill_chunk)]

    return [
        ("serving_decode", decode_keys, (1, nb, 1)),
        ("serving_verify", verify_keys, (1, nb, 1)),
        ("serving_prefill", prefill_keys, (nb, nb + 1)),
    ]
