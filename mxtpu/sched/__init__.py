"""mxtpu.sched — multi-tenant SLO-aware serving control plane.

Sits between the ``ServingEngine`` admission queue and its scheduler
thread, strictly OPT-IN (``ServingEngine(sched=...)``; without it the
engine is byte-identical to the plain FIFO path):

* :mod:`.policy` — priority tiers + weighted fair share across tenants,
  latency-tier preemption of decode slots (park the paged-KV block,
  re-enter the queue, bit-exact on resume), and deadline shedding with a
  distinct :exc:`~mxtpu.serving.api.ShedError` so callers can tell
  "rejected early under overload" from "queue full".
* :mod:`.admission` — batched prefill: the suffixes of several pending
  prompts packed into ONE fixed-budget chunk program's batch dimension,
  keyed so programs never retrace per prompt mix.
* :mod:`.autoscale` — a controller reading the PR 15 exporter histograms
  (TTFT p99, queue-wait p99, slot occupancy) against per-tier SLO
  targets and driving ``ElasticRun.request_resize`` / a drain→adopt
  respawn callable, with hysteresis, cooldown, and a dry-run mode.
* :mod:`.replay` — deterministic bursty / diurnal / heavy-tail arrival
  traces over shared-prefix multi-tenant populations, the workload
  ``tests/test_router_guard.py`` replays through two replicas.

See ``docs/serving.md`` (scheduling section) and
``docs/observability.md`` (autoscaler signal table).
"""

from .admission import PrefillGroup, build_prefill_batch
from .autoscale import AutoscalePolicy, Autoscaler
from .policy import DEFAULT_TIERS, SLOPolicy, SLOScheduler, TierSpec
from .replay import (KINDS, TenantProfile, TrafficRequest, TrafficTrace,
                     make_trace)

__all__ = ["SLOPolicy", "SLOScheduler", "TierSpec", "DEFAULT_TIERS",
           "PrefillGroup", "build_prefill_batch",
           "Autoscaler", "AutoscalePolicy",
           "TrafficRequest", "TenantProfile", "TrafficTrace", "make_trace",
           "KINDS"]
