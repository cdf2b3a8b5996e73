"""mxtpu.serving — the online serving subsystem.

Two serving modes, one package:

* **Offline / throughput** — :class:`ChainedPredictor` (the original
  ``mxtpu/serving.py`` surface, unchanged): one compiled scan over a stack
  of pre-collected batches, amortizing the per-call dispatch floor.
* **Online / latency** — :class:`ServingEngine`: continuous batching over a
  fixed slot batch with bucketed KV admission, decode-overlapped chunked
  prefill, shared-prefix radix KV reuse, per-request
  :class:`SamplingParams`, deadlines, cancellation, and explicit
  backpressure. ``submit()`` from any thread; greedy output is bit-exact
  with per-request ``TransformerLM.generate``.

See ``docs/serving.md`` for architecture, knobs, and what is measured.
"""

from .api import (CANCELLED, DONE, EXPIRED, PENDING, RUNNING, SHED, TIERS,
                  DeadlineExceeded, HandoffMismatch, QueueFullError,
                  RequestCancelled, SamplingParams, ServingConfig,
                  ServingRequest, ShedError)
from .chained import ChainedPredictor
from .engine import ServingEngine, ServingHandoff
from .router import Replica, Router, RouterRequest
from .spec import Drafter, ModelDrafter, NgramDrafter, SpecConfig
from . import kv

__all__ = ["ChainedPredictor", "ServingEngine", "ServingHandoff",
           "ServingRequest", "SamplingParams", "ServingConfig",
           "Router", "Replica", "RouterRequest",
           "SpecConfig", "Drafter", "NgramDrafter", "ModelDrafter",
           "QueueFullError", "RequestCancelled", "DeadlineExceeded",
           "ShedError", "HandoffMismatch", "TIERS",
           "PENDING", "RUNNING", "DONE", "CANCELLED", "EXPIRED", "SHED",
           "kv"]
