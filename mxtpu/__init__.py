"""mxtpu — a TPU-native deep-learning framework with the capabilities of Apache MXNet.

Built from scratch on JAX/XLA/Pallas/pjit (SURVEY.md is the blueprint): the reference's
dependency engine, graph passes, and CUDA kernels collapse into XLA; the NCCL/ps-lite
KVStore becomes a collectives layer over ICI/DCN; the user-facing capability surface
(NDArray eager ops, autograd, Gluon-style modules, Module.fit, KVStore, data pipelines,
model zoo) is preserved.

Top-level layout mirrors the ``mx.*`` namespaces:

* ``mxtpu.nd`` — imperative NDArray ops (mx.nd)
* ``mxtpu.autograd`` — record/backward (mx.autograd)
* ``mxtpu.gluon`` — Block/HybridBlock/Trainer/data/model_zoo (mx.gluon)
* ``mxtpu.mod`` — Module API (mx.mod)
* ``mxtpu.io`` — data iterators (mx.io)
* ``mxtpu.kv`` — KVStore (mx.kvstore)
* ``mxtpu.parallel`` — device meshes, collectives, sharded training (TPU-first, new)
"""

import time as _time

_T_IMPORT = _time.perf_counter_ns()      # the import's own clock: its spans
import os as _os                         # are recorded at the last line

# pod bring-up MUST precede any backend-initializing import (see mxtpu/dist.py);
# reference parity: ps-lite InitPSEnv runs at library load (kvstore.h:257)
if _os.environ.get("DMLC_NUM_WORKER", "1") not in ("", "0", "1"):
    from . import dist as _dist
    _dist.auto_initialize()

_t = _time.perf_counter_ns()
import jax as _jax                       # 0 where the caller imported it
_T_JAX = (_t, _time.perf_counter_ns() - _t)   # start, duration

from .base import __version__
from . import base
from . import context
from .context import Context, cpu, cpu_pinned, current_context, device_mesh, gpu, num_devices, num_gpus, num_tpus, tpu
from . import rng
from . import ops
from . import ndarray
from . import ndarray as nd
from . import autograd
from . import random
from .ndarray import NDArray

# subsystem imports (populated as the build proceeds; see SURVEY.md §7 build order)
import importlib as _importlib

_SUBSYSTEMS = ["initializer", "optimizer", "lr_scheduler", "metric", "callback",
               "io", "recordio", "kvstore", "symbol", "gluon", "module", "parallel",
               "profiler", "test_utils", "model", "image", "visualization",
               "contrib", "operator", "monitor", "rtc", "capi", "rnn",
               "attribute", "engine", "serving", "step_cache", "checkpoint",
               "device_feed", "analysis", "observability", "resilience",
               "quant"]
for _name in _SUBSYSTEMS:
    try:
        globals()[_name] = _importlib.import_module(f".{_name}", __name__)
    except ModuleNotFoundError as _e:
        if f"mxtpu.{_name}" not in str(_e):
            raise

if "kvstore" in globals():
    kv = globals()["kvstore"]
if "symbol" in globals():
    sym = globals()["symbol"]
    Symbol = sym.Symbol
if "module" in globals():
    mod = globals()["module"]
    Module = mod.Module
if "model" in globals():
    save_checkpoint = model.save_checkpoint
    load_checkpoint = model.load_checkpoint
if "attribute" in globals():
    AttrScope = attribute.AttrScope


# what the import cost, by the names docs/observability.md gives them:
# ``import/mxtpu`` from the first line to here with ``import/jax`` inside it,
# and the host's memory at its end (no backend exists yet, and none is made)
_import_span = observability.tracer.record_span(
    "import/mxtpu", _T_IMPORT, _time.perf_counter_ns() - _T_IMPORT)
observability.tracer.record_span("import/jax", *_T_JAX, parent=_import_span)
observability.metrics.mark_memory("import/mxtpu")
del _import_span, _t
