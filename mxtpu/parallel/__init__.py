"""TPU-first parallelism: meshes, collectives, sharded data-parallel training.

This package is the re-imagining of the reference's distributed stack (SURVEY.md §2.3):
Comm/NCCL/ps-lite → XLA collectives over ICI/DCN; DataParallelExecutorGroup → sharded
SPMD steps; ``ctx_group`` model parallelism → pjit shardings. Long-context sequence
parallelism lives in ``ring_attention`` (K/V rotation, O(T/n) memory) and
``ulysses`` (all-to-all head/sequence reshuffle, 2 collectives).

``moe`` holds two mixture-of-experts layers for two jobs: ``SparseExperts``,
the dropless top-k layer a model trains with, as one chip of an
expert-parallel deployment holds it (told which experts it holds, no
exchange); and ``expert_parallel_ffn``, the older top-1, capacity-drop hook
that shows the all-to-all exchange over an ``ep`` axis and nothing else.
"""

from . import collectives
from . import mesh
from .collectives import (all_gather, all_to_all, all_to_all_array,
                          allgather_array, allreduce, allreduce_array,
                          allreduce_processes, barrier, broadcast_array,
                          broadcast_processes, pmean, ppermute,
                          process_barrier, psum, reduce_scatter,
                          reduce_scatter_array)
from .data_parallel import DataParallelTrainer, place, replicate, shard_batch
from .mesh import (Mesh, NamedSharding, P, data_axis_names,
                   data_parallel_mesh, data_size, dp_axis_name, dp_size,
                   force_virtual_cpu_devices, fsdp_axis_name, fsdp_size,
                   get_default_mesh, make_mesh, set_default_mesh)
from . import zero
from .zero import ZeroLayout, zero_bucket_bytes, zero_enabled
from . import fsdp
from .fsdp import (SpecLayout, compose_spec, filter_spec, fsdp_param_specs,
                   layout_scope, parameter_spec_from_name, zero_stage)
from . import ring_attention
from .ring_attention import ring_attention_inner, ring_self_attention
from . import ulysses
from .ulysses import ulysses_attention_inner, ulysses_self_attention
from . import pipeline
from .pipeline import gpipe
from . import moe
from .moe import SparseExperts, expert_parallel_ffn
from . import flagship
from .flagship import (flagship_mesh, flagship_param_shardings,
                       flagship_pp_forward, train_flagship)
