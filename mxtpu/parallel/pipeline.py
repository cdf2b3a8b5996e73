"""Pipeline parallelism over a ``pp`` mesh axis — the TPU-native PP hook
(SURVEY.md §2.3: the reference has no pipeline parallelism; TP/PP/SP hooks are
mandated because pjit meshes make them cheap).

GPipe-style schedule expressed as ONE ``shard_map``-ed ``lax.scan``: every
device holds one stage's parameters (stacked pytree sharded over ``pp``);
each scan step, activations hop one stage forward over ICI via ``ppermute``
while a new microbatch enters stage 0 — the classic pipelined loop, compiled
into a single XLA program. Differentiable end-to-end (jax autodiff through
``ppermute`` reverses the ring), so the same function serves training.

Bubble fraction is the usual (S-1)/(M+S-1) for S stages / M microbatches.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from .mesh import Mesh, get_default_mesh

__all__ = ["gpipe"]


def gpipe(stage_fn: Callable, stacked_params, x, mesh: Optional[Mesh] = None,
          axis_name: str = "pp", batch_spec: Optional[P] = None):
    """Run ``x`` through S pipelined stages.

    ``stage_fn(params_i, h) -> h`` applies one stage. ``stacked_params`` is a
    pytree whose leaves are stacked along a leading S axis (stage i's slice
    lives on pp-rank i). ``x``: (M, B, ...) microbatches with M >= 1; the
    activation shape must be constant across stages (uniform-width pipeline —
    standard for transformer blocks). Returns (M, B, ...) outputs.

    ``batch_spec`` composes pp with the mesh's OTHER axes: the spec of one
    microbatch (B, ...) — e.g. ``P(("dp", "fsdp"))`` to shard B over the data
    axes while the pp ring permutes over its own axis. Stream and output
    carry the spec shifted one dim right (the leading M axis stays
    unsharded); default keeps the old fully-replicated behavior.
    """
    mesh = mesh or get_default_mesh()
    S = mesh.shape[axis_name]
    M = x.shape[0]
    n_steps = M + S - 1

    # pad the microbatch stream with S-1 dummy slots that flush the pipeline
    pad = jnp.zeros((S - 1,) + x.shape[1:], x.dtype)
    stream = jnp.concatenate([x, pad], axis=0)          # (n_steps, B, ...)

    def spmd(params_stacked, stream_loc):
        # params_stacked: (1, ...) — this device's stage slice
        my_params = jax.tree.map(lambda p: p[0], params_stacked)
        idx = lax.axis_index(axis_name)

        def step(carry, x_t):
            h_in = carry                                 # activation entering my stage
            # stage 0 consumes the incoming microbatch; others their buffer
            h = jnp.where(idx == 0, x_t, h_in)
            h_out = stage_fn(my_params, h)
            # the finished output of the LAST stage, broadcast to every rank
            # (masked psum) so the scan output is pp-replicated
            y_t = lax.psum(jnp.where(idx == S - 1, h_out,
                                     jnp.zeros_like(h_out)), axis_name)
            # hop one stage forward over the ICI ring
            shifted = lax.ppermute(h_out, axis_name,
                                   [(i, (i + 1) % S) for i in range(S)])
            return shifted, y_t

        # a carry that becomes device-varying must start varying
        carry0 = lax.pcast(jnp.zeros_like(stream_loc[0]), axis_name,
                           to="varying")
        _, ys = lax.scan(step, carry0, stream_loc)
        return ys                                        # (n_steps, B, ...)

    params_spec = jax.tree.map(lambda _: P(axis_name), stacked_params)
    # stream/output spec: microbatch spec shifted right of the leading M axis
    stream_spec = P(None, *batch_spec) if batch_spec is not None else P()
    from .collectives import shard_map_compat
    fn = shard_map_compat(spmd, mesh,
                          (params_spec, stream_spec),
                          stream_spec)
    ys = fn(stacked_params, stream)
    # outputs for microbatch m exit the last stage at step m + S - 1 and are
    # visible (after the rotation) on every rank at that step
    return ys[S - 1:]
