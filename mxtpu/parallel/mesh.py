"""Device meshes — the TPU-native device model (SURVEY.md §2.3 TPU-equivalents).

The reference enumerates GPUs into flat context lists; here parallelism is a named-axis
mesh (``jax.sharding.Mesh``) over which pjit shardings and shard_map collectives are
expressed. Standard axis names: ``dp`` (data), ``tp`` (tensor), ``pp`` (pipeline),
``sp`` (sequence/context). ICI topology is honored by device order (jax returns
devices in torus order, so contiguous mesh axes ride ICI neighbors).
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["Mesh", "NamedSharding", "P", "force_virtual_cpu_devices",
           "make_mesh", "data_parallel_mesh", "dp_axis_name", "dp_size",
           "data_axis_names", "data_size", "fsdp_axis_name", "fsdp_size",
           "get_default_mesh", "set_default_mesh"]

_default_mesh: Optional[Mesh] = None


def make_mesh(shape: Sequence[int] = None, axis_names: Sequence[str] = ("dp",),
              devices=None) -> Mesh:
    devices = np.asarray(devices if devices is not None else jax.devices())
    if shape is None:
        shape = (devices.size,)
    need = int(np.prod(shape))
    if need > devices.size:
        raise ValueError(f"mesh {tuple(shape)} needs {need} devices, have {devices.size}")
    return Mesh(devices[:need].reshape(tuple(shape)), tuple(axis_names))


def data_parallel_mesh(num_devices: Optional[int] = None) -> Mesh:
    devs = jax.devices()
    n = num_devices or len(devs)
    return make_mesh((n,), ("dp",), devs[:n])


def dp_axis_name(mesh: Mesh) -> str:
    """The data-parallel axis by convention: the mesh's FIRST named axis
    (batches shard over it; ZeRO-1 shards gradients/optimizer state over it)."""
    return mesh.axis_names[0]


def dp_size(mesh: Mesh) -> int:
    """Degree of the data-parallel axis — the N in ZeRO's 1/N state shards."""
    return int(mesh.shape[mesh.axis_names[0]])


def data_axis_names(mesh: Mesh) -> Tuple[str, ...]:
    """The axes the BATCH shards over: every ``dp``/``fsdp`` axis present.

    An HSDP mesh ``("dp", "fsdp", "tp")`` feeds batches sharded over
    ``("dp", "fsdp")`` — replicas × shards both consume distinct data — while
    ``tp`` sees the batch replicated. Meshes with neither conventional name
    keep the first-axis-is-data convention (``dp_axis_name``)."""
    named = tuple(a for a in mesh.axis_names if a in ("dp", "fsdp"))
    return named or (mesh.axis_names[0],)


def data_size(mesh: Mesh) -> int:
    """Combined degree of the data axes — the N in ZeRO's 1/N shards."""
    n = 1
    for a in data_axis_names(mesh):
        n *= int(mesh.shape[a])
    return n


def fsdp_axis_name(mesh: Mesh) -> str:
    """The axis PARAMETERS shard over in ZeRO-3/FSDP: the ``fsdp`` axis when
    the mesh names one, else the last data axis (pure-dp meshes double their
    data axis as the parameter-shard axis — plain single-level FSDP)."""
    return "fsdp" if "fsdp" in mesh.axis_names else data_axis_names(mesh)[-1]


def fsdp_size(mesh: Mesh) -> int:
    return int(mesh.shape[fsdp_axis_name(mesh)])


def get_default_mesh() -> Mesh:
    global _default_mesh
    if _default_mesh is None:
        _default_mesh = data_parallel_mesh()
    return _default_mesh


def set_default_mesh(mesh: Optional[Mesh]):
    global _default_mesh
    _default_mesh = mesh


def force_virtual_cpu_devices(n_devices: int) -> int:
    """Best-effort switch to an ``n_devices`` virtual CPU pod
    (``--xla_force_host_platform_device_count``) for sharding dry-runs on
    hosts without that many chips. The env route only works before jax's
    backends initialize; the config route flips the platform for a process
    that has imported jax already. Returns the usable device count — callers
    must clamp their mesh to it."""
    import os
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_devices}"
        ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass
    return min(n_devices, len(jax.devices()))
