"""Production meshes. Functions (not module constants) so importing this
module never touches jax device state."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple, axes: tuple):
    """``jax.make_mesh`` with every axis ``Auto``: the sharded paths here
    place data with explicit ``NamedSharding``s and ``shard_map``, and rely
    on the compiler to propagate everything else."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e: one pod = 16×16 = 256 chips (data × model); two pods add a
    leading 'pod' axis used for data parallelism across the DCN/ICI link."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2):
    """Small host-device mesh for in-repo tests (run in a subprocess with
    XLA_FLAGS=--xla_force_host_platform_device_count set)."""
    return make_mesh((n_data, n_model), ("data", "model"))


def make_ep_mesh(n_shards: int, n_data: int = 1):
    """Expert-parallel serving mesh: ``n_shards`` devices on the model axis
    each own E/n_shards experts (and, under ``DistContext.tokens_ep_sharded``,
    a token slice); an optional data axis replicates the expert layout."""
    return make_mesh((n_data, n_shards), ("data", "model"))


def data_axes(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis(mesh) -> str:
    return "model"
