"""Production mesh construction (TPU v5e fleet).

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run driver sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import, while tests and benches must keep seeing the single real device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _auto_mesh(shape, axes) -> Mesh:
    """``jax.make_mesh`` with every axis ``AxisType.Auto``: the engine's
    ``shard_map``/``NamedSharding`` code leaves propagation to the
    compiler, and explicit axes (``make_mesh``'s default) would type the
    shardings of every intermediate reshape instead."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh() -> Mesh:
    """(n_devices, 1) mesh over the local device(s) — the CPU examples and
    the engine's ``MeshBackend`` use it so the same pjit/shard_map code
    paths run everywhere.  Under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` this yields an
    N-way ``data`` axis on plain CPU hosts (the multi-device CI recipe)."""
    n = len(jax.devices())
    return _auto_mesh((n, 1), ("data", "model"))


def data_axes(mesh: Mesh):
    """Axes the global batch — or the engine's population axis — is
    sharded over."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def fsdp_axes(mesh: Mesh):
    """Axes the parameter 'replicated' dim is FSDP-sharded over."""
    return data_axes(mesh)


def mesh_axis_size(mesh: Mesh, axes) -> int:
    """Total device count along ``axes`` (one name or a tuple of names)."""
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n
