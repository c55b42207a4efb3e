"""Production meshes.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state.  The dry-run sets
``--xla_force_host_platform_device_count=512`` before any jax import; real
deployments get the same topology from the TPU runtime.

Mesh axes:
  single-pod : (16, 16)      = ("data", "model")      — 256 chips (v5e pod)
  multi-pod  : (2, 16, 16)   = ("pod", "data", "model") — 512 chips
``pod`` composes with ``data`` for the batch/FSDP dimension; gradient
all-reduce crosses pods, params are FSDP-sharded within a pod.
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (possibly fake) devices tests have."""
    return _make_mesh((data, model), ("data", "model"))


def mesh_num_chips(mesh) -> int:
    n = 1
    for s in mesh.devices.shape:
        n *= s
    return n
