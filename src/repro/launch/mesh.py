"""Production mesh construction (assignment MULTI-POD DRY-RUN §1).

A function, not a module constant, so importing never touches jax device
state.  Axis semantics (repro.models.sharding.MeshRules):
  pod   — data parallelism across pods (gradient all-reduce over DCI)
  data  — data parallelism / FSDP within a pod
  model — tensor/expert/sequence parallelism (highest-bandwidth ICI ring)

`paper_device_order` applies the paper's placement idea at mesh-build time:
`jax.make_mesh` lays logical axes over the physical torus in device-id
order; passing an explicit permutation (from core.placement / DeviceMapper)
reorders devices so heavy-traffic logical neighbours are physical ICI
neighbours.  On CPU placeholders all devices are equivalent — the permuted
mesh exists to prove the mechanism lowers (the hop accounting lives in the
NoC model), so dryrun exercises it but the default is identity.
"""
from __future__ import annotations

import numpy as np

__all__ = ["make_production_mesh", "make_smoke_mesh", "mesh_devices"]


def make_production_mesh(*, multi_pod: bool = False, device_permutation=None):
    import jax
    from jax.sharding import AxisType, Mesh

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    if device_permutation is None:
        return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
    devices = np.asarray(jax.devices())[np.asarray(device_permutation)].reshape(shape)
    return Mesh(devices, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_smoke_mesh(shape=(1, 1), axes=("data", "model")):
    """Single-device mesh for CPU tests (same code path, trivial axes)."""
    import jax
    from jax.sharding import AxisType

    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def mesh_devices(mesh) -> int:
    return int(np.prod(list(mesh.shape.values())))
