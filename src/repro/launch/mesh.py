"""Production mesh construction.

Single pod : (data=16, model=16)            = 256 chips (one v5e pod slice)
Multi-pod  : (pod=2, data=16, model=16)     = 512 chips

``make_production_mesh`` is a function (never a module-level constant) so
importing this module touches no JAX device state. The dry-run forces 512
host devices via XLA_FLAGS before any JAX import; real launches get the same
shapes from the TPU runtime.
"""
from __future__ import annotations

from repro.sharding.specs import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def describe(mesh) -> str:
    return f"mesh{dict(zip(mesh.axis_names, mesh.devices.shape))}" \
           f" ({mesh.devices.size} devices)"
