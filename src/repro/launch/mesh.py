"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before any jax initialization).

Single pod:  (data=16, model=16)            = 256 chips (one v5e pod)
Multi-pod:   (pod=2, data=16, model=16)     = 512 chips; the pod axis is
             pure data parallelism across DCI — gradients reduce
             hierarchically (ICI ring within a pod, DCI across), which XLA
             emits automatically for the nested (pod, data) batch sharding.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def _mesh_kwargs(n_axes: int) -> dict:
    return {"axis_types": (AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(jax.devices())} — "
            "run under launch/dryrun.py (it forces 512 host devices)")
    return jax.make_mesh(shape, axes, devices=devices,
                         **_mesh_kwargs(len(axes)))


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """Arbitrary mesh (tests / elastic restarts)."""
    n = 1
    for s in shape:
        n *= s
    return jax.make_mesh(tuple(shape), tuple(axes),
                         devices=jax.devices()[:n],
                         **_mesh_kwargs(len(axes)))


def single_device_mesh():
    return make_mesh((1, 1), ("data", "model"))
