"""Production meshes.

Single pod: 16 x 16 = 256 chips, axes ("data", "model").
Multi-pod:  2 x 16 x 16 = 512 chips, axes ("pod", "data", "model") — the
"pod" axis crosses the DCN boundary; FedCET's single aggregated vector is
the only collective that traverses it, once per tau local steps.

Functions (not module-level constants) so importing never touches jax
device state; the dry-run process sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 BEFORE importing jax.
"""

from __future__ import annotations

import numpy as np

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)}; run "
            "under XLA_FLAGS=--xla_force_host_platform_device_count=512")
    dev = np.asarray(devices[:n]).reshape(shape)
    return Mesh(dev, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(shape=(2, 2), axes=("data", "model")) -> Mesh:
    """Small mesh for sharding unit tests (subprocesses with 4-8 fake devs)."""
    n = int(np.prod(shape))
    dev = np.asarray(jax.devices()[:n]).reshape(shape)
    return Mesh(dev, axes, axis_types=(AxisType.Auto,) * len(axes))


def client_axes(mesh: Mesh) -> tuple[str, ...]:
    """The mesh axes that enumerate federated clients (model/fsdp excluded)."""
    return tuple(a for a in mesh.axis_names if a not in ("model", "fsdp"))


def n_clients(mesh: Mesh) -> int:
    out = 1
    for a in client_axes(mesh):
        out *= mesh.shape[a]
    return out


def tp_size(mesh: Mesh) -> int:
    return mesh.shape["model"]
