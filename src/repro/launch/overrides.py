"""Per-architecture distribution overrides.

The production device grid is fixed (16x16 per pod, 2x16x16 multi-pod), but
how the non-model axes are *interpreted* is a per-arch design decision:

* train: memory-heavy archs split the 16-way data axis into
  (clients x fsdp): each client's FedCET state (x, d — 4 bytes/param in
  bf16) additionally shards over `fsdp`, and the per-client batch also
  splits over `fsdp` (ZeRO-style: per-layer all-gather of weights inside
  the layer scan, gradient all-reduce over fsdp). llama4-scout's 109B total
  params (2 copies = 436 GB/client) simply cannot live on one client's 16
  model-shards of 16 GB HBM.

* serve: llama4-scout also needs weights sharded over BOTH non-batch axes
  (2D tensor parallelism: experts over `model`, d_ff over `data`), or
  13.6 GB/device of weights crowd out the KV cache.

Everything else keeps the plain layout: data=clients, model=TP.
"""

from __future__ import annotations

import dataclasses

import numpy as np
from jax.sharding import AxisType, Mesh


@dataclasses.dataclass(frozen=True)
class ArchDistribution:
    fsdp: int = 1            # train: data axis splits into (data/fsdp, fsdp)
    serve_wide: bool = False  # serve: also shard weights over the data axis


OVERRIDES: dict[str, ArchDistribution] = {
    "llama4-scout-17b-a16e": ArchDistribution(fsdp=4, serve_wide=True),
    "llava-next-34b": ArchDistribution(fsdp=2),
}


def distribution_for(arch: str) -> ArchDistribution:
    return OVERRIDES.get(arch, ArchDistribution())


def train_mesh_view(mesh: Mesh, fsdp: int) -> Mesh:
    """Reinterpret the production device grid with an fsdp axis split out of
    the data axis: (pod?, data, model) -> (pod?, data/fsdp, fsdp, model)."""
    if fsdp == 1:
        return mesh
    names = mesh.axis_names
    assert "data" in names and mesh.shape["data"] % fsdp == 0
    new_shape, new_names = [], []
    for n in names:
        if n == "data":
            new_shape += [mesh.shape["data"] // fsdp, fsdp]
            new_names += ["data", "fsdp"]
        else:
            new_shape.append(mesh.shape[n])
            new_names.append(n)
    dev = np.asarray(mesh.devices).reshape(new_shape)
    return Mesh(dev, tuple(new_names),
                axis_types=(AxisType.Auto,) * len(new_names))
