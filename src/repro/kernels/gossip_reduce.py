"""Pallas TPU kernel for the gossip fixed-slot segment reduce.

The sparse neighbor-exchange lowering (repro/core/topology.py: ``Mixing``
with ``lowering="sparse"``) turns the dense N x N gossip contraction into
a gather plus a PADDED segment reduce: every node owns exactly
``S = max_degree + 1`` weighted neighbor contributions (pad slots carry
weight 0), so the reduce is a fixed-stride sum — ``segment_sum`` whose
segments all have equal length S. That regularity is what makes it a
clean Pallas kernel: grid over (node blocks, lane blocks), each step
loads one ``(nb * S, db)`` tile of contributions, views it as
``(nb, S, db)`` and sums the slot axis — one HBM visit per edge
contribution (the memory-roofline floor for the reduce), no scatter, no
atomics, no segment-boundary bookkeeping.

Like the quantize kernel, all randomness/weighting happens OUTSIDE the
kernel (the caller gathers and weights the contributions), keeping the
kernel a pure function that is bit-comparable to its
``ref.py:segment_reduce`` oracle (``jax.ops.segment_sum`` over the same
fixed-slot ids) in interpret mode on CPU — tests/test_gossip_kernel.py.
On TPU it lowers through Mosaic next to the fedcet_update kernels.

Layout: ops.py pads the lane (coordinate) axis to a multiple of the
block width and the node count to a multiple of the node block, so every
BlockSpec tile is rectangular; zero-padded rows reduce to zero rows that
the wrapper slices off. The slot axis is NEVER padded — it is static
(the graph's max degree + 1), set by the neighbor tables.
"""

from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
import jax.numpy as jnp

NODE_BLOCK = 8
LANE_BLOCK = 1024


def _seg_reduce_kernel(v_ref, o_ref, *, slots: int):
    v = v_ref[...]
    nb = v.shape[0] // slots
    o_ref[...] = jnp.sum(v.reshape(nb, slots, v.shape[1]), axis=1)


def segment_reduce_2d(vals, *, slots: int, node_block: int = NODE_BLOCK,
                      interpret: bool):
    """Fixed-slot segment sum: ``vals`` is ``[n * slots, d]`` (row
    ``i * slots + s`` = node i's slot-s contribution; pre-padded by
    ops.py so ``n % node_block == 0`` and ``d % lane block == 0``);
    returns the per-node sums ``[n, d]``."""
    rows, d = vals.shape
    assert rows % slots == 0, (rows, slots)
    n = rows // slots
    nb = min(node_block, n)
    db = min(LANE_BLOCK, d)
    grid = (pl.cdiv(n, nb), pl.cdiv(d, db))
    return pl.pallas_call(
        functools.partial(_seg_reduce_kernel, slots=slots),
        grid=grid,
        in_specs=[pl.BlockSpec((nb * slots, db), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((nb, db), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, d), vals.dtype),
        interpret=interpret,
    )(vals)
