"""Packed parameter arena: the model pytree as ONE lane-aligned buffer.

The engine's message/aggregate seam is element-wise over the whole model
(compress -> reduce -> FedCET ``(d', x')`` pair). Executed per leaf it is
dozens of small XLA ops per round — many dispatches on TPU, and once the
per-client arrays outgrow cache it re-streams every intermediate from
HBM/DRAM. The arena flattens the pytree ONCE into a contiguous
``[rows, LANES]`` f32 buffer (LANES = 1024, the Pallas kernels' lane
tiling) so the whole seam is a handful of big array ops — and, with
``FedCET(use_fused_kernel=True)``, a single fused kernel visit per
element (kernels/fedcet_update.py ``fedcet_round_tail``).

Layout: leaves are flattened in ``jax.tree.flatten`` order, each padded
up to a whole number of 1024-lane rows (pad values are ZERO and every
seam operation preserves zero pads — add/sub of zero is zero, the
dither rows are zero-padded so ``floor(0 + 0) = 0``, and reductions are
per-leaf via the static row->leaf segment map). The static
:class:`ArenaLayout` records the treedef, per-leaf shapes and row
extents; it is hashable (jit-static) and rides as pytree aux data, so an
:class:`Arena` is itself a pytree whose single leaf is ``data``:

* ``data.ndim == 2`` — ``[rows, LANES]``: one model (e.g. the global
  mean);
* ``data.ndim == 3`` — ``[lead, rows, LANES]``: a stacked
  ``[clients, ...]`` tree (the repo-wide client-axis convention; axis 0
  keeps meaning clients, so ``gather/scatter/select_clients``,
  ``tree_client_mean`` and participation masking work on arenas
  unchanged).

Pack/unpack happen only at the model-apply boundary (the engine wraps
the vmapped grad fn) and at checkpoint adaptation
(:func:`adapt_state` — flips a per-leaf checkpoint into an arena run
and back, so the ``--arena`` knob stays flippable mid-sweep).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "LANES",
    "Arena",
    "ArenaLayout",
    "adapt_state",
    "pack",
    "pack_rows",
    "unpack",
]

#: lane width of one arena row — matches kernels/fedcet_update.py LANES.
LANES = 1024
#: the arena's row count is a multiple of this (the f32 sublane tile).
SUBLANES = 8


def _rows_of(shape: tuple) -> int:
    return max(1, -(-math.prod(shape) // LANES))


@dataclasses.dataclass(frozen=True)
class ArenaLayout:
    """Static (hashable) description of how a pytree maps onto the arena."""

    treedef: Any
    shapes: tuple  # per-leaf MODEL shapes (no client axis), flatten order
    dtype: Any     # the single float dtype every leaf shares
    rows_per_leaf: tuple

    @classmethod
    def for_tree(cls, tree) -> "ArenaLayout":
        """Layout for a MODEL pytree (leaves carry no client axis; arrays
        or ``ShapeDtypeStruct``s)."""
        leaves, treedef = jax.tree.flatten(tree)
        if not leaves:
            raise ValueError("cannot build an arena layout for an empty tree")
        dtypes = {jnp.result_type(l) for l in leaves}
        if len(dtypes) != 1:
            raise ValueError(
                "arena requires a homogeneous leaf dtype (mixed dtypes would "
                f"change per-leaf rounding): {sorted(map(str, dtypes))}")
        (dtype,) = dtypes
        if not jnp.issubdtype(dtype, jnp.floating):
            raise ValueError(f"arena leaves must be floating, got {dtype}")
        shapes = tuple(tuple(jnp.shape(l)) for l in leaves)
        rows = [_rows_of(s) for s in shapes]
        # the last leaf takes zero rows up to a multiple of the 8-row
        # sublane tile: with an unaligned row count XLA lays a TPU
        # [clients, rows, LANES] buffer out client-minor and copies every
        # operand into and out of each Pallas kernel.
        rows[-1] += -sum(rows) % SUBLANES
        return cls(treedef=treedef, shapes=shapes, dtype=dtype,
                   rows_per_leaf=tuple(rows))

    @property
    def rows(self) -> int:
        return sum(self.rows_per_leaf)

    @property
    def num_params(self) -> int:
        return sum(math.prod(s) for s in self.shapes)

    def row_segments(self) -> np.ndarray:
        """Static row -> leaf-index map ``[rows]`` (int32) for per-leaf
        segment reductions (quantizer scales) over the packed buffer."""
        return np.repeat(np.arange(len(self.shapes), dtype=np.int32),
                         self.rows_per_leaf)

    def leaf_sizes(self) -> tuple:
        """Per-leaf coordinate counts in flatten order — the segment index
        of ``row_segments`` IS the leaf index a
        :class:`~repro.core.compressors.CompressionPlan` digit rule names,
        and these sizes are the ``n`` its exact ``wire_bits`` rounding
        bills (same order as ``repro.core.comm.leaf_info_of`` on the
        unpacked tree)."""
        return tuple(math.prod(s) for s in self.shapes)


class Arena:
    """A pytree whose leaves live packed in one ``[..., rows, LANES]``
    buffer. Registered as a pytree node (child: ``data``; aux: layout),
    so ``jax.tree.map`` arithmetic, ``eval_shape``, donation, sharding
    and checkpointing all treat it as a single big leaf."""

    __slots__ = ("data", "layout")

    def __init__(self, data, layout: ArenaLayout):
        self.data = data
        self.layout = layout

    def __repr__(self):
        return (f"Arena(shape={tuple(jnp.shape(self.data))}, "
                f"leaves={len(self.layout.shapes)}, "
                f"params={self.layout.num_params})")


jax.tree_util.register_pytree_node(
    Arena,
    lambda a: ((a.data,), a.layout),
    lambda layout, children: Arena(children[0], layout),
)


def _lead_of(leaf_shape: tuple, model_shape: tuple) -> int | None:
    """None for an unstacked (model-shaped) leaf, else the stack size."""
    if tuple(leaf_shape) == tuple(model_shape):
        return None
    if tuple(leaf_shape[1:]) == tuple(model_shape):
        return int(leaf_shape[0])
    raise ValueError(f"leaf shape {leaf_shape} matches neither the model "
                     f"shape {model_shape} nor a stacked [lead, ...] of it")


def pack(tree, layout: ArenaLayout | None = None) -> Arena:
    """Flatten ``tree`` (model-shaped, or stacked ``[lead, ...]``) into an
    :class:`Arena`. Padding is zero; pure reshape/pad/concat — bitwise."""
    if layout is None:
        layout = ArenaLayout.for_tree(tree)
    leaves = jax.tree.leaves(tree)
    if len(leaves) != len(layout.shapes):
        raise ValueError(f"tree has {len(leaves)} leaves, layout expects "
                         f"{len(layout.shapes)}")
    leads = {_lead_of(jnp.shape(l), s)
             for l, s in zip(leaves, layout.shapes)}
    if len(leads) != 1:
        raise ValueError(f"inconsistent leading axes across leaves: {leads}")
    (lead,) = leads
    return Arena(pack_rows(leaves, layout, lead=lead), layout)


def pack_rows(leaves, layout: ArenaLayout, lead: int | None = None):
    """Pack a list of per-leaf arrays (layout order; model-shaped, or
    ``[lead, ...]``-stacked when ``lead`` is given) into a raw
    ``[(lead,) rows, LANES]`` buffer — the dither-packing path, which
    needs rows without the Arena wrapper.

    Each leaf becomes its own zero-padded ``[(lead,) nr, LANES]`` row
    block and the blocks are concatenated along the rows axis, so the
    buffer comes out in the shape and row-major layout of the arenas it
    meets, and FedCET's ``v = x - a*g - a*d`` reads a packed gradient in
    place. One flat ``[lead, rows * LANES]`` concatenate reshaped after
    would be tiled for the flat shape on the TPU: XLA scales it by ``a``
    there, in an arena-sized pass of its own, and relays it out inside
    the update (tests/test_tpu_compile.py). For the same reason only a
    leaf's partial last row is padded flat; the whole zero rows that
    round the arena up to the sublane tile are padded as rows. Padded
    flat, they sent the last leaf through the flat tiling and back, a
    pass of its own, and compiling that took most of a round program's
    compile time where the leaf held 47M elements."""
    blocks = []
    for leaf, shape, nr in zip(leaves, layout.shapes, layout.rows_per_leaf):
        n, full = math.prod(shape), _rows_of(shape)
        flat = jnp.reshape(leaf, (n,) if lead is None else (lead, n))
        if full * LANES != n:
            pad = [(0, 0)] * (flat.ndim - 1) + [(0, full * LANES - n)]
            flat = jnp.pad(flat, pad)
        block = jnp.reshape(flat, flat.shape[:-1] + (full, LANES))
        if nr != full:
            pad = [(0, 0)] * (block.ndim - 2) + [(0, nr - full), (0, 0)]
            block = jnp.pad(block, pad)
        blocks.append(block)
    return jnp.concatenate(blocks, axis=-2)


def unpack(arena: Arena):
    """Invert :func:`pack`: slice each leaf's rows back out and reshape.
    ``data.ndim == 2`` yields the model tree; 3 yields a stacked
    ``[lead, ...]`` tree. Bitwise (pads dropped, no arithmetic)."""
    lo, data = arena.layout, arena.data
    if data.ndim not in (2, 3):
        raise ValueError(f"arena data must be [lead?, rows, {LANES}], got "
                         f"shape {tuple(data.shape)}")
    lead = None if data.ndim == 2 else data.shape[0]
    out, off = [], 0
    for shape, nr in zip(lo.shapes, lo.rows_per_leaf):
        n = math.prod(shape)
        if lead is None:
            a = jnp.reshape(data[off:off + nr], (nr * LANES,))[:n]
            out.append(jnp.reshape(a, shape))
        else:
            a = jnp.reshape(data[:, off:off + nr], (lead, nr * LANES))[:, :n]
            out.append(jnp.reshape(a, (lead,) + shape))
        off += nr
    return jax.tree.unflatten(lo.treedef, out)


def pin_layouts(tree):
    """Every :class:`Arena` buffer of ``tree`` held in its default
    row-major layout, the one the Pallas kernels read. Applied to the state
    a round scan carries: left free, the TPU compiler may carry a
    ``[clients, rows, LANES]`` buffer clients-minor across rounds and copy
    it back and forth every round (two copies a round and 2.6 GB more
    compiled peak on fedlm-100m with 4 clients and τ=2)."""
    def pin(a):
        if not isinstance(a, Arena):
            return a
        return Arena(row_major(a.data), a.layout)

    return jax.tree.map(pin, tree, is_leaf=lambda a: isinstance(a, Arena))


def row_major(a):
    """``a`` held in its default row-major layout. The gradient pack pins
    its leaves so: left free, the TPU compiler keeps the gradient of a
    layer-stacked weight ``[layers, clients, ...]``-major, as the layer
    scan writes it, and copies it into row-major inside every gradient
    call."""
    from jax.experimental.layout import Layout, with_layout_constraint

    return with_layout_constraint(a, Layout(tuple(range(jnp.ndim(a)))))


def adapt_state(src, like):
    """Structurally adapt a checkpointed engine state between the per-leaf
    and arena representations: wherever ``like`` carries an :class:`Arena`
    and ``src`` carries the corresponding subtree (or vice versa), pack /
    unpack; everything else is recursed field-by-field. Keeps checkpoints
    knob-flippable: a per-leaf run restores into an ``--arena`` run and
    back with bitwise-identical leaf values."""
    if isinstance(like, Arena):
        if isinstance(src, Arena):
            return src
        return pack(src, like.layout)
    if isinstance(src, Arena):
        return unpack(src)
    # namedtuples (EngineState / FedCETState / DelayState / TopoState ...)
    if isinstance(like, tuple) and hasattr(like, "_fields"):
        return type(like)(*(adapt_state(s, l) for s, l in zip(src, like)))
    if isinstance(like, tuple):
        return tuple(adapt_state(s, l) for s, l in zip(src, like))
    if isinstance(like, list):
        return [adapt_state(s, l) for s, l in zip(src, like)]
    if isinstance(like, dict):
        return {k: adapt_state(src[k], like[k]) for k in like}
    return src
