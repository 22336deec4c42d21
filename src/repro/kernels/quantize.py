"""Pallas TPU kernel for the stochastic-quantize round-trip.

With the compressor subsystem (repro/core/compressors.py) the new per-round
hot path on a compressed uplink is ``StochasticQuant``: every coordinate of
the transmitted message is scaled, dithered, floored and rescaled —
4 elementwise ops over a model-sized tensor, bound by the same HBM streams
as the FedCET update triad. Fusing the whole round-trip into one kernel
visit per element (2 reads — value + dither — and 1 write) is the
memory-roofline-optimal schedule, and on TPU it lowers through Mosaic next
to the fedcet_update kernels.

The dither ``u ~ U[0,1)`` and the scale arrive as INPUTS (generated by the
host-side PRNG schedule in core/compressors.py — shared across clients by
design; see that module). Keeping the randomness outside the kernel makes
the kernel a pure function, bit-comparable to its
``ref.py:stochastic_quantize`` oracle in interpret mode on CPU; an on-chip
variant could draw the bits with ``pltpu.prng_random_bits`` at the cost of
that comparability. The scale is a single (1, 1) block mapped to every
grid step — a scalar load, not a third model-sized HBM stream.

Layout matches fedcet_update.py: ops.py reshapes to [rows, 1024] so each
BlockSpec tile is a hardware-aligned (256, 1024) VMEM block; 2 input tiles
+ 1 output tile ~= 3 MiB of f32 VMEM per step. Zero-padded tail entries
(value 0, dither 0) quantize to exactly 0, so padding never leaks into the
unpadded result.
"""

from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
import jax.numpy as jnp

ROW_BLOCK = 256
LANES = 1024


def _stoch_quant_kernel(a_ref, u_ref, s_ref, o_ref, *, levels: int):
    a = a_ref[...]
    u = u_ref[...]
    s = s_ref[0, 0]
    inv = jnp.where(s > 0, 1.0 / s, 0.0)
    q = jnp.clip(jnp.floor(a * inv + u), -levels, levels)
    o_ref[...] = q * s


def stochastic_quantize_2d(a, u, s, *, bits: int, interpret: bool):
    """Dithered quantize round-trip; a, u: [rows, LANES] (pre-tiled by
    ops.py), ``s``: the per-leaf scale as a (1, 1) array — every grid step
    maps the same single block (a scalar load per tile)."""
    levels = 2 ** (bits - 1) - 1
    rows = a.shape[0]
    rb = min(ROW_BLOCK, rows)
    grid = (pl.cdiv(rows, rb),)
    spec = pl.BlockSpec((rb, LANES), lambda i: (i, 0))
    s_spec = pl.BlockSpec((1, 1), lambda i: (0, 0))
    return pl.pallas_call(
        functools.partial(_stoch_quant_kernel, levels=levels),
        grid=grid,
        in_specs=[spec, spec, s_spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        interpret=interpret,
    )(a, u, s)


def _stoch_quant_rows_kernel(a_ref, u_ref, s_ref, o_ref, *, levels: int):
    a = a_ref[...]
    s = s_ref[...]                      # [rb, 1] per-row (per-leaf) step
    inv = jnp.where(s > 0, 1.0 / s, 0.0)
    q = jnp.clip(jnp.floor(a * inv + u_ref[...]), -levels, levels)
    o_ref[...] = q * s


def stochastic_quantize_rows_2d(a, u, s, *, bits: int,
                                interpret: bool):
    """Row-wise-scale variant for the packed parameter arena: the whole
    pytree is quantized in ONE kernel launch, with each 1024-lane row
    carrying its own leaf's scale — ``s`` is [rows, 1] (the per-leaf
    max|.|/levels step broadcast over the leaf's rows by
    core/arena.py's segment map), streamed alongside ``a``/``u``."""
    levels = 2 ** (bits - 1) - 1
    rows = a.shape[0]
    rb = min(ROW_BLOCK, rows)
    grid = (pl.cdiv(rows, rb),)
    spec = pl.BlockSpec((rb, LANES), lambda i: (i, 0))
    s_spec = pl.BlockSpec((rb, 1), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_stoch_quant_rows_kernel, levels=levels),
        grid=grid,
        in_specs=[spec, spec, s_spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        interpret=interpret,
    )(a, u, s)
