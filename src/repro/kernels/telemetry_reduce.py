"""Pallas TPU kernel for the one-pass telemetry distribution sketch.

The distributional telemetry (core/telemetry.py: ``sketch_client_norms``)
needs, once per round, the per-client norms ``||x_i||`` over the FULL
``[N, rows, 1024]`` packed arena store plus their log-histogram — an
O(N * D) read that must not become three separate sweeps (norms, then
binning, then outliers). This kernel fuses norm accumulation and histogram
binning into ONE pass over the store, read in its own 3-D shape and
layout (the arena's ``(8, 128)`` tiling over ``(rows, lanes)``), so no
relaid copy of the store is made to feed it.

Blocking: grid over (client blocks, row blocks), the row axis minor — TPU
grid steps run sequentially in row-major order. A block is
``(cb, rb, lanes)`` with ``cb = min(N, 8)`` and ``rb`` derived from a
fixed per-block byte budget (``BLOCK_BYTES``, 4 MiB): ``rb = BLOCK_BYTES
/ (cb * lanes * itemsize)`` rounded down to a multiple of 8, or the rows
rounded up to 8 when fewer. For f32 that is 256 rows at N=4, 512 at N=2
and 128 at N>=8, so the double-buffered input takes at most 8 MiB of
VMEM. The fixed cost of a grid step is then paid once per 4 MiB, not
once per ``cb`` lane rows.

Each row step folds ``x * x`` into a ``[cb, 8, lanes]`` VMEM scratch
accumulator with elementwise adds only (the block viewed as
``(cb, rb/8, 8, lanes)``, summed over its second axis). At the client
block's LAST row step the accumulator is reduced across sublanes and
lanes once into the ``[cb, 1]`` square-norm output block, and the
now-complete norms are binned into a single revisited ``[1, bins]``
histogram block shared by every grid step. The top-k outlier selection
runs on the tiny ``[N]`` norms vector back in ops.py
(``jax.lax.top_k``).

Masking: a row count that ``rb`` does not divide leaves a ragged last row
block, whose rows past the store's end are zeroed in the kernel before
squaring (only on that step). A client count that ``cb`` does not divide
leaves a ragged last client block: its out-of-range clients' norms fall
outside the output and are dropped, and the histogram counts only the
first ``N`` clients. So the wrapper pads nothing.

Binning is the shared verbatim formula (telemetry.log_histogram /
ref.client_sketch): ``idx = clip(floor((log10(v) - lo) * bins/(hi-lo)),
0, bins-1)``, zeros pinned to bin 0. The histogram one-hot uses a 2-D
``broadcasted_iota`` (TPU requires >=2-D iota). The bin axis is padded to
a 128-lane multiple in the block; ops.py slices the logical ``[:bins]``
off.

Oracle: kernels/ref.py:client_sketch (equal to f64 rounding in interpret
mode — tests/test_telemetry_dist.py); discipline as quantize/gossip_reduce.
"""

from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
import jax.numpy as jnp

CLIENT_BLOCK = 8
#: bytes of one input block; double-buffered, twice this sits in VMEM
BLOCK_BYTES = 4 * 2**20


def row_block(clients: int, rows: int, lanes: int, itemsize: int) -> int:
    """Rows of a ``(min(clients, 8), rb, lanes)`` block of the byte
    budget, a multiple of 8; no more than the rows rounded up to 8."""
    cb = min(CLIENT_BLOCK, clients)
    rb = BLOCK_BYTES // (cb * lanes * itemsize) // 8 * 8
    return min(rb, -(-rows // 8) * 8)


def _fold(x, acc_ref):
    """Add the block's ``x * x`` into the ``[cb, 8, lanes]`` accumulator:
    elementwise adds of its 8-row tiles."""
    cb, rb, lanes = x.shape
    acc_ref[...] += jnp.sum((x * x).reshape(cb, rb // 8, 8, lanes), axis=1)


def _sketch_kernel(x_ref, sq_ref, h_ref, acc_ref, *, bins: int, lo: float,
                   hi: float, n_valid: int, rows: int, nj: int):
    i = pl.program_id(0)
    j = pl.program_id(1)
    cb, rb, _ = x_ref.shape

    @pl.when(j == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _init_hist():
        h_ref[...] = jnp.zeros_like(h_ref)

    if rows % rb:
        @pl.when(j < nj - 1)
        def _full():
            _fold(x_ref[...], acc_ref)

        @pl.when(j == nj - 1)
        def _ragged():
            x = x_ref[...]
            row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
            _fold(jnp.where(row < rows - (nj - 1) * rb, x, 0), acc_ref)
    else:
        _fold(x_ref[...], acc_ref)

    @pl.when(j == nj - 1)
    def _bin():
        bins_pad = h_ref.shape[1]
        sq = jnp.sum(jnp.sum(acc_ref[...], axis=1), axis=1, keepdims=True)
        sq_ref[...] = sq                                     # [cb, 1]
        # broadcast the [cb, 1] norms to the full bin tile BEFORE the
        # transcendental: f64 log on a width-1 column crashes the XLA CPU
        # backend (interpret mode), and the [cb, bins] tile is the
        # natural register shape for the one-hot compare anyway.
        v = jnp.broadcast_to(jnp.sqrt(sq), (cb, bins_pad))
        logs = jnp.where(v > 0, jnp.log10(v), jnp.asarray(lo, v.dtype))
        idx = jnp.clip(jnp.floor((logs - lo) * (bins / (hi - lo))),
                       0, bins - 1).astype(jnp.int32)       # [cb, bins_pad]
        cols = jax.lax.broadcasted_iota(jnp.int32, (cb, bins_pad), 1)
        client = jax.lax.broadcasted_iota(jnp.int32, (cb, bins_pad), 0)
        valid = client + jnp.int32(i * cb) < jnp.int32(n_valid)
        hit = jnp.where(jnp.logical_and(cols == idx, valid),
                        jnp.int32(1), jnp.int32(0))
        h_ref[...] += jnp.sum(hit, axis=0, keepdims=True).astype(jnp.int32)


def client_sketch_3d(x, *, bins: int, lo: float, hi: float,
                     interpret: bool):
    """Fused per-client square-norm + log-histogram over the client store
    ``x`` ``[n, rows, lanes]`` (the arena's own buffer; pad entries zero).
    Returns ``(sq_norms [n, 1], hist [1, bins_pad] int32)`` with
    ``bins_pad`` the bin count padded to 128 lanes (logical bins first)."""
    from jax.experimental.pallas import tpu as pltpu

    n, rows, lanes = x.shape
    cb = min(CLIENT_BLOCK, n)
    rb = row_block(n, rows, lanes, x.dtype.itemsize)
    bins_pad = -(-bins // 128) * 128
    grid = (pl.cdiv(n, cb), pl.cdiv(rows, rb))
    return pl.pallas_call(
        functools.partial(_sketch_kernel, bins=bins, lo=lo, hi=hi,
                          n_valid=n, rows=rows, nj=grid[1]),
        grid=grid,
        in_specs=[pl.BlockSpec((cb, rb, lanes), lambda i, j: (i, j, 0))],
        out_specs=[pl.BlockSpec((cb, 1), lambda i, j: (i, 0)),
                   pl.BlockSpec((1, bins_pad), lambda i, j: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((n, 1), x.dtype),
                   jax.ShapeDtypeStruct((1, bins_pad), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((cb, 8, lanes), x.dtype)],
        interpret=interpret,
    )(x)
