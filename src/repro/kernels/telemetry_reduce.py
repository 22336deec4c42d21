"""Pallas TPU kernel for the one-pass telemetry distribution sketch.

The distributional telemetry (core/telemetry.py: ``sketch_client_norms``)
needs, once per round, the per-client norms ``||x_i||`` over the FULL
``[N, rows, 1024]`` packed arena store plus their log-histogram — an
O(N * D) read that must not become three separate sweeps (norms, then
binning, then outliers) at N = 1e6. This kernel fuses norm accumulation
and histogram binning into ONE pass over the store: grid over
(client blocks, lane blocks) with the lane axis minor — TPU grid steps
run sequentially in row-major order, so each client block's partial
square-sums accumulate across its lane steps into a revisited ``[cb, 1]``
output block (the flash-attention accumulation pattern), and at the
block's LAST lane step the now-complete norms are binned into a single
revisited ``[1, bins]`` histogram block shared by every grid step. The
top-k outlier selection runs on the tiny ``[N]`` norms vector back in
ops.py (``jax.lax.top_k``) — fusing it into the sweep would buy nothing:
the norms output is 4 bytes per client against D * 4 read.

Binning is the shared verbatim formula (telemetry.log_histogram /
ref.client_sketch): ``idx = clip(floor((log10(v) - lo) * bins/(hi-lo)),
0, bins-1)``, zeros pinned to bin 0. The histogram one-hot uses a 2-D
``broadcasted_iota`` (TPU requires >=2-D iota) and masks padded client
rows via the static ``n_valid`` — zero pad LANES already contribute 0 to
the norms, but pad CLIENTS must not count in the histogram. The bin axis
is padded to a 128-lane multiple in the block; ops.py slices the logical
``[:bins]`` off.

Oracle: kernels/ref.py:client_sketch (bit-comparable in interpret mode —
tests/test_telemetry_dist.py); discipline as quantize/gossip_reduce.
"""

from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
import jax.numpy as jnp

CLIENT_BLOCK = 8
LANE_BLOCK = 1024


def _sketch_kernel(x_ref, sq_ref, h_ref, *, bins: int, lo: float, hi: float,
                   n_valid: int, nj: int):
    i = pl.program_id(0)
    j = pl.program_id(1)
    x = x_ref[...]
    part = jnp.sum(x * x, axis=1, keepdims=True)            # [cb, 1]

    @pl.when(j == 0)
    def _init_sq():
        sq_ref[...] = jnp.zeros_like(sq_ref)

    sq_ref[...] += part

    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _init_hist():
        h_ref[...] = jnp.zeros_like(h_ref)

    @pl.when(j == nj - 1)
    def _bin():
        cb, bins_pad = x.shape[0], h_ref.shape[1]
        # broadcast the [cb, 1] norms to the full bin tile BEFORE the
        # transcendental: f64 log on a width-1 column crashes the XLA CPU
        # backend (interpret mode), and the [cb, bins] tile is the
        # natural register shape for the one-hot compare anyway.
        v = jnp.broadcast_to(jnp.sqrt(sq_ref[...]), (cb, bins_pad))
        logs = jnp.where(v > 0, jnp.log10(v), jnp.asarray(lo, v.dtype))
        idx = jnp.clip(jnp.floor((logs - lo) * (bins / (hi - lo))),
                       0, bins - 1).astype(jnp.int32)       # [cb, bins_pad]
        cols = jax.lax.broadcasted_iota(jnp.int32, (cb, bins_pad), 1)
        rows = jax.lax.broadcasted_iota(jnp.int32, (cb, bins_pad), 0)
        valid = rows + jnp.int32(i * cb) < jnp.int32(n_valid)
        hit = jnp.where(jnp.logical_and(cols == idx, valid),
                        jnp.int32(1), jnp.int32(0))
        h_ref[...] += jnp.sum(hit, axis=0, keepdims=True).astype(jnp.int32)


def client_sketch_2d(x, *, bins: int, lo: float, hi: float, n_valid: int,
                     client_block: int = CLIENT_BLOCK, interpret: bool):
    """Fused per-client square-norm + log-histogram over the flattened
    store ``x`` ``[n, d]`` (pre-padded by ops.py: ``n % client_block == 0``,
    ``d`` a lane-block multiple, pad entries zero). Returns
    ``(sq_norms [n, 1], hist [1, bins_pad] int32)`` with ``bins_pad`` the
    bin count padded to 128 lanes (logical bins first); only the first
    ``n_valid`` clients count in the histogram."""
    n, d = x.shape
    cb = min(client_block, n)
    db = min(LANE_BLOCK, d)
    bins_pad = -(-bins // 128) * 128
    grid = (pl.cdiv(n, cb), pl.cdiv(d, db))
    return pl.pallas_call(
        functools.partial(_sketch_kernel, bins=bins, lo=lo, hi=hi,
                          n_valid=n_valid, nj=grid[1]),
        grid=grid,
        in_specs=[pl.BlockSpec((cb, db), lambda i, j: (i, j))],
        out_specs=[pl.BlockSpec((cb, 1), lambda i, j: (i, 0)),
                   pl.BlockSpec((1, bins_pad), lambda i, j: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((n, 1), x.dtype),
                   jax.ShapeDtypeStruct((1, bins_pad), jnp.int32)],
        interpret=interpret,
    )(x)
