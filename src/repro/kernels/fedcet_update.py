"""Pallas TPU kernels for the FedCET update hot-path.

The FedCET local step applies ``v = x - alpha*g - alpha*d`` to EVERY
parameter of the model, tau times per communication round; the comm step
additionally applies the paired update ``(d', x') = (d + c*delta,
v - c*alpha*delta)``. On a multi-B-parameter model these streams are the
per-step HBM bottleneck of the algorithm (the paper's eq. (2)/(3) applied at
scale): 3 reads + 1 write per element for the triad, 3 reads + 2 writes for
the fused comm pair. Fusing them in one kernel visit per element is the
memory-roofline-optimal schedule.

Layout: inputs are reshaped by ops.py to [rows, 1024] — the minor dimension
is a multiple of the TPU lane width (128) and the row block (256) is a
multiple of the f32 sublane (8), so each BlockSpec tile is a
hardware-aligned (256, 1024) VMEM block (1 MiB for f32): 4 input tiles + 2
output tiles ~= 6 MiB of VMEM per step, comfortably inside the ~16 MiB
budget. Kernels are validated against kernels/ref.py in interpret mode
(CPU) across shapes and dtypes in tests/test_kernels.py.
"""

from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl

ROW_BLOCK = 256
LANES = 1024


def _fedcet_v_kernel(x_ref, g_ref, d_ref, o_ref, *, alpha: float):
    x = x_ref[...]
    g = g_ref[...]
    d = d_ref[...]
    o_ref[...] = x - alpha * g - alpha * d


def fedcet_v_2d(x, g, d, *, alpha: float, interpret: bool):
    """x, g, d: [rows, LANES] (pre-tiled by ops.py)."""
    rows = x.shape[0]
    rb = min(ROW_BLOCK, rows)
    grid = (pl.cdiv(rows, rb),)
    spec = pl.BlockSpec((rb, LANES), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_fedcet_v_kernel, alpha=alpha),
        grid=grid,
        in_specs=[spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(x, g, d)


def _fedcet_comm_kernel(d_ref, v_ref, vb_ref, d_out_ref, x_out_ref, *,
                        c: float, alpha: float):
    v = v_ref[...]
    delta = v - vb_ref[...]
    d_out_ref[...] = d_ref[...] + c * delta
    x_out_ref[...] = v - (c * alpha) * delta


def _fedcet_comm4_kernel(d_ref, m_ref, mb_ref, v_ref, d_out_ref, x_out_ref,
                         *, c: float, alpha: float):
    delta = m_ref[...] - mb_ref[...]
    d_out_ref[...] = d_ref[...] + c * delta
    x_out_ref[...] = v_ref[...] - (c * alpha) * delta


def fedcet_comm4_2d(d, m, m_bar, v, *, c: float, alpha: float,
                    interpret: bool):
    """The compressed-message aggregation pair (oracle:
    ref.fedcet_comm with ``v=``): delta comes from the WIRE message
    ``m`` while the x-update starts from the exact local ``v``.
    All operands [rows, LANES]."""
    rows = d.shape[0]
    rb = min(ROW_BLOCK, rows)
    grid = (pl.cdiv(rows, rb),)
    spec = pl.BlockSpec((rb, LANES), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_fedcet_comm4_kernel, c=c, alpha=alpha),
        grid=grid,
        in_specs=[spec, spec, spec, spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct(d.shape, d.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        interpret=interpret,
    )(d, m, m_bar, v)


def _round_tail_kernel(v_ref, h_ref, d_ref, u_ref, s_ref, w_ref, den_ref,
                       d_out_ref, x_out_ref, h_out_ref, *,
                       c: float, alpha: float, beta: float, levels: int):
    import jax.numpy as jnp

    v = v_ref[...]                      # [C, rb, LANES]
    h = h_ref[...]
    s = s_ref[...]                      # [rb, 1] per-leaf quant step
    inv = jnp.where(s > 0, 1.0 / s, 0.0)
    q = jnp.clip(jnp.floor((v - h) * inv + u_ref[...][None]),
                 -levels, levels)
    qs = q * s
    recon = h + qs
    w = w_ref[...][:, :, None]          # [C, 1, 1] client weights
    m_bar = jnp.sum(recon * w, axis=0, keepdims=True) / den_ref[0, 0]
    delta = recon - m_bar
    d_out_ref[...] = d_ref[...] + c * delta
    x_out_ref[...] = v - (c * alpha) * delta
    h_out_ref[...] = h + beta * qs


#: scoped-VMEM bytes the round tail's double-buffered blocks may take:
#: half of the 16 MiB default scoped limit on v5e, leaving the other half
#: for the kernel body's [C, rb, lb] temporaries.
TAIL_BLOCK_BUDGET = 8 * 2**20
#: the default scoped-VMEM limit; the tail asks for more only above it.
DEFAULT_SCOPED_VMEM = 16 * 2**20


def tail_blocks(n_clients: int, rows: int, itemsize: int) -> tuple[int, int]:
    """(row block, lane block) of the fused round tail. Each grid step
    keeps every client of its block resident (the cross-client reduce
    happens in-kernel), so the block shrinks as the client count grows:
    first rows, down to one 8-row sublane tile, then lanes, halving down
    to one 128-lane tile. A row block is a multiple of 8 or all ``rows``;
    a lane block divides the row's ``LANES``."""
    # double-buffered v, h, d in + d', x', h' out per client, plus u
    per_elem = 2 * (6 * n_clients + 1) * itemsize
    rb = TAIL_BLOCK_BUDGET // (per_elem * LANES) // 8 * 8
    if rb >= 8:
        return (rows if rows <= rb else rb), LANES
    lb = LANES
    while lb > 128 and per_elem * 8 * lb > TAIL_BLOCK_BUDGET:
        lb //= 2
    return min(rows, 8), lb


def fedcet_round_tail_3d(v, h, d, u, scale, w, den, *, c: float,
                         alpha: float, beta: float, bits: int,
                         interpret: bool):
    """The fused shift:q8 -> weighted reduce -> FedCET pair round tail
    (oracle: ref.fedcet_round_tail) — ONE kernel visit per element: the
    quantizer codes, the reconstructed wire message and the client mean
    all live in VMEM and never round-trip to HBM.

    ``v``/``h``/``d``: [clients, rows, LANES]; ``u``: [rows, LANES];
    ``scale``: [rows, 1]; ``w``: [clients, 1]; ``den``: [1, 1]. The grid
    tiles rows and lanes (:func:`tail_blocks`); every client of a block
    is resident so the cross-client reduction happens in-kernel."""
    n_clients, rows, _ = v.shape
    rb, lb = tail_blocks(n_clients, rows, v.dtype.itemsize)
    grid = (pl.cdiv(rows, rb), LANES // lb)
    cs = pl.BlockSpec((n_clients, rb, lb), lambda i, j: (0, i, j))
    rs = pl.BlockSpec((rb, lb), lambda i, j: (i, j))
    ss = pl.BlockSpec((rb, 1), lambda i, j: (i, 0))
    ws = pl.BlockSpec((n_clients, 1), lambda i, j: (0, 0))
    ds = pl.BlockSpec((1, 1), lambda i, j: (0, 0))
    sds = jax.ShapeDtypeStruct(v.shape, v.dtype)
    # streams + ~6 [C, rb, lb] body temporaries; above the default scoped
    # limit (several hundred clients) ask Mosaic for what the step needs.
    need = ((2 * (6 * n_clients + 1) + 6 * n_clients) * rb * lb
            * v.dtype.itemsize)
    params = None
    if need > DEFAULT_SCOPED_VMEM:
        from jax.experimental.pallas import tpu as pltpu

        params = pltpu.CompilerParams(vmem_limit_bytes=need + 2**20)
    return pl.pallas_call(
        functools.partial(_round_tail_kernel, c=c, alpha=alpha, beta=beta,
                          levels=2 ** (bits - 1) - 1),
        grid=grid,
        in_specs=[cs, cs, cs, rs, ss, ws, ds],
        out_specs=[cs, cs, cs],
        out_shape=[sds, sds, sds],
        compiler_params=params,
        interpret=interpret,
    )(v, h, d, u, scale, w, den)


def fedcet_comm_2d(d, v, v_bar, *, c: float, alpha: float,
                   interpret: bool):
    """Fused aggregation update; all operands [rows, LANES]."""
    rows = d.shape[0]
    rb = min(ROW_BLOCK, rows)
    grid = (pl.cdiv(rows, rb),)
    spec = pl.BlockSpec((rb, LANES), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_fedcet_comm_kernel, c=c, alpha=alpha),
        grid=grid,
        in_specs=[spec, spec, spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct(d.shape, d.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        interpret=interpret,
    )(d, v, v_bar)
