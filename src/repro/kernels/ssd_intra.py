"""Pallas TPU kernel: Mamba2 SSD intra-chunk (quadratic) term.

The SSD dual form's hot spot is the per-chunk attention-like computation

    y[i] = sum_{j<=i} (C_i . B_j) * exp(a_cs[i] - a_cs[j]) * dt[j] * x[j]

(arXiv:2405.21060, "quadratic mode"). Per (batch, chunk, head) tile this is
two MXU matmuls — scores = C @ B^T [Lc, Lc] and y = (scores * decay * dt)
@ x [Lc, P] — plus a VPU decay mask. Grid = (B, n_chunks, H); block shapes
are the natural (Lc=128, N=128/64, P=64) tiles, all lane/sublane aligned.

VMEM per step: C,B [Lc,N] + x,y [Lc,P] + scores [Lc,Lc] f32 ~ 0.2 MiB —
far under budget, so the kernel is bandwidth-friendly and leaves room for a
future double-buffered multi-head variant.

Validated against the pure-jnp oracle (kernels/ref.py:ssd_intra) in
interpret mode; the inter-chunk recurrence stays in the XLA scan
(models/mamba2.ssd_chunked), which can consume this kernel via
``use_kernel=True`` on TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ssd_intra_kernel(x_ref, dt_ref, acs_ref, b_ref, c_ref, o_ref):
    # blocks: x [Lc, P], dt [Lc], a_cs [Lc], B/C [Lc, N], o [Lc, P]
    cb = jnp.dot(c_ref[...].astype(jnp.float32),
                 b_ref[...].astype(jnp.float32).T)          # [Lc, Lc] MXU
    acs = acs_ref[...].astype(jnp.float32)                  # [Lc]
    seg = acs[:, None] - acs[None, :]                       # [Lc(i), Lc(j)]
    lc = seg.shape[0]
    causal = (jax.lax.broadcasted_iota(jnp.int32, (lc, lc), 0)
              >= jax.lax.broadcasted_iota(jnp.int32, (lc, lc), 1))
    seg = jnp.where(causal, seg, -jnp.inf)
    w = cb * jnp.exp(seg) * dt_ref[...].astype(jnp.float32)[None, :]
    y = jnp.dot(w, x_ref[...].astype(jnp.float32))          # [Lc, P] MXU
    o_ref[...] = y.astype(o_ref.dtype)


def ssd_intra(x, dt, a_cs, Bm, Cm, *, interpret: bool):
    """x: [B, Nc, Lc, H, P]; dt/a_cs: [B, Nc, Lc, H]; Bm/Cm: [B, Nc, Lc, N].
    Returns y_intra [B, Nc, Lc, H, P] (f32 accumulated, cast to x.dtype)."""
    Bsz, Nc, Lc, H, P = x.shape
    N = Bm.shape[-1]
    grid = (Bsz, Nc, H)
    return pl.pallas_call(
        _ssd_intra_kernel,
        grid=grid,
        in_specs=[
            # None block dims are squeezed away inside the kernel refs.
            pl.BlockSpec((None, None, Lc, None, P), lambda b, c, h: (b, c, 0, h, 0)),
            pl.BlockSpec((None, None, Lc, None), lambda b, c, h: (b, c, 0, h)),
            pl.BlockSpec((None, None, Lc, None), lambda b, c, h: (b, c, 0, h)),
            pl.BlockSpec((None, None, Lc, N), lambda b, c, h: (b, c, 0, 0)),
            pl.BlockSpec((None, None, Lc, N), lambda b, c, h: (b, c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, Lc, None, P),
                               lambda b, c, h: (b, c, 0, h, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(x, dt, a_cs, Bm, Cm)
