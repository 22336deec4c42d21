"""Pallas TPU kernel: grouped-GQA flash attention (forward).

The canonical TPU online-softmax schedule: grid (batch, kv_head, q_block,
kv_block), with the kv_block axis innermost so the (m, l, acc) running
statistics live in VMEM scratch across kv iterations and each output block
is written once on the last kv step. GQA is handled in grouped form — q
blocks are [q_blk, G, D] tiles against [kv_blk, D] K/V tiles, so KV is
never repeated to the query-head count (the same 6x saving the XLA
blockwise path gets, here made explicit in the kernel's BlockSpecs).

Masking (causal / sliding window / chunked-local) is applied from global
q/k indices computed off the grid position — mask kinds are static kernel
parameters, so each variant compiles its own specialized kernel.

VMEM budget per step (q_blk=256, kv_blk=256, G<=8, D<=256, f32 scratch):
q 0.5-2 MiB + k/v 0.25-1 MiB + acc/l/m ~2 MiB — comfortably inside v5e's
~128 MiB. Validated in interpret mode against models/attention.attend_naive
across shapes, dtypes, group counts and mask kinds (tests/test_kernels.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  kind: str, window: int, chunk: int, q_blk: int,
                  kv_blk: int, seq_len: int, kv_len: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...]                       # [q_blk, G, D]
    k = k_ref[...]                       # [kv_blk, D]
    v = v_ref[...]                       # [kv_blk, D]
    D = q.shape[-1]

    scores = jax.lax.dot_general(
        q, k, (((2,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)          # [q_blk, G, kv_blk]
    scores = scores / jnp.sqrt(jnp.asarray(D, jnp.float32))

    qpos = iq * q_blk + jax.lax.broadcasted_iota(
        jnp.int32, (q_blk, 1, kv_blk), 0)
    kpos = ik * kv_blk + jax.lax.broadcasted_iota(
        jnp.int32, (q_blk, 1, kv_blk), 2)
    ok = (kpos < kv_len) & (qpos < seq_len)
    if kind != "bidirectional":
        ok &= kpos <= qpos
    if kind == "sliding":
        ok &= kpos > qpos - window
    elif kind == "chunked":
        ok &= (kpos // chunk) == (qpos // chunk)
    scores = jnp.where(ok, scores, NEG_INF)

    m_prev = m_scr[...]                              # [q_blk, G]
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new[..., None])           # [q_blk, G, kv_blk]
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1)
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((2,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # [q_blk, G, D]
    acc_scr[...] = acc_scr[...] * alpha[..., None] + pv
    m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-30)[..., None]
        o_ref[...] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention(q, k, v, *, kind: str = "causal", window: int = 0,
                    chunk: int = 0, q_blk: int = 256, kv_blk: int = 256,
                    interpret: bool):
    """q: [B, S, Hq, D]; k/v: [B, T, Hkv, D]. Returns [B, S, Hq, D]."""
    B, S, Hq, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    q_blk = min(q_blk, S)
    kv_blk = min(kv_blk, T)
    nq = -(-S // q_blk)
    nk = -(-T // kv_blk)
    pad_q = nq * q_blk - S
    pad_k = nk * kv_blk - T
    qg = q.reshape(B, S, Hkv, G, D)
    if pad_q:
        qg = jnp.pad(qg, ((0, 0), (0, pad_q), (0, 0), (0, 0), (0, 0)))
    # [B, Hkv, T, D]: the K/V block's last two dims are then (kv_blk, D)
    # — Mosaic tiles the last two dims, and a squeezed Hkv there is refused.
    k = jnp.swapaxes(k, 1, 2)
    v = jnp.swapaxes(v, 1, 2)
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))

    kernel = functools.partial(
        _flash_kernel, kind=kind, window=window, chunk=chunk, q_blk=q_blk,
        kv_blk=kv_blk, seq_len=S, kv_len=T)
    import jax.experimental.pallas.tpu as pltpu

    out = pl.pallas_call(
        kernel,
        grid=(B, Hkv, nq, nk),
        in_specs=[
            pl.BlockSpec((None, q_blk, None, G, D),
                         lambda b, h, iq, ik: (b, iq, h, 0, 0)),
            pl.BlockSpec((None, None, kv_blk, D),
                         lambda b, h, iq, ik: (b, h, ik, 0)),
            pl.BlockSpec((None, None, kv_blk, D),
                         lambda b, h, iq, ik: (b, h, ik, 0)),
        ],
        out_specs=pl.BlockSpec((None, q_blk, None, G, D),
                               lambda b, h, iq, ik: (b, iq, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_blk, G), jnp.float32),      # running max m
            pltpu.VMEM((q_blk, G), jnp.float32),      # running denom l
            pltpu.VMEM((q_blk, G, D), jnp.float32),   # accumulator
        ],
        interpret=interpret,
    )(qg, k, v)
    if pad_q:
        out = out[:, :S]
    return out.reshape(B, S, Hq, D)
