"""Attention: GQA/MQA/MHA with RoPE, qk-norm, full/sliding/chunked masks.

Three execution paths, chosen by context:

* ``attend_naive`` — materializes the [S, S] score matrix. Used for short
  sequences and as the oracle the blockwise path is tested against.
* ``attend_blockwise`` — flash-style streaming softmax over KV blocks
  (lax.scan, running max/denominator), so a 32k-token prefill never
  materializes a 32k x 32k matrix. Mask structure (causal / sliding window /
  chunked-local a la Llama-4 iRoPE) is applied per block from indices.
* ``attend_decode`` — single-query attention against a KV cache in grouped
  form (no KV-head repetition; queries reshaped to [B, 1, Hkv, G, Dh]), so
  the cache can be sequence-sharded over the `model` mesh axis and the
  softmax reductions lower to small all-reduces.

KV caches come in two flavors: full-length (``init_cache``) and ring-buffer
(``init_swa_cache``) whose size is just the attention window — the latter is
what makes `long_500k` decode O(window) for sliding-window architectures.
Keys are stored post-RoPE (absolute positions), so ring wraparound needs no
re-rotation.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.models.layers import apply_rope, dense_init, rms_norm

NEG_INF = -1e30


# ------------------------------------------------------------------- params
def init_attention(key, d_model: int, n_heads: int, n_kv_heads: int,
                   head_dim: int, dtype, *, qk_norm: bool = False,
                   with_bias: bool = False):
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d_model, n_heads * head_dim, dtype),
        "wk": dense_init(ks[1], d_model, n_kv_heads * head_dim, dtype),
        "wv": dense_init(ks[2], d_model, n_kv_heads * head_dim, dtype),
        "wo": dense_init(ks[3], n_heads * head_dim, d_model, dtype),
    }
    if qk_norm:
        p["q_norm"] = jnp.zeros((head_dim,), dtype)
        p["k_norm"] = jnp.zeros((head_dim,), dtype)
    if with_bias:
        p["bq"] = jnp.zeros((n_heads * head_dim,), dtype)
        p["bk"] = jnp.zeros((n_kv_heads * head_dim,), dtype)
        p["bv"] = jnp.zeros((n_kv_heads * head_dim,), dtype)
        p["bo"] = jnp.zeros((d_model,), dtype)
    return p


def _project_qkv(params, x, n_heads, n_kv_heads, head_dim):
    B, S, _ = x.shape
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if "bq" in params:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    q = q.reshape(B, S, n_heads, head_dim)
    k = k.reshape(B, S, n_kv_heads, head_dim)
    v = v.reshape(B, S, n_kv_heads, head_dim)
    return q, k, v


def _qk_norm(params, q, k):
    if "q_norm" in params:
        q = rms_norm(q, params["q_norm"])
        k = rms_norm(k, params["k_norm"])
    return q, k


# -------------------------------------------------------------------- masks
def mask_fn(kind: str, *, window: int = 0, chunk: int = 0):
    """Returns allowed(q_pos, k_pos) -> bool array, broadcasting over inputs."""

    def allowed(qp, kp):
        ok = kp <= qp  # causal
        if kind == "sliding":
            ok &= kp > qp - window
        elif kind == "chunked":
            ok &= (kp // chunk) == (qp // chunk)
        elif kind == "bidirectional":
            ok = jnp.ones_like(ok)
        return ok

    return allowed


# ------------------------------------------------------------- naive oracle
def _scaled(scores, head_dim: int, scale):
    """Scores times ``scale`` (None: over sqrt(head_dim))."""
    if scale is None:
        return scores / jnp.sqrt(head_dim)
    return scores * scale


def attend_naive(q, k, v, allowed, *, q_positions=None, k_positions=None,
                 scale=None):
    """q [B,S,H,D], k/v [B,T,H,D] (heads already matched). Oracle path.
    ``scale`` multiplies the scores (None: 1/sqrt(D))."""
    B, S, H, D = q.shape
    T = k.shape[1]
    qp = jnp.arange(S) if q_positions is None else q_positions
    kp = jnp.arange(T) if k_positions is None else k_positions
    scores = _scaled(jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32),
                     D, scale)
    mask = allowed(qp[:, None], kp[None, :])  # [S, T]
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhst,bthd->bshd", probs.astype(q.dtype), v)


# ------------------------------------------------------ blockwise (flash)
def attend_blockwise(q, k, v, allowed, *, block_size: int = 512, scale=None):
    """Streaming-softmax attention, scanning KV blocks. Memory per step is
    O(S * block) instead of O(S^2). Matches attend_naive to float tolerance
    (property-tested in tests/test_attention.py).

    GQA is handled in GROUPED form — q reshaped to [B,S,Hkv,G,D], k/v kept
    at Hkv heads — so the KV stream is never materialized repeated to Hq
    heads (a 6x traffic/memory saving for 48q/8kv configs; EXPERIMENTS.md
    §Perf). Score/AV dots take bf16 inputs with f32 accumulation
    (preferred_element_type), so no f32 copy of K/V is ever created.
    """
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    T = k.shape[1]
    nblk = -(-T // block_size)
    pad = nblk * block_size - T
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(B, nblk, block_size, Hkv, D).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nblk, block_size, Hkv, D).transpose(1, 0, 2, 3, 4)
    qg = q.reshape(B, S, Hkv, G, D)
    qpos = jnp.arange(S)

    def body(carry, inp):
        acc, m, denom = carry  # [B,S,Hkv,G,D] f32, [B,S,Hkv,G] x2
        blk_idx, kblk, vblk = inp
        kpos = blk_idx * block_size + jnp.arange(block_size)
        scores = _scaled(jnp.einsum("bshgd,bthd->bshgt", qg, kblk,
                                    preferred_element_type=jnp.float32),
                         D, scale)
        ok = allowed(qpos[:, None], kpos[None, :]) & (kpos < T)[None, :]
        scores = jnp.where(ok[None, :, None, None, :], scores, NEG_INF)
        m_blk = jnp.max(scores, axis=-1)
        m_new = jnp.maximum(m, m_blk)
        # renormalize the running accumulator
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new[..., None])
        acc = acc * alpha[..., None] + jnp.einsum(
            "bshgt,bthd->bshgd", p.astype(q.dtype), vblk,
            preferred_element_type=jnp.float32)
        denom = denom * alpha + jnp.sum(p, axis=-1)
        return (acc, m_new, denom), None

    acc0 = jnp.zeros((B, S, Hkv, G, D), jnp.float32)
    m0 = jnp.full((B, S, Hkv, G), NEG_INF, jnp.float32)
    d0 = jnp.zeros((B, S, Hkv, G), jnp.float32)
    # remat the per-block body: without it, scan AD saves the f32 score/prob
    # tensors of EVERY kv block as backward residuals (O(S * T) memory —
    # tens of GB at 4k x 4k training shapes); with it, backward recomputes
    # each block's scores from (q, kblk) for flash-attention-like memory.
    (acc, _, denom), _ = jax.lax.scan(
        jax.checkpoint(body), (acc0, m0, d0), (jnp.arange(nblk), kb, vb))
    out = acc / jnp.maximum(denom[..., None], 1e-30)
    return out.reshape(B, S, Hq, D).astype(q.dtype)


# ------------------------------------------------------------ full attention
def attention(params, x, *, n_heads: int, n_kv_heads: int, head_dim: int,
              kind: str = "causal", window: int = 0, chunk: int = 0,
              rope_theta: float = 1e4, use_rope: bool = True,
              positions=None, block_size: int = 512,
              force_naive: bool = False, use_pallas: bool = False,
              scale=None):
    """Training / prefill attention over a full sequence. Returns [B,S,d].
    ``scale`` multiplies the attention scores (None: 1/sqrt(head_dim))."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(params, x, n_heads, n_kv_heads, head_dim)
    q, k = _qk_norm(params, q, k)
    if use_rope:
        pos = jnp.arange(S)[None, :] if positions is None else positions
        q = apply_rope(q, pos, rope_theta)
        k = apply_rope(k, pos, rope_theta)
    allowed = mask_fn("causal" if kind == "full" else kind, window=window,
                      chunk=chunk)
    if use_pallas and not force_naive:
        from repro.kernels import ops as kops

        if scale is not None:   # the kernel divides by sqrt(head_dim)
            q = q * (scale * head_dim ** 0.5)
        out = kops.flash_attention(
            q, k, v, kind=("causal" if kind == "full" else kind),
            window=window, chunk=chunk,
            q_blk=min(block_size, 256), kv_blk=min(block_size, 256))
        out = out.reshape(B, S, n_heads * head_dim) @ params["wo"]
        if "bo" in params:
            out = out + params["bo"]
        return out
    if force_naive or S <= 1024:
        # naive oracle path: repeat KV heads up to the query-head count
        groups = n_heads // n_kv_heads
        if groups > 1:
            k = jnp.repeat(k, groups, axis=2)
            v = jnp.repeat(v, groups, axis=2)
        out = attend_naive(q, k, v, allowed, scale=scale)
    else:
        # blockwise path handles GQA in grouped form (no KV repeat)
        out = attend_blockwise(q, k, v, allowed, block_size=block_size,
                               scale=scale)
    out = out.reshape(B, S, n_heads * head_dim) @ params["wo"]
    if "bo" in params:
        out = out + params["bo"]
    return out


# ----------------------------------------------------------------- KV cache
class KVCache(NamedTuple):
    k: jax.Array          # [B, C, Hkv, D] (C = max len, or window for SWA)
    v: jax.Array          # [B, C, Hkv, D]
    pos: jax.Array        # [C] absolute position stored in each slot (-1 empty)
    length: jax.Array     # scalar: tokens seen so far


def init_cache(batch: int, capacity: int, n_kv_heads: int, head_dim: int,
               dtype) -> KVCache:
    return KVCache(
        k=jnp.zeros((batch, capacity, n_kv_heads, head_dim), dtype),
        v=jnp.zeros((batch, capacity, n_kv_heads, head_dim), dtype),
        pos=jnp.full((capacity,), -1, jnp.int32),
        length=jnp.zeros((), jnp.int32),
    )


def prefill_into_cache(cache: KVCache, k, v, *, ring: bool = False) -> KVCache:
    """Write a prefix [B, S, Hkv, D] (post-RoPE) into the cache.

    Non-ring: slots [0, S). Ring (cap < S possible): token at absolute
    position p lands in slot p % cap, so subsequent ring appends
    (slot = t % cap) always evict exactly the expired entry."""
    S = k.shape[1]
    cap = cache.k.shape[1]
    if ring and S > cap:
        k, v = k[:, -cap:], v[:, -cap:]
        kept_pos = jnp.arange(S - cap, S, dtype=jnp.int32)
        shift = S % cap  # kept[i] has pos S-cap+i -> slot (i + S%cap) % cap
        new_k = jnp.roll(k, shift, axis=1)
        new_v = jnp.roll(v, shift, axis=1)
        pos = jnp.roll(kept_pos, shift)
        return cache._replace(k=new_k, v=new_v, pos=pos,
                              length=jnp.asarray(S, jnp.int32))
    new_k = jax.lax.dynamic_update_slice(cache.k, k, (0, 0, 0, 0))
    new_v = jax.lax.dynamic_update_slice(cache.v, v, (0, 0, 0, 0))
    pos = cache.pos.at[:S].set(jnp.arange(S, dtype=jnp.int32))
    return cache._replace(k=new_k, v=new_v, pos=pos,
                          length=jnp.asarray(S, jnp.int32))


def append_to_cache(cache: KVCache, k1, v1, *, ring: bool = False) -> KVCache:
    """Append one token's K/V [B, 1, Hkv, D]; ring caches wrap."""
    cap = cache.k.shape[1]
    t = cache.length
    slot = ((t % cap) if ring else jnp.minimum(t, cap - 1)).astype(jnp.int32)
    zero = jnp.zeros((), jnp.int32)
    new_k = jax.lax.dynamic_update_slice(cache.k, k1, (zero, slot, zero, zero))
    new_v = jax.lax.dynamic_update_slice(cache.v, v1, (zero, slot, zero, zero))
    pos = jax.lax.dynamic_update_slice(cache.pos, t[None].astype(jnp.int32), (slot,))
    return cache._replace(k=new_k, v=new_v, pos=pos, length=t + 1)


def attend_decode(q1, cache: KVCache, *, window: int = 0, chunk: int = 0,
                  kind: str = "full", scale=None):
    """One-token attention vs cache, grouped-query form (no KV repeat).

    q1: [B, Hq, D]. Returns [B, Hq, D]. The cache slot positions (absolute)
    drive masking, so full, sliding-window(ring) and chunked all share this
    path. Softmax reductions are over the (possibly `model`-sharded) cache
    slot axis.
    """
    B, Hq, D = q1.shape
    Hkv = cache.k.shape[2]
    G = Hq // Hkv
    qg = q1.reshape(B, Hkv, G, D)
    t = cache.length - 1  # absolute position of the query token
    scores = _scaled(jnp.einsum("bhgd,bshd->bhgs", qg.astype(jnp.float32),
                                cache.k.astype(jnp.float32)), D, scale)
    kp = cache.pos
    ok = (kp >= 0) & (kp <= t)
    if kind == "sliding":
        ok &= kp > t - window
    elif kind == "chunked":
        ok &= (kp // chunk) == (t // chunk)
    scores = jnp.where(ok[None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", probs,
                     cache.v.astype(jnp.float32))
    return out.reshape(B, Hq, D).astype(cache.k.dtype)


def decode_attention(params, x1, cache: KVCache, *, n_heads: int,
                     n_kv_heads: int, head_dim: int, kind: str = "full",
                     window: int = 0, chunk: int = 0, rope_theta: float = 1e4,
                     use_rope: bool = True, ring: bool = False, scale=None):
    """Full decode step for one layer: project, rope at absolute position,
    append to cache, attend. x1: [B, 1, d]. Returns ([B, 1, d], new cache)."""
    B = x1.shape[0]
    q, k, v = _project_qkv(params, x1, n_heads, n_kv_heads, head_dim)
    q, k = _qk_norm(params, q, k)
    if use_rope:
        pos = cache.length[None, None].astype(jnp.int32)  # [1,1]
        q = apply_rope(q, pos, rope_theta)
        k = apply_rope(k, pos, rope_theta)
    cache = append_to_cache(cache, k, v, ring=ring)
    out = attend_decode(q[:, 0], cache, window=window, chunk=chunk, kind=kind,
                        scale=scale)
    out = out.reshape(B, 1, n_heads * head_dim) @ params["wo"]
    if "bo" in params:
        out = out + params["bo"]
    return out, cache


def prefill_attention(params, x, *, n_heads: int, n_kv_heads: int,
                      head_dim: int, cache: KVCache, kind: str = "full",
                      window: int = 0, chunk: int = 0, rope_theta: float = 1e4,
                      use_rope: bool = True, block_size: int = 512,
                      ring: bool = False, scale=None):
    """Prefill: full-sequence attention AND populate the cache (post-RoPE)."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(params, x, n_heads, n_kv_heads, head_dim)
    q, k = _qk_norm(params, q, k)
    if use_rope:
        pos = jnp.arange(S)[None, :]
        q = apply_rope(q, pos, rope_theta)
        k = apply_rope(k, pos, rope_theta)
    cache = prefill_into_cache(cache, k, v, ring=ring)
    allowed = mask_fn("causal" if kind == "full" else kind, window=window,
                      chunk=chunk)
    if S <= 1024:
        groups = n_heads // n_kv_heads
        if groups > 1:
            k = jnp.repeat(k, groups, axis=2)
            v = jnp.repeat(v, groups, axis=2)
        out = attend_naive(q, k, v, allowed, scale=scale)
    else:
        out = attend_blockwise(q, k, v, allowed, block_size=block_size,
                               scale=scale)
    out = out.reshape(B, S, n_heads * head_dim) @ params["wo"]
    if "bo" in params:
        out = out + params["bo"]
    return out, cache
