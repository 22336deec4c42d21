"""jit'd public wrappers around the Pallas kernels.

Handles arbitrary leaf shapes: flatten -> pad to a whole number of
(rows x 1024) lanes -> kernel -> unpad/reshape. On non-TPU backends the
kernels run in interpret mode (Python emulation of the kernel body), which
is how the CPU test suite validates them; on TPU they lower through Mosaic.

The FedCET hot-path entry points (``fedcet_v``, ``fedcet_comm``,
``fedcet_round_tail``) additionally take ``impl``:

* ``"auto"`` (default) — the Mosaic kernel on TPU; OFF-TPU the same math
  as plain XLA-compiled jnp (for the fused round tail: with explicit
  ``optimization_barrier`` materialization points replicating the
  kernel's staging — see ``fedcet_round_tail``). This is what the engine
  uses: interpret-mode Pallas re-emulates the grid in Python and is far
  too slow to EXECUTE a real round on CPU.
* ``"kernel"`` — force the pallas_call (interpret mode off-TPU); the
  kernel parity tests pin this against ``"ref"``.
* ``"ref"`` — force the kernels/ref.py oracle expression.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import fedcet_update as K
from repro.kernels import ref as R


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _use_kernel(impl: str) -> bool:
    if impl == "auto":
        return jax.default_backend() == "tpu"
    if impl in ("kernel", "ref"):
        return impl == "kernel"
    raise ValueError(f"unknown impl {impl!r} (auto | kernel | ref)")


def _tile(a):
    n = a.size
    rows = -(-n // K.LANES)
    pad = rows * K.LANES - n
    flat = jnp.pad(a.reshape(-1), (0, pad))
    return flat.reshape(rows, K.LANES), n


def _untile(t, n, shape):
    return t.reshape(-1)[:n].reshape(shape)


@functools.partial(jax.jit, static_argnames=("alpha", "impl"))
def fedcet_v(x, g, d, alpha: float, impl: str = "auto"):
    """Fused FedCET local-step triad (see kernels/ref.py:fedcet_v)."""
    if not _use_kernel(impl):
        return R.fedcet_v(x, g, d, alpha)
    t_x, n = _tile(x)
    t_g, _ = _tile(g)
    t_d, _ = _tile(d)
    out = K.fedcet_v_2d(t_x, t_g, t_d, alpha=alpha, interpret=_interpret())
    return _untile(out, n, x.shape)


@functools.partial(jax.jit, static_argnames=("kind", "window", "chunk",
                                              "q_blk", "kv_blk"))
def flash_attention(q, k, v, *, kind: str = "causal", window: int = 0,
                    chunk: int = 0, q_blk: int = 256, kv_blk: int = 256):
    """Grouped-GQA Pallas flash attention (see kernels/flash_attention.py)."""
    from repro.kernels import flash_attention as K3

    return K3.flash_attention(q, k, v, kind=kind, window=window, chunk=chunk,
                              q_blk=q_blk, kv_blk=kv_blk,
                              interpret=_interpret())


@jax.jit
def ssd_intra(x, dt, a_cs, Bm, Cm):
    """Pallas SSD intra-chunk term (see kernels/ssd_intra.py)."""
    from repro.kernels import ssd_intra as K2

    return K2.ssd_intra(x, dt, a_cs, Bm, Cm, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("bits",))
def stochastic_quantize(a, u, scale, bits: int):
    """Fused dithered-quantize round-trip (see kernels/quantize.py;
    oracle: kernels/ref.py:stochastic_quantize). ``u`` is the uniform
    dither (same shape as ``a``), ``scale`` the scalar per-leaf step."""
    from repro.kernels import quantize as KQ

    t_a, n = _tile(a)
    t_u, _ = _tile(u)
    t_s = jnp.asarray(scale, a.dtype).reshape(1, 1)  # scalar block, not a stream
    out = KQ.stochastic_quantize_2d(t_a, t_u, t_s, bits=bits,
                                    interpret=_interpret())
    return _untile(out, n, a.shape)


@functools.partial(jax.jit, static_argnames=("slots",))
def gossip_reduce(contrib, *, slots: int):
    """Fixed-slot gossip segment reduce (see kernels/gossip_reduce.py;
    oracle: kernels/ref.py:segment_reduce). ``contrib`` is the
    ``[n * slots, D]`` gathered-and-weighted neighbor contributions of
    the sparse exchange lowering (pad slots already zero-weighted);
    returns the per-node sums ``[n, D]``. Pads nodes to the node block
    and lanes to the lane block; zero pad rows reduce to zero rows that
    are sliced off."""
    from repro.kernels import gossip_reduce as KG

    rows, d = contrib.shape
    n = rows // slots
    nb = min(KG.NODE_BLOCK, n)
    db = min(KG.LANE_BLOCK, -(-d // 128) * 128)
    n_pad = -n % nb
    d_pad = -d % db
    t = jnp.pad(contrib, ((0, n_pad * slots), (0, d_pad)))
    out = KG.segment_reduce_2d(t, slots=slots, interpret=_interpret())
    return out[:n, :d]


@functools.partial(jax.jit, static_argnames=("bins", "lo", "hi", "k", "impl"))
def telemetry_sketch(data, *, bins: int, lo: float, hi: float, k: int,
                     impl: str = "auto"):
    """One-pass per-client distribution sketch over the packed client
    store (see kernels/telemetry_reduce.py; oracle:
    kernels/ref.py:client_sketch). ``data`` is ``[clients, ...]`` —
    typically the arena's ``[clients, rows, 1024]`` buffer, flattened
    per client here (zero pad entries contribute 0 to the norms).

    Returns ``(norms [clients], hist [bins] int32, top_vals [k],
    top_ids [k] int32)``: the per-client ``||x_i||``, their
    log10-histogram over ``[10^lo, 10^hi)`` and the k largest with their
    client indices. The top-k runs on the ``[clients]`` norms vector out
    here — next to a D-wide sweep it is free."""
    from repro.kernels import telemetry_reduce as KT

    n = data.shape[0]
    if _use_kernel(impl):
        sq, hist = KT.client_sketch_3d(data, bins=bins, lo=lo, hi=hi,
                                       interpret=_interpret())
        sq, hist = sq[:, 0], hist[0, :bins]
    else:
        sq, hist = R.client_sketch(data.reshape(n, -1), bins=bins, lo=lo,
                                   hi=hi)
    norms = jnp.sqrt(sq)
    tv, ti = jax.lax.top_k(norms, min(k, n))
    return norms, hist, tv, ti.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("c", "alpha", "impl"))
def fedcet_comm(d, m, m_bar, c: float, alpha: float, v=None,
                impl: str = "auto"):
    """Fused FedCET aggregation pair (see kernels/ref.py:fedcet_comm).

    ``m`` is the client's own WIRE message; pass ``v`` (the exact local
    vector, the engine's ``mctx``) when the message path is compressed —
    the drift delta comes from ``m`` while the x-update starts from
    ``v``. ``v=None`` keeps the uncompressed behavior (``v = m``)."""
    if not _use_kernel(impl):
        d_new, x_new = R.fedcet_comm(d, m, jnp.broadcast_to(m_bar, m.shape),
                                     c, alpha, v=v)
        return d_new, x_new
    t_d, n = _tile(d)
    t_m, _ = _tile(m)
    t_mb, _ = _tile(jnp.broadcast_to(m_bar, m.shape))
    if v is None:
        d_new, x_new = K.fedcet_comm_2d(t_d, t_m, t_mb, c=c, alpha=alpha,
                                        interpret=_interpret())
    else:
        t_v, _ = _tile(v)
        d_new, x_new = K.fedcet_comm4_2d(t_d, t_m, t_mb, t_v, c=c,
                                         alpha=alpha, interpret=_interpret())
    return _untile(d_new, n, d.shape), _untile(x_new, n, m.shape)


@functools.partial(jax.jit, static_argnames=("bits",))
def stochastic_quantize_rows(a, u, scale_rows, bits: int):
    """Row-wise-scale dithered-quantize round-trip over a pre-tiled
    ``[rows, 1024]`` arena buffer (see kernels/quantize.py
    ``stochastic_quantize_rows_2d``); ``scale_rows`` is ``[rows, 1]``."""
    from repro.kernels import quantize as KQ

    return KQ.stochastic_quantize_rows_2d(a, u, scale_rows, bits=bits,
                                          interpret=_interpret())


@functools.partial(jax.jit,
                   static_argnames=("c", "alpha", "beta", "bits", "impl"))
def fedcet_round_tail(v, h, d, u, scale, w, den, *, c: float, alpha: float,
                      beta: float, bits: int, impl: str = "auto"):
    """The fused shift-compressed FedCET round tail (oracle:
    kernels/ref.py:fedcet_round_tail): dithered-quantize the shifted
    residual, reconstruct the wire message, weighted-reduce it across
    clients and apply the paired ``(d', x')`` update plus the DIANA shift
    step — one kernel visit per element on TPU.

    Shapes: ``v``/``h``/``d`` [clients, rows, 1024]; ``u`` [rows, 1024];
    ``scale`` [rows, 1]; ``w`` [clients, 1]; ``den`` [1, 1].

    Off-TPU ``"auto"`` compiles the oracle expression with
    ``optimization_barrier`` at the kernel's two natural materialization
    points — the int8 quantizer codes and the client mean — pinning the
    two-pass schedule the Mosaic kernel implements (second pass re-reads
    1-byte codes). On CPU this lands AT the measured stream roofline
    (~39 B/elem model); XLA's per-leaf fusion reaches the same byte
    floor, so the CPU win is structural (a ~10x compiled-instruction
    collapse), not wall-clock — measured at 128 clients on the reduced
    fedlm-100m geometry, see benchmarks/fed_lm_bench.py."""
    if _use_kernel(impl):
        return K.fedcet_round_tail_3d(v, h, d, u, scale, w, den,
                                      c=c, alpha=alpha, beta=beta, bits=bits,
                                      interpret=_interpret())
    if impl == "ref":
        return R.fedcet_round_tail(v, h, d, u, scale, w[:, :, None],
                                   den[0, 0], c=c, alpha=alpha, beta=beta,
                                   bits=bits)
    bar = jax.lax.optimization_barrier
    levels = 2 ** (bits - 1) - 1
    code_t = jnp.int8 if bits <= 8 else jnp.int16
    inv = jnp.where(scale > 0, 1.0 / scale, 0.0)
    # pass 1: materialize the integral codes once, 1 byte/elem (exact:
    # floor lands on integers within +-levels, so the cast round-trips).
    q = bar(jnp.clip(jnp.floor((v - h) * inv + u), -levels,
                     levels).astype(code_t))
    qs = q.astype(v.dtype) * scale
    m_bar = bar(jnp.sum((h + qs) * w[:, :, None], axis=0, keepdims=True)
                / den[0, 0])
    # pass 2: one fused elementwise sweep reading q (i8), h, d, v.
    qs = q.astype(v.dtype) * scale
    delta = (h + qs) - m_bar
    return d + c * delta, v - (c * alpha) * delta, h + beta * qs
