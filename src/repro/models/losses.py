"""Memory-bounded language-model losses.

``chunked_ce`` never materializes the full [B, S, V] logits tensor: it scans
the sequence in chunks, projecting each chunk through the LM head and
computing its cross-entropy inside a remat'd scan body (backward recomputes
the chunk's logits). Per-chunk logits carry a vocab-sharded constraint
(sharding_ctx.shard_logits). At llama4-scout scale this replaces ~13 GB of
live f32 logits per device with ~0.4 GB per chunk.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.utils.sharding_ctx import shard_logits

CE_CHUNK = 512


import functools


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _guard(x, dtype_name: str):
    return x


def _guard_fwd(x, dtype_name):
    return x, None


def _guard_bwd(dtype_name, _, g):
    return (g.astype(dtype_name),)


_guard.defvjp(_guard_fwd, _guard_bwd)


def _grad_dtype_guard(x):
    """Identity forward; backward casts the cotangent to x's dtype.

    The CE loss upcasts to f32 at the very end of the graph, and JAX
    transpose rules propagate that f32 cotangent UNCHANGED through every
    residual add — so without this guard the whole backward pass (saved
    activation stacks, attention bwd, weight-grad accumulators) runs in
    f32: 2x the bytes of the bf16 forward. Verified on a minimal scan
    repro; see EXPERIMENTS.md §Dry-run.
    """
    return _guard(x, str(x.dtype))


def chunked_ce(x, head, tokens, *, prefix: int = 0, chunk: int = CE_CHUNK,
               logits_scaling: float = 1.0):
    """Mean next-token CE of the logits ``x @ head / logits_scaling``.

    x:      [B, S_total, d] final-norm hidden states
    head:   [d, V]
    tokens: [B, S_text] — x positions prefix..prefix+S_text-1 align with them
            (prefix = image-token count for VLMs, else 0).
    """
    B = x.shape[0]
    x = _grad_dtype_guard(x)
    preds = x[:, prefix:-1, :]              # predicts tokens[:, 1:]
    targets = tokens[:, 1:]
    n = targets.shape[1]
    c = min(chunk, n)
    pad = (-n) % c
    if pad:
        preds = jnp.pad(preds, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
    mask = (jnp.arange(n + pad) < n).astype(jnp.float32)
    nc = (n + pad) // c
    preds = preds.reshape(B, nc, c, -1).transpose(1, 0, 2, 3)
    targets = targets.reshape(B, nc, c).transpose(1, 0, 2)
    maskc = mask.reshape(nc, c)

    def body(acc, inp):
        x_c, t_c, m_c = inp                 # [B,c,d], [B,c], [c]
        logits = shard_logits((x_c @ head).astype(jnp.float32))
        if logits_scaling != 1.0:
            logits = logits / logits_scaling
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, t_c[..., None], axis=-1)[..., 0]
        return acc + jnp.sum((logz - gold) * m_c[None, :]), None

    total, _ = jax.lax.scan(jax.checkpoint(body), jnp.zeros((), jnp.float32),
                            (preds, targets, maskc))
    return total / (B * n)
