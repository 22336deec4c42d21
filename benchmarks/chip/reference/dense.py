"""Plain reference of the dense decoder LM (fedlm-100m): weights from the
seed, and the next-token loss, in straightforward ``jax.numpy``.

It follows the configuration file, not the program's model code: pre-norm
blocks of grouped-query causal attention with rotary positions (the rotation
acts on the two halves of each head) and a SwiGLU MLP, RMSNorm with its
weight stored as an offset from 1, a separate LM head, and the mean
cross-entropy of each position's prediction of the next token.

``init`` draws the weights with the same keys, shapes and distributions as
the program's initialisation, so that both start from the same point; the
test at reduced sizes pins that. The type of the weights passed to ``loss``
is the storage and matmul-input type (float32 for the reference, bfloat16
for its control); norm statistics, softmax and the loss are float32 in
both.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _dense(key, d_in, d_out):
    return jax.random.normal(key, (d_in, d_out)) * (1.0 / jnp.sqrt(d_in))


def init(cfg: dict, key) -> dict:
    """Float32 weights of one model, as a nested dict with [layers, ...]
    stacked block leaves."""
    d, dff, hd = cfg["d_model"], cfg["d_ff"], cfg["head_dim"]
    hq, hk, vocab = cfg["n_heads"], cfg["n_kv_heads"], cfg["vocab_size"]
    kemb, klayers, khead = jax.random.split(key, 3)

    def block(k):
        kattn, kmlp = jax.random.split(k)
        ka = jax.random.split(kattn, 4)
        km = jax.random.split(kmlp, 3)
        return {
            "attn": {"wq": _dense(ka[0], d, hq * hd),
                     "wk": _dense(ka[1], d, hk * hd),
                     "wv": _dense(ka[2], d, hk * hd),
                     "wo": _dense(ka[3], hq * hd, d)},
            "ln1": {"weight": jnp.zeros((d,))},
            "ln2": {"weight": jnp.zeros((d,))},
            "mlp": {"gate": _dense(km[0], d, dff), "up": _dense(km[1], d, dff),
                    "down": _dense(km[2], dff, d)},
        }

    return {
        "embed": jax.random.normal(kemb, (vocab, d)) * 0.02,
        "layers": jax.vmap(block)(jax.random.split(klayers, cfg["n_layers"])),
        "final_norm": {"weight": jnp.zeros((d,))},
        "lm_head": (jax.random.normal(khead, (vocab, d)) * 0.02).T,
    }


def rms_norm(x, w, eps=1e-6):
    xf = x.astype(jnp.float32)
    xf = xf / jnp.sqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def rotate(x, theta):
    """Rotary positions on [B, S, H, D]: pairs (i, i + D/2) turn by
    position * theta^(-2i/D)."""
    s, dh = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def block(p, x, cfg):
    bsz, s, _ = x.shape
    hq, hk, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    h = rms_norm(x, p["ln1"]["weight"])
    q = rotate((h @ p["attn"]["wq"]).reshape(bsz, s, hq, hd), cfg["rope_theta"])
    k = rotate((h @ p["attn"]["wk"]).reshape(bsz, s, hk, hd), cfg["rope_theta"])
    v = (h @ p["attn"]["wv"]).reshape(bsz, s, hk, hd)
    k = jnp.repeat(k, hq // hk, axis=2)     # head i reads kv head i // group
    v = jnp.repeat(v, hq // hk, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(hd))
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    att = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(x.dtype), v)
    x = x + att.reshape(bsz, s, hq * hd) @ p["attn"]["wo"]
    h = rms_norm(x, p["ln2"]["weight"])
    m = p["mlp"]
    return x + (jax.nn.silu(h @ m["gate"]) * (h @ m["up"])) @ m["down"]


def loss(params, tokens, cfg: dict):
    """Mean next-token cross-entropy of one client's [B, S] tokens."""
    x = params["embed"][tokens]

    def body(x, p):
        return jax.checkpoint(lambda p, x: block(p, x, cfg))(p, x), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    x = rms_norm(x, params["final_norm"]["weight"])
    logits = (x[:, :-1] @ params["lm_head"]).astype(jnp.float32)
    gold = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)
