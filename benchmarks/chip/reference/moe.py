"""Plain reference of the sparse-expert decoder LM (granite-3.0-3b-a800m as
one chip's expert share): weights from the seed, and the next-token loss,
in straightforward ``jax.numpy``.

It follows the configuration file and HF GraniteMoe, not the program's
model code: pre-norm blocks of grouped-query causal attention with rotary
positions and a sparse-expert SwiGLU layer, RMSNorm with its weight stored
as an offset from 1, and Granite's four multipliers: the embedding times
``embedding_multiplier``, each block branch times ``residual_multiplier``
before it joins the residual stream, attention scores times
``attention_multiplier`` (in place of 1/sqrt(head_dim)), and the logits of
the tied head (the embedding's vocabulary slice, transposed) divided by
``logits_scaling``.

The expert layer is written as what it computes, for every token ``t``:

    y_t = sum over held experts e of w[t, e] * FFN_e(x_t)

with ``FFN_e(x) = (silu(x G_e) * (x U_e)) D_e`` evaluated for every token
and every held expert, and ``w[t, e]`` the softmax over the token's top-k
router logits (float32, over all ``router_experts``) where ``e`` is among
them and 0 elsewhere. Experts outside the held range contribute nothing.
The loss adds ``AUX_COEF`` times the sum over layers of the Switch-style
load-balance term ``R * sum_e frac_e * prob_e`` (``frac_e`` the share of
tokens whose first choice is ``e``, ``prob_e`` the mean router probability
of ``e``, over all R router outputs), which is the program's term and
coefficient.

``init`` draws the weights with the same keys, shapes and distributions as
the program's initialisation: each expert's from its global id, so a share
holds the whole model's rows for its experts. The type of the weights
passed to ``loss`` is the storage and matmul-input type (float32 for the
reference, bfloat16 for its control); norms, router, softmax and the loss
are float32 in both.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from reference.dense import _dense, rms_norm, rotate

#: weight of the load-balance term in the loss (the program's MOE_AUX_COEF)
AUX_COEF = 0.01


def init(cfg: dict, key) -> dict:
    """Float32 weights of one model, as a nested dict with [layers, ...]
    stacked block leaves."""
    d, dff, hd = cfg["d_model"], cfg["d_ff"], cfg["head_dim"]
    hq, hk, vocab = cfg["n_heads"], cfg["n_kv_heads"], cfg["vocab_size"]
    held = cfg["expert_offset"] + jnp.arange(cfg["n_experts"])
    kemb, klayers, _ = jax.random.split(key, 3)

    def experts(k, d_in, d_out):
        return jnp.stack([_dense(jax.random.fold_in(k, e), d_in, d_out)
                          for e in held])

    def block(k):
        kattn, kmoe = jax.random.split(k)
        ka = jax.random.split(kattn, 4)
        km = jax.random.split(kmoe, 5)
        return {
            "attn": {"wq": _dense(ka[0], d, hq * hd),
                     "wk": _dense(ka[1], d, hk * hd),
                     "wv": _dense(ka[2], d, hk * hd),
                     "wo": _dense(ka[3], hq * hd, d)},
            "ln1": {"weight": jnp.zeros((d,))},
            "ln2": {"weight": jnp.zeros((d,))},
            "moe": {"router": jax.random.normal(
                        km[0], (d, cfg["router_experts"])) * 0.02,
                    "gate": experts(km[1], d, dff),
                    "up": experts(km[2], d, dff),
                    "down": experts(km[3], dff, d)},
        }

    return {
        "embed": jax.random.normal(kemb, (vocab, d)) * 0.02,
        "layers": jax.vmap(block)(jax.random.split(klayers, cfg["n_layers"])),
        "final_norm": {"weight": jnp.zeros((d,))},
    }


def route(router, h, cfg: dict):
    """[T, held] weights ``w[t, e]`` and the load-balance term, from the
    float32 router logits of tokens ``h`` [T, d]."""
    logits = h.astype(jnp.float32) @ router.astype(jnp.float32)
    n = logits.shape[-1]
    top, ids = jax.lax.top_k(logits, cfg["experts_per_token"])
    chosen = jnp.sum(jax.nn.one_hot(ids, n) * jax.nn.softmax(top)[..., None],
                     axis=1)                                # [T, R]
    lo = cfg["expert_offset"]
    w = chosen[:, lo:lo + cfg["n_experts"]]
    frac = jnp.mean(jax.nn.one_hot(jnp.argmax(logits, -1), n), axis=0)
    prob = jnp.mean(jax.nn.softmax(logits, axis=-1), axis=0)
    return w, n * jnp.sum(frac * prob)


def experts(m, h, w):
    """sum over held experts e of w[:, e] * FFN_e(h), for tokens h [T, d]."""
    def one(g, u, dn):
        return (jax.nn.silu(h @ g) * (h @ u)) @ dn          # [T, d]

    y = jax.vmap(one)(m["gate"], m["up"], m["down"])        # [E, T, d]
    return jnp.einsum("te,etd->td", w.astype(h.dtype), y)


def moe_layer(m, h, cfg: dict):
    """The expert layer on [B, S, d] and its load-balance term."""
    bsz, s, d = h.shape
    flat = h.reshape(bsz * s, d)
    w, aux = route(m["router"], flat, cfg)
    return experts(m, flat, w).reshape(bsz, s, d), aux


def block(p, x, cfg):
    bsz, s, _ = x.shape
    hq, hk, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    rm = cfg["residual_multiplier"]
    h = rms_norm(x, p["ln1"]["weight"])
    q = rotate((h @ p["attn"]["wq"]).reshape(bsz, s, hq, hd), cfg["rope_theta"])
    k = rotate((h @ p["attn"]["wk"]).reshape(bsz, s, hk, hd), cfg["rope_theta"])
    v = (h @ p["attn"]["wv"]).reshape(bsz, s, hk, hd)
    k = jnp.repeat(k, hq // hk, axis=2)     # head i reads kv head i // group
    v = jnp.repeat(v, hq // hk, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores = scores * cfg["attention_multiplier"]
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    att = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(x.dtype), v)
    x = x + rm * (att.reshape(bsz, s, hq * hd) @ p["attn"]["wo"])
    y, aux = moe_layer(p["moe"], rms_norm(x, p["ln2"]["weight"]), cfg)
    return x + rm * y, aux


def loss(params, tokens, cfg: dict):
    """Mean next-token cross-entropy of one client's [B, S] tokens plus the
    weighted load-balance term."""
    x = params["embed"][tokens] * cfg["embedding_multiplier"]

    def body(x, p):
        x, aux = jax.checkpoint(lambda p, x: block(p, x, cfg))(p, x)
        return x, aux

    x, aux = jax.lax.scan(body, x, params["layers"])
    x = rms_norm(x, params["final_norm"]["weight"])
    logits = (x[:, :-1] @ params["embed"].T).astype(jnp.float32)
    logits = logits / cfg["logits_scaling"]
    gold = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    ce = jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)
    return ce + AUX_COEF * jnp.sum(aux)
