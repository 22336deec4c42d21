"""Plain reference of the Mamba-2 LM (mamba2-130m): weights from the seed,
and the next-token loss, in straightforward ``jax.numpy``.

It follows the SSD paper (Dao and Gu, arXiv:2405.21060) and the
configuration file, not the program's model code. Each block: RMSNorm; z, x,
B and C projections (one group of B and C shared by all heads) and a
per-head step dt = softplus(. + dt_bias); a depthwise causal convolution of
width ``ssm_conv`` and SiLU over [x, B, C]; the state-space mixer in its
quadratic (attention-like) form, y_i = sum_{j<=i} (C_i . B_j)
exp(sum_{k=j+1..i} dt_k A) dt_j x_j + D x_i with A = -exp(A_log); gated
RMSNorm of y * silu(z); the output projection and the residual. The program
computes the mixer in chunks with a recurrence between them; the quadratic
form needs neither.

``init`` draws the weights with the same keys, shapes and distributions as
the program's initialisation (the test at reduced sizes pins that). The
type of the weights passed to ``loss`` is the storage and matmul-input type
(float32 for the reference, bfloat16 for its control); norm statistics, the
mixer and the loss are float32 in both.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _dense(key, d_in, d_out):
    return jax.random.normal(key, (d_in, d_out)) * (1.0 / jnp.sqrt(d_in))


def dims(cfg: dict):
    d_inner = cfg["ssm_expand"] * cfg["d_model"]
    return d_inner, d_inner // cfg["ssm_headdim"], cfg["ssm_state"]


def init(cfg: dict, key) -> dict:
    d, vocab = cfg["d_model"], cfg["vocab_size"]
    d_inner, heads, n = dims(cfg)
    conv_ch = d_inner + 2 * n
    kemb, klayers, khead = jax.random.split(key, 3)

    def block(k):
        ks = jax.random.split(k, 8)
        return {
            "norm": jnp.zeros((d,)),
            "wz": _dense(ks[0], d, d_inner),
            "wx": _dense(ks[1], d, d_inner),
            "wB": _dense(ks[2], d, n),
            "wC": _dense(ks[3], d, n),
            "wdt": _dense(ks[4], d, heads),
            "dt_bias": jnp.zeros((heads,)),
            "A_log": jnp.log(jnp.linspace(1.0, 16.0, heads)),
            "D": jnp.ones((heads,)),
            "conv_w": jax.random.normal(ks[5], (conv_ch, cfg["ssm_conv"])) * 0.1,
            "conv_b": jnp.zeros((conv_ch,)),
            "out_norm": jnp.zeros((d_inner,)),
            "out_proj": _dense(ks[6], d_inner, d),
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


def causal_conv(x, w, b):
    """out[t, c] = b[c] + sum_k w[c, k] x[t - (K-1) + k, c], zeros before
    the sequence starts. x: [B, S, C]; w: [C, K]."""
    width = w.shape[1]
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    s = x.shape[1]
    return b + sum(xp[:, k:k + s] * w[:, k] for k in range(width))


def segsum(a):
    """[..., S] -> [..., S, S] with [i, j] = sum_{k=j+1..i} a_k for j <= i
    and -inf above the diagonal; a running sum down each column, so no
    difference of large partial sums is taken."""
    s = a.shape[-1]
    rep = jnp.broadcast_to(a[..., :, None], a.shape + (s,))
    rep = jnp.where(jnp.tril(jnp.ones((s, s), bool), -1), rep, 0.0)
    out = jnp.cumsum(rep, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((s, s), bool)), out, -jnp.inf)


def mixer(x, dt, a_neg, bm, cm):
    """Quadratic SSD. x [B,S,H,P], dt [B,S,H], a_neg [H], bm/cm [B,S,N]."""
    decay = jnp.exp(segsum(jnp.swapaxes(dt * a_neg, 1, 2)))     # [B,H,S,S]
    cb = jnp.einsum("bin,bjn->bij", cm, bm)
    return jnp.einsum("bhij,bij,bjh,bjhp->bihp", decay, cb, dt, x)


def block(p, u, cfg):
    bsz, s, _ = u.shape
    d_inner, heads, n = dims(cfg)
    h = rms_norm(u, p["norm"])
    z = h @ p["wz"]
    xbc = jnp.concatenate([h @ p["wx"], h @ p["wB"], h @ p["wC"]], axis=-1)
    xbc = jax.nn.silu(causal_conv(xbc, p["conv_w"], p["conv_b"]))
    x, bm, cm = jnp.split(xbc, [d_inner, d_inner + n], axis=-1)
    x = x.reshape(bsz, s, heads, cfg["ssm_headdim"])
    dt = jax.nn.softplus(h @ p["wdt"] + p["dt_bias"])
    a_neg = -jnp.exp(p["A_log"].astype(jnp.float32))
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
    y = mixer(f32(x), f32(dt), a_neg, f32(bm), f32(cm)).astype(u.dtype)
    y = (y + p["D"][None, None, :, None] * x).reshape(bsz, s, d_inner)
    y = rms_norm(y * jax.nn.silu(z), p["out_norm"])
    return u + y @ p["out_proj"]


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
