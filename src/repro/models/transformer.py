"""Decoder-only transformer LM covering the dense, moe and vlm families.

Layers are stacked ([L, ...] leaves) and applied with ``lax.scan`` (+
optional ``jax.checkpoint`` remat) so multi-B-parameter configs lower to a
compact HLO; the reduced smoke variants unroll in Python instead
(``scan_layers=False``).

The VLM family (llava-next) consumes stub-frontend image-patch embeddings:
the sequence layout is ``[n_modal image tokens][text tokens]`` and the LM
loss is applied on text positions only. The anyres tiling itself lives in
the (stubbed) vision tower; what this backbone implements is the token
interleave + the 60-layer language model that attends across both regions.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as attn
from repro.models.layers import (
    apply_mlp,
    apply_norm,
    embed_init,
    init_mlp,
    init_norm,
)
from repro.models.moe import apply_moe, init_moe
from repro.utils.sharding_ctx import shard_residual

MOE_AUX_COEF = 0.01


# ------------------------------------------------------------------- blocks
def init_block(key, cfg: ArchConfig):
    kattn, kmlp = jax.random.split(key)
    dtype = jnp.dtype(cfg.param_dtype)
    with_bias = cfg.norm == "layernorm"
    p = {
        "ln1": init_norm(cfg.d_model, dtype, with_bias=with_bias),
        "attn": attn.init_attention(
            key=kattn, d_model=cfg.d_model, n_heads=cfg.n_heads,
            n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim, dtype=dtype,
            qk_norm=cfg.qk_norm, with_bias=cfg.attn_bias),
        "ln2": init_norm(cfg.d_model, dtype, with_bias=with_bias),
    }
    if cfg.n_experts:
        p["moe"] = init_moe(kmlp, cfg.d_model, cfg.d_ff, cfg.n_experts, dtype,
                            shared_expert=cfg.moe_shared_expert,
                            activation=cfg.activation,
                            router_experts=cfg.router_experts,
                            expert_offset=cfg.expert_offset)
    else:
        p["mlp"] = init_mlp(kmlp, cfg.d_model, cfg.d_ff, dtype,
                            activation=cfg.activation, with_bias=cfg.mlp_bias)
    return p


def _apply_ffn(p, h, cfg: ArchConfig):
    """(out, aux loss, routed slots per held expert or None)."""
    if cfg.n_experts:
        return apply_moe(
            p["moe"], h, n_experts=cfg.n_experts, k=cfg.experts_per_token,
            capacity_factor=cfg.capacity_factor, activation=cfg.activation,
            shared_expert=cfg.moe_shared_expert,
            router_experts=cfg.router_experts, expert_offset=cfg.expert_offset)
    return (apply_mlp(h, p["mlp"], activation=cfg.activation),
            jnp.zeros((), jnp.float32), None)


def _branch(h, cfg: ArchConfig):
    """A block branch as it joins the residual stream (granite scales it)."""
    if cfg.residual_multiplier != 1.0:
        return h * cfg.residual_multiplier
    return h


def apply_block(p, x, cfg: ArchConfig):
    """(x, aux, routed slots per held expert or None) for one decoder block
    over a full sequence."""
    x = shard_residual(x)
    h = apply_norm(x, p["ln1"], cfg.norm)
    h = attn.attention(
        p["attn"], h, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, kind=cfg.attention, window=cfg.window,
        chunk=cfg.chunk, rope_theta=cfg.rope_theta, use_rope=cfg.use_rope,
        block_size=cfg.attn_block_size, use_pallas=cfg.use_pallas_attention,
        scale=cfg.attention_multiplier)
    x = x + _branch(h, cfg)
    h = apply_norm(x, p["ln2"], cfg.norm)
    h, aux, counts = _apply_ffn(p, h, cfg)
    return x + _branch(h, cfg), aux, counts


def apply_block_decode(p, x1, cache, cfg: ArchConfig, *, ring: bool):
    h = apply_norm(x1, p["ln1"], cfg.norm)
    h, cache = attn.decode_attention(
        p["attn"], h, cache, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, kind=cfg.attention, window=cfg.window,
        chunk=cfg.chunk, rope_theta=cfg.rope_theta, use_rope=cfg.use_rope,
        ring=ring, scale=cfg.attention_multiplier)
    x1 = x1 + _branch(h, cfg)
    h = apply_norm(x1, p["ln2"], cfg.norm)
    h = _apply_ffn(p, h, cfg)[0]
    return x1 + _branch(h, cfg), cache


def apply_block_prefill(p, x, cache, cfg: ArchConfig, *, ring: bool):
    x = shard_residual(x)
    h = apply_norm(x, p["ln1"], cfg.norm)
    h, cache = attn.prefill_attention(
        p["attn"], h, cache=cache, n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim, kind=cfg.attention,
        window=cfg.window, chunk=cfg.chunk, rope_theta=cfg.rope_theta,
        use_rope=cfg.use_rope, block_size=cfg.attn_block_size, ring=ring,
        scale=cfg.attention_multiplier)
    x = x + _branch(h, cfg)
    h = apply_norm(x, p["ln2"], cfg.norm)
    h = _apply_ffn(p, h, cfg)[0]
    return x + _branch(h, cfg), cache


# ---------------------------------------------------------------------- LM
class TransformerLM(NamedTuple):
    cfg: ArchConfig

    # ------------------------------------------------------------------ init
    def init(self, key) -> dict:
        cfg = self.cfg
        dtype = jnp.dtype(cfg.param_dtype)
        kemb, klayers, khead = jax.random.split(key, 3)
        layer_keys = jax.random.split(klayers, cfg.n_layers)
        if cfg.scan_layers:
            layers = jax.vmap(lambda k: init_block(k, cfg))(layer_keys)
        else:
            layers = [init_block(k, cfg) for k in layer_keys]
        p = {
            "embed": embed_init(kemb, cfg.vocab_size, cfg.d_model, dtype),
            "layers": layers,
            "final_norm": init_norm(cfg.d_model, dtype,
                                    with_bias=cfg.norm == "layernorm"),
        }
        if not cfg.tie_embeddings:
            p["lm_head"] = embed_init(khead, cfg.vocab_size, cfg.d_model, dtype).T
        return p

    # -------------------------------------------------------------- forward
    def _embed(self, params, tokens, image_embeds=None):
        cfg = self.cfg
        x = params["embed"][tokens]
        if cfg.embed_scale:
            x = x * jnp.sqrt(jnp.asarray(cfg.d_model, x.dtype))
        if cfg.embedding_multiplier != 1.0:
            x = x * cfg.embedding_multiplier
        if image_embeds is not None:
            x = jnp.concatenate([image_embeds.astype(x.dtype), x], axis=1)
        return x.astype(jnp.dtype(cfg.dtype))

    def _stack(self, params, x):
        """(x, aux loss, routed slots per held expert summed over layers —
        None without experts)."""
        cfg = self.cfg
        aux_total = jnp.zeros((), jnp.float32)
        slots = (jnp.zeros((cfg.n_experts,), jnp.int32) if cfg.n_experts
                 else None)
        if cfg.scan_layers:
            def body(carry, p):
                x, aux, n = carry
                x, a, c = apply_block(p, x, cfg)
                return (x, aux + a, None if n is None else n + c), None

            body_fn = jax.checkpoint(body) if cfg.remat else body
            (x, aux_total, slots), _ = jax.lax.scan(
                body_fn, (x, aux_total, slots), params["layers"])
        else:
            for p in params["layers"]:
                x, a, c = apply_block(p, x, cfg)
                aux_total = aux_total + a
                slots = None if slots is None else slots + c
        return x, aux_total, slots

    def _logits(self, params, x):
        cfg = self.cfg
        x = apply_norm(x, params["final_norm"], cfg.norm)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = x @ head
        if cfg.logits_scaling != 1.0:
            logits = logits / cfg.logits_scaling
        return logits

    def forward(self, params, batch) -> jax.Array:
        """Full-sequence logits [B, S(+n_modal), V]."""
        x = self._embed(params, batch["tokens"], batch.get("image_embeds"))
        x = self._stack(params, x)[0]
        return self._logits(params, x)

    # ----------------------------------------------------------------- loss
    def loss(self, params, batch) -> jax.Array:
        """Next-token cross entropy (chunked; for VLM, text positions only)."""
        return self.loss_and_stats(params, batch)[0]

    def loss_and_stats(self, params, batch):
        """(loss, counters): ``loss`` and, for a model with experts,
        ``{"moe_expert_slots": [n_experts] int32}``, the routed slots that
        landed on each held expert, summed over layers (``{}`` without
        experts). ``repro.core.api.grad_with_stats`` differentiates it."""
        cfg = self.cfg
        from repro.models.losses import chunked_ce

        x = self._embed(params, batch["tokens"], batch.get("image_embeds"))
        x, aux, slots = self._stack(params, x)
        x = apply_norm(x, params["final_norm"], cfg.norm)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        n_img = 0
        if batch.get("image_embeds") is not None:
            n_img = batch["image_embeds"].shape[1]
        ce = chunked_ce(x, head, batch["tokens"], prefix=n_img,
                        logits_scaling=cfg.logits_scaling)
        stats = {} if slots is None else {"moe_expert_slots": slots}
        return ce + MOE_AUX_COEF * aux, stats

    # ---------------------------------------------------------------- serve
    def _ring(self) -> bool:
        # sliding windows and chunked-local both keep a bounded ring cache
        return self.cfg.attention in ("sliding", "chunked")

    def cache_capacity(self, seq_len: int) -> int:
        cfg = self.cfg
        if cfg.attention == "sliding":
            return min(cfg.window, seq_len)
        if cfg.attention == "chunked":
            return min(cfg.chunk, seq_len)
        return seq_len

    def init_caches(self, batch: int, seq_len: int):
        cfg = self.cfg
        cap = self.cache_capacity(seq_len)
        dtype = jnp.dtype(cfg.dtype)
        one = lambda: attn.init_cache(batch, cap, cfg.n_kv_heads, cfg.head_dim,
                                      dtype)
        if cfg.scan_layers:
            return jax.tree.map(
                lambda *ls: jnp.stack(ls), *[one() for _ in range(cfg.n_layers)])
        return [one() for _ in range(cfg.n_layers)]

    def prefill(self, params, batch, caches):
        """Run the prompt, returning (last-token logits, populated caches)."""
        cfg = self.cfg
        x = self._embed(params, batch["tokens"], batch.get("image_embeds"))
        ring = self._ring()
        if cfg.scan_layers:
            def body(x, inp):
                p, cache = inp
                x, cache = apply_block_prefill(p, x, cache, cfg, ring=ring)
                return x, cache

            body_fn = jax.checkpoint(body) if cfg.remat else body
            x, caches = jax.lax.scan(body_fn, x, (params["layers"], caches))
        else:
            new = []
            for p, cache in zip(params["layers"], caches):
                x, cache = apply_block_prefill(p, x, cache, cfg, ring=ring)
                new.append(cache)
            caches = new
        logits = self._logits(params, x[:, -1:, :])
        return logits, caches

    def decode_step(self, params, token, caches):
        """One decode step. token: [B, 1] int32 -> (logits [B,1,V], caches)."""
        cfg = self.cfg
        x = self._embed(params, token)
        ring = self._ring()
        if cfg.scan_layers:
            def body(x, inp):
                p, cache = inp
                x, cache = apply_block_decode(p, x, cache, cfg, ring=ring)
                return x, cache

            x, caches = jax.lax.scan(body, x, (params["layers"], caches))
        else:
            new = []
            for p, cache in zip(params["layers"], caches):
                x, cache = apply_block_decode(p, x, cache, cfg, ring=ring)
                new.append(cache)
            caches = new
        return self._logits(params, x), caches
