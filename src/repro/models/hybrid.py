"""Zamba2-style hybrid LM: Mamba2 backbone + ONE shared attention block.

The architecture alternates groups of ``shared_attn_every`` Mamba2 layers
with an application of a single *parameter-shared* attention(+MLP) block
[arXiv:2411.15242]. The shared block's parameters exist once; each
application at runtime gets its own KV cache. (Zamba2 additionally inserts
per-application LoRA adapters on the shared block; we share it verbatim and
note the simplification in DESIGN.md.)

Layer layout for n_layers = G * every + R:
    [every x mamba, shared-attn] * G  then  R trailing mamba layers.
Mamba groups are scanned ([G, every, ...] stacked params) so the lowered
HLO stays small at depth.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as attn_mod
from repro.models.layers import apply_mlp, apply_norm, embed_init, init_mlp, init_norm
from repro.models.mamba2 import (
    apply_mamba_block,
    apply_mamba_block_decode,
    apply_mamba_block_prefill,
    init_mamba_block,
    init_ssm_cache,
)
from repro.models.transformer import apply_block, apply_block_decode, apply_block_prefill, init_block


def _layout(cfg: ArchConfig) -> tuple[int, int, int]:
    every = cfg.shared_attn_every
    groups = cfg.n_layers // every if every else 0
    rest = cfg.n_layers - groups * every
    return groups, every, rest


class HybridLM(NamedTuple):
    cfg: ArchConfig

    def init(self, key) -> dict:
        cfg = self.cfg
        dtype = jnp.dtype(cfg.param_dtype)
        groups, every, rest = _layout(cfg)
        kemb, kgrp, krest, kshared, khead = jax.random.split(key, 5)
        gkeys = jax.random.split(kgrp, max(groups * every, 1))
        if cfg.scan_layers and groups:
            stacked = jax.vmap(lambda k: init_mamba_block(k, cfg))(
                gkeys[: groups * every])
            grouped = jax.tree.map(
                lambda a: a.reshape((groups, every) + a.shape[1:]), stacked)
        else:
            grouped = [
                [init_mamba_block(gkeys[g * every + i], cfg) for i in range(every)]
                for g in range(groups)
            ]
        rkeys = jax.random.split(krest, max(rest, 1))
        return {
            "embed": embed_init(kemb, cfg.vocab_size, cfg.d_model, dtype),
            "groups": grouped,
            "shared_attn": init_block(kshared, cfg),   # attention + MLP block
            "rest": [init_mamba_block(rkeys[i], cfg) for i in range(rest)],
            "final_norm": init_norm(cfg.d_model, dtype),
            "lm_head": embed_init(khead, cfg.vocab_size, cfg.d_model, dtype).T,
        }

    def _embed(self, params, tokens):
        return params["embed"][tokens].astype(jnp.dtype(self.cfg.dtype))

    def _logits(self, params, x):
        x = apply_norm(x, params["final_norm"], self.cfg.norm)
        return x @ params["lm_head"]

    # ------------------------------------------------------------- training
    def _stack(self, params, x):
        cfg = self.cfg
        groups, every, rest = _layout(cfg)
        shared = params["shared_attn"]
        if cfg.scan_layers and groups:
            def group_body(x, gparams):
                def inner(x, p):
                    return apply_mamba_block(p, x, cfg), None

                x, _ = jax.lax.scan(inner, x, gparams)
                x = apply_block(shared, x, cfg)[0]
                return x, None

            body = jax.checkpoint(group_body) if cfg.remat else group_body
            x, _ = jax.lax.scan(body, x, params["groups"])
        else:
            for g in range(groups):
                for p in params["groups"][g]:
                    x = apply_mamba_block(p, x, cfg)
                x = apply_block(shared, x, cfg)[0]
        for p in params["rest"]:
            x = apply_mamba_block(p, x, cfg)
        return x

    def forward(self, params, batch) -> jax.Array:
        return self._logits(params, self._stack(params, self._embed(params, batch["tokens"])))

    def loss(self, params, batch) -> jax.Array:
        from repro.models.losses import chunked_ce

        x = self._stack(params, self._embed(params, batch["tokens"]))
        x = apply_norm(x, params["final_norm"], self.cfg.norm)
        return chunked_ce(x, params["lm_head"], batch["tokens"])

    # ---------------------------------------------------------------- serve
    def _attn_window_cap(self, seq_len: int) -> int:
        cfg = self.cfg
        # the shared attention block runs sliding-window in long-context
        # serving so the hybrid stays sub-quadratic (DESIGN.md §5).
        if cfg.attention == "sliding":
            return min(cfg.window, seq_len)
        return seq_len

    def init_caches(self, batch: int, seq_len: int):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        groups, every, rest = _layout(cfg)
        cap = self._attn_window_cap(seq_len)
        ssm_one = lambda: init_ssm_cache(batch, cfg, dtype)
        kv_one = lambda: attn_mod.init_cache(batch, cap, cfg.n_kv_heads,
                                             cfg.head_dim, dtype)
        if cfg.scan_layers and groups:
            ssm = jax.tree.map(lambda *ls: jnp.stack(ls),
                               *[ssm_one() for _ in range(groups * every)])
            ssm = jax.tree.map(
                lambda a: a.reshape((groups, every) + a.shape[1:]), ssm)
            kv = jax.tree.map(lambda *ls: jnp.stack(ls),
                              *[kv_one() for _ in range(groups)])
        else:
            ssm = [[ssm_one() for _ in range(every)] for _ in range(groups)]
            kv = [kv_one() for _ in range(groups)]
        rest_c = [ssm_one() for _ in range(rest)]
        return {"ssm": ssm, "kv": kv, "rest": rest_c}

    def prefill(self, params, batch, caches):
        cfg = self.cfg
        groups, every, rest = _layout(cfg)
        shared = params["shared_attn"]
        ring = cfg.attention == "sliding"
        x = self._embed(params, batch["tokens"])
        if cfg.scan_layers and groups:
            def group_body(x, inp):
                gparams, ssm_c, kv_c = inp

                def inner(x, pc):
                    p, c = pc
                    x, c = apply_mamba_block_prefill(p, x, c, cfg)
                    return x, c

                x, ssm_c = jax.lax.scan(inner, x, (gparams, ssm_c))
                x, kv_c = apply_block_prefill(shared, x, kv_c, cfg, ring=ring)
                return x, (ssm_c, kv_c)

            body = jax.checkpoint(group_body) if cfg.remat else group_body
            x, (ssm, kv) = jax.lax.scan(
                body, x, (params["groups"], caches["ssm"], caches["kv"]))
        else:
            ssm, kv = [], []
            for g in range(groups):
                gc = []
                for p, c in zip(params["groups"][g], caches["ssm"][g]):
                    x, c = apply_mamba_block_prefill(p, x, c, cfg)
                    gc.append(c)
                x, kvc = apply_block_prefill(shared, x, caches["kv"][g], cfg,
                                             ring=ring)
                ssm.append(gc)
                kv.append(kvc)
        rest_c = []
        for p, c in zip(params["rest"], caches["rest"]):
            x, c = apply_mamba_block_prefill(p, x, c, cfg)
            rest_c.append(c)
        caches = {"ssm": ssm, "kv": kv, "rest": rest_c}
        return self._logits(params, x[:, -1:, :]), caches

    def decode_step(self, params, token, caches):
        cfg = self.cfg
        groups, every, rest = _layout(cfg)
        shared = params["shared_attn"]
        ring = cfg.attention == "sliding"
        x = self._embed(params, token)
        if cfg.scan_layers and groups:
            def group_body(x, inp):
                gparams, ssm_c, kv_c = inp

                def inner(x, pc):
                    p, c = pc
                    x, c = apply_mamba_block_decode(p, x, c, cfg)
                    return x, c

                x, ssm_c = jax.lax.scan(inner, x, (gparams, ssm_c))
                x, kv_c = apply_block_decode(shared, x, kv_c, cfg, ring=ring)
                return x, (ssm_c, kv_c)

            x, (ssm, kv) = jax.lax.scan(
                group_body, x, (params["groups"], caches["ssm"], caches["kv"]))
        else:
            ssm, kv = [], []
            for g in range(groups):
                gc = []
                for p, c in zip(params["groups"][g], caches["ssm"][g]):
                    x, c = apply_mamba_block_decode(p, x, c, cfg)
                    gc.append(c)
                x, kvc = apply_block_decode(shared, x, caches["kv"][g], cfg,
                                            ring=ring)
                ssm.append(gc)
                kv.append(kvc)
        rest_c = []
        for p, c in zip(params["rest"], caches["rest"]):
            x, c = apply_mamba_block_decode(p, x, c, cfg)
            rest_c.append(c)
        caches = {"ssm": ssm, "kv": kv, "rest": rest_c}
        return self._logits(params, x), caches
