"""Mixture-of-Experts block: a top-k router over every expert, and the part
of the result that the experts held here give.

An expert layer is told which experts it holds: ``n_experts`` stacked
``[E, d, f]`` experts, global ids ``expert_offset`` .. ``expert_offset +
E - 1`` of the ``router_experts`` the router scores. Each token's weights
are the softmax of its top-k router logits (as HF GraniteMoe); the layer
returns ``sum over its chosen held experts e of w_e * FFN_e(x)``, so the
shares of one layer over disjoint held sets add up to the whole layer.

Two lowerings:

* **drop-free, grouped** (one device, and any call outside a production
  mesh): the T*k routed slots are sorted by expert, which puts the held
  experts' slots in one contiguous run at the front; grouped products
  (``jax.lax.ragged_dot``, ``grouped_matmul``) compute the held experts'
  SwiGLU products over that run, and a weighted scatter-add returns the
  result to token order. The static buffer holds every slot that could
  land here (T*k rows), so no token is dropped. The router runs in
  float32 at highest precision (a [d, router] product).
* **sort-based capacity dispatch** (production-mesh lowerings only, every
  expert held): argsort by expert id, scatter into [E, C, d] buffers,
  batched expert matmuls, gather back. Slots past an expert's capacity C
  are dropped. On the production mesh E is sharded over `model` when
  divisible (expert parallelism), otherwise the token stream is
  (see ``apply_moe``).

Neither uses one-hot dispatch, whose O(T * E*C * d) FLOPs would exceed the
experts' own at 4k-sequence training shapes. Names: ``moe.route`` (router,
top-k, permutation, combine) and ``moe.experts`` (the grouped products).

Load-balance aux loss is the standard Switch-style mean(fraction * prob)
term over all routed experts, returned so the trainer can weight it; the
layer also returns the routed slots that land on each held expert.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init


def init_moe(key, d: int, d_ff: int, n_experts: int, dtype,
             *, shared_expert: bool, activation: str,
             router_experts: int | None = None, expert_offset: int = 0):
    """Expert e's weights are drawn from its global id, so a layer that
    holds experts [offset, offset + n_experts) holds the same weights as
    those rows of the whole layer."""
    ks = jax.random.split(key, 5)
    held = expert_offset + jnp.arange(n_experts)

    def experts(k, d_in, d_out):
        return jax.vmap(lambda e: dense_init(jax.random.fold_in(k, e), d_in,
                                             d_out, dtype))(held)

    p = {
        "router": dense_init(ks[0], d, router_experts or n_experts, dtype,
                             scale=0.02),
        "up": experts(ks[2], d, d_ff),
        "down": experts(ks[3], d_ff, d),
    }
    if activation in ("swiglu", "geglu"):
        p["gate"] = experts(ks[1], d, d_ff)
    if shared_expert:
        from repro.models.layers import init_mlp

        p["shared"] = init_mlp(ks[4], d, d_ff, dtype, activation=activation)
    return p


def apply_moe(params, x, *, n_experts: int, k: int, capacity_factor: float,
              activation: str, shared_expert: bool,
              router_experts: int | None = None, expert_offset: int = 0):
    """x: [B, S, d] -> ([B, S, d], aux_loss scalar, [n_experts] int32 routed
    slots per held expert).

    Outside a production-mesh lowering the layer is the drop-free grouped
    one. Under a mesh every expert is held and the capacity dispatch runs;
    when the ambient sharding context requests token-sharded dispatch
    (granite's 40 experts don't divide a 16-way model axis, so the plain
    scatter makes GSPMD replicate + all-reduce the [E, C, d] buffers —
    ~116 GB/layer at prefill_32k), the token stream is reshaped so its
    SHARDED dimension (batch for serving, sequence for training) becomes a
    leading vmapped axis: every shard routes into its own local capacity
    buffer and no cross-device scatter traffic exists. Per-shard capacity
    (the standard per-device-capacity MoE semantics) replaces global
    capacity; tests cover equivalence in the drop-free regime.
    """
    from repro.utils.sharding_ctx import meshed, moe_shards

    B, S, d = x.shape
    router_experts = router_experts or n_experts
    if not meshed():
        out, aux, counts = _moe_dropfree(
            params, x.reshape(B * S, d), n_experts=n_experts,
            router_experts=router_experts, expert_offset=expert_offset, k=k,
            activation=activation)
        out = out.reshape(B, S, d)
        if shared_expert and "shared" in params:
            from repro.models.layers import apply_mlp

            out = out + apply_mlp(x, params["shared"], activation=activation)
        return out, aux, counts

    if router_experts != n_experts:
        raise ValueError("the capacity dispatch holds every expert: "
                         f"{n_experts} held of {router_experts}")
    shards = moe_shards()
    if shards is not None:
        nb, ns, spec = shards["nb"], shards["ns"], shards.get("spec")
        grid_axes = shards.get("axes")  # mesh axes of the token grid
        kw = dict(n_experts=n_experts, k=k, capacity_factor=capacity_factor,
                  activation=activation)
        ok = (B % nb == 0 and B >= nb and S % ns == 0 and S >= ns)
        if ok:
            n = nb * ns
            xs = (x.reshape(nb, B // nb, ns, S // ns, d)
                  .transpose(0, 2, 1, 3, 4)
                  .reshape(n, (B // nb) * (S // ns), d))
            if spec is not None:
                xs = jax.lax.with_sharding_constraint(xs, spec)
            # Gather-at-use: force-replicate the (small) expert weights for
            # this layer's dispatch so the per-shard expert matmul is fully
            # local. Without this GSPMD resolves the token-grid x f-shard
            # layout conflict by all-gathering the [grid, E, C, d] buffers
            # (64 GB/layer at granite prefill_32k) instead of the 0.2 GB
            # weights. Weights at rest stay sharded.
            import jax.sharding as jsh

            p_rep = dict(params)
            for w in ("gate", "up", "down"):
                if w in params:
                    p_rep[w] = jax.lax.with_sharding_constraint(
                        params[w], jsh.PartitionSpec(*(None,) * params[w].ndim))
            # spmd_axis_name pins the vmapped shard dim to the mesh axes of
            # the token grid, making every constraint inside _moe_tokens
            # (incl. the scatter outputs) shard-local by construction.
            out, aux, counts = jax.vmap(
                lambda t: _moe_tokens(p_rep, t, shard_local=True, **kw),
                spmd_axis_name=grid_axes,
            )(xs)
            out = (out.reshape(nb, ns, B // nb, S // ns, d)
                   .transpose(0, 2, 1, 3, 4).reshape(B, S, d))
            if shared_expert and "shared" in params:
                from repro.models.layers import apply_mlp

                out = out + apply_mlp(x, params["shared"], activation=activation)
            return out, jnp.mean(aux), jnp.sum(counts, axis=0)

    out, aux, counts = _moe_tokens(params, x.reshape(B * S, d),
                                   n_experts=n_experts, k=k,
                                   capacity_factor=capacity_factor,
                                   activation=activation)
    if shared_expert and "shared" in params:
        from repro.models.layers import apply_mlp

        out = out + apply_mlp(x.reshape(B * S, d), params["shared"],
                              activation=activation)
    return out.reshape(B, S, d), aux, counts


def _activation(activation: str):
    if activation == "swiglu":
        return jax.nn.silu
    return lambda a: jax.nn.gelu(a, approximate=True)


def _route(params, xt, k: int):
    """float32 router logits [T, R] at highest precision, the top-k expert
    ids [T, k] and their weights (softmax over the chosen logits), and the
    Switch-style load-balance term over all R experts."""
    logits = jnp.dot(xt.astype(jnp.float32),
                     params["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    top_logits, tope = jax.lax.top_k(logits, k)
    topw = jax.nn.softmax(top_logits, axis=-1)
    n = logits.shape[-1]
    frac = jnp.mean(jax.nn.one_hot(tope[:, 0], n, dtype=jnp.float32), 0)
    prob = jnp.mean(jax.nn.softmax(logits, axis=-1), axis=0)
    return tope, topw, n * jnp.sum(frac * prob)


def _per_example(fn):
    """Give a ``custom_vmap`` function a batching rule that maps it over the
    batch one example at a time: the TPU's ragged dot takes no batch
    dimension, and the engine vmaps each client's gradient."""
    def rule(axis_size, in_batched, *args):
        args = tuple(a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
                     for a, b in zip(args, in_batched))
        return jax.lax.map(lambda a: fn(*a), args), True

    fn.def_vmap(rule)
    return fn


@_per_example
@jax.custom_batching.custom_vmap
def _gmm(lhs, rhs, sizes):
    """[M, k] rows x [G, k, n] groups -> [M, n]: rows of group g (the g-th
    run of ``sizes``) times rhs[g]; rows past the runs give 0."""
    return jax.lax.ragged_dot(lhs, rhs, sizes)


@_per_example
@jax.custom_batching.custom_vmap
def _tgmm(lhs, dout, sizes):
    """[G, k, n]: per group g, its rows of lhs transposed times dout's."""
    shape = jax.ShapeDtypeStruct((sizes.shape[0], lhs.shape[1],
                                  dout.shape[1]), dout.dtype)
    return jax.linear_transpose(
        lambda r: jax.lax.ragged_dot(lhs, r, sizes), shape)(dout)[0]


@jax.custom_vjp
def grouped_matmul(lhs, rhs, sizes):
    """The grouped product ``_gmm`` with its gradient written in grouped
    products too, so that no transformation gives the ragged dot a batch
    dimension."""
    return _gmm(lhs, rhs, sizes)


def _grouped_fwd(lhs, rhs, sizes):
    return _gmm(lhs, rhs, sizes), (lhs, rhs, sizes)


def _grouped_bwd(res, dout):
    lhs, rhs, sizes = res
    return (_gmm(dout, jnp.swapaxes(rhs, 1, 2), sizes),
            _tgmm(lhs, dout, sizes), None)


grouped_matmul.defvjp(_grouped_fwd, _grouped_bwd)


def _moe_dropfree(params, xt, *, n_experts: int, router_experts: int,
                  expert_offset: int, k: int, activation: str):
    """The drop-free grouped layer over a flat token stream xt: [T, d]."""
    T, d = xt.shape
    with jax.named_scope("moe.route"):
        tope, topw, aux = _route(params, xt, k)
        local = tope.reshape(-1) - expert_offset           # [T*k]
        held = (local >= 0) & (local < n_experts)
        # unheld slots sort after every held one: key n_experts
        key = jnp.where(held, local, n_experts)
        order = jnp.argsort(key, stable=True)
        counts = jnp.zeros((n_experts + 1,), jnp.int32).at[key].add(1)
        counts = counts[:n_experts]
        tok = order // k                                   # slot -> token
        held = held[order]
        w = jnp.where(held, topw.reshape(-1)[order], 0.0)
        rows = xt[tok]                                     # [T*k, d]
    with jax.named_scope("moe.experts"):
        dot = lambda a, b: grouped_matmul(a, b, counts)  # noqa: E731
        if "gate" in params:
            h = _activation(activation)(dot(rows, params["gate"]))
            h = h * dot(rows, params["up"])
        else:
            h = jax.nn.gelu(dot(rows, params["up"]), approximate=True)
        y = dot(h, params["down"])                         # [T*k, d]
    with jax.named_scope("moe.route"):
        # rows past the held run belong to no group; they are never summed
        y = jnp.where(held[:, None], y * w[:, None].astype(y.dtype),
                      jnp.zeros((), y.dtype))
        out = jnp.zeros((T, d), y.dtype).at[tok].add(y)
    return out.astype(xt.dtype), aux, counts


def _moe_tokens(params, xt, *, n_experts: int, k: int, capacity_factor: float,
                activation: str, shard_local: bool = False):
    """Core sort-based dispatch over a flat token stream xt: [T, d].
    shard_local=True (under the spmd_axis_name'd vmap) constrains the
    dispatch buffers to be unsharded WITHIN the shard."""
    T, d = xt.shape

    def local(a):
        if not shard_local:
            return a
        from jax.sharding import PartitionSpec

        return jax.lax.with_sharding_constraint(
            a, PartitionSpec(*(None,) * a.ndim))

    logits = xt @ params["router"]                       # [T, E]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    topw, tope = jax.lax.top_k(probs, k)                 # [T, k]
    topw = topw / jnp.maximum(jnp.sum(topw, -1, keepdims=True), 1e-9)

    # ---- sort-based dispatch ------------------------------------------------
    flat_e = tope.reshape(-1)                            # [T*k] expert ids
    flat_t = jnp.repeat(jnp.arange(T), k)                # [T*k] token ids
    flat_w = topw.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    # slot within the expert's buffer = rank within its sorted run
    run_start = jnp.searchsorted(se, se, side="left")
    slot = jnp.arange(T * k) - run_start
    C = max(1, int(capacity_factor * T * k / n_experts))
    keep = slot < C
    slot = jnp.where(keep, slot, 0)

    buf = jnp.zeros((n_experts, C, d), xt.dtype)
    keep_x = local(jnp.where(keep[:, None], xt[st], jnp.zeros((), xt.dtype)))
    buf = local(buf.at[se, slot].add(keep_x.astype(xt.dtype)))

    # ---- expert computation (the only FLOPs) --------------------------------
    if "gate" in params:
        act = jax.nn.silu if activation == "swiglu" else (
            lambda a: jax.nn.gelu(a, approximate=True))
        h = act(jnp.einsum("ecd,edf->ecf", buf, params["gate"]))
        h = h * jnp.einsum("ecd,edf->ecf", buf, params["up"])
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", buf, params["up"]),
                        approximate=True)
    y = jnp.einsum("ecf,efd->ecd", h, params["down"])    # [E, C, d]

    # ---- gather back + weighted combine ------------------------------------
    w_keep = jnp.where(keep, sw, 0.0).astype(xt.dtype)
    out_slots = local(local(y[se, slot]) * w_keep[:, None])
    out = local(jnp.zeros((T, d), xt.dtype).at[st].add(out_slots.astype(xt.dtype)))

    # ---- Switch-style load-balance loss -------------------------------------
    frac = jnp.mean(jax.nn.one_hot(tope[:, 0], n_experts, dtype=jnp.float32), 0)
    prob = jnp.mean(probs, axis=0)
    aux = n_experts * jnp.sum(frac * prob)
    counts = jnp.zeros((n_experts,), jnp.int32).at[flat_e].add(1)

    return out, aux, counts
