"""Plain reference of the federated job: FedCET rounds (arXiv:2503.15804,
Algorithm 2 in the (d, x) form of its Lemma 1) with the shift:q8 uplink, in
straightforward ``jax.numpy`` over per-leaf parameter dicts.

Warm-up: x = x0 - alpha g(x0), d = 0, then one aggregating step. A round is
tau - 1 local steps x <- x - alpha g - alpha d and one aggregating step:

    v  = x - alpha g - alpha d                 (the one uplink vector)
    q  = quantize(v - h)                       (DIANA shift h, step 1)
    m  = h + q,  h <- h + q,  mbar = mean over clients of m
    d <- d + c (m - mbar),  x <- v - c alpha (m - mbar)

``quantize`` is dithered 8-bit quantization with one scale per leaf,
max|leaf over all clients| / 127, and one dither per leaf shared by all
clients: u = uniform(fold_in(k, leaf index)) with k = fold_in(fold_in(
key(scenario seed), 0x7A11A5), round-entry step counter), q = clip(floor(r
/ s + u), -127, 127) s; the scenario seed is the traffic's, the weights
come from the run's seed. The step counter is -1 at the warm-up and advances by tau per
round. This is the uplink the configuration states; the draw has to be the
same as the program's for the two trajectories to be comparable at all.

Each client's gradient is taken one client at a time. The loss logged for a
round is the clients' mean loss on the round's last batch, before the round.
After each round the reference also keeps each client's norm of its drift
d and of its distance from the clients' mean x: the sources of the
program's telemetry sketches.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp

#: domain tag of the uplink's dither key (the first transform of the stack)
DITHER_TAG = 0x7A11A5


def uplink_bits(spec: str) -> int:
    m = re.fullmatch(r"shift:q(\d+)", spec)
    if m is None:
        raise ValueError(f"the reference models the shift:q<bits> uplink "
                         f"only, not {spec!r}")
    return int(m.group(1))


def _norm(a):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))


def _client_norms(tree):
    """[clients] norms of a tree whose leaves lead with the clients axis."""
    return jnp.sqrt(sum(jnp.sum(jnp.square(a.astype(jnp.float32)),
                                axis=tuple(range(1, a.ndim)))
                        for a in jax.tree.leaves(tree)))


def make_trajectory(family, cfg: dict, traffic: dict, dtype):
    """Jitted ``run(seed, tokens)`` for ``tokens`` [rounds, tau, clients, B,
    S]. Returns per-round ``loss`` and ``grad_norm`` (mean client norm of the
    gradient at the aggregating step), per round and client the norms of d
    (``d_norm``) and of x less the clients' mean (``drift``) after the
    round, and per model leaf (flatten order) the norms of the parameters'
    change over the rounds (``dx``, all clients together), of the drift
    ``d`` after them, and of the warm-up gradient (``g0``)."""
    alpha, c, tau = traffic["alpha"], traffic["c"], traffic["tau"]
    ca = c * alpha
    levels = 2 ** (uplink_bits(traffic["compression"]) - 1) - 1
    grad_one = jax.grad(lambda p, t: family.loss(p, t, cfg))
    loss_one = lambda p, t: family.loss(p, t, cfg)  # noqa: E731
    tmap = jax.tree.map

    def grads(x, toks):
        return jax.lax.map(lambda a: grad_one(*a), (x, toks))

    def quantize(r, step):
        key = jax.random.fold_in(jax.random.key(traffic["scenario_seed"]),
                                 DITHER_TAG)
        key = jax.random.fold_in(key, jnp.asarray(step, jnp.int32))
        leaves, treedef = jax.tree.flatten(r)
        out = []
        for i, a in enumerate(leaves):
            s = jnp.max(jnp.abs(a)) / levels
            u = jax.random.uniform(jax.random.fold_in(key, i), a.shape[1:],
                                   dtype=a.dtype)
            inv = jnp.where(s > 0, 1.0 / s, 0.0)
            out.append(jnp.clip(jnp.floor(a * inv + u), -levels, levels) * s)
        return jax.tree.unflatten(treedef, out)

    def aggregate(x, d, h, toks, step):
        g = grads(x, toks)
        v = tmap(lambda xx, gg, dd: xx - alpha * gg - alpha * dd, x, g, d)
        q = quantize(tmap(jnp.subtract, v, h), step)
        m = tmap(jnp.add, h, q)
        h = tmap(lambda hh, qq: hh + qq, h, q)
        dm = tmap(lambda mm: mm - jnp.mean(mm, axis=0, keepdims=True), m)
        d = tmap(lambda dd, e: dd + c * e, d, dm)
        x = tmap(lambda vv, e: vv - ca * e, v, dm)
        return x, d, h, jnp.mean(_client_norms(g))

    def run(seed, tokens):
        params = tmap(lambda a: a.astype(dtype),
                      family.init(cfg, jax.random.key(seed)))
        n = tokens.shape[2]
        x0 = tmap(lambda a: jnp.broadcast_to(a, (n,) + a.shape), params)
        g0 = grads(x0, tokens[0, 0])
        x = tmap(lambda xx, gg: xx - alpha * gg, x0, g0)
        d = tmap(jnp.zeros_like, x)
        x, d, h, _ = aggregate(x, d, d, tokens[0, 0], -1)

        def one_round(carry, toks):
            x, d, h, t = carry
            loss = jnp.mean(jax.lax.map(lambda a: loss_one(*a),
                                        (x, toks[tau - 1])))

            def local(x, tk):
                g = grads(x, tk)
                return tmap(lambda xx, gg, dd: xx - alpha * gg - alpha * dd,
                            x, g, d), None

            x, _ = jax.lax.scan(local, x, toks[:tau - 1])
            x, d, h, gn = aggregate(x, d, h, toks[tau - 1], t)
            drift = tmap(lambda a: a - jnp.mean(a, axis=0, keepdims=True), x)
            return (x, d, h, t + tau), (loss.astype(jnp.float32), gn,
                                        _client_norms(d), _client_norms(drift))

        (x, d, _, _), (losses, gnorm, d_norm, drift) = jax.lax.scan(
            one_round, (x, d, h, jnp.asarray(0, jnp.int32)), tokens)
        leaves = jax.tree.leaves
        return {
            "loss": losses, "grad_norm": gnorm, "d_norm": d_norm,
            "drift": drift,
            "dx": jnp.stack([_norm(a - p[None]) for a, p in
                             zip(leaves(x), leaves(params))]),
            "d": jnp.stack([_norm(a) for a in leaves(d)]),
            "g0": jnp.stack([_norm(a) for a in leaves(g0)]),
        }

    return jax.jit(run)
