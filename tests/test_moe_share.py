"""The sparse-expert LM against the plain reference (``benchmarks/chip/
reference/moe.py``) at a small size on the CPU, with seeded random weights.

* the program's loss and gradients of a layer-share model (4 of 8 experts
  held, Granite's multipliers, tied head) against the reference's, from
  the same weights bit for bit;
* the shares add up: expert layers over disjoint held sets that cover all
  experts sum to the uncut reference layer, with the router and its
  load-balance term, which every share computes alike, counted once;
* no drops: every token routed to the same held experts still gives the
  reference's output (a capacity buffer would have dropped most of them);
* the round's telemetry sums the routed slots over layers, clients and
  local steps (``moe_held_slots``).

Weights are float32 (x64 is off inside each test): the reference draws
them in the default float type.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "chip"))

from reference import moe as ref  # noqa: E402
from repro.configs.base import ArchConfig  # noqa: E402
from repro.core.api import StatsGrad  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.models.moe import apply_moe  # noqa: E402

#: a layer share of a Granite-shaped model: experts 2..5 of 8 held
CFG = dict(n_layers=2, d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
           d_ff=16, vocab_size=64, n_experts=4, router_experts=8,
           expert_offset=2, experts_per_token=2, rope_theta=10000.0,
           embedding_multiplier=12.0, residual_multiplier=0.22,
           attention_multiplier=0.015625, logits_scaling=6.0)
B, S = 2, 8


def program_cfg(cfg: dict) -> ArchConfig:
    fields = {f.name for f in dataclasses.fields(ArchConfig)}
    return ArchConfig(name="moe-share", family="moe", activation="swiglu",
                      tie_embeddings=True, scan_layers=True, remat=False,
                      **{k: v for k, v in cfg.items() if k in fields})


@pytest.fixture(autouse=True)
def float32():
    with jax.enable_x64(False), jax.default_matmul_precision("highest"):
        yield


def _tokens(key=1):
    return jax.random.randint(jax.random.key(key), (B, S), 0,
                              CFG["vocab_size"])


def test_loss_and_grads_match_reference():
    model = build_model(program_cfg(CFG))
    key = jax.random.key(7)
    mine, theirs = ref.init(CFG, key), model.init(key)
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    toks = _tokens()
    want, want_g = jax.value_and_grad(ref.loss)(mine, toks, CFG)
    got = model.loss(theirs, {"tokens": toks})
    got_g, stats = StatsGrad(model.loss_and_stats)(theirs, {"tokens": toks})
    # float32 both; only the order of the sums differs (the scatter-add
    # combine against the reference's einsum, the chunked cross-entropy):
    # a few float32 ulps of a loss near log(64)
    np.testing.assert_allclose(float(got), float(want), rtol=2e-6)
    for a, b in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        # each leaf to 1e-5 of its own largest entry (rounding of sums)
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5 * scale, rtol=0)
    # every layer routes B*S*k slots over 8 experts; 4 are held here
    slots = np.asarray(stats["moe_expert_slots"])
    assert slots.shape == (CFG["n_experts"],)
    assert 0 < slots.sum() <= CFG["n_layers"] * B * S * CFG["experts_per_token"]


def _layer_params(cfg: dict, key):
    """One block's expert-layer weights as the reference draws them."""
    return jax.tree.map(lambda a: a[0], ref.init(dict(cfg, n_layers=1),
                                                 key)["layers"]["moe"])


def _program_layer(m, h, cfg: dict):
    return apply_moe(m, h, n_experts=cfg["n_experts"],
                     k=cfg["experts_per_token"], capacity_factor=1.0,
                     activation="swiglu", shared_expert=False,
                     router_experts=cfg["router_experts"],
                     expert_offset=cfg["expert_offset"])


@pytest.mark.parametrize("held", [1, 2, 4])
def test_shares_add_up_to_the_uncut_layer(held):
    whole = dict(CFG, n_experts=8, expert_offset=0)
    key = jax.random.key(3)
    m_whole = _layer_params(whole, key)
    h = jax.random.normal(jax.random.key(4), (B, S, CFG["d_model"]))
    want, want_aux = ref.moe_layer(m_whole, h, whole)
    total, auxes = jnp.zeros_like(h), []
    for lo in range(0, 8, held):
        share = dict(CFG, n_experts=held, expert_offset=lo)
        m = _layer_params(share, key)
        # a share's weights are the uncut layer's rows for its experts
        for w in ("gate", "up", "down"):
            np.testing.assert_array_equal(m[w], m_whole[w][lo:lo + held])
        out, aux, slots = _program_layer(m, h, share)
        total, auxes = total + out, auxes + [aux]
        assert slots.shape == (held,)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-5 * float(jnp.max(jnp.abs(want))))
    # the router and its load-balance term are the same in every share
    np.testing.assert_allclose(np.asarray(auxes), float(want_aux), rtol=1e-6)


@pytest.mark.parametrize("hot", [2, 5])
def test_no_token_dropped_when_all_route_to_one_held_expert(hot):
    m = _layer_params(CFG, jax.random.key(5))
    # every token's first choice is expert ``hot`` (held: 2..5), its second
    # the next held one: 32 slots on two experts, 4 times a 1.25 capacity
    second = 2 + (hot - 1) % 4
    router = np.zeros((CFG["d_model"], CFG["router_experts"]), np.float32)
    router[:, hot], router[:, second] = 1.0, 0.5
    m = dict(m, router=jnp.asarray(router))
    h = jnp.abs(jax.random.normal(jax.random.key(6), (B, S, CFG["d_model"])))
    want, _ = ref.moe_layer(m, h, CFG)
    got, _, slots = _program_layer(m, h, CFG)
    assert int(slots[hot - 2]) == B * S and int(slots.sum()) == 2 * B * S
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5 * float(jnp.max(jnp.abs(want))))


@pytest.mark.parametrize("arena", [False, True])
def test_round_telemetry_counts_every_routed_slot(arena):
    """With every expert held, the round's ``moe_held_slots`` is every
    routed slot of every layer, client and local step."""
    from repro.configs.base import FedScenario
    from repro.core import telemetry as tele
    from repro.core.engine import make_round_runner
    from repro.core.fedcet import FedCET

    cfg = dict(CFG, n_experts=8, expert_offset=0)
    model = build_model(program_cfg(cfg))
    n, tau = 2, 3
    algo = FedScenario(compression="shift:q8", arena=arena,
                       telemetry="memory").apply(
        FedCET(alpha=0.05, c=0.05, tau=tau, n_clients=n))
    grad_fn = StatsGrad(model.loss_and_stats)
    toks = jax.random.randint(jax.random.key(2), (1, tau, n, B, S), 0,
                              CFG["vocab_size"])
    state = algo.init(grad_fn, model.init(jax.random.key(0)),
                      {"tokens": toks[0, 0]})
    _, ys = make_round_runner(algo, grad_fn)(state, {"tokens": toks})
    series = tele.split_metrics(algo, ys)[1]
    slots = n * tau * CFG["n_layers"] * B * S * CFG["experts_per_token"]
    assert int(series["moe_held_slots"][0]) == slots
    assert float(series["moe_load_max"][0]) >= 1.0
