"""Cohort execution: the O(cohort) gathered round vs the dense path.

The gather lowering runs per-client work (begin_round, the local scan,
message) on the cohort's gathered ``[m, ...]`` rows only; the ``dense``
lowering runs it on all ``[N, ...]`` rows and gathers the results. All
cross-client work (transforms, delay buffering, the weighted reduce,
server_aggregate, the within-cohort participation freeze) is shared
between the two lowerings on cohort-sized arrays — so the lowerings must
agree EXACTLY (these tests run in f64 via conftest; everything here pins
<= 1e-12 and in practice lands bitwise)."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.core import (
    CohortSpec,
    FedAvg,
    FedTrack,
    Scaffold,
    parse_cohort,
    run_rounds,
    with_cohort,
    with_compression,
    with_delay,
    with_participation,
    with_topology,
)
from repro.core.baselines import FedLin
from repro.core.fedcet import FedCET
from repro.data.quadratic import make_hetero_hessian_problem

N, M, TAU, ROUNDS = 24, 7, 2, 6
TOL = 1e-12

PROB = make_hetero_hessian_problem(0, n_clients=N, dim=12, n_measurements=4)
GRAD = jax.grad(PROB.client_loss)
BATCHES = PROB.stacked_batches(TAU)
FIRST = jax.tree.map(lambda b: b[0], BATCHES)


def _algos():
    return {
        "fedcet": FedCET(alpha=0.02, c=0.3, tau=TAU, n_clients=N),
        "fedavg": FedAvg(alpha=0.05, tau=TAU, n_clients=N),
        "scaffold": Scaffold(alpha_l=0.02, tau=TAU, n_clients=N),
        "fedlin": FedTrack(alpha=0.02, tau=TAU, n_clients=N),
    }


def _run(algo, rounds=ROUNDS, state=None):
    if state is None:
        state = algo.init(GRAD, jnp.zeros((PROB.dim,), PROB.b.dtype), FIRST)
    final, _ = run_rounds(algo, GRAD, state, BATCHES, rounds=rounds)
    return final


def _assert_close(a, b, tol=TOL):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert float(jnp.max(jnp.abs(x - y))) <= tol


def _composed(algo):
    """The full scenario stack of the issue: shift:q8 x 0.8 participation
    x fixed:2 delay (compose first, cohort wraps the whole spec)."""
    algo = with_participation(algo, 0.8, seed=3)
    algo = with_compression(algo, compressor="shift:q8", seed=5)
    return with_delay(algo, "fixed:2", policy="last", seed=7)


# ------------------------------------------------- gather == dense lowering
@pytest.mark.parametrize("name", list(_algos()))
def test_cohort_lowerings_agree_bare(name):
    algo = _algos()[name]
    g = with_cohort(algo, CohortSpec(size=M, lowering="gather"))
    d = with_cohort(algo, CohortSpec(size=M, lowering="dense"))
    _assert_close(_run(g), _run(d))


@pytest.mark.parametrize("name", list(_algos()))
def test_cohort_lowerings_agree_composed(name):
    algo = _composed(_algos()[name])
    g = with_cohort(algo, CohortSpec(size=M, lowering="gather"))
    d = with_cohort(algo, CohortSpec(size=M, lowering="dense"))
    _assert_close(_run(g), _run(d))


def test_cohort_lowerings_agree_drop_policy():
    """The drop policy's continuation step (local_step on the stale rows)
    also runs on cohort rows only — both lowerings must agree."""
    algo = with_delay(FedCET(alpha=0.02, c=0.3, tau=TAU, n_clients=N),
                      "rr:2", policy="drop")
    g = with_cohort(algo, CohortSpec(size=M, lowering="gather"))
    d = with_cohort(algo, CohortSpec(size=M, lowering="dense"))
    _assert_close(_run(g), _run(d))


def test_cohort_lowerings_agree_hierarchical_tier_compression():
    """Hierarchical reduce over a cohort: first-tier segment ids are the
    full-population assignment gathered at the cohort ids, so stateful
    tier memory ([g, ...], full-N groups) advances identically."""
    algo = with_topology(FedCET(alpha=0.02, c=0.3, tau=TAU, n_clients=N),
                         "hier:g4", tier_compression="shift:q8")
    g = with_cohort(algo, CohortSpec(size=M, lowering="gather"))
    d = with_cohort(algo, CohortSpec(size=M, lowering="dense"))
    _assert_close(_run(g), _run(d))


@pytest.mark.parametrize("selector", ["block", "rr", "uniform"])
def test_cohort_selectors_lowering_invariant(selector):
    algo = FedCET(alpha=0.02, c=0.3, tau=TAU, n_clients=N)
    g = with_cohort(algo, CohortSpec(size=M, selector=selector,
                                     lowering="gather"))
    d = with_cohort(algo, CohortSpec(size=M, selector=selector,
                                     lowering="dense"))
    _assert_close(_run(g), _run(d))


def test_rr_selector_covers_population():
    """Round-robin blocks sweep every client id across ceil(N/m) rounds."""
    spec = CohortSpec(size=M, selector="rr")
    seen = set()
    for r in range(-(-N // M)):
        seen.update(int(i) for i in spec.indices(r * TAU, TAU, N))
    assert seen == set(range(N))



# ------------------------------------------------------------- counters
@pytest.mark.parametrize("lowering", ["population", "gather", "dense"])
def test_round_counts_every_local_step(lowering):
    """A round's per-call counters sum over all tau gradient calls — the
    tau-1 local steps of the muted scan and the aggregating step's — on
    the rows each call runs on: the cohort's in the gather lowering,
    every client's in the population round and the dense reference."""
    from repro.core import make_round_runner, split_metrics, with_telemetry
    from repro.core.api import StatsGrad

    tau = 3

    def loss_and_stats(x, batch):
        return PROB.client_loss(x, batch), {
            "grad_rows": jnp.ones((), jnp.int32)}

    grad = StatsGrad(loss_and_stats)
    batches = PROB.stacked_batches(tau)
    algo = FedCET(alpha=0.02, c=0.3, tau=tau, n_clients=N)
    rows = N
    if lowering != "population":
        algo = with_cohort(algo, CohortSpec(size=M, lowering=lowering))
        rows = M if lowering == "gather" else N
    algo = with_telemetry(algo, True)
    state = algo.init(grad, jnp.zeros((PROB.dim,), PROB.b.dtype),
                      jax.tree.map(lambda b: b[0], batches))
    _, ys = make_round_runner(algo, grad, repeat=True)(state, batches, 2)
    counted = split_metrics(algo, ys)[1]["grad_rows"]
    assert [int(c) for c in counted] == [tau * rows] * 2


# ------------------------------------------------------- checkpoint/resume
def test_cohort_checkpoint_resume_mid_sweep(tmp_path):
    """Save after 4 rounds, reload, run 4 more — identical to 8 straight
    rounds: the cohort schedule keys off the state's step counter, and
    the relocated extras (shift memory, delay buffers) round-trip."""
    from repro.checkpoint.ckpt import load_pytree, save_pytree

    algo = with_cohort(_composed(FedCET(alpha=0.02, c=0.3, tau=TAU,
                                        n_clients=N)), M)
    straight = _run(algo, rounds=8)
    mid = _run(algo, rounds=4)
    path = str(tmp_path / "ckpt.npz")
    save_pytree(path, mid)
    resumed_state = load_pytree(path, mid)
    resumed = _run(algo, rounds=4, state=resumed_state)
    _assert_close(straight, resumed, tol=0.0)


# ----------------------------------------------------- factory + validation
def test_with_cohort_identity_cases():
    algo = FedCET(alpha=0.02, c=0.3, tau=TAU, n_clients=N)
    for spec in (None, "none", "off", "full", 0, "0", "", N, str(N)):
        assert with_cohort(algo, spec) is algo
    with pytest.raises(ValueError):
        with_cohort(algo, N + 1)


def test_with_cohort_rejects_stacking():
    algo = with_cohort(FedCET(alpha=0.02, c=0.3, tau=TAU, n_clients=N), M)
    with pytest.raises(ValueError):
        with_cohort(algo, M)


def test_with_cohort_rejects_mixing_both_orders():
    algo = FedCET(alpha=0.02, c=0.3, tau=TAU, n_clients=N)
    gossip = with_topology(algo, "ring")
    with pytest.raises(ValueError):
        with_cohort(gossip, M)
    with pytest.raises(ValueError):
        with_topology(with_cohort(algo, M), "ring")


def test_with_cohort_rejects_fedlin_cross_client_topk():
    sparse = FedLin(alpha=0.02, tau=TAU, n_clients=N, k_frac=0.3)
    with pytest.raises(ValueError):
        with_cohort(sparse, M)
    # k_frac=1 (FedTrack) is dense — cohort-safe
    assert with_cohort(FedTrack(alpha=0.02, tau=TAU, n_clients=N),
                       M).cohort is not None


def test_parse_cohort_grammar():
    assert parse_cohort(None) is None
    assert parse_cohort("none") is None
    assert parse_cohort(256) == CohortSpec(size=256)
    assert parse_cohort("256") == CohortSpec(size=256)
    assert parse_cohort("block:256") == CohortSpec(size=256, selector="block")
    assert parse_cohort("rr:64:dense") == CohortSpec(
        size=64, selector="rr", lowering="dense")
    assert parse_cohort("1024:dense") == CohortSpec(size=1024,
                                                    lowering="dense")
    for bad in ("block", "block:", "nope:8", "8:nope", "block:8:gather:x"):
        with pytest.raises(ValueError):
            parse_cohort(bad)


def test_cohort_spec_validation():
    with pytest.raises(ValueError):
        CohortSpec(size=0)
    with pytest.raises(ValueError):
        CohortSpec(size=4, selector="nope")
    with pytest.raises(ValueError):
        CohortSpec(size=4, lowering="nope")


def test_cohort_scenario_applies_last():
    """FedScenario(cohort=...) wraps the fully-composed spec."""
    from repro.configs.base import FedScenario

    sc = FedScenario(compression="shift:q8", participation=0.8,
                     delay="fixed:2", cohort=f"block:{M}", seed=3)
    algo = sc.apply(FedCET(alpha=0.02, c=0.3, tau=TAU, n_clients=N))
    assert algo.cohort == CohortSpec(size=M, selector="block", seed=3)
    ref = with_cohort(
        FedScenario(compression="shift:q8", participation=0.8,
                    delay="fixed:2", seed=3).apply(
            FedCET(alpha=0.02, c=0.3, tau=TAU, n_clients=N)),
        CohortSpec(size=M, selector="block", seed=3))
    _assert_close(_run(algo), _run(ref), tol=0.0)


def test_cohort_converges_on_quadratic():
    """Sanity: the cohort path optimizes — FedCET with a rotating block
    cohort (every client visited each ceil(N/m) rounds) drives the
    paper's quadratic toward x*. Partial rounds contract slower than the
    synchronous rate, so this pins steady progress, not the paper's
    linear rate (which assumes full participation)."""
    from repro.data.quadratic import make_quadratic_problem

    prob = make_quadratic_problem(1, n_clients=N, dim=12, n_measurements=4)
    grad = jax.grad(prob.client_loss)
    batches = prob.stacked_batches(TAU)
    algo = with_cohort(FedCET(alpha=0.05, c=0.5, tau=TAU, n_clients=N),
                       CohortSpec(size=M, selector="rr"))
    state = algo.init(grad, jnp.zeros((prob.dim,), prob.b.dtype),
                      jax.tree.map(lambda b: b[0], batches))
    err0 = float(jnp.linalg.norm(
        algo.client_params(state)[0] - prob.x_star))
    final, _ = run_rounds(algo, grad, state, batches, rounds=400)
    err = float(jnp.linalg.norm(
        algo.client_params(final)[0] - prob.x_star))
    assert err < 0.2 * err0, (err0, err)
