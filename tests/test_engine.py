"""Unified round engine: equivalence with the seed implementations.

The engine refactor (repro/core/engine.py) replaced seven hand-rolled round
bodies with one driver + slim per-algorithm specs. These tests pin the
refactor to the seed semantics:

* each migrated algorithm reproduces a reference implementation transcribed
  from the seed round bodies (python loops, no scan — so the tests also
  validate the engine's lax.scan lowering) to <= 1e-12 in float64. The
  residual is 1-2 ulp of XLA fusion rounding between jitted and op-by-op
  execution: running the engine against the JITTED seed implementation
  reproduces its floats exactly (verified during the migration; e.g. the
  compressed EF ablation numbers match the seed to the last bit);
* ``with_participation(rate=1.0)`` and ``with_compression(k_frac=1.0,
  quantize=False)`` are exact no-ops;
* the previously-impossible composition — compressed-uplink,
  partial-participation FedCET — converges to the exact optimum on the
  paper's quadratic problem;
* regression tests for the two participation bugs the refactor fixed
  (step counter advancing 2*tau-1 per round; shared PRNG key between the
  Bernoulli draw and the non-empty fallback).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    FedAvg,
    FedCET,
    FedCETCompressed,
    FedCETPartial,
    FedLin,
    FedTrack,
    Scaffold,
    max_weight_c,
    participation_mask,
    with_compression,
    with_participation,
)
from repro.core.comm import topk_sparsify
from repro.core.engine import make_round_runner
from repro.core.lr_search import lr_search
from repro.core.simulate import simulate_quadratic
from repro.data.quadratic import make_quadratic_problem

jax.config.update("jax_enable_x64", True)

TAU = 2
ROUNDS = 25


@pytest.fixture(scope="module")
def problem():
    return make_quadratic_problem(0)


def _setup(problem, tau=TAU):
    """Shared pieces of every reference run: the vmapped gradient, the
    stacked full-batch rounds, and the replicated start point."""
    gf = jax.vmap(jax.grad(problem.client_loss), in_axes=(0, 0))
    batches = problem.stacked_batches(tau)
    init_b = jax.tree.map(lambda b: b[0], batches)
    x0 = jnp.zeros((problem.dim,), problem.b.dtype)
    x = jnp.broadcast_to(x0[None], (problem.n_clients, problem.dim))
    return gf, batches, init_b, x


def _errs(problem, traj):
    return np.asarray([float(jnp.linalg.norm(x.mean(0) - problem.x_star))
                       for x in traj])


# jitted-scan vs op-by-op reference: identical math, <= 2 ulp of fusion
# rounding (float32 tolerance — the acceptance bar — would be ~1e-7).
_TOL = dict(rtol=1e-12, atol=1e-12)


def _assert_same_run(problem, algo, ref_traj, ref_final_leaves, res):
    """Engine run == reference: error curve and final state."""
    np.testing.assert_allclose(np.asarray(res.errors),
                               _errs(problem, ref_traj), **_TOL)
    for got, want in zip(jax.tree.leaves(res.state), ref_final_leaves):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), **_TOL)


# ------------------------------------------------------------------- FedCET
def _ref_fedcet(problem, alpha, c, tau, rounds, *, k_frac=1.0, quantize=False):
    """Seed FedCET / FedCETCompressed round body, transcribed verbatim
    (k_frac=1.0, quantize=False reduces to the uncompressed seed path)."""
    gf, batches, init_b, x = _setup(problem, tau)
    compressing = k_frac < 1.0 or quantize

    def compress(a):
        out = a
        if k_frac < 1.0:
            out = topk_sparsify(out, k_frac)
        if quantize:
            out = out.astype(jnp.bfloat16).astype(a.dtype)
        return out

    def comm(x, d, e, batch):
        g = gf(x, batch)
        v = x - alpha * g - alpha * d
        if compressing:
            e = e + v
            v_tx = compress(e)
            e = e - v_tx
        else:
            v_tx = v
        v_bar = v_tx.mean(0, keepdims=True)
        d = d + c * (v_tx - v_bar)
        x = v - c * alpha * (v_tx - v_bar)
        return x, d, e

    g = gf(x, init_b)
    x = x - alpha * g
    d = jnp.zeros_like(x)
    e = jnp.zeros_like(x)
    x, d, e = comm(x, d, e, init_b)
    traj = [x]
    for _ in range(rounds):
        for s in range(tau - 1):
            b = jax.tree.map(lambda a, s=s: a[s], batches)
            g = gf(x, b)
            x = x - alpha * g - alpha * d
        b = jax.tree.map(lambda a: a[tau - 1], batches)
        x, d, e = comm(x, d, e, b)
        traj.append(x)
    return traj, (x, d, e)


def test_fedcet_matches_seed(problem):
    alpha = lr_search(problem.mu, problem.L, TAU)
    c = max_weight_c(problem.mu, alpha)
    algo = FedCET(alpha=alpha, c=c, tau=TAU, n_clients=problem.n_clients)
    traj, (x, d, _) = _ref_fedcet(problem, alpha, c, TAU, ROUNDS)
    res = simulate_quadratic(algo, problem, rounds=ROUNDS)
    # state leaves: (x, d, t)
    _assert_same_run(problem, algo, traj,
                     [x, d, jnp.asarray((ROUNDS + 1) * TAU - TAU)], res)


def test_fedcet_tau1_and_tau4(problem):
    """The local-scan boundary cases: no local steps (tau=1) and several."""
    for tau in (1, 4):
        alpha = lr_search(problem.mu, problem.L, tau)
        c = max_weight_c(problem.mu, alpha)
        algo = FedCET(alpha=alpha, c=c, tau=tau, n_clients=problem.n_clients)
        traj, _ = _ref_fedcet(problem, alpha, c, tau, 10)
        res = simulate_quadratic(algo, problem, rounds=10)
        np.testing.assert_allclose(np.asarray(res.errors),
                                   _errs(problem, traj), **_TOL)


@pytest.mark.parametrize("repeat", [False, True])
def test_round_runner_metric_before_reads_the_entering_state(problem,
                                                             repeat):
    """``metric_before=True`` evaluates the metric on the state ENTERING
    each round: round r reads what the default runner reports after round
    r - 1, and the final state is unchanged."""
    alpha = lr_search(problem.mu, problem.L, TAU)
    algo = FedCET(alpha=alpha, c=max_weight_c(problem.mu, alpha), tau=TAU,
                  n_clients=problem.n_clients)
    grad_fn = jax.grad(problem.client_loss)
    batches = problem.stacked_batches(TAU)
    state0 = algo.init(grad_fn, jnp.zeros((problem.dim,), problem.b.dtype),
                       jax.tree.map(lambda b: b[0], batches))

    def err(s, b):
        return jnp.linalg.norm(algo.global_params(s) - problem.x_star)

    def run(before):
        runner = make_round_runner(algo, grad_fn, metric_fn=err,
                                   metric_with_batch=True, repeat=repeat,
                                   metric_before=before)
        if repeat:
            return runner(state0, batches, 5)
        stacked = jax.tree.map(lambda a: jnp.stack([a] * 5), batches)
        return runner(state0, stacked)

    after_state, after = run(False)
    before_state, before = run(True)
    np.testing.assert_allclose(np.asarray(before[0]),
                               np.asarray(err(state0, None)), **_TOL)
    np.testing.assert_allclose(np.asarray(before[1:]),
                               np.asarray(after[:-1]), **_TOL)
    for got, want in zip(jax.tree.leaves(before_state),
                         jax.tree.leaves(after_state)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), **_TOL)


def test_fedcet_compressed_matches_seed(problem):
    """Error-feedback top-k + bf16 — the full compressed seed recursion,
    including the transform state (feedback memory e) in EngineState."""
    alpha = lr_search(problem.mu, problem.L, TAU)
    c = max_weight_c(problem.mu, alpha)
    algo = FedCETCompressed(alpha=alpha, c=c, tau=TAU,
                            n_clients=problem.n_clients,
                            k_frac=0.3, quantize=True)
    traj, (x, d, e) = _ref_fedcet(problem, alpha, c, TAU, ROUNDS,
                                  k_frac=0.3, quantize=True)
    res = simulate_quadratic(algo, problem, rounds=ROUNDS)
    np.testing.assert_allclose(np.asarray(res.errors), _errs(problem, traj),
                               **_TOL)
    inner, extras = res.state
    np.testing.assert_allclose(np.asarray(inner.x), np.asarray(x), **_TOL)
    np.testing.assert_allclose(np.asarray(inner.d), np.asarray(d), **_TOL)
    np.testing.assert_allclose(np.asarray(extras[0]), np.asarray(e), **_TOL)


# ------------------------------------------------------------------- FedAvg
def test_fedavg_matches_seed(problem):
    alpha = 1.0 / (2 * TAU * problem.L)
    algo = FedAvg(alpha=alpha, tau=TAU, n_clients=problem.n_clients)
    gf, batches, _, x = _setup(problem)
    traj = [x]
    for _ in range(ROUNDS):
        for s in range(TAU):
            b = jax.tree.map(lambda a, s=s: a[s], batches)
            x = x - alpha * gf(x, b)
        x = jnp.broadcast_to(x.mean(0, keepdims=True), x.shape)
        traj.append(x)
    res = simulate_quadratic(algo, problem, rounds=ROUNDS)
    _assert_same_run(problem, algo, traj, [x, jnp.asarray(ROUNDS * TAU)], res)


# ----------------------------------------------------------------- SCAFFOLD
def test_scaffold_matches_seed(problem):
    a_l, a_g = 1.0 / (81 * TAU * problem.L), 1.0
    algo = Scaffold(alpha_l=a_l, alpha_g=a_g, tau=TAU,
                    n_clients=problem.n_clients)
    gf, batches, _, x = _setup(problem)
    ci = jnp.zeros_like(x)
    cc = jnp.zeros_like(x)
    traj = [x]
    for _ in range(ROUNDS):
        y = x
        for s in range(TAU):
            b = jax.tree.map(lambda a, s=s: a[s], batches)
            y = y - a_l * (gf(y, b) - ci + cc)
        ci_new = ci - cc + (x - y) / (TAU * a_l)
        x = x + a_g * (y - x).mean(0, keepdims=True)
        cc = cc + (ci_new - ci).mean(0, keepdims=True)
        ci = ci_new
        traj.append(x)
    res = simulate_quadratic(algo, problem, rounds=ROUNDS)
    _assert_same_run(problem, algo, traj,
                     [x, ci, cc, jnp.asarray(ROUNDS * TAU)], res)


# ----------------------------------------------------------- FedTrack/FedLin
def _ref_fedlin(problem, alpha, tau, rounds, k_frac):
    gf, batches, _, x = _setup(problem, tau)
    mem = jnp.zeros_like(x)
    traj = [x]
    for _ in range(rounds):
        b0 = jax.tree.map(lambda a: a[0], batches)
        g_i = gf(x, b0)
        if k_frac < 1.0:
            g_eff = g_i + mem
            g_i = topk_sparsify(g_eff, k_frac)
            mem = g_eff - g_i
        g_bar = g_i.mean(0, keepdims=True)
        y = x
        for s in range(tau):
            b = jax.tree.map(lambda a, s=s: a[s], batches)
            y = y - alpha * (gf(y, b) - g_i + g_bar)
        x = jnp.broadcast_to(y.mean(0, keepdims=True), y.shape)
        traj.append(x)
    return traj, (x, mem)


def test_fedtrack_matches_seed(problem):
    alpha = 1.0 / (18 * TAU * problem.L)
    algo = FedTrack(alpha=alpha, tau=TAU, n_clients=problem.n_clients)
    traj, (x, mem) = _ref_fedlin(problem, alpha, TAU, ROUNDS, 1.0)
    res = simulate_quadratic(algo, problem, rounds=ROUNDS)
    _assert_same_run(problem, algo, traj,
                     [x, mem, jnp.asarray(ROUNDS * TAU)], res)


def test_fedlin_topk_matches_seed(problem):
    alpha = 1.0 / (18 * TAU * problem.L)
    algo = FedLin(alpha=alpha, tau=TAU, n_clients=problem.n_clients,
                  k_frac=0.3)
    traj, (x, mem) = _ref_fedlin(problem, alpha, TAU, ROUNDS, 0.3)
    res = simulate_quadratic(algo, problem, rounds=ROUNDS)
    _assert_same_run(problem, algo, traj,
                     [x, mem, jnp.asarray(ROUNDS * TAU)], res)


# --------------------------------------------------------- transform no-ops
def test_identity_transforms_are_exact_noops(problem):
    alpha = lr_search(problem.mu, problem.L, TAU)
    base = FedCET(alpha=alpha, c=max_weight_c(problem.mu, alpha), tau=TAU,
                  n_clients=problem.n_clients)
    assert with_participation(base, 1.0) is base
    assert with_compression(base, k_frac=1.0, quantize=False) is base
    # ...and through the construction-sugar factories too
    part = FedCETPartial(alpha=base.alpha, c=base.c, tau=TAU,
                         n_clients=problem.n_clients, participation=1.0)
    comp = FedCETCompressed(alpha=base.alpha, c=base.c, tau=TAU,
                            n_clients=problem.n_clients, k_frac=1.0)
    r_base = simulate_quadratic(base, problem, rounds=20)
    for algo in (part, comp):
        r = simulate_quadratic(algo, problem, rounds=20)
        np.testing.assert_array_equal(np.asarray(r.errors),
                                      np.asarray(r_base.errors))


# --------------------------------------------------- composition (new-ability)
def test_composed_compression_participation_exact_convergence(problem):
    """The composed ``with_compression(with_participation(FedCET(...)))``
    expression converges to the EXACT optimum on the paper's quadratic
    problem (top-30%-sparsified single-vector uplink; measured ~1e-14)."""
    alpha = lr_search(problem.mu, problem.L, TAU)
    algo = with_compression(
        with_participation(
            FedCET(alpha=alpha, c=max_weight_c(problem.mu, alpha), tau=TAU,
                   n_clients=problem.n_clients),
            1.0, seed=3),
        k_frac=0.5)
    res = simulate_quadratic(algo, problem, rounds=4000)
    assert res.final_error < 1e-9, res.final_error


def test_composed_sampled_bf16_converges_to_quantization_floor(problem):
    """Beyond-paper finding (measured, not theory-claimed): with RANDOM
    client subsets, biased compression floors the error at the compressor's
    resolution — bf16 uplinks + 80% participation settle ~1e-5, the same
    order as full-participation compressed FedCET-C's bf16 floor (so
    sampling adds no systematic bias), and 5+ orders below FedAvg's drift
    floor. Top-k+EF behaves analogously with a larger (~3e-3) floor: the
    feedback limit cycle does not average out over random subsets."""
    alpha = lr_search(problem.mu, problem.L, TAU)
    algo = with_compression(
        with_participation(
            FedCET(alpha=alpha, c=max_weight_c(problem.mu, alpha), tau=TAU,
                   n_clients=problem.n_clients),
            0.8, seed=3),
        quantize=True)
    res = simulate_quadratic(algo, problem, rounds=3000)
    assert res.final_error < 2e-5, res.final_error


def test_unbiased_compressors_x_participation_no_error_floor(problem):
    """THE pinned upgrade over the biased-compressor caveat above: with the
    first-class UNBIASED compressors, compression x random participation
    converges to the exact optimum — no stochastic error floor.

    Measured (4000 rounds, 80% participation, seed 3): uncompressed
    ~2.9e-15; randk:0.5 ~3.0e-15; shift:q8 (DIANA-style shifted 8-bit
    dithered quantization) ~3.3e-15; shift:randk:0.5+q8 (4 bits/coord, an
    8x uplink cut) ~3.3e-15. All within 10x of the uncompressed run —
    i.e. at the float64 measurement floor, vs the 3e-3 (top-k+EF) and
    ~1e-5 (bf16) floors of the biased stacks."""
    alpha = lr_search(problem.mu, problem.L, TAU)
    base = with_participation(
        FedCET(alpha=alpha, c=max_weight_c(problem.mu, alpha), tau=TAU,
               n_clients=problem.n_clients), 0.8, seed=3)
    ref_err = simulate_quadratic(base, problem, rounds=4000).final_error
    assert ref_err < 1e-12  # participation alone: exact (pinned in PR 1)
    for spec in ("randk:0.5", "shift:q8", "shift:randk:0.5+q8"):
        algo = with_compression(base, compressor=spec)
        err = simulate_quadratic(algo, problem, rounds=4000).final_error
        assert err < 10 * ref_err, (spec, err, ref_err)


def test_plain_dithered_quant_floor_is_participation_induced(problem):
    """Documented-as-measured boundary of the result above: PLAIN (unshifted)
    dithered quantization is unbiased and converges exactly under FULL
    participation, but under random participation its fixed quantization
    step sustains a small re-excitation floor (~3e-5 ~ the kick scale
    c*alpha*step) — the shift wrapper quantizes the shrinking residual
    instead and removes it (previous test). Pinning both sides keeps the
    mechanism honest."""
    alpha = lr_search(problem.mu, problem.L, TAU)
    base = FedCET(alpha=alpha, c=max_weight_c(problem.mu, alpha), tau=TAU,
                  n_clients=problem.n_clients)
    full = with_compression(base, compressor="q8")
    assert simulate_quadratic(full, problem, rounds=4000).final_error < 1e-12
    part = with_compression(with_participation(base, 0.8, seed=3),
                            compressor="q8")
    err = simulate_quadratic(part, problem, rounds=3000).final_error
    assert 1e-8 < err < 5e-4, err  # the floor: present but small (meas 3e-5)


def test_composed_other_order_and_drift_invariant(problem):
    """Transforms compose in either order; sum_i d_i = 0 survives the
    composition (the Lemma 2 mean-zero invariant: drift updates use the
    client's own compressed message)."""
    alpha = lr_search(problem.mu, problem.L, TAU)
    algo = with_participation(
        with_compression(
            FedCET(alpha=alpha, c=max_weight_c(problem.mu, alpha), tau=TAU,
                   n_clients=problem.n_clients),
            k_frac=0.5),
        0.7, seed=11)
    res = simulate_quadratic(algo, problem, rounds=60)
    inner, _extras = res.state
    d_mean = np.asarray(jnp.mean(inner.d, axis=0))
    np.testing.assert_allclose(d_mean, 0.0, atol=1e-10)


def test_composed_up_frac_accounting(problem):
    """Uplink byte fractions under composition: FedLin's two up vectors
    compress independently (its own top-k on the round-start gradient, the
    engine transform on the endpoint message)."""
    n = problem.n_clients
    assert FedLin(alpha=0.01, tau=2, n_clients=n, k_frac=0.1).up_frac \
        == pytest.approx(0.6)  # (2*0.1 + 1)/2
    assert with_compression(FedTrack(alpha=0.01, tau=2, n_clients=n),
                            quantize=True).up_frac == pytest.approx(0.75)
    assert with_compression(
        FedCET(alpha=0.01, c=0.3, tau=2, n_clients=n),
        k_frac=0.3).up_frac == pytest.approx(0.6)


def test_stale_checkpoint_layout_fails_loudly(tmp_path, problem):
    """A checkpoint written with the pre-engine FedCETCompressed leaf order
    (x, d, e, t) must NOT silently restore transposed into the new
    EngineState layout (x, d, t, e) — same leaf count, different shapes."""
    from repro.checkpoint.ckpt import load_pytree, save_pytree

    alpha = lr_search(problem.mu, problem.L, TAU)
    algo = with_compression(
        FedCET(alpha=alpha, c=max_weight_c(problem.mu, alpha), tau=TAU,
               n_clients=problem.n_clients), quantize=True)
    res = simulate_quadratic(algo, problem, rounds=2)
    inner, (e,) = res.state
    old_layout = (inner.x, inner.d, e, inner.t)  # seed FedCETCState order
    path = str(tmp_path / "old.npz")
    save_pytree(path, old_layout)
    with pytest.raises(ValueError, match="incompatible"):
        load_pytree(path, res.state)


def test_composed_state_checkpoint_roundtrip(tmp_path, problem):
    """EngineState (inner + transform extras) survives checkpointing."""
    from repro.checkpoint.ckpt import load_pytree, save_pytree

    alpha = lr_search(problem.mu, problem.L, TAU)
    algo = with_compression(
        FedCET(alpha=alpha, c=max_weight_c(problem.mu, alpha), tau=TAU,
               n_clients=problem.n_clients), quantize=True)
    res = simulate_quadratic(algo, problem, rounds=3)
    path = str(tmp_path / "state.npz")
    save_pytree(path, res.state)
    back = load_pytree(path, res.state)
    for a, b in zip(jax.tree.leaves(res.state), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -------------------------------------------------- participation bug fixes
def test_participation_step_counter_advances_tau_per_round(problem):
    """Regression (seed bug): FedCETPartial advanced t by 2*tau-1 per round
    (the local scan already bumped it tau-1 times, then t + tau was applied
    on top), skewing the per-round mask key schedule. The engine advances t
    by exactly tau regardless of sampling."""
    alpha = lr_search(problem.mu, problem.L, TAU)
    algo = FedCETPartial(alpha=alpha, c=max_weight_c(problem.mu, alpha),
                         tau=TAU, n_clients=problem.n_clients,
                         participation=0.6)
    res = simulate_quadratic(algo, problem, rounds=7)
    assert int(res.state.t) == 7 * TAU


def test_participation_mask_key_split():
    """Regression (seed bug): the Bernoulli draw and the non-empty fallback
    used the SAME key. With independent subkeys the forced client index is
    uniform: at rate=0 every client must be selected across enough seeds."""
    n = 10
    chosen = set()
    for s in range(300):
        m = participation_mask(jax.random.key(s), n, 0.0)
        idx = np.flatnonzero(np.asarray(m))
        assert idx.size == 1  # exactly the forced client
        chosen.add(int(idx[0]))
    assert chosen == set(range(n))


def test_participation_masks_deterministic_per_round(problem):
    """Same seed + same round counter => same mask (restart-stable)."""
    key = jax.random.fold_in(jax.random.key(5), 12)
    m1 = participation_mask(key, 8, 0.4)
    m2 = participation_mask(key, 8, 0.4)
    np.testing.assert_array_equal(np.asarray(m1), np.asarray(m2))
