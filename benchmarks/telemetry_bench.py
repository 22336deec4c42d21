"""Benchmark: live monitor boundaries of the in-trace telemetry.

Two claims, both asserted (telemetry's device cost is measured on the chip
by the chip benchmark's ``telemetry_ms``):

1. **Live invariant boundary**: the invariant monitor reproduces the
   PR 3 pinned staleness boundary FROM A SINGLE RUN'S JSONL — no offline
   re-simulation: ``fixed:2`` + ``poly:1`` keeps uniform ages, so the
   streamed ``invariant_residual`` series stays at f64 noise and the
   monitor is SILENT; ``rr:2`` + ``poly:1`` makes ages non-uniform, the
   residual drifts above the 1e-6 bound, and the monitor emits WARN
   events naming the offending axis (stale_policy).

2. **Live rate boundary**: the online linear-rate estimator
   (``RateMonitor``, windowed log-residual regression over the streamed
   ``err`` series) detects the SAME boundary as a rate break: ``fixed:2``
   + ``poly:1`` contracts linearly every round (rho_hat < 1, silent);
   ``rr:2`` + ``poly:1`` floors, the windowed rho_hat crosses 1 after
   linear convergence was established, and the monitor WARNs naming the
   suspect axis — verified both live at drain time and by re-running
   ``replay_jsonl`` over the finished file alone.

Emits ``results/BENCH_telemetry.json``. Runs via benchmarks/run.py (late:
it enables x64 for the f64 residual floor) or directly.
"""

from __future__ import annotations

import json
import os
import tempfile

from benchmarks._timing import results_dir, write_bench_json

BOUNDARY_ROUNDS = 60
N_CLIENTS = 32
DIM = 512
#: measurements per client of the quadratic problem
N_MEAS = 64


def _fedcet(problem, tau=2):
    from repro.core import FedCET, max_weight_c
    from repro.core.lr_search import lr_search

    alpha = lr_search(problem.mu, problem.L, tau)
    return FedCET(alpha=alpha, c=max_weight_c(problem.mu, alpha), tau=tau,
                  n_clients=problem.n_clients)


def _jsonl_boundary(base, problem, delay_spec: str, path: str):
    """One LIVE run: simulate with telemetry attached, drain the stacked
    series (plus the distance-to-optimum ``err`` series the rate
    estimator watches) into a JSONL sink, then read the FILE back and
    return the parsed residual series + WARN events split by monitor kind
    (what a dashboard would see)."""
    import numpy as np

    from repro.core import (INVARIANT_MONITOR, JsonlSink, RateMonitor, drain,
                            rate_axis, run_manifest, with_delay,
                            with_telemetry)
    from repro.core.simulate import simulate_quadratic

    algo = with_telemetry(
        with_delay(base, delay_spec, policy="poly:1"), True)
    monitors = (INVARIANT_MONITOR, RateMonitor(axis=rate_axis(algo)))
    res = simulate_quadratic(algo, problem, rounds=BOUNDARY_ROUNDS)
    sink = JsonlSink(path)
    sink.emit(run_manifest(algo, n_params=problem.dim,
                           config={"delay": delay_spec, "policy": "poly:1"},
                           monitors=monitors))
    # errors[0] is the pre-round state; round r's event carries errors[r+1]
    drain({**res.telemetry, "err": np.asarray(res.errors)[1:]},
          sinks=[sink], monitors=monitors, algo=algo, n_params=problem.dim)
    sink.close()
    with open(path) as f:
        events = [json.loads(line) for line in f]
    assert events[0]["event"] == "manifest", events[0]
    residuals = [e["invariant_residual"] for e in events
                 if e["event"] == "round"]
    warns = [e for e in events
             if e["event"] == "monitor" and e.get("level") == "WARN"]
    inv_warns = [w for w in warns if w.get("kind") != "rate_break"]
    rate_warns = [w for w in warns if w.get("kind") == "rate_break"]
    assert len(residuals) == BOUNDARY_ROUNDS
    return residuals, inv_warns, rate_warns


def run(csv_rows=None, quick: bool = False):
    import jax

    jax.config.update("jax_enable_x64", True)  # residual floor is f64 noise

    from repro.data.quadratic import make_quadratic_problem

    problem = make_quadratic_problem(0, n_clients=N_CLIENTS, dim=DIM,
                                     n_measurements=N_MEAS)
    base = _fedcet(problem)

    # ---- the staleness boundary, live from one run's JSONL --------------
    tmp = tempfile.mkdtemp(prefix="telemetry_bench_")
    exact_path = os.path.join(tmp, "fixed2_poly1.jsonl")
    drift_path = os.path.join(tmp, "rr2_poly1.jsonl")
    exact, exact_warns, exact_rate = _jsonl_boundary(
        base, problem, "fixed:2", exact_path)
    drift, drift_warns, drift_rate = _jsonl_boundary(
        base, problem, "rr:2", drift_path)
    # fixed:k -> uniform ages -> poly weights uniform -> exact: the monitor
    # stays silent and the streamed residual series sits at f64 noise.
    assert max(exact) < 1e-9, max(exact)
    assert not exact_warns, exact_warns[:2]
    # rr:2 -> non-uniform ages -> poly weights non-uniform -> Lemma 2
    # breaks: the residual drifts above the bound and the monitor fires,
    # naming the offending axis.
    assert max(drift) > 1e-4, max(drift)
    assert drift_warns, "monitor failed to fire on rr:2 + poly:1"
    assert "stale_policy" in drift_warns[0]["axis"]
    # the rate estimator sees the same boundary: fixed:2 contracts
    # linearly to the end (no break); rr:2 floors and the windowed
    # rho_hat crossing 1 fires a rate break naming the suspect axis.
    assert not exact_rate, exact_rate[:2]
    assert drift_rate, "rate monitor failed to fire on rr:2 + poly:1"
    assert "stale_policy" in drift_rate[0]["axis"]
    assert drift_rate[0]["rho_hat"] >= 0.99, drift_rate[0]
    # ... and reproduces POST HOC from the finished file alone.
    from repro.core import RateMonitor, replay_jsonl

    replayed = [w for w in replay_jsonl(drift_path, (RateMonitor(),))
                if w.get("kind") == "rate_break"]
    assert replayed, "replay_jsonl missed the rr:2 rate break"
    assert replayed[0]["round"] == drift_rate[0]["round"], (
        replayed[0], drift_rate[0])

    write_bench_json(
        "telemetry",
        config={"n_clients": N_CLIENTS, "dim": DIM,
                "n_measurements": N_MEAS,
                "boundary_rounds": BOUNDARY_ROUNDS},
        timings={},
        extra={"boundary": {
            "fixed2_poly1_max_residual": max(exact),
            "rr2_poly1_max_residual": max(drift),
            "rr2_poly1_warns": len(drift_warns),
            "fixed2_poly1_rate_breaks": len(exact_rate),
            "rr2_poly1_rate_breaks": len(drift_rate),
            "rr2_poly1_break_round": drift_rate[0]["round"],
            "rr2_poly1_break_rho_hat": drift_rate[0]["rho_hat"]}},
        out_dir=results_dir())
    if csv_rows is not None:
        csv_rows.append((
            "telemetry/boundary", 0.0,
            f"fixed2_poly1_max_res={max(exact):.3e}"
            f";rr2_poly1_max_res={max(drift):.3e}"
            f";warns={len(drift_warns)}"
            f";rate_breaks={len(drift_rate)}"))
    return {"exact": max(exact), "drift": max(drift),
            "rate_breaks": len(drift_rate)}


if __name__ == "__main__":
    rows = []
    run(csv_rows=rows)
    print("name,us_per_call,derived")
    for r in rows:
        print(",".join(map(str, r)))
