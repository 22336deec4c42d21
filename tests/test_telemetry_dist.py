"""Distributional telemetry + online rate estimation (PR 9).

The contracts: (1) sketches OFF stays the PR 8 BITWISE no-op even with
the sketch machinery present; (2) the population sketches read the full
``[N, ...]`` client store, so the cohort gather lowering and the dense
reference lowering produce IDENTICAL sketches; (3) the Pallas
``telemetry_reduce`` kernel matches its jnp oracle on arena-packed
stores including zero-pad rows and ragged client counts; (4) the rate
estimator recovers rho on synthetic geometric series and reproduces the
PR 3 staleness boundary (rr:2 + poly:1 rate break naming the axis,
fixed:2 + poly:1 silent) live from one run's drain and post hoc from its
JSONL alone; (5) the sinks handle vector-valued events explicitly.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FedScenario
from repro.core import (
    CsvSink,
    FedCET,
    MemorySink,
    RateMonitor,
    Telemetry,
    drain,
    fit_rate,
    max_weight_c,
    parse_sinks,
    parse_telemetry,
    rate_axis,
    replay_jsonl,
    resolve_monitors,
    split_metrics,
    with_delay,
    with_telemetry,
)
from repro.core.lr_search import lr_search
from repro.core.simulate import simulate_quadratic
from repro.core.telemetry import SKETCH_SOURCES, log_histogram
from repro.data.quadratic import make_quadratic_problem
from repro.kernels import ops
from repro.kernels import ref as R

jax.config.update("jax_enable_x64", True)

ROUNDS = 6
SKETCH_SPEC = Telemetry(sketches="auto", topk=3, leaf_stats=True)
COMPOSED = dict(compression="shift:q8", participation=0.8, delay="fixed:2",
                stale_policy="poly:1", cohort="block:4", arena=True)


def _problem(n_clients=8, dim=24, **kw):
    return make_quadratic_problem(0, n_clients=n_clients, dim=dim, **kw)


def _fedcet(problem, tau=2):
    alpha = lr_search(problem.mu, problem.L, tau)
    return FedCET(alpha=alpha, c=max_weight_c(problem.mu, alpha), tau=tau,
                  n_clients=problem.n_clients)


def _assert_bitwise_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape
        diff = np.abs(x.astype(np.float64) - y.astype(np.float64)).max() \
            if x.size else 0.0
        assert diff == 0.0, f"max abs diff {diff} != 0.0"


def _sketch_keys(series):
    return [k for k in series
            if any(k.startswith(s + "_") for s in SKETCH_SOURCES)]


# ------------------------------------------------------ bitwise no-op
def test_sketches_off_is_bitwise_noop():
    """With the sketch machinery present in the codebase, a telemetry-OFF
    run and a full-sketch run still agree at EXACTLY 0.0 state diff on
    the fully composed scenario — sketches only observe."""
    problem = _problem()
    off = FedScenario(telemetry=False, **COMPOSED).apply(_fedcet(problem))
    on = FedScenario(telemetry=SKETCH_SPEC, **COMPOSED).apply(_fedcet(problem))
    res_off = simulate_quadratic(off, problem, rounds=ROUNDS)
    res_on = simulate_quadratic(on, problem, rounds=ROUNDS)
    _assert_bitwise_equal(res_off.state, res_on.state)
    _assert_bitwise_equal(res_off.errors, res_on.errors)
    assert _sketch_keys(res_on.telemetry), "sketches did not materialize"


# ----------------------------------------------------- sketch content
def test_sketch_series_shapes_and_invariants():
    problem = _problem()
    algo = FedScenario(telemetry=SKETCH_SPEC, **COMPOSED).apply(
        _fedcet(problem))
    res = simulate_quadratic(algo, problem, rounds=ROUNDS)
    tel = res.telemetry
    n, cohort = problem.n_clients, 4
    for src, count in [("d_norm", n), ("drift", n), ("age", n),
                       ("compress_err", cohort)]:
        hist = np.asarray(tel[f"{src}_hist"])
        assert hist.shape == (ROUNDS, SKETCH_SPEC.hist_bins)
        # every client lands in exactly one bin (cohort-sized for the
        # wire-data sketch — compression error exists only for senders)
        assert (hist.sum(axis=1) == count).all(), (src, hist.sum(axis=1))
        p50 = np.asarray(tel[f"{src}_p50"])
        p90 = np.asarray(tel[f"{src}_p90"])
        p99 = np.asarray(tel[f"{src}_p99"])
        mx = np.asarray(tel[f"{src}_max"])
        assert (p50 <= p90 + 1e-12).all() and (p90 <= p99 + 1e-12).all()
        assert (p99 <= mx + 1e-12).all()
        tv = np.asarray(tel[f"{src}_top_vals"])
        ti = np.asarray(tel[f"{src}_top_ids"])
        assert tv.shape == (ROUNDS, SKETCH_SPEC.topk) == ti.shape
        assert tv[:, 0] == pytest.approx(np.asarray(mx), abs=1e-12)
        assert ti.min() >= 0 and ti.max() < n  # GLOBAL ids under cohorts
    # per-leaf breakdown rides as leaf_ vectors (1 leaf: the quadratic x)
    assert np.asarray(tel["leaf_msg_norm"]).shape == (ROUNDS, 1)
    assert np.asarray(tel["leaf_compress_err"]).shape == (ROUNDS, 1)


def test_histogram_matches_shared_binning_formula():
    spec = Telemetry(sketches="auto")
    vals = jnp.asarray([0.0, 1e-13, 3e-7, 0.5, 2.0, 9e3, 1e9])
    hist = np.asarray(log_histogram(vals, spec.hist_bins, spec.hist_lo,
                                    spec.hist_hi))
    assert hist.sum() == vals.shape[0]
    # zeros pin to bin 0; overflow clips into the top bin
    assert hist[0] >= 1 and hist[-1] >= 1


# ------------------------------------------- cohort vs dense lowering
def test_cohort_and_dense_lowerings_sketch_identically():
    """Sketches read the post-round store, which both cohort lowerings
    produce bitwise-equal — so every sketch series must agree exactly
    (integer histograms / ids) or <=1e-12 (float quantiles)."""
    problem = _problem()
    res_g = simulate_quadratic(
        FedScenario(telemetry=SKETCH_SPEC, **COMPOSED).apply(
            _fedcet(problem)), problem, rounds=ROUNDS)
    res_d = simulate_quadratic(
        FedScenario(telemetry=SKETCH_SPEC,
                    **{**COMPOSED, "cohort": "block:4:dense"}).apply(
            _fedcet(problem)), problem, rounds=ROUNDS)
    keys = _sketch_keys(res_g.telemetry)
    assert keys and set(keys) == set(_sketch_keys(res_d.telemetry))
    for k in keys:
        a = np.asarray(res_g.telemetry[k])
        b = np.asarray(res_d.telemetry[k])
        if a.dtype.kind in "iu":
            assert (a == b).all(), k
        else:
            assert np.abs(a - b).max() <= 1e-12, (
                k, np.abs(a - b).max())


# --------------------------------------------------- kernel vs oracle
# (clients, rows): the f64 store's row block (kernels/telemetry_reduce.py:
# row_block) is 256 rows at 2 clients, 128 at 4 and 64 at 8 or more
@pytest.mark.parametrize("n_clients,rows", [
    # every row in one block; 13: a ragged client block
    pytest.param(8, 3, id="8"), pytest.param(13, 3, id="13"),
    (2, 512), (4, 256),     # the cells' client counts, whole row blocks
    (4, 300),               # a ragged last row block
    (13, 70),               # two client blocks and a ragged last row block
])
def test_telemetry_reduce_kernel_matches_ref(n_clients, rows):
    """Pallas kernel (interpret mode) vs the jnp oracle on an arena-style
    ``[N, rows, 1024]`` store with zero-pad tail entries, over client and
    row counts that do and do not divide the kernel's blocks."""
    rng = np.random.default_rng(0)
    lanes = 1024
    # norms span 1e-4 .. 5e3 at every row count, the range the absolute
    # tolerances below were set for (1 ulp of the largest is 9e-13)
    data = rng.normal(size=(n_clients, rows, lanes)) \
        * np.logspace(-6, 2, n_clients)[:, None, None] * np.sqrt(3 / rows)
    data[:, -1, 512:] = 0.0  # arena zero padding
    data = jnp.asarray(data)
    kw = dict(bins=48, lo=-12.0, hi=4.0, k=4)
    nk, hk, tvk, tik = ops.telemetry_sketch(data, impl="kernel", **kw)
    nr, hr, tvr, tir = ops.telemetry_sketch(data, impl="ref", **kw)
    assert float(jnp.max(jnp.abs(nk - nr))) <= 1e-12
    assert bool(jnp.all(hk == hr)) and int(hk.sum()) == n_clients
    assert bool(jnp.all(tik == tir))
    assert float(jnp.max(jnp.abs(tvk - tvr))) <= 1e-12


def test_telemetry_reduce_ref_oracle_is_exact():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(6, 40)))
    sq, hist = R.client_sketch(x, bins=32, lo=-12.0, hi=4.0)
    np.testing.assert_allclose(np.asarray(sq),
                               np.asarray(jnp.sum(x * x, axis=1)),
                               rtol=0, atol=0)
    expect = np.asarray(log_histogram(jnp.sqrt(jnp.sum(x * x, axis=1)),
                                      32, -12.0, 4.0))
    assert (np.asarray(hist) == expect).all()


# ----------------------------------------------------- rate estimator
def test_fit_rate_recovers_rho_on_geometric_series():
    for rho in (0.5, 0.9, 0.99):
        r = np.arange(40)
        v = 3.7 * rho ** r
        assert fit_rate(r, v) == pytest.approx(rho, rel=1e-9)


def test_rate_monitor_fires_on_synthetic_stall():
    """A geometric decay that flatlines: the windowed rho_hat crosses 1
    after linear convergence was established -> exactly one rate-break
    WARN (cooldown suppresses repeats within its horizon)."""
    m = RateMonitor(axis="synthetic-axis")
    vals = [0.8 ** r for r in range(30)] + [0.8 ** 30] * 25
    events = drain({"err": np.asarray(vals)}, monitors=(m,))
    warns = [e for e in events if e.get("kind") == "rate_break"]
    assert warns and warns[0]["axis"] == "synthetic-axis"
    assert warns[0]["rho_hat"] >= m.stall_rho
    assert warns[0]["round"] >= 30
    # rho_hat rides the round events from the moment the window fills
    annotated = [e for e in events
                 if e["event"] == "round" and "rho_hat" in e]
    assert len(annotated) >= len(vals) - m.window
    assert annotated[0]["rho_hat"] == pytest.approx(0.8, rel=1e-6)


def test_rate_monitor_silent_on_clean_contraction():
    m = RateMonitor()
    vals = [0.9 ** r for r in range(60)]
    events = drain({"err": np.asarray(vals)}, monitors=(m,))
    assert not [e for e in events if e.get("kind") == "rate_break"]


def _boundary_run(delay_spec, path):
    problem = _problem()
    algo = with_telemetry(
        with_delay(_fedcet(problem), delay_spec, policy="poly:1"), True)
    monitors = (RateMonitor(axis=rate_axis(algo)),)
    res = simulate_quadratic(algo, problem, rounds=48)
    sinks = parse_sinks(f"jsonl:{path}")
    events = drain({**res.telemetry, "err": np.asarray(res.errors)[1:]},
                   sinks=sinks, monitors=monitors, algo=algo,
                   n_params=problem.dim)
    for s in sinks:
        s.close()
    return [e for e in events if e.get("kind") == "rate_break"]


def test_rate_monitor_reproduces_staleness_boundary(tmp_path):
    """The PR 3 boundary as a LIVE rate-break detection: rr:2 + poly:1
    floors FedCET (non-uniform ages break Lemma 2) -> rate break naming
    stale_policy; fixed:2 + poly:1 stays exact -> silent. And the same
    detection replays from the finished JSONL alone."""
    silent = _boundary_run("fixed:2", str(tmp_path / "fixed2.jsonl"))
    assert not silent, silent[:1]
    breaks = _boundary_run("rr:2", str(tmp_path / "rr2.jsonl"))
    assert breaks, "no rate break on rr:2 + poly:1"
    assert "stale_policy" in breaks[0]["axis"]
    assert breaks[0]["rho_hat"] >= 0.99
    # post hoc, from the file alone — no re-simulation
    replayed = [w for w in replay_jsonl(str(tmp_path / "rr2.jsonl"),
                                        (RateMonitor(),))
                if w.get("kind") == "rate_break"]
    assert replayed and replayed[0]["round"] == breaks[0]["round"]
    again = [w for w in replay_jsonl(str(tmp_path / "fixed2.jsonl"),
                                     (RateMonitor(),))
             if w.get("kind") == "rate_break"]
    assert not again


def test_rate_axis_names_lossy_axes():
    problem = _problem()
    base = _fedcet(problem)
    assert "no lossy axis" in rate_axis(base)
    assert "stale_policy" in rate_axis(
        with_delay(base, "rr:2", policy="poly:1"))


def test_resolve_monitors_adds_rate_monitor_with_algo():
    problem = _problem()
    algo = with_telemetry(_fedcet(problem), True)
    plain = resolve_monitors(algo.telemetry)
    withalgo = resolve_monitors(algo.telemetry, algo)
    assert not any(isinstance(m, RateMonitor) for m in plain)
    rms = [m for m in withalgo if isinstance(m, RateMonitor)]
    assert len(rms) == 1


# ------------------------------------------------------------- sinks
def test_csv_sink_flattens_vector_metrics(tmp_path):
    path = str(tmp_path / "m.csv")
    sink = CsvSink(path)
    sink.emit({"event": "round", "round": 0, "loss": 1.5,
               "d_norm_hist": [1, 2, 3], "d_norm_p50": 0.5})
    sink.emit({"event": "round", "round": 1, "loss": 1.2,
               "d_norm_hist": [0, 4, 2], "d_norm_p50": 0.4})
    sink.close()
    lines = open(path).read().strip().split("\n")
    header = lines[0].split(",")
    assert "d_norm_hist.0" in header and "d_norm_hist.2" in header
    assert "d_norm_p50" in header
    row = dict(zip(header, lines[2].split(",")))
    assert row["d_norm_hist.1"] == "4"


def test_csv_sink_rejects_nested_vectors():
    sink = CsvSink("/dev/null")
    with pytest.raises(ValueError, match="jsonl"):
        sink.emit({"event": "round", "round": 0, "bad": [[1, 2], [3, 4]]})
    sink.close()


def test_jsonl_round_events_carry_vectors(tmp_path):
    path = str(tmp_path / "r.jsonl")
    sinks = parse_sinks(f"jsonl:{path}")
    drain({"loss": np.asarray([1.0, 0.5]),
           "d_norm_hist": np.asarray([[1, 2], [3, 4]], np.int32)},
          sinks=sinks)
    for s in sinks:
        s.close()
    evs = [json.loads(line) for line in open(path)]
    assert evs[0]["d_norm_hist"] == [1, 2]
    assert evs[1]["d_norm_hist"] == [3, 4]


def test_drain_splits_leaf_series_into_leaf_stats_events():
    sink = MemorySink()
    drain({"loss": np.asarray([1.0, 0.5]),
           "leaf_msg_norm": np.asarray([[1.0, 2.0], [3.0, 4.0]]),
           "leaf_compress_err": np.asarray([[0.1, 0.2], [0.3, 0.4]])},
          sinks=[sink], leaf_names=["embed", "head"])
    rounds = [e for e in sink.events if e["event"] == "round"]
    leaves = [e for e in sink.events if e["event"] == "leaf_stats"]
    assert len(rounds) == len(leaves) == 2
    assert "leaf_msg_norm" not in rounds[0]
    assert leaves[0]["names"] == ["embed", "head"]  # first event only
    assert "names" not in leaves[1]
    assert leaves[1]["msg_norm"] == [3.0, 4.0]
    assert leaves[0]["compress_err"] == [0.1, 0.2]


# ----------------------------------------------------------- parsing
def test_parse_telemetry_sketch_grammar():
    spec = parse_telemetry("jsonl:r.jsonl,hist:32:-10:2,topk:6,leafstats")
    assert spec.sketches == "auto" and spec.hist_bins == 32
    assert spec.hist_lo == -10.0 and spec.hist_hi == 2.0
    assert spec.topk == 6 and spec.leaf_stats
    bare = parse_telemetry("jsonl:r.jsonl")
    assert bare.sketches is False and not bare.leaf_stats
    assert parse_telemetry("hist").sketches == "auto"


def test_parse_sinks_skips_spec_parts(tmp_path):
    sinks = parse_sinks(f"jsonl:{tmp_path}/a.jsonl,hist:48,topk:4,leafstats")
    assert len(sinks) == 1
    for s in sinks:
        s.close()
    with pytest.raises(ValueError, match="unknown telemetry sink"):
        parse_sinks("histogram:48")


def test_wants_sketch_selection():
    assert Telemetry(sketches="auto").wants_sketch("d_norm")
    assert not Telemetry(sketches=False).wants_sketch("d_norm")
    only = Telemetry(sketches=("drift",))
    assert only.wants_sketch("drift") and not only.wants_sketch("d_norm")


def test_metrics_filter_applies_to_sketches():
    problem = _problem()
    spec = Telemetry(sketches="auto", metrics=("d_norm_hist", "d_norm_p99"))
    algo = FedScenario(telemetry=spec, **COMPOSED).apply(_fedcet(problem))
    res = simulate_quadratic(algo, problem, rounds=2)
    assert set(res.telemetry) == {"d_norm_hist", "d_norm_p99"}
