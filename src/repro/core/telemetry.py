"""In-trace telemetry: per-round metrics, invariant monitors, event sinks.

The engine's round body runs inside one jitted ``lax.scan`` over K rounds —
a host callback per round would serialize the scan, and re-running the
round outside jit to measure it would double the work. This module instead
captures scalars *while the round is being traced*:

* :func:`capture` writes a named scalar onto the active **tape** — a
  trace-time collector the round runner opens around ``algo.round`` via
  :func:`collect`. Outside a tape (direct ``algo.round`` calls, ``init``,
  ``eval_shape``) and inside :func:`muted` regions (the engine mutes the
  tau-1 local ``lax.scan`` — a capture there would leak inner-scan tracers
  into the round-level tape) it is a no-op, so instrumented code needs no
  caller-side discipline.
* :meth:`Telemetry.finalize` turns tape + post-round state into the round's
  metric dict — tape scalars plus state-derived series: FedCET's
  ``sum_i d_i = 0`` invariant residual (Lemma 2 — the quantity PR 3/PR 5
  measured drifting under poly staleness / tier recompression, now live)
  and the consensus error ``max_i ||x_i - x_bar||`` (the gossip-descent
  quantity). The dict becomes the scan's stacked ys: metrics stay
  on-device for the whole segment, ZERO host syncs inside the scan.
* :func:`drain` device-gets the stacked series ONCE per segment and feeds
  per-round events (plus :class:`Monitor` WARN events and static per-round
  bit accounting from :func:`repro.core.comm.comm_bits_per_round`) into
  pluggable sinks: :class:`JsonlSink` (one JSON object per line, manifest
  first), :class:`CsvSink`, :class:`StdoutSink`, :class:`MemorySink`.

Beyond scalars, the spec can request **distribution sketches**
(``Telemetry(sketches="auto")`` / the ``--telemetry hist:...`` grammar):
fixed-bin log-histograms, p50/p90/p99/max quantiles and top-k
outlier-client ids of the per-client ``||d_i||``, the drift
``||x_i - x_bar||``, the per-client compression error and the staleness
ages — vector-valued captures that ride the scan ys next to the scalars.
Sketches are computed in :meth:`Telemetry.finalize` from the post-round
state, so under cohort mode they read the FULL ``[N, ...]`` client store
in one O(N) pass (the scalars above see only the cohort) and are
identical between the gather and dense cohort lowerings. On a packed
parameter arena the norm+histogram reduction routes through the fused
Pallas kernel (``kernels/telemetry_reduce.py`` via
``kernels/ops.py:telemetry_sketch``). ``leaf_stats=True`` adds the
per-leaf msg-norm / compression-error breakdown (the bit-budget
allocator's future input) via the arena's row->leaf segment map,
drained as ``leaf_stats`` events.

At drain time :class:`RateMonitor` fits the **online linear-rate
estimator** rho_hat — a windowed least-squares slope of ``log(residual)``
vs round — annotating round events and emitting a ``rate_break`` WARN
(naming the scenario axis) when a series that was contracting stalls
above the numerical floor: the PR 3 (rr:2 + poly:1) and PR 5 (tier
shift:q8) error floors become live detections from one run's JSONL
alone (:func:`replay_jsonl`).

Telemetry disabled (``algo.telemetry is None``) must be a BITWISE no-op:
the engine guards every capture on the attached spec, so the disabled
round traces the exact same jaxpr as before this module existed —
tests/test_telemetry.py pins 0.0 divergence across the composed-scenario
matrix.

Profiling hooks live here too: :class:`TraceSession` brackets a
``--trace-rounds a:b`` window with ``jax.profiler`` trace capture;
:func:`scope` names the round program's layers (:data:`SCOPES`) in the
compiled program's ``op_name`` metadata, so a trace's device ops can be
summed per layer; and :func:`drain` marks its host phases with
``drain.*`` profiler spans.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import math
import os
import subprocess
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

# ------------------------------------------------------------------ the tape
#: stack of active trace-time collectors (nested collect()s shadow like
#: dynamic scope) and a mute depth counter. Trace-time only — never part of
#: traced state, so it adds no jaxpr inputs and costs nothing when empty.
_TAPES: list[dict] = []
_MUTE: int = 0
#: open count regions (:func:`counting`), innermost last
_COUNTS: list[dict] = []


def collecting() -> bool:
    """True when a tape is active and not muted — the engine's guard for
    building capture ops at all (disabled telemetry traces zero extra ops)."""
    return bool(_TAPES) and _MUTE == 0


def capture(name: str, value) -> None:
    """Record a named scalar on the active tape (no-op without one).
    Repeated captures of the same name within a round keep the LAST value
    (e.g. ``grad_norm`` at the aggregating step, not a begin_round probe)."""
    if collecting():
        _TAPES[-1][name] = value


@contextlib.contextmanager
def collect():
    """Open a tape around a traced region; yields the dict of captured
    tracers (valid within the same trace — the caller folds them into its
    outputs before the trace ends)."""
    tape: dict = {}
    _TAPES.append(tape)
    try:
        yield tape
    finally:
        _TAPES.pop()


def count(values: dict) -> None:
    """Add per-call counters ``{name: array}`` to the innermost open
    :func:`counting` region, else to the round's tape (summing with what
    is there), else nowhere."""
    if _COUNTS:
        target = _COUNTS[-1]
    elif collecting():
        target = _TAPES[-1]
    else:
        return
    for name, v in values.items():
        target[name] = target[name] + v if name in target else v


@contextlib.contextmanager
def counting():
    """Open a count region around one iteration of a muted ``lax.scan``
    body; yields the dict of its summed counters, which the body returns
    as the scan's outputs."""
    counts: dict = {}
    _COUNTS.append(counts)
    try:
        yield counts
    finally:
        _COUNTS.pop()


@contextlib.contextmanager
def muted():
    """Suppress captures while tracing an inner ``lax.scan`` body (whose
    tracers must not escape onto the round-level tape)."""
    global _MUTE
    _MUTE += 1
    try:
        yield
    finally:
        _MUTE -= 1


# ----------------------------------------------------------- metric helpers
def client_sq_norms(tree):
    """``[clients]`` squared L2 norms: per-client sum of squares over every
    leaf's non-leading axes (leaves carry a leading clients axis; an Arena
    leaf's zero pads contribute nothing, so packed == per-leaf)."""
    tot = None
    for a in jax.tree.leaves(tree):
        s = jnp.sum(jnp.square(a), axis=tuple(range(1, a.ndim)))
        tot = s if tot is None else tot + s
    return tot


def mean_client_norm(tree):
    """Mean over clients of the per-client L2 norm."""
    return jnp.mean(jnp.sqrt(client_sq_norms(tree)))


def _tree_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(a)) for a in jax.tree.leaves(tree)))


# ------------------------------------------------------ distribution sketches
#: the state-derived per-client distributions ``sketches="auto"`` tracks
#: (each is silently absent when its source state is — e.g. no ``age_*``
#: without a delay model, no ``compress_err_*`` without transforms).
SKETCH_SOURCES = ("d_norm", "drift", "compress_err", "age")


def log_histogram(vals, bins: int, lo: float, hi: float):
    """``[bins]`` int32 counts of ``vals`` (non-negative) over log10-spaced
    bins covering ``[10^lo, 10^hi)``; zeros and underflow clip into bin 0,
    overflow into the last bin. The binning expression is shared verbatim
    with ``kernels/ref.py:client_sketch`` and the Pallas
    ``telemetry_reduce`` kernel (their parity contract)."""
    logs = jnp.where(vals > 0, jnp.log10(vals), lo)
    idx = jnp.clip(jnp.floor((logs - lo) * (bins / (hi - lo))),
                   0, bins - 1).astype(jnp.int32)
    return jnp.zeros((bins,), jnp.int32).at[idx].add(1)


def _finish_sketch(name, vals, hist, spec, ids=None,
                   top=None) -> dict:
    """Quantiles + top-k around a per-client value vector whose histogram
    is already computed; ``ids`` maps local (cohort-slot) indices back to
    global client ids, ``top`` passes kernel-computed top-k through."""
    q = jnp.quantile(vals, jnp.asarray([0.5, 0.9, 0.99], vals.dtype))
    if top is None:
        top = jax.lax.top_k(vals, min(spec.topk, vals.shape[0]))
    tv, ti = top
    ti = ti.astype(jnp.int32)
    if ids is not None:
        ti = ids[ti]
    return {f"{name}_hist": hist,
            f"{name}_p50": q[0], f"{name}_p90": q[1], f"{name}_p99": q[2],
            f"{name}_max": jnp.max(vals),
            f"{name}_top_vals": tv, f"{name}_top_ids": ti}


def sketch_values(name, vals, spec, ids=None) -> dict:
    """Distribution sketch of a per-client ``[n]`` value vector: log-bin
    histogram, p50/p90/p99/max and the top-k outlier (value, client-id)
    pairs — all still traced (they ride the scan ys)."""
    if not jnp.issubdtype(vals.dtype, jnp.floating):
        vals = vals.astype(jnp.float32)
    hist = log_histogram(vals, spec.hist_bins, spec.hist_lo, spec.hist_hi)
    return _finish_sketch(name, vals, hist, spec, ids=ids)


def sketch_client_norms(name, tree, spec, ids=None) -> dict:
    """Sketch the per-client L2 norms of a ``[clients, ...]`` state tree.
    A packed-arena tree takes the fused one-pass Pallas norm+histogram
    reduction (``kernels/ops.py:telemetry_sketch`` — the Mosaic kernel on
    TPU, the same-math XLA expression elsewhere); any other pytree takes
    the generic ``client_sq_norms`` path. Both bin identically."""
    from repro.core.arena import Arena

    if isinstance(tree, Arena) and tree.data.ndim == 3:
        from repro.kernels import ops

        norms, hist, tv, ti = ops.telemetry_sketch(
            tree.data, bins=spec.hist_bins, lo=spec.hist_lo,
            hi=spec.hist_hi, k=min(spec.topk, tree.data.shape[0]))
        return _finish_sketch(name, norms, hist, spec, ids=ids,
                              top=(tv, ti))
    return sketch_values(name, jnp.sqrt(client_sq_norms(tree)), spec,
                         ids=ids)


def leaf_client_norms(tree):
    """``[n_leaves]`` mean-client L2 norm per MODEL leaf — the per-leaf
    breakdown of ``msg_norm`` / ``compress_err`` (``leaf_stats`` events;
    the input a per-leaf bit-budget allocator would consume). On an arena
    the reduction runs over the packed buffer through the static
    row->leaf segment map; on a plain pytree it is the per-leaf norm
    stack. Arena zero pads contribute nothing, so packed ~= per-leaf."""
    from repro.core.arena import Arena

    if isinstance(tree, Arena):
        seg = jnp.asarray(tree.layout.row_segments())
        n_leaves = len(tree.layout.shapes)
        row_sq = jnp.sum(jnp.square(tree.data), axis=-1)
        if row_sq.ndim == 1:
            row_sq = row_sq[None, :]
        per = jax.ops.segment_sum(row_sq.T, seg,
                                  num_segments=n_leaves)  # [leaves, clients]
        return jnp.mean(jnp.sqrt(per), axis=1)
    return jnp.stack([
        jnp.mean(jnp.sqrt(jnp.sum(jnp.square(a),
                                  axis=tuple(range(1, a.ndim)))))
        for a in jax.tree.leaves(tree)])


# ------------------------------------------------------------------ monitors
@dataclasses.dataclass(frozen=True)
class Monitor:
    """Declarative per-round alert: WARN when ``metric`` crosses ``bound``
    (``mode="max"``: value > bound; ``"min"``: value < bound). ``axis``
    names the scenario axis the violation implicates — the WARN event
    carries it so a drifting invariant points at its cause."""

    metric: str
    bound: float
    mode: str = "max"
    axis: str = ""

    def violated(self, value) -> bool:
        v = float(value)
        return v > self.bound if self.mode == "max" else v < self.bound


#: the PR 3 pinned boundary as a live check: FedCET's redistributive drift
#: updates keep sum_i d_i = 0 exactly (Lemma 2) under every exact scenario
#: (fixed:k delay included — uniform ages make poly discounting uniform);
#: non-uniform stale-policy weights (poly:a with rr/geom ages) and tier
#: recompression break the redistribution. The residual is RELATIVE
#: (||mean_i d_i|| / mean_i ||d_i||): exact scenarios sit at accumulation
#: noise (~1e-13 in f64), the pinned drift scenarios reach O(1e-2..1).
INVARIANT_MONITOR = Monitor(
    metric="invariant_residual", bound=1e-6, mode="max",
    axis="stale_policy (poly:a discounting with non-uniform ages) or "
         "tier_compression — non-uniform aggregation weights break the "
         "sum_i d_i = 0 redistribution (Lemma 2)")


# ------------------------------------------------------ linear-rate estimator
def fit_rate(rounds, values) -> float:
    """Windowed log-residual regression: the least-squares slope of
    ``ln(value)`` against round index, returned as the per-round
    contraction factor ``rho_hat = exp(slope)`` — the paper's linear rate
    as a measured number (``rho_hat < 1``: still converging linearly;
    ``>= 1``: stalled or diverging)."""
    r = np.asarray(rounds, dtype=float)
    v = np.log(np.asarray(values, dtype=float))
    r = r - r.mean()
    denom = float(np.sum(r * r)) or 1.0
    return float(math.exp(float(np.sum(r * (v - v.mean()))) / denom))


def rate_axis(algo) -> str:
    """The scenario axes attached to ``algo`` that can break the paper's
    linear rate — what a :class:`RateMonitor` WARN names as the suspects
    (mirroring the measured boundaries: PR 3 stale-policy discounting,
    PR 5 tier recompression, biased compression)."""
    parts = []
    delay = getattr(algo, "delay", None)
    if delay is not None:
        parts.append("stale_policy (poly:a discounting under non-uniform "
                     "delay ages floors FedCET — the PR 3 boundary)")
    topo = getattr(algo, "topology", None)
    if topo is not None and getattr(topo, "tier_compression", None) is not None:
        parts.append("tier_compression (interior-hop recompression lacks "
                     "wire-consistency — the PR 5 freeze)")
    if getattr(algo, "transforms", ()):
        parts.append("compression (a biased compressor without error "
                     "feedback keeps an error floor)")
    return " or ".join(parts) or "no lossy axis attached"


@dataclasses.dataclass
class RateMonitor:
    """Online linear-rate estimator + rate-break alert, evaluated at drain
    time over the streamed round events (stateful across a run's drain
    segments — :func:`resolve_monitors` builds a fresh one per run).

    Each round it appends ``(round, metric)`` and fits
    :func:`fit_rate` over the trailing ``window`` points, annotating the
    round event with ``rho_hat``. A **rate break** fires when a series
    that had established linear convergence (best windowed estimate
    ``<= ref_rho``) stalls (``rho_hat >= stall_rho``) while still far
    above the numerical floor (``value > floor`` — so the healthy f64
    noise plateau of an exact run never alerts). The WARN event carries
    ``kind="rate_break"`` and ``axis`` — the scenario axes under
    suspicion (:func:`rate_axis`).

    ``metric`` defaults to ``"err"`` — a residual-type series the caller
    merges into the drained round events (``simulate_quadratic``'s
    distance-to-optimum; anything that decays to ZERO under exact
    scenarios). Non-residual series (e.g. an LM loss with a nonzero
    irreducible floor) would false-alarm at convergence; rounds without
    the metric are simply skipped, so attaching the monitor to a run
    that never emits it is harmless."""

    metric: str = "err"
    window: int = 12
    stall_rho: float = 0.99
    ref_rho: float = 0.97
    floor: float = 1e-10
    cooldown: int = 10
    axis: str = ""

    def __post_init__(self):
        self._rounds: list[int] = []
        self._values: list[float] = []
        self._best: float | None = None
        self._last_warn: int | None = None

    def observe(self, ev: dict) -> dict | None:
        """Feed one round event (annotates it with ``rho_hat`` in place);
        returns the rate-break WARN event when one fires, else None."""
        v = ev.get(self.metric)
        if not isinstance(v, (int, float)) or isinstance(v, bool) or v <= 0:
            return None
        r = int(ev.get("round", len(self._rounds)))
        self._rounds.append(r)
        self._values.append(float(v))
        if len(self._rounds) < self.window:
            return None
        rho = fit_rate(self._rounds[-self.window:],
                       self._values[-self.window:])
        ev["rho_hat"] = rho
        self._best = rho if self._best is None else min(self._best, rho)
        if (rho >= self.stall_rho and self._best <= self.ref_rho
                and v > self.floor
                and (self._last_warn is None
                     or r - self._last_warn >= self.cooldown)):
            self._last_warn = r
            return {"event": "monitor", "kind": "rate_break",
                    "level": "WARN", "metric": self.metric, "round": r,
                    "value": float(v), "rho_hat": rho,
                    "rho_ref": self._best, "axis": self.axis}
        return None


def replay_jsonl(path: str, monitors) -> list[dict]:
    """Re-run a monitor set over a finished run's JSONL file ALONE — no
    re-simulation: stream its round events through threshold
    :class:`Monitor` checks and :class:`RateMonitor` observers exactly as
    a live drain would, returning the WARN events. This is how the
    pinned scenario boundaries are reproduced post hoc from one run's
    log (benchmarks/telemetry_bench.py, benchmarks/report.py)."""
    warns: list[dict] = []
    with open(path) as f:
        for line in f:
            ev = json.loads(line)
            if ev.get("event") != "round":
                continue
            for m in monitors:
                if hasattr(m, "observe"):
                    w = m.observe(ev)
                    if w:
                        warns.append(w)
                    continue
                v = ev.get(m.metric)
                if (isinstance(v, (int, float))
                        and not isinstance(v, bool) and m.violated(v)):
                    warns.append({"event": "monitor", "level": "WARN",
                                  "metric": m.metric, "round": ev["round"],
                                  "value": v, "bound": m.bound,
                                  "mode": m.mode, "axis": m.axis})
    return warns


# ------------------------------------------------------------- the spec
@dataclasses.dataclass(frozen=True)
class Telemetry:
    """The telemetry spec attached to an engine algorithm
    (``with_telemetry`` / ``FedScenario(telemetry=...)``). Hashable and
    stateless — it adds NO algorithm state (checkpoints are unaffected)
    and selects which metrics the runner stacks and which monitors the
    drain evaluates.

    ``metrics="auto"`` keeps everything captured plus the state-derived
    series; a tuple restricts to those names (unavailable names are
    silently absent — e.g. no ``age_*`` without a delay model).
    ``monitors="auto"`` evaluates :data:`INVARIANT_MONITOR` on algorithms
    that expose the drift state (plus a :class:`RateMonitor` when
    :func:`resolve_monitors` is given the algorithm); a tuple of
    :class:`Monitor` overrides.

    ``sketches`` turns on the population-scale distribution sketches:
    ``False`` (default — scalar telemetry only, the pre-sketch stream),
    ``"auto"`` / ``True`` (every source in :data:`SKETCH_SOURCES` whose
    state exists) or a tuple of source names. Each source ``s`` adds
    ``s_hist`` (``[hist_bins]`` int32 log-histogram over
    ``[10^hist_lo, 10^hist_hi)``), ``s_p50``/``s_p90``/``s_p99``/
    ``s_max`` and the ``[topk]`` outlier pairs ``s_top_vals`` /
    ``s_top_ids`` (GLOBAL client ids, also under cohort mode).
    ``leaf_stats=True`` adds the per-leaf ``leaf_msg_norm`` /
    ``leaf_compress_err`` vectors (drained as ``leaf_stats`` events)."""

    metrics: tuple | str = "auto"
    monitors: tuple | str = "auto"
    sketches: tuple | str | bool = False
    hist_bins: int = 48
    hist_lo: float = -12.0
    hist_hi: float = 4.0
    topk: int = 4
    leaf_stats: bool = False

    def wants_sketch(self, name: str) -> bool:
        """Whether the spec sketches source ``name`` — the engine's guard
        for building the per-client capture ops at all."""
        if not self.sketches:
            return False
        if self.sketches is True or self.sketches == "auto":
            return True
        return name in self.sketches

    def finalize(self, tape: dict, algo, state) -> dict:
        """Tape + post-round state -> the round's metric dict (still
        traced values; becomes the scan's stacked ys). Sketches read the
        post-round state, which is the FULL ``[N, ...]`` client store in
        both cohort lowerings — the one O(N) pass per round."""
        out = dict(tape)
        # raw per-client seam captures feed sketches only — never emitted.
        cohort_ids = out.pop("cohort_ids", None)
        err_clients = out.pop("compress_err_clients", None)
        slots = out.pop("moe_expert_slots", None)
        if slots is not None:
            # routed slots computed by the held experts, over clients and
            # steps; the most-loaded held expert's over the held mean
            out["moe_held_slots"] = jnp.sum(slots)
            mean = jnp.mean(slots.astype(jnp.float32))
            out["moe_load_max"] = jnp.max(slots).astype(jnp.float32) / (
                jnp.maximum(mean, 1.0))
        inner = algo._inner(state)
        d = getattr(inner, "d", None)
        if d is not None:
            num = _tree_norm(jax.tree.map(lambda a: jnp.mean(a, axis=0), d))
            den = mean_client_norm(d)
            out["invariant_residual"] = num / jnp.maximum(
                den, jnp.asarray(1e-30, den.dtype))
        x = getattr(inner, "x", None)
        if x is None:
            x = getattr(inner, "x_curr", None)
        dev = None
        if x is not None:
            dev = jax.tree.map(
                lambda a: a - jnp.mean(a, axis=0, keepdims=True), x)
            out["consensus_err"] = jnp.sqrt(jnp.max(client_sq_norms(dev)))
        if self.sketches:
            if d is not None and self.wants_sketch("d_norm"):
                out.update(sketch_client_norms("d_norm", d, self))
            if dev is not None and self.wants_sketch("drift"):
                out.update(sketch_client_norms("drift", dev, self))
            if err_clients is not None and self.wants_sketch("compress_err"):
                out.update(sketch_values("compress_err", err_clients, self,
                                         ids=cohort_ids))
            if self.wants_sketch("age"):
                split = getattr(algo, "_split", None)
                dstate = split(state)[3] if split is not None else None
                if dstate is not None:
                    out.update(sketch_values(
                        "age", dstate.age.astype(jnp.float32), self))
        if self.metrics != "auto":
            out = {k: out[k] for k in self.metrics if k in out}
        return out


#: spec-string parts that configure the SPEC rather than name a sink —
#: ``parse_telemetry`` consumes them, ``parse_sinks`` skips them, so one
#: ``--telemetry`` string drives both (``"jsonl:run.jsonl,hist:48"``).
_SPEC_PART_KINDS = ("hist", "topk", "leafstats", "leaf_stats")


def _spec_overrides(spec: str) -> dict:
    """Telemetry-field overrides encoded in a sink-spec string:
    ``hist[:bins[:lo:hi]]`` (log10 bin range) and ``topk[:k]`` turn the
    distribution sketches on, ``leafstats`` the per-leaf breakdown."""
    ov: dict = {}
    for part in spec.split(","):
        kind, _, arg = part.strip().partition(":")
        kind = kind.lower()
        if kind == "hist":
            ov["sketches"] = "auto"
            sub = [s for s in arg.split(":") if s]
            if sub:
                ov["hist_bins"] = int(sub[0])
            if len(sub) >= 3:
                ov["hist_lo"], ov["hist_hi"] = float(sub[1]), float(sub[2])
        elif kind == "topk":
            ov["sketches"] = "auto"
            if arg:
                ov["topk"] = int(arg)
        elif kind in ("leafstats", "leaf_stats"):
            ov["leaf_stats"] = True
    return ov


def parse_telemetry(spec) -> Telemetry | None:
    """Normalize a telemetry knob: ``None`` / ``False`` / ``"none"`` /
    ``"off"`` / ``""`` -> None (disabled — the factory returns the
    algorithm unchanged); a :class:`Telemetry` passes through; any other
    truthy value (``True``, a sink spec string) -> the default spec, with
    ``hist``/``topk``/``leafstats`` parts of a spec string turning the
    distribution sketches on (see :func:`_spec_overrides`)."""
    if spec is None or spec is False:
        return None
    if isinstance(spec, Telemetry):
        return spec
    if isinstance(spec, str):
        if spec.strip().lower() in ("", "none", "off", "0", "false"):
            return None
        return Telemetry(**_spec_overrides(spec))
    return Telemetry()


def resolve_monitors(telemetry: Telemetry | None, algo=None) -> tuple:
    """The drain-time monitor set for a spec: explicit tuples pass
    through; ``"auto"`` is the invariant monitor plus — when the
    algorithm is given, so the WARN can name its attached lossy axes —
    a fresh (stateful) :class:`RateMonitor` on the residual series."""
    if telemetry is None:
        return ()
    if telemetry.monitors == "auto":
        if algo is None:
            return (INVARIANT_MONITOR,)
        return (INVARIANT_MONITOR, RateMonitor(axis=rate_axis(algo)))
    return tuple(telemetry.monitors)


def split_metrics(algo, ys):
    """Split a round runner's stacked ys into ``(metrics, telemetry)`` —
    the runner nests them only when the algorithm has telemetry attached,
    so un-instrumented callers see the exact pre-telemetry structure."""
    if getattr(algo, "telemetry", None) is None or ys is None:
        return ys, None
    return ys["metric"], ys["telemetry"]


# --------------------------------------------------------------------- sinks
def _scalar(v):
    a = np.asarray(v)
    if a.dtype.kind == "b":
        return bool(a)
    if a.dtype.kind in "iu":
        return int(a)
    return float(a)


def _jsonable(v):
    """Host value -> JSON-serializable event value: native scalar, or a
    list for the 1-D sketch vectors (histogram bins, top-k ids)."""
    a = np.asarray(v)
    if a.ndim == 0:
        return _scalar(a)
    if a.ndim == 1:
        return [_scalar(x) for x in a]
    raise ValueError("telemetry events carry scalars or 1-D vectors, got "
                     f"shape {a.shape}")


class MemorySink:
    """Collects events in a list (tests / programmatic consumers)."""

    def __init__(self):
        self.events: list[dict] = []

    def emit(self, event: dict) -> None:
        self.events.append(event)

    def close(self) -> None:
        pass


class JsonlSink:
    """One JSON object per line; the run manifest is the first event."""

    def __init__(self, path: str):
        self.path = path
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        self._f = open(path, "w")

    def emit(self, event: dict) -> None:
        self._f.write(json.dumps(event) + "\n")
        self._f.flush()

    def close(self) -> None:
        self._f.close()


class CsvSink:
    """Round events as CSV; columns fixed by the first round event
    (non-round events are skipped — JSONL is the full stream).

    Vector-valued metrics (the distribution sketches: ``*_hist`` bins,
    ``*_top_ids``/``*_top_vals``) are flattened into stable indexed
    columns ``name.0 .. name.{k-1}`` — the column set stays fixed because
    sketch shapes are static (``hist_bins``/``topk`` are spec fields).
    Anything deeper than 1-D is rejected with a pointer at the JSONL
    sink, never silently stringified into an unparseable cell."""

    def __init__(self, path: str):
        self.path = path
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        self._f = open(path, "w")
        self._keys: list[str] | None = None

    @staticmethod
    def _flatten(event: dict) -> dict:
        flat = {}
        for k, v in event.items():
            if k == "event":
                continue
            if isinstance(v, (list, tuple)):
                if any(isinstance(x, (list, tuple)) for x in v):
                    raise ValueError(
                        f"CsvSink cannot flatten nested vector metric {k!r}"
                        " — route this stream to a jsonl:<path> sink")
                for i, x in enumerate(v):
                    flat[f"{k}.{i}"] = x
            else:
                flat[k] = v
        return flat

    def emit(self, event: dict) -> None:
        if event.get("event") != "round":
            return
        flat = self._flatten(event)
        if self._keys is None:
            self._keys = list(flat)
            self._f.write(",".join(self._keys) + "\n")
        self._f.write(",".join(str(flat.get(k, "")) for k in self._keys)
                      + "\n")

    def close(self) -> None:
        self._f.close()


class StdoutSink:
    """Human-readable summary lines; round lines gated by ``every``."""

    def __init__(self, every: int = 1):
        self.every = max(int(every), 1)

    @staticmethod
    def _fmt(v):
        return f"{v:.6g}" if isinstance(v, float) else str(v)

    def emit(self, event: dict) -> None:
        kind = event.get("event")
        if kind == "round":
            if event.get("round", 0) % self.every:
                return
            # sketch vectors stay in jsonl/csv — a 48-bin histogram per
            # line would drown the summary.
            body = "  ".join(f"{k}={self._fmt(v)}" for k, v in event.items()
                             if k not in ("event", "round")
                             and not isinstance(v, (list, tuple)))
            print(f"[telemetry] round {event.get('round', 0):5d}  {body}")
        elif kind == "monitor" and event.get("kind") == "rate_break":
            print(f"[telemetry] WARN round {event.get('round')}: rate break "
                  f"on {event.get('metric')} — rho_hat="
                  f"{self._fmt(event.get('rho_hat'))} after established "
                  f"{self._fmt(event.get('rho_ref'))} at value "
                  f"{self._fmt(event.get('value'))}  "
                  f"(axis: {event.get('axis', '')})")
        elif kind == "monitor":
            print(f"[telemetry] WARN round {event.get('round')}: "
                  f"{event.get('metric')}={self._fmt(event.get('value'))} "
                  f"{'>' if event.get('mode', 'max') == 'max' else '<'} "
                  f"{event.get('bound')}  (axis: {event.get('axis', '')})")
        elif kind == "manifest":
            print(f"[telemetry] run algo={event.get('algo')} "
                  f"n_clients={event.get('n_clients')} tau={event.get('tau')} "
                  f"commit={event.get('commit')}")
        elif kind == "profile":
            print(f"[telemetry] profiler {event.get('action')} at round "
                  f"{event.get('round')} -> {event.get('dir')}")

    def close(self) -> None:
        pass


def parse_sinks(spec) -> list:
    """Sink spec grammar (the ``--telemetry`` CLI knob): comma-separated
    ``jsonl:<path>`` | ``csv:<path>`` | ``stdout[:every]`` | ``memory``.
    Spec-configuring parts (``hist``/``topk``/``leafstats`` — consumed by
    :func:`parse_telemetry`) are skipped so one string drives both.
    Sink objects / lists pass through; None -> []."""
    if spec is None or spec is True:
        return []
    if not isinstance(spec, str):
        return list(spec) if isinstance(spec, (list, tuple)) else [spec]
    sinks = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        kind, _, arg = part.partition(":")
        kind = kind.lower()
        if kind in _SPEC_PART_KINDS:
            continue
        if kind == "jsonl":
            sinks.append(JsonlSink(arg or "telemetry.jsonl"))
        elif kind == "csv":
            sinks.append(CsvSink(arg or "telemetry.csv"))
        elif kind == "stdout":
            sinks.append(StdoutSink(every=int(arg) if arg else 1))
        elif kind in ("memory", "mem"):
            sinks.append(MemorySink())
        else:
            raise ValueError(f"unknown telemetry sink {part!r} "
                             "(jsonl:<path> | csv:<path> | stdout[:k] | "
                             "memory)")
    return sinks


def emit_event(sinks, event: dict) -> None:
    for s in sinks:
        s.emit(event)


def close_sinks(sinks) -> None:
    for s in sinks:
        s.close()


# ----------------------------------------------------------- manifest/drain
def _git_commit() -> str | None:
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], capture_output=True,
            text=True, cwd=os.path.dirname(os.path.abspath(__file__)))
        return out.stdout.strip() or None
    except OSError:
        return None


def run_manifest(algo, *, n_params: int | None = None, config: dict | None = None,
                 monitors: tuple = (), extra: dict | None = None,
                 leaf_info=None) -> dict:
    """The run's first event: what ran, where, and what one round costs on
    the wire (the ``comm_hops_per_round`` per-hop contract + totals).
    ``leaf_info`` (``repro.core.comm.leaf_info_of``) upgrades billing to
    exact per-leaf wire bits and records the per-leaf budget breakdown
    (``leaf_names`` / ``leaf_bits``) for report.py's budget-vs-leaf
    view."""
    tel = getattr(algo, "telemetry", None)
    ev = {
        "event": "manifest", "schema": 1,
        "algo": getattr(algo, "name", type(algo).__name__),
        "n_clients": getattr(algo, "n_clients", None),
        "tau": getattr(algo, "tau", None),
        "commit": _git_commit(),
        "mesh": {"backend": jax.default_backend(),
                 "n_devices": jax.device_count()},
        "metrics": (list(tel.metrics)
                    if tel is not None and tel.metrics != "auto" else "auto"),
        "monitors": [dataclasses.asdict(m) for m in monitors],
        "config": dict(config or {}),
    }
    if n_params:
        from repro.core.comm import (comm_bits_per_round,
                                     comm_hops_per_round,
                                     message_leaf_bits_of)

        nc = getattr(algo, "n_clients", 1)
        ev["bits_per_round"] = comm_bits_per_round(algo, n_params, nc,
                                                   leaf_info)
        ev["hops"] = comm_hops_per_round(algo, n_params, nc, leaf_info)
        if leaf_info is not None:
            lb = message_leaf_bits_of(algo, leaf_info)
            if lb is not None:
                ev["leaf_names"] = [nm for nm, _ in leaf_info]
                ev["leaf_sizes"] = [int(n) for _, n in leaf_info]
                ev["leaf_bits"] = [float(b) for b in lb]
    if extra:
        ev.update(extra)
    return ev


def drain(series: dict | None, *, sinks=(), monitors=(), start_round: int = 0,
          static: dict | None = None, algo=None,
          n_params: int | None = None, leaf_names=None,
          leaf_bits=None) -> list:
    """Device-get the stacked per-round telemetry pytree ONCE and emit one
    ``round`` event per round into the sinks, evaluating ``monitors``
    against each (violations emit a structured WARN event right after
    their round). ``static`` merges constant per-round fields; passing
    ``algo``/``n_params`` derives the bit-true ``bits_up``/``bits_down``
    per round from the comm accounting. Returns the emitted events.

    Vector-valued series (the distribution sketches) land in the round
    event as JSON lists; ``leaf_*`` series split off into a per-round
    ``leaf_stats`` event (``leaf_names`` labels its entries — and
    ``leaf_bits``, the exact per-leaf wire bits from the comm accounting,
    rides along as ``bits`` — on the first round of the segment). Observer monitors (:class:`RateMonitor` —
    anything with ``.observe``) see and annotate each round event BEFORE
    it is emitted, so ``rho_hat`` rides the stream; threshold
    :class:`Monitor` checks skip vector values."""
    events: list[dict] = []
    if not series:
        return events
    span = jax.profiler.TraceAnnotation
    with span("drain.device_get"):
        host = {k: np.asarray(jax.device_get(v)) for k, v in series.items()}
    n = len(next(iter(host.values())))
    stat = dict(static or {})
    if algo is not None and n_params:
        from repro.core.comm import comm_bits_per_round

        with span("drain.comm_bits"):
            bits = comm_bits_per_round(algo, n_params,
                                       getattr(algo, "n_clients", 1))
        stat.setdefault("bits_up", bits["up_bits"])
        stat.setdefault("bits_down", bits["down_bits"])
    leaf_keys = [k for k in host if k.startswith("leaf_")]
    observers = [m for m in monitors if hasattr(m, "observe")]
    checks = [m for m in monitors if not hasattr(m, "observe")]
    with span("drain.events"):
        for i in range(n):
            ev = {"event": "round", "round": int(start_round + i)}
            for k, v in host.items():
                if k in leaf_keys:
                    continue
                ev[k] = _jsonable(v[i])
            ev.update(stat)
            rate_warns = [w for w in (m.observe(ev) for m in observers) if w]
            events.append(ev)
            emit_event(sinks, ev)
            if leaf_keys:
                lev = {"event": "leaf_stats", "round": ev["round"]}
                if leaf_names is not None and i == 0:
                    lev["names"] = list(leaf_names)
                if leaf_bits is not None and i == 0:
                    lev["bits"] = [float(b) for b in leaf_bits]
                for k in leaf_keys:
                    lev[k[len("leaf_"):]] = _jsonable(host[k][i])
                events.append(lev)
                emit_event(sinks, lev)
            for m in checks:
                v = ev.get(m.metric)
                if (isinstance(v, (int, float)) and not isinstance(v, bool)
                        and m.violated(v)):
                    warn = {"event": "monitor", "level": "WARN",
                            "metric": m.metric, "round": ev["round"],
                            "value": v, "bound": m.bound,
                            "mode": m.mode, "axis": m.axis}
                    events.append(warn)
                    emit_event(sinks, warn)
            for w in rate_warns:
                events.append(w)
                emit_event(sinks, w)
    return events


def write_csv_rows(path: str, rows: list[dict]) -> None:
    """The trainer's CSV contract, verbatim (``FedTrainer._write_csv``
    routes through this so the bytes stay identical): header from the
    first row's keys, ``str()``-formatted values."""
    if not rows:
        return
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    keys = list(rows[0])
    with open(path, "w") as f:
        f.write(",".join(keys) + "\n")
        for row in rows:
            f.write(",".join(str(row[k]) for k in keys) + "\n")


# ----------------------------------------------------------------- profiling
def parse_trace_rounds(spec) -> tuple[int, int] | None:
    """``"a:b"`` -> the half-open round window [a, b) to trace; ``"a"``
    traces the single round a. None/empty -> no tracing."""
    if spec is None or spec == "":
        return None
    if isinstance(spec, tuple):
        lo, hi = spec
    else:
        a, _, b = str(spec).partition(":")
        lo = int(a)
        hi = int(b) if b else lo + 1
    if hi <= lo or lo < 0:
        raise ValueError(f"bad --trace-rounds window {spec!r} (want a:b "
                         "with 0 <= a < b)")
    return lo, hi


@dataclasses.dataclass
class TraceSession:
    """Brackets a ``--trace-rounds a:b`` window with ``jax.profiler``
    trace capture. The caller forces scan-segment boundaries at the
    window edges (:meth:`boundaries`) and calls :meth:`maybe_start` before
    / :meth:`maybe_stop` after each segment; both return a ``profile``
    event for the sinks when they act."""

    window: tuple[int, int] | None
    out_dir: str = "profile_trace"
    active: bool = False

    def boundaries(self) -> tuple:
        """Round indices that must END a scan segment so the traced
        segment starts/stops exactly at the window edges."""
        if self.window is None:
            return ()
        return tuple(b for b in (self.window[0] - 1, self.window[1] - 1)
                     if b >= 0)

    def maybe_start(self, first_round: int) -> dict | None:
        if (self.window is None or self.active
                or not (self.window[0] <= first_round < self.window[1])):
            return None
        jax.profiler.start_trace(self.out_dir)
        self.active = True
        return {"event": "profile", "action": "start_trace",
                "round": first_round, "dir": self.out_dir}

    def maybe_stop(self, next_round: int) -> dict | None:
        if not self.active or next_round < self.window[1]:
            return None
        jax.profiler.stop_trace()
        self.active = False
        return {"event": "profile", "action": "stop_trace",
                "round": next_round, "dir": self.out_dir}

    def close(self) -> None:
        if self.active:
            jax.profiler.stop_trace()
            self.active = False


#: the round program's layers, as ``jax.named_scope`` names the engine
#: places at its call sites (so every algorithm gets the same ones): the
#: clients' vmapped forward/backward, the arena pack of its gradient, the
#: spec's local update and message, the uplink transforms, the reduction
#: and server update, telemetry captures and finalize, and the per-round
#: loss. A scope changes only the compiled ops' ``op_name`` metadata; an
#: op's innermost scope names its layer (a fusion takes its root's).
SCOPES = ("fed.client_grad", "fed.arena_pack", "fed.local_update",
          "fed.uplink", "fed.aggregate", "fed.telemetry", "fed.round_loss")


def scope(name: str):
    """``jax.named_scope(name)`` for one of :data:`SCOPES`."""
    if name not in SCOPES:
        raise ValueError(f"unknown scope {name!r}; have {SCOPES}")
    return jax.named_scope(name)
