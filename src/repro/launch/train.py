"""Distributed federated training driver.

``build_train_step`` assembles the jitted FedCET communication round for a
given (arch, mesh): the paper's Algorithm 2 applied to the real model, with

  * clients laid out along the ("pod", "data") mesh axes (one model replica
    + one heterogeneous data shard per client),
  * each replica tensor-parallel over "model" (partition.py rules),
  * Megatron-style sequence-sharded residual activations,
  * the single FedCET vector aggregated by ONE cross-client all-reduce per
    tau gradient steps — the only collective crossing the pod boundary.

Also provides ``run_training`` — the end-to-end loop used by the examples
(single host: same code, 1x1 mesh semantics, no sharding constraints).

Run as a script for a production-launch entry point:
    python -m repro.launch.train --arch qwen3-1.7b --steps 100 ...
"""

from __future__ import annotations

import dataclasses
import os
from pathlib import Path
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.configs.base import (INPUT_SHAPES, ArchConfig, FedScenario,
                                ShapeConfig)
from repro.core.api import StatsGrad
from repro.core.engine import EngineState, make_round_runner, scan_segments
from repro.core.fedcet import FedCET, FedCETState
from repro.core.staleness import DelayState
from repro.core.topology import TopoState
from repro.launch import input_specs as ispec
from repro.launch import partition
from repro.launch.mesh import client_axes, n_clients, tp_size
from repro.models import build_model
from repro.utils.sharding_ctx import activation_sharding


@dataclasses.dataclass(frozen=True)
class TrainPlan:
    cfg: ArchConfig
    algo: Any  # FedCET, possibly wrapped by scenario transforms
    mesh: Any
    n_clients: int
    per_client_batch: int
    seq_len: int

    @property
    def client_axes(self) -> tuple[str, ...]:
        return client_axes(self.mesh)


def make_plan(arch: str, mesh, *, shape: str | ShapeConfig = "train_4k",
              tau: int = 2, alpha: float = 1e-3, c: float = 0.05,
              dtype: str = "bfloat16",
              scenario: FedScenario | None = None) -> TrainPlan:
    """``shape`` names an ``INPUT_SHAPES`` entry or is a ``ShapeConfig``
    (its global batch splits evenly over the mesh's clients)."""
    from repro.launch.overrides import distribution_for, train_mesh_view

    cfg = get_config(arch).with_dtype(dtype)
    shp = INPUT_SHAPES[shape] if isinstance(shape, str) else shape
    dist = distribution_for(arch)
    mesh = train_mesh_view(mesh, dist.fsdp)  # may split data -> (data, fsdp)
    nc = n_clients(mesh)
    assert shp.global_batch % nc == 0, (shp.global_batch, nc)
    algo = FedCET(alpha=alpha, c=c, tau=tau, n_clients=nc,
                  spmd_client_axes=client_axes(mesh))
    if scenario is not None:
        algo = scenario.apply(algo)
    return TrainPlan(cfg=cfg, algo=algo, mesh=mesh, n_clients=nc,
                     per_client_batch=shp.global_batch // nc,
                     seq_len=shp.seq_len)


def _fsdp(plan: TrainPlan) -> str | None:
    return "fsdp" if "fsdp" in plan.mesh.axis_names else None


def state_shardings(plan: TrainPlan, state_shapes):
    """Shardings for the algorithm state: x and d are stacked-client param
    trees; transform extras (error-feedback / shift memory) and the delay
    buffer are message-shaped — the same stacked layout as x — and shard
    identically (the buffer's ``[clients] int32`` age vector shards over
    the client axes); a stateful topology's ``TopoState`` is replicated —
    the scalar mixing round index, plus (for hierarchies with stateful
    tier compression) the small per-aggregator tier memory."""
    mesh, tp, ca = plan.mesh, tp_size(plan.mesh), plan.client_axes
    inner_shapes = (state_shapes.inner
                    if isinstance(state_shapes, EngineState) else state_shapes)
    tree_sh = lambda tree: partition.tree_shardings(  # noqa: E731
        tree, mesh, tp, ca, extra_axis=_fsdp(plan))
    inner_sh = FedCETState(x=tree_sh(inner_shapes.x), d=tree_sh(inner_shapes.d),
                           t=NamedSharding(mesh, P()))
    if not isinstance(state_shapes, EngineState):
        return inner_sh

    def extra_sh(e):
        if e is None:
            return None
        if isinstance(e, TopoState):
            return jax.tree.map(lambda _: NamedSharding(mesh, P()), e)
        return tree_sh(e)

    return EngineState(inner=inner_sh,
                       extras=tuple(extra_sh(e) for e in state_shapes.extras))


def abstract_state(plan: TrainPlan):
    """Shape-only algorithm state (no allocation) for AOT lowering:
    FedCETState, wrapped in EngineState when the plan's scenario attaches
    message transforms (extras shaped via ``eval_shape`` over each
    transform's ``init_extra`` on the message = x-shaped tree), a
    STATEFUL topology (a scalar ``TopoState`` round index, just before
    the delay slot) and/or a delay model (final extras slot = the server
    buffer: an x-shaped last-known message tree plus the ``[clients]
    int32`` age vector)."""
    model = build_model(plan.cfg)
    params = jax.eval_shape(lambda k: model.init(k), jax.random.key(0))
    stack = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((plan.n_clients,) + a.shape, a.dtype), tree)
    inner = FedCETState(x=stack(params), d=stack(params),
                        t=jax.ShapeDtypeStruct((), jnp.int64))
    transforms = getattr(plan.algo, "transforms", ())
    delay = getattr(plan.algo, "delay", None)
    topo = getattr(plan.algo, "topology", None)
    topo_stateful = topo is not None and topo.stateful
    if not transforms and delay is None and not topo_stateful:
        return inner
    extras = tuple(jax.eval_shape(lambda t=t: t.init_extra(inner.x))
                   for t in transforms)
    if topo_stateful:
        # the scalar round index, plus — for hierarchies with stateful
        # tier compression — the per-tier memory shaped from the
        # (x-shaped) message tree, exactly as the engine inits it.
        extras = extras + (jax.eval_shape(lambda: topo.init_state(inner.x)),)
    if delay is not None:
        extras = extras + (DelayState(
            buf=inner.x,
            age=jax.ShapeDtypeStruct((plan.n_clients,), jnp.int32)),)
    return EngineState(inner=inner, extras=extras)


def build_round_fn(plan: TrainPlan) -> Callable:
    """The pure function jitted as the production train step."""
    model = build_model(plan.cfg)
    grad_fn = jax.grad(model.loss)
    algo = plan.algo

    def train_round(state: FedCETState, batches):
        return algo.round(grad_fn, state, batches)

    return train_round


def lower_train_step(plan: TrainPlan, *, donate: bool = True):
    """AOT lower + compile the FedCET round on the production mesh.

    ``donate`` aliases the state argument into the output so the stacked
    client store ((x, d), transform extras, delay buffers) updates in
    place instead of doubling peak memory at large N — essential once the
    cohort path scatters into an O(N)-row store. The dry-run path passes
    ``donate=False``: on the CPU backend, ``memory_analysis`` double-counts
    the aliased while-carry, so recorded numbers stay donation-free
    (EXPERIMENTS.md §Dry-run)."""
    mesh = plan.mesh
    state_shapes = abstract_state(plan)
    batch_shapes = ispec.fed_batch_specs(
        plan.cfg, plan.algo.tau, plan.n_clients, plan.per_client_batch,
        plan.seq_len)
    st_sh = state_shardings(plan, state_shapes)
    b_sh = partition.batch_shardings(
        batch_shapes, mesh,
        dim_axes=(None, plan.client_axes, _fsdp(plan)))
    fn = build_round_fn(plan)
    tp = tp_size(mesh)
    # token-sharded MoE dispatch when experts don't divide the model axis
    # (EXPERIMENTS.md §Perf iteration 1); per-client tokens are seq-sharded
    # over `model` (and batch over fsdp when present).
    moe = None
    if plan.cfg.n_experts and plan.cfg.n_experts % tp:
        fs = _fsdp(plan)
        nb = mesh.shape[fs] if fs else 1
        axes = (fs, "model") if fs else ("model",)
        moe = {"nb": nb, "ns": tp, "axes": axes,
               "spec": P(axes if len(axes) > 1 else axes[0], None, None)}
    with mesh:
        # per-client activations [B, S, d]: batch over fsdp (when present),
        # sequence over model (Megatron SP), d replicated.
        with activation_sharding(residual=P(_fsdp(plan), "model", None),
                                 logits=P(_fsdp(plan), None, "model"),
                                 moe_shards=moe):
            lowered = jax.jit(
                fn, in_shardings=(st_sh, b_sh), out_shardings=st_sh,
                donate_argnums=(0,) if donate else (),
            ).lower(state_shapes, batch_shapes)
    return lowered


# --------------------------------------------------------- single-host loop
def run_training(arch: str, *, steps: int = 100, tau: int = 2,
                 n_clients: int = 4, batch: int = 8, seq_len: int = 128,
                 alpha: float = 3e-3, c: float = 0.05, heterogeneity: float = 0.8,
                 reduced: bool = True, seed: int = 0,
                 compression: str = "none", compression_plan="none",
                 plan_adapt: float = 0.0, participation: float = 1.0,
                 delay: str = "none", stale_policy: str = "last",
                 topology: str = "star", tier_compression: str = "none",
                 cohort: int | str | None = "none", arena: bool = False,
                 telemetry: str | None = None, trace_rounds: str | None = None,
                 trace_dir: str = "profile_trace",
                 log_every: int = 10, ckpt_dir: str | None = None,
                 callback=None) -> dict:
    """End-to-end FedCET LM training on the host device(s). Returns metrics
    history. Used by examples/fed_train_lm.py.

    ``compression`` (a compressor spec — ``"randk:0.25"``, ``"shift:q8"``,
    ``"ef:topk:0.3+bf16"``, ...) or ``compression_plan`` (the PER-LEAF
    alternative: first-match-wins ``pattern:spec`` rules over leaf paths,
    ``"embed*:q12,ln*:bf16,*:shift:q6"``, or a ready
    ``CompressionPlan`` — e.g. from ``plan.allocate`` — billed exactly
    per leaf; ``plan_adapt > 1`` additionally tightens the plan one step
    each time the telemetry ``compress_err`` residual shrinks by that
    factor, re-jitting at the segment boundary with the carried state —
    requires ``telemetry``), ``participation``, ``delay`` /
    ``stale_policy`` (asynchronous rounds — ``"fixed:2"``, ``"rr:1"``,
    ``"geom:0.5"`` with ``drop``/``last``/``poly:a`` aggregation),
    ``topology`` (aggregation geometry — ``"hier:g8"`` edge-aggregator
    tree, ``"ring"``/``"torus"``/``"er:0.4"`` gossip mixing; a trailing
    ``":sparse"`` selects the O(edges) padded neighbor-exchange
    lowering) and ``tier_compression`` (hierarchies: re-compress the
    interior edge->root tier uplinks, e.g. ``"shift:q8"``) compose
    the corresponding engine transforms onto the FedCET spec, so the
    production LM loop runs any scenario the simulation tests pin; comm
    metering is bit-true from the resulting compressor stack, the delay
    model's uplink duty cycle, the sampling rate's downlink duty cycle,
    and the topology's per-hop traffic shape (compressed interior tiers
    included). ``cohort`` (``"none"`` | ``256`` | ``"block:256"`` |
    ``"rr:256"``) runs each round on a gathered fixed-size cohort of the
    client-state store — O(cohort) per-round work with only the cohort's
    uplink billed. ``arena`` packs the client store into the contiguous
    ``[clients, rows, 1024]`` parameter arena (unpacking only at the
    per-client gradient call) so the round tail streams one buffer
    instead of one per pytree leaf — numerically <=1e-12-equivalent.

    ``telemetry`` is a sink spec (``"jsonl:run.jsonl"``, ``"csv:m.csv"``,
    ``"stdout[:k]"``, ``"memory"``, comma-chained) — any non-empty spec
    attaches the in-trace telemetry transform (per-round norms, invariant
    residual, consensus error, staleness ages — captured inside the jitted
    scan, drained into the sinks per segment behind a run manifest).
    Adding ``hist[:bins[:lo:hi]]`` / ``topk[:k]`` parts to the same
    string turns on the population distribution sketches (per-client
    ``||d_i||``, drift, compression error and age log-histograms +
    quantiles + top-k outlier client ids, one O(N) pass over the full
    client store per round); ``leafstats`` adds the per-leaf
    msg_norm/compress_err breakdown as ``leaf_stats`` events. The drain
    also runs an online linear-rate estimator whose ``rho_hat`` rides
    each round event and WARNs on rate breaks naming the suspect axis.
    ``trace_rounds`` (``"a:b"`` or ``"a"``) brackets that round window
    with a ``jax.profiler`` trace written under ``trace_dir`` — segment
    boundaries are forced at the window edges so the trace covers exactly
    those rounds, each segment with its host work under the spans
    ``segment_dispatch``, ``telemetry_drain`` (around ``drain``'s own
    ``drain.*`` spans) and ``results_fetch``. Per-round stdout summary
    lines (round, loss, bits_up, active_clients) print for every
    ``log_every``-th round."""
    from repro.checkpoint.ckpt import save
    from repro.core import telemetry as tele
    from repro.core.comm import CommMeter, comm_bits_per_round, leaf_info_of
    from repro.data.synthetic import make_hetero_lm_dataset

    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(seed))
    scenario = FedScenario(compression=compression,
                           compression_plan=compression_plan,
                           participation=participation, delay=delay,
                           stale_policy=stale_policy, topology=topology,
                           tier_compression=tier_compression, cohort=cohort,
                           arena=arena, telemetry=telemetry or False,
                           seed=seed)
    algo = scenario.apply(FedCET(alpha=alpha, c=c, tau=tau, n_clients=n_clients))
    ds = make_hetero_lm_dataset(cfg.vocab_size, n_clients, seq_len, batch,
                                heterogeneity=heterogeneity, seed=seed)
    # an expert model's gradient also gives its routing counters, which
    # the telemetry sums into each round's event
    grad_fn = (StatsGrad(model.loss_and_stats) if cfg.n_experts
               else jax.grad(model.loss))

    def batches_for(r):
        toks = ds.sample_round(r, tau)  # [tau, C, B, S]
        return {"tokens": toks}

    # jitted: run op by op, the init keeps its warm-up gradient's
    # intermediates alive — at published widths that, not the round, set
    # the peak device memory of a one-chip run.
    state = jax.jit(lambda p, b: algo.init(grad_fn, p, b))(
        params, jax.tree.map(lambda b: b[0], batches_for(0)))

    # per-round mean client loss ON-DEVICE inside the scan, on tokens no
    # step has trained on yet: the clients' parameters ENTERING the round,
    # on its last local batch (round 0's first batch fed the warm-up). A
    # loss read after the round on a batch it stepped on is an in-sample
    # loss: at published widths one step lowers its own batch's loss by
    # ~1.5 nats while unseen tokens gain ~0.01.
    def round_loss(s, b):
        bl = jax.tree.map(lambda a: a[-1], b)
        return jnp.mean(jax.vmap(model.loss)(algo.client_params(s), bl))

    # the shared multi-round scan driver: rounds between log/checkpoint
    # boundaries run as one jitted lax.scan segment. The carry is donated
    # so the client store ((x, d), extras, delay buffers) updates in
    # place — the loop below rebinds `state` each call, never reusing the
    # donated buffers.
    runner = make_round_runner(algo, grad_fn, metric_fn=round_loss,
                               metric_with_batch=True, metric_before=True,
                               donate=True)

    sinks = tele.parse_sinks(telemetry)
    tel_spec = getattr(algo, "telemetry", None)
    # passing the algo gives the monitor set a RateMonitor that names the
    # attached lossy axes when the measured linear rate breaks.
    monitors = tele.resolve_monitors(tel_spec, algo)
    leaf_info = leaf_info_of(params)
    leaf_names = None
    if tel_spec is not None and tel_spec.leaf_stats:
        # the canonical slash-joined names — the same vocabulary plan
        # globs match and per-leaf billing reports, so report.py can join
        # leaf_stats rows against the manifest's leaf_bits budget.
        leaf_names = [nm for nm, _ in leaf_info]
    trace = tele.TraceSession(tele.parse_trace_rounds(trace_rounds),
                              out_dir=trace_dir)
    trace_stops = set(trace.boundaries())

    def is_stop(r):
        return (r % log_every == 0 or r == steps - 1 or r in trace_stops
                or (ckpt_dir is not None and (r + 1) % 50 == 0))

    meter = CommMeter.for_params(params, algo=algo, n_clients=n_clients)
    per_round_bits = comm_bits_per_round(algo, meter.n_params, n_clients,
                                         leaf_info)
    adaptive = None
    if plan_adapt and plan_adapt > 1.0:
        from repro.core.compressors import AdaptivePlan, CompressionPlan

        plans = [t.compressor for t in algo.transforms
                 if isinstance(getattr(t, "compressor", None),
                               CompressionPlan)]
        if not plans:
            raise ValueError("plan_adapt needs a compression_plan attached")
        if telemetry is None:
            raise ValueError("plan_adapt reads the telemetry compress_err "
                             "residual; pass --telemetry")
        adaptive = AdaptivePlan(plan=plans[-1], factor=float(plan_adapt))

    def _swap_plan(a, plan):
        from repro.core.compressors import CompressionPlan

        ts = tuple(dataclasses.replace(t, compressor=plan)
                   if isinstance(getattr(t, "compressor", None),
                                 CompressionPlan) else t
                   for t in a.transforms)
        return dataclasses.replace(a, transforms=ts)
    # fallback when telemetry is off: the expected participant count (with
    # telemetry on, the line reports the exact in-trace count).
    expected_active = int(round(n_clients * min(participation, 1.0)))
    if sinks:
        tele.emit_event(sinks, tele.run_manifest(
            algo, n_params=meter.n_params,
            config={"arch": arch, "steps": steps, "tau": tau,
                    "n_clients": n_clients, "batch": batch,
                    "seq_len": seq_len, "compression": compression,
                    "compression_plan": str(compression_plan),
                    "plan_adapt": plan_adapt,
                    "participation": participation, "delay": delay,
                    "stale_policy": stale_policy, "topology": topology,
                    "tier_compression": tier_compression,
                    "cohort": str(cohort), "arena": arena, "seed": seed},
            monitors=monitors, leaf_info=leaf_info))
    history = {"round": [], "loss": [], "comm_bytes": []}
    # host spans on the profiler's clock, named as the chip benchmark names
    # its own around the same calls.
    span = jax.profiler.TraceAnnotation
    for r, stop in scan_segments(0, steps, is_stop):
        ev = trace.maybe_start(r)
        if ev:
            tele.emit_event(sinks, ev)
        per_round = [batches_for(i) for i in range(r, stop + 1)]
        stacked = jax.tree.map(lambda *bs: jnp.stack(bs), *per_round)
        with span("segment_dispatch"):
            state, ys = runner(state, stacked)
            losses, tel_series = tele.split_metrics(algo, ys)
        with span("telemetry_drain"):
            if tel_series is not None and sinks:
                # the per-round loss rides the round events so the rate
                # estimator / report can read the LM convergence curve.
                tele.drain({**tel_series, "loss": losses}, sinks=sinks,
                           monitors=monitors, start_round=r, algo=algo,
                           n_params=meter.n_params, leaf_names=leaf_names,
                           leaf_bits=meter.leaf_bits)
            for _ in range(r, stop + 1):
                meter.tick_round(algo)
        if adaptive is not None and tel_series is not None \
                and "compress_err" in tel_series:
            new_plan = adaptive.update(
                float(jax.device_get(tel_series["compress_err"])[-1]))
            if new_plan is not None:
                # segment boundary: swap the tightened plan into the
                # attached transform and re-jit. Wrapper structure (and so
                # the extras pytree) is preserved, so the donated state
                # carries straight into the new runner.
                algo = _swap_plan(algo, new_plan)
                runner = make_round_runner(algo, grad_fn,
                                           metric_fn=round_loss,
                                           metric_with_batch=True,
                                           metric_before=True, donate=True)
                meter = dataclasses.replace(
                    CommMeter.for_params(params, algo=algo,
                                         n_clients=n_clients),
                    rounds=meter.rounds, bytes_up=meter.bytes_up,
                    bytes_down=meter.bytes_down)
                per_round_bits = comm_bits_per_round(
                    algo, meter.n_params, n_clients, leaf_info)
                if sinks:
                    tele.emit_event(sinks, {
                        "event": "plan_adapt", "round": stop,
                        "bits_per_round": per_round_bits["up_bits"]})
        with span("results_fetch"):
            losses = jax.device_get(losses)
        ev = trace.maybe_stop(stop + 1)
        if ev:
            tele.emit_event(sinks, ev)
        active = None if tel_series is None else tel_series.get("participating")
        for i, rr in enumerate(range(r, stop + 1)):
            if rr % log_every == 0 or rr == steps - 1:
                a = expected_active if active is None else int(active[i])
                print(f"round {rr:5d}  loss {float(losses[i]):.4f}  "
                      f"bits_up {(rr + 1) * per_round_bits['up_bits']:.4g}  "
                      f"active_clients {a}")
        if stop % log_every == 0 or stop == steps - 1:
            loss = float(losses[-1])
            history["round"].append(stop)
            history["loss"].append(loss)
            history["comm_bytes"].append(meter.total)
            if callback:
                callback(stop, loss, meter.total)
        if ckpt_dir and (stop + 1) % 50 == 0:
            save(ckpt_dir, stop + 1, state)
    trace.close()
    tele.close_sinks(sinks)
    return history


def use_compile_cache() -> str:
    """Keep JAX's persistent compilation cache where
    ``JAX_COMPILATION_CACHE_DIR`` says (JAX reads the variable itself), or
    else in ``.jax-cache/`` at the root of the checkout — a fixed path,
    because the path is part of what a later run must find again. Call
    before the first compile; returns the directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(Path(__file__).resolve().parents[3] / ".jax-cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def main(argv=None):
    import argparse

    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--tau", type=int, default=2)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--alpha", type=float, default=3e-3)
    ap.add_argument("--full", action="store_true",
                    help="use the full (non-reduced) architecture")
    ap.add_argument("--compression", default="none",
                    help="uplink compressor spec: none | bf16 | topk:0.3 | "
                         "randk:0.25 | q8 | shift:q8 | randk:0.5+q8 | ef:...")
    ap.add_argument("--compression-plan", default="none",
                    help="PER-LEAF uplink compression plan: comma-separated"
                         " first-match-wins pattern:spec rules over leaf "
                         "paths (glob or flatten-order leaf index), e.g. "
                         "'embed*:q12,ln*:bf16,*:shift:q6'; mutually "
                         "exclusive with --compression; billed exactly per "
                         "leaf (actual kept counts)")
    ap.add_argument("--plan-adapt", type=float, default=0.0,
                    help="adaptive plan schedule: tighten the plan one "
                         "step (quantizers -1 bit, sparsifiers k/2) each "
                         "time the telemetry compress_err residual shrinks"
                         " by this factor (> 1 enables; needs "
                         "--compression-plan and --telemetry)")
    ap.add_argument("--participation", type=float, default=1.0,
                    help="per-round Bernoulli client participation rate")
    ap.add_argument("--delay", default="none",
                    help="uplink delay model: none | fixed:2 | rr:1 | geom:0.5")
    ap.add_argument("--stale-policy", default="last",
                    help="stale-aggregation policy: drop | last | poly:1")
    ap.add_argument("--topology", default="star",
                    help="aggregation geometry: star | hier:g8 | hier:16x4 "
                         "| ring | torus | er:0.4 (gossip specs take a "
                         "trailing :sparse for the padded neighbor-exchange "
                         "lowering, e.g. ring:sparse, er:0.4:t:sparse)")
    ap.add_argument("--tier-compression", default="none",
                    help="hierarchies only: compressor spec for interior "
                         "edge->root tier uplinks (e.g. shift:q8)")
    ap.add_argument("--cohort", default="none",
                    help="cohort spec: none | 256 | block:256 | rr:256 "
                         "(optional trailing :dense forces the dense "
                         "reference lowering) — run each round on a "
                         "sampled fixed-size cohort, O(cohort) not O(N)")
    ap.add_argument("--arena", action="store_true",
                    help="pack the client store into the contiguous "
                         "[clients, rows, 1024] parameter arena (fused "
                         "round tail; <=1e-12-equivalent to per-leaf)")
    ap.add_argument("--telemetry", default=None,
                    help="telemetry sink spec: jsonl:<path> | csv:<path> | "
                         "stdout[:every] | memory (comma-chained). Any "
                         "non-empty spec enables in-trace round telemetry "
                         "+ invariant/rate monitors; add hist[:bins[:lo:hi]]"
                         " / topk:<k> parts for the per-client distribution"
                         " sketches and leafstats for the per-leaf "
                         "msg_norm/compress_err breakdown (e.g. "
                         "'jsonl:run.jsonl,hist:48,topk:4'); omitted = "
                         "bitwise no-op")
    ap.add_argument("--log-every", type=int, default=10,
                    help="print a per-round summary line (round, loss, "
                         "bits_up, active_clients) every k rounds")
    ap.add_argument("--trace-rounds", default=None,
                    help="profile round window 'a:b' (or 'a') with "
                         "jax.profiler — trace written under --trace-dir")
    ap.add_argument("--trace-dir", default="profile_trace")
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args(argv)
    hist = run_training(
        args.arch, steps=args.steps, tau=args.tau, n_clients=args.clients,
        batch=args.batch, seq_len=args.seq_len, alpha=args.alpha,
        reduced=not args.full, ckpt_dir=args.ckpt_dir,
        compression=args.compression,
        compression_plan=args.compression_plan, plan_adapt=args.plan_adapt,
        participation=args.participation,
        delay=args.delay, stale_policy=args.stale_policy,
        topology=args.topology, tier_compression=args.tier_compression,
        cohort=args.cohort, arena=args.arena,
        telemetry=args.telemetry, trace_rounds=args.trace_rounds,
        trace_dir=args.trace_dir, log_every=args.log_every)
    print("final loss:", hist["loss"][-1])


if __name__ == "__main__":
    main()
