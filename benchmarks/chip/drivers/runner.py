"""Driver of the one-chip cells: the program's K-round scan
(``repro.core.engine.make_round_runner``), built as
``repro.launch.train.run_training`` builds it, and driven segment by
segment with run_training's host work.

Set-up builds the one object the window drives: the weights from the seed
(``model.init``, jitted), the client token pool (``datagen``, on the device),
the jitted ``algo.init`` state, and the runner compiled ahead of time for
the cell's one segment shape. It then drives that object through its first
segment with the window's own ``call`` and the pool's first segment, and
keeps what the check compares: the per-round losses, gradient norms and
telemetry sketches of that call, and per model leaf the norms of the
parameters' change and of the drift after it. After the window, ``check`` frees the program's state
and runs the plain reference over the same rounds.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib

import jax
import jax.numpy as jnp
import numpy as np

import compare
import datagen
from reference import fedcet as ref_fedcet


def program_config(config: dict):
    """The program's ArchConfig for a configuration file: its registry
    entry with every field the file states set to the file's value."""
    from repro.configs import get_config
    from repro.configs.base import ArchConfig

    fields = {f.name for f in dataclasses.fields(ArchConfig)}
    fields -= {"name", "citation"}
    return dataclasses.replace(get_config(config["arch"]),
                               **{k: v for k, v in config.items()
                                  if k in fields})


def compiled_peak_bytes(compiled) -> int:
    """The compiler's peak for the program; an error where the backend
    leaves it empty."""
    peak = getattr(compiled.memory_analysis(), "peak_memory_in_bytes", 0)
    if not peak:
        raise RuntimeError("the compiler reports no peak memory for the "
                           "round program")
    return int(peak)


def reference_run(config: dict, traffic: dict, dtype):
    family = importlib.import_module(f"reference.{config['family']}")
    return ref_fedcet.make_trajectory(family, config, traffic, dtype)


class Job:
    def __init__(self, config: dict, traffic: dict, seed: int, devices):
        from repro.configs.base import FedScenario
        from repro.core import telemetry as tele
        from repro.core.comm import CommMeter
        from repro.core.engine import make_round_runner
        from repro.core.fedcet import FedCET
        from repro.models import build_model

        del devices  # one chip: JAX's default device
        self.config, self.traffic, self.seed = config, traffic, seed
        self.tele = tele
        k, n, tau = traffic["rounds_per_call"], traffic["n_clients"], traffic["tau"]
        batch, seq = traffic["batch"], traffic["seq_len"]
        self.rounds_per_call = k
        self.tokens_per_call = k * n * tau * batch * seq

        cfg = program_config(config)
        model = build_model(cfg)
        grad_fn = jax.grad(model.loss)
        scenario = FedScenario(compression=traffic["compression"],
                               arena=traffic["arena"],
                               telemetry=traffic["telemetry"],
                               seed=traffic["scenario_seed"])
        algo = scenario.apply(FedCET(alpha=traffic["alpha"], c=traffic["c"],
                                     tau=tau, n_clients=n))
        self.algo = algo
        params = jax.jit(model.init)(jax.random.key(seed))
        self.pool = [{"tokens": datagen.segment(
            i * k, seed, vocab=cfg.vocab_size, n_clients=n, tau=tau,
            batch=batch, seq_len=seq, heterogeneity=traffic["heterogeneity"],
            rounds=k)} for i in range(traffic["pool_segments"])]
        state = jax.jit(lambda p, b: algo.init(grad_fn, p, b))(
            params, jax.tree.map(lambda a: a[0, 0], self.pool[0]))

        def round_loss(s, b):
            last = jax.tree.map(lambda a: a[-1], b)
            return jnp.mean(jax.vmap(model.loss)(algo.client_params(s), last))

        runner = make_round_runner(algo, grad_fn, metric_fn=round_loss,
                                   metric_with_batch=True, metric_before=True,
                                   donate=True)
        self.compiled = runner.lower(state, self.pool[0]).compile()
        self.peak_bytes = compiled_peak_bytes(self.compiled)
        self.sinks = tele.parse_sinks(traffic["telemetry"])
        self.monitors = tele.resolve_monitors(algo.telemetry, algo)
        self.meter = CommMeter.for_params(params, algo=algo, n_clients=n)
        self.n_params = self.meter.n_params
        self.state, self.round, self.segment = state, 0, 0

        self.call()
        x_norms, d_norms = jax.jit(self._leaf_norms)(self.state, params)
        self.sketch_spec = compare.sketch_spec(traffic["telemetry"])
        self.first = {
            "loss": self.losses,
            "grad_norm": np.asarray([e.get("grad_norm", np.nan)
                                     for e in self.events
                                     if e["event"] == "round"]),
            "sketch": compare.sketch_of_events(self.events,
                                               self.sketch_spec),
            "dx": np.asarray(x_norms), "d": np.asarray(d_norms)}
        del params

    def _leaf_norms(self, state, params):
        from repro.core.arena import Arena, unpack

        x = self.algo.client_params(state)
        d = self.algo._inner(state).d
        d = unpack(d) if isinstance(d, Arena) else d
        norm = lambda a: jnp.sqrt(jnp.sum(jnp.square(a)))  # noqa: E731
        leaves = jax.tree.leaves
        return (jnp.stack([norm(a - p[None]) for a, p in
                           zip(leaves(x), leaves(params))]),
                jnp.stack([norm(a) for a in leaves(d)]))

    def call(self) -> int:
        """One segment of rounds with run_training's host work: dispatch,
        telemetry split and drain, comm metering, loss fetch. Returns the
        number of rounds whose loss was not finite."""
        tele, k = self.tele, self.rounds_per_call
        with jax.profiler.TraceAnnotation("segment_dispatch"):
            self.state, ys = self.compiled(
                self.state, self.pool[self.segment % len(self.pool)])
            losses, series = tele.split_metrics(self.algo, ys)
        with jax.profiler.TraceAnnotation("telemetry_drain"):
            self.events = tele.drain(
                {**series, "loss": losses}, sinks=self.sinks,
                monitors=self.monitors, start_round=self.round,
                algo=self.algo, n_params=self.n_params,
                leaf_bits=self.meter.leaf_bits)
            for _ in range(k):
                self.meter.tick_round(self.algo)
        with jax.profiler.TraceAnnotation("results_fetch"):
            self.losses = np.asarray(jax.device_get(losses))
        self.round += k
        self.segment += 1
        return int(np.sum(~np.isfinite(self.losses)))

    def reference(self, dtype, run=None) -> dict:
        """The plain reference (``dtype`` float32 at highest matmul
        precision; bfloat16 for the control) over the first segment;
        ``run`` is a trajectory from ``reference_run`` to reuse."""
        if run is None:
            run = reference_run(self.config, self.traffic, dtype)
        precision = "highest" if dtype == jnp.float32 else "default"
        with jax.default_matmul_precision(precision):
            return jax.device_get(run(self.seed, self.pool[0]["tokens"]))

    def release(self) -> None:
        """Free the program's state and compiled program."""
        self.state = self.compiled = None
        self.pool = self.pool[:1]
        gc.collect()

    def check(self, limits: dict) -> dict:
        self.release()
        return compare.judge(compare.numbers(
            self.first, self.reference(jnp.float32), self.sketch_spec), limits)
