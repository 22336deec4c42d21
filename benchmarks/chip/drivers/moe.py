"""Driver of the one-chip sparse-expert cells: ``drivers/runner.py``'s job,
with the gradient that also gives the expert layer's routing counters
(``repro.core.api.StatsGrad`` over ``loss_and_stats``, as
``repro.launch.train.run_training`` builds it for a model with experts).

The telemetry sums those counters into each round's event:
``moe_held_slots`` (the routed slots the held experts computed, over
clients and the round's steps) and ``moe_load_max`` (the most-loaded held
expert's slots over the held experts' mean). The job also keeps
``held_slots``, the sum of ``moe_held_slots`` over every round it has run
since the window began, for the grouped products' roofline.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import compare
import datagen
from drivers import runner


class Job(runner.Job):
    def __init__(self, config: dict, traffic: dict, seed: int, devices):
        from repro.configs.base import FedScenario
        from repro.core import telemetry as tele
        from repro.core.api import StatsGrad
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

        cfg = runner.program_config(config)
        model = build_model(cfg)
        grad_fn = StatsGrad(model.loss_and_stats)
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

        program = make_round_runner(algo, grad_fn, metric_fn=round_loss,
                                    metric_with_batch=True, metric_before=True,
                                    donate=True)
        self.compiled = program.lower(state, self.pool[0]).compile()
        self.peak_bytes = runner.compiled_peak_bytes(self.compiled)
        self.sinks = tele.parse_sinks(traffic["telemetry"])
        self.monitors = tele.resolve_monitors(algo.telemetry, algo)
        self.meter = CommMeter.for_params(params, algo=algo, n_clients=n)
        self.n_params = self.meter.n_params
        self.state, self.round, self.segment = state, 0, 0

        self.held_slots = 0
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
        self.held_slots = 0   # the window's rounds only
        del params

    def call(self) -> int:
        failed = super().call()
        self.held_slots += sum(e["moe_held_slots"] for e in self.events
                               if e["event"] == "round")
        return failed
