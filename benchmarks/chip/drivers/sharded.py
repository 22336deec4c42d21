"""Driver of the cross-silo cells on several chips: one silo a chip, the
FedCET round of ``repro.launch.train.make_plan`` + ``lower_train_step`` on
a ``(chips, 1)`` ``("data", "model")`` mesh, one round a call.

The round is the production launcher's: each silo's state (x, d and the
shift:q8 uplink's shift memory h) lives on its own chip, the local steps
run on it, and the aggregating step's clients' mean is the round's one
cross-chip all-reduce. That round takes no parameter arena and no
telemetry, and logs no loss.

Set-up draws the weights from the seed (``model.init``, jitted, on chip
0), the silos' tokens (``datagen``, the whole pool, then placed one silo a
chip), runs the jitted, sharded ``algo.init``, compiles the round ahead of
time, and drives it through its first round with the window's own
``call``. After that first round it keeps what the check compares: per
model leaf the norms of the parameters' change and of the drift. The
round logs no loss, so none is compared. ``check`` frees the program's
state and runs the plain reference over the same round on chip 0.
``calibrate_sharded.py`` reads the limits' upper readings.
"""

from __future__ import annotations

import dataclasses
import gc

import jax
import jax.numpy as jnp
import numpy as np

import compare
import datagen
from drivers import runner


def numbers(prog: dict, ref: dict) -> dict:
    """``dx_gap`` and ``d_gap`` as ``compare.numbers`` reads them: over
    model leaves (still leaves left out) the largest gap of the norms of
    the parameters' change and of the drift, over the larger of the
    reference's norm of that leaf and of the median leaf."""
    g0 = np.asarray(ref["g0"], np.float64)
    keep = g0 >= compare.STILL_LEAF * np.median(g0)

    def leaf(name):
        p = np.asarray(prog[name], np.float64)[keep]
        r = np.asarray(ref[name], np.float64)[keep]
        return np.max(np.abs(p - r) / np.maximum(r, np.median(r)))

    return {"dx_gap": compare._finite(leaf("dx")),
            "d_gap": compare._finite(leaf("d"))}


class Job:
    def __init__(self, config: dict, traffic: dict, seed: int, devices):
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from repro.configs.base import FedScenario, ShapeConfig
        from repro.core.comm import CommMeter
        from repro.launch.train import lower_train_step, make_plan
        from repro.models import build_model

        n, tau = traffic["n_clients"], traffic["tau"]
        batch, seq = traffic["batch"], traffic["seq_len"]
        if n != len(devices) or traffic["rounds_per_call"] != 1:
            raise ValueError("one silo a chip and one round a call: "
                             f"{n} silos on {len(devices)} chips, "
                             f"{traffic['rounds_per_call']} rounds a call")
        self.config, self.traffic, self.seed = config, traffic, seed
        self.rounds_per_call = 1
        self.tokens_per_call = n * tau * batch * seq
        self.mesh = Mesh(np.asarray(devices).reshape(n, 1), ("data", "model"))
        scenario = FedScenario(compression=traffic["compression"],
                               seed=traffic["scenario_seed"])
        plan = make_plan(config["arch"], self.mesh,
                         shape=ShapeConfig("silo", seq, n * batch, "train"),
                         tau=tau, alpha=traffic["alpha"], c=traffic["c"],
                         dtype=config["dtype"], scenario=scenario)
        plan = dataclasses.replace(plan, cfg=runner.program_config(config))
        self.algo = algo = plan.algo
        model = build_model(plan.cfg)
        grad_fn = jax.grad(model.loss)

        self.compiled = lower_train_step(plan).compile()
        self.peak_bytes = runner.compiled_peak_bytes(self.compiled)
        state_sh, batch_sh = self.compiled.input_shardings[0]
        params = jax.jit(model.init)(jax.random.key(seed))
        self.meter = CommMeter.for_params(params, algo=algo, n_clients=n)
        self.n_params = self.meter.n_params
        tokens = datagen.segment(
            0, seed, vocab=plan.cfg.vocab_size, n_clients=n, tau=tau,
            batch=batch, seq_len=seq, heterogeneity=traffic["heterogeneity"],
            rounds=traffic["pool_segments"])
        self.pool = [jax.device_put({"tokens": t}, batch_sh) for t in tokens]
        self.first_tokens = tokens[:1]          # the check's round, chip 0
        del tokens
        silos = NamedSharding(self.mesh, P("data"))
        with self.mesh:
            state = jax.jit(lambda p, b: algo.init(grad_fn, p, b),
                            out_shardings=state_sh)(
                params, jax.device_put({"tokens": self.first_tokens[0, 0]},
                                       silos))
        self.state, self.round = state, 0
        self.call()
        with self.mesh:
            x_norms, d_norms = jax.jit(self._leaf_norms)(self.state, params)
        self.first = {"dx": np.asarray(x_norms), "d": np.asarray(d_norms)}
        del params

    def _leaf_norms(self, state, params):
        x = self.algo.client_params(state)
        d = self.algo._inner(state).d
        norm = lambda a: jnp.sqrt(jnp.sum(jnp.square(a)))  # noqa: E731
        leaves = jax.tree.leaves
        return (jnp.stack([norm(a - p[None]) for a, p in
                           zip(leaves(x), leaves(params))]),
                jnp.stack([norm(a) for a in leaves(d)]))

    def call(self) -> int:
        """One round with the launcher's host work: dispatch, comm
        metering, and the wait for the round's state. The round logs no
        loss, so no round is counted as failed."""
        with jax.profiler.TraceAnnotation("segment_dispatch"):
            self.state = self.compiled(
                self.state, self.pool[self.round % len(self.pool)])
        with jax.profiler.TraceAnnotation("telemetry_drain"):
            self.meter.tick_round(self.algo)
        with jax.profiler.TraceAnnotation("results_fetch"):
            jax.block_until_ready(self.state)
        self.round += 1
        return 0

    def reference(self, dtype, run=None, tokens=None) -> dict:
        """The plain reference over the first round on chip 0 (``dtype``
        float32 at highest matmul precision; bfloat16 for the control);
        ``run`` is a trajectory from ``runner.reference_run`` to reuse,
        ``tokens`` the round's tokens in its place."""
        if run is None:
            run = runner.reference_run(self.config, self.traffic, dtype)
        precision = "highest" if dtype == jnp.float32 else "default"
        with jax.default_matmul_precision(precision):
            return jax.device_get(run(self.seed, self.first_tokens
                                      if tokens is None else tokens))

    def release(self) -> None:
        """Free the program's state, token pool and compiled program."""
        self.state = self.compiled = None
        self.pool = []
        gc.collect()

    def check(self, limits: dict) -> dict:
        self.release()
        return compare.judge(numbers(self.first, self.reference(jnp.float32)),
                             limits)
