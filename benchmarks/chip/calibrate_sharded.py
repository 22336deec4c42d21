"""Upper readings for a silo cell's output check (``drivers/sharded.py``),
on one chip at the cell's size: the plain reference over the cell's first
round in float32 at highest precision, against three stand-ins put in the
program's place, each over the same round:

* ``control``: the reference in bfloat16 at default precision;
* ``half_batch``: the reference over the first half of each silo's batch;
* ``no_exchange``: each silo's round alone, its clients' mean its own
  message, as a round whose cross-chip all-reduce is left out would run
  (the parameters' change and the drift of all silos together).

    python3 benchmarks/chip/calibrate_sharded.py \
        --workload fedlm-100m.silo4-tau16 --seeds 1,2,3

Prints one JSON line per seed, ``sharded.numbers`` of each stand-in
against the float32 reference. The sound readings are the ``check`` of
the cell's own runs, which need the cell's chips. The limits in
``limits/<cell>.json`` lie between the two, as PERF.md records.
"""

from __future__ import annotations

import argparse
import json

import numpy as np

import datagen
import run
from drivers import runner, sharded


def first_round(config: dict, traffic: dict, seed: int) -> np.ndarray:
    """The tokens of the cell's first round, as ``sharded.Job`` draws them:
    [1, tau, silos, batch, seq]."""
    return datagen.segment(
        0, seed, vocab=runner.program_config(config).vocab_size,
        n_clients=traffic["n_clients"], tau=traffic["tau"],
        batch=traffic["batch"], seq_len=traffic["seq_len"],
        heterogeneity=traffic["heterogeneity"],
        rounds=traffic["pool_segments"])[:1]


def readings(config: dict, traffic: dict, seeds):
    """One line of readings a seed, as each is read."""
    import jax
    import jax.numpy as jnp

    f32 = runner.reference_run(config, traffic, jnp.float32)
    bf16 = runner.reference_run(config, traffic, jnp.bfloat16)

    def highest(toks, seed):
        with jax.default_matmul_precision("highest"):
            return jax.device_get(f32(seed, toks))

    for s in seeds:
        seed = s % run.SEED_RANGE
        toks = first_round(config, traffic, seed)
        ref = highest(toks, seed)
        with jax.default_matmul_precision("default"):
            control = jax.device_get(bf16(seed, toks))
        half = highest(toks[..., : toks.shape[-2] // 2, :], seed)
        alone = [highest(toks[:, :, i:i + 1], seed)
                 for i in range(toks.shape[2])]
        no_exchange = {name: np.sqrt(sum(np.square(np.asarray(
            a[name], np.float64)) for a in alone)) for name in ("dx", "d")}
        yield {"seed": s, **{
            name: sharded.numbers(got, ref) for name, got in
            (("control", control), ("half_batch", half),
             ("no_exchange", no_exchange))}}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    spec = run.cell_spec(args.workload)
    run.use_compile_cache()
    for line in readings(spec["config"], spec["traffic"],
                         [int(s) for s in args.seeds.split(",")]):
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
