"""Readings for the output check's limits, in one process (set-up is paid
per seed, compilation once): the program against the plain reference on
many seeds; on the control seeds the bfloat16 control, the half-batch
fault (the reference over half of each batch, the mean taken over the
rest) and the shifted-histogram fault (the reference's own sketch with
every count moved up one bin), each put in the program's place.

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 1,2,...,12 --control-seeds 1,2,3

Prints one JSON line per seed: the program's numbers (``program``), the
program's and the reference's per-round losses and gradient norms, and,
for a control seed, the control's and the faults' numbers (``control``,
``half_batch``, ``sketch_bin_shifted``). The limits in
``limits/<cell>.json`` are set from these readings as PERF.md records.
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import compare
import run
from drivers import runner


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp

    spec = run.cell_spec(args.workload)
    run.use_compile_cache()
    run.require_chips(spec["cell"]["chips"])
    cfg, traffic = spec["config"], spec["traffic"]
    f32 = runner.reference_run(cfg, traffic, jnp.float32)
    bf16 = runner.reference_run(cfg, traffic, jnp.bfloat16)
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in [int(s) for s in args.seeds.split(",")]:
        job = runner.Job(cfg, traffic, seed % run.SEED_RANGE, None)
        job.release()
        ref = job.reference(jnp.float32, f32)
        sk = job.sketch_spec
        line = {"seed": seed,
                "program": compare.numbers(job.first, ref, sk),
                "loss": [job.first["loss"].tolist(), ref["loss"].tolist()],
                "grad_norm": [job.first["grad_norm"].tolist(),
                              ref["grad_norm"].tolist()]}
        if seed in controls:
            line["control"] = compare.numbers(
                compare.as_program(job.reference(jnp.bfloat16, bf16), sk),
                ref, sk)
            toks = job.pool[0]["tokens"]
            half = toks[..., : toks.shape[-2] // 2, :]
            with jax.default_matmul_precision("highest"):
                got = jax.device_get(f32(job.seed, half))
            line["half_batch"] = compare.numbers(compare.as_program(got, sk),
                                                 ref, sk)
            shifted = compare.as_program(ref, sk)
            for s in compare.SOURCES:
                shifted["sketch"][s]["hist"] = np.roll(
                    shifted["sketch"][s]["hist"], 1, axis=1)
            line["sketch_bin_shifted"] = compare.numbers(shifted, ref, sk)
        print(json.dumps(line), flush=True)
        del job
    sys.stdout.flush()


if __name__ == "__main__":
    main()
