"""Compile a chip-benchmark cell's round program for a described TPU v5e,
without the chip, and summarize what the compiler made of it.

    JAX_PLATFORMS=cpu python benchmarks/describe_cell.py \
        --workload fedlm-100m.c4-tau16 [--root CHECKOUT] [--hlo OUT.hlo]

The program is the one ``benchmarks/chip/drivers/runner.py`` (or, for a
``moe`` cell, ``drivers/moe.py``) runs: ``make_round_runner`` over the
cell's K-round segment, with the TPU kernels forced on as on the chip.
``--root`` compiles another checkout's program (say, a parent unpacked
with ``git archive``) with that checkout's code. One JSON line is
printed: the compiled peak (what ``hbm_gb`` reads on the chip), the
compiler's estimated cycles summed over the instructions of each
``fed.*`` scope (a loop body counted once, whatever its trip count), the
count of instructions of the flat ``[clients, rows * 1024]`` arena
shape, and the seconds the compile took on this host, which say little
about set-up on the chip's host. ``--hlo`` writes the optimized HLO,
whose instructions carry their layouts, memory spaces (``S(1)`` is
VMEM) and estimated cycles.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time
from collections import defaultdict


def scope_cycles(hlo: str) -> dict:
    """Estimated cycles summed by the innermost ``fed.*`` scope of each
    instruction (``unscoped`` where there is none)."""
    out = defaultdict(int)
    for line in hlo.splitlines():
        if not re.match(r"\s*(?:ROOT )?%\S+ = ", line):
            continue
        c = re.search(r'"estimated_cycles":"(\d+)"', line)
        if c:
            scopes = re.findall(r"fed\.[a-z_]+", line)
            out[scopes[-1] if scopes else "unscoped"] += int(c.group(1))
    return dict(sorted(out.items()))


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--root", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--hlo", default=None)
    args = ap.parse_args(argv)
    root = os.path.abspath(args.root)
    sys.path[:0] = [os.path.join(root, "benchmarks/chip"),
                    os.path.join(root, "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import run
    from drivers import runner
    from repro.configs.base import FedScenario
    from repro.core.api import StatsGrad
    from repro.core.arena import ArenaLayout
    from repro.core.engine import make_round_runner
    from repro.core.fedcet import FedCET
    from repro.kernels import ops
    from repro.models import build_model

    # the kernels as on the chip: compiled, not interpreted
    ops._interpret = lambda: False
    use_kernel = ops._use_kernel
    ops._use_kernel = lambda impl: impl == "auto" or use_kernel(impl)

    spec = run.cell_spec(args.workload)
    traffic = spec["traffic"]
    model = build_model(runner.program_config(spec["config"]))
    grad_fn = (StatsGrad(model.loss_and_stats)
               if traffic.get("driver") == "moe" else jax.grad(model.loss))
    k, n, tau = traffic["rounds_per_call"], traffic["n_clients"], traffic["tau"]
    algo = FedScenario(
        compression=traffic["compression"], arena=traffic["arena"],
        telemetry=traffic["telemetry"], seed=traffic["scenario_seed"],
    ).apply(FedCET(alpha=traffic["alpha"], c=traffic["c"], tau=tau,
                   n_clients=n))

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])
    sds = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=dev), t)
    params = sds(jax.eval_shape(model.init, jax.random.key(0)))
    pool = {"tokens": jax.ShapeDtypeStruct(
        (k, tau, n, traffic["batch"], traffic["seq_len"]), jnp.int32,
        sharding=dev)}
    first = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape[2:], a.dtype, sharding=dev), pool)

    def round_loss(s, b):
        last = jax.tree.map(lambda a: a[-1], b)
        return jnp.mean(jax.vmap(model.loss)(algo.client_params(s), last))

    t0 = time.time()
    with jax.enable_x64(False):
        state = sds(jax.eval_shape(lambda p, b: algo.init(grad_fn, p, b),
                                   params, first))
        compiled = make_round_runner(
            algo, grad_fn, metric_fn=round_loss, metric_with_batch=True,
            metric_before=True, donate=True).lower(state, pool).compile()
    seconds = time.time() - t0
    hlo = compiled.as_text()
    if args.hlo:
        with open(args.hlo, "w") as f:
            f.write(hlo)
    rows = ArenaLayout.for_tree(params).rows
    flat = re.compile(rf"^\s*(?:ROOT )?%\S+ = f32\[{n},{rows * 1024}\]", re.M)
    cycles = scope_cycles(hlo)
    out = {"workload": args.workload, "root": root, "rows": rows,
           "peak_gb": compiled.memory_analysis().peak_memory_in_bytes / 1e9,
           "flat_instructions": len(flat.findall(hlo)),
           "estimated_cycles": sum(cycles.values()),
           "estimated_cycles_by_scope": cycles,
           "host_compile_s": round(seconds, 1)}
    print(json.dumps(out))
    return out


if __name__ == "__main__":
    main()
