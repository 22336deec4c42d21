import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture x input shape x mesh) combination this lowers and
compiles the production step — the federated FedCET train round for
train_4k, serve prefill for prefill_32k, one-token cached serve_step for
decode_32k / long_500k — against 512 placeholder host devices, then records

  * compiled.memory_analysis()  (per-device bytes: proves it fits),
  * compiled.cost_analysis()    (raw XLA numbers, loop-undercount caveat),
  * the collective schedule parsed from the compiled HLO
    (loop-multiplier-corrected byte totals per collective kind),
  * the three roofline terms (analytic FLOPs/HBM model + parsed collectives)

into a JSON results file consumed by EXPERIMENTS.md and
benchmarks/roofline_table.py.

Usage:
  python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k
  python -m repro.launch.dryrun --all --out results/dryrun.json
  python -m repro.launch.dryrun --all --multi-pod
"""

import argparse
import json
import time
import traceback


def run_one(arch: str, shape_name: str, *, multi_pod: bool,
            verbose: bool = True) -> dict:
    import jax

    from repro.configs import INPUT_SHAPES, get_config, supports_shape
    from repro.launch.mesh import make_production_mesh
    from repro.roofline.analysis import analyze_compiled
    from repro.roofline.flops import cost_for

    mesh_name = "2x16x16" if multi_pod else "16x16"
    shape = INPUT_SHAPES[shape_name]
    cfg = get_config(arch).with_dtype("bfloat16")
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "status": "ok"}

    ok, why = supports_shape(cfg, shape)
    if not ok:
        rec.update(status="skipped", reason=why)
        if verbose:
            print(f"[dryrun] SKIP {arch} x {shape_name} x {mesh_name}: {why}")
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_devices = mesh.size
    t0 = time.time()
    if shape.kind == "train":
        from repro.launch.train import lower_train_step, make_plan

        plan = make_plan(arch, mesh, shape=shape_name)
        # donation off: CPU memory_analysis double-counts aliased carries,
        # and the dry-run's recorded numbers predate donation.
        lowered = lower_train_step(plan, donate=False)
    elif shape.kind == "prefill":
        from repro.launch.serve import lower_prefill

        lowered = lower_prefill(arch, mesh, shape_name=shape_name)
    else:
        from repro.launch.serve import lower_decode

        lowered = lower_decode(arch, mesh, shape_name=shape_name)
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    raw_cost = compiled.cost_analysis()
    if isinstance(raw_cost, (list, tuple)):
        raw_cost = raw_cost[0] if raw_cost else {}
    hlo = compiled.as_text()
    cost = cost_for(cfg, shape, n_devices=n_devices)
    report = analyze_compiled(
        arch=arch, shape=shape_name, mesh_name=mesh_name,
        n_devices=n_devices, cost=cost, hlo_text=hlo, memory_stats=mem,
        raw_cost=raw_cost)

    if verbose:
        print(f"[dryrun] {arch} x {shape_name} x {mesh_name}: "
              f"lower {t_lower:.1f}s compile {t_compile:.1f}s")
        print(f"  memory_analysis: args={mem.argument_size_in_bytes/1e9:.3f}GB "
              f"temp={mem.temp_size_in_bytes/1e9:.3f}GB "
              f"out={mem.output_size_in_bytes/1e9:.3f}GB per device")
        print(f"  cost_analysis:   flops={raw_cost.get('flops', 0):.3e} "
              f"(raw, loop bodies counted once)")
        print(f"  collectives:     {report.collective_detail['bytes_by_kind']}")
        print(f"  roofline terms:  compute={report.compute_s*1e3:.3f}ms "
              f"memory={report.memory_s*1e3:.3f}ms "
              f"collective={report.collective_s*1e3:.3f}ms "
              f"-> {report.bottleneck}-bound")

    rec.update(
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        memory={
            "argument_bytes": int(mem.argument_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
        },
        roofline=report.as_dict(),
    )
    return rec


def merge_results(path: str, records: list[dict]) -> None:
    data = {}
    if os.path.exists(path):
        with open(path) as f:
            data = json.load(f)
    for r in records:
        data[f"{r['arch']}|{r['shape']}|{r['mesh']}"] = r
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(data, f, indent=1)
    os.replace(tmp, path)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="every (arch x shape) for the chosen mesh")
    ap.add_argument("--out", default="results/dryrun.json")
    args = ap.parse_args(argv)

    from repro.configs import ASSIGNED, INPUT_SHAPES

    if args.all:
        combos = [(a, s) for a in ASSIGNED for s in INPUT_SHAPES]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        combos = [(args.arch, args.shape)]

    records, failures = [], 0
    for arch, shape in combos:
        try:
            rec = run_one(arch, shape, multi_pod=args.multi_pod)
        except Exception as e:  # a failure here is a sharding bug: report it
            failures += 1
            rec = {"arch": arch, "shape": shape,
                   "mesh": "2x16x16" if args.multi_pod else "16x16",
                   "status": "error", "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-2000:]}
            print(f"[dryrun] ERROR {arch} x {shape}: {e}")
        records.append(rec)
        merge_results(args.out, records)  # persist incrementally
    print(f"[dryrun] done: {len(records) - failures}/{len(records)} OK "
          f"-> {args.out}")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
