"""Benchmark harness: one module per paper table/figure + system benches.

Prints ``name,us_per_call,derived`` CSV rows. Modules:
  fig1_convergence — the paper's Fig. 1 (FedCET vs FedTrack vs SCAFFOLD)
  comm_table       — Remark 2: bytes/round per algorithm x architecture
  lr_search_bench  — Algorithm 1 output/timing across regimes
  fed_lm_bench     — federated LM round throughput + bytes-to-target-error
  comp_plan_bench  — per-leaf compression plans: budget-matched allocated
                     plan vs uniform shift:q8 on the LM track (plan must
                     win at equal-or-fewer measured bits/round)
  kernel_bench     — Pallas fedcet-update kernels (interpret mode)
  roofline_table   — (arch x shape x mesh) roofline terms from the dry-run
                     results JSON, when present
  gossip_scaling   — sparse neighbor-exchange lowering O(E) vs the dense
                     N^2 gossip contraction at N in {64, 256, 1024}
  cohort_scaling   — O(cohort) gathered round vs the dense O(N) vmap path
                     at N = 1e3..1e6 (runs late: it enables x64)
  staleness_sweep  — error floors under asynchronous rounds: delay model x
                     stale policy x compression (runs LAST: it enables x64)
  topology_sweep   — aggregation geometry: hierarchical exactness, NIDS
                     gossip rate vs spectral gap (also x64: keep last)
  telemetry_bench  — the invariant- and rate-monitor staleness
                     boundaries replayed live from one run's JSONL (also
                     x64: keep last)

After the module loop every ``results/BENCH_*.json`` merges into
``results/BENCH_trajectory.json`` — the one-file perf trajectory.

Flags:
  ``--only mod1,mod2``      run a subset of the modules above
  ``--check-drift``         after the loop, diff freshly emitted
                            ``results/BENCH_*.json`` timings against the
                            committed copies (``git show HEAD:...``) and
                            print ``# drift:`` WARN lines on regressions
                            past ``--drift-threshold`` (default 1.5x).
                            Never exits nonzero — a non-gating CI step.
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time


def check_drift(threshold: float = 1.5) -> list[str]:
    """Compare every working-tree ``results/BENCH_<name>.json`` timing
    against the committed copy (``git show HEAD:<path>``): a fresh timing
    more than ``threshold``x the committed one is flagged as a WARN line
    (``# drift: ...``). New benches / new timing keys are noted, never
    flagged. Returns the WARN lines (also printed to stderr); advisory
    only — wall-clock on shared CI runners is noisy, so this gates
    nothing."""
    from benchmarks._timing import results_dir

    import glob
    import os

    warns: list[str] = []
    for path in sorted(glob.glob(os.path.join(results_dir(),
                                              "BENCH_*.json"))):
        name = os.path.basename(path)
        if name == "BENCH_trajectory.json":
            continue
        rel = os.path.relpath(path, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        try:
            committed = json.loads(subprocess.run(
                ["git", "show", f"HEAD:{rel}"], capture_output=True,
                text=True, check=True,
                cwd=os.path.dirname(os.path.abspath(__file__))).stdout)
        except (subprocess.CalledProcessError, json.JSONDecodeError):
            # freshly added bench (not in HEAD yet — e.g. the file this
            # very run just emitted): new, skip. NOT a failure.
            print(f"# drift: new {name}: no committed baseline, skipping",
                  file=sys.stderr)
            continue
        try:
            fresh = json.loads(open(path).read())
        except (OSError, json.JSONDecodeError) as e:
            print(f"# drift: WARN {name}: unreadable working-tree file "
                  f"({e})", file=sys.stderr)
            continue
        base_t = committed.get("timings_us", {})
        for k, v in fresh.get("timings_us", {}).items():
            b = base_t.get(k)
            if b is None:
                print(f"# drift: {name}:{k}: new timing key",
                      file=sys.stderr)
                continue
            if not (isinstance(b, (int, float)) and b > 0
                    and isinstance(v, (int, float))):
                continue
            if v > b * threshold:
                w = (f"# drift: WARN {name}:{k} regressed "
                     f"{v / b:.2f}x ({b:.1f} -> {v:.1f} us, "
                     f"threshold {threshold}x)")
                warns.append(w)
                print(w, file=sys.stderr)
    if not warns:
        print(f"# drift: no regressions past {threshold}x", file=sys.stderr)
    return warns


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of bench modules to run "
                         "(e.g. 'kernel_bench,telemetry_bench')")
    ap.add_argument("--check-drift", action="store_true",
                    help="after the loop, WARN on fresh-vs-committed "
                         "BENCH_*.json timing regressions (non-gating)")
    ap.add_argument("--drift-threshold", type=float, default=1.5,
                    help="drift WARN threshold as a fresh/committed ratio")
    args = ap.parse_args(argv)

    from benchmarks import (
        cohort_scaling,
        comm_table,
        comp_plan_bench,
        fed_lm_bench,
        fig1_convergence,
        gossip_scaling,
        kernel_bench,
        lr_search_bench,
        roofline_table,
        staleness_sweep,
        telemetry_bench,
        topology_sweep,
    )
    from benchmarks._timing import aggregate_trajectory

    modules = [
        ("fig1_convergence", fig1_convergence),
        ("comm_table", comm_table),
        ("lr_search_bench", lr_search_bench),
        ("fed_lm_bench", fed_lm_bench),
        ("comp_plan_bench", comp_plan_bench),
        ("kernel_bench", kernel_bench),
        ("roofline_table", roofline_table),
        ("gossip_scaling", gossip_scaling),
        ("cohort_scaling", cohort_scaling),    # enables x64: keep last
        ("staleness_sweep", staleness_sweep),  # also x64
        ("topology_sweep", topology_sweep),    # also x64
        ("telemetry_bench", telemetry_bench),  # also x64
    ]
    if args.only:
        keep = {m.strip() for m in args.only.split(",") if m.strip()}
        unknown = keep - {n for n, _ in modules}
        if unknown:
            ap.error(f"unknown bench module(s): {sorted(unknown)}")
        modules = [(n, m) for n, m in modules if n in keep]

    rows: list[tuple] = []
    t0 = time.time()
    for name, mod in modules:
        t = time.time()
        try:
            mod.run(csv_rows=rows)
            print(f"# {name}: ok ({time.time() - t:.1f}s)", file=sys.stderr)
        except Exception as e:  # keep the harness going; report at the end
            rows.append((f"{name}/FAILED", 0.0, repr(e)[:120]))
            print(f"# {name}: FAILED {e!r}", file=sys.stderr)
    traj = aggregate_trajectory()
    if traj:
        print(f"# trajectory: {traj}", file=sys.stderr)
    if args.check_drift:
        check_drift(args.drift_threshold)
    print("name,us_per_call,derived")
    for r in rows:
        print(",".join(str(c) for c in r))
    print(f"# total {time.time() - t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
