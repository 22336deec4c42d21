"""Chip benchmark of FedCET federated training: one run of one cell.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout. The cell (``BENCHMARK.json`` ``workloads``)
names a model configuration (``configs/<config>.json``) and a traffic mix
(``traffic/<traffic>.json``); the mix names the driver
(``drivers/<driver>.py``) that builds and drives the program, and the
cell's output limits are in ``limits/<cell>.json``. Per-layer metrics are
read by ``metrics/<metric>.py``.

A run: set-up (weights, client tokens and state from the seed on the device,
compilation, the first segment of rounds), then whole segments for
``--seconds`` of host clock, then the output check against the plain
reference. With ``--trace 1`` the window runs under the JAX profiler and the
run reports the per-layer metrics instead of the end-to-end ones. The last
line of standard output is the result as one JSON object; the numbers the
check compared are the last lines of standard error. Without a TPU, or with
fewer chips than the cell asks for, the run exits non-zero and prints no
result.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]

#: seeds reach JAX as 32-bit integers; the run's seed is reduced to this
#: range (the generator adds up to 2 to it)
SEED_RANGE = 2**31 - 16


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def cell_spec(name: str) -> dict:
    """Everything one cell runs from: its BENCHMARK.json entry, its
    configuration, traffic and limits files, and the per-layer metrics
    that list it (or list no cells)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    return {
        "cell": cell,
        "config": load_json("configs", cell["config"] + ".json"),
        "traffic": load_json("traffic", cell["traffic"] + ".json"),
        "limits": load_json("limits", name + ".json"),
        "per_layer": [m["name"] for m in bench["per_layer"]
                      if name in m.get("workloads", [name])],
    }


class CompileLog:
    """Backend compiles and persistent-cache hits/misses, from JAX's
    monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def count(self) -> int:
        return self.compiles + self.hits


class GcLog:
    """Passes of Python's garbage collector: how many, their seconds and
    the longest, to tell a collection from other host stalls."""

    def __init__(self):
        self.passes, self.seconds, self.longest = 0, 0.0, 0.0
        self._start = 0.0
        gc.callbacks.append(self._callback)

    def _callback(self, phase, info):
        if phase == "start":
            self._start = time.perf_counter()
            return
        took = time.perf_counter() - self._start
        self.passes += 1
        self.seconds += took
        self.longest = max(self.longest, took)

    def close(self):
        gc.callbacks.remove(self._callback)


def use_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (or where JAX_COMPILATION_CACHE_DIR says), every program
    cached however fast it compiled."""
    import jax

    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(ROOT, ".jax-cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def require_chips(n: int) -> list:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < n:
        raise SystemExit(f"run.py: needs {n} TPU chip(s); JAX sees "
                         f"{len(devices)} {devices[0].platform} device(s)")
    return devices[:n]


def device_record(devices) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices),
            "memory_peak_bytes": int(max(peaks))}


def read_per_layer(names, ctx) -> dict:
    out = {}
    for name in names:
        reader = importlib.import_module(f"metrics.{name}")
        value = reader.read(ctx)
        if value is not None:
            out[name] = {"value": value, "unit": reader.UNIT}
    return out


def run_cell(spec: dict, seed: int, seconds: float, trace: bool, devices,
             t0: float = T0) -> dict:
    """One run of the cell described by ``spec``; returns the result line's
    object. ``devices`` are the chips the run may use."""
    import jax

    log = CompileLog()
    traffic = spec["traffic"]
    driver = importlib.import_module(f"drivers.{traffic['driver']}")
    job = driver.Job(spec["config"], traffic, seed % SEED_RANGE, devices)

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir)
    window = jax.profiler.TraceAnnotation("bench_window")
    start = time.perf_counter()
    setup_s = start - t0
    compiles0 = log.count()
    gc_log = GcLog()
    window.__enter__()
    calls = failed = 0
    ends = [start]
    while True:
        failed += job.call()
        calls += 1
        ends.append(time.perf_counter())
        if ends[-1] - start >= seconds:
            break
    window_s = ends[-1] - start
    window.__exit__(None, None, None)
    gc_log.close()
    compiles = log.count() - compiles0
    if trace:
        jax.profiler.stop_trace()
    print(f"window: {calls} segments, {calls * job.rounds_per_call} rounds "
          f"in {window_s:.3f} s; compiles in window {compiles}; set-up "
          f"{setup_s:.3f} s (backend compile {log.seconds:.1f} s, cache "
          f"hits {log.hits}, misses {log.misses})", file=sys.stderr)
    print("segment seconds: " + " ".join(
        f"{b - a:.4f}" for a, b in zip(ends, ends[1:])), file=sys.stderr)
    print(f"garbage collection in window: {gc_log.passes} passes, "
          f"{gc_log.seconds:.4f} s, longest {gc_log.longest:.4f} s",
          file=sys.stderr)

    record = device_record(devices)
    rounds = calls * job.rounds_per_call
    tokens_per_s = calls * job.tokens_per_call / window_s
    ctx = {"job": job, "config": spec["config"], "traffic": traffic,
           "rounds": rounds, "window_s": window_s,
           "tokens_per_s": tokens_per_s, "chips": len(devices)}
    breakdown = None
    if trace:
        import tracefile

        ctx["peaks"] = load_json("peaks.json")[record["kind"]]
        tr = tracefile.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx["trace"] = tr
        metrics = read_per_layer(spec["per_layer"], ctx)
        record["busy_s"] = sum(tracefile.busy_s(tr, i)
                               for i in range(len(tr.devices))) / len(tr.devices)
        record["window_s"] = tr.window_s
        breakdown = tracefile.breakdown(tr)
    else:
        metrics = {"tokens_per_s": {"value": tokens_per_s, "unit": "tokens/s"},
                   "setup_s": {"value": setup_s, "unit": "s"}}

    numbers = job.check(spec["limits"])
    correct = (failed == 0 and all(
        n["value"] is not None and n["value"] <= n["limit"]
        for n in numbers.values()))
    result = {"correct": correct, "attempted": rounds, "failed": failed,
              "metrics": metrics, "device": record}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = numbers
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = cell_spec(args.workload)
    use_compile_cache()
    devices = require_chips(spec["cell"]["chips"])
    result = run_cell(spec, args.seed, args.seconds, bool(args.trace),
                      devices)
    for name, n in result["check"].items():
        print(f"check: {name} {n['value']} limit {n['limit']}",
              file=sys.stderr)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
