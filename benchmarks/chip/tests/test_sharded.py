"""The four-chip silo driver (``drivers/sharded.py``) on four virtual CPU
devices at reduced sizes, in a process of its own (the device count is
fixed when JAX starts): one silo a device, the state sharded so, and its
check against the plain reference. And the stand-ins of
``calibrate_sharded.py`` at the same sizes: each fails the cell's
limits."""

import json
import os
import subprocess
import sys

import calibrate_sharded
import run

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
import json, sys
import jax
import run
from drivers import sharded

spec = run.cell_spec("fedlm-100m.silo4-tau16")
spec["config"].update(CONFIG)
spec["traffic"].update(TRAFFIC)
devices = jax.devices()[:4]
job = sharded.Job(spec["config"], spec["traffic"],
                  (2**33 + 7) % run.SEED_RANGE, devices)
leaves = jax.tree.leaves(job.state)
out = {
    "devices": sorted({len(a.sharding.device_set) for a in leaves
                       if a.ndim > 0}),
    "rows_per_device": sorted({a.addressable_shards[0].data.shape[0]
                               for a in leaves if a.ndim > 0}),
    "peak_bytes": job.peak_bytes, "n_params": job.n_params,
    "tokens_per_call": job.tokens_per_call,
    "rounds_per_call": job.rounds_per_call,
    "failed": job.call(),
    "round": job.round,
    "check": job.check(spec["limits"]),
}
print(json.dumps(out))
"""

CELL = "fedlm-100m.silo4-tau16"
CONFIG = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
              d_ff=128, vocab_size=256)
TRAFFIC = dict(tau=2, batch=2, seq_len=32)


def test_four_silos_on_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [HERE, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                       "src")]))
    script = SCRIPT.replace("CONFIG", repr(CONFIG)).replace(
        "TRAFFIC", repr(TRAFFIC))
    proc = subprocess.run([sys.executable, "-c", script], env=env, cwd=HERE,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    # every client-stacked leaf on all four devices, one silo on each
    assert out["devices"] == [4] and out["rows_per_device"] == [1]
    assert out["peak_bytes"] > 0 and out["n_params"] > 0
    assert out["tokens_per_call"] == 4 * 2 * 2 * 32
    assert out["rounds_per_call"] == 1
    assert out["failed"] == 0 and out["round"] == 2
    assert set(out["check"]) == {"dx_gap", "d_gap"}
    for n in out["check"].values():
        assert n["value"] is not None and n["value"] < 1e-4, out["check"]


def test_stand_ins_fail_the_cell_limits():
    spec = run.cell_spec(CELL)
    spec["config"].update(CONFIG)
    spec["traffic"].update(TRAFFIC)
    limits = spec["limits"]
    (line,) = calibrate_sharded.readings(spec["config"], spec["traffic"],
                                         [2**33 + 7])
    assert set(line) == {"seed", "control", "half_batch", "no_exchange"}
    for name in ("control", "half_batch", "no_exchange"):
        got = line[name]
        assert set(got) == set(limits), (name, got)
        assert any(got[k] is None or got[k] > lim
                   for k, lim in limits.items()), (name, got)
    # no exchange leaves every silo's drift at zero
    assert line["no_exchange"]["d_gap"] == 1.0
