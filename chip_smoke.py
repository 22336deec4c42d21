"""Smoke run of the FedCET training path on a TPU.

Run from anywhere, with the checkout's own ``src/`` on the path:

    python chip_smoke.py             # one chip: main path + fused round tail
    python chip_smoke.py --chips 4   # four chips: the sharded round only

One chip:

* **main path** — ``repro.launch.train.run_training`` (what ``python -m
  repro.launch.train --full`` runs) on fedlm-100m at its published widths
  (14 layers, d_model 640, vocab 16384, ~107M parameters): FedCET with
  shift:q8 compression over the packed parameter arena, telemetry with the
  per-client sketches (the Pallas ``client_sketch`` kernel runs in place).
  Fails unless every round's loss is finite, the last round's loss is below
  the first's, and the logged ``sum_i d_i = 0`` invariant residual (Lemma
  2) stays within ``RESIDUAL_TOL``.
* **kernel** — the fused FedCET round tail (``impl="kernel"``, a Mosaic
  kernel) against its reference (``impl="ref"``) at the arena shape of the
  run above. Fails unless the compiled program holds the kernel
  (``tpu_custom_call``) and the two agree within the stated tolerance.

Four chips (``--chips 4``): the sharded round of ``make_plan`` +
``lower_train_step`` on a ``(4, 1)`` ``("data", "model")`` mesh, one client
per chip, against the same round run by ``algo.round`` on one chip.

The script fails, printing no result, when JAX finds no TPU. Lines that
start with ``info:`` are information, not metrics. The last line of
standard output is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import math
import os
import statistics
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "fedlm-100m"
#: memory_analysis() for a described v5e chip at 4 clients: 14.89 GB for
#: the round program (5.14 GB of donated state + 9.75 GB of temporaries),
#: 14.34 GB for the jitted state init — within one chip's 16 GB.
CLIENTS = 4
#: A step size at which the logged loss (on tokens no step has seen yet)
#: falls within a few rounds; at 1e-2 it barely moves in tens of steps.
ROUNDS, TAU, BATCH, SEQ = 10, 2, 8, 128
ALPHA, C = 0.3, 0.05
#: ||mean_i d_i|| / mean_i ||d_i|| in float32: the drift updates
#: redistribute exactly (Lemma 2), so only f32 rounding of the per-element
#: ``recon_i - mean`` differences is left.
RESIDUAL_TOL = 1e-3
#: fused tail vs reference: elements further apart than KERNEL_ATOL (a few
#: f32 ulp at the O(1) test values) must be rarer than KERNEL_FLIP_FRAC,
#: and none may be further apart than one quantization step — a 1-ulp
#: difference in ``(v - h) / scale`` can send a dithered code to the other
#: side of its floor.
KERNEL_ATOL = 1e-6
KERNEL_FLIP_FRAC = 1e-5
TAIL = dict(c=C, alpha=ALPHA, beta=0.5, bits=8)
#: four chips vs one: max |sharded - one chip| over the state relative to
#: max |x|, and the relative loss gap (float32 with "highest" matmul
#: precision; only reduction orders differ).
SHARD_RTOL = 1e-5


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileLog:
    """Backend compile seconds and persistent-cache hits/misses, from JAX's
    monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def report(self, phase: str) -> None:
        print(f"info: {phase}: backend compile {self.seconds:.1f} s, "
              f"persistent cache hits {self.hits}, misses {self.misses}")


def main_path(out_dir: str) -> None:
    from repro.launch.train import run_training

    log = CompileLog()
    jsonl = os.path.join(out_dir, "smoke.jsonl")
    stamps, losses = [], []

    def on_round(_round, loss, _bytes):
        stamps.append(time.perf_counter())
        losses.append(loss)

    print(f"info: main path: {ARCH} at published widths, {CLIENTS} clients, "
          f"{ROUNDS} rounds at tau={TAU}, batch {BATCH} x seq {SEQ}")
    run_training(ARCH, steps=ROUNDS, tau=TAU, n_clients=CLIENTS,
                 batch=BATCH, seq_len=SEQ, alpha=ALPHA, c=C, reduced=False,
                 compression="shift:q8", arena=True,
                 telemetry=f"jsonl:{jsonl},hist:48,topk:4", log_every=1,
                 callback=on_round)
    with open(jsonl) as f:
        events = [json.loads(line) for line in f]
    rounds = [e for e in events if e["event"] == "round"]
    residual = max(e["invariant_residual"] for e in rounds)
    print("info: loss by round " + " ".join(f"{x:.6f}" for x in losses))
    print(f"info: max invariant residual {residual:.3e} "
          f"(tolerance {RESIDUAL_TOL:.0e})")
    log.report("main path")
    if len(stamps) > 2:
        steady = statistics.median(b - a for a, b in zip(stamps[1:],
                                                         stamps[2:]))
        print(f"info: host seconds per steady round {steady:.3f} (callback "
              f"to callback; includes host batch synthesis)")
    stats = jax.devices()[0].memory_stats() or {}
    for k in ("peak_bytes_in_use", "bytes_limit"):
        if k in stats:
            print(f"info: {k} {stats[k]}")
    check(len(losses) == ROUNDS and len(rounds) == ROUNDS,
          f"{len(losses)} losses, {len(rounds)} round events for {ROUNDS}")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    check(residual <= RESIDUAL_TOL,
          f"invariant residual {residual} > {RESIDUAL_TOL}")


def kernel_phase() -> None:
    from repro.configs import get_config
    from repro.core.arena import LANES, ArenaLayout
    from repro.kernels import ops as kops
    from repro.models import build_model

    model = build_model(get_config(ARCH))
    rows = ArenaLayout.for_tree(
        jax.eval_shape(model.init, jax.random.key(0))).rows
    print(f"info: kernel: fused round tail at [{CLIENTS}, {rows}, {LANES}]")

    @jax.jit
    def inputs(key):
        kv, kh, kd, ku = jax.random.split(key, 4)
        shape = (CLIENTS, rows, LANES)
        v = jax.random.normal(kv, shape)
        h = 0.5 * jax.random.normal(kh, shape)
        d = 0.01 * jax.random.normal(kd, shape)
        u = jax.random.uniform(ku, (rows, LANES))
        scale = jnp.max(jnp.abs(v - h), axis=(0, 2))[:, None] / 127.0
        w = jnp.ones((CLIENTS, 1))
        den = jnp.full((1, 1), float(CLIENTS))
        return v, h, d, u, scale, w, den

    # one output against the reference at a time: the three reference
    # outputs and the kernel's would not fit beside the inputs at 4 clients
    @functools.partial(jax.jit, static_argnums=0)
    def gap(i, out, *args):
        diff = jnp.abs(out - kops.fedcet_round_tail(*args, impl="ref",
                                                    **TAIL)[i])
        return jnp.max(diff), jnp.sum(diff > KERNEL_ATOL)

    args = inputs(jax.random.key(1))
    kernel = jax.jit(functools.partial(
        kops.fedcet_round_tail, impl="kernel", **TAIL)).lower(*args).compile()
    check("tpu_custom_call" in kernel.as_text(),
          "the fused tail's program holds no Mosaic kernel")
    max_gap, n_far = zip(*(jax.device_get(gap(i, out, *args))
                           for i, out in enumerate(kernel(*args))))
    step = max(TAIL["c"], TAIL["beta"]) * float(jnp.max(args[4]))
    total = 3 * CLIENTS * rows * LANES
    far = int(sum(n_far))
    print(f"info: kernel vs ref: max |gap| (d', x', h') "
          + " ".join(f"{float(g):.3e}" for g in max_gap)
          + f"; {far} of {total} elements beyond {KERNEL_ATOL:.0e}; "
          f"one quantization step {step:.3e}")
    check(far <= KERNEL_FLIP_FRAC * total,
          f"{far} of {total} elements differ by more than {KERNEL_ATOL}")
    check(max(float(g) for g in max_gap) <= step + KERNEL_ATOL,
          f"kernel gap {max_gap} exceeds one quantization step {step}")


def four_chips() -> None:
    from repro.configs.base import ShapeConfig
    from repro.core.fedcet import FedCETState
    from repro.data.synthetic import make_hetero_lm_dataset
    from repro.launch.mesh import make_test_mesh
    from repro.launch.train import lower_train_step, make_plan
    from repro.models import build_model

    check(len(jax.devices()) == 4, f"{len(jax.devices())} devices, want 4")
    log = CompileLog()
    mesh = make_test_mesh((4, 1), ("data", "model"))
    shape = ShapeConfig("smoke", SEQ, 4 * BATCH, "train")
    with jax.default_matmul_precision("highest"):
        plan = make_plan(ARCH, mesh, shape=shape, tau=TAU, alpha=ALPHA, c=C,
                         dtype="float32")
        print(f"info: four chips: {plan.cfg.name} at published widths, "
              f"{plan.n_clients} clients on mesh {dict(mesh.shape)}, "
              f"batch {plan.per_client_batch} x seq {plan.seq_len}")
        sharded_round = lower_train_step(plan).compile()
        state_sh, batch_sh = sharded_round.input_shardings[0]

        model = build_model(plan.cfg)
        grad_fn = jax.grad(model.loss)
        one_chip = dataclasses.replace(plan.algo, spmd_client_axes=())
        params = model.init(jax.random.key(0))
        n = plan.n_clients
        state0 = FedCETState(
            x=jax.tree.map(lambda p: jnp.stack([p] * n), params),
            d=jax.tree.map(lambda p: jnp.zeros((n,) + p.shape, p.dtype),
                           params),
            t=jnp.asarray(0))
        ds = make_hetero_lm_dataset(plan.cfg.vocab_size, n, plan.seq_len,
                                    plan.per_client_batch, seed=0)
        batches = {"tokens": ds.sample_round(0, TAU)}
        # t from the host: a replicated put of state0.t would share its
        # device-0 buffer, which the reference's donation deletes.
        state_in = jax.device_put(state0._replace(t=np.asarray(state0.t)),
                                  state_sh)
        batches_in = jax.device_put(batches, batch_sh)
        del params

        def mean_loss(s, b):
            b0 = jax.tree.map(lambda a: a[0], b)
            return jnp.mean(jax.vmap(model.loss)(s.x, b0))

        # the one-chip round of four clients takes 15.0 GB undonated
        @functools.partial(jax.jit, donate_argnums=0)
        def reference(s, b):
            s = one_chip.round(grad_fn, s, b)
            return s, mean_loss(s, b)

        ref_state, ref_loss = reference(state0, batches)
        got = sharded_round(state_in, batches_in)
        got_loss = jax.jit(mean_loss)(got, batches_in)

    for name in ("x", "d"):
        for leaf in jax.tree.leaves(getattr(got, name)):
            check(len(leaf.sharding.device_set) == 4,
                  f"{name} leaf on {len(leaf.sharding.device_set)} devices")
            check(leaf.addressable_shards[0].data.shape[0] == 1,
                  f"{name} leaf not split one client per chip: "
                  f"{leaf.sharding}")
    # d accumulates C * (v_i - mean v): differences of x-sized vectors,
    # so both fields are held to the scale of x (d's times C).
    x_got, d_got = jax.device_get((got.x, got.d))
    x_ref, d_ref = jax.device_get((ref_state.x, ref_state.d))
    scale = max(float(np.max(np.abs(a))) for a in jax.tree.leaves(x_ref))
    tol = {"x": SHARD_RTOL * scale, "d": SHARD_RTOL * C * scale}
    gap = {name: max(float(np.max(np.abs(a - b))) for a, b in zip(
        jax.tree.leaves(mine), jax.tree.leaves(theirs)))
        for name, mine, theirs in (("x", x_got, x_ref), ("d", d_got, d_ref))}
    loss_gap = abs(float(got_loss) - float(ref_loss)) / abs(float(ref_loss))
    for name in ("x", "d"):
        print(f"info: four chips vs one: {name} max |gap| {gap[name]:.3e} "
              f"(tolerance {tol[name]:.3e})")
    print(f"info: four chips vs one: loss {float(got_loss):.6f} vs "
          f"{float(ref_loss):.6f}, relative gap {loss_gap:.3e}")
    log.report("four chips")
    for name in ("x", "d"):
        check(gap[name] <= tol[name],
              f"{name}: sharded round differs by {gap[name]} > {tol[name]}")
    check(loss_gap <= SHARD_RTOL, f"loss differs by {loss_gap} relative")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded round on a four-chip host")
    ap.add_argument("--out", default=os.path.join(ROOT, "smoke_out"),
                    help="directory for the run's telemetry JSONL")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX sees {dev.platform})")
    from repro.launch.train import use_compile_cache

    print(f"info: compile cache {use_compile_cache()}")
    if args.chips == 4:
        phases = [("four chips", four_chips)]
    else:
        phases = [("main path", functools.partial(main_path, args.out)),
                  ("kernel", kernel_phase)]
    # every phase runs, so one call reports them all; any failure still
    # ends the script non-zero, with no result line
    failed = []
    for name, phase in phases:
        try:
            phase()
        except Exception as e:
            traceback.print_exc()
            failed.append(f"{name}: {e}")
    if failed:
        raise SystemExit("chip_smoke FAILED\n" + "\n".join(failed))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
