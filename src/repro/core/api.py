"""Federated-algorithm API: the consumer-facing protocol.

Every algorithm in this framework (FedCET and the baselines it is compared
against in the paper: FedAvg, SCAFFOLD, FedTrack, FedLin) presents the same
functional interface so drivers, benchmarks and the distributed launcher can
swap them via config:

* state is a *stacked* pytree — every per-client leaf has a leading
  ``clients`` axis (plus a scalar step counter ``t``);
* ``init(grad_fn, x0, init_batch)`` builds per-client state from a single
  set of initial parameters (replicated, then algorithm-specific warm-up);
* ``round(grad_fn, state, batches)`` runs one *communication round*:
  ``tau`` local gradient steps plus exactly one aggregation. ``batches`` is a
  pytree whose leaves have leading axes ``[tau, clients, ...]`` (full-batch
  callers simply broadcast the same batch ``tau`` times);
* communication cost is exposed *declaratively* via ``vectors_up`` /
  ``vectors_down`` (number of n-dimensional vectors moved per client per
  round) and the transform-aware ``up_frac``, so the benchmark harness can
  account bytes without tracing.

Algorithms do NOT hand-roll ``init``/``round``: they are slim specs —
``init_warmup`` / ``local_step`` / ``message`` / ``server_aggregate`` (and
optionally ``begin_round``) — on top of :class:`repro.core.engine.RoundEngine`,
which owns the round structure once: batch slicing, the ``vmap_grads`` lift,
the ``lax.scan`` over the tau-1 local steps, the single aggregating step,
message transforms (``with_compression``), client sampling
(``with_participation``), delayed uplinks (``with_delay``) and the
aggregation geometry (``with_topology`` — hierarchical tiers / gossip
mixing). See engine.py's module docstring and ARCHITECTURE.md for the
decomposition and the transform-composition rules.
Multi-round execution likewise goes through one shared scan-based driver,
``engine.run_rounds``, consumed by ``core/simulate.py``, ``fed/trainer.py``
and ``launch/train.py`` alike.

``grad_fn(params, batch) -> grads`` takes a SINGLE client's parameters; the
engine vmaps it over the client axis. Under ``pjit`` the vmapped axis is
sharded over the client mesh axes, and the aggregation's ``tree_client_mean``
lowers to the only collective that crosses the pod boundary.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Protocol, runtime_checkable

import jax

GradFn = Callable[[Any, Any], Any]  # (params, batch) -> grads, single client
AlgState = Any


@dataclasses.dataclass(frozen=True)
class StatsGrad:
    """A grad_fn of a loss that also returns per-call counters:
    ``loss_and_stats(params, batch) -> (loss, {name: array})``. Called, it
    gives ``(grads, counters)``; the engine keeps the gradient and passes
    the counters to its telemetry (``repro.core.telemetry.count``), which
    sums them over clients and local steps into the round's event."""

    loss_and_stats: Callable

    def __call__(self, params, batch):
        return jax.grad(self.loss_and_stats, has_aux=True)(params, batch)


@runtime_checkable
class FederatedAlgorithm(Protocol):
    """Structural interface shared by FedCET and all baselines."""

    name: str
    tau: int
    #: n-dimensional vectors transmitted per client per round (client->server).
    vectors_up: int
    #: n-dimensional vectors transmitted per client per round (server->client).
    vectors_down: int

    def init(self, grad_fn: GradFn, x0, init_batch) -> AlgState: ...

    def round(self, grad_fn: GradFn, state: AlgState, batches) -> AlgState: ...

    def global_params(self, state: AlgState): ...


def vmap_grads(grad_fn: GradFn, spmd_axis_name=None) -> GradFn:
    """Lift a single-client grad_fn to stacked [clients, ...] pytrees.

    ``spmd_axis_name`` (the mesh axes carrying the client dimension, e.g.
    ("pod", "data")) lets GSPMD pin the vmapped axis for every sharding
    decision inside the per-client computation — used by the production
    launcher; simulation callers leave it None."""
    return jax.vmap(grad_fn, in_axes=(0, 0), spmd_axis_name=spmd_axis_name)


def replicate(x0, n_clients: int):
    """Stack a single parameter pytree into [n_clients, ...]."""
    import jax.numpy as jnp

    return jax.tree.map(lambda a: jnp.broadcast_to(a[None], (n_clients,) + a.shape), x0)


def comm_bytes_per_round(algo: FederatedAlgorithm, n_params: int,
                         itemsize: int = 4, n_clients: int = 1) -> dict:
    """Bytes moved per communication round (Remark 2 accounting)."""
    up = algo.vectors_up * n_params * itemsize * n_clients
    down = algo.vectors_down * n_params * itemsize * n_clients
    return {"up": up, "down": down, "total": up + down}


@dataclasses.dataclass(frozen=True)
class RoundMetrics:
    """Optional per-round diagnostics emitted by drivers."""

    round_index: int
    error_to_opt: float | None = None
    grad_norm: float | None = None
    bytes_up: int = 0
    bytes_down: int = 0
