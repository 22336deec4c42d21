"""Ambient activation-sharding context.

Model code is mesh-agnostic; the launch drivers (dryrun/train/serve) enable
activation constraints for the production mesh via::

    with activation_sharding(residual=P(None, "model", None)):
        ... trace/lower the step ...

and the model blocks call ``shard_residual(x)`` on the residual stream at
layer boundaries. On CPU tests (no context) it is the identity. The default
production spec shards the SEQUENCE dimension over the `model` axis between
layers (Megatron-style sequence parallelism): with remat + scan-over-layers
the per-layer saved carry is the residual stream, so sequence-sharding it is
what keeps multi-B-parameter training inside HBM (see EXPERIMENTS.md
§Dry-run for the before/after).
"""

from __future__ import annotations

import contextlib
import threading

import jax

_state = threading.local()


@contextlib.contextmanager
def activation_sharding(residual=None, logits=None, moe_shards=None):
    """moe_shards: optional ('batch'|'seq', n_shards) enabling the
    locality-preserving token-sharded MoE dispatch (see models/moe.py)."""
    prev = (getattr(_state, "residual", None), getattr(_state, "logits", None),
            getattr(_state, "moe_shards", None))
    _state.residual = residual
    _state.logits = logits
    _state.moe_shards = moe_shards
    try:
        yield
    finally:
        _state.residual, _state.logits, _state.moe_shards = prev


def moe_shards():
    return getattr(_state, "moe_shards", None)


def meshed() -> bool:
    """Whether a production-mesh lowering has set the activation specs."""
    return getattr(_state, "residual", None) is not None


def shard_residual(x):
    spec = getattr(_state, "residual", None)
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def shard_logits(x):
    """Per-chunk CE logits: vocab over `model` (the residual constraint moves
    the model axis to seq, so un-constrained logits would replicate the
    vocab dim — 13 GB/device at llama4's 202k vocab)."""
    spec = getattr(_state, "logits", None)
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)
