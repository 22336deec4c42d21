"""The unified federated round engine.

Every algorithm in this repo shares the paper's round structure (Remark 2):
``tau - 1`` pure-local steps, then exactly ONE aggregating step in which each
client transmits a message, the server reduces it, and clients apply the
result. Before this module existed that structure was hand-rolled seven times
(FedCET, FedCETLiteral, FedCETPartial, FedCETCompressed, FedAvg, SCAFFOLD,
FedLin); now :class:`RoundEngine` owns it once and each algorithm is a slim
*spec* — a frozen dataclass subclass declaring five hooks:

* ``init_warmup(gf, x0, init_batch) -> (state, run_init_comm_step)`` —
  build the pre-round state from replicated initial parameters (FedCET's
  warm-up block additionally requests one aggregating step);
* ``begin_round(gf, state, first_batch, agg) -> (state, rctx)`` — optional
  round-start exchange (FedLin's gradient uplink); ``rctx`` is closed over
  by the local scan and the aggregating step;
* ``local_step(gf, state, batch, rctx) -> state`` — one pure-local step;
* ``message(gf, state, batch, rctx) -> (msg, mctx)`` — the transmitted
  pytree at the aggregating step (FedCET: the single vector ``v``;
  SCAFFOLD: the ``{dy, dc}`` pair). ``mctx`` carries client-local values the
  aggregation needs but the network never sees (FedCET's exact ``v``);
* ``server_aggregate(state, msg, msg_bar, mctx, rctx) -> state`` — apply
  the reduced message. ``msg`` is the client's own message AFTER transforms
  (see below), ``msg_bar`` the aggregate over (participating) clients.

The engine owns everything else: the ``vmap_grads`` lift with
``spmd_client_axes``, batch slicing (leaves ``[tau, clients, ...]``), the
``lax.scan`` over the tau-1 local steps (the aggregation stays OUTSIDE the
scan so the cross-pod all-reduce appears exactly once per round in the HLO),
message transforms, and client sampling.

Message transforms & composition
--------------------------------
:func:`with_compression` and :func:`with_participation` wrap ANY engine
algorithm without forking its round body, and compose in either order::

    algo = with_compression(with_participation(FedCET(...), 0.5), k_frac=0.3)
    algo = with_compression(algo2, compressor="randk:0.25")  # unbiased

* ``with_compression`` inserts a :class:`repro.core.compressors.Compressor`
  stack into the message path (the legacy ``k_frac=``/``quantize=`` kwargs
  are sugar for the seed's cross-client top-k + bf16 chain under error
  feedback: ``e += msg; tx = C(e); e -= tx``). Transform state such as the
  per-client feedback memory rides along in an :class:`EngineState` wrapper;
  stochastic compressors draw a fresh PRNG key per round from the state's
  step counter (via :class:`MessageCompression`). Crucially the spec's
  ``server_aggregate`` receives the client's own COMPRESSED message as
  ``msg`` — FedCET's drift update ``d += c (msg - msg_bar)`` therefore stays
  mean-zero across clients (``sum_i (tx_i - mean tx) = 0``), preserving the
  Lemma 2 fixed-point structure; the exact local vector needed for the
  x-update travels in ``mctx``.
* ``with_participation`` draws a Bernoulli client mask per round
  (deterministic from the state's step counter, which the engine advances by
  exactly ``tau`` per round), replaces the aggregation mean with a
  present-clients-only mean, and freezes absent clients — every state leaf
  with a leading ``n_clients`` axis reverts to its pre-round value, so
  absent clients neither compute nor transmit, and redistributive invariants
  (``sum_i d_i = 0``) survive sampling.
* ``with_delay`` simulates ASYNCHRONOUS rounds (delayed uplinks) on the
  same seam: a per-client delay model decides which uplinks land each
  round, the server keeps a last-known message buffer
  (:class:`repro.core.staleness.DelayState`, riding in ``EngineState``
  extras like transform memory), and a stale-aggregation policy
  (``drop`` / ``last`` / ``poly:a``) folds buffered messages into the
  server mean. Delay applies AFTER compression (the buffer holds wire
  messages) and composes with participation (absent clients cannot
  deliver; their buffer entry keeps aging). See staleness.py.
* ``with_topology`` replaces the flat all-to-one reduction itself:
  hierarchical edge-aggregator trees (per-hop comm accounting, root
  ingress of ``g`` messages instead of ``n_clients``) or doubly-stochastic
  gossip mixing (per-client neighborhood means — no server at all; the
  NIDS lineage FedCET descends from). Every reduction is a WEIGHTED one,
  fed the same weight vector the star engine uses (uniform / the
  participation mask / the stale policy's weights), so topology composes
  with all three transforms above with no algorithm-side code. Stateful
  topologies (per-round resampled graphs) ride a
  :class:`repro.core.topology.TopoState` in ``EngineState`` extras, just
  before the delay buffer. See topology.py.
* ``with_cohort`` makes per-round WORK O(cohort) instead of O(N): the full
  per-client state (FedCET's ``d_i``, SCAFFOLD's ``c_i``, error-feedback /
  shift memory, the delay buffer) stays server-side as the sharded
  client-state store, and each round the engine gathers the sampled
  cohort's rows into a fixed-shape ``[cohort, ...]`` batch, runs
  ``begin_round`` / the local scan / ``message`` on the cohort only, and
  scatters the updated rows back — all inside the jitted round step
  (static shapes, checkpoint/resume-stable; the cohort index is derived
  from the step counter through a domain-separated PRNG stream). See
  `Cohort execution` below.

Cohort execution
----------------
A cohort round runs the population round's body, :meth:`RoundEngine.round`,
on the cohort's rows: it gathers them from the client-state store, runs
the same two phases on them, and scatters them back. Phase A is the
per-client compute (``begin_round``, the tau-1 local scan, ``message``):
row-wise vmapped work whose per-row values do not depend on how many rows
run beside them, so running it on the gathered ``[cohort, ...]`` rows (the
default ``lowering="gather"``) or on the full ``[N, ...]`` store and
gathering its outputs (``lowering="dense"``, the O(N) reference the
equivalence tests pin against) yields identical rows. Phase B is
everything cross-client (message transforms, the delay buffer, the
weighted reduction, ``server_aggregate``, the participation freeze) and
runs on the cohort's rows in both lowerings, so cross-client compressors
(shared-scale quantizers, cross-client top-k) are defined OVER THE COHORT.
What differs from a population round is only which rows and which
reduction the body is given: ``with_participation`` draws its mask over
the cohort slots; ``with_delay`` buffers index by GLOBAL client id (the
body updates the cohort's gathered buffer rows; entries outside the
cohort keep aging; ``fresh_mask`` is evaluated at global ids so rr/fixed
schedules are client-stable); the reduction is the weighted cohort mean,
or a hierarchy's :meth:`~repro.core.topology.Topology.reduce_cohort`
(first-tier segment ids gathered at the cohort's global ids, so every
edge aggregator still sees exactly its own members). CommMeter bills
uplink AND present-only downlink at the ``cohort/N`` duty cycle. Gossip
mixing has no server to sample a cohort — ``with_cohort`` rejects it —
and FedLin's spec-internal cross-client top-k (``k_frac < 1``) is
rejected via ``cohort_compatible``. The store scatter is
``x.at[idx].set(rows)`` on every ``[N, ...]`` leaf: donate the round
carry (``make_round_runner(..., donate=True)``, the launch default) so
XLA updates the store in place instead of copying O(N) state per round.

All five factories are EXACT no-ops at their identity settings
(``rate >= 1.0``; ``k_frac >= 1.0 and not quantize``; delay ``fixed:0`` /
``rr:0`` / ``geom:1`` / ``none``; topology ``star``; cohort ``none`` /
``0`` / ``size >= n_clients``): they return the algorithm object
unchanged.

The shared multi-round driver
-----------------------------
:func:`run_rounds` / :func:`make_round_runner` scan ``algo.round`` over K
rounds with an optional per-round metric hook. ``simulate_quadratic``,
``FedTrainer.fit`` and ``launch.train.run_training`` all consume it — one
lowered while-loop whether the payload is the paper's 60-dim quadratic or a
sharded multi-B-parameter LM.
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import telemetry as tele
from repro.core.api import GradFn, StatsGrad, vmap_grads
from repro.core.comm import sparsified_up_frac
from repro.core.staleness import (
    DelayState,
    StalenessConfig,
    parse_delay,
    parse_policy,
    weighted_client_mean,
)
from repro.core.topology import Star, TopoState, parse_topology
from repro.utils.tree import tree_client_mean


class EngineState(NamedTuple):
    """Algorithm state plus per-transform extra state (e.g. error-feedback
    memory), plus — when a STATEFUL topology is attached — its
    :class:`repro.core.topology.TopoState` (the mixing round index), plus
    — when ``with_delay`` is attached — the server's last-known message
    buffer as the FINAL extras slot
    (:class:`repro.core.staleness.DelayState`). Only used when at least one
    transform, a stateful topology or a delay model is attached; bare
    algorithms keep their bare spec state, so existing checkpoints and
    sharding specs are unaffected."""

    inner: Any
    extras: tuple


# --------------------------------------------------------------------- masks
def participation_mask(key, n_clients: int, rate: float) -> jax.Array:
    """Bernoulli(rate) participation mask, guaranteed non-empty: if no client
    draws in, one uniformly random client is forced in. The Bernoulli draw
    and the fallback index use independent subkeys."""
    k_draw, k_fallback = jax.random.split(key)
    m = jax.random.bernoulli(k_draw, rate, (n_clients,))
    first = jax.nn.one_hot(jax.random.randint(k_fallback, (), 0, n_clients),
                           n_clients, dtype=bool)
    return jnp.where(jnp.any(m), m, first)


def masked_client_mean(tree, mask: jax.Array, *, keepdims: bool = True):
    """Mean over the leading clients axis restricted to ``mask``-selected
    clients (the server average under partial participation)."""
    denom = jnp.maximum(jnp.sum(mask.astype(jnp.int32)), 1)

    def mean_leaf(a):
        mb = mask.reshape((-1,) + (1,) * (a.ndim - 1)).astype(a.dtype)
        return jnp.sum(a * mb, axis=0, keepdims=keepdims) / denom.astype(a.dtype)

    return jax.tree.map(mean_leaf, tree)


def select_clients(new, old, mask: jax.Array, n_clients: int):
    """Per-client select between two same-structure pytrees: leaves with a
    leading ``n_clients`` axis take ``new`` where the mask is set and ``old``
    elsewhere; all other leaves (global scalars like the step counter) take
    ``new`` unconditionally."""

    def sel(n, o):
        if getattr(n, "ndim", 0) >= 1 and n.shape[0] == n_clients:
            mb = mask.reshape((-1,) + (1,) * (n.ndim - 1))
            return jnp.where(mb, n, o)
        return n

    return jax.tree.map(sel, new, old)


# --------------------------------------------------------------------- cohort
#: domain-separation tag folded into cohort-selection keys so the cohort
#: stream never collides with the participation (bare seed), compression
#: (0x7A11A5 + index), delay (0x57A1E) or topology (0x70_70 / 0x71_E5)
#: schedules at the default seed=0.
_COHORT_KEY_TAG = 0xC0_807


def gather_clients(tree, idx: jax.Array, n_clients: int):
    """Gather the ``idx`` rows of every per-client leaf (leading
    ``n_clients`` axis) of the client-state store; leaves without the
    client axis (global scalars like the step counter, ``[1, ...]``
    broadcast means) pass through unchanged."""

    def g(a):
        if getattr(a, "ndim", 0) >= 1 and a.shape[0] == n_clients:
            return a[idx]
        return a

    return jax.tree.map(g, tree)


def scatter_clients(store, rows, idx: jax.Array, n_clients: int):
    """Scatter updated cohort ``rows`` back into the client-state store:
    per-client store leaves take ``store.at[idx].set(row)``; all other
    leaves (global scalars) take the cohort's value unconditionally —
    the mirror of :func:`select_clients`'s convention."""

    def s(o, r):
        if getattr(o, "ndim", 0) >= 1 and o.shape[0] == n_clients:
            return o.at[idx].set(r)
        return r

    return jax.tree.map(s, store, rows)


@dataclasses.dataclass(frozen=True)
class CohortSpec:
    """Per-round cohort selection for O(cohort) round execution.

    ``selector`` picks which ``size`` global client ids train each round
    (all derived from the round-entry step counter, so the schedule is
    deterministic and checkpoint/resume-stable):

    * ``"uniform"`` — a uniformly random size-subset without replacement
      (``jax.random.permutation`` — O(N log N) selection work per round,
      O(cohort) everything else);
    * ``"block"`` — a contiguous block at a random offset (O(cohort)
      selection — the default for the scaling benchmark);
    * ``"rr"`` — round-robin blocks ``[r*size, (r+1)*size) mod N``
      (deterministic, key-free — every client trains once per N/size
      rounds).

    ``lowering`` selects the execution strategy: ``"gather"`` (gather the
    cohort rows, run phase A on ``[size, ...]`` — the O(cohort) path) or
    ``"dense"`` (run phase A on the full ``[N, ...]`` store and gather the
    results — the O(N) reference both benchmarks and equivalence tests
    compare against; phase B is cohort-sized either way, so the two agree
    bitwise)."""

    size: int
    selector: str = "uniform"
    seed: int = 0
    lowering: str = "gather"

    def __post_init__(self):
        if self.size < 1:
            raise ValueError(f"cohort size must be >= 1: {self.size}")
        if self.selector not in ("uniform", "block", "rr"):
            raise ValueError(f"unknown cohort selector {self.selector!r} "
                             "(uniform | block | rr)")
        if self.lowering not in ("gather", "dense"):
            raise ValueError(f"unknown cohort lowering {self.lowering!r} "
                             "(gather | dense)")

    def indices(self, step, tau: int, n_clients: int) -> jax.Array:
        """The round's sorted-free ``[size] int32`` global client ids,
        keyed by the round-entry step counter ``step`` (advanced by
        exactly ``tau`` per round — restart-stable)."""
        m = self.size
        if self.selector == "rr":
            r = jnp.asarray(step, jnp.int32) // tau
            return (r * m + jnp.arange(m, dtype=jnp.int32)) % n_clients
        key = jax.random.fold_in(jax.random.key(self.seed), _COHORT_KEY_TAG)
        key = jax.random.fold_in(key, jnp.asarray(step, jnp.int32))
        if self.selector == "block":
            off = jax.random.randint(key, (), 0, n_clients, dtype=jnp.int32)
            return (off + jnp.arange(m, dtype=jnp.int32)) % n_clients
        return jax.random.permutation(key, n_clients)[:m].astype(jnp.int32)


def parse_cohort(spec):
    """Parse a cohort spec; returns ``None`` for identity specs (``None`` /
    ``"none"`` / ``"off"`` / ``"full"`` / ``0``) so ``with_cohort`` can be
    an exact no-op, like every other transform factory.

    Grammar: an int, ``"256"``, ``"uniform:256"``, ``"block:256"``,
    ``"rr:256"``, with an optional trailing ``":dense"`` / ``":gather"``
    lowering selector (``"block:256:dense"``)."""
    if spec is None or isinstance(spec, CohortSpec):
        return spec
    if isinstance(spec, int):
        return CohortSpec(size=spec) if spec > 0 else None
    s = str(spec).strip().lower()
    if s in ("", "none", "off", "full", "0"):
        return None
    parts = s.split(":")
    lowering = "gather"
    if parts[-1] in ("gather", "dense"):
        lowering = parts.pop()
    if len(parts) == 1:
        selector, size = "uniform", parts[0]
    elif len(parts) == 2:
        selector, size = parts
    else:
        raise ValueError(f"bad cohort spec {spec!r} "
                         "(try 256, block:256, rr:256, block:256:dense)")
    try:
        size_i = int(size)
    except ValueError:
        raise ValueError(f"bad cohort size in spec {spec!r}: {size!r}")
    if size_i <= 0:
        return None
    return CohortSpec(size=size_i, selector=selector, lowering=lowering)


# ---------------------------------------------------------------- transforms
#: domain-separation tag folded into compression keys so they never collide
#: with the participation-mask key schedule (both default to seed=0).
_COMPRESS_KEY_TAG = 0x7A11A5


@dataclasses.dataclass(frozen=True)
class MessageCompression:
    """Message transform adapting a :class:`repro.core.compressors.Compressor`
    (possibly ``ErrorFeedback``-wrapped) into the engine's message path.

    Owns the per-round PRNG schedule for stochastic compressors: the key is
    ``fold_in(fold_in(key(seed), TAG), step)`` where ``step`` is the state's
    step counter at round entry (advanced by exactly ``tau`` per round, -1
    at the warm-up aggregation) — a fresh key every round, deterministic
    under restart, never shared with the participation mask schedule.
    Randomness is synchronized across clients (see compressors.py: this is
    what makes unbiased compressors preserve the FedCET fixed point and
    lets RandK skip index traffic)."""

    compressor: Any
    seed: int = 0
    #: position in the algorithm's transform stack, folded into the key so
    #: two stacked stochastic transforms at the same (default) seed never
    #: replay each other's randomness (which would de-unbias them).
    index: int = 0

    @property
    def up_frac(self) -> float:
        return self.compressor.up_frac

    @property
    def bits_per_coord(self) -> float:
        return self.compressor.bits_per_coord

    @property
    def keep_frac(self) -> float:
        return self.compressor.keep_frac

    @property
    def index_bits(self) -> float:
        return self.compressor.index_bits

    @property
    def value_bits(self) -> float | None:
        return self.compressor.value_bits

    @property
    def unbiased(self) -> bool:
        return getattr(self.compressor, "unbiased", False)

    def init_extra(self, msg_shapes):
        return self.compressor.init_extra(msg_shapes)

    def apply(self, msg, extra, step):
        key = None
        if self.compressor.requires_key:
            key = jax.random.fold_in(
                jax.random.key(self.seed), _COMPRESS_KEY_TAG + self.index)
            key = jax.random.fold_in(key, jnp.asarray(step, jnp.int32))
        return self.compressor.apply(key, msg, extra)


@dataclasses.dataclass(frozen=True)
class ErrorFeedbackCompression:
    """Legacy message transform (the seed's scheme, kept as construction
    sugar with its exact semantics): cross-client top-k sparsification
    and/or bf16 quantization with optional client-side error feedback.

    Since the compressor subsystem this is a thin shim over
    ``ErrorFeedback(Chain((TopK(k_frac, per_client=False), Bf16())))`` —
    the compress path is bit-identical to the seed (seed-equivalence tests
    pin it to <= 1e-12). ``up_frac`` keeps the seed's APPROXIMATE accounting
    ("bf16 halves whatever remains") for backward compatibility;
    ``bits_per_coord`` reports the bit-true cost (bf16 halves VALUES only —
    top-k index traffic stays int32), which is what ``CommMeter`` now
    meters. New code should pass ``with_compression(..., compressor=...)``
    objects instead."""

    k_frac: float = 1.0
    quantize: bool = False
    error_feedback: bool = True

    @property
    def up_frac(self) -> float:
        """Effective uplink fraction vs a dense f32 payload (top-k transmits
        values + int32 indices; bf16 halves whatever remains)."""
        frac = sparsified_up_frac(self.k_frac)
        if self.quantize:
            frac = min(0.5 * frac, 0.5)
        return min(frac, 1.0)

    def _compressor(self):
        from repro.core.compressors import (
            Bf16, Chain, ErrorFeedback, Identity, TopK)

        stages = []
        if self.k_frac < 1.0:
            stages.append(TopK(self.k_frac, per_client=False))
        if self.quantize:
            stages.append(Bf16())
        comp = (stages[0] if len(stages) == 1
                else Chain(tuple(stages)) if stages else Identity())
        return ErrorFeedback(comp) if self.error_feedback else comp

    @property
    def bits_per_coord(self) -> float:
        return self._compressor().bits_per_coord

    @property
    def keep_frac(self) -> float:
        return self._compressor().keep_frac

    @property
    def index_bits(self) -> float:
        return self._compressor().index_bits

    @property
    def value_bits(self) -> float | None:
        return self._compressor().value_bits

    def init_extra(self, msg_shapes):
        """Feedback memory, shaped like the message (from ``eval_shape``)."""
        return self._compressor().init_extra(msg_shapes)

    def apply(self, msg, extra, step):
        del step  # deterministic stack
        return self._compressor().apply(None, msg, extra)


@dataclasses.dataclass(frozen=True)
class ClientSampling:
    """Per-round Bernoulli client participation policy."""

    rate: float
    seed: int = 0


# --------------------------------------------------------------------- engine
@dataclasses.dataclass(frozen=True)
class RoundEngine:
    """Shared round driver; algorithms subclass this and implement the spec
    hooks (``init_warmup``, ``local_step``, ``message``,
    ``server_aggregate``, optionally ``begin_round`` / ``client_params``).

    Subclasses must declare ``name``, ``tau``, ``n_clients``, ``vectors_up``
    and ``vectors_down`` fields (the FederatedAlgorithm protocol), and their
    state must be a pytree whose per-client leaves carry a leading
    ``n_clients`` axis plus a scalar step counter ``t`` that the engine-run
    round advances by exactly ``tau``."""

    transforms: tuple = dataclasses.field(default=(), kw_only=True)
    sampling: ClientSampling | None = dataclasses.field(default=None, kw_only=True)
    #: asynchronous-round simulation (delay model + buffer + stale policy);
    #: attach via ``with_delay`` — see repro/core/staleness.py.
    delay: StalenessConfig | None = dataclasses.field(default=None, kw_only=True)
    #: aggregation geometry (hierarchical tiers / gossip mixing); attach via
    #: ``with_topology`` — see repro/core/topology.py. None = the flat star.
    topology: Any | None = dataclasses.field(default=None, kw_only=True)
    #: O(cohort) round execution (gather/scatter on the sharded client-state
    #: store); attach via ``with_cohort``. None = every client trains.
    cohort: CohortSpec | None = dataclasses.field(default=None, kw_only=True)
    #: pack the model pytree into the contiguous [rows, 1024] parameter
    #: arena (core/arena.py): state/message leaves become single packed
    #: buffers, unpacked only at the model-apply (gradient) boundary;
    #: attach via ``with_arena``. The whole engine seam is
    #: representation-transparent (Arena is a pytree node), so every
    #: transform/axis above composes unchanged.
    arena: bool = dataclasses.field(default=False, kw_only=True)
    #: in-trace telemetry spec (core/telemetry.py): when attached, the
    #: round captures per-round scalars (gradient/message norms,
    #: compression error, participation, staleness ages; the runner adds
    #: the invariant residual and consensus error from the post-round
    #: state) onto the runner's tape with no host sync. None (the
    #: default) is a BITWISE no-op: every capture site is guarded on this
    #: field, so the disabled round traces the identical jaxpr.
    telemetry: Any | None = dataclasses.field(default=None, kw_only=True)
    #: mesh axes carrying the client dimension (production launcher only).
    spmd_client_axes: tuple = dataclasses.field(default=(), kw_only=True)

    # ------------------------------------------------------------ spec hooks
    def init_warmup(self, gf, x0, init_batch):
        raise NotImplementedError

    def begin_round(self, gf, state, first_batch, agg):
        """Optional round-start exchange; returns (state, round context)."""
        del gf, first_batch, agg
        return state, None

    def local_step(self, gf, state, batch, rctx):
        raise NotImplementedError

    def message(self, gf, state, batch, rctx):
        raise NotImplementedError

    def server_aggregate(self, state, msg, msg_bar, mctx, rctx):
        raise NotImplementedError

    def _fused_tail(self, inner, msg, mctx, extras, step, mask):
        """Optional whole-round-tail fusion hook, consulted by
        ``_comm_step`` on plain synchronous arena rounds (no topology, no
        delay). A spec that can execute transform -> reduce ->
        ``server_aggregate`` as one fused pass over its packed message
        returns ``(new_inner, new_extras)``; ``None`` falls through to
        the generic seam. FedCET implements it for the shift-quantized
        uplink via the kernels/ops.py ``fedcet_round_tail`` kernel."""
        del inner, msg, mctx, extras, step, mask
        return None

    def client_params(self, state):
        """Stacked [clients, ...] model parameters (default: ``state.x``),
        unpacked from the parameter arena when the state carries one."""
        x = self._inner(state).x
        from repro.core.arena import Arena, unpack

        return unpack(x) if isinstance(x, Arena) else x

    def global_params(self, state):
        p = tree_client_mean(self.client_params(state), keepdims=False)
        from repro.core.arena import Arena, unpack

        return unpack(p) if isinstance(p, Arena) else p

    # ------------------------------------------------------------ accounting
    @property
    def up_frac(self) -> float:
        """Effective uplink bytes fraction after message transforms."""
        frac = 1.0
        for t in self.transforms:
            frac *= getattr(t, "up_frac", 1.0)
        return frac

    def _transforms_bits(self, bits: float = 32.0) -> float:
        """Fold the attached transforms' bit-true cost onto a dense width.

        Stacked transforms compose like Chain stages — via their
        (keep_frac, index_bits, value_bits) triple, NOT by multiplying
        total fractions (that would wrongly scale a sparsifier's int32
        index bits by a later quantizer's value fraction: top-k 30% then
        q8 is 0.3*(8+32)=12 bits/coord, not 32*0.6*0.25)."""
        keep, idx, value = 1.0, 0.0, bits
        for t in self.transforms:
            kf = getattr(t, "keep_frac", None)
            if kf is None:  # unknown transform: coarse fractional fallback
                per = getattr(t, "bits_per_coord", None)
                per = 32.0 * getattr(t, "up_frac", 1.0) if per is None else per
                value *= per / 32.0
                continue
            keep *= kf
            idx += keep * t.index_bits
            vb = t.value_bits
            if vb is not None:
                # first-narrowest-wins, mirroring Chain.value_bits: a later
                # wider stage cannot put information back on the wire.
                value = min(value, vb)
        return keep * value + idx

    @property
    def bits_per_coord(self) -> float:
        """Bit-true average wire bits per model coordinate per UP vector,
        derived from the attached compressor stack (32.0 when dense).
        Specs with internal compression (FedLin's round-start top-k)
        override this alongside ``up_frac``."""
        return self._transforms_bits(32.0)

    def message_leaf_bits(self, leaf_info):
        """EXACT per-leaf uplink wire bits for one client's one UP vector,
        given the message leaf decomposition ``[(name, n_coords), ...]``
        (see repro/core/comm.py:leaf_info_of) — the actual-kept-count,
        per-leaf-plan-aware refinement of ``n * bits_per_coord``.

        Returns ``None`` when per-leaf accounting does not apply: a spec
        that overrides ``bits_per_coord`` bills internal compression the
        engine cannot decompose (FedLin), and an unknown transform without
        a compressor has no stage algebra to walk. Never inspects the
        arena: the decomposition comes from the unpacked params either
        way, which is what makes arena and per-leaf lowerings bill
        identically (pinned in benchmarks/comm_table.py)."""
        if type(self).bits_per_coord is not RoundEngine.bits_per_coord:
            return None
        stack = []
        for t in self.transforms:
            comp = getattr(t, "compressor", None)
            if comp is None and hasattr(t, "_compressor"):
                comp = t._compressor()
            if comp is None:
                return None
            stack.append(comp)
        from repro.core.compressors import stack_wire_bits

        return [stack_wire_bits(stack, i, nm, int(n))
                for i, (nm, n) in enumerate(leaf_info)]

    @property
    def down_frac(self) -> float:
        return 1.0

    @property
    def transmit_frac(self) -> float:
        """Expected fraction of rounds a client's uplink actually lands
        (1.0 synchronous). Buffered rounds transmit zero uplink bits —
        CommMeter folds this duty cycle into bytes_up. With client
        sampling attached the effective arrival mask is ``fresh AND
        present`` (an absent client cannot deliver), and the two schedules
        are independent PRNG streams, so the expectations multiply.
        (The participation factor ignores the non-empty-mask fallback's
        tiny upward correction at very low rates.) With a cohort attached
        only its ``size/N`` slice of clients computes at all — non-sampled
        clients transmit ZERO uplink bits, so the duty cycle multiplies
        by the cohort fraction."""
        frac = self._cohort_frac
        if self.sampling is not None:
            frac *= min(self.sampling.rate, 1.0)
        if self.delay is not None:
            frac *= self.delay.transmit_frac(self.n_clients)
        return frac

    @property
    def receive_frac(self) -> float:
        """Expected fraction of rounds a client RECEIVES the downlink
        broadcast (1.0 synchronous). Under client sampling the server
        broadcasts to PRESENT clients only — absent clients keep their
        frozen replica instead of receiving a phantom broadcast, so
        CommMeter bills downlink bytes at the participation rate. Delay
        models do not reduce downlink: stale-but-present clients still
        apply the (buffered-mean) update, which still has to reach them.
        A cohort is present-only downlink taken to its O(cohort)
        conclusion: only the sampled ``size/N`` slice receives anything,
        so the cohort fraction multiplies here too."""
        frac = self._cohort_frac
        if self.sampling is not None:
            frac *= min(self.sampling.rate, 1.0)
        return frac

    @property
    def _cohort_frac(self) -> float:
        return (self.cohort.size / self.n_clients
                if self.cohort is not None else 1.0)

    @property
    def cohort_compatible(self) -> bool:
        """Whether this spec's own math is cohort-safe: True unless the
        spec performs a CROSS-CLIENT computation outside the engine's
        phase-B seam (FedLin's internal cross-client top-k overrides
        this). Engine-level transforms need no flag — they always run on
        the gathered cohort rows."""
        return True

    # ------------------------------------------------------- state wrapping
    @property
    def _topo_stateful(self) -> bool:
        return self.topology is not None and self.topology.stateful

    @property
    def _wrapped(self) -> bool:
        return (bool(self.transforms) or self.delay is not None
                or self._topo_stateful)

    def _wrap(self, inner, extras, tstate=None, dstate=None):
        if not self._wrapped:
            return inner
        extras = tuple(extras)
        if self._topo_stateful:
            extras += (tstate,)
        if self.delay is not None:
            extras += (dstate,)
        return EngineState(inner, extras)

    def _split(self, state):
        """-> (inner, transform extras, TopoState | None, DelayState | None).

        Extras layout: per-transform slots first, then the stateful
        topology's TopoState (when attached), then the delay buffer as the
        FINAL slot (when attached)."""
        if not self._wrapped:
            return state, (), None, None
        extras, tstate, dstate = state.extras, None, None
        if self.delay is not None:
            extras, dstate = extras[:-1], extras[-1]
        if self._topo_stateful:
            extras, tstate = extras[:-1], extras[-1]
        return state.inner, extras, tstate, dstate

    def _inner(self, state):
        return state.inner if self._wrapped else state

    # ------------------------------------------------------------- plumbing
    def _grad(self, grad_fn: GradFn) -> GradFn:
        vg = vmap_grads(grad_fn, spmd_axis_name=(self.spmd_client_axes or None))
        if isinstance(grad_fn, StatsGrad):
            vg_stats = vg

            def vg(x, batch):
                g, stats = vg_stats(x, batch)
                tele.count({k: jnp.sum(v, axis=0) for k, v in stats.items()})
                return g

        def gf(x, batch):
            with tele.scope("fed.client_grad"):
                return vg(x, batch)

        if self.arena:
            from repro.core.arena import Arena, pack, row_major, unpack

            base = gf

            # the model-apply boundary: the loss sees the real pytree, the
            # engine sees the arena. The unpack is pure slicing that XLA
            # fuses into the gradient's consumers; the pack writes g once,
            # in the x and d arenas' own [clients, rows, LANES] layout, so
            # the spec's v = x - a*g - a*d reads it in place in one pass
            # (tests/test_tpu_compile.py). (Returning RAW grads and folding
            # the pack into the spec's first consumer was slower on the
            # CPU: outside the grad closure the unpacked x/d slices
            # materialized as copies instead of fusing.)
            def arena_gf(x, batch):
                if not isinstance(x, Arena):
                    return base(x, batch)
                with tele.scope("fed.client_grad"):
                    g = vg(unpack(x), batch)
                with tele.scope("fed.arena_pack"):
                    return pack(jax.tree.map(row_major, g), x.layout)

            gf = arena_gf
        if self.telemetry is None:
            return gf

        inner_gf = gf

        # the capture is a no-op outside the runner's tape and inside the
        # muted tau-1 local scan; an Arena gradient's zero pads make the
        # packed norm equal the per-leaf norm.
        def recording_gf(x, batch):
            g = inner_gf(x, batch)
            if tele.collecting():
                with tele.scope("fed.telemetry"):
                    tele.capture("grad_norm", tele.mean_client_norm(g))
            return g

        return recording_gf

    def _local_update(self, gf, state, batch, rctx):
        """The spec's ``local_step`` under the ``fed.local_update`` scope
        (its gradient call keeps its own inner scope)."""
        with tele.scope("fed.local_update"):
            return self.local_step(gf, state, batch, rctx)

    def _message(self, gf, state, batch, rctx):
        """The spec's ``message`` under the ``fed.local_update`` scope."""
        with tele.scope("fed.local_update"):
            return self.message(gf, state, batch, rctx)

    def _msg_shapes(self, gf, inner, init_batch):
        """Abstract (eval_shape) wire-message tree of the current state —
        shapes transform extras and stateful-topology tier memory."""
        def msg_of(s, b):
            s2, rctx = self.begin_round(gf, s, b, tree_client_mean)
            return self.message(gf, s2, b, rctx)[0]

        return jax.eval_shape(msg_of, inner, init_batch)

    def _init_extras(self, msg_shapes) -> tuple:
        """Per-transform extra state, shaped from the (abstract) message."""
        return tuple(t.init_extra(msg_shapes) for t in self.transforms)

    def _local_phase(self, gf, inner, batches, agg):
        """Phase A, the per-client compute, over whatever rows ``inner``
        holds: ``begin_round``, the tau-1 local steps under ``lax.scan``,
        and the aggregating step's ``message``. Returns ``(inner, rctx,
        msg, mctx, last_batch)``; ``batches`` leaves are ``[tau, rows,
        ...]``."""
        first_b = jax.tree.map(lambda b: b[0], batches)
        inner, rctx = self.begin_round(gf, inner, first_b, agg)

        if self.tau > 1:
            local_b = jax.tree.map(lambda b: b[: self.tau - 1], batches)
            counted = self.telemetry is not None and tele.collecting()

            def body(s, b):
                if not counted:
                    return self._local_update(gf, s, b, rctx), None
                with tele.counting() as counts:
                    s = self._local_update(gf, s, b, rctx)
                return s, counts

            # muted: a capture inside the scan body would leak inner-scan
            # tracers onto the round-level telemetry tape; the body's
            # counters leave it as the scan's outputs instead.
            with tele.muted():
                inner, counts = jax.lax.scan(body, inner, local_b)
            if counted:
                tele.count(jax.tree.map(lambda a: jnp.sum(a, axis=0), counts))

        last_b = jax.tree.map(lambda b: b[self.tau - 1], batches)
        msg, mctx = self._message(gf, inner, last_b, rctx)
        return inner, rctx, msg, mctx, last_b

    def _comm_step(self, gf, inner, extras, batch, msg, mctx, rctx, reduce,
                   step, dstate=None, fresh=None, mask=None, ids=None):
        """Phase B, the single aggregating step over the rows phase A ran
        on: transforms -> [staleness buffer] -> reduce -> apply. The only
        place a cross-client collective fires. ``msg`` / ``mctx`` are the
        rows' message and its client-local context; ``reduce`` is the
        round's aggregating reduction (see :meth:`_aggregator`); ``ids``
        holds a cohort's global client ids (None for the population).
        ``step`` is the state's step counter at round entry — stochastic
        transforms derive their per-round PRNG key from it (never reused
        across rounds; stack multiple stochastic transforms with distinct
        seeds).

        With ``dstate``/``fresh`` set (a ``with_delay`` round; the rows of
        the buffer, ``fresh`` over the same rows), the wire message lands
        in the server buffer only where ``fresh`` is true, the stale
        policy turns buffer + ages into the weights ``reduce`` takes, and
        stale clients either apply the update with their BUFFERED own
        message (``last``/``poly`` — the copy both ends kept) or take the
        tau-th step as a pure local continuation (``drop``). Stale clients
        never transmitted, so their transform memory (error feedback /
        shift) reverts to its pre-round value. Returns
        ``(inner, extras, tstate, dstate, tx)`` — ``tx`` is the
        post-transform wire message (``init`` seeds the buffer from it)."""
        # observer-only telemetry: rec is False when telemetry is detached
        # (bitwise no-op) or no tape is active (init / direct round calls).
        rec = self.telemetry is not None and tele.collecting()
        if rec:
            with tele.scope("fed.telemetry"):
                tele.capture("msg_norm", tele.mean_client_norm(msg))
                if self.telemetry.leaf_stats:
                    tele.capture("leaf_msg_norm",
                                 tele.leaf_client_norms(msg))
        if (ids is None and dstate is None and self.delay is None
                and self.topology is None and self.arena):
            with tele.scope("fed.aggregate"):
                fused = self._fused_tail(inner, msg, mctx, extras, step, mask)
            if fused is not None:
                inner, new_extras = fused
                return inner, tuple(new_extras), None, None, None
        raw = msg
        new_extras = []
        with tele.scope("fed.uplink"):
            for t, e in zip(self.transforms, extras):
                msg, e = t.apply(msg, e, step)
                new_extras.append(e)
        if rec and self.transforms:
            with tele.scope("fed.telemetry"):
                diff = jax.tree.map(lambda a, b: a - b, msg, raw)
                tele.capture("compress_err", tele.mean_client_norm(diff))
                if self.telemetry.wants_sketch("compress_err"):
                    tele.capture("compress_err_clients",
                                 jnp.sqrt(tele.client_sq_norms(diff)))
                    if ids is not None:
                        # finalize maps the sketch's top-k cohort slots
                        # to global client ids through this.
                        tele.capture("cohort_ids", ids.astype(jnp.int32))
                if self.telemetry.leaf_stats:
                    tele.capture("leaf_compress_err",
                                 tele.leaf_client_norms(diff))

        if dstate is None:  # synchronous path (and always: init)
            with tele.scope("fed.aggregate"):
                msg_bar, tstate = reduce(msg)
                inner = self.server_aggregate(inner, msg, msg_bar, mctx,
                                              rctx)
            return inner, tuple(new_extras), tstate, None, msg

        # fresh arrivals replace the buffered copy and reset its age.
        n = fresh.shape[0]
        with tele.scope("fed.aggregate"):
            buf = select_clients(msg, dstate.buf, fresh, n)
            age = jnp.where(fresh, 0, dstate.age + 1).astype(dstate.age.dtype)
            # the stale policy's weights feed the same reduction as the
            # synchronous path, so hierarchical aggregation composes with
            # staleness with no extra code.
            w = self.delay.policy.weights(age, fresh)
            msg_bar, tstate = reduce(buf, w)
            # each client's own-message slot is what the server attributed
            # to it: the fresh wire message where it landed, the buffer
            # elsewhere.
            agg_inner = self.server_aggregate(inner, buf, msg_bar, mctx, rctx)
        if not self.delay.policy.apply_stale:
            # drop: no-arrival clients take the tau-th step as a pure local
            # step instead of the aggregation update (XLA CSEs the repeated
            # gradient evaluation at the same point).
            local = self._local_update(gf, inner, batch, rctx)
            agg_inner = select_clients(agg_inner, local, fresh, n)
        new_extras = tuple(select_clients(ne, e, fresh, n)
                           for ne, e in zip(new_extras, extras))
        return agg_inner, new_extras, tstate, DelayState(buf=buf, age=age), msg

    def _would_transmit(self, gf, inner, extras, batch):
        """The wire message the current state WOULD transmit (begin_round
        context and transform-memory updates discarded) — seeds the delay
        buffer for specs whose warm-up runs no init aggregation."""
        st, rctx = self.begin_round(gf, inner, batch, tree_client_mean)
        msg, _ = self.message(gf, st, batch, rctx)
        for t, e in zip(self.transforms, extras):
            msg, _ = t.apply(msg, e, inner.t)
        return msg

    def _topo_weights(self, mask, n: int | None = None):
        """The per-client weight vector a round reduces under on
        non-delayed rounds: uniform, or the participation mask. ``n``
        overrides the vector length (cohort rounds reduce over the
        cohort slots, not the full population)."""
        ft = jax.dtypes.canonicalize_dtype(jnp.float64)
        return (mask.astype(ft) if mask is not None
                else jnp.ones((n if n is not None else self.n_clients,), ft))

    def _aggregator(self, mask, tstate, ids=None):
        """The round's two cross-client reductions over its rows (the
        population, or the cohort whose global client ids are ``ids``):
        ``(agg, reduce)``.

        ``agg(tree)`` is READ-ONLY (fed to ``begin_round`` — e.g. FedLin's
        gradient exchange; topology state frozen). ``reduce(tree, w=None)
        -> (mean, tstate)`` is the aggregating step's, the one place
        topology state (resampled-graph index, tier-compression memory)
        moves; it reduces under the round's own weights (uniform, or the
        participation mask) or, on a delayed round, the stale policy's
        ``w``. The population reduces through the topology's weighted
        reduce, else the star mean / masked mean the engine always used; a
        cohort through the topology's (or the star's) cohort reduce, fed
        the global ids so a hierarchy routes each member to its own edge
        aggregator."""
        if self.topology is None and ids is None:
            agg = (tree_client_mean if mask is None
                   else lambda tr: masked_client_mean(tr, mask))
            return agg, lambda tr, w=None: (
                agg(tr) if w is None else weighted_client_mean(tr, w), None)
        topo, N = self.topology or Star(), self.n_clients
        if ids is None:
            w0 = self._topo_weights(mask)
            return (lambda tr: topo.reduce(tr, w0, tstate),
                    lambda tr, w=None: topo.reduce_and_advance(
                        tr, w0 if w is None else w, tstate))
        w0 = self._topo_weights(mask, self.cohort.size)
        return (lambda tr: topo.reduce_cohort(tr, w0, ids, N, tstate),
                lambda tr, w=None: topo.reduce_cohort_and_advance(
                    tr, w0 if w is None else w, ids, N, tstate))

    # -------------------------------------------------------------- protocol
    def init(self, grad_fn: GradFn, x0, init_batch):
        """Replicate-and-warm-up, plus one aggregating step if the spec's
        warm-up requests it. Client sampling and delay never apply at init
        (matching the full-participation synchronous initialization of the
        paper) but the TOPOLOGY does — it is the physical network, so a
        warm-up aggregation already flows through the tree / gossip graph.
        The delay buffer is seeded with each client's (would-be) init-time
        wire message, age 0 — so early stale rounds average real messages,
        never zeros."""
        gf = self._grad(grad_fn)
        if self.arena:
            from repro.core.arena import Arena, pack

            if not isinstance(x0, Arena):
                x0 = pack(x0)
            # from here on EVERY state/message tree the spec builds from
            # x0 (replicate, zeros_like, eval_shape, transform extras,
            # the delay buffer) is arena-valued by construction.
        inner, run_comm = self.init_warmup(gf, x0, init_batch)
        topo_shapes = (self.topology is not None
                       and self.topology.needs_msg_shapes)
        msg_shapes = (self._msg_shapes(gf, inner, init_batch)
                      if (self.transforms or topo_shapes) else None)
        extras = self._init_extras(msg_shapes)
        tstate = None
        if self.topology is not None:
            tstate = self.topology.init_state(msg_shapes if topo_shapes
                                              else None)
        tx = None
        if run_comm:
            msg, mctx = self._message(gf, inner, init_batch, None)
            inner, extras, tstate, _, tx = self._comm_step(
                gf, inner, extras, init_batch, msg, mctx, None,
                self._aggregator(None, tstate)[1], step=inner.t)
        dstate = None
        if self.delay is not None:
            if tx is None:
                tx = self._would_transmit(gf, inner, extras, init_batch)
            dstate = DelayState(
                buf=tx, age=jnp.zeros((self.n_clients,), jnp.int32))
        return self._wrap(inner, extras, tstate, dstate)

    def round(self, grad_fn: GradFn, state, batches):
        """One communication round: optional round-start exchange, tau-1
        local steps under ``lax.scan``, one aggregating step.

        ``batches`` leaves have leading ``[tau, clients, ...]`` axes. The
        scan keeps the lowered HLO small for multi-B parameter models; the
        aggregation sits OUTSIDE the scan so the cross-pod all-reduce
        appears exactly once per round in the HLO.

        With a cohort attached the same phases run on the cohort's rows,
        gathered from the client-state store and scattered back after the
        participation freeze (see the module docstring's `Cohort
        execution`) — same state layout, same hooks, O(cohort) work."""
        gf = self._grad(grad_fn)
        inner, extras, tstate, dstate = self._split(state)
        N = self.n_clients

        step0 = inner.t  # round-entry counter: keys cohort, masks, dither
        ids = (None if self.cohort is None
               else self.cohort.indices(step0, self.tau, N))
        n = N if ids is None else self.cohort.size
        mask = None
        if self.sampling is not None:
            # over a cohort: Bernoulli participation within its slots.
            key = jax.random.fold_in(jax.random.key(self.sampling.seed),
                                     jnp.asarray(step0, jnp.int32))
            mask = participation_mask(key, n, self.sampling.rate)
        agg, reduce = self._aggregator(mask, tstate, ids)
        fresh = None
        if self.delay is not None:
            # delay schedules key off GLOBAL client ids (an rr straggler
            # stays the same physical client whichever round samples it).
            fresh = self.delay.fresh_mask(step0, self.tau, N)
            if ids is not None:
                fresh = fresh[ids]
            if mask is not None:
                fresh = jnp.logical_and(fresh, mask)  # absent can't deliver
        rec = self.telemetry is not None and tele.collecting()
        if rec:
            with tele.scope("fed.telemetry"):
                tele.capture("participating",
                             jnp.sum(mask.astype(jnp.int32))
                             if mask is not None
                             else jnp.asarray(n, jnp.int32))

        # the rows the round runs on: the population, or the cohort's
        # gathered from the store (pre-round rows: the freeze reverts to
        # them).
        rows, rows_extras, rows_dstate = inner, extras, dstate
        if ids is not None:
            rows, rows_extras, rows_dstate = gather_clients(
                (inner, extras, dstate), ids, N)
        if ids is not None and self.cohort.lowering == "dense":
            # the O(N) reference lowering: every client computes and the
            # cohort's rows of the results feed phase B. Row-wise vmapped
            # compute does not depend on the batch size, so they match the
            # gather lowering's bitwise.
            phase_a = gather_clients(self._local_phase(
                gf, inner, batches,
                lambda tr: agg(gather_clients(tr, ids, N))), ids, N)
        else:
            if ids is not None:
                batches = jax.tree.map(
                    lambda b: (b[:, ids] if getattr(b, "ndim", 0) >= 2
                               and b.shape[1] == N else b), batches)
            phase_a = self._local_phase(gf, rows, batches, agg)
        st, rctx, msg, mctx, last_b = phase_a

        new, new_extras, tstate, new_dstate, _ = self._comm_step(
            gf, st, rows_extras, last_b, msg, mctx, rctx, reduce, step0,
            dstate=rows_dstate, fresh=fresh, mask=mask, ids=ids)

        if mask is not None:
            # absent clients keep their pre-round state entirely; the delay
            # buffer and the topology round index are SERVER/NETWORK state
            # and are never reverted — an absent client's last-known
            # message simply keeps aging.
            new = select_clients(new, rows, mask, n)
            new_extras = tuple(select_clients(e, fe, mask, n)
                               for e, fe in zip(new_extras, rows_extras))
        if ids is not None:
            new = scatter_clients(inner, new, ids, N)
            new_extras = tuple(scatter_clients(e, r, ids, N)
                               for e, r in zip(extras, new_extras))
            if dstate is not None:
                # the buffer is server state: every entry outside the
                # cohort keeps aging (its owner could not deliver).
                new_dstate = DelayState(
                    buf=scatter_clients(dstate.buf, new_dstate.buf, ids, N),
                    age=(dstate.age + 1).astype(dstate.age.dtype
                                                ).at[ids].set(new_dstate.age))
        if rec and new_dstate is not None:
            # arrivals over the round's rows; ages over the whole server
            # buffer (outside a cohort, entries keep aging).
            with tele.scope("fed.telemetry"):
                tele.capture("fresh_count", jnp.sum(fresh.astype(jnp.int32)))
                tele.capture("age_min", jnp.min(new_dstate.age))
                tele.capture("age_mean",
                             jnp.mean(new_dstate.age.astype(jnp.float32)))
                tele.capture("age_max", jnp.max(new_dstate.age))
        return self._wrap(new, new_extras, tstate, new_dstate)


# ------------------------------------------------------- transform factories
def with_participation(algo: RoundEngine, rate: float, seed: int = 0) -> RoundEngine:
    """Per-round Bernoulli client sampling for ANY engine algorithm.
    ``rate >= 1.0`` is an exact no-op (returns ``algo`` unchanged)."""
    if rate >= 1.0:
        return algo
    return dataclasses.replace(algo, sampling=ClientSampling(rate=rate, seed=seed))


def with_compression(algo: RoundEngine, *, k_frac: float = 1.0,
                     quantize: bool = False,
                     error_feedback: bool | None = None,
                     compressor=None, seed: int = 0) -> RoundEngine:
    """Compressed uplink for ANY engine algorithm's message path.

    Two entry forms:

    * ``compressor=`` — a :class:`repro.core.compressors.Compressor` object
      or spec string (``"randk:0.25"``, ``"topk:0.3+bf16"``, ``"q8"``, ...).
      ``error_feedback=None`` (the default) wraps BIASED compressors in
      :class:`~repro.core.compressors.ErrorFeedback` and leaves unbiased
      ones bare (EF around an unbiased compressor reintroduces a feedback
      limit cycle); pass True/False to force. ``seed`` keys the per-round
      randomness of stochastic compressors.
    * legacy ``k_frac=`` / ``quantize=`` — the seed's cross-client top-k +
      bf16 error-feedback scheme, bit-identical to the original
      (``error_feedback=None`` means True here). ``k_frac >= 1.0 and not
      quantize`` is an exact no-op (returns ``algo`` unchanged).

    Transforms stack: the last one attached compresses the output of the
    previous one."""
    if compressor is not None:
        if k_frac < 1.0 or quantize:
            raise ValueError(
                "pass EITHER compressor= or the legacy k_frac=/quantize= "
                "kwargs, not both (the legacy pair would be silently "
                f"ignored): compressor={compressor!r}, k_frac={k_frac}, "
                f"quantize={quantize}")
        from repro.core.compressors import (CompressionPlan, auto_wrap,
                                            from_spec)

        comp = from_spec(compressor)
        if comp is None:  # the "none" spec — exact no-op, like k_frac=1.0
            return algo
        # auto mode: EF around biased STATELESS compressors only — wrapping
        # a Shifted/ErrorFeedback would clobber its extra slot. Plans own
        # their per-RULE error-feedback policy (parse_plan applies the same
        # auto_wrap rule-wise), so the whole-tree wrap must not double up.
        if not isinstance(comp, CompressionPlan):
            comp = auto_wrap(comp, error_feedback)
        t = MessageCompression(comp, seed=seed, index=len(algo.transforms))
        return dataclasses.replace(algo, transforms=algo.transforms + (t,))
    if k_frac >= 1.0 and not quantize:
        return algo
    t = ErrorFeedbackCompression(
        k_frac=k_frac, quantize=quantize,
        error_feedback=True if error_feedback is None else error_feedback)
    return dataclasses.replace(algo, transforms=algo.transforms + (t,))


def with_delay(algo: RoundEngine, delay, *, policy="last",
               seed: int = 0) -> RoundEngine:
    """Asynchronous rounds for ANY engine algorithm: simulate delayed
    uplinks with a server-side last-known message buffer and a
    stale-aggregation policy (see repro/core/staleness.py).

    ``delay`` is a spec string (``"fixed:2"``, ``"rr:1"``, ``"geom:0.5"``)
    or a delay-model object; ``policy`` is ``"drop"`` / ``"last"`` /
    ``"poly:<a>"`` (or a :class:`~repro.core.staleness.StalePolicy`);
    ``seed`` keys stochastic schedules (domain-separated from the
    participation and compression streams). Identity delays (``"none"``,
    ``"fixed:0"``, ``"rr:0"``, ``"geom:1"``) are exact no-ops — the
    algorithm object is returned unchanged, for every policy.

    Delay applies at the aggregation seam AFTER any compression transforms
    (the buffer holds wire messages), so composition with
    ``with_compression`` / ``with_participation`` is order-independent."""
    model = parse_delay(delay)
    if model is None:
        return algo
    if algo.delay is not None:
        raise ValueError("algorithm already has a delay model attached "
                         f"({algo.delay!r}); stacked delays are undefined")
    cfg = StalenessConfig(model=model, policy=parse_policy(policy), seed=seed)
    return dataclasses.replace(algo, delay=cfg)


def with_topology(algo: RoundEngine, topology, *, seed: int = 0,
                  tier_compression=None) -> RoundEngine:
    """Non-star aggregation geometry for ANY engine algorithm: hierarchical
    (edge-aggregator tree) or gossip (doubly-stochastic mixing) reduction
    at the aggregation seam (see repro/core/topology.py).

    ``topology`` is a spec string (``"hier:g8"``, ``"hier:16x4"``,
    ``"ring"``, ``"torus"``, ``"er:0.4"``, ``"er:0.4:t"`` for a per-round
    resampled graph; gossip specs take a trailing ``":sparse"`` selecting
    the padded neighbor-exchange lowering) or a
    :class:`~repro.core.topology.Topology` object; ``seed`` keys
    stochastic graph draws and tier-compression dither (domain-separated
    from the participation / compression / delay streams).
    ``tier_compression`` (hierarchies only) re-compresses interior
    aggregator-tier uplinks with any compressor spec — see topology.py's
    `Tier recompression`. Star specs (``"star"`` / ``"none"`` / a
    :class:`~repro.core.topology.Star` object) are exact no-ops — the
    algorithm object is returned unchanged.

    The topology applies wherever the engine reduces across clients — the
    aggregating step, FedLin's round-start gradient exchange, and the
    warm-up aggregation at ``init`` — and receives the SAME per-client
    weight vector the star engine uses (uniform, the participation mask,
    or the stale policy's weights), so it composes with
    ``with_compression`` / ``with_participation`` / ``with_delay`` in any
    factory order."""
    topo = parse_topology(topology, algo.n_clients, seed=seed,
                          tier_compression=tier_compression)
    if topo is None:
        return algo
    if algo.topology is not None:
        raise ValueError("algorithm already has a topology attached "
                         f"({algo.topology!r}); stacked topologies are "
                         "undefined")
    if algo.cohort is not None and not topo.supports_cohort:
        raise ValueError(
            f"topology {topo!r} does not support cohort execution (gossip "
            "mixing has no server to sample a cohort — every node exchanges "
            "with its neighbors every round)")
    return dataclasses.replace(algo, topology=topo)


def with_cohort(algo: RoundEngine, cohort, *, seed: int = 0) -> RoundEngine:
    """O(cohort) round execution for ANY engine algorithm: keep the
    per-client state server-side and run each round on a gathered
    fixed-shape cohort only (see the module docstring's `Cohort
    execution`).

    ``cohort`` is a size (int), a spec string (``"256"``,
    ``"block:256"``, ``"rr:256"``, optional trailing ``":dense"`` for the
    O(N) reference lowering) or a :class:`CohortSpec`; ``seed`` keys the
    stochastic selectors (domain-separated from every other engine
    stream). Identity specs (``None`` / ``"none"`` / ``0`` / ``size >=
    n_clients`` — the whole population trains anyway) are exact no-ops:
    the algorithm object is returned unchanged.

    Composition: attach the cohort LAST (after compression /
    participation / delay / topology) — the factory validates the
    already-attached axes. Gossip mixing topologies and specs whose own
    math crosses clients outside the engine seam (``cohort_compatible``
    False — FedLin with ``k_frac < 1``) are rejected."""
    spec = cohort if isinstance(cohort, CohortSpec) else parse_cohort(cohort)
    if spec is not None and not isinstance(cohort, CohortSpec):
        spec = dataclasses.replace(spec, seed=seed)
    if spec is None or spec.size >= algo.n_clients:
        if spec is not None and spec.size > algo.n_clients:
            raise ValueError(f"cohort size {spec.size} exceeds "
                             f"n_clients={algo.n_clients}")
        return algo
    if algo.cohort is not None:
        raise ValueError("algorithm already has a cohort attached "
                         f"({algo.cohort!r}); stacked cohorts are undefined")
    if not algo.cohort_compatible:
        raise ValueError(
            f"{algo.name} is not cohort-compatible: its spec performs a "
            "cross-client computation outside the engine's aggregation "
            "seam (FedLin's internal cross-client top-k needs the full "
            "population — use k_frac=1.0 / FedTrack, or move compression "
            "to with_compression)")
    if algo.topology is not None and not algo.topology.supports_cohort:
        raise ValueError(
            f"topology {algo.topology!r} does not support cohort execution "
            "(gossip mixing has no server to sample a cohort)")
    return dataclasses.replace(algo, cohort=spec)


def with_arena(algo: RoundEngine, enable: bool = True) -> RoundEngine:
    """Packed-parameter-arena execution for ANY engine algorithm: ``init``
    flattens the model pytree once into the contiguous lane-aligned
    ``[rows, 1024]`` buffer of core/arena.py, and every state / message /
    transform-memory tree stays packed for the life of the run — the
    per-leaf tree.map seam becomes a handful of whole-model array ops,
    unpacked only at the gradient boundary. Composes with every other
    factory in any order (the Arena is a pytree node, so compression /
    participation / delay / topology / cohort code paths are untouched),
    and is pinned <= 1e-12-equivalent to the per-leaf representation
    (tests/test_arena.py). ``enable=False`` is an exact no-op. Checkpoints
    flip between representations via ``core.arena.adapt_state``."""
    if not enable:
        return algo
    return dataclasses.replace(algo, arena=True)


def with_telemetry(algo: RoundEngine, telemetry=True) -> RoundEngine:
    """In-trace round telemetry for ANY engine algorithm (see
    repro/core/telemetry.py): the round captures per-round scalar metrics
    (gradient/message norms, compression error, participation, staleness
    ages, the ``sum_i d_i`` invariant residual, the consensus error) onto
    the runner's scan — no host sync, no extra algorithm state
    (checkpoints unaffected).

    ``telemetry`` is ``True`` / a :class:`~repro.core.telemetry.Telemetry`
    spec / any truthy spec string; disabled specs (``None`` / ``False`` /
    ``"none"`` / ``"off"``) are exact no-ops — the algorithm object is
    returned unchanged, so telemetry OFF is bitwise identical to the
    un-instrumented engine (pinned in tests/test_telemetry.py)."""
    spec = tele.parse_telemetry(telemetry)
    if spec is None:
        return algo
    return dataclasses.replace(algo, telemetry=spec)


# --------------------------------------------------------- multi-round driver
def make_round_runner(algo, grad_fn: GradFn, *, metric_fn=None,
                      repeat: bool = False, metric_with_batch: bool = False,
                      metric_before: bool = False, donate: bool = False):
    """Build the jitted K-round scan over ``algo.round``.

    * ``repeat=False`` (default): the returned ``run(state, batches)`` scans
      over stacked per-round batches (leaves ``[rounds, tau, clients, ...]``).
    * ``repeat=True``: ``run(state, batches, rounds)`` replays the SAME
      per-round batch pytree (leaves ``[tau, clients, ...]``) for ``rounds``
      rounds — the full-batch simulation mode.

    ``metric_fn(state) -> pytree`` is evaluated after every round and stacked
    into the second return value; with ``metric_with_batch=True`` it is
    called as ``metric_fn(state, round_batches)`` instead (the per-round
    ``[tau, clients, ...]`` pytree) — this is how ``FedTrainer.fit`` keeps
    its eval-loss series on-device inside the scan. ``metric_before=True``
    evaluates it on the state ENTERING each round instead, before any step
    has seen that round's batches. Keep ONE runner per training loop: jit
    caching is per function instance.

    ``donate=True`` donates the state argument (``donate_argnums=(0,)``)
    so the carry aliases in/out — for a cohort algorithm the scatter back
    into the ``[N, ...]`` client-state store then updates IN PLACE instead
    of copying O(N) state per call, which is what keeps round time
    O(cohort) and peak memory ~1x the store. The caller must rebind
    (``state = run(state, ...)``) and never touch the donated value again
    — callers that re-read the input state afterwards (e.g.
    ``simulate_quadratic``'s err(state0)) must keep the default.

    With telemetry attached (``with_telemetry``) each round's body runs
    under a :func:`repro.core.telemetry.collect` tape and the stacked ys
    become ``{"metric": ..., "telemetry": {...}}`` — split them with
    :func:`repro.core.telemetry.split_metrics`. Without telemetry the ys
    structure (and the traced jaxpr) is exactly the pre-telemetry one."""
    def _metric(s, b):
        if metric_fn is None:
            return None
        with tele.scope("fed.round_loss"):
            return metric_fn(s, b) if metric_with_batch else metric_fn(s)

    tel = getattr(algo, "telemetry", None)

    def _round(s, b):
        if tel is None:
            return algo.round(grad_fn, s, b), None
        with tele.collect() as tape:
            s = algo.round(grad_fn, s, b)
        with tele.scope("fed.telemetry"):
            return s, tel.finalize(tape, algo, s)

    def _step(s, b):
        from repro.core.arena import pin_layouts

        m = _metric(s, b) if metric_before else None
        s, tl = _round(s, b)
        s = pin_layouts(s)
        if not metric_before:
            m = _metric(s, b)
        return s, (m if tel is None else {"metric": m, "telemetry": tl})

    donate_kw = {"donate_argnums": (0,)} if donate else {}
    if repeat:
        def run(state, batches, rounds):
            return jax.lax.scan(lambda s, _: _step(s, batches), state, None,
                                length=rounds)

        return jax.jit(run, static_argnums=2, **donate_kw)

    def run(state, batches):
        return jax.lax.scan(_step, state, batches)

    return jax.jit(run, **donate_kw)


def scan_segments(start: int, total: int, is_boundary, *, max_rounds: int = 32):
    """Yield ``(first, last)`` round indices for jitted scan segments.

    Each segment ends at the next boundary round (inclusive — the round
    after which the caller wants to eval/checkpoint/log) or after
    ``max_rounds``, whichever comes first; the cap bounds the memory spent
    on stacked per-round batches. Shared by ``FedTrainer.fit`` and
    ``launch.train.run_training``."""
    r = start
    while r < total:
        cap = min(total - 1, r + max_rounds - 1)
        stop = next((s for s in range(r, cap) if is_boundary(s)), cap)
        yield r, stop
        r = stop + 1


def run_rounds(algo, grad_fn: GradFn, state, batches, *, rounds: int | None = None,
               metric_fn=None):
    """Run K communication rounds through one ``lax.scan`` (the shared
    driver behind ``simulate_quadratic`` and ``FedTrainer.fit``).

    With ``rounds=None``, ``batches`` leaves are ``[rounds, tau, clients,
    ...]`` stacks and the round count is their leading axis; with
    ``rounds=K``, ``batches`` is a single per-round pytree (leaves
    ``[tau, clients, ...]``) replayed every round. Returns
    ``(final_state, stacked_metrics)`` (metrics ``None`` without a hook)."""
    if rounds is not None:
        return make_round_runner(algo, grad_fn, metric_fn=metric_fn,
                                 repeat=True)(state, batches, rounds)
    return make_round_runner(algo, grad_fn, metric_fn=metric_fn)(state, batches)
