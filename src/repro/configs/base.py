"""Architecture + workload-shape config system.

Every assigned architecture gets one ``ArchConfig`` in its own module under
``repro.configs``; the registry maps ``--arch <id>`` to it. ``reduced()``
produces the CPU-smoke-test variant of the same family (<=2 layers,
d_model<=512, <=4 experts) as required by the assignment.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                      # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    citation: str = ""
    head_dim: Optional[int] = None   # default d_model // n_heads

    # attention variants
    qk_norm: bool = False
    attention: str = "full"          # full | sliding | chunked
    window: int = 4096               # sliding-window size
    chunk: int = 8192                # chunked-local (iRoPE) chunk size
    rope_theta: float = 1e4
    use_rope: bool = True
    attn_bias: bool = False

    # mlp
    activation: str = "swiglu"       # swiglu | geglu | gelu
    mlp_bias: bool = False

    # norm / embeddings
    norm: str = "rmsnorm"            # rmsnorm | layernorm
    tie_embeddings: bool = False
    embed_scale: bool = False        # gemma: multiply embeddings by sqrt(d)

    # granite's multipliers; at these defaults none adds an operation
    embedding_multiplier: float = 1.0   # embeddings times this
    residual_multiplier: float = 1.0    # each block branch times this
    attention_multiplier: Optional[float] = None  # None: 1/sqrt(head_dim)
    logits_scaling: float = 1.0         # logits divided by this

    # moe: ``n_experts`` experts are held here, experts ``expert_offset`` ..
    # ``expert_offset + n_experts - 1`` of the ``router_experts`` the router
    # scores (None: all of them are held)
    n_experts: int = 0
    experts_per_token: int = 0
    router_experts: Optional[int] = None
    expert_offset: int = 0
    moe_shared_expert: bool = False
    capacity_factor: float = 1.25

    # ssm (mamba2)
    ssm_state: int = 0
    ssm_headdim: int = 64
    ssm_expand: int = 2
    ssm_conv: int = 4

    # hybrid (zamba2): one SHARED attention(+MLP) block applied every k layers
    shared_attn_every: int = 0

    # modality frontends (stubs): precomputed embeddings prepended/consumed
    modality: str = "text"           # text | vision | audio
    n_modal_tokens: int = 0          # vision: image-patch tokens per sample
    encoder_layers: int = 0          # audio: enc-dec encoder depth
    encoder_len: int = 1500          # audio: encoder frames

    # numerics / lowering
    dtype: str = "float32"           # activations
    param_dtype: str = "float32"
    remat: bool = True
    scan_layers: bool = True
    attn_block_size: int = 512
    #: use the Pallas kernels for attention / SSD (TPU target; interpret
    #: mode on CPU — enabled in tests/integration, off for XLA dry-runs
    #: since Pallas-TPU can't lower on the CPU host backend).
    use_pallas_attention: bool = False
    use_pallas_ssd: bool = False

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(self, "head_dim", self.d_model // max(self.n_heads, 1))
        if self.router_experts is None:
            object.__setattr__(self, "router_experts", self.n_experts)
        if not 0 <= self.expert_offset <= self.router_experts - self.n_experts:
            raise ValueError(
                f"{self.name}: experts {self.expert_offset}.."
                f"{self.expert_offset + self.n_experts - 1} are not among "
                f"the router's {self.router_experts}")
        if self.n_heads and self.n_kv_heads:
            assert self.n_heads % self.n_kv_heads == 0, (self.n_heads, self.n_kv_heads)

    # ------------------------------------------------------------- variants
    def reduced(self) -> "ArchConfig":
        """Smoke-test variant of the same family: 2 layers, d_model<=512,
        <=4 experts, small vocab/window — runs a train step on one CPU."""
        d = min(self.d_model, 256)
        heads = min(self.n_heads, 4) or 0
        kv = min(self.n_kv_heads, heads) if self.n_kv_heads else 0
        if heads and kv:
            kv = heads // max(1, heads // kv)  # keep a GQA ratio > 1 if it had one
        changes = dict(
            n_layers=2,
            d_model=d,
            n_heads=heads,
            n_kv_heads=kv,
            head_dim=(d // heads if heads else None),
            d_ff=min(self.d_ff, 512) if self.d_ff else 0,
            vocab_size=min(self.vocab_size, 512),
            window=min(self.window, 64),
            chunk=min(self.chunk, 64),
            n_experts=min(self.n_experts, 4) if self.n_experts else 0,
            router_experts=(min(self.router_experts, 4)
                            if self.router_experts else 0),
            expert_offset=0,
            experts_per_token=(min(self.experts_per_token, 2)
                               if self.experts_per_token else 0),
            # drop-free capacity for the mesh lowerings' capacity dispatch
            # (production configs keep the real 1.25 and drop there).
            capacity_factor=float(max(self.n_experts, 1)),
            ssm_state=min(self.ssm_state, 16) if self.ssm_state else 0,
            ssm_headdim=16 if self.ssm_state else self.ssm_headdim,
            shared_attn_every=(1 if self.shared_attn_every else 0),
            n_modal_tokens=min(self.n_modal_tokens, 16),
            encoder_layers=min(self.encoder_layers, 2),
            encoder_len=min(self.encoder_len, 32),
            scan_layers=False,
            remat=False,
        )
        return dataclasses.replace(self, **changes)

    def with_dtype(self, dtype: str, param_dtype: str | None = None) -> "ArchConfig":
        return dataclasses.replace(self, dtype=dtype,
                                   param_dtype=param_dtype or dtype)


@dataclasses.dataclass(frozen=True)
class FedScenario:
    """Launch-level federated-scenario knob: which compressor stack rides
    the uplink, what fraction of clients participates per round, and which
    delay model / stale-aggregation policy simulates asynchronous uplinks.

    ``compression`` is a spec string for
    :func:`repro.core.compressors.from_spec` — ``"none"``, ``"bf16"``,
    ``"topk:0.3"`` (per-client), ``"randk:0.25"``, ``"q8"``,
    ``"shift:q8"`` (DIANA-style shifted quantization), chains via ``+``
    (``"randk:0.5+q8"``), ``"ef:"`` prefix to force error feedback.
    ``error_feedback=None`` auto-wraps biased compressors only.
    ``compression_plan`` is the PER-LEAF alternative
    (:func:`repro.core.compressors.parse_plan`): first-match-wins
    ``pattern:spec`` rules over leaf paths, e.g.
    ``"embed*:q12,ln*:bf16,*:shift:q6"`` — mutually exclusive with
    ``compression``.

    ``delay`` is a spec string for :func:`repro.core.staleness.parse_delay`
    — ``"none"``, ``"fixed:2"`` (periodic uplink), ``"rr:1"`` (round-robin
    straggler), ``"geom:0.5"`` (Bernoulli arrivals) — with
    ``stale_policy`` one of ``"drop"`` / ``"last"`` / ``"poly:<a>"``.

    ``topology`` is a spec string for
    :func:`repro.core.topology.parse_topology` — ``"star"`` (the flat
    default), ``"hier:g8"`` / ``"hier:16x4"`` (edge-aggregator tree with
    per-hop comm accounting), ``"ring"`` / ``"torus"`` / ``"er:0.4"``
    (doubly-stochastic gossip mixing; ``"er:0.4:t"`` resamples the graph
    every round; a trailing ``":sparse"`` — ``"ring:sparse"``,
    ``"er:0.4:t:sparse"`` — selects the padded neighbor-exchange
    lowering, O(edges) instead of the dense N^2 contraction).
    ``tier_compression`` (hierarchies only) is a compressor spec applied
    to the interior edge->root tier uplinks (``"shift:q8"`` compresses
    the FULL uplink end to end), with per-hop bit-true accounting.

    ``cohort`` is a spec string (or int) for
    :func:`repro.core.engine.parse_cohort` — ``"none"`` (dense: every
    round touches all N client rows), ``256`` / ``"256"`` (uniformly
    sampled cohort of that size), ``"block:256"`` / ``"rr:256"``
    (contiguous-block / round-robin selectors), optional trailing
    ``":dense"`` to force the dense reference lowering. With a cohort
    the round's per-client work is O(cohort): the engine gathers the
    sampled rows from the server-side client-state store, runs the local
    scan on the cohort only, and scatters updates back.

    ``arena`` lowers the engine's stacked client store onto the packed
    parameter arena (:mod:`repro.core.arena`): the model pytree lives as
    one contiguous lane-aligned ``[clients, rows, 1024]`` buffer for the
    whole round and unpacks only at the gradient boundary. Composes with
    every knob above and is pinned <=1e-12-equivalent to the per-leaf
    lowering, so checkpoints and shardings stay flippable either way.

    ``telemetry`` attaches the in-trace round telemetry spec
    (:mod:`repro.core.telemetry`): per-round metric capture inside the
    jitted round (norms, compression error, invariant residual, consensus
    error, participation, staleness ages) with no host sync. ``False`` /
    ``"none"`` (the default) is a BITWISE no-op — the algorithm object is
    returned unchanged; any truthy value (``True``, a sink spec string, a
    ``Telemetry`` object) enables the default metric set.

    ``apply`` composes the scenario onto ANY engine algorithm — the same
    expression the simulation tests pin, now reachable from the production
    LM loop (`launch/train.py --compression ... --participation ...
    --delay ... --stale-policy ... --topology ... --tier-compression
    ... --cohort ... --arena ... --telemetry jsonl:path`)."""

    compression: str = "none"
    #: per-leaf compression plan — comma-separated ``pattern:spec`` rules
    #: for :func:`repro.core.compressors.parse_plan`, first-match-wins
    #: (``"embed*:q12,ln*:bf16,*:shift:q6"``; patterns glob slash-joined
    #: leaf paths or name flatten-order leaf indices), or a ready
    #: :class:`~repro.core.compressors.CompressionPlan` (e.g. from
    #: ``plan.allocate``). Mutually exclusive with ``compression`` — a
    #: plan IS the uplink compressor; ``error_feedback`` applies per rule.
    compression_plan: Any = "none"
    participation: float = 1.0
    delay: str = "none"
    stale_policy: str = "last"
    topology: str = "star"
    tier_compression: str = "none"
    error_feedback: bool | None = None
    cohort: int | str | None = "none"
    arena: bool = False
    telemetry: Any = False
    seed: int = 0

    def apply(self, algo):
        from repro.core.compressors import from_spec, parse_plan
        from repro.core.engine import (with_arena, with_cohort,
                                       with_compression, with_delay,
                                       with_participation, with_telemetry,
                                       with_topology)

        algo = with_arena(algo, self.arena)
        algo = with_topology(algo, self.topology, seed=self.seed,
                             tier_compression=self.tier_compression)
        algo = with_participation(algo, self.participation, seed=self.seed)
        comp = from_spec(self.compression)  # one normalizer for the grammar
        plan = parse_plan(self.compression_plan,
                          error_feedback=self.error_feedback)
        if comp is not None and plan is not None:
            raise ValueError(
                "pass EITHER compression= or compression_plan=, not both — "
                "a plan IS the uplink compressor (put a '*:<spec>' "
                "catch-all rule in the plan for the uniform part): "
                f"compression={self.compression!r}, "
                f"compression_plan={self.compression_plan!r}")
        if plan is not None:
            algo = with_compression(algo, compressor=plan, seed=self.seed)
        if comp is not None:
            algo = with_compression(algo, compressor=comp,
                                    error_feedback=self.error_feedback,
                                    seed=self.seed)
        algo = with_delay(algo, self.delay, policy=self.stale_policy,
                          seed=self.seed)
        # cohort last: it wraps the fully-composed spec so every transform
        # above runs inside the O(cohort) gathered round.
        algo = with_cohort(algo, self.cohort, seed=self.seed)
        # telemetry is an observer — attach after everything so captures
        # see the final composed round (exact no-op when disabled).
        return with_telemetry(algo, self.telemetry)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """One assigned workload shape."""

    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


INPUT_SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


def supports_shape(cfg: ArchConfig, shape: ShapeConfig) -> tuple[bool, str]:
    """Shape-coverage policy (documented in DESIGN.md §5)."""
    if shape.name == "long_500k":
        sub_quadratic = (
            cfg.family in ("ssm", "hybrid")
            or cfg.attention in ("sliding", "chunked")
        )
        if not sub_quadratic:
            return False, ("pure full-attention arch: 500k decode requires a "
                           "sub-quadratic attention variant (DESIGN.md §5)")
    return True, ""
