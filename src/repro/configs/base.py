"""Config dataclasses for models, federated training, and input shapes.

Every assigned architecture gets one ``configs/<id>.py`` exporting ``CONFIG``
(a :class:`ModelConfig` with the exact published hyper-parameters) plus a
``reduced()`` variant used by the CPU smoke tests (2 layers, d_model<=512,
<=4 experts).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Layer kinds used in ModelConfig.layer_pattern:
#   F  full causal self-attention + MLP
#   W  sliding-window causal self-attention + MLP
#   M  Mamba2 (SSD) block (attention-free); where the layer uses MoE, the
#      MoE tail of F/W layers follows it
#   Y  hybrid block: parallel attention + mamba heads (Hymba-style)
# The pattern string is tiled to ``num_layers`` (e.g. gemma3 "WWWWWF").
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """Multi-head Latent Attention (DeepSeek-V2/V3, MiniCPM3)."""

    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """Routed mixture-of-experts FFN."""

    num_experts: int
    top_k: int
    expert_d_ff: int
    num_shared_experts: int = 0
    shared_d_ff: int = 0
    # layers [0, first_dense_layers) use a dense MLP instead of MoE
    first_dense_layers: int = 0
    dense_d_ff: int = 0
    router_aux_coef: float = 0.001
    # GShard dispatch tuning (§Perf HC1): dispatch/combine einsum cost is
    # ∝ group_size · capacity_factor, so smaller groups cut the one-hot
    # overhead linearly (at the cost of more scan iterations)
    gshard_group_size: int = 2048
    capacity_factor: float = 1.25
    # the experts this device holds under expert parallelism: experts
    # [first_held_expert, first_held_expert + held_experts) of the
    # num_experts the router scores; 0 holds all of them
    held_experts: int = 0
    first_held_expert: int = 0

    def held(self) -> int:
        """How many experts this device holds."""
        return self.held_experts or self.num_experts


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    """Mamba2 / SSD state-space block."""

    d_state: int
    head_dim: int = 64
    expand: int = 2
    conv_kernel: int = 4
    chunk_size: int = 256
    n_groups: int = 1

    def d_inner(self, d_model: int) -> int:
        return self.expand * d_model

    def n_heads(self, d_model: int) -> int:
        return self.d_inner(d_model) // self.head_dim


@dataclasses.dataclass(frozen=True)
class EncoderConfig:
    """Encoder tower for encoder-decoder models (whisper)."""

    num_layers: int
    num_frames: int  # stub conv frontend output length (whisper: 1500)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    arch_type: str  # dense | moe | ssm | hybrid | audio | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    citation: str = ""

    layer_pattern: str = "F"
    sliding_window: int = 0  # required if pattern contains W
    mlp_kind: str = "silu_gated"  # silu_gated | gelu_gated | gelu
    norm_kind: str = "rmsnorm"  # rmsnorm | layernorm
    rope_theta: Optional[float] = 10000.0  # None: no position embedding (NoPE)
    tie_embeddings: bool = True
    logit_softcap: float = 0.0
    scale_embeddings: bool = False  # gemma-family: embeds *= sqrt(d_model)
    # published model constants (granite-4.0-h): attention scores times
    # attention_multiplier (None: 1/sqrt(head_dim)), embeddings times
    # embedding_multiplier, each residual branch times
    # residual_multiplier, logits over logits_scaling; 1.0 adds no op
    attention_multiplier: Optional[float] = None
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    moe_impl: str = "ragged"  # ragged | gshard (dispatch implementation)
    # >0: streaming cross-entropy over vocab chunks of this size (never
    # materialises the (tokens, V) fp32 logits — §Perf HC3)
    loss_chunk_vocab: int = 0

    mla: Optional[MLAConfig] = None
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    encoder: Optional[EncoderConfig] = None

    # vlm/audio prefix: number of stub modality tokens prepended to text
    num_prefix_tokens: int = 0

    # precision policy
    param_dtype: str = "float32"
    compute_dtype: str = "float32"

    # distribution defaults (overridable per round-plan)
    remat: bool = True

    def __post_init__(self):
        mo = self.moe
        if mo is not None and mo.held() != mo.num_experts:
            if self.moe_impl == "gshard":
                raise ValueError("an expert share (held_experts) needs the "
                                 "dropless ragged dispatch, not moe_impl='gshard'")
            if not (0 <= mo.first_held_expert
                    and mo.first_held_expert + mo.held() <= mo.num_experts):
                raise ValueError(f"held experts [{mo.first_held_expert}, "
                                 f"{mo.first_held_expert + mo.held()}) lie outside "
                                 f"the {mo.num_experts} the router scores")

    def pattern_for_layers(self) -> str:
        p = (self.layer_pattern * ((self.num_layers // len(self.layer_pattern)) + 1))
        return p[: self.num_layers]

    def layer_uses_moe(self, layer_idx: int) -> bool:
        return self.moe is not None and layer_idx >= self.moe.first_dense_layers

    def num_params(self) -> int:
        """Analytic parameter count (approximate: matches our impl exactly)."""
        from repro.models.model import count_params_analytic

        return count_params_analytic(self)


@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


@dataclasses.dataclass(frozen=True)
class FedRoundSpec:
    """How one communication round maps onto a global batch.

    ``global_batch == num_sampled * local_steps * local_batch`` — a round
    consumes the whole global batch: each of the S sampled clients runs K
    local steps on b_local sequences each.
    """

    algorithm: str  # any name in repro.core.api's algorithm registry
    num_clients: int  # N
    num_sampled: int  # S
    local_steps: int  # K
    local_batch: int  # b_local
    eta_l: float = 0.05
    eta_g: float = 1.0
    scaffold_option: str = "II"  # I | II
    fedprox_mu: float = 1.0
    strategy: str = "client_parallel"  # client_parallel | client_sequential
    # server optimizer applied to the aggregated round delta (repro.core.api
    # registry: sgd | momentum | adam). "" resolves to "momentum" when
    # server_momentum > 0, else the algorithm's default.
    server_optimizer: str = ""
    # beyond-paper: heavy-ball momentum on the aggregated server update
    # (FedAvgM, Hsu et al. 2019) — composes with any algorithm; also the
    # beta of the "momentum" server optimizer. Momentum-default algorithms
    # (scaffold_m/fedavgm) write 0.9 here when left unset, so the running
    # beta is always visible on the spec.
    server_momentum: float = 0.0
    # FedAdam (Reddi et al. 2021) moments for the "adam" server optimizer
    server_beta1: float = 0.9
    server_beta2: float = 0.99
    server_eps: float = 1e-8
    # beyond-paper: uplink compression of the client deltas with
    # client-side error feedback. ``compress`` names a codec in the
    # repro.core.compression registry (none | int8_ef | topk_ef |
    # randk_ef | sign_ef) and is the source of truth; after construction
    # it is always a concrete name. ``compress_uplink`` is back-compat
    # constructor sugar ("" + True -> int8_ef, the pre-registry codec),
    # declared as an InitVar so ``dataclasses.replace`` never carries a
    # stale copy: replace(spec, compress=...) flips compression freely,
    # while an explicitly contradictory flag (e.g. replace(spec,
    # compress_uplink=False) on a compressed spec) fails loudly in
    # __post_init__ instead of being silently overwritten. Reads of
    # ``spec.compress_uplink`` hit the property installed below the
    # class: the live ``compress != "none"`` mirror.
    compress: str = ""
    compress_uplink: dataclasses.InitVar[Optional[bool]] = None
    # k kept coordinates per leaf for the topk_ef / randk_ef codecs
    compress_k: int = 32
    # optional compression of the server->client broadcast (x, c) pair
    # (stateless: the server re-sends fresh state every round)
    compress_downlink: str = "none"
    # paper §2 "weighted case": aggregate client deltas weighted by their
    # dataset sizes instead of uniformly
    weighted_aggregation: bool = False
    # beyond-paper: the client's inner optimizer, a name in the
    # repro.core.local_solver registry (sgd | momentum | adam |
    # sgd_sched). "sgd" (also resolved from "") is the paper's plain
    # corrected step, bit-for-bit the pre-registry path. Stateful
    # solvers (momentum/adam) persist per-client slots in the client
    # store next to c_i (DESIGN.md §12).
    local_solver: str = "sgd"
    # heavy-ball beta of the "momentum" local solver / beta1 of "adam"
    local_momentum: float = 0.9
    # second-moment decay of the "adam" local solver
    local_beta2: float = 0.99
    # per-local-step eta_l schedule of the "sgd_sched" solver
    # (repro.optim.schedules: constant | warmup | cosine); must stay ""
    # for every other solver (rejected loudly, like the whole-batch
    # combinations below)
    eta_l_schedule: str = ""
    # beyond-paper: differential privacy of the aggregated update, a name
    # in the repro.core.privatizer registry (none | server_gauss |
    # distributed_gauss — DESIGN.md §16). Gaussian privatizers L2-clip
    # every client delta to ``clip_norm``, add noise calibrated to
    # ``clip_norm * noise_multiplier`` (at the server post-aggregation or
    # distributed across clients pre-aggregation), and surface the
    # moments-accountant ``dp_epsilon`` at ``dp_delta`` in every round's
    # metrics. Composition order is clip -> compress -> aggregate.
    privatizer: str = "none"
    clip_norm: float = 0.0
    noise_multiplier: float = 0.0
    dp_delta: float = 1e-5
    # beyond-paper: parameter-efficient federated updates, a name in the
    # repro.core.update_space registry (full | lora | head_only —
    # DESIGN.md §17). "full" (also resolved from "") is the identity:
    # the engine trains the whole parameter pytree, bit-for-bit the
    # pre-registry path. Any other space freezes the base parameters at
    # round 0 and makes ``server.x`` the trainable-delta pytree, so c,
    # c_i, residuals, solver slots, store rows and bytes_up/bytes_down
    # all shrink to delta shape. ``update_targets`` is a comma-separated
    # fnmatch pattern list over escaped leaf paths ("" = the lora
    # defaults; required for head_only).
    update_space: str = ""
    lora_rank: int = 0
    lora_alpha: float = 0.0
    update_targets: str = ""
    # beyond-paper perf: fuse the whole K-step local loop into ONE Pallas
    # kernel per dtype group per round
    # (kernels/scaffold_update/megakernel.py, DESIGN.md §15). Like
    # use_fused_update this is a kernel-routing hint, never a semantics
    # change: combinations the kernel can't express (non-quadratic grads,
    # the adam solver, whole-batch algorithms, FedProx) fall back to the
    # per-step path and surface a ``megakernel_fallback_reason`` in round
    # metrics, mirroring ``scan_fallback_reason``.
    use_megakernel: bool = False

    def __post_init__(self, compress_uplink):
        # lazy import: the registries live above configs in the layering
        from repro.core.api import (
            algorithm_names,
            get_algorithm,
            server_optimizer_names,
        )

        from repro.core.compression import compressor_names
        from repro.core.local_solver import local_solver_names
        from repro.core.privatizer import get_privatizer, privatizer_names
        from repro.core.update_space import (
            get_update_space,
            update_space_names,
        )
        from repro.optim.schedules import schedule_names

        assert self.algorithm in algorithm_names(), (
            self.algorithm, algorithm_names())
        assert self.server_optimizer in ("",) + server_optimizer_names(), (
            self.server_optimizer, server_optimizer_names())
        if self.local_solver == "":
            object.__setattr__(self, "local_solver", "sgd")
        assert self.local_solver in local_solver_names(), (
            self.local_solver, local_solver_names())
        assert 0.0 <= self.local_momentum < 1.0, self.local_momentum
        assert 0.0 <= self.local_beta2 < 1.0, self.local_beta2
        if self.local_solver == "sgd_sched":
            assert self.eta_l_schedule in schedule_names(), (
                f"local_solver='sgd_sched' needs eta_l_schedule in "
                f"{schedule_names()}, got {self.eta_l_schedule!r}")
        else:
            assert self.eta_l_schedule == "", (
                f"eta_l_schedule={self.eta_l_schedule!r} has no effect for "
                f"local_solver={self.local_solver!r}; use "
                f"local_solver='sgd_sched'")
        if self.compress == "":
            # only an *explicit* bool resolves "" to the legacy codec; a
            # carried _CompressUplinkMirror (replace(spec, compress=""))
            # must not smuggle the pre-replace codec back in as int8_ef
            explicit = (compress_uplink
                        if isinstance(compress_uplink, bool) else False)
            object.__setattr__(
                self, "compress", "int8_ef" if explicit else "none")
        assert self.compress in compressor_names(), (
            self.compress, compressor_names())
        assert self.compress_downlink in compressor_names(), (
            self.compress_downlink, compressor_names())
        assert self.compress_k >= 1, self.compress_k
        # An explicit bool flag must agree with the resolved codec —
        # reject a contradiction (e.g. replace(spec, compress_uplink=
        # False) on a compressed spec) instead of silently overriding.
        # A carried _CompressUplinkMirror (dataclasses.replace re-passes
        # the property value) reflects the *pre-replace* codec and is
        # ignored: ``compress`` is the source of truth.
        if isinstance(compress_uplink, bool):
            assert compress_uplink == (self.compress != "none"), (
                f"compress_uplink={compress_uplink} contradicts "
                f"compress={self.compress!r}; set compress "
                f"('none' disables) instead of the back-compat flag")
        assert self.privatizer in privatizer_names(), (
            self.privatizer, privatizer_names())
        priv = get_privatizer(self.privatizer)
        if priv.clips:
            # the Gaussian mechanisms are meaningless without a finite
            # sensitivity bound and a noise scale — reject silent no-DP
            assert self.clip_norm > 0.0, (
                f"privatizer={self.privatizer!r} needs clip_norm > 0 "
                f"(the L2 sensitivity bound), got {self.clip_norm}")
            assert self.noise_multiplier > 0.0, (
                f"privatizer={self.privatizer!r} needs noise_multiplier > 0 "
                f"(z of the Gaussian mechanism), got "
                f"{self.noise_multiplier}")
            assert 0.0 < self.dp_delta < 1.0, (
                f"dp_delta must lie in (0, 1), got {self.dp_delta}")
            # the noise std is calibrated for the uniform S-client mean;
            # a size-weighted mean changes per-client sensitivity and
            # would silently void the accountant
            assert not self.weighted_aggregation, (
                f"privatizer={self.privatizer!r} noise is calibrated for "
                f"the uniform mean; weighted_aggregation is unsupported")
        else:
            assert self.clip_norm == 0.0, (
                f"clip_norm={self.clip_norm} has no effect for "
                f"privatizer={self.privatizer!r}")
            assert self.noise_multiplier == 0.0, (
                f"noise_multiplier={self.noise_multiplier} has no effect "
                f"for privatizer={self.privatizer!r}")
        if self.update_space == "":
            object.__setattr__(self, "update_space", "full")
        assert self.update_space in update_space_names(), (
            self.update_space, update_space_names())
        space = get_update_space(self.update_space)
        if space.uses_rank:
            # rank-0 degeneracy (an adapter that trains nothing) is
            # rejected loudly here, before any engine state is built
            assert self.lora_rank >= 1, (
                f"update_space={self.update_space!r} needs lora_rank >= 1, "
                f"got {self.lora_rank}")
            assert self.lora_alpha >= 0.0, self.lora_alpha
        else:
            # selection knobs of the other spaces must not dangle
            assert self.lora_rank == 0, (
                f"lora_rank={self.lora_rank} has no effect for "
                f"update_space={self.update_space!r}")
            assert self.lora_alpha == 0.0, (
                f"lora_alpha={self.lora_alpha} has no effect for "
                f"update_space={self.update_space!r}")
        if space.requires_targets:
            assert self.update_targets != "", (
                f"update_space={self.update_space!r} needs update_targets "
                f"(an empty selection trains nothing)")
        if not space.trains_subset:
            assert self.update_targets == "", (
                f"update_targets={self.update_targets!r} has no effect for "
                f"update_space={self.update_space!r}")
        algo = get_algorithm(self.algorithm)
        if (self.server_optimizer == "" and self.server_momentum == 0.0
                and algo.default_server_optimizer == "momentum"):
            # momentum-default algorithms (scaffold_m/fedavgm) get a visible
            # beta on the spec; an *explicit* server_optimizer="momentum"
            # keeps server_momentum as given, so beta=0.0 stays expressible
            object.__setattr__(self, "server_momentum", 0.9)
        if algo.whole_batch:
            # the sgd baseline takes one pooled server step: per-client
            # weights, server-optimizer slots and uplink compression never
            # enter its round — reject them loudly rather than no-op
            assert not self.weighted_aggregation, (
                f"weighted_aggregation has no effect for whole-batch "
                f"{self.algorithm!r}")
            assert self.server_optimizer in ("", "sgd"), (
                f"server_optimizer={self.server_optimizer!r} has no effect "
                f"for whole-batch {self.algorithm!r}")
            assert self.server_momentum == 0.0, (
                f"server_momentum has no effect for whole-batch "
                f"{self.algorithm!r}")
            assert not self.compress_uplink, (
                f"compress_uplink has no effect for whole-batch "
                f"{self.algorithm!r}")
            assert self.compress_downlink == "none", (
                f"compress_downlink has no effect for whole-batch "
                f"{self.algorithm!r}")
            # there are no per-client deltas to clip or noise
            assert self.privatizer == "none", (
                f"privatizer={self.privatizer!r} has no effect for "
                f"whole-batch {self.algorithm!r}")
            # no local steps at all: a non-trivial local solver (incl.
            # every stateful one) would silently never run
            assert self.local_solver == "sgd", (
                f"local_solver={self.local_solver!r} has no effect for "
                f"whole-batch {self.algorithm!r}")
        assert self.scaffold_option in ("I", "II")
        assert self.strategy in ("client_parallel", "client_sequential")
        assert self.num_sampled <= self.num_clients

    @property
    def global_batch(self) -> int:
        return self.num_sampled * self.local_steps * self.local_batch


class _CompressUplinkMirror(int):
    """Truthy/falsy view of ``compress != "none"`` returned by the
    ``FedRoundSpec.compress_uplink`` property. An ``int`` subclass
    (``bool`` is final) so ``__post_init__`` can tell the value
    ``dataclasses.replace`` automatically re-passes (a stale mirror of
    the *pre-replace* codec — recomputed, never binding) apart from an
    explicit user bool (validated against the codec)."""

    def __repr__(self):
        return repr(bool(self))


# the live "uplink codec active" mirror (InitVars are not stored, so the
# read surface is installed post-class; the generated __init__ captured
# the InitVar default before this assignment)
FedRoundSpec.compress_uplink = property(
    lambda self: _CompressUplinkMirror(self.compress != "none"))


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    model: ModelConfig
    round_spec: FedRoundSpec
    seq_len: int = 1024
    rounds: int = 100
    seed: int = 0
    log_every: int = 10
    eval_every: int = 50
    checkpoint_every: int = 0
    checkpoint_dir: str = ""
