"""Configuration system for the repro framework.

Frozen dataclasses, explicit field-by-field construction (no magic), and a
small validation layer.  Every assigned architecture gets a module in
``repro.configs`` that builds a :class:`ModelConfig`; run-level knobs
(parallelism, Hermes hyper-parameters, data) live in sibling dataclasses so a
full experiment is a single :class:`RunConfig` value that can be serialized to
JSON for checkpoint manifests.
"""
from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

# ---------------------------------------------------------------------------
# Model family tags (mirror the assignment brief).
# ---------------------------------------------------------------------------
FAMILY_DENSE = "dense"
FAMILY_MOE = "moe"
FAMILY_SSM = "ssm"
FAMILY_HYBRID = "hybrid"
FAMILY_VLM = "vlm"
FAMILY_AUDIO = "audio"
FAMILY_CNN = "cnn"  # the paper's own small models

VALID_FAMILIES = (
    FAMILY_DENSE,
    FAMILY_MOE,
    FAMILY_SSM,
    FAMILY_HYBRID,
    FAMILY_VLM,
    FAMILY_AUDIO,
    FAMILY_CNN,
)


@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts block configuration."""

    num_experts: int
    top_k: int
    expert_ff: int  # per-expert FFN hidden size
    num_shared_experts: int = 0
    shared_ff: int = 0  # hidden size of the shared expert(s), 0 = same as expert_ff
    router_jitter: float = 0.0
    capacity_factor: float = 1.25
    # top-k weights renormalised to sum to one (False: the router's softmax
    # probabilities themselves, as DeepSeek-V2-Lite's scaling factor of 1)
    norm_topk_prob: bool = True
    # every assignment reaches its expert: grouped products over the
    # sorted assignments, no capacity (``models/moe.py: moe_dropless``)
    dropless: bool = False
    # sequence-wise balance-loss coefficient (DeepSeek-V2's ``seq_aux``
    # term, one per expert layer, added to the training loss); 0 = none
    aux_loss_alpha: float = 0.0
    # the chip's share under expert parallelism: routed experts
    # ``first_held .. first_held + num_held - 1`` live here (0 = all).  The
    # router still scores every expert; assignments to absent experts add
    # nothing on this chip.
    num_held: int = 0
    first_held: int = 0

    @property
    def held(self) -> int:
        return self.num_held or self.num_experts

    def validate(self) -> None:
        assert self.num_experts >= 1
        assert 1 <= self.top_k <= self.num_experts
        assert self.expert_ff >= 1
        assert 0 <= self.first_held and \
            self.first_held + self.held <= self.num_experts
        # the capacity path stacks every expert
        assert self.dropless or self.held == self.num_experts


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head Latent Attention (DeepSeek-V2) configuration."""

    kv_lora_rank: int  # compressed KV latent dim (paper: 512 for v2-lite)
    q_lora_rank: int = 0  # 0 = full-rank queries (v2-lite uses full-rank q)
    rope_head_dim: int = 64  # decoupled RoPE key/query head dim
    v_head_dim: int = 0  # 0 = same as nope head dim


@dataclass(frozen=True)
class YarnConfig:
    """YaRN rope scaling (arXiv:2309.00071; DeepSeek-V2's ``rope_scaling``).

    Rotary frequencies above ``beta_fast`` rotations over the original
    context keep their value, those below ``beta_slow`` are divided by
    ``factor``, with a linear ramp between; cos/sin carry
    mscale(mscale) / mscale(mscale_all_dim), and an MLA softmax scale
    mscale(mscale_all_dim)**2, where mscale(m) = 0.1 m ln(factor) + 1."""

    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclass(frozen=True)
class RecurrentConfig:
    """Linear-recurrence blocks (RWKV6 / RG-LRU)."""

    kind: str  # "rwkv6" | "rglru"
    lru_width: int = 0  # RG-LRU recurrence width (0 = d_model)
    conv1d_width: int = 4  # temporal conv width in the RecurrentGemma block
    block_pattern: Tuple[str, ...] = ()  # e.g. ("rec","rec","attn") for 1:2 hybrid


@dataclass(frozen=True)
class ModelConfig:
    """A single architecture; shapes follow the assignment brief verbatim."""

    name: str
    family: str
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0  # 0 => d_model // num_heads
    # --- attention options -------------------------------------------------
    qk_norm: bool = False
    attn_window: int = 0  # 0 = full/global attention; >0 = local sliding window
    rope_theta: float = 10000.0
    rope_scaling: Optional[YarnConfig] = None
    use_rope: bool = True
    # --- block options ------------------------------------------------------
    mlp_kind: str = "swiglu"  # swiglu | gelu | relu_sq
    norm_kind: str = "rmsnorm"  # rmsnorm | layernorm
    tie_embeddings: bool = False
    moe: Optional[MoEConfig] = None
    # leading layers that keep a dense MLP of width d_ff before the expert
    # layers (DeepSeek-V2's first_k_dense_replace)
    first_dense_layers: int = 0
    mla: Optional[MLAConfig] = None
    recurrent: Optional[RecurrentConfig] = None
    # --- enc-dec ------------------------------------------------------------
    is_encoder_decoder: bool = False
    num_encoder_layers: int = 0
    # --- modality frontend stub ---------------------------------------------
    frontend: str = "none"  # none | vision | audio — stub providing embeddings
    frontend_tokens: int = 0  # number of pre-computed embedding positions
    # --- misc -----------------------------------------------------------------
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    remat: bool = True
    # pad q-heads (per KV group, preserving the GQA mapping) so the head
    # count divides this TP degree; 0 = off.  Zero-q padded heads are
    # masked out after attention — function exactly preserved.
    tp_pad_heads: int = 0
    notes: str = ""

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def is_attention_free(self) -> bool:
        return self.recurrent is not None and not self.recurrent.block_pattern

    @property
    def supports_long_context(self) -> bool:
        """True when decode with 500k state is sub-quadratic (SSM / hybrid-local)."""
        if self.recurrent is not None:
            return True  # rwkv6 (pure) and recurrentgemma (local window bounded)
        return False

    def validate(self) -> None:
        assert self.family in VALID_FAMILIES, self.family
        assert self.num_layers >= 1 and self.d_model >= 1
        if self.family != FAMILY_CNN:
            assert self.num_heads >= 1
            assert self.num_kv_heads >= 1
            assert self.num_heads % self.num_kv_heads == 0, (
                f"{self.name}: heads {self.num_heads} not divisible by kv {self.num_kv_heads}")
        if self.moe is not None:
            self.moe.validate()
        assert 0 <= self.first_dense_layers < self.num_layers
        assert self.moe is not None or self.first_dense_layers == 0
        if self.recurrent is not None:
            assert self.recurrent.kind in ("rwkv6", "rglru")

    # -- parameter counting (used by roofline MODEL_FLOPS = 6·N·D) ----------
    def param_count(self, active_only: bool = False) -> int:
        """Approximate parameter count from the config (embedding included)."""
        d, dff, L = self.d_model, self.d_ff, self.num_layers
        hd = self.resolved_head_dim
        n_q = self.num_heads * hd
        n_kv = self.num_kv_heads * hd
        total = self.vocab_size * d  # embedding
        if not self.tie_embeddings:
            total += self.vocab_size * d  # lm head
        layers = L + (self.num_encoder_layers if self.is_encoder_decoder else 0)

        def attn_params() -> int:
            if self.mla is not None:
                m = self.mla
                vd = m.v_head_dim or hd
                p = d * m.kv_lora_rank  # kv down-proj
                p += m.kv_lora_rank * (self.num_heads * (hd + vd))  # kv up-proj
                p += d * (self.num_heads * (hd + m.rope_head_dim))  # q (full rank)
                p += self.num_heads * vd * d  # o proj
                return p
            return d * n_q + 2 * d * n_kv + n_q * d

        def mlp_params(active: bool, dense: bool = False) -> int:
            if self.moe is not None and not dense:
                me = self.moe
                per_expert = 3 * d * me.expert_ff if self.mlp_kind == "swiglu" else 2 * d * me.expert_ff
                shared_ff = me.shared_ff or me.expert_ff
                shared = me.num_shared_experts * (
                    3 * d * shared_ff if self.mlp_kind == "swiglu" else 2 * d * shared_ff)
                router = d * me.num_experts
                n_e = me.top_k if active else me.num_experts
                return n_e * per_expert + shared + router
            return 3 * d * dff if self.mlp_kind == "swiglu" else 2 * d * dff

        def rec_params() -> int:
            # rwkv6: time-mix (r,k,v,g,o ≈ 5·d² + decay lora) + channel-mix (~3·d·dff…)
            if self.recurrent and self.recurrent.kind == "rwkv6":
                return 5 * d * d + 2 * d * 64  # time-mix block approx
            if self.recurrent and self.recurrent.kind == "rglru":
                w = self.recurrent.lru_width or d
                return 2 * d * w + w * d + 2 * w  # linear in/out + gates
            return 0

        if self.recurrent is not None and not self.recurrent.block_pattern:
            # pure recurrent (rwkv6): every layer = time-mix + channel-mix
            per_layer = rec_params() + mlp_params(active_only)
            total += layers * per_layer
        elif self.recurrent is not None:
            pat = self.recurrent.block_pattern
            n_rec = sum(1 for p in pat if p == "rec")
            n_attn = len(pat) - n_rec
            blocks = layers // len(pat)
            rem = layers % len(pat)
            n_rec = blocks * n_rec + sum(1 for p in pat[:rem] if p == "rec")
            n_attn = layers - n_rec
            total += n_rec * (rec_params() + mlp_params(active_only))
            total += n_attn * (attn_params() + mlp_params(active_only))
        else:
            n_dense = self.first_dense_layers
            total += layers * attn_params()
            total += n_dense * mlp_params(active_only, dense=True)
            total += (layers - n_dense) * mlp_params(active_only)
        return int(total)


@dataclass(frozen=True)
class ShapeConfig:
    """One input-shape cell from the assignment brief."""

    name: str  # train_4k | prefill_32k | decode_32k | long_500k
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"

    def validate(self) -> None:
        assert self.kind in ("train", "prefill", "decode")


SHAPES: Dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


@dataclass(frozen=True)
class ParallelConfig:
    """How a run maps onto the mesh."""

    data_axis: str = "data"
    model_axis: str = "model"
    pod_axis: str = "pod"
    fsdp: bool = False  # shard params over the data axis as well (ZeRO-3)
    zero1: bool = True  # shard optimizer state over (data, model)
    sequence_parallel: bool = True  # shard layer-boundary activations on seq
    expert_parallel: bool = True  # shard MoE experts over model axis
    remat_policy: str = "layer"  # none | layer | dots_saveable
    microbatch: int = 0  # 0 = no gradient accumulation
    collective_matmul: bool = False  # overlap all-gather with matmul (hillclimb)


@dataclass(frozen=True)
class HermesConfig:
    """Hyper-parameters of the paper (Table I + §IV)."""

    alpha: float = -1.3  # z-score gate threshold (negative)
    beta: float = 0.1  # alpha decay step
    lam: int = 5  # λ: iterations without a push before alpha decays
    window: int = 10  # w: loss-queue length
    eta: float = 0.1  # PS learning rate (Algorithm 2)
    alpha_min: float = -3.0
    alpha_max: float = 0.0
    # allocator (§IV-A)
    iqr_k: float = 1.5
    mbs_choices: Tuple[int, ...] = (2, 4, 8, 16, 32, 64, 128, 256)
    target: str = "median"  # target statistic for the dual binary search
    # compression (§IV-D; int8/int4 are our beyond-paper upgrades of fp16).
    # Any name in the repro.dist.wire registry is valid (see validate()).
    # Default int4 (nibble-packed + stochastic rounding + error feedback):
    # the --formats convergence study matches int8 accuracy on MNIST and at
    # LM scale (launch/train.py --hermes --compression ...) while shipping
    # ~0.52 B/element — half of int8's measured wire bytes.  Opt back with
    # HermesConfig(compression="int8").
    compression: str = "int4"
    error_feedback: bool = True
    # Pallas-vs-jnp dispatch for the Level-B merge (hermes_round's
    # use_kernel resolution): "auto" probes the backend (kernels on TPU),
    # "on"/"off" force it.  The REPRO_WIRE_KERNEL env var overrides this —
    # and also governs the config-free flat quantize helpers — so CPU CI
    # can exercise the kernel path in interpret mode.
    kernel_dispatch: str = "auto"  # auto | on | off
    # async double-buffered rounds (DESIGN.md §8): a gate-open round
    # *dispatches* its packed payload and keeps training; the merged
    # global lands one round late (staleness-1, absorbed by the per-pod
    # error-feedback residuals).  Level B pipelines hermes_dispatch /
    # hermes_commit through train_hermes (--async-rounds); Level A bills
    # the push transfer concurrently with the next iteration's compute.
    async_rounds: bool = False
    # elastic membership (DESIGN.md §7).  A member that stops responding is
    # declared dead after failure_timeout_factor x the typical iteration
    # time (the Level-A barrier detection stall and the Level-B liveness
    # monitor share the knob); a resize may never shrink the membership
    # below min_live_pods.
    failure_timeout_factor: float = 3.0
    min_live_pods: int = 1
    # re-admission policy (the grow path): rejoining a recovered pod costs
    # a recompile + re-shard stall worth this many synchronization rounds.
    # ``core.allocator.should_readmit`` admits only when the Eq.-3 speedup
    # from one more member over the expected remaining rounds exceeds it.
    rejoin_cost_rounds: float = 2.0
    # participation-rate admission (DESIGN.md §11): on top of the z-score
    # gate, at most ``ceil`` — actually ``max(1, floor(participation_rate
    # * n_open))`` — of the gate-OPEN members actually ship their push in
    # a given round; the rest are deferred.  Deferral is safe because the
    # push is the w0-anchored gradient-sum (Level A) / the w_global-anchored
    # delta with error feedback (Level B): a deferred pod's progress stays
    # in its local replica + residual and ships whole on its next admitted
    # push — admission changes *when* bytes move, never what the wire
    # eventually carries.  ``participation_rate=1.0`` is a static no-op:
    # the admission code is not even traced, so the lowering is
    # bit-identical to the plain gate by construction.
    participation_rate: float = 1.0
    # "topk": deterministic — keep the open pods with the largest merge
    # weight w2 = 1/loss (ties broken by pod index), so the budget spends
    # on the pushes Algorithm 2 weights most.  "prob": i.i.d. Bernoulli
    # thinning of the open gates (needs an rng at the round call sites;
    # the Level-A event engine uses this mode, where no cohort exists to
    # rank).
    admission: str = "topk"
    # hierarchical topology (DESIGN.md §10): pods are grouped into
    # ``n_clusters`` latency clusters (k-means over the allocator's
    # observed iteration+transfer times).  The gated loss-weighted merge
    # runs intra-cluster over the fast "pod" axis; only each cluster's
    # merged, re-encoded delta crosses the slow "cluster" axis.
    # ``n_clusters=1`` lowers bit-identically to the flat ``hermes_round``.
    n_clusters: int = 1

    def validate(self) -> None:
        # lazy import: repro.dist imports this module at load time
        from repro.dist.wire import available_formats
        assert self.compression in available_formats(), (
            f"compression {self.compression!r} not registered "
            f"(want one of {available_formats()})")
        assert self.kernel_dispatch in ("auto", "on", "off"), \
            self.kernel_dispatch
        assert self.window >= 1 and self.lam >= 1
        assert self.failure_timeout_factor > 0.0, self.failure_timeout_factor
        assert self.min_live_pods >= 1, self.min_live_pods
        assert self.rejoin_cost_rounds >= 0.0, self.rejoin_cost_rounds
        assert 0.0 < self.participation_rate <= 1.0, self.participation_rate
        assert self.admission in ("topk", "prob"), self.admission
        assert self.n_clusters >= 1, self.n_clusters


@dataclass(frozen=True)
class OptimizerConfig:
    name: str = "sgd"  # sgd | sgdm | adamw
    lr: float = 0.1
    momentum: float = 0.0
    beta1: float = 0.9
    beta2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.0
    grad_clip: float = 0.0


@dataclass(frozen=True)
class RunConfig:
    model: ModelConfig
    shape: ShapeConfig
    parallel: ParallelConfig = field(default_factory=ParallelConfig)
    hermes: HermesConfig = field(default_factory=HermesConfig)
    optimizer: OptimizerConfig = field(default_factory=OptimizerConfig)
    seed: int = 0

    def validate(self) -> None:
        self.model.validate()
        self.shape.validate()
        self.hermes.validate()

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), indent=2, default=str)


def replace(cfg: Any, **kw: Any) -> Any:
    """dataclasses.replace that works through our frozen configs."""
    return dataclasses.replace(cfg, **kw)
