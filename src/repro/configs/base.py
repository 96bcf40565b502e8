"""Configuration dataclasses for models, shapes, meshes and adapters.

Every assigned architecture gets a ``ModelConfig`` in its own module under
``repro/configs``; the registry maps ``--arch`` ids to those configs plus the
set of input shapes that are applicable to the family (encoder-only archs have
no decode step; pure full-attention archs skip long_500k).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple


@dataclass(frozen=True)
class ShapeSpec:
    """One input-shape cell: (seq_len, global_batch, kind)."""

    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"

    @property
    def tokens(self) -> int:
        return self.seq_len * self.global_batch


# The four assigned LM-family shapes.
SHAPES = {
    "train_4k": ShapeSpec("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524_288, 1, "decode"),
}


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 0           # routed experts
    top_k: int = 0
    num_shared: int = 0            # always-on shared experts (DeepSeek-V2 style)
    d_ff: int = 0                  # per-expert hidden dim
    first_dense_layers: int = 0    # leading layers that use a dense FFN instead
    first_dense_d_ff: int = 0      # hidden dim of those dense layers
    router_jitter: float = 0.0
    norm_topk_prob: bool = True    # renormalise the top-k router weights
    # The routed experts this chip holds: ``held_experts`` of them from
    # ``first_held`` on (0 = all). ``num_experts`` stays the router's width.
    held_experts: int = 0
    first_held: int = 0

    @property
    def held(self) -> int:
        return self.held_experts or self.num_experts


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head Latent Attention (DeepSeek-V2)."""

    kv_lora_rank: int = 512
    q_lora_rank: int = 0           # 0 => project q directly from d_model
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128


@dataclass(frozen=True)
class YaRNConfig:
    """YaRN rotary scaling, as DeepSeek-V2's ``rope_scaling`` states it."""

    factor: float = 40.0
    original_max_position: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 0.707
    mscale_all_dim: float = 0.707


@dataclass(frozen=True)
class SSMConfig:
    """Mamba2 / SSD mixer."""

    d_state: int = 128
    expand: int = 2
    head_dim: int = 64
    d_conv: int = 4
    n_groups: int = 1
    chunk: int = 128               # SSD chunk length
    dt_min: float = 0.001
    dt_max: float = 0.1


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0              # 0 => d_model // num_heads
    attn_type: str = "gqa"         # gqa | mla | none
    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    yarn: Optional[YaRNConfig] = None  # YaRN-scaled rotary (MLA archs)
    norm_eps: float = 1e-5
    act: str = "silu"              # silu (SwiGLU) | gelu (vanilla MLP)
    tie_embeddings: bool = False
    causal: bool = True            # False for encoder-only (hubert)
    encoder_only: bool = False
    logit_softcap: float = 0.0

    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    ssm: Optional[SSMConfig] = None

    # Hybrid (zamba2): a single *shared* attention block applied after every
    # ``hybrid_attn_every`` SSM layers (weights reused at every site).
    hybrid_attn_every: int = 0

    # Modality frontends (stubs: input_specs provides precomputed embeddings).
    modality: str = "text"         # text | vision | audio
    num_prefix_embeds: int = 0     # e.g. image patches prepended (paligemma)

    # Distribution policy.
    fsdp: bool = False             # shard params along the data axis too
    remat: str = "full"            # full | dots | none — layer-scan remat policy
    # Head-group padding (optimized variants, §Perf): pad q heads per kv
    # group (and optionally kv heads) with zero-init dead heads so the head
    # dim shards evenly over 16-way TP instead of replicating attention.
    pad_heads_to: int = 0          # padded total q heads (0 = exact config)
    pad_kv_to: int = 0             # padded kv heads
    # Repeat kv heads to the q-head count before attention so the head dim
    # shards as one flat axis (a (KV, G) split cannot absorb a single 16-way
    # mesh axis). Costs a local repeat; buys fully-sharded attention.
    attn_repeat_kv: bool = False

    # Sub-quadratic? (drives long_500k applicability)
    @property
    def subquadratic(self) -> bool:
        return self.family in ("ssm", "hybrid")

    # Embedding tables are padded to a multiple of 256 (Megatron-style) so the
    # vocab dim shards evenly over 16-way TP; pad logits are masked to -inf.
    @property
    def padded_vocab(self) -> int:
        m = 256
        return ((self.vocab_size + m - 1) // m) * m

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // self.num_heads)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class AdapterConfig:
    """The paper's contribution, as a first-class config."""

    kind: str = "none"             # none | shira | lora | dora | shira-dora
    mask: str = "wm"               # struct | rand | wm | grad | snip  (shira masks)
    sparsity: float = 0.99         # fraction of *zeros* in the mask (1-2% trainable)
    rank: int = 32                 # lora/dora rank
    alpha: float = 1.0             # inference-time strength W + alpha * S
    lora_alpha: float = 64.0       # lora scaling numerator (alpha/rank)
    target_modules: Tuple[str, ...] = (
        "wq", "wk", "wv", "wo", "w_up", "w_gate", "w_down",
        "in_proj", "out_proj", "w_dkv", "w_uk", "w_uv",
    )
    # struct-mask knobs
    struct_rows: int = 8           # trainable rows per matrix (rank-1-ish part)
    struct_cols: int = 8
    # packed mode keeps optimizer state only for the nz set (paper App. D)
    packed: bool = True
    # beyond-paper: compress the cross-pod gradient all-reduce to the nz set
    sparse_grad_sync: bool = False


@dataclass(frozen=True)
class TrainConfig:
    seed: int = 0
    learning_rate: float = 5e-4
    weight_decay: float = 0.0
    beta1: float = 0.9
    beta2: float = 0.999
    eps: float = 1e-8
    grad_clip: float = 1.0
    warmup_steps: int = 10
    schedule: str = "linear"       # linear | cosine | constant
    total_steps: int = 300
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    microbatch: int = 0            # 0 => no gradient accumulation


@dataclass(frozen=True)
class RunConfig:
    model: ModelConfig
    shape: ShapeSpec
    adapter: AdapterConfig = field(default_factory=AdapterConfig)
    train: TrainConfig = field(default_factory=TrainConfig)
