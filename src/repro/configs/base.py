"""Config dataclasses: model architecture, shapes, sparsity, training.

One ``ModelConfig`` per assigned architecture lives in ``configs/<arch>.py``;
``configs/__init__.py`` is the registry (``get_config(name)``).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

from repro.sparsity import SparsityConfig, SparsityPlan, lower_config

__all__ = [
    "MoEConfig",
    "YarnConfig",
    "MLAConfig",
    "MambaConfig",
    "RWKVConfig",
    "ModelConfig",
    "ShapeConfig",
    "LM_SHAPES",
    "TrainConfig",
]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    n_shared: int = 0
    d_expert: int = 0            # expert hidden dim (d_ff of each expert)
    every_n_layers: int = 1      # MoE replaces the MLP every n layers
    first_dense: int = 0         # first k layers keep a dense MLP
    capacity_factor: float = 1.25
    aux_loss_coef: float = 0.01
    router_dtype: str = "float32"


@dataclasses.dataclass(frozen=True)
class YarnConfig:
    """YaRN rotary scaling (transformers' ``rope_type: yarn``) on the layer
    kinds it names; every other layer keeps plain RoPE at the same theta."""
    factor: float
    original_max_position: int
    beta_fast: float
    beta_slow: float
    attention_factor: float          # scales cos and sin
    layer_kinds: tuple[str, ...]


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    kv_lora_rank: int = 512
    q_lora_rank: int = 1536
    rope_head_dim: int = 64
    nope_head_dim: int = 128
    v_head_dim: int = 128


@dataclasses.dataclass(frozen=True)
class MambaConfig:
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0  # 0 -> ceil(d_model / 16)


@dataclasses.dataclass(frozen=True)
class RWKVConfig:
    head_size: int = 64
    decay_lora: int = 64
    mix_lora: int = 32


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                # 0 -> d_model // n_heads
    # layer pattern, repeated cyclically over layers; entries:
    #   'attn' (full causal), 'swa' (sliding window), 'mla', 'mamba', 'rwkv'
    layer_pattern: tuple[str, ...] = ("attn",)
    sliding_window: int = 1024
    hidden_act: str = "silu"         # 'gelu' -> GeGLU MLP
    rmsnorm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: Optional[YarnConfig] = None   # None: plain RoPE everywhere
    max_seq_len: int = 8192
    tie_embeddings: bool = False
    logit_softcap: float = 0.0
    # sub-configs
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    mamba: Optional[MambaConfig] = None
    rwkv: Optional[RWKVConfig] = None
    # modality frontend stub: 'vision' | 'audio' | None.
    # vision: input_specs provides patch embeddings prepended to the text
    # audio: tokens carry n_codebooks codebook ids per step (embedded + summed)
    frontend: Optional[str] = None
    n_codebooks: int = 1
    n_patches: int = 0
    # the paper's technique — first-class field.  ``sparsity`` is the
    # legacy uniform knob (a one-rule shim); ``plan`` is the declarative
    # per-layer SparsityPlan and wins when set.  Model constructors only
    # ever see the resolved plan (``sparsity_rules``) and match their
    # module paths against it.
    sparsity: SparsityConfig = dataclasses.field(default_factory=SparsityConfig)
    plan: Optional[SparsityPlan] = None
    # numerics
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    # scan-over-layers: period length (pattern length) is the scan body size
    remat: bool = True
    # sequence-scan unroll factor (mamba/rwkv recurrences).  Hypothesis
    # "unroll cuts scan-state HBM round-trips U-fold" was REFUTED under the
    # fusion-boundary byte model (EXPERIMENTS.md section Perf iteration J2):
    # carries alias in place and the stacked-ys writes grow with U, so the
    # default stays 1; the knob remains for real-TPU wall-clock tuning.
    ssm_unroll: int = 1

    @property
    def sparsity_rules(self) -> SparsityPlan:
        """The plan every model constructor resolves against: ``plan`` if
        set, else ``sparsity`` lowered to a uniform one-rule plan."""
        return self.plan if self.plan is not None else lower_config(self.sparsity)

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    def layer_kind(self, i: int) -> str:
        return self.layer_pattern[i % len(self.layer_pattern)]

    def is_moe_layer(self, i: int) -> bool:
        if self.moe is None:
            return False
        if i < self.moe.first_dense:
            return False
        return (i - self.moe.first_dense) % self.moe.every_n_layers == 0

    def with_(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """One assigned input-shape cell."""

    name: str
    seq_len: int
    global_batch: int
    kind: str  # 'train' | 'prefill' | 'decode'


LM_SHAPES = (
    ShapeConfig("train_4k", 4096, 256, "train"),
    ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    ShapeConfig("decode_32k", 32768, 128, "decode"),
    ShapeConfig("long_500k", 524288, 1, "decode"),
)


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    optimizer: str = "sgdm"          # paper uses SGD momentum 0.9, wd 1e-4
    lr: float = 0.1
    momentum: float = 0.9
    weight_decay: float = 1e-4
    adam_b1: float = 0.9
    adam_b2: float = 0.95
    adam_eps: float = 1e-8
    schedule: str = "cosine"         # 'step' for the paper's VGG/WRN recipe
    warmup_steps: int = 100
    total_steps: int = 1000
    lr_step_epochs: tuple[int, ...] = (60, 120, 160)
    lr_step_gamma: float = 0.1
    microbatches: int = 1            # grad accumulation via lax.scan
    grad_clip: float = 1.0
    distill_alpha: float = 0.0       # knowledge-distillation mix (paper §6)
    distill_temp: float = 4.0
    grad_compression: str = "none"   # 'int8' -> error-feedback int8 all-reduce
    checkpoint_every: int = 100
    checkpoint_dir: str = "/tmp/repro_ckpt"
    seed: int = 0
