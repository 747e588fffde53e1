"""Model / training configuration schema and registry.

Each assigned architecture gets a module ``repro/configs/<id>.py`` that
exports ``CONFIG`` (the exact published configuration) and
``SMOKE_CONFIG`` (a reduced same-family config for CPU tests). The
registry maps the CLI ``--arch`` ids to those modules.

``block_pattern`` is the central abstraction: the repeating group of
heterogeneous layer kinds; the model scans over ``num_layers /
len(block_pattern)`` groups. Kinds:

  attn / attn_moe      — full causal attention + MLP / MoE
  swa / swa_moe        — sliding-window attention + MLP / MoE
  local / global       — gemma2-style alternating SWA / full attention
  mamba / mamba_moe    — Mamba mixer + MLP / MoE
  mlstm / slstm        — xLSTM blocks (no FFN, per the architecture)
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

ATTN_KINDS = {"attn", "attn_moe", "swa", "swa_moe", "local", "global"}
MOE_KINDS = {"attn_moe", "swa_moe", "mamba_moe"}
RECURRENT_KINDS = {"mamba", "mamba_moe", "mlstm", "slstm"}


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                     # dense | moe | ssm | hybrid | audio | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    block_pattern: tuple[str, ...] = ("attn",)
    head_dim: int | None = None
    sliding_window: int | None = None    # for swa/local kinds
    attn_logit_softcap: float | None = None
    final_logit_softcap: float | None = None
    qkv_bias: bool = False
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    embed_scale: bool = False            # gemma: scale embeddings by √d
    # MoE
    num_experts: int = 0
    num_experts_per_token: int = 0
    capacity_factor: float = 1.25
    # SSM
    ssm_state_dim: int = 16
    ssm_conv_dim: int = 4
    ssm_expand: int = 2
    # xLSTM
    mlstm_heads: int = 4
    # Modality frontends (STUBS — input_specs provides embeddings)
    frontend: str | None = None          # None | "audio_codec" | "vision_patches"
    num_patches: int = 576
    # Numerics
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"

    def __post_init__(self):
        if self.num_layers % len(self.block_pattern) != 0:
            raise ValueError(
                f"{self.name}: num_layers={self.num_layers} not divisible by "
                f"pattern length {len(self.block_pattern)}"
            )
        if any(k in MOE_KINDS for k in self.block_pattern):
            if self.num_experts <= 0 or self.num_experts_per_token <= 0:
                raise ValueError(f"{self.name}: MoE kinds need expert counts")
        for k in self.block_pattern:
            if k not in ATTN_KINDS | RECURRENT_KINDS:
                raise ValueError(f"{self.name}: unknown block kind {k!r}")
        if self.num_heads % max(self.num_kv_heads, 1) != 0:
            raise ValueError(f"{self.name}: heads not divisible by kv heads")

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def num_groups(self) -> int:
        return self.num_layers // len(self.block_pattern)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """One input-shape cell of a training step."""

    name: str
    seq_len: int
    global_batch: int
    kind: str  # always "train"; callers pass it positionally


TRAIN_4K = ShapeConfig("train_4k", 4096, 256, "train")
ALL_SHAPES = (TRAIN_4K,)


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """Distributed-training knobs for one (arch × shape) cell."""

    agent_layout: str = "data"     # "data": agents on (pod×)data axis, TP on
                                   # model; "pod": agents on pod axis,
                                   # FSDP on data + TP on model (big archs)
    remat: str = "full"            # "none" | "full" — activation ckpt policy
    learning_rate: float = 0.01
    momentum: float = 0.9
    gossip: str = "auto"           # "auto" | "sparse" | "allreduce"
    microbatch: int = 0            # >0: gradient accumulation steps
    moe_aux_weight: float = 1e-2
    router_z_weight: float = 1e-3


ARCH_IDS = (
    "mixtral-8x22b",
    "mixtral-8x7b",
    "xlstm-125m",
    "qwen1.5-0.5b",
    "mistral-large-123b",
    "gemma2-2b",
    "qwen2-0.5b",
    "musicgen-large",
    "jamba-1.5-large-398b",
    "llava-next-34b",
)

_MODULE_FOR_ARCH = {
    "mixtral-8x22b": "mixtral_8x22b",
    "mixtral-8x7b": "mixtral_8x7b",
    "xlstm-125m": "xlstm_125m",
    "qwen1.5-0.5b": "qwen1_5_0_5b",
    "mistral-large-123b": "mistral_large_123b",
    "gemma2-2b": "gemma2_2b",
    "qwen2-0.5b": "qwen2_0_5b",
    "musicgen-large": "musicgen_large",
    "jamba-1.5-large-398b": "jamba_1_5_large_398b",
    "llava-next-34b": "llava_next_34b",
}


def get_config(arch: str, smoke: bool = False) -> ModelConfig:
    import importlib

    if arch not in _MODULE_FOR_ARCH:
        raise KeyError(f"unknown arch {arch!r}; known: {ARCH_IDS}")
    mod = importlib.import_module(f"repro.configs.{_MODULE_FOR_ARCH[arch]}")
    return mod.SMOKE_CONFIG if smoke else mod.CONFIG


def get_train_config(arch: str) -> TrainConfig:
    import importlib

    mod = importlib.import_module(f"repro.configs.{_MODULE_FOR_ARCH[arch]}")
    return getattr(mod, "TRAIN_CONFIG", TrainConfig())


def get_shape(name: str) -> ShapeConfig:
    for s in ALL_SHAPES:
        if s.name == name:
            return s
    raise KeyError(f"unknown shape {name!r}")
