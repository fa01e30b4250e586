"""Model configuration schema + the registry of assigned architectures.

Every architecture in the assignment is a ``ModelConfig``; ``reduced()``
yields the scaled-down variant used by the per-arch CPU smoke tests (the
full configs are exercised only through the dry-run's ShapeDtypeStructs).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

__all__ = ["ModelConfig", "ExpertShare", "ShapeConfig", "SHAPES", "register",
           "get_config", "list_configs"]


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | ssm | hybrid | encdec | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None   # default d_model // num_heads

    # attention flavour
    qk_norm: bool = False
    rope_theta: float = 10_000.0
    window: Optional[int] = None         # sliding-window size for local layers
    local_global_ratio: int = 0          # gemma3: N local layers per global
    mlp_act: str = "swiglu"              # swiglu | squared_relu | gelu

    # MoE
    moe_experts: int = 0
    moe_topk: int = 0
    moe_shared_experts: int = 0
    moe_dff: int = 0                     # per-expert hidden dim

    # SSM (Mamba-2)
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_groups: int = 1
    ssm_conv: int = 4

    # hybrid (zamba2): one *shared* attention block applied every k layers
    hybrid_attn_every: int = 0

    # encoder-decoder (whisper)
    encoder_layers: int = 0
    encoder_tokens: int = 0              # stub frame count (1500 for whisper)

    # VLM (paligemma): stub patch-embedding prefix
    prefix_tokens: int = 0               # 256 patches

    tie_embeddings: bool = True
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"
    # which shape cells are valid for this arch (DESIGN.md §4.2)
    skip_shapes: tuple[str, ...] = ()

    # ------------------------------------------------------------------ #
    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(self, "head_dim",
                               self.d_model // max(self.num_heads, 1))

    @property
    def d_inner(self) -> int:
        """SSM inner width."""
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def held_experts(self) -> range:
        """Ids of the experts this chip holds: every one (``ExpertShare``
        holds a share)."""
        return range(self.moe_experts)

    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    def n_params(self) -> float:
        """Analytic parameter count (embedding included once if tied)."""
        d, hd = self.d_model, self.head_dim
        h, kv = self.num_heads, self.num_kv_heads
        attn = d * h * hd + 2 * d * kv * hd + h * hd * d
        if self.mlp_act in ("swiglu", "geglu"):
            mlp = 3 * d * self.d_ff        # gated: gate + up + down
        else:
            mlp = 2 * d * self.d_ff
        if self.family == "moe":
            e_mlp = 3 * d * self.moe_dff
            mlp = (len(self.held_experts) + self.moe_shared_experts) * e_mlp \
                + d * self.moe_experts          # router
        per_layer = attn + mlp + 2 * d
        if self.family == "ssm":
            di, ns = self.d_inner, self.ssm_state
            per_layer = (
                d * (2 * di + 2 * self.ssm_groups * ns + self.ssm_heads)
                + self.ssm_conv * (di + 2 * self.ssm_groups * ns)
                + 2 * self.ssm_heads + di * d + di + 2 * d
            )
        if self.family == "hybrid":
            di, ns = self.d_inner, self.ssm_state
            mamba_layer = (
                d * (2 * di + 2 * self.ssm_groups * ns + self.ssm_heads)
                + self.ssm_conv * (di + 2 * self.ssm_groups * ns)
                + 2 * self.ssm_heads + di * d + di + 2 * d
            )
            shared = attn + 3 * d * self.d_ff + 2 * d
            return (self.num_layers * mamba_layer + shared
                    + self.vocab_size * d * (1 if self.tie_embeddings else 2))
        total = self.num_layers * per_layer
        if self.family == "encdec":
            # encoder layers: self-attn + mlp; decoder adds cross-attn
            enc = self.encoder_layers * (attn + mlp + 2 * d)
            total += enc + self.num_layers * attn       # cross-attn blocks
        emb = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        return float(total + emb + d)

    def n_params_active(self) -> float:
        """Active params per token (MoE: routed top-k + shared only)."""
        if self.family != "moe":
            return self.n_params()
        d = self.d_model
        e_mlp = 3 * d * self.moe_dff
        inactive = max(len(self.held_experts) - self.moe_topk, 0) * e_mlp
        return self.n_params() - self.num_layers * inactive

    def reduced(self) -> "ModelConfig":
        """Smoke-test variant: same family/topology, tiny dims."""
        return dataclasses.replace(
            self,
            name=self.name + "-reduced",
            num_layers=min(self.num_layers, 4 if self.hybrid_attn_every else 2),
            d_model=128,
            num_heads=4,
            num_kv_heads=min(self.num_kv_heads, 2) if self.num_kv_heads else 0,
            d_ff=256,
            vocab_size=512,
            head_dim=32,
            moe_experts=min(self.moe_experts, 8),
            moe_topk=min(self.moe_topk, 2),
            moe_shared_experts=min(self.moe_shared_experts, 1),
            moe_dff=64 if self.moe_dff else 0,
            ssm_state=min(self.ssm_state, 16),
            ssm_head_dim=16,
            window=min(self.window, 32) if self.window else None,
            hybrid_attn_every=min(self.hybrid_attn_every, 2),
            encoder_layers=min(self.encoder_layers, 2),
            encoder_tokens=min(self.encoder_tokens, 24),
            prefix_tokens=min(self.prefix_tokens, 8),
        )


@dataclasses.dataclass(frozen=True)
class ExpertShare(ModelConfig):
    """A MoE config of which this chip holds one share of each layer's
    experts, as one rank of an expert-parallel deployment holds them:
    ``moe_held`` experts from ``moe_first`` on.  The router keeps all
    ``moe_experts`` outputs and its ``moe_topk``; the experts held
    elsewhere add nothing here."""

    moe_first: int = 0
    moe_held: int = 0

    def __post_init__(self):
        super().__post_init__()
        if not (self.family == "moe" and self.moe_held > 0
                and 0 <= self.moe_first
                and self.moe_first + self.moe_held <= self.moe_experts):
            raise ValueError(
                f"{self.name}: experts {self.moe_first}.."
                f"{self.moe_first + self.moe_held - 1} are not a share of "
                f"{self.moe_experts} {self.family} experts")

    @classmethod
    def of(cls, cfg: ModelConfig, first: int, held: int) -> "ExpertShare":
        """``cfg`` with ``held`` of its experts, from ``first`` on, held
        here."""
        return cls(**{f.name: getattr(cfg, f.name)
                      for f in dataclasses.fields(ModelConfig)},
                   moe_first=first, moe_held=held)

    @property
    def held_experts(self) -> range:
        return range(self.moe_first, self.moe_first + self.moe_held)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str          # train | prefill | decode


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


_REGISTRY: dict[str, ModelConfig] = {}


def register(cfg: ModelConfig) -> ModelConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ModelConfig:
    if name not in _REGISTRY:
        # import the arch modules lazily so `--arch <id>` always works
        import repro.configs  # noqa: F401  (populates the registry)
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def list_configs() -> list[str]:
    import repro.configs  # noqa: F401
    return sorted(_REGISTRY)


def cell_is_valid(cfg: ModelConfig, shape: ShapeConfig) -> tuple[bool, str]:
    """DESIGN.md §4.2: which (arch x shape) cells run."""
    if shape.name in cfg.skip_shapes:
        return False, "skipped per assignment (sub-quadratic attention required)"
    return True, ""
