"""Architecture config schema + the assigned input-shape suite.

Every assigned architecture provides:
  * ``full()``    — the exact published configuration;
  * ``reduced()`` — a same-family miniature for CPU smoke tests;
  * shapes come from ``SHAPES`` (train_4k / prefill_32k / decode_32k /
    long_500k) and ``input_specs(cfg, shape)`` builds the
    ShapeDtypeStruct stand-ins the dry-run lowers against.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import precision as prec

__all__ = [
    "MLAConfig", "MoEConfig", "SSMConfig", "ModelConfig",
    "ShapeSpec", "SHAPES", "input_specs", "cache_specs",
]


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_routed: int
    n_shared: int
    top_k: int
    d_expert: int
    dense_ff: int            # FFN width of the leading dense layer(s)
    first_dense: int = 1
    capacity_factor: float = 1.25
    norm_topk_prob: bool = True
    aux_weight: float = 0.01
    z_weight: float = 1e-4


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    state_dim: int = 16
    chunk: int = 64
    mlstm_proj_factor: int = 2
    mamba_expand: int = 1
    slstm_period: int = 8     # one sLSTM per this many blocks (xLSTM [7:1])
    # Mamba2 mixer: heads (0 = the model's n_heads, Hymba's SSD heads),
    # B/C groups (each read by mamba_heads / n_groups heads) and the causal
    # depthwise conv window, with bias, over x|B|C (0 = none, Hymba).  A
    # conv window also selects Mamba2's gate-then-norm over the inner width.
    mamba_heads: int = 0
    n_groups: int = 1
    d_conv: int = 0

    def slstm_ffn_dim(self, d: int) -> int:
        return -(-(4 * d) // (3 * 64)) * 64  # ceil(4d/3) to a 64 multiple


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str               # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0         # 0 -> d_model // n_heads
    qk_norm: bool = False
    use_bias: bool = False
    rope_theta: float = 1e4
    sliding_window: Optional[int] = None
    full_attn_layers: Tuple[int, ...] = ()
    mla: Optional[MLAConfig] = None
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    norm: str = "rmsnorm"     # rmsnorm | layernorm
    act: str = "silu"
    mlp: str = "glu"          # glu | plain
    input_mode: str = "tokens"   # tokens | embeddings (audio/vlm stubs)
    tie_embeddings: bool = False
    policy_name: str = "tpu_bf16"
    param_dtype: str = "float32"
    q_chunk: int = 1024
    # fused CE: batch rows per chunk; 0 = materialize (B, S, V) logits
    ce_chunk: int = 0
    # MoE expert parallelism: gspmd (auto) | shard_map (manual all_to_all)
    moe_impl: str = "gspmd"
    remat: str = "full"       # none | dots | full
    # per-layer mixer ("mamba" | "attention"), as a published
    # ``layer_types`` lists it; empty = one block kind for the whole stack
    layer_types: Tuple[str, ...] = ()
    norm_eps: float = 1e-6    # every RMSNorm's epsilon
    position_embedding: str = "rope"   # rope | nope
    attention_multiplier: Optional[float] = None  # score scale; None = hd^-0.5
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0   # on both branches of a pattern layer
    logits_scaling: float = 1.0        # logits are divided by it
    notes: str = ""

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)

    @property
    def policy(self) -> prec.Policy:
        return prec.resolve(self.policy_name)

    @property
    def compute_dtype(self):
        return self.policy.compute_dtype

    @property
    def block_kind(self) -> str:
        if self.layer_types:
            return "pattern"
        if self.family == "moe":
            return "moe"
        if self.family == "ssm":
            return "xlstm"
        if self.family == "hybrid":
            return "hymba"
        return "attn"

    @property
    def supports_long_context_decode(self) -> bool:
        """True for sub-quadratic (SSM/hybrid) families — long_500k cells."""
        return self.family in ("ssm", "hybrid")

    def param_count(self) -> int:
        """Total parameters (embedding included), for MODEL_FLOPS."""
        from repro.models import transformer  # local: avoid import cycle
        return transformer.count_params(self)

    def active_param_count(self) -> int:
        from repro.models import transformer
        return transformer.count_params(self, active_only=True)


# --------------------------------------------------------------------- #
# Assigned shape suite
# --------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    kind: str           # train | prefill | decode
    seq_len: int
    global_batch: int


SHAPES: Dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", "train", 4_096, 256),
    "prefill_32k": ShapeSpec("prefill_32k", "prefill", 32_768, 32),
    "decode_32k": ShapeSpec("decode_32k", "decode", 32_768, 128),
    "long_500k": ShapeSpec("long_500k", "decode", 524_288, 1),
}


def input_specs(cfg: ModelConfig, shape: ShapeSpec) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for the step function's data arguments."""
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    if shape.kind in ("train", "prefill"):
        if cfg.input_mode == "embeddings":
            return {
                "embeddings": jax.ShapeDtypeStruct((B, S, cfg.d_model), cfg.compute_dtype),
                "labels": jax.ShapeDtypeStruct((B, S), i32),
            }
        return {
            "inputs": jax.ShapeDtypeStruct((B, S), i32),
            "labels": jax.ShapeDtypeStruct((B, S), i32),
        }
    # decode: one new token against a cache of length S
    return {
        "inputs": jax.ShapeDtypeStruct((B, 1), i32),
        "pos": jax.ShapeDtypeStruct((), i32),
    }


def cache_specs(cfg: ModelConfig, shape: ShapeSpec) -> Dict:
    """Abstract KV/state cache for decode shapes (built in transformer.py)."""
    from repro.models import transformer
    return jax.eval_shape(
        lambda: transformer.init_cache(cfg, shape.global_batch, shape.seq_len)
    )
