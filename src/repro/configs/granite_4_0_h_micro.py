"""granite-4.0-h-micro — Mamba2 layers beside NoPE GQA layers [hf: ibm-granite/granite-4.0-h-micro].

``model_type`` granitemoehybrid with no experts: 40 layers, of which the
4 at indices 5, 15, 25 and 35 are causal GQA attention with no position
embedding (32 query heads, 8 KV heads of 64) and the other 36 are Mamba2
(64 heads of 64, d_state 128, one B/C group, expand 2, a causal conv of
window 4 with bias, chunk 256).  A SwiGLU MLP of width 8192 follows every
mixer.  The muP multipliers: embeddings times 12, scores times 1/64 (in
place of 1/sqrt(64)), both residual branches times 0.22, logits divided by
8.  Tied embeddings, RMSNorm epsilon 1e-5.
"""

import dataclasses

from repro.configs.base import ModelConfig, SSMConfig

ARCH_ID = "granite-4.0-h-micro"

PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4


def full() -> ModelConfig:
    return ModelConfig(
        name=ARCH_ID,
        family="hybrid",
        n_layers=40,
        d_model=2048,
        n_heads=32,
        n_kv_heads=8,
        head_dim=64,
        d_ff=8192,
        vocab_size=100352,
        rope_theta=1e4,
        tie_embeddings=True,
        layer_types=PERIOD * 4,
        norm_eps=1e-5,
        position_embedding="nope",
        attention_multiplier=1 / 64,
        embedding_multiplier=12.0,
        residual_multiplier=0.22,
        logits_scaling=8.0,
        ssm=SSMConfig(state_dim=128, chunk=256, mamba_expand=2,
                      mamba_heads=64, n_groups=1, d_conv=4),
        notes="36 Mamba2 + 4 NoPE GQA layers; mamba_d_head 64 = 2*2048/64",
    )


def reduced() -> ModelConfig:
    """One period (attention at index 5) at CPU size."""
    return dataclasses.replace(
        full(), n_layers=10, d_model=64, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=128, vocab_size=512, q_chunk=64,
        layer_types=PERIOD, attention_multiplier=1 / 16,
        ssm=SSMConfig(state_dim=16, chunk=16, mamba_expand=2, mamba_heads=4,
                      n_groups=1, d_conv=4),
    )
