"""Operations and bytes of a Granite-4.0-H hybrid decoder, from its shapes alone.

The benchmark's own arithmetic for the hybrid cell's MFU and roofline
shares, beside ``counts.py`` (dense GQA).  It counts what the mathematics
needs, never what a program's events bill: attention's causal keys, the
Mamba2 mixer's SSD in its chunked form, the recurrence's state read once
and written once per decode step.  Sizes come from
``reference/granite_hybrid.Dims``.
"""

from __future__ import annotations

from typing import Sequence

from chipbench.reference.granite_hybrid import Dims


def n_kind(d: Dims, kind: str) -> int:
    return sum(1 for t in d.layer_types if t == kind)


def conv_width(d: Dims) -> int:
    """Channels of the causal conv: x, B and C."""
    return d.di + 2 * d.groups * d.state


def mamba_matmul_params(d: Dims) -> int:
    """Weights one Mamba2 layer multiplies by: x|z, B|C|dt, output."""
    return (d.d * 2 * d.di + d.d * (2 * d.groups * d.state + d.heads)
            + d.di * d.d)


def attn_matmul_params(d: Dims) -> int:
    return d.d * (d.hq + 2 * d.hkv) * d.hd + d.hq * d.hd * d.d


def mlp_params(d: Dims) -> int:
    return 3 * d.d * d.ff


def matmul_params(d: Dims) -> int:
    """Every weight a token multiplies by, the tied head included."""
    return (n_kind(d, "mamba") * mamba_matmul_params(d)
            + n_kind(d, "attention") * attn_matmul_params(d)
            + len(d.layer_types) * mlp_params(d) + d.vocab * d.d)


def weight_bytes(d: Dims, width: int = 2) -> float:
    """All weights once, at the bf16 width: the tied embedding is read as
    the head, the norms and the conv beside."""
    small = (n_kind(d, "mamba") * (conv_width(d) * (d.conv + 1)
                                   + 3 * d.heads + d.di)
             + 2 * len(d.layer_types) * d.d + d.d)
    return float(width * (matmul_params(d) + small))


def state_bytes(d: Dims) -> float:
    """One slot's recurrent state: fp32 SSM state and conv window of every
    Mamba2 layer."""
    per = d.heads * d.head_dim * d.state + (d.conv - 1) * conv_width(d)
    return 4.0 * per * n_kind(d, "mamba")


def attn_flops(d: Dims, q_len: int, kv_len: int) -> float:
    """Causal scores and probabilities-times-values over the attention
    layers, for ``q_len`` queries ending at key ``kv_len``."""
    keys = q_len * kv_len - q_len * (q_len - 1) / 2
    return 4.0 * d.hq * d.hd * keys * n_kind(d, "attention")


def ssd_chunk_flops(d: Dims, length: int) -> float:
    """One head's SSD over one chunk of ``length`` tokens: intra-chunk
    scores (C B^T) and their product with x, the state's read-out and its
    update."""
    L, N, P = length, d.state, d.head_dim
    return 2.0 * L * L * N + 2.0 * L * L * P + 4.0 * L * N * P


def ssd_flops(d: Dims, seq: int, chunk: int) -> float:
    """The chunked SSD of one sequence over all Mamba2 layers and heads."""
    full, rest = divmod(seq, chunk)
    per = full * ssd_chunk_flops(d, chunk) + (
        ssd_chunk_flops(d, rest) if rest else 0.0)
    return per * d.heads * n_kind(d, "mamba")


def ssd_kernel_flops(d: Dims, seq: int, chunk: int) -> float:
    """What the linear-attention kernel computes for one sequence: every
    chunk whole, the last one padded."""
    n = -(-seq // chunk)
    return n * ssd_chunk_flops(d, chunk) * d.heads * n_kind(d, "mamba")


def ssd_kernel_bytes(d: Dims, seq: int, chunk: int,
                     in_bytes: int = 4) -> float:
    """The kernel's least HBM traffic for one sequence: per head q (C), k
    (B), v (dt x), the decay and the output over the padded sequence, and
    the final state once, over all Mamba2 layers."""
    S = -(-seq // chunk) * chunk
    N, P = d.state, d.head_dim
    per_head = S * (in_bytes * (2 * N + 2 * P) + 4) + 4 * N * P
    return float(per_head * d.heads * n_kind(d, "mamba"))


def prefill_flops(d: Dims, prompt_len: int, chunk: int) -> float:
    """A prompt's forward pass: every layer's products over the prompt,
    causal attention, the SSD in its chunked form and the head at the last
    position.  The conv and the elementwise work are not counted."""
    body = matmul_params(d) - d.vocab * d.d
    return (2.0 * body * prompt_len + 2.0 * d.vocab * d.d
            + attn_flops(d, prompt_len, prompt_len)
            + ssd_flops(d, prompt_len, chunk))


def decode_flops(d: Dims, kv_lens: Sequence[int]) -> float:
    """One decode step over the active slots; ``kv_lens`` are the keys each
    slot's new query attends, itself included.  The recurrence's update
    and read-out count 4 H P N per Mamba2 layer."""
    ssm = 4.0 * d.heads * d.head_dim * d.state * n_kind(d, "mamba")
    per_token = 2.0 * matmul_params(d) + ssm
    return sum(per_token + attn_flops(d, 1, k) for k in kv_lens)


def decode_bytes(d: Dims, kv_lens: Sequence[int], kv_bytes: int) -> float:
    """Weights once, each active slot's recurrent state read and written,
    and its valid key and value rows at the cache's storage width."""
    kv = 2 * d.hkv * d.hd * n_kind(d, "attention") * kv_bytes * sum(kv_lens)
    return weight_bytes(d) + 2.0 * state_bytes(d) * len(kv_lens) + kv
