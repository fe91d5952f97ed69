"""Operations and bytes of a dense GQA decoder, from its shapes alone.

The benchmark's own arithmetic, for MFU and roofline shares.  It counts
what the mathematics needs (causal attention counts each query's keys up to
and including itself), never what a program's events bill.
"""

from __future__ import annotations

from typing import Sequence

from chipbench.model import Dims


def layer_matmul_params(d: Dims) -> int:
    """Weights one layer multiplies by: q|k|v, output, gate|up, down."""
    qkv = d.d * (d.hq + 2 * d.hkv) * d.hd
    return qkv + d.hq * d.hd * d.d + 3 * d.d * d.ff


def head_params(d: Dims) -> int:
    return d.d * d.vocab


def attn_flops(d: Dims, q_len: int, kv_len: int) -> float:
    """Scores and probabilities-times-values of ``q_len`` queries that end
    at key position ``kv_len`` (causal), over all layers."""
    keys = q_len * kv_len - q_len * (q_len - 1) / 2
    return 4.0 * d.hq * d.hd * keys * d.layers


def prefill_flops(d: Dims, prompt_len: int) -> float:
    """A prompt's forward pass, with the head at its last position only."""
    return (2.0 * d.layers * layer_matmul_params(d) * prompt_len
            + 2.0 * head_params(d) + attn_flops(d, prompt_len, prompt_len))


def decode_flops(d: Dims, kv_lens: Sequence[int]) -> float:
    """One decode step over the active slots; ``kv_lens`` are the keys each
    slot's new query attends, itself included."""
    per_token = 2.0 * (d.layers * layer_matmul_params(d) + head_params(d))
    return sum(per_token + attn_flops(d, 1, k) for k in kv_lens)


def decode_weight_bytes(d: Dims, weight_bytes: int = 2) -> float:
    """Matmul weights one decode step must read once, at the operand width."""
    return float(weight_bytes * (d.layers * layer_matmul_params(d)
                                 + head_params(d)))


def decode_kv_bytes(d: Dims, kv_lens: Sequence[int], kv_bytes: int) -> float:
    """Valid key and value rows one decode step reads, at the storage width."""
    return float(2 * d.hkv * d.hd * d.layers * kv_bytes * sum(kv_lens))


def decode_bytes(d: Dims, kv_lens: Sequence[int], kv_bytes: int,
                 weight_bytes: int = 2) -> float:
    return decode_weight_bytes(d, weight_bytes) + decode_kv_bytes(
        d, kv_lens, kv_bytes)


def train_model_flops(d: Dims, batch: int, seq: int) -> float:
    """Forward and backward of one step: three times the forward's
    products (6N per token) plus causal attention; recompute not counted."""
    fwd = (2.0 * (d.layers * layer_matmul_params(d) + head_params(d))
           * batch * seq + batch * attn_flops(d, seq, seq))
    return 3.0 * fwd


def train_matmul_flops(d: Dims, batch: int, seq: int, remat: str) -> float:
    """Weight products a step runs (attention's are not counted): under
    ``remat == "full"`` each layer's forward runs again in the backward
    pass (the head's does not)."""
    layers_fwd = 2.0 * d.layers * layer_matmul_params(d) * batch * seq
    head_fwd = 2.0 * head_params(d) * batch * seq
    return (4.0 if remat == "full" else 3.0) * layers_fwd + 3.0 * head_fwd
