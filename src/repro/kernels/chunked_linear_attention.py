"""Chunked linear attention (mLSTM / Mamba2-SSD) as a Pallas TPU kernel.

The §Perf-3 analysis showed the xLSTM chunked engine's dominant HBM traffic
is the (dk x dv) matrix state crossing HBM once per chunk.  This kernel is
the RedMulE store-once rule applied to the *state*: the running state lives
in a VMEM fp32 scratch across the entire sequence sweep and is written to
HBM exactly once, at the last chunk — the same schedule the paper's Z-buffer
uses for the GEMM accumulator, generalized to a decaying recurrence:

    S_t = exp(g_t) * S_{t-1} + k_t v_t^T ;   out_t = q_t @ S_t

Per (head, chunk) step (all in VMEM, grid = (BH, S/chunk), chunk axis
sequential):
    L      = cumsum(g_chunk)                       (c,)
    intra  = ((q k^T) * exp(L_i - L_j) * [i>=j]) v
    inter  = (q * exp(L)) @ S
    S     <- exp(L_c) S + (k * exp(L_c - L))^T v

With log-decays g <= 0 every factor is exp(<=0): numerically stable with no
extra stabilizer (same argument as models/ssm.py, which is the oracle).

Mosaic has no cumsum, and a ``(1, chunk)`` block of a ``(BH, S)`` array
breaks the (8, 128) tiling rule for chunks under 128 lanes.  So the wrapper
takes the per-chunk prefix sums ``L`` in XLA (one pass over ``(BH, S)``
fp32, small beside the q/k/v streams) and hands them to the kernel twice,
as ``(BH, n, chunk, 1)`` columns for the row factors and as
``(BH, n, 1, chunk)`` rows for ``L_i - L_j``: each block's last two dims
are then whole array dims.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["chunked_linear_attention_pallas"]


def _kernel(q_ref, k_ref, v_ref, lc_ref, lr_ref, o_ref, state_out_ref,
            state_ref, *, n_chunks: int, chunk: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _reset():
        state_ref[...] = jnp.zeros_like(state_ref)

    q = q_ref[0].astype(jnp.float32)          # (c, dk)
    k = k_ref[0].astype(jnp.float32)          # (c, dk)
    v = v_ref[0].astype(jnp.float32)          # (c, dv)
    L = lc_ref[0, 0]                           # (c, 1) inclusive prefix sums
    L_row = lr_ref[0, 0]                       # (1, c) the same, as a row

    idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jdx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = idx >= jdx
    last = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1) == chunk - 1
    Ltot = jnp.sum(jnp.where(last, L_row, 0.0))
    A = jnp.where(causal, jnp.exp(L - L_row), 0.0)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * A
    out = jnp.dot(s, v, preferred_element_type=jnp.float32)
    out = out + jnp.dot(q * jnp.exp(L), state_ref[...],
                        preferred_element_type=jnp.float32)

    kdec = k * jnp.exp(Ltot - L)
    state_ref[...] = (
        jnp.exp(Ltot) * state_ref[...]
        + jax.lax.dot_general(kdec, v, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32))

    o_ref[0] = out.astype(o_ref.dtype)

    @pl.when(j == n_chunks - 1)
    def _store_state_once():
        state_out_ref[0] = state_ref[...]


@functools.partial(
    jax.jit, static_argnames=("chunk", "interpret"))
def chunked_linear_attention_pallas(
    q: jax.Array,      # (BH, S, dk)
    k: jax.Array,      # (BH, S, dk)
    v: jax.Array,      # (BH, S, dv)
    log_g: jax.Array,  # (BH, S), <= 0
    *,
    chunk: int = 128,
    interpret: bool = False,
):
    """Returns (out (BH, S, dv), final_state (BH, dk, dv) fp32).

    S must be a multiple of ``chunk`` (callers pad with g=0, k=0 — inert)."""
    BH, S, dk = q.shape
    dv = v.shape[-1]
    assert S % chunk == 0, (S, chunk)
    n_chunks = S // chunk
    grid = (BH, n_chunks)
    L = jnp.cumsum(log_g.astype(jnp.float32).reshape(BH, n_chunks, chunk),
                   axis=-1)

    out, state = pl.pallas_call(
        functools.partial(_kernel, n_chunks=n_chunks, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, dk), lambda h, j: (h, j, 0)),
            pl.BlockSpec((1, chunk, dk), lambda h, j: (h, j, 0)),
            pl.BlockSpec((1, chunk, dv), lambda h, j: (h, j, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda h, j: (h, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda h, j: (h, j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, dv), lambda h, j: (h, j, 0)),
            pl.BlockSpec((1, dk, dv), lambda h, j: (h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, dv), q.dtype),
            jax.ShapeDtypeStruct((BH, dk, dv), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="redmule_chunked_linear_attention",
    )(q, k, v, L[..., None], L[:, :, None, :])
    return out, state
