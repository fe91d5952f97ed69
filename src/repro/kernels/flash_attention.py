"""RedMulE-tiled flash attention (Pallas TPU).

The paper's store-once Z-buffer rule generalizes to attention: the output
tile (and the online-softmax running max/sum) stay in VMEM scratch across
the whole KV sweep and are written to HBM exactly once.  Q tiles are held
stationary (the X-buffer role) while K/V tiles stream (the W-buffer role),
double-buffered by the Pallas pipeline.

Layout: q (BH, S, D) queries, k/v (BH_kv, T, D); GQA is expressed in the
index maps (kv head = q head // group) so K/V are never materialized per
q-head.  Causal masking skips fully-masked KV blocks via ``pl.when``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_pallas"]

_NEG_INF = -1e30


def _kernel(
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, kv_tiles: int, bq: int, bkv: int, causal: bool, scale: float,
    t_valid: int, q_offset: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * bq
    kv_start = ki * bkv

    # Causal: a KV block strictly after the last query row of this Q block
    # contributes nothing — skip it (the grid-restriction optimization is
    # handled by the wrapper for the common S == T case).
    run = (not causal) or (kv_start < q_offset + q_start + bq)

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32)            # (bq, d)
        k = k_ref[0].astype(jnp.float32)            # (bkv, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                    # (bq, bkv)

        col = kv_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = col < t_valid                         # padded tail of KV
        if causal:
            row = q_offset + q_start + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            mask = mask & (col <= row)
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[...]                          # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                       # (bq, bkv)
        # A fully-masked row has m_new == _NEG_INF, so exp(s - m_new) above
        # evaluates to exp(0) == 1 on its masked columns; zero them so l and
        # acc stay exactly 0 for rows with no visible KV position.
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)              # (bq, 1)

        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)             # (bkv, d)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ki == kv_tiles - 1)
    def _store_once():
        # Fully-masked rows (t_valid == 0, or every KV block causally
        # skipped) have l == 0 AND acc == 0: guard the divide so they store
        # exact zeros instead of NaN.
        l = l_ref[...]
        l_inv = jnp.where(l == 0.0, 1.0, 1.0 / l)
        o_ref[0] = (acc_ref[...] * l_inv).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("group", "causal", "scale", "bq", "bkv", "t_valid",
                     "q_offset", "interpret"),
)
def flash_attention_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    group: int = 1,
    causal: bool = True,
    scale: Optional[float] = None,
    bq: int = 256,
    bkv: int = 512,
    t_valid: Optional[int] = None,
    q_offset: int = 0,
    interpret: bool = False,
) -> jax.Array:
    """q: (BHq, S, D), k/v: (BHkv, T, D) with BHq == BHkv * group.

    S and T must be multiples of bq / bkv (the ops wrapper pads); ``t_valid``
    marks the unpadded KV length for masking, ``q_offset`` the absolute
    position of query row 0 (causal mask: col <= q_offset + row).
    Returns (BHq, S, D).
    """
    BHq, S, D = q.shape
    BHkv, T, _ = k.shape
    assert BHq == BHkv * group, (q.shape, k.shape, group)
    assert S % bq == 0 and T % bkv == 0, ((S, bq), (T, bkv))
    if scale is None:
        scale = D ** -0.5
    if t_valid is None:
        t_valid = T
    grid = (BHq, S // bq, T // bkv)

    kernel = functools.partial(
        _kernel,
        kv_tiles=grid[2], bq=bq, bkv=bkv, causal=causal,
        scale=float(scale), t_valid=int(t_valid), q_offset=int(q_offset),
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bkv, D), lambda h, i, j, g=group: (h // g, j, 0)),
            pl.BlockSpec((1, bkv, D), lambda h, i, j, g=group: (h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BHq, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="redmule_flash_attention",
    )(q, k, v)
