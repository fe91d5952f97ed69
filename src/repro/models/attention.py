"""Attention on the RedMulE engine: GQA (+qk-norm, sliding window) and MLA.

GQA takes rotary positions or none (``cfg.position_embedding`` "nope"), and
its score scale from ``cfg.attention_multiplier`` (default ``hd**-0.5``).

Prefill/train uses a q-chunked online attention (flash-style in pure jnp) so
32k-sequence score tensors are never materialized whole; on TPU the Pallas
``flash_attention`` kernel implements the same schedule.  Decode attends one
query against the KV cache.

Caches:
  * GQA — k/v tensors (B, Hkv, T, hd), updated in place at ``pos``;
  * MLA — the *compressed* (c_kv, k_rope) pair (B, T, r[+dr]): the paper's
    store-small / recompute-fat trade, k_nope/v re-expanded on the fly.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine
from repro.core import precision as prec
from repro.models import layers
from repro.models.layers import Param
from repro.optim import scale as oscale
from repro.runtime import sharding

__all__ = [
    "gqa_schema",
    "mla_schema",
    "gqa_attention",
    "mla_attention",
    "init_gqa_cache",
    "init_mla_cache",
    "chunked_attention",
]

NEG_INF = jnp.float32(-1e30)


def _static_int(x) -> Optional[int]:
    """Concrete scalar -> int; None for traced values or per-slot arrays
    (those keep the mask-driven chunked path)."""
    try:
        return int(x)
    except Exception:
        return None


# --------------------------------------------------------------------- #
# Schemas
# --------------------------------------------------------------------- #
def gqa_schema(cfg) -> Dict[str, Any]:
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    s: Dict[str, Any] = {
        # fused qkv: one fat RedMulE GEMM; split after
        "wqkv": Param((d, (hq + 2 * hkv) * hd), ("embed", "heads")),
        "wo": Param((hq * hd, d), ("heads", "embed")),
    }
    if cfg.use_bias:
        s["bqkv"] = Param(((hq + 2 * hkv) * hd,), ("heads",), init="zeros")
    if cfg.qk_norm:
        s["q_norm"] = Param((hd,), (None,), init="ones")
        s["k_norm"] = Param((hd,), (None,), init="ones")
    return s


def mla_schema(cfg) -> Dict[str, Any]:
    m = cfg.mla
    d, hq = cfg.d_model, cfg.n_heads
    return {
        "wq": Param((d, hq * (m.qk_nope_dim + m.qk_rope_dim)), ("embed", "heads")),
        # fused down-projection: compressed kv rank + shared rope key
        "wdkv": Param((d, m.kv_lora_rank + m.qk_rope_dim), ("embed", "kv_rank")),
        "kv_norm": Param((m.kv_lora_rank,), (None,), init="ones"),
        "wuk": Param((m.kv_lora_rank, hq * m.qk_nope_dim), ("kv_rank", "heads")),
        "wuv": Param((m.kv_lora_rank, hq * m.v_head_dim), ("kv_rank", "heads")),
        "wo": Param((hq * m.v_head_dim, d), ("heads", "embed")),
    }


# --------------------------------------------------------------------- #
# Caches
# --------------------------------------------------------------------- #
SCALE_HISTORY = 16  # delayed-scaling amax window per cache scale leaf


def _init_scale_leaves(lead_shape: Tuple[int, ...]) -> Dict[str, jax.Array]:
    """Per-head (or per-tensor, ``lead_shape == ()``) delayed-scaling state,
    stored as plain cache leaves so it rides the cache pytree through
    jit/scan/donation: the three fields of :class:`repro.optim.scale.
    Fp8ScaleState`, broadcast over the leading head dim."""
    return {
        "scale": jnp.ones(lead_shape, jnp.float32),
        "amax_history": jnp.zeros((*lead_shape, SCALE_HISTORY), jnp.float32),
        "overflow_count": jnp.zeros(lead_shape, jnp.int32),
    }


def _scale_leaf_axes(head_axes: Tuple) -> Dict[str, Tuple]:
    return {
        "scale": head_axes,
        "amax_history": (*head_axes, None),
        "overflow_count": head_axes,
    }


def _refresh_scale(sc: Dict[str, jax.Array], new_rows: jax.Array,
                   reduce_axes: Tuple[int, ...]
                   ) -> Tuple[Dict[str, jax.Array], jax.Array]:
    """Fold the new rows' amax into the delayed-scaling window
    (:func:`repro.optim.scale.update_fp8_scale`, vmapped over heads) and
    return ``(updated leaves, applied scale)``.  The applied scale
    *ratchets* (``max`` with the stored scale): rows quantized under an
    older scale can only shrink on requantization, never clip."""
    st = oscale.Fp8ScaleState(
        sc["scale"], sc["amax_history"], sc["overflow_count"])
    amax = jnp.max(jnp.abs(new_rows.astype(jnp.float32)), axis=reduce_axes)
    upd = oscale.update_fp8_scale
    for _ in range(amax.ndim):   # nest over (layers, heads) leading dims
        upd = jax.vmap(upd)
    st2 = upd(st, amax)
    applied = jnp.maximum(sc["scale"], st2.scale)
    return ({"scale": applied, "amax_history": st2.amax_history,
             "overflow_count": st2.overflow_count}, applied)


def init_gqa_cache(cfg, batch: int, max_len: int, dtype,
                   storage_dtype=None) -> Dict[str, jax.Array]:
    hkv, hd = cfg.n_kv_heads, cfg.head_dim
    shape = (batch, hkv, max_len, hd)
    if storage_dtype is None:
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    st = jnp.dtype(storage_dtype)
    if not prec.is_fp8(st):
        raise ValueError(
            f"storage_dtype must be an FP8 format {prec.FP8_FORMATS}, "
            f"got {st.name!r}")
    return {
        "k": jnp.zeros(shape, st), "v": jnp.zeros(shape, st),
        "k_scale": _init_scale_leaves((hkv,)),
        "v_scale": _init_scale_leaves((hkv,)),
    }


def init_mla_cache(cfg, batch: int, max_len: int, dtype,
                   storage_dtype=None) -> Dict[str, jax.Array]:
    m = cfg.mla
    if storage_dtype is None:
        return {
            "ckv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
            "kr": jnp.zeros((batch, max_len, m.qk_rope_dim), dtype),
        }
    st = jnp.dtype(storage_dtype)
    if not prec.is_fp8(st):
        raise ValueError(
            f"storage_dtype must be an FP8 format {prec.FP8_FORMATS}, "
            f"got {st.name!r}")
    return {
        "ckv": jnp.zeros((batch, max_len, m.kv_lora_rank), st),
        "kr": jnp.zeros((batch, max_len, m.qk_rope_dim), st),
        # MLA scales are per-tensor: the compressed latent has no head dim
        "ckv_scale": _init_scale_leaves(()),
        "kr_scale": _init_scale_leaves(()),
    }


# --------------------------------------------------------------------- #
# Core attention math (q-chunked online)
# --------------------------------------------------------------------- #
def _masked_softmax_block(
    s: jax.Array,  # (B, Hkv, G, qc, T) fp32 scores
    rows: jax.Array,  # (qc,) or (B, qc) absolute query positions
    kv_valid: jax.Array,  # scalar or (B,): number of valid kv slots
    causal: bool,
    window: Optional[jax.Array],
) -> jax.Array:
    # Serving decode batches carry per-slot positions: rows/kv_valid grow a
    # leading batch dim and the mask broadcasts (Bm, 1, 1, qc, T) over the
    # scores; single-sequence callers keep Bm == 1.
    cols = jnp.arange(s.shape[-1])
    rows2 = rows if rows.ndim == 2 else rows[None]            # (Bm, qc)
    kv = jnp.reshape(jnp.asarray(kv_valid), (-1, 1, 1))       # (Bm, 1, 1)
    mask = cols[None, None, :] < kv
    if causal:
        mask = mask & (cols[None, None, :] <= rows2[:, :, None])
    if window is not None:
        mask = mask & (cols[None, None, :] > rows2[:, :, None] - window)
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    return jax.nn.softmax(s, axis=-1)


def chunked_attention(
    q: jax.Array,  # (B, Hkv, G, S, hd)
    k: jax.Array,  # (B, Hkv, T, hd)
    v: jax.Array,  # (B, Hkv, T, hdv)
    *,
    q_offset: jax.Array,  # scalar or (B,): absolute position of q[..., 0, :]
    kv_valid: jax.Array,  # scalar or (B,): valid kv length
    causal: bool = True,
    window: Optional[jax.Array] = None,
    q_chunk: int = 1024,
    scale: Optional[float] = None,
    kv_group_sizes: Optional[Any] = None,
    policy: prec.Policy,
) -> jax.Array:
    """Returns (B, Hkv, G, S, hdv). Scores fp32, never materialized beyond
    one q-chunk (the RedMulE store-once rule applied to attention).

    ``kv_group_sizes`` (serving decode, S == 1 only): per-batch-slot valid
    kv lengths.  The score GEMM then dispatches through the Engine's
    ragged ``grouped_matmul`` path — one group per (slot, kv-head), group
    size = that slot's kv length — so mixed-length decode batches bill
    flops/bytes for the *valid* kv rows only.  Concrete sizes (numpy, at
    an instrumentation trace) pin ``valid_rows`` on the event; traced
    sizes fall back to dense billing with identical numerics."""
    B, Hkv, G, S, hd = q.shape
    if scale is None:
        scale = hd**-0.5
    scores_policy = dataclasses.replace(
        policy, name=policy.name + "_scores", output_dtype=jnp.float32,
        faithful_accum=False,
    )
    if kv_group_sizes is not None:
        if S != 1:
            raise ValueError("kv_group_sizes is a decode-only (S == 1) path")
        return _ragged_decode_attention(
            q, k, v, q_offset=q_offset, kv_valid=kv_valid, window=window,
            kv_group_sizes=kv_group_sizes, scale=scale,
            scores_policy=scores_policy, policy=policy)
    # Decode: pin the attention dots to the sequence-sharded KV layout —
    # scores/pv become partial over the seq shards (small softmax
    # all-reduces) instead of GSPMD "involuntarily rematerializing" the
    # whole cache to match the head-sharded output (a 537 MB x layers
    # all-gather).  Training keeps GSPMD's head-sharded schedule.
    rules = sharding.current_rules()
    pin = rules is not None and rules.serve_attention
    if (window is None and not pin and v.shape[-1] == hd
            and engine.backend_supports(engine.default_backend(),
                                        "attention")):
        # First-class engine op: the backend's fused flash sweep (same
        # numerics contract and identical billed flops as the q-chunked
        # path below, but online-softmax in VMEM with causally dead KV
        # blocks skipped).  Backends without the capability keep the
        # q-chunked path — the engine's reference composition would
        # materialize the full S x T score tensor.  Traced
        # offsets/lengths (serving's per-slot decode) also stay here.
        off_i = _static_int(q_offset)
        kvv_i = _static_int(kv_valid)
        if off_i is not None and kvv_i is not None:
            out = engine.attention(
                q.reshape(B, Hkv * G, S, hd), k, v, causal=causal,
                scale=scale, q_offset=off_i, t_valid=kvv_i, policy=policy)
            return out.reshape(B, Hkv, G, S, -1)
    kt = jnp.swapaxes(k, -1, -2)[:, :, None]  # (B, Hkv, 1, hd, T)
    vb = v[:, :, None]

    def c(x, *axes):
        return sharding.constrain(x, *axes) if pin else x

    kt = c(kt, "batch", "kv_heads", None, None, "kv_seq")
    vb = c(vb, "batch", "kv_heads", None, "kv_seq", None)

    def rows_at(start):
        off = jnp.asarray(q_offset)
        n = min(q_chunk, S)
        r = jnp.arange(n) + start
        return off[:, None] + r[None] if off.ndim == 1 else off + r

    def block(q_blk: jax.Array, rows: jax.Array) -> jax.Array:
        q_blk = c(q_blk, "batch", "kv_heads", None, None, None)
        s = engine.matmul(q_blk, kt, policy=scores_policy) * scale
        s = c(s, "batch", "kv_heads", None, None, "kv_seq")
        p = _masked_softmax_block(s, rows, kv_valid, causal, window)
        out = engine.matmul(p.astype(policy.compute_dtype), vb, policy=policy)
        return c(out, "batch", "kv_heads", None, None, None)

    if S <= q_chunk:
        return block(q, rows_at(0))

    n = -(-S // q_chunk)
    pad = n * q_chunk - S
    if pad:
        q = jnp.pad(q, [(0, 0)] * 3 + [(0, pad), (0, 0)])
    qs = jnp.moveaxis(q.reshape(B, Hkv, G, n, q_chunk, hd), 3, 0)

    def step(_, xs):
        q_blk, idx = xs
        return None, block(q_blk, rows_at(idx * q_chunk))

    with engine.repeat(n):  # body traced once, runs n q-chunks
        _, out = jax.lax.scan(step, None, (qs, jnp.arange(n)))
    out = jnp.moveaxis(out, 0, 3).reshape(B, Hkv, G, n * q_chunk, -1)
    return out[:, :, :, :S]


def _ragged_decode_attention(
    q: jax.Array,  # (B, Hkv, G, 1, hd)
    k: jax.Array,  # (B, Hkv, T, hd)
    v: jax.Array,  # (B, Hkv, T, hdv)
    *,
    q_offset: jax.Array,
    kv_valid: jax.Array,
    window: Optional[jax.Array],
    kv_group_sizes: Any,
    scale: float,
    scores_policy: prec.Policy,
    policy: prec.Policy,
) -> jax.Array:
    """Mixed-length decode batch through the ragged grouped-GEMM path.

    The GQA query group sits in the rows of both cache contractions: each
    (slot, kv-head) is one batch entry with M = G query rows, and K and V
    are read once, in their stored ``(B·Hkv, T, hd)`` layout — nothing is
    broadcast over G, and T is the scores' lane dimension.

    Scores ``(B·Hkv, G, hd) · Kᵀ -> (B·Hkv, G, T)`` dispatch through the
    Engine's ragged ``grouped_matmul`` under the "nt" layout (K is never
    transposed), one group per (slot, kv-head) with ``group_sizes`` = the
    slot's valid kv length: the Engine's ``valid_rows`` accounting bills
    ``sum(sizes) · Hkv`` kv rows on the ragged T dimension, not ``B · T``
    dense.  Columns at or beyond a group's size come back zeroed and are
    re-masked to -inf by the softmax mask, so numerics match the dense
    block exactly.  PV ``(B·Hkv, G, T) · (B·Hkv, T, hdv)`` is a dense
    batched dispatch: its ragged dim is the *contraction* (masked
    probabilities are exact zeros), which forward grouped GEMMs cannot
    bill raggedly."""
    B, Hkv, G, S, hd = q.shape
    T = k.shape[2]
    sizes = kv_group_sizes
    if isinstance(sizes, (list, tuple)):
        sizes = np.asarray(sizes, np.int32)
    gs = (np.repeat(sizes, Hkv) if isinstance(sizes, np.ndarray)
          else jnp.repeat(jnp.asarray(sizes), Hkv))
    s = engine.grouped_matmul(
        q.reshape(B * Hkv, G, hd), k.reshape(B * Hkv, T, hd),
        group_sizes=gs, layout="nt", policy=scores_policy)
    s = s.reshape(B, Hkv, G, 1, T) * scale
    off = jnp.asarray(q_offset)
    rows = off[:, None] if off.ndim == 1 else off + jnp.arange(1)
    p = _masked_softmax_block(s, rows, kv_valid, True, window)
    out = engine.matmul(
        p.reshape(B, Hkv, G, T).astype(policy.compute_dtype), v,
        policy=policy)
    return out[:, :, :, None, :]


# --------------------------------------------------------------------- #
# GQA forward
# --------------------------------------------------------------------- #
def gqa_attention(
    params: Dict[str, jax.Array],
    x: jax.Array,  # (B, S, d)
    cfg,
    *,
    pos_offset: jax.Array,
    cache: Optional[Dict[str, jax.Array]] = None,
    window: Optional[jax.Array] = None,
    policy: prec.Policy,
    q_chunk: int = 1024,
    kv_group_sizes: Optional[Any] = None,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    B, S, d = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    g = hq // hkv
    off = jnp.asarray(pos_offset)
    if off.ndim == 1 and S != 1:
        raise ValueError("per-slot pos_offset is a decode-only (S == 1) path")

    qkv = engine.matmul(x, params["wqkv"], policy=policy)
    if "bqkv" in params:
        qkv = qkv + params["bqkv"].astype(qkv.dtype)
    q, kk, vv = jnp.split(qkv, [hq * hd, (hq + hkv) * hd], axis=-1)
    q = q.reshape(B, S, hq, hd).transpose(0, 2, 1, 3)       # (B, Hq, S, hd)
    kk = kk.reshape(B, S, hkv, hd).transpose(0, 2, 1, 3)    # (B, Hkv, S, hd)
    vv = vv.reshape(B, S, hkv, hd).transpose(0, 2, 1, 3)

    if cfg.qk_norm:
        q = layers.rmsnorm(q, params["q_norm"], cfg.norm_eps)
        kk = layers.rmsnorm(kk, params["k_norm"], cfg.norm_eps)

    if cfg.position_embedding == "rope":
        positions = (off[:, None] + jnp.arange(S)[None] if off.ndim == 1
                     else off + jnp.arange(S))
        cos, sin = layers.rope(positions, hd, cfg.rope_theta)
        q = layers.apply_rope(q, cos, sin)
        kk = layers.apply_rope(kk, cos, sin)

    if cache is not None:
        fp8 = prec.is_fp8(cache["k"].dtype)
        if fp8:
            # upcast on read: E4M3 tensors widen to the compute dtype
            # against the per-head delayed scales stored alongside them
            ks = cache["k_scale"]["scale"].reshape(1, -1, 1, 1)
            vs = cache["v_scale"]["scale"].reshape(1, -1, 1, 1)
            k_prev = prec.dequantize_fp8(cache["k"], ks, kk.dtype)
            v_prev = prec.dequantize_fp8(cache["v"], vs, vv.dtype)
        else:
            k_prev, v_prev = cache["k"], cache["v"]
        if S == 1:
            # decode: masked merge — elementwise over the (possibly
            # TP-sharded) cache sequence dim, so no gather is forced the way
            # a dynamic-update-slice at a traced position would; a per-slot
            # (B,) pos_offset broadcasts each slot's own hit row
            T = k_prev.shape[2]
            hit = (jnp.arange(T)[None, :]
                   == jnp.reshape(off, (-1, 1)))[:, None, :, None]
            k_all = jnp.where(hit, kk.astype(k_prev.dtype), k_prev)
            v_all = jnp.where(hit, vv.astype(v_prev.dtype), v_prev)
        else:
            zero = jnp.zeros((), jnp.int32)
            k_all = jax.lax.dynamic_update_slice(
                k_prev, kk.astype(k_prev.dtype),
                (zero, zero, pos_offset, zero))
            v_all = jax.lax.dynamic_update_slice(
                v_prev, vv.astype(v_prev.dtype),
                (zero, zero, pos_offset, zero))
        if fp8:
            # write-back: refresh the per-head delayed scales with the new
            # rows' amax, requantize under the (ratcheted) applied scale
            k_sc, k_as = _refresh_scale(cache["k_scale"], kk, (0, 2, 3))
            v_sc, v_as = _refresh_scale(cache["v_scale"], vv, (0, 2, 3))
            k_q, _ = prec.quantize_fp8(
                k_all, cache["k"].dtype, scale=k_as.reshape(1, -1, 1, 1))
            v_q, _ = prec.quantize_fp8(
                v_all, cache["v"].dtype, scale=v_as.reshape(1, -1, 1, 1))
            new_cache = {"k": k_q, "v": v_q,
                         "k_scale": k_sc, "v_scale": v_sc}
        else:
            new_cache = {"k": k_all, "v": v_all}
        kv_valid = pos_offset + S
    else:
        k_all, v_all, new_cache, kv_valid = kk, vv, None, jnp.int32(S)

    k_all = sharding.constrain(k_all, "batch", "kv_heads", "kv_seq", None)
    v_all = sharding.constrain(v_all, "batch", "kv_heads", "kv_seq", None)

    qg = q.reshape(B, hkv, g, S, hd)
    o = chunked_attention(
        qg, k_all, v_all,
        q_offset=pos_offset, kv_valid=kv_valid, causal=True,
        window=window, q_chunk=q_chunk, scale=cfg.attention_multiplier,
        policy=policy, kv_group_sizes=kv_group_sizes,
    )
    o = o.reshape(B, hq, S, hd).transpose(0, 2, 1, 3).reshape(B, S, hq * hd)
    o = sharding.constrain(o, "batch", None, "heads")
    out = engine.matmul(o, params["wo"], policy=policy)
    return out, new_cache


# --------------------------------------------------------------------- #
# MLA forward (DeepSeek-V2 family)
# --------------------------------------------------------------------- #
def mla_attention(
    params: Dict[str, jax.Array],
    x: jax.Array,
    cfg,
    *,
    pos_offset: jax.Array,
    cache: Optional[Dict[str, jax.Array]] = None,
    policy: prec.Policy,
    q_chunk: int = 1024,
    kv_group_sizes: Optional[Any] = None,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    # kv_group_sizes is accepted for API parity with gqa_attention; the
    # absorbed MLA decode is einsum-shaped (no grouped ragged form), so
    # per-slot lengths only drive the mask here, not the billing.
    del kv_group_sizes
    m = cfg.mla
    B, S, d = x.shape
    hq = cfg.n_heads
    dn, dr, dv, r = m.qk_nope_dim, m.qk_rope_dim, m.v_head_dim, m.kv_lora_rank
    off = jnp.asarray(pos_offset)
    if off.ndim == 1 and S != 1:
        raise ValueError("per-slot pos_offset is a decode-only (S == 1) path")

    q = engine.matmul(x, params["wq"], policy=policy).reshape(B, S, hq, dn + dr)
    q = q.transpose(0, 2, 1, 3)  # (B, Hq, S, dn+dr)
    qn, qr = q[..., :dn], q[..., dn:]

    dkv = engine.matmul(x, params["wdkv"], policy=policy)  # (B, S, r + dr)
    ckv, kr = dkv[..., :r], dkv[..., r:]
    ckv = layers.rmsnorm(ckv, params["kv_norm"], cfg.norm_eps)

    positions = (off[:, None] + jnp.arange(S)[None] if off.ndim == 1
                 else off + jnp.arange(S))
    cos, sin = layers.rope(positions, dr, cfg.rope_theta)
    qr = layers.apply_rope(qr, cos, sin)
    kr = layers.apply_rope(kr[:, None], cos, sin)[:, 0]  # (B, S, dr)

    if cache is not None:
        fp8 = prec.is_fp8(cache["ckv"].dtype)
        if fp8:
            ckv_prev = prec.dequantize_fp8(
                cache["ckv"], cache["ckv_scale"]["scale"], ckv.dtype)
            kr_prev = prec.dequantize_fp8(
                cache["kr"], cache["kr_scale"]["scale"], kr.dtype)
        else:
            ckv_prev, kr_prev = cache["ckv"], cache["kr"]
        if S == 1:
            T = ckv_prev.shape[1]
            hit = (jnp.arange(T)[None, :]
                   == jnp.reshape(off, (-1, 1)))[:, :, None]
            ckv_all = jnp.where(hit, ckv.astype(ckv_prev.dtype), ckv_prev)
            kr_all = jnp.where(hit, kr.astype(kr_prev.dtype), kr_prev)
        else:
            zero = jnp.zeros((), jnp.int32)
            ckv_all = jax.lax.dynamic_update_slice(
                ckv_prev, ckv.astype(ckv_prev.dtype),
                (zero, pos_offset, zero))
            kr_all = jax.lax.dynamic_update_slice(
                kr_prev, kr.astype(kr_prev.dtype),
                (zero, pos_offset, zero))
        if fp8:
            c_sc, c_as = _refresh_scale(cache["ckv_scale"], ckv, (0, 1, 2))
            r_sc, r_as = _refresh_scale(cache["kr_scale"], kr, (0, 1, 2))
            ckv_q, _ = prec.quantize_fp8(
                ckv_all, cache["ckv"].dtype, scale=c_as)
            kr_q, _ = prec.quantize_fp8(kr_all, cache["kr"].dtype, scale=r_as)
            new_cache = {"ckv": ckv_q, "kr": kr_q,
                         "ckv_scale": c_sc, "kr_scale": r_sc}
        else:
            new_cache = {"ckv": ckv_all, "kr": kr_all}
        kv_valid = pos_offset + S
    else:
        ckv_all, kr_all, new_cache, kv_valid = ckv, kr, None, jnp.int32(S)

    ckv_all = sharding.constrain(ckv_all, "batch", "kv_seq", None)
    T = ckv_all.shape[1]

    if S == 1 and cache is not None:
        # Absorbed decode: fold W_uk into the query and W_uv into the
        # context so the compressed cache is attended DIRECTLY — no
        # per-step (T, Hq*dn) k/v re-expansion (saves a factor of dn=128
        # on the T-dependent FLOPs; this was the useful~0 diagnosis of the
        # MLA decode cells in EXPERIMENTS.md §Roofline).
        # fp32-out engine policy: every absorbed contraction accumulates
        # (and is returned) in fp32, exactly like the old preferred_element_type
        abs_policy = prec.Policy(
            policy.name + "_absorbed", policy.compute_dtype,
            jnp.float32, jnp.float32)
        wuk = params["wuk"].reshape(r, hq, dn)
        wuv = params["wuv"].reshape(r, hq, dv)
        q_abs = engine.einsum2d("bhsd,rhd->bhsr", qn, wuk, policy=abs_policy)
        s = engine.einsum2d("bhsr,btr->bhst", q_abs, ckv_all, policy=abs_policy)
        s = s + engine.einsum2d("bhsd,btd->bhst", qr, kr_all, policy=abs_policy)
        s = s * (dn + dr) ** -0.5
        mask = (jnp.arange(T)[None, None, None, :]
                < jnp.reshape(jnp.asarray(kv_valid), (-1, 1, 1, 1)))
        s = jnp.where(mask, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        ctx = engine.einsum2d("bhst,btr->bhsr", p, ckv_all, policy=abs_policy)
        o = engine.einsum2d("bhsr,rhd->bhsd", ctx, wuv, policy=abs_policy)
        o = o.astype(policy.compute_dtype)
        o = o.transpose(0, 2, 1, 3).reshape(B, S, hq * dv)
        o = sharding.constrain(o, "batch", None, "heads")
        return engine.matmul(o, params["wo"], policy=policy), new_cache

    # Prefill/train: re-expand the compressed cache (the MLA trade:
    # small cache, extra GEMM)
    kn = engine.matmul(ckv_all, params["wuk"], policy=policy).reshape(B, T, hq, dn)
    vv = engine.matmul(ckv_all, params["wuv"], policy=policy).reshape(B, T, hq, dv)
    kn = kn.transpose(0, 2, 1, 3)  # (B, Hq, T, dn)
    vv = vv.transpose(0, 2, 1, 3)
    k_full = jnp.concatenate(
        [kn, jnp.broadcast_to(kr_all[:, None], (B, hq, T, dr))], axis=-1)
    q_full = jnp.concatenate([qn, qr], axis=-1)

    o = chunked_attention(
        q_full[:, :, None], k_full, vv,
        q_offset=pos_offset, kv_valid=kv_valid, causal=True,
        q_chunk=q_chunk, scale=(dn + dr) ** -0.5, policy=policy,
    )
    o = o[:, :, 0].transpose(0, 2, 1, 3).reshape(B, S, hq * dv)
    o = sharding.constrain(o, "batch", None, "heads")
    out = engine.matmul(o, params["wo"], policy=policy)
    return out, new_cache
