"""FP8 KV cache: slot admission + analytic byte accounting for serving.

The quantized cache itself lives in the model layer
(:func:`repro.models.transformer.init_cache` with ``storage_dtype``, the
attention step dequantizes on read and requantizes on write under the
per-head delayed scales).  This module owns the two serving-side pieces:

* :func:`insert_slot` — write a freshly prefilled single-request cache
  (batch == 1) into one slot of the pooled decode cache.  FP8 pools are
  merged *wide* and requantized under the ratcheted pool scale, so the
  admission is just another delayed-scaling observation: rows quantized
  under an older (smaller) scale can only shrink on requantization,
  never clip.

* Analytic KV byte accounting (:func:`decode_step_kv_bytes`,
  :func:`cache_size_bytes`) — the Engine's GemmEvents price the GEMM
  operand streams in the *compute* dtype (the datapath is binary16
  either way, which is also why flops are identical across storage
  dtypes), so cache-storage traffic needs its own model.  These feed the
  ``benchmarks/baselines/serve_bytes.json`` CI gate.

* Slot integrity (:func:`slot_checksum`, :func:`corrupt_slot_rows`) —
  CRC32 over one slot's *stored* KV rows (raw bytes, so FP8 and FP16
  pools are covered uniformly), used by the scheduler's audit cadence
  to detect bit-flipped cache state, plus the matching deterministic
  corruptor the fault injector uses (docs/serving.md failure model).
"""

from __future__ import annotations

import zlib
from typing import Any, Dict, Iterator, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import precision as prec
from repro.models import attention

CacheTree = Dict[str, Any]

__all__ = [
    "is_fp8_cache", "insert_slot", "n_cache_layers", "token_elems",
    "n_scale_elems", "storage_width", "decode_step_kv_bytes",
    "cache_size_bytes", "scale_health", "iter_kv_leaves",
    "slot_checksum", "corrupt_slot_rows", "state_bytes",
]


# --------------------------------------------------------------------- #
# Slot admission
# --------------------------------------------------------------------- #
def is_fp8_cache(cache: CacheTree) -> bool:
    if "k_scale" in cache or "ckv_scale" in cache:
        return True
    return any(is_fp8_cache(v) for v in cache.values() if isinstance(v, dict))


def _gqa_bcast(scale: jax.Array) -> jax.Array:
    # (..., Hkv) -> (..., 1, Hkv, 1, 1), aligned with k/v (..., B, Hkv, T, hd)
    return scale[..., None, :, None, None]


def _mla_bcast(scale: jax.Array) -> jax.Array:
    # (...,) -> (..., 1, 1, 1), aligned with ckv/kr (..., B, T, r)
    return scale[..., None, None, None]


def _gqa_reduce(ndim: int, bax: int):
    # keep (leading layers..., Hkv): fold batch, seq, head_dim
    return (bax, *range(bax + 2, ndim))


def _mla_reduce(ndim: int, bax: int):
    # per-tensor scales: fold everything from the batch axis on
    return tuple(range(bax, ndim))


def _insert_leaf(pool_sub, single_sub, name, slot, dtype, bcast, tail, reduce_of):
    fp8 = f"{name}_scale" in pool_sub
    p, s = pool_sub[name], single_sub[name]
    if fp8:
        pw = prec.dequantize_fp8(
            p, bcast(pool_sub[f"{name}_scale"]["scale"]), dtype)
        sw = prec.dequantize_fp8(
            s, bcast(single_sub[f"{name}_scale"]["scale"]), dtype)
    else:
        pw, sw = p, s
    bax = pw.ndim - tail
    merged = jax.lax.dynamic_update_slice_in_dim(
        pw, sw.astype(pw.dtype), slot, axis=bax)
    if not fp8:
        return {name: merged}
    sc2, applied = attention._refresh_scale(
        pool_sub[f"{name}_scale"], merged, reduce_of(merged.ndim, bax))
    q, _ = prec.quantize_fp8(merged, p.dtype, scale=bcast(applied))
    return {name: q, f"{name}_scale": sc2}


# Recurrent-state leaves (Mamba2 layers of a pattern stack): name -> the
# number of trailing axes from the batch axis on, as for KV's ``tail``.
STATE_TAILS = {"ssm": 4, "conv": 3}   # (B, H, P, N), (B, K-1, C)


def insert_slot(pool: CacheTree, single: CacheTree, slot,
                dtype=jnp.float16) -> CacheTree:
    """Write a single-request cache (batch == 1) into ``slot`` of the pool.

    ``slot`` may be traced (one jit trace serves every slot).  Supports the
    attn/moe cache trees (gqa and MLA subtrees, stacked or not) and a
    pattern stack's per-kind subtrees, whose Mamba2 state (``ssm``,
    ``conv``) is written as it is.  FP8 pools dequantize both sides to
    ``dtype``, merge, refresh the pool's delayed scales with the merged
    amax, and requantize under the ratcheted scale.
    """
    def sub(ps, ss):
        if "k" in ps:
            out = {}
            for name in ("k", "v"):
                out.update(_insert_leaf(
                    ps, ss, name, slot, dtype, _gqa_bcast, 4, _gqa_reduce))
            return out
        if "ckv" in ps:
            out = {}
            for name in ("ckv", "kr"):
                out.update(_insert_leaf(
                    ps, ss, name, slot, dtype, _mla_bcast, 3, _mla_reduce))
            return out
        if "ssm" in ps:
            return {name: jax.lax.dynamic_update_slice_in_dim(
                        ps[name], ss[name].astype(ps[name].dtype), slot,
                        axis=ps[name].ndim - STATE_TAILS[name])
                    for name in ps}
        if all(isinstance(v, dict) for v in ps.values()):
            return {key: sub(ps[key], ss[key]) for key in ps}
        raise ValueError(
            "slot insertion supports attn/moe (gqa/MLA) and pattern caches")

    return sub(pool, single)


# --------------------------------------------------------------------- #
# Slot integrity: checksums + deterministic corruption
# --------------------------------------------------------------------- #
def iter_kv_leaves(cache: CacheTree, _path: Tuple[str, ...] = ()
                   ) -> Iterator[Tuple[Tuple[str, ...], str, Any, int]]:
    """Yield ``(path, name, leaf, batch_axis)`` for every KV data leaf.

    Covers the gqa (``k``/``v``, trailing ``(B, Hkv, T, hd)``) and MLA
    (``ckv``/``kr``, trailing ``(B, T, r)``) subtrees, stacked or not and
    at any depth (``path`` is the keys down to the subtree); scale-state
    and recurrent-state leaves are skipped.  The sequence axis is always
    the second-to-last axis of the leaf.
    """
    for key, sub in cache.items():
        if not isinstance(sub, dict):
            continue
        path = _path + (key,)
        if "k" in sub:
            names, tail = ("k", "v"), 4
        elif "ckv" in sub:
            names, tail = ("ckv", "kr"), 3
        else:
            yield from iter_kv_leaves(sub, path)
            continue
        for name in names:
            leaf = sub[name]
            yield path, name, leaf, leaf.ndim - tail


def slot_checksum(cache: CacheTree, slot: int, length: int) -> int:
    """CRC32 over the raw stored bytes of one slot's first ``length`` rows.

    Hashes the *storage* representation (FP8 codes or FP16 halves) of
    every cached layer, so any bit flip in the slot's valid rows changes
    the digest.  Pool-wide scale state is deliberately excluded: under
    ratcheted delayed scaling an unrelated slot's admission may requantize
    the whole pool, which is why the scheduler re-arms guards after every
    cache mutation rather than only at insert.
    """
    crc = 0
    for _key, _name, leaf, bax in iter_kv_leaves(cache):
        arr = np.asarray(leaf)
        rows = np.take(arr, int(slot), axis=bax)[..., :int(length), :]
        crc = zlib.crc32(np.ascontiguousarray(rows).tobytes(), crc)
    return crc


def corrupt_slot_rows(cache: CacheTree, slot: int,
                      rows: Sequence[int]) -> CacheTree:
    """Bit-flip the stored bytes of ``rows`` in one slot (fault injection).

    Deterministic (XOR ``0xFF`` on every byte of the named rows across
    all cached layers), dtype-agnostic, and confined to ``slot`` — the
    matching :func:`slot_checksum` audit must flag exactly this slot and
    no co-resident one.  Returns a new cache tree; scale state is left
    untouched (real corruption hits the payload, and detection must not
    depend on the corruptor being polite).
    """
    idx = np.asarray(sorted({int(r) for r in rows}), np.intp)

    def flip(leaf, bax):
        arr = np.array(leaf)  # host copy we can mutate in place
        sel: list = [slice(None)] * arr.ndim
        sel[bax] = int(slot)
        slot_view = arr[tuple(sel)]
        row_sel: list = [slice(None)] * slot_view.ndim
        row_sel[-2] = idx
        chunk = np.ascontiguousarray(slot_view[tuple(row_sel)])
        flipped = (chunk.view(np.uint8) ^ np.uint8(0xFF)).view(chunk.dtype)
        slot_view[tuple(row_sel)] = flipped
        return jnp.asarray(arr)

    flipped = {path + (n,): flip(leaf, bax)
               for path, n, leaf, bax in iter_kv_leaves(cache)}

    def rebuild(node, path):
        if not isinstance(node, dict):
            return flipped.get(path, node)
        return {k: rebuild(v, path + (k,)) for k, v in node.items()}

    return rebuild(cache, ())


# --------------------------------------------------------------------- #
# Analytic byte accounting
# --------------------------------------------------------------------- #
def n_cache_layers(cfg) -> int:
    """Number of attention caches in the tree (mirror of ``init_cache``)."""
    if cfg.block_kind == "attn":
        return cfg.n_layers
    if cfg.block_kind == "moe":
        return 1 + (cfg.n_layers - cfg.moe.first_dense)
    if cfg.block_kind == "pattern":
        return sum(1 for t in cfg.layer_types if t == "attention")
    raise ValueError(f"serving byte accounting supports attn/moe/pattern, "
                     f"not {cfg.block_kind!r}")


def state_bytes(cfg) -> int:
    """One slot's recurrent state (fp32 SSM state and conv window of every
    Mamba2 layer), resident; a decode step reads and writes it once."""
    if cfg.block_kind != "pattern":
        return 0
    from repro.models import ssm

    di, H, P, G, N = ssm.mamba_dims(cfg)
    per = H * N * P + (cfg.ssm.d_conv - 1) * (di + 2 * G * N)
    return 4 * per * sum(1 for t in cfg.layer_types if t == "mamba")


def token_elems(cfg) -> int:
    """KV-cache elements appended per token, summed across cached layers."""
    if cfg.mla:
        per = cfg.mla.kv_lora_rank + cfg.mla.qk_rope_dim
    else:
        per = 2 * cfg.n_kv_heads * cfg.head_dim
    return n_cache_layers(cfg) * per


def n_scale_elems(cfg) -> int:
    """Delayed-scale scalars across the tree (k+v per-head, or 2 per-tensor)."""
    per = 2 if cfg.mla else 2 * cfg.n_kv_heads
    return n_cache_layers(cfg) * per


def storage_width(cfg, storage_dtype=None) -> int:
    return jnp.dtype(storage_dtype or cfg.policy.compute_dtype).itemsize


def decode_step_kv_bytes(cfg, lengths: Sequence[int],
                         storage_dtype: Optional[str] = None) -> int:
    """Semantic KV traffic of one continuous-batching decode step.

    Each active slot with ``l`` tokens already cached reads its merged
    ``l + 1`` rows (history plus the freshly appended one) and writes 1
    new row, all at the storage width; an FP8 cache adds the f32 scale
    vectors' round-trip (read for dequant, write-back of the refreshed
    delayed scale).  This prices what a serving memory system *moves* —
    not the CPU emulation's whole-tensor requantize — and since the
    datapath dequantizes to the compute dtype before the GEMMs, flops
    are identical across storage dtypes: FP8 vs FP16 at equal lengths
    is a pure byte ratio.
    """
    w = storage_width(cfg, storage_dtype)
    rows = sum(int(l) + 2 for l in lengths)  # (l + 1) reads + 1 write
    data = w * token_elems(cfg) * rows
    if storage_dtype is None:
        return data
    return data + 2 * 4 * n_scale_elems(cfg)  # f32 scale read + write


def cache_size_bytes(cfg, batch: int, max_len: int,
                     storage_dtype: Optional[str] = None) -> int:
    """Resident bytes of ``init_cache``'s output (data + scale-state leaves
    + recurrent state)."""
    w = storage_width(cfg, storage_dtype)
    data = w * token_elems(cfg) * batch * max_len + state_bytes(cfg) * batch
    if storage_dtype is None:
        return data
    # scale + amax_history + overflow_count per quantized tensor (4 B each)
    state = n_scale_elems(cfg) * (1 + attention.SCALE_HISTORY + 1) * 4
    return data + state


def scale_health(cache: CacheTree) -> Dict[str, Dict[str, float]]:
    """Max applied scale + total overflow count per quantized cache leaf."""
    out: Dict[str, Dict[str, float]] = {}
    for key, sub in cache.items():
        for name in ("k", "v", "ckv", "kr"):
            sc = sub.get(f"{name}_scale") if isinstance(sub, dict) else None
            if sc is None:
                continue
            out[f"{key}/{name}"] = {
                "max_scale": float(jnp.max(sc["scale"])),
                "overflow_total": int(jnp.sum(sc["overflow_count"])),
            }
    return out
