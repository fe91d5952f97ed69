"""Gradient compression with error feedback (distributed-optimization trick).

For cross-pod data parallelism the gradient all-reduce dominates the slow
inter-pod links.  We compress per-leaf *before* the manual ``psum`` in the
shard_map DP step and keep the quantization residual in an fp32
error-feedback buffer (EF-SGD), which keeps convergence unbiased in
expectation.  Four wires:

* ``fp16``     — plain downcast; the psum itself runs on the 16-bit dtype.
* ``int8``     — symmetric per-tensor scale, quantized to ±127.
* ``fp8_e4m3`` — FP8 wire (``fp8`` is an alias), quantized through
  :func:`repro.core.precision.quantize_fp8` under **delayed scaling**: a
  per-leaf :class:`repro.optim.scale.Fp8ScaleState` rolling-amax window
  supplies the scale the *next* step divides by (one overflowed gradient
  cannot poison it; an all-zero run cannot collapse it), and the residual
  ``g - dequant(q)`` — including anything clipped at the format max —
  lands in the error-feedback buffer.
* ``fp8_e5m2`` — the wide-range FP8 variant (gradients span more orders
  of magnitude than they need mantissa).

Per-host scales (int8/fp8) are handled *per host*: the all-reduce sums the
dequantized per-host terms ``q_i * s_i`` so a host with tiny gradients is
never reweighted by another host's large scale (the seed version averaged
the scales into one shared divisor, which mis-weighted hosts with very
different gradient magnitudes by orders of magnitude — pinned against the
fp32 oracle in tests/test_optim.py).  In the simulation the summed term
travels as f32; on a real network the 8-bit payload crosses the wire and
each hop dequantizes locally, which is what :meth:`Compressor.wire_bytes`
prices — analytically, like GEMM bytes, and pinned in CI against
``benchmarks/baselines/collective_bytes.json``.

Used by ``launch/train.py --compress={none,fp16,int8,fp8,fp8_e4m3,
fp8_e5m2}``, the elastic worker (``runtime/elastic.py``), and the
``ft-gates`` CI job.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core import precision as prec
from repro.optim.scale import (Fp8ScaleState, fp8_scale_of, init_fp8_scale,
                               update_fp8_scale)

__all__ = [
    "Compressor", "Fp8LeafState", "collective_wire_bytes",
    "NONE", "FP16", "INT8", "FP8_E4M3", "FP8_E5M2", "KINDS",
]

KINDS = ("none", "fp16", "int8", "fp8_e4m3", "fp8_e5m2")

_WIRE_BITS = {"none": 32, "fp16": 16, "int8": 8,
              "fp8_e4m3": 8, "fp8_e5m2": 8}
_FP8_DTYPES = {"fp8_e4m3": "float8_e4m3fn", "fp8_e5m2": "float8_e5m2"}


class Fp8LeafState(NamedTuple):
    """Per-leaf compressor state for the FP8 wires: the fp32 error-feedback
    buffer plus the delayed-scaling window the next quantization reads."""

    ef: jax.Array            # fp32, shape of the gradient leaf
    scale: Fp8ScaleState     # rolling-amax delayed scale


def _is_wire_pair(x) -> bool:
    # (q, scale) wire leaves; Fp8ScaleState is a 3-tuple so it never matches
    return isinstance(x, tuple) and len(x) == 2 and not isinstance(x, Fp8LeafState)


@dataclasses.dataclass(frozen=True)
class Compressor:
    kind: str = "none"  # none | fp16 | int8 | fp8[_e4m3] | fp8_e5m2
    history_len: int = 16  # delayed-scaling window (fp8 kinds)

    def __post_init__(self):
        kind = "fp8_e4m3" if self.kind == "fp8" else self.kind
        if kind not in KINDS:
            raise ValueError(
                f"unknown compression kind {self.kind!r}; known: "
                f"{KINDS + ('fp8',)}")
        object.__setattr__(self, "kind", kind)

    @property
    def is_fp8(self) -> bool:
        return self.kind in _FP8_DTYPES

    @property
    def fp8_dtype(self):
        return jnp.dtype(_FP8_DTYPES[self.kind])

    @property
    def wire_bits(self) -> int:
        return _WIRE_BITS[self.kind]

    @property
    def scaled(self) -> bool:
        """True when the wire carries a per-tensor f32 scale next to q."""
        return self.kind == "int8" or self.is_fp8

    # ------------------------------------------------------------- #
    def init(self, params) -> Any:
        if self.kind == "none":
            return None
        if self.is_fp8:
            return jax.tree.map(
                lambda p: Fp8LeafState(
                    ef=jnp.zeros(p.shape, jnp.float32),
                    scale=init_fp8_scale(self.history_len)),
                params)
        return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

    def compress(self, grads, ef) -> Tuple[Any, Any]:
        """Returns (wire_grads, new_error_feedback). wire_grads are what
        crosses the network; callers psum them and then ``decompress``."""
        if self.kind == "none":
            return grads, ef
        if self.is_fp8:
            return self._compress_fp8(grads, ef)

        def comp(g, e):
            g = g.astype(jnp.float32) + e
            if self.kind == "fp16":
                wire = g.astype(jnp.float16)
                resid = g - wire.astype(jnp.float32)
                return wire, resid
            # int8: symmetric per-tensor scale
            amax = jnp.maximum(jnp.max(jnp.abs(g)), 1e-12)
            scale = amax / 127.0
            q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
            resid = g - q.astype(jnp.float32) * scale
            return (q, scale), resid

        flat = jax.tree.map(comp, grads, ef)
        wire = jax.tree.map(lambda t: t[0], flat, is_leaf=_is_wire_pair)
        new_ef = jax.tree.map(lambda t: t[1], flat, is_leaf=_is_wire_pair)
        return wire, new_ef

    def _compress_fp8(self, grads, state) -> Tuple[Any, Any]:
        """FP8 wire: delayed scale in, residual (incl. clipping) out."""
        dt = self.fp8_dtype
        fmax = prec.fp8_max(dt)

        def comp(g, st: Fp8LeafState):
            g32 = g.astype(jnp.float32) + st.ef
            s = fp8_scale_of(st.scale)
            # clip at the format max *under the delayed scale*: a sudden
            # amax growth saturates instead of overflowing; the clipped
            # mass rides in the error feedback until the window catches up
            q, s = prec.quantize_fp8(
                jnp.clip(g32, -fmax * s, fmax * s), dt, scale=s)
            resid = g32 - prec.dequantize_fp8(q, s)
            new_st = Fp8LeafState(
                ef=resid,
                scale=update_fp8_scale(st.scale, jnp.max(jnp.abs(g32))))
            return (q, s), new_st

        flat_g, gdef = jax.tree.flatten(grads)
        flat_s = jax.tree.flatten(
            state, is_leaf=lambda x: isinstance(x, Fp8LeafState))[0]
        pairs = [comp(g, st) for g, st in zip(flat_g, flat_s)]
        wire = jax.tree.unflatten(gdef, [p[0] for p in pairs])
        new_state = jax.tree.unflatten(gdef, [p[1] for p in pairs])
        return wire, new_state

    def decompress(self, wire) -> Any:
        if self.kind == "none":
            return wire
        if self.kind == "fp16":
            return jax.tree.map(lambda w: w.astype(jnp.float32), wire)

        def dec(leaf):
            q, scale = leaf
            return q.astype(jnp.float32) * scale

        return jax.tree.map(dec, wire, is_leaf=_is_wire_pair)

    def psum_wire(self, wire, axis_names) -> Any:
        """Mean-all-reduce the wire representation inside shard_map.

        Scaled wires (int8/fp8) reduce the *per-host dequantized* terms
        ``q_i * s_i``: each host's payload is weighted by its own scale, so
        hosts with very different gradient magnitudes contribute exactly
        (the seed averaged the scales into one shared divisor — a host with
        a 1e-4 amax next to a 1e3-amax host was inflated ~1e7x).  Wire cost
        is still billed at ``wire_bits`` per element (:meth:`wire_bytes`):
        the 8-bit payload is what a ring implementation moves, dequantizing
        locally at each hop."""
        if self.scaled:
            def ps(leaf):
                q, scale = leaf
                tot = jax.lax.psum(
                    q.astype(jnp.float32) * scale, axis_names)
                n = jax.lax.psum(jnp.ones((), jnp.float32), axis_names)
                return tot / n
            return jax.tree.map(ps, wire, is_leaf=_is_wire_pair)

        def ps(g):
            # reduce on the 16-bit wire — upcasting first would defeat the
            # compression (EF bounds the f16 summation error over steps)
            tot = jax.lax.psum(g, axis_names)
            cnt = jax.lax.psum(jnp.ones((), jnp.float32), axis_names)
            return tot.astype(jnp.float32) / cnt

        return jax.tree.map(ps, wire)

    # ------------------------------------------------------------- #
    def wire_bytes(self, tree) -> int:
        """Analytic network bytes one gradient all-reduce of ``tree`` puts
        on the wire under this compressor — priced like GEMM bytes (what
        the algorithm sends, not what the simulation materializes), over
        any pytree of arrays or ShapeDtypeStructs.  Scaled wires add one
        f32 scale per tensor.  Pinned in CI against
        ``benchmarks/baselines/collective_bytes.json`` (ft-gates)."""
        total = 0
        for leaf in jax.tree.leaves(tree):
            n = int(math.prod(getattr(leaf, "shape", ()) or (1,)))
            total += n * self.wire_bits // 8
            if self.scaled:
                total += 4
        return total


def collective_wire_bytes(kind: str, tree) -> int:
    """Convenience: :meth:`Compressor.wire_bytes` for a kind name."""
    return Compressor(kind).wire_bytes(tree)


NONE = Compressor("none")
FP16 = Compressor("fp16")
INT8 = Compressor("int8")
FP8_E4M3 = Compressor("fp8_e4m3")
FP8_E5M2 = Compressor("fp8_e5m2")


def compressed_mean_allreduce(grads, ef, compressor: Compressor, mesh,
                              axis_names=("data",)):
    """Mean-all-reduce gradients across DP shards on a compressed wire.

    shard_map over the DP axes: each shard compresses (grads + error
    feedback), the psum crosses the network in fp16/int8/fp8, and the
    residual stays local for the next step.  For a p-bit wire this cuts the
    gradient collective bytes 32/p x at the cost of EF-bounded quantization
    error (unbiased over steps — tests/test_optim.py).

    grads must be replicated across the DP axes *within* each shard's view
    (i.e. per-shard local gradients); returns (mean_grads fp32, new_ef).
    """
    from jax.sharding import PartitionSpec as P

    if compressor.kind == "none":
        def mean_fn(g):
            return jax.tree.map(
                lambda x: jax.lax.pmean(x.astype(jnp.float32), axis_names), g)
        f = jax.shard_map(mean_fn, mesh=mesh,
                          in_specs=(jax.tree.map(lambda _: P(), grads),),
                          out_specs=jax.tree.map(lambda _: P(), grads),
                          check_vma=False)
        return f(grads), ef

    def local_fn(g, e):
        wire, e2 = compressor.compress(g, e)
        summed = compressor.psum_wire(wire, axis_names)
        return summed, e2

    specs_g = jax.tree.map(lambda _: P(), grads)
    specs_e = jax.tree.map(lambda _: P(), ef)
    f = jax.shard_map(local_fn, mesh=mesh, in_specs=(specs_g, specs_e),
                      out_specs=(specs_g, specs_e), check_vma=False)
    return f(grads, ef)
