"""Precision policies for RedMulE-JAX — the **per-operand** storage model.

The source paper's RedMulE computes IEEE binary16 (FP16) FMAs end to end;
its successor ("RedMule: A Mixed-Precision Matrix-Matrix Operation Engine",
arXiv:2301.03904) generalizes the same datapath to mixed FP8/FP16
operation: operands may be *stored* narrower than the datapath *computes*,
and the engine widens them on the way into the array.  This module models
exactly that split.  A :class:`Policy` names five dtype roles:

* **storage** — ``x_dtype`` (activations / left operand), ``w_dtype``
  (weights / right operand) and ``grad_dtype`` (backward cotangents):
  the dtype each operand occupies in HBM.  ``None`` means "same as
  ``compute_dtype``" (the uniform-precision policies below).  FP8 storage
  (``float8_e4m3fn`` / ``float8_e5m2``) travels with a **per-tensor
  scale**: the engine quantizes ``q = v / s`` with ``s = amax(v)``
  (unit-max — see :func:`quantize_fp8` for why full-fp8-range scaling
  would overflow the binary16 datapath) around each dispatch and
  multiplies the scale product back into the accumulator afterwards,
  while capable kernels upcast the FP8 tiles *on load* inside the
  K-loop — HBM traffic shrinks to the storage width, the datapath never
  sees FP8 arithmetic.
* **compute_dtype** — the dtype tiles are widened to before the MXU.
* **accum_dtype** — the on-array accumulator (the Z-buffer).
* **out_dtype** — the dtype results are stored back to HBM in.

Shipped policies:

* ``PAPER_FP16``       — faithful to the source paper: fp16 storage,
  compute, accumulation and outputs.
* ``TPU_FP16``         — fp16 storage/compute, fp32 accumulation (the
  TPU-native realization; DESIGN.md §2, §8.3).
* ``TPU_BF16``         — bf16 storage/compute, fp32 accumulation (the LM
  default).
* ``FP32``             — reference precision for oracles and tests.
* ``MIXED_FP8_E4M3``   — the mixed-precision RedMulE point: E4M3 weights
  and activations, E5M2 gradients, per-tensor scales, FP16 compute and
  FP16 (in-datapath) accumulation.
* ``MIXED_FP8_E5M2``   — the wide-range variant: E5M2 storage everywhere,
  FP16 compute, FP32 accumulation (TPU-native mixed-precision training).

Every dtype field is validated at construction: a typo'd dtype raises a
``ValueError`` naming the offending field and the known-policy registry
instead of surfacing later as a deep Pallas lowering error.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "Policy",
    "PAPER_FP16",
    "TPU_FP16",
    "TPU_BF16",
    "FP32",
    "MIXED_FP8_E4M3",
    "MIXED_FP8_E5M2",
    "FP8_FORMATS",
    "resolve",
    "known_policies",
    "is_fp8",
    "fp8_max",
    "quantize_fp8",
    "quantize_fp8_rows",
    "dequantize_fp8",
]

# The FP8 storage formats the engine understands (E4M3 for weights and
# activations — more mantissa; E5M2 for gradients — more range).
FP8_FORMATS = ("float8_e4m3fn", "float8_e5m2")


def is_fp8(dtype) -> bool:
    """True when ``dtype`` is one of the FP8 storage formats."""
    try:
        return jnp.dtype(dtype).name in FP8_FORMATS
    except TypeError:
        return False


def fp8_max(dtype) -> float:
    """Largest finite value of an FP8 format (448 for E4M3, 57344 for E5M2)."""
    return float(jnp.finfo(jnp.dtype(dtype)).max)


def _validate_dtype(owner: str, field: str, value, *,
                    optional: bool = False) -> None:
    """A dtype field must name a real floating dtype; fail loudly at
    construction (not as a deep Pallas lowering error) naming the field
    and the known-policy registry."""
    if value is None and optional:
        return
    try:
        dt = jnp.dtype(value)
        ok = jnp.issubdtype(dt, jnp.floating)
    except TypeError:
        ok = False
    if not ok:
        raise ValueError(
            f"{owner}.{field} = {value!r} is not a floating dtype; "
            f"known precision policies: {known_policies()}")


@dataclasses.dataclass(frozen=True)
class Policy:
    """A matmul precision policy with per-operand storage dtypes.

    Attributes:
      name: human-readable identifier.
      compute_dtype: dtype tiles are widened to before the MXU.
      accum_dtype: dtype of the on-array accumulator (the Z-buffer).
      output_dtype: dtype results are stored to HBM in. ``None`` means
        "same as compute_dtype".
      faithful_accum: when True, the accumulator is re-rounded to
        ``accum_dtype`` after every reduction block, emulating the paper's
        in-pipeline fp16 accumulation error model (rather than doing one
        final downcast from fp32).
      x_dtype / w_dtype / grad_dtype: HBM *storage* dtypes of the left
        operand, the right operand, and the backward cotangent (dZ).
        ``None`` means "same as compute_dtype".  FP8 storage dtypes make
        the policy *scaled*: the engine applies per-tensor scales around
        every dispatch (see the module docstring).
    """

    name: str
    compute_dtype: jnp.dtype
    accum_dtype: jnp.dtype
    output_dtype: Optional[jnp.dtype] = None
    faithful_accum: bool = False
    x_dtype: Optional[jnp.dtype] = None
    w_dtype: Optional[jnp.dtype] = None
    grad_dtype: Optional[jnp.dtype] = None

    def __post_init__(self):
        _validate_dtype("Policy", "compute_dtype", self.compute_dtype)
        _validate_dtype("Policy", "accum_dtype", self.accum_dtype)
        _validate_dtype("Policy", "output_dtype", self.output_dtype,
                        optional=True)
        for f in ("x_dtype", "w_dtype", "grad_dtype"):
            _validate_dtype("Policy", f, getattr(self, f), optional=True)

    @property
    def out_dtype(self) -> jnp.dtype:
        return self.output_dtype if self.output_dtype is not None else self.compute_dtype

    # -- per-operand storage resolution (None -> compute_dtype) -------- #
    @property
    def x_storage_dtype(self) -> jnp.dtype:
        return self.x_dtype if self.x_dtype is not None else self.compute_dtype

    @property
    def w_storage_dtype(self) -> jnp.dtype:
        return self.w_dtype if self.w_dtype is not None else self.compute_dtype

    @property
    def grad_storage_dtype(self) -> jnp.dtype:
        return (self.grad_dtype if self.grad_dtype is not None
                else self.compute_dtype)

    @property
    def mixed_storage(self) -> bool:
        """True when any operand is stored in a dtype other than
        ``compute_dtype`` (the engine's per-operand dispatch path)."""
        return any(getattr(self, f) is not None
                   for f in ("x_dtype", "w_dtype", "grad_dtype"))

    @property
    def scaled(self) -> bool:
        """True when any operand storage is FP8 — per-tensor scales are
        applied/undone by the engine around every dispatch."""
        return any(is_fp8(d) for d in (self.x_dtype, self.w_dtype,
                                       self.grad_dtype) if d is not None)


PAPER_FP16 = Policy(
    name="paper_fp16",
    compute_dtype=jnp.float16,
    accum_dtype=jnp.float16,
    output_dtype=jnp.float16,
    faithful_accum=True,
)

TPU_FP16 = Policy(
    name="tpu_fp16",
    compute_dtype=jnp.float16,
    accum_dtype=jnp.float32,
    output_dtype=jnp.float16,
)

TPU_BF16 = Policy(
    name="tpu_bf16",
    compute_dtype=jnp.bfloat16,
    accum_dtype=jnp.float32,
    output_dtype=jnp.bfloat16,
)

FP32 = Policy(
    name="fp32",
    compute_dtype=jnp.float32,
    accum_dtype=jnp.float32,
    output_dtype=jnp.float32,
)

# The mixed-precision RedMulE point (arXiv:2301.03904): FP8 storage with
# per-tensor scales, widened to the FP16 datapath on load, accumulated in
# the datapath precision (faithful to the engine's FMA feedback path).
MIXED_FP8_E4M3 = Policy(
    name="mixed_fp8_e4m3",
    compute_dtype=jnp.float16,
    accum_dtype=jnp.float16,
    output_dtype=jnp.float16,
    faithful_accum=True,
    x_dtype=jnp.float8_e4m3fn,
    w_dtype=jnp.float8_e4m3fn,
    grad_dtype=jnp.float8_e5m2,
)

# Wide-range FP8 everywhere, fp32 accumulation — the TPU-native mixed
# point for gradient-heavy workloads.
MIXED_FP8_E5M2 = Policy(
    name="mixed_fp8_e5m2",
    compute_dtype=jnp.float16,
    accum_dtype=jnp.float32,
    output_dtype=jnp.float16,
    x_dtype=jnp.float8_e5m2,
    w_dtype=jnp.float8_e5m2,
    grad_dtype=jnp.float8_e5m2,
)

_BY_NAME = {p.name: p for p in (PAPER_FP16, TPU_FP16, TPU_BF16, FP32,
                                MIXED_FP8_E4M3, MIXED_FP8_E5M2)}


def known_policies() -> Tuple[str, ...]:
    """Sorted names of the registered policies (for error messages)."""
    return tuple(sorted(_BY_NAME))


def resolve(policy) -> Policy:
    """Accept a Policy or its string name."""
    if isinstance(policy, Policy):
        return policy
    if policy is None:
        return TPU_BF16
    try:
        return _BY_NAME[str(policy)]
    except KeyError as e:
        raise ValueError(
            f"unknown precision policy {policy!r}; known: {sorted(_BY_NAME)}"
        ) from e


# --------------------------------------------------------------------- #
# Per-tensor FP8 quantization (the engine's around-dispatch scale model)
# --------------------------------------------------------------------- #
def quantize_fp8(v: jax.Array, dtype,
                 scale: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """Per-tensor amax quantization: ``q = v / s`` stored in ``dtype``.

    ``s = amax(|v|)`` (computed in fp32) unless an explicit ``scale`` is
    given (e.g. a delayed scale from :mod:`repro.optim.scale`) — the
    quantized values are normalized to ``[-1, 1]``, *not* stretched to
    the format's full range: this engine widens FP8 to a **binary16**
    datapath (the mixed-precision RedMulE), and full-range E4M3/E5M2
    values (448 / 57344) would overflow fp16 products and accumulators.
    Unit-max scaling keeps every product ≤ 1 and a K-long fp16
    accumulation safely below 65504; the format's constant relative
    precision (ε = 2⁻³ / 2⁻²) is unaffected by where the window sits.
    An all-zero or non-finite tensor gets ``s = 1`` so the quantized
    values stay well-defined.  Returns ``(q, s)`` with ``s`` an f32
    scalar; ``dequantize_fp8`` inverts it."""
    dt = jnp.dtype(dtype)
    if not is_fp8(dt):
        raise ValueError(
            f"quantize_fp8 target must be one of {FP8_FORMATS}, got "
            f"{dt.name!r}")
    vf = v.astype(jnp.float32)
    if scale is None:
        amax = jnp.max(jnp.abs(vf))
        scale = jnp.where((amax > 0) & jnp.isfinite(amax), amax, 1.0)
    scale = jnp.asarray(scale, jnp.float32)
    return (vf / scale).astype(dt), scale


def quantize_fp8_rows(v: jax.Array, dtype) -> Tuple[jax.Array, jax.Array]:
    """Per-row amax quantization: :func:`quantize_fp8`'s unit-max rule
    with one scale per row of the last axis.

    Returns ``(q, s)`` with ``s`` of shape ``v.shape[:-1]`` (f32): each row
    is ``q = v / amax(|row|)``, an all-zero or non-finite row gets ``s =
    1``.  A row quantized this way is never requantized: its scale is its
    own, so writing other rows leaves its bytes as they are."""
    vf = v.astype(jnp.float32)
    amax = jnp.max(jnp.abs(vf), axis=-1)
    s = jnp.where((amax > 0) & jnp.isfinite(amax), amax, 1.0)
    q, _ = quantize_fp8(vf, dtype, scale=s[..., None])
    return q, s


def dequantize_fp8(q: jax.Array, scale: jax.Array,
                   dtype=jnp.float32) -> jax.Array:
    """Invert :func:`quantize_fp8`: widen and multiply the scale back."""
    return (q.astype(jnp.float32) * jnp.asarray(scale, jnp.float32)).astype(dtype)
