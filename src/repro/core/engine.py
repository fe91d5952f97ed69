"""The RedMulE Engine — a first-class GEMM surface with pluggable backends.

The paper's thesis is that *one* parametric GEMM engine serves every DL
kernel — inference, training, attention, experts.  This module is that
engine as an API:

* :class:`GemmSpec`   — a frozen description of one contraction (einsum-style
  tag, M/N/K, batching/grouping, precision :class:`~repro.core.precision.Policy`,
  :class:`~repro.core.tiling.TileConfig`).
* :class:`Engine`     — resolves a spec to a backend and dispatches it.  The
  op family covers what the models need: :meth:`Engine.matmul`,
  :meth:`Engine.linear` (fused bias+activation epilogue),
  :meth:`Engine.grouped_matmul` (ragged per-expert GEMM for MoE) and
  :meth:`Engine.einsum2d` (two-operand contractions).
* a **backend registry** — :func:`register_backend` replaces the old
  hard-coded backend tuple; "pallas", "interpret" and "xla" are ordinary
  registered entries and third-party/GPU backends plug in at runtime without
  editing this module.  Each entry carries a set of **capability flags**:
  ``"fused_epilogue"`` means the backend applies bias+activation inside its
  kernel's store step (one HBM write per affine layer — see
  :meth:`Engine.linear`); ``"tiled"`` means it consumes ``spec.tile``.
* **instrumentation** — every dispatch emits a :class:`GemmEvent` (flops,
  bytes, the *resolved* tile, backend, policy) into the thread-local
  :func:`instrument` collector; :mod:`repro.roofline.analysis` and
  :mod:`repro.core.perf_model` consume these instead of re-deriving shapes
  by hand.

Backend resolution precedence: explicit ``backend=`` argument >
:func:`use_backend` context (thread-local) > ``REPRO_MATMUL_BACKEND`` env
var (validated at read time) > platform default ("pallas" on TPU, "xla"
elsewhere).

Tile resolution precedence (per dispatch): explicit ``tile=`` argument >
the :mod:`repro.core.autotune` cache (measured-or-modeled winners keyed on
the canonicalized spec, persisted via ``REPRO_AUTOTUNE_CACHE``) > the
:func:`repro.core.tiling.choose_tiles` heuristic (memoized).  The resolved
tile rides on the emitted :class:`GemmEvent`.

Events are emitted at *trace* time: under ``jax.jit`` a cached executable
re-runs without re-tracing, so wrap the tracing call (``.lower()``,
``jax.eval_shape`` or the first invocation) in :func:`instrument`.  Code
that traces a loop body once but executes it N times (``lax.scan`` layer
stacks, q-chunk loops, grad-accumulation) wraps the scan in
:func:`repeat` so each event carries the right multiplicity.

**The backward contract.**  Every op in the family carries a
``jax.custom_vjp``, so ``jax.grad`` through an Engine op re-enters the
Engine instead of falling back to XLA-derived ``dot_general`` transposes:

* the VJP rules dispatch dX = dZ·Wᵀ and dW = Xᵀ·dZ through the same
  backend registry, as **transpose-layout** specs (``spec.layout`` "nt" /
  "tn") — backends with the ``"layouts"`` capability ("pallas",
  "interpret", "xla") consume the operands in their forward storage with
  no materialized transpose (the Pallas kernels run the same
  X-stationary / store-once schedule with remapped BlockSpecs); for
  backends without it the engine pre-transposes and dispatches an "nn"
  spec;
* backward dispatches emit :class:`GemmEvent`\\ s tagged ``op="matmul_dx"``
  / ``"matmul_dw"`` (whatever the forward op), so instrumented training
  traces carry the full fwd+bwd GEMM workload — three tile-stamped events
  per affine layer;
* **grad dtypes**: residuals (X, W, and the pre-activation for ``linear``
  epilogues without an output-form derivative) are saved in the policy's
  *compute* dtype; backward GEMMs run under the same policy with their
  output held in the *accum* dtype until the final cast to the primal
  operand's dtype.  The bias gradient is the accum-dtype row reduction of
  the pre-activation cotangent;
* **epilogue derivatives** (``linear``): ``ds = dZ * act'(s)`` uses the
  derivative registry in :mod:`repro.core.epilogues`.  relu/tanh recover
  ``act'`` from the fused output (the forward stays fully fused);
  gelu/silu save the pre-activation, so their forward-for-grad applies
  the activation post-op (~2 ulp from the fused inference path, same
  bound as the documented fused-vs-unfused contract);
* **one-pass backward** (the ``"fused_bwd_epilogue"`` capability;
  "pallas"/"interpret", 2D weights): the dX and dW kernels apply ``act'``
  to the dZ tile *on load* — the saved residual rides as a derivative
  operand in the dispatch (``GemmSpec.grad_epilogue`` / ``grad_mode`` /
  ``fused_bwd``) — and the dW kernel accumulates ``db = Σ_rows ds`` into a
  second accum-dtype output in the same pass (``fused_bias_grad``), so the
  pre-activation cotangent ``ds`` never round-trips HBM.  Non-capable
  backends (and batched weights) keep the two-pass fallback, whose
  standalone multiply and separate bias-grad reduction are billed as
  ``linear_dact`` / ``linear_dbias`` *pass events* (zero flops, real
  bytes) so the byte accounting of both paths is comparable;
* **remat**: ``jax.checkpoint`` recompute traces are detected
  automatically (see ``_fwd_trace_kind``: the custom-VJP primal and fwd
  rules both trace under one call context exactly when a region re-traces
  for remat) — recompute events are tagged ``recompute=True``, inherit
  the multiplicity captured at the primal trace, and partial-eval
  artifact re-traces are suppressed, so remat train traces report true
  flops/bytes with no model-code changes;
* backward events inherit the :func:`repeat` multiplicity captured at
  *forward* trace time — a GEMM traced in a scanned layer body gets the
  same ``count`` on its dX/dW events even though JAX traces the backward
  scan outside the ``repeat`` context.

**The mixed-precision contract** (per-operand storage, PR 5).  A
:class:`~repro.core.precision.Policy` may store each operand narrower
than it computes (``x_dtype`` / ``w_dtype`` / ``grad_dtype``; the FP8
policies ``mixed_fp8_e4m3`` / ``mixed_fp8_e5m2``):

* the engine quantizes FP8 operands **per tensor** around every dispatch
  (``q = v / s``, ``s = amax`` — unit-max, so the binary16 datapath
  cannot overflow) and multiplies the scale product
  back into the accumulator afterwards; backends with the
  ``"operand_dtypes"`` capability receive the narrow arrays and upcast
  tiles to the compute dtype *on load* inside their kernels (no HBM-side
  cast pass), others receive the quantized values widened before
  dispatch — the quantization point is backend-invariant, so the same
  policy yields the same numerics on every backend;
* residuals are saved in the dispatch storage (FP8), so the backward
  GEMMs re-read them narrow; the cotangent quantizes to ``grad_dtype``
  (E5M2: range over precision) *after* the activation-derivative
  multiply, once, in the engine — scaled specs therefore always run the
  post-op epilogue and the two-pass backward (``fuse``/``fuse_bwd`` off),
  and the bias grad reduces from the wide cotangent (no FP8 error); the
  forced post-op forward pass is billed honestly as a ``*_postep`` pass
  event (the stored result's HBM round-trip — so FP8 traces compare
  like-for-like against fused FP16 ones);
* ``GemmSpec.x_dtype`` / ``w_dtype`` record what each slot actually
  carried, and the byte accounting prices each operand at its true
  element width — **bytes drop, flops don't** (the paper's successor
  engine's whole point);
* **FP8 tolerance rows** (extending the fused-vs-unfused table in
  :meth:`Engine.linear`): quantize→dequantize round-trips are bounded by
  the format's relative epsilon (E4M3: 2⁻³; E5M2: 2⁻²) for values within
  ~2⁻⁹ of the tensor amax; cross-backend grads under one FP8 policy
  agree to the *compute*-dtype tolerance (fp16 ~2e-2), because the FP8
  rounding itself is deterministic and shared.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import os
import threading
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import autotune
from repro.core import epilogues as epi
from repro.core import precision as prec
from repro.core import tiling

__all__ = [
    "GemmSpec",
    "GemmEvent",
    "Engine",
    "BackendSpec",
    "register_backend",
    "unregister_backend",
    "registered_backends",
    "get_backend",
    "backend_available",
    "backend_supports",
    "default_backend",
    "set_default_backend",
    "use_backend",
    "matmul",
    "linear",
    "grouped_matmul",
    "einsum2d",
    "attention",
    "linear_attention",
    "is_backward_op",
    "is_pass_op",
    "instrument",
    "repeat",
    "paused",
    "op_scope",
    "total_flops",
    "total_bytes",
    "summarize",
    "DEFAULT_ENGINE",
]

ENV_VAR = "REPRO_MATMUL_BACKEND"


# --------------------------------------------------------------------- #
# Spec / event
# --------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class GemmSpec:
    """One contraction, fully described.

    Attributes:
      op: op-family name ("matmul" | "linear" | "grouped_matmul" | "einsum2d").
      tag: einsum-style contraction tag (e.g. ``"mn,nk->mk"``).
      m, n, k: the 2D GEMM problem per batch element per group
        (Z[m,k] = X[m,n] @ W[n,k] — the paper's naming).
      batch: product of leading (vmapped/broadcast) dims.
      groups: expert-group count for grouped GEMMs (1 otherwise).
      policy: resolved precision policy.
      tile: the resolved tile config (explicit arg > autotune cache >
        ``choose_tiles`` heuristic; the Engine resolves it before emitting
        the event, so instrumentation always sees the real block geometry).
      epilogue: fused epilogue activation name for ``linear`` (or None).
      layout: operand storage of the logical contraction — "nn" (forward),
        "nt" (w stored transposed; the dX dispatch) or "tn" (x stored
        transposed; the dW dispatch).  m/n/k keep their *logical* meaning
        in every layout, so flops/bytes are layout-invariant.
      valid_rows: for ragged grouped GEMMs, the total valid rows of the
        ragged dimension summed over groups (``sum(min(group_sizes, dim))``)
        when statically known — replaces ``groups * <ragged dim>`` in the
        flops/bytes accounting so masked rows are not billed.  None means
        dense (or the sizes were traced and unknowable at trace time).
      ragged_dim: which logical dim ``valid_rows`` masks — "m" (forward and
        dX: ragged output rows), "n" (dW: ragged contraction rows) or "k"
        (an "nt" grouped forward: ragged output columns, i.e. the stored
        rows of the w operand).
      grad_epilogue: on a backward dispatch, the activation whose derivative
        feeds this GEMM (``ds = dZ * act'``); None on forward dispatches
        and epilogue-free backwards.
      grad_mode: how ``act'`` is recovered — "output" (from the fused
        forward output; relu/tanh) or "preact" (from the saved
        pre-activation; gelu/silu).
      fused_bwd: True when the backend applies ``act'`` to the dZ tile *on
        load* inside the kernel (the ``"fused_bwd_epilogue"`` capability) —
        the derivative operand is streamed alongside the GEMM operands and
        ``ds`` is never materialized in HBM.  False on the two-pass
        fallback, whose standalone multiply is billed by a separate
        ``*_dact`` pass event instead.
      fused_bias_grad: True when this (dW) dispatch also accumulates
        ``db = Σ_rows ds`` into a second accum-dtype output in the same
        pass (no separate ``*_dbias`` reduction event).
      x_dtype / w_dtype: per-operand *storage* dtype names the dispatch
        actually carries (None = the policy's compute dtype).  Under a
        mixed-storage policy on an ``"operand_dtypes"``-capable backend
        these are the narrow (FP8) names — the byte accounting prices
        each operand slot at its true element width.  On backward
        dispatches the slots swap roles (the dZ operand rides in the
        *grad* storage: the x slot on dX, the w slot on dW).
      scaled: True when per-tensor scales travel with this dispatch (FP8
        storage): the engine quantizes ``q = v / s`` before the GEMM and
        multiplies the scale product back into the accumulator after —
        scale scalars are metadata here, their bytes are negligible.
      io_bytes: exact HBM operand + result bytes of one execution, when
        the generic per-slot formula below cannot express them.  The
        attention sweeps set this: their operands are shared across many
        per-block GEMMs (Q is read once per Q block, not once per score
        GEMM; the linear-attention state never leaves VMEM until the final
        store), so the engine bills each sweep's true traffic here and
        :attr:`bytes` returns it verbatim.  None (all plain GEMMs) keeps
        the formula.
    """

    op: str
    tag: str
    m: int
    n: int
    k: int
    batch: int = 1
    groups: int = 1
    policy: prec.Policy = prec.TPU_BF16
    tile: Optional[tiling.TileConfig] = None
    epilogue: Optional[str] = None
    # the weight operand is shared across the batch (read once per group)
    w_shared: bool = False
    layout: str = "nn"
    valid_rows: Optional[int] = None
    ragged_dim: str = "m"
    grad_epilogue: Optional[str] = None
    grad_mode: Optional[str] = None
    fused_bwd: bool = False
    fused_bias_grad: bool = False
    x_dtype: Optional[str] = None
    w_dtype: Optional[str] = None
    scaled: bool = False
    io_bytes: Optional[int] = None

    def __post_init__(self):
        if self.layout not in ("nn", "nt", "tn"):
            raise ValueError(
                f"GemmSpec.layout = {self.layout!r}; known: ('nn', 'nt', 'tn')")
        if self.ragged_dim not in ("m", "n", "k"):
            raise ValueError(f"GemmSpec.ragged_dim = {self.ragged_dim!r}; "
                             f"known: ('m', 'n', 'k')")
        # a typo'd dtype fails here, naming the field, instead of deep in
        # Pallas lowering (one validator shared with Policy)
        for f in ("x_dtype", "w_dtype"):
            prec._validate_dtype("GemmSpec", f, getattr(self, f),
                                 optional=True)

    @property
    def flops(self) -> int:
        """MAC-derived flops of one execution (2 * B * G * M * N * K; for
        ragged grouped GEMMs ``valid_rows`` replaces ``G * <ragged dim>``).
        Pass events (``*_dact`` / ``*_dbias``) carry no MACs."""
        if is_pass_op(self.op):
            return 0
        if self.valid_rows is None:
            return 2 * self.batch * self.groups * self.m * self.n * self.k
        if self.ragged_dim == "m":
            return 2 * self.batch * self.valid_rows * self.n * self.k
        if self.ragged_dim == "k":
            return 2 * self.batch * self.m * self.n * self.valid_rows
        return 2 * self.batch * self.m * self.valid_rows * self.k

    @property
    def dense_flops(self) -> int:
        """Flops of the *dense* contraction this spec lowers to
        (``2 * B * G * M * N * K``), ignoring ragged ``valid_rows``
        billing.

        Ragged grouped GEMMs bill only their valid rows in :attr:`flops`,
        but the ``dot_general`` the XLA backend emits is dense — masking
        happens around it, not inside it.  ``dense_flops`` is therefore
        the quantity the static escape auditor
        (:mod:`repro.analysis.jaxpr_audit`) uses to reconcile engine
        dispatches against the equations found in a traced jaxpr.  Pass
        events (``*_dact`` / ``*_dbias`` / ``*_postep``) lower no
        contraction and report 0."""
        if is_pass_op(self.op):
            return 0
        return 2 * self.batch * self.groups * self.m * self.n * self.k

    @property
    def bytes(self) -> int:
        """HBM-side operand + result bytes of one execution.

        When ``w_shared`` the weight operand is read once per group, not
        once per batch element (weight GEMMs: one (N, K) matrix serves the
        whole batch).  Ragged grouped GEMMs (``valid_rows``) bill only the
        valid rows of the ragged operand(s) and — for ``ragged_dim`` "m"
        and "k" — of the output.

        Backward-epilogue traffic is billed where it actually flows:
        ``*_dact`` pass events (the two-pass fallback) pay the full
        ``ds = dZ ⊙ act'`` HBM round-trip (read dZ, read the saved
        activation residual, write ds) and ``*_dbias`` events pay the
        separate bias-grad reduction; fused dispatches instead add the
        streamed derivative operand (``fused_bwd``) and the db output row
        (``fused_bias_grad``) to the GEMM's own operand bytes — strictly
        less than the round-trip they replace.

        Per-operand storage (``x_dtype`` / ``w_dtype``) prices each
        operand slot at its **true element width**: an FP8-stored operand
        pays one byte per element while the output (and the streamed
        derivative residual) stay at the out/compute width — narrower
        storage drops bytes, never flops."""
        if self.io_bytes is not None:
            return self.io_bytes
        cb = jnp.dtype(self.policy.compute_dtype).itemsize
        ob = jnp.dtype(self.policy.out_dtype).itemsize
        ab = jnp.dtype(self.policy.accum_dtype).itemsize
        xb = jnp.dtype(self.x_dtype).itemsize if self.x_dtype else cb
        wb = jnp.dtype(self.w_dtype).itemsize if self.w_dtype else cb
        bg = self.batch * self.groups
        if self.op.endswith("_dact"):
            # standalone ds = dZ * act'(residual) over the (M, K) cotangent:
            # read dZ, read the residual, write ds
            return 3 * bg * self.m * self.k * cb
        if self.op.endswith("_dbias"):
            # separate bias-grad pass: re-read the cotangent, write the row
            return bg * self.m * self.k * cb + self.k * ab
        if self.op.endswith("_postep"):
            # the policy-forced post-op epilogue pass (scaled specs only):
            # the stored GEMM result round-trips HBM around the
            # scale-undo + bias/activation, plus the accum-dtype bias row
            return 2 * bg * self.m * self.k * ob + self.k * ab
        if self.valid_rows is None:
            x_elems = bg * self.m * self.n
            z_elems = bg * self.m * self.k
            w_elems = (self.groups if self.w_shared else bg) * self.n * self.k
        elif self.ragged_dim == "m":
            x_elems = self.batch * self.valid_rows * self.n
            z_elems = self.batch * self.valid_rows * self.k
            w_elems = (self.groups if self.w_shared else bg) * self.n * self.k
        elif self.ragged_dim == "k":  # ragged output columns (w's rows)
            x_elems = bg * self.m * self.n
            z_elems = self.batch * self.m * self.valid_rows
            w_elems = (1 if self.w_shared else self.batch) \
                * self.valid_rows * self.n
        else:  # ragged contraction rows (the dW dispatch)
            x_elems = self.batch * self.m * self.valid_rows
            z_elems = bg * self.m * self.k
            w_elems = (self.groups * self.n if self.w_shared
                       else self.batch * self.valid_rows) * self.k
        total = x_elems * xb + z_elems * ob + w_elems * wb
        if self.fused_bwd and self.grad_epilogue is not None:
            # the streamed derivative operand shadows the dZ operand: the
            # x slot on dX ("nt"), the w slot on dW ("tn"); the residual
            # rides in the compute dtype
            total += (x_elems if self.op.endswith("_dx") else w_elems) * cb
        if self.fused_bias_grad:
            total += self.k * ab   # the fused db output row
        return total


@dataclasses.dataclass(frozen=True)
class GemmEvent:
    """One engine dispatch, as observed by :func:`instrument`.

    ``count`` is the trace-context multiplicity (see :func:`repeat`):
    a GEMM traced inside a 28-layer ``lax.scan`` body appears once with
    ``count=28``.  ``recompute`` marks events emitted during a
    ``jax.checkpoint`` recompute trace — the GEMM re-executes during the
    backward pass (real flops/bytes at run time, but not new forward
    work); such events inherit the multiplicity captured at the *primal*
    forward trace.
    """

    spec: GemmSpec
    backend: str

    count: int = 1
    recompute: bool = False

    @property
    def flops(self) -> int:
        return self.spec.flops

    @property
    def bytes(self) -> int:
        return self.spec.bytes

    @property
    def total_flops(self) -> int:
        return self.spec.flops * self.count

    @property
    def total_bytes(self) -> int:
        return self.spec.bytes * self.count


def is_backward_op(op: str) -> bool:
    """True for op tags emitted by the Engine's VJP rules (dX / dW GEMMs
    and the ``*_dact`` / ``*_dbias`` epilogue pass events of the two-pass
    fallback).

    The single source of truth for the fwd/bwd event split —
    :mod:`repro.roofline.analysis` and :mod:`repro.core.perf_model` both
    defer here."""
    return op.endswith(("_dx", "_dw", "_dact", "_dbias"))


def is_pass_op(op: str) -> bool:
    """True for non-GEMM *pass* events: the standalone ``ds = dZ ⊙ act'``
    multiply (``*_dact``) and the separate bias-grad reduction
    (``*_dbias``) of the two-pass backward fallback, and the
    policy-forced post-op epilogue round-trip of scaled FP8 forwards
    (``*_postep`` — a forward event).  Pass events carry HBM bytes but
    zero MAC flops; cycle models skip them."""
    return op.endswith(("_dact", "_dbias", "_postep"))


def total_flops(events: Sequence[GemmEvent]) -> int:
    return sum(ev.total_flops for ev in events)


def total_bytes(events: Sequence[GemmEvent]) -> int:
    return sum(ev.total_bytes for ev in events)


def dispatch_footprint(events: Sequence[GemmEvent]) -> Dict[int, int]:
    """Map ``dense_flops -> total dispatch count`` over an event stream.

    The trace-capture hook for the static escape auditor: each non-pass
    engine dispatch lowers to exactly one ``dot_general`` on the XLA
    backend, costing :attr:`GemmSpec.dense_flops`, with trace multiplicity
    ``count``.  The auditor subtracts this footprint from the multiset of
    contractions found by walking the same trace's jaxpr; whatever remains
    escaped the Engine."""
    foot: Dict[int, int] = {}
    for ev in events:
        df = ev.spec.dense_flops
        if df <= 0:
            continue
        foot[df] = foot.get(df, 0) + ev.count
    return foot


def summarize(events: Sequence[GemmEvent]) -> Dict[str, Dict[str, float]]:
    """Per-op totals plus a grand total (for CLI printouts)."""
    out: Dict[str, Dict[str, float]] = {}
    for ev in events:
        d = out.setdefault(ev.spec.op, {"calls": 0, "flops": 0, "bytes": 0})
        d["calls"] += ev.count
        d["flops"] += ev.total_flops
        d["bytes"] += ev.total_bytes
    out["total"] = {
        "calls": sum(d["calls"] for d in out.values()),
        "flops": total_flops(events),
        "bytes": total_bytes(events),
    }
    return out


# --------------------------------------------------------------------- #
# Backend registry
# --------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class BackendSpec:
    """A registered backend: ``fn(x, w, *, spec) -> array``.

    ``fn`` receives operands already cast to ``spec.policy.compute_dtype``
    (or, with the ``"operand_dtypes"`` capability, to the per-operand
    storage dtypes named by ``spec.x_dtype``/``spec.w_dtype``) with
    ``x: (..., M, N)`` and ``w: (N, K)`` or broadcast-compatible
    ``(..., N, K)``; it returns ``(..., M, K)`` in any float dtype (the
    engine downcasts to ``spec.policy.out_dtype``).

    ``capabilities`` is a frozenset of opt-in flags:

    * ``"fused_epilogue"`` — ``fn`` additionally accepts
      ``fn(x, w, *, spec, bias=None, fuse_epilogue=False)``.  When the
      engine passes ``fuse_epilogue=True`` the backend must apply
      ``spec.epilogue`` (and ``bias``, an accum-dtype ``(K,)`` row when not
      None) to the accumulator *before* its single output store; the
      engine then skips its own post-op epilogue pass.
    * ``"tiled"`` — ``fn`` honors ``spec.tile`` as its block geometry (the
      engine resolves a tile for every dispatch regardless, for
      instrumentation; untiled backends simply ignore it).
    * ``"layouts"`` — ``fn`` honors ``spec.layout`` ("nn" | "nt" | "tn"):
      operands arrive in the storage the layout names (the Engine's
      backward dispatches pass W / X in their forward storage) and the
      backend contracts accordingly without materializing a transpose.
      Backends *without* this flag only ever see "nn" specs — the engine
      pre-transposes backward operands before dispatching to them.
    * ``"fused_bwd_epilogue"`` — ``fn`` additionally accepts
      ``fn(a, b, *, spec, deriv=None, bias_grad=False)`` on backward
      dispatches.  When ``spec.grad_epilogue`` is set, ``deriv`` is the
      activation-derivative operand (the fused forward output when
      ``spec.grad_mode == "output"``, else the saved pre-activation),
      stored exactly like the dZ operand; the backend must apply
      ``ds = dZ * act'(deriv)`` to the dZ tiles *on load*, in the accum
      dtype, so ``ds`` is never materialized in HBM.  With
      ``bias_grad=True`` (only on "tn" dW dispatches) ``fn`` returns
      ``(dW, db)`` where ``db`` is the accum-dtype ``(K,)`` row sum of
      the (derivative-adjusted) dZ rows, accumulated in the same pass.
      Backends without this flag get the engine's two-pass fallback (a
      standalone ``ds`` multiply + separate bias-grad reduction, billed
      as ``*_dact`` / ``*_dbias`` pass events).  Requires ``"layouts"``.
    * ``"operand_dtypes"`` — ``fn`` accepts operands in per-operand
      *storage* dtypes narrower than ``spec.policy.compute_dtype`` (FP8
      under the mixed-precision policies; ``spec.x_dtype`` /
      ``spec.w_dtype`` name what each slot carries) and upcasts them to
      the compute dtype **on load** inside its kernel — the result must
      equal dispatching the pre-upcast operands.  Backends without this
      flag only ever see compute-dtype operands: the engine widens the
      (already-quantized) values before dispatch, an HBM-side cast pass
      billed at the wide width.
    * ``"attention"`` — the backend implements the fused attention sweeps
      and ``attention_fn`` must be provided:
      ``attention_fn(kind, operands, **params)`` where ``kind`` is
      ``"attention"`` (operands ``(q, k, v)`` of shape ``(BH, S, D)`` /
      ``(BH_kv, T, D)``, params ``group / causal / scale / bq / bkv /
      t_valid / q_offset``, returns ``(BH, S, D)``) or
      ``"linear_attention"`` (operands ``(q, k, v, log_g)`` of shape
      ``(BH, S, dk)`` / ``(BH, S, dv)`` / ``(BH, S)``, param ``chunk``,
      returns ``(out (BH, S, dv), state (BH, dk, dv) fp32)``).  Operands
      arrive pre-cast and pre-padded to the block geometry; backends
      without this flag are served by the engine's reference composition
      of :func:`einsum2d` calls, so every backend answers attention.
    """

    name: str
    fn: Callable[..., jax.Array]
    available: Union[bool, Callable[[], bool]] = True
    description: str = ""
    capabilities: frozenset = frozenset()
    attention_fn: Optional[Callable[..., Any]] = None

    def is_available(self) -> bool:
        a = self.available
        return bool(a()) if callable(a) else bool(a)

    def supports(self, capability: str) -> bool:
        return capability in self.capabilities


_REGISTRY: Dict[str, BackendSpec] = {}


def register_backend(
    name: str,
    fn: Callable[..., jax.Array],
    *,
    available: Union[bool, Callable[[], bool]] = True,
    description: str = "",
    capabilities=(),
    attention_fn: Optional[Callable[..., Any]] = None,
) -> BackendSpec:
    """Register (or replace) a GEMM backend under ``name``.

    Third-party backends plug in here at runtime; no edits to core are
    needed for a new backend to be dispatchable by name through
    :func:`matmul` and friends.  ``capabilities`` declares the optional
    contracts the backend implements (see :class:`BackendSpec`); an empty
    set gets the baseline pure-GEMM treatment (the engine applies
    epilogues itself, post-op)."""
    if not name or not isinstance(name, str):
        raise ValueError(f"backend name must be a non-empty string, got {name!r}")
    caps = frozenset(capabilities)
    unknown = caps - {"fused_epilogue", "tiled", "layouts",
                      "fused_bwd_epilogue", "operand_dtypes", "attention"}
    if unknown:
        raise ValueError(f"unknown backend capabilities: {sorted(unknown)}")
    if "attention" in caps and attention_fn is None:
        raise ValueError(
            f"backend {name!r} declares the 'attention' capability but "
            "provides no attention_fn")
    spec = BackendSpec(name=name, fn=fn, available=available,
                       description=description, capabilities=caps,
                       attention_fn=attention_fn)
    _REGISTRY[name] = spec
    return spec


def unregister_backend(name: str) -> None:
    _REGISTRY.pop(name, None)


def registered_backends() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def get_backend(name: str) -> BackendSpec:
    try:
        return _REGISTRY[name]
    except KeyError as e:
        raise ValueError(
            f"unknown backend {name!r}; registered: {registered_backends()}"
        ) from e


def backend_available(name: str) -> bool:
    return get_backend(name).is_available()


def backend_supports(name: str, capability: str) -> bool:
    return get_backend(name).supports(capability)


# --------------------------------------------------------------------- #
# Thread-local state: backend override, instrumentation, repeat scopes
# --------------------------------------------------------------------- #
_state = threading.local()


def _thread_backend() -> Optional[str]:
    return getattr(_state, "backend", None)


def _collectors() -> List[List[GemmEvent]]:
    c = getattr(_state, "collectors", None)
    if c is None:
        c = _state.collectors = []
    return c


def _repeat_multiplier() -> int:
    stack = getattr(_state, "repeat", None)
    if not stack:
        return 1
    m = 1
    for n in stack:
        m *= n
    return m


def default_backend() -> str:
    """Thread-local context > env var (validated here) > platform default."""
    b = _thread_backend()
    if b is not None:
        return b
    b = os.environ.get(ENV_VAR)
    if b:
        if b not in _REGISTRY:
            raise ValueError(
                f"environment variable {ENV_VAR}={b!r} names an unknown "
                f"backend; registered backends: {registered_backends()}")
        return b
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def set_default_backend(backend: Optional[str]) -> None:
    if backend is not None:
        get_backend(backend)  # validate against the registry
    _state.backend = backend


@contextlib.contextmanager
def use_backend(backend: str):
    """Thread-locally pin the default backend within the context."""
    old = _thread_backend()
    set_default_backend(backend)
    try:
        yield
    finally:
        set_default_backend(old)


@contextlib.contextmanager
def instrument() -> Iterator[List[GemmEvent]]:
    """Collect every engine dispatch traced in this thread.

        with engine.instrument() as events:
            logits, _, _ = transformer.forward(params, cfg, batch)
        print(engine.summarize(events))

    Nested collectors each observe all events.  Events are emitted at trace
    time — wrap the *tracing* call (first invocation, ``.lower()`` or
    ``jax.eval_shape``), not a cached jit re-execution.  Entering the
    *outermost* collector also resets the per-call primal/recompute
    bookkeeping (``jax.checkpoint`` detection — see ``_fwd_trace_kind``),
    so each instrumented trace classifies forward re-traces afresh."""
    events: List[GemmEvent] = []
    stack = _collectors()
    if not stack:
        _state.fwd_seen = {}
    stack.append(events)
    try:
        yield events
    finally:
        # remove by identity: equal-but-distinct lists (e.g. two empty
        # nested collectors) must not be confused by list.remove()
        for i in range(len(stack) - 1, -1, -1):
            if stack[i] is events:
                del stack[i]
                break


@contextlib.contextmanager
def paused():
    """Suppress event emission within the context.

    For shape probes and oracle re-traces that would otherwise double-count
    dispatches inside an active :func:`instrument` collector."""
    prev = getattr(_state, "paused", False)
    _state.paused = True
    try:
        yield
    finally:
        _state.paused = prev


@contextlib.contextmanager
def repeat(n: int):
    """Mark a region whose traced dispatches execute ``n`` times.

    Wrap ``lax.scan``/``fori_loop`` calls whose body contains engine ops:
    the body is traced once but runs ``n`` times, so events inside get
    ``count`` multiplied by ``n``.  Nesting multiplies."""
    stack = getattr(_state, "repeat", None)
    if stack is None:
        stack = _state.repeat = []
    stack.append(int(n))
    try:
        yield
    finally:
        stack.pop()


@contextlib.contextmanager
def op_scope(label: str):
    """Tag every event traced in the context with ``label/`` op prefix.

    Serving (and any other subsystem) wraps its traces so the GEMM events
    it dispatches are attributable in a mixed stream: a decode step traced
    under ``op_scope("serve_decode")`` emits ``serve_decode/matmul``,
    ``serve_decode/grouped_matmul``, ... .  Prefixing preserves the op
    *suffix*, so :func:`is_backward_op` / :func:`is_pass_op` (and every
    fwd/bwd split built on them) classify scoped events unchanged.
    Nesting joins with "/" (outermost first)."""
    prev = getattr(_state, "op_scope", None)
    _state.op_scope = label if prev is None else f"{prev}/{label}"
    try:
        yield
    finally:
        _state.op_scope = prev


def _emit(spec: GemmSpec, backend: str,
          count: Optional[int] = None, recompute: bool = False) -> None:
    """Append one event to every active collector.

    ``count`` overrides the live :func:`repeat` multiplier — backward
    dispatches pass the multiplicity captured at *forward* trace time,
    because JAX traces the backward of a scanned body outside the
    ``repeat`` context that wrapped the scan."""
    stack = _collectors()
    if not stack or getattr(_state, "paused", False):
        return
    scope = getattr(_state, "op_scope", None)
    if scope is not None:
        spec = dataclasses.replace(spec, op=f"{scope}/{spec.op}")
    ev = GemmEvent(spec=spec, backend=backend,
                   count=_repeat_multiplier() if count is None else count,
                   recompute=recompute)
    for events in stack:
        events.append(ev)


# --------------------------------------------------------------------- #
# Built-in backends
# --------------------------------------------------------------------- #
def _xla_fn(xc: jax.Array, wc: jax.Array, *, spec: GemmSpec) -> jax.Array:
    """``lax.dot_general`` with the engine's accumulation policy.

    Honors ``spec.layout`` ("layouts" capability): the contraction axis of
    each operand moves with the storage, so transpose-layout backward
    dispatches lower to a single ``dot_general`` — XLA fuses the transposed
    access into the dot, no materialized transpose.  Honors per-operand
    storage dtypes ("operand_dtypes" capability): narrower (FP8) operands
    are widened right at the dot's input, a cast XLA fuses into the
    contraction — HBM-side the operand stays at storage width."""
    policy = spec.policy
    comp = jnp.dtype(policy.compute_dtype)
    if xc.dtype != comp:
        xc = xc.astype(comp)
    if wc.dtype != comp:
        wc = wc.astype(comp)
    # per-layout contraction axis, counted from the end of each operand
    x_coff = 2 if spec.layout == "tn" else 1   # x stored (N, M) under tn
    w_coff = 1 if spec.layout == "nt" else 2   # w stored (K, N) under nt
    if xc.ndim > 2 and wc.ndim == 2 and spec.layout != "tn":
        # weight GEMM: single dot over collapsed leading dims
        return jax.lax.dot_general(
            xc, wc,
            (((xc.ndim - 1,), (wc.ndim - w_coff,)), ((), ())),
            preferred_element_type=policy.accum_dtype,
        )
    x_batch = tuple(range(xc.ndim - 2)) if xc.ndim > 2 else ()
    w_batch = tuple(range(wc.ndim - 2)) if wc.ndim > 2 else ()
    if x_batch != w_batch or xc.shape[:-2] != wc.shape[:-2]:
        lead = np.broadcast_shapes(xc.shape[:-2], wc.shape[:-2])
        xc = jnp.broadcast_to(xc, (*lead, *xc.shape[-2:]))
        wc = jnp.broadcast_to(wc, (*lead, *wc.shape[-2:]))
        x_batch = w_batch = tuple(range(len(lead)))
    return jax.lax.dot_general(
        xc, wc,
        (((xc.ndim - x_coff,), (wc.ndim - w_coff,)), (x_batch, w_batch)),
        preferred_element_type=policy.accum_dtype,
    )


# Datapath dtypes Mosaic refuses on TPU v5e (jax 0.9.0, libtpu 0.0.34),
# found by compiling for a described v5e: an fp16 accumulator raises
# "NotImplementedError: float16", fp16 tiles fail with "Invalid vector
# type for load", and casts to fp16 fail to legalize
# tpu.pack_subelements.  FP8 storage widened to bf16 or fp32 compiles.
# Only v5e was tried; the refusal holds for every TPU kind.
_CHIP_REFUSED_DTYPES = frozenset({"float16"})


def _device_kind(operands) -> str:
    """The kind of device the operands are placed on: a concrete array's
    device, or the mesh a tracer's sharding names; else the default
    device when it is a TPU."""
    for a in operands:
        if isinstance(a, jax.Array) and not isinstance(a, jax.core.Tracer):
            return next(iter(a.devices())).device_kind
        dev = getattr(jax.typeof(a).sharding.mesh, "abstract_device", None)
        if dev is not None:
            return dev.device_kind
    dev = jax.devices()[0]
    return dev.device_kind if dev.platform == "tpu" else "unknown TPU"


def _require_chip_dtypes(what: str, dtypes, operands) -> None:
    """Refuse a compiled Pallas dispatch whose datapath Mosaic cannot
    lower, before Mosaic is reached; nothing falls back to another
    backend."""
    bad = sorted({jnp.dtype(d).name for d in dtypes} & _CHIP_REFUSED_DTYPES)
    if bad:
        kind = _device_kind(operands)
        raise ValueError(
            f"{what} cannot run on the 'pallas' backend on device kind "
            f"{kind!r}: Mosaic does not compile a {'/'.join(bad)} datapath "
            f"there; choose a bf16 or fp32 policy (tpu_bf16, fp32)")


def _pallas_fn(xc: jax.Array, wc: jax.Array, *, spec: GemmSpec,
               interpret: bool = False, bias: Optional[jax.Array] = None,
               fuse_epilogue: bool = False,
               deriv: Optional[jax.Array] = None,
               bias_grad: bool = False):
    """The Pallas RedMulE kernel (X-stationary, W-streamed, store-once Z).

    With ``fuse_epilogue=True`` the bias row and ``spec.epilogue`` are
    folded into the kernel's store-once step (the "fused_epilogue"
    capability contract) — on the 2D *and* the batched-grid kernel.
    ``spec.layout`` selects the transpose-layout kernel entry points
    (the "layouts" capability): backward operands stay in their forward
    storage, the BlockSpec walk changes instead.  ``deriv``/``bias_grad``
    implement the "fused_bwd_epilogue" contract on the 2D kernel: act' is
    applied to the dZ tiles on load and — for ``bias_grad`` — the bias
    grad accumulates as a second kernel output (see
    :mod:`repro.kernels.redmule_matmul`)."""
    from repro.kernels import ops  # local import: kernels depend on core

    policy, tile, layout = spec.policy, spec.tile, spec.layout
    if not interpret:
        _require_chip_dtypes(
            f"precision policy {policy.name!r}",
            (policy.compute_dtype, policy.accum_dtype, policy.out_dtype,
             xc.dtype, wc.dtype), (xc, wc))
    kw = dict(policy=policy, tile=tile, layout=layout, interpret=interpret,
              bias=bias if fuse_epilogue else None,
              epilogue=spec.epilogue if fuse_epilogue else None)
    fused_bwd = deriv is not None or bias_grad
    if fused_bwd:
        kw.update(deriv=deriv, grad_epilogue=spec.grad_epilogue,
                  grad_from_output=spec.grad_mode == "output",
                  bias_grad=bias_grad)
    if wc.ndim == 2 and (xc.ndim == 2 or layout != "tn"):
        # weight GEMM: collapse leading dims into rows (nn/nt store the
        # logical M in x's second-to-last dim, so the collapse is exact)
        lead = xc.shape[:-2]
        x2 = xc.reshape((-1, xc.shape[-1])) if lead else xc
        if deriv is not None and lead:
            kw["deriv"] = deriv.reshape((-1, deriv.shape[-1]))
        out = ops.redmule_matmul(x2, wc, **kw)
        z2, db = out if bias_grad else (out, None)
        m = xc.shape[-1] if layout == "tn" else xc.shape[-2]
        k = wc.shape[-2] if layout == "nt" else wc.shape[-1]
        z = z2.reshape((*lead, m, k))
        return (z, db) if bias_grad else z
    assert not fused_bwd, \
        "fused backward epilogues are a 2D-weight (w_shared) contract"
    lead = np.broadcast_shapes(xc.shape[:-2], wc.shape[:-2])
    xb = jnp.broadcast_to(xc, (*lead, *xc.shape[-2:])).reshape(
        (-1, *xc.shape[-2:]))
    wb = jnp.broadcast_to(wc, (*lead, *wc.shape[-2:])).reshape(
        (-1, *wc.shape[-2:]))
    z = ops.redmule_matmul_batched(xb, wb, **kw)
    m = xc.shape[-1] if layout == "tn" else xc.shape[-2]
    k = wc.shape[-2] if layout == "nt" else wc.shape[-1]
    return z.reshape((*lead, m, k))


def _interpret_fn(xc: jax.Array, wc: jax.Array, *, spec: GemmSpec,
                  bias: Optional[jax.Array] = None,
                  fuse_epilogue: bool = False,
                  deriv: Optional[jax.Array] = None,
                  bias_grad: bool = False):
    return _pallas_fn(xc, wc, spec=spec, interpret=True, bias=bias,
                      fuse_epilogue=fuse_epilogue, deriv=deriv,
                      bias_grad=bias_grad)


def _pallas_attention(kind: str, operands, *, interpret: bool = False,
                      **params):
    """The "attention" capability for the Pallas backends (see
    :class:`BackendSpec`): dispatch to the fused sweep kernels."""
    from repro.kernels import flash_attention, chunked_linear_attention

    if not interpret:
        _require_chip_dtypes(f"{kind} on {operands[0].dtype} operands",
                             (o.dtype for o in operands), operands)
    if kind == "attention":
        q, k, v = operands
        return flash_attention.flash_attention_pallas(
            q, k, v, interpret=interpret, **params)
    if kind == "linear_attention":
        q, k, v, log_g = operands
        return chunked_linear_attention.chunked_linear_attention_pallas(
            q, k, v, log_g, interpret=interpret, **params)
    raise ValueError(f"unknown attention kind {kind!r}")


def _interpret_attention(kind: str, operands, **params):
    return _pallas_attention(kind, operands, interpret=True, **params)


register_backend(
    "xla", _xla_fn,
    capabilities=("layouts", "operand_dtypes"),
    description="lax.dot_general with the engine's precision policy "
                "(the default off the TPU; XLA:CPU dry-runs; epilogues applied "
                "post-op by the engine; transpose layouts fold into the "
                "dot's dimension numbers; FP8 storage widens at the dot's "
                "input — the cast fuses into the contraction)")
register_backend(
    "pallas", _pallas_fn,
    available=lambda: jax.default_backend() == "tpu",
    capabilities=("fused_epilogue", "tiled", "layouts",
                  "fused_bwd_epilogue", "operand_dtypes", "attention"),
    attention_fn=_pallas_attention,
    description="TPU Pallas RedMulE kernel (double-buffered in-kernel "
                "K-loop, store-once Z with the bias+activation epilogue "
                "fused into the store; nt/tn entry points serve the "
                "backward pass without materialized transposes, with "
                "act' applied to dZ on load and the bias grad accumulated "
                "in the dW pass — ds never touches HBM; FP8 storage tiles "
                "DMA narrow and upcast on load inside the K-loop; fused "
                "flash / chunked-linear attention sweeps)")
register_backend(
    "interpret", _interpret_fn,
    capabilities=("fused_epilogue", "tiled", "layouts",
                  "fused_bwd_epilogue", "operand_dtypes", "attention"),
    attention_fn=_interpret_attention,
    description="the same Pallas kernel body in interpreter mode "
                "(CPU CI; bit-faithful to the kernel's schedule, fused "
                "forward and backward epilogues, transpose layouts, "
                "FP8 upcast-on-load and the attention sweeps included)")


# Fused epilogue registry — shared with the kernels (repro.core.epilogues)
# so the in-kernel and post-op paths can never drift apart.
_EPILOGUES: Dict[str, Callable[[jax.Array], jax.Array]] = epi.EPILOGUES


# --------------------------------------------------------------------- #
# Tile resolution (module-level so the VJP rules can resolve backward
# tiles without an Engine instance)
# --------------------------------------------------------------------- #
def _resolve_tile(
    tile: Optional[tiling.TileConfig],
    *,
    m: int,
    n: int,
    k: int,
    policy: prec.Policy,
    backend: str,
    epilogue: Optional[str] = None,
    layout: str = "nn",
    fused_bwd: bool = False,
    x_dtype: Optional[str] = None,
    w_dtype: Optional[str] = None,
) -> tiling.TileConfig:
    """Tile precedence: explicit arg > autotune cache > heuristic.

    ``fused_bwd`` keys fused-backward-epilogue dispatches separately: the
    streamed derivative operand changes the VMEM working set and the
    DMA-per-FLOP ratio, so their tuned tiles must not collide with plain
    transpose-layout GEMMs of the same shape.  ``x_dtype``/``w_dtype``
    (per-operand storage names) key — and size — mixed-precision
    dispatches: FP8 streams halve their VMEM tiles and DMA bytes."""
    if tile is not None:
        return tile
    t = autotune.cached_tile(m, n, k, policy=policy, backend=backend,
                             epilogue=epilogue, layout=layout,
                             fused_bwd=fused_bwd,
                             x_dtype=x_dtype, w_dtype=w_dtype)
    if t is not None:
        return t
    return tiling.choose_tiles(
        m, n, k, compute_dtype=policy.compute_dtype,
        accum_dtype=policy.accum_dtype, fused_bwd=fused_bwd,
        x_dtype=x_dtype, w_dtype=w_dtype)


# --------------------------------------------------------------------- #
# Per-operand storage: dispatch-dtype resolution and quantization
# --------------------------------------------------------------------- #
def _dispatch_storage(
    policy: prec.Policy, backend: str,
) -> Tuple[Optional[str], Optional[str], Optional[str]]:
    """``(x_store, w_store, grad_store)`` dtype names one dispatch to
    ``backend`` actually carries (None = the compute dtype).

    Mixed-storage policies hand narrow operands only to backends with the
    ``"operand_dtypes"`` capability (which upcast on load inside their
    kernels); other backends receive the quantized values widened to the
    compute dtype before dispatch — numerically identical, but an
    HBM-side cast pass billed at the wide width."""
    if not policy.mixed_storage:
        return None, None, None
    if not get_backend(backend).supports("operand_dtypes"):
        return None, None, None
    comp = jnp.dtype(policy.compute_dtype).name

    def nm(d):
        n = jnp.dtype(d).name
        return None if n == comp else n

    return (nm(policy.x_storage_dtype), nm(policy.w_storage_dtype),
            nm(policy.grad_storage_dtype))


def _prep_operand(v: jax.Array, storage_dtype, store_name: Optional[str],
                  policy: prec.Policy,
                  ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Cast (or per-tensor-quantize) one operand for dispatch.

    Returns ``(array, scale)``: FP8 storage quantizes ``q = v / s`` with
    ``s = amax`` (see :func:`repro.core.precision.quantize_fp8`)
    and returns the f32 scalar scale; everything else casts with
    ``scale=None``.  ``store_name`` is the dtype the dispatch carries
    (None -> compute): when the backend can't consume narrow storage the
    quantized values are widened back to the compute dtype — the
    quantization point (and therefore the numerics) is backend-invariant.
    """
    comp = jnp.dtype(policy.compute_dtype)
    sd = jnp.dtype(storage_dtype)
    if prec.is_fp8(sd):
        q, s = prec.quantize_fp8(v, sd)
        if store_name is None:
            q = q.astype(comp)
        return q, s
    q = v.astype(sd)
    if store_name is None and sd != comp:
        q = q.astype(comp)
    return q, None


def _scale_product(*scales: Optional[jax.Array]) -> Optional[jax.Array]:
    """Product of the non-None per-tensor scales (None when there are
    none — the uniform-precision fast path)."""
    out = None
    for s in scales:
        if s is not None:
            out = s if out is None else out * s
    return out


# --------------------------------------------------------------------- #
# Custom-VJP dispatch: forward AND backward GEMMs through the registry
# --------------------------------------------------------------------- #
@functools.lru_cache(maxsize=None)
def _grad_policy(policy: prec.Policy) -> prec.Policy:
    """The backward-dispatch policy: same datapath, output held in the
    accumulation dtype (the final cast to the primal operand dtype happens
    once, at the custom-VJP boundary)."""
    return dataclasses.replace(policy, name=policy.name + "+grad",
                               output_dtype=policy.accum_dtype)


@dataclasses.dataclass(frozen=True)
class _GradCtx:
    """Static context threaded through a custom-VJP op (hashable: rides as
    a ``nondiff_argnums`` argument).

    ``count`` is the :func:`repeat` multiplicity captured when the engine
    method traced the forward — backward emissions reuse it, because the
    backward of a scanned body is traced after the scan's ``repeat``
    context has exited."""

    spec: GemmSpec
    backend: str
    count: int
    x_dtype: str
    w_dtype: str
    b_dtype: Optional[str] = None
    fuse: bool = False          # linear: backend runs the fused-epilogue path
    fuse_bwd: bool = False      # linear: backend fuses act'/db into dX/dW
    store_g: Optional[str] = None  # grad (dZ) dispatch storage dtype name


def _make_ctx(spec: GemmSpec, backend: str, x, w, b=None,
              fuse: bool = False, fuse_bwd: bool = False) -> _GradCtx:
    _, _, store_g = _dispatch_storage(spec.policy, backend)
    return _GradCtx(
        spec=spec, backend=backend, count=_repeat_multiplier(),
        x_dtype=jnp.dtype(x.dtype).name, w_dtype=jnp.dtype(w.dtype).name,
        b_dtype=None if b is None else jnp.dtype(b.dtype).name,
        fuse=fuse, fuse_bwd=fuse_bwd, store_g=store_g)


def _fwd_trace_kind(ctx: _GradCtx) -> Optional[str]:
    """Classify one forward trace of an engine call (keyed on the call's
    :class:`_GradCtx` identity, which both the custom-VJP primal and its
    fwd rule share).

    JAX traces each engine call's forward exactly once in an ordinary
    program — the primal fun *or* the fwd rule, never both.  Under
    ``jax.checkpoint`` the region is re-traced to stage out the backward
    recompute, so the same ctx sees a **second** forward trace: that one
    is the recompute (it executes during the backward pass at run time)
    and its events are tagged ``recompute=True`` with the multiplicity
    captured at the primal trace.  Any *further* traces of the same ctx
    are partial-eval artifacts (e.g. a scanned remat body re-traced while
    splitting the scan) that never execute — their events are suppressed,
    so a remat train trace reports true flops/bytes.  (Known limitation:
    nested checkpoint regions recompute more than once at run time but are
    still reported once.)

    Returns "primal", "recompute", or None (suppress).  Bookkeeping lives
    per-thread and resets when the outermost :func:`instrument` collector
    is entered; with no active collector nothing is observed and nothing
    is tracked."""
    if not _collectors() or getattr(_state, "paused", False):
        return "primal"
    table = getattr(_state, "fwd_seen", None)
    if table is None:
        table = _state.fwd_seen = {}
    entry = table.get(id(ctx))
    if entry is None:
        table[id(ctx)] = [ctx, 1]   # hold ctx: no id reuse while tracked
        return "primal"
    entry[1] += 1
    return "recompute" if entry[1] == 2 else None


def _emit_fwd(ctx: _GradCtx, spec: Optional[GemmSpec] = None,
              extra_specs: Sequence[GemmSpec] = ()) -> None:
    """Emit one *forward* event for ``ctx``, with remat-recompute
    classification (see :func:`_fwd_trace_kind`).

    ``extra_specs`` ride along with the *same* classification and
    count — companion pass events (the scaled post-op ``*_postep``) must
    be deduplicated, multiplied and recompute-tagged exactly like the
    GEMM event they accompany, and ``_fwd_trace_kind`` is call-counted
    per ctx, so they cannot classify separately."""
    kind = _fwd_trace_kind(ctx)
    if kind == "primal":
        _emit(spec or ctx.spec, ctx.backend)
        for s in extra_specs:
            _emit(s, ctx.backend)
    elif kind == "recompute":
        _emit(spec or ctx.spec, ctx.backend, count=ctx.count,
              recompute=True)
        for s in extra_specs:
            _emit(s, ctx.backend, count=ctx.count, recompute=True)


def _dispatch(ctx: _GradCtx, xc: jax.Array, wc: jax.Array,
              spec: Optional[GemmSpec] = None,
              extra_specs: Sequence[GemmSpec] = ()) -> jax.Array:
    """Emit + run one forward pure-GEMM dispatch on compute-dtype operands;
    returns the backend-native result (xla: accum dtype; pallas: stored
    dtype)."""
    spec = spec or ctx.spec
    _emit_fwd(ctx, spec, extra_specs)
    return get_backend(ctx.backend).fn(xc, wc, spec=spec)


def _static_valid_rows(group_sizes, m: int) -> Optional[int]:
    """``sum(clip(group_sizes, 0, m))`` when concrete at trace time, else
    None (a traced ragged spec falls back to the dense count)."""
    if group_sizes is None:
        return None
    try:
        sizes = np.asarray(group_sizes)
    except Exception:
        return None
    return int(np.clip(sizes, 0, m).sum())


def _unbroadcast(g: jax.Array, shape: Tuple[int, ...]) -> jax.Array:
    """Sum a gradient down to the (possibly broadcast) primal shape."""
    extra = g.ndim - len(shape)
    if extra:
        g = g.sum(axis=tuple(range(extra)))
    axes = tuple(i for i, (gs, ss) in enumerate(zip(g.shape, shape))
                 if ss == 1 and gs != 1)
    if axes:
        g = g.sum(axis=axes, keepdims=True)
    return g


def _grad_dispatch(spec: GemmSpec, backend: str, a: jax.Array, b: jax.Array,
                   count: int, *, deriv: Optional[jax.Array] = None,
                   want_db: bool = False,
                   ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """One backward GEMM through the registry; returns ``(grad, db)``.

    ``spec`` carries a transpose layout; backends without the "layouts"
    capability get pre-transposed operands and an equivalent "nn" spec
    (same logical m/n/k, same event accounting).  ``deriv``/``want_db``
    run the "fused_bwd_epilogue" contract (only ever passed to capable
    backends): act' applied to the dZ tiles on load, and — for
    ``want_db`` — the bias grad accumulated in the same pass (``db`` is
    None otherwise)."""
    if spec.layout != "nn" and not get_backend(backend).supports("layouts"):
        if spec.layout == "nt":
            b = jnp.swapaxes(b, -1, -2)
        else:
            a = jnp.swapaxes(a, -1, -2)
        spec = dataclasses.replace(spec, layout="nn")
    _emit(spec, backend, count=count)
    fn = get_backend(backend).fn
    if spec.fused_bwd or want_db:
        out = fn(a, b, spec=spec, deriv=deriv, bias_grad=want_db)
        db = None
        if want_db:
            out, db = out
        return out.astype(spec.policy.out_dtype), db
    out = fn(a, b, spec=spec)
    return out.astype(spec.policy.out_dtype), None  # grad policy: accum


def _bwd_gemms(ctx: _GradCtx, xc: jax.Array, wc: jax.Array,
               dzc: jax.Array, *, deriv: Optional[jax.Array] = None,
               grad_mode: Optional[str] = None, want_db: bool = False,
               ) -> Tuple[jax.Array, jax.Array, Optional[jax.Array]]:
    """dX = dZ·Wᵀ ("nt") and dW = Xᵀ·dZ ("tn"), both Engine dispatches;
    returns ``(dx, dw, db)``.

    ``dzc`` is the cotangent in the compute dtype — the *pre-activation*
    cotangent on the two-pass path, the raw output cotangent on the fused
    path (``deriv`` set: the backend kernels apply ``act'(deriv)`` to the
    dZ tiles on load, so ds is never materialized).  ``want_db`` makes the
    dW dispatch accumulate the accum-dtype bias grad in the same pass.
    The returned grads are in the *accum* dtype (the caller casts to the
    primal dtypes)."""
    spec = ctx.spec
    gpol = _grad_policy(spec.policy)
    bk = ctx.backend

    if spec.valid_rows == 0:
        # degenerate ragged backward (every group empty): the masked
        # cotangent is identically zero, so skip the backend dispatches
        # (and their events) entirely — the forward's mirror short-circuit
        dx = jnp.zeros(xc.shape, gpol.out_dtype)
        dw = jnp.zeros(wc.shape, gpol.out_dtype)
        return dx, dw, None

    act = spec.epilogue if deriv is not None else None

    # backward per-slot storage: dZ rides in the grad storage (the x slot
    # on dX "nt", the w slot on dW "tn"); the saved residuals keep the
    # forward dispatch's storage (spec.x_dtype / spec.w_dtype)
    g_store = ctx.store_g

    if wc.ndim == 2:
        # weight GEMM — dW collapses all leading dims into one fat
        # contraction (the X-stationary schedule reads X in its forward
        # storage: no materialized transpose)
        dx_spec = GemmSpec(
            op="matmul_dx", tag="mk,nk->mn", layout="nt",
            m=spec.m, n=spec.k, k=spec.n, batch=spec.batch,
            policy=gpol, w_shared=True,
            valid_rows=spec.valid_rows, ragged_dim="m",
            grad_epilogue=act, grad_mode=grad_mode,
            fused_bwd=deriv is not None,
            x_dtype=g_store, w_dtype=spec.w_dtype, scaled=spec.scaled,
            tile=_resolve_tile(None, m=spec.m, n=spec.k, k=spec.n,
                               policy=gpol, backend=bk, layout="nt",
                               fused_bwd=deriv is not None,
                               x_dtype=g_store, w_dtype=spec.w_dtype),
        )
        dx, _ = _grad_dispatch(dx_spec, bk, dzc, wc, ctx.count, deriv=deriv)

        x2 = xc.reshape((-1, xc.shape[-1]))
        dz2 = dzc.reshape((-1, dzc.shape[-1]))
        d2 = None if deriv is None else deriv.reshape((-1, deriv.shape[-1]))
        rows = x2.shape[0]                      # batch * M
        dw_spec = GemmSpec(
            op="matmul_dw", tag="mn,mk->nk", layout="tn",
            m=spec.n, n=rows, k=spec.k, batch=1,
            policy=gpol, w_shared=False,
            grad_epilogue=act, grad_mode=grad_mode,
            fused_bwd=deriv is not None, fused_bias_grad=want_db,
            x_dtype=spec.x_dtype, w_dtype=g_store, scaled=spec.scaled,
            tile=_resolve_tile(None, m=spec.n, n=rows, k=spec.k,
                               policy=gpol, backend=bk, layout="tn",
                               fused_bwd=deriv is not None or want_db,
                               x_dtype=spec.x_dtype, w_dtype=g_store),
        )
        dw, db = _grad_dispatch(dw_spec, bk, x2, dz2, ctx.count,
                                deriv=d2, want_db=want_db)
        return dx, dw, db

    # batched / grouped GEMM: both grads stay batched; broadcast leading
    # dims are summed back down to the primal shapes afterwards.  (The
    # fused backward epilogue is a 2D-weight contract — callers fall back
    # to the two-pass path here.)
    assert deriv is None and not want_db
    dx_spec = GemmSpec(
        op="matmul_dx", tag="bmk,bnk->bmn", layout="nt",
        m=spec.m, n=spec.k, k=spec.n, batch=spec.batch, groups=spec.groups,
        policy=gpol, w_shared=spec.w_shared,
        valid_rows=spec.valid_rows, ragged_dim="m",
        x_dtype=g_store, w_dtype=spec.w_dtype, scaled=spec.scaled,
        tile=_resolve_tile(None, m=spec.m, n=spec.k, k=spec.n,
                           policy=gpol, backend=bk, layout="nt",
                           x_dtype=g_store, w_dtype=spec.w_dtype),
    )
    dx, _ = _grad_dispatch(dx_spec, bk, dzc, wc, ctx.count)
    dx = _unbroadcast(dx, xc.shape)

    dw_spec = GemmSpec(
        op="matmul_dw", tag="bmn,bmk->bnk", layout="tn",
        m=spec.n, n=spec.m, k=spec.k, batch=spec.batch, groups=spec.groups,
        policy=gpol, w_shared=False,
        valid_rows=spec.valid_rows,
        ragged_dim="n" if spec.valid_rows is not None else "m",
        x_dtype=spec.x_dtype, w_dtype=g_store, scaled=spec.scaled,
        tile=_resolve_tile(None, m=spec.n, n=spec.m, k=spec.k,
                           policy=gpol, backend=bk, layout="tn",
                           x_dtype=spec.x_dtype, w_dtype=g_store),
    )
    dw, _ = _grad_dispatch(dw_spec, bk, xc, dzc, ctx.count)
    dw = _unbroadcast(dw, wc.shape)
    return dx, dw, None


def _prep_xw(ctx: _GradCtx, x: jax.Array, w: jax.Array):
    """Cast/quantize both GEMM operands per the spec's per-operand storage;
    returns ``(xd, wd, sx, sw)`` (scales None on uniform policies)."""
    pol = ctx.spec.policy
    xd, sx = _prep_operand(x, pol.x_storage_dtype, ctx.spec.x_dtype, pol)
    wd, sw = _prep_operand(w, pol.w_storage_dtype, ctx.spec.w_dtype, pol)
    return xd, wd, sx, sw


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _gemm_call(ctx: _GradCtx, x: jax.Array, w: jax.Array) -> jax.Array:
    """Pure-GEMM op with a custom VJP (matmul / grouped_matmul / einsum2d
    inner dispatch / epilogue-free linear)."""
    pol = ctx.spec.policy
    xd, wd, sx, sw = _prep_xw(ctx, x, w)
    z = _dispatch(ctx, xd, wd)
    sp = _scale_product(sx, sw)
    if sp is not None:
        z = z.astype(pol.accum_dtype) * sp
    return z.astype(pol.out_dtype)


def _gemm_fwd(ctx: _GradCtx, x: jax.Array, w: jax.Array):
    pol = ctx.spec.policy
    xd, wd, sx, sw = _prep_xw(ctx, x, w)
    z = _dispatch(ctx, xd, wd)
    sp = _scale_product(sx, sw)
    if sp is not None:
        z = z.astype(pol.accum_dtype) * sp
    # residuals stay in the *dispatch* storage (FP8 on scaled policies —
    # the backward GEMMs re-read them narrow), scales ride alongside
    return z.astype(pol.out_dtype), (xd, wd, sx, sw)


def _quantized_bwd(ctx: _GradCtx, xd: jax.Array, wd: jax.Array,
                   sx: Optional[jax.Array], sw: Optional[jax.Array],
                   dz_wide: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Shared two-pass backward tail: quantize/cast the cotangent to the
    grad storage, run both backward GEMMs, undo the per-tensor scales.

    The scale algebra lives in exactly one place: dX = dZ·Wᵀ undoes the
    dZ and W scales, dW = Xᵀ·dZ undoes the X and dZ scales.  Returns
    ``(dx, dw)`` in the accum dtype (scale products are None — and the
    multiplies skipped — on uniform policies)."""
    pol = ctx.spec.policy
    dzd, sdz = _prep_operand(dz_wide, pol.grad_storage_dtype, ctx.store_g,
                             pol)
    dx, dw, _ = _bwd_gemms(ctx, xd, wd, dzd)
    spx = _scale_product(sdz, sw)
    spw = _scale_product(sx, sdz)
    if spx is not None:
        dx = dx * spx
    if spw is not None:
        dw = dw * spw
    return dx, dw


def _gemm_bwd(ctx: _GradCtx, res, dz: jax.Array):
    xd, wd, sx, sw = res
    nt = ctx.spec.layout == "nt"
    if ctx.spec.ragged_dim == "k":
        # a grouped "nt" forward (Z = X·Wᵀ, ragged output columns): the
        # "nn" rules on the stored w's transpose, billed dense
        ctx = dataclasses.replace(ctx, spec=dataclasses.replace(
            ctx.spec, layout="nn", valid_rows=None, ragged_dim="m"))
    if nt:
        wd = jnp.swapaxes(wd, -1, -2)
    dx, dw = _quantized_bwd(ctx, xd, wd, sx, sw, dz)
    if nt:
        dw = jnp.swapaxes(dw, -1, -2)
    return dx.astype(ctx.x_dtype), dw.astype(ctx.w_dtype)


_gemm_call.defvjp(_gemm_fwd, _gemm_bwd)


def _linear_primal_prepped(ctx: _GradCtx, xd: jax.Array, wd: jax.Array,
                           sp: Optional[jax.Array],
                           b: Optional[jax.Array]) -> jax.Array:
    """Inference-path linear on already-prepped operands: fused epilogue
    on capable backends, post-op otherwise (exactly the PR-2 contract).
    ``sp`` is the per-tensor scale product to undo (None on uniform
    policies); scaled dispatches always run post-op — the scale must be
    multiplied back into the accumulator *before* the bias/activation, so
    :meth:`Engine.linear` never sets ``fuse`` for them."""
    spec, bk = ctx.spec, ctx.backend
    pol = spec.policy
    has_epilogue = b is not None or spec.epilogue is not None
    if has_epilogue and ctx.fuse:
        bc = None if b is None else b.astype(pol.accum_dtype)
        _emit_fwd(ctx)
        z = get_backend(bk).fn(xd, wd, spec=spec, bias=bc,
                               fuse_epilogue=True)
        return z.astype(pol.out_dtype)
    # scaled specs *force* the post-op pass on every backend (the
    # scale-undo must precede the bias/activation), so the engine bills
    # its HBM round-trip as a companion pass event — unlike the
    # uniform-policy post-op fallback, which is a backend choice and
    # keeps the PR-2 unbilled convention.  It rides through _dispatch so
    # remat recompute traces classify it exactly like the GEMM event.
    extra = ((dataclasses.replace(spec, op=spec.op + "_postep", tile=None),)
             if has_epilogue and spec.scaled else ())
    z = _dispatch(ctx, xd, wd, extra_specs=extra)
    if sp is not None:
        z = z.astype(pol.accum_dtype) * sp
    if has_epilogue:
        za = z.astype(pol.accum_dtype)
        if b is not None:
            za = za + b.astype(pol.accum_dtype)
        za = epi.apply_epilogue(spec.epilogue, za)
        z = za
    return z.astype(pol.out_dtype)


def _linear_primal(ctx: _GradCtx, x: jax.Array, w: jax.Array,
                   b: Optional[jax.Array]) -> jax.Array:
    xd, wd, sx, sw = _prep_xw(ctx, x, w)
    return _linear_primal_prepped(ctx, xd, wd, _scale_product(sx, sw), b)


def _linear_fwd_core(ctx: _GradCtx, x: jax.Array, w: jax.Array,
                     b: Optional[jax.Array]):
    """Forward-for-grad: decide what to save for the epilogue derivative.

    * no activation — fused/post-op forward unchanged; residual aux=None;
    * activation with an output-form derivative (relu/tanh) — fully fused
      forward unchanged; save the output z;
    * otherwise (gelu/silu) — dispatch with the bias fused but the
      activation post-op, save the pre-activation s (compute dtype).  The
      value differs from the fused inference path by the documented ~2 ulp
      fused-vs-post-op bound.

    Residuals are saved in the *dispatch* storage (FP8 on scaled
    policies, compute dtype otherwise) with the per-tensor scales
    alongside; the epilogue aux (fused output or pre-activation) always
    rides in the out/compute dtype."""
    spec, bk = ctx.spec, ctx.backend
    pol = spec.policy
    act = spec.epilogue
    xd, wd, sx, sw = _prep_xw(ctx, x, w)
    sp = _scale_product(sx, sw)
    if act is None:
        z = _linear_primal_prepped(ctx, xd, wd, sp, b)
        return z, (xd, wd, None, sx, sw)
    grad = epi.epilogue_grad(act)
    if grad.deriv_from_output is not None:
        z = _linear_primal_prepped(ctx, xd, wd, sp, b)
        return z, (xd, wd, z, sx, sw)
    # pre-activation needed: bias-fused (or post-op) GEMM, activation after
    if ctx.fuse:
        bc = None if b is None else b.astype(pol.accum_dtype)
        _emit_fwd(ctx)
        s = get_backend(bk).fn(
            xd, wd, spec=dataclasses.replace(spec, epilogue=None),
            bias=bc, fuse_epilogue=True)
        sa = s.astype(pol.accum_dtype)
    else:
        # the policy-forced post-op pass bills like in
        # _linear_primal_prepped, classified with its GEMM event
        extra = ((dataclasses.replace(spec, op=spec.op + "_postep",
                                      tile=None),)
                 if spec.scaled else ())
        s = _dispatch(ctx, xd, wd, extra_specs=extra)
        sa = s.astype(pol.accum_dtype)
        if sp is not None:
            sa = sa * sp
        if b is not None:
            sa = sa + b.astype(pol.accum_dtype)
    z = epi.apply_epilogue(act, sa).astype(pol.out_dtype)
    return z, (xd, wd, sa.astype(pol.compute_dtype), sx, sw)


def _linear_bwd_core(ctx: _GradCtx, res, dz: jax.Array):
    """Shared linear backward: activation derivative, bias-grad reduction,
    then the two backward GEMMs.

    On backends with the ``"fused_bwd_epilogue"`` capability (2D weights)
    this is **one pass**: the raw output cotangent goes straight into the
    backward GEMMs, which apply ``act'`` to the dZ tiles on load from the
    saved residual and accumulate the bias grad inside the dW kernel — the
    pre-activation cotangent ``ds`` is never materialized in HBM.  Other
    backends (and batched weights) run the two-pass fallback: a standalone
    ``ds = dZ ⊙ act'`` multiply (billed as a ``*_dact`` pass event) and a
    separate accum-dtype bias-grad reduction (a ``*_dbias`` event).

    **Scaled (FP8) policies always take the two-pass path** — the engine
    quantizes the *post-derivative* cotangent ``ds`` to the grad storage
    once, in one place, so the quantization point (and the grads) are
    identical on every backend; the bias grad reduces from the wide
    ``ds`` before quantization, so it carries no FP8 error."""
    xd, wd, aux, sx, sw = res
    spec = ctx.spec
    pol = spec.policy
    act = spec.epilogue

    if ctx.fuse_bwd and (act is not None or ctx.b_dtype is not None):
        deriv = grad_mode = None
        if act is not None:
            grad = epi.epilogue_grad(act)
            grad_mode = ("output" if grad.deriv_from_output is not None
                         else "preact")
            deriv = aux.astype(pol.compute_dtype)
        want_db = ctx.b_dtype is not None
        dx, dw, db = _bwd_gemms(
            ctx, xd, wd, dz.astype(pol.compute_dtype),
            deriv=deriv, grad_mode=grad_mode, want_db=want_db)
        if db is not None:
            db = db.astype(ctx.b_dtype)
        return dx.astype(ctx.x_dtype), dw.astype(ctx.w_dtype), db

    dza = dz.astype(pol.accum_dtype)
    if act is not None:
        grad = epi.epilogue_grad(act)
        if grad.deriv_from_output is not None:
            dza = dza * grad.deriv_from_output(aux.astype(pol.accum_dtype))
        else:
            dza = dza * grad.deriv(aux.astype(pol.accum_dtype))
        # the standalone multiply materializes ds: bill its HBM round-trip
        _emit(dataclasses.replace(spec, op=spec.op + "_dact", tile=None),
              ctx.backend, count=ctx.count)
    db = None
    if ctx.b_dtype is not None:
        # bias grad: accum-dtype reduction over every row of the cotangent
        db = dza.sum(axis=tuple(range(dza.ndim - 1))).astype(ctx.b_dtype)
        _emit(dataclasses.replace(spec, op=spec.op + "_dbias", tile=None),
              ctx.backend, count=ctx.count)
    dx, dw = _quantized_bwd(ctx, xd, wd, sx, sw, dza)
    return dx.astype(ctx.x_dtype), dw.astype(ctx.w_dtype), db


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _linear_call(ctx: _GradCtx, x: jax.Array, w: jax.Array,
                 b: jax.Array) -> jax.Array:
    return _linear_primal(ctx, x, w, b)


def _linear_call_fwd(ctx, x, w, b):
    return _linear_fwd_core(ctx, x, w, b)


def _linear_call_bwd(ctx, res, dz):
    dx, dw, db = _linear_bwd_core(ctx, res, dz)
    return dx, dw, db


_linear_call.defvjp(_linear_call_fwd, _linear_call_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _linear_call_nobias(ctx: _GradCtx, x: jax.Array,
                        w: jax.Array) -> jax.Array:
    return _linear_primal(ctx, x, w, None)


def _linear_nobias_fwd(ctx, x, w):
    return _linear_fwd_core(ctx, x, w, None)


def _linear_nobias_bwd(ctx, res, dz):
    dx, dw, _ = _linear_bwd_core(ctx, res, dz)
    return dx, dw


_linear_call_nobias.defvjp(_linear_nobias_fwd, _linear_nobias_bwd)


# --------------------------------------------------------------------- #
# Attention ops ("attention" capability)
# --------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class _AttnCtx:
    """Static context of one attention dispatch (the custom-VJP
    nondiff argument).  Duck-types :class:`_GradCtx` for
    :func:`_emit_fwd` — ``spec`` / ``backend`` / ``count`` carry the
    same meaning; ``extra`` holds the sweep's companion GEMM specs
    (PV, inter, state-update), emitted with identical classification."""

    kind: str
    spec: GemmSpec
    backend: str
    count: int
    extra: Tuple[GemmSpec, ...] = ()
    group: int = 1
    causal: bool = True
    scale: float = 1.0
    q_offset: int = 0
    t_valid: int = 0
    bq: int = 256
    bkv: int = 512
    chunk: int = 64
    policy: prec.Policy = prec.FP32


def _attention_reference(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         group: int, causal: bool, scale: float,
                         q_offset: int, t_valid: int,
                         policy: prec.Policy, backend: str) -> jax.Array:
    """Reference attention as a composition of :func:`einsum2d` calls.

    Serves backends without the ``"attention"`` capability (XLA) and the
    ``custom_vjp`` backward of the kernel path: both score and PV GEMMs
    re-enter the registry and self-bill, so jaxpr audits reconcile with
    no attention-specific rules.  Numerics match the flash kernel's
    contract: fp32 scores/softmax, fully-masked query rows return exact
    zeros (the kernel's ``l == 0`` guard)."""
    B, Hq, S, D = q.shape
    _, Hkv, T, Dv = v.shape
    qg = q.reshape(B, Hkv, group, S, D)
    scores_pol = dataclasses.replace(
        policy, name=policy.name + "_scores",
        output_dtype=jnp.float32, faithful_accum=False)
    s = DEFAULT_ENGINE.einsum2d("bhgsd,bhtd->bhgst", qg, k,
                                policy=scores_pol, backend=backend)
    s = s * jnp.float32(scale)
    rows = q_offset + jnp.arange(S, dtype=jnp.int32)
    cols = jnp.arange(T, dtype=jnp.int32)
    mask = cols[None, :] < t_valid
    if causal:
        mask = mask & (cols[None, :] <= rows[:, None])
    else:
        mask = jnp.broadcast_to(mask, (S, T))
    s = jnp.where(mask, s, jnp.float32(-1e30))
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask.any(axis=-1)[:, None], p, jnp.float32(0.0))
    out = DEFAULT_ENGINE.einsum2d(
        "bhgst,bhtd->bhgsd", p.astype(policy.compute_dtype), v,
        policy=policy, backend=backend)
    return out.reshape(B, Hq, S, Dv).astype(policy.out_dtype)


def _linear_attention_reference(q: jax.Array, k: jax.Array, v: jax.Array,
                                log_g: jax.Array, *, chunk: int,
                                state: Optional[jax.Array],
                                backend: str) -> Tuple[jax.Array, jax.Array]:
    """Reference chunked linear-attention state sweep (mLSTM/SSD form)
    over ``(B, H, S, d)`` operands, composed of registry dispatches.

    The per-chunk recurrence matches the Pallas kernel exactly: an fp32
    intra-chunk score GEMM with the decay matrix ``A``, an intra-chunk
    PV GEMM, the inter-chunk ``q·exp(L) @ state`` read, and the decayed
    ``k^T·v`` state update.  Returns ``(out fp32, state fp32)``."""
    B, H, S, dk = q.shape
    dv = v.shape[-1]
    f32 = prec.FP32
    pad = (-S) % chunk
    if pad:
        zq = [(0, 0), (0, 0), (0, pad), (0, 0)]
        q = jnp.pad(q, zq)
        k = jnp.pad(k, zq)
        v = jnp.pad(v, [(0, 0), (0, 0), (0, pad), (0, 0)])
        log_g = jnp.pad(log_g, [(0, 0), (0, 0), (0, pad)])
    Sp = S + pad
    n = Sp // chunk
    qf = q.astype(jnp.float32).reshape(B, H, n, chunk, dk)
    kf = k.astype(jnp.float32).reshape(B, H, n, chunk, dk)
    vf = v.astype(jnp.float32).reshape(B, H, n, chunk, dv)
    gf = log_g.astype(jnp.float32).reshape(B, H, n, chunk)
    S0 = (jnp.zeros((B, H, dk, dv), jnp.float32) if state is None
          else state.astype(jnp.float32))
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))

    def step(S_prev, xs):
        qc, kc, vc, gc = xs
        L = jnp.cumsum(gc, axis=-1)                    # (B, H, chunk)
        Ltot = L[..., -1:]
        Dm = L[..., :, None] - L[..., None, :]
        A = jnp.where(causal[None, None], jnp.exp(Dm), 0.0)
        s = DEFAULT_ENGINE.einsum2d("bhik,bhjk->bhij", qc, kc,
                                    policy=f32, backend=backend) * A
        out = DEFAULT_ENGINE.matmul(s, vc, policy=f32, backend=backend)
        out = out + DEFAULT_ENGINE.matmul(
            qc * jnp.exp(L)[..., None], S_prev, policy=f32, backend=backend)
        kdec = kc * jnp.exp(Ltot - L)[..., None]
        S_new = jnp.exp(Ltot)[..., None] * S_prev + DEFAULT_ENGINE.matmul(
            jnp.swapaxes(kdec, -1, -2), vc, policy=f32, backend=backend)
        return S_new, out

    with repeat(n):
        S_fin, outs = jax.lax.scan(
            step, S0, (jnp.moveaxis(qf, 2, 0), jnp.moveaxis(kf, 2, 0),
                       jnp.moveaxis(vf, 2, 0), jnp.moveaxis(gf, 2, 0)))
    out = jnp.moveaxis(outs, 0, 2).reshape(B, H, Sp, dv)[:, :, :S]
    return out, S_fin


def _attention_specs(*, B: int, Hq: int, S: int, T: int, D: int, Dv: int,
                     bq: int, bkv: int, causal: bool, q_offset: int,
                     policy: prec.Policy) -> Tuple[GemmSpec, ...]:
    """Per-sweep event specs for one flash-attention dispatch.

    ``groups`` is the number of **executed** (Q-block, KV-block) pairs —
    causally skipped blocks are excluded, so billed flops are exact.
    ``io_bytes`` carries the sweep's true HBM traffic: Q is read once
    per Q row, K/V stream once per executed pair, the output stores
    once (the kernel's store-once Z contract)."""
    pairs = autotune._attn_pairs(S, T, bq, bkv, causal=causal,
                                 q_offset=q_offset)
    S_pad = -(-S // bq) * bq
    BHq = B * Hq
    cb = jnp.dtype(policy.compute_dtype).itemsize
    ob = jnp.dtype(policy.out_dtype).itemsize
    tile = tiling.TileConfig(bm=bq, bn=bkv, bk=bkv)
    score = GemmSpec(
        op="attention_score", tag="bsd,btd->bst", m=bq, n=D, k=bkv,
        batch=BHq, groups=pairs, policy=policy, tile=tile,
        io_bytes=BHq * (S_pad * D + pairs * bkv * D) * cb)
    pv = GemmSpec(
        op="attention_pv", tag="bst,btd->bsd", m=bq, n=bkv, k=Dv,
        batch=BHq, groups=pairs, policy=policy, tile=tile,
        io_bytes=BHq * (pairs * bkv * Dv * cb + S_pad * Dv * ob))
    return (score, pv)


def _linear_attention_specs(*, B: int, H: int, S: int, dk: int, dv: int,
                            chunk: int, in_bytes: int) -> Tuple[GemmSpec, ...]:
    """Event specs for one chunked linear-attention sweep: the four
    per-chunk GEMMs (intra-chunk score, intra-chunk PV, inter-chunk
    state read, state update) billed separately, ``groups`` = number of
    chunks.  The running state lives in VMEM across the whole sweep and
    stores once (fp32), exactly like the kernel."""
    S_pad = -(-S // chunk) * chunk
    n = S_pad // chunk
    BH = B * H
    f32 = prec.FP32
    tile = tiling.TileConfig(bm=chunk, bn=chunk, bk=chunk)
    score = GemmSpec(
        op="linear_attention_score", tag="bik,bjk->bij",
        m=chunk, n=dk, k=chunk, batch=BH, groups=n, policy=f32, tile=tile,
        io_bytes=BH * S_pad * (2 * dk * in_bytes + 4))
    pv = GemmSpec(
        op="linear_attention_pv", tag="bij,bjv->biv",
        m=chunk, n=chunk, k=dv, batch=BH, groups=n, policy=f32, tile=tile,
        io_bytes=BH * S_pad * dv * in_bytes)
    inter = GemmSpec(
        op="linear_attention_inter", tag="bik,bkv->biv",
        m=chunk, n=dk, k=dv, batch=BH, groups=n, policy=f32, tile=tile,
        io_bytes=BH * S_pad * dv * in_bytes)
    state = GemmSpec(
        op="linear_attention_state", tag="bki,bkv->biv",
        m=dk, n=chunk, k=dv, batch=BH, groups=n, policy=f32, tile=tile,
        io_bytes=BH * dk * dv * 4)
    return (score, pv, inter, state)


def _attention_kernel_dispatch(actx: _AttnCtx, q: jax.Array, k: jax.Array,
                               v: jax.Array) -> jax.Array:
    """Pad, flatten and hand the operands to the backend's flash kernel,
    emitting the sweep's events with remat classification."""
    pol = actx.policy
    comp = pol.compute_dtype
    B, Hq, S, D = q.shape
    _, Hkv, T, _ = k.shape
    S_pad = -(-S // actx.bq) * actx.bq
    T_pad = -(-T // actx.bkv) * actx.bkv
    qc = q.astype(comp)
    kc = k.astype(comp)
    vc = v.astype(comp)
    if S_pad != S:
        qc = jnp.pad(qc, [(0, 0), (0, 0), (0, S_pad - S), (0, 0)])
    if T_pad != T:
        zt = [(0, 0), (0, 0), (0, T_pad - T), (0, 0)]
        kc = jnp.pad(kc, zt)
        vc = jnp.pad(vc, zt)
    _emit_fwd(actx, actx.spec, actx.extra)
    fn = get_backend(actx.backend).attention_fn
    out = fn("attention",
             (qc.reshape(B * Hq, S_pad, D),
              kc.reshape(B * Hkv, T_pad, D),
              vc.reshape(B * Hkv, T_pad, D)),
             group=actx.group, causal=actx.causal, scale=actx.scale,
             bq=actx.bq, bkv=actx.bkv, t_valid=actx.t_valid,
             q_offset=actx.q_offset)
    out = out.reshape(B, Hq, S_pad, D)[:, :, :S]
    return out.astype(pol.out_dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _attention_call(actx: _AttnCtx, q: jax.Array, k: jax.Array,
                    v: jax.Array) -> jax.Array:
    return _attention_kernel_dispatch(actx, q, k, v)


def _attention_call_fwd(actx, q, k, v):
    return _attention_kernel_dispatch(actx, q, k, v), (q, k, v)


def _attention_call_bwd(actx, res, do):
    # Flash-style backward schedule: recompute the forward as the
    # reference einsum2d composition and differentiate through it — the
    # recompute and all four backward GEMMs re-enter the registry on the
    # same backend, each self-billing its events.
    q, k, v = res

    def ref(q_, k_, v_):
        return _attention_reference(
            q_, k_, v_, group=actx.group, causal=actx.causal,
            scale=actx.scale, q_offset=actx.q_offset, t_valid=actx.t_valid,
            policy=actx.policy, backend=actx.backend)

    with repeat(actx.count):
        _, vjp = jax.vjp(ref, q, k, v)
        dq, dk, dv = vjp(do)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_attention_call.defvjp(_attention_call_fwd, _attention_call_bwd)


def _linear_attention_kernel_dispatch(
        actx: _AttnCtx, q: jax.Array, k: jax.Array, v: jax.Array,
        log_g: jax.Array) -> Tuple[jax.Array, jax.Array]:
    B, H, S, dk = q.shape
    dv = v.shape[-1]
    chunk = actx.chunk
    pad = (-S) % chunk
    if pad:
        zs = [(0, 0), (0, 0), (0, pad), (0, 0)]
        q = jnp.pad(q, zs)
        k = jnp.pad(k, zs)
        v = jnp.pad(v, [(0, 0), (0, 0), (0, pad), (0, 0)])
        log_g = jnp.pad(log_g, [(0, 0), (0, 0), (0, pad)])
    Sp = S + pad
    _emit_fwd(actx, actx.spec, actx.extra)
    fn = get_backend(actx.backend).attention_fn
    out, st = fn("linear_attention",
                 (q.reshape(B * H, Sp, dk), k.reshape(B * H, Sp, dk),
                  v.reshape(B * H, Sp, dv),
                  log_g.astype(jnp.float32).reshape(B * H, Sp)),
                 chunk=chunk)
    out = out.reshape(B, H, Sp, dv)[:, :, :S].astype(jnp.float32)
    return out, st.reshape(B, H, dk, dv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _linear_attention_call(actx: _AttnCtx, q, k, v, log_g):
    return _linear_attention_kernel_dispatch(actx, q, k, v, log_g)


def _linear_attention_call_fwd(actx, q, k, v, log_g):
    out = _linear_attention_kernel_dispatch(actx, q, k, v, log_g)
    return out, (q, k, v, log_g)


def _linear_attention_call_bwd(actx, res, cts):
    q, k, v, log_g = res

    def ref(q_, k_, v_, g_):
        return _linear_attention_reference(
            q_, k_, v_, g_, chunk=actx.chunk, state=None,
            backend=actx.backend)

    with repeat(actx.count):
        _, vjp = jax.vjp(ref, q, k, v, log_g)
        grads = vjp(cts)
    return tuple(g.astype(p.dtype) for g, p in zip(grads, (q, k, v, log_g)))


_linear_attention_call.defvjp(_linear_attention_call_fwd,
                              _linear_attention_call_bwd)


# --------------------------------------------------------------------- #
# The Engine
# --------------------------------------------------------------------- #
class Engine:
    """Resolves :class:`GemmSpec`s to backends and dispatches them.

    The default instance (:data:`DEFAULT_ENGINE`, aliased by the
    module-level :func:`matmul` / :func:`linear` / :func:`grouped_matmul` /
    :func:`einsum2d`) carries no overrides; custom instances can pin a
    backend and/or precision policy for a subsystem::

        fp16_engine = Engine(policy=prec.PAPER_FP16)
        z = fp16_engine.matmul(x, w)
    """

    def __init__(self, *, backend: Optional[str] = None, policy=None):
        self._backend = backend
        self._policy = policy

    # -- resolution ---------------------------------------------------- #
    def resolve_backend(self, backend: Optional[str] = None) -> str:
        b = backend or self._backend or default_backend()
        spec = get_backend(b)
        # an explicit per-call argument or a constructor-pinned backend is
        # a deliberate choice — only implicitly resolved backends (context /
        # env / platform default) are availability-gated
        if backend is None and self._backend is None \
                and not spec.is_available():
            raise ValueError(
                f"default backend {b!r} is not available on this platform "
                f"(registered: {registered_backends()}); pass backend= "
                f"explicitly to override")
        return b

    def resolve_policy(self, policy=None) -> prec.Policy:
        return prec.resolve(policy if policy is not None else self._policy)

    def resolve_tile(
        self,
        tile: Optional[tiling.TileConfig],
        *,
        m: int,
        n: int,
        k: int,
        policy: prec.Policy,
        backend: str,
        epilogue: Optional[str] = None,
        layout: str = "nn",
        x_dtype: Optional[str] = None,
        w_dtype: Optional[str] = None,
    ) -> tiling.TileConfig:
        """Tile precedence: explicit arg > autotune cache > heuristic.

        Runs for every dispatch (so the emitted :class:`GemmEvent` always
        carries the tile the kernel would use); both fallbacks are cheap —
        the autotune lookup is a dict hit and ``choose_tiles`` is memoized.
        Backward dispatches resolve their own tiles with ``layout`` "nt" /
        "tn" and the transposed problem shape in the key; mixed-precision
        dispatches key (and size) their per-operand storage dtypes."""
        return _resolve_tile(tile, m=m, n=n, k=k, policy=policy,
                             backend=backend, epilogue=epilogue,
                             layout=layout, x_dtype=x_dtype,
                             w_dtype=w_dtype)

    # -- op family ----------------------------------------------------- #
    def matmul(
        self,
        x: jax.Array,
        w: jax.Array,
        *,
        policy=None,
        tile: Optional[tiling.TileConfig] = None,
        backend: Optional[str] = None,
    ) -> jax.Array:
        """Z = X @ W with the RedMulE dataflow.

        Shapes: ``x: (..., M, N)``, ``w: (N, K)`` (weight GEMM) or
        ``w: (..., N, K)`` with broadcast-compatible leading dims (batched
        GEMM, e.g. attention).  Output: ``(..., M, K)`` in the policy's
        output dtype.

        Differentiable end to end: ``jax.grad`` dispatches dX = dZ·Wᵀ and
        dW = Xᵀ·dZ through the backend registry as transpose-layout specs
        tagged ``matmul_dx`` / ``matmul_dw`` (see the module docstring's
        backward contract)."""
        policy = self.resolve_policy(policy)
        b = self.resolve_backend(backend)
        if x.ndim < 2 or w.ndim < 2:
            raise ValueError(f"matmul needs >=2D operands, got {x.shape} @ {w.shape}")
        if x.shape[-1] != w.shape[-2]:
            raise ValueError(f"contraction mismatch: {x.shape} @ {w.shape}")
        if w.ndim == 2:
            lead = x.shape[:-2]
            tag = "mn,nk->mk"
        else:
            lead = np.broadcast_shapes(x.shape[:-2], w.shape[:-2])
            tag = "bmn,bnk->bmk"
        m, n, k = x.shape[-2], x.shape[-1], w.shape[-1]
        xs, ws, _ = _dispatch_storage(policy, b)
        tile = self.resolve_tile(tile, m=m, n=n, k=k, policy=policy,
                                 backend=b, x_dtype=xs, w_dtype=ws)
        spec = GemmSpec(
            op="matmul", tag=tag, m=m, n=n, k=k,
            batch=int(np.prod(lead, dtype=np.int64)) if lead else 1,
            policy=policy, tile=tile, w_shared=(w.ndim == 2),
            x_dtype=xs, w_dtype=ws, scaled=policy.scaled,
        )
        return _gemm_call(_make_ctx(spec, b, x, w), x, w)

    def linear(
        self,
        x: jax.Array,
        w: jax.Array,
        b: Optional[jax.Array] = None,
        *,
        activation: Optional[str] = None,
        policy=None,
        tile: Optional[tiling.TileConfig] = None,
        backend: Optional[str] = None,
    ) -> jax.Array:
        """Affine layer with a *fused* epilogue: ``act(x @ w + b)``.

        On backends with the ``"fused_epilogue"`` capability ("pallas",
        "interpret") the bias add and activation execute inside the GEMM
        kernel, on the accumulator in the policy's accumulation dtype,
        immediately before the store-once HBM write — the affine layer
        costs exactly one output pass.  Other backends ("xla") fall back
        to the post-op path: the epilogue runs in the accumulation dtype
        on the backend's result, then one downcast.

        Numerics: under ``paper_fp16`` (accum == out dtype) the two paths
        are bitwise identical for bias-only and relu epilogues;
        transcendental epilogues (gelu/silu/tanh) may differ by ~2 ulp
        because XLA rounds fp16 transcendentals differently inside a
        compiled kernel than in an eager post-op pass.  Under fp32-accum
        policies the fused path additionally applies the epilogue *before*
        the out-dtype rounding while the unfused path re-widens the
        already-rounded store — results agree to ~2 ulp of the output
        dtype (the fused value is the more accurate one).  The equivalence
        suite in tests/test_engine.py pins exactly this contract.  Batched
        weights ``(..., N, K)`` get the same contract on the batched-grid
        kernel (bias row shared across the batch).

        FP8 rows of the same table (the mixed-precision policies): scaled
        specs always run the epilogue post-op — the per-tensor scale
        product must hit the accumulator before the bias — so there is no
        fused-vs-unfused gap to bound; the contract is instead
        *backend-invariance*: the engine quantizes once, every backend
        sees the same FP8 values, and results across backends agree to
        the compute-dtype tolerance (fp16 ~2e-2).  Each operand's
        quantize→dequantize round-trip is bounded by its format's
        relative epsilon (E4M3 2⁻³, E5M2 2⁻²) — pinned by
        tests/test_precision_fp8.py.

        Backward (see the module docstring): ``jax.grad`` dispatches dX/dW
        through the registry as ``matmul_dx`` / ``matmul_dw``
        transpose-layout GEMMs.  On backends with the
        ``"fused_bwd_epilogue"`` capability (2D weights) the backward is
        **one pass**: the kernels apply the activation derivative
        (registry in :mod:`repro.core.epilogues`) to the dZ tile on load
        and accumulate the accum-dtype bias grad inside the dW kernel —
        the pre-activation cotangent is never materialized.  Other
        backends (and batched weights) run the two-pass fallback
        (standalone ``ds = dZ·act'(s)`` multiply + separate bias-grad
        reduction, billed as ``linear_dact`` / ``linear_dbias`` pass
        events)."""
        policy = self.resolve_policy(policy)
        bk = self.resolve_backend(backend)
        epi.validate_epilogue(activation)
        if x.ndim < 2 or w.ndim < 2:
            raise ValueError(f"linear needs x>=2D, w>=2D; got {x.shape} @ {w.shape}")
        if x.shape[-1] != w.shape[-2]:
            raise ValueError(f"contraction mismatch: {x.shape} @ {w.shape}")
        if b is not None and b.shape != (w.shape[-1],):
            raise ValueError(
                f"bias must have shape ({w.shape[-1]},), got {b.shape}")
        if w.ndim == 2:
            lead = x.shape[:-2]
            tag = "mn,nk->mk"
        else:
            lead = np.broadcast_shapes(x.shape[:-2], w.shape[:-2])
            tag = "bmn,bnk->bmk"
        m, n, k = x.shape[-2], x.shape[-1], w.shape[-1]
        xs, ws, _ = _dispatch_storage(policy, bk)
        tile = self.resolve_tile(tile, m=m, n=n, k=k, policy=policy,
                                 backend=bk, epilogue=activation,
                                 x_dtype=xs, w_dtype=ws)
        spec = GemmSpec(
            op="linear", tag=tag, m=m, n=n, k=k,
            batch=int(np.prod(lead, dtype=np.int64)) if lead else 1,
            policy=policy, tile=tile, epilogue=activation,
            w_shared=(w.ndim == 2),
            x_dtype=xs, w_dtype=ws, scaled=policy.scaled,
        )
        has_epilogue = b is not None or activation is not None
        # scaled (FP8) policies run the epilogue post-op and the two-pass
        # backward: the per-tensor scale product must be undone on the
        # accumulator *before* the bias/activation (and the quantization
        # point of ds must be backend-invariant) — see _linear_bwd_core
        fuse = (has_epilogue and not policy.scaled
                and get_backend(bk).supports("fused_epilogue"))
        # one-pass backward: the dX/dW kernels apply act' to dZ on load and
        # accumulate db in the dW pass (2D weights; batched weights keep
        # the two-pass fallback)
        fuse_bwd = (has_epilogue and w.ndim == 2 and not policy.scaled
                    and get_backend(bk).supports("fused_bwd_epilogue"))
        if not has_epilogue:
            return _gemm_call(_make_ctx(spec, bk, x, w), x, w)
        ctx = _make_ctx(spec, bk, x, w, b, fuse=fuse, fuse_bwd=fuse_bwd)
        if b is None:
            return _linear_call_nobias(ctx, x, w)
        return _linear_call(ctx, x, w, b)

    def grouped_matmul(
        self,
        x: jax.Array,
        w: jax.Array,
        *,
        group_sizes: Optional[jax.Array] = None,
        layout: str = "nn",
        policy=None,
        tile: Optional[tiling.TileConfig] = None,
        backend: Optional[str] = None,
    ) -> jax.Array:
        """Per-group GEMM: ``Z[g] = X[g] @ W[g]`` for every group at once.

        Shapes: ``x: (..., G, M, N)``, ``w: (G, N, K)``; output
        ``(..., G, M, K)``.  This is the MoE expert GEMM — all experts run
        as one fat batched RedMulE GEMM (the paper's Fig 4d batching
        restoration) instead of a per-expert Python loop.

        ``group_sizes`` (optional, shape ``(G,)`` int) marks the number of
        valid M rows per group for ragged workloads; output rows at or
        beyond a group's size are zeroed.  When the sizes are statically
        known (concrete at trace time) the emitted :class:`GemmEvent`
        carries ``valid_rows = sum(min(size, M))`` so flops/bytes scale
        with the *valid* work, not ``G * M`` — forward and backward alike.
        Traced (data-dependent) sizes fall back to the dense count.

        ``layout="nt"`` takes ``w`` stored ``(G, K, N)`` — ``Z[g] = X[g] @
        W[g]ᵀ`` with no materialized transpose on backends with the
        "layouts" capability (decode attention's scores against the key
        cache).  There ``group_sizes`` count the valid stored rows of
        ``w``, i.e. the valid output *columns*: columns at or beyond a
        group's size are zeroed and billed as ``ragged_dim="k"``.

        Backward: dX/dW run as batched transpose-layout dispatches per
        group (``matmul_dx`` / ``matmul_dw`` events); the masked rows'
        cotangent is zeroed by the ``where``'s own autodiff, so invalid
        rows contribute nothing to dW.  An "nt" forward differentiates
        through the same dispatches on ``w``'s transpose, billed dense."""
        policy = self.resolve_policy(policy)
        b = self.resolve_backend(backend)
        if layout not in ("nn", "nt"):
            raise ValueError(
                f"grouped_matmul layout = {layout!r}; known: ('nn', 'nt')")
        nt = layout == "nt"
        if x.ndim < 3 or w.ndim != 3:
            raise ValueError(
                f"grouped_matmul needs x (..., G, M, N) and w (G, N, K) "
                f"(or (G, K, N) under 'nt'); got {x.shape} @ {w.shape}")
        if x.shape[-3] != w.shape[0]:
            raise ValueError(
                f"group mismatch: x has {x.shape[-3]} groups, w has {w.shape[0]}")
        if x.shape[-1] != w.shape[-1 if nt else -2]:
            raise ValueError(f"contraction mismatch: {x.shape} @ {w.shape}"
                             f" ({layout})")
        lead = x.shape[:-3]
        m, n = x.shape[-2], x.shape[-1]
        k = w.shape[-2] if nt else w.shape[-1]
        if nt and not get_backend(b).supports("layouts"):
            w, layout = jnp.swapaxes(w, -1, -2), "nn"
        xs, ws, _ = _dispatch_storage(policy, b)
        tile = self.resolve_tile(tile, m=m, n=n, k=k, policy=policy,
                                 backend=b, layout=layout,
                                 x_dtype=xs, w_dtype=ws)
        spec = GemmSpec(
            op="grouped_matmul", tag="gmn,gnk->gmk", m=m, n=n, k=k,
            batch=int(np.prod(lead, dtype=np.int64)) if lead else 1,
            groups=w.shape[0],
            policy=policy, tile=tile, w_shared=True, layout=layout,
            valid_rows=_static_valid_rows(group_sizes, k if nt else m),
            ragged_dim="k" if nt else "m",
            x_dtype=xs, w_dtype=ws, scaled=policy.scaled,
        )
        z = _gemm_call(_make_ctx(spec, b, x, w), x, w)
        if group_sizes is not None:
            size = jnp.asarray(group_sizes)[:, None]
            if nt:
                valid = (jnp.arange(k)[None, :] < size)[:, None, :]
            else:
                valid = (jnp.arange(m)[None, :] < size)[..., None]
            z = jnp.where(valid, z, jnp.zeros((), z.dtype))
        return z

    def einsum2d(
        self,
        eq: str,
        x: jax.Array,
        w: jax.Array,
        *,
        policy=None,
        tile: Optional[tiling.TileConfig] = None,
        backend: Optional[str] = None,
    ) -> jax.Array:
        """Two-operand einsum lowered onto the engine's GEMM dispatch.

        Supports any equation with exactly two operands, single-letter
        axes, no repeated labels within an operand and no ellipses (e.g.
        ``"bhsd,rhd->bhsr"``).  Shared labels absent from the output are
        contracted; labels unique to one operand and absent from the
        output are summed out first."""
        policy = self.resolve_policy(policy)
        b = self.resolve_backend(backend)
        plan = _plan_einsum2d(eq, x.shape, w.shape)
        (batch_l, m_l, k_l, c_l, sum_x, sum_w, a_lab, b_lab, out_lab,
         dims) = plan
        if sum_x:
            x = jnp.sum(x, axis=tuple(a_lab.index(l) for l in sum_x))
            a_lab = [l for l in a_lab if l not in sum_x]
        if sum_w:
            w = jnp.sum(w, axis=tuple(b_lab.index(l) for l in sum_w))
            b_lab = [l for l in b_lab if l not in sum_w]
        xt = jnp.transpose(x, [a_lab.index(l) for l in batch_l + m_l + c_l])
        wt = jnp.transpose(w, [b_lab.index(l) for l in batch_l + c_l + k_l])
        bsz = int(np.prod([dims[l] for l in batch_l], dtype=np.int64)) \
            if batch_l else 1
        m = int(np.prod([dims[l] for l in m_l], dtype=np.int64)) if m_l else 1
        k = int(np.prod([dims[l] for l in k_l], dtype=np.int64)) if k_l else 1
        c = int(np.prod([dims[l] for l in c_l], dtype=np.int64)) if c_l else 1
        xs, ws, _ = _dispatch_storage(policy, b)
        tile = self.resolve_tile(tile, m=m, n=c, k=k, policy=policy,
                                 backend=b, x_dtype=xs, w_dtype=ws)
        spec = GemmSpec(
            op="einsum2d", tag=eq.replace(" ", ""),
            m=m, n=c, k=k, batch=bsz, policy=policy, tile=tile,
            w_shared=not batch_l,
            x_dtype=xs, w_dtype=ws, scaled=policy.scaled,
        )
        if batch_l:
            x2 = xt.reshape(bsz, m, c)
            w2 = wt.reshape(bsz, c, k)
        else:
            x2 = xt.reshape(m, c)
            w2 = wt.reshape(c, k)
        # the custom VJP lives on the inner 2D/batched dispatch; the
        # surrounding transposes/reshapes/sums are linear ops JAX
        # differentiates natively, so einsum2d's backward GEMMs are
        # matmul_dx / matmul_dw registry dispatches too
        z = _gemm_call(_make_ctx(spec, b, x2, w2), x2, w2)
        cur = batch_l + m_l + k_l
        z = z.reshape([dims[l] for l in cur])
        return jnp.transpose(z, [cur.index(l) for l in out_lab])

    def attention(
        self,
        q: jax.Array,
        k: jax.Array,
        v: jax.Array,
        *,
        causal: bool = True,
        scale: Optional[float] = None,
        q_offset: int = 0,
        t_valid: Optional[int] = None,
        bq: Optional[int] = None,
        bkv: Optional[int] = None,
        policy=None,
        backend: Optional[str] = None,
    ) -> jax.Array:
        """Fused scaled-dot-product attention as a first-class engine op.

        Shapes: ``q: (B, Hq, S, D)``, ``k/v: (B, Hkv, T, Dv)`` with
        ``Hq % Hkv == 0`` (GQA group = ``Hq // Hkv``; the kernel maps KV
        heads in its index maps, never materializing per-q-head copies).
        ``t_valid`` masks the padded KV tail (cols >= t_valid are dead),
        ``q_offset`` is the absolute position of query row 0 for the
        causal mask (``col <= q_offset + row``).  Fully-masked query rows
        return exact zeros.  Output: ``(B, Hq, S, Dv)`` in the policy's
        output dtype.

        Backends with the ``"attention"`` capability run the flash sweep
        (online softmax, store-once output, causally dead KV blocks
        skipped), billed as ``attention_score`` / ``attention_pv``
        :class:`GemmEvent` pairs whose ``groups`` count only executed
        blocks and whose ``io_bytes`` carry the sweep's true HBM traffic.
        Block sizes resolve explicit ``bq``/``bkv`` > the autotune cache
        (sweep key ``attnc``/``attn``) > a shape-fitted heuristic.  Other
        backends (XLA) get the reference :func:`einsum2d` composition —
        identical numerics contract, events self-billed by the inner
        dispatches.  ``jax.grad`` re-enters the registry either way (the
        kernel path's ``custom_vjp`` recomputes via the reference, flash
        style: no S×T tensor is saved between forward and backward)."""
        policy = self.resolve_policy(policy)
        b = self.resolve_backend(backend)
        if q.ndim != 4 or k.ndim != 4 or v.ndim != 4:
            raise ValueError(
                f"attention needs (B, H, S, D) operands, got "
                f"{q.shape} / {k.shape} / {v.shape}")
        B, Hq, S, D = q.shape
        _, Hkv, T, Dv = v.shape
        if k.shape != (B, Hkv, T, D):
            raise ValueError(f"k/v shape mismatch: {k.shape} vs {v.shape}")
        if q.shape[0] != k.shape[0] or q.shape[-1] != k.shape[-1]:
            raise ValueError(f"q/k shape mismatch: {q.shape} vs {k.shape}")
        if Hq % Hkv != 0:
            raise ValueError(f"Hq={Hq} not a multiple of Hkv={Hkv}")
        group = Hq // Hkv
        scale = float(D ** -0.5 if scale is None else scale)
        q_offset = int(q_offset)
        t_valid = T if t_valid is None else min(int(t_valid), T)
        if not (get_backend(b).supports("attention") and Dv == D):
            return _attention_reference(
                q, k, v, group=group, causal=causal, scale=scale,
                q_offset=q_offset, t_valid=t_valid, policy=policy,
                backend=b)
        if bq is None or bkv is None:
            t = autotune.cached_tile(
                S, T, D, policy=policy, backend=b,
                sweep="attnc" if causal else "attn")
            if t is not None:
                bq = bq or t.bm
                bkv = bkv or t.bn
        bq = int(bq) if bq else min(256, -(-S // 8) * 8)
        bkv = int(bkv) if bkv else min(512, -(-T // 8) * 8)
        specs = _attention_specs(
            B=B, Hq=Hq, S=S, T=T, D=D, Dv=Dv, bq=bq, bkv=bkv,
            causal=causal, q_offset=q_offset, policy=policy)
        actx = _AttnCtx(
            kind="attention", spec=specs[0], backend=b,
            count=_repeat_multiplier(), extra=specs[1:], group=group,
            causal=causal, scale=scale, q_offset=q_offset,
            t_valid=t_valid, bq=bq, bkv=bkv, policy=policy)
        return _attention_call(actx, q, k, v)

    def linear_attention(
        self,
        q: jax.Array,
        k: jax.Array,
        v: jax.Array,
        log_g: jax.Array,
        *,
        chunk: Optional[int] = None,
        state: Optional[jax.Array] = None,
        backend: Optional[str] = None,
    ) -> Tuple[jax.Array, jax.Array]:
        """Chunked linear attention (mLSTM/SSD state sweep) as a
        first-class engine op.

        Shapes: ``q/k: (B, H, S, dk)``, ``v: (B, H, S, dv)``,
        ``log_g: (B, H, S)`` per-step log decay; optional ``state``
        carries an ``(B, H, dk, dv)`` fp32 recurrent state in (decode /
        chunked prefill).  Returns ``(out (B, H, S, dv) fp32,
        state (B, H, dk, dv) fp32)``.

        Backends with the ``"attention"`` capability run the fused sweep
        kernel when no state is carried in (the kernel owns the zero
        init), billed as four per-chunk GEMM events
        (``linear_attention_{score,pv,inter,state}``) with ``groups`` =
        number of chunks and exact ``io_bytes`` (the running state never
        leaves VMEM until its single final store).  The chunk size
        resolves explicit ``chunk`` > autotune cache (sweep key
        ``lattn``) > 64.  Other backends — and state carry-in — run the
        reference chunked scan, whose dispatches self-bill."""
        b = self.resolve_backend(backend)
        if q.ndim != 4 or k.ndim != 4 or v.ndim != 4 or log_g.ndim != 3:
            raise ValueError(
                f"linear_attention needs (B, H, S, d) q/k/v and "
                f"(B, H, S) log_g, got {q.shape} / {k.shape} / "
                f"{v.shape} / {log_g.shape}")
        B, H, S, dk = q.shape
        dv = v.shape[-1]
        if k.shape != q.shape or v.shape[:3] != q.shape[:3] \
                or log_g.shape != q.shape[:3]:
            raise ValueError(
                f"operand shape mismatch: {q.shape} / {k.shape} / "
                f"{v.shape} / {log_g.shape}")
        if chunk is None:
            t = autotune.cached_tile(S, dk, dv, policy=prec.FP32,
                                     backend=b, sweep="lattn")
            chunk = t.bm if t is not None else 64
        chunk = int(chunk)
        if not (get_backend(b).supports("attention") and state is None):
            return _linear_attention_reference(
                q, k, v, log_g, chunk=chunk, state=state, backend=b)
        specs = _linear_attention_specs(
            B=B, H=H, S=S, dk=dk, dv=dv, chunk=chunk,
            in_bytes=jnp.dtype(q.dtype).itemsize)
        actx = _AttnCtx(
            kind="linear_attention", spec=specs[0], backend=b,
            count=_repeat_multiplier(), extra=specs[1:], chunk=chunk,
            policy=prec.FP32)
        return _linear_attention_call(actx, q, k, v, log_g)

    # expose the collectors on the instance too, for discoverability
    instrument = staticmethod(instrument)
    repeat = staticmethod(repeat)


def _plan_einsum2d(eq: str, x_shape, w_shape):
    """Parse an einsum2d equation into (batch, m, k, contract, ...) labels."""
    e = eq.replace(" ", "")
    if "->" not in e or "..." in e:
        raise ValueError(f"einsum2d needs an explicit '->' and no ellipsis: {eq!r}")
    lhs, out = e.split("->")
    terms = lhs.split(",")
    if len(terms) != 2:
        raise ValueError(f"einsum2d takes exactly two operands: {eq!r}")
    a, bt = terms
    for t in (a, bt, out):
        if len(set(t)) != len(t):
            raise ValueError(f"repeated labels are not supported: {eq!r}")
    if len(a) != len(x_shape) or len(bt) != len(w_shape):
        raise ValueError(
            f"equation {eq!r} does not match operand ranks "
            f"{len(x_shape)} and {len(w_shape)}")
    dims: Dict[str, int] = {}
    for labels, shape in ((a, x_shape), (bt, w_shape)):
        for lab, s in zip(labels, shape):
            if lab in dims and dims[lab] != s:
                raise ValueError(
                    f"size mismatch for label {lab!r} in {eq!r}: "
                    f"{dims[lab]} vs {s}")
            dims[lab] = int(s)
    for lab in out:
        if lab not in dims:
            raise ValueError(f"output label {lab!r} not in any operand: {eq!r}")
    batch_l = [l for l in a if l in bt and l in out]
    c_l = [l for l in a if l in bt and l not in out]
    m_l = [l for l in a if l not in bt and l in out]
    k_l = [l for l in bt if l not in a and l in out]
    sum_x = [l for l in a if l not in bt and l not in out]
    sum_w = [l for l in bt if l not in a and l not in out]
    return (batch_l, m_l, k_l, c_l, sum_x, sum_w,
            list(a), list(bt), list(out), dims)


DEFAULT_ENGINE = Engine()


# --------------------------------------------------------------------- #
# Module-level conveniences (the framework-wide call surface)
# --------------------------------------------------------------------- #
def matmul(x, w, **kwargs) -> jax.Array:
    return DEFAULT_ENGINE.matmul(x, w, **kwargs)


def linear(x, w, b=None, **kwargs) -> jax.Array:
    return DEFAULT_ENGINE.linear(x, w, b, **kwargs)


def grouped_matmul(x, w, **kwargs) -> jax.Array:
    return DEFAULT_ENGINE.grouped_matmul(x, w, **kwargs)


def einsum2d(eq, x, w, **kwargs) -> jax.Array:
    return DEFAULT_ENGINE.einsum2d(eq, x, w, **kwargs)


def attention(q, k, v, **kwargs) -> jax.Array:
    return DEFAULT_ENGINE.attention(q, k, v, **kwargs)


def linear_attention(q, k, v, log_g, **kwargs):
    return DEFAULT_ENGINE.linear_attention(q, k, v, log_g, **kwargs)


matmul.__doc__ = Engine.matmul.__doc__
linear.__doc__ = Engine.linear.__doc__
grouped_matmul.__doc__ = Engine.grouped_matmul.__doc__
einsum2d.__doc__ = Engine.einsum2d.__doc__
attention.__doc__ = Engine.attention.__doc__
linear_attention.__doc__ = Engine.linear_attention.__doc__
