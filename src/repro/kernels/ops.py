"""jit'd wrappers around the Pallas GEMM kernel — the bodies behind the
Engine's registered "pallas" / "interpret" backends.

Handles padding to tile multiples (zeros are accumulation-neutral and the
registered epilogues all map 0 -> finite values that the final slice
discards), tile selection via :mod:`repro.core.tiling`, the fused
bias+activation epilogue, batching (a leading batch grid dimension inside
the kernel — not a ``vmap`` wrapper — so the tile choice sees the true
per-core working set), the transpose **layouts** the Engine's backward
pass dispatches (``"nt"`` for dX = dZ·Wᵀ, ``"tn"`` for dW = Xᵀ·dZ — the
operands stay in their forward storage, no materialized transpose), and
the **fused backward epilogue** (``deriv``/``grad_epilogue``/``bias_grad``:
act′ applied to the dZ tiles on load, the bias grad accumulated as a
second output of the dW pass — the Engine's ``"fused_bwd_epilogue"``
capability; see :mod:`repro.kernels.redmule_matmul`), and **per-operand
storage dtypes** (the ``"operand_dtypes"`` capability: FP8 operands pad
and stream at one byte per element, the kernel upcasts tiles to the
compute dtype on load; the tile chooser sizes the VMEM working set at the
true storage widths).  Model code should
not call these directly: route through :mod:`repro.core.engine` so
dispatches are instrumented and backend-switchable.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import precision as prec
from repro.core import tiling
from repro.kernels.redmule_matmul import (_check_layout,
                                          _logical_dims as _kernel_logical_dims,
                                          redmule_matmul_batched_pallas,
                                          redmule_matmul_pallas)

__all__ = ["redmule_matmul", "redmule_matmul_batched"]


def _pad_to(x: jax.Array, rows: int, cols: int) -> jax.Array:
    pr, pc = rows - x.shape[-2], cols - x.shape[-1]
    if pr == 0 and pc == 0:
        return x
    pad = [(0, 0)] * (x.ndim - 2) + [(0, pr), (0, pc)]
    return jnp.pad(x, pad)


def _padded_dims(M: int, N: int, K: int, t: tiling.TileConfig):
    up = lambda v, b: -(-v // b) * b
    return up(M, t.bm), up(N, t.bn), up(K, t.bk)


def _logical_dims(x: jax.Array, w: jax.Array, layout: str) -> Tuple[int, int, int]:
    """(M, N, K) of the logical Z[M,K] = Σ_N X·W from stored shapes —
    the kernel module's mapping, applied to the trailing 2D of each
    operand (one source of truth for what each layout stores where)."""
    _check_layout(layout)
    return _kernel_logical_dims(x.shape[-2:], w.shape[-2:], layout)


def _pad_operands(x: jax.Array, w: jax.Array, layout: str,
                  Mp: int, Np: int, Kp: int) -> Tuple[jax.Array, jax.Array]:
    """Pad each *stored* operand so the logical dims hit (Mp, Np, Kp)."""
    if layout == "nn":
        return _pad_to(x, Mp, Np), _pad_to(w, Np, Kp)
    if layout == "nt":
        return _pad_to(x, Mp, Np), _pad_to(w, Kp, Np)
    return _pad_to(x, Np, Mp), _pad_to(w, Np, Kp)  # tn


def redmule_matmul(
    x: jax.Array,
    w: jax.Array,
    *,
    policy: prec.Policy,
    tile: Optional[tiling.TileConfig] = None,
    bias: Optional[jax.Array] = None,
    epilogue: Optional[str] = None,
    layout: str = "nn",
    deriv: Optional[jax.Array] = None,
    grad_epilogue: Optional[str] = None,
    grad_from_output: bool = False,
    bias_grad: bool = False,
    pipeline_depth: int = 2,
    interpret: bool = False,
):
    """2D Z = act(X @ W + bias) on the RedMulE kernel (pads, runs, slices).

    ``bias`` (optional, shape ``(K,)`` or ``(1, K)``) and ``epilogue``
    (optional activation name) are fused into the kernel's store-once step
    in the accumulation dtype — the affine layer costs one HBM write.
    ``layout`` names the operand storage of the logical contraction
    ("nn" | "nt" | "tn"); the result is always the logical ``(M, K)``.

    Backward fusion (the ``"fused_bwd_epilogue"`` capability, transpose
    layouts only): ``grad_epilogue``/``grad_from_output`` + ``deriv``
    multiply the dZ operand's tiles by ``act'(deriv)`` on load inside the
    kernel (``deriv`` stored exactly like the dZ operand: the x slot for
    "nt", the w slot for "tn"); ``bias_grad=True`` (the dW "tn" dispatch)
    returns ``(dW, db)`` with ``db`` the accum-dtype ``(K,)`` row sum of
    the (derivative-adjusted) dZ rows, accumulated in the same pass."""
    M, N, K = _logical_dims(x, w, layout)
    if M == 0 or K == 0 or N == 0:
        # degenerate GEMM (e.g. an empty ragged group): an empty — or, for
        # N == 0, all-zero — result with no kernel launch.  The fused
        # epilogue still applies (act(0 + bias) for N == 0).
        z = jnp.zeros((M, K), policy.accum_dtype)
        if bias is not None:
            z = z + bias.reshape(1, K).astype(policy.accum_dtype)
        if epilogue is not None:
            from repro.core import epilogues as epi
            z = epi.apply_epilogue(epilogue, z)
        if bias_grad:
            # db = Σ_rows ds is independent of the degenerate output dims
            # (m == 0 just means dW has no rows); reduce eagerly.
            dsa = w.astype(policy.accum_dtype)   # tn: the dZ operand
            if grad_epilogue is not None:
                from repro.core import epilogues as epi
                g = epi.epilogue_grad(grad_epilogue)
                d = deriv.astype(policy.accum_dtype)
                dsa = dsa * (g.deriv_from_output(d) if grad_from_output
                             else g.deriv(d))
            db = (dsa.sum(axis=0) if dsa.size
                  else jnp.zeros((K,), policy.accum_dtype))
            return z.astype(policy.out_dtype), db
        return z.astype(policy.out_dtype)
    if tile is None:
        tile = tiling.choose_tiles(
            M, N, K, compute_dtype=policy.compute_dtype,
            accum_dtype=policy.accum_dtype,
            fused_bwd=grad_epilogue is not None or bias_grad,
            x_dtype=x.dtype, w_dtype=w.dtype,
        )
    Mp, Np, Kp = _padded_dims(M, N, K, tile)
    xp, wp = _pad_operands(x, w, layout, Mp, Np, Kp)
    bp = None
    if bias is not None:
        bp = _pad_to(bias.reshape(1, K).astype(policy.accum_dtype), 1, Kp)
    dp = None
    if grad_epilogue is not None:
        # the deriv operand pads like the dZ operand it shadows (zero rows
        # multiply a zero dZ padding, so the padding stays neutral)
        dp = (_pad_to(deriv, Mp, Np) if layout == "nt"
              else _pad_to(deriv, Np, Kp))
    out = redmule_matmul_pallas(xp, wp, bp, dp, tile=tile, policy=policy,
                                epilogue=epilogue, layout=layout,
                                grad_epilogue=grad_epilogue,
                                grad_from_output=grad_from_output,
                                bias_grad=bias_grad,
                                pipeline_depth=pipeline_depth,
                                interpret=interpret)
    if bias_grad:
        z, db = out
        return z[:M, :K], db[0, 0, :K]
    return out[:M, :K]


def redmule_matmul_batched(
    x: jax.Array,
    w: jax.Array,
    *,
    policy: prec.Policy,
    tile: Optional[tiling.TileConfig] = None,
    bias: Optional[jax.Array] = None,
    epilogue: Optional[str] = None,
    layout: str = "nn",
    w_index: Optional[jax.Array] = None,
    interpret: bool = False,
) -> jax.Array:
    """Batched Z[b] = act(X[b] @ W[b] + bias); e.g. x: (B, M, N), w: (B, N, K).

    The batch rides as the kernel's leading grid dimension (one tile set
    live at a time), not as a ``vmap`` that would multiply the VMEM
    working set by B behind the tile chooser's back.  ``bias`` (optional,
    shape ``(K,)`` or ``(1, K)``, shared across the batch) and ``epilogue``
    are fused into the store-once step like the 2D path; ``layout`` selects
    the operand storage ("nn" | "nt" | "tn").  ``w_index``: ``w`` is a
    stack ``(L, B, ...)`` and the kernel reads layer ``w_index`` in place;
    a stack whose layer would need padding is sliced (and padded) first."""
    B = x.shape[0]
    M, N, K = _logical_dims(x, w, layout)
    if B == 0 or M == 0 or K == 0 or N == 0:
        z = jnp.zeros((B, M, K), policy.accum_dtype)
        if bias is not None:
            z = z + bias.reshape(1, 1, K).astype(policy.accum_dtype)
        if epilogue is not None:
            from repro.core import epilogues as epi
            z = epi.apply_epilogue(epilogue, z)
        return z.astype(policy.out_dtype)
    if tile is None:
        tile = tiling.choose_tiles(
            M, N, K, compute_dtype=policy.compute_dtype,
            accum_dtype=policy.accum_dtype,
            x_dtype=x.dtype, w_dtype=w.dtype,
        )
    Mp, Np, Kp = _padded_dims(M, N, K, tile)
    w_padded = (Kp, Np) if layout == "nt" else (Np, Kp)
    if w_index is not None and w.shape[-2:] != w_padded:
        w, w_index = jax.lax.dynamic_index_in_dim(w, w_index, 0, False), None
    xp, wp = _pad_operands(x, w, layout, Mp, Np, Kp)
    bp = None
    if bias is not None:
        bp = _pad_to(bias.reshape(1, 1, K).astype(policy.accum_dtype),
                     1, Kp)
    z = redmule_matmul_batched_pallas(xp, wp, bp, w_index, tile=tile,
                                      policy=policy, epilogue=epilogue,
                                      layout=layout, interpret=interpret)
    return z[:, :M, :K]
