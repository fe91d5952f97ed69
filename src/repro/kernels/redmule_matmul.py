"""RedMulE matmul as a Pallas TPU kernel.

The paper's dataflow (§II-B/C), re-derived for the TPU memory hierarchy
(DESIGN.md §2):

* the 2D kernel runs a grid of (M/bm, K/bk) Z tiles; the contraction (N)
  is an **in-kernel double-buffered K-loop**: every reduction step's X and
  W tiles are DMA'd from HBM into ``pipeline_depth`` VMEM scratch slots,
  and the DMA for step ``r+1`` is issued *before* the FMA step for ``r``
  runs — an explicit re-creation of the Streamer's interleaved load
  schedule (the X tile for a given (m, k) stays resident logically; the
  prefetch hides the W-stream latency behind the MXU);
* the Z tile lives in a VMEM scratch accumulator for the entire reduction
  and is written to HBM exactly once, after the loop (the Z-buffer
  store-once rule);
* the accumulator is fp32 by default (MXU-native) or fp16 re-rounded per
  N-block in ``paper_faithful`` mode (the binary16 in-pipeline accumulation
  error model);
* the **forward epilogue is fused**: when a bias row and/or activation name
  is given, ``act(acc + bias)`` is applied to the accumulator *in the
  accumulation dtype* inside the store-once step, so an affine layer costs
  exactly one HBM write — the GEMM-*layer* datapath of the follow-up
  RedMule engine paper (arXiv:2301.03904), not a GEMM unit plus a separate
  HBM round-trip;
* the **backward epilogue is fused too** (the ``"fused_bwd_epilogue"``
  backend capability): a backward dispatch may carry a ``deriv`` operand —
  the fused forward output (``grad_from_output=True``: relu/tanh) or the
  saved pre-activation (gelu/silu) — and the kernel applies ``ds = dZ *
  act'(deriv)`` to the dZ tile **on load**, in the accumulation dtype, so
  the pre-activation cotangent ``ds`` is never materialized in HBM.  With
  ``bias_grad=True`` (the dW "tn" dispatch) the kernel also accumulates
  ``db = Σ_rows ds`` into a second accum-dtype output in the same pass,
  eliminating the separate bias-grad reduction;
* **per-operand storage dtypes** (the mixed-precision RedMulE,
  arXiv:2301.03904): operands may arrive narrower than the compute dtype
  (FP8 ``float8_e4m3fn`` / ``float8_e5m2`` under the mixed policies) —
  tiles DMA from HBM in their storage width and are upcast to the compute
  dtype **on load**, inside the K-loop, so the HBM stream (and the VMEM
  slots) stay narrow and no cast pass ever materializes the wide operand.
  This composes with the fused backward epilogue (an FP8 dZ stream is
  widened, multiplied by ``act'`` and fed to the MXU tile-wise) and with
  every layout.  Per-tensor scales are the *engine's* job
  (:mod:`repro.core.engine` applies/undoes them around the dispatch) —
  the kernel only ever sees the already-quantized integers-in-fp8;
* batched operands get a leading **batch grid dimension**
  (:func:`redmule_matmul_batched_pallas`) instead of a ``vmap`` wrapper, so
  the tile choice and the Pallas pipeline see the true per-core working set
  (one X/W/Z tile set, not B concurrent copies);
* **transpose layouts** serve the backward pass without materialized
  transposes: the logical GEMM is always ``Z[M, K] = Σ_N X·W``, and
  ``layout`` names how the operands are *stored* — ``"nn"`` (x: (M, N),
  w: (N, K), the forward), ``"nt"`` (w stored (K, N); dX = dZ·Wᵀ reads W
  in its forward layout) and ``"tn"`` (x stored (N, M); dW = Xᵀ·dZ reads
  the saved activations in their forward layout).  Only the DMA index
  arithmetic and the in-kernel ``dot_general`` dimension numbers change;
  the store-once schedule — and therefore the accumulator error model —
  is identical in all three.

Shapes must be pre-padded to tile multiples by ``ops.py``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import epilogues as epi
from repro.core import precision as prec
from repro.core import tiling

__all__ = ["redmule_matmul_pallas", "redmule_matmul_batched_pallas", "LAYOUTS"]

# storage layouts of the logical Z[M,K] = X[M,N] @ W[N,K] contraction:
#   nn: x (M, N), w (N, K)   — forward
#   nt: x (M, N), w (K, N)   — dX = dZ @ W^T (w in forward storage)
#   tn: x (N, M), w (N, K)   — dW = X^T @ dZ (x in forward storage)
LAYOUTS = ("nn", "nt", "tn")

# in-kernel contraction dimension numbers per layout (2D tiles)
_DIMS = {
    "nn": (((1,), (0,)), ((), ())),
    "nt": (((1,), (1,)), ((), ())),
    "tn": (((0,), (0,)), ((), ())),
}


def _check_layout(layout: str) -> None:
    if layout not in LAYOUTS:
        raise ValueError(f"unknown layout {layout!r}; known: {LAYOUTS}")


def _store_value(acc, bias, *, epilogue, out_dtype):
    """The fused store-once epilogue: ``act(acc + bias)`` in the accumulator
    dtype, then a single downcast to the stored dtype.

    In ``paper_faithful`` mode the accumulator is fp16, so the epilogue runs
    in binary16 too — the whole layer stays inside the paper's datapath."""
    if bias is not None:
        acc = acc + bias.astype(acc.dtype)
    acc = epi.apply_epilogue(epilogue, acc)
    return acc.astype(out_dtype)


def _logical_dims(x_shape, w_shape, layout: str):
    """(M, N, K) of the logical contraction from stored operand shapes."""
    if layout == "nn":
        (M, N), (_, K) = x_shape, w_shape
    elif layout == "nt":
        (M, N), (K, _) = x_shape, w_shape
    else:  # tn
        (N, M), (_, K) = x_shape, w_shape
    return M, N, K


def _deriv_on(layout: str) -> Optional[str]:
    """Which operand slot holds dZ in a backward dispatch: the x slot for
    "nt" (dX = dZ·Wᵀ), the w slot for "tn" (dW = Xᵀ·dZ)."""
    return {"nt": "x", "tn": "w"}.get(layout)


def _pipelined_kernel(*refs, n_steps: int, depth: int, tile, layout: str,
                      out_dtype, compute_dtype, epilogue: Optional[str],
                      grad_epilogue: Optional[str], grad_from_output: bool,
                      bias_grad: bool, has_bias: bool):
    """One (bm, bk) Z tile: the whole N-reduction as a double-buffered
    in-kernel loop.

    Operand tiles are DMA'd from HBM into ``depth`` VMEM slots; the copy
    for step ``r+1`` is issued before the FMA for step ``r`` runs, so the
    load of the next K-step overlaps the MXU (the Streamer's interleaved
    schedule, made explicit).  When ``grad_epilogue`` is set the dZ tile is
    multiplied by ``act'(deriv tile)`` in the accumulation dtype right
    after its load — ``ds`` exists only tile-wise in VMEM, never in HBM;
    ``bias_grad`` additionally accumulates ``db = Σ_rows ds`` into a second
    accum-dtype output in the same pass."""
    bm, bn, bk = tile.bm, tile.bn, tile.bk
    has_deriv = grad_epilogue is not None
    # positional ref parse: inputs, outputs, scratch (pallas ordering)
    x_hbm, w_hbm = refs[0], refs[1]
    pos = 2
    bias_ref = None
    if has_bias:
        bias_ref = refs[pos]
        pos += 1
    d_hbm = None
    if has_deriv:
        d_hbm = refs[pos]
        pos += 1
    z_ref = refs[pos]
    pos += 1
    db_ref = None
    if bias_grad:
        db_ref = refs[pos]
        pos += 1
    acc_ref, xbuf, wbuf = refs[pos], refs[pos + 1], refs[pos + 2]
    pos += 3
    dbuf = None
    if has_deriv:
        dbuf = refs[pos]
        pos += 1
    db_acc = None
    if bias_grad:
        db_acc = refs[pos]
        pos += 1
    sems = refs[pos]

    i = pl.program_id(0)
    j = pl.program_id(1)
    deriv_on = _deriv_on(layout)

    def _x_dma(slot, r):
        if layout == "tn":
            src = x_hbm.at[pl.ds(r * bn, bn), pl.ds(i * bm, bm)]
        else:
            src = x_hbm.at[pl.ds(i * bm, bm), pl.ds(r * bn, bn)]
        return pltpu.make_async_copy(src, xbuf.at[slot], sems.at[slot, 0])

    def _w_dma(slot, r):
        if layout == "nt":
            src = w_hbm.at[pl.ds(j * bk, bk), pl.ds(r * bn, bn)]
        else:
            src = w_hbm.at[pl.ds(r * bn, bn), pl.ds(j * bk, bk)]
        return pltpu.make_async_copy(src, wbuf.at[slot], sems.at[slot, 1])

    def _d_dma(slot, r):
        # the deriv tile shadows the dZ operand's walk exactly
        if deriv_on == "x":
            src = d_hbm.at[pl.ds(i * bm, bm), pl.ds(r * bn, bn)]
        else:
            src = d_hbm.at[pl.ds(r * bn, bn), pl.ds(j * bk, bk)]
        return pltpu.make_async_copy(src, dbuf.at[slot], sems.at[slot, 2])

    def _dmas(slot, r):
        cps = [_x_dma(slot, r), _w_dma(slot, r)]
        if has_deriv:
            cps.append(_d_dma(slot, r))
        return cps

    acc_ref[...] = jnp.zeros_like(acc_ref)
    if db_acc is not None:
        db_acc[...] = jnp.zeros_like(db_acc)
    # pipeline prologue: fill depth-1 slots ahead (the classic schedule —
    # at steady state depth-1 DMAs are in flight while one slot computes)
    for r0 in range(min(depth - 1, n_steps)):
        for c in _dmas(r0, r0):
            c.start()

    def _step(r, carry):
        slot = jax.lax.rem(r, depth)
        ahead = r + depth - 1

        # prefetch the step that lands in the slot just freed by step r-1,
        # keeping the pipeline depth-1 steps ahead of the FMA
        @pl.when(ahead < n_steps)
        def _prefetch():
            for c in _dmas(jax.lax.rem(ahead, depth), ahead):
                c.start()

        for c in _dmas(slot, r):
            c.wait()
        # per-operand storage: tiles DMA in their HBM dtype (FP8 under the
        # mixed-precision policies) and are upcast to the compute dtype
        # **on load**, right here in VMEM — no HBM-side cast pass ever
        # materializes the wide operand (the mixed-precision RedMulE's
        # input-cast stage, arXiv:2301.03904)
        xt = xbuf[slot]
        wt = wbuf[slot]
        if xt.dtype != compute_dtype:
            xt = xt.astype(compute_dtype)
        if wt.dtype != compute_dtype:
            wt = wt.astype(compute_dtype)
        if has_deriv or bias_grad:
            # the fused backward epilogue: ds = dZ * act'(deriv), applied
            # on load in the accumulation dtype (the same dtype chain as
            # the engine's two-pass fallback), then one downcast feeds the
            # MXU.  ds never exists outside this VMEM tile.
            dz_t = xt if deriv_on == "x" else wt
            dsa = dz_t.astype(acc_ref.dtype)
            if has_deriv:
                g = epi.epilogue_grad(grad_epilogue)
                d = dbuf[slot].astype(acc_ref.dtype)
                dsa = dsa * (g.deriv_from_output(d) if grad_from_output
                             else g.deriv(d))
            if db_acc is not None:
                db_acc[...] += jnp.sum(dsa, axis=0, keepdims=True)
            ds_t = dsa.astype(compute_dtype)
            if deriv_on == "x":
                xt = ds_t
            else:
                wt = ds_t
        # The MXU step; in faithful-fp16 mode acc_ref is fp16 so the +=
        # re-rounds to binary16 every block, like the paper's FMA feedback
        # path.  The layout only changes which operand axes contract.
        acc_ref[...] += jax.lax.dot_general(
            xt, wt, _DIMS[layout],
            preferred_element_type=acc_ref.dtype,
        )
        return carry

    jax.lax.fori_loop(0, n_steps, _step, 0)
    z_ref[...] = _store_value(
        acc_ref[...], None if bias_ref is None else bias_ref[...],
        epilogue=epilogue, out_dtype=out_dtype)
    if db_ref is not None:
        db_ref[0] = db_acc[...]


def _stored_tile_shapes(tile: tiling.TileConfig, layout: str):
    """((x tile), (w tile)) in *stored* orientation for one layout."""
    if layout == "nn":
        return (tile.bm, tile.bn), (tile.bn, tile.bk)
    if layout == "nt":
        return (tile.bm, tile.bn), (tile.bk, tile.bn)
    return (tile.bn, tile.bm), (tile.bn, tile.bk)  # tn


@functools.partial(
    jax.jit,
    static_argnames=("tile", "policy", "epilogue", "layout", "grad_epilogue",
                     "grad_from_output", "bias_grad", "pipeline_depth",
                     "interpret"),
)
def redmule_matmul_pallas(
    x: jax.Array,
    w: jax.Array,
    bias: Optional[jax.Array] = None,
    deriv: Optional[jax.Array] = None,
    *,
    tile: tiling.TileConfig,
    policy: prec.Policy,
    epilogue: Optional[str] = None,
    layout: str = "nn",
    grad_epilogue: Optional[str] = None,
    grad_from_output: bool = False,
    bias_grad: bool = False,
    pipeline_depth: int = 2,
    interpret: bool = False,
):
    """Z = act(X @ W + bias) for 2D operands already padded to tile multiples.

    ``bias`` (optional) is a ``(1, K)`` row in the accumulation dtype;
    ``epilogue`` (optional) names an activation from
    :mod:`repro.core.epilogues`.  Both are applied inside the kernel's
    store-once step (no extra HBM pass).  ``layout`` selects the operand
    storage (see module docstring); the output is always ``(M, K)``.

    Backward fusion (the Engine's ``"fused_bwd_epilogue"`` capability):
    ``grad_epilogue`` + ``deriv`` apply ``act'`` to the dZ operand's tiles
    on load (``grad_from_output`` picks the output-form derivative;
    ``deriv`` must be stored exactly like the dZ operand — the x slot for
    "nt", the w slot for "tn").  ``bias_grad=True`` (only meaningful on the
    "tn" dW dispatch) returns ``(Z, db)`` where ``db`` is a
    ``(M/bm, 1, K)`` accum-dtype array whose every row is the full
    ``Σ_rows ds`` (each grid row sweeps the whole reduction; callers take
    row 0; the unit axis keeps the ``(1, bk)`` block a whole-dim block,
    as Mosaic's (8, 128) tiling rule requires).  ``pipeline_depth`` sets the number of buffer slots of the
    in-kernel K-loop: 1 = single-buffered (each step's DMA issues and
    completes before its FMA — no overlap, the minimal-VMEM schedule),
    2 = classic double buffering, deeper = more DMAs in flight."""
    _check_layout(layout)
    M, N, K = _logical_dims(x.shape, w.shape, layout)
    if layout == "nn":
        assert x.shape[1] == w.shape[0], (x.shape, w.shape)
    elif layout == "nt":
        assert x.shape[1] == w.shape[1], (x.shape, w.shape)
    else:
        assert x.shape[0] == w.shape[0], (x.shape, w.shape)
    assert M % tile.bm == 0 and N % tile.bn == 0 and K % tile.bk == 0, (
        f"shapes {(M, N, K)} not padded to tiles {tile}"
    )
    if bias is not None:
        assert bias.shape == (1, K), (bias.shape, K)
    if grad_epilogue is not None:
        assert layout in ("nt", "tn"), \
            "the fused backward epilogue is a transpose-layout contract"
        want = x.shape if _deriv_on(layout) == "x" else w.shape
        assert deriv is not None and deriv.shape == want, \
            (None if deriv is None else deriv.shape, want)
    if bias_grad:
        assert layout == "tn", "bias_grad rides on the dW (tn) dispatch"
    depth = max(1, int(pipeline_depth))
    grid = (M // tile.bm, K // tile.bk)
    n_steps = N // tile.bn
    x_tile, w_tile = _stored_tile_shapes(tile, layout)

    in_specs = [pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY)]
    operands = [x, w]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, tile.bk), lambda i, j: (0, j)))
        operands.append(bias)
    if grad_epilogue is not None:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        operands.append(deriv)

    out_shape = [jax.ShapeDtypeStruct((M, K), policy.out_dtype)]
    out_specs = [pl.BlockSpec((tile.bm, tile.bk), lambda i, j: (i, j))]
    if bias_grad:
        out_shape.append(
            jax.ShapeDtypeStruct((grid[0], 1, K), policy.accum_dtype))
        out_specs.append(
            pl.BlockSpec((1, 1, tile.bk), lambda i, j: (i, 0, j)))

    scratch = [pltpu.VMEM((tile.bm, tile.bk), policy.accum_dtype),
               pltpu.VMEM((depth, *x_tile), x.dtype),
               pltpu.VMEM((depth, *w_tile), w.dtype)]
    n_streams = 2
    if grad_epilogue is not None:
        d_tile = x_tile if _deriv_on(layout) == "x" else w_tile
        scratch.append(pltpu.VMEM((depth, *d_tile), deriv.dtype))
        n_streams = 3
    if bias_grad:
        scratch.append(pltpu.VMEM((1, tile.bk), policy.accum_dtype))
    scratch.append(pltpu.SemaphoreType.DMA((depth, n_streams)))

    kernel = functools.partial(
        _pipelined_kernel, n_steps=n_steps, depth=depth, tile=tile,
        layout=layout, out_dtype=policy.out_dtype,
        compute_dtype=policy.compute_dtype, epilogue=epilogue,
        grad_epilogue=grad_epilogue, grad_from_output=grad_from_output,
        bias_grad=bias_grad, has_bias=bias is not None)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs if bias_grad else out_specs[0],
        out_shape=out_shape if bias_grad else out_shape[0],
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
        name=f"redmule_matmul_{layout}",
    )(*operands)
    return out


def _load_compute(ref_tile, compute_dtype):
    """Upcast a loaded operand tile to the compute dtype (FP8 storage under
    the mixed-precision policies; a no-op for uniform policies)."""
    if ref_tile.dtype != compute_dtype:
        return ref_tile.astype(compute_dtype)
    return ref_tile


def _kernel_batched(x_ref, w_ref, z_ref, acc_ref, *, n_tiles: int, out_dtype,
                    compute_dtype, epilogue: Optional[str], layout: str):
    """The same X-stationary schedule under a leading batch grid dim.

    Block refs carry a unit batch dim ((1, bm, bn) etc.); the reduction is
    grid axis 3.  Operand tiles arrive in their storage dtype and are
    upcast to ``compute_dtype`` on load."""

    @pl.when(pl.program_id(3) == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        _load_compute(x_ref[0], compute_dtype),
        _load_compute(w_ref[0], compute_dtype), _DIMS[layout],
        preferred_element_type=acc_ref.dtype,
    )

    @pl.when(pl.program_id(3) == n_tiles - 1)
    def _store_once():
        z_ref[0] = _store_value(acc_ref[...], None, epilogue=epilogue,
                                out_dtype=out_dtype)


def _kernel_batched_bias(x_ref, w_ref, bias_ref, z_ref, acc_ref, *,
                         n_tiles: int, out_dtype, compute_dtype,
                         epilogue: Optional[str], layout: str):
    """Batched schedule with the shared (1, 1, bk) bias row in the store."""

    @pl.when(pl.program_id(3) == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        _load_compute(x_ref[0], compute_dtype),
        _load_compute(w_ref[0], compute_dtype), _DIMS[layout],
        preferred_element_type=acc_ref.dtype,
    )

    @pl.when(pl.program_id(3) == n_tiles - 1)
    def _store_once():
        z_ref[0] = _store_value(acc_ref[...], bias_ref[0],
                                epilogue=epilogue, out_dtype=out_dtype)


def _operand_specs_batched(tile: tiling.TileConfig, layout: str):
    if layout == "nn":
        return (pl.BlockSpec((1, tile.bm, tile.bn),
                             lambda b, i, j, k: (b, i, k)),
                pl.BlockSpec((1, tile.bn, tile.bk),
                             lambda b, i, j, k: (b, k, j)))
    if layout == "nt":
        return (pl.BlockSpec((1, tile.bm, tile.bn),
                             lambda b, i, j, k: (b, i, k)),
                pl.BlockSpec((1, tile.bk, tile.bn),
                             lambda b, i, j, k: (b, j, k)))
    # tn
    return (pl.BlockSpec((1, tile.bn, tile.bm),
                         lambda b, i, j, k: (b, k, i)),
            pl.BlockSpec((1, tile.bn, tile.bk),
                         lambda b, i, j, k: (b, k, j)))


def _layer_spec(bs: pl.BlockSpec, stacked: bool = False) -> pl.BlockSpec:
    """``bs`` under a one-scalar prefetch: the index map takes the layer
    ref last; a ``stacked`` operand's block reads that layer in place."""
    im = bs.index_map
    if stacked:
        return pl.BlockSpec((None, *bs.block_shape),
                            lambda *g: (g[-1][0], *im(*g[:-1])))
    return pl.BlockSpec(bs.block_shape, lambda *g: im(*g[:-1]))


@functools.partial(
    jax.jit,
    static_argnames=("tile", "policy", "epilogue", "layout", "interpret"),
)
def redmule_matmul_batched_pallas(
    x: jax.Array,
    w: jax.Array,
    bias: Optional[jax.Array] = None,
    w_index: Optional[jax.Array] = None,
    *,
    tile: tiling.TileConfig,
    policy: prec.Policy,
    epilogue: Optional[str] = None,
    layout: str = "nn",
    interpret: bool = False,
) -> jax.Array:
    """Z[b] = act(X[b] @ W[b] + bias) with the batch as a leading grid dim.

    Unlike a ``vmap`` wrapper (which multiplies the VMEM working set by B
    and hides the batch from the scheduler), the batch here is just the
    outermost parallel grid axis: one X/W/Z tile set is live at a time, so
    the tile choice sees the true per-core working set.

    ``bias`` (optional) is a ``(1, 1, K)`` row in the accumulation dtype,
    shared across the batch, folded — with ``epilogue`` — into the
    store-once step exactly like the 2D kernel (the PR-2 follow-up gap:
    the batched grid fuses the full bias+activation epilogue now).

    ``w_index`` (an int32 scalar): ``w`` carries one more leading axis, a
    stack of layers, and the kernel reads layer ``w_index`` of it in place
    — the index is a scalar-prefetch operand of the BlockSpecs, so no
    slice of the stack is copied out first."""
    _check_layout(layout)
    B = x.shape[0]
    wl = w.shape[1:] if w_index is not None else w.shape
    assert wl[0] == B, (x.shape, w.shape)
    M, N, K = _logical_dims(x.shape[1:], wl[1:], layout)
    assert M % tile.bm == 0 and N % tile.bn == 0 and K % tile.bk == 0, (
        f"shapes {(M, N, K)} not padded to tiles {tile}"
    )
    if bias is not None:
        assert bias.shape == (1, 1, K), (bias.shape, K)
    grid = (B, M // tile.bm, K // tile.bk, N // tile.bn)

    in_specs = list(_operand_specs_batched(tile, layout))
    operands = [x, w]
    if bias is None:
        kernel = functools.partial(_kernel_batched, n_tiles=grid[3],
                                   out_dtype=policy.out_dtype,
                                   compute_dtype=policy.compute_dtype,
                                   epilogue=epilogue, layout=layout)
    else:
        kernel = functools.partial(_kernel_batched_bias, n_tiles=grid[3],
                                   out_dtype=policy.out_dtype,
                                   compute_dtype=policy.compute_dtype,
                                   epilogue=epilogue, layout=layout)
        in_specs.append(pl.BlockSpec((1, 1, tile.bk),
                                     lambda b, i, j, k: (0, 0, j)))
        operands.append(bias)

    out_spec = pl.BlockSpec((1, tile.bm, tile.bk),
                            lambda b, i, j, k: (b, i, j))
    scratch = [pltpu.VMEM((tile.bm, tile.bk), policy.accum_dtype)]
    call = dict(
        out_shape=jax.ShapeDtypeStruct((B, M, K), policy.out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
        name=f"redmule_matmul_batched_{layout}",
    )
    if w_index is None:
        return pl.pallas_call(kernel, grid=grid, in_specs=in_specs,
                              out_specs=out_spec, scratch_shapes=scratch,
                              **call)(*operands)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=grid,
        in_specs=[_layer_spec(bs, stacked=n == 1)
                  for n, bs in enumerate(in_specs)],
        out_specs=_layer_spec(out_spec), scratch_shapes=scratch)
    return pl.pallas_call(lambda _, *refs: kernel(*refs),
                          grid_spec=grid_spec, **call)(
        jnp.reshape(w_index, (1,)).astype(jnp.int32), *operands)
