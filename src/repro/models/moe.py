"""Mixture-of-Experts on the RedMulE engine (DeepSeek-style).

Fine-grained experts are exactly the small-GEMM regime where the paper shows
utilization collapse (Fig 3d): a single 1408-wide expert GEMM over a few
tokens cannot fill the array.  The dispatch below therefore *groups* tokens
by expert (sort + capacity buffer) and runs all experts as one batched
RedMulE GEMM (E, C, d) x (E, d, f) — the fat-GEMM restoration the paper's
batching experiment (Fig 4d) performs for the AutoEncoder.

Expert-parallel sharding: the (E, ...) dimension carries the "experts"
logical axis -> the mesh "model" axis; GSPMD inserts the token all-to-all.

Dispatch is the sort-based, dropping implementation (MaxText/Switch style):
top-k -> stable sort by expert -> per-expert rank via one-hot cumsum ->
capacity clamp -> scatter into (E*C, d) -> batched GEMMs -> gather+combine.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core import engine
from repro.core import precision as prec
from repro.models import layers
from repro.models.layers import Param
from repro.runtime import sharding

__all__ = ["moe_schema", "moe_forward"]


def _combine_policy(policy: prec.Policy) -> prec.Policy:
    """Combiner precision: gate-weighted slot reduction in the datapath
    compute dtype with an fp32 accumulator/output (like the router, the
    combine wants full-precision arithmetic regardless of any FP8
    storage the expert GEMMs declare)."""
    return prec.Policy("moe_combine", policy.compute_dtype,
                       jnp.float32, jnp.float32)


def moe_schema(cfg) -> Dict[str, Any]:
    mo = cfg.moe
    d, E, f = cfg.d_model, mo.n_routed, mo.d_expert
    s: Dict[str, Any] = {
        "router": Param((d, E), ("embed", None)),
        "w_in": Param((E, d, 2 * f), ("experts", "embed_unsharded", "expert_ff")),
        "w_out": Param((E, f, d), ("experts", "expert_ff", "embed_unsharded")),
    }
    if mo.n_shared:
        fs = mo.n_shared * f
        s["shared"] = {
            "w_in": Param((d, 2 * fs), ("embed", "ff")),
            "w_out": Param((fs, d), ("ff", "embed")),
        }
    return s


def _dispatch_row(xs, ids, gate, *, E: int, k: int, C: int, dtype):
    """Dispatch one batch row. xs: (S, d), ids/gate: (S, k).

    Only *permutation* gathers/scatters are used (no duplicate-index
    scatter-adds): their transposes are permutations too, so the backward
    pass stays shard-local instead of lowering to full-tensor fp32
    all-reduces (observed with the classic token-indexed combine).

    Returns (buf (E, C, d), dest (S*k,), inv (S*k,), w_slot (S*k,))."""
    S = xs.shape[0]
    flat_e = ids.reshape(-1)                              # (S*k,)
    order = jnp.argsort(flat_e, stable=True)
    se = flat_e[order]
    oh = (se[:, None] == jnp.arange(E, dtype=se.dtype)[None, :]).astype(jnp.int32)
    rank = (jnp.cumsum(oh, axis=0) * oh).sum(-1) - 1      # rank within expert
    keep = rank < C
    dest = jnp.where(keep, se * C + rank, E * C)          # dropped -> spill row
    # token t occupies slots t*k..t*k+k-1: replicate rows, then permute
    x_rep = jnp.broadcast_to(xs[:, None], (S, k, xs.shape[1])).reshape(S * k, -1)
    x_sorted = jnp.take(x_rep, order, axis=0)             # permutation gather
    buf = jnp.zeros((E * C + 1, xs.shape[1]), dtype)
    buf = buf.at[dest].set(x_sorted.astype(dtype), mode="drop")
    inv = jnp.argsort(order)                              # sorted -> slot order
    w_slot = (gate.reshape(-1)[order] * keep).astype(jnp.float32)
    return buf[: E * C].reshape(E, C, -1), dest, inv, w_slot


def moe_forward(
    params: Dict[str, Any],
    x: jax.Array,  # (B, S, d)
    cfg,
    *,
    policy: prec.Policy,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Per-row dispatch (DP-local routing) + one EP layout change.

    Routing, sort and scatter are vmapped over the batch dim, so every DP
    shard dispatches its own tokens with zero cross-shard traffic; the only
    communication is the (B, E, C, d) -> expert-sharded constraint (the MoE
    all-to-all) around the batched expert GEMM.
    """
    mo = cfg.moe
    B, S, d = x.shape
    E, k, f = mo.n_routed, mo.top_k, mo.d_expert
    # dispatch math must be batch-local: pin x here (upstream attention
    # leaves the hidden d-sharded over TP, which would turn every gather
    # below into a cross-shard select+all-reduce)
    x = sharding.constrain_both(x, "batch", None, None)

    # ---- router (fp32 logits — routing decisions want full precision) ----
    logits = engine.matmul(
        x, params["router"],
        policy=prec.Policy("router", policy.compute_dtype, jnp.float32, jnp.float32),
    )                                                     # (B, S, E) fp32
    logits = sharding.constrain(logits, "batch", None, None)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, ids = jax.lax.top_k(probs, k)                   # (B, S, k)
    if mo.norm_topk_prob:
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # ---- load-balance aux (Switch-style) + router z-loss ----
    counts = jnp.zeros((E,), jnp.float32).at[ids.reshape(-1)].add(1.0)
    frac_tokens = counts / (B * S * k)
    mean_prob = probs.mean(axis=(0, 1))
    aux_loss = E * jnp.sum(frac_tokens * mean_prob)
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)

    # ---- per-row sort-based dispatch with capacity ----
    C = int(math.ceil(S * k / E * mo.capacity_factor))
    C = -(-C // 8) * 8  # sublane-align the expert batch
    bufs, dest, inv, w_slot = jax.vmap(
        functools.partial(_dispatch_row, E=E, k=k, C=C,
                          dtype=policy.compute_dtype))(x, ids, gate)
    # EP layout change: batch-sharded rows -> expert-sharded GEMM operands
    # (value expert-sharded; cotangent must re-enter the dispatch-scatter
    #  transpose batch-local, hence the asymmetric pin)
    bufs = sharding.constrain_fb(
        bufs, ("batch", "experts", None, None), ("batch", None, None, None))

    # ---- all experts as ONE grouped RedMulE GEMM (fat-GEMM restoration) ----
    h = engine.grouped_matmul(bufs, params["w_in"], policy=policy)  # (B, E, C, 2f)
    g_, u_ = jnp.split(h, 2, axis=-1)
    h = layers.activation(g_, cfg.act) * u_
    h = sharding.constrain(h, "batch", "experts", None, "expert_ff")
    out = engine.grouped_matmul(h, params["w_out"], policy=policy)  # (B, E, C, d)
    # return all-to-all: expert-sharded -> batch-local BEFORE the combine
    # gather, else GSPMD lowers the gather-from-sharded as fp32 partial
    # all-reduces of the full (S*k, d) slot tensor (7x the traffic)
    out = sharding.constrain_fb(
        out, ("batch", None, None, None), ("batch", "experts", None, None))

    # ---- combine: ONE permutation gather + a local k-reduction ----
    flat = jnp.concatenate(
        [out.reshape(B, E * C, d), jnp.zeros((B, 1, d), out.dtype)], axis=1)
    flat = sharding.constrain_both(flat, "batch", None, None)
    # fold the inverse sort into the slot indices (index gathers are cheap)
    dest_u = jnp.take_along_axis(dest, inv, axis=1)             # (B, S*k)
    w_u = jnp.take_along_axis(w_slot, inv, axis=1)
    slot_u = jnp.take_along_axis(flat, dest_u[..., None], axis=1)  # (B,S*k,d)
    slot_u = sharding.constrain_both(slot_u, "batch", None, None)
    # combine is a contraction over the k routed slots — an Engine GEMM
    # like any other (events, autotuned tiles), fp32-accumulated with the
    # operands staying in the 16-bit compute dtype
    y = engine.einsum2d(
        "bskd,bsk->bsd", slot_u.reshape(B, S, k, d), w_u.reshape(B, S, k),
        policy=_combine_policy(policy)).astype(x.dtype)
    y = sharding.constrain_both(y, "batch", None, None)

    if "shared" in params:
        y = y + layers.mlp_glu(params["shared"], x, act=cfg.act, policy=policy)

    metrics = {
        "moe_aux_loss": aux_loss,
        "moe_z_loss": z_loss,
        "moe_drop_frac": (dest >= E * C).astype(jnp.float32).mean(),
    }
    return y, metrics


# --------------------------------------------------------------------- #
# Manual expert parallelism (shard_map) — the production EP path
# --------------------------------------------------------------------- #
def moe_forward_shard_map(
    params: Dict[str, Any],
    x: jax.Array,  # (B, S, d) — batch sharded over DP axes
    cfg,
    *,
    policy: prec.Policy,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """EP with explicit ``all_to_all``s inside ``shard_map``.

    GSPMD's transposed scatter/gathers for the sort-based dispatch lower to
    full-tensor fp32 all-reduces (§Perf, measured ~7x the necessary wire).
    Under shard_map the only collectives are the two token all-to-alls whose
    transposes are all-to-alls again — backward traffic == forward traffic
    by construction.

    Requires: mesh with a "model" axis dividing n_routed; tokens already
    batch-sharded.  Falls back to ``moe_forward`` outside a mesh.
    """
    mesh = jax.sharding.get_abstract_mesh()
    dp_size = 1
    if not mesh.empty:
        for a in ("pod", "data"):
            dp_size *= mesh.shape.get(a, 1)
    if (mesh is None or mesh.empty or "model" not in mesh.shape
            or cfg.moe.n_routed % mesh.shape["model"] != 0
            or (x.shape[0] // max(dp_size, 1)) % mesh.shape["model"] != 0):
        return moe_forward(params, x, cfg, policy=policy)

    from jax.sharding import PartitionSpec as P

    mo = cfg.moe
    B, S, d = x.shape
    E, k = mo.n_routed, mo.top_k
    ep = mesh.shape["model"]
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    dp = dp_axes[0] if len(dp_axes) == 1 else dp_axes

    def local_fn(w_in_l, w_out_l, router_w, x_full):
        # x_full: (B_loc, S, d), replicated over the model axis.  Slice the
        # rows across model peers FIRST — otherwise every TP peer would
        # dispatch and compute the same tokens (16x redundant work+wire).
        Bfull = x_full.shape[0]
        mi = jax.lax.axis_index("model")
        rows = Bfull // ep
        x_l = jax.lax.dynamic_slice_in_dim(x_full, mi * rows, rows, axis=0)
        Bl = x_l.shape[0]
        logits = engine.matmul(
            x_l, router_w,
            policy=prec.Policy("router", policy.compute_dtype,
                               jnp.float32, jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        gate, ids = jax.lax.top_k(probs, k)
        if mo.norm_topk_prob:
            gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
        C = int(math.ceil(S * k / E * mo.capacity_factor))
        C = -(-C // 8) * 8
        bufs, dest, inv, w_slot = jax.vmap(
            functools.partial(_dispatch_row, E=E, k=k, C=C,
                              dtype=policy.compute_dtype))(x_l, ids, gate)
        # (B_loc, E, C, d) -> exchange expert shards over the model axis:
        # peer-major layout + symmetric tiled all_to_all (its transpose is
        # an all_to_all of identical shape — backward wire == forward wire)
        t = bufs.reshape(Bl, ep, E // ep, C, d)
        t = jnp.moveaxis(t, 1, 0)                          # (ep, Bl, E/ep, C, d)
        t = jax.lax.all_to_all(t, "model", split_axis=0, concat_axis=0,
                               tiled=True)                 # axis0 now = source peer
        t = jnp.moveaxis(t, 2, 0)                          # (E/ep, ep, Bl, C, d)

        h = engine.grouped_matmul(
            t.reshape(E // ep, -1, d), w_in_l, policy=policy)
        g_, u_ = jnp.split(h, 2, axis=-1)
        h = layers.activation(g_, cfg.act) * u_
        out = engine.grouped_matmul(h, w_out_l, policy=policy)  # (E/ep, ep*Bl*C, d)

        out = out.reshape(E // ep, ep, Bl, C, d)
        out = jnp.moveaxis(out, 0, 2)                      # (ep, Bl, E/ep, C, d)
        out = jax.lax.all_to_all(out, "model", split_axis=0, concat_axis=0,
                                 tiled=True)               # back to expert-major
        out = jnp.moveaxis(out, 0, 1).reshape(Bl, E, C, d)

        flat = jnp.concatenate(
            [out.reshape(Bl, E * C, d), jnp.zeros((Bl, 1, d), out.dtype)],
            axis=1)
        dest_u = jnp.take_along_axis(dest, inv, axis=1)
        w_u = jnp.take_along_axis(w_slot, inv, axis=1)
        slot_u = jnp.take_along_axis(flat, dest_u[..., None], axis=1)
        y = engine.einsum2d(
            "bskd,bsk->bsd", slot_u.reshape(Bl, S, k, d),
            w_u.reshape(Bl, S, k),
            policy=_combine_policy(policy)).astype(x_l.dtype)
        # restore the model-replicated row layout
        y = jax.lax.all_gather(y, "model", axis=0, tiled=True)  # (B_loc, S, d)

        # every device now routes a distinct token slice: stats reduce over
        # data AND model axes
        all_axes = dp_axes + ("model",)
        counts = jnp.zeros((E,), jnp.float32).at[ids.reshape(-1)].add(1.0)
        aux = E * jnp.sum(
            jax.lax.psum(counts, all_axes) /
            jax.lax.psum(jnp.float32(S * k * Bl), all_axes)
            * jax.lax.pmean(probs.mean(axis=(0, 1)), all_axes))
        z = jax.lax.pmean(
            jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2), ("model",))
        drop = jax.lax.pmean(
            (dest >= E * C).astype(jnp.float32).mean(), ("model",))
        return y, aux, z, drop

    in_specs = (
        P("model", None, None),   # w_in  (E, d, 2f)
        P("model", None, None),   # w_out (E, f, d)
        P(),                      # router (replicated)
        P(dp, None, None),        # x
    )
    out_specs = (P(dp, None, None), P(), P(), P())
    # instrumentation: local_fn is traced once with per-shard shapes but
    # executes once per (dp x model) shard — the axes in_specs partitions
    # over — so carry that count as the event multiplier; engine_flops
    # stays a *global* count, consistent with the globally-shaped GEMMs
    # traced outside shard_map
    n_shards = dp_size * ep
    with engine.repeat(n_shards):
        y, aux, z, drop = jax.shard_map(
            local_fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )(params["w_in"], params["w_out"], params["router"], x)

    if "shared" in params:
        y = y + layers.mlp_glu(params["shared"], x, act=cfg.act, policy=policy)
    metrics = {"moe_aux_loss": aux, "moe_z_loss": z, "moe_drop_frac": drop}
    return y, metrics
