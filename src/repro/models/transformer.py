"""Model composition: decoder LMs (dense / MoE / xLSTM / Hymba / audio / VLM).

Layers are scanned (stacked params) so HLO size is O(1) in depth.
Heterogeneous stacks are expressed structurally:

  * dense/audio/vlm — one scanned stack of attention blocks;
  * moe (DeepSeek)  — an unstacked ``layer0`` (dense FFN) + scanned MoE stack;
  * xlstm           — scanned super-blocks of (7 mLSTM + 1 sLSTM);
  * hymba           — one scanned stack of parallel attn+SSM blocks with a
                      per-layer sliding-window array (full-attn layers get a
                      2^30 window);
  * pattern         — a published ``layer_types`` list of Mamba2 and
                      attention layers (Granite-4.0-H): scanned over whole
                      periods of the pattern, the period's layers unrolled in
                      its order, so the compile is one period's size.

Public API: ``schema / init_params / param_specs / abstract_params /
forward / loss_fn / serve_step / init_cache / count_params``.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import engine
from repro.models import attention, layers, moe, ssm
from repro.models.layers import Param
from repro.runtime import sharding

__all__ = [
    "schema", "init_params", "param_specs", "abstract_params",
    "forward", "loss_fn", "serve_step", "init_cache", "count_params",
    "window_array", "layer_period",
]

BIG_WINDOW = 1 << 30


# --------------------------------------------------------------------- #
# Schemas
# --------------------------------------------------------------------- #
def _norm_param(cfg) -> Param:
    return Param((cfg.d_model,), (None,), init="ones")


def _mlp_schema(cfg, d_ff: int) -> Dict[str, Any]:
    d = cfg.d_model
    if cfg.mlp == "glu":
        return {
            "w_in": Param((d, 2 * d_ff), ("embed", "ff")),
            "w_out": Param((d_ff, d), ("ff", "embed")),
        }
    return {
        "w_in": Param((d, d_ff), ("embed", "ff")),
        "w_out": Param((d_ff, d), ("ff", "embed")),
    }


def _attn_schema(cfg) -> Dict[str, Any]:
    return attention.mla_schema(cfg) if cfg.mla else attention.gqa_schema(cfg)


def _attn_block_schema(cfg, d_ff: Optional[int] = None) -> Dict[str, Any]:
    return {
        "ln1": _norm_param(cfg),
        "attn": _attn_schema(cfg),
        "ln2": _norm_param(cfg),
        "mlp": _mlp_schema(cfg, d_ff or cfg.d_ff),
    }


def _moe_block_schema(cfg) -> Dict[str, Any]:
    return {
        "ln1": _norm_param(cfg),
        "attn": _attn_schema(cfg),
        "ln2": _norm_param(cfg),
        "moe": moe.moe_schema(cfg),
    }


def _hymba_block_schema(cfg) -> Dict[str, Any]:
    return {
        "ln1": _norm_param(cfg),
        "attn": attention.gqa_schema(cfg),
        "attn_out_norm": _norm_param(cfg),
        "mamba": ssm.mamba_schema(cfg),
        "mamba_out_norm": _norm_param(cfg),
        "ln2": _norm_param(cfg),
        "mlp": _mlp_schema(cfg, cfg.d_ff),
    }


def layer_period(cfg) -> Tuple[str, ...]:
    """The shortest repeating unit of ``cfg.layer_types``."""
    t = tuple(cfg.layer_types)
    for p in range(1, len(t) + 1):
        if len(t) % p == 0 and t == t[:p] * (len(t) // p):
            return t[:p]
    raise ValueError("empty layer_types")


def _pattern_schemas(cfg) -> Dict[str, Dict[str, Any]]:
    return {
        "mamba": {
            "ln1": _norm_param(cfg),
            "mamba": ssm.mamba_schema(cfg),
            "ln2": _norm_param(cfg),
            "mlp": _mlp_schema(cfg, cfg.d_ff),
        },
        "attention": _attn_block_schema(cfg),
    }


def _pattern_schema(cfg) -> Dict[str, Any]:
    """Per layer kind, its layers stacked (periods, layers of the kind in
    one period)."""
    period = layer_period(cfg)
    n = cfg.n_layers // len(period)
    return {kind: layers.stack_schema(
                layers.stack_schema(one, period.count(kind), None), n)
            for kind, one in _pattern_schemas(cfg).items() if kind in period}


def _xlstm_super_schema(cfg) -> Dict[str, Any]:
    n_m = cfg.ssm.slstm_period - 1
    m_block = {"ln": _norm_param(cfg), "cell": ssm.mlstm_schema(cfg)}
    s_block = {"ln": _norm_param(cfg), "cell": ssm.slstm_schema(cfg)}
    return {
        "mlstm": layers.stack_schema(m_block, n_m),
        "slstm": s_block,
    }


def schema(cfg) -> Dict[str, Any]:
    d, v = cfg.d_model, cfg.vocab_size
    s: Dict[str, Any] = {
        "embed": Param((v, d), ("vocab", "embed"), init="embed"),
        "final_norm": _norm_param(cfg),
    }
    if not cfg.tie_embeddings:
        s["lm_head"] = Param((d, v), ("embed", "vocab"))

    kind = cfg.block_kind
    if kind == "attn":
        s["layers"] = layers.stack_schema(_attn_block_schema(cfg), cfg.n_layers)
    elif kind == "moe":
        nd = cfg.moe.first_dense
        s["layer0"] = _attn_block_schema(cfg, cfg.moe.dense_ff)
        assert nd == 1, "only first_dense=1 supported"
        s["layers"] = layers.stack_schema(_moe_block_schema(cfg), cfg.n_layers - nd)
    elif kind == "hymba":
        s["layers"] = layers.stack_schema(_hymba_block_schema(cfg), cfg.n_layers)
    elif kind == "pattern":
        s["layers"] = _pattern_schema(cfg)
    elif kind == "xlstm":
        n_super, rem = divmod(cfg.n_layers, cfg.ssm.slstm_period)
        assert rem == 0, f"n_layers {cfg.n_layers} % period {cfg.ssm.slstm_period}"
        s["layers"] = layers.stack_schema(_xlstm_super_schema(cfg), n_super)
    else:
        raise ValueError(kind)
    return s


def init_params(rng: jax.Array, cfg):
    return layers.init_tree(rng, schema(cfg), dtype=jnp.dtype(cfg.param_dtype))


def param_specs(cfg, rules: Optional[sharding.Rules]):
    return layers.spec_tree(schema(cfg), rules)


def abstract_params(cfg):
    return layers.abstract_tree(schema(cfg), dtype=jnp.dtype(cfg.param_dtype))


def count_params(cfg, active_only: bool = False) -> int:
    import numpy as np

    total = 0
    routed = 0

    def go(node, path):
        nonlocal total, routed
        if isinstance(node, Param):
            n = int(np.prod(node.shape)) if node.shape else 1
            total += n
            if "experts" in node.axes:
                routed += n
            return
        for k, v in node.items():
            go(v, path + (k,))

    go(schema(cfg), ())
    if active_only and cfg.moe:
        inactive = routed * (cfg.moe.n_routed - cfg.moe.top_k) / cfg.moe.n_routed
        return int(total - inactive)
    return total


def window_array(cfg) -> Optional[jax.Array]:
    """Per-layer attention windows (hymba); None when not applicable."""
    if cfg.sliding_window is None:
        return None
    w = [
        BIG_WINDOW if i in cfg.full_attn_layers else cfg.sliding_window
        for i in range(cfg.n_layers)
    ]
    return jnp.asarray(w, jnp.int32)


# --------------------------------------------------------------------- #
# Blocks
# --------------------------------------------------------------------- #
def _norm(cfg, x, scale):
    if cfg.norm == "layernorm":
        return layers.layernorm(x, scale)
    return layers.rmsnorm(x, scale, cfg.norm_eps)


def _at_layer(fn, cache, layer):
    """``fn(layer's cache)`` on layer ``layer`` of a cache stacked over
    layers, its new cache (the result's second item) written back in
    place; ``layer`` None: ``cache`` is the layer's own."""
    if cache is None or layer is None:
        return fn(cache)
    out = fn(jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, False), cache))
    new = jax.tree.map(
        lambda a, u: jax.lax.dynamic_update_index_in_dim(
            a, u.astype(a.dtype), layer, 0), cache, out[1])
    return (out[0], new, *out[2:])


def _run_attn(cfg, p, h, *, pos, cache, window, policy, kv_group_sizes=None,
              layer=None):
    if cfg.mla:
        return _at_layer(lambda c: attention.mla_attention(
            p, h, cfg, pos_offset=pos, cache=c, policy=policy,
            q_chunk=cfg.q_chunk, kv_group_sizes=kv_group_sizes), cache, layer)
    return attention.gqa_attention(
        p, h, cfg, pos_offset=pos, cache=cache, layer=layer, window=window,
        policy=policy, q_chunk=cfg.q_chunk, kv_group_sizes=kv_group_sizes)


def _attn_block(p, h, cfg, *, pos, cache, window, policy, d_ff=None,
                kv_group_sizes=None, layer=None):
    a, cache = _run_attn(cfg, p["attn"], _norm(cfg, h, p["ln1"]),
                         pos=pos, cache=cache, window=window, policy=policy,
                         kv_group_sizes=kv_group_sizes, layer=layer)
    h = h + a
    if cfg.mlp == "glu":
        m = layers.mlp_glu(p["mlp"], _norm(cfg, h, p["ln2"]), act=cfg.act, policy=policy)
    else:
        hh = engine.linear(_norm(cfg, h, p["ln2"]), p["mlp"]["w_in"],
                           activation=cfg.act, policy=policy)
        m = engine.matmul(hh, p["mlp"]["w_out"], policy=policy)
    return h + m, cache, {}


def _moe_block(p, h, cfg, *, pos, cache, policy, kv_group_sizes=None,
               layer=None):
    a, cache = _run_attn(cfg, p["attn"], _norm(cfg, h, p["ln1"]),
                         pos=pos, cache=cache, window=None, policy=policy,
                         kv_group_sizes=kv_group_sizes, layer=layer)
    h = h + a
    moe_fn = (moe.moe_forward_shard_map if cfg.moe_impl == "shard_map"
              else moe.moe_forward)
    m, metrics = moe_fn(p["moe"], _norm(cfg, h, p["ln2"]), cfg, policy=policy)
    return h + m, cache, metrics


def _hymba_block(p, h, cfg, *, pos, cache, window, policy):
    hn = _norm(cfg, h, p["ln1"])
    a, attn_cache = attention.gqa_attention(
        p["attn"], hn, cfg, pos_offset=pos,
        cache=None if cache is None else cache["attn"],
        window=window, policy=policy, q_chunk=cfg.q_chunk)
    m, ssm_state = ssm.mamba_mixer(
        p["mamba"], hn, cfg, policy=policy,
        state=None if cache is None else cache["ssm"])
    fused = 0.5 * (_norm(cfg, a, p["attn_out_norm"]) + _norm(cfg, m, p["mamba_out_norm"]))
    h = h + fused
    mlp_out = layers.mlp_glu(p["mlp"], _norm(cfg, h, p["ln2"]), act=cfg.act, policy=policy)
    new_cache = None if cache is None else {"attn": attn_cache, "ssm": ssm_state}
    return h + mlp_out, new_cache, {}


def _pattern_layer(kind, p, h, cfg, *, pos, cache, layer, policy,
                   kv_group_sizes, fresh):
    """One layer of a pattern stack: its mixer (Mamba2 or NoPE GQA) and
    MLP, each branch scaled by ``residual_multiplier``.  ``cache`` is the
    kind's cache stacked over its layers, ``layer`` this layer's index in
    it.  A parked slot's Mamba state runs on unread; admission overwrites
    it whole (``kv_cache.insert_slot``), so every request starts from its
    prefill's state."""
    x = _norm(cfg, h, p["ln1"])
    if kind == "mamba":
        with jax.named_scope("mamba_mixer"):
            m, new_cache = _at_layer(lambda c: ssm.mamba_mixer(
                p["mamba"], x, cfg, policy=policy, state=c,
                carry=not fresh), cache, layer)
            if cache is None:
                new_cache = None
    else:
        with jax.named_scope("attention"):
            m, new_cache = attention.gqa_attention(
                p["attn"], x, cfg, pos_offset=pos, cache=cache, layer=layer,
                window=None, policy=policy, q_chunk=cfg.q_chunk,
                kv_group_sizes=kv_group_sizes)
    r = cfg.residual_multiplier
    h = h + r * m
    mlp = layers.mlp_glu(p["mlp"], _norm(cfg, h, p["ln2"]), act=cfg.act,
                         policy=policy)
    return h + r * mlp, new_cache


def _pattern_stack(cfg, params, h, cache, *, pos, policy, kv_group_sizes,
                   fresh):
    """Scan over whole periods of the layer pattern.  The cache rides the
    scan's carry, each kind's layers on one flat axis, and each layer
    updates its own slice of it in place."""
    period = layer_period(cfg)
    n = cfg.n_layers // len(period)
    has_cache = cache is not None
    count = {kind: period.count(kind) for kind in params}
    if has_cache:   # (periods, layers of the kind) -> one axis of layers
        cache = {k: jax.tree.map(lambda a: a.reshape(-1, *a.shape[2:]), c)
                 for k, c in cache.items()}

    def at(a, i, j):
        """Layer (period i, index j) of a stacked leaf."""
        start = (i, j) + (0,) * (a.ndim - 2)
        return jax.lax.dynamic_slice(a, start, (1, 1) + a.shape[2:])[0, 0]

    def body(carry, i):
        h, c = carry
        h = sharding.constrain(h, "batch", "seq_sharded", None)
        seen = {k: 0 for k in params}
        for kind in period:
            j = seen[kind]
            seen[kind] += 1
            h, lc = _pattern_layer(
                kind, jax.tree.map(lambda a: at(a, i, j), params[kind]), h,
                cfg, pos=pos, cache=c[kind] if has_cache else None,
                layer=i * count[kind] + j, policy=policy,
                kv_group_sizes=kv_group_sizes, fresh=fresh)
            if has_cache:
                c = dict(c, **{kind: lc})
        return (h, c), 0

    # the weights are the loop's invariants and each layer slices its own,
    # so no period's weights are copied out whole
    with engine.repeat(n):  # body traced once, runs n periods
        (h, cache), _ = jax.lax.scan(
            _remat(cfg, body), (h, cache if has_cache else 0), jnp.arange(n))
    if not has_cache:
        return h, None
    return h, {k: jax.tree.map(
        lambda a: a.reshape(n, count[k], *a.shape[1:]), c)
        for k, c in cache.items()}


def _xlstm_super_block(p, h, cfg, *, cache, policy):
    """7 scanned mLSTM blocks + 1 sLSTM block."""

    m_cache = None if cache is None else cache["mlstm"]
    if m_cache is None:
        # training/prefill-from-zero: in-sequence state starts at zero
        # inside the chunked engine; nothing is carried across layers
        def m_body(hh, lp):
            out, _ = ssm.mlstm_block(
                lp["cell"], _norm(cfg, hh, lp["ln"]), cfg, policy=policy)
            return hh + out, 0
        n_m = jax.tree_util.tree_leaves(p["mlstm"])[0].shape[0]
        with engine.repeat(n_m):
            h, m_states = jax.lax.scan(m_body, h, p["mlstm"])
        m_states = None
    else:
        def m_body(hh, xs):
            lp, st = xs
            out, st_new = ssm.mlstm_block(
                lp["cell"], _norm(cfg, hh, lp["ln"]), cfg, policy=policy, state=st)
            return hh + out, st_new
        n_m = jax.tree_util.tree_leaves(p["mlstm"])[0].shape[0]
        with engine.repeat(n_m):
            h, m_states = jax.lax.scan(m_body, h, (p["mlstm"], m_cache))

    s_cache = None if cache is None else cache["slstm"]
    out, s_state = ssm.slstm_block(
        p["slstm"]["cell"], _norm(cfg, h, p["slstm"]["ln"]), cfg,
        policy=policy, state=s_cache)
    h = h + out
    new_cache = None if cache is None else {"mlstm": m_states, "slstm": s_state}
    return h, new_cache, {}


# --------------------------------------------------------------------- #
# Stacks
# --------------------------------------------------------------------- #
def _remat(cfg, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "full":
        return jax.checkpoint(fn)
    return jax.checkpoint(
        fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)


def _scan_stack(cfg, block_fn, stack_params, h, cache_stack, windows):
    """Generic layer scan. cache_stack/windows may be None.  The cache
    rides the scan's carry: ``block_fn`` gets the whole stack and the
    layer's index, and updates that layer's slice in place."""
    has_cache = cache_stack is not None
    has_win = windows is not None

    def body(carry, xs):
        h, aux = carry[:2]
        lp = xs[0]
        win = xs[1] if has_win else None
        c, li = (carry[2], xs[-1]) if has_cache else (None, None)
        # sequence parallelism: residual stream (and the per-layer saved
        # activations) live sequence-sharded over the TP axis between
        # blocks; no-op unless rules enable seq_sharded
        h = sharding.constrain(h, "batch", "seq_sharded", None)
        h, c, m = block_fn(lp, h, cache=c, layer=li, window=win)
        h = sharding.constrain(h, "batch", "seq_sharded", None)
        aux = {k: aux[k] + m.get(k, 0.0) for k in aux}
        return ((h, aux, c) if has_cache else (h, aux)), 0

    aux0 = (
        {k: jnp.zeros((), jnp.float32)
         for k in ("moe_aux_loss", "moe_z_loss", "moe_drop_frac")}
        if cfg.block_kind == "moe" else {}
    )
    n_layers = jax.tree_util.tree_leaves(stack_params)[0].shape[0]
    xs: Tuple = (stack_params,)
    if has_win:
        xs = xs + (windows,)
    if has_cache:
        xs = xs + (jnp.arange(n_layers),)
    init = (h, aux0, cache_stack) if has_cache else (h, aux0)
    with engine.repeat(n_layers):  # body traced once, runs n_layers times
        out, _ = jax.lax.scan(_remat(cfg, body), init, xs)
    return out[0], (out[2] if has_cache else None), out[1]


# --------------------------------------------------------------------- #
# Forward / loss / serve
# --------------------------------------------------------------------- #
def forward(
    params: Dict[str, Any],
    cfg,
    batch: Dict[str, jax.Array],
    *,
    cache: Optional[Dict[str, Any]] = None,
    pos: jax.Array | int = 0,
    last_only: bool = False,
    head: bool = True,
    kv_group_sizes: Optional[Any] = None,
) -> Tuple[jax.Array, Optional[Dict[str, Any]], Dict[str, jax.Array]]:
    policy = cfg.policy
    # a prompt prefilled from position 0: recurrent layers start from zero
    fresh = isinstance(pos, int) and pos == 0
    pos = jnp.asarray(pos, jnp.int32)

    if "embeddings" in batch:
        h = batch["embeddings"].astype(policy.compute_dtype)
    else:
        h = params["embed"][batch["inputs"]].astype(policy.compute_dtype)
    if cfg.embedding_multiplier != 1.0:
        h = h * cfg.embedding_multiplier
    h = sharding.constrain(h, "batch", "seq_sharded", None)

    kind = cfg.block_kind
    new_cache: Dict[str, Any] = {}
    if kind == "attn":
        fn = lambda lp, hh, *, cache, layer, window: _attn_block(
            lp, hh, cfg, pos=pos, cache=cache, window=window, policy=policy,
            kv_group_sizes=kv_group_sizes, layer=layer)
        h, nc, aux = _scan_stack(
            cfg, fn, params["layers"], h,
            None if cache is None else cache["layers"], window_array(cfg))
        new_cache["layers"] = nc
    elif kind == "moe":
        c0 = None if cache is None else cache["layer0"]
        h, nc0, _ = _attn_block(
            params["layer0"], h, cfg, pos=pos, cache=c0, window=None,
            policy=policy, d_ff=cfg.moe.dense_ff,
            kv_group_sizes=kv_group_sizes)
        fn = lambda lp, hh, *, cache, layer, window: _moe_block(
            lp, hh, cfg, pos=pos, cache=cache, policy=policy,
            kv_group_sizes=kv_group_sizes, layer=layer)
        h, nc, aux = _scan_stack(
            cfg, fn, params["layers"], h,
            None if cache is None else cache["layers"], None)
        new_cache["layer0"] = nc0
        new_cache["layers"] = nc
    elif kind == "hymba":
        fn = lambda lp, hh, *, cache, layer, window: _at_layer(
            lambda c: _hymba_block(lp, hh, cfg, pos=pos, cache=c,
                                   window=window, policy=policy),
            cache, layer)
        h, nc, aux = _scan_stack(
            cfg, fn, params["layers"], h,
            None if cache is None else cache["layers"], window_array(cfg))
        new_cache["layers"] = nc
    elif kind == "pattern":
        h, nc = _pattern_stack(
            cfg, params["layers"], h,
            None if cache is None else cache["layers"], pos=pos,
            policy=policy, kv_group_sizes=kv_group_sizes, fresh=fresh)
        new_cache["layers"] = nc
        aux = {}
    elif kind == "xlstm":
        fn = lambda lp, hh, *, cache, layer, window: _at_layer(
            lambda c: _xlstm_super_block(lp, hh, cfg, cache=c, policy=policy),
            cache, layer)
        h, nc, aux = _scan_stack(
            cfg, fn, params["layers"], h,
            None if cache is None else cache["layers"], None)
        new_cache["layers"] = nc
    else:
        raise ValueError(kind)

    if last_only:
        h = h[:, -1:]  # serving: never materialize (B, S, V) prompt logits
    h = _norm(cfg, h, params["final_norm"])
    if not head:
        return h, (new_cache if cache is not None else None), aux
    logits = _head(params, cfg, h)
    return logits, (new_cache if cache is not None else None), aux


def _head(params, cfg, h):
    w_head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = engine.matmul(h, w_head, policy=cfg.policy)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return sharding.constrain(logits, "batch", "seq_sharded", "vocab")


def _chunked_ce(params, cfg, h, labels) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Fused/chunked CE: the (B, S, V) logits tensor is never materialized.

    Scans batch-row chunks; each chunk's vocab GEMM + log-softmax is inside
    a jax.checkpoint so backward recomputes the chunk logits instead of
    storing them.  Peak extra memory: one chunk of fp32 logits."""
    B = h.shape[0]
    c = max(1, min(cfg.ce_chunk, B))
    n = -(-B // c)
    pad = n * c - B
    if pad:
        h = jnp.concatenate([h, jnp.zeros((pad, *h.shape[1:]), h.dtype)])
        labels = jnp.concatenate(
            [labels, jnp.full((pad, labels.shape[1]), -1, labels.dtype)])

    @jax.checkpoint
    def chunk(h_c, y_c):
        lf = _head(params, cfg, h_c).astype(jnp.float32)
        lse = jax.nn.logsumexp(lf, axis=-1)
        gold = jnp.take_along_axis(
            lf, jnp.maximum(y_c, 0)[..., None], axis=-1)[..., 0]
        mask = (y_c >= 0).astype(jnp.float32)
        return jnp.sum((lse - gold) * mask), jnp.sum(mask)

    def body(carry, xs):
        tot, cnt = carry
        s, m = chunk(*xs)
        return (tot + s, cnt + m), 0

    hs = h.reshape(n, c, *h.shape[1:])
    ys = labels.reshape(n, c, labels.shape[1])
    with engine.repeat(n):  # CE chunks: body traced once, runs n times
        (tot, cnt), _ = jax.lax.scan(
            body, (jnp.float32(0), jnp.float32(0)), (hs, ys))
    loss = tot / jnp.maximum(cnt, 1.0)
    return loss, {"loss": loss, "ntokens": cnt}


def loss_fn(params, cfg, batch) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    if cfg.ce_chunk:
        h, _, aux = forward(params, cfg, batch, head=False)
        loss, metrics = _chunked_ce(params, cfg, h, batch["labels"])
    else:
        logits, _, aux = forward(params, cfg, batch)
        loss, metrics = layers.cross_entropy(logits, batch["labels"])
    if cfg.moe:
        loss = loss + cfg.moe.aux_weight * aux["moe_aux_loss"] / max(cfg.n_layers - 1, 1)
        loss = loss + cfg.moe.z_weight * aux["moe_z_loss"] / max(cfg.n_layers - 1, 1)
        metrics.update({k: v for k, v in aux.items()})
    metrics["loss"] = loss
    return loss, metrics


def serve_step(params, cfg, tokens, cache, pos, *, kv_group_sizes=None):
    """One decode step: tokens (B, 1) + cache @ pos -> (logits (B, V), cache').

    ``pos`` may be a scalar (uniform batch — the classic greedy loop) or a
    per-slot ``(B,)`` vector (the serving scheduler's continuous batch).
    ``kv_group_sizes`` (optional, per-slot valid kv lengths) routes the
    decode score GEMMs through the Engine's ragged grouped path — see
    :func:`repro.models.attention.chunked_attention`."""
    logits, new_cache, _ = forward(
        params, cfg, {"inputs": tokens}, cache=cache, pos=pos,
        kv_group_sizes=kv_group_sizes)
    return logits[:, -1], new_cache


def prefill(params, cfg, batch, max_len: int, storage_dtype=None):
    """Prefill: run the prompt, build the cache, return last-token logits.

    ``storage_dtype`` (an FP8 format name) builds the quantized serving
    cache — the prompt's k/v rows are quantized on write, each GQA row
    under its own scale (see :func:`init_cache`)."""
    some = batch.get("inputs", batch.get("embeddings"))
    B = some.shape[0]
    cache = init_cache(cfg, B, max_len, dtype=cfg.policy.compute_dtype,
                       storage_dtype=storage_dtype)
    logits, cache, _ = forward(params, cfg, batch, cache=cache, pos=0,
                               last_only=True)
    return logits[:, -1], cache


# --------------------------------------------------------------------- #
# Caches
# --------------------------------------------------------------------- #
def cache_axes(cfg, storage_dtype=None):
    """Logical sharding axes for every leaf of ``init_cache``'s output.

    With ``storage_dtype`` set (FP8 serving cache) the tree grows the
    scale leaves next to each quantized tensor — GQA's per-row scales,
    MLA's per-tensor delayed-scaling state — mirror of
    :func:`init_cache`'s structure, leaf for leaf."""
    kind = cfg.block_kind
    gqa = {"k": ("batch", "kv_heads", "kv_seq", None),
           "v": ("batch", "kv_heads", "kv_seq", None)}
    mla = {"ckv": ("batch", "kv_seq", None), "kr": ("batch", "kv_seq", None)}
    if storage_dtype is not None:
        gqa = dict(gqa, k_scale=("batch", "kv_heads", "kv_seq"),
                   v_scale=("batch", "kv_heads", "kv_seq"))
        mla = dict(mla, ckv_scale=attention.MLA_SCALE_AXES,
                   kr_scale=attention.MLA_SCALE_AXES)
    attn = mla if cfg.mla else gqa
    stackax = lambda tree: jax.tree.map(
        lambda ax: ("layers", *ax), tree, is_leaf=lambda x: isinstance(x, tuple))
    if kind == "attn":
        return {"layers": stackax(attn)}
    if kind == "moe":
        return {"layer0": attn, "layers": stackax(attn)}
    if kind == "hymba":
        one = {"attn": gqa, "ssm": ("batch", None, None, None)}
        return {"layers": stackax(one)}
    if kind == "pattern":
        period = layer_period(cfg)
        kinds = {"mamba": {"ssm": ("batch", None, None, None),
                           "conv": ("batch", None, None)},
                 "attention": gqa}
        return {"layers": {
            k: jax.tree.map(lambda ax: ("layers", None, *ax), one,
                            is_leaf=lambda x: isinstance(x, tuple))
            for k, one in kinds.items() if k in period}}
    if kind == "xlstm":
        one = {
            "mlstm": (None, "batch", None, None, None),
            "slstm": {k: ("batch", None, None) for k in ("c", "n", "h", "m")},
        }
        return {"layers": stackax(one)}
    raise ValueError(kind)


def init_cache(cfg, batch: int, max_len: int, dtype=None, storage_dtype=None):
    """Build the decode cache.

    ``storage_dtype`` (an FP8 format name, serving) stores the attention
    k/v tensors narrow with their scales alongside (GQA: one per row and
    head; MLA: a per-tensor delayed scale) — the RedMulE mixed-precision
    trade (narrow storage, wide datapath) applied to the KV cache.  Only attention caches quantize; SSM/xLSTM
    state stays wide (attn/moe/pattern block kinds only).  A pattern
    stack's cache holds, per layer kind, its layers stacked (periods,
    layers of the kind in a period): KV rows for attention, fp32 SSM
    state and conv window for Mamba2."""
    dtype = dtype or cfg.policy.compute_dtype
    kind = cfg.block_kind
    if storage_dtype is not None and kind not in ("attn", "moe", "pattern"):
        raise ValueError(
            f"FP8 cache storage supports attn/moe/pattern block kinds, "
            f"not {kind!r}")

    def stack(tree, n):
        return jax.tree.map(lambda x: jnp.broadcast_to(x[None], (n, *x.shape)), tree)

    if kind == "attn":
        one = (attention.init_mla_cache if cfg.mla else attention.init_gqa_cache)(
            cfg, batch, max_len, dtype, storage_dtype)
        return {"layers": stack(one, cfg.n_layers)}
    if kind == "moe":
        one = (attention.init_mla_cache if cfg.mla else attention.init_gqa_cache)(
            cfg, batch, max_len, dtype, storage_dtype)
        return {"layer0": one, "layers": stack(one, cfg.n_layers - cfg.moe.first_dense)}
    if kind == "pattern":
        period = layer_period(cfg)
        n = cfg.n_layers // len(period)
        kinds = {"attention": lambda: attention.init_gqa_cache(
                     cfg, batch, max_len, dtype, storage_dtype),
                 "mamba": lambda: ssm.init_mamba_state(cfg, batch)}
        return {"layers": {
            k: stack(stack(make(), period.count(k)), n)
            for k, make in kinds.items() if k in period}}
    if kind == "hymba":
        di = cfg.ssm.mamba_expand * cfg.d_model
        one = {
            "attn": attention.init_gqa_cache(cfg, batch, max_len, dtype),
            "ssm": jnp.zeros(
                (batch, cfg.n_heads, di // cfg.n_heads, cfg.ssm.state_dim),
                jnp.float32),
        }
        return {"layers": stack(one, cfg.n_layers)}
    if kind == "xlstm":
        n_super = cfg.n_layers // cfg.ssm.slstm_period
        n_m = cfg.ssm.slstm_period - 1
        hd_m = cfg.ssm.mlstm_proj_factor * cfg.d_model // cfg.n_heads
        hd_s = cfg.d_model // cfg.n_heads
        z = lambda *s: jnp.zeros(s, jnp.float32)
        one = {
            "mlstm": z(n_m, batch, cfg.n_heads, hd_m, hd_m),
            "slstm": {
                "c": z(batch, cfg.n_heads, hd_s),
                "n": z(batch, cfg.n_heads, hd_s),
                "h": z(batch, cfg.n_heads, hd_s),
                "m": jnp.full((batch, cfg.n_heads, hd_s), -1e30, jnp.float32),
            },
        }
        return {"layers": stack(one, n_super)}
    raise ValueError(kind)
