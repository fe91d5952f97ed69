"""SSM blocks: xLSTM (mLSTM + sLSTM) and Mamba2/SSD (Hymba, Granite-4.0-H).

One *chunkwise linear-attention engine* serves both mLSTM and SSD: the
recurrence ``S_t = exp(g_t) * S_{t-1} + k_t v_t^T`` is evaluated in chunks —
intra-chunk terms become dense GEMMs (RedMulE territory; this is the
GEMM-dominated form claimed in DESIGN.md §5) and only the chunk-to-chunk
state crosses the scan.  With log-decays g <= 0 every factor is exp(<=0),
so the chunked form is numerically stable without a separate stabilizer.

sLSTM is inherently sequential (scalar-state recurrence with a stabilizer,
paper-inapplicable — no GEMM shape in the recurrence); it runs as a
``lax.scan`` over time with its input projections hoisted into one big GEMM.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import engine
from repro.core import precision as prec
from repro.models import layers
from repro.models.layers import Param

__all__ = [
    "chunked_linear_attention",
    "linear_attention_step",
    "mlstm_schema",
    "mlstm_block",
    "slstm_schema",
    "slstm_block",
    "mamba_dims",
    "mamba_schema",
    "init_mamba_state",
    "mamba_step",
    "mamba_mixer",
]

_F32 = prec.FP32


# --------------------------------------------------------------------- #
# Chunkwise linear attention engine
# --------------------------------------------------------------------- #
def chunked_linear_attention(
    q: jax.Array,        # (B, H, S, dk)
    k: jax.Array,        # (B, H, S, dk)
    v: jax.Array,        # (B, H, S, dv)
    log_g: jax.Array,    # (B, H, S) log-decay, <= 0
    *,
    chunk: int = 64,
    state: Optional[jax.Array] = None,  # (B, H, dk, dv)
    backend: Optional[str] = None,      # xla | pallas | interpret
) -> Tuple[jax.Array, jax.Array]:
    """Returns (out (B,H,S,dv), final_state (B,H,dk,dv)).

    A thin wrapper over the Engine's first-class ``linear_attention`` op:
    backends with the ``"attention"`` capability (pallas / interpret) run
    the VMEM-resident-state kernel (the store-once rule applied to the
    recurrence) when no initial state is carried in; everything else —
    including state carry-in (decode prefix) — runs the engine's
    reference chunked scan.  Either way every GEMM of the sweep is billed
    through the registry."""
    return engine.linear_attention(
        q, k, v, log_g, chunk=chunk, state=state, backend=backend)


def linear_attention_step(
    state: jax.Array,  # (B, H, dk, dv)
    q: jax.Array,      # (B, H, dk)
    k: jax.Array,
    v: jax.Array,      # (B, H, dv)
    log_g: jax.Array,  # (B, H)
) -> Tuple[jax.Array, jax.Array]:
    """One decode step: S' = exp(g) S + k v^T; out = q @ S'."""
    state = (
        jnp.exp(log_g.astype(jnp.float32))[..., None, None] * state
        + k.astype(jnp.float32)[..., :, None] * v.astype(jnp.float32)[..., None, :]
    )
    out = engine.einsum2d("bhk,bhkv->bhv", q.astype(jnp.float32), state,
                          policy=_F32)
    return out, state


def _per_head_rmsnorm(x: jax.Array, scale: jax.Array, H: int,
                      eps: float) -> jax.Array:
    """Group-norm over each head's channels. x: (B, S, di), scale: (di,)."""
    B, S, di = x.shape
    xh = x.reshape(B, S, H, di // H)
    xh = layers.rmsnorm(xh, jnp.ones((di // H,), x.dtype), eps)
    return xh.reshape(B, S, di) * scale.astype(x.dtype)


# --------------------------------------------------------------------- #
# mLSTM block (xLSTM)
# --------------------------------------------------------------------- #
def mlstm_schema(cfg) -> Dict[str, Any]:
    d = cfg.d_model
    di = cfg.ssm.mlstm_proj_factor * d
    H = cfg.n_heads
    hd = di // H
    return {
        "w_up": Param((d, 2 * di), ("embed", "ff")),
        # block-diagonal per-head q/k/v (xLSTM's linear_headwise): H
        # independent hd->3hd projections, not one dense di->3di
        "w_qkv": Param((H, hd, 3 * hd), (None, None, None)),
        "w_if": Param((di, 2 * H), (None, None)),
        "b_if": Param((2 * H,), (None,), init="zeros"),
        "norm": Param((di,), (None,), init="ones"),
        "w_down": Param((di, d), ("ff", "embed")),
    }


def mlstm_block(
    params, x, cfg, *, policy, state=None,
) -> Tuple[jax.Array, Optional[jax.Array]]:
    """x: (B, S, d). state: (B, H, hd, hd) carried across decode steps."""
    B, S, d = x.shape
    H = cfg.n_heads
    di = cfg.ssm.mlstm_proj_factor * d
    hd = di // H

    u = engine.matmul(x, params["w_up"], policy=policy)
    xin, z = jnp.split(u, 2, axis=-1)
    xh = xin.reshape(B, S, H, hd).transpose(2, 0, 1, 3).reshape(H, B * S, hd)
    qkv = engine.matmul(xh, params["w_qkv"], policy=policy)  # (H, B*S, 3hd)
    qkv = qkv.reshape(H, B, S, 3 * hd).transpose(1, 0, 2, 3)
    q, k, v = jnp.split(qkv, 3, axis=-1)                  # (B, H, S, hd)
    q = q * hd**-0.5

    gates = engine.matmul(xin, params["w_if"], policy=_F32) + params["b_if"].astype(jnp.float32)
    i_raw, f_raw = jnp.split(gates, 2, axis=-1)          # (B, S, H)
    log_f = -jax.nn.softplus(-(f_raw + 3.0))             # log sigmoid(f+3) <= 0
    i_gate = jax.nn.sigmoid(i_raw)
    k = k * i_gate.transpose(0, 2, 1)[..., None].astype(k.dtype)
    log_g = log_f.transpose(0, 2, 1)                     # (B, H, S)

    if S == 1 and state is not None:
        o, state = linear_attention_step(
            state, q[:, :, 0], k[:, :, 0], v[:, :, 0], log_g[:, :, 0])
        o = o[:, :, None]
    else:
        o, state = chunked_linear_attention(
            q, k, v, log_g, chunk=cfg.ssm.chunk, state=state)

    o = o.transpose(0, 2, 1, 3).reshape(B, S, di).astype(x.dtype)
    o = _per_head_rmsnorm(o, params["norm"], H, cfg.norm_eps)
    o = o * jax.nn.silu(z)
    return engine.matmul(o, params["w_down"], policy=policy), state


# --------------------------------------------------------------------- #
# sLSTM block (xLSTM) — sequential scalar recurrence
# --------------------------------------------------------------------- #
def slstm_schema(cfg) -> Dict[str, Any]:
    d = cfg.d_model
    H = cfg.n_heads
    hd = d // H
    ff = cfg.ssm.slstm_ffn_dim(d)
    return {
        "w_gates": Param((d, 4 * d), ("embed", "ff")),
        "r_gates": Param((H, hd, 4 * hd), (None, None, None)),
        "b_gates": Param((4 * d,), (None,), init="zeros"),
        "norm": Param((d,), (None,), init="ones"),
        "ffn": {
            "w_in": Param((d, 2 * ff), ("embed", "ff")),
            "w_out": Param((ff, d), ("ff", "embed")),
        },
    }


def slstm_block(
    params, x, cfg, *, policy, state=None,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """state: dict(c, n, h, m) each (B, H, hd)."""
    B, S, d = x.shape
    H = cfg.n_heads
    hd = d // H

    wx = engine.matmul(x, params["w_gates"], policy=policy)  # (B, S, 4d) — one GEMM
    wx = wx.reshape(B, S, 4, H, hd).astype(jnp.float32)
    if state is None:
        zeros = jnp.zeros((B, H, hd), jnp.float32)
        state = {"c": zeros, "n": zeros, "h": zeros,
                 "m": jnp.full((B, H, hd), -1e30, jnp.float32)}
    b = params["b_gates"].astype(jnp.float32).reshape(4, H, hd)
    r = params["r_gates"].astype(jnp.float32)

    def step(st, wx_t):  # wx_t: (B, 4, H, hd)
        rec = engine.einsum2d("bhd,hde->bhe", st["h"], r,
                              policy=_F32).reshape(B, H, 4, hd)
        g = wx_t + rec.transpose(0, 2, 1, 3) + b[None]
        z_t, i_t, f_t, o_t = g[:, 0], g[:, 1], g[:, 2], g[:, 3]
        log_f = -jax.nn.softplus(-(f_t + 3.0))
        m_new = jnp.maximum(log_f + st["m"], i_t)
        i_p = jnp.exp(i_t - m_new)
        f_p = jnp.exp(log_f + st["m"] - m_new)
        c = f_p * st["c"] + i_p * jnp.tanh(z_t)
        n = f_p * st["n"] + i_p
        h = jax.nn.sigmoid(o_t) * c / jnp.maximum(jnp.abs(n), 1.0)
        return {"c": c, "n": n, "h": h, "m": m_new}, h

    with engine.repeat(S):  # time scan: body traced once, runs S times
        state, hs = jax.lax.scan(step, state, jnp.moveaxis(wx, 1, 0))
    h = jnp.moveaxis(hs, 0, 1).reshape(B, S, d).astype(x.dtype)
    h = layers.rmsnorm(h, params["norm"], cfg.norm_eps)
    y = h + layers.mlp_glu(params["ffn"], h, act=cfg.act, policy=policy)
    return y, state


# --------------------------------------------------------------------- #
# Mamba2 / SSD mixer (Hymba's SSM heads, Granite-4.0-H's Mamba2 layers)
# --------------------------------------------------------------------- #
def mamba_dims(cfg) -> Tuple[int, int, int, int, int]:
    """(inner width, heads, head width, groups, state width)."""
    di = cfg.ssm.mamba_expand * cfg.d_model
    H = cfg.ssm.mamba_heads or cfg.n_heads
    return di, H, di // H, cfg.ssm.n_groups, cfg.ssm.state_dim


def mamba_schema(cfg) -> Dict[str, Any]:
    d = cfg.d_model
    di, H, _, G, N = mamba_dims(cfg)
    s = {
        "w_xz": Param((d, 2 * di), ("embed", "ff")),
        "w_bcdt": Param((d, 2 * G * N + H), ("embed", None)),
        "a_log": Param((H,), (None,), init="zeros"),
        "skip_d": Param((H,), (None,), init="ones"),
        "dt_bias": Param((H,), (None,), init="zeros"),
        "norm": Param((di,), (None,), init="ones"),
        "w_out": Param((di, d), ("ff", "embed")),
    }
    K = cfg.ssm.d_conv
    if K:
        s["conv_w"] = Param((K, di + 2 * G * N), (None, None))
        s["conv_b"] = Param((di + 2 * G * N,), (None,), init="zeros")
    return s


def init_mamba_state(cfg, batch: int) -> Dict[str, jax.Array]:
    """One Mamba2 layer's decode state: the SSM state ``(B, H, P, N)`` and
    the conv window's last ``d_conv - 1`` inputs ``(B, d_conv - 1, C)``,
    both fp32 and zero at a sequence's start."""
    di, H, P, G, N = mamba_dims(cfg)
    return {
        "ssm": jnp.zeros((batch, H, P, N), jnp.float32),
        "conv": jnp.zeros((batch, cfg.ssm.d_conv - 1, di + 2 * G * N),
                          jnp.float32),
    }


def mamba_step(state: jax.Array, c: jax.Array, b: jax.Array, v: jax.Array,
               log_g: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """One SSM decode step with the state held ``(B, H, P, N)``:
    ``S' = exp(g) S + v b^T``, ``y = S' c``.

    The state keeps N (the state width) on the lanes, so a head width P
    under 128 is not padded to a tile's width.  The read-out is a
    matrix-vector product bound by the state's bytes: this one elementwise
    pass reads S once and writes S' once, where a GEMM dispatch would read
    S' again (and widen c to a lane tile)."""
    f32 = jnp.float32
    state = (jnp.exp(log_g.astype(f32))[..., None, None] * state
             + v.astype(f32)[..., :, None] * b.astype(f32)[..., None, :])
    return jnp.sum(state * c.astype(f32)[..., None, :], axis=-1), state


def _causal_conv(xbc: jax.Array, w: jax.Array, b: jax.Array,
                 prev: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal conv, then SiLU.  xbc (B, S, C) fp32; w (K, C);
    ``prev`` (B, K-1, C) the inputs before ``xbc[:, 0]``.  Returns the
    activations and the last K-1 inputs (the next call's ``prev``)."""
    K, S = w.shape[0], xbc.shape[1]
    full = jnp.concatenate([prev, xbc], axis=1)          # (B, S+K-1, C)
    w = w.astype(jnp.float32)
    y = full[:, 0:S] * w[0]
    for j in range(1, K):
        y = y + full[:, j:j + S] * w[j]
    return jax.nn.silu(y + b.astype(jnp.float32)), full[:, S:]


def _per_group(m: jax.Array, H: int, G: int, N: int) -> jax.Array:
    """(B, S, G*N) -> (B, H, S, N): head h reads group h // (H / G)."""
    B, S, _ = m.shape
    m = m.reshape(B, S, G, 1, N).transpose(0, 2, 3, 1, 4)
    return jnp.broadcast_to(m, (B, G, H // G, S, N)).reshape(B, H, S, N)


def mamba_mixer(
    params, x, cfg, *, policy, state=None, carry: bool = True,
) -> Tuple[jax.Array, Any]:
    """Mamba2 / SSD: linear attention with q=C, k=B, v=dt*x and decay
    exp(dt*A), A = -exp(a_log), plus the D skip.  Returns ``(out, state)``.

    x: (B, S, d).  ``state`` is the decode state: the SSM state
    ``(B, H, P, N)``, or with a conv window the dict ``{"ssm", "conv"}`` of
    :func:`init_mamba_state`.  S == 1 with a state is one decode step
    (:func:`mamba_step` and the conv window's shift).  A longer x runs the
    Engine's chunked sweep from ``state``, or from zero when ``carry`` is
    false: that is a prompt's prefill, and it runs the linear-attention
    kernel.

    With ``cfg.ssm.d_conv`` set (Granite-4.0-H) x|B|C pass a causal
    depthwise conv and SiLU first, and the output is gated then normed over
    the whole inner width (Mamba2's ``RMSNormGated``); without it (Hymba)
    each head's output is normed, then gated."""
    B_, S, d = x.shape
    di, H, P, G, N = mamba_dims(cfg)
    K = cfg.ssm.d_conv
    ssm_state, conv = (state["ssm"], state["conv"]) if K and state is not None \
        else (state, None)

    xz = engine.matmul(x, params["w_xz"], policy=policy)
    xin, z = jnp.split(xz, 2, axis=-1)
    bcdt = engine.matmul(x, params["w_bcdt"], policy=_F32)   # (B, S, 2GN + H)
    bc, dt = bcdt[..., :2 * G * N], bcdt[..., 2 * G * N:]
    if K:
        prev = (conv if carry and conv is not None
                else jnp.zeros((B_, K - 1, di + 2 * G * N), jnp.float32))
        xbc, conv = _causal_conv(
            jnp.concatenate([xin.astype(jnp.float32), bc], axis=-1),
            params["conv_w"], params["conv_b"], prev)
        xin, bc = xbc[..., :di], xbc[..., di:]
    bmat, cmat = bc[..., :G * N], bc[..., G * N:]
    dt = jax.nn.softplus(dt + params["dt_bias"].astype(jnp.float32))  # (B,S,H)
    a = -jnp.exp(params["a_log"].astype(jnp.float32))
    log_g = (dt * a[None, None]).transpose(0, 2, 1)      # (B, H, S) <= 0

    v = xin.reshape(B_, S, H, P).transpose(0, 2, 1, 3)   # (B, H, S, P)
    v_in = v * dt.transpose(0, 2, 1)[..., None].astype(v.dtype)
    q = _per_group(cmat, H, G, N)
    k = _per_group(bmat, H, G, N)

    if S == 1 and ssm_state is not None:
        o, ssm_state = mamba_step(ssm_state, q[:, :, 0], k[:, :, 0],
                                  v_in[:, :, 0], log_g[:, :, 0])
        o = o[:, :, None]
    else:
        # the Engine's sweep holds the state (N, P)
        st = ssm_state.swapaxes(-1, -2) if carry and ssm_state is not None \
            else None
        o, st = chunked_linear_attention(
            q, k, v_in, log_g, chunk=cfg.ssm.chunk, state=st)
        ssm_state = st.swapaxes(-1, -2)

    o = o + v.astype(jnp.float32) * params["skip_d"].astype(jnp.float32)[None, :, None, None]
    o = o.transpose(0, 2, 1, 3).reshape(B_, S, di)
    if K:
        o = o * jax.nn.silu(z.astype(jnp.float32))
        o = layers.rmsnorm(o, params["norm"], cfg.norm_eps).astype(x.dtype)
    else:
        o = _per_head_rmsnorm(o.astype(x.dtype), params["norm"], H,
                              cfg.norm_eps)
        o = o * jax.nn.silu(z)
    out = engine.matmul(o, params["w_out"], policy=policy)
    return out, ({"ssm": ssm_state, "conv": conv} if K else ssm_state)
