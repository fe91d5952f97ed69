"""Plain float32 reference of a Granite-4.0-H hybrid decoder, and its FP8 control.

``granitemoehybrid`` with no experts (transformers'
``GraniteMoeHybridForCausalLM``): token embedding times
``embedding_multiplier``; per layer, as ``layer_types`` lists it, RMSNorm,
then either a Mamba2 mixer or causal grouped-query attention with no
position embedding and score scale ``attention_multiplier``; the mixer's
output times ``residual_multiplier`` onto the residual; RMSNorm, a SiLU-gated
MLP (``w_in`` holds gate then up) times ``residual_multiplier`` onto the
residual; a final RMSNorm, the tied head (the embedding transposed) and the
logits divided by ``logits_scaling``.

The Mamba2 mixer (arXiv:2405.21060), for one sequence: ``x|z`` and
``B|C|dt`` projections; ``x|B|C`` through a causal depthwise conv of window
``mamba_d_conv`` (zeros before the first token) with bias, then SiLU;
``dt = softplus(dt + dt_bias)``, ``A = -exp(a_log)``; head h reads B and C
of group ``h // (heads / groups)``; then over time, one step at a time::

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T        (per head, P x N)
    y_t = h_t C_t + D x_t

then ``RMSNormGated``: ``y * silu(z)`` normed over the whole inner width,
times its weight, and the output projection.

Every product runs in float32 at ``Precision.HIGHEST`` (and under
``jax.default_matmul_precision("highest")``).  ``fp8=True`` is the control:
both operands of every product (projections, scores, probabilities times
values, the state's read-out ``C h``, the head) rounded to E4M3 under a
per-tensor absolute-maximum scale.  The recurrence's elementwise update is
left wide.

Imports nothing from the program.  Reads weights in the layout the
``serve_hybrid`` driver makes (the program's parameter tree):
``embed``, ``final_norm``, ``layers/mamba/{ln1, mamba/{w_xz, w_bcdt,
conv_w, conv_b, dt_bias, a_log, skip_d, norm, w_out}, ln2, mlp/{w_in,
w_out}}`` and ``layers/attention/{ln1, attn/{wqkv, wo}, ln2, mlp}``, each
stacked (periods of the layer pattern, layers of that kind in a period).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0
QBLOCK = 512       # query rows per attention block
PAD = 512          # sequences are padded up to a multiple of this


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of a Granite-4.0-H decoder, as its configuration states them."""

    d: int
    ff: int
    hq: int
    hkv: int
    hd: int
    vocab: int
    eps: float
    layer_types: Tuple[str, ...]
    heads: int          # Mamba heads
    head_dim: int       # Mamba head width P
    groups: int
    state: int          # d_state N
    conv: int           # d_conv K
    expand: int
    emb_mult: float
    attn_mult: float
    res_mult: float
    logits_scaling: float

    @classmethod
    def of(cls, c: Dict[str, Any]) -> "Dims":
        hd = c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]
        return cls(
            d=c["hidden_size"], ff=c["shared_intermediate_size"],
            hq=c["num_attention_heads"], hkv=c["num_key_value_heads"], hd=hd,
            vocab=c["vocab_size"], eps=float(c["rms_norm_eps"]),
            layer_types=tuple(c["layer_types"]), heads=c["mamba_n_heads"],
            head_dim=c["mamba_d_head"], groups=c["mamba_n_groups"],
            state=c["mamba_d_state"], conv=c["mamba_d_conv"],
            expand=c["mamba_expand"],
            emb_mult=float(c["embedding_multiplier"]),
            attn_mult=float(c["attention_multiplier"]),
            res_mult=float(c["residual_multiplier"]),
            logits_scaling=float(c["logits_scaling"]))

    @property
    def di(self) -> int:
        return self.expand * self.d

    @property
    def period(self) -> Tuple[str, ...]:
        t = self.layer_types
        for p in range(1, len(t) + 1):
            if len(t) % p == 0 and t == t[:p] * (len(t) // p):
                return t[:p]
        raise ValueError("empty layer_types")

    def where(self, i: int) -> Tuple[str, int, int]:
        """Layer i's kind, period and index among its kind in the period."""
        p = self.period
        r = i % len(p)
        return p[r], i // len(p), p[:r].count(p[r])


def _q8(x: jax.Array) -> jax.Array:
    """Round to E4M3 under a per-tensor absolute-maximum scale, back to f32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a: jax.Array, b: jax.Array, fp8: bool, spec: str) -> jax.Array:
    if fp8:
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=HI)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def mlp(x, mw, fp8):
    gate, up = jnp.split(_mm(x, mw["w_in"], fp8, "sd,df->sf"), 2, -1)
    return _mm(jax.nn.silu(gate) * up, mw["w_out"], fp8, "sf,fd->sd")


def attention(x, aw, dims: Dims, fp8: bool):
    """Causal GQA with no position embedding, for one sequence x (S, d)."""
    S = x.shape[0]
    hq, hkv, hd = dims.hq, dims.hkv, dims.hd
    g = hq // hkv
    qkv = _mm(x, aw["wqkv"], fp8, "sd,dn->sn")
    q, k, v = jnp.split(qkv, [hq * hd, (hq + hkv) * hd], axis=-1)
    k, v = k.reshape(S, hkv, hd), v.reshape(S, hkv, hd)
    qb = min(QBLOCK, S)
    nb = S // qb
    qg = q.reshape(nb, qb, hkv, g, hd)
    cols = jnp.arange(S)

    def block(args):
        qblk, b = args
        s = _mm(qblk, k, fp8, "qkgd,tkd->kgqt") * dims.attn_mult
        rows = b * qb + jnp.arange(qb)
        s = jnp.where(cols[None, None, None, :] <= rows[None, None, :, None],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _mm(p, v, fp8, "kgqt,tkd->qkgd")

    o = jax.lax.map(block, (qg, jnp.arange(nb))).reshape(S, hq * hd)
    return _mm(o, aw["wo"], fp8, "sn,nd->sd")


def mamba(x, mw, dims: Dims, fp8: bool):
    """The Mamba2 mixer over one sequence x (S, d), its recurrence a
    sequential scan over time."""
    S = x.shape[0]
    di, H, P, G, N, K = (dims.di, dims.heads, dims.head_dim, dims.groups,
                         dims.state, dims.conv)
    xs, z = jnp.split(_mm(x, mw["w_xz"], fp8, "sd,dn->sn"), 2, -1)
    bcdt = _mm(x, mw["w_bcdt"], fp8, "sd,dn->sn")
    xbc = jnp.concatenate([xs, bcdt[:, :2 * G * N]], -1)          # (S, C)
    full = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc], 0)
    conv = sum(full[j:j + S] * mw["conv_w"][j] for j in range(K))
    xbc = jax.nn.silu(conv + mw["conv_b"])
    xs, bm, cm = jnp.split(xbc, [di, di + G * N], -1)
    dt = jax.nn.softplus(bcdt[:, 2 * G * N:] + mw["dt_bias"])      # (S, H)
    a = -jnp.exp(mw["a_log"])
    grp = jnp.arange(H) // (H // G)
    bh = bm.reshape(S, G, N)[:, grp]                               # (S, H, N)
    ch = cm.reshape(S, G, N)[:, grp]
    xh = xs.reshape(S, H, P)

    def step(h, t):
        dt_t, x_t, b_t, c_t = t
        h = (jnp.exp(dt_t * a)[:, None, None] * h
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])  # (H, P, N)
        y = _mm(h, c_t, fp8, "hpn,hn->hp")
        return h, y

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                        (dt, xh, bh, ch))
    y = (y + mw["skip_d"][None, :, None] * xh).reshape(S, di)
    y = rmsnorm(y * jax.nn.silu(z), mw["norm"], dims.eps)
    return _mm(y, mw["w_out"], fp8, "sn,nd->sd")


def layer(h, lw, kind: str, dims: Dims, fp8: bool):
    x = rmsnorm(h, lw["ln1"], dims.eps)
    m = (mamba(x, lw["mamba"], dims, fp8) if kind == "mamba"
         else attention(x, lw["attn"], dims, fp8))
    h = h + dims.res_mult * m
    x = rmsnorm(h, lw["ln2"], dims.eps)
    return h + dims.res_mult * mlp(x, lw["mlp"], fp8)


@functools.partial(jax.jit, static_argnames=("kind", "dims", "fp8"))
def _layer_jit(h, lw, kind, dims, fp8):
    with jax.default_matmul_precision("highest"):
        return layer(h, lw, kind, dims, fp8)


@functools.partial(jax.jit, static_argnames=("dims", "fp8"))
def _head_jit(h, rows, final_norm, embed, dims, fp8):
    with jax.default_matmul_precision("highest"):
        x = rmsnorm(h[rows], final_norm, dims.eps)
        return _mm(x, embed, fp8, "sd,vd->sv") / dims.logits_scaling


def logits_at(w: Dict[str, Any], dims: Dims, tokens: np.ndarray,
              rows: Sequence[int], fp8: bool = False) -> np.ndarray:
    """Logits (len(rows), vocab) at the given positions of one sequence.

    The sequence is padded at its end to a multiple of ``PAD``; attention
    and the recurrence are causal, so the padding reaches no real position.
    Layer by layer, each layer's weights cast to float32 as it runs."""
    n = len(tokens)
    S = -(-n // PAD) * PAD
    toks = np.zeros(S, np.int32)
    toks[:n] = tokens
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    embed = f32(w["embed"])
    h = embed[jnp.asarray(toks)] * dims.emb_mult
    for i in range(len(dims.layer_types)):
        kind, per, j = dims.where(i)
        lw = jax.tree.map(lambda a: f32(a[per, j]), w["layers"][kind])
        h = _layer_jit(h, lw, kind, dims, fp8)
    out = _head_jit(h, jnp.asarray(np.asarray(rows, np.int32)),
                    f32(w["final_norm"]), embed, dims, fp8)
    return np.asarray(out)
