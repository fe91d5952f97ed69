"""Plain float32 reference of a dense GQA decoder, and its FP8 control.

Llama/Mistral form: token embedding; per layer RMSNorm, fused q|k|v
projection, rotary position embedding (half-split rotation, inverse
frequencies ``theta ** (-2i / head_dim)``), causal grouped-query attention
(query head ``h`` reads key/value head ``h // (hq / hkv)``), output
projection and residual, RMSNorm, SiLU-gated MLP (``w_in`` holds gate then
up) and residual; a final RMSNorm and an untied head.  Every product runs in
float32 at ``Precision.HIGHEST``; attention runs in blocks of query rows so
an 8k-token sequence fits one chip.

``fp8=True`` is the control: the same mathematics with both operands of
every product (projections, scores, probabilities times values, head)
rounded to E4M3 under a per-tensor absolute-maximum scale, the narrower
datapath a later change could be tempted to take.  Under ``jax.grad`` the
backward products take E4M3 operands too: the cotangent arriving at each
product is rounded the same way, and the forward's rounded operands are
the ones it meets.

Imports nothing from the program.  Reads weights in the benchmark's own
layout (``chipbench/model.py``).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0
QBLOCK = 512       # query rows per attention block
PAD = 512          # sequences are padded up to a multiple of this


def _q8(x: jax.Array) -> jax.Array:
    """Round to E4M3 under a per-tensor absolute-maximum scale, back to f32;
    gradients pass the rounding straight through."""
    s = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
                              / E4M3_MAX)
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


@jax.custom_vjp
def _q8_cotangent(x: jax.Array) -> jax.Array:
    """Identity forward; rounds the cotangent to E4M3 on the way back."""
    return x


_q8_cotangent.defvjp(lambda x: (x, None), lambda _, g: (_q8(g),))


def _mm(a: jax.Array, b: jax.Array, fp8: bool, spec: str) -> jax.Array:
    if fp8:
        return _q8_cotangent(
            jnp.einsum(spec, _q8(a), _q8(b), precision=HI))
    return jnp.einsum(spec, a, b, precision=HI)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """x (..., S, H, D); pos (S,)."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * inv           # (S, D/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, fp8: bool):
    """Causal GQA for one sequence: q (S, hq, hd), k/v (S, hkv, hd)."""
    S, hq, hd = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    qb = min(QBLOCK, S)
    nb = S // qb
    qg = q.reshape(nb, qb, hkv, g, hd)
    cols = jnp.arange(S)

    def block(args):
        qblk, b = args
        s = _mm(qblk, k, fp8, "qkgd,tkd->kgqt") * hd ** -0.5
        rows = b * qb + jnp.arange(qb)
        s = jnp.where(cols[None, None, None, :] <= rows[None, None, :, None],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _mm(p, v, fp8, "kgqt,tkd->qkgd")

    out = jax.lax.map(block, (qg, jnp.arange(nb)))
    return out.reshape(S, hq * hd)


def layer(h, lw, dims, fp8: bool):
    """One decoder layer on one sequence h (S, d)."""
    S = h.shape[0]
    hq, hkv, hd = dims.hq, dims.hkv, dims.hd
    x = rmsnorm(h, lw["ln1"], dims.eps)
    qkv = _mm(x, lw["attn"]["wqkv"], fp8, "sd,dn->sn")
    q, k, v = jnp.split(qkv, [hq * hd, (hq + hkv) * hd], axis=-1)
    pos = jnp.arange(S)
    q = rope(q.reshape(S, hq, hd), pos, dims.rope_theta)
    k = rope(k.reshape(S, hkv, hd), pos, dims.rope_theta)
    v = v.reshape(S, hkv, hd)
    h = h + _mm(attention(q, k, v, fp8), lw["attn"]["wo"], fp8, "sn,nd->sd")
    x = rmsnorm(h, lw["ln2"], dims.eps)
    gate, up = jnp.split(_mm(x, lw["mlp"]["w_in"], fp8, "sd,df->sf"), 2, -1)
    return h + _mm(jax.nn.silu(gate) * up, lw["mlp"]["w_out"], fp8,
                   "sf,fd->sd")


@functools.partial(jax.jit, static_argnames=("dims", "fp8"))
def _layer_jit(h, lw, dims, fp8):
    return layer(h, lw, dims, fp8)


@functools.partial(jax.jit, static_argnames=("dims", "fp8"))
def _head_jit(h, rows, final_norm, lm_head, dims, fp8):
    x = rmsnorm(h[rows], final_norm, dims.eps)
    return _mm(x, lm_head, fp8, "sd,dv->sv")


def logits_at(w: Dict[str, Any], dims, tokens: np.ndarray,
              rows: Sequence[int], fp8: bool = False) -> np.ndarray:
    """Logits (len(rows), vocab) at the given positions of one sequence.

    The sequence is padded at its end to a multiple of ``PAD``; causal
    attention keeps the padding out of every real position.  Layer by
    layer, so only one layer's activations live at a time."""
    n = len(tokens)
    S = -(-n // PAD) * PAD
    toks = np.zeros(S, np.int32)
    toks[:n] = tokens
    h = w["embed"][jnp.asarray(toks)]
    for i in range(dims.layers):
        lw = jax.tree.map(lambda a: a[i], w["layers"])
        h = _layer_jit(h, lw, dims, fp8)
    out = _head_jit(h, jnp.asarray(np.asarray(rows, np.int32)),
                    w["final_norm"], w["lm_head"], dims, fp8)
    return np.asarray(out)


# --------------------------------------------------------------------- #
# Training: loss and gradient of the mean next-token cross-entropy
# --------------------------------------------------------------------- #
def loss(w: Dict[str, Any], dims, inputs: jax.Array, labels: jax.Array,
         fp8: bool = False) -> jax.Array:
    """Mean cross-entropy over a (B, S) batch; each layer is rematerialized
    and the head runs one row at a time, so a 64k vocabulary fits."""
    h = w["embed"][inputs]

    def one_row(h_row):
        for i in range(dims.layers):
            lw = jax.tree.map(lambda a: a[i], w["layers"])
            h_row = jax.checkpoint(
                lambda hh, ll: layer(hh, ll, dims, fp8))(h_row, lw)
        return rmsnorm(h_row, w["final_norm"], dims.eps)

    @jax.checkpoint
    def ce(args):
        h_row, y = args
        logits = _mm(one_row(h_row), w["lm_head"], fp8, "sd,dv->sv")
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - gold)

    return jnp.sum(jax.lax.map(ce, (h, labels))) / labels.size


@functools.partial(jax.jit, static_argnames=("dims", "fp8"))
def loss_and_grad(w, dims, inputs, labels, fp8=False):
    return jax.value_and_grad(loss)(w, dims, inputs, labels, fp8)
