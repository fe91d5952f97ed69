"""Ragged decode attention with the GQA group in the rows.

* ``_ragged_decode_attention`` against a plain float32 masked softmax,
  under the ``xla`` and ``interpret`` backends, over ragged slot lengths
  (a parked slot, a length-1 slot, a full slot) and G in {1, 2, 4}, with
  and without a window;
* ``gqa_attention``'s ragged decode over a bf16 and an FP8 cache against
  its dense path under the fp32 policy;
* the decode step's attention dispatches: one batch entry per (slot, kv
  head) with the G query rows as M, nothing broadcast over G;
* ``grouped_matmul(layout="nt")``: ragged output columns, their billing,
  and its gradients.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import engine
from repro.core import precision as prec
from repro.models import attention, transformer
from repro.serving import SchedulerConfig, instrumented_decode_events

FP8 = "float8_e4m3fn"
HKV, T, HD = 2, 16, 32
SIZES = [0, 1, T, 5]          # parked, length 1, full, partial
TOL = dict(rtol=2e-2, atol=2e-2)


def _rand(key, shape, dtype=jnp.bfloat16):
    return jax.random.normal(jax.random.PRNGKey(key), shape).astype(dtype)


def _scores_policy(policy):
    return dataclasses.replace(policy, name=policy.name + "_scores",
                               output_dtype=jnp.float32, faithful_accum=False)


def _reference(q, k, v, sizes, q_off, kv_valid, window):
    """Plain float32: scores past a slot's size read 0 (as the ragged
    dispatch returns them), then the causal / valid / window mask."""
    q, k, v = (np.asarray(a, np.float32) for a in (q, k, v))
    s = np.einsum("bhgd,bhtd->bhgt", q[:, :, :, 0], k) * q.shape[-1] ** -0.5
    cols = np.arange(k.shape[2])
    s = np.where(cols[None, :] < np.asarray(sizes)[:, None],
                 s.transpose(1, 2, 0, 3), 0.0).transpose(2, 0, 1, 3)
    ok = (cols[None] < kv_valid[:, None]) & (cols[None] <= q_off[:, None])
    if window is not None:
        ok &= cols[None] > q_off[:, None] - window
    s = np.where(ok[:, None, None], s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhgt,bhtd->bhgd", p, v)[:, :, :, None]


@pytest.mark.parametrize("backend", ["xla", "interpret"])
@pytest.mark.parametrize("g", [1, 2, 4])
@pytest.mark.parametrize("window", [None, 4])
def test_ragged_decode_matches_float32_reference(backend, g, window):
    B = len(SIZES)
    q = _rand(0, (B, HKV, g, 1, HD))
    k = _rand(1, (B, HKV, T, HD))
    v = _rand(2, (B, HKV, T, HD))
    sizes = np.asarray(SIZES, np.int32)
    # a parked slot sits at the last row with nothing valid in its group
    q_off = np.where(sizes == 0, T - 1, sizes - 1).astype(np.int32)
    kv_valid = q_off + 1
    pol = prec.TPU_BF16
    with engine.use_backend(backend):
        out = attention._ragged_decode_attention(
            q, k, v, q_offset=jnp.asarray(q_off),
            kv_valid=jnp.asarray(kv_valid),
            window=None if window is None else jnp.int32(window),
            kv_group_sizes=jnp.asarray(sizes), scale=HD ** -0.5,
            scores_policy=_scores_policy(pol), policy=pol)
    assert out.shape == (B, HKV, g, 1, HD)
    ref = _reference(q, k, v, sizes, q_off, kv_valid, window)
    np.testing.assert_allclose(np.asarray(out, np.float32), ref, **TOL)


@pytest.mark.parametrize("storage", [None, FP8], ids=["bf16", "fp8"])
def test_gqa_ragged_decode_matches_dense_fp32(storage):
    cfg = configs.get_reduced("yi-9b")
    B, max_len = len(SIZES), T
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    attn = jax.tree.map(lambda a: a[0], params["layers"]["attn"])
    cache = transformer.init_cache(cfg, B, max_len,
                                   dtype=cfg.policy.compute_dtype,
                                   storage_dtype=storage)
    layer = jax.tree.map(lambda a: a[0], cache["layers"])
    # fill the cache with random rows (FP8: under the stored scales)
    for i, name in enumerate(("k", "v")):
        rows = _rand(3 + i, layer[name].shape, jnp.float32)
        if storage is None:
            layer[name] = rows.astype(layer[name].dtype)
        else:
            scale = layer[name + "_scale"]["scale"].reshape(1, -1, 1, 1)
            layer[name] = prec.quantize_fp8(rows, layer[name].dtype,
                                            scale=scale)[0]
    x = _rand(5, (B, 1, cfg.d_model), cfg.policy.compute_dtype)
    sizes = np.asarray(SIZES, np.int32)
    pos = jnp.asarray(np.where(sizes == 0, max_len - 1, sizes - 1), jnp.int32)
    ragged, _ = attention.gqa_attention(
        attn, x, cfg, pos_offset=pos, cache=layer, policy=cfg.policy,
        kv_group_sizes=jnp.asarray(sizes))
    dense, _ = attention.gqa_attention(
        attn, x, cfg, pos_offset=pos, cache=layer, policy=prec.FP32)
    active = sizes > 0
    np.testing.assert_allclose(np.asarray(ragged, np.float32)[active],
                               np.asarray(dense, np.float32)[active],
                               rtol=3e-2, atol=3e-2)
    assert np.isfinite(np.asarray(ragged, np.float32)).all()


def test_decode_attention_dispatches_put_the_group_in_the_rows():
    cfg = configs.get_reduced("yi-9b")
    g = cfg.n_heads // cfg.n_kv_heads
    n = 4
    scfg = SchedulerConfig(n_slots=n, max_len=32, storage_dtype=FP8)
    ev = instrumented_decode_events(transformer.abstract_params(cfg), cfg,
                                    scfg, [5, 10, 0, 18])
    attn = [e.spec for e in ev if not e.spec.w_shared
            or e.spec.op.endswith("grouped_matmul")]
    assert {s.op for s in attn} == {"serve_decode/grouped_matmul",
                                    "serve_decode/matmul"}
    for s in attn:
        assert s.batch * s.groups == n * cfg.n_kv_heads, s
        assert s.m == g, s
    # nothing broadcast over G, and G never the output's lane dimension
    assert not any(s.batch * s.groups == n * cfg.n_kv_heads * g
                   for s in attn)
    assert not any(s.k == g for s in attn)
    (scores,) = [s for s in attn if s.op.endswith("grouped_matmul")]
    assert (scores.layout, scores.k, scores.ragged_dim) == ("nt", 32, "k")


def test_grouped_matmul_nt_ragged_columns_and_billing():
    G, M, N, K = 3, 4, 32, 24
    sizes = np.asarray([5, 0, 24], np.int32)
    x = _rand(6, (G, M, N), jnp.float32)
    w = _rand(7, (G, K, N), jnp.float32)   # stored (G, K, N)
    ref = np.einsum("gmn,gkn->gmk", np.asarray(x), np.asarray(w))
    ref = np.where(np.arange(K)[None, None] < sizes[:, None, None], ref, 0)
    for backend in ("xla", "interpret"):
        with engine.instrument() as ev:
            z = engine.grouped_matmul(x, w, group_sizes=sizes, layout="nt",
                                      policy=prec.FP32, backend=backend)
        np.testing.assert_allclose(np.asarray(z), ref, rtol=1e-5, atol=1e-4)
        (e,) = ev
        assert (e.spec.layout, e.spec.ragged_dim) == ("nt", "k")
        assert e.spec.valid_rows == int(sizes.sum())
        assert e.flops == 2 * M * N * int(sizes.sum())
        assert e.spec.dense_flops == 2 * G * M * N * K
    with pytest.raises(ValueError, match="layout"):
        engine.grouped_matmul(x, w, layout="tn")
    with pytest.raises(ValueError, match="contraction"):
        engine.grouped_matmul(x, w)   # (G, K, N) read as (G, N, K)


@pytest.fixture
def plain_backend():
    """A backend without the "layouts" capability: it ignores the spec's
    layout, so it must be handed "nn" operands."""
    engine.register_backend(
        "plain-nn", lambda x, w, *, spec: jnp.matmul(
            x.astype(jnp.float32), w.astype(jnp.float32)))
    try:
        yield "plain-nn"
    finally:
        engine.unregister_backend("plain-nn")


@pytest.mark.parametrize("backend", ["xla", "plain"])
def test_grouped_matmul_nt_grads_match_nn(backend, plain_backend):
    G, M, N, K = 2, 3, 8, 5
    x = _rand(8, (G, M, N), jnp.float32)
    w = _rand(9, (G, K, N), jnp.float32)
    sizes = jnp.asarray([2, 5])
    bk = plain_backend if backend == "plain" else backend

    def nt(x_, w_):
        return jnp.sum(jnp.sin(engine.grouped_matmul(
            x_, w_, group_sizes=sizes, layout="nt", policy=prec.FP32,
            backend=bk)))

    def ref(x_, w_):
        z = jnp.einsum("gmn,gkn->gmk", x_, w_)
        z = jnp.where(jnp.arange(K)[None, None] < sizes[:, None, None], z, 0)
        return jnp.sum(jnp.sin(z))

    got = jax.grad(nt, argnums=(0, 1))(x, w)
    want = jax.grad(ref, argnums=(0, 1))(x, w)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
