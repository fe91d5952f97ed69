"""Serving subsystem (PR 6): FP8 KV cache numerics, slot admission,
scheduler determinism, and the generate() cache-consistency invariant."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import engine
from repro.core import precision as prec
from repro.launch import serve
from repro.models import transformer
from repro.serving import (LoadConfig, Request, Scheduler, SchedulerConfig,
                           insert_slot, poisson_requests)

FP8 = "float8_e4m3fn"
E4M3_EPS = 2.0 ** -3  # same bound as tests/test_precision_fp8.py::_EPS


@pytest.fixture(scope="module")
def yi():
    cfg = configs.get_reduced("yi-9b")
    return cfg, transformer.init_params(jax.random.PRNGKey(0), cfg)


# --------------------------------------------------------------------- #
# FP8 KV cache numerics
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("per", ["head", "row"])
def test_fp8_kv_roundtrip_per_head_bounds(per):
    """Quantize -> upcast round-trip stays inside the E4M3
    relative-precision bound for values within 2^-6 of their scale's amax:
    one scale per head, or one per row (the GQA cache's own)."""
    rng = np.random.default_rng(0)
    mag = np.array([0.05, 1.0, 30.0, 400.0])  # per-head dynamic ranges
    x = (rng.standard_normal((2, 4, 16, 8)) * mag[None, :, None, None]
         ).astype(np.float32)
    if per == "head":
        amax = np.abs(x).max(axis=(0, 2, 3), keepdims=True)
        scale = jnp.asarray(amax)
        q, _ = prec.quantize_fp8(jnp.asarray(x), FP8, scale=scale)
    else:
        amax = np.abs(x).max(axis=-1, keepdims=True)
        q, scale = prec.quantize_fp8_rows(jnp.asarray(x), FP8)
        np.testing.assert_array_equal(np.asarray(scale), amax[..., 0])
        scale = scale[..., None]
    dq = np.asarray(prec.dequantize_fp8(q, scale, jnp.float32))
    err = np.abs(dq - x)
    m = np.abs(x) >= np.broadcast_to(amax, x.shape) * 2.0 ** -6
    assert np.all(err[m] <= E4M3_EPS * np.abs(x[m]) * 1.001), \
        "relative error above 2^-3"


def _dequant(cache, name):
    """An FP8 GQA cache's rows under their own per-row scales."""
    return np.asarray(prec.dequantize_fp8(
        cache[name], cache[name + "_scale"][..., None], jnp.float32))


def _e4m3_bound(want, row_scale):
    """Relative 2^-3 for normalized values plus the subnormal grid's
    absolute term (the row's scale * 2^-9) below scale * 2^-6."""
    return E4M3_EPS * np.abs(want) + np.asarray(row_scale)[..., None] * 2.0 ** -9


def test_prefill_fp8_cache_rows_match_fp16_within_bound(yi):
    """The FP8 prefill cache's k/v rows, widened under their per-row
    scales, match the FP16 cache within the E4M3 bound."""
    cfg, params = yi
    prompts = jax.random.randint(
        jax.random.PRNGKey(1), (2, 6), 0, cfg.vocab_size, jnp.int32)
    _, c16 = transformer.prefill(params, cfg, {"inputs": prompts}, 8)
    _, c8 = transformer.prefill(params, cfg, {"inputs": prompts}, 8,
                                storage_dtype=FP8)
    for name in ("k", "v"):
        wide = np.asarray(c16["layers"][name], np.float32)
        dq = _dequant(c8["layers"], name)
        sc = np.asarray(c8["layers"][name + "_scale"])
        # each written row's scale is its own amax; unwritten rows read 0
        np.testing.assert_allclose(sc[..., :6], np.abs(wide).max(-1)[..., :6],
                                   rtol=1e-6)
        assert np.all(sc[..., 6:] == 1) and np.all(dq[..., 6:, :] == 0)
        err = np.abs(dq - wide)[:, :, :, :6]
        assert np.all(err <= _e4m3_bound(wide[:, :, :, :6], sc[..., :6])), \
            name


def test_fp8_decode_logits_vs_fp16_oracle(yi):
    """Multi-step decode from the FP8 cache tracks the FP16-cache oracle:
    same greedy token stream fed to both, logits stay within a small
    absolute band of the oracle's (scale ~4)."""
    cfg, params = yi
    prompts = jax.random.randint(
        jax.random.PRNGKey(1), (2, 6), 0, cfg.vocab_size, jnp.int32)
    lg16, c16 = transformer.prefill(params, cfg, {"inputs": prompts}, 14)
    lg8, c8 = transformer.prefill(params, cfg, {"inputs": prompts}, 14,
                                  storage_dtype=FP8)
    np.testing.assert_allclose(np.asarray(lg16, np.float32),
                               np.asarray(lg8, np.float32), atol=1e-2)
    tok = jnp.argmax(lg16, -1)[:, None].astype(jnp.int32)
    diffs = []
    for i in range(6):
        lg16, c16 = transformer.serve_step(params, cfg, tok, c16,
                                           jnp.int32(6 + i))
        lg8, c8 = transformer.serve_step(params, cfg, tok, c8,
                                         jnp.int32(6 + i))
        diffs.append(float(np.abs(
            np.asarray(lg16, np.float32) - np.asarray(lg8, np.float32)).max()))
        tok = jnp.argmax(lg16, -1)[:, None].astype(jnp.int32)
    assert max(diffs) < 0.5, diffs
    assert sum(diffs) / len(diffs) < 0.3, diffs


# --------------------------------------------------------------------- #
# Slot admission
# --------------------------------------------------------------------- #
def _filled_pool(cfg, storage, n=3, max_len=8, seed=9):
    """A pool whose every slot already holds rows (and, FP8, scales)."""
    pool = transformer.init_cache(cfg, n, max_len,
                                  dtype=cfg.policy.compute_dtype,
                                  storage_dtype=storage)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 8))
    return jax.tree.map(
        lambda a: jax.random.uniform(next(keys), a.shape, jnp.float32,
                                     0.5, 2.0).astype(a.dtype), pool)


def _bytes(a):
    """An array's stored bits, as unsigned ints of its width."""
    a = np.asarray(a)
    return a.view(f"u{a.dtype.itemsize}")


@pytest.mark.parametrize("storage", [None, FP8])
def test_insert_slot_preserves_other_slots(yi, storage):
    """The slot receives the request's rows and scales bit for bit;
    co-resident slots keep every byte."""
    cfg, params = yi
    pool = _filled_pool(cfg, storage)
    prompts = jax.random.randint(
        jax.random.PRNGKey(2), (1, 5), 0, cfg.vocab_size, jnp.int32)
    _, single = transformer.prefill(params, cfg, {"inputs": prompts}, 8,
                                    storage_dtype=storage)
    out = insert_slot(pool, single, jnp.int32(1),
                      dtype=cfg.policy.compute_dtype)
    names = ("k", "v") if storage is None else ("k", "v", "k_scale",
                                                "v_scale")
    assert set(out["layers"]) == set(names)
    for name in names:
        got, was = _bytes(out["layers"][name]), _bytes(pool["layers"][name])
        want = _bytes(single["layers"][name])[:, 0]
        np.testing.assert_array_equal(got[:, 1], want, err_msg=name)
        np.testing.assert_array_equal(got[:, 0], was[:, 0], err_msg=name)
        np.testing.assert_array_equal(got[:, 2], was[:, 2], err_msg=name)


@pytest.mark.parametrize("storage", [None, FP8])
def test_decode_step_writes_one_row_per_active_slot(yi, storage):
    """A scheduler decode step writes exactly one row (and its scale) per
    active slot and GQA layer, at the slot's position; a parked slot and
    every other row keep every byte."""
    cfg, params = yi
    n, max_len = 4, 8
    pool = _filled_pool(cfg, storage, n=n, max_len=max_len)
    pos = np.asarray([3, 0, max_len, 7], np.int32)     # slot 2 parked
    sizes = np.where(pos < max_len, pos + 1, 0).astype(np.int32)
    toks = jnp.asarray([[5], [6], [0], [7]], jnp.int32)
    _, out = jax.jit(lambda c: transformer.serve_step(
        params, cfg, toks, c, jnp.asarray(pos),
        kv_group_sizes=jnp.asarray(sizes)))(pool)
    L = cfg.n_layers
    for name in pool["layers"]:
        got, was = _bytes(out["layers"][name]), _bytes(pool["layers"][name])
        changed = got != was
        if changed.ndim == 5:                       # (L, B, Hkv, T, hd)
            changed = changed.any(-1)
        rows = {tuple(r) for r in np.argwhere(changed.any(2))}
        want = {(l, b, int(pos[b])) for l in range(L) for b in range(n)
                if sizes[b]}
        assert rows == want, name                   # (layer, slot, row)


def test_decode_kernels_on_e4m3_match_xla(yi):
    """A decode step over an FP8 cache, its E4M3 rows fed to the Pallas
    kernels (interpret), matches the xla backend, which widens them at the
    dot, within the E4M3 bound; the written caches are bitwise equal."""
    cfg, params = yi
    pool = _filled_pool(cfg, FP8, n=4, max_len=32)
    pos = jnp.asarray([3, 17, 32, 30], jnp.int32)
    sizes = jnp.asarray([4, 18, 0, 31], jnp.int32)
    toks = jnp.asarray([[5], [6], [0], [7]], jnp.int32)
    got = {}
    for backend in ("xla", "interpret"):
        with engine.use_backend(backend):
            got[backend] = jax.jit(lambda c: transformer.serve_step(
                params, cfg, toks, c, pos, kv_group_sizes=sizes))(pool)
    (lx, cx), (li, ci) = got["xla"], got["interpret"]
    active = np.asarray(sizes) > 0
    lx = np.asarray(lx, np.float32)[active]
    li = np.asarray(li, np.float32)[active]
    assert np.all(np.abs(li - lx) <= E4M3_EPS * np.abs(lx) + 2e-2)
    for a, b in zip(jax.tree.leaves(cx), jax.tree.leaves(ci)):
        np.testing.assert_array_equal(_bytes(a), _bytes(b))


def test_lowered_decode_holds_no_widened_cache(yi):
    """The scheduler's decode step on the kernel path lowers with the FP8
    cache only at one byte: no bf16 or f32 array of its (B, Hkv, T, hd)
    shape (or the (B·Hkv, T, hd) the kernels read)."""
    import re

    cfg, _ = yi
    n, max_len = 4, 64
    scfg = SchedulerConfig(n_slots=n, max_len=max_len, storage_dtype=FP8)
    cabs = jax.eval_shape(lambda: transformer.init_cache(
        cfg, n, max_len, dtype=cfg.policy.compute_dtype, storage_dtype=FP8))
    pabs = transformer.abstract_params(cfg)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    with engine.use_backend("interpret"):
        text = jax.jit(
            lambda p, c, t, q, g: transformer.serve_step(
                p, cfg, t, c, q, kv_group_sizes=g),
            donate_argnums=(1,)).lower(
                pabs, cabs, i32(n, 1), i32(n), i32(n)).as_text()
    hkv, hd = cfg.n_kv_heads, cfg.head_dim
    wide = {(n, hkv, max_len, hd), (n * hkv, max_len, hd),
            (cfg.n_layers, n, hkv, max_len, hd)}
    seen = {}
    for dims, dt in re.findall(r"tensor<((?:\d+x)+)(\w+)>", text):
        seen.setdefault(tuple(int(d) for d in dims[:-1].split("x")),
                        set()).add(dt)
    assert any("f8E4M3FN" in dts for dts in seen.values())
    for shape in wide:
        assert not seen.get(shape, set()) & {"bf16", "f32", "f16"}, shape


# --------------------------------------------------------------------- #
# Scheduler: determinism + pinned trace
# --------------------------------------------------------------------- #
PINNED_TRACE = [
    ("admit", 1.415059, 0),
    ("prefill", 2.415059, 0, 0, 5),
    ("admit", 2.415059, 1),
    ("prefill", 3.415059, 1, 1, 5),
    ("finish", 7.415059, 0, 0),
    ("finish", 7.415059, 1, 1),
    ("admit", 7.446556, 2),
    ("prefill", 8.446556, 2, 0, 5),
    ("admit", 8.446556, 3),
    ("prefill", 9.446556, 3, 1, 5),
    ("admit", 12.446556, 4),
    ("finish", 13.446556, 2, 0),
    ("finish", 13.446556, 3, 1),
    ("prefill", 14.446556, 4, 0, 5),
    ("finish", 18.446556, 4, 0),
]


def _run_sched(cfg, params):
    scfg = SchedulerConfig(n_slots=2, max_len=16)
    lc = LoadConfig(rate=0.5, n_requests=5, prompt_len=5, gen_len=4, seed=7)
    sched = Scheduler(params, cfg, scfg)
    sched.submit(poisson_requests(cfg, lc))
    results = sched.run()
    return sched, results


def test_scheduler_trace_pinned(yi):
    """Seeded arrivals -> exact slot-assignment/eviction trace.  Continuous
    batching is visible in the pin: rid 2 takes slot 0 the tick after rid
    0 finishes, mid-flight of rid 3."""
    cfg, params = yi
    sched, results = _run_sched(cfg, params)
    got = [(e[0], round(e[1], 6), *e[2:]) for e in sched.trace]
    assert got == PINNED_TRACE
    assert all(len(r.tokens) == 4 and r.finish_tick is not None
               for r in results)


def test_scheduler_deterministic(yi):
    """Two fresh runs of the same seeded load: identical traces, identical
    emitted tokens, identical health logs."""
    cfg, params = yi
    s1, r1 = _run_sched(cfg, params)
    s2, r2 = _run_sched(cfg, params)
    assert s1.trace == s2.trace
    assert [r.tokens for r in r1] == [r.tokens for r in r2]
    assert s1.health == s2.health


def test_final_logits_hold_their_own_row(yi):
    """A finished request's final logits are its row alone: a view would
    keep the whole (slots, vocab) host array of its last decode step alive
    with every result held."""
    cfg, params = yi
    _, results = _run_sched(cfg, params)
    for r in results:
        assert r.final_logits.shape == (cfg.vocab_size,)
        assert r.final_logits.base is None


def test_scheduler_moe_fp8_smoke():
    cfg = configs.get_reduced("deepseek-moe-16b")
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    scfg = SchedulerConfig(n_slots=2, max_len=12, storage_dtype=FP8)
    sched = Scheduler(params, cfg, scfg)
    sched.submit(poisson_requests(
        cfg, LoadConfig(rate=1.0, n_requests=3, prompt_len=4, gen_len=3,
                        seed=1)))
    results = sched.run()
    assert all(len(r.tokens) == 3 for r in results)
    assert all(0 <= t < cfg.vocab_size for r in results for t in r.tokens)


def test_scheduler_rejects_oversized_request(yi):
    """An invalid request is rejected per-request — structured Rejection,
    trace event, results entry — and never aborts the rest of the batch
    (the old contract raised out of submit() and dropped everything)."""
    cfg, params = yi
    sched = Scheduler(params, cfg, SchedulerConfig(n_slots=1, max_len=8))
    good = Request(rid=2, arrival=0.0, prompt=np.zeros(3, np.int32),
                   max_new_tokens=3)
    sched.submit([
        Request(rid=0, arrival=0.0, prompt=np.zeros(6, np.int32),
                max_new_tokens=4),                      # oversized
        Request(rid=1, arrival=0.0, prompt=np.zeros(3, np.int32),
                max_new_tokens=0),                      # invalid budget
        good,                                           # must still run
    ])
    assert [(r.rid, r.reason) for r in sched.rejections] == \
        [(0, "oversized"), (1, "invalid")]
    assert all(r.retry_after is None for r in sched.rejections)
    assert sched.results[0].status == "rejected"
    assert sched.results[1].status == "rejected"
    assert ("reject", 0.0, 0, "oversized") in sched.trace
    results = sched.run()
    ok = sched.results[good.rid]
    assert ok.status == "finished" and len(ok.tokens) == 3
    assert len(results) == 3


# --------------------------------------------------------------------- #
# generate(): thin scheduler client + satellite-1 bugfix pin
# --------------------------------------------------------------------- #
def test_generate_cache_consistent_with_emitted_sequence(yi):
    """The pre-PR-6 generate() broke out of the loop before the final
    step, leaving the cache stale by one token.  Pin the fix two ways:
    (a) the returned final logits are exactly the next-token distribution
    a longer run continues with, (b) the returned cache equals a full
    prefill over the emitted sequences bit for bit."""
    cfg, params = yi
    prompts = jax.random.randint(
        jax.random.PRNGKey(3), (2, 6), 0, cfg.vocab_size, jnp.int32)
    seqs, cache, final = serve.generate(params, cfg, prompts, 4,
                                        return_state=True)
    assert seqs.shape == (2, 10)
    seqs5 = np.asarray(serve.generate(params, cfg, prompts, 5))
    np.testing.assert_array_equal(np.asarray(seqs), seqs5[:, :10])
    np.testing.assert_array_equal(np.argmax(final, axis=-1), seqs5[:, -1])
    _, oracle = transformer.prefill(params, cfg, {"inputs": seqs}, 10)
    for name in ("k", "v"):
        np.testing.assert_array_equal(
            np.asarray(cache["layers"][name], np.float32),
            np.asarray(oracle["layers"][name], np.float32))


def test_generate_fp8_storage(yi):
    cfg, params = yi
    prompts = jax.random.randint(
        jax.random.PRNGKey(3), (2, 6), 0, cfg.vocab_size, jnp.int32)
    seqs = serve.generate(params, cfg, prompts, 4, storage_dtype=FP8)
    assert seqs.shape == (2, 10)
    assert np.array_equal(np.asarray(seqs)[:, :6], np.asarray(prompts))
