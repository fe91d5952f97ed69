"""Serving subsystem (PR 6): FP8 KV cache numerics, slot admission,
scheduler determinism, and the generate() cache-consistency invariant."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
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
def test_fp8_kv_roundtrip_per_head_bounds():
    """Per-head quantize -> upcast round-trip stays inside the E4M3
    relative-precision bound for values within 2^-6 of the head amax."""
    rng = np.random.default_rng(0)
    mag = np.array([0.05, 1.0, 30.0, 400.0])  # per-head dynamic ranges
    x = (rng.standard_normal((2, 4, 16, 8)) * mag[None, :, None, None]
         ).astype(np.float32)
    amax = np.abs(x).max(axis=(0, 2, 3))
    scale = jnp.asarray(amax)[None, :, None, None]
    q, _ = prec.quantize_fp8(jnp.asarray(x), FP8, scale=scale)
    dq = np.asarray(prec.dequantize_fp8(q, scale, jnp.float32))
    err = np.abs(dq - x)
    for h in range(4):
        m = np.abs(x[:, h]) >= amax[h] * 2.0 ** -6
        assert np.all(err[:, h][m] <= E4M3_EPS * np.abs(x[:, h][m]) * 1.001), \
            f"head {h}: relative error above 2^-3"


def test_prefill_fp8_cache_rows_match_fp16_within_bound(yi):
    """The FP8 prefill cache's dequantized k/v rows match the FP16 cache
    within the per-head E4M3 bound (upcast-on-read inside attention)."""
    cfg, params = yi
    prompts = jax.random.randint(
        jax.random.PRNGKey(1), (2, 6), 0, cfg.vocab_size, jnp.int32)
    _, c16 = transformer.prefill(params, cfg, {"inputs": prompts}, 8)
    _, c8 = transformer.prefill(params, cfg, {"inputs": prompts}, 8,
                                storage_dtype=FP8)
    for name in ("k", "v"):
        wide = np.asarray(c16["layers"][name], np.float32)
        scale = np.asarray(c8["layers"][f"{name}_scale"]["scale"])
        dq = np.asarray(prec.dequantize_fp8(
            c8["layers"][name], jnp.asarray(scale)[:, None, :, None, None],
            jnp.float32))
        # rows past the prompt are zero in both caches; bound on the rest:
        # relative 2^-3 for normalized values plus the subnormal grid's
        # absolute term (scale * 2^-9) for values below scale * 2^-6
        err = np.abs(dq - wide)[:, :, :, :6]
        ref = np.abs(wide)[:, :, :, :6]
        sub = scale[:, None, :, None, None] * 2.0 ** -9
        assert np.all(err <= E4M3_EPS * ref + sub), name


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
@pytest.mark.parametrize("storage", [None, FP8])
def test_insert_slot_preserves_other_slots(yi, storage):
    cfg, params = yi
    pool = transformer.init_cache(cfg, 3, 8, dtype=cfg.policy.compute_dtype,
                                  storage_dtype=storage)
    prompts = jax.random.randint(
        jax.random.PRNGKey(2), (1, 5), 0, cfg.vocab_size, jnp.int32)
    _, single = transformer.prefill(params, cfg, {"inputs": prompts}, 8,
                                    storage_dtype=storage)
    out = insert_slot(pool, single, jnp.int32(1),
                      dtype=cfg.policy.compute_dtype)
    for name in ("k", "v"):
        if storage is None:
            got = np.asarray(out["layers"][name], np.float32)
            want = np.asarray(single["layers"][name], np.float32)[:, 0]
            np.testing.assert_array_equal(got[:, 1], want)
            assert np.all(got[:, 0] == 0) and np.all(got[:, 2] == 0)
        else:
            sc = jnp.asarray(out["layers"][f"{name}_scale"]["scale"])
            got = np.asarray(prec.dequantize_fp8(
                out["layers"][name], sc[:, None, :, None, None], jnp.float32))
            ssc = jnp.asarray(single["layers"][f"{name}_scale"]["scale"])
            want = np.asarray(prec.dequantize_fp8(
                single["layers"][name], ssc[:, None, :, None, None],
                jnp.float32))[:, 0]
            # inserted slot within quant tolerance (relative + subnormal
            # grid at the pool's per-head scale); empty slots stay zero
            sub = np.asarray(sc)[:, None, :, None, None] * 2.0 ** -9
            assert np.all(np.abs(got[:, 1] - want)
                          <= E4M3_EPS * np.abs(want) + sub[:, 0])
            assert np.all(got[:, 0] == 0) and np.all(got[:, 2] == 0)


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
