"""Serving resilience (PR 9): deadlines, admission control, fault-injected
decode recovery, and SLO-gated degradation (docs/serving.md failure model).

The recovery contract pinned here is the tentpole: with ``nan_logits`` or
``kv_corrupt`` injected at step k, the victim request's emitted tokens
and final logits are **bit-identical** to an uninjected run on the FP16
cache (rebuild = re-prefill of ``prompt + emitted`` reproduces the
decode-built cache bitwise — the PR-6 drain invariant), co-resident
slots bitwise unaffected; on the FP8 cache the rebuilt slot stays within
the documented E4M3 bound of the FP16 oracle.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import precision as prec
from repro.models import transformer
from repro.runtime.fault_tolerance import FailureInjector
from repro.serving import (LoadConfig, Request, Scheduler, SchedulerConfig,
                           ShedPolicy, run_load, slo_rows)
from repro.serving import kv_cache

FP8 = "float8_e4m3fn"
E4M3_EPS = 2.0 ** -3  # same bound as tests/test_precision_fp8.py::_EPS


@pytest.fixture(scope="module")
def yi():
    cfg = configs.get_reduced("yi-9b")
    return cfg, transformer.init_params(jax.random.PRNGKey(0), cfg)


def _requests(cfg, n=2, plen=5, gen=5, arrival=0.0, **kw):
    rng = np.random.default_rng(11)
    return [Request(rid=i, arrival=arrival,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        size=plen + i).astype(np.int32),
                    max_new_tokens=gen, **kw)
            for i in range(n)]


def _drain(sched):
    results = sched.run()
    return {r.rid: r for r in results}


# --------------------------------------------------------------------- #
# Satellite: nan-safe RequestResult metrics on an undrained scheduler
# --------------------------------------------------------------------- #
def test_undrained_result_metrics_are_nan(yi):
    """Regression: .ttft/.tokens_per_tick used to raise TypeError while a
    request was still queued or mid-decode (None ticks)."""
    cfg, params = yi
    sched = Scheduler(params, cfg, SchedulerConfig(n_slots=1, max_len=16))
    sched.submit(_requests(cfg, n=2))
    assert math.isnan(sched.results[0].ttft)
    assert math.isnan(sched.results[0].tokens_per_tick)
    sched.step()  # rid 0 prefilled; rid 1 still queued, rid 0 unfinished
    assert math.isnan(sched.results[0].tokens_per_tick)
    assert math.isnan(sched.results[1].ttft)
    assert sched.results[1].status == "pending"


# --------------------------------------------------------------------- #
# Deadlines: queued + mid-decode eviction under the drain invariant
# --------------------------------------------------------------------- #
def test_deadline_evicts_queued_and_mid_decode(yi):
    """One slot, two requests: rid 1 expires in the queue behind rid 0's
    long decode; a third with a budget too small to decode is evicted
    mid-flight, and the freed slot still serves later work."""
    cfg, params = yi
    sched = Scheduler(params, cfg, SchedulerConfig(n_slots=1, max_len=24))
    rng = np.random.default_rng(3)
    mk = lambda rid, arr, gen, dl: Request(
        rid=rid, arrival=arr,
        prompt=rng.integers(0, cfg.vocab_size, size=4).astype(np.int32),
        max_new_tokens=gen, deadline_ticks=dl)
    sched.submit([
        mk(0, 0.0, 8, None),   # hogs the single slot for ~9 ticks
        mk(1, 0.0, 2, 3.0),    # queued behind rid 0 -> expires waiting
        mk(2, 20.0, 8, 3.0),   # starts, but cannot finish in 3 ticks
        mk(3, 40.0, 2, None),  # proves the evicted slot is reusable
    ])
    res = _drain(sched)
    assert res[0].status == "finished" and len(res[0].tokens) == 8
    assert res[1].status == "expired" and res[1].finish_tick is None
    assert res[2].status == "expired" and 0 < len(res[2].tokens) < 8
    assert res[3].status == "finished" and len(res[3].tokens) == 2
    kinds = [(e[0], e[2]) for e in sched.trace]
    assert ("expire", 1) in kinds   # queued expiry
    assert ("evict", 2) in kinds    # mid-decode eviction
    assert kinds.index(("evict", 2)) < kinds.index(("prefill", 3))
    # eviction is billed: rid 2's emitted tokens are waste, not useful
    assert sched.goodput.expired == 2
    assert sched.goodput.wasted_tokens == len(res[2].tokens)
    assert sched.goodput.useful_tokens == 10


# --------------------------------------------------------------------- #
# Bounded admission + client retry/backoff
# --------------------------------------------------------------------- #
def test_bounded_queue_rejects_with_retry_after(yi):
    cfg, params = yi
    scfg = SchedulerConfig(n_slots=1, max_len=16, max_queue=1)
    sched = Scheduler(params, cfg, scfg)
    sched.submit(_requests(cfg, n=4, plen=4, gen=2))
    sched.step()
    rej = {r.rid: r for r in sched.rejections}
    # slot takes rid 0, queue holds rid 1; rids 2-3 bounce with a hint
    assert set(rej) == {2, 3}
    assert all(r.reason == "queue_full" and r.retry_after >= 1.0
               for r in rej.values())
    assert sched.results[2].status == "rejected"


def test_loadgen_retries_until_served_and_reports_rates(yi):
    """Client-side retry with exponential backoff + jitter turns
    queue_full rejections into eventual service; rates are reported and
    unfinished requests are skipped (nan-free aggregation)."""
    cfg, params = yi
    scfg = SchedulerConfig(n_slots=1, max_len=16, max_queue=1)
    lc = LoadConfig(rate=4.0, n_requests=5, prompt_len=4, gen_len=2, seed=0,
                    max_retries=4)
    m = run_load(params, cfg, scfg, lc)
    assert m["retries"] > 0 and m["retry_rate"] > 0
    assert m["n_finished"] + m["abandons"] >= lc.n_requests
    assert np.isfinite(m["p50_ttft_ticks"])
    assert m["slo_rejected"] == m["retries"] + m["abandons"]
    # deterministic end to end: same seed, same story
    m2 = run_load(params, cfg, scfg, lc)
    for k in ("retries", "abandons", "n_finished", "total_tokens", "ticks",
              "p50_ttft_ticks", "deadline_hit_rate", "slo_goodput"):
        assert m[k] == m2[k], k


# --------------------------------------------------------------------- #
# Load shedding
# --------------------------------------------------------------------- #
def test_shed_policy_deterministic_ordering():
    reqs = [Request(rid=i, arrival=float(i % 3),
                    prompt=np.zeros(4, np.int32), max_new_tokens=4,
                    priority=i % 2) for i in range(6)]
    pol = ShedPolicy(queue_high_water=2, shed_infeasible=False)
    victims = pol.select_shed(reqs, clock=10.0, prefill_ticks=1.0)
    # lowest priority first, youngest (latest-arriving) first in a class
    assert [r.rid for r in victims] == [2, 4, 0, 5]
    assert victims == pol.select_shed(reqs, clock=10.0, prefill_ticks=1.0)


def test_scheduler_sheds_infeasible_and_overflow(yi):
    """Deadline-infeasible queued work is shed outright; the high-water
    mark then trims the lowest-priority tail."""
    cfg, params = yi
    scfg = SchedulerConfig(
        n_slots=1, max_len=24, shed=ShedPolicy(queue_high_water=1))
    sched = Scheduler(params, cfg, scfg)
    rng = np.random.default_rng(5)
    mk = lambda rid, gen, dl, pr: Request(
        rid=rid, arrival=0.0,
        prompt=rng.integers(0, cfg.vocab_size, size=4).astype(np.int32),
        max_new_tokens=gen, deadline_ticks=dl, priority=pr)
    sched.submit([
        mk(0, 4, None, 0),   # takes the slot
        mk(1, 8, 5.0, 1),    # infeasible: 1 + 8 > 5 -> shed
        mk(2, 4, None, 1),   # queued, high priority -> survives
        mk(3, 4, None, 0),   # overflow beyond high water, low pri -> shed
    ])
    res = _drain(sched)
    assert res[1].status == "shed" and res[3].status == "shed"
    assert res[0].status == "finished" and res[2].status == "finished"
    assert sched.goodput.shed == 2
    shed_events = [e for e in sched.trace if e[0] == "shed"]
    assert [e[2] for e in shed_events] == [1, 3]


# --------------------------------------------------------------------- #
# Fault injection: prefill_crash
# --------------------------------------------------------------------- #
def test_prefill_crash_retries_and_matches_uninjected(yi):
    cfg, params = yi
    scfg = SchedulerConfig(n_slots=2, max_len=16)
    base = Scheduler(params, cfg, scfg)
    base.submit(_requests(cfg))
    rb = _drain(base)
    inj = Scheduler(params, cfg, scfg,
                    injector=FailureInjector(fail_at_step=1,
                                             mode="prefill_crash"))
    inj.submit(_requests(cfg))
    ri = _drain(inj)
    assert any(e[0] == "prefill_retry" for e in inj.trace)
    for rid in rb:
        assert rb[rid].tokens == ri[rid].tokens
        np.testing.assert_array_equal(rb[rid].final_logits,
                                      ri[rid].final_logits)
    assert inj.goodput.recoveries == 1
    assert inj.goodput.goodput < base.goodput.goodput  # retry billed waste


# --------------------------------------------------------------------- #
# Fault injection: checksum plumbing
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("storage", [None, FP8])
def test_slot_checksum_flags_exactly_the_corrupted_slot(yi, storage):
    cfg, params = yi
    pool = transformer.init_cache(cfg, 3, 8, dtype=cfg.policy.compute_dtype,
                                  storage_dtype=storage)
    rng = np.random.default_rng(7)
    seq = jnp.asarray(
        rng.integers(0, cfg.vocab_size, size=(1, 5)).astype(np.int32))
    _, single = transformer.prefill(params, cfg, {"inputs": seq}, 8,
                                    storage_dtype=storage)
    pool = kv_cache.insert_slot(pool, single, 1, cfg.policy.compute_dtype)
    before = {i: kv_cache.slot_checksum(pool, i, 5) for i in range(3)}
    corrupted = kv_cache.corrupt_slot_rows(pool, 1, [0, 4])
    after = {i: kv_cache.slot_checksum(corrupted, i, 5) for i in range(3)}
    assert after[1] != before[1]
    assert after[0] == before[0] and after[2] == before[2]
    # the flip is confined to the named rows: a checksum over rows [1, 4)
    assert (kv_cache.slot_checksum(corrupted, 1, 4)
            != kv_cache.slot_checksum(pool, 1, 4))  # row 0 is inside
    twice = kv_cache.corrupt_slot_rows(corrupted, 1, [0, 4])
    assert kv_cache.slot_checksum(twice, 1, 5) == before[1]  # involution


# --------------------------------------------------------------------- #
# The recovery contract (acceptance): bit-identical continuation on FP16
# --------------------------------------------------------------------- #
def _paired_run(cfg, params, scfg, mode, step, target=0):
    base = Scheduler(params, cfg, scfg)
    base.submit(_requests(cfg, n=2, plen=4, gen=6))
    rb = _drain(base)
    inj = Scheduler(params, cfg, scfg,
                    injector=FailureInjector(fail_at_step=step, mode=mode,
                                             target=target))
    inj.submit(_requests(cfg, n=2, plen=4, gen=6))
    ri = _drain(inj)
    return base, rb, inj, ri


@pytest.mark.parametrize("mode,detect", [("nan_logits", "nan_detect"),
                                         ("kv_corrupt", "kv_quarantine")])
def test_recovery_bit_identical_fp16(yi, mode, detect):
    """nan_logits / kv_corrupt at decode step 2: the victim's emitted
    tokens and final logits are bit-identical to the uninjected run, and
    the co-resident slot is bitwise unaffected (FP16 cache)."""
    cfg, params = yi
    scfg = SchedulerConfig(n_slots=2, max_len=16, audit_every=1)
    base, rb, inj, ri = _paired_run(cfg, params, scfg, mode, 2)
    ev = [e[0] for e in inj.trace]
    assert detect in ev and "recover" in ev
    victim = [e for e in inj.trace if e[0] == detect][0][2]
    assert victim == 0  # the injector's target rid
    for rid in (0, 1):
        assert ri[rid].status == "finished"
        assert rb[rid].tokens == ri[rid].tokens, f"rid {rid} tokens diverge"
        np.testing.assert_array_equal(
            rb[rid].final_logits, ri[rid].final_logits,
            err_msg=f"rid {rid} final logits not bit-identical")
        assert rb[rid].finish_tick == ri[rid].finish_tick
    # recovery overlapped the clock but was billed as waste slot-ticks
    assert inj.goodput.recoveries == 1
    assert inj.goodput.slot_ticks > base.goodput.slot_ticks
    assert inj.goodput.goodput < base.goodput.goodput
    # the event log is the uninjected one plus the quarantine/recovery pair
    assert [e for e in inj.trace
            if e[0] not in (detect, "recover")] == base.trace


def test_recovery_trace_deterministic_two_runs(yi):
    """Two fresh runs of the same injected-fault + eviction scenario:
    identical trace, health log, tokens, and rejections (seeded injector,
    one-shot latch re-created per run)."""
    cfg, params = yi

    def once():
        scfg = SchedulerConfig(n_slots=2, max_len=16, max_queue=2,
                               audit_every=1)
        lc = LoadConfig(rate=2.0, n_requests=6, prompt_len=4, gen_len=4,
                        seed=9, deadline_ticks=10.0, max_retries=1)
        inj = FailureInjector(fail_at_step=2, mode="kv_corrupt")
        sched = Scheduler(params, cfg, scfg, injector=inj)
        m = run_load(params, cfg, scfg, lc,
                     injector=FailureInjector(fail_at_step=2,
                                              mode="nan_logits"))
        del sched
        return m

    m1, m2 = once(), once()
    for k in sorted(m1):
        if k in ("wall_s", "s_per_tick", "p50_tokens_per_s",
                 "p99_tokens_per_s"):
            continue  # wall-clock derived
        assert m1[k] == m2[k], k


def _run_traced(cfg, params, scfg, lc, injector):
    """run_load plus the scheduler's full event log, for determinism pins."""
    sched = Scheduler(params, cfg, scfg, injector=injector)
    from repro.serving import poisson_requests
    sched.submit(poisson_requests(cfg, lc))
    while sched.step():
        pass
    return (sched.trace, sched.health,
            {rid: r.tokens for rid, r in sched.results.items()},
            sched.rejections)


def test_full_event_log_deterministic_under_faults_and_eviction(yi):
    cfg, params = yi
    scfg = SchedulerConfig(n_slots=2, max_len=16, audit_every=1)
    lc = LoadConfig(rate=1.0, n_requests=5, prompt_len=4, gen_len=5,
                    seed=13, deadline_ticks=9.0)
    runs = [_run_traced(cfg, params, scfg, lc,
                        FailureInjector(fail_at_step=3, mode="kv_corrupt"))
            for _ in range(2)]
    assert runs[0][0] == runs[1][0]    # trace
    assert runs[0][1] == runs[1][1]    # health
    assert runs[0][2] == runs[1][2]    # tokens
    assert runs[0][3] == runs[1][3]    # rejections
    ev = [e[0] for e in runs[0][0]]
    assert "kv_quarantine" in ev       # the fault actually fired
    assert "evict" in ev or "expire" in ev  # and the deadline bit


# --------------------------------------------------------------------- #
# FP8: quarantine/rebuild within the E4M3 bound vs the FP16 oracle
# --------------------------------------------------------------------- #
def test_fp8_rebuild_within_e4m3_bound_vs_fp16_oracle(yi):
    """Corrupt an FP8 slot mid-flight, let the audit quarantine and
    rebuild it, then check the rebuilt rows against the FP16 oracle
    (full prefill over prompt + emitted) within the E4M3 bound, with the
    co-resident slot bitwise untouched."""
    cfg, params = yi
    scfg = SchedulerConfig(n_slots=2, max_len=16, storage_dtype=FP8,
                           audit_every=1)
    sched = Scheduler(params, cfg, scfg)
    sched.submit(_requests(cfg, n=2, plen=4, gen=6))
    for _ in range(4):  # both slots prefillled + a couple decode steps
        sched.step()
    s0, s1 = sched.slots[0], sched.slots[1]
    assert s0 is not None and s1 is not None
    other_before = {
        name: np.asarray(leaf).copy()
        for _k, name, leaf, bax in kv_cache.iter_kv_leaves(sched.cache)}
    sched.cache = kv_cache.corrupt_slot_rows(sched.cache, 0,
                                             [0, s0.pos - 1])
    sched._audit_slots()
    assert any(e[0] == "kv_quarantine" and e[2] == s0.rid
               for e in sched.trace)
    # co-resident slot 1: bitwise identical storage (each row keeps its
    # own scale, so the rebuild's insert touches slot 0 alone)
    for _k, name, leaf, bax in kv_cache.iter_kv_leaves(sched.cache):
        got = np.take(np.asarray(leaf), 1, axis=bax)
        want = np.take(other_before[name], 1, axis=bax)
        np.testing.assert_array_equal(got.view(np.uint8),
                                      want.view(np.uint8), err_msg=name)
    # victim slot 0 vs the FP16 oracle of exactly its absorbed tokens
    absorbed = np.concatenate(
        [s0.prompt, np.asarray(sched.results[s0.rid].tokens[:s0.fed],
                               np.int32)])
    _, oracle = transformer.prefill(
        params, cfg, {"inputs": jnp.asarray(absorbed)[None]}, scfg.max_len)
    n = absorbed.shape[0]
    sub = sched.cache["layers"]
    for name in ("k", "v"):
        sc = np.asarray(sub[f"{name}_scale"])          # (L, B, Hkv, T)
        dq = np.asarray(prec.dequantize_fp8(
            sub[name], jax.numpy.asarray(sc)[..., None],
            jax.numpy.float32))
        got = dq[:, 0, :, :n]
        want = np.asarray(oracle["layers"][name], np.float32)[:, 0, :, :n]
        bound = (E4M3_EPS * np.abs(want)
                 + sc[:, 0, :, :n, None] * 2.0 ** -9)
        assert np.all(np.abs(got - want) <= bound), name


def test_fp8_recovery_continues_and_is_deterministic(yi):
    """End-to-end FP8 injected run: recovery completes every request and
    two runs agree exactly (the within-bound FP8 analogue of the FP16
    bit-identical pin)."""
    cfg, params = yi
    scfg = SchedulerConfig(n_slots=2, max_len=16, storage_dtype=FP8,
                           audit_every=1)

    def once(mode):
        inj = Scheduler(params, cfg, scfg,
                        injector=FailureInjector(fail_at_step=2, mode=mode,
                                                 target=0))
        inj.submit(_requests(cfg, n=2, plen=4, gen=6))
        return inj, _drain(inj)

    for mode in ("nan_logits", "kv_corrupt"):
        i1, r1 = once(mode)
        i2, r2 = once(mode)
        assert any(e[0] == "recover" for e in i1.trace), mode
        assert i1.trace == i2.trace, mode
        for rid in r1:
            assert r1[rid].status == "finished"
            assert r1[rid].tokens == r2[rid].tokens
            np.testing.assert_array_equal(r1[rid].final_logits,
                                          r2[rid].final_logits)


# --------------------------------------------------------------------- #
# CI serve-resilience-gates: SLO floors on the interpret backend
# --------------------------------------------------------------------- #
def _slo_baseline():
    import json
    import os
    path = os.path.join(os.path.dirname(__file__), os.pardir,
                        "benchmarks", "baselines", "serve_slo.json")
    with open(path) as f:
        return json.load(f)


@pytest.mark.parametrize("mode", [None, "nan_logits", "kv_corrupt"])
def test_slo_gate_injected_run_above_floors(mode):
    """The pinned SLO scenario (benchmarks/baselines/serve_slo.json) on
    the interpret backend: the injected run's serve goodput and deadline
    hit rate land above the floors, with the fault demonstrably fired and
    recovered.  This is what the serve-resilience-gates CI job runs."""
    from repro.core import engine
    base = _slo_baseline()
    sc = base["scenario"]
    cfg = configs.get_reduced(sc["arch"])
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    scfg = SchedulerConfig(
        n_slots=sc["n_slots"], max_len=sc["max_len"],
        storage_dtype=sc["storage_dtype"], max_queue=sc["max_queue"],
        audit_every=sc["audit_every"])
    lc = LoadConfig(
        rate=sc["rate"], n_requests=sc["n_requests"],
        prompt_len=sc["prompt_len"], gen_len=sc["gen_len"], seed=sc["seed"],
        deadline_ticks=sc["deadline_ticks"], max_retries=sc["max_retries"])
    injector = None if mode is None else FailureInjector(
        fail_at_step=sc["inject_step"], mode=mode)
    with engine.use_backend("interpret"):
        rows, m = slo_rows(params, cfg, scfg, sc["arch"], lc,
                           injector=injector,
                           tag=f"slo_{mode}" if mode else "slo")
    floor = base["goodput_floor_uninjected"] if mode is None \
        else base["goodput_floor_injected"]
    assert m["slo_goodput"] >= floor, \
        f"serve goodput {m['slo_goodput']:.4f} below floor {floor}"
    assert m["deadline_hit_rate"] >= base["deadline_hit_rate_floor"]
    if mode is not None:
        assert m["slo_recoveries"] >= base["recoveries_min"], \
            "the injected fault never fired/recovered — the gate is vacuous"
        assert injector.fired
    assert m["n_finished"] == sc["n_requests"]
    (name, us, derived), = rows
    assert name.startswith(f"serve/{sc['arch']}/slo")
    assert "goodput=" in derived and "hit=" in derived


# --------------------------------------------------------------------- #
# Guardrails
# --------------------------------------------------------------------- #
def test_kv_corrupt_without_audit_is_refused(yi):
    cfg, params = yi
    with pytest.raises(ValueError, match="audit_every"):
        Scheduler(params, cfg, SchedulerConfig(n_slots=1, max_len=8),
                  injector=FailureInjector(fail_at_step=1,
                                           mode="kv_corrupt"))


def test_injector_serving_modes_noop_in_training_path():
    inj = FailureInjector(fail_at_step=1, mode="nan_logits")
    inj.maybe_fail(1)  # must not raise/exit
    assert not inj.fired
    assert inj.fires(1, "kv_corrupt") is False  # wrong mode
    assert inj.fires(1, "nan_logits") is True
    assert inj.fires(2, "nan_logits") is False  # one-shot latch
