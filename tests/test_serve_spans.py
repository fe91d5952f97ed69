"""The serving scheduler's spans (docs/serving.md, Spans): names, nesting
and metadata as the profiler records them, and a run that is the same with
the profiler on and off."""

import glob
import os

import jax
import pytest

from repro import configs
from repro.models import transformer
from repro.runtime.fault_tolerance import FailureInjector
from repro.serving import (LoadConfig, Scheduler, SchedulerConfig,
                           poisson_requests)
from repro.serving import scheduler as S


@pytest.fixture(scope="module")
def yi():
    cfg = configs.get_reduced("yi-9b")
    return cfg, transformer.init_params(jax.random.PRNGKey(0), cfg)


def _run(cfg, params, injector=None):
    """The seeded load of ``test_serving._run_sched``: five requests over
    two slots, so requests wait in the queue for a slot.  Returns the
    scheduler and the number of ``step`` calls."""
    sched = Scheduler(params, cfg, SchedulerConfig(n_slots=2, max_len=16),
                      injector=injector)
    sched.submit(poisson_requests(cfg, LoadConfig(
        rate=0.5, n_requests=5, prompt_len=5, gen_len=4, seed=7)))
    steps = 1
    while sched.step():
        steps += 1
    return sched, steps


def _spans(log_dir):
    """The ``serve.*`` events of the host plane: (name, start_ns, end_ns,
    metadata), by start."""
    path = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    pd = jax.profiler.ProfileData.from_file(path)
    host = next(p for p in pd.planes if p.name == "/host:CPU")
    out = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
           for ln in host.lines for e in ln.events
           if e.name.startswith("serve.")]
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _traced(tmp_path, cfg, params, injector=None):
    with jax.profiler.trace(str(tmp_path)):
        sched, steps = _run(cfg, params, injector)
    return sched, steps, _spans(str(tmp_path))


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.fixture(scope="module")
def traced(yi, tmp_path_factory):
    return _traced(tmp_path_factory.mktemp("prof"), *yi)


def test_span_names_are_the_modules_constants(traced):
    _, _, spans = traced
    assert {s[0] for s in spans} == {
        S.SPAN_STEP, S.SPAN_ADMIT, S.SPAN_PREFILL, S.SPAN_DECODE,
        S.SPAN_FETCH, S.SPAN_SAMPLE}


def test_one_prefill_span_per_request(traced):
    sched, _, spans = traced
    pre = [s for s in spans if s[0] == S.SPAN_PREFILL]
    prefilled = [e for e in sched.trace if e[0] == "prefill"]
    assert [s[3]["rid"] for s in pre] == [e[2] for e in prefilled]
    for s, e in zip(pre, prefilled):
        assert s[3]["plen"] == e[4]
        res = sched.results[e[2]]
        assert s[3]["queued_ms"] >= 0
        assert s[3]["queued_ms"] == pytest.approx(1e3 * res.queue_s, rel=1e-9)
    # rid 4 arrives with both slots taken and waits for one
    assert sched.results[4].queue_s > 0


def test_decode_spans_hold_one_fetch_then_one_sample(traced):
    sched, _, spans = traced
    dec = [s for s in spans if s[0] == S.SPAN_DECODE]
    assert len(dec) == sched.decode_steps
    assert [s[3]["active"] for s in dec] == [
        h["active_slots"] for h in sched.health]
    assert all(s[3]["slots"] == 2 for s in dec)
    for d in dec:
        inner = [s for s in spans if s is not d and _inside(s, d)]
        assert [s[0] for s in inner] == [S.SPAN_FETCH, S.SPAN_SAMPLE]
        assert inner[0][2] <= inner[1][1]


def test_every_span_nests_in_one_step(traced):
    _, steps, spans = traced
    step = [s for s in spans if s[0] == S.SPAN_STEP]
    assert len(step) == steps
    assert all(a[2] <= b[1] for a, b in zip(step, step[1:]))
    for s in spans:
        if s[0] != S.SPAN_STEP:
            assert sum(_inside(s, t) for t in step) == 1, s
    # admission: before the prefills and after them, and once per prefill
    admit = [s for s in spans if s[0] == S.SPAN_ADMIT]
    n_pre = sum(s[0] == S.SPAN_PREFILL for s in spans)
    assert len(admit) == 2 * steps + n_pre
    assert all(s[3]["queued"] >= 0 for s in admit)


def test_profiler_changes_nothing_the_scheduler_records(yi, traced):
    on, steps_on, _ = traced
    off, steps_off = _run(*yi)
    assert steps_on == steps_off
    assert on.trace == off.trace
    assert on.health == off.health
    for rid in on.results:
        a, b = on.results[rid], off.results[rid]
        assert a.tokens == b.tokens
        assert (a.queue_s is None) == (b.queue_s is None)
        assert a.queue_s is None or (a.queue_s >= 0 and b.queue_s >= 0)


def test_recover_span_carries_the_victims_rid(yi, tmp_path):
    inj = FailureInjector(fail_at_step=2, mode="nan_logits", target=0)
    sched, _, spans = _traced(tmp_path, *yi, injector=inj)
    rec = [s for s in spans if s[0] == S.SPAN_RECOVER]
    detected = [e[2] for e in sched.trace if e[0] == "nan_detect"]
    assert detected and [s[3]["rid"] for s in rec] == detected
    sample = [s for s in spans if s[0] == S.SPAN_SAMPLE]
    assert all(any(_inside(r, s) for s in sample) for r in rec)
