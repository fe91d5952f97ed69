"""The program's own spans in a trace recorded on the chip.

``data/serve_spans.xplane.pb.gz`` was recorded on a TPU v5e by
``record_serve_spans.py``: one yi-9b layer, 2 slots, three requests at once,
so the third waits in the queue; the scheduler's ``serve.*`` spans sit
inside the benchmark's ``cb.*`` ones.  The reduction leaves the program's
spans out of everything it returns, and on the host's clock of the
reduction each device run of a prefill or a decode ends inside the host
span that waits for it.
"""

from pathlib import Path

import pytest

from chipbench import trace as tr
from repro.serving import scheduler as S

SPANS = Path(__file__).resolve().parent / "data" / "serve_spans.xplane.pb.gz"


@pytest.fixture(scope="module")
def t():
    return tr.load(str(SPANS))


@pytest.fixture(scope="module")
def raw():
    """Host spans of the program (name, start s, end s, metadata), by
    start, as the profiler wrote them."""
    pd = tr.read_profile(str(SPANS))
    host = next(p for p in pd.planes if p.name == tr.HOST_PLANE)
    return sorted(((e.name, e.start_ns * 1e-9,
                    (e.start_ns + e.duration_ns) * 1e-9, dict(e.stats))
                   for ln in host.lines for e in ln.events
                   if e.name.startswith("serve.")), key=lambda s: s[1])


def test_reduction_leaves_program_spans_out(t, raw):
    assert raw
    assert all(s.name.startswith(tr.HOST_PREFIX) for s in t.host)
    names = {k for k, _ in tr.breakdown(t)["idle_gaps"]}
    assert names and all(n.startswith(tr.HOST_PREFIX) for n in names)


def test_span_names_on_the_chip(raw):
    assert {s[0] for s in raw} == {
        S.SPAN_STEP, S.SPAN_ADMIT, S.SPAN_PREFILL, S.SPAN_DECODE,
        S.SPAN_FETCH, S.SPAN_SAMPLE}


def test_prefill_metadata_on_the_chip(raw):
    pre = [s[3] for s in raw if s[0] == S.SPAN_PREFILL]
    assert [m["rid"] for m in pre] == [0, 1, 2]
    assert all(m["plen"] == 128 for m in pre)
    # the third request waited in the queue for the first two to finish
    assert pre[2]["queued_ms"] > pre[1]["queued_ms"] \
        > pre[0]["queued_ms"] >= 0


@pytest.mark.parametrize("span,program", [
    (S.SPAN_PREFILL, "jit_pre"),
    (S.SPAN_FETCH, "jit__decode"),
])
def test_each_device_run_ends_inside_the_span_that_waits_for_it(
        t, raw, span, program):
    runs = [m for m in t.devices[0].modules if m.name == program]
    spans = [s for s in raw if s[0] == span]
    assert len(spans) >= 3 and len(runs) == len(spans)
    for s, m in zip(spans, runs):
        assert s[1] <= m.end <= s[2], (s, m)
