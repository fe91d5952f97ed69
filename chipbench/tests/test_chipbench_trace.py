"""The trace reduction against a small trace recorded on a TPU v5e.

``data/serve_small.xplane.pb.gz`` holds one scheduler request at yi-9b widths
(one layer, 2 slots, E4M3 cache) inside the benchmark's ``cb.window`` with
``cb.submit``, ``cb.step`` and ``cb.wait`` spans.  Each number the
reduction gives is checked against a plain recomputation from the raw
events read here.
"""

from pathlib import Path

import pytest

from chipbench import trace as tr

FIXTURE = Path(__file__).resolve().parent / "data" / "serve_small.xplane.pb.gz"


@pytest.fixture(scope="module")
def raw():
    pd = tr.read_profile(str(FIXTURE))
    dev = next(p for p in pd.planes if p.name == "/device:TPU:0")
    lines = {ln.name: [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in ln.events] for ln in dev.lines}
    return lines


@pytest.fixture(scope="module")
def t():
    return tr.load(str(FIXTURE))


def brute_union(intervals):
    """Covered length by sweeping sorted boundaries with a depth counter."""
    edges = sorted([(a, 1) for a, _ in intervals]
                   + [(b, -1) for _, b in intervals])
    depth, last, total = 0, None, 0.0
    for x, d in edges:
        if depth > 0:
            total += x - last
        depth += d
        last = x
    return total


def test_planes_and_window(t):
    assert set(t.devices) == {0}
    names = {s.name for s in t.host}
    assert {"cb.window", "cb.step", "cb.submit", "cb.wait"} <= names
    lo, hi = t.window
    assert 0 < hi - lo < 1.0
    # device programs start after the host enqueued them
    assert t.offset > 0


def test_busy_union_and_idle_share(t, raw):
    lo, hi = t.window
    mods = [((a * 1e-9) + t.offset, (b * 1e-9) + t.offset)
            for _, a, b in raw["XLA Modules"]]
    clipped = [(max(a, lo), min(b, hi)) for a, b in mods if b > lo and a < hi]
    want = brute_union(clipped)
    assert want > 0
    assert tr.busy_s(t) == pytest.approx(want, rel=1e-9)
    assert tr.idle_share(t) == pytest.approx(1 - want / (hi - lo), rel=1e-9)


def test_module_and_kernel_time(t, raw):
    decode = tr.modules_named(t, "jit__decode")
    pre = tr.modules_named(t, "jit_pre")
    assert len(pre) == 1 and len(decode) >= 2
    want = sum(b - a for n, a, b in raw["XLA Modules"]
               if n.startswith("jit__decode(")) * 1e-9
    assert sum(m.dur for m in decode) == pytest.approx(want, rel=1e-9)
    ops = tr.ops_within(t, decode)
    k = tr.kernel_s(ops)
    starts = [(a * 1e-9 + t.offset, b * 1e-9 + t.offset)
              for _, a, b in raw["XLA Modules"] if _.startswith("jit__decode(")]
    want_k = sum((b - a) * 1e-9 for n, a, b in raw["XLA Ops"]
                 if n.lstrip("%").startswith("redmule_")
                 and any(s <= a * 1e-9 + t.offset and b * 1e-9 + t.offset <= e
                         for s, e in starts))
    assert k > 0
    assert k == pytest.approx(want_k, rel=1e-9)
    assert k < sum(m.dur for m in decode)


def test_gap_attribution(t):
    gaps = tr.gap_attribution(t)
    lo, hi = t.window
    assert sum(gaps.values()) == pytest.approx(
        (hi - lo) - tr.busy_s(t), rel=1e-6)
    # the driver slept in cb.wait with nothing queued on the device
    assert gaps.get("cb.wait", 0) > 0.002
    bd = tr.breakdown(t)
    assert bd["idle_gaps"][0][1] >= bd["idle_gaps"][-1][1]
    assert any(k.startswith("jit__decode/redmule_") for k, _ in bd["device_ops"])
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
