"""Reduce a JAX profiler trace (``.xplane.pb``) to device time.

What is read:

* device planes ``/device:TPU:<n>``: the ``XLA Modules`` line (one event per
  executable run, ``jit_<fn>(<hash>)``) and the ``XLA Ops`` line (one event
  per operation, ``%<op>.<n> = <hlo>``);
* the host plane ``/host:CPU``: the benchmark's own ``TraceAnnotation``
  spans, named ``cb.<what>``, and the runtime's ``DoEnqueueProgram`` events,
  whose ``run_id`` matches a device module's.

Host and device events come on clocks that differ by an offset.  A module
cannot start before the host enqueued it, so the offset is taken as the
largest ``enqueue - device start`` over matched ``run_id``s; it is exact
where the device was idle when a program arrived, and the device was idle
for every gap that matters here.

All times are seconds on the host's clock of the trace.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
HOST_PREFIX = "cb."
WINDOW = "cb.window"
KERNEL = re.compile(r"^redmule_")


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Device:
    modules: List[Span]
    ops: List[Span]      # by start; an enclosing op before those it holds
    op_starts: List[float] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Trace:
    devices: Dict[int, Device]
    host: List[Span]             # cb.* annotations, by start
    window: Tuple[float, float]  # the cb.window annotation
    offset: float                # seconds added to device times


def module_name(raw: str) -> str:
    """``jit__decode(982...)`` -> ``jit__decode``."""
    return raw.split("(", 1)[0]


def op_name(raw: str) -> str:
    """``%redmule_matmul_nn.9 = bf16[..] ...`` -> ``redmule_matmul_nn.9``."""
    return raw.split(" = ", 1)[0].lstrip("%").strip()


def op_kind(name: str) -> str:
    """``redmule_matmul_nn.9`` -> ``redmule_matmul_nn``."""
    return re.sub(r"\.\d+$", "", name)


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def read_profile(path: str):
    """``ProfileData`` of an ``.xplane.pb``, or of one gzipped (``.gz``)."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def load(path: str) -> Trace:
    pd = read_profile(path)
    devices: Dict[int, Device] = {}
    dev_runs: Dict[int, float] = {}
    host: List[Span] = []
    enqueue: Dict[int, float] = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = Device(modules=[], ops=[])
            for line in plane.lines:
                if line.name not in ("XLA Modules", "XLA Ops"):
                    continue
                for ev in line.events:
                    t0 = ev.start_ns * 1e-9
                    t1 = t0 + ev.duration_ns * 1e-9
                    if line.name == "XLA Modules":
                        dev.modules.append(Span(module_name(ev.name), t0, t1))
                        for k, v in ev.stats:
                            if k == "run_id":
                                dev_runs.setdefault(int(v), t0)
                    else:
                        dev.ops.append(Span(op_name(ev.name), t0, t1))
            devices[int(m.group(1))] = dev
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    t0 = ev.start_ns * 1e-9
                    if ev.name.startswith(HOST_PREFIX):
                        host.append(Span(ev.name, t0,
                                         t0 + ev.duration_ns * 1e-9))
                    elif ev.name == "DoEnqueueProgram":
                        for k, v in ev.stats:
                            if k == "run_id":
                                r = int(v)
                                enqueue[r] = min(enqueue.get(r, t0), t0)
    if not devices:
        raise ValueError(f"{path}: no TPU device plane")
    shifts = [enqueue[r] - t for r, t in dev_runs.items() if r in enqueue]
    offset = max(shifts) if shifts else 0.0
    if offset:
        for dev in devices.values():
            dev.modules = [Span(s.name, s.start + offset, s.end + offset)
                           for s in dev.modules]
            dev.ops = [Span(s.name, s.start + offset, s.end + offset)
                       for s in dev.ops]
    for dev in devices.values():
        dev.modules.sort(key=lambda s: s.start)
        dev.ops.sort(key=lambda s: (s.start, -s.end))
        dev.op_starts = [o.start for o in dev.ops]
    host.sort(key=lambda s: s.start)
    wins = [s for s in host if s.name == WINDOW]
    if wins:
        window = (wins[0].start, wins[-1].end)
    else:
        spans = [s for d in devices.values() for s in d.modules]
        window = (min(s.start for s in spans), max(s.end for s in spans))
    return Trace(devices=devices, host=host, window=window, offset=offset)


# --------------------------------------------------------------------- #
# Interval arithmetic
# --------------------------------------------------------------------- #
def merged(spans: Iterable[Span], lo: float, hi: float
           ) -> List[Tuple[float, float]]:
    """Union of the spans, clipped to [lo, hi], as disjoint sorted pieces."""
    pieces = sorted((max(s.start, lo), min(s.end, hi)) for s in spans
                    if s.end > lo and s.start < hi)
    out: List[Tuple[float, float]] = []
    for a, b in pieces:
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def covered(spans: Iterable[Span], lo: float, hi: float) -> float:
    return sum(b - a for a, b in merged(spans, lo, hi))


def busy_s(tr: Trace) -> float:
    """Seconds in the window in which some module ran, averaged over chips."""
    lo, hi = tr.window
    return sum(covered(d.modules, lo, hi) for d in tr.devices.values()) \
        / len(tr.devices)


def window_s(tr: Trace) -> float:
    return tr.window[1] - tr.window[0]


def idle_share(tr: Trace) -> float:
    return 1.0 - busy_s(tr) / window_s(tr)


def modules_named(tr: Trace, name: str, device: int = 0) -> List[Span]:
    lo, hi = tr.window
    return [s for s in tr.devices[device].modules
            if s.name == name and s.start >= lo and s.end <= hi]


def ops_within(tr: Trace, outer: Sequence[Span], device: int = 0
               ) -> List[Span]:
    """Operations that lie inside any of the given module runs."""
    ops, starts = tr.devices[device].ops, tr.devices[device].op_starts
    out: List[Span] = []
    for m in outer:
        i = bisect.bisect_left(starts, m.start)
        while i < len(ops) and ops[i].start < m.end:
            if ops[i].end <= m.end:
                out.append(ops[i])
            i += 1
    return out


def kernel_s(ops: Iterable[Span]) -> float:
    """Summed time of the ``redmule_*`` kernel operations among ``ops``."""
    return sum(o.dur for o in ops if KERNEL.match(o.name))


def idle_gaps(tr: Trace, device: int = 0) -> List[Tuple[float, float]]:
    """The window's stretches in which no program ran on the device."""
    lo, hi = tr.window
    gaps, t = [], lo
    for a, b in merged(tr.devices[device].modules, lo, hi):
        if a > t:
            gaps.append((t, a))
        t = b
    if hi > t:
        gaps.append((t, hi))
    return gaps


def host_at(tr: Trace, t: float, look_back: int = 64,
            starts: Optional[List[float]] = None) -> Optional[str]:
    """The innermost cb.* span open at host time t: of the spans open then,
    the one that opened last.  cb.window answers only where none other is
    open among the ``look_back`` spans that opened last before t."""
    if starts is None:
        starts = [s.start for s in tr.host]
    i = bisect.bisect_right(starts, t)
    for s in reversed(tr.host[max(0, i - look_back):i]):
        if s.end >= t and s.name != WINDOW:
            return s.name
    return WINDOW if tr.window[0] <= t <= tr.window[1] else None


def gap_attribution(tr: Trace, device: int = 0) -> Dict[str, float]:
    """Idle seconds of the window, by the host span open mid-gap."""
    out: Dict[str, float] = {}
    starts = [s.start for s in tr.host]
    for a, b in idle_gaps(tr, device):
        name = host_at(tr, 0.5 * (a + b), starts=starts) or "outside"
        out[name] = out.get(name, 0.0) + (b - a)
    return out


def self_times(ops: Sequence[Span]) -> List[float]:
    """Each operation's time less that of the operations nested in it (a
    ``while`` holds its body's operations)."""
    self_t = [o.dur for o in ops]
    stack: List[int] = []
    for i, o in enumerate(ops):
        while stack and ops[stack[-1]].end <= o.start:
            stack.pop()
        if stack and o.end <= ops[stack[-1]].end:
            self_t[stack[-1]] -= o.dur
        stack.append(i)
    return self_t


def op_time(tr: Trace, device: int = 0) -> Dict[str, float]:
    """Device seconds of the window by ``<module>/<op kind>``, each
    operation's own time only."""
    lo, hi = tr.window
    mods = [m for m in tr.devices[device].modules
            if m.start >= lo and m.end <= hi]
    out: Dict[str, float] = {}
    for m in mods:
        ops = ops_within(tr, [m], device)
        for o, t in zip(ops, self_times(ops)):
            k = f"{m.name}/{op_kind(o.name)}"
            out[k] = out.get(k, 0.0) + t
    return out


def breakdown(tr: Trace, top: int = 10) -> Dict[str, List[List]]:
    def best(d: Dict[str, float]) -> List[List]:
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                ][:top]

    return {"device_ops": best(op_time(tr)),
            "idle_gaps": best(gap_attribution(tr))}
