"""The highest rate the hybrid serving cell sustains, by a sweep on one server.

    python3 chipbench/knee_hybrid.py --workload granite4h-chat \
        --rates 1.75,2,2.25,2.5 --seconds 45 --drain 15 [--write]

``calibrate.py knee`` builds the dense ``serve`` driver only; this sweep
builds ``serve_hybrid``'s scheduler once and offers the cell's mix at each
rate in turn, lowest first, for a window each (choose windows longer than
a request's life), then drains for at most ``--drain`` seconds before the
next rate; requests still running then stay in their slots, so a later
rate starts with some slots taken and the knee errs low.  A rate is held
when the requests due but not yet started are no more at the window's end
than at its half, and at most two.  The knee is the highest rate held with
every lower rate held; the cell's rate is four fifths of it.  With
``--write`` both go into the cell's traffic file.

Per window it also reports the medians of the scheduler's ``serve.*``
spans, timed on the host clock: a decode step (``serve.decode``) and its
parts, the wait for the device and the logits' copy (``serve.decode.fetch``)
and the host's sampling (``serve.decode.sample``).  One JSON line per rate
goes to stdout and to a file under ``--out``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != HERE]
sys.path.insert(0, str(ROOT))
sys.path.insert(1, str(ROOT / "src"))

from chipbench import run as R  # noqa: E402


class SpanClock:
    """Stands in for the scheduler's span: the same annotation, and its
    duration on the host clock kept under its name."""

    def __init__(self, real):
        self.real = real
        self.times = collections.defaultdict(list)

    def __call__(self, name, **kw):
        return _Timed(self, name, self.real(name, **kw))


class _Timed:
    def __init__(self, clock, name, inner):
        self.clock, self.name, self.inner = clock, name, inner

    def __enter__(self):
        self.t = time.perf_counter()
        return self.inner.__enter__()

    def __exit__(self, *exc):
        out = self.inner.__exit__(*exc)
        self.clock.times[self.name].append(time.perf_counter() - self.t)
        return out


def median_ms(xs):
    return 1e3 * statistics.median(xs) if xs else None


def window_stats(plan, w, seconds):
    """Backlog and latency of one window, from the driver's bookkeeping."""
    from chipbench.drivers import serve

    starts = sorted(w["first"].get(a.rid, float("inf")) - w["t0"]
                    for a in plan)
    dues = [a.due_s for a in plan]

    def waiting(t):
        return sum(d <= t for d in dues) - sum(s <= t for s in starts)

    ttft = [w["first"][a.rid] - w["t0"] - a.due_s for a in plan
            if a.rid in w["first"]]
    q = max(len(ttft) // 4, 1)
    inside = [s for s in w["steps"] if s[1] <= w["window_end"]]
    dec = [s for s in inside if s[3] and not s[2]]
    return {"n": len(plan), "latency": serve.latency(plan, w),
            "waiting_half": waiting(seconds / 2),
            "waiting_end": waiting(seconds),
            "ttft_first_quarter_mean": sum(ttft[:q]) / q,
            "ttft_last_quarter_mean": sum(ttft[-q:]) / q,
            "decode_only_step_ms_median": median_ms(
                [s[1] - s[0] for s in dec]),
            "decode_slots_median": (statistics.median(len(s[3]) for s in dec)
                                    if dec else None),
            "prefill_steps": sum(1 for s in inside if s[2]),
            "drain_s": w["end"] - w["window_end"],
            "unfinished": len(w["unfinished"])}


def held(rec) -> bool:
    return (rec["waiting_end"] <= rec["waiting_half"]
            and rec["waiting_end"] <= 2)


def sweep(args, out, bench=None, **where):
    """The sweep; ``bench`` and ``where`` (passed to ``run.cell_context``)
    let the tests point it at small stand-ins on the CPU."""
    from repro.serving import scheduler as S

    if bench is None:
        bench = R.load_json(ROOT / "BENCHMARK.json")
    run_args = argparse.Namespace(seed=args.seed, seconds=args.seconds,
                                  trace=0)
    ctx, _ = R.cell_context(bench, args.workload, run_args,
                            t_start=time.perf_counter(), **where)
    clock = SpanClock(S._span)
    S._span = clock
    try:
        _sweep(args, out, ctx, clock)
    finally:
        S._span = clock.real


def _sweep(args, out, ctx, clock):
    from chipbench import traffic
    from chipbench.drivers import serve, serve_hybrid

    t = time.perf_counter()
    sched = serve_hybrid.build(ctx)
    emit(out, {"build_s": time.perf_counter() - t})
    ctx.mix = dict(ctx.mix, drain_cap_s=args.drain)
    knee, all_held = None, True
    for i, rate in enumerate(float(x) for x in args.rates.split(",")):
        plan = traffic.open_loop(ctx.mix, args.seed + i, args.seconds,
                                 ctx.conf["vocab_size"], rate=rate)
        base = 100000 * (i + 1)
        plan = [traffic.Arrival(a.rid + base, a.due_s, a.prompt, a.max_new)
                for a in plan]
        taken = sum(s is not None for s in sched.slots)
        clock.times.clear()
        w = serve.serve_window(ctx, sched, plan, args.seconds)
        rec = {"rate": rate, "slots_taken_at_start": taken,
               **window_stats(plan, w, args.seconds),
               "span_ms_median": {k: median_ms(v)
                                  for k, v in sorted(clock.times.items())}}
        rec["held"] = held(rec)
        all_held = all_held and rec["held"]
        if all_held:
            knee = rate
        emit(out, rec)
    rate = None if knee is None else round(0.8 * knee, 2)
    emit(out, {"knee_per_s": knee, "rate_per_s": rate})
    if args.write and knee is not None:
        path = HERE / "traffic" / f"{ctx.cell['traffic']}.json"
        mix = R.load_json(path)
        mix.update(rate_per_s=rate, knee_per_s=knee)
        path.write_text(json.dumps(mix, indent=2) + "\n")


def emit(out, rec):
    out.write(json.dumps(rec) + "\n")
    out.flush()
    print(json.dumps(rec), flush=True)


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--seconds", type=float, default=45.0)
    p.add_argument("--drain", type=float, default=15.0)
    p.add_argument("--write", action="store_true")
    p.add_argument("--out", default=str(ROOT / "calibrate_out"))
    args = p.parse_args()
    d = Path(args.out)
    d.mkdir(parents=True, exist_ok=True)
    name = f"knee-{args.workload}-{int(time.time())}.jsonl"
    with open(d / name, "w") as out:
        sweep(args, out)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
