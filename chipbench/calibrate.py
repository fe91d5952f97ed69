"""Readings that set the benchmark's limits and its traffic rates.

    python3 chipbench/calibrate.py readings --workload <cell> --seeds 1,2,3 --seconds 20
    python3 chipbench/calibrate.py knee --workload <cell> --rates 1,2,3 --seconds 30

``readings`` runs the cell once per seed in one process, as ``run.py``
does, and beside the program's compared numbers reads the control's: the
plain reference put in the program's place at E4M3 operands (and, for
training, the reference fed half of each batch).  ``knee`` builds one
server and offers the cell's mix at each rate in turn, a window each, and
reports whether the backlog grew.  Neither is part of a benchmark run; each
prints one JSON line per seed or rate and writes the whole records to a
file under ``--out`` (``calibrate_out/`` at the checkout's root).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != HERE]
sys.path.insert(0, str(ROOT))
sys.path.insert(1, str(ROOT / "src"))

from chipbench import run as R  # noqa: E402


def context(args, seed):
    """The context ``run.py`` builds for one run of the cell, at ``seed``."""
    run_args = argparse.Namespace(seed=seed, seconds=args.seconds, trace=0)
    return R.cell_context(R.load_json(ROOT / "BENCHMARK.json"),
                          args.workload, run_args,
                          t_start=time.perf_counter())


def emit(out, rec):
    """The whole record to the file, its short form to stdout."""
    out.write(json.dumps(rec) + "\n")
    out.flush()
    short = {k: v for k, v in rec.items()
             if k not in ("prog", "ref", "names", "control_ref", "half_ref")}
    if "compare" in short:
        short["compare"] = {k: v for k, v in short["compare"].items()
                            if not isinstance(v, list)}
    print(json.dumps(short), flush=True)


def readings(args, out):
    for seed in [int(s) for s in args.seeds.split(",")]:
        ctx, driver = context(args, seed)
        t = time.perf_counter()
        r = driver.run_once(ctx, control=True)
        rec = {"seed": seed, "run_s": time.perf_counter() - t,
               "setup_s": r["setup_s"], "attempted": r["attempted"],
               "failed": r["failed"], "memory": r["memory"]}
        if "compare" in r:
            rec.update(latency=r["latency"], compare=r["compare"])
        else:
            rec.update({k: r[k] for k in (
                "train_tok_s", "readings", "control", "half_batch", "prog",
                "ref", "names", "control_ref", "half_ref")})
        emit(out, rec)
        del r
        gc.collect()


def knee(args, out):
    from chipbench import traffic
    from chipbench.drivers import serve

    ctx, _ = context(args, args.seed)
    sched = serve.build(ctx)
    for i, rate in enumerate(float(x) for x in args.rates.split(",")):
        plan = traffic.open_loop(ctx.mix, args.seed + i, args.seconds,
                                 ctx.dims.vocab, rate=rate)
        base = 100000 * (i + 1)
        plan = [traffic.Arrival(a.rid + base, a.due_s, a.prompt, a.max_new)
                for a in plan]
        w = serve.serve_window(ctx, sched, plan, args.seconds)
        lat = serve.latency(plan, w)
        # backlog: requests due but not started, sampled at each step's end
        starts = sorted(w["first"].get(a.rid, float("inf")) - w["t0"]
                        for a in plan)
        dues = [a.due_s for a in plan]
        half = args.seconds / 2
        def waiting(t):
            return sum(d <= t for d in dues) - sum(s <= t for s in starts)
        ttft = [w["first"][a.rid] - w["t0"] - a.due_s for a in plan
                if a.rid in w["first"]]
        q = len(ttft) // 4
        emit(out, {"rate": rate, "n": len(plan), "latency": lat,
                   "waiting_half": waiting(half),
                   "waiting_end": waiting(args.seconds),
                   "drain_s": w["end"] - w["window_end"],
                   "ttft_first_quarter_mean": sum(ttft[:q]) / max(q, 1),
                   "ttft_last_quarter_mean": sum(ttft[-q:]) / max(q, 1),
                   "decode_steps": len([s for s in w["steps"] if s[3]]),
                   "window_steps_s": w["window_end"] - w["t0"],
                   "unfinished": len(w["unfinished"])})


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("what", choices=("readings", "knee"))
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", default="1")
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--rates", default="1")
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--out", default=str(ROOT / "calibrate_out"))
    args = p.parse_args()
    d = Path(args.out)
    d.mkdir(parents=True, exist_ok=True)
    name = f"{args.what}-{args.workload}-{int(time.time())}.jsonl"
    with open(d / name, "w") as out:
        (readings if args.what == "readings" else knee)(args, out)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
