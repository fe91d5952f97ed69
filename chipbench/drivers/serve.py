"""Open-loop serving through the program's continuous-batching scheduler.

Set-up makes the weights from the seed, builds one ``Scheduler`` and warms
every prompt length of the mix (one short request each) and the decode
step.  The window then offers the mix's requests at their due times,
whatever the server is doing: between ``Scheduler.step`` calls every
request whose time has come is submitted.  Time to first token runs from a
request's due time to the end of the step that produced its first token;
the gaps between tokens are taken from the second token on (a request's
prefill step also decodes once, so its first two tokens appear together).
After the window no request is offered; those already due are drained
under the mix's cap, and any left count as failed.

``correct`` compares what the window served with the plain float32
reference, once the program's state is freed: a sample drawn from the seed
of the requests finished in the window, the one with the longest sequence
among them, of at least ``check_tokens`` served tokens.  Compared: the
mean over those tokens of the gap by which a served token's reference
logit lies below the reference's best at its position
(``token_gap_mean``).  ``calibrate.py`` also reads the widest such gap and
the final logits' error, beside the control's.
"""

from __future__ import annotations

import gc
import math
import time
from typing import Dict, List

import numpy as np

from chipbench import model, traffic
from chipbench.common import checks, memory_peak_bytes, now, passed, percentile
from chipbench.reference import dense

WARM_NEW_TOKENS = 2


def build(ctx):
    """The weights and the scheduler, warmed on every shape the mix uses."""
    import jax
    from repro.serving import scheduler as S

    conf, mix = ctx.conf, ctx.mix
    cfg = model.program_config(conf)
    weights = model.init_weights(ctx.args.seed, ctx.dims)
    model.check_layout(weights, cfg)
    sv = conf["serving"]
    sched = S.Scheduler(weights, cfg, S.SchedulerConfig(
        n_slots=sv["slots"], max_len=sv["max_len"],
        storage_dtype=sv["kv_storage"]))
    rng = np.random.default_rng([int(ctx.args.seed), 0xC0DE])
    lens = sorted(mix["prompt"]["buckets"])
    sched.submit([S.Request(
        rid=-1 - i, arrival=sched.clock,
        prompt=rng.integers(0, ctx.dims.vocab, n, dtype=np.int32),
        max_new_tokens=WARM_NEW_TOKENS) for i, n in enumerate(lens)])
    while sched.step():
        pass
    jax.block_until_ready(sched.cache)
    return sched


def serve_window(ctx, sched, plan: List[traffic.Arrival], seconds: float,
                 tracer=None) -> Dict:
    """Offer ``plan`` open-loop for ``seconds``, then drain.  Returns the
    per-request timings and the steps of the window."""
    import jax
    from repro.serving import scheduler as S

    ann = jax.profiler.TraceAnnotation
    by_rid = {a.rid: a for a in plan}
    first: Dict[int, float] = {}
    times: Dict[int, List[float]] = {a.rid: [] for a in plan}
    inflight: Dict[int, int] = {}      # rid -> tokens seen so far
    steps = []                         # (t0, t1, [prompt lens], [kv lens])
    nxt = 0
    if tracer is not None:
        tracer.start()
    t0 = now()
    t_end = t0 + seconds
    win = ann("cb.window")
    win.__enter__()
    in_window, window_end = True, None
    cap = t0 + seconds + ctx.mix["drain_cap_s"]
    while True:
        t = now()
        if in_window and t >= t_end:
            in_window, window_end = False, t
            win.__exit__(None, None, None)
            if tracer is not None:
                tracer.stop()
        if nxt < len(plan) and t0 + plan[nxt].due_s <= t:
            with ann("cb.submit"):
                batch = []
                while nxt < len(plan) and t0 + plan[nxt].due_s <= t:
                    a = plan[nxt]
                    batch.append(S.Request(rid=a.rid, arrival=sched.clock,
                                           prompt=a.prompt,
                                           max_new_tokens=a.max_new))
                    inflight[a.rid] = 0
                    nxt += 1
                sched.submit(batch)
        if not inflight:
            if nxt >= len(plan):
                if in_window:
                    with ann("cb.wait"):
                        time.sleep(max(0.0, t_end - now()))
                    continue
                break
            with ann("cb.wait"):
                time.sleep(max(0.0, min(t0 + plan[nxt].due_s, t_end) - now()))
            continue
        if not in_window and t > cap:
            break
        # the driver's own bookkeeping of this step's decode batch: a
        # request with e tokens so far attends P + e keys, a new one P + 1
        kv = [len(by_rid[r].prompt) + e for r, e in inflight.items() if e]
        ts = now()
        with ann("cb.step"):
            sched.step()
        te = now()
        started, done = [], []
        for r, seen in inflight.items():
            res = sched.results[r]
            n = len(res.tokens)
            if n > seen:
                if seen == 0:
                    first[r] = te
                    started.append(len(by_rid[r].prompt))
                    kv.append(len(by_rid[r].prompt) + 1)
                times[r].extend([te] * (n - seen))
                inflight[r] = n
            if res.status != "pending":
                done.append(r)
        for r in done:
            del inflight[r]
        steps.append((ts, te, started, kv if kv else []))
    if in_window:
        win.__exit__(None, None, None)
        if tracer is not None:
            tracer.stop()
        window_end = now()
    return {"t0": t0, "window_end": window_end, "end": now(), "first": first,
            "times": times, "steps": steps, "unfinished": sorted(inflight)}


def latency(plan, w) -> Dict[str, float]:
    ttft, itl = [], []
    for a in plan:
        f = w["first"].get(a.rid)
        ttft.append(math.inf if f is None else f - (w["t0"] + a.due_s))
        ts = w["times"][a.rid]
        itl.extend(ts[k] - ts[k - 1] for k in range(2, len(ts)))
    out = {"ttft_p95_s": percentile(ttft, 95), "itl_p95_s": percentile(itl, 95)}
    for k, v in out.items():
        if not math.isfinite(v):
            # a failed request's wait is at least the whole run
            out[k] = w["end"] - w["t0"]
    return out


def sample(plan, results, seed: int, min_tokens: int) -> List[int]:
    """Finished requests drawn from the seed, the longest sequence first,
    until they hold ``min_tokens`` served tokens."""
    fin = [a for a in plan if results[a.rid].status == "finished"]
    if not fin:
        return []
    longest = max(fin, key=lambda a: (len(a.prompt) + a.max_new, a.rid))
    rng = np.random.default_rng([int(seed), 0x5A3])
    rest = [fin[i] for i in rng.permutation(len(fin)) if fin[i] is not longest]
    out, n = [], 0
    for a in [longest] + rest:
        out.append(a.rid)
        n += len(results[a.rid].tokens)
        if n >= min_tokens:
            break
    return out


def token_gaps(ref: np.ndarray, toks: np.ndarray) -> np.ndarray:
    """Per position, how far the chosen token's reference logit lies below
    the reference's best."""
    return ref.max(axis=1) - ref[np.arange(len(toks)), toks]


def compare(ctx, plan, served: Dict[int, Dict], rids: List[int],
            control: bool = False) -> Dict[str, float]:
    """Served tokens of the sampled requests against the float32
    reference.  With ``control`` also the widest gap, the final logits'
    error, and what the FP8 control gives at the same positions, by the
    token it ranks first."""
    by_rid = {a.rid: a for a in plan}
    dims = ctx.dims
    w = model.init_weights(ctx.args.seed, dims)
    gaps, cgaps, err, cerr = [], [], [], []
    for r in rids:
        a, s = by_rid[r], served[r]
        toks = np.asarray(s["tokens"], np.int64)
        P, n = len(a.prompt), len(toks)
        seq = np.concatenate([a.prompt, toks]).astype(np.int32)
        rows = list(range(P - 1, P + n))
        ref = dense.logits_at(w, dims, seq, rows)
        gaps.append(token_gaps(ref[:n], toks))
        if control:
            fin = np.asarray(s["final_logits"], np.float32)
            c = dense.logits_at(w, dims, seq, rows, fp8=True)
            cgaps.append(token_gaps(ref[:n], c[:n].argmax(axis=1)))
            for e, x in ((err, fin), (cerr, c[n])):
                e.append(float(np.linalg.norm(x - ref[n])
                               / np.linalg.norm(ref[n])))
    del w
    g = np.concatenate(gaps)
    out = {"token_gap_mean": float(g.mean()), "tokens_checked": int(g.size),
           "requests_checked": len(rids)}
    if control:
        c = np.concatenate(cgaps)
        out.update(token_gap=float(g.max()), final_logit_err=max(err),
                   control_token_gap=float(c.max()),
                   control_token_gap_mean=float(c.mean()),
                   control_final_logit_err=max(cerr),
                   gaps=[round(float(x), 5) for x in g if x > 0],
                   control_gaps=[round(float(x), 5) for x in c if x > 0],
                   errs=err, control_errs=cerr)
    return out


def run_once(ctx, control=False) -> Dict:
    """One run of the cell: set-up, window, drain, check; with ``control``
    also the control's readings (``calibrate.py``)."""
    seconds = ctx.args.seconds
    sched = build(ctx)
    plan = traffic.open_loop(ctx.mix, ctx.args.seed, seconds, ctx.dims.vocab)
    setup_s = now() - ctx.t_start
    w = serve_window(ctx, sched, plan, seconds, tracer=ctx.tracer)
    lat = latency(plan, w)
    memory = memory_peak_bytes(ctx.devices)
    served = {a.rid: {"tokens": list(sched.results[a.rid].tokens),
                      "final_logits": sched.results[a.rid].final_logits}
              for a in plan}
    status = {a.rid: sched.results[a.rid].status for a in plan}
    failed = sum(1 for s in status.values() if s != "finished")
    rids = sample(plan, sched.results, ctx.args.seed, ctx.mix["check_tokens"])
    record = window_record(ctx, w)
    del sched
    gc.collect()
    cmp = compare(ctx, plan, served, rids, control=control) if rids else {}
    return {"setup_s": setup_s, "latency": lat, "attempted": len(plan),
            "failed": failed, "memory": memory, "compare": cmp,
            "record": record}


def window_record(ctx, w) -> Dict:
    """What the per-layer readers need from the driver: the prefills and
    decode batches of the steps inside the measured window."""
    end = w["window_end"]
    inside = [s for s in w["steps"] if s[1] <= end]
    return {"prefill_lens": [n for s in inside for n in s[2]],
            "decode_kv": [s[3] for s in inside if s[3]],
            "kv_bytes": 1 if "float8" in ctx.conf["serving"]["kv_storage"]
            else 2}


def run(ctx) -> Dict:
    r = run_once(ctx)
    cmp = r["compare"]
    ch = checks(ctx.limits, cmp)
    correct = passed(ch)
    return {"setup_s": r["setup_s"], "metrics": dict(r["latency"]),
            "attempted": r["attempted"], "failed": r["failed"],
            "memory": r["memory"], "checks": ch, "correct": correct,
            "record": r["record"]}
