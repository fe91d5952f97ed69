"""Serving driver: sharded prefill + decode steps over the serving subsystem.

Decode shardings: KV caches shard over batch (DP axes) and, crucially, over
the *sequence* dimension on the model axis ("kv_seq" -> "model") — KV-head
counts (4-24) never divide a 16-way TP axis, so the cache's parallel dim at
32k-500k context is the sequence (DESIGN.md §5).

Generation routes through ``repro.serving`` (docs/serving.md): `generate`
is a thin fixed-batch client of the continuous-batching scheduler, and
``--sched`` runs the full Poisson loadgen sweep with the FP8 KV cache,
merging ``serve/*`` p50/p99 rows into ``BENCH_engine.json``:

    PYTHONPATH=src python -m repro.launch.serve --arch yi-9b --reduced \\
        --batch 4 --prompt-len 32 --gen 16
    PYTHONPATH=src python -m repro.launch.serve --sched --arch yi-9b --reduced
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.core import engine
from repro.launch import compile_cache
from repro.models import transformer
from repro.runtime import sharding
from repro.serving import kv_cache as kv_lib
from repro.serving import loadgen as loadgen_lib
from repro.serving import scheduler as sched_lib
from repro.serving import specs as specs_lib

__all__ = [
    "serve_rules", "cache_spec_tree", "build_serve_step", "build_prefill",
    "make_sharded_serve_step", "generate", "main",
]


def serve_rules(base: Optional[sharding.Rules] = None) -> sharding.Rules:
    """Decode-time rules: shard the KV sequence over the model axis.

    KV-head counts (4-24) never divide the 16-way TP axis, so heads must be
    declared replicated *up front* — otherwise they'd claim the model axis in
    logical_spec and leave the sequence dim unsharded after sanitization."""
    base = base or sharding.Rules()
    return dataclasses.replace(
        base, serve_attention=True,
        overrides=base.overrides + (
            ("kv_heads", None),
            ("kv_seq", ("model",)),
        ))


def cache_spec_tree(cfg, rules, mesh, batch: int, max_len: int,
                    storage_dtype: Optional[str] = None):
    """Sanitized decode-cache PartitionSpecs (serving.specs is the source)."""
    return specs_lib.decode_cache_specs(
        cfg, rules, mesh, batch, max_len, storage_dtype=storage_dtype)[1]


def build_serve_step(cfg, rules: Optional[sharding.Rules]):
    def step(params, cache, tokens, pos):
        with sharding.use_rules(rules):
            return transformer.serve_step(params, cfg, tokens, cache, pos)
    return step


def build_prefill(cfg, rules: Optional[sharding.Rules], max_len: int):
    def pre(params, batch):
        with sharding.use_rules(rules):
            return transformer.prefill(params, cfg, batch, max_len)
    return pre


def make_sharded_serve_step(cfg, mesh, rules, *, batch: int, max_len: int,
                            donate: bool = True):
    rules = serve_rules(rules)
    step = build_serve_step(cfg, rules)
    pspec = transformer.param_specs(cfg, rules)
    pshape = transformer.abstract_params(cfg)
    pspec = jax.tree.map(
        lambda s, a: sharding.sanitize_spec(s, a.shape, mesh),
        pspec, pshape, is_leaf=lambda x: isinstance(x, P))
    cspec = cache_spec_tree(cfg, rules, mesh, batch, max_len)
    dp = tuple(a for a in ("pod", "data") if a in mesh.shape)
    dp = dp[0] if len(dp) == 1 else dp
    tok_spec = P(dp, None) if batch % _axsize(mesh, dp) == 0 else P()
    ns = lambda t: jax.tree.map(
        lambda s: NamedSharding(mesh, s), t, is_leaf=lambda x: isinstance(x, P))
    jitted = jax.jit(
        step,
        in_shardings=(ns(pspec), ns(cspec), ns(tok_spec), None),
        out_shardings=(None, ns(cspec)),
        donate_argnums=(1,) if donate else (),
    )
    return jitted, pspec, cspec


def _axsize(mesh, name):
    if isinstance(name, tuple):
        n = 1
        for a in name:
            n *= mesh.shape[a]
        return n
    return mesh.shape.get(name, 1) if hasattr(mesh.shape, "get") else mesh.shape[name]


# --------------------------------------------------------------------- #
# Generation: thin fixed-batch client of the scheduler
# --------------------------------------------------------------------- #
def generate(params, cfg, prompts: jax.Array, gen_len: int,
             rules: Optional[sharding.Rules] = None, *,
             storage_dtype: Optional[str] = None, return_state: bool = False):
    """prompts: (B, S) int32. Returns (B, S+gen_len) greedy continuations.

    Runs the serving scheduler with B slots and B simultaneous arrivals —
    every slot stays in lockstep, so this is the classic batched greedy
    loop, but with the scheduler's drain invariant: the final emitted
    token's KV is absorbed before eviction, so the returned cache (with
    ``return_state=True``: ``(seqs, cache, final_logits)``) is consistent
    with the emitted sequences — ``argmax(final_logits)`` is exactly the
    token a ``gen_len + 1`` run would emit next.  ``storage_dtype`` serves
    from the FP8 KV cache."""
    B, S = prompts.shape
    if gen_len < 1:
        raise ValueError("gen_len must be >= 1")
    scfg = sched_lib.SchedulerConfig(
        n_slots=B, max_len=S + gen_len, storage_dtype=storage_dtype)
    sched = sched_lib.Scheduler(params, cfg, scfg, rules=rules)
    pnp = np.asarray(prompts)
    sched.submit([
        sched_lib.Request(rid=i, arrival=0.0, prompt=pnp[i],
                          max_new_tokens=gen_len)
        for i in range(B)
    ])
    results = sched.run()
    seqs = jnp.asarray(np.concatenate(
        [pnp, np.array([r.tokens for r in results], np.int32)], axis=1))
    if return_state:
        final = np.stack([r.final_logits for r in results])
        return seqs, sched.cache, final
    return seqs


# --------------------------------------------------------------------- #
# CLI
# --------------------------------------------------------------------- #
def _parse_inject(spec: str):
    """``MODE@STEP`` -> FailureInjector with a serving mode (docs/serving.md),
    e.g. ``nan_logits@2``, ``kv_corrupt@3``, ``prefill_crash@1``."""
    from repro.runtime.fault_tolerance import FailureInjector
    mode, _, at = spec.partition("@")
    if mode not in FailureInjector.SERVING_MODES or not at.isdigit():
        raise SystemExit(
            f"--inject wants MODE@STEP with MODE in "
            f"{FailureInjector.SERVING_MODES}, got {spec!r}")
    return FailureInjector(fail_at_step=int(at), mode=mode)


def _run_sched(cfg, params, args) -> None:
    if args.policy:
        # FP8 end to end: the decode GEMMs dispatch under the policy's
        # per-operand storage dtypes (MIXED_FP8_E4M3 by default), on top
        # of the FP8 KV cache selected by --storage
        cfg = dataclasses.replace(cfg, policy_name=args.policy)
    resilient = bool(args.inject or args.deadline or args.max_queue)
    audit = args.audit_every if args.audit_every is not None else \
        (1 if args.inject else 0)
    scfg = sched_lib.SchedulerConfig(
        n_slots=args.slots, max_len=args.prompt_len + args.gen + 4,
        storage_dtype=args.storage or None,
        max_queue=args.max_queue or None, audit_every=audit)
    rates = [float(r) for r in args.rates.split(",")]
    lc = loadgen_lib.LoadConfig(
        rate=rates[0], n_requests=args.requests,
        prompt_len=args.prompt_len, gen_len=args.gen, seed=args.seed,
        deadline_ticks=args.deadline or None, max_retries=args.retries)

    if args.instrument:
        # one sweep under instrumentation: the jit traces of the serving
        # path land here, tagged serve_prefill / serve_admit / serve_decode
        with engine.instrument() as events:
            sched = sched_lib.Scheduler(params, cfg, scfg)
            sched.submit(loadgen_lib.poisson_requests(cfg, lc))
            sched.run()
        for op, d in engine.summarize(events).items():
            print(f"[engine] {op}: calls={d['calls']} "
                  f"gflops={d['flops']/1e9:.3f} gbytes={d['bytes']/1e9:.3f}")
        print("[sched] tick queue pend active fill")
        for h in sched.health:
            print(f"[sched] {h['tick']:8.2f} {h['queue_depth']:5d} "
                  f"{h['pending']:4d} {h['active_slots']:6d} "
                  f"{h['batch_fill']:.2f}")
        for leaf, d in kv_lib.scale_health(sched.cache).items():
            over = ("" if "overflow_total" not in d
                    else f" overflow={d['overflow_total']}")
            print(f"[kv] {leaf}: max_scale={d['max_scale']:.3g}{over}")
        # one exactly-billed ragged decode step at the drained lengths
        lengths = [args.prompt_len + args.gen if i == 0 else 0
                   for i in range(scfg.n_slots)]
        ev = sched_lib.instrumented_decode_events(params, cfg, scfg, lengths)
        print(f"[kv] ragged decode step flops={engine.total_flops(ev)} "
              f"kv_bytes={kv_lib.decode_step_kv_bytes(cfg, [l for l in lengths if l], scfg.storage_dtype)}")

    rows = loadgen_lib.bench_rows(
        params, cfg, scfg, cfg.name, rates, lc)
    if resilient:
        # the SLO scenario: deadlines / bounded queue / injected fault at
        # the first offered rate — a fresh one-shot injector per run
        injector = _parse_inject(args.inject) if args.inject else None
        tag = f"slo_{injector.mode}" if injector else "slo"
        srows, m = loadgen_lib.slo_rows(
            params, cfg, scfg, cfg.name, lc, injector=injector, tag=tag)
        rows += srows
        print(f"[slo] goodput={m['slo_goodput']:.4f} "
              f"deadline_hit={m['deadline_hit_rate']:.3f} "
              f"finished={m['n_finished']}/{m['n_requests']} "
              f"retries={m['retries']} abandons={m['abandons']} "
              f"recoveries={m['slo_recoveries']:.0f} "
              f"shed={m['slo_shed']:.0f} expired={m['slo_expired']:.0f}")
    for name, us, derived in rows:
        print(f"{name},{us:.3f},{derived}")
    if args.json:
        loadgen_lib.merge_bench_json(args.json, rows)
        print(f"merged {len(rows)} serve/* rows into {args.json}")


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", default="yi-9b", choices=configs.ARCH_IDS)
    p.add_argument("--reduced", action="store_true", default=True)
    p.add_argument("--full", dest="reduced", action="store_false")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=32)
    p.add_argument("--gen", type=int, default=16)
    p.add_argument("--instrument", action="store_true",
                   help="trace the serving path under engine.instrument() "
                        "and print the GEMM summary; with --sched also the "
                        "per-step scheduler health (queue depth, slot "
                        "occupancy, batch fill) and KV scale state")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--sched", action="store_true",
                   help="run the continuous-batching scheduler + Poisson "
                        "loadgen sweep and merge serve/* rows into --json")
    p.add_argument("--slots", type=int, default=4,
                   help="--sched: decode slot pool size")
    p.add_argument("--requests", type=int, default=8,
                   help="--sched: requests per offered-load point")
    p.add_argument("--rates", default="0.25,1.0",
                   help="--sched: offered loads (requests/tick), comma-sep")
    p.add_argument("--storage", default="float8_e4m3fn",
                   help="--sched: KV cache storage dtype ('' for fp16)")
    p.add_argument("--policy", default="mixed_fp8_e4m3",
                   help="--sched: precision policy for the serve GEMMs "
                        "('' keeps the arch default)")
    p.add_argument("--json", default="BENCH_engine.json",
                   help="--sched: merge rows into this file ('' to skip)")
    p.add_argument("--inject", default="",
                   help="--sched: serving fault MODE@STEP "
                        "(nan_logits/kv_corrupt at the Nth decode step, "
                        "prefill_crash at the Nth prefill); adds the "
                        "serve/*/slo_* recovery rows")
    p.add_argument("--deadline", type=float, default=0.0,
                   help="--sched: per-request deadline budget in ticks "
                        "(0 = none); expired work is evicted")
    p.add_argument("--max-queue", type=int, default=0,
                   help="--sched: bounded admission queue (0 = unbounded); "
                        "overflow is rejected with retry_after")
    p.add_argument("--retries", type=int, default=2,
                   help="--sched: loadgen client retry budget per rejection")
    p.add_argument("--audit-every", type=int, default=None,
                   help="--sched: KV checksum audit cadence in decode steps "
                        "(default: 1 when --inject is set, else off)")
    args = p.parse_args(argv)
    compile_cache.enable()

    cfg = configs.get_reduced(args.arch) if args.reduced else configs.get(args.arch)
    rng = jax.random.PRNGKey(args.seed)
    params = transformer.init_params(rng, cfg)

    if args.sched:
        _run_sched(cfg, params, args)
        return

    prompts = jax.random.randint(
        rng, (args.batch, args.prompt_len), 0, cfg.vocab_size, jnp.int32)
    if args.instrument:
        max_len = args.prompt_len + args.gen
        cache_abs = jax.eval_shape(
            lambda: transformer.init_cache(cfg, args.batch, max_len))
        tok_abs = jax.ShapeDtypeStruct((args.batch, 1), jnp.int32)
        phases = {
            "prefill": lambda: jax.eval_shape(
                lambda p_, b_: transformer.prefill(p_, cfg, b_, max_len),
                params, {"inputs": prompts}),
            "decode": lambda: jax.eval_shape(
                lambda p_, c_, t_: transformer.serve_step(
                    p_, cfg, t_, c_, jnp.int32(args.prompt_len)),
                params, cache_abs, tok_abs),
        }
        for phase, trace in phases.items():
            with engine.instrument() as events:
                trace()
            for op, d in engine.summarize(events).items():
                print(f"[engine] {phase} {op}: calls={d['calls']} "
                      f"gflops={d['flops']/1e9:.3f} "
                      f"gbytes={d['bytes']/1e9:.3f}")
    t0 = time.perf_counter()
    seqs = generate(params, cfg, prompts, args.gen)
    jax.block_until_ready(seqs)
    dt = time.perf_counter() - t0
    tps = args.batch * args.gen / dt
    print(f"arch={cfg.name} batched-generate {seqs.shape} in {dt:.2f}s "
          f"({tps:.1f} tok/s)")
    print("sample:", np.asarray(seqs[0, args.prompt_len:]))


if __name__ == "__main__":
    main()
