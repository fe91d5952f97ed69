"""Open-loop serving of a Granite-4.0-H hybrid (Mamba2 and NoPE GQA layers).

The window, the latencies and the sample of requests checked are those of
``serve.py`` (imported from there): between ``Scheduler.step`` calls every
request whose time has come is submitted, and after the window those due
are drained under the mix's cap.  What this driver adds is the model: its
configuration file's published keys become the program's ``ModelConfig``,
and the weights are made here from the seed in the program's parameter
tree (``shapes``), where the plain reference ``reference/granite_hybrid.py``
reads them.

``correct`` compares the sampled requests with that reference once the
program's state is freed: the mean gap by which a served token's reference
logit lies below the reference's best (``token_gap_mean``, as ``serve.py``
decides it), and the relative error of each request's final logits, the
distribution after its last served token, the largest over the sample
(``final_logit_err``).  Under random weights with tied embeddings the
served tokens mostly repeat the prompt's last one (``last_token_share``
says how many), so few argmaxes are close and the token gaps alone would
let a lower precision pass; the final logits hold every position of the
vocabulary after the longest run of decode steps.  ``calibrate.py
readings`` also reads the FP8 control's numbers (``control=True``) and
puts them through the same limits (``control_checks``).

The record for the per-layer readers is ``serve.py``'s: ``decode_kv``
lists each decode step's active slots' key counts, so its length is the
step's slots, whose Mamba state ``counts_hybrid`` prices.
"""

from __future__ import annotations

import gc
import sys
from typing import Any, Dict, List

import numpy as np

from chipbench import model, traffic
from chipbench.common import checks, memory_peak_bytes, now, passed
from chipbench.drivers.serve import latency, sample, serve_window, token_gaps
from chipbench.reference import granite_hybrid as ref

WARM_NEW_TOKENS = 2


def program_config(c: Dict[str, Any]):
    """The program's ``ModelConfig`` from the configuration file's
    published keys."""
    from repro.configs.base import ModelConfig, SSMConfig

    d = ref.Dims.of(c)
    if (c["num_local_experts"] or c["mamba_proj_bias"] or c["attention_bias"]
            or not c["mamba_conv_bias"] or c["hidden_act"] != "silu"
            or c["position_embedding_type"] != "nope"
            or d.head_dim * d.heads != d.di):
        raise ValueError(f"{c['name']}: not a dense NoPE Granite-4.0-H config")
    prec = c["precision"]
    return ModelConfig(
        name=c["model"], family="hybrid", n_layers=c["num_hidden_layers"],
        d_model=d.d, n_heads=d.hq, n_kv_heads=d.hkv, head_dim=d.hd,
        d_ff=d.ff, vocab_size=d.vocab, rope_theta=float(c["rope_theta"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        layer_types=d.layer_types, norm_eps=d.eps, position_embedding="nope",
        attention_multiplier=d.attn_mult, embedding_multiplier=d.emb_mult,
        residual_multiplier=d.res_mult, logits_scaling=d.logits_scaling,
        ssm=SSMConfig(state_dim=d.state, chunk=c["mamba_chunk_size"],
                      mamba_expand=d.expand, mamba_heads=d.heads,
                      n_groups=d.groups, d_conv=d.conv),
        policy_name=prec["policy"], param_dtype=prec["param_dtype"])


def shapes(d: ref.Dims) -> Dict[str, Any]:
    """Leaf shapes of the weight tree, with each leaf's initializer."""
    per = d.period
    n = len(d.layer_types) // len(per)
    G, N, H = d.groups, d.state, d.heads
    C = d.di + 2 * G * N

    def stack(kind, tree):
        c = per.count(kind)
        return {k: (stack(kind, v) if isinstance(v, dict)
                    else ((n, c, *v[0]), v[1])) for k, v in tree.items()}

    mlp = {"w_in": ((d.d, 2 * d.ff), "proj"), "w_out": ((d.ff, d.d), "proj")}
    kinds = {
        "mamba": {
            "ln1": ((d.d,), "norm"),
            "mamba": {
                "w_xz": ((d.d, 2 * d.di), "proj"),
                "w_bcdt": ((d.d, 2 * G * N + H), "proj"),
                "a_log": ((H,), "a_log"),
                "skip_d": ((H,), "norm"),
                "dt_bias": ((H,), "dt_bias"),
                "norm": ((d.di,), "norm"),
                "w_out": ((d.di, d.d), "proj"),
                "conv_w": ((d.conv, C), "proj"),
                "conv_b": ((C,), "bias"),
            },
            "ln2": ((d.d,), "norm"),
            "mlp": mlp,
        },
        "attention": {
            "ln1": ((d.d,), "norm"),
            "attn": {"wqkv": ((d.d, (d.hq + 2 * d.hkv) * d.hd), "proj"),
                     "wo": ((d.hq * d.hd, d.d), "proj")},
            "ln2": ((d.d,), "norm"),
            "mlp": mlp,
        },
    }
    return {"embed": ((d.vocab, d.d), "embed"),
            "final_norm": ((d.d,), "norm"),
            "layers": {k: stack(k, v) for k, v in kinds.items() if k in per}}


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


def _init(key, d: ref.Dims, dtype):
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree.flatten(shapes(d), is_leaf=_is_leaf)
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, (shape, kind) in zip(keys, leaves):
        if kind in ("a_log", "dt_bias"):
            u = jax.random.uniform(k, shape, jnp.float32)
            if kind == "a_log":
                # A = -exp(a_log) in [-16, -1], Mamba2's init range
                w = jnp.log(1.0 + 15.0 * u)
            else:
                # softplus(dt_bias) = dt in [1e-3, 1e-1], log-uniform
                dt = jnp.exp(jnp.log(1e-3) + u * (jnp.log(1e-1) - jnp.log(1e-3)))
                w = dt + jnp.log(-jnp.expm1(-dt))
        else:
            z = jax.random.normal(k, shape, jnp.float32)
            if kind == "embed":
                w = 0.02 * z
            elif kind == "norm":
                # not all ones, so a norm scale applied wrongly shows
                w = 1.0 + 0.1 * z
            elif kind == "bias":
                w = 0.1 * z
            else:
                w = z * shape[-2] ** -0.5
        out.append(w.astype(dtype))
    return jax.tree.unflatten(treedef, out)


def init_weights(seed: int, d: ref.Dims, dtype) -> Dict[str, Any]:
    """The run's weights, made on the default device in one jitted call."""
    import jax
    import jax.numpy as jnp

    fn = jax.jit(_init, static_argnums=(1, 2))
    return fn(model.seed_key(seed), d, jnp.dtype(dtype))


def check_layout(weights, cfg) -> None:
    """Fail loudly if the program's parameter tree is not the one made here."""
    import jax
    from repro.models import transformer

    want = jax.tree.map(lambda a: (a.shape, a.dtype),
                        transformer.abstract_params(cfg))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), weights)
    if want != got:
        raise RuntimeError(f"the program's parameter tree {want} is not the "
                           f"benchmark's {got}")


def build(ctx):
    """The weights and the scheduler, warmed on every shape the mix uses."""
    import jax
    from repro.serving import scheduler as S

    conf, mix = ctx.conf, ctx.mix
    cfg = program_config(conf)
    weights = init_weights(ctx.args.seed, ref.Dims.of(conf), cfg.param_dtype)
    check_layout(weights, cfg)
    sv = conf["serving"]
    sched = S.Scheduler(weights, cfg, S.SchedulerConfig(
        n_slots=sv["slots"], max_len=sv["max_len"],
        storage_dtype=sv["kv_storage"]))
    rng = np.random.default_rng([int(ctx.args.seed), 0xC0DE])
    lens = sorted(mix["prompt"]["buckets"])
    sched.submit([S.Request(
        rid=-1 - i, arrival=sched.clock,
        prompt=rng.integers(0, conf["vocab_size"], n, dtype=np.int32),
        max_new_tokens=WARM_NEW_TOKENS) for i, n in enumerate(lens)])
    while sched.step():
        pass
    jax.block_until_ready(sched.cache)
    return sched


def compare(ctx, plan, served: Dict[int, Dict], rids: List[int],
            control: bool = False) -> Dict[str, float]:
    """The sampled requests against the float32 reference; with
    ``control`` also the FP8 control's numbers, and its checks."""
    by_rid = {a.rid: a for a in plan}
    d = ref.Dims.of(ctx.conf)
    w = init_weights(ctx.args.seed, d, ctx.conf["precision"]["param_dtype"])
    gaps, cgaps, err, cerr, repeats = [], [], [], [], []

    def rel(x, want):
        return float(np.linalg.norm(x - want) / np.linalg.norm(want))

    for r in rids:
        a, s = by_rid[r], served[r]
        toks = np.asarray(s["tokens"], np.int64)
        P, n = len(a.prompt), len(toks)
        seq = np.concatenate([a.prompt, toks]).astype(np.int32)
        rows = list(range(P - 1, P + n))
        rl = ref.logits_at(w, d, seq, rows)
        gaps.append(token_gaps(rl[:n], toks))
        err.append(rel(np.asarray(s["final_logits"], np.float32), rl[n]))
        repeats.append(toks == a.prompt[-1])
        if control:
            c = ref.logits_at(w, d, seq, rows, fp8=True)
            cgaps.append(token_gaps(rl[:n], c[:n].argmax(axis=1)))
            cerr.append(rel(c[n], rl[n]))
    del w
    g = np.concatenate(gaps)
    out = {"token_gap_mean": float(g.mean()), "final_logit_err": max(err),
           "tokens_checked": int(g.size), "requests_checked": len(rids),
           "last_token_share": float(np.concatenate(repeats).mean()),
           "errs": err}
    if control:
        c = np.concatenate(cgaps)
        ctl = {"token_gap_mean": float(c.mean()), "final_logit_err": max(cerr)}
        ch = checks(ctx.limits, ctl)
        out.update(token_gap=float(g.max()),
                   control_token_gap=float(c.max()),
                   control_token_gap_mean=ctl["token_gap_mean"],
                   control_final_logit_err=ctl["final_logit_err"],
                   control_checks=ch, control_correct=passed(ch),
                   control_errs=cerr)
    return out


def window_record(ctx, w) -> Dict:
    """What the per-layer readers need from the driver: the prefills and
    decode batches of the steps inside the measured window."""
    end = w["window_end"]
    inside = [s for s in w["steps"] if s[1] <= end]
    return {"prefill_lens": [n for s in inside for n in s[2]],
            "decode_kv": [s[3] for s in inside if s[3]],
            "kv_bytes": 1 if "float8" in ctx.conf["serving"]["kv_storage"]
            else 2}


def run_once(ctx, control=False) -> Dict:
    """One run of the cell: set-up, window, drain, check."""
    seconds = ctx.args.seconds
    sched = build(ctx)
    plan = traffic.open_loop(ctx.mix, ctx.args.seed, seconds,
                             ctx.conf["vocab_size"])
    setup_s = now() - ctx.t_start
    w = serve_window(ctx, sched, plan, seconds, tracer=ctx.tracer)
    lat = latency(plan, w)
    memory = memory_peak_bytes(ctx.devices)
    served = {a.rid: {"tokens": list(sched.results[a.rid].tokens),
                      "final_logits": sched.results[a.rid].final_logits}
              for a in plan}
    failed = sum(1 for a in plan if sched.results[a.rid].status != "finished")
    rids = sample(plan, sched.results, ctx.args.seed, ctx.mix["check_tokens"])
    record = window_record(ctx, w)
    del sched
    gc.collect()
    cmp = compare(ctx, plan, served, rids, control=control) if rids else {}
    return {"setup_s": setup_s, "latency": lat, "attempted": len(plan),
            "failed": failed, "memory": memory, "compare": cmp,
            "record": record}


def run(ctx) -> Dict:
    r = run_once(ctx)
    ch = checks(ctx.limits, r["compare"])
    if r["compare"]:
        print(f"served tokens equal to the prompt's last "
              f"{r['compare']['last_token_share']!r}", file=sys.stderr)
    return {"setup_s": r["setup_s"], "metrics": dict(r["latency"]),
            "attempted": r["attempted"], "failed": r["failed"],
            "memory": r["memory"], "checks": ch, "correct": passed(ch),
            "record": r["record"]}
