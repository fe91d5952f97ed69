"""Training driver: sharded train step + fault-tolerant loop.

``build_train_step`` assembles the paper's full recipe:
  * forward/backward with every GEMM on the RedMulE engine,
  * optional dynamic FP16 loss scaling (the paper's precision regime),
  * gradient clipping, AdamW, MoE aux losses,
  * non-finite-step skipping (scale halves, params untouched).

``make_sharded_train_step`` binds it to a mesh with logical-axis shardings
(DP/TP/EP/SP(/FSDP)) and donates the state buffers.

CLI (end-to-end driver, deliverable (b)): train a reduced or full arch on
synthetic data with checkpoint/restart:

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b \\
        --reduced --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

``--arch ae`` trains the paper's TinyMLPerf AutoEncoder use case (§III-B)
in pure FP16 instead of an LM arch.  With ``--instrument``, one step is
traced under ``engine.instrument()`` first and the per-op GEMM summary is
printed with the fwd/bwd split — the Engine ops carry a custom VJP, so the
backward GEMMs (``matmul_dx`` / ``matmul_dw``) are counted too (the CI
train gate pins these totals against
``benchmarks/baselines/train_flops.json``).

``--compress {none,fp16,int8,fp8,fp8_e4m3,fp8_e5m2}`` (optionally with
``--dp-procs N``) switches to the data-parallel step with a compressed
gradient wire: each shard's gradients cross the all-reduce at the wire
width with fp32 error feedback kept locally (FP8 wires use
``Fp8ScaleState`` delayed scaling).  ``--instrument`` then also prints the
per-step collective wire bytes vs the fp32 wire, and — when a
``--ckpt-dir`` fault-tolerant loop ran — the goodput breakdown
(useful/wall, time lost to restarts, recomputed steps; the ft-gates CI job
floor-gates the injected-failure scenario).  Simulate N processes on one
machine with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
"""

from __future__ import annotations

import argparse
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.checkpoint import CheckpointManager
from repro.core import engine
from repro.data import Prefetcher, SyntheticLM
from repro.launch import compile_cache
from repro.models import transformer
from repro.optim import (AdamW, Compressor, OptState, adjust,
                         clip_by_global_norm, init_scale, scale_loss,
                         unscale_and_check)
from repro.runtime import sharding
from repro.runtime.fault_tolerance import TrainLoop

__all__ = [
    "TrainState", "build_train_step", "state_specs", "batch_specs",
    "make_sharded_train_step", "init_state", "main",
]


class TrainState(NamedTuple):
    params: Any
    opt: OptState
    scale: Any          # LossScaleState or () when disabled


def init_state(rng, cfg, opt, *, use_scale: bool = False) -> TrainState:
    params = transformer.init_params(rng, cfg)
    return TrainState(
        params=params,
        opt=opt.init(params),
        scale=init_scale() if use_scale else (),
    )


def build_train_step(
    cfg,
    opt,
    rules: Optional[sharding.Rules],
    *,
    use_scale: bool = False,
    clip_norm: float = 1.0,
    cast_params: bool = False,
    grad_accum: int = 1,
):
    """(state, batch) -> (state, metrics); pure, jit-able, donate-able.

    cast_params: cast fp32 master params to the compute dtype at step entry —
    the FSDP all-gathers and gradient reductions then run on 16-bit wire
    (half the collective bytes; grads re-widen at the cast boundary).

    grad_accum: split the batch into microbatches and accumulate fp32 grads
    across a scan — the per-pass activation working set shrinks by the
    accumulation factor (the standard fit-big-models lever)."""

    def step(state: TrainState, batch) -> Tuple[TrainState, dict]:
        with sharding.use_rules(rules):
            def lf(p, b):
                if cast_params:
                    p = jax.tree.map(
                        lambda x: x.astype(cfg.policy.compute_dtype)
                        if jnp.issubdtype(x.dtype, jnp.floating) else x, p)
                loss, metrics = transformer.loss_fn(p, cfg, b)
                if use_scale:
                    loss = scale_loss(loss, state.scale)
                return loss, metrics

            if grad_accum > 1:
                mb = jax.tree.map(
                    lambda x: x.reshape(
                        grad_accum, x.shape[0] // grad_accum, *x.shape[1:]),
                    batch)

                def mb_body(carry, b):
                    g_acc, m_acc = carry
                    (_, m), g = jax.value_and_grad(
                        lf, has_aux=True)(state.params, b)
                    g_acc = jax.tree.map(
                        lambda a, x: a + x.astype(jnp.float32), g_acc, g)
                    m_acc = jax.tree.map(lambda a, x: a + x, m_acc, m)
                    return (g_acc, m_acc), 0

                g0 = jax.tree.map(
                    lambda x: jnp.zeros(x.shape, jnp.float32), state.params)
                with engine.paused():  # shape probe: don't double-count GEMMs
                    m0 = jax.eval_shape(
                        lambda: jax.value_and_grad(lf, has_aux=True)(
                            state.params, jax.tree.map(lambda x: x[0], mb))[0][1])
                m0 = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), m0)
                with engine.repeat(grad_accum):  # microbatch scan
                    (grads, metrics), _ = jax.lax.scan(mb_body, (g0, m0), mb)
                inv = 1.0 / grad_accum
                grads = jax.tree.map(lambda g: g * inv, grads)
                metrics = jax.tree.map(lambda x: x * inv, metrics)
            else:
                (_, metrics), grads = jax.value_and_grad(
                    lf, has_aux=True)(state.params, batch)

            if use_scale:
                grads, finite = unscale_and_check(grads, state.scale)
                new_scale = adjust(state.scale, finite)
            else:
                finite = jnp.bool_(True)
                new_scale = state.scale

            grads, gnorm = clip_by_global_norm(grads, clip_norm)
            updates, new_opt = opt.update(grads, state.opt, state.params)

            # skip the update entirely on overflow (params AND moments)
            def apply(_):
                return opt.apply(state.params, updates), new_opt

            def keep(_):
                return state.params, state.opt

            new_params, new_opt = jax.lax.cond(finite, apply, keep, None)
            metrics = dict(metrics)
            metrics["grad_norm"] = gnorm
            if use_scale:
                metrics["loss_scale"] = new_scale.scale
                metrics["finite"] = finite.astype(jnp.float32)
        return TrainState(new_params, new_opt, new_scale), metrics

    return step


def build_compressed_dp_train_step(
    cfg, opt, mesh, compressor, *, clip_norm: float = 1.0,
):
    """Pure-DP train step with gradient compression on the wire.

    The per-shard gradient is computed inside shard_map over the data axes
    (params replicated, batch sharded); the cross-shard mean runs on the
    compressor's wire dtype (fp16/int8 + error feedback) instead of fp32 —
    the distributed-optimization trick for slow inter-pod links.

    The error-feedback state (fp32 residual + fp8 scale windows) is
    genuinely per-host — each host accumulates the residual of *its* batch
    shard — so it carries an explicit leading host axis, sharded over the
    data axes.  Storing it "replicated" would silently checkpoint only
    host 0's residual (shard_map's ``check_vma=False`` stamps the
    out-spec without verifying it), breaking bit-identical kill/resume.

    Returns (step, init_fn) where state = (TrainState, ef_hosts).
    """
    from jax.sharding import PartitionSpec as Pspec

    dp = tuple(a for a in ("pod", "data") if a in mesh.shape)
    ndev = 1
    for a in dp:
        ndev *= mesh.shape[a]

    def init_fn(rng):
        state = init_state(rng, cfg, opt)
        ef = compressor.init(state.params)
        if ef is not None:
            ef = jax.tree.map(lambda l: jnp.stack([l] * ndev), ef)
        return state, ef

    def step(state_and_ef, batch):
        state, ef_hosts = state_and_ef

        def local(params, ef_h, batch_l):
            loss, grads = jax.value_and_grad(
                lambda p: transformer.loss_fn(p, cfg, batch_l)[0])(params)
            # strip this host's slot off the leading host axis, compress,
            # and put the new residual back in the same slot
            ef_l = (jax.tree.map(lambda x: x[0], ef_h)
                    if ef_h is not None else None)
            wire, ef2 = compressor.compress(grads, ef_l)
            mean_g = compressor.psum_wire(wire, dp)
            ef2_h = (jax.tree.map(lambda x: x[None], ef2)
                     if ef2 is not None else None)
            loss = jax.lax.pmean(loss, dp)
            return mean_g, ef2_h, loss

        pspec = jax.tree.map(lambda _: Pspec(), state.params)
        espec = jax.tree.map(lambda _: Pspec(dp), ef_hosts)
        bspec = jax.tree.map(lambda _: Pspec(dp), batch)
        mean_g, ef_hosts, loss = jax.shard_map(
            local, mesh=mesh,
            in_specs=(pspec, espec, bspec),
            out_specs=(pspec, espec, Pspec()),
            check_vma=False,
        )(state.params, ef_hosts, batch)

        mean_g, gnorm = clip_by_global_norm(mean_g, clip_norm)
        updates, new_opt = opt.update(mean_g, state.opt, state.params)
        params = opt.apply(state.params, updates)
        return (TrainState(params, new_opt, state.scale), ef_hosts), {
            "loss": loss, "grad_norm": gnorm}

    return step, init_fn


# --------------------------------------------------------------------- #
# Sharding plumbing
# --------------------------------------------------------------------- #
def _sanitize_tree(spec_tree, shape_tree, mesh):
    return jax.tree.map(
        lambda s, a: sharding.sanitize_spec(s, a.shape, mesh),
        spec_tree, shape_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def state_specs(cfg, rules, mesh, opt, *, use_scale: bool = False) -> TrainState:
    pspec = transformer.param_specs(cfg, rules)
    pshape = transformer.abstract_params(cfg)
    pspec = _sanitize_tree(pspec, pshape, mesh)
    scalar = P()
    opt_spec = OptState(
        step=scalar,
        mu=jax.tree.map(lambda s: s, pspec),
        nu=jax.tree.map(lambda s: s, pspec),
    )
    scale_spec = (
        jax.tree.map(lambda _: scalar, init_scale()) if use_scale else ()
    )
    return TrainState(params=pspec, opt=opt_spec, scale=scale_spec)


def batch_specs(cfg, mesh) -> dict:
    dp = tuple(a for a in ("pod", "data") if a in mesh.shape)
    dp = dp[0] if len(dp) == 1 else dp
    if cfg.input_mode == "embeddings":
        return {"embeddings": P(dp, None, None), "labels": P(dp, None)}
    return {"inputs": P(dp, None), "labels": P(dp, None)}


def make_sharded_train_step(
    cfg, mesh, rules, opt, *, use_scale: bool = False, donate: bool = True,
):
    step = build_train_step(cfg, opt, rules, use_scale=use_scale)
    sspec = state_specs(cfg, rules, mesh, opt, use_scale=use_scale)
    bspec = batch_specs(cfg, mesh)
    ns = lambda tree: jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree, is_leaf=lambda x: isinstance(x, P))
    return jax.jit(
        step,
        in_shardings=(ns(sspec), ns(bspec)),
        out_shardings=(ns(sspec), None),
        donate_argnums=(0,) if donate else (),
    ), sspec


# --------------------------------------------------------------------- #
# CLI end-to-end driver
# --------------------------------------------------------------------- #
def _print_goodput(out):
    g = out.get("goodput")
    if not g:
        return
    print(f"[ft] goodput={g['goodput']:.3f} "
          f"useful={g['useful_time']:.2f}s wall={g['wall_time']:.2f}s "
          f"lost_to_restart={g['time_lost_to_restart']:.2f}s "
          f"recomputed_steps={g['recomputed_steps']} "
          f"restarts={g['restarts']}")


def _compressed_dp_main(args, cfg) -> float:
    """Data-parallel training with a compressed gradient wire (and the
    fault-tolerant loop when --ckpt-dir is set); returns the final loss."""
    import json

    from repro.optim import Compressor
    from repro.runtime import compat
    from repro.runtime.elastic import _digest
    from repro.runtime.fault_tolerance import FailureInjector

    ndev = args.dp_procs or len(jax.devices())
    if len(jax.devices()) < ndev:
        raise SystemExit(
            f"--dp-procs {ndev} but jax sees {len(jax.devices())} devices; "
            "simulate with XLA_FLAGS=--xla_force_host_platform_device_"
            f"count={ndev}")
    if args.batch % ndev:
        raise SystemExit(f"--batch {args.batch} must divide by the "
                         f"{ndev}-way data mesh")
    mesh = compat.make_mesh((ndev,), ("data",))
    comp = Compressor(args.compress)
    opt = AdamW(lr=args.lr, warmup_steps=10)
    step, init_fn = build_compressed_dp_train_step(cfg, opt, mesh, comp)
    ds = SyntheticLM(
        vocab_size=cfg.vocab_size, seq_len=args.seq, global_batch=args.batch,
        seed=args.seed,
        embed_dim=cfg.d_model if cfg.input_mode == "embeddings" else 0)

    # Canonical placement — the bit-identical-resume invariant (mirrors
    # runtime/elastic.py).  A resumed process's first step receives host
    # (np) arrays from the checkpoint while a clean run's steps receive
    # the previous step's device outputs; pinned in_/out_shardings force
    # every step of every incarnation through one executable and one
    # placement: TrainState replicated, EF sharded over the host axis,
    # batch sharded over data.
    rep = NamedSharding(mesh, P())
    dp_sh = NamedSharding(mesh, P("data"))
    ts0, ef0 = jax.eval_shape(init_fn, jax.random.PRNGKey(args.seed))
    state_sh = (jax.tree.map(lambda _: rep, ts0),
                jax.tree.map(lambda _: dp_sh, ef0))
    # The state is built in its placement and each step overwrites it: at
    # yi-9b widths two copies of the replicated state (and a state built
    # whole on one device) do not fit a 16 GB chip.
    state = jax.jit(init_fn, out_shardings=state_sh)(
        jax.random.PRNGKey(args.seed))
    jstep = jax.jit(step, in_shardings=(state_sh, dp_sh),
                    out_shardings=(state_sh, rep), donate_argnums=(0,))
    if args.instrument:
        wire = comp.wire_bytes(state[0].params)
        full = Compressor("none").wire_bytes(state[0].params)
        print(f"[ft] gradient wire: kind={comp.kind} "
              f"bytes/step={wire} fp32_bytes/step={full} "
              f"ratio={full / max(wire, 1):.2f}x")
    injector = None
    if args.fail_step is not None:
        injector = FailureInjector(fail_at_step=args.fail_step,
                                   mode=args.fail_mode)
    final_state, final_loss = state, float("nan")
    if args.ckpt_dir:
        ckpt = CheckpointManager(args.ckpt_dir, keep=2)
        loop = TrainLoop(jstep, ckpt, save_every=args.save_every,
                         injector=injector)
        out = loop.run(state, ds.batch, args.steps)
        final_state = out["final_state"]
        final_loss = float(out["history"][-1]["loss"])
        print(f"final loss: {final_loss:.4f} "
              f"(stragglers: {out['straggler_steps']})")
        if args.instrument:
            _print_goodput(out)
    else:
        metrics = None
        for i in range(args.steps):
            state, metrics = jstep(state, ds.batch(i))
            if i % 10 == 0:
                print(f"[{i}] loss={float(metrics['loss']):.4f}")
        final_state, final_loss = state, float(metrics["loss"])
        print(f"final loss: {final_loss:.4f}")
    if args.result:
        res = {
            "digest": _digest(final_state[0].params),
            "ef_digest": _digest(final_state[1]),
            "opt_digest": _digest(final_state[0].opt),
            "loss": final_loss,
        }
        with open(args.result, "w") as f:
            json.dump(res, f, indent=1)
        print(f"[ft] result digests -> {args.result}")
    return final_loss


def _print_instrument_summary(events):
    """Per-op engine summary + the fwd/bwd GEMM flop split of one step."""
    from repro.roofline import analysis

    for op, d in engine.summarize(events).items():
        print(f"[engine] {op}: calls={d['calls']} "
              f"gflops={d['flops']/1e9:.3f} gbytes={d['bytes']/1e9:.3f}")
    split = analysis.flops_by_direction(events)
    bsplit = analysis.bytes_by_direction(events)
    fwd, bwd = split["fwd"], split["bwd"]
    ratio = (fwd + bwd) / fwd if fwd else 0.0
    print(f"[engine] fwd_gflops={fwd/1e9:.3f} bwd_gflops={bwd/1e9:.3f} "
          f"train/inference={ratio:.2f}x")
    print(f"[engine] fwd_gbytes={bsplit['fwd']/1e9:.4f} "
          f"bwd_gbytes={bsplit['bwd']/1e9:.4f}")


def _ae_main(args):
    """The paper's §III-B use case on the CLI: AE training in pure FP16
    (default) or any registered precision policy — ``--policy
    mixed_fp8_e4m3`` trains with FP8 storage + per-tensor scales (the
    mixed-precision RedMulE regime; GEMM bytes drop, flops don't)."""
    from repro.core import precision as prec
    from repro.data import SyntheticAE
    from repro.models import autoencoder

    policy = prec.resolve(args.policy or "paper_fp16")
    params = autoencoder.init_ae(jax.random.PRNGKey(args.seed))
    opt = AdamW(lr=args.lr, warmup_steps=0)
    opt_state = opt.init(params)
    ds = SyntheticAE(batch=args.batch, seed=args.seed)

    def step(p_, s_, x):
        (loss, _), g = jax.value_and_grad(
            lambda q: autoencoder.ae_loss(q, x, policy=policy),
            has_aux=True)(p_)
        g, _ = clip_by_global_norm(g, 1.0)
        u, s_ = opt.update(g, s_, p_)
        return opt.apply(p_, u), s_, loss

    if args.instrument:
        with engine.instrument() as events:
            jax.eval_shape(step, params, opt_state,
                           jax.ShapeDtypeStruct((args.batch, ds.dim),
                                                jnp.float32))
        _print_instrument_summary(events)

    step = jax.jit(step, donate_argnums=(0, 1))
    loss = None
    for i in range(args.steps):
        x = jnp.asarray(ds.sample(i))
        params, opt_state, loss = step(params, opt_state, x)
        if i % 10 == 0:
            print(f"[{i}] mse={float(loss):.4f}")
    print(f"final mse: {float(loss):.4f}")


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", default="qwen3-1.7b",
                   choices=(*configs.ARCH_IDS, "ae"))
    p.add_argument("--reduced", action="store_true", default=True)
    p.add_argument("--full", dest="reduced", action="store_false")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--ckpt-dir", default="")
    p.add_argument("--save-every", type=int, default=50)
    p.add_argument("--fp16-scale", action="store_true",
                   help="pure-FP16 compute with dynamic loss scaling")
    p.add_argument("--policy", default=None,
                   help="precision policy for --arch ae (default "
                        "paper_fp16; mixed_fp8_e4m3 / mixed_fp8_e5m2 "
                        "train with FP8 storage + per-tensor scales)")
    p.add_argument("--instrument", action="store_true",
                   help="trace one step under engine.instrument() and print "
                        "the per-op GEMM flop/byte summary before training "
                        "(plus wire bytes / goodput on the DP paths)")
    p.add_argument("--compress", default="none",
                   choices=("none", "fp16", "int8", "fp8", "fp8_e4m3",
                            "fp8_e5m2"),
                   help="gradient all-reduce wire for data-parallel "
                        "training (fp8* = E4M3/E5M2 with delayed scaling "
                        "+ error feedback)")
    p.add_argument("--dp-procs", type=int, default=0,
                   help="data-parallel width; 0 = all visible devices "
                        "(simulate N on one host with XLA_FLAGS="
                        "--xla_force_host_platform_device_count=N)")
    p.add_argument("--fail-step", type=int, default=None,
                   help="inject a failure at this step on the compressed-DP "
                        "path (kill/resume digest testing; needs --ckpt-dir)")
    p.add_argument("--fail-mode", default="die",
                   choices=("raise", "die", "sigterm", "ckpt_crash"),
                   help="failure kind for --fail-step")
    p.add_argument("--result", default="",
                   help="write final params/EF/opt sha256 digests + loss as "
                        "JSON (compressed-DP path; bit-identical-resume "
                        "verification)")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    compile_cache.enable()

    if args.arch == "ae":
        return _ae_main(args)

    cfg = configs.get_reduced(args.arch) if args.reduced else configs.get(args.arch)
    if args.compress != "none" or args.dp_procs:
        return _compressed_dp_main(args, cfg)
    if args.fp16_scale:
        import dataclasses
        cfg = dataclasses.replace(cfg, policy_name="tpu_fp16")
    opt = AdamW(lr=args.lr, warmup_steps=10)
    step = build_train_step(cfg, opt, rules=None, use_scale=args.fp16_scale)
    step = jax.jit(step, donate_argnums=(0,))

    state = init_state(jax.random.PRNGKey(args.seed), cfg, opt,
                       use_scale=args.fp16_scale)
    ds = SyntheticLM(
        vocab_size=cfg.vocab_size, seq_len=args.seq, global_batch=args.batch,
        seed=args.seed,
        embed_dim=cfg.d_model if cfg.input_mode == "embeddings" else 0)
    batches = Prefetcher(iter(ds), depth=2)

    if args.instrument:
        # abstract trace only — events are emitted at trace time; the
        # value_and_grad inside the step makes the custom-VJP backward
        # GEMMs (matmul_dx / matmul_dw) part of the trace too
        with engine.instrument() as events:
            jax.eval_shape(step, state, ds.batch(0))
        _print_instrument_summary(events)

    if args.ckpt_dir:
        ckpt = CheckpointManager(args.ckpt_dir, keep=2)
        loop = TrainLoop(step, ckpt, save_every=args.save_every)
        # step-indexed batches: the stream replays exactly after a restart
        out = loop.run(state, ds.batch, args.steps)
        print(f"final loss: {out['history'][-1]['loss']:.4f} "
              f"(stragglers: {out['straggler_steps']})")
        if args.instrument:
            _print_goodput(out)
    else:
        for i in range(args.steps):
            state, metrics = step(state, next(batches))
            if i % 10 == 0:
                print(f"[{i}] loss={float(metrics['loss']):.4f}")
        print(f"final loss: {float(metrics['loss']):.4f}")
    batches.close()


if __name__ == "__main__":
    main()
