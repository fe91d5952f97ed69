"""Elastic multi-process data-parallel training worker.

The process-level half of the fault-tolerance story: `fault_tolerance.py`
hardens one process's step loop; this module is the *unit that dies*.  A
worker is a real OS process (spawned by tests, a shell, or a cluster
scheduler) that runs a data-parallel train job over a simulated multi-host
mesh (``--dp N`` sets ``XLA_FLAGS=--xla_force_host_platform_device_count``
before JAX initializes — imports here are lazy for exactly that reason)
with the full resilience stack: compressed gradient all-reduce
(``--compress fp8`` etc., optim/compression.py), checksum-verified
checkpoints, the goodput heartbeat, and deterministic fault injection
(``--fail-step/--fail-mode``).

Crash-tested contracts (tests/test_ft_gates.py, CI ft-gates):

* **kill-and-resume** — SIGKILL-grade death (``--fail-mode die``) at step
  k, relaunch with the same flags: the resumed worker restores the last
  checkpoint, replays the step-indexed batch stream, and reaches a final
  state **bit-identical** to an uninterrupted run — on the fp32 wire and
  on the FP8-compressed wire (error feedback and delayed-scale windows are
  part of the checkpointed state, so the wire's history survives too).
* **torn checkpoint write** (``--fail-mode ckpt_crash``) — dying mid-save
  leaves only a ``.tmp`` payload; resume lands on the previous complete
  checkpoint.
* **elastic resume** — relaunch with a different ``--dp``: checkpoints are
  logical; params/opt are replicated over the data axis, while the
  per-host compression state (error-feedback residuals, FP8 amax windows)
  is stored with an explicit leading host axis and *regrouped* on attach —
  residuals are summed within each merge group (total uncommunicated
  gradient mass is conserved) and scale statistics take the group max — so
  a 4-process checkpoint continues on a 2-process mesh (gradient *means*
  are mathematically identical across regroupings; bit-level identity is
  only promised at fixed mesh shape).
* **preemption** — SIGTERM (external, or ``--fail-mode sigterm``) makes
  the loop checkpoint and exit 0; the result file records ``preempted``.

The model is deliberately tiny (a 2-layer MLP regression on step-indexed
synthetic data): what is under test is the distributed loop, the wire, and
the recovery machinery, not the FLOPs.  ``launch/train.py --compress
--dp-procs`` drives the same machinery with the real LM/AE models.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
from typing import Any, Optional

__all__ = ["run_worker", "main", "WorkerConfig"]

_MODEL_DIMS = (8, 32, 8)  # in -> hidden -> out


def _build(args):
    """Construct (step_fn, init_state, batch_fn) — lazy jax imports."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.optim import AdamW, Compressor
    from repro.runtime import compat

    ndev = len(jax.devices())
    if ndev < args.dp:
        raise SystemExit(
            f"worker needs {args.dp} devices but jax sees {ndev}; spawn "
            "with XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{args.dp} (or pass --dp {ndev})")
    mesh = compat.make_mesh((args.dp,), ("data",))
    comp = Compressor(args.compress)
    opt = AdamW(lr=1e-2, warmup_steps=0)

    din, dh, dout = _MODEL_DIMS
    k0 = jax.random.PRNGKey(args.seed)
    kw1, kw2, ka = jax.random.split(k0, 3)
    target_A = jax.random.normal(ka, (din, dout), jnp.float32)

    def init_state(dp: Optional[int] = None):
        params = {
            "w1": jax.random.normal(kw1, (din, dh), jnp.float32) * 0.3,
            "b1": jnp.zeros((dh,), jnp.float32),
            "w2": jax.random.normal(kw2, (dh, dout), jnp.float32) * 0.3,
            "b2": jnp.zeros((dout,), jnp.float32),
        }
        # Compression state (EF residual + fp8 scale windows) is genuinely
        # per-host — each host accumulates the residual of *its* batch
        # shard — so it carries an explicit leading host axis, sharded
        # P("data").  Storing it "replicated" would silently checkpoint
        # only host 0's residual (shard_map's check_vma=False stamps the
        # out-spec without verifying it), breaking bit-identical resume.
        ef = comp.init(params)
        if ef is not None:
            ef = jax.tree.map(lambda l: jnp.stack([l] * (dp or args.dp)), ef)
        return {"params": params, "opt": opt.init(params), "ef": ef}

    def loss_fn(params, batch):
        x, y = batch["x"], batch["y"]
        h = jnp.tanh(x @ params["w1"] + params["b1"])
        pred = h @ params["w2"] + params["b2"]
        return jnp.mean((pred - y) ** 2)

    def batch_fn(step: int):
        kx = jax.random.fold_in(jax.random.PRNGKey(args.seed + 1), step)
        x = jax.random.normal(kx, (args.batch, din), jnp.float32)
        return {"x": x, "y": x @ target_A}

    def local(params, ef_hosts, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        if comp.kind == "none":
            mean_g = jax.tree.map(
                lambda g: jax.lax.pmean(g.astype(jnp.float32), ("data",)),
                grads)
            ef2_hosts = ef_hosts
        else:
            # strip this host's slot off the leading host axis, compress,
            # and put the new residual back in the same slot
            ef = jax.tree.map(lambda x: x[0], ef_hosts)
            wire, ef2 = comp.compress(grads, ef)
            mean_g = comp.psum_wire(wire, ("data",))
            ef2_hosts = jax.tree.map(lambda x: x[None], ef2)
        return mean_g, ef2_hosts, jax.lax.pmean(loss, ("data",))

    state0 = jax.eval_shape(init_state)
    pspec = jax.tree.map(lambda _: P(), state0["params"])
    espec = jax.tree.map(lambda _: P("data"), state0["ef"])
    bspec = {"x": P("data"), "y": P("data")}

    sharded_local = jax.shard_map(
        local, mesh=mesh,
        in_specs=(pspec, espec, bspec),
        out_specs=(pspec, espec, P()),
        check_vma=False)

    def step_fn(state, batch):
        mean_g, ef2, loss = sharded_local(state["params"], state["ef"], batch)
        updates, new_opt = opt.update(mean_g, state["opt"], state["params"])
        new_params = opt.apply(state["params"], updates)
        return ({"params": new_params, "opt": new_opt, "ef": ef2},
                {"loss": loss})

    # Canonical placement — the bit-identical-resume invariant.  A resumed
    # process's first step receives host (np) arrays from the checkpoint
    # while a clean run's steps receive the previous step's device
    # outputs; pinned in_/out_shardings force every step of every
    # incarnation — fresh, resumed, re-meshed — through one executable and
    # one placement per mesh shape.
    from jax.sharding import NamedSharding
    rep = NamedSharding(mesh, P())
    dp_sh = NamedSharding(mesh, P("data"))
    state_sh = {
        "params": jax.tree.map(lambda _: rep, state0["params"]),
        "opt": jax.tree.map(lambda _: rep, state0["opt"]),
        "ef": jax.tree.map(lambda _: dp_sh, state0["ef"]),
    }
    jitted = jax.jit(step_fn,
                     in_shardings=(state_sh, {"x": dp_sh, "y": dp_sh}),
                     out_shardings=(state_sh, rep))

    def canonical_step(state, batch):
        out = jitted(state, batch)
        if args.step_ms > 0:
            import time
            time.sleep(args.step_ms / 1e3)  # SIGTERM-mid-run test hook
        return out

    return canonical_step, init_state, batch_fn, mesh


def _digest(tree) -> str:
    """Order-stable sha256 over the float bytes of every leaf."""
    import jax
    import numpy as np

    h = hashlib.sha256()
    for leaf in jax.tree.leaves(tree):
        h.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
    return h.hexdigest()


def _regroup_axis0(x, dp_new: int, how: str):
    """Regroup a per-host-stacked array onto ``dp_new`` hosts.

    ``how="sum"`` (EF residuals): conserves the total along axis 0 — merge
    groups are summed, split groups divide evenly — so the uncommunicated
    gradient mass survives any resize.  ``how="max"`` (fp8 scale stats,
    amax windows, step counts): conservative group maximum.
    """
    import numpy as np

    x = np.asarray(x)
    dp_old = x.shape[0]
    if dp_old == dp_new:
        return x
    if dp_old % dp_new == 0:
        g = x.reshape((dp_new, dp_old // dp_new) + x.shape[1:])
        return g.sum(axis=1) if how == "sum" else g.max(axis=1)
    if dp_new % dp_old == 0:
        r = dp_new // dp_old
        rep = np.repeat(x, r, axis=0)
        return rep / np.asarray(r, x.dtype) if how == "sum" else rep
    # non-divisible resize: collapse to one logical host, pad the rest
    tot = x.sum(axis=0) if how == "sum" else x.max(axis=0)
    out = np.zeros((dp_new,) + x.shape[1:], x.dtype)
    out[0] = tot
    if how == "max":
        out[:] = tot
    return out


def _regroup_ef(ef, dp_new: int):
    """Regroup the per-host compression-state tree onto ``dp_new`` hosts."""
    import jax

    from repro.optim import Fp8LeafState

    if ef is None:
        return None

    def one(node):
        if isinstance(node, Fp8LeafState):
            return Fp8LeafState(
                ef=_regroup_axis0(node.ef, dp_new, "sum"),
                scale=jax.tree.map(
                    lambda s: _regroup_axis0(s, dp_new, "max"), node.scale))
        return _regroup_axis0(node, dp_new, "sum")

    return jax.tree.map(one, ef,
                        is_leaf=lambda n: isinstance(n, Fp8LeafState))


def _maybe_migrate_elastic(ckpt, init_state, dp_new: int, log=print) -> None:
    """Elastic attach: if the newest valid checkpoint was written by a
    job with a different ``--dp``, regroup its per-host compression state
    onto this job's host count and rewrite the checkpoint in place (the
    atomic save makes the migration itself crash-safe).  Params/opt are
    replicated and pass through untouched."""
    import jax

    from repro.checkpoint import CheckpointCorruptError

    like_new = jax.eval_shape(init_state)
    if like_new["ef"] is None:
        return  # no per-host state on the fp32 wire
    ef_leaf0 = jax.tree.leaves(like_new["ef"])[0]
    # leaf index of the first ef leaf within the flattened state
    idx = jax.tree.leaves(like_new).index(ef_leaf0)
    for step in reversed(ckpt.all_steps()):
        try:
            arrays, manifest = ckpt._load_verified(step)
        except CheckpointCorruptError:
            continue  # restore_latest will warn about this one
        dp_old = int(manifest["shapes"][f"leaf_{idx}"][0])
        if dp_old == dp_new:
            return
        log(f"[ft] elastic attach: regrouping step-{step} checkpoint "
            f"from dp={dp_old} to dp={dp_new}")
        state, meta = ckpt.restore(step, init_state(dp_old))
        state["ef"] = _regroup_ef(state["ef"], dp_new)
        meta = dict(meta)
        meta["elastic_migrated_from_dp"] = dp_old
        ckpt.save(step, state, meta)
        return


def run_worker(args) -> dict:
    from repro.checkpoint import CheckpointManager
    from repro.runtime.fault_tolerance import (FailureInjector,
                                               StragglerWatchdog, TrainLoop)

    step_fn, init_state, batch_fn, mesh = _build(args)
    ckpt = CheckpointManager(args.ckpt, keep=args.keep)
    _maybe_migrate_elastic(ckpt, init_state, args.dp)
    injector = None
    if args.fail_step is not None:
        injector = FailureInjector(fail_at_step=args.fail_step,
                                   mode=args.fail_mode)
    loop = TrainLoop(
        step_fn,
        ckpt,
        save_every=args.save_every,
        injector=injector,
        handle_sigterm=args.handle_sigterm,
        watchdog=StragglerWatchdog(threshold=100.0),  # no flakes in CI
    )
    out = loop.run(init_state(), batch_fn, args.steps,
                   log_every=args.log_every)
    result = {
        "last_step": int(out["last_step"]),
        "loss": float(out["history"][-1]["loss"]) if out["history"] else None,
        "digest": _digest(out["final_state"]["params"]),
        "preempted": bool(out["preempted"]),
        "goodput": out["goodput"],
        "dp": args.dp,
        "compress": args.compress,
    }
    if args.result:
        tmp = args.result + ".tmp"
        with open(tmp, "w") as f:
            json.dump(result, f)
        os.replace(tmp, args.result)
    return result


def main(argv: Optional[Any] = None) -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--ckpt", required=True)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--save-every", type=int, default=2)
    p.add_argument("--keep", type=int, default=3)
    p.add_argument("--dp", type=int, default=1,
                   help="data-parallel processes to simulate (host devices)")
    p.add_argument("--compress", default="none",
                   help="gradient wire: none|fp16|int8|fp8|fp8_e4m3|fp8_e5m2")
    p.add_argument("--batch", type=int, default=8,
                   help="global batch (must divide by --dp)")
    p.add_argument("--fail-step", type=int, default=None)
    p.add_argument("--fail-mode", default="die",
                   choices=("raise", "die", "sigterm", "ckpt_crash"))
    p.add_argument("--handle-sigterm", action="store_true")
    p.add_argument("--step-ms", type=int, default=0,
                   help="artificial per-step delay (signal-delivery tests)")
    p.add_argument("--result", default="",
                   help="write the final {digest, loss, goodput} JSON here")
    p.add_argument("--log-every", type=int, default=1000)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    if args.batch % args.dp:
        raise SystemExit(f"--batch {args.batch} must divide by --dp {args.dp}")
    # must happen before the first jax import anywhere in this process
    if args.dp > 1 and "XLA_FLAGS" not in os.environ:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.dp}")
    run_worker(args)


if __name__ == "__main__":
    main()
