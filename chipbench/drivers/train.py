"""Training through the program's train step, with its data pipeline in the loop.

Set-up makes the weights from the seed, builds the optimizer state, jits
``launch.train.build_train_step`` once and drives that one object through
the first ``checked_steps`` steps on ``SyntheticLM`` batches 0, 1, 2 (the
first call compiles).  From them it reads what ``correct`` compares: each
step's loss, the first gradient as the optimizer got it (AdamW's first
moment after one step over ``1 - b1``), and after the last of them how far
each weight moved from where the seed put it.  The window then goes on with
the same state and stream; each step's batch is made on the host inside
the loop, and the host waits for a step only after it has queued the next.

The plain float32 reference repeats the checked steps once the program's
state is freed: cross-entropy, its gradient, global-norm clipping and AdamW
as the configuration file states them.  Compared, by the worst weight:
the norm of the first gradient and the norm of the change after the
checked steps, each gap of norms over the larger of that weight's
reference norm and the median weight's.  Weights whose reference gradient
is under a thousandth of the median weight's move by round-off alone under
Adam; they are left out of the change.  No loss is compared (PERF.md says
why); ``calibrate.py`` reads the first step's loss gap beside the others.
"""

from __future__ import annotations

import functools
import gc
import math
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import model
from chipbench.common import checks, memory_peak_bytes, now, passed
from chipbench.reference import dense

TINY_GRAD = 1e-3   # of the median weight's first-gradient norm


def leaf_names(tree) -> List[str]:
    return [jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_leaves_with_path(tree)]


@jax.jit
def leaf_norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree.leaves(tree)]


@functools.partial(jax.jit, static_argnums=(2,))
def change_norms(params, key, dims):
    """Per weight, ``||params - init(seed)||``; the initial weights are
    made again inside this program, never held beside the state."""
    p0 = model._init(key, dims, jnp.float32)
    return [jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32) - b)))
            for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p0))]


def floats(xs) -> List[float]:
    return [float(x) for x in xs]


def optimizer(tc):
    from repro.optim import AdamW

    return AdamW(lr=tc["lr"], b1=tc["b1"], b2=tc["b2"], eps=tc["eps"],
                 weight_decay=tc["weight_decay"],
                 warmup_steps=tc["warmup_steps"])


def reference_steps(ctx, batches, fp8: bool = False, rows=None) -> Dict:
    """The checked steps in plain float32 from the seed's weights: losses,
    first clipped gradient's norms and the change's norms, per weight.
    ``rows`` keeps only those rows of each batch (a fault for the test)."""
    tc, dims = ctx.conf["training"], ctx.dims
    w = model.init_weights(ctx.args.seed, dims)
    m = jax.tree.map(jnp.zeros_like, w)
    v = jax.tree.map(jnp.zeros_like, w)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def adam(w, m, v, g, t):
        gn = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        g = jax.tree.map(lambda x: x * jnp.minimum(
            1.0, tc["clip_norm"] / jnp.maximum(gn, 1e-9)), g)
        lr = tc["lr"] * jnp.minimum(1.0, (t + 1.0) / tc["warmup_steps"]) \
            if tc["warmup_steps"] > 0 else tc["lr"]
        m = jax.tree.map(lambda a, b: tc["b1"] * a + (1 - tc["b1"]) * b, m, g)
        v = jax.tree.map(lambda a, b: tc["b2"] * a + (1 - tc["b2"]) * b * b,
                         v, g)
        c1, c2 = 1 - tc["b1"] ** t, 1 - tc["b2"] ** t

        def upd(p, a, b):
            u = (a / c1) / (jnp.sqrt(b / c2) + tc["eps"])
            return p - lr * (u + tc["weight_decay"] * p)

        w = jax.tree.map(upd, w, m, v)
        return w, m, v, [jnp.sqrt(jnp.sum(x * x)) for x in jax.tree.leaves(g)]

    losses, g1 = [], None
    for k, b in enumerate(batches):
        x = jnp.asarray(b["inputs"] if rows is None else b["inputs"][rows])
        y = jnp.asarray(b["labels"] if rows is None else b["labels"][rows])
        loss, g = dense.loss_and_grad(w, dims, x, y, fp8)
        losses.append(float(loss))
        w, m, v, gnorms = adam(w, m, v, g, jnp.float32(k + 1))
        del g
        if g1 is None:
            g1 = floats(gnorms)
    del m, v
    ch = floats(change_norms(w, model.seed_key(ctx.args.seed), dims))
    del w
    return {"losses": losses, "grad": g1, "change": ch}


def gaps(prog: Dict, ref: Dict, first_loss: bool = False) -> Dict[str, float]:
    """The compared numbers: the worst weight's gap of first-gradient and
    change norms; with ``first_loss`` also the first step's loss gap
    (relative), which is read but not compared."""
    g_r = np.asarray(ref["grad"])
    g_med = float(np.median(g_r))
    grad = max(abs(a - b) / max(b, g_med)
               for a, b in zip(prog["grad"], ref["grad"]))
    c_r = np.asarray(ref["change"])
    keep = g_r >= TINY_GRAD * g_med
    c_med = float(np.median(c_r[keep]))
    change = max(abs(a - b) / max(b, c_med)
                 for a, b, k in zip(prog["change"], ref["change"], keep) if k)
    out = {"grad_gap": grad, "change_gap": change}
    if first_loss:
        out["first_loss_gap"] = (abs(prog["losses"][0] - ref["losses"][0])
                                 / abs(ref["losses"][0]))
    return out


def run_once(ctx, control: bool = False) -> Dict:
    """One run of the cell: set-up, window, check; with ``control`` also
    the control's and half a batch's readings (``calibrate.py``)."""
    from repro.data import SyntheticLM
    from repro.launch import train as T

    seconds = ctx.args.seconds
    conf, mix, dims = ctx.conf, ctx.mix, ctx.dims
    tc = conf["training"]
    cfg = model.program_config(conf)
    opt = optimizer(tc)
    weights = model.init_weights(ctx.args.seed, dims)
    model.check_layout(weights, cfg)
    names = leaf_names(weights)
    state = T.TrainState(params=weights, opt=opt.init(weights), scale=())
    del weights
    step = jax.jit(T.build_train_step(cfg, opt, rules=None,
                                      clip_norm=tc["clip_norm"]),
                   donate_argnums=(0,))
    ds = SyntheticLM(vocab_size=dims.vocab, seq_len=mix["seq"],
                     global_batch=mix["batch"], seed=int(ctx.args.seed))
    n_check = mix["checked_steps"]
    prog = {"losses": [], "grad": None, "change": None}
    for k in range(n_check):
        state, met = step(state, ds.batch(k))
        prog["losses"].append(float(met["loss"]))
        if k == 0:
            # AdamW's first moment after one step is (1 - b1) g
            prog["grad"] = [x / (1 - tc["b1"])
                            for x in floats(leaf_norms(state.opt.mu))]
    prog["change"] = floats(change_norms(state.params,
                                         model.seed_key(ctx.args.seed), dims))
    setup_s = now() - ctx.t_start

    ann = jax.profiler.TraceAnnotation
    tracer = ctx.tracer
    tracer.start()
    losses, k, prev = [], n_check, None
    t0 = now()
    with ann("cb.window"):
        while True:
            with ann("cb.batch"):
                b = ds.batch(k)
            with ann("cb.train_step"):
                state, met = step(state, b)
            k += 1
            if prev is not None:
                with ann("cb.sync"):
                    losses.append(float(prev["loss"]))
            prev = met
            if now() - t0 >= seconds:
                break
        with ann("cb.sync"):
            losses.append(float(prev["loss"]))
    t1 = now()
    tracer.stop()
    n = len(losses)
    tok_s = n * mix["batch"] * mix["seq"] / (t1 - t0)
    memory = memory_peak_bytes(ctx.devices)
    del state, met, prev, step
    gc.collect()

    batches = [ds.batch(i) for i in range(n_check)]
    ref = reference_steps(ctx, batches)
    readings = gaps(prog, ref, first_loss=control)
    out = {"setup_s": setup_s, "train_tok_s": tok_s, "attempted": n,
           "failed": sum(1 for x in losses if not math.isfinite(x)),
           "memory": memory, "readings": readings, "prog": prog, "ref": ref,
           "names": names, "record": {"steps": n, "batch": mix["batch"],
                                      "seq": mix["seq"], "remat": tc["remat"]}}
    if control:
        out["control_ref"] = reference_steps(ctx, batches, fp8=True)
        out["control"] = gaps(out["control_ref"], ref, first_loss=True)
        half = np.arange(mix["batch"] // 2)
        out["half_ref"] = reference_steps(ctx, batches, rows=half)
        out["half_batch"] = gaps(out["half_ref"], ref, first_loss=True)
    return out


def run(ctx) -> Dict:
    r = run_once(ctx)
    ch = checks(ctx.limits, r["readings"])
    return {"setup_s": r["setup_s"], "metrics": {"train_tok_s": r["train_tok_s"]},
            "attempted": r["attempted"], "failed": r["failed"],
            "memory": r["memory"], "checks": ch,
            "correct": passed(ch) and r["failed"] == 0, "record": r["record"]}
