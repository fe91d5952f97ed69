"""Drive yi-9b serving and training once on a TPU through the Pallas kernels.

    python chip_smoke.py             # one chip: serve, pallas-vs-xla, train
    python chip_smoke.py --chips 4   # four chips: compressed-gradient DP only

yi-9b runs at its published widths (d_model 4096, 32 query / 4 KV heads,
d_ff 11008, vocab 64000) with random weights from a fixed seed; only the
depth is cut, because 48 fp32 layers do not fit one chip's 16 GB.

One chip, in one process:

* serve: the continuous-batching ``Scheduler`` with the config's own
  ``tpu_bf16`` policy and the E4M3 KV cache takes prompts of three lengths
  between 512 and 2048 tokens, 32 new tokens each, over 8 slots;
* compare: prefill logits of one prompt on the ``pallas`` and the ``xla``
  backend, same chip and weights, within ``PREFILL_REL_TOL``;
* train: ``build_train_step`` + ``init_state`` take 3 AdamW steps, so the
  custom-VJP nt/tn backward kernels run; the first step's loss and each
  gradient leaf match the same gradient on ``xla`` within
  ``TRAIN_LOSS_REL_TOL`` and ``TRAIN_GRAD_REL_TOL``.

Every traced ``GemmEvent`` must name the ``pallas`` backend and every
compiled step must hold a ``tpu_custom_call``.  With ``--chips 4`` only the
data-parallel path runs (``launch/train.py``'s compressed-DP main), once
with the fp32 wire and once with the FP8 E4M3 wire, and the final losses
must agree within ``DP_LOSS_REL_TOL``.

Phase results print as JSON lines, then the device's memory statistics.  The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
any failure exits non-zero before it, and a host without a TPU is refused.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import configs  # noqa: E402
from repro.core import engine  # noqa: E402
from repro.data import SyntheticLM  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.launch import train as train_lib  # noqa: E402
from repro.models import transformer  # noqa: E402
from repro.optim import AdamW  # noqa: E402
from repro.serving import scheduler as sched_lib  # noqa: E402

ARCH = "yi-9b"
SEED = 0
# 8 fp32 layers are 5.5 GB and the 64000-row embedding and head 2.1 GB.
# Compiled for a v5e, the decode step needs 10.4 GiB and the 2048-token
# prefill 10.4 GiB, bf16 copies of the weights included.
SERVE_LAYERS = 8
PROMPT_LENS = (512, 1024, 2048)
SERVE_REQUESTS = 10          # 8 fill the slots, 2 wait for a free slot
NEW_TOKENS = 32
SLOTS = 8
KV_STORAGE = "float8_e4m3fn"
# Training keeps params, grads and two Adam moments in fp32 (16 bytes per
# parameter).  Compiled for a v5e, one layer at 4 x 1024 tokens needs
# 11.0 GiB; two layers need 15.1 GiB at 2 x 1024, too close to the chip's
# 15.75 GiB.
TRAIN_LAYERS = 1
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 4, 1024, 3
# The first step's loss and gradient, pallas against xla, same init and
# batch.  A v5e read 6.4e-6 for the loss; the bound leaves 15x.
TRAIN_LOSS_REL_TOL = 1e-4
# Relative L2 of the gradient's difference, on its worst leaf.  A v5e
# read 2.0e-3 (ln2; every leaf 7.5e-4 to 2.0e-3, about one bf16 rounding
# of 2^-9); the bound leaves 5x.
TRAIN_GRAD_REL_TOL = 1e-2
# Both backends run bf16 operands with fp32 accumulation and store bf16
# between ops, but round at different points (fused vs post-op epilogue,
# fp32 vs bf16 probabilities in attention, accumulation order).  A bf16
# rounding is at most 2^-9 relative; about ten differing rounding points
# per layer over 8 layers add up, as independent errors, to
# sqrt(80) * 2^-9 = 1.7e-2 of the logits' norm at worst.  An FP8 E4M3
# datapath (2^-4 per rounding) would miss this bound on its first GEMM.
PREFILL_REL_TOL = 3e-2
# Pure data-parallel training replicates params and Adam state on every
# chip, next to each chip's fp32 error-feedback residual.  Compiled for a
# v5e:2x2, one layer at the full vocabulary holds 11157067776 B of
# arguments and 3847289344 B of temporaries per chip on the E4M3 wire
# (8367792640 B and 4877986816 B on the fp32 wire), inside 16 GB because
# the step donates its state.
DP_LAYERS = 1
DP_BATCH, DP_SEQ, DP_STEPS = 8, 512, 5
# Final loss, E4M3 wire against fp32 wire.  Four v5e chips read 1.7e-5
# (7.3e-5 at an eighth of the vocabulary); the bound leaves 12x.
DP_LOSS_REL_TOL = 2e-4
# AdamW's lr in the train and DP phases.  launch/train.py's default of
# 3e-3 suits the reduced presets; at yi-9b widths it drove the DP loss
# from 9.6 to 23 in 5 steps on a v5e.
LR = 1e-4


def report(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def _check_events(events, backend: str, phase: str) -> int:
    if not events:
        raise RuntimeError(f"{phase}: no GemmEvent was traced")
    seen = sorted({ev.backend for ev in events})
    if seen != [backend]:
        raise RuntimeError(f"{phase}: GemmEvents on backends {seen}, "
                           f"expected only {backend!r}")
    return len(events)


def _memory(compiled) -> dict:
    m = compiled.memory_analysis()
    return {k: int(getattr(m, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "alias_size_in_bytes")}


def serve_phase(cfg, *, prompt_lens=PROMPT_LENS, n_requests=SERVE_REQUESTS,
                new_tokens=NEW_TOKENS, slots=SLOTS) -> dict:
    """Serve ``n_requests`` greedy requests through the scheduler, then
    compare one prompt's prefill logits on this backend and on ``xla``."""
    backend = engine.DEFAULT_ENGINE.resolve_backend()
    params = transformer.init_params(jax.random.PRNGKey(SEED), cfg)
    max_len = max(prompt_lens) + new_tokens
    scfg = sched_lib.SchedulerConfig(n_slots=slots, max_len=max_len,
                                     storage_dtype=KV_STORAGE)
    sched = sched_lib.Scheduler(params, cfg, scfg)
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, cfg.vocab_size, prompt_lens[i % len(prompt_lens)],
                            dtype=np.int32) for i in range(n_requests)]
    n = slots
    with engine.instrument() as events:
        # compile the decode step and each prefill length ahead of the run;
        # the scheduler's own calls then reuse these executables
        t0 = time.perf_counter()
        decode = sched._decode.lower(
            params, sched.cache, jnp.zeros((n, 1), jnp.int32),
            jnp.zeros((n,), jnp.int32), jnp.zeros((n,), jnp.int32)).compile()
        decode_compile_s = time.perf_counter() - t0
        prefill_compile_s, prefills = {}, []
        for plen in sorted(set(prompt_lens)):
            t0 = time.perf_counter()
            prefills.append(sched._prefill_fn(plen).lower(
                params, jnp.zeros((1, plen), jnp.int32)).compile())
            prefill_compile_s[plen] = time.perf_counter() - t0
        sched.submit([
            sched_lib.Request(rid=i, arrival=0.0, prompt=p,
                              max_new_tokens=new_tokens)
            for i, p in enumerate(prompts)])
        step_s = []
        t_run = time.perf_counter()
        while True:
            t0 = time.perf_counter()
            more = sched.step()
            if not more:
                break
            step_s.append(time.perf_counter() - t0)
        run_s = time.perf_counter() - t_run
    n_events = _check_events(events, backend, "serve")
    results = [sched.results[i] for i in range(n_requests)]
    for r in results:
        if r.status != "finished" or len(r.tokens) != new_tokens:
            raise RuntimeError(f"request {r.rid}: status {r.status}, "
                               f"{len(r.tokens)}/{new_tokens} tokens")
        if not np.all(np.isfinite(r.final_logits)):
            raise RuntimeError(f"request {r.rid}: non-finite logits")
    custom_call = all("tpu_custom_call" in c.as_text()
                      for c in (decode, *prefills))
    n_prefills = sum(1 for e in sched.trace if e[0] == "prefill")

    # the serving prefill's logits against the same prefill on the xla
    # backend, same chip and weights
    prompt = jnp.asarray(prompts[0])[None]
    a = np.asarray(sched._prefill_fn(prompt.shape[1])(params, prompt)[0],
                   np.float32)
    with engine.use_backend("xla"):
        ref_fn = jax.jit(lambda p, t: transformer.prefill(
            p, cfg, {"inputs": t}, max_len, storage_dtype=KV_STORAGE)[0])
        ref = np.asarray(ref_fn(params, prompt), np.float32)
    if not (np.all(np.isfinite(a)) and np.all(np.isfinite(ref))):
        raise RuntimeError("prefill logits are not finite")
    rel = float(np.linalg.norm(a - ref) / np.linalg.norm(ref))
    if not rel <= PREFILL_REL_TOL:
        raise RuntimeError(f"{backend} vs xla prefill logits: relative L2 "
                           f"{rel:.3e} > {PREFILL_REL_TOL:.1e}")
    return {
        "backend": backend, "layers": cfg.n_layers, "slots": slots,
        "requests": n_requests, "prompt_lens": list(prompt_lens),
        "tokens_served": sum(len(r.tokens) for r in results),
        "prefills": n_prefills, "decode_steps": sched.decode_steps,
        "decode_compile_s": decode_compile_s,
        "prefill_compile_s": prefill_compile_s,
        "run_s": run_s, "first_step_s": step_s[0],
        "median_step_s": float(np.median(step_s[1:])) if len(step_s) > 1
        else None,
        "gemm_events": n_events, "tpu_custom_call": custom_call,
        "decode_memory": _memory(decode),
        "prefill_memory": _memory(prefills[-1]),
        "prefill_rel_l2_vs_xla": rel, "prefill_rel_tol": PREFILL_REL_TOL,
        "prefill_max_abs_vs_xla": float(np.max(np.abs(a - ref))),
    }


def train_phase(cfg, *, batch=TRAIN_BATCH, seq=TRAIN_SEQ,
                steps=TRAIN_STEPS) -> dict:
    """``steps`` AdamW steps of ``build_train_step`` from ``init_state``,
    then the first step's loss and gradient, leaf by leaf, against the
    same gradient on ``xla``."""
    backend = engine.DEFAULT_ENGINE.resolve_backend()
    # the warmup launch/train.py's main builds its optimizer with
    opt = AdamW(lr=LR, warmup_steps=10)
    ds = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=seq,
                     global_batch=batch, seed=SEED)

    state = train_lib.init_state(jax.random.PRNGKey(SEED), cfg, opt)
    step = jax.jit(train_lib.build_train_step(cfg, opt, rules=None),
                   donate_argnums=(0,))
    with engine.instrument() as events:
        t0 = time.perf_counter()
        compiled = step.lower(state, ds.batch(0)).compile()
        compile_s = time.perf_counter() - t0
    n_events = _check_events(events, backend, "train")
    if not any(engine.is_backward_op(ev.spec.op) for ev in events):
        raise RuntimeError("train: no backward GEMM was traced")
    losses, grad_norms, step_s = [], [], []
    for i in range(steps):
        t0 = time.perf_counter()
        state, metrics = compiled(state, ds.batch(i))
        losses.append(float(metrics["loss"]))
        grad_norms.append(float(metrics["grad_norm"]))
        step_s.append(time.perf_counter() - t0)
    if not all(math.isfinite(x) for x in losses + grad_norms):
        raise RuntimeError(f"train: non-finite losses {losses} or gradient "
                           f"norms {grad_norms}")
    out = {
        "backend": backend, "layers": cfg.n_layers, "batch": batch,
        "seq": seq, "losses": losses, "grad_norms": grad_norms,
        "compile_s": compile_s, "step_s": step_s, "gemm_events": n_events,
        "tpu_custom_call": "tpu_custom_call" in compiled.as_text(),
        "memory": _memory(compiled),
    }
    del state, compiled
    gc.collect()

    # the first step's loss and gradient on each backend: init_state's
    # params, batch 0, the loss build_train_step differentiates
    params = transformer.init_params(jax.random.PRNGKey(SEED), cfg)

    def loss_and_grads(name):
        with engine.use_backend(name):
            return jax.jit(jax.value_and_grad(
                lambda p, b: transformer.loss_fn(p, cfg, b)[0]))(
                    params, ds.batch(0))

    loss, grads = loss_and_grads(backend)
    ref_loss, ref_grads = loss_and_grads("xla")
    loss_rel = abs(float(loss) - float(ref_loss)) / abs(float(ref_loss))
    if not loss_rel <= TRAIN_LOSS_REL_TOL:
        raise RuntimeError(f"train: step-0 loss {float(loss):.6g} on "
                           f"{backend} vs {float(ref_loss):.6g} on xla: "
                           f"relative gap {loss_rel:.3e} > "
                           f"{TRAIN_LOSS_REL_TOL:.1e}")
    leaf_rel = {
        jax.tree_util.keystr(path): float(
            jnp.linalg.norm(g - r) / jnp.linalg.norm(r))
        for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(grads),
                                jax.tree.leaves(ref_grads))}
    worst = max(leaf_rel, key=leaf_rel.get)
    if not leaf_rel[worst] <= TRAIN_GRAD_REL_TOL:
        raise RuntimeError(f"train: step-0 gradient of {worst} on {backend} "
                           f"vs xla: relative L2 {leaf_rel[worst]:.3e} > "
                           f"{TRAIN_GRAD_REL_TOL:.1e}")
    out.update(step0_loss_rel_vs_xla=loss_rel,
               step0_loss_rel_tol=TRAIN_LOSS_REL_TOL,
               step0_grad_rel_l2_vs_xla=leaf_rel,
               step0_worst_grad_leaf=worst,
               step0_grad_rel_tol=TRAIN_GRAD_REL_TOL)
    return out


def dp_phase(cfg, *, ndev, batch=DP_BATCH, seq=DP_SEQ,
             steps=DP_STEPS) -> dict:
    """Compressed-gradient DP training over ``ndev`` devices: the fp32
    wire and the FP8 E4M3 wire from the same init and batches."""
    out = {"layers": cfg.n_layers, "vocab": cfg.vocab_size, "devices": ndev,
           "batch": batch, "seq": seq, "steps": steps}
    for kind in ("none", "fp8_e4m3"):
        args = argparse.Namespace(
            dp_procs=ndev, batch=batch, seq=seq, steps=steps, lr=LR,
            seed=SEED, compress=kind, instrument=False, fail_step=None,
            fail_mode="die", ckpt_dir="", save_every=0, result="")
        t0 = time.perf_counter()
        out[f"loss_{kind}"] = train_lib._compressed_dp_main(args, cfg)
        out[f"wall_s_{kind}"] = time.perf_counter() - t0
        gc.collect()
    base, fp8 = out["loss_none"], out["loss_fp8_e4m3"]
    if not (math.isfinite(base) and math.isfinite(fp8)):
        raise RuntimeError(f"dp: non-finite losses {base}, {fp8}")
    rel = abs(fp8 - base) / abs(base)
    if not rel <= DP_LOSS_REL_TOL:
        raise RuntimeError(f"dp: fp8 wire loss {fp8:.5f} vs fp32 wire "
                           f"{base:.5f}: relative gap {rel:.3e} > "
                           f"{DP_LOSS_REL_TOL}")
    out.update(loss_rel_gap=rel, loss_rel_tol=DP_LOSS_REL_TOL)
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="1: serve, compare and train on one chip; 4: only "
                        "compressed-gradient DP training over four chips")
    args = p.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke.py needs a TPU; JAX found platform "
                         f"{dev.platform!r}")
    if len(devices) < args.chips:
        raise SystemExit(f"--chips {args.chips} but JAX sees "
                         f"{len(devices)} device(s)")
    cache_dir = compile_cache.enable()
    backend = engine.DEFAULT_ENGINE.resolve_backend()
    if backend != "pallas":
        raise SystemExit(f"the engine resolved backend {backend!r} on the "
                         f"TPU, expected 'pallas'")
    full = configs.get(ARCH)
    report("setup", arch=ARCH, d_model=full.d_model, n_heads=full.n_heads,
           n_kv_heads=full.n_kv_heads, d_ff=full.d_ff,
           vocab_size=full.vocab_size, published_layers=full.n_layers,
           policy=full.policy_name, backend=backend, compile_cache=cache_dir,
           jax=jax.__version__)

    if args.chips == 4:
        report("dp", **dp_phase(dataclasses.replace(full, n_layers=DP_LAYERS),
                                ndev=4))
    else:
        for phase, fn, layers in (("serve", serve_phase, SERVE_LAYERS),
                                  ("train", train_phase, TRAIN_LAYERS)):
            res = fn(dataclasses.replace(full, n_layers=layers))
            if not res["tpu_custom_call"]:
                raise RuntimeError(f"{phase}: a compiled step holds no "
                                   f"tpu_custom_call")
            report(phase, **res)
            gc.collect()
    # the allocator's counters on the first chip, over the whole process:
    # *_bytes_in_use counts live arrays, *_bytes_reserved the executables'
    # temporaries, so a step's need shows in neither alone
    report("memory", **(dev.memory_stats() or {}))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
