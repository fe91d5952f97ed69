"""Multi-pod dry-run: AOT lower+compile every (arch x shape x mesh) cell.

THE FIRST TWO LINES must run before any other import — jax locks the device
count at first init, and the production meshes need 512 host devices."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ruff: noqa: E402
import argparse
import dataclasses
import json
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.core import engine
from repro.launch import mesh as mesh_lib
from repro.launch import serve as serve_lib
from repro.launch import train as train_lib
from repro.models import transformer
from repro.optim import AdamW
from repro.roofline import analysis as roofline_lib
from repro.runtime import sharding
from repro.serving import specs as serving_specs

__all__ = ["dryrun_cell", "main"]


def _ns(mesh, tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda x: isinstance(x, P))


def _sanitize(tree_spec, tree_abs, mesh):
    return jax.tree.map(
        lambda s, a: sharding.sanitize_spec(s, a.shape, mesh),
        tree_spec, tree_abs, is_leaf=lambda x: isinstance(x, P))


def dryrun_cell(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    fsdp: bool = True,
    sequence_parallel: bool = False,
    remat: Optional[str] = None,
    policy: Optional[str] = None,
    q_chunk: Optional[int] = None,
    ce_chunk: Optional[int] = None,
    cast_params: bool = False,
    grad_accum: int = 1,
    moe_impl: Optional[str] = None,
    ssm_chunk: Optional[int] = None,
    donate: bool = True,
    verbose: bool = True,
) -> dict:
    """Lower + compile one cell; return the roofline/memory record."""
    cfg = configs.get(arch)
    overrides = {}
    if remat is not None:
        overrides["remat"] = remat
    if policy is not None:
        overrides["policy_name"] = policy
    if q_chunk is not None:
        overrides["q_chunk"] = q_chunk
    if ce_chunk is not None:
        overrides["ce_chunk"] = ce_chunk
    if moe_impl is not None:
        overrides["moe_impl"] = moe_impl
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    if ssm_chunk is not None and cfg.ssm is not None:
        cfg = dataclasses.replace(
            cfg, ssm=dataclasses.replace(cfg.ssm, chunk=ssm_chunk))
    shape = configs.SHAPES[shape_name]
    if shape.kind != "train":
        # serving stores parameters in the serving compute precision
        cfg = dataclasses.replace(
            cfg, param_dtype=jnp.dtype(cfg.policy.compute_dtype).name)
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    n_dev = mesh.devices.size

    if shape.kind == "decode" and shape.name == "long_500k" \
            and not cfg.supports_long_context_decode:
        return {
            "arch": arch, "shape": shape_name, "mesh": mesh_name,
            "skipped": "pure full-attention arch: quadratic 500k decode "
                       "(DESIGN.md §5)",
        }

    t0 = time.time()
    specs = configs.input_specs(cfg, shape)

    # every GEMM dispatched while the cell is traced lands in gemm_events;
    # the roofline consumes them instead of re-deriving shapes by hand
    with jax.set_mesh(mesh), engine.instrument() as gemm_events:
        if shape.kind == "train":
            rules = sharding.Rules(fsdp=fsdp, sequence_parallel=sequence_parallel)
            opt = AdamW(lr=1e-4)
            step = train_lib.build_train_step(cfg, opt, rules,
                                              cast_params=cast_params,
                                              grad_accum=grad_accum)
            state_abs = jax.eval_shape(
                lambda: train_lib.init_state(jax.random.PRNGKey(0), cfg, opt))
            sspec = train_lib.state_specs(cfg, rules, mesh, opt)
            bspec = _sanitize(train_lib.batch_specs(cfg, mesh), specs, mesh)
            jitted = jax.jit(
                step,
                in_shardings=(_ns(mesh, sspec), _ns(mesh, bspec)),
                out_shardings=(_ns(mesh, sspec), None),
                donate_argnums=(0,) if donate else (),
            )
            lowered = jitted.lower(state_abs, specs)
        elif shape.kind == "prefill":
            rules = serve_lib.serve_rules(
                sharding.Rules(sequence_parallel=sequence_parallel))
            pre = serve_lib.build_prefill(cfg, rules, max_len=shape.seq_len)
            pabs = transformer.abstract_params(cfg)
            pspec = _sanitize(transformer.param_specs(cfg, rules), pabs, mesh)
            bspec = _sanitize(train_lib.batch_specs(cfg, mesh), specs, mesh)
            bspec = {k: bspec[k] for k in specs}  # prefill has no labels
            jitted = jax.jit(
                pre,
                in_shardings=(_ns(mesh, pspec), _ns(mesh, bspec)),
            )
            lowered = jitted.lower(pabs, specs)
        else:  # decode
            rules = serve_lib.serve_rules(sharding.Rules())
            step = serve_lib.build_serve_step(cfg, rules)
            pabs = transformer.abstract_params(cfg)
            pspec = _sanitize(transformer.param_specs(cfg, rules), pabs, mesh)
            # one source of truth with serve.cache_spec_tree (serving.specs)
            cabs, cspec = serving_specs.decode_cache_specs(
                cfg, rules, mesh, shape.global_batch, shape.seq_len)
            dp = mesh_lib.data_axes(mesh)
            tok_spec = (P(dp, None)
                        if shape.global_batch % _prod(mesh, dp) == 0 else P())
            jitted = jax.jit(
                step,
                in_shardings=(_ns(mesh, pspec), _ns(mesh, cspec),
                              NamedSharding(mesh, tok_spec), None),
                out_shardings=(None, _ns(mesh, cspec)),
                donate_argnums=(1,) if donate else (),
            )
            lowered = jitted.lower(
                pabs, cabs, specs["inputs"],
                jax.ShapeDtypeStruct((), jnp.int32))

        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    hlo = compiled.as_text()
    report = roofline_lib.roofline(
        compiled, arch=arch, shape=shape_name, mesh_name=mesh_name,
        n_devices=n_dev,
        model_flops_val=roofline_lib.model_flops(cfg, shape), hlo_text=hlo,
        gemm_events=gemm_events)
    rec = report.to_json()
    rec.update(
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        hlo_bytes=len(hlo),
        fsdp=fsdp,
        sequence_parallel=sequence_parallel,
        remat=cfg.remat,
        policy=cfg.policy_name,
        ce_chunk=cfg.ce_chunk,
        cast_params=cast_params,
        grad_accum=grad_accum,
        per_device_hbm_gib=round(
            (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes) / 2**30, 3),
    )
    if verbose:
        print(f"[{mesh_name}] {arch} x {shape_name}: "
              f"mem={rec['per_device_hbm_gib']:.2f} GiB/dev  "
              f"compute={report.compute_s*1e3:.2f}ms "
              f"memory={report.memory_s*1e3:.2f}ms "
              f"collective={report.collective_s*1e3:.2f}ms "
              f"-> {report.dominant}-bound  "
              f"(useful={report.useful_flops_ratio:.2f}, "
              f"roofline={report.roofline_fraction:.2%}; "
              f"lower {t_lower:.1f}s compile {t_compile:.1f}s)", flush=True)
    return rec


def _prod(mesh, axes):
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", default="all",
                   help="arch id or 'all'")
    p.add_argument("--shape", default="all",
                   choices=["all"] + list(configs.SHAPES))
    p.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    p.add_argument("--out", default="experiments/dryrun")
    p.add_argument("--tag", default="baseline")
    p.add_argument("--no-fsdp", dest="fsdp", action="store_false")
    p.add_argument("--sp", dest="sequence_parallel", action="store_true")
    p.add_argument("--remat", default=None, choices=[None, "none", "dots", "full"])
    p.add_argument("--policy", default=None)
    p.add_argument("--q-chunk", type=int, default=None)
    p.add_argument("--ce-chunk", type=int, default=None)
    p.add_argument("--cast-params", action="store_true")
    p.add_argument("--grad-accum", type=int, default=1)
    p.add_argument("--moe-impl", default=None, choices=[None, "gspmd", "shard_map"])
    p.add_argument("--ssm-chunk", type=int, default=None)
    args = p.parse_args(argv)

    archs = list(configs.ARCH_IDS) if args.arch == "all" else [args.arch]
    shapes = list(configs.SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    failures = []
    for arch in archs:
        for shape_name in shapes:
            for multi in meshes:
                mesh_name = "pod2x16x16" if multi else "pod16x16"
                fname = os.path.join(
                    args.out, f"{args.tag}__{arch}__{shape_name}__{mesh_name}.json")
                try:
                    rec = dryrun_cell(
                        arch, shape_name, multi_pod=multi, fsdp=args.fsdp,
                        sequence_parallel=args.sequence_parallel,
                        remat=args.remat, policy=args.policy,
                        q_chunk=args.q_chunk, ce_chunk=args.ce_chunk,
                        cast_params=args.cast_params,
                        grad_accum=args.grad_accum,
                        moe_impl=args.moe_impl,
                        ssm_chunk=args.ssm_chunk)
                except Exception as e:
                    traceback.print_exc()
                    failures.append((arch, shape_name, mesh_name, repr(e)))
                    rec = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_name, "error": repr(e)}
                rec["tag"] = args.tag
                with open(fname, "w") as f:
                    json.dump(rec, f, indent=1)
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f_ in failures:
            print("  ", f_)
        raise SystemExit(1)
    print("\nall dry-run cells compiled OK")


if __name__ == "__main__":
    main()
