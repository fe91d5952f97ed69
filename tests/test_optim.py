"""Optimizer substrate: AdamW/SGD, dynamic loss scaling, compression."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim import (AdamW, SGD, Compressor, adjust, clip_by_global_norm,
                         global_norm, init_scale, scale_loss,
                         unscale_and_check)


def test_adamw_converges_quadratic():
    opt = AdamW(lr=0.1)
    params = {"w": jnp.asarray([5.0, -3.0, 2.0])}
    state = opt.init(params)

    @jax.jit
    def step(p, s):
        g = jax.grad(lambda q: jnp.sum(q["w"] ** 2))(p)
        u, s = opt.update(g, s, p)
        return opt.apply(p, u), s

    for _ in range(200):
        params, state = step(params, state)
    assert float(jnp.max(jnp.abs(params["w"]))) < 1e-2


def test_sgd_momentum_converges():
    opt = SGD(lr=0.05, momentum=0.9)
    params = jnp.asarray([4.0, -4.0])
    state = opt.init(params)
    for _ in range(150):
        g = jax.grad(lambda q: jnp.sum(q ** 2))(params)
        u, state = opt.update(g, state, params)
        params = opt.apply(params, u)
    assert float(jnp.max(jnp.abs(params))) < 1e-2


def test_weight_decay_shrinks_params():
    opt = AdamW(lr=1e-2, weight_decay=0.1)
    p = {"w": jnp.ones(4)}
    s = opt.init(p)
    zero_g = {"w": jnp.zeros(4)}
    for _ in range(50):
        u, s = opt.update(zero_g, s, p)
        p = opt.apply(p, u)
    assert float(p["w"][0]) < 1.0


def test_clip_by_global_norm():
    tree = {"a": jnp.full((10,), 3.0), "b": jnp.full((10,), 4.0)}
    clipped, norm = clip_by_global_norm(tree, 1.0)
    assert abs(float(norm) - np.sqrt(10 * 9 + 10 * 16)) < 1e-4
    assert abs(float(global_norm(clipped)) - 1.0) < 1e-5
    # under the limit -> untouched
    same, _ = clip_by_global_norm(tree, 1e9)
    np.testing.assert_allclose(np.asarray(same["a"]), np.asarray(tree["a"]))


# ------------------------------------------------------------------ #
# Dynamic loss scaling (the paper's FP16 training regime)
# ------------------------------------------------------------------ #
def test_loss_scale_halves_on_overflow_and_skips():
    s = init_scale(initial=2.0**15)
    grads = {"w": jnp.asarray([jnp.inf, 1.0])}
    g2, finite = unscale_and_check(grads, s)
    assert not bool(finite)
    s2 = adjust(s, finite)
    assert float(s2.scale) == 2.0**14
    assert int(s2.overflow_count) == 1
    assert int(s2.good_steps) == 0


def test_loss_scale_grows_after_interval():
    s = init_scale(initial=1024.0, growth_interval=3)
    for _ in range(3):
        s = adjust(s, jnp.bool_(True))
    assert float(s.scale) == 2048.0
    assert int(s.good_steps) == 0  # reset after growth


def test_scale_roundtrip():
    s = init_scale(initial=512.0)
    loss = jnp.float32(0.25)
    scaled = scale_loss(loss, s)
    assert float(scaled) == 128.0
    grads = {"w": jnp.asarray([512.0])}
    g, finite = unscale_and_check(grads, s)
    assert bool(finite)
    np.testing.assert_allclose(np.asarray(g["w"]), [1.0])


def test_fp16_training_with_scaling_survives_overflow():
    """End-to-end: a step that overflows is skipped, training continues."""
    from repro import configs
    from repro.launch.train import build_train_step, init_state

    cfg = configs.get_reduced("qwen3-1.7b")
    import dataclasses
    cfg = dataclasses.replace(cfg, policy_name="tpu_fp16")
    opt = AdamW(lr=1e-3)
    step = jax.jit(build_train_step(cfg, opt, rules=None, use_scale=True))
    state = init_state(jax.random.PRNGKey(0), cfg, opt, use_scale=True)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    batch = {"inputs": toks, "labels": toks}
    losses = []
    for _ in range(5):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
        assert np.isfinite(losses[-1])
    assert losses[-1] < losses[0]


# ------------------------------------------------------------------ #
# Gradient compression with error feedback
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("kind", ["fp16", "int8", "fp8_e4m3", "fp8_e5m2"])
def test_compression_roundtrip_error_bounded(kind):
    comp = Compressor(kind)
    g = {"w": jnp.asarray(np.random.default_rng(0).normal(size=(256,)), jnp.float32)}
    ef = comp.init(g)
    wire, ef = comp.compress(g, ef)
    rec = comp.decompress(wire)
    err = float(jnp.max(jnp.abs(rec["w"] - g["w"])))
    bound = {"fp16": 1e-2, "int8": 0.1,
             "fp8_e4m3": 0.25, "fp8_e5m2": 0.5}[kind]
    assert err < bound


def test_fp8_alias_and_unknown_kind():
    assert Compressor("fp8").kind == "fp8_e4m3"
    with pytest.raises(ValueError, match="unknown compression kind"):
        Compressor("fp7")


@pytest.mark.parametrize("kind", ["fp8_e4m3", "fp8_e5m2"])
def test_fp8_error_feedback_recovers_clipped_mass(kind):
    """The delayed scale starts at 1.0, so a first step with |g| above the
    format max clips hard — the clipped mass must land in the EF buffer
    and drain over the following steps as the amax window catches up."""
    comp = Compressor(kind)
    g_true = jnp.full((32,), 900.0, jnp.float32)  # above e4m3's 448 max
    ef = comp.init({"w": g_true})
    total_sent = jnp.zeros_like(g_true)
    for _ in range(8):
        wire, ef = comp.compress({"w": g_true}, ef)
        total_sent = total_sent + comp.decompress(wire)["w"]
    # over 8 steps the transmitted mean tracks the true gradient closely
    rel = float(jnp.max(jnp.abs(total_sent / 8 - g_true))) / 900.0
    assert rel < 0.05, rel
    # and the residual is what is still in flight, not lost
    resid = jax.tree.leaves(ef)[0]
    np.testing.assert_allclose(
        np.asarray(total_sent + resid), np.asarray(8 * g_true), rtol=1e-4)


@pytest.mark.parametrize("kind", ["fp16", "int8"])
def test_error_feedback_is_unbiased_over_steps(kind):
    """EF property: sum of decompressed grads ~= sum of true grads (the
    residual is carried, not lost)."""
    comp = Compressor(kind)
    rng = np.random.default_rng(1)
    g_true = jnp.asarray(rng.normal(size=(64,)), jnp.float32) * 1e-3
    ef = comp.init({"w": g_true})
    total_sent = jnp.zeros_like(g_true)
    n = 50
    for _ in range(n):
        wire, ef = comp.compress({"w": g_true}, ef)
        total_sent = total_sent + comp.decompress(wire)["w"]
    # accumulated transmission error == final residual, which is bounded
    resid = float(jnp.max(jnp.abs(total_sent - n * g_true)))
    one_step_q = float(jnp.max(jnp.abs(g_true))) * (2**-10 if kind == "fp16" else 1/127)
    assert resid < 4 * one_step_q * 1.5 + 1e-6


def test_compression_wire_sizes():
    assert Compressor("none").wire_bits == 32
    assert Compressor("fp16").wire_bits == 16
    assert Compressor("int8").wire_bits == 8
    assert Compressor("fp8_e4m3").wire_bits == 8
    assert Compressor("fp8_e5m2").wire_bits == 8


def test_wire_bytes_analytic():
    """wire_bytes prices what a ring all-reduce moves: wire_bits/8 per
    element plus one f32 scale per tensor on the scaled wires."""
    tree = {"w": jnp.zeros((16, 16)), "b": jnp.zeros((16,))}  # 272 elems
    assert Compressor("none").wire_bytes(tree) == 272 * 4
    assert Compressor("fp16").wire_bytes(tree) == 272 * 2
    assert Compressor("int8").wire_bytes(tree) == 272 + 2 * 4
    assert Compressor("fp8_e4m3").wire_bytes(tree) == 272 + 2 * 4
    # ShapeDtypeStructs price identically (no materialization needed)
    import jax
    abstract = jax.eval_shape(lambda: tree)
    assert (Compressor("fp8_e5m2").wire_bytes(abstract)
            == Compressor("fp8_e5m2").wire_bytes(tree))


def test_per_host_scales_match_fp32_oracle():
    """Multi-device (subprocess): hosts with gradient magnitudes 7 orders
    of magnitude apart.  The all-reduce must weight each host's payload by
    its OWN scale — the seed averaged the per-host scales into one shared
    divisor, inflating the small-gradient host's contribution ~1e7x.  Both
    8-bit wires are pinned against the fp32 oracle."""
    import os
    import subprocess
    import sys

    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.optim import Compressor
from repro.runtime import compat

mesh = compat.make_mesh((2,), ("data",))
rng = np.random.default_rng(0)
# host 0: tiny gradients; host 1: huge gradients
g = np.stack([rng.normal(size=256).astype(np.float32) * 1e-4,
              rng.normal(size=256).astype(np.float32) * 1e3])
oracle = g.astype(np.float64).mean(axis=0)

for kind in ("int8", "fp8_e4m3", "fp8_e5m2"):
    comp = Compressor(kind)
    ef0 = comp.init({"w": jnp.zeros(256, jnp.float32)})
    n_steps = 6

    def local(gs, ef):
        sent = jnp.zeros(256, jnp.float32)
        for _ in range(n_steps):  # EF drains over steps (delayed fp8 scale)
            wire, ef = comp.compress({"w": gs[0]}, ef)
            sent = sent + comp.psum_wire(wire, ("data",))["w"]
        return sent / n_steps

    espec = jax.tree.map(lambda _: P(), ef0)
    f = jax.shard_map(local, mesh=mesh, in_specs=(P("data"), espec),
                      out_specs=P(), check_vma=False)
    out = np.asarray(jax.jit(f)(jnp.asarray(g), ef0))
    rel = float(np.max(np.abs(out - oracle)) / np.max(np.abs(oracle)))
    print(kind, "rel_err_vs_oracle:", rel)
    assert rel < 0.02, (kind, rel)
print("OK")
"""
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": "src"})
    assert "OK" in out.stdout, (out.stdout[-1000:], out.stderr[-2000:])


def test_compressed_dp_train_step_matches_uncompressed():
    """Multi-device (subprocess): fp16-wire DP training tracks fp32-wire DP,
    and the all-reduce in the compiled module really runs on the 16-bit
    wire dtype."""
    import os
    import subprocess
    import sys

    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np, re
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import configs
from repro.launch.train import build_compressed_dp_train_step
from repro.optim import AdamW, Compressor
from repro.runtime import compat

cfg = configs.get_reduced("qwen3-1.7b")
mesh = compat.make_mesh((4, 1), ("data", "model"))
opt = AdamW(lr=1e-3)
toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab_size)
batch = {"inputs": toks, "labels": toks}

results = {}
with jax.set_mesh(mesh):
    for kind in ("none", "fp16"):
        comp = Compressor(kind)
        step, init_fn = build_compressed_dp_train_step(cfg, opt, mesh, comp)
        state = init_fn(jax.random.PRNGKey(0))
        jstep = jax.jit(step)
        if kind == "fp16":
            hlo = jstep.lower(state, batch).compile().as_text()
            # XLA merges psums into variadic all-reduces: check the result
            # tuple dtypes on every all-reduce line
            lines = [l for l in hlo.splitlines()
                     if " all-reduce(" in l and "= " in l]
            assert lines, "no all-reduce found"
            assert any("f16[" in l.split(" all-reduce(")[0] for l in lines), \\
                "no f16 wire: " + lines[0][:200]
        for _ in range(5):
            state, metrics = jstep(state, batch)
        results[kind] = (jax.tree.leaves(state[0].params), float(metrics["loss"]))

d = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
        for a, b in zip(*[results[k][0] for k in ("none", "fp16")]))
print("param divergence:", d, "losses:", results["none"][1], results["fp16"][1])
assert d < 5e-3, d
assert abs(results["none"][1] - results["fp16"][1]) < 0.05
print("OK")
"""
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": "src"})
    assert "OK" in out.stdout, (out.stdout[-1000:], out.stderr[-2000:])
