"""Sharding rules, spec sanitization, and the roofline HLO parser."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.roofline import analysis as A
from repro.runtime import compat, sharding


def _mesh(shape=(1, 1), axes=("data", "model")):
    return compat.make_mesh(shape, axes)


def _abstract_mesh(shape=(2, 2), axes=("data", "model")):
    """Shape-only mesh stand-in (tests run on 1 CPU device)."""
    return compat.abstract_mesh(shape, axes)


# ------------------------------------------------------------------ #
# Logical axis rules
# ------------------------------------------------------------------ #
def test_logical_spec_basic():
    rules = sharding.Rules()
    spec = sharding.logical_spec(("batch", None, "ff"), rules)
    assert spec == P(("pod", "data"), None, "model")


def test_logical_spec_no_axis_reuse():
    """Two logical axes can't claim the same mesh axis in one spec."""
    rules = sharding.Rules()
    spec = sharding.logical_spec(("heads", "ff"), rules)
    assert spec == P("model", None)


def test_fsdp_shards_embed_axis():
    spec = sharding.logical_spec(("embed", "ff"), sharding.Rules(fsdp=True))
    assert spec == P(("pod", "data"), "model")
    spec = sharding.logical_spec(("embed", "ff"), sharding.Rules(fsdp=False))
    assert spec == P(None, "model")


def test_overrides_win():
    rules = sharding.Rules(overrides=(("kv_seq", ("model",)),))
    assert sharding.logical_spec(("kv_seq",), rules) == P("model")


def test_sanitize_drops_indivisible_and_unknown_axes():
    mesh = _abstract_mesh((2, 2))
    # 'pod' unknown on this mesh -> filtered; 5 not divisible by 2 -> dropped
    spec = P(("pod", "data"), "model")
    out = sharding.sanitize_spec(spec, (4, 5), mesh)
    assert out == P("data")
    out2 = sharding.sanitize_spec(P("model"), (6,), mesh)
    assert out2 == P("model")


def test_constrain_noop_outside_rules():
    x = jnp.ones((4, 4))
    assert sharding.constrain(x, "batch", None) is x


def test_constrain_inside_jit_applies():
    mesh = _mesh((1, 1))
    rules = sharding.Rules()

    def f(x):
        with sharding.use_rules(rules):
            return sharding.constrain(x * 1.0, "batch", "ff")

    with jax.set_mesh(mesh):
        txt = jax.jit(f).lower(jax.ShapeDtypeStruct((4, 4), jnp.float32)).as_text()
    assert "sharding" in txt.lower()


def test_constrain_fb_grad_path():
    """constrain_fb must be transparent to values and gradients."""
    x = jnp.arange(8.0)
    mesh = _mesh((1, 1))
    rules = sharding.Rules()

    def f(v):
        with sharding.use_rules(rules):
            y = sharding.constrain_fb(v * 2.0, ("batch",), (None,))
            return jnp.sum(y ** 2)

    with jax.set_mesh(mesh):
        g = jax.jit(jax.grad(f))(x)
    np.testing.assert_allclose(np.asarray(g), np.asarray(8.0 * x))


# ------------------------------------------------------------------ #
# Roofline HLO parsing
# ------------------------------------------------------------------ #
SYNTH_HLO = """
HloModule jit_step

%wide.body (p: (s32[], f32[16,512])) -> (s32[], f32[16,512]) {
  %p = (s32[], f32[16,512]) parameter(0)
  %ar = f32[16,512]{1,0} all-reduce(%gte), channel_id=1, replica_groups=[4,16]<=[64], to_apply=%add
  ROOT %t = (s32[], f32[16,512]) tuple(%c, %ar)
}

%wide.cond (p: (s32[], f32[16,512])) -> pred[] {
  %p = (s32[], f32[16,512]) parameter(0)
  ROOT %cmp = pred[] compare(%a, %b), direction=LT
}

ENTRY %main (x: f32[16,512]) -> f32[16,512] {
  %x = f32[16,512] parameter(0)
  %ag = f32[64,512]{1,0} all-gather(%x), channel_id=2, replica_groups=[16,4]<=[64], dimensions={0}
  %w = (s32[], f32[16,512]) while(%init), condition=%wide.cond, body=%wide.body, backend_config={"known_trip_count":{"n":"28"}}
  %rs = f32[4,512]{1,0} reduce-scatter(%ag2), channel_id=3, replica_groups=[16,4]<=[64], dimensions={0}
  %cp = f32[16,512]{1,0} collective-permute(%x), channel_id=4, source_target_pairs={{0,1}}
  ROOT %out = f32[16,512] add(%a, %b)
}
"""


def test_parse_collectives_kinds_groups_trips():
    ops = A.parse_collectives(SYNTH_HLO)
    by_kind = {o.kind: o for o in ops}
    ar = by_kind["all-reduce"]
    assert ar.group_size == 16
    assert ar.multiplier == 28           # inside the while body
    assert ar.result_bytes == 16 * 512 * 4
    ag = by_kind["all-gather"]
    assert ag.group_size == 4 and ag.multiplier == 1
    rs = by_kind["reduce-scatter"]
    assert rs.result_bytes == 4 * 512 * 4
    cp = by_kind["collective-permute"]
    assert cp.wire_bytes == 16 * 512 * 4


def test_ring_cost_model():
    op = A.CollectiveOp("all-reduce", result_bytes=1000, group_size=4,
                        computation="x")
    assert op.wire_bytes == 2 * 1000 * 3 / 4
    op = A.CollectiveOp("all-gather", result_bytes=1000, group_size=4,
                        computation="x")
    assert op.wire_bytes == 1000 * 3 / 4
    op = A.CollectiveOp("reduce-scatter", result_bytes=250, group_size=4,
                        computation="x")
    assert op.wire_bytes == 250 * 3
    op = A.CollectiveOp("all-reduce", result_bytes=1000, group_size=1,
                        computation="x")
    assert op.wire_bytes == 0.0


def test_collective_parser_on_real_module():
    """Compile a sharded matmul+psum step (in a 2-device subprocess — the
    test env itself sees 1 device) and check the parser finds the
    all-reduce."""
    import subprocess
    import sys

    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.roofline import analysis as A
from repro.runtime import compat
mesh = compat.make_mesh((1, 2), ("data", "model"))
def f(x, w):
    return jnp.sum((x @ w).astype(jnp.float32))
with jax.set_mesh(mesh):
    c = jax.jit(f,
        in_shardings=(NamedSharding(mesh, P(None, None)),
                      NamedSharding(mesh, P(None, "model"))),
        out_shardings=NamedSharding(mesh, P())).lower(
        jax.ShapeDtypeStruct((8, 16), jnp.float32),
        jax.ShapeDtypeStruct((16, 32), jnp.float32)).compile()
ops = A.parse_collectives(c.as_text())
ars = [o for o in ops if o.kind == "all-reduce"]
assert ars, "expected an all-reduce"
assert all(o.group_size == 2 for o in ars)
print("OK")
"""
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env={**__import__("os").environ,
                                          "PYTHONPATH": "src"})
    assert "OK" in out.stdout, out.stderr[-2000:]


def test_structural_costs_count_dot_flops():
    mesh = _mesh((1, 1))
    from jax.sharding import NamedSharding

    M, N, K = 64, 128, 32

    def f(x, w):
        return x @ w

    with jax.set_mesh(mesh):
        c = jax.jit(f).lower(
            jax.ShapeDtypeStruct((M, N), jnp.float32),
            jax.ShapeDtypeStruct((N, K), jnp.float32)).compile()
        flops, byts = A.structural_costs(c.as_text())
    assert abs(flops - 2 * M * N * K) / (2 * M * N * K) < 0.05
    io = 4 * (M * N + N * K + M * K)
    assert byts >= io  # at least the operand+result traffic


def test_structural_costs_scan_trip_multiplier():
    """A scanned matmul must count layers x body flops."""
    mesh = _mesh((1, 1))
    L, D = 7, 32

    def f(ws, x):
        def body(h, w):
            return jnp.tanh(h @ w), 0
        h, _ = jax.lax.scan(body, x, ws)
        return h

    with jax.set_mesh(mesh):
        c = jax.jit(f).lower(
            jax.ShapeDtypeStruct((L, D, D), jnp.float32),
            jax.ShapeDtypeStruct((D, D), jnp.float32)).compile()
        flops, _ = A.structural_costs(c.as_text())
    expect = L * 2 * D * D * D
    assert abs(flops - expect) / expect < 0.1


def test_model_flops_conventions():
    from repro import configs
    from repro.roofline.analysis import model_flops

    cfg = configs.get("yi-9b")
    tr = model_flops(cfg, configs.SHAPES["train_4k"])
    pf = model_flops(cfg, configs.SHAPES["prefill_32k"])
    dc = model_flops(cfg, configs.SHAPES["decode_32k"])
    n = cfg.param_count() - cfg.vocab_size * cfg.d_model
    assert tr == pytest.approx(6.0 * n * 256 * 4096)
    assert pf == pytest.approx(2.0 * n * 32 * 32768)
    assert dc == pytest.approx(2.0 * n * 128)
    # MoE uses active params only
    ds = configs.get("deepseek-v2-lite-16b")
    assert ds.active_param_count() < 0.4 * ds.param_count()
