"""Mixed-precision (per-operand FP8) numerics — the PR-5 tentpole's tests.

* quantize→dequantize round-trip error bounded per format (E4M3 ε=2⁻³,
  E5M2 ε=2⁻²) — the unit-max scaling keeps the FP16 datapath
  overflow-free without touching the formats' relative precision;
* interpret-vs-xla gradients under the FP8 policies agree to the
  compute-dtype tolerance on kink-free sweeps (the engine quantizes once,
  so the FP8 rounding is backend-invariant by construction);
* per-tensor scale robustness in optim/scale.py: overflowed amax
  observations are dropped (never poison the scale), all-zero windows
  keep the previous scale (never collapse it);
* pipeline-depth ∈ {1, 2, 3} kernel equivalence under FP8 storage;
* Policy/GemmSpec dtype validation fails at construction with a message
  naming the offending field and the known-policy registry;
* the byte-accounting acceptance: an FP8 AE train trace carries strictly
  fewer engine bytes than the FP16 one at identical engine flops.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine
from repro.core import precision as prec
from repro.kernels import ops
from repro.optim import scale as oscale

RNG = np.random.default_rng(3)

FP8_POLICIES = [prec.MIXED_FP8_E4M3, prec.MIXED_FP8_E5M2]

# round-trip relative error bound: one rounding step at the format's
# machine epsilon (ε/2 for round-to-nearest; ε is the loose bound we pin)
_EPS = {"float8_e4m3fn": 2.0 ** -3, "float8_e5m2": 2.0 ** -2}


def _rand(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.normal(size=shape) * scale, dtype)


# ------------------------------------------------------------------ #
# quantize / dequantize round trips
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("fmt", prec.FP8_FORMATS)
def test_fp8_roundtrip_error_bound(fmt):
    eps = _EPS[fmt]
    v = _rand((64, 64), jnp.float32, 2.5)
    q, s = prec.quantize_fp8(v, fmt)
    assert q.dtype == jnp.dtype(fmt)
    back = np.asarray(prec.dequantize_fp8(q, s), np.float32)
    vf = np.asarray(v, np.float32)
    # values within the format's normal window below the tensor amax
    # round-trip with relative error <= eps; tinier values hit the
    # subnormal floor (absolute error <= eps * 2^-6 * s)
    amax = np.abs(vf).max()
    normal = np.abs(vf) >= amax * 2.0 ** -6
    rel = np.abs(back - vf) / np.maximum(np.abs(vf), 1e-30)
    assert rel[normal].max() <= eps, (
        f"{fmt} round-trip relative error {rel[normal].max():.4g} > {eps}")
    np.testing.assert_allclose(back, vf, atol=float(amax) * eps,
                               rtol=eps)


@pytest.mark.parametrize("fmt", prec.FP8_FORMATS)
def test_fp8_quantized_values_unit_max(fmt):
    """Unit-max scaling: |q| <= 1, so FP16 products cannot overflow."""
    v = _rand((32, 32), jnp.float32, 123.0)
    q, s = prec.quantize_fp8(v, fmt)
    assert float(jnp.max(jnp.abs(q.astype(jnp.float32)))) <= 1.0
    assert float(s) == pytest.approx(float(jnp.max(jnp.abs(v))), rel=1e-6)


def test_fp8_quantize_degenerate_tensors():
    zq, zs = prec.quantize_fp8(jnp.zeros((4, 4)), "float8_e4m3fn")
    assert float(zs) == 1.0 and not np.any(np.asarray(zq, np.float32))
    bad = jnp.full((4, 4), np.inf, jnp.float32)
    _, bs = prec.quantize_fp8(bad, "float8_e5m2")
    assert float(bs) == 1.0  # non-finite amax falls back to s=1
    with pytest.raises(ValueError, match="quantize_fp8 target"):
        prec.quantize_fp8(jnp.zeros(3), jnp.float16)


# ------------------------------------------------------------------ #
# interpret-vs-xla grads under the FP8 policies (kink-free sweeps)
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("policy", FP8_POLICIES, ids=lambda p: p.name)
@pytest.mark.parametrize("act", [None, "tanh", "gelu"])
def test_fp8_linear_grads_interpret_vs_xla(policy, act):
    x = _rand((9, 33), jnp.float32, 0.3)
    w = _rand((33, 12), jnp.float32, 0.3)
    b = _rand((12,), jnp.float32, 0.1)

    def loss(p, backend):
        z = engine.linear(p["x"], p["w"], p["b"], activation=act,
                          policy=policy, backend=backend)
        return jnp.sum(z.astype(jnp.float32) ** 2)

    p = {"x": x, "w": w, "b": b}
    gi = jax.grad(lambda q: loss(q, "interpret"))(p)
    gx = jax.grad(lambda q: loss(q, "xla"))(p)
    # the engine quantizes once (backend-invariant FP8 rounding), so the
    # cross-backend gap is only the fp16 accumulation-order difference
    jax.tree.map(
        lambda a, bb: np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(bb, np.float32),
            rtol=2e-2, atol=2e-2), gi, gx)


@pytest.mark.parametrize("policy", FP8_POLICIES, ids=lambda p: p.name)
def test_fp8_matmul_grads_close_to_f32_reference(policy):
    """FP8 grads track the FP32 reference within the quantization bound:
    one E5M2 rounding of the cotangent (ε=2⁻²) plus operand roundings."""
    x = _rand((8, 16), jnp.float32, 0.5)
    w = _rand((16, 8), jnp.float32, 0.5)

    g8 = jax.grad(lambda q: jnp.sum(engine.matmul(
        q, w, policy=policy, backend="interpret").astype(jnp.float32) ** 2))(x)
    gr = jax.grad(lambda q: jnp.sum((q @ w) ** 2))(x)
    ref = np.asarray(gr, np.float32)
    bound = 0.5 * max(1.0, float(np.abs(ref).max()))
    np.testing.assert_allclose(np.asarray(g8, np.float32), ref, atol=bound)
    assert g8.dtype == x.dtype


def test_fp8_events_carry_per_operand_dtypes_and_scaled_flag():
    x = _rand((8, 16), jnp.float32)
    w = _rand((16, 8), jnp.float32)
    b = _rand((8,), jnp.float32)
    with engine.instrument() as ev:
        jax.eval_shape(lambda xx, ww, bb: jax.value_and_grad(
            lambda q: jnp.sum(engine.linear(
                xx, q, bb, policy=prec.MIXED_FP8_E4M3,
                backend="interpret").astype(jnp.float32) ** 2))(ww),
            x, w, b)
    by_op = {e.spec.op: e.spec for e in ev}
    fwd = by_op["linear"]
    assert fwd.x_dtype == "float8_e4m3fn" and fwd.w_dtype == "float8_e4m3fn"
    assert fwd.scaled
    # backward: dZ rides in the grad storage (E5M2) — the x slot on dX,
    # the w slot on dW; the residual slots keep the forward storage
    assert by_op["matmul_dx"].x_dtype == "float8_e5m2"
    assert by_op["matmul_dx"].w_dtype == "float8_e4m3fn"
    assert by_op["matmul_dw"].x_dtype == "float8_e4m3fn"
    assert by_op["matmul_dw"].w_dtype == "float8_e5m2"
    # scaled specs take the two-pass backward: the bias grad is its own
    # pass event, reduced from the wide cotangent
    assert "linear_dbias" in by_op


def test_fp8_bytes_drop_flops_dont_on_ae_train():
    """The acceptance criterion: the FP8 AE train trace carries strictly
    fewer engine bytes than the FP16 one at identical engine flops."""
    from repro.data import SyntheticAE
    from repro.models import autoencoder

    params = autoencoder.init_ae(jax.random.PRNGKey(0))
    x = jnp.asarray(SyntheticAE(batch=16).sample(0))

    def trace(policy):
        with engine.instrument() as events:
            jax.eval_shape(lambda p: jax.value_and_grad(
                lambda q: autoencoder.ae_loss(
                    q, x, policy=policy, backend="interpret")[0])(p), params)
        return events

    ev8, ev16 = trace(prec.MIXED_FP8_E4M3), trace(prec.PAPER_FP16)
    assert engine.total_flops(ev8) == engine.total_flops(ev16)
    assert engine.total_bytes(ev8) < engine.total_bytes(ev16)


def test_fp8_postep_pass_classifies_like_its_gemm_under_remat():
    """The forced post-op pass event rides through the same remat
    classification as the GEMM it accompanies: one primal + one
    recompute-tagged emission per checkpoint region, no partial-eval
    phantoms — so FP8 byte totals stay honest under jax.checkpoint."""
    x = _rand((8, 16), jnp.float32, 0.3)
    w = _rand((16, 8), jnp.float32, 0.3)
    b = _rand((8,), jnp.float32, 0.1)

    def f(q):
        h = jax.checkpoint(lambda ww: engine.linear(
            x, ww, b, activation="gelu", policy=prec.MIXED_FP8_E4M3,
            backend="interpret"))(q)
        return jnp.sum(h.astype(jnp.float32) ** 2)

    with engine.instrument() as ev:
        jax.eval_shape(lambda q: jax.value_and_grad(f)(q), w)
    postep = [e for e in ev if e.spec.op == "linear_postep"]
    assert [(e.count, e.recompute) for e in postep] == \
        [(1, False), (1, True)]
    gemm = [e for e in ev if e.spec.op == "linear"]
    assert [(e.count, e.recompute) for e in gemm] == \
        [(e.count, e.recompute) for e in postep]


# ------------------------------------------------------------------ #
# optim/scale.py: FP8 per-tensor delayed scaling robustness
# ------------------------------------------------------------------ #
def test_fp8_scale_tracks_amax_window():
    st = oscale.init_fp8_scale(history_len=4)
    for amax in (1.0, 4.0, 2.0):
        st = oscale.update_fp8_scale(st, jnp.float32(amax))
    assert float(st.scale) == 4.0            # window max
    # 4.0 rolls out of the window after 4 more observations
    for _ in range(4):
        st = oscale.update_fp8_scale(st, jnp.float32(0.5))
    assert float(st.scale) == 0.5
    assert int(st.overflow_count) == 0


def test_fp8_scale_overflow_observation_is_dropped():
    st = oscale.init_fp8_scale(history_len=4)
    st = oscale.update_fp8_scale(st, jnp.float32(2.0))
    before = float(st.scale)
    for bad in (np.inf, np.nan, -1.0):
        st = oscale.update_fp8_scale(st, jnp.float32(bad))
        assert np.isfinite(float(st.scale))
        assert float(st.scale) == before, (
            "an overflowed amax observation must not poison the scale")
    assert int(st.overflow_count) == 3


def test_fp8_scale_underflow_keeps_previous_scale():
    st = oscale.init_fp8_scale(history_len=2)
    st = oscale.update_fp8_scale(st, jnp.float32(8.0))
    # a run of all-zero grads longer than the window
    for _ in range(5):
        st = oscale.update_fp8_scale(st, jnp.float32(0.0))
    assert float(st.scale) == 8.0, (
        "an all-zero window must keep the previous scale, not collapse it")
    st = oscale.observe_amax(st, jnp.zeros((3, 3)))
    assert float(oscale.fp8_scale_of(st)) == 8.0


def test_fp8_scale_margin_headroom():
    st = oscale.init_fp8_scale(history_len=2)
    st = oscale.update_fp8_scale(st, jnp.float32(2.0), margin=1.5)
    assert float(st.scale) == 3.0
    # works inside jit (all state traced); margin is per-update, so the
    # default-margin refresh re-derives scale = window max = 2.0
    st2 = jax.jit(oscale.update_fp8_scale)(st, jnp.float32(1.0))
    assert float(st2.scale) == pytest.approx(2.0)


# ------------------------------------------------------------------ #
# pipeline-depth equivalence under FP8 storage
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("layout", ["nn", "nt", "tn"])
def test_pipeline_depth_equivalence_under_fp8(layout):
    pol = prec.MIXED_FP8_E4M3
    M, N, K = 24, 33, 17
    shapes = {"nn": ((M, N), (N, K)), "nt": ((M, N), (K, N)),
              "tn": ((N, M), (N, K))}
    xs, ws = shapes[layout]
    x = _rand(xs, jnp.float8_e4m3fn, 0.3)
    w = _rand(ws, jnp.float8_e4m3fn, 0.3)
    outs = [np.asarray(ops.redmule_matmul(
        x, w, policy=pol, layout=layout, pipeline_depth=d,
        interpret=True), np.float32) for d in (1, 2, 3)]
    for o in outs[1:]:
        np.testing.assert_array_equal(outs[0], o)


# ------------------------------------------------------------------ #
# construction-time dtype validation (Policy and GemmSpec)
# ------------------------------------------------------------------ #
def test_policy_validates_dtypes_at_construction():
    with pytest.raises(ValueError) as e:
        prec.Policy(name="typo", compute_dtype="floatt16",
                    accum_dtype=jnp.float32)
    msg = str(e.value)
    assert "Policy.compute_dtype" in msg and "floatt16" in msg
    assert "mixed_fp8_e4m3" in msg  # names the known-policy registry
    with pytest.raises(ValueError, match="Policy.grad_dtype"):
        prec.Policy(name="typo", compute_dtype=jnp.float16,
                    accum_dtype=jnp.float32, grad_dtype="fp8_e5m2")
    with pytest.raises(ValueError, match="Policy.accum_dtype"):
        prec.Policy(name="typo", compute_dtype=jnp.float16,
                    accum_dtype=jnp.int32)  # not a floating dtype


def test_gemmspec_validates_dtypes_and_enums_at_construction():
    with pytest.raises(ValueError) as e:
        engine.GemmSpec(op="matmul", tag="t", m=8, n=8, k=8,
                        x_dtype="float8_e4m3fnuz_typo")
    msg = str(e.value)
    assert "GemmSpec.x_dtype" in msg and "known precision policies" in msg
    with pytest.raises(ValueError, match="GemmSpec.layout"):
        engine.GemmSpec(op="matmul", tag="t", m=8, n=8, k=8, layout="tt")
    with pytest.raises(ValueError, match="GemmSpec.ragged_dim"):
        engine.GemmSpec(op="matmul", tag="t", m=8, n=8, k=8, ragged_dim="b")


def test_resolve_rejects_unknown_policy_naming_registry():
    with pytest.raises(ValueError) as e:
        prec.resolve("mixed_fp9")
    assert "mixed_fp8_e4m3" in str(e.value)


def test_fp8_policy_properties():
    p = prec.MIXED_FP8_E4M3
    assert p.mixed_storage and p.scaled
    assert jnp.dtype(p.x_storage_dtype) == jnp.dtype(jnp.float8_e4m3fn)
    assert jnp.dtype(p.grad_storage_dtype) == jnp.dtype(jnp.float8_e5m2)
    assert not prec.PAPER_FP16.mixed_storage
    assert not prec.PAPER_FP16.scaled
    # the grad policy replace() used by the engine keeps validity
    g = dataclasses.replace(p, name="g", output_dtype=p.accum_dtype)
    assert g.scaled
