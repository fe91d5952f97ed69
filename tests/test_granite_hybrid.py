"""granite-4.0-h-micro's reduced preset: Mamba2 layers beside NoPE GQA layers.

The Mamba2 mixer's chunked prefill and step decode against a plain
sequential scan; the scheduler's slots over a cache that holds SSM state,
conv window and KV rows side by side (a reused slot starts from its
prefill's state, bit-identical recovery); every RMSNorm reading
``cfg.norm_eps``; the named scopes and span arguments the tracing reads.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import engine
from repro.core import precision as prec
from repro.models import layers, ssm, transformer
from repro.models.layers import init_tree
from repro.runtime.fault_tolerance import FailureInjector
from repro.serving import Request, Scheduler, SchedulerConfig
from repro.serving import scheduler as S

ARCH = "granite-4.0-h-micro"


@pytest.fixture(scope="module")
def granite():
    cfg = configs.get_reduced(ARCH)
    return cfg, transformer.init_params(jax.random.PRNGKey(0), cfg)


# --------------------------------------------------------------------- #
# The mixer against a sequential scan
# --------------------------------------------------------------------- #
def _sequential_mamba(p, x, cfg):
    """The Mamba2 mixer one token at a time, in float64 numpy."""
    di, H, P, G, N = ssm.mamba_dims(cfg)
    K = cfg.ssm.d_conv
    p = {k: np.asarray(v, np.float64) for k, v in p.items()}
    x = np.asarray(x[0], np.float64)
    xz = x @ p["w_xz"]
    xs, z = xz[:, :di], xz[:, di:]
    bcdt = x @ p["w_bcdt"]
    xbc = np.concatenate([xs, bcdt[:, :2 * G * N]], 1)
    full = np.concatenate([np.zeros((K - 1, xbc.shape[1])), xbc])
    conv = sum(full[j:j + len(x)] * p["conv_w"][j] for j in range(K))
    xbc = conv + p["conv_b"]
    xbc = xbc / (1 + np.exp(-xbc))
    xs, bm, cm = xbc[:, :di], xbc[:, di:di + G * N], xbc[:, di + G * N:]
    dt = np.log1p(np.exp(bcdt[:, 2 * G * N:] + p["dt_bias"]))
    a = -np.exp(p["a_log"])
    grp = np.arange(H) // (H // G)
    h = np.zeros((H, P, N))
    ys = []
    for t in range(len(x)):
        b_t = bm[t].reshape(G, N)[grp]
        c_t = cm[t].reshape(G, N)[grp]
        x_t = xs[t].reshape(H, P)
        h = (np.exp(dt[t] * a)[:, None, None] * h
             + (dt[t][:, None] * x_t)[:, :, None] * b_t[:, None, :])
        ys.append(np.einsum("hpn,hn->hp", h, c_t) + p["skip_d"][:, None] * x_t)
    y = np.stack(ys).reshape(len(x), di) * (z / (1 + np.exp(-z)))
    y = y / np.sqrt(np.mean(y * y, -1, keepdims=True) + cfg.norm_eps)
    return (y * p["norm"]) @ p["w_out"]


@pytest.mark.parametrize("backend", ["xla", "interpret"])
@pytest.mark.parametrize("length", [1, 15, 16, 17])
def test_mamba_prefill_then_decode_matches_sequential_scan(backend, length):
    """Chunked prefill of ``length`` tokens (chunk 16) from zero, then three
    decode steps carrying the SSM state and conv window from it: every
    output against the sequential scan.  float32 against float64, the
    chunked form summing in another order: 1e-4 of the output's scale."""
    cfg = dataclasses.replace(configs.get_reduced(ARCH), policy_name="fp32")
    schema = ssm.mamba_schema(cfg)
    p = init_tree(jax.random.PRNGKey(3), schema)
    p = dict(p, a_log=jnp.log(jnp.arange(1.0, 5.0)),
             dt_bias=jnp.full((4,), -2.0), conv_b=0.1 * p["conv_w"][0])
    x = jax.random.normal(jax.random.PRNGKey(4), (1, length + 3, cfg.d_model))
    with engine.use_backend(backend):
        y0, st = ssm.mamba_mixer(p, x[:, :length], cfg, policy=prec.FP32,
                                 carry=False)
        ys = [y0]
        for t in range(length, length + 3):
            y, st = ssm.mamba_mixer(p, x[:, t:t + 1], cfg, policy=prec.FP32,
                                    state=st)
            ys.append(y)
    got = np.asarray(jnp.concatenate(ys, axis=1)[0])
    want = _sequential_mamba(p, x, cfg)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-4 * np.abs(want).max())
    assert st["ssm"].shape == (1, 4, 32, 16) and st["conv"].shape == (1, 3, 160)


# --------------------------------------------------------------------- #
# Serving: parked and reused slots, recovery
# --------------------------------------------------------------------- #
def _requests(cfg, n, gen=6, seed=11):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, arrival=0.0,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        size=4 + 3 * i).astype(np.int32),
                    max_new_tokens=gen + 4 * i) for i in range(n)]


def _serve(params, cfg, scfg, reqs, **kw):
    sched = Scheduler(params, cfg, scfg, **kw)
    sched.submit(reqs)
    return sched, {r.rid: r for r in sched.run()}


def test_reused_slot_serves_as_a_fresh_one(granite):
    """One slot, two requests in turn: the second runs in the slot the first
    left, and its tokens and final logits equal a fresh scheduler's."""
    cfg, params = granite
    scfg = SchedulerConfig(n_slots=1, max_len=32)
    reqs = _requests(cfg, 2)
    _, both = _serve(params, cfg, scfg, reqs)
    _, alone = _serve(params, cfg, scfg,
                      [dataclasses.replace(reqs[1], rid=1)])
    assert both[1].tokens == alone[1].tokens
    np.testing.assert_array_equal(both[1].final_logits, alone[1].final_logits)


def test_parked_slot_holds_zero_state(granite):
    """A slot parked while another decodes keeps stepping its Mamba state
    (it is not zeroed); the request admitted into it later starts from its
    own prefill's state all the same, since admission overwrites the slot's
    state and conv window whole: its tokens and final logits equal a fresh
    scheduler's."""
    cfg, params = granite
    scfg = SchedulerConfig(n_slots=2, max_len=64)
    reqs = _requests(cfg, 2)
    late = dataclasses.replace(reqs[0], rid=7, arrival=12.0,
                               max_new_tokens=5)
    sched = Scheduler(params, cfg, scfg)
    sched.submit(reqs + [late])
    parked_steps, stale = 0, False
    while sched.step():
        if sched.slots[0] is None and sched.slots[1] is not None:
            parked_steps += 1
            stale |= bool(np.any(np.asarray(
                sched.cache["layers"]["mamba"]["ssm"])[:, :, 0]))
    assert parked_steps > 0 and stale
    assert [e[3] for e in sched.trace if e[0] == "finish" and e[2] == 7] \
        == [0]
    _, alone = _serve(params, cfg, scfg,
                      [dataclasses.replace(late, arrival=0.0)])
    got = sched.results[7]
    assert got.tokens == alone[7].tokens
    np.testing.assert_array_equal(got.final_logits, alone[7].final_logits)


@pytest.mark.parametrize("mode,detect", [("nan_logits", "nan_detect"),
                                         ("kv_corrupt", "kv_quarantine")])
def test_recovery_bit_identical(granite, mode, detect):
    """A fault at decode step 2: the victim's slot is rebuilt (prompt
    re-prefilled, its decode steps replayed batch-1, so SSM state, conv
    window and KV rows come back as the steps built them) and both
    requests' tokens and final logits equal the uninjected run's."""
    cfg, params = granite
    scfg = SchedulerConfig(n_slots=2, max_len=32, audit_every=1)
    reqs = _requests(cfg, 2)
    base, rb = _serve(params, cfg, scfg, reqs)
    inj, ri = _serve(params, cfg, scfg, reqs, injector=FailureInjector(
        fail_at_step=2, mode=mode, target=0))
    assert [e[0] for e in inj.trace if e[0] in (detect, "recover")] == \
        [detect, "recover"]
    for rid in (0, 1):
        assert ri[rid].status == "finished"
        assert rb[rid].tokens == ri[rid].tokens
        np.testing.assert_array_equal(rb[rid].final_logits,
                                      ri[rid].final_logits)


def test_fp8_cache_serves_the_hybrid(granite):
    """E4M3 rows on the attention layers, fp32 state on the Mamba2 ones."""
    cfg, params = granite
    sched, res = _serve(params, cfg, SchedulerConfig(
        n_slots=2, max_len=32, storage_dtype="float8_e4m3fn"),
        _requests(cfg, 3))
    assert all(r.status == "finished" for r in res.values())
    att = sched.cache["layers"]["attention"]
    assert att["k"].dtype == jnp.float8_e4m3fn and "k_scale" in att
    assert sched.cache["layers"]["mamba"]["ssm"].dtype == jnp.float32


# --------------------------------------------------------------------- #
# Configuration fields and tracing
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("arch,norms", [
    # per period: 3 per Mamba2 layer (ln1, ln2, the gated norm), 2 per
    # attention layer; the final norm
    (ARCH, 9 * 3 + 2 + 1),
    ("yi-9b", 2 + 1),                 # the scanned layer, the final norm
    ("hymba-1.5b", 5 + 1),            # four block norms, per-head SSD norm
])
def test_norm_eps_reaches_every_rmsnorm(monkeypatch, arch, norms):
    cfg = dataclasses.replace(configs.get_reduced(arch), norm_eps=1e-3)
    seen = []
    real = layers.rmsnorm

    def rec(x, scale, eps=1e-6):
        seen.append(eps)
        return real(x, scale, eps)

    monkeypatch.setattr(layers, "rmsnorm", rec)
    params = transformer.abstract_params(cfg)
    jax.eval_shape(lambda p: transformer.forward(
        p, cfg, {"inputs": jnp.zeros((1, 8), jnp.int32)}), params)
    assert seen == [1e-3] * norms


def test_decode_hlo_names_both_mixers(granite):
    cfg, params = granite
    sched = Scheduler(params, cfg, SchedulerConfig(n_slots=2, max_len=16))
    n = np.zeros((2,), np.int32)
    text = sched._decode.lower(params, sched.cache,
                               jnp.zeros((2, 1), jnp.int32), n, n).as_text(
        debug_info=True)
    assert "mamba_mixer" in text and "/attention/" in text


def test_spans_carry_the_recurrent_state(granite, monkeypatch):
    cfg, params = granite
    got = []

    class Rec:
        def __init__(self, name, **kw):
            got.append((name, kw))

        def __enter__(self):
            return self

        def __exit__(self, *a):
            return False

        def set_metadata(self, **kw):
            pass

    monkeypatch.setattr(S, "_span", Rec)
    sched, _ = _serve(params, cfg, SchedulerConfig(n_slots=2, max_len=32),
                      _requests(cfg, 2))
    per_slot = sched.slot_state_bytes
    di, H, P, G, N = ssm.mamba_dims(cfg)
    assert per_slot == 4 * 9 * (H * P * N + 3 * (di + 2 * G * N))
    dec = [kw for name, kw in got if name == S.SPAN_DECODE]
    assert dec and all(kw["ssm_slots"] == kw["active"]
                       and kw["state_bytes"] == kw["active"] * per_slot
                       for kw in dec)
    pre = [kw for name, kw in got if name == S.SPAN_PREFILL]
    assert len(pre) == 2 and all(kw["ssm_layers"] == 9 for kw in pre)
