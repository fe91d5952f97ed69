"""The hybrid serving cell, on small stand-ins.

Each serving test drives ``run.run_cell`` as the benchmark's command would,
on the CPU with the tiny Granite-4.0-H configuration (``tests/data``), and
breaks the served path where a fault says; ``correct`` has to come out
false for each and true for the sound program.  The cell's per-layer
readers read small made-up traces whose answers are known.
"""

import argparse
import importlib.util
import json
import types
from pathlib import Path

import jax
import numpy as np
import pytest

from chipbench import run as R
from chipbench import trace as tr

DATA = Path(__file__).resolve().parent / "data"
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
BENCH = {
    "configs": [{"name": "tiny-granite", "file": "configs/tiny-granite.json"}],
    "workloads": [
        {"name": "tiny-granite-chat", "config": "tiny-granite",
         "traffic": "tiny-chat-hybrid", "chips": 1}],
    "end_to_end": [
        {"name": "ttft_p95_s", "unit": "s", "workloads": ["tiny-granite-chat"]},
        {"name": "itl_p95_s", "unit": "s", "workloads": ["tiny-granite-chat"]},
        {"name": "setup_s", "unit": "s"}],
    "per_layer": [],
}


def run(cell, seed=2**31 + 3, seconds=1.0):
    args = argparse.Namespace(workload=cell, seed=seed, seconds=seconds,
                              trace=0)
    out = R.run_cell(BENCH, cell, args, root=DATA, data=DATA,
                     devices=jax.devices(), peaks=PEAKS)
    json.dumps(out)  # the result line must serialize
    return out


def reader(name):
    path = R.HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"m_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# --------------------------------------------------------------------- #
# Hybrid serving
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("seed", [2**31 + 3, 2**33 + 17])
def test_sound_hybrid_serving_is_correct(seed):
    out = run("tiny-granite-chat", seed=seed)
    assert out["correct"], out["checks"]
    assert set(out["checks"]) == {"token_gap_mean", "final_logit_err"}
    assert out["attempted"] == 20 and out["failed"] == 0
    assert set(out["metrics"]) == {"ttft_p95_s", "itl_p95_s", "setup_s"}


def test_hybrid_altered_token_fails(monkeypatch):
    """A served token altered where the scheduler produces it."""
    from repro.serving import scheduler as S

    calls = {"n": 0}

    class AlteringNumpy:
        def __getattr__(self, name):
            return getattr(np, name)

        @staticmethod
        def argmax(x, *a, **k):
            calls["n"] += 1
            i = np.argmax(x, *a, **k)
            return (i + 1) % x.shape[-1] if calls["n"] % 7 == 0 else i

    monkeypatch.setattr(S, "np", AlteringNumpy())
    out = run("tiny-granite-chat")
    assert calls["n"] > 7
    assert not out["correct"], out["checks"]


def test_hybrid_unchanged_state_fails(monkeypatch):
    """Decode steps that leave the Mamba2 state as it was."""
    from repro.models import ssm

    step = ssm.mamba_step

    def stuck(state, *a, **k):
        return step(state, *a, **k)[0], state

    monkeypatch.setattr(ssm, "mamba_step", stuck)
    out = run("tiny-granite-chat")
    assert not out["correct"], out["checks"]
    c = out["checks"]["final_logit_err"]
    assert c["value"] > c["limit"]


def test_hybrid_control_is_not_correct():
    """The plain reference at E4M3 operands, put in the program's place,
    fails the cell's limits that the sound program keeps under: through
    ``checks``, as a run would decide it."""
    from chipbench.drivers import serve_hybrid as SH

    conf = R.load_json(DATA / "configs" / "tiny-granite.json")
    lim = R.load_json(DATA / "limits" / "tiny-granite-chat.json")
    ctx = types.SimpleNamespace(
        args=argparse.Namespace(seed=2**31 + 3, seconds=1.0, trace=0),
        conf=conf, mix=R.load_json(DATA / "traffic" / "tiny-chat-hybrid.json"),
        limits=lim, devices=jax.devices(), t_start=0.0,
        tracer=R.Tracer(False))
    c = SH.run_once(ctx, control=True)["compare"]
    assert not c["control_correct"], c["control_checks"]
    assert set(c["control_checks"]) == set(lim)
    for k, v in lim.items():
        assert c[k] <= v["limit"]
    err = c["control_checks"]["final_logit_err"]
    assert err["value"] > err["limit"]
    assert 0.0 <= c["last_token_share"] <= 1.0


def test_hybrid_config_maps_onto_the_program():
    """The cell's configuration file, read as the driver reads it, gives
    the registry's granite-4.0-h-micro at published widths."""
    import dataclasses

    from chipbench.drivers import serve_hybrid as SH
    from repro import configs

    c = R.load_json(R.HERE / "configs" / "hybrid" /
                    "granite4h-micro-serve.json")
    got = SH.program_config(c)
    want = configs.get("granite-4.0-h-micro")
    keep = ("name", "notes", "param_dtype", "q_chunk")
    assert dataclasses.replace(got, **{k: getattr(want, k) for k in keep}) \
        == want
    assert got.param_dtype == "bfloat16"
    assert c["reduced"] == {} and c["serving"]["slots"] == 48


def test_knee_sweep_reports_each_rate(tmp_path):
    """The hybrid's knee sweep on the tiny stand-in: one line per rate with
    the backlog and the timed spans, then the knee and four fifths of it;
    the scheduler's own span is put back afterwards."""
    from chipbench import knee_hybrid as K
    from repro.serving import scheduler as S

    real = S._span
    args = argparse.Namespace(workload="tiny-granite-chat", rates="5,10",
                              seed=2**31 + 3, seconds=1.0, drain=5.0,
                              write=False)
    with open(tmp_path / "k.jsonl", "w") as out:
        K.sweep(args, out, bench=BENCH, root=DATA, data=DATA,
                devices=jax.devices(), peaks=PEAKS)
    assert S._span is real
    recs = [json.loads(x) for x in (tmp_path / "k.jsonl").read_text()
            .splitlines()]
    rates = [r for r in recs if "rate" in r]
    assert [r["rate"] for r in rates] == [5.0, 10.0]
    for r in rates:
        assert r["n"] == int(r["rate"])
        assert r["span_ms_median"]["serve.decode.fetch"] > 0
        assert r["held"] == K.held(r)
    last = recs[-1]
    if last["knee_per_s"] is not None:
        assert last["rate_per_s"] == round(0.8 * last["knee_per_s"], 2)


# --------------------------------------------------------------------- #
# The readers, on made-up traces
# --------------------------------------------------------------------- #
def _trace(modules, ops=(), host=()):
    dev = tr.Device(modules=sorted(modules, key=lambda s: s.start),
                    ops=sorted(ops, key=lambda s: (s.start, -s.end)))
    dev.op_starts = [o.start for o in dev.ops]
    return tr.Trace(devices={0: dev}, host=list(host), window=(0.0, 100.0),
                    offset=0.0)


def _hybrid_ctx(trace, record):
    conf = R.load_json(R.HERE / "configs" / "hybrid" /
                       "granite4h-micro-serve.json")
    return types.SimpleNamespace(trace=trace, run=record, conf=conf,
                                 peaks={"bf16_flops_per_s": 197e12,
                                        "hbm_bytes_per_s": 819e9}, chips=1)


def test_hybrid_readers_stay_under_their_roofline():
    """Decode at exactly the least time reads 100%; prefill and the SSD
    kernel at twice theirs read 50%."""
    from chipbench import counts_hybrid as ch
    from chipbench.reference.granite_hybrid import Dims

    S = tr.Span
    conf = _hybrid_ctx(None, None).conf
    d, pk = Dims.of(conf), {"f": 197e12, "b": 819e9}
    kv = [[600, 900], [601, 901, 130]]
    least = [max(ch.decode_flops(d, k) / pk["f"],
                 ch.decode_bytes(d, k, 1) / pk["b"]) for k in kv]
    lens = [512, 3072]
    pre = [ch.prefill_flops(d, n, 256) / pk["f"] for n in lens]
    ssd = [max(ch.ssd_kernel_flops(d, n, 256) / pk["f"],
               ch.ssd_kernel_bytes(d, n, 256) / pk["b"]) for n in lens]
    mods = [S("jit__decode", 1.0, 1.0 + least[0]),
            S("jit__decode", 2.0, 2.0 + least[1]),
            S("jit_pre", 3.0, 3.0 + 2 * pre[0]),
            S("jit_pre", 4.0, 4.0 + 2 * pre[1])]
    ops = [S("redmule_chunked_linear_attention.1", 3.0, 3.0 + 2 * ssd[0]),
           S("redmule_chunked_linear_attention.2", 4.0, 4.0 + 2 * ssd[1])]
    ctx = _hybrid_ctx(_trace(mods, ops), {"decode_kv": kv,
                                          "prefill_lens": lens,
                                          "kv_bytes": 1})
    assert reader("mfu.decode.hybrid").read(ctx) == pytest.approx(100.0)
    assert reader("mfu.prefill.hybrid").read(ctx) == pytest.approx(50.0)
    assert reader("ssd_roofline.prefill").read(ctx) == pytest.approx(50.0)
    # the dense cell's scheduler reader reads the hybrid too: the device
    # idles from the first decode's end to the second's start
    assert reader("host_gap_ms.decode").read(ctx) == pytest.approx(
        1e3 * (1.0 - least[0]))
    # the weight products at exactly their least time in the kernels: 100%
    wt = 2.0 * ch.matmul_params(d) / pk["b"]
    kops = [S("redmule_matmul_nn.1", 1.0, 1.0 + wt / 2),
            S("redmule_matmul_nn.2", 2.0, 2.0 + wt / 2),
            S("redmule_matmul_nn.3", 2.0 + wt / 2, 2.0 + wt)]
    mods2 = [S("jit__decode", 1.0, 1.0 + wt), S("jit__decode", 2.0, 2.0 + wt)]
    ctx2 = _hybrid_ctx(_trace(mods2, kops[:1] + [
        S("redmule_matmul_nn.4", 1.0 + wt / 2, 1.0 + wt)] + kops[1:]),
        {"decode_kv": kv, "kv_bytes": 1})
    assert reader("matmul_roofline.decode.hybrid").read(ctx2) == \
        pytest.approx(100.0)
    # over 48 full slots the Mamba state is most of a decode step's bytes
    full = [700] * 48
    assert 2 * ch.state_bytes(d) * 48 > 0.5 * ch.decode_bytes(d, full, 1)


def test_hybrid_readers_find_nothing_on_a_dense_run():
    conf = R.load_json(R.HERE / "configs" / "yi9b-serve-8l.json")
    ctx = types.SimpleNamespace(trace=_trace([]), run={}, conf=conf,
                                peaks={}, chips=1)
    for name in ("mfu.decode.hybrid", "mfu.prefill.hybrid",
                 "ssd_roofline.prefill", "matmul_roofline.decode.hybrid"):
        assert reader(name).read(ctx) is None
