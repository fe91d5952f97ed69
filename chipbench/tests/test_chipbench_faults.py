"""A whole run of a cell, past the look for a chip, on small stand-ins.

Each test drives ``run.run_cell`` as the benchmark's command would, on the
CPU with small configurations (``tests/data``), and breaks the timed path
underneath where a fault says: the served tokens, the train step's state,
its batch.  ``correct`` has to come out false for each; the sound program
has to come out true.
"""

import argparse
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from chipbench import run as R

DATA = Path(__file__).resolve().parent / "data"
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
BENCH = {
    "configs": [
        {"name": "tiny-serve", "file": "configs/tiny-serve.json"},
        {"name": "tiny-train", "file": "configs/tiny-train.json"},
    ],
    "workloads": [
        {"name": "tiny-chat", "config": "tiny-serve", "traffic": "tiny-chat",
         "chips": 1},
        {"name": "tiny-train", "config": "tiny-train",
         "traffic": "tiny-train", "chips": 1},
    ],
    "end_to_end": [
        {"name": "ttft_p95_s", "unit": "s", "workloads": ["tiny-chat"]},
        {"name": "itl_p95_s", "unit": "s", "workloads": ["tiny-chat"]},
        {"name": "train_tok_s", "unit": "tokens/s",
         "workloads": ["tiny-train"]},
        {"name": "setup_s", "unit": "s"},
    ],
    "per_layer": [],
}


def run(cell, seed=2**31 + 3, seconds=1.0):
    args = argparse.Namespace(workload=cell, seed=seed, seconds=seconds,
                              trace=0)
    out = R.run_cell(BENCH, cell, args, root=DATA, data=DATA,
                     devices=jax.devices(), peaks=PEAKS)
    json.dumps(out)  # the result line must serialize
    return out


def test_sound_serving_is_correct():
    out = run("tiny-chat")
    assert out["correct"], out["checks"]
    assert out["attempted"] == 20 and out["failed"] == 0
    assert set(out["metrics"]) == {"ttft_p95_s", "itl_p95_s", "setup_s"}
    assert list(out)[-1] == "checks"


def test_control_is_not_correct():
    """The plain reference at E4M3 operands, put in the program's place,
    reads above the limit the sound program keeps under."""
    import types

    from chipbench import model
    from chipbench.drivers import serve

    limit = R.load_json(DATA / "limits" / "tiny-chat.json")
    conf = R.load_json(DATA / "configs" / "tiny-serve.json")
    ctx = types.SimpleNamespace(
        args=argparse.Namespace(seed=2**31 + 3, seconds=1.0, trace=0),
        conf=conf, mix=R.load_json(DATA / "traffic" / "tiny-chat.json"),
        dims=model.Dims.of(conf), devices=jax.devices(), t_start=0.0,
        tracer=R.Tracer(False))
    c = serve.run_once(ctx, control=True)["compare"]
    lim = limit["token_gap_mean"]["limit"]
    assert c["token_gap_mean"] <= lim < c["control_token_gap_mean"]


def test_altered_token_fails(monkeypatch):
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
    out = run("tiny-chat")
    assert calls["n"] > 7
    assert not out["correct"], out["checks"]


def test_sound_training_is_correct():
    out = run("tiny-train")
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"train_tok_s", "setup_s"}


def _patch_step(monkeypatch, wrap):
    from repro.launch import train as T

    real = T.build_train_step

    def build(*a, **k):
        return wrap(real(*a, **k))

    monkeypatch.setattr(T, "build_train_step", build)


def test_unchanged_state_fails(monkeypatch):
    """A step that returns its state unchanged."""
    def wrap(step):
        def stuck(state, batch):
            _, metrics = step(state, batch)
            return state, metrics
        return stuck

    _patch_step(monkeypatch, wrap)
    out = run("tiny-train")
    assert not out["correct"], out["checks"]
    assert out["checks"]["change_gap"]["value"] > 0.9


def test_half_batch_fails(monkeypatch):
    """Half of the batch left out, the mean taken over the rest."""
    def wrap(step):
        def half(state, batch):
            n = batch["inputs"].shape[0] // 2
            return step(state, {k: v[:n] for k, v in batch.items()})
        return half

    _patch_step(monkeypatch, wrap)
    out = run("tiny-train")
    assert not out["correct"], out["checks"]


def test_training_control_is_not_correct():
    """The reference at E4M3 operands and cotangents, put in the program's
    place, reads a first gradient above the limit the program keeps
    under."""
    import types

    from chipbench import model
    from chipbench.drivers import train

    limit = R.load_json(DATA / "limits" / "tiny-train.json")["grad_gap"]
    conf = R.load_json(DATA / "configs" / "tiny-train.json")
    ctx = types.SimpleNamespace(
        args=argparse.Namespace(seed=2**31 + 3, seconds=0.5, trace=0),
        conf=conf, mix=R.load_json(DATA / "traffic" / "tiny-train.json"),
        dims=model.Dims.of(conf), devices=jax.devices(), t_start=0.0,
        tracer=R.Tracer(False))
    r = train.run_once(ctx, control=True)
    assert r["readings"]["grad_gap"] <= limit["limit"] < r["control"]["grad_gap"]


def test_cpu_host_is_refused():
    with pytest.raises(SystemExit):
        R.check_devices(1)


def test_command_on_a_cpu_host_prints_no_result():
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(R.HERE / "run.py"), "--workload", "yi9b-chat",
         "--seed", str(2**31 + 9), "--seconds", "1", "--trace", "0"],
        cwd=R.ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert not p.stdout.strip()
    assert "needs a TPU" in p.stderr


def test_device_missing_from_peaks_is_refused():
    with pytest.raises(SystemExit):
        R.peaks_for("TPU v99")
    assert R.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
