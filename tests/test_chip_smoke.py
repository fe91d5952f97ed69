"""chip_smoke.py between chip runs: its phases on the CPU at the reduced
yi-9b preset through the Pallas interpreter, and its refusal to report a
result anywhere but on a TPU."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro import configs
from repro.core import engine
from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_serve_phase_interpret(smoke):
    cfg = configs.get_reduced("yi-9b")
    with engine.use_backend("interpret"):
        res = smoke.serve_phase(cfg, prompt_lens=(8, 16, 24), n_requests=3,
                                new_tokens=4, slots=2)
    assert res["backend"] == "interpret"
    assert res["tokens_served"] == 12 and res["prefills"] == 3
    assert res["gemm_events"] > 0
    assert res["prefill_rel_l2_vs_xla"] <= res["prefill_rel_tol"]


def test_train_phase_interpret(smoke):
    cfg = configs.get_reduced("yi-9b")
    with engine.use_backend("interpret"):
        res = smoke.train_phase(cfg, batch=2, seq=16, steps=2)
    assert res["backend"] == "interpret" and len(res["losses"]) == 2
    assert res["gemm_events"] > 0


def test_dp_phase_on_four_virtual_devices():
    code = f"""
import importlib.util, json, sys
spec = importlib.util.spec_from_file_location("chip_smoke", {str(SCRIPT)!r})
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
from repro import configs
res = smoke.dp_phase(configs.get_reduced("yi-9b"), ndev=4, batch=8, seq=32,
                     steps=3)
print("RESULT", json.dumps(res))
"""
    env = {**os.environ, "PYTHONPATH": str(ROOT / "src"),
           "JAX_PLATFORMS": "cpu", "REPRO_MATMUL_BACKEND": "interpret",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT ")][-1]
    res = json.loads(line[len("RESULT "):])
    assert res["devices"] == 4
    assert res["loss_rel_gap"] <= res["loss_rel_tol"]


def _run_script(script: Path, cwd: Path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(script)], capture_output=True,
                          text=True, env=env, cwd=cwd, timeout=300)


def test_script_refuses_a_host_without_tpu():
    out = _run_script(SCRIPT, ROOT)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "needs a TPU" in out.stderr


def test_script_alone_fails(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, lone)
    out = _run_script(lone, tmp_path)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_compile_cache_location(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        assert compile_cache.enable() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", before)
        monkeypatch.setenv(compile_cache.ENV_VAR, "/elsewhere")
        assert compile_cache.enable() == "/elsewhere"
        # JAX reads the variable itself; nothing is set in code
        assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
