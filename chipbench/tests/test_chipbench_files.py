"""The benchmark's data files: every configuration maps onto the program,
and every serving mix gives each seed the same work."""

import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import model, traffic

HERE = Path(__file__).resolve().parents[1]
CONFIGS = sorted((HERE / "configs").glob("*.json"))
SERVE_MIXES = [p for p in sorted((HERE / "traffic").glob("*.json"))
               if json.loads(p.read_text())["kind"] == "serve"]


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_config_file_maps_onto_the_program(path):
    c = json.loads(path.read_text())
    cfg = model.program_config(c)
    dims = model.Dims.of(c)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff,
            cfg.vocab_size) == (dims.d, dims.hq, dims.hkv, dims.ff, dims.vocab)
    assert c["name"] == path.stem
    for key, (_, used) in c["reduced"].items():
        assert c[key] == used


@pytest.mark.parametrize("path", SERVE_MIXES, ids=lambda p: p.stem)
def test_mix_gives_every_seed_the_same_work(path):
    mix = json.loads(path.read_text())
    a, b = (traffic.open_loop(mix, s, 20.0, 1000)
            for s in (2**31 + 5, 2**33 + 1))
    assert [(x.due_s, len(x.prompt), x.max_new) for x in a] == \
        [(x.due_s, len(x.prompt), x.max_new) for x in b]
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert set(len(x.prompt) for x in a) <= set(mix["prompt"]["buckets"])
    assert all(x.due_s < 20.0 for x in a)
