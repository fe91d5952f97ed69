"""The plain reference against the program's ``xla`` path at reduced presets.

Both sides run in float32 (the ``fp32`` policy) on the CPU from the same
benchmark-made weights, so they differ only by the order of their
roundings: the program's q-chunked online softmax and fused products
against the reference's blocked softmax.  Logits then agree to about 1e-6
relative; the bound of 1e-4 leaves 100x, and a bfloat16 datapath (2^-9 per
rounding) would miss it on its first product.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import model
from chipbench.reference import dense

REL_TOL = 1e-4


def conf_of(arch, **over):
    from repro import configs

    c = dataclasses.replace(configs.get_reduced(arch), **over)
    return {"name": arch, "model": arch, "hidden_size": c.d_model,
            "intermediate_size": c.d_ff, "num_attention_heads": c.n_heads,
            "num_key_value_heads": c.n_kv_heads, "head_dim": c.head_dim,
            "num_hidden_layers": c.n_layers, "vocab_size": c.vocab_size,
            "rope_theta": c.rope_theta, "rms_norm_eps": 1e-6,
            "hidden_act": "silu", "tie_word_embeddings": False,
            "precision": {"policy": "fp32", "param_dtype": "float32"}}


CASES = {
    "yi-9b": conf_of("yi-9b"),
    "mistral-nemo-12b": conf_of("mistral-nemo-12b"),
    # q-proj (4 x 32) wider than d_model 64, as in the published nemo
    "mistral-nemo-12b-wide-q": conf_of("mistral-nemo-12b", head_dim=32),
}


def rel(a, b):
    return float(np.linalg.norm(np.asarray(a) - np.asarray(b))
                 / np.linalg.norm(np.asarray(b)))


@pytest.mark.parametrize("name", sorted(CASES))
def test_logits_match_program(name):
    from repro.core import engine
    from repro.models import transformer

    conf = CASES[name]
    dims = model.Dims.of(conf)
    cfg = model.program_config(conf)
    w = model.init_weights(7, dims)
    model.check_layout(w, cfg)
    toks = np.random.default_rng(0).integers(0, dims.vocab, 150, np.int32)
    with engine.use_backend("xla"):
        got = transformer.forward(w, cfg, {"inputs": jnp.asarray(toks)[None]})[0][0]
    ref = dense.logits_at(w, dims, toks, list(range(len(toks))))
    assert rel(got, ref) < REL_TOL


@pytest.mark.parametrize("name", ["yi-9b", "mistral-nemo-12b"])
def test_loss_and_gradient_match_program(name):
    from repro.core import engine
    from repro.models import transformer

    conf = CASES[name]
    dims = model.Dims.of(conf)
    cfg = model.program_config(conf)
    w = model.init_weights(3, dims)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.integers(0, dims.vocab, (2, 64), np.int32))
    y = jnp.asarray(rng.integers(0, dims.vocab, (2, 64), np.int32))
    with engine.use_backend("xla"):
        loss, g = jax.value_and_grad(
            lambda p: transformer.loss_fn(p, cfg, {"inputs": x, "labels": y})[0])(w)
    ref_loss, ref_g = dense.loss_and_grad(w, dims, x, y)
    assert abs(float(loss) - float(ref_loss)) / float(ref_loss) < REL_TOL
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(ref_g)):
        assert rel(a, b) < REL_TOL


def test_control_is_coarser_than_reference():
    """The FP8 control departs from the reference by far more than the
    float32 program does."""
    from repro.core import engine
    from repro.models import transformer

    conf = CASES["yi-9b"]
    dims = model.Dims.of(conf)
    cfg = model.program_config(conf)
    w = model.init_weights(5, dims)
    toks = np.random.default_rng(2).integers(0, dims.vocab, 64, np.int32)
    rows = list(range(len(toks)))
    ref = dense.logits_at(w, dims, toks, rows)
    ctl = dense.logits_at(w, dims, toks, rows, fp8=True)
    with engine.use_backend("xla"):
        got = transformer.forward(w, cfg, {"inputs": jnp.asarray(toks)[None]})[0][0]
    assert rel(ctl, ref) > 100 * rel(got, ref)
    assert rel(ctl, ref) > 1e-2


def test_seed_key_takes_large_seeds():
    a = model.seed_key(2**31 + 5)
    b = model.seed_key(2**33 + 5)
    c = model.seed_key(5)
    ka, kb, kc = (jax.random.key_data(k) for k in (a, b, c))
    assert not np.array_equal(ka, kb) and not np.array_equal(ka, kc)
