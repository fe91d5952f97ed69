"""A configuration file's model: its shapes, its weights and its program config.

The benchmark owns the weights.  ``init_weights`` makes them on the device
in one jitted call from the run's seed, in the layout the program's dense
decoder takes (``embed``, ``layers/{ln1,attn/{wqkv,wo},ln2,mlp/{w_in,w_out}}``,
``final_norm``, ``lm_head``); the plain reference reads the same layout,
so neither needs anything the other made.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp

@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of a dense GQA decoder, as a configuration file states them."""

    d: int
    ff: int
    hq: int
    hkv: int
    hd: int
    layers: int
    vocab: int
    rope_theta: float
    eps: float

    @classmethod
    def of(cls, c: Dict[str, Any]) -> "Dims":
        hd = c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]
        return cls(d=c["hidden_size"], ff=c["intermediate_size"],
                   hq=c["num_attention_heads"], hkv=c["num_key_value_heads"],
                   hd=hd, layers=c["num_hidden_layers"], vocab=c["vocab_size"],
                   rope_theta=float(c["rope_theta"]),
                   eps=float(c["rms_norm_eps"]))


def program_config(c: Dict[str, Any]):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig

    dims = Dims.of(c)
    if c.get("hidden_act", "silu") != "silu" or c.get("tie_word_embeddings"):
        raise ValueError(f"{c['name']}: only untied SiLU-gated decoders")
    if dims.eps != 1e-6:
        raise ValueError(f"{c['name']}: the program's RMSNorm runs eps 1e-6, "
                         f"the file states {dims.eps}")
    prec = c["precision"]
    extra = {}
    if "training" in c:
        extra["remat"] = c["training"]["remat"]
    return ModelConfig(
        name=c["model"], family="dense", n_layers=dims.layers, d_model=dims.d,
        n_heads=dims.hq, n_kv_heads=dims.hkv, head_dim=dims.hd, d_ff=dims.ff,
        vocab_size=dims.vocab, rope_theta=dims.rope_theta,
        use_bias=bool(c.get("attention_bias")), policy_name=prec["policy"],
        param_dtype=prec["param_dtype"], **extra)


def shapes(dims: Dims) -> Dict[str, Any]:
    """Leaf shapes of the weight tree, with each leaf's initializer."""
    d, L = dims.d, dims.layers
    qkv = (dims.hq + 2 * dims.hkv) * dims.hd
    return {
        "embed": ((dims.vocab, d), "embed"),
        "final_norm": ((d,), "norm"),
        "lm_head": ((d, dims.vocab), "proj"),
        "layers": {
            "ln1": ((L, d), "norm"),
            "attn": {"wqkv": ((L, d, qkv), "proj"),
                     "wo": ((L, dims.hq * dims.hd, d), "proj")},
            "ln2": ((L, d), "norm"),
            "mlp": {"w_in": ((L, d, 2 * dims.ff), "proj"),
                    "w_out": ((L, dims.ff, d), "proj")},
        },
    }


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number, also those above 32 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError("seeds are non-negative")
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def _init(key: jax.Array, dims: Dims, dtype) -> Dict[str, Any]:
    tree = shapes(dims)
    leaves, treedef = jax.tree.flatten(tree, is_leaf=_is_leaf)
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, (shape, kind) in zip(keys, leaves):
        z = jax.random.normal(k, shape, jnp.float32)
        if kind == "embed":
            w = 0.02 * z
        elif kind == "norm":
            # not all ones, so a norm scale applied wrongly shows
            w = 1.0 + 0.1 * z
        else:
            w = z * shape[-2] ** -0.5
        out.append(w.astype(dtype))
    return jax.tree.unflatten(treedef, out)


def init_weights(seed: int, dims: Dims, dtype=jnp.float32) -> Dict[str, Any]:
    """The run's weights, made on the default device in one jitted call."""
    fn = jax.jit(_init, static_argnums=(1, 2))
    return fn(seed_key(seed), dims, jnp.dtype(dtype))


def check_layout(weights, cfg) -> None:
    """Fail loudly if the program's parameter tree is not the one made here."""
    from repro.models import transformer

    want = jax.tree.map(lambda a: (a.shape, a.dtype),
                        transformer.abstract_params(cfg))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), weights)
    if want != got:
        raise RuntimeError(f"the program's parameter tree {want} is not the "
                           f"benchmark's {got}")
