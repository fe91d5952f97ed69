"""The names of the compiled programs that profiler traces are read by.

A trace names each device program after its HLO module (``jit__decode``,
``jit_pre``, ``jit_step``); the benchmark's readers of decode, prefill and
training time find the programs by these names, so a refactor that renames
one has to fail here rather than silence them.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.data import SyntheticLM
from repro.launch import train as T
from repro.models import transformer
from repro.optim import AdamW
from repro.serving import Scheduler, SchedulerConfig


def hlo_module(lowered) -> str:
    text = lowered.as_text(dialect="hlo")
    return re.match(r"HloModule (\w+)", text).group(1)


@pytest.fixture(scope="module")
def yi():
    cfg = configs.get_reduced("yi-9b")
    return cfg, transformer.init_params(jax.random.PRNGKey(0), cfg)


def test_scheduler_decode_and_prefill_names(yi):
    cfg, params = yi
    sched = Scheduler(params, cfg, SchedulerConfig(n_slots=2, max_len=16))
    n = np.zeros((2,), np.int32)
    decode = sched._decode.lower(params, sched.cache,
                                 jnp.zeros((2, 1), jnp.int32), n, n)
    prefill = sched._prefill_fn(5).lower(params, jnp.zeros((1, 5), jnp.int32))
    assert hlo_module(decode) == "jit__decode"
    assert hlo_module(prefill) == "jit_pre"


def test_train_step_name(yi):
    cfg, _ = yi
    opt = AdamW(lr=1e-4)
    state = jax.eval_shape(lambda: T.init_state(jax.random.PRNGKey(0), cfg,
                                                opt))
    batch = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=16,
                        global_batch=2).batch(0)
    step = jax.jit(T.build_train_step(cfg, opt, rules=None),
                   donate_argnums=(0,))
    assert hlo_module(step.lower(state, batch)) == "jit_step"
