"""The Pallas kernels at yi-9b widths, compiled for a described TPU v5e.

No chip is attached: ``get_topology_desc`` describes a v5e:2x2 slice and
each test lowers and compiles for its first chip, so Mosaic's tiling,
VMEM and dtype rules are checked at real shapes on every CPU run.  The
topology is described inside the module fixture, never at import: only
one process may load the TPU library at a time.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import engine
from repro.core import precision as prec
from repro.kernels import ops
from repro.kernels.chunked_linear_attention import \
    chunked_linear_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas

# yi-9b: d_model 4096, d_ff 11008, 32 query and 4 KV heads of 128
M, D, F = 512, 4096, 11008
HQ, HKV, HD = 32, 4, 128


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler or topology here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("layout", ["nn", "nt", "tn"])
def test_redmule_matmul_layouts_compile(one_chip, layout):
    # the logical Z[M, K] = X[M, N] @ W[N, K] with each operand stored as
    # the layout says: the forward (nn) and the dX (nt) / dW (tn) backward
    x = (M, D) if layout != "tn" else (D, M)
    w = (D, F) if layout != "nt" else (F, D)
    text = _compile(
        lambda a, b: ops.redmule_matmul(a, b, policy=prec.TPU_BF16,
                                        layout=layout),
        _sds(one_chip, x), _sds(one_chip, w))
    assert "tpu_custom_call" in text


def test_redmule_matmul_fused_bias_silu_compiles(one_chip):
    text = _compile(
        lambda a, b, c: ops.redmule_matmul(a, b, bias=c, epilogue="silu",
                                           policy=prec.TPU_BF16),
        _sds(one_chip, (M, D)), _sds(one_chip, (D, F)),
        _sds(one_chip, (F,), jnp.float32))
    assert "tpu_custom_call" in text


def test_redmule_matmul_decode_shape_compiles(one_chip):
    # eight decode slots against the FFN weight
    text = _compile(
        lambda a, b: ops.redmule_matmul(a, b, policy=prec.TPU_BF16),
        _sds(one_chip, (8, D)), _sds(one_chip, (D, F)))
    assert "tpu_custom_call" in text


def test_decode_attention_dispatches_compile(one_chip):
    # ragged decode attention over 32 slots of 4096: the query group in the
    # rows, scores on the batched "nt" entry, PV on the batched "nn" one
    B, T, G = 32, 4096, HQ // HKV

    def attend(q, k, v, sizes):
        s = engine.grouped_matmul(q, k, group_sizes=sizes, layout="nt",
                                  policy=prec.TPU_BF16, backend="pallas")
        return engine.matmul(s.astype(jnp.bfloat16), v,
                             policy=prec.TPU_BF16, backend="pallas")

    text = _compile(attend, _sds(one_chip, (B * HKV, G, HD)),
                    _sds(one_chip, (B * HKV, T, HD)),
                    _sds(one_chip, (B * HKV, T, HD)),
                    _sds(one_chip, (B * HKV,), jnp.int32))
    assert text.count("tpu_custom_call") >= 2
    assert "redmule_matmul_batched_nt" in text


def test_flash_attention_gqa_causal_compiles(one_chip):
    S = 1024
    text = _compile(
        lambda q, k, v: flash_attention_pallas(
            q, k, v, group=HQ // HKV, causal=True, bq=256, bkv=512),
        _sds(one_chip, (HQ, S, HD)), _sds(one_chip, (HKV, S, HD)),
        _sds(one_chip, (HKV, S, HD)))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("chunk", [64, 128])
def test_chunked_linear_attention_compiles(one_chip, chunk):
    BH, S, dk, dv = 8, 1024, 128, 256
    text = _compile(
        lambda q, k, v, g: chunked_linear_attention_pallas(
            q, k, v, g, chunk=chunk),
        _sds(one_chip, (BH, S, dk)), _sds(one_chip, (BH, S, dk)),
        _sds(one_chip, (BH, S, dv)), _sds(one_chip, (BH, S), jnp.float32))
    assert "tpu_custom_call" in text


def test_granite_ssd_kernel_compiles(one_chip):
    # granite-4.0-h-micro's Mamba2 prefill: 64 heads, fp32 C/B of state
    # width 128 and dt x of head width 64, chunk 256
    BH, S, dk, dv = 64, 512, 128, 64
    text = _compile(
        lambda q, k, v, g: chunked_linear_attention_pallas(
            q, k, v, g, chunk=256),
        _sds(one_chip, (BH, S, dk), jnp.float32),
        _sds(one_chip, (BH, S, dk), jnp.float32),
        _sds(one_chip, (BH, S, dv), jnp.float32),
        _sds(one_chip, (BH, S), jnp.float32))
    assert "redmule_chunked_linear_attention" in text


@pytest.mark.parametrize("policy", ["tpu_bf16", "fp32"])
def test_engine_linear_and_grad_compile(one_chip, policy):
    # forward plus the custom-VJP backward through the Engine's registry
    def loss(x, w, b):
        y = engine.linear(x, w, b, activation="silu", policy=policy,
                          backend="pallas")
        return jnp.sum(y.astype(jnp.float32))

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)),
                    _sds(one_chip, (M, D), jnp.float32),
                    _sds(one_chip, (D, F), jnp.float32),
                    _sds(one_chip, (F,), jnp.float32))
    assert text.count("tpu_custom_call") >= 3


@pytest.mark.parametrize("policy", ["paper_fp16", "tpu_fp16",
                                    "mixed_fp8_e4m3", "mixed_fp8_e5m2"])
def test_fp16_datapath_refused_before_mosaic(one_chip, policy):
    # a one-device mesh, so the traced operands name the chip's kind
    (dev,) = one_chip.device_set
    on_mesh = NamedSharding(Mesh(np.array([dev]), ("d",)), P())
    with pytest.raises(ValueError) as e:
        _compile(lambda a, b: engine.matmul(a, b, policy=policy,
                                            backend="pallas"),
                 _sds(on_mesh, (M, D)), _sds(on_mesh, (D, F)))
    msg = str(e.value)
    assert repr(policy) in msg and "'pallas' backend" in msg
    assert f"device kind {dev.device_kind!r}" in msg and "float16" in msg
