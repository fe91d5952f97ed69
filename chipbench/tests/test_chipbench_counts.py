"""The benchmark's operation and byte counts against a hand count at yi-9b widths."""

from chipbench import counts
from chipbench.model import Dims

YI = Dims(d=4096, ff=11008, hq=32, hkv=4, hd=128, layers=8, vocab=64000,
          rope_theta=5e6, eps=1e-6)

# one layer's weights: q|k|v 4096 x (32 + 2 * 4) * 128 = 20971520,
# output 4096 * 4096 = 16777216, gate|up and down 3 * 4096 * 11008 =
# 135266304
LAYER = 20971520 + 16777216 + 135266304
HEAD = 4096 * 64000


def test_layer_and_head_params():
    assert counts.layer_matmul_params(YI) == LAYER == 173015040
    assert counts.head_params(YI) == HEAD == 262144000


def test_decode_counts():
    # two slots attending 100 and 1000 keys: per token 2 * (8 layers + head)
    # weights, per key 2 products * 2 flops * 32 heads * 128 per layer
    per_token = 2 * (8 * LAYER + HEAD)
    attn = 4 * 32 * 128 * 8 * (100 + 1000)
    assert counts.decode_flops(YI, [100, 1000]) == 2 * per_token + attn
    # bf16 weights once: 2 * (8 * 173015040 + 262144000)
    assert counts.decode_weight_bytes(YI) == 3292528640
    # E4M3 keys and values: 2 tensors * 4 heads * 128 * 8 layers * 1 byte
    assert counts.decode_kv_bytes(YI, [100, 1000], 1) == 2 * 4 * 128 * 8 * 1100
    assert counts.decode_bytes(YI, [100, 1000], 1) == 3292528640 + 8192 * 1100


def test_prefill_counts():
    # 512 prompt tokens attend 1 + 2 + ... + 512 = 131328 keys
    attn = 4 * 32 * 128 * 8 * 131328
    want = 2 * 8 * LAYER * 512 + 2 * HEAD + attn
    assert counts.prefill_flops(YI, 512) == want


def test_train_counts():
    one = Dims(**{**YI.__dict__, "layers": 1})
    tokens = 4 * 1024
    # 1 + ... + 1024 = 524800 keys per row, 4 rows, one layer
    attn = 4 * 4 * 32 * 128 * 524800
    fwd = 2 * (LAYER + HEAD) * tokens + attn
    assert counts.train_model_flops(one, 4, 1024) == 3 * fwd
    assert round(3 * fwd / tokens / 1e9, 3) == 2.636  # GFLOP per token
    weights_fwd = 2 * LAYER * tokens
    assert counts.train_matmul_flops(one, 4, 1024, "full") == \
        4 * weights_fwd + 3 * 2 * HEAD * tokens
    assert counts.train_matmul_flops(one, 4, 1024, "none") == \
        3 * weights_fwd + 3 * 2 * HEAD * tokens
