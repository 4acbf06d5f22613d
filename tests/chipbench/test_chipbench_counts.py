"""The benchmark's operation and byte counts against hand-worked numbers."""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "..", "chipbench"))

import counts  # noqa: E402

# a smoke-sized model: 2 layers, d 64, 4 heads of 16, 2 KV heads, d_ff 128
M = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
     "d_head": 16, "d_ff": 128, "vocab": 256, "dtype": "bfloat16"}


def test_dense_flops_per_token():
    # per layer: q 64x64 + k,v 2 x 64x32 + o 64x64 + ffn 3 x 64x128
    per_layer = 4096 + 2 * 2048 + 4096 + 3 * 8192      # 36864 MACs
    assert counts.dense_flops_per_token(M) == 2 * 2 * per_layer == 147456


def test_attention_and_head():
    # QK^T and PV: 2 x (64 wide) x 8 queries x 40 keys, x 2 flops, x 2 layers
    assert counts.attn_flops(M, 8, 40) == 4 * 2 * 64 * 8 * 40 == 163840
    assert counts.head_flops(M, 8) == 2 * 8 * 64 * 256 == 262144
    # head 64 x 256 bf16 once, 8 rows of 64 bf16 in, 8 x (f32 + i32) out
    assert counts.head_bytes(M, 8, 2) == 32768 + 1024 + 64


def test_tick_counts_the_least_work():
    # one request of 40 real positions mid-block: an 8-row block forward
    # attending to 40 keys, plus the head over 8 rows
    mid = 8 * 147456 + 163840 + 262144
    assert counts.tick_flops(M, 8, [(40, False)]) == mid
    # on a block's first tick the whole request is forwarded once more
    first = mid + 40 * 147456 + counts.attn_flops(M, 40, 40)
    assert counts.tick_flops(M, 8, [(40, True)]) == first
    assert counts.tick_flops(M, 8, [(40, False), (40, True)]) == mid + first
    assert counts.tick_flops(M, 8, []) == 0
