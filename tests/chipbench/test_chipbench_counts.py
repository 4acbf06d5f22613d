"""The benchmark's operation and byte counts against hand-worked numbers."""
import json
import os
import sys
import types

import pytest

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "..")
sys.path.insert(0, os.path.join(ROOT, "chipbench"))

import counts  # noqa: E402
import run  # noqa: E402

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


def test_a_reference_may_count_its_own_work():
    """A reference module that defines a count replaces that count; one
    that does not leaves the dense formulas."""
    own = types.SimpleNamespace(
        tick_flops=lambda m, block, active: 11 * len(active),
        head_flops=lambda m, rows: 13 * rows,
        head_bytes=lambda m, rows, itemsize: 17 * rows * itemsize)
    assert counts.tick_flops(M, 8, [(40, False)] * 3, own) == 33
    assert counts.head_flops(M, 8, own) == 104
    assert counts.head_bytes(M, 8, 2, own) == 272
    plain = types.SimpleNamespace()
    assert counts.tick_flops(M, 8, [(40, True)], plain) == counts.tick_flops(
        M, 8, [(40, True)])
    assert counts.head_flops(M, 8, plain) == 262144
    assert counts.head_bytes(M, 8, 2, plain) == 32768 + 1024 + 64


def _metric(name):
    return run._module(os.path.join(ROOT, "chipbench", "metrics",
                                    name + ".py"), f"metric_{name}").read


def _ev(tick, block, step):
    return [0.0, tick, block, step, [0, 1], [1, 1]]


# three requests' commits around the traced ticks 5..8 (ticks 4..8 seen):
# real lengths 104, 132, 145; first ticks of a block at 5 and 6
RECORDS = [
    {"prompt": [1] * 40, "gen": 64, "events": [
        _ev(4, 0, 15), _ev(5, 1, 0), _ev(6, 1, 1), _ev(7, 1, 2)]},
    {"prompt": [1] * 100, "gen": 32, "events": [
        _ev(5, 0, 3), _ev(6, 0, 4), _ev(7, 0, 5), _ev(8, 0, 6),
        _ev(9, 0, 7)]},
    {"prompt": [1] * 17, "gen": 128, "events": [
        _ev(6, 2, 0), _ev(7, 2, 1), _ev(8, 2, 2)]},
]
TRACE = {"window_s": 4.0, "devices": {"/device:TPU:0": {
    "ticks": 4, "tick_span_s": 0.61, "head_calls": 4, "head_s": 0.0173}}}


@pytest.mark.parametrize("config, tick_mfu, head_roofline", [
    ("llada-8b-16l", 3.5964003715802613, 29.265891380296004),
    ("qwen2-0.5b", 0.4165607833702255, 7.6905661634447755),
])
def test_readers_read_as_before(config, tick_mfu, head_roofline):
    """``tick_mfu`` and ``head_roofline`` on a hand-built run context,
    against what the readers gave before a reference could count its own
    work (the literals)."""
    with open(os.path.join(ROOT, "chipbench", "configs",
                           config + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "chipbench", "peaks.json")) as f:
        peaks = json.load(f)["TPU v5 lite"]
    ctx = types.SimpleNamespace(
        records=RECORDS, trace=TRACE, trace_ticks=[[4], [8]], config=cfg,
        model=cfg["model"], reference=run.reference_of(cfg), peaks=peaks)
    assert _metric("tick_mfu")(ctx) == tick_mfu
    assert _metric("head_roofline")(ctx) == head_roofline
