"""The reduction from a profiler trace to the per-layer metrics' inputs."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "chipbench"))

import trace_reduce  # noqa: E402

MS = 1_000_000
DEV = "/device:TPU:0"


def test_busy_idle_gaps_head_and_ticks_by_hand():
    events = [
        [DEV, "XLA Ops", "fusion.1", 0, 10 * MS, "jit_batched_tick"],
        [DEV, "XLA Ops", "fusion.2", 5 * MS, 20 * MS, "jit_batched_tick"],
        [DEV, "XLA Ops", "%fused_head_sampling.3 = custom-call()", 30 * MS, 40 * MS,
         ""],
        [DEV, "XLA Modules", "jit__unknown(7)", 0, 20 * MS, ""],
        [DEV, "XLA Modules", "jit__unknown(7)", 25 * MS, 45 * MS, ""],
        [DEV, "XLA Modules", "jit_scatter(8)", 21 * MS, 22 * MS, ""],
        ["/host:CPU", "python", "PjitFunction(batched_tick)", 18 * MS,
         33 * MS, ""],
        ["/host:CPU", "python", "TransferToDevice", 41 * MS, 42 * MS, ""],
    ]
    red = trace_reduce.reduce(events, (0, 50 * MS))
    d = red["devices"][DEV]
    assert red["window_s"] == pytest.approx(0.05)
    assert d["busy_s"] == pytest.approx(0.030)     # [0, 20) and [30, 40)
    assert d["idle_share"] == pytest.approx(0.4)
    assert d["idle_gaps"] == [["PjitFunction(batched_tick)", 0.01],
                              ["TransferToDevice", 0.01]]
    assert d["top_ops"][0] == ["fusion.2", pytest.approx(0.015)]
    assert d["head_calls"] == 1 and d["head_s"] == pytest.approx(0.01)
    # a tick is a module run that holds a head call: only the second
    assert d["ticks"] == 1 and d["tick_span_s"] == pytest.approx(0.02)


def test_recorded_chip_trace():
    """An excerpt of a trace recorded on one TPU v5e in a traced run of
    ``llada8b-batch``, reduced against the numbers worked out from it."""
    with open(os.path.join(HERE, "data", "trace_llada.json")) as f:
        rec = json.load(f)
    red = trace_reduce.reduce(rec["events"], tuple(rec["window_ns"]))
    d = red["devices"][rec["device"]]
    for key, want in rec["expect"].items():
        # the expectations were reckoned on a 1 us grid
        assert d[key] == pytest.approx(want, abs=5e-5), key
