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
    assert d["kernels"] == {
        "fusion": {"calls": 2, "s": pytest.approx(0.025)},
        "fused_head_sampling": {"calls": 1, "s": pytest.approx(0.01)}}


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


def _recorded():
    with open(os.path.join(HERE, "data", "trace_llada.json")) as f:
        rec = json.load(f)
    d = trace_reduce.reduce(rec["events"], tuple(rec["window_ns"]))[
        "devices"][rec["device"]]
    return rec, d


def test_recorded_head_reads_as_before():
    """The head's calls and time in the recorded trace, as the reduction
    read them before it kept a table of every kernel."""
    _, d = _recorded()
    assert d["head_calls"] == 1
    assert d["head_s"] == 0.087627607


def test_recorded_kernel_table():
    """The kernel table holds every op of the device once, whole, by the
    stem of its name; the head's entry is the head's calls and time."""
    rec, d = _recorded()
    ops = [ev for ev in rec["events"]
           if ev[0] == rec["device"] and ev[1] == "XLA Ops"]
    assert sum(k["calls"] for k in d["kernels"].values()) == len(ops)
    assert sum(k["s"] for k in d["kernels"].values()) == pytest.approx(
        sum(ev[4] - ev[3] for ev in ops) / 1e9, rel=1e-12)
    assert d["kernels"]["fused_head_sampling"] == {
        "calls": d["head_calls"], "s": d["head_s"]}
    assert all("%" not in k and not k[-1].isdigit() for k in d["kernels"])


@pytest.mark.parametrize("name, want", [
    ("%fused_head_sampling.1 = (f32[8,1]) custom-call(%a)",
     "fused_head_sampling"),
    ("%fusion.212", "fusion"),
    ("%slice-start", "slice-start"),
    ("%copy-start.13", "copy-start"),
    ("%topk_mask", "topk_mask"),
])
def test_kernel_stem(name, want):
    assert trace_reduce.stem(name) == want
