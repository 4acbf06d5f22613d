"""The program's spans and scopes in a profiler trace (trace_spans.py), and
the reader of the delivery counter."""
import importlib.util
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(ROOT, "chipbench"))

import trace_reduce  # noqa: E402
import trace_spans  # noqa: E402

MS = 1_000_000
DEV = "/device:TPU:0"
HOST = "/host:CPU"
# the tick's ops by scope, as scope_map reads them from its HLO
OPS = {"%while.1": "forward", "%fusion.2": "forward", "%fusion.3": "forward",
       "%fused_head_sampling.1": "sampling", "%scatter.4": "commit"}


def _events():
    """One tick module [10, 60) ms on the device: a %while [10, 30) with
    two body ops nested in it (forward), the head [32, 45) (sampling), a
    scatter [46, 50) (commit) and an unscoped copy [50, 52); then idle.
    The engine thread: host_prep [0, 9) holding admit, dispatch [9, 11),
    device_sync [11, 55), commit [55, 80) holding canvas_fetch [56, 70),
    worker_sync [80, 85), then host_prep [85, 95) and dispatch [95, 100)."""
    op = "XLA Ops"
    return [
        [DEV, op, "%while.1 = (s32[]) while(%t)", 10 * MS, 30 * MS, ""],
        [DEV, op, "%fusion.2 = bf16[8] fusion(%a)", 11 * MS, 20 * MS, ""],
        [DEV, op, "%fusion.3 = bf16[8] fusion(%b)", 20 * MS, 29 * MS, ""],
        [DEV, op, "%fused_head_sampling.1 = (f32[8,1]) custom-call(%c)",
         32 * MS, 45 * MS, ""],
        [DEV, op, "%scatter.4 = s32[16] scatter(%d)", 46 * MS, 50 * MS, ""],
        [DEV, op, "%copy.5 = s32[16] copy(%e)", 50 * MS, 52 * MS, ""],
        [DEV, "XLA Modules", "jit_batched_tick(1)", 10 * MS, 60 * MS, ""],
        [HOST, "python3", "dllm.host_prep", 0, 9 * MS, ""],
        [HOST, "python3", "dllm.admit", 1 * MS, 5 * MS, ""],
        [HOST, "python3", "dllm.dispatch", 9 * MS, 11 * MS, ""],
        [HOST, "python3", "dllm.device_sync", 11 * MS, 55 * MS, ""],
        [HOST, "python3", "np.asarray(jax.Array)", 11 * MS, 55 * MS, ""],
        [HOST, "python3", "dllm.commit", 55 * MS, 80 * MS, ""],
        [HOST, "python3", "dllm.canvas_fetch", 56 * MS, 70 * MS, ""],
        [HOST, "python3", "np.asarray(jax.Array)", 56 * MS, 70 * MS, ""],
        [HOST, "python3", "dllm.worker_sync", 80 * MS, 85 * MS, ""],
        [HOST, "python3", "dllm.sse_write", 60 * MS, 61 * MS, ""],
        [HOST, "python3", "dllm.host_prep", 85 * MS, 95 * MS, ""],
        [HOST, "python3", "dllm.dispatch", 95 * MS, 100 * MS, ""],
    ]


def test_scopes_count_top_level_ops_once():
    x = trace_spans.split(_events(), (0, 100 * MS), OPS)[DEV]
    # the %while's 20 ms, not 20 + 18 with its nested body ops
    assert x["scope_s"] == pytest.approx(
        {"forward": 0.020, "sampling": 0.013, "commit": 0.004,
         "other": 0.002})
    assert x["tick_busy_s"] == pytest.approx(0.020 + 0.013 + 0.006)


def test_idle_goes_to_the_innermost_engine_span():
    x = trace_spans.split(_events(), (0, 100 * MS), OPS)[DEV]
    idle = x["idle_by_span"]
    # [0, 9) host_prep less [1, 5) admit; [9, 10) dispatch; [30, 32) and
    # [45, 46) device_sync; [52, 55) device_sync; [55, 56) commit;
    # [56, 70) canvas_fetch, not commit and not the sse_write of another
    # thread; [70, 80) commit; [80, 85) worker_sync; [85, 95) host_prep;
    # [95, 100) dispatch
    assert idle == pytest.approx({
        "dllm.host_prep": 0.015, "dllm.admit": 0.004,
        "dllm.dispatch": 0.006, "dllm.device_sync": 0.006,
        "dllm.commit": 0.011, "dllm.canvas_fetch": 0.014,
        "dllm.worker_sync": 0.005})
    assert x["host_idle_s"] == pytest.approx(0.061)
    assert sum(idle.values()) == pytest.approx(
        0.1 - trace_reduce.reduce(_events(), (0, 100 * MS))[
            "devices"][DEV]["busy_s"])


def test_idle_at_the_edges_and_between_spans():
    """Spans open when the profiler starts or stops are not recorded:
    idle before the first span and after the last is reported as such;
    idle between recorded spans is unattributed."""
    gone = {"dllm.host_prep", "dllm.admit", "dllm.dispatch"}
    ev = [e for e in _events() if e[2] not in gone]
    ev.append([HOST, "python3", "dllm.dispatch", 9 * MS, 11 * MS, ""])
    idle = trace_spans.split(ev, (0, 100 * MS), OPS)[DEV]["idle_by_span"]
    # [0, 9) before the first span (dispatch at 9); [85, 100) after the
    # last (worker_sync ends at 85)
    assert idle[trace_spans.BEFORE] == pytest.approx(0.009)
    assert idle[trace_spans.AFTER] == pytest.approx(0.015)
    assert trace_spans.NONE not in idle
    ev = [e for e in _events() if e[2] != "dllm.worker_sync"]
    idle = trace_spans.split(ev, (0, 100 * MS), OPS)[DEV]["idle_by_span"]
    assert idle[trace_spans.NONE] == pytest.approx(0.005)      # [80, 85)
    assert trace_spans.BEFORE not in idle and trace_spans.AFTER not in idle


def test_the_reduction_keeps_its_keys():
    """trace_reduce.reduce gives what it gave before the spans existed,
    and the kernel table: the spans name the idle gaps by overlap, as any
    host event does, and are no kernels."""
    d = trace_reduce.reduce(_events(), (0, 100 * MS))["devices"][DEV]
    assert set(d) == {"busy_s", "idle_share", "top_ops", "idle_gaps",
                      "kernels", "head_calls", "head_s", "ticks",
                      "tick_span_s"}
    assert set(d["kernels"]) == {"while", "fusion", "fused_head_sampling",
                                 "scatter", "copy"}
    assert d["head_calls"] == 1 and d["ticks"] == 1
    assert d["busy_s"] == pytest.approx(0.039)


def test_scope_map_from_hlo_text():
    hlo = "\n".join([
        "ENTRY %main.1 (p: f32[2]) -> f32[2] {",
        '  %while.25 = (s32[]) while(%t), metadata={op_name='
        '"jit(<unknown>)/forward/while" stack_frame_id=1}',
        '  %fused_head_sampling.1 = (f32[8,1]) custom-call(%a), '
        'metadata={op_name="jit(<unknown>)/sampling/jit(fused_head_'
        'sampling)/fused_head_sampling/pallas_call"}',
        '  ROOT %fusion.3 = s32[16] fusion(%b), metadata={op_name='
        '"jit(megatick)/while/body/commit/reduce_sum"}',
        '  %copy.4 = s32[16] copy(%c), metadata={op_name="jit(f)/tick_'
        'forward"}',
        "  %param.5 = s32[16] parameter(0)",
        "}"])
    assert trace_spans.scope_map(hlo) == {
        "%while.25": "forward", "%fused_head_sampling.1": "sampling",
        "%fusion.3": "commit", "%copy.4": "other"}


def _reader(name):
    path = os.path.join(ROOT, "chipbench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _scrape(count, total):
    return ("# TYPE dllm_delivery_seconds histogram\n"
            f'dllm_delivery_seconds_bucket{{replica="replica-0",le="+Inf"}} '
            f"{count}\n"
            f'dllm_delivery_seconds_sum{{replica="replica-0"}} {total}\n'
            f'dllm_delivery_seconds_count{{replica="replica-0"}} {count}\n')


def test_delivery_mean_reader():
    read = _reader("delivery_mean_s")
    run = types.SimpleNamespace(scrapes={"open": (0.0, _scrape(10, 0.5)),
                                         "close": (4.0, _scrape(410, 2.5))})
    assert read(run) == pytest.approx(2.0 / 400)
    # a program without the counter: nothing to read, no error
    run = types.SimpleNamespace(scrapes={"open": (0.0, ""),
                                         "close": (4.0, "")})
    assert read(run) is None
