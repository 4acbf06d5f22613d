"""Observability subsystem: Prometheus exposition golden format, Perfetto
trace schema, drift calibration, engine integration (trace events match
streamed commit events bit-for-bit), metrics hardening, and the /metrics
HTTP endpoint."""
import json
import math
import threading
import types

import numpy as np
import pytest

from repro.obs import (Counter, DriftMonitor, Gauge, Histogram, Registry,
                       ServingObs, TraceCollector, exp_buckets,
                       frontend_metrics, parse_exposition,
                       validate_histogram, validate_trace)
from repro.obs.drift import HOST_DRIFT_BAND, modeled_tick_stages


# ---------------------------------------------------------------------------
# Registry / Prometheus exposition
# ---------------------------------------------------------------------------

def test_counter_exposition_golden_format():
    r = Registry()
    c = r.counter("dllm_requests_total", "Requests seen",
                  ("replica", "event"))
    c.inc(replica="replica-0", event="queued")
    c.inc(2, replica="replica-0", event="queued")
    c.inc(replica="replica-1", event="shed")
    text = r.expose()
    assert "# HELP dllm_requests_total Requests seen\n" in text
    assert "# TYPE dllm_requests_total counter\n" in text
    assert ('dllm_requests_total{replica="replica-0",event="queued"} 3'
            in text)
    assert ('dllm_requests_total{replica="replica-1",event="shed"} 1'
            in text)
    assert text.endswith("\n")


def test_label_value_escaping_round_trips():
    r = Registry()
    g = r.gauge("weird", "escaping", ("k",))
    nasty = 'a"b\\c\nd'
    g.set(1.5, k=nasty)
    text = r.expose()
    assert 'k="a\\"b\\\\c\\nd"' in text
    parsed = parse_exposition(text)
    assert parsed["weird"] == {'{k="a\\"b\\\\c\\nd"}': 1.5}


def test_histogram_buckets_cumulative_with_inf_and_sum_count():
    r = Registry()
    h = r.histogram("lat_seconds", "latency", ("replica",),
                    buckets=(0.001, 0.01, 0.1))
    for v in (0.0005, 0.005, 0.005, 0.05, 5.0):
        h.observe(v, replica="r0")
    parsed = parse_exposition(r.expose())
    validate_histogram(parsed, "lat_seconds")
    buckets = parsed["lat_seconds_bucket"]
    assert buckets['{replica="r0",le="0.001"}'] == 1
    assert buckets['{replica="r0",le="0.01"}'] == 3
    assert buckets['{replica="r0",le="0.1"}'] == 4
    assert buckets['{replica="r0",le="+Inf"}'] == 5
    assert parsed["lat_seconds_count"]['{replica="r0"}'] == 5
    assert parsed["lat_seconds_sum"]['{replica="r0"}'] == \
        pytest.approx(5.0605)


def test_histogram_le_boundary_is_inclusive():
    h = Histogram("h", "x", buckets=(1.0, 2.0))
    h.observe(1.0)                       # le="1" must include 1.0
    cum, total, count = h.snapshot()
    assert cum == [1, 1, 1] and count == 1


def test_counter_rejects_negative_and_wrong_labels():
    c = Counter("c_total", "x", ("a",))
    with pytest.raises(ValueError):
        c.inc(-1, a="v")
    with pytest.raises(ValueError):
        c.inc(b="v")
    with pytest.raises(ValueError):
        c.inc()                          # missing required label


def test_bound_handles_write_same_series():
    r = Registry()
    c = r.counter("c_total", "x", ("a",))
    h = r.histogram("h_seconds", "x", ("a",), buckets=(1.0,))
    b = c.labels(a="v")
    b.inc()
    b.inc(2)
    with pytest.raises(ValueError):
        b.inc(-1)
    h.labels(a="v").observe(0.5)
    assert c.value(a="v") == 3
    parsed = parse_exposition(r.expose())
    assert parsed["h_seconds_count"]['{a="v"}'] == 1


def test_registry_idempotent_and_conflict_rejection():
    r = Registry()
    c1 = r.counter("x_total", "x", ("a",))
    assert r.counter("x_total", "x", ("a",)) is c1
    with pytest.raises(ValueError):
        r.counter("x_total", "x", ("b",))     # different labels
    with pytest.raises(ValueError):
        r.gauge("x_total", "x", ("a",))       # different type


def test_exp_buckets_and_name_validation():
    bs = exp_buckets(50e-6, 2.0, 4)
    assert bs == (50e-6, 100e-6, 200e-6, 400e-6)
    with pytest.raises(ValueError):
        exp_buckets(0, 2.0, 4)
    with pytest.raises(ValueError):
        Counter("9bad", "x")
    with pytest.raises(ValueError):
        Histogram("h", "x", buckets=(2.0, 1.0))


def test_parse_exposition_rejects_garbage():
    with pytest.raises(ValueError):
        parse_exposition("not a sample line at all, no value")
    with pytest.raises(ValueError):
        parse_exposition("x{unterminated 3")
    with pytest.raises(ValueError):
        parse_exposition("x not_a_float")


# ---------------------------------------------------------------------------
# Tracing / Perfetto schema
# ---------------------------------------------------------------------------

def test_span_pairing_and_validation():
    tr = TraceCollector()
    with tr.span("outer", cat="t"):
        with tr.span("inner", cat="t"):
            pass
    tr.complete("done_work", cat="t", ts=1.0, dur=2.0)
    payload = tr.to_json()
    validate_trace(payload)
    names = [e["name"] for e in payload["traceEvents"] if e["ph"] != "M"]
    assert names == ["outer", "inner", "inner", "outer", "done_work"]


def test_unbalanced_spans_fail_validation():
    tr = TraceCollector()
    tr.begin("left_open")
    with pytest.raises(ValueError, match="unclosed"):
        validate_trace(tr.to_json())
    tr2 = TraceCollector()
    tr2.begin("a")
    tr2.end("b")
    with pytest.raises(ValueError, match="closes"):
        validate_trace(tr2.to_json())
    tr3 = TraceCollector()
    tr3.end("orphan")
    with pytest.raises(ValueError, match="E without B"):
        validate_trace(tr3.to_json())


def test_async_span_pairing_and_orphans():
    tr = TraceCollector()
    tr.begin_async("request", id=7)
    tr.instant_async("progress", id=7)
    tr.end_async("request", id=7)
    validate_trace(tr.to_json())
    tr2 = TraceCollector()
    tr2.instant_async("progress", id=9)   # n outside b..e
    with pytest.raises(ValueError, match="outside"):
        validate_trace(tr2.to_json())


def test_thread_ids_stable_and_named():
    tr = TraceCollector()

    def work(n):
        for _ in range(3):
            with tr.span(f"w{n}"):
                pass

    threads = [threading.Thread(target=work, args=(i,),
                                name=f"worker-{i}") for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    evs = tr.events()
    metas = [e for e in evs if e["ph"] == "M"]
    names = {e["args"]["name"] for e in metas}
    assert {"worker-0", "worker-1"} <= names
    # each worker keeps one stable small tid across all its events
    for n in range(2):
        tids = {e["tid"] for e in evs
                if e.get("name", "").startswith(f"w{n}")}
        assert len(tids) == 1
    validate_trace(tr.to_json())


def test_disabled_collector_records_nothing():
    tr = TraceCollector(enabled=False)
    with tr.span("x"):
        tr.instant("y")
    tr.begin_async("r", id=1)
    assert tr.events() == []


def test_bounded_buffer_drops_and_counts():
    tr = TraceCollector(max_events=3)
    for i in range(6):
        tr.instant(f"e{i}")
    assert len(tr.events()) == 3
    assert tr.dropped >= 2          # first event may be the M metadata
    tr.emit_many([{"ph": "i", "name": "x", "ts": 0.0, "pid": 1, "tid": 1}])
    assert tr.dropped >= 3
    assert tr.to_json()["otherData"]["dropped_events"] == tr.dropped


def test_trace_timestamps_monotone_per_thread():
    """Clock audit: all span timestamps come from one monotonic clock, so
    per-thread B/E ts must never go backwards (validate_trace enforces)."""
    tr = TraceCollector()
    for _ in range(50):
        with tr.span("tick"):
            pass
    evs = [e for e in tr.events() if e["ph"] in ("B", "E")]
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts)
    validate_trace(tr.to_json())


def test_save_emits_valid_json(tmp_path):
    tr = TraceCollector()
    with tr.span("x"):
        pass
    path = tr.save(str(tmp_path / "t.json"))
    payload = json.load(open(path))
    validate_trace(payload)
    assert payload["displayTimeUnit"] == "ms"


# ---------------------------------------------------------------------------
# Drift monitor
# ---------------------------------------------------------------------------

def test_drift_exactly_one_when_measured_equals_modeled():
    modeled = {"forward": 2e-3, "sampling": 1e-3, "tick": 3.2e-3}
    mon = DriftMonitor(modeled)
    for _ in range(5):
        mon.observe_tick(modeled)
    assert mon.scale == pytest.approx(1.0)
    for stage, ratio in mon.ratios().items():
        assert ratio == pytest.approx(1.0), stage


def test_drift_calibration_cancels_hardware_scale():
    """A uniformly 1000x slower host keeps every calibrated ratio at 1.0
    (the gauge measures stage-share drift, not the absolute gap)."""
    modeled = {"forward": 2e-3, "sampling": 1e-3}
    mon = DriftMonitor(modeled)
    mon.observe_tick({k: v * 1000.0 for k, v in modeled.items()})
    assert mon.scale == pytest.approx(1000.0)
    for ratio in mon.ratios().values():
        assert ratio == pytest.approx(1.0)


def test_drift_detects_stage_share_shift():
    modeled = {"forward": 2e-3, "sampling": 1e-3}
    mon = DriftMonitor(modeled)
    # sampling 4x its modeled share of the tick, forward on-model
    mon.observe_tick({"forward": 2e-3, "sampling": 4e-3})
    ratios = mon.ratios()
    assert ratios["sampling"] > 1.5
    assert ratios["forward"] < 1.0
    assert ratios["sampling"] / ratios["forward"] == pytest.approx(4.0)


def test_drift_unknown_stage_and_uncalibrated():
    mon = DriftMonitor({"forward": 1e-3}, calibrate=False)
    mon.observe("forward", 2e-3)
    mon.observe("mystery", 5e-3)
    assert mon.scale == 1.0
    assert mon.ratios()["forward"] == pytest.approx(2.0)
    assert mon.ratios()["mystery"] is None
    rep = mon.report()
    assert rep["ticks"] == 1 and "mystery" in rep["measured_mean_s"]
    with pytest.raises(ValueError):
        DriftMonitor({"forward": 0.0})


def test_modeled_tick_stages_covers_llada_config():
    from repro.configs import base
    from repro.core import diffusion

    cfg = base.get_config("llada-8b", smoke=True)
    dcfg = diffusion.DiffusionConfig(gen_length=16, block_length=8,
                                     steps_per_block=4, cache_mode="none")
    modeled = modeled_tick_stages(cfg, dcfg, batch=4, prompt_len=16)
    assert set(modeled) == {"forward", "sampling", "tick"}
    assert all(v > 0 for v in modeled.values())
    # per-tick stages must sum to no more than the roofline tick total
    assert modeled["forward"] + modeled["sampling"] <= \
        modeled["tick"] * 1.001
    lo, hi = HOST_DRIFT_BAND
    assert 0 < lo < 1 < hi


# ---------------------------------------------------------------------------
# ServingObs
# ---------------------------------------------------------------------------

def test_serving_obs_replica_views_share_registry():
    root = ServingObs()
    a, b = root.for_replica("replica-0"), root.for_replica("replica-1")
    a.tick({"forward": 1e-3}, 1e-3, 2, 0)
    b.tick({"forward": 2e-3}, 2e-3, 1, 3)
    b.tick({"forward": 2e-3}, 2e-3, 1, 3)
    parsed = parse_exposition(root.registry.expose())
    ticks = parsed["dllm_ticks_total"]
    assert ticks['{replica="replica-0"}'] == 1
    assert ticks['{replica="replica-1"}'] == 2
    assert parsed["dllm_queue_depth"]['{replica="replica-1"}'] == 3


def test_serving_obs_drift_gauge_exported():
    obs = ServingObs().for_replica("replica-0")
    obs.set_drift_model({"forward": 1e-3, "tick": 1e-3})
    obs.tick({"forward": 1e-3}, 1e-3, 1, 0)   # first tick refreshes
    parsed = parse_exposition(obs.registry.expose())
    drift = parsed["dllm_drift_ratio"]
    assert drift['{replica="replica-0",stage="forward"}'] == \
        pytest.approx(1.0)
    assert parsed["dllm_drift_scale"]['{replica="replica-0"}'] == \
        pytest.approx(1.0)


def test_serving_obs_request_lifecycle_and_trace():
    obs = ServingObs(trace=TraceCollector())
    obs.request_queued(3)
    obs.request_admitted(3, 0.25)
    obs.request_first_commit(3, 0.5)
    obs.block_committed(3, 0, 4, 2, positions=[1, 2], tokens=[7, 8])
    obs.tokens_committed(2)
    obs.request_done(3, 1.0, 8)
    validate_trace(obs.trace.to_json())
    parsed = parse_exposition(obs.registry.expose())
    req = parsed["dllm_requests_total"]
    assert req['{replica="replica-0",event="queued"}'] == 1
    assert req['{replica="replica-0",event="completed"}'] == 1
    ev = [e for e in obs.trace.events()
          if e.get("name") == "block_committed"][0]
    assert ev["args"]["positions"] == [1, 2]
    assert ev["args"]["tokens"] == [7, 8]
    assert ev["id"] == "3"


def test_frontend_metrics_counters():
    r = Registry()
    http, submits, overloaded = frontend_metrics(r)
    http.inc(route="/metrics", code="200")
    submits.inc(replica="replica-0")
    overloaded.inc()
    # idempotent second wiring (ServeFrontend + tests sharing a registry)
    http2, _, _ = frontend_metrics(r)
    assert http2 is http
    parsed = parse_exposition(r.expose())
    assert parsed["dllm_router_overloaded_total"][""] == 1


def test_policy_early_exit_counter():
    from repro.serving import SlowFastPolicy

    pol = SlowFastPolicy(threshold=0.5)
    slot = types.SimpleNamespace(step_in_block=1, block_masks_left=6,
                                 last_conf=0.9)
    assert pol.step_k(slot, 2) == 6
    assert pol.early_exits == 1
    # committing the scheduled remainder is not an early exit
    slot2 = types.SimpleNamespace(step_in_block=3, block_masks_left=2,
                                  last_conf=0.9)
    assert pol.step_k(slot2, 2) == 2
    assert pol.early_exits == 1


# ---------------------------------------------------------------------------
# MetricsTracker hardening
# ---------------------------------------------------------------------------

def test_metrics_summary_empty_tracker():
    from repro.serving.metrics import MetricsTracker

    m = MetricsTracker(num_slots=4)
    s = m.summary()
    assert s["requests_completed"] == 0 and s["ticks"] == 0
    assert s["tokens_per_s"] == 0.0 and s["slot_occupancy"] == 0.0
    assert m.format_summary()       # renders without dividing by zero


def test_metrics_summary_all_shed():
    from repro.serving.metrics import MetricsTracker

    m = MetricsTracker(num_slots=2)
    for uid in (1, 2):
        m.request_arrived(uid, 0.0, 16)
        m.request_shed(uid, 1.0)
    s = m.summary()
    assert s["requests_completed"] == 0
    assert s["requests_shed"] == 2
    assert s["shed_rate"] == 1.0
    assert s["ttft_p50_s"] == 0.0 and s["latency_p99_s"] == 0.0
    assert "shed: 2" in m.format_summary()


def test_metrics_summary_tolerates_mismatched_tick_lists():
    """A /metrics scrape can land between record_tick's two appends; the
    summary must truncate to the common length instead of crashing."""
    from repro.serving.metrics import MetricsTracker

    m = MetricsTracker(num_slots=1)
    m.record_tick(0.1, 1)
    m._tick_s.append(0.2)            # torn write: active not yet appended
    s = m.summary()
    assert s["ticks"] == 1
    assert s["busy_s"] == pytest.approx(0.1)


# ---------------------------------------------------------------------------
# Engine integration (smoke model)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine_setup():
    import jax

    from repro.configs import base
    from repro.core import diffusion
    from repro.models.registry import build_model

    cfg = base.get_config("llada-8b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    dcfg = diffusion.DiffusionConfig(gen_length=16, block_length=8,
                                     steps_per_block=4, cache_mode="none")
    return cfg, model, params, dcfg


def _run_instrumented(cfg, model, params, dcfg, n_requests=3, **eng_kw):
    import jax

    from repro.serving import Request, ServingEngine

    obs = ServingObs(trace=TraceCollector())
    eng = ServingEngine(model, params, dcfg, num_slots=2, max_seq_len=48,
                        mode="none", rng=jax.random.PRNGKey(0), obs=obs,
                        **eng_kw)
    rs = np.random.RandomState(0)
    events = []
    for i in range(n_requests):
        prompt = rs.randint(0, cfg.vocab - 2, size=(8,)).astype(np.int32)
        eng.submit(Request(uid=1 + i, prompt=prompt, gen_length=16),
                   on_commit=events.append)
    done = eng.run()
    return obs, eng, done, events


def test_engine_trace_matches_commit_events_bitforbit(engine_setup):
    """Acceptance: per-request block_committed trace events carry exactly
    the positions/tokens the SSE-visible CommitEvents carried."""
    cfg, model, params, dcfg = engine_setup
    obs, eng, done, events = _run_instrumented(cfg, model, params, dcfg)
    assert len(done) == 3
    validate_trace(obs.trace.to_json())
    sse = {(ev.uid, ev.block_idx): ev for ev in events
           if ev.masks_left == 0 and ev.positions is not None}
    traced = [e for e in obs.trace.events()
              if e.get("name") == "block_committed"]
    assert len(traced) == len(sse) == 6      # 3 requests x 2 blocks
    for e in traced:
        ev = sse[(int(e["id"]), e["args"]["block_idx"])]
        assert e["args"]["positions"] == [int(p) for p in ev.positions]
        assert e["args"]["tokens"] == [int(t) for t in ev.tokens]
        assert e["args"]["tick"] == ev.tick
        assert e["args"]["n_tokens"] == len(ev.positions)


def test_engine_counters_match_work_done(engine_setup):
    cfg, model, params, dcfg = engine_setup
    obs, eng, done, events = _run_instrumented(cfg, model, params, dcfg)
    parsed = parse_exposition(obs.registry.expose())
    assert parsed["dllm_tokens_committed_total"][
        '{replica="replica-0"}'] == 3 * 16
    assert parsed["dllm_blocks_committed_total"][
        '{replica="replica-0"}'] == 6
    assert parsed["dllm_ticks_total"]['{replica="replica-0"}'] == \
        eng.ticks_total
    req = parsed["dllm_requests_total"]
    for event in ("queued", "admitted", "completed"):
        assert req[f'{{replica="replica-0",event="{event}"}}'] == 3
    validate_histogram(parsed, "dllm_tick_seconds")
    validate_histogram(parsed, "dllm_tick_stage_seconds")
    # non-breakdown stage attribution: dispatch + device_sync present
    stage_count = parsed["dllm_tick_stage_seconds_count"]
    for stage in ("host_prep", "dispatch", "device_sync", "commit"):
        assert stage_count[
            f'{{replica="replica-0",stage="{stage}"}}'] == eng.ticks_total


def test_engine_breakdown_stages_and_summary(engine_setup):
    cfg, model, params, dcfg = engine_setup
    obs, eng, done, events = _run_instrumented(cfg, model, params, dcfg,
                                               breakdown=True)
    parsed = parse_exposition(obs.registry.expose())
    stage_count = parsed["dllm_tick_stage_seconds_count"]
    for stage in ("host_prep", "forward", "sampling", "host_sync",
                  "commit"):
        assert stage_count[
            f'{{replica="replica-0",stage="{stage}"}}'] == eng.ticks_total
    s = eng.metrics.summary()
    for stage in ("forward", "sampling", "host_prep", "commit"):
        assert s[f"stage_{stage}_s"] >= 0.0
    assert s["stage_forward_s"] > 0 and s["stage_sampling_s"] > 0


def test_engine_clock_audit_durations_nonnegative(engine_setup):
    """Clock audit: every duration the engine records comes from the
    monotonic clock and is non-negative; the virtual serving clock never
    runs backwards across ticks."""
    cfg, model, params, dcfg = engine_setup
    obs, eng, done, events = _run_instrumented(cfg, model, params, dcfg)
    assert all(t >= 0 for t in eng.metrics._tick_s)
    assert all(v >= 0 for v in eng.metrics.stage_s.values())
    assert eng.now >= 0
    for rec in eng.metrics.requests.values():
        assert rec.completed is None or rec.completed >= rec.arrival
        assert rec.admitted is None or rec.admitted >= rec.arrival
    # tick trace spans are emitted after their stage spans and must
    # still come out monotone per thread
    ts = [e["ts"] for e in obs.trace.events()
          if e["ph"] == "X" and e["name"] == "tick"]
    assert ts == sorted(ts)
