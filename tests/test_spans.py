"""The program's marks for the profiler: ``dllm.*`` host spans of the
engine, the worker loop and the HTTP frontend (repro.obs.tracing.Spans),
the ``forward`` / ``sampling`` / ``commit`` name scopes of the tick on the
device, and the commit -> SSE delivery histogram."""
import asyncio
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base
from repro.core import diffusion
from repro.models.registry import build_model
from repro.obs import (ServingObs, Spans, TraceCollector, parse_exposition,
                       validate_trace)
from repro.serving import EngineConfig, Request, ServingEngine
from repro.serving.frontend import build_frontend, loadgen

TICK_STAGES = ("dllm.host_prep", "dllm.dispatch", "dllm.device_sync",
               "dllm.commit")


@pytest.fixture(scope="module")
def setup():
    cfg = base.get_config("llada-8b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    dcfg = diffusion.DiffusionConfig(gen_length=16, block_length=8,
                                     steps_per_block=4, cache_mode="none")
    return cfg, model, params, dcfg


def _prompt(cfg, seed, n=8):
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (n,), 0, cfg.vocab - 2), np.int32)


def test_spans_sum_stages_and_feed_the_collector():
    tr = TraceCollector()
    sp = Spans(tr, cat="engine")
    with sp.stage("dllm.dispatch"):
        with sp.span("dllm.admit", uid=3) as inner:
            inner.set(n=2)
    with sp.stage("dllm.dispatch"):
        pass
    seconds = sp.take()
    assert list(seconds) == ["dispatch"] and seconds["dispatch"] >= 0
    assert sp.take() == {}
    ev = [e for e in tr.events() if e["ph"] == "X"]
    assert [e["name"] for e in ev] == ["admit", "dispatch", "dispatch"]
    assert ev[0]["args"] == {"uid": 3, "n": 2}
    assert all(e["cat"] == "engine" and e["dur"] >= 0 for e in ev)
    validate_trace(tr.to_json())
    # without a collector a span still measures
    with Spans().stage("dllm.commit") as s:
        pass
    assert s.t1 >= s.t0


def test_engine_stage_spans_in_the_trace_out(setup):
    """--trace-out keeps its schema: per tick a ``tick`` span and the
    stage spans, which the stage histogram counts once per tick."""
    cfg, model, params, dcfg = setup
    obs = ServingObs(trace=TraceCollector())
    eng = ServingEngine(model, params, dcfg, EngineConfig(
        num_slots=2, max_seq_len=48, mode="none", obs=obs))
    events = []
    for i in range(2):
        eng.submit(Request(prompt=_prompt(cfg, i), gen_length=16),
                   on_commit=events.append)
    eng.run()
    payload = obs.trace.to_json()
    validate_trace(payload)
    x = [e for e in payload["traceEvents"] if e["ph"] == "X"]
    names = [e["name"] for e in x]
    assert names.count("tick") == eng.ticks_total
    for stage in ("host_prep", "admit", "dispatch", "device_sync", "commit",
                  "canvas_fetch"):
        assert names.count(stage) == eng.ticks_total, stage
    parsed = parse_exposition(obs.registry.expose())
    count = parsed["dllm_tick_stage_seconds_count"]
    assert {k.split('stage="')[1][:-2] for k in count} == {
        "host_prep", "dispatch", "device_sync", "commit"}
    # each tick span opens with its host_prep stage
    prep = sorted(e["ts"] for e in x if e["name"] == "host_prep")
    assert sorted(e["ts"] for e in x if e["name"] == "tick") == prep


def _xplane_spans(d):
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("dllm."):
                    out.append((e.start_ns, e.end_ns, e.name,
                                dict(e.stats)))
    return sorted(out, key=lambda s: (s[0], -s[1]))


def _inside(a, b):
    return b[0] <= a[0] and a[1] <= b[1]


def test_profiler_trace_holds_engine_and_frontend_spans(setup, tmp_path):
    """A jax.profiler session over one streamed request: per tick the
    engine's stage spans in order, admit inside host_prep, the canvas
    fetch inside commit; the worker's submit span, and one sse_write span
    per SSE event the request received; and the delivery histogram
    counts each streamed commit once."""
    cfg, model, params, dcfg = setup
    fe = build_frontend(model, params, dcfg, model_name="llada-8b",
                        mode="none", max_seq_len=48, replicas=1,
                        num_slots=1)

    async def go():
        await fe.start()
        jax.profiler.start_trace(str(tmp_path))
        try:
            return await loadgen.complete(fe.url, _prompt(cfg, 7).tolist(),
                                          16)
        finally:
            # the worker joins first: a span still open when the profiler
            # stops is not recorded
            await fe.shutdown()
            jax.profiler.stop_trace()

    row = asyncio.run(go())
    assert row["status"] == "ok"
    spans = _xplane_spans(str(tmp_path))
    eng = fe.router.workers[0].engine
    ticks = eng.ticks_total
    stages = [s for s in spans if s[2] in TICK_STAGES]
    # the tick's stages tile it in order, tick after tick
    seq = [s[2] for s in stages]
    starts = [i for i, n in enumerate(seq) if n == "dllm.dispatch"]
    assert len(starts) == ticks
    for i in starts:
        assert seq[i - 1:i + 3] == list(TICK_STAGES)
        assert all(stages[j][1] <= stages[j + 1][0]
                   for j in range(i - 1, i + 2))
    by = {n: [s for s in spans if s[2] == n] for n in
          ("dllm.host_prep", "dllm.admit", "dllm.commit",
           "dllm.canvas_fetch", "dllm.submit", "dllm.sse_write",
           "dllm.router.submit", "dllm.worker_sync", "dllm.engine_tick")}
    # each tick lies inside one of the worker's tick calls
    assert all(any(_inside(s, t) for t in by["dllm.engine_tick"])
               for s in stages)
    assert all(any(_inside(a, h) for h in by["dllm.host_prep"])
               for a in by["dllm.admit"])
    assert len(by["dllm.canvas_fetch"]) == ticks       # a streamed request
    assert all(any(_inside(f, c) for c in by["dllm.commit"])
               for f in by["dllm.canvas_fetch"])
    assert [s[3].get("requests") for s in by["dllm.submit"]
            if "requests" in s[3]] == [1]
    assert len(by["dllm.router.submit"]) == 1 and by["dllm.worker_sync"]
    # one sse_write per block_committed event plus the done event
    writes = by["dllm.sse_write"]
    assert len(writes) == len(row["ticks"]) + 1
    assert len({s[3]["uid"] for s in writes}) == 1
    count = parse_exposition(fe.obs.registry.expose())[
        "dllm_delivery_seconds_count"]
    assert count['{replica="replica-0"}'] == len(row["ticks"])


def test_tick_programs_carry_the_stage_scopes(setup):
    """The compiled K=1 tick, megatick and paged tick name the forward,
    sampling and commit scopes in their op metadata."""
    cfg, model, params, dcfg = setup
    B = 2
    z = jnp.zeros((B,), jnp.int32)
    srng = jax.random.split(jax.random.PRNGKey(0))[1]

    def engine(**kw):
        return ServingEngine(model, params, dcfg, EngineConfig(
            num_slots=B, max_seq_len=48, mode="none", **kw))

    eng = engine()
    texts = {"tick": eng._tick_fn.lower(
        params, eng.x, eng.kv_valid, z, z, srng, None)}
    mega = engine(megatick_k=2)
    state = diffusion.megatick_state(z, z, dcfg,
                                     active=np.zeros((B,), bool))
    texts["megatick"] = mega._megatick_fn.lower(
        params, mega.x, mega.kv_valid, state, jax.random.PRNGKey(0),
        jnp.int32(1), jnp.asarray(False), None)
    paged = engine(pool="paged", page_size=8)
    pool = paged.pool
    texts["paged"] = paged._tick_fn.lower(
        params, pool.canvas_pages, None, pool.canvas_table, pool.kv_table,
        paged.kv_valid, z, z, srng)
    for name, lowered in texts.items():
        hlo = lowered.compile().as_text()
        for scope in ("forward", "sampling", "commit"):
            assert f"/{scope}/" in hlo, (name, scope)
