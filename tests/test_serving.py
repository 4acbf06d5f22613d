"""Serving engine: state machine resumability, engine/generate equivalence,
continuous batching, slot-pool reuse, and scheduler policies."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base
from repro.core import diffusion
from repro.models.registry import build_model
from repro.serving import (CachePool, EngineConfig, FIFOPolicy, Request,
                           ServingEngine, ShortestGenFirstPolicy,
                           SlowFastPolicy, get_policy)


@pytest.fixture(scope="module")
def setup():
    cfg = base.get_config("llada-8b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _prompt(cfg, seed, n):
    return jax.random.randint(jax.random.PRNGKey(seed), (1, n), 0,
                              cfg.vocab - 2)


def _dcfg(cache="none", gen=16, block=8, steps=4):
    return diffusion.DiffusionConfig(gen_length=gen, block_length=block,
                                     steps_per_block=steps, cache_mode=cache)


# ---------------------------------------------------------------------------
# State machine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cache", ["none", "prefix", "dual"])
def test_manual_stepping_matches_generate(setup, cache):
    """Driving (init_state, step) by hand reproduces generate() exactly and
    exposes the per-step counters a serving engine needs."""
    cfg, model, params = setup
    dcfg = _dcfg(cache)
    prompt = _prompt(cfg, 1, 16)
    ref = diffusion.generate(model, params, prompt, dcfg,
                             rng=jax.random.PRNGKey(7))
    state = diffusion.init_state(model, prompt, dcfg,
                                 rng=jax.random.PRNGKey(7))
    seen = []
    while not state.done:
        seen.append((state.block_idx, state.step_in_block))
        state = diffusion.step(model, params, state)
    assert seen == [(b, t) for b in range(2) for t in range(4)]
    np.testing.assert_array_equal(np.asarray(state.tokens), np.asarray(ref))
    with pytest.raises(ValueError):
        diffusion.step(model, params, state)


def test_state_is_resumable_mid_block(setup):
    """A state captured mid-request continues to the same tokens as an
    uninterrupted run (the property continuous batching relies on)."""
    cfg, model, params = setup
    dcfg = _dcfg("dual")
    prompt = _prompt(cfg, 2, 16)
    s1 = diffusion.init_state(model, prompt, dcfg, rng=jax.random.PRNGKey(3))
    for _ in range(3):                    # stop mid-block (T=4)
        s1 = diffusion.step(model, params, s1)
    snapshot = dataclasses.replace(s1)
    while not s1.done:
        s1 = diffusion.step(model, params, s1)
    s2 = snapshot
    while not s2.done:
        s2 = diffusion.step(model, params, s2)
    np.testing.assert_array_equal(np.asarray(s1.x), np.asarray(s2.x))


# ---------------------------------------------------------------------------
# Engine vs generate()
# ---------------------------------------------------------------------------

def test_engine_bit_identical_to_generate_single_request(setup):
    """Acceptance: a one-slot engine (no padding) produces tokens
    bit-identical to generate() for a greedy request — both run the same
    jitted batched_tick executable."""
    cfg, model, params = setup
    dcfg = _dcfg("none")
    prompt = _prompt(cfg, 5, 16)
    ref = diffusion.generate(model, params, prompt, dcfg,
                             rng=jax.random.PRNGKey(11))
    eng = ServingEngine(model, params, dcfg, num_slots=1, max_seq_len=32,
                        mode="none", rng=jax.random.PRNGKey(99))
    done = eng.run([Request(uid=1, prompt=np.asarray(prompt[0]),
                            gen_length=16)])
    assert len(done) == 1
    np.testing.assert_array_equal(done[0].tokens, np.asarray(ref[0]))


@pytest.mark.parametrize("max_seq_len", [32, 48, 64])
def test_engine_tokens_independent_of_canvas_padding(setup, max_seq_len):
    """A request's tokens do not depend on how much masked canvas padding
    follows it: the padded positions are hidden from attention by
    kv_valid, so every max_seq_len gives generate()'s tokens."""
    cfg, model, params = setup
    dcfg = _dcfg("none")
    prompt = _prompt(cfg, 5, 16)
    ref = diffusion.generate(model, params, prompt, dcfg,
                             rng=jax.random.PRNGKey(11))
    eng = ServingEngine(model, params, dcfg, EngineConfig(
        num_slots=1, max_seq_len=max_seq_len, mode="none"))
    done = eng.run([Request(prompt=np.asarray(prompt[0]), gen_length=16)])
    np.testing.assert_array_equal(done[0].tokens, np.asarray(ref[0]))


@pytest.mark.parametrize("mode", ["none", "warm"])
def test_engine_multi_request_mixed_lengths(setup, mode):
    """Mixed prompt/gen lengths interleave in shared ticks: every request
    completes fully unmasked with its prompt intact, and requests overlap
    (total ticks < sum of per-request ticks)."""
    cfg, model, params = setup
    dcfg = _dcfg("dual" if mode == "warm" else "none")
    eng = ServingEngine(model, params, dcfg, num_slots=2, max_seq_len=48,
                        mode=mode, rng=jax.random.PRNGKey(0))
    rs = np.random.RandomState(0)
    reqs = [Request(uid=1 + i,
                    prompt=rs.randint(0, cfg.vocab - 2,
                                      size=(8 + 4 * i,)).astype(np.int32),
                    gen_length=8 * (1 + i % 2))
            for i in range(4)]
    done = eng.run(list(reqs))
    assert len(done) == 4
    by_uid = {c.uid: c for c in done}
    total_req_ticks = 0
    for r in reqs:
        c = by_uid[r.uid]
        np.testing.assert_array_equal(c.tokens[:r.prompt_len], r.prompt)
        assert not (c.tokens[r.prompt_len:] == cfg.mask_id).any()
        total_req_ticks += c.ticks
    assert eng.metrics.summary()["ticks"] < total_req_ticks


def test_engine_queues_beyond_slots_and_reuses_pool(setup):
    """More requests than slots: the queue drains through slot reuse and
    the pool acquire/release accounting balances."""
    cfg, model, params = setup
    dcfg = _dcfg("dual", gen=8)
    eng = ServingEngine(model, params, dcfg, num_slots=2, max_seq_len=24,
                        mode="warm", rng=jax.random.PRNGKey(0))
    reqs = [Request(uid=1 + i, prompt=np.asarray(_prompt(cfg, 20 + i, 8)[0]),
                    gen_length=8) for i in range(5)]
    done = eng.run(reqs)
    assert len(done) == 5
    stats = eng.pool.stats()
    assert stats == {"num_slots": 2, "in_use": 0, "acquires": 5,
                     "releases": 5, "peak_in_use": 2}
    for c in done:
        assert not (c.tokens[c.prompt_len:] == cfg.mask_id).any()


def test_engine_rejects_invalid_requests(setup):
    cfg, model, params = setup
    eng = ServingEngine(model, params, _dcfg("none"), num_slots=1,
                        max_seq_len=32, mode="none")
    with pytest.raises(ValueError):
        eng.submit(Request(uid=1, prompt=np.zeros(8, np.int32),
                           gen_length=12))      # not a block multiple
    with pytest.raises(ValueError):
        eng.submit(Request(uid=2, prompt=np.zeros(30, np.int32),
                           gen_length=16))      # exceeds max_seq_len


def test_engine_rejects_duplicate_and_nonpositive_uids(setup):
    """A duplicate uid would silently overwrite the slot_of_uid + metrics
    entries of the request already using it — reject at submit."""
    cfg, model, params = setup
    eng = ServingEngine(model, params, _dcfg("none", gen=8), num_slots=1,
                        max_seq_len=24, mode="none")
    req = Request(uid=7, prompt=np.zeros(8, np.int32), gen_length=8)
    eng.submit(req)
    with pytest.raises(ValueError, match="duplicate"):
        eng.submit(Request(uid=7, prompt=np.zeros(4, np.int32),
                           gen_length=8))
    eng.run()                                   # drain uid=7 to completion
    with pytest.raises(ValueError, match="duplicate"):
        eng.submit(Request(uid=7, prompt=np.zeros(8, np.int32),
                           gen_length=8))       # uids are never recycled
    for bad in (0, -3, 1.5, "9"):
        with pytest.raises(ValueError, match="positive"):
            eng.submit(Request(uid=bad, prompt=np.zeros(8, np.int32),
                               gen_length=8))
    # uid=None is the auto-assign path: submit mints a fresh unused uid,
    # writes it onto the request, and returns it
    auto = eng.submit(Request(prompt=np.zeros(8, np.int32), gen_length=8))
    assert isinstance(auto, int) and auto > 0 and auto != 7
    eng.cancel(auto)


def test_engine_cancel_only_queued_requests(setup):
    """cancel() sheds a still-queued request (metrics record it) but never
    interrupts admitted work or unknown uids."""
    cfg, model, params = setup
    eng = ServingEngine(model, params, _dcfg("none", gen=8), num_slots=1,
                        max_seq_len=24, mode="none")
    r1 = Request(uid=1, prompt=np.zeros(8, np.int32), gen_length=8)
    r2 = Request(uid=2, prompt=np.zeros(8, np.int32), gen_length=8)
    eng.submit(r1)
    eng.submit(r2)
    eng.tick()                                  # r1 admitted, r2 queued
    assert eng.cancel(1) is False               # admitted: not cancellable
    assert eng.cancel(99) is False              # unknown uid
    assert eng.cancel(2) is True
    assert eng.cancel(2) is False               # already shed
    done = eng.run()
    assert [c.uid for c in done] == [1]
    s = eng.metrics.summary()
    assert s["requests_shed"] == 1
    assert 0 < s["shed_rate"] < 1


# ---------------------------------------------------------------------------
# Cache pool
# ---------------------------------------------------------------------------

def test_cache_pool_accounting(setup):
    cfg, model, params = setup
    pool = CachePool(model, num_slots=3, max_seq_len=16)
    assert pool.cache["k"].shape[1] == 3        # one row per slot
    a = pool.acquire()
    b = pool.acquire()
    assert {a, b} == {0, 1} and pool.in_use == 2
    pool.release(a)
    with pytest.raises(ValueError):
        pool.release(a)                          # double release
    assert pool.acquire() == a                   # freed slot is reused
    pool2 = CachePool(model, num_slots=1, max_seq_len=8, with_cache=False)
    assert pool2.cache is None and pool2.free_slots == 1


# ---------------------------------------------------------------------------
# Scheduler policies
# ---------------------------------------------------------------------------

def test_policy_admission_ordering():
    q = [Request(uid=0, prompt=np.zeros(4, np.int32), gen_length=32),
         Request(uid=1, prompt=np.zeros(4, np.int32), gen_length=8),
         Request(uid=2, prompt=np.zeros(4, np.int32), gen_length=16)]
    assert FIFOPolicy().select(q, 0.0) == 0
    assert ShortestGenFirstPolicy().select(q, 0.0) == 1
    assert get_policy("sjf").name == "sgf"
    with pytest.raises(ValueError):
        get_policy("nope")


def test_sgf_policy_orders_engine_admissions(setup):
    """With 1 slot, shortest-gen-first admits the short queued request
    before the longer one that arrived earlier."""
    cfg, model, params = setup
    dcfg = _dcfg("none", gen=8)
    eng = ServingEngine(model, params, dcfg, num_slots=1, max_seq_len=40,
                        mode="none", policy=ShortestGenFirstPolicy())
    reqs = [Request(uid=1, prompt=np.asarray(_prompt(cfg, 30, 8)[0]),
                    gen_length=8),
            Request(uid=2, prompt=np.asarray(_prompt(cfg, 31, 8)[0]),
                    gen_length=32),
            Request(uid=3, prompt=np.asarray(_prompt(cfg, 32, 8)[0]),
                    gen_length=8)]
    done = eng.run(reqs)
    order = [c.uid for c in done]
    assert order == [1, 3, 2]                   # uid=3 jumps the long uid=2


def test_slowfast_early_exit_reduces_ticks(setup):
    """threshold=-inf-like (0.0) always triggers after the first step of a
    block, so each block finishes in 2 ticks instead of steps_per_block."""
    cfg, model, params = setup
    dcfg = _dcfg("none", gen=16, block=8, steps=8)
    prompt = np.asarray(_prompt(cfg, 40, 8)[0])

    def run(policy):
        eng = ServingEngine(model, params, dcfg, num_slots=1, max_seq_len=24,
                            mode="none", policy=policy,
                            rng=jax.random.PRNGKey(0))
        done = eng.run([Request(uid=1, prompt=prompt, gen_length=16)])
        assert not (done[0].tokens[8:] == cfg.mask_id).any()
        return done[0].ticks

    default_ticks = run(FIFOPolicy())
    fast_ticks = run(SlowFastPolicy(threshold=0.0))
    assert default_ticks == 2 * 8               # num_blocks * steps_per_block
    assert fast_ticks == 2 * 2                  # 1 probe + 1 flush per block
    strict_ticks = run(SlowFastPolicy(threshold=2.0))  # conf <= 1 never fires
    assert strict_ticks == default_ticks


def test_slowfast_step_k_edge_cases():
    """step_k must fall back to the schedule at block boundaries and on
    garbage confidence values — never early-exit on them."""
    import dataclasses as dc

    @dc.dataclass
    class Slot:
        step_in_block: int = 3
        block_masks_left: int = 5
        last_conf: float = 0.95

    pol = SlowFastPolicy(threshold=0.9)
    assert pol.step_k(Slot(), 2) == 5           # convergent: flush block
    # block start: last_conf belongs to the previous block -> schedule
    assert pol.step_k(Slot(step_in_block=0), 2) == 2
    # nothing left to commit in this block -> schedule
    assert pol.step_k(Slot(block_masks_left=0), 2) == 2
    # non-finite confidence (block-start -inf, overflow inf, NaN) never
    # triggers the early exit
    assert pol.step_k(Slot(last_conf=float("-inf")), 2) == 2
    assert pol.step_k(Slot(last_conf=float("inf")), 2) == 2
    assert pol.step_k(Slot(last_conf=float("nan")), 2) == 2
    assert pol.step_k(Slot(last_conf=0.5), 2) == 2   # below threshold


# ---------------------------------------------------------------------------
# Commit-callback streaming hook
# ---------------------------------------------------------------------------

def test_commit_callback_streams_exact_token_sets(setup):
    """The per-tick CommitEvents partition the generation region, carry
    the exact committed tokens, tick monotonically, and end with a done
    event whose final_tokens equal the CompletedRequest."""
    cfg, model, params = setup
    dcfg = _dcfg("none", gen=16, block=8, steps=4)
    prompt = np.asarray(_prompt(cfg, 60, 12)[0])
    eng = ServingEngine(model, params, dcfg, num_slots=2, max_seq_len=32,
                        mode="none", rng=jax.random.PRNGKey(0))
    events = []
    eng.submit(Request(uid=1, prompt=prompt, gen_length=16),
               on_commit=events.append)
    eng.submit(Request(uid=2, prompt=prompt.copy(), gen_length=8))  # no cb
    done = eng.run()
    by_uid = {c.uid: c for c in done}

    assert all(ev.uid == 1 for ev in events)    # uid=2 never streams
    ticks = [ev.tick for ev in events]
    assert ticks == sorted(ticks) and len(set(ticks)) == len(ticks)
    assert [ev.done for ev in events] == [False] * (len(events) - 1) + [True]
    # commit sets partition [prompt_len, total) exactly once
    all_pos = np.concatenate([ev.positions for ev in events])
    assert sorted(all_pos.tolist()) == list(range(12, 28))
    final = by_uid[1].tokens
    for ev in events:
        np.testing.assert_array_equal(ev.tokens, final[ev.positions])
        assert ev.masks_left == 0 or len(ev.positions) > 0
    np.testing.assert_array_equal(events[-1].final_tokens, final)
    # block_idx is non-decreasing and ends on the last block
    blocks = [ev.block_idx for ev in events]
    assert blocks == sorted(blocks) and blocks[-1] == 1


def test_commit_callback_masks_left_and_block_structure(setup):
    """masks_left hits 0 exactly once per block and resets across the
    block boundary (the out-of-order commit window is one block wide)."""
    cfg, model, params = setup
    dcfg = _dcfg("none", gen=16, block=8, steps=4)
    eng = ServingEngine(model, params, dcfg, num_slots=1, max_seq_len=32,
                        mode="none")
    events = []
    eng.submit(Request(uid=1, prompt=np.asarray(_prompt(cfg, 61, 8)[0]),
                       gen_length=16), on_commit=events.append)
    eng.run()
    boundary = [ev for ev in events if ev.masks_left == 0]
    assert len(boundary) == 2                   # one per block
    for ev in events:
        in_block = [p - 8 - ev.block_idx * 8 for p in ev.positions]
        assert all(0 <= q < 8 for q in in_block), \
            "commits leaked outside the active block"


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------

def test_metrics_summary_fields(setup):
    cfg, model, params = setup
    eng = ServingEngine(model, params, _dcfg("none", gen=8), num_slots=2,
                        max_seq_len=24, mode="none", breakdown=True)
    reqs = [Request(uid=1 + i, prompt=np.asarray(_prompt(cfg, 50 + i, 8)[0]),
                    gen_length=8, arrival_time=0.0) for i in range(3)]
    eng.run(reqs)
    s = eng.metrics.summary()
    assert s["requests_completed"] == 3
    assert s["gen_tokens"] == 24
    assert s["tokens_per_s"] > 0
    assert 0 < s["slot_occupancy"] <= 1
    assert s["latency_p99_s"] >= s["latency_p50_s"] > 0
    assert s["stage_forward_s"] > 0 and s["stage_sampling_s"] > 0
    text = eng.metrics.format_summary()
    assert "steady-state TPS" in text and "p99" in text


def test_metrics_ttft_and_goodput(setup):
    """TTFT (first committed tokens) is recorded for every request,
    bounded by admission wait and end-to-end latency, and goodput counts
    completed tokens over the elapsed wall window."""
    cfg, model, params = setup
    eng = ServingEngine(model, params, _dcfg("none", gen=16), num_slots=1,
                        max_seq_len=32, mode="none")
    reqs = [Request(uid=1 + i, prompt=np.asarray(_prompt(cfg, 70 + i, 8)[0]),
                    gen_length=16) for i in range(3)]
    eng.run(reqs)
    s = eng.metrics.summary()
    assert s["ttft_p99_s"] >= s["ttft_p50_s"] > 0
    # with a 1-slot engine later requests queue: TTFT p99 ~ latency of the
    # requests ahead + one tick, and is always <= full latency
    assert s["ttft_p99_s"] <= s["latency_p99_s"]
    for rec in eng.metrics.requests.values():
        assert rec.first_commit is not None
        assert rec.admitted <= rec.first_commit <= rec.completed
    assert s["goodput_tok_s"] > 0
    assert s["requests_shed"] == 0 and s["shed_rate"] == 0.0
    text = eng.metrics.format_summary()
    assert "TTFT" in text and "goodput" in text


def test_metrics_compaction_preserves_totals(setup):
    """compact() bounds per-request/per-tick state for server lifetimes
    while keeping totals exact and duplicate-uid rejection intact."""
    cfg, model, params = setup
    eng = ServingEngine(model, params, _dcfg("none", gen=8), num_slots=2,
                        max_seq_len=24, mode="none")
    reqs = [Request(uid=1 + i, prompt=np.asarray(_prompt(cfg, 80 + i, 8)[0]),
                    gen_length=8) for i in range(6)]
    eng.run(reqs)
    before = eng.metrics.summary()
    eng.metrics.compact(keep=2)             # fold all but 2 finished
    assert len(eng.metrics.requests) == 2
    assert len(eng.metrics._tick_s) <= 2
    after = eng.metrics.summary()
    for key in ("requests_completed", "gen_tokens", "ticks",
                "requests_shed", "shed_rate"):
        assert after[key] == before[key], key
    assert after["busy_s"] == pytest.approx(before["busy_s"])
    assert after["slot_occupancy"] == pytest.approx(
        before["slot_occupancy"])
    with pytest.raises(ValueError, match="duplicate"):
        eng.submit(Request(uid=1, prompt=np.zeros(8, np.int32),
                           gen_length=8))   # folded uid still rejected
