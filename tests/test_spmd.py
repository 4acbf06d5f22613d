"""SPMD sharded tick: greedy parity vs the single-device fused path across
(data, model) debug mesh shapes, sharded-sampling building blocks, and the
serving-clock/rng bugfix batch riding along in the same PR.

Multi-device shapes need forced host devices *before* jax initializes:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m pytest -q tests/test_spmd.py

Under the plain tier-1 run (1 CPU device) those shapes skip; the (1, 1)
mesh still exercises the full shard_map plumbing.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base
from repro.core import diffusion, sampling as sampling_lib
from repro.launch.mesh import make_debug_mesh
from repro.models.registry import build_model
from repro.serving import Request, ServingEngine, get_policy

MESHES = [(1, 1), (2, 1), (1, 4), (2, 2)]


@pytest.fixture(scope="module")
def setup():
    cfg = base.get_config("llada-8b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _skip_unless(n_devices: int):
    if jax.device_count() < n_devices:
        pytest.skip(f"needs {n_devices} devices (XLA_FLAGS="
                    f"--xla_force_host_platform_device_count=8)")


def _dcfg(gen=16, block=8, steps=4, cache="none"):
    return diffusion.DiffusionConfig(gen_length=gen, block_length=block,
                                     steps_per_block=steps, cache_mode=cache)


# ---------------------------------------------------------------------------
# Tentpole: SPMD tick parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("data,model_ax", MESHES)
def test_generate_spmd_bit_identical(setup, data, model_ax):
    """Acceptance: greedy generate() under every debug mesh shape produces
    tokens bit-identical to the single-device fused head path — the smoke
    vocab (257) is not divisible by the model axis, so this also pins the
    MX-block-aligned head padding + col_limit masking."""
    _skip_unless(data * model_ax)
    cfg, model, params = setup
    dcfg = _dcfg()
    prompt = jax.random.randint(jax.random.PRNGKey(1), (4, 12), 0,
                                cfg.vocab - 2)
    ref = diffusion.generate(model, params, prompt, dcfg,
                             rng=jax.random.PRNGKey(7))
    out = diffusion.generate(model, params, prompt, dcfg,
                             rng=jax.random.PRNGKey(7),
                             mesh=make_debug_mesh(data, model_ax))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("mode", ["none", "warm"])
@pytest.mark.parametrize("data,model_ax", MESHES)
def test_engine_spmd_bit_identical(setup, data, model_ax, mode):
    """A mesh engine (both tick modes, mixed gen lengths) completes the
    same requests with bit-identical tokens to the single-device engine."""
    _skip_unless(data * model_ax)
    cfg, model, params = setup
    dcfg = _dcfg(cache="dual" if mode == "warm" else "none")
    rs = np.random.RandomState(3)
    reqs = [Request(uid=1 + i,
                    prompt=rs.randint(0, cfg.vocab - 2,
                                      size=(8 + 2 * i,)).astype(np.int32),
                    gen_length=8 * (1 + i % 2)) for i in range(4)]

    def run(mesh):
        eng = ServingEngine(model, params, dcfg, num_slots=2, max_seq_len=32,
                            mode=mode, rng=jax.random.PRNGKey(0), mesh=mesh)
        done = eng.run([Request(uid=r.uid, prompt=r.prompt,
                                gen_length=r.gen_length) for r in reqs])
        return {c.uid: c.tokens for c in done}

    ref = run(None)
    got = run(make_debug_mesh(data, model_ax))
    assert set(got) == set(ref)
    for uid in ref:
        np.testing.assert_array_equal(got[uid], ref[uid])


def test_sharded_stable_max_matches_dense(setup):
    """The combine primitives under an explicit shard_map reproduce dense
    stable_max over an uneven (padded) vocab."""
    _skip_unless(4)
    from jax.sharding import PartitionSpec as P
    V, d = 257, 32
    h = jax.random.normal(jax.random.PRNGKey(0), (8, d), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (d, V), jnp.float32) * 0.1
    conf_ref, idx_ref = sampling_lib.fused_head_stable_max(
        h, w, "mxfp8_e4m3", suppress_id=V - 1)
    wp = sampling_lib.pad_head_for_mesh(w, 4)
    assert wp.shape[-1] % (4 * 32) == 0
    mesh = make_debug_mesh(1, 4)

    def body(h, w_shard):
        return sampling_lib.sharded_fused_head_stable_max(
            h, w_shard, "model", "mxfp8_e4m3", suppress_id=V - 1,
            col_limit=V)

    conf, idx = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(), P(None, "model")),
        out_specs=(P(), P())))(h, wp)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx_ref))
    np.testing.assert_allclose(np.asarray(conf), np.asarray(conf_ref),
                               rtol=1e-5)


def test_spmd_rejects_bad_configs(setup):
    cfg, model, params = setup
    mesh = make_debug_mesh(1, 1)
    with pytest.raises(ValueError, match="head_path='fused'"):
        diffusion.get_spmd_tick_fn(
            model, diffusion.DiffusionConfig(head_path="legacy"),
            cfg.mask_id, mesh)
    with pytest.raises(NotImplementedError, match="greedy"):
        diffusion.get_spmd_tick_fn(
            model, diffusion.DiffusionConfig(
                sampling=sampling_lib.SamplingConfig(temperature=0.7)),
            cfg.mask_id, mesh)
    with pytest.raises(ValueError, match="cache_mode='none'"):
        diffusion.generate(model, params, jnp.zeros((1, 8), jnp.int32),
                           _dcfg(cache="dual"), mesh=mesh)


def test_engine_rejects_indivisible_slots(setup):
    _skip_unless(2)
    cfg, model, params = setup
    with pytest.raises(ValueError, match="divisible"):
        ServingEngine(model, params, _dcfg(), num_slots=3, max_seq_len=32,
                      mode="none", mesh=make_debug_mesh(2, 1))


# ---------------------------------------------------------------------------
# Satellite bugfixes
# ---------------------------------------------------------------------------

def test_warmup_keeps_clock_and_metrics_clean(setup):
    """warmup() compiles the tick without touching now/metrics/rng/canvas,
    and the warmed engine's first *timed* tick carries no compile time."""
    cfg, model, params = setup
    dcfg = _dcfg(gen=8)
    # fresh model objects force fresh jit cache keys -> real compiles
    cold_model = build_model(cfg)
    warm_model = build_model(cfg)
    req = Request(uid=1, prompt=np.zeros(8, np.int32), gen_length=8)

    cold = ServingEngine(cold_model, params, dcfg, num_slots=1,
                         max_seq_len=16, mode="none")
    cold.submit(Request(uid=1, prompt=req.prompt, gen_length=8))
    t0 = time.perf_counter()
    cold.tick()
    cold_first = time.perf_counter() - t0

    warm = ServingEngine(warm_model, params, dcfg, num_slots=1,
                         max_seq_len=16, mode="none")
    rng_before = np.asarray(warm.rng)
    assert warm.warmup() is warm
    assert warm.now == 0.0
    assert warm.metrics.summary()["ticks"] == 0
    np.testing.assert_array_equal(np.asarray(warm.rng), rng_before)
    warm.submit(Request(uid=1, prompt=req.prompt, gen_length=8))
    t0 = time.perf_counter()
    warm.tick()
    warm_first = time.perf_counter() - t0
    # first cold tick pays trace+compile (~seconds); a warmed tick is ~ms
    assert warm_first < cold_first / 2
    assert 0.0 < warm.now <= warm_first        # clock got tick time only
    assert warm.now < cold_first / 2           # ... and no compile time


def test_kv_valid_uploaded_once_per_tick(setup):
    """Admitting/releasing N requests costs at most one (num_slots,
    max_seq_len) host->device upload per tick, not one per request."""
    cfg, model, params = setup
    eng = ServingEngine(model, params, _dcfg(gen=8), num_slots=2,
                        max_seq_len=24, mode="warm")
    reqs = [Request(uid=1 + i, prompt=np.full((8,), i, np.int32), gen_length=8)
            for i in range(5)]
    done = eng.run(reqs)
    assert len(done) == 5
    ticks = eng.metrics.summary()["ticks"]
    assert 1 <= eng.kv_valid_uploads <= ticks


def test_num_blocks_raises_value_error():
    with pytest.raises(ValueError, match="multiple of"):
        diffusion.DiffusionConfig(gen_length=10, block_length=8).num_blocks
    assert diffusion.DiffusionConfig(gen_length=16,
                                     block_length=8).num_blocks == 2


def test_serve_cli_policy_and_mesh_flags():
    from repro.launch import serve
    ap = serve.build_parser()
    args = ap.parse_args(["--policy", "sjf"])
    assert get_policy(args.policy).name == "sgf"      # sjf alias round-trip
    args = ap.parse_args(["--mesh", "2,4"])
    assert args.mesh == "2,4"
    with pytest.raises(SystemExit):
        ap.parse_args(["--policy", "nope"])


def test_legacy_serve_rng_decorrelated(monkeypatch, setup):
    """run_legacy draws the synthetic prompt and the generate() rng chain
    from *different* split keys."""
    cfg, model, params = setup
    from repro.launch import serve
    seen = {}
    real_randint = jax.random.randint

    def spy_randint(key, *a, **kw):
        seen["prompt_key"] = np.asarray(key)
        return real_randint(key, *a, **kw)

    real_generate = diffusion.generate

    def spy_generate(model, params, prompt, dcfg, rng=None, **kw):
        seen["gen_key"] = np.asarray(rng)
        return real_generate(model, params, prompt, dcfg, rng=rng, **kw)

    monkeypatch.setattr(jax.random, "randint", spy_randint)
    monkeypatch.setattr(serve.diffusion, "generate", spy_generate)
    args = serve.build_parser().parse_args(
        ["--batch", "1", "--prompt-len", "8", "--gen-len", "8",
         "--block-len", "8", "--steps", "2", "--requests", "1",
         "--cache", "none", "--no-baos", "--legacy"])
    serve.run_legacy(args, cfg, model, params, serve.make_dcfg(args))
    assert not np.array_equal(seen["prompt_key"], seen["gen_key"])
