"""Fused LM-head + Stable-Max path (docs/fused_sampling.md).

Covers: kernel-vs-oracle parity across sampling formats / suppression /
temperature (Pallas interpret mode, CPU CI), oracle-vs-unfused greedy
equivalence, the vocab-sharded combine, and the acceptance pin — greedy
tokens bit-identical across head_path in {fused, unfused, legacy} for both
``generate()`` and the serving engine."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base
from repro.core import diffusion, sampling
from repro.kernels import fused_head_sampling as fh
from repro.kernels import ops
from repro.models.layers import QuantPolicy
from repro.models.registry import build_model
from repro.serving import Request, ServingEngine

FMTS = ["none", "bf16", "mxfp8_e4m3"]


def _hw(seed, R=13, d=48, V=257, dtype=jnp.float32, scale=1.0):
    h = (jax.random.normal(jax.random.PRNGKey(seed), (R, d)) * 2).astype(dtype)
    w = (jax.random.normal(jax.random.PRNGKey(seed + 1), (d, V)) * scale
         ).astype(dtype)
    return h, w


# ---------------------------------------------------------------------------
# Oracle vs the unfused materialize-then-reduce reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fmt", FMTS)
@pytest.mark.parametrize("suppress", [None, 100])
def test_oracle_matches_unfused(fmt, suppress):
    h, w = _hw(0)
    logits = sampling.head_logits(h, w)
    c_ref, i_ref = sampling.stable_max(logits, fmt, suppress_id=suppress)
    c_fus, i_fus = sampling.fused_head_stable_max(
        h, w, fmt, suppress_id=suppress, chunk_v=64)
    np.testing.assert_array_equal(i_ref, i_fus)      # greedy tokens exact
    np.testing.assert_allclose(c_ref, c_fus, rtol=1e-6)


def test_oracle_matches_unfused_with_quant_policy():
    """The MX GEMM-boundary policy applies identically on both paths."""
    h, w = _hw(2)
    q = QuantPolicy(enabled=True)
    logits = sampling.head_logits(h, w, quant=q)
    c_ref, i_ref = sampling.stable_max(logits, "bf16")
    c_fus, i_fus = sampling.fused_head_stable_max(h, w, "bf16", quant=q,
                                                  chunk_v=64)
    np.testing.assert_array_equal(i_ref, i_fus)
    np.testing.assert_allclose(c_ref, c_fus, rtol=1e-6)


def test_oracle_logit_scale():
    h, w = _hw(3)
    c_ref, i_ref = sampling.stable_max(
        sampling.head_logits(h, w, logit_scale=0.25), "none")
    c_fus, i_fus = sampling.fused_head_stable_max(h, w, "none",
                                                  logit_scale=0.25,
                                                  chunk_v=96)
    np.testing.assert_array_equal(i_ref, i_fus)
    np.testing.assert_allclose(c_ref, c_fus, rtol=1e-6)


def test_sharded_partials_combine_equals_global():
    """Per-shard streamed partials merged with the sharded_stable_max rule
    reproduce the global fused result (no multi-device needed)."""
    h, w = _hw(4, V=512)
    nsh, vloc = 4, 512 // 4
    gm = gi = gs = None
    for sh in range(nsh):
        m, gidx, s = sampling.fused_head_local_partials(
            h, w[:, sh * vloc:(sh + 1) * vloc], "bf16",
            col_offset=sh * vloc, chunk_v=32)
        if gm is None:
            gm, gi, gs = m, gidx, s
        else:
            m_new = jnp.maximum(gm, m)
            gs = gs * jnp.exp(gm - m_new) + s * jnp.exp(m - m_new)
            gi = jnp.where(m > gm, gidx, gi)
            gm = m_new
    c_ref, i_ref = sampling.fused_head_stable_max(h, w, "bf16", chunk_v=32)
    np.testing.assert_array_equal(gi, i_ref)
    np.testing.assert_allclose(1.0 / gs, c_ref, rtol=1e-6)


def test_sharded_suppress_respects_global_column():
    h, w = _hw(5, V=128)
    sup = 70                                 # lives in shard 1 of 2
    m0, i0, s0 = sampling.fused_head_local_partials(
        h, w[:, :64], "none", col_offset=0, suppress_id=sup, chunk_v=32)
    m1, i1, s1 = sampling.fused_head_local_partials(
        h, w[:, 64:], "none", col_offset=64, suppress_id=sup, chunk_v=32)
    assert not bool(jnp.any(i1 == sup))
    m_new = jnp.maximum(m0, m1)
    gi = jnp.where(m1 > m0, i1, i0)
    c_ref, i_ref = sampling.fused_head_stable_max(h, w, "none",
                                                  suppress_id=sup,
                                                  chunk_v=32)
    np.testing.assert_array_equal(gi, i_ref)


# ---------------------------------------------------------------------------
# Pallas kernel vs oracle (interpret mode -> runs in CPU CI)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fmt", FMTS)
@pytest.mark.parametrize("suppress", [None, 100])
def test_kernel_matches_oracle(fmt, suppress):
    h, w = _hw(10)
    c_or, i_or = sampling.fused_head_stable_max(
        h, w, fmt, suppress_id=suppress, chunk_v=64)
    c_kn, i_kn = ops.fused_head_sampling(
        h, w, fmt=fmt, suppress_id=suppress, chunk_v=64)
    np.testing.assert_array_equal(i_or, i_kn)
    np.testing.assert_allclose(c_or, c_kn, rtol=1e-6)
    if suppress is not None:
        assert not bool(jnp.any(i_kn == suppress))


@pytest.mark.parametrize("d", [64, 896, 4096])
@pytest.mark.parametrize("R", [8, 32, 40, 300, 512, 2048])
def test_head_tiles(R, d):
    """The fewest equal row tiles, each padded by under 8 rows; a vocab
    chunk never past the caller's, cut to 128-lane multiples, and tiles
    inside the logit-tile and VMEM budgets."""
    for caller in (100, 2336, 4096):
        tile_r, chunk = ops.head_tiles(R, d, caller, 2)
        n = -(-R // tile_r)
        assert tile_r % 8 == 0 and tile_r <= ops.HEAD_ROW_TILE
        assert n == -(-R // ops.HEAD_ROW_TILE)
        assert n * tile_r - R < 8 * n
        assert chunk <= caller
        if chunk < caller:
            assert chunk % 128 == 0
        assert tile_r * chunk <= ops.HEAD_TILE_ELEMS
        assert fh.vmem_bytes(tile_r, chunk, d, 2) <= ops.HEAD_VMEM_BUDGET
    # the engine's ticks: one pass of LLaDA-8B's head, four of Qwen2's
    assert ops.head_tiles(512, 4096, 4096, 2) == (512, 256)
    assert ops.head_tiles(2048, 896, 2048, 2) == (512, 256)


@pytest.mark.parametrize("R,d,V", [(1, 32, 64), (8, 64, 512), (32, 48, 1000),
                                   (40, 64, 1000), (300, 48, 1000)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_shape_dtype_sweep(R, d, V, dtype):
    h, w = _hw(R + V, R=R, d=d, V=V, dtype=dtype)
    c_or, i_or = sampling.fused_head_stable_max(h, w, "mxfp8_e4m3",
                                                chunk_v=256)
    c_kn, i_kn = ops.fused_head_sampling(h, w, fmt="mxfp8_e4m3", chunk_v=256)
    np.testing.assert_array_equal(i_or, i_kn)
    np.testing.assert_allclose(c_or, c_kn, rtol=1e-6)


def test_kernel_mixed_dtype_matches_oracle():
    """bf16 hidden states with an f32 lm_head: the kernel must cast the
    weights into the activation dtype exactly like layers.qdot does."""
    h, _ = _hw(30, dtype=jnp.bfloat16)
    _, w = _hw(31, dtype=jnp.float32)
    c_ref, i_ref = sampling.stable_max(sampling.head_logits(h, w), "none")
    c_kn, i_kn = ops.fused_head_sampling(h, w, fmt="none", chunk_v=64)
    np.testing.assert_array_equal(i_ref, i_kn)
    np.testing.assert_allclose(c_ref, c_kn, rtol=1e-6)


def test_odd_chunk_width_rounds_to_mx_blocks():
    """chunk_v not a multiple of 32 is rounded down identically by oracle
    and kernel (no assert, no mis-tiled MX blocks)."""
    h, w = _hw(32, V=300)
    c_ref, i_ref = sampling.stable_max(
        sampling.head_logits(h, w), "mxfp8_e4m3")
    c_or, i_or = sampling.fused_head_stable_max(h, w, "mxfp8_e4m3",
                                                chunk_v=100)
    c_kn, i_kn = ops.fused_head_sampling(h, w, fmt="mxfp8_e4m3", chunk_v=100)
    np.testing.assert_array_equal(i_ref, i_or)
    np.testing.assert_array_equal(i_ref, i_kn)
    np.testing.assert_allclose(c_or, c_kn, rtol=1e-6)
    np.testing.assert_allclose(c_ref, c_or, rtol=1e-6)


@pytest.mark.parametrize("fmt", FMTS)
@pytest.mark.parametrize("R", [13, 600])
def test_kernel_temperature_matches_oracle(fmt, R):
    """Gumbel sampling: kernel and oracle share the counter-based noise
    stream, so the sampled tokens agree exactly given the same seed.  The
    draw is keyed by global row, so 8-row tiles draw what the chosen tiles
    (two of 304 rows at R=600) draw."""
    h, w = _hw(20, R=R)
    rng = jax.random.PRNGKey(9)
    seed = sampling.gumbel_seed(rng)
    c_or, i_or = sampling.fused_head_stable_max(
        h, w, fmt, rng=rng, temperature=0.8, suppress_id=5, chunk_v=64)
    c_kn, i_kn = ops.fused_head_sampling(
        h, w, fmt=fmt, temperature=0.8, suppress_id=5, seed=seed,
        chunk_v=64)
    np.testing.assert_array_equal(i_or, i_kn)
    np.testing.assert_allclose(c_or, c_kn, rtol=1e-6)
    assert not bool(jnp.any(i_kn == 5))
    c_8, i_8 = fh.fused_head_sampling(
        h, w, seed, tile_r=8, chunk_v=64, fmt=fmt, temperature=0.8,
        suppress_id=5, interpret=True)
    np.testing.assert_array_equal(i_8, i_kn)
    np.testing.assert_allclose(c_8, c_kn, rtol=1e-6)
    # conf is the softmax prob of the *sampled* token (LLaDA convention),
    # taken over the fmt-quantized logits
    from repro.core import mx
    logits = mx.mx_fake_quant(sampling.head_logits(h, w), fmt)
    z = jnp.where(jnp.arange(w.shape[-1]) == 5, sampling.NEG_INF,
                  jax.numpy.asarray(logits, jnp.float32))
    p = jax.nn.softmax(z, -1)
    np.testing.assert_allclose(
        c_or, np.take_along_axis(np.asarray(p), np.asarray(i_or)[:, None],
                                 1)[:, 0], rtol=1e-4)


def test_counter_gumbel_moments():
    """The hash-counter Gumbel stream has roughly Gumbel(0,1) moments."""
    g = sampling.counter_gumbel(jnp.uint32(123),
                                jnp.arange(64)[:, None],
                                jnp.arange(256)[None, :])
    mean, std = float(jnp.mean(g)), float(jnp.std(g))
    assert abs(mean - 0.5772) < 0.05         # Euler-Mascheroni
    assert abs(std - 1.2825) < 0.05          # pi/sqrt(6)
    # distinct seeds decorrelate
    g2 = sampling.counter_gumbel(jnp.uint32(124),
                                 jnp.arange(64)[:, None],
                                 jnp.arange(256)[None, :])
    assert float(jnp.corrcoef(g.ravel(), g2.ravel())[0, 1]) < 0.05


# ---------------------------------------------------------------------------
# Acceptance pin: greedy bit-identity across head paths, end to end
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def setup():
    cfg = base.get_config("llada-8b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                cfg.vocab - 2)
    return cfg, model, params, prompt


@pytest.mark.parametrize("cache", ["none", "dual", "prefix"])
def test_generate_bit_identical_across_head_paths(setup, cache):
    cfg, model, params, prompt = setup
    outs = {}
    for hp in ["fused", "unfused", "legacy"]:
        dcfg = diffusion.DiffusionConfig(
            gen_length=16, block_length=8, steps_per_block=4,
            cache_mode=cache, head_path=hp, head_chunk=96)
        outs[hp] = np.asarray(diffusion.generate(
            model, params, prompt, dcfg, rng=jax.random.PRNGKey(7)))
    np.testing.assert_array_equal(outs["fused"], outs["legacy"])
    np.testing.assert_array_equal(outs["unfused"], outs["legacy"])


def test_engine_fused_bit_identical_to_legacy_generate(setup):
    """A one-slot fused engine reproduces legacy (pre-fusion) generate()
    greedy tokens bit-for-bit — the PR's acceptance pin."""
    cfg, model, params, prompt = setup
    ref = diffusion.generate(
        model, params, prompt[:1],
        diffusion.DiffusionConfig(gen_length=16, block_length=8,
                                  steps_per_block=4, cache_mode="none",
                                  head_path="legacy"),
        rng=jax.random.PRNGKey(11))
    dcfg = diffusion.DiffusionConfig(gen_length=16, block_length=8,
                                     steps_per_block=4, cache_mode="none",
                                     head_path="fused", head_chunk=96)
    eng = ServingEngine(model, params, dcfg, num_slots=1, max_seq_len=32,
                        mode="none", rng=jax.random.PRNGKey(99))
    done = eng.run([Request(uid=1, prompt=np.asarray(prompt[0]),
                            gen_length=16)])
    np.testing.assert_array_equal(done[0].tokens, np.asarray(ref[0]))


def test_fused_step_without_rng_is_greedy_on_both_backends(setup):
    """temperature > 0 with rng=None must decode greedily (stable_max's
    gating) on the oracle AND kernel routes — not sample from a constant
    seed-0 Gumbel stream."""
    cfg, model, params, _ = setup
    h = jax.random.normal(jax.random.PRNGKey(6), (2, 8, cfg.d_model)) * 0.5
    w = params["lm_head"]
    x = jnp.full((2, 8), cfg.mask_id, jnp.int32)
    k = jnp.full((2,), 8, jnp.int32)
    scfg = sampling.SamplingConfig(fmt="none", temperature=0.9)
    greedy = sampling.SamplingConfig(fmt="none", temperature=0.0)
    x_ref, _, _ = sampling.fused_sampling_step_full(
        h, w, x, cfg.mask_id, k, greedy, jax.random.PRNGKey(0), chunk_v=96)
    for use_kernel in [False, True]:
        x_t, _, _ = sampling.fused_sampling_step_full(
            h, w, x, cfg.mask_id, k, scfg, None, chunk_v=96,
            use_kernel=use_kernel)
        np.testing.assert_array_equal(np.asarray(x_ref), np.asarray(x_t))


def test_kernel_unsupported_fmt_falls_back_to_oracle(setup):
    """Sampling formats outside the kernel's set (e.g. mxint8) run on the
    lax.scan oracle off the chip; where the kernel path is taken (the
    default on TPU) they raise instead of quietly leaving the kernel."""
    cfg, model, params, _ = setup
    h = jax.random.normal(jax.random.PRNGKey(8), (2, 8, cfg.d_model)) * 0.5
    w = params["lm_head"]
    x = jnp.full((2, 8), cfg.mask_id, jnp.int32)
    k = jnp.full((2,), 8, jnp.int32)
    scfg = sampling.SamplingConfig(fmt="mxint8")
    x_ref, _, _ = sampling.sampling_step_full(
        sampling.head_logits(h, w), x, cfg.mask_id, k, scfg)
    x_fus, _, _ = sampling.fused_sampling_step_full(
        h, w, x, cfg.mask_id, k, scfg, chunk_v=96, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(x_ref), np.asarray(x_fus))
    with pytest.raises(ValueError, match="mxint8"):
        sampling.fused_sampling_step_full(
            h, w, x, cfg.mask_id, k, scfg, chunk_v=96, use_kernel=True)


def test_quant_policy_reaches_jitted_ticks(setup):
    """A QuantPolicy in fwd_kw must be bound statically into the jitted
    step/tick fns (it is not a jax type) and must change the output —
    engine and generate() agree under quantization, all head paths."""
    cfg, model, params, prompt = setup
    q = QuantPolicy(enabled=True)
    outs = {}
    for hp in ["fused", "unfused", "legacy"]:
        dcfg = diffusion.DiffusionConfig(
            gen_length=16, block_length=8, steps_per_block=4,
            cache_mode="none", head_path=hp, head_chunk=96)
        outs[hp] = np.asarray(diffusion.generate(
            model, params, prompt, dcfg, rng=jax.random.PRNGKey(7), quant=q))
    np.testing.assert_array_equal(outs["fused"], outs["legacy"])
    np.testing.assert_array_equal(outs["unfused"], outs["legacy"])
    dcfg = diffusion.DiffusionConfig(gen_length=16, block_length=8,
                                     steps_per_block=4, cache_mode="none",
                                     head_path="fused", head_chunk=96)
    for breakdown in [False, True]:
        eng = ServingEngine(model, params, dcfg, num_slots=1, max_seq_len=32,
                            mode="none", rng=jax.random.PRNGKey(99),
                            breakdown=breakdown, fwd_kw={"quant": q})
        done = eng.run([Request(uid=1, prompt=np.asarray(prompt[0]),
                                gen_length=16)])
        np.testing.assert_array_equal(done[0].tokens, outs["fused"][0])
    # and quantization does change the trajectory vs the unquantized run
    noq = np.asarray(diffusion.generate(
        model, params, prompt, dcfg, rng=jax.random.PRNGKey(7)))
    assert (noq != outs["fused"]).any()


def test_fused_sampling_step_matches_unfused(setup):
    """fused_sampling_step_full == sampling_step_full(head_logits(...))
    on tokens *and* transfer mask for greedy decoding."""
    cfg, model, params, _ = setup
    B, L, d = 2, 8, cfg.d_model
    h = jax.random.normal(jax.random.PRNGKey(5), (B, L, d)) * 0.5
    w = params["lm_head"]
    x = jnp.full((B, L), cfg.mask_id, jnp.int32).at[:, 0].set(7)
    k = jnp.array([3, 5], jnp.int32)
    scfg = sampling.SamplingConfig(fmt="mxfp8_e4m3")
    x_ref, t_ref, c_ref = sampling.sampling_step_full(
        sampling.head_logits(h, w), x, cfg.mask_id, k, scfg)
    x_fus, t_fus, c_fus = sampling.fused_sampling_step_full(
        h, w, x, cfg.mask_id, k, scfg, chunk_v=96)
    np.testing.assert_array_equal(np.asarray(x_ref), np.asarray(x_fus))
    np.testing.assert_array_equal(np.asarray(t_ref), np.asarray(t_fus))
    np.testing.assert_allclose(c_ref, c_fus, rtol=1e-6)
