"""End-to-end behaviour tests for the full system (paper pipeline)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base
from repro.core import baos as baos_lib
from repro.core import diffusion, sampling
from repro.models.registry import build_model

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _child_env(tmp_path) -> dict:
    """The CLI child inherits this environment, stays on the CPU (another
    process may hold the TPU library), and keeps any compilation cache
    out of the checkout."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(REPO, "src"), env.get("PYTHONPATH")) if p)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "xla_cache")
    return env


def test_full_dart_pipeline_quality_preserved():
    """The paper's headline accuracy claim, container-scale: a trained tiny
    dLLM generates the same tokens under the full DART quantization stack
    (MXINT4 KV via BAOS + MXFP8 sampling) as under BF16 on >=60% of
    positions, and task accuracy is comparable."""
    from repro.optim import adamw
    cfg = base.get_config("llada-8b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    period, B, S = 4, 16, 64
    opt = adamw.OptConfig(lr=1e-2, schedule="const", warmup_steps=10)
    state = adamw.init_state(params)

    from repro.data.pipeline import motif_pool_batch

    def batch(i):
        return motif_pool_batch(i, period=period, batch=B, seq_len=S,
                                vocab=cfg.vocab)

    @jax.jit
    def step(p, s, toks, i):
        rng = jax.random.fold_in(jax.random.PRNGKey(1), i)
        (loss, _), g = jax.value_and_grad(
            lambda pp: diffusion.masked_diffusion_loss(model, pp, toks, rng),
            has_aux=True)(p)
        p, s, _ = adamw.apply_updates(p, g, s, opt)
        return p, s, loss

    for i in range(400):
        params, state, loss = step(params, state, batch(i), i)

    prompt = batch(999)[:4, :32]

    def gen(baos_cfg, fmt):
        d = diffusion.DiffusionConfig(
            gen_length=16, block_length=8, steps_per_block=4,
            cache_mode="dual", baos=baos_cfg,
            sampling=sampling.SamplingConfig(fmt=fmt))
        return np.asarray(diffusion.generate(
            model, params, prompt, d, rng=jax.random.PRNGKey(3))[:, 32:])

    ref = gen(baos_lib.BAOSConfig(enabled=False), "none")
    dart = gen(baos_lib.BAOSConfig(enabled=True, variant="minmax",
                                   kv_format="mxint4"), "mxfp8_e4m3")
    agreement = float((ref == dart).mean())
    assert agreement >= 0.6, f"agreement {agreement}"


@pytest.mark.parametrize("cache", ["prefix", "dual"])
def test_multi_block_generation_uses_committed_context(cache):
    """Later blocks must attend to earlier committed tokens: generation of a
    trained periodic model continues the motif across block boundaries."""
    from repro.optim import adamw
    cfg = base.get_config("qwen2-0.5b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    period, B, S = 4, 16, 64
    opt = adamw.OptConfig(lr=1e-2, schedule="const", warmup_steps=10)
    state = adamw.init_state(params)

    from repro.data.pipeline import motif_pool_batch

    def batch(i):
        return motif_pool_batch(i, period=period, batch=B, seq_len=S,
                                vocab=cfg.vocab)

    @jax.jit
    def step(p, s, toks, i):
        rng = jax.random.fold_in(jax.random.PRNGKey(1), i)
        (loss, _), g = jax.value_and_grad(
            lambda pp: diffusion.masked_diffusion_loss(model, pp, toks, rng),
            has_aux=True)(p)
        p, s, _ = adamw.apply_updates(p, g, s, opt)
        return p, s, loss

    for i in range(300):
        params, state, _ = step(params, state, batch(i), i)

    prompt = batch(998)[:4, :32]
    d = diffusion.DiffusionConfig(gen_length=16, block_length=8,
                                  steps_per_block=4, cache_mode=cache)
    out = np.asarray(diffusion.generate(model, params, prompt, d,
                                        rng=jax.random.PRNGKey(5)))
    target = np.asarray(prompt[:, :period])
    gen = out[:, 32:]
    acc = float((gen == np.tile(target, (1, 4))).mean())
    assert acc > 0.3, f"continuation acc {acc}"


def test_train_driver_cli(tmp_path):
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "qwen2-0.5b",
         "--steps", "6", "--batch", "2", "--seq", "32",
         "--ckpt-dir", str(tmp_path / "ckpt")],
        capture_output=True, text=True, timeout=600,
        env=_child_env(tmp_path), cwd=REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "done:" in out.stdout


def test_serve_driver_cli(tmp_path):
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "qwen2-0.5b",
         "--batch", "2", "--prompt-len", "16", "--gen-len", "16",
         "--block-len", "8", "--steps", "4", "--requests", "2"],
        capture_output=True, text=True, timeout=600,
        env=_child_env(tmp_path), cwd=REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "steady-state TPS" in out.stdout


def test_train_driver_failure_recovery_cli(tmp_path):
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "qwen2-0.5b",
         "--steps", "10", "--batch", "2", "--seq", "32", "--ckpt-every", "3",
         "--inject-failure-at", "5",
         "--ckpt-dir", str(tmp_path / "ckpt")],
        capture_output=True, text=True, timeout=600,
        env=_child_env(tmp_path), cwd=REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "restarts=1" in out.stdout
