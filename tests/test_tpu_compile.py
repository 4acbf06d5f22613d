"""Compile the main path for a TPU v5e that is described, not attached.

The TPU compiler is installed with jaxlib, so these tests lower and compile
the Pallas kernels and one whole engine tick at LLaDA-8B widths for a
``v5e:2x2`` topology from a CPU host.  They catch what interpret mode
cannot: block shapes Mosaic cannot tile, in-kernel ops it cannot lay out
or cast, and VMEM overflows.  Nothing runs, so they say nothing about
results or speed.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and the test worker that runs this
file keeps it until it exits.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs import base
from repro.core import baos as baos_lib
from repro.core import diffusion
from repro.core import sampling as sampling_lib
from repro.kernels import fused_head_sampling as fh
from repro.kernels import ops
from repro.kernels import topk_mask as tk
from repro.models.registry import build_model

D, VOCAB, ROWS = 4096, 126464, 512      # LLaDA-8B head; 16 slots x block 32
MASK_ID = 126336
# the engine's vocab chunk (DiffusionConfig.head_chunk)
HEAD_CHUNK = diffusion.DiffusionConfig.head_chunk


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """Lower as the chip would: the library picks its Pallas kernels (not
    interpret mode, not the jnp oracles) from jax.default_backend()."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_head(one_chip, fmt, temperature, rows=ROWS, d=D, vocab=VOCAB,
                  chunk_v=HEAD_CHUNK):
    """The head as the engine calls it (ops.fused_head_sampling picks the
    tiles), lowered for the chip; also returns the kernel's grid."""
    def head(h, w, seed):
        return ops.fused_head_sampling(h, w, fmt=fmt, temperature=temperature,
                                       seed=seed, suppress_id=MASK_ID,
                                       chunk_v=chunk_v, interpret=False)
    args = (_spec((rows, d), jnp.bfloat16, one_chip),
            _spec((d, vocab), jnp.bfloat16, one_chip),
            _spec((), jnp.uint32, one_chip))
    jaxpr = jax.make_jaxpr(head)(*args)
    grids = [e.params["grid_mapping"].grid
             for e in _eqns(jaxpr.jaxpr) if e.primitive.name == "pallas_call"]
    return jax.jit(head).lower(*args).compile(), grids


def _eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("fmt", fh.SUPPORTED_FMTS)
def test_fused_head_compiles_at_llada_width(one_chip, fmt):
    """At the engine's tick, 512 rows: at most two row tiles, so the
    (4096, 126464) head is streamed at most twice a call."""
    compiled, grids = _compile_head(one_chip, fmt, 0.0)
    assert "tpu_custom_call" in compiled.as_text()
    assert len(grids) == 1 and grids[0][0] <= 2


def test_fused_head_compiles_with_temperature(one_chip):
    """The in-kernel counter-Gumbel draw (uint32 hash -> f32) lowers."""
    compiled, _ = _compile_head(one_chip, "mxfp8_e4m3", 0.7)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("temperature", [0.0, 0.7])
@pytest.mark.parametrize("fmt", fh.SUPPORTED_FMTS)
@pytest.mark.parametrize("chunk_v", [2048, 4096])
def test_fused_head_compiles_at_qwen2_width(one_chip, chunk_v, fmt,
                                            temperature):
    """Qwen2-0.5B's tick: 64 slots x 32 rows over its (896, 151936) head,
    at the benchmark's chunk and at the engine's."""
    compiled, _ = _compile_head(one_chip, fmt, temperature, rows=2048,
                                d=896, vocab=151936, chunk_v=chunk_v)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rows", [16, 64])
def test_topk_mask_compiles(one_chip, rows):
    compiled = jax.jit(tk.topk_mask).lower(
        _spec((rows, 32), jnp.float32, one_chip),
        _spec((rows, 32), jnp.int32, one_chip),
        _spec((rows,), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _two_layer_llada():
    """LLaDA-8B widths at 2 layers with serve.py's defaults (BAOS mxint4
    KV, mxfp8 sampling, block 32, 16 steps)."""
    cfg = dataclasses.replace(base.get_config("llada-8b"), n_layers=2)
    dcfg = diffusion.DiffusionConfig(
        gen_length=64, block_length=32, steps_per_block=16,
        sampling=sampling_lib.SamplingConfig(fmt="mxfp8_e4m3"),
        baos=baos_lib.BAOSConfig(enabled=True, kv_format="mxint4"))
    return cfg, build_model(cfg), dcfg


def test_batched_tick_compiles_with_kernels_on_path(one_chip, on_tpu):
    """One warm engine tick at LLaDA-8B widths, 2 layers, 16 slots x 256
    positions: the fused-head and top-k kernels sit inside it."""
    cfg, model, dcfg = _two_layer_llada()
    B, S = 16, 256

    def place(tree):
        return jax.tree.map(lambda a: _spec(a.shape, a.dtype, one_chip), tree)

    params = place(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(lambda: model.init_cache(B, S)))
    tick = diffusion.get_tick_fn.__wrapped__(model, dcfg, cfg.mask_id)
    compiled = tick.lower(
        params, _spec((B, S), jnp.int32, one_chip),
        _spec((B, S), jnp.bool_, one_chip), _spec((B,), jnp.int32, one_chip),
        _spec((B,), jnp.int32, one_chip),
        _spec((2,), jnp.uint32, one_chip), cache).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2     # fused head + top-k mask


@pytest.mark.parametrize("shape", [(1, 4), (2, 2)])
def test_spmd_tick_compiles_on_four_chips(topo, on_tpu, shape):
    """The shard_mapped warm tick on a (data, model) mesh over the four
    described chips, LM-head columns over 'model': it compiles with the
    collectives of the Stable-Max combine."""
    cfg, model, dcfg = _two_layer_llada()
    mesh = Mesh(np.array(topo.devices).reshape(shape), ("data", "model"))
    B, S = 8 * shape[0], 256

    def spec(a, pspec):
        return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                    sharding=NamedSharding(mesh, pspec))

    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = {k: jax.tree.map(
        lambda a, k=k: spec(a, P(None, "model") if k == "lm_head" else P()),
        v) for k, v in shapes.items()}
    cache = jax.tree.map(lambda a: spec(a, P(None, "data")),
                         jax.eval_shape(lambda: model.init_cache(B, S)))
    row = jax.ShapeDtypeStruct((B, S), jnp.int32)
    tick = diffusion.get_spmd_tick_fn.__wrapped__(model, dcfg, cfg.mask_id,
                                                  mesh)
    compiled = tick.lower(
        params, spec(row, P("data", None)),
        spec(jax.ShapeDtypeStruct((B, S), jnp.bool_), P("data", None)),
        spec(jax.ShapeDtypeStruct((B,), jnp.int32), P("data")),
        spec(jax.ShapeDtypeStruct((B,), jnp.int32), P("data")),
        spec(jax.ShapeDtypeStruct((2,), jnp.uint32), P()), cache).compile()
    assert "all-reduce" in compiled.as_text()
