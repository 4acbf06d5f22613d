"""Blocked diffusion inference + masked-diffusion training objective.

Implements the full dLLM pipeline of paper §2 / Alg. 2 on top of any model
exposing the `forward(params, tokens, cache, seg_start, ...)` contract:

  * generation proceeds block-autoregressively over N_B blocks of length L;
  * each block begins with a **warm step**: full-sequence bidirectional
    forward that (re)computes KV for *all* positions, writes the smoothed/
    quantized cache, and serves as the BAOS online-calibration point;
  * T-1 **refinement steps** then run per cache mode:
      - "dual":   process only the active block (KV replaced in place;
                  suffix KV frozen from the warm step),
      - "prefix": process block + suffix (fresh suffix KV each step),
      - "none":   full-sequence recompute every step (Block Diffusion);
  * each step ends with the Stable-Max sampling stage committing the top-k
    most confident tokens of the active block.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import baos as baos_lib
from repro.core import sampling as sampling_lib
from repro.core import schedule as schedule_lib
from repro.sim import trace as trace_lib


@dataclasses.dataclass(frozen=True)
class DiffusionConfig:
    gen_length: int = 128
    block_length: int = 32
    steps_per_block: int = 8
    cache_mode: str = "dual"          # none | prefix | dual
    # LM-head routing for the sampling stage (docs/fused_sampling.md):
    #   fused   — stream the head GEMM into the online Stable-Max reduction
    #             (logits never in HBM); greedy tokens bit-identical to
    #             the unfused path (pinned by tests/test_fused_head.py)
    #   unfused — slice active-block hidden states (B, L, d) first, then
    #             materialize at most (B, L, V) block logits
    #   legacy  — pre-head-fusion behavior: full logits out of forward()
    # Models without supports_head_mode silently fall back to "legacy".
    head_path: str = "fused"
    head_chunk: int = 4096            # vocab tile width of the fused stream
    sampling: sampling_lib.SamplingConfig = sampling_lib.SamplingConfig()
    baos: baos_lib.BAOSConfig = baos_lib.BAOSConfig(enabled=False)

    @property
    def num_blocks(self) -> int:
        if self.gen_length % self.block_length:
            raise ValueError(
                f"gen_length {self.gen_length} must be a multiple of "
                f"block_length {self.block_length}")
        return self.gen_length // self.block_length


def head_feed_mode(model, dcfg: "DiffusionConfig") -> str:
    """Resolve the sampling-stage feed for ``model``: 'fused'/'unfused'
    (active blocks sliced at the hidden level, head applied after) or
    'logits' (legacy full-logits forward) for models without head_mode."""
    if dcfg.head_path not in ("fused", "unfused", "legacy"):
        raise ValueError(f"unknown head_path {dcfg.head_path!r}")
    if dcfg.head_path != "legacy" and getattr(model, "supports_head_mode",
                                              False):
        return dcfg.head_path
    return "logits"


# ---------------------------------------------------------------------------
# Inference
# ---------------------------------------------------------------------------

def _active_mask(batch: int, s_tot: int, block_start, block_len: int):
    pos = jnp.arange(s_tot, dtype=jnp.int32)[None, :]
    m = (pos >= block_start) & (pos < block_start + block_len)
    return jnp.broadcast_to(m, (batch, s_tot))


def warm_step(model, params, x: jax.Array, cache, block_start,
              dcfg: DiffusionConfig, head_mode: str = "logits", **fwd_kw):
    """Full-sequence forward; returns (active-block logits — or, with
    ``head_mode='hidden'``, pre-head hidden states (B, L, d) — new cache)."""
    B, s_tot = x.shape
    L = dcfg.block_length
    calib_mask = (_active_mask(B, s_tot, block_start, L)
                  if dcfg.baos.calib_scope == "active_block" else None)
    extra = {} if head_mode == "logits" else {"head_mode": head_mode}
    feats, cache, _ = model.forward(
        params, tokens=x, cache=cache, seg_start=0,
        baos_cfg=dcfg.baos, calibrate=True, calib_mask=calib_mask,
        logits_slice=(block_start, L), **extra, **fwd_kw)
    return feats, cache


def refine_step(model, params, x: jax.Array, cache, block_start,
                dcfg: DiffusionConfig, suffix_len: int = 0,
                head_mode: str = "logits", **fwd_kw):
    """One refinement forward (paper Fig. 4).

    dual:   segment = active block (suffix_len = 0)
    prefix: segment = active block + suffix (suffix_len = s_tot - end)
    Returns (active-block logits or hidden states per ``head_mode``,
    new cache).
    """
    L = dcfg.block_length
    seg_len = L + suffix_len
    seg = jax.lax.dynamic_slice_in_dim(x, block_start, seg_len, axis=1)
    extra = {} if head_mode == "logits" else {"head_mode": head_mode}
    feats, cache, _ = model.forward(
        params, tokens=seg, cache=cache, seg_start=block_start,
        baos_cfg=dcfg.baos, calibrate=False,
        logits_slice=(0, L), **extra, **fwd_kw)
    return feats, cache


# ---------------------------------------------------------------------------
# Resumable per-request state machine
#
# ``generate()`` below is a thin loop over (init_state, step); the serving
# engine (repro.serving) drives the same machine one step at a time so
# requests at different block/step offsets can share an engine tick.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DiffusionState:
    """Everything needed to resume blocked-diffusion decoding of one request.

    ``x`` is the full canvas (prompt + masked generation region), ``cache``
    the KV cache pytree (None for cache_mode='none'), ``ks`` the per-block
    transfer schedule (B, steps_per_block).  ``block_idx``/``step_in_block``
    are host-side ints so the driving loop stays un-traced.
    """
    x: jax.Array
    cache: Any
    rng: jax.Array
    ks: jax.Array
    dcfg: DiffusionConfig
    mask_id: int
    prompt_len: int
    block_idx: int = 0
    step_in_block: int = 0

    @property
    def done(self) -> bool:
        return self.block_idx >= self.dcfg.num_blocks

    @property
    def block_start(self) -> int:
        return self.prompt_len + self.block_idx * self.dcfg.block_length

    @property
    def tokens(self) -> jax.Array:
        return self.x


def init_state(model, prompt: jax.Array, dcfg: DiffusionConfig,
               rng: Optional[jax.Array] = None,
               mask_id: Optional[int] = None) -> DiffusionState:
    """Build the step-0 state for a (batched) request: masked canvas, fresh
    KV cache, per-block transfer schedule, rng chain."""
    mask_id = model.cfg.mask_id if mask_id is None else mask_id
    B, P = prompt.shape
    s_tot = P + dcfg.gen_length
    x = jnp.concatenate(
        [prompt.astype(jnp.int32),
         jnp.full((B, dcfg.gen_length), mask_id, jnp.int32)], axis=1)
    cache = model.init_cache(B, s_tot) if dcfg.cache_mode != "none" else None
    ks = schedule_lib.get_num_transfer_tokens(
        jnp.full((B,), dcfg.block_length, jnp.int32), dcfg.steps_per_block)
    return DiffusionState(
        x=x, cache=cache,
        rng=rng if rng is not None else jax.random.PRNGKey(0),
        ks=ks, dcfg=dcfg, mask_id=mask_id, prompt_len=P)


def _active_sampling_step(feats, xa, k, step_rng, params, mode: str,
                          dcfg: DiffusionConfig, mask_id: int, model,
                          quant=None, axis_name: Optional[str] = None):
    """Route one active block through the selected head path.

    feats is (B, L, V) block logits (mode='logits') or (B, L, d) pre-head
    hidden states (mode 'fused'/'unfused').  Returns the full
    (new tokens, transfer, conf) triple of ``sampling_step_full``.

    With ``axis_name`` (inside shard_map) ``params['lm_head']`` is this
    chip's (d, V/n) column shard: the streamed partials merge over the
    mesh axis and ``col_limit`` masks the head's zero-pad columns."""
    if mode == "logits":
        return sampling_lib.sampling_step_full(
            feats, xa, mask_id, k, dcfg.sampling, step_rng)
    scale = float(model.cfg.logit_scale)
    if axis_name is not None:
        if mode != "fused":
            raise ValueError("the SPMD tick requires head_path='fused'")
        return sampling_lib.sharded_fused_sampling_step_full(
            feats, params["lm_head"], xa, mask_id, k, dcfg.sampling,
            step_rng, axis_name=axis_name, logit_scale=scale, quant=quant,
            chunk_v=dcfg.head_chunk, col_limit=int(model.cfg.vocab))
    if mode == "fused":
        return sampling_lib.fused_sampling_step_full(
            feats, params["lm_head"], xa, mask_id, k, dcfg.sampling,
            step_rng, logit_scale=scale, quant=quant,
            chunk_v=dcfg.head_chunk)
    # unfused fallback: head applied *after* the (B, L, d) slice, so at
    # most (B, L, V) block logits ever exist (never (B, S, V))
    logits = sampling_lib.head_logits(
        feats, params["lm_head"], logit_scale=scale, quant=quant)
    return sampling_lib.sampling_step_full(
        logits, xa, mask_id, k, dcfg.sampling, step_rng)


@functools.lru_cache(maxsize=64)
def _cached_commit_fn(model, dcfg: DiffusionConfig, mask_id: int, mode: str,
                      quant, jit_steps: bool):
    """Jitted active-block commit (head + Stable-Max + scatter-back) shared
    across generate() calls and serving engines, keyed like the step fns."""
    L = dcfg.block_length

    def commit(params, feats, x, bs, k, step_rng):
        xa = jax.lax.dynamic_slice_in_dim(x, bs, L, axis=1)
        xa_new, _, _ = _active_sampling_step(
            feats, xa, k, step_rng, params, mode, dcfg, mask_id, model,
            quant=quant)
        return jax.lax.dynamic_update_slice_in_dim(x, xa_new, bs, axis=1)

    return jax.jit(commit) if jit_steps else commit


@functools.lru_cache(maxsize=64)
def _cached_step_fn(model, dcfg: DiffusionConfig, kind: str, suffix_len: int,
                    jit_steps: bool, head_mode: str = "logits", quant=None):
    """Per-(model, dcfg) jitted forward for one step kind.  Cached at module
    level so generate() calls and long-lived serving engines share compiles.
    The GEMM-boundary ``quant`` policy is part of the cache key and bound
    statically — a QuantPolicy is not a jax type and must never reach a
    jitted function as a runtime argument."""
    if kind == "warm":
        fn = functools.partial(warm_step, model, dcfg=dcfg,
                               head_mode=head_mode, quant=quant)
    elif kind == "refine":
        fn = functools.partial(refine_step, model, dcfg=dcfg,
                               suffix_len=suffix_len, head_mode=head_mode,
                               quant=quant)
    else:
        raise ValueError(kind)
    return jax.jit(fn) if jit_steps else fn


def step(model, params, state: DiffusionState, jit_steps: bool = True,
         mesh=None, **fwd_kw) -> DiffusionState:
    """Advance one denoising step (one forward + one sampling commit).

    Mirrors the inner loop of paper Alg. 2 exactly: warm step at
    step_in_block==0, refinement (per cache mode) afterwards, Stable-Max
    commit of ks[:, t] tokens, one rng split per step.  With ``mesh``
    (cache_mode='none' only) the step runs the shard_mapped SPMD tick.
    """
    if state.done:
        raise ValueError("step() called on a finished DiffusionState")
    dcfg = state.dcfg
    L, T = dcfg.block_length, dcfg.steps_per_block
    B, s_tot = state.x.shape
    bs = state.block_start
    t = state.step_in_block
    rng, srng = jax.random.split(state.rng)
    cache = state.cache
    # bind the (hashable, non-jax-type) quant policy statically into the
    # cached jitted fns instead of letting it ride **fwd_kw into jit
    fwd_kw = dict(fwd_kw)
    quant = fwd_kw.pop("quant", None)
    if mesh is not None and dcfg.cache_mode != "none":
        raise ValueError(
            "step(mesh=...) supports cache_mode='none' only (the SPMD "
            "path runs the batched tick; use the serving engine for "
            "pooled warm-cache SPMD ticks)")
    if mesh is not None and fwd_kw:
        raise ValueError("step(mesh=...) does not support extra forward "
                         "kwargs")

    if dcfg.cache_mode == "none":
        if mesh is not None:
            tick = get_spmd_tick_fn(model, dcfg, state.mask_id, mesh,
                                    jit_steps=jit_steps, quant=quant)
        else:
            tick = get_tick_fn(model, dcfg, state.mask_id,
                               jit_steps=jit_steps, quant=quant)
        x, _, _, _ = tick(params, state.x,
                          jnp.ones((B, s_tot), bool),
                          jnp.full((B,), bs, jnp.int32),
                          state.ks[:, t], srng, None, **fwd_kw)
    else:
        mode = head_feed_mode(model, dcfg)
        head_mode = "logits" if mode == "logits" else "hidden"
        if t == 0:
            fn = _cached_step_fn(model, dcfg, "warm", 0, jit_steps,
                                 head_mode, quant)
        else:
            suffix = (s_tot - (bs + L)) if dcfg.cache_mode == "prefix" else 0
            fn = _cached_step_fn(model, dcfg, "refine", suffix, jit_steps,
                                 head_mode, quant)
        feats, cache = fn(params, state.x, cache, jnp.int32(bs), **fwd_kw)
        commit = _cached_commit_fn(model, dcfg, state.mask_id, mode,
                                   quant, jit_steps)
        x = commit(params, feats, state.x, jnp.int32(bs), state.ks[:, t],
                   srng)

    t += 1
    block_idx = state.block_idx
    ks = state.ks
    if t == T:
        t = 0
        block_idx += 1
        ks = schedule_lib.get_num_transfer_tokens(
            jnp.full((B,), L, jnp.int32), T)
    return dataclasses.replace(state, x=x, cache=cache, rng=rng, ks=ks,
                               block_idx=block_idx, step_in_block=t)


def generate(model, params, prompt: jax.Array, dcfg: DiffusionConfig,
             rng: Optional[jax.Array] = None, mask_id: Optional[int] = None,
             jit_steps: bool = True, mesh=None, megatick_k: int = 1,
             **fwd_kw) -> jax.Array:
    """Blocked diffusion generation (paper Alg. 2 outer loops).

    prompt: (B, P) int32.  Returns (B, P + gen_length) tokens.  Thin loop
    over the resumable state machine (init_state / step).  With ``mesh``
    (a (data, model) mesh; cache_mode='none' only) every step runs the
    shard_mapped SPMD tick: batch rows shard over 'data', the LM head
    columns over 'model' (docs/sharded_serving.md).

    ``megatick_k > 1`` (cache_mode='none' only) fuses K denoising ticks
    into one device-resident while_loop dispatch (docs/megatick.md); the
    rng chain splits once per tick inside the loop, so tokens stay
    bit-identical to the per-step path.
    """
    if mesh is not None and dcfg.cache_mode != "none":
        raise ValueError(
            "generate(mesh=...) requires cache_mode='none' (the SPMD path "
            "runs the batched tick)")
    if megatick_k > 1:
        return _generate_megatick(model, params, prompt, dcfg, rng=rng,
                                  mask_id=mask_id, jit_steps=jit_steps,
                                  mesh=mesh, megatick_k=megatick_k,
                                  **fwd_kw)
    if mesh is not None:
        params = place_spmd_params(params, mesh)   # once, not per step
    state = init_state(model, prompt, dcfg, rng=rng, mask_id=mask_id)
    while not state.done:
        state = step(model, params, state, jit_steps=jit_steps, mesh=mesh,
                     **fwd_kw)
    return state.x


def _generate_megatick(model, params, prompt: jax.Array,
                       dcfg: DiffusionConfig, *, rng, mask_id, jit_steps,
                       mesh, megatick_k: int, **fwd_kw) -> jax.Array:
    """generate() via the fused K-tick while_loop (docs/megatick.md): the
    denoising tick count is static (num_blocks * steps_per_block), so the
    host loop runs ceil(total / K) megasteps with no per-step sync at all —
    the single block_until_ready is the final .block_until_ready() the
    caller does on the returned tokens."""
    if dcfg.cache_mode != "none":
        raise ValueError(
            "generate(megatick_k>1) requires cache_mode='none' (the "
            "megatick is built on the uniform batched tick)")
    quant = fwd_kw.pop("quant", None)
    if fwd_kw:
        raise ValueError("generate(megatick_k>1) does not support extra "
                         f"forward kwargs: {sorted(fwd_kw)}")
    if mesh is not None:
        params = place_spmd_params(params, mesh)
    mask_id = int(model.cfg.mask_id if mask_id is None else mask_id)
    B, P = prompt.shape
    x = jnp.concatenate(
        [prompt.astype(jnp.int32),
         jnp.full((B, dcfg.gen_length), mask_id, jnp.int32)], axis=1)
    kv_valid = jnp.ones((B, P + dcfg.gen_length), bool)
    state = megatick_state(jnp.full((B,), P, jnp.int32),
                           jnp.full((B,), dcfg.num_blocks, jnp.int32), dcfg)
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    fn = get_megatick_fn(model, dcfg, mask_id, int(megatick_k), mesh=mesh,
                         jit_steps=jit_steps, quant=quant)
    total = dcfg.num_blocks * dcfg.steps_per_block
    for _ in range(-(-total // megatick_k)):
        x, _, rng, state, _, _ = fn(params, x, kv_valid, state, rng,
                                    jnp.int32(megatick_k),
                                    jnp.asarray(False), None)
    return x


# ---------------------------------------------------------------------------
# Batched serving tick: full-sequence forward + per-row active-block sampling
# ---------------------------------------------------------------------------

def tick_forward(model, params, x: jax.Array, kv_valid: jax.Array,
                 block_start: jax.Array, cache, dcfg: DiffusionConfig,
                 quant=None, **fwd_kw):
    """Forward half of a serving tick over per-row block offsets.

    Without ``cache`` this is the Block-Diffusion full recompute
    (cache_mode='none'); with it, a warm step per tick: all KV is recomputed
    and rewritten through the BAOS smoothing/quantization path, so attention
    reads the same quantized cache the paper's warm step produces.

    For head-mode-capable models this returns the *full-sequence hidden
    states* (B, S, d) — the LM head runs after the per-row active-block
    slice in ``tick_sample``, so vocab-wide logits are at most (B, L, V)
    (unfused) or never materialized at all (fused).  Legacy models return
    full-sequence logits as before.
    """
    B, s_tot = x.shape
    L = dcfg.block_length
    mode = head_feed_mode(model, dcfg)
    extra = {} if mode == "logits" else {"head_mode": "hidden"}
    if trace_lib.is_active():
        # opaque transformer marker (costed by the analytical per-phase
        # model in the hybrid e2e); the legacy path's full-sequence head
        # GEMM + logits writeback is the one head cost paid in-forward, so
        # it is charged here rather than in the sampling stage
        trace_lib.emit("XU_FORWARD", (B, s_tot, int(model.cfg.d_model)),
                       stage="forward", note=f"cache={cache is not None}")
        if mode == "logits":
            trace_lib.emit_legacy_head(B * s_tot, int(model.cfg.d_model),
                                       int(model.cfg.vocab))
    if cache is None:
        feats, _, _ = model.forward(
            params, tokens=x, cache=None, seg_start=0, kv_valid=kv_valid,
            quant=quant, **extra, **fwd_kw)
        return feats, None
    calib_mask = None
    if dcfg.baos.calib_scope == "active_block":
        pos = jnp.arange(s_tot, dtype=jnp.int32)[None, :]
        calib_mask = ((pos >= block_start[:, None]) &
                      (pos < block_start[:, None] + L))
    feats, new_cache, _ = model.forward(
        params, tokens=x, cache=cache, seg_start=0, kv_valid=kv_valid,
        baos_cfg=dcfg.baos, calibrate=True, calib_mask=calib_mask,
        quant=quant, **extra, **fwd_kw)
    return feats, new_cache


def tick_sample(params, feats: jax.Array, x: jax.Array,
                block_start: jax.Array, k: jax.Array, srng: jax.Array,
                dcfg: DiffusionConfig, mask_id: int, model=None, quant=None,
                axis_name: Optional[str] = None):
    """Sampling half of a serving tick: per-row active-block slice at the
    *hidden* level (B, L, d) for head-capable models, then the selected
    head path (fused streamed head / unfused block logits / legacy), the
    Stable-Max commit of k tokens (k=0 rows are no-ops), scatter back.

    Returns (x_new, conf_min, masks_left) where conf_min is the minimum
    Stable-Max confidence over the tokens committed this tick (+inf when
    none) — the SlowFast early-exit signal — and masks_left counts masked
    positions remaining in each row's active block.
    """
    L = dcfg.block_length
    mode = head_feed_mode(model, dcfg) if model is not None else "logits"

    def row_slice(a, s):
        return jax.lax.dynamic_slice_in_dim(a, s, L, axis=0)

    fa = jax.vmap(row_slice)(feats, block_start)   # (B, L, d) or (B, L, V)
    xa = jax.vmap(row_slice)(x, block_start)
    xa_new, transfer, conf = _active_sampling_step(
        fa, xa, k, srng, params, mode, dcfg, mask_id, model, quant=quant,
        axis_name=axis_name)
    x_new = jax.vmap(
        lambda row, upd, s: jax.lax.dynamic_update_slice_in_dim(
            row, upd, s, axis=0))(x, xa_new, block_start)
    conf_min = jnp.min(jnp.where(transfer, conf, jnp.inf), axis=-1)
    masks_left = jnp.sum(xa_new == mask_id, axis=-1).astype(jnp.int32)
    return x_new, conf_min, masks_left


def batched_tick(model, params, x, kv_valid, block_start, k, srng, cache,
                 dcfg: DiffusionConfig = None, mask_id: int = 0, quant=None,
                 tracer=None, **fwd_kw):
    """One fused engine tick: single forward + single Stable-Max sampling
    call over all serving slots.  Also the cache_mode='none' step of the
    state machine (block_start broadcast), so a one-slot engine runs the
    exact computation ``generate()`` runs — bit-identical greedy tokens.

    ``tracer`` (a sim.trace.Tracer) records the tick's instruction stream
    for the cycle simulator while jax traces this call — pass it only on
    un-jitted invocations (sim.trace.capture_tick_trace does this via
    jax.eval_shape; compiled ticks never re-trace, so a tracer would see
    nothing).  Emission hooks are no-ops when ``tracer`` is None.
    """
    with trace_lib.activate(tracer):
        feats, new_cache = tick_forward(model, params, x, kv_valid,
                                        block_start, cache, dcfg,
                                        quant=quant, **fwd_kw)
        x_new, conf_min, masks_left = tick_sample(
            params, feats, x, block_start, k, srng, dcfg, mask_id,
            model=model, quant=quant)
    return x_new, new_cache, conf_min, masks_left


@functools.lru_cache(maxsize=32)
def get_tick_fn(model, dcfg: DiffusionConfig, mask_id: int,
                jit_steps: bool = True, quant=None):
    """Jitted ``batched_tick`` shared by generate() and the serving engine
    (same (model, dcfg) key -> same compiled executable).  ``quant`` is
    bound statically (QuantPolicy is not a jax type)."""
    fn = functools.partial(batched_tick, model, dcfg=dcfg, mask_id=mask_id,
                           quant=quant)
    return jax.jit(fn) if jit_steps else fn


def place_spmd_params(params, mesh):
    """One-time SPMD placement of a param pytree for the sharded tick:
    the LM head is zero-padded to MX-aligned shard boundaries
    (``sampling.pad_head_for_mesh``) and column-sharded over 'model';
    everything else replicates.  With params placed this way the jitted
    tick's internal pad + sharding constraint are no-ops, so ticks never
    move parameters — without it every tick re-broadcasts the full pytree
    across the mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    if "model" not in mesh.axis_names:
        raise ValueError(f"SPMD params need a mesh with a 'model' axis; "
                         f"got {mesh.axis_names}")
    w = sampling_lib.pad_head_for_mesh(params["lm_head"],
                                       mesh.shape["model"])
    rep = NamedSharding(mesh, P())
    head = NamedSharding(mesh, P(None, "model"))
    return {k: jax.device_put(w if k == "lm_head" else v,
                              head if k == "lm_head" else rep)
            for k, v in params.items()}


@functools.lru_cache(maxsize=16)
def get_spmd_tick_fn(model, dcfg: DiffusionConfig, mask_id: int, mesh,
                     jit_steps: bool = True, quant=None):
    """``batched_tick`` shard_mapped over a ``(data, model)`` mesh.

    The data axis shards engine batch slots (each chip's forward sees only
    its (B/n_data, S) canvas rows); the model axis shards the LM-head
    columns, so each chip streams only its (d, V/n_model) shard through
    ``fused_head_local_partials`` and the per-chip (m, idx, s) partials
    merge with the one-pmax/psum/pmin ``combine_partials`` collective —
    per-chip sampling traffic drops from O(R*d + d*V) to O(R*d + d*V/n)
    (sim/analytical.sharded_fused_head_sampling_stage models exactly this).

    Greedy tokens are bit-identical to the single-device fused tick: the
    head is zero-padded to MX-block-aligned shard boundaries
    (``sampling.pad_head_for_mesh``), so per-shard fake-quant blocks match
    full-row blocks and the combine's lowest-index tie-break matches the
    fused scan's first-chunk-wins rule (pinned by tests/test_spmd.py).
    """
    from jax.sharding import PartitionSpec as P

    for ax in ("data", "model"):
        if ax not in mesh.axis_names:
            raise ValueError(
                f"SPMD tick needs mesh axes ('data', 'model'); "
                f"got {mesh.axis_names}")
    if head_feed_mode(model, dcfg) != "fused":
        raise ValueError(
            "the SPMD tick requires head_path='fused' and a "
            "head-mode-capable model (supports_head_mode)")
    if dcfg.sampling.temperature > 0.0 or dcfg.sampling.strategy == "random":
        raise NotImplementedError(
            "SPMD tick supports greedy Stable-Max decoding only "
            "(temperature == 0, strategy='stablemax'): the tick rng is "
            "replicated across the mesh, so per-shard noise draws would "
            "silently correlate data shards")
    n_model = mesh.shape["model"]
    n_data = mesh.shape["data"]

    def body(params, x, kv_valid, block_start, k, srng, cache):
        feats, new_cache = tick_forward(model, params, x, kv_valid,
                                        block_start, cache, dcfg, quant=quant)
        x_new, conf_min, masks_left = tick_sample(
            params, feats, x, block_start, k, srng, dcfg, mask_id,
            model=model, quant=quant, axis_name="model")
        return x_new, new_cache, conf_min, masks_left

    def tick(params, x, kv_valid, block_start, k, srng, cache=None):
        if x.shape[0] % n_data:
            raise ValueError(
                f"batch {x.shape[0]} is not divisible by the data axis "
                f"size {n_data}")
        params = dict(params)
        params["lm_head"] = sampling_lib.pad_head_for_mesh(
            params["lm_head"], n_model)
        pspec = jax.tree.map(lambda _: P(), params)
        pspec["lm_head"] = P(None, "model")
        cspec = jax.tree.map(lambda _: P(None, "data"), cache)
        row = P("data")
        f = jax.shard_map(
            body, mesh=mesh,
            in_specs=(pspec, P("data", None), P("data", None), row, row,
                      P(), cspec),
            out_specs=(P("data", None), cspec, row, row))
        return f(params, x, kv_valid, block_start, k, srng, cache)

    return jax.jit(tick) if jit_steps else tick


# ---------------------------------------------------------------------------
# Device-resident megatick: K fused ticks in one lax.while_loop
# (docs/megatick.md).  One host dispatch + one device sync per K denoising
# ticks; per-tick commit records accumulate into fixed-size on-device
# buffers the host drains after the megastep.
# ---------------------------------------------------------------------------

def megatick_state(prompt_len, gen_blocks, dcfg: DiffusionConfig,
                   block_idx=None, step_in_block=None, block_masks_left=None,
                   last_conf=None, active=None) -> dict:
    """Per-row device state pytree carried through the megatick while_loop.

    ``prompt_len``/``gen_blocks`` are (B,) int vectors (per-row prompt
    offsets and block counts — the megatick serves mixed-length slots);
    the remaining fields default to block-0/step-0 for every row.
    """
    pl = jnp.asarray(prompt_len, jnp.int32)
    B = pl.shape[0]
    L = dcfg.block_length
    return {
        "prompt_len": pl,
        "gen_blocks": jnp.asarray(gen_blocks, jnp.int32),
        "block_idx": (jnp.zeros((B,), jnp.int32) if block_idx is None
                      else jnp.asarray(block_idx, jnp.int32)),
        "step_in_block": (jnp.zeros((B,), jnp.int32) if step_in_block is None
                          else jnp.asarray(step_in_block, jnp.int32)),
        "block_masks_left": (jnp.full((B,), L, jnp.int32)
                             if block_masks_left is None
                             else jnp.asarray(block_masks_left, jnp.int32)),
        "last_conf": (jnp.full((B,), -jnp.inf, jnp.float32)
                      if last_conf is None
                      else jnp.asarray(last_conf, jnp.float32)),
        "active": (jnp.ones((B,), bool) if active is None
                   else jnp.asarray(active, bool)),
    }


@functools.lru_cache(maxsize=16)
def get_megatick_fn(model, dcfg: DiffusionConfig, mask_id: int, k_max: int,
                    mesh=None, jit_steps: bool = True, quant=None,
                    slowfast_threshold: Optional[float] = None):
    """Fused K-tick megastep: ``lax.while_loop`` over the serving tick.

    The loop carries canvas ``x``, KV ``cache``, the rng chain, and the
    per-row policy state (``megatick_state``) entirely on device, splitting
    the rng exactly as the engine's one-split-per-tick chain does — greedy
    tokens are bit-identical to ``k_max`` single ticks (tests/test_megatick).
    Each iteration appends one commit record to fixed-size ``(k_max, ...)``
    buffers (post-tick active-block tokens, block offsets, masks_left,
    per-row release/early-exit flags); the loop exits early when every
    active row has released, when ``stop_on_release`` is set and any row
    released this tick (the engine's queue-pressure knob: freed slots
    should refill at the next megastep boundary), or after the *traced*
    ``k_req <= k_max`` ticks — so one compiled executable serves every
    requested depth up to ``k_max``.

    ``slowfast_threshold`` moves SlowFastPolicy.step_k on device: once a
    row's previous-tick min confidence clears the threshold, the rest of
    its block commits in one tick (the ``early`` buffer records exits for
    the host-side ``policy.early_exits`` accounting).

    Returns ``(x, cache, rng, state, buffers, n_ticks)``.  The jitted
    callable donates ``x`` and ``cache`` (the engine rebinds both every
    megastep); under ``mesh`` the whole loop runs inside one shard_map
    over the (data, model) mesh — the stop flag psums over 'data' in the
    loop *body* (collectives in a while_loop cond are unsafe), so the
    carried scalars every shard's cond reads are replicated.
    """
    if k_max < 1:
        raise ValueError(f"megatick k_max must be >= 1, got {k_max}")
    L, T = dcfg.block_length, dcfg.steps_per_block
    thr = None if slowfast_threshold is None else float(slowfast_threshold)
    if mesh is not None:
        # reuse the SPMD tick's validation (mesh axes, fused+greedy head)
        get_spmd_tick_fn(model, dcfg, mask_id, mesh, jit_steps=False,
                         quant=quant)

    def body(params, x, kv_valid, state, rng, k_req, stop_on_release,
             cache, axis_name=None):
        B = x.shape[0]
        ksched = jnp.asarray(schedule_lib.linear_unmask_schedule(L, T))
        k_req = jnp.minimum(jnp.asarray(k_req, jnp.int32), k_max)
        zi = jnp.zeros((k_max, B), jnp.int32)
        zb = jnp.zeros((k_max, B), bool)
        bufs0 = {"xa": jnp.zeros((k_max, B, L), jnp.int32),
                 "block_start": zi, "block_idx": zi, "step_in_block": zi,
                 "masks_left": zi, "k": zi,
                 "conf": jnp.zeros((k_max, B), jnp.float32),
                 "active": zb, "released": zb, "early": zb}

        def cond(carry):
            i, stop = carry[0], carry[1]
            return (i < k_req) & jnp.logical_not(stop)

        def step(carry):
            i, stop, x, cache, rng, st, bufs = carry
            bi, t = st["block_idx"], st["step_in_block"]
            bml, lc, act = (st["block_masks_left"], st["last_conf"],
                            st["active"])
            bs = jnp.where(act, st["prompt_len"] + bi * L, 0)
            dk = jnp.where(t < T, jnp.take(ksched, jnp.clip(t, 0, T - 1)),
                           bml)
            if thr is not None:
                fire = (t > 0) & (bml > 0) & jnp.isfinite(lc) & (lc >= thr)
                k = jnp.where(fire, bml, dk)
                early = fire & (bml > dk)
            else:
                k, early = dk, jnp.zeros((B,), bool)
            k = jnp.where(act, jnp.minimum(k, L), 0)
            rng, srng = jax.random.split(rng)
            feats, new_cache = tick_forward(model, params, x, kv_valid, bs,
                                            cache, dcfg, quant=quant)
            x_new, conf_min, masks_left = tick_sample(
                params, feats, x, bs, k, srng, dcfg, mask_id, model=model,
                quant=quant, axis_name=axis_name)
            boundary = act & (masks_left == 0)
            released = boundary & (bi + 1 >= st["gen_blocks"])
            st2 = dict(st)
            st2["block_idx"] = jnp.where(boundary, bi + 1, bi)
            st2["step_in_block"] = jnp.where(
                act, jnp.where(boundary, 0, t + 1), t)
            st2["last_conf"] = jnp.where(
                act, jnp.where(boundary, -jnp.inf, conf_min), lc)
            st2["block_masks_left"] = jnp.where(
                act, jnp.where(boundary, L, masks_left), bml)
            st2["active"] = act & jnp.logical_not(released)

            def row_slice(a, s):
                return jax.lax.dynamic_slice_in_dim(a, s, L, axis=0)

            upd = {"xa": jax.vmap(row_slice)(x_new, bs), "block_start": bs,
                   "block_idx": bi, "step_in_block": t, "conf": conf_min,
                   "masks_left": jnp.where(act, masks_left, 0), "k": k,
                   "active": act, "released": released, "early": early}
            bufs = {key: jax.lax.dynamic_update_index_in_dim(
                        bufs[key], upd[key].astype(bufs[key].dtype), i, 0)
                    for key in bufs}
            any_active = jnp.any(st2["active"])
            any_released = jnp.any(released)
            if axis_name is not None:
                any_active = jax.lax.psum(
                    any_active.astype(jnp.int32), "data") > 0
                any_released = jax.lax.psum(
                    any_released.astype(jnp.int32), "data") > 0
            stop = (jnp.logical_not(any_active)
                    | (stop_on_release & any_released))
            return (i + 1, stop, x_new, new_cache, rng, st2, bufs)

        carry = (jnp.int32(0), jnp.asarray(False), x, cache, rng,
                 dict(state), bufs0)
        i, _, x, cache, rng, st, bufs = jax.lax.while_loop(cond, step, carry)
        return x, cache, rng, st, bufs, i

    if mesh is None:
        def megatick(params, x, kv_valid, state, rng, k_req,
                     stop_on_release, cache=None):
            return body(params, x, kv_valid, state, rng, k_req,
                        stop_on_release, cache, axis_name=None)

        return (jax.jit(megatick, donate_argnums=(1, 7)) if jit_steps
                else megatick)

    from jax.sharding import PartitionSpec as P

    n_model = mesh.shape["model"]
    n_data = mesh.shape["data"]

    def megatick(params, x, kv_valid, state, rng, k_req, stop_on_release,
                 cache=None):
        if x.shape[0] % n_data:
            raise ValueError(
                f"batch {x.shape[0]} is not divisible by the data axis "
                f"size {n_data}")
        params = dict(params)
        params["lm_head"] = sampling_lib.pad_head_for_mesh(
            params["lm_head"], n_model)
        pspec = jax.tree.map(lambda _: P(), params)
        pspec["lm_head"] = P(None, "model")
        cspec = jax.tree.map(lambda _: P(None, "data"), cache)
        row = P("data")
        sspec = {key: row for key in state}
        bspec = {"xa": P(None, "data", None)}
        for key in ("block_start", "block_idx", "step_in_block",
                    "masks_left", "k", "conf", "active", "released",
                    "early"):
            bspec[key] = P(None, "data")
        f = jax.shard_map(
            functools.partial(body, axis_name="model"), mesh=mesh,
            in_specs=(pspec, P("data", None), P("data", None), sspec,
                      P(), P(), P(), cspec),
            out_specs=(P("data", None), cspec, P(), sspec, bspec, P()),
            check_vma=False)
        return f(params, x, kv_valid, state, rng, k_req, stop_on_release,
                 cache)

    return (jax.jit(megatick, donate_argnums=(1, 7)) if jit_steps
            else megatick)


# ---------------------------------------------------------------------------
# Paged block-pool tick: the serving canvas and KV cache live in fixed-size
# physical pages addressed through per-slot block tables (docs/paged_cache.md).
# The device math is the *unchanged* batched tick: a paged tick gathers the
# pages into the dense (B, S) views the tick body expects, runs it, and
# scatters the results back — so greedy tokens stay bit-identical to the slot
# pool by construction, across cache modes, meshes, and megatick depths.
# ---------------------------------------------------------------------------

def paged_cache_layout(model, page_size: int, s_tot: int):
    """Probe ``model.init_cache``'s leaf layout for the paged pool.

    Returns ``(treedef, paged, batch_axis)`` where ``paged`` and
    ``batch_axis`` are flat per-leaf lists: ``paged[i]`` is True for leaves
    carrying a full sequence dimension (these move into page stores) and
    ``batch_axis[i]`` locates the batch dimension of the remaining per-slot
    leaves (BAOS calibration rows, recurrent states) for spill/restore.
    Probing uses ``jax.eval_shape``, so no dense cache is ever allocated.
    Layouts whose sequence axis is not axis 2 (with batch at axis 1) are
    rejected — the gather/scatter views assume (stack, batch, seq, ...).
    """
    def shapes(batch, s):
        return jax.eval_shape(lambda: model.init_cache(batch, s))

    base = shapes(2, s_tot)
    flat_b, treedef = jax.tree_util.tree_flatten(base)
    flat_g = jax.tree_util.tree_leaves(shapes(2, s_tot + page_size))
    flat_w = jax.tree_util.tree_leaves(shapes(3, s_tot))
    paged, batch_axis = [], []
    for lb, lg, lw in zip(flat_b, flat_g, flat_w):
        seq_axes = [i for i, (a, b) in enumerate(zip(lb.shape, lg.shape))
                    if a != b]
        bat_axes = [i for i, (a, b) in enumerate(zip(lb.shape, lw.shape))
                    if a != b]
        if len(bat_axes) != 1:
            raise ValueError(
                f"paged pool: cannot locate the batch axis of cache leaf "
                f"with shape {lb.shape}")
        if seq_axes:
            if seq_axes != [2] or bat_axes != [1]:
                raise ValueError(
                    f"paged pool supports (stack, batch, seq, ...) cache "
                    f"leaves only; got shape {lb.shape} with seq axes "
                    f"{seq_axes}, batch axes {bat_axes}")
            paged.append(True)
        else:
            paged.append(False)
        batch_axis.append(bat_axes[0])
    return treedef, paged, batch_axis


def gather_canvas_rows(canvas_pages: jax.Array,
                       canvas_table: jax.Array) -> jax.Array:
    """(NP, page) canvas pages + (B, R) block table -> dense (B, S) rows."""
    B, R = canvas_table.shape
    ps = canvas_pages.shape[1]
    return jnp.take(canvas_pages, canvas_table.reshape(-1),
                    axis=0).reshape(B, R * ps)


def scatter_canvas_rows(canvas_pages: jax.Array, canvas_table: jax.Array,
                        rows: jax.Array) -> jax.Array:
    """Write dense (B, S) rows back through the block table.

    Pages referenced by more than one table entry (shared radix-cached
    prompt pages, the reserved null page 0) receive identical values from
    every writer — prompt content never changes and null-mapped tail/idle
    positions carry the page's own gathered content — so duplicate-index
    scatter order cannot change the result.
    """
    B, R = canvas_table.shape
    ps = canvas_pages.shape[1]
    upd = rows.reshape(B * R, ps)
    return canvas_pages.at[canvas_table.reshape(-1)].set(upd)


def _gather_pages_axis1(store: jax.Array, table: jax.Array) -> jax.Array:
    B, R = table.shape
    ps = store.shape[2]
    g = jnp.take(store, table.reshape(-1), axis=1)
    return g.reshape(store.shape[:1] + (B, R * ps) + store.shape[3:])


def _scatter_pages_axis1(store: jax.Array, table: jax.Array,
                         dense: jax.Array) -> jax.Array:
    B, R = table.shape
    ps = store.shape[2]
    upd = dense.reshape(dense.shape[:1] + (B * R, ps) + dense.shape[3:])
    return store.at[:, table.reshape(-1)].set(upd)


def gather_cache_rows(cache_store, kv_table: jax.Array, paged_flags):
    """Page-store cache pytree -> the dense per-slot cache the tick body
    expects.  Non-paged leaves (per-slot calibration/recurrent state) pass
    through unchanged."""
    flat, treedef = jax.tree_util.tree_flatten(cache_store)
    dense = [_gather_pages_axis1(leaf, kv_table) if f else leaf
             for leaf, f in zip(flat, paged_flags)]
    return jax.tree_util.tree_unflatten(treedef, dense)


def scatter_cache_rows(cache_store, kv_table: jax.Array, new_cache,
                       paged_flags):
    """Write a tick's functionally-updated dense cache back into the page
    stores.  KV pages are private per slot (the warm tick rewrites every
    position each tick, so sharing would break the moment it was
    established); only tail/idle entries alias the null page, and those
    positions are kv_valid-masked — never read by any valid position."""
    flat_s, treedef = jax.tree_util.tree_flatten(cache_store)
    flat_n = jax.tree_util.tree_leaves(new_cache)
    out = [_scatter_pages_axis1(s, kv_table, n) if f else n
           for s, n, f in zip(flat_s, flat_n, paged_flags)]
    return jax.tree_util.tree_unflatten(treedef, out)


@functools.lru_cache(maxsize=16)
def get_paged_tick_fn(model, dcfg: DiffusionConfig, mask_id: int,
                      page_size: int, s_tot: int, with_cache: bool = True,
                      mesh=None, jit_steps: bool = True, quant=None):
    """``batched_tick`` reading/writing through block tables.

    One jitted call: gather canvas/KV pages into dense (B, S) views, run
    the unchanged tick body (the shard_mapped SPMD tick under ``mesh`` —
    XLA inserts the reshard at the shard_map boundary), scatter back.
    Returns ``(canvas_pages, cache_store, x, conf_min, masks_left)`` where
    ``x`` is the post-tick dense canvas view — the one host copy streaming
    diffs and request release read, exactly like the slot-pool tick's
    ``x_new``.  Not donated: the engine's warmup calls it on live stores.
    """
    if mesh is not None:
        inner = get_spmd_tick_fn(model, dcfg, mask_id, mesh,
                                 jit_steps=False, quant=quant)
    else:
        inner = functools.partial(batched_tick, model, dcfg=dcfg,
                                  mask_id=mask_id, quant=quant)
    flags = (paged_cache_layout(model, page_size, s_tot)[1]
             if with_cache else None)

    def tick(params, canvas_pages, cache_store, canvas_table, kv_table,
             kv_valid, block_start, k, srng):
        x = gather_canvas_rows(canvas_pages, canvas_table)
        cache = (None if cache_store is None
                 else gather_cache_rows(cache_store, kv_table, flags))
        x_new, new_cache, conf_min, masks_left = inner(
            params, x, kv_valid, block_start, k, srng, cache)
        canvas_pages = scatter_canvas_rows(canvas_pages, canvas_table, x_new)
        if cache_store is not None:
            cache_store = scatter_cache_rows(cache_store, kv_table,
                                             new_cache, flags)
        return canvas_pages, cache_store, x_new, conf_min, masks_left

    return jax.jit(tick) if jit_steps else tick


@functools.lru_cache(maxsize=16)
def get_paged_megatick_fn(model, dcfg: DiffusionConfig, mask_id: int,
                          k_max: int, page_size: int, s_tot: int,
                          with_cache: bool = True, mesh=None,
                          jit_steps: bool = True, quant=None,
                          slowfast_threshold: Optional[float] = None):
    """Paged ``get_megatick_fn``: gather once before the fused K-tick
    while_loop, scatter once after — the block tables are constant across
    a megastep (admission/release only happens at megastep boundaries).
    Donates the page stores, mirroring the slot-pool megatick's donation
    of canvas and cache; the engine rebinds both from the outputs."""
    inner = get_megatick_fn(model, dcfg, mask_id, k_max, mesh=mesh,
                            jit_steps=False, quant=quant,
                            slowfast_threshold=slowfast_threshold)
    flags = (paged_cache_layout(model, page_size, s_tot)[1]
             if with_cache else None)

    def megatick(params, canvas_pages, cache_store, canvas_table, kv_table,
                 kv_valid, state, rng, k_req, stop_on_release):
        x = gather_canvas_rows(canvas_pages, canvas_table)
        cache = (None if cache_store is None
                 else gather_cache_rows(cache_store, kv_table, flags))
        x, cache, rng, st, bufs, n = inner(params, x, kv_valid, state, rng,
                                           k_req, stop_on_release, cache)
        canvas_pages = scatter_canvas_rows(canvas_pages, canvas_table, x)
        if cache_store is not None:
            cache_store = scatter_cache_rows(cache_store, kv_table, cache,
                                             flags)
        return canvas_pages, cache_store, x, rng, st, bufs, n

    if not jit_steps:
        return megatick
    return jax.jit(megatick,
                   donate_argnums=(1, 2) if with_cache else (1,))


@functools.lru_cache(maxsize=32)
def get_tick_stage_fns(model, dcfg: DiffusionConfig, mask_id: int,
                       jit_steps: bool = True, quant=None):
    """(forward, sampling) jitted separately — the engine's per-stage
    latency-breakdown mode (Fig. 1 attribution); math identical to the
    fused tick.  The sampling stage owns the LM head for head-capable
    models (the paper's sampling engine owns the vocab traffic), so its
    signature is (params, feats, x, block_start, k, srng); the GEMM-boundary
    ``quant`` policy is bound statically so the staged head quantizes
    exactly like the fused tick's."""
    fwd = functools.partial(tick_forward, model, dcfg=dcfg, quant=quant)
    smp = functools.partial(tick_sample, dcfg=dcfg, mask_id=mask_id,
                            model=model, quant=quant)
    if jit_steps:
        fwd, smp = jax.jit(fwd), jax.jit(smp)
    return fwd, smp


# ---------------------------------------------------------------------------
# Training objective (LLaDA masked diffusion)
# ---------------------------------------------------------------------------

def forward_mask(rng: jax.Array, tokens: jax.Array, mask_id: int,
                 eps: float = 1e-3):
    """LLaDA forward process: t ~ U(eps, 1) per sequence, mask iid w.p. t."""
    B, S = tokens.shape
    r1, r2 = jax.random.split(rng)
    t = jax.random.uniform(r1, (B, 1), minval=eps, maxval=1.0)
    mask = jax.random.uniform(r2, (B, S)) < t
    noisy = jnp.where(mask, mask_id, tokens)
    return noisy, mask, t


def masked_diffusion_loss(model, params, tokens: jax.Array, rng: jax.Array,
                          quant=None, aux_weight: float = 0.0,
                          valid: Optional[jax.Array] = None,
                          loss_chunk: Optional[int] = None, **fwd_kw):
    """LLaDA objective: E_t E_mask [ 1/t * sum_masked CE ] / (B*S).

    ``loss_chunk``: compute the CE reduction in sequence chunks so the f32
    upcast of the (B, S, V) logits is never materialized whole (§Perf
    memory-term optimization for train cells)."""
    cfg = model.cfg
    noisy, mask, t = forward_mask(rng, tokens, cfg.mask_id)
    logits, _, aux = model.forward(params, tokens=noisy, cache=None,
                                   quant=quant, **fwd_kw)
    if loss_chunk is not None and tokens.shape[1] % loss_chunk == 0:
        S = tokens.shape[1]
        nch = S // loss_chunk

        def chunk_ce(c):
            lg = jax.lax.dynamic_slice_in_dim(
                logits, c * loss_chunk, loss_chunk, 1).astype(jnp.float32)
            tk = jax.lax.dynamic_slice_in_dim(tokens, c * loss_chunk,
                                              loss_chunk, 1)
            lz = jax.nn.logsumexp(lg, axis=-1)
            gd = jnp.take_along_axis(lg, tk[..., None], axis=-1)[..., 0]
            return lz - gd

        ce = jnp.concatenate([chunk_ce(c) for c in range(nch)], axis=1)
    else:
        lf = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(lf, axis=-1)
        gold = jnp.take_along_axis(lf, tokens[..., None], axis=-1)[..., 0]
        ce = logz - gold
    w = mask.astype(jnp.float32) / t
    if valid is not None:
        w = w * valid.astype(jnp.float32)
    loss = jnp.sum(ce * w) / (tokens.shape[0] * tokens.shape[1])
    if aux_weight:
        loss = loss + aux_weight * aux
    metrics = {
        "loss": loss,
        "ce_masked": jnp.sum(ce * mask) / jnp.maximum(jnp.sum(mask), 1),
        "mask_frac": jnp.mean(mask.astype(jnp.float32)),
        "aux": aux,
    }
    return loss, metrics
