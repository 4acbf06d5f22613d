"""Declarative registry the analysis passes read: which functions are
jit-traced hot paths, which modules carry thread-shared state, each Pallas
kernel's tile/scratch footprint at production scale, every jitted entry
point with its donation/transfer/collective budgets, and the recompilation
bounds.  New jitted paths register *here* (docs/static_analysis.md) — the
passes themselves never hardcode repo structure.

Everything importing jax or model code is built lazily inside functions so
the pure-AST passes (hotpath_lint, locks) stay import-light and fast.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Callable, Dict, List, Optional, Tuple

# repo-relative source root the source-level passes scan
SRC_ROOT = os.path.normpath(os.path.join(os.path.dirname(__file__), ".."))
PKG_PREFIX = "repro"


def src_files() -> List[str]:
    """All library sources, as ``repro/...`` relpaths, sorted."""
    out = []
    for dirpath, _, files in os.walk(SRC_ROOT):
        for f in sorted(files):
            if f.endswith(".py"):
                full = os.path.join(dirpath, f)
                rel = os.path.relpath(full, os.path.dirname(SRC_ROOT))
                out.append(rel.replace(os.sep, "/"))
    return sorted(out)


def abspath(rel: str) -> str:
    return os.path.join(os.path.dirname(SRC_ROOT), rel)


def default_allowlist_path() -> str:
    return os.path.join(os.path.dirname(__file__), "allowlist.txt")


# ---------------------------------------------------------------------------
# Hot paths: top-level functions whose bodies run under jax tracing — a
# host sync there is either a silent per-call round-trip or a tracer leak.
# Registering a name covers every function lexically nested inside it
# (shard_map bodies, while_loop steps, jitted closures).  "*" = every
# function in the module.
# ---------------------------------------------------------------------------

HOT_PATHS: Dict[str, object] = {
    "repro/core/diffusion.py": {
        "warm_step", "refine_step", "_active_sampling_step",
        "_cached_commit_fn", "_cached_step_fn", "tick_forward",
        "tick_sample", "batched_tick", "get_tick_fn", "get_spmd_tick_fn",
        "megatick_state", "_megatick_commit", "get_megatick_fn",
        "get_tick_stage_fns",
        "gather_canvas_rows", "scatter_canvas_rows", "_gather_pages_axis1",
        "_scatter_pages_axis1", "gather_cache_rows", "scatter_cache_rows",
        "get_paged_tick_fn", "get_paged_megatick_fn",
    },
    "repro/core/sampling.py": "*",
    "repro/kernels/fused_head_sampling.py": "*",
    "repro/kernels/stablemax_sampling.py": "*",
    "repro/kernels/topk_mask.py": "*",
    "repro/kernels/flash_bidir.py": "*",
    "repro/kernels/baos_mx_quant.py": "*",
    "repro/kernels/ops.py": "*",
}

# ---------------------------------------------------------------------------
# Lock-discipline scope: every module that shares state across the asyncio
# frontend thread and the per-replica engine worker threads.
# ---------------------------------------------------------------------------

LOCK_SCOPE_PREFIXES: Tuple[str, ...] = (
    "repro/serving/",
    "repro/obs/",
)


def lock_scope_files() -> List[str]:
    return [f for f in src_files()
            if f.startswith(LOCK_SCOPE_PREFIXES)]


# ---------------------------------------------------------------------------
# Event-emit paths: host-side functions on the per-tick / per-request path
# that feed the structured event log (repro.obs.events).  The crash-safety
# design keeps the emit side to a dict build + deque append under the lock
# — JSON serialization, file writes, flush, and fsync belong to the
# flusher thread only.  The hotpath lint's ANL-EMITIO rule enforces that
# split over the qualnames registered here.
# ---------------------------------------------------------------------------

EVENT_EMIT_PATHS: Dict[str, Tuple[str, ...]] = {
    "repro/obs/events.py": ("EventLog.emit",),
    "repro/obs/serving.py": ("ServingObs.event",),
    "repro/serving/engine.py": ("ServingEngine._emit_commit",),
}


# ---------------------------------------------------------------------------
# Pallas kernel SRAM/VMEM footprints.  Per grid step: streamed in/out
# blocks are double-buffered by the Pallas pipeline (x2); scratch and
# resident compute intermediates are single instances.  Shapes mirror the
# BlockSpecs in repro/kernels/*; the production point is LLaDA-8B
# (d=4096, V=126464, d_head=128) at an 8-slot x L=32 engine batch.
# ---------------------------------------------------------------------------

# the ~4 MiB weight-slab cap applied by kernels/ops.fused_head_sampling so
# the double-buffered slab fits a ~16 MiB/core VMEM budget at prod d
W_SLAB_CAP_BYTES = 4 * 1024 * 1024


def head_chunk_cap(d: int, itemsize: int) -> int:
    """Vocab-chunk cap the fused-head wrapper applies (kernels/ops.py)."""
    return max(128, W_SLAB_CAP_BYTES // (d * itemsize))


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    name: str                       # public kernel entry
    point: Dict[str, int]           # production shape point
    buffers: Dict[str, int]         # buffer name -> bytes per instance
    double_buffered: Tuple[str, ...]  # names counted twice (pipelining)

    def footprint(self) -> Dict[str, int]:
        return {n: b * (2 if n in self.double_buffered else 1)
                for n, b in self.buffers.items()}

    @property
    def total_bytes(self) -> int:
        return sum(self.footprint().values())


def kernel_specs(d: int = 4096, v: int = 126464, d_head: int = 128,
                 batch: int = 8, n_heads: int = 32, seq: int = 4096,
                 block_len: int = 32) -> List[KernelSpec]:
    """Per-kernel VMEM accounting at the given scale (defaults: LLaDA-8B
    production serving).  Dtypes: bf16 staging (2 B), fp32 scratch/accum
    (4 B), int32 indices (4 B) — matching the kernels' BlockSpecs."""
    bf16, f32, i32 = 2, 4, 4
    rows = batch * block_len                       # flattened (B*L, d)
    tile_r = 8

    # fused_head_sampling: grid (Rp/tile_r, n_chunks); the wrapper caps the
    # (d, chunk) slab at W_SLAB_CAP_BYTES before padding V
    chunk = min(512, head_chunk_cap(d, bf16), v)
    fused_head = KernelSpec(
        "fused_head_sampling",
        {"rows": rows, "d": d, "V": v, "tile_r": tile_r, "chunk_v": chunk},
        {
            "hidden_tile": tile_r * d * bf16,
            "w_slab": d * chunk * bf16,
            "out_conf": tile_r * f32,
            "out_token": tile_r * i32,
            "scratch": 5 * tile_r * f32,           # m/s/best/idx/carry rows
        },
        ("hidden_tile", "w_slab", "out_conf", "out_token"))

    # stablemax_sampling: grid (Rp/tile_r, n_chunks) over (R, V) logits
    sm_chunk = min(512, v)
    stablemax = KernelSpec(
        "stablemax_sampling",
        {"rows": rows, "V": v, "tile_r": tile_r, "chunk_v": sm_chunk},
        {
            "logit_tile": tile_r * sm_chunk * bf16,
            "out_conf": tile_r * f32,
            "out_token": tile_r * i32,
            "scratch": 3 * tile_r * f32,
        },
        ("logit_tile", "out_conf", "out_token"))

    # topk_mask: grid (Rp/tile_r,); whole (tile_r, L) rows per step plus
    # the in-register (tile_r, L, L) pairwise-rank intermediate
    topk = KernelSpec(
        "topk_mask",
        {"rows": rows, "L": block_len, "tile_r": tile_r},
        {
            "conf_tile": tile_r * block_len * f32,
            "mask_tile": tile_r * block_len * i32,
            "k_tile": tile_r * i32,
            "out_tile": tile_r * block_len * i32,
            "rank_matrix": tile_r * block_len * block_len * f32,
        },
        ("conf_tile", "mask_tile", "k_tile", "out_tile"))

    # flash_bidir: grid (B*Hq, Sq/bq, n_kv); bq=128/bk=512 defaults
    bq, bk = 128, min(512, seq)
    flash = KernelSpec(
        "flash_bidir",
        {"B": batch, "H": n_heads, "S": seq, "D": d_head,
         "bq": bq, "bk": bk},
        {
            "q_tile": bq * d_head * bf16,
            "k_tile": bk * d_head * bf16,
            "v_tile": bk * d_head * bf16,
            "calib": 3 * d_head * bf16,            # fk / fv / cv rows
            "out_tile": bq * d_head * bf16,
            "m_l_scratch": 2 * bq * f32,
            "acc_scratch": bq * d_head * f32,
        },
        ("q_tile", "k_tile", "v_tile", "calib", "out_tile"))

    # baos_mx_quant: grid (G, S/tile_s) over (G, S, D) per-head KV slabs
    tile_s = 128
    baos = KernelSpec(
        "baos_mx_quant",
        {"G": batch * n_heads, "S": seq, "D": d_head, "tile_s": tile_s},
        {
            "x_tile": tile_s * d_head * f32,
            "center": d_head * f32,
            "factor": d_head * f32,
            "out_tile": tile_s * d_head * f32,
        },
        ("x_tile", "center", "factor", "out_tile"))

    return [fused_head, stablemax, topk, flash, baos]


# band for the fused-head static footprint vs the cycle simulator's
# exact-fit allocator peak, both in the trace's modeled storage formats
# (sampling.TRACE_W_FMT weights) — the two must never silently diverge
SRAM_CROSSVAL_BAND: Tuple[float, float] = (0.8, 1.25)


# ---------------------------------------------------------------------------
# Jitted entry points for the jaxpr/HLO audit.  Budgets:
#   max_h2d — array leaves the host supplies per call beyond the
#             device-resident operands (params / canvas / KV / carried
#             state): the per-tick upload bound.
#   max_d2h — output leaves the host may fetch per call.
#   mesh_axes — the only axis names collectives may reference.
#   min_aliased — array leaves that must lower with input/output aliasing
#             (buffer donation made real), checked on the jitted variant.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class EntryPoint:
    name: str
    fn: Callable                    # un-jitted, traceable with .args
    args: tuple
    resident_argnums: Tuple[int, ...]
    max_h2d: int
    max_d2h: int
    mesh_axes: Tuple[str, ...] = ()
    jitted: Optional[Callable] = None   # for the donation-aliasing check
    min_aliased: int = 0
    kernel_only: bool = False       # kernel wrapper: primitive scan only


def _smoke_setup():
    import jax

    from repro.configs import base
    from repro.core import diffusion
    from repro.models.registry import build_model

    cfg = base.get_config("llada-8b", smoke=True)
    model = build_model(cfg)
    B, prompt, gen = 2, 8, 16
    dcfg = diffusion.DiffusionConfig(gen_length=gen, block_length=8,
                                     steps_per_block=4, cache_mode="none",
                                     head_path="fused")
    s_tot = prompt + gen
    sds = jax.ShapeDtypeStruct
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    common = dict(x=sds((B, s_tot), "int32"),
                  kv_valid=sds((B, s_tot), "bool"),
                  bs=sds((B,), "int32"), k=sds((B,), "int32"),
                  srng=jax.random.PRNGKey(0))
    return cfg, model, dcfg, params, B, s_tot, common


def entry_points() -> List[EntryPoint]:
    """Build every registered entry point with abstract (shape-only) args
    at smoke scale — tracing never allocates a weight."""
    import functools

    import jax
    import jax.numpy as jnp

    from repro.core import diffusion
    from repro.kernels import ops
    from repro.launch.mesh import make_debug_mesh

    cfg, model, dcfg, params, B, s_tot, c = _smoke_setup()
    mask_id = cfg.mask_id
    sds = jax.ShapeDtypeStruct
    eps: List[EntryPoint] = []

    # -- batched_tick (generate() + the serving engine's per-tick path) ---
    tick = functools.partial(diffusion.batched_tick, model, dcfg=dcfg,
                             mask_id=mask_id)
    tick_args = (params, c["x"], c["kv_valid"], c["bs"], c["k"], c["srng"],
                 None)
    eps.append(EntryPoint(
        "batched_tick", tick, tick_args,
        resident_argnums=(0, 1, 2, 6),      # params, canvas, kv_valid, cache
        max_h2d=4, max_d2h=6))

    # -- warm-cache tick: the BAOS smoothing/quantization KV path ---------
    dcfg_warm = dataclasses.replace(dcfg, cache_mode="dual")
    cache = jax.eval_shape(lambda: model.init_cache(B, s_tot))
    warm = functools.partial(diffusion.batched_tick, model, dcfg=dcfg_warm,
                             mask_id=mask_id)
    # outputs include the swapped warm-cache pytree (device-resident: the
    # engine pool rebinds it without fetching), so the fetchable-output
    # budget tracks the smoke cache leaf count plus the tick outputs
    n_cache = len(jax.tree_util.tree_leaves(cache))
    eps.append(EntryPoint(
        "batched_tick_warm", warm,
        (params, c["x"], c["kv_valid"], c["bs"], c["k"], c["srng"], cache),
        resident_argnums=(0, 1, 2, 6),
        max_h2d=4, max_d2h=6 + n_cache))

    # -- SPMD shard_mapped tick (bypass the lru_cache: __wrapped__) -------
    mesh = make_debug_mesh(1, 1)
    spmd = diffusion.get_spmd_tick_fn.__wrapped__(
        model, dcfg, mask_id, mesh, jit_steps=False)
    eps.append(EntryPoint(
        "spmd_tick", spmd,
        (params, c["x"], c["kv_valid"], c["bs"], c["k"], c["srng"], None),
        resident_argnums=(0, 1, 2, 6),
        max_h2d=4, max_d2h=6, mesh_axes=("data", "model")))

    # -- megatick: K fused ticks in one while_loop dispatch ---------------
    k_max = 4
    state = jax.eval_shape(
        lambda: diffusion.megatick_state(
            jnp.full((B,), 8, jnp.int32), jnp.full((B,), 2, jnp.int32),
            dcfg))
    mega_args = (params, c["x"], c["kv_valid"], state, c["srng"],
                 sds((), "int32"), sds((), "bool"), None)
    mega = diffusion.get_megatick_fn.__wrapped__(
        model, dcfg, mask_id, k_max, jit_steps=False)
    eps.append(EntryPoint(
        "megatick", mega, mega_args,
        resident_argnums=(0, 1, 2, 3, 7),   # params, x, kv, state, cache
        max_h2d=4, max_d2h=24,
        jitted=diffusion.get_megatick_fn.__wrapped__(
            model, dcfg, mask_id, k_max, jit_steps=True),
        min_aliased=1))                     # donated canvas (cache is None)

    # -- mesh megatick: while_loop inside one shard_map -------------------
    mega_mesh = diffusion.get_megatick_fn.__wrapped__(
        model, dcfg, mask_id, k_max, mesh=mesh, jit_steps=False)
    eps.append(EntryPoint(
        "megatick_mesh", mega_mesh, mega_args,
        resident_argnums=(0, 1, 2, 3, 7),
        max_h2d=4, max_d2h=24, mesh_axes=("data", "model"),
        jitted=diffusion.get_megatick_fn.__wrapped__(
            model, dcfg, mask_id, k_max, mesh=mesh, jit_steps=True),
        min_aliased=1))

    # -- paged tick/megatick: block-table gather -> tick body -> scatter --
    ps = 8
    R = s_tot // ps
    n_pages = 1 + B * R                     # page 0 reserved null
    table = sds((B, R), "int32")
    pages = sds((n_pages, ps), "int32")
    ptick = diffusion.get_paged_tick_fn.__wrapped__(
        model, dcfg, mask_id, ps, s_tot, with_cache=False, jit_steps=False)
    eps.append(EntryPoint(
        "paged_tick", ptick,
        (params, pages, None, table, table, c["kv_valid"], c["bs"],
         c["k"], c["srng"]),
        # params, page store, cache, both block-table mirrors, kv_valid
        resident_argnums=(0, 1, 2, 3, 4, 5),
        max_h2d=4, max_d2h=7))

    pmega = diffusion.get_paged_megatick_fn.__wrapped__(
        model, dcfg, mask_id, k_max, ps, s_tot, with_cache=False,
        jit_steps=False)
    pmega_args = (params, pages, None, table, table, c["kv_valid"], state,
                  c["srng"], sds((), "int32"), sds((), "bool"))
    eps.append(EntryPoint(
        "paged_megatick", pmega, pmega_args,
        resident_argnums=(0, 1, 2, 3, 4, 5, 6),
        max_h2d=4, max_d2h=25,
        jitted=diffusion.get_paged_megatick_fn.__wrapped__(
            model, dcfg, mask_id, k_max, ps, s_tot, with_cache=False,
            jit_steps=True),
        min_aliased=1))                     # donated page store (no cache)

    # -- Pallas kernel wrappers (callback-primitive scan only) ------------
    d, v, dh = 64, 257, 16                  # smoke dims
    kernels = [
        ("ops.fused_head_sampling",
         functools.partial(ops.fused_head_sampling, interpret=True),
         (sds((16, d), "float32"), sds((d, v), "float32"))),
        ("ops.fused_sampling",
         functools.partial(ops.fused_sampling, interpret=True),
         (sds((16, v), "float32"),)),
        ("ops.transfer_mask",
         functools.partial(ops.transfer_mask, interpret=True),
         (sds((4, 8), "float32"), sds((4, 8), "bool"),
          sds((4,), "int32"))),
        ("ops.baos_quantize",
         functools.partial(ops.baos_quantize, interpret=True),
         (sds((2, 128, 2, 32), "float32"), sds((2, 1, 2, 32), "float32"),
          sds((2, 1, 2, 32), "float32"))),
        ("ops.flash_attention",
         functools.partial(ops.flash_attention, interpret=True),
         (sds((1, 4, 32, dh), "float32"), sds((1, 4, 32, dh), "float32"),
          sds((1, 4, 32, dh), "float32"))),
    ]
    for name, fn, args in kernels:
        eps.append(EntryPoint(name, fn, args, resident_argnums=(),
                              max_h2d=99, max_d2h=99, kernel_only=True))
    return eps


# jaxpr primitives that smuggle host round-trips into compiled code
FORBIDDEN_PRIMITIVES: Tuple[str, ...] = (
    "pure_callback", "io_callback", "debug_callback", "callback",
    "infeed", "outfeed",
)

# collective primitives whose axis names must stay on declared mesh axes
COLLECTIVE_PRIMITIVES: Tuple[str, ...] = (
    "psum", "pmax", "pmin", "pmean", "ppermute", "pbroadcast",
    "all_gather", "all_to_all", "reduce_scatter", "axis_index",
    "psum_scatter",
)

# recompilation guard: max distinct jit-cache entries per executable over
# the replayed engine shape trace (mixed k_req / stop flags / rng must all
# be traced operands, never static keys)
RECOMPILE_BOUNDS: Dict[str, int] = {
    "megatick": 1,
    "megatick_mesh": 1,
    "tick": 2,          # one per distinct live batch shape in the replay
}
