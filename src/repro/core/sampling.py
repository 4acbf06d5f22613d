"""Diffusion sampling stage (paper §3.2, Alg. 2) in JAX.

Per masked position, over the vocabulary logit vector z in R^V:

  Stable-Max (Eq. 3):  m = max_i z_i,  i* = argmax_i z_i,
                       conf = softmax(z)[i*] = 1 / sum_j exp(z_j - m)

followed by a top-k over positions (V_TOPK_MASK) and an integer masked
commit (V_SELECT_INT == jnp.where).  The full probability vector is *never*
materialized — that is the paper's core sampling insight and what the Pallas
kernel (kernels/stablemax_sampling.py) implements with VMEM chunking.

This module provides
  * the pure-jnp reference used as the kernels' oracle,
  * the **fused LM-head + Stable-Max** path (``fused_head_stable_max`` /
    ``fused_sampling_step_full``): the head GEMM is streamed vocab-chunk by
    vocab-chunk straight into the online (m, argmax, exp-sum) reduction so
    the (R, V) logits tensor is *never materialized* — HBM traffic drops
    from O(R*V) to O(R*d + d*V) (docs/fused_sampling.md),
  * the *vocab-sharded* combine used under the production mesh (model-axis
    sharded LM head -> per-shard (m, idx, S) triples merged with one tiny
    collective; the cross-chip analogue of the paper's V_chunk streaming),
  * the position-level top-k transfer mask and token commit.

Sampling precision (paper Fig. 1 / §6.1: FP64 -> BF16 -> MXFP8) is emulated
by fake-quantizing the logits to ``fmt`` before the reductions.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import mx
from repro.sim import isa as isa_lib
from repro.sim import trace as trace_lib

# Modeled storage format of the LM-head weight stream for trace capture
# (matches sim/analytical's w_bytes=0.5 MXINT4 default).
TRACE_W_FMT = "mxint4"


def _rows_of(a: jax.Array) -> int:
    """Static product of the leading (non-vocab) dims — shapes are always
    concrete under jax tracing, so trace hooks can read them."""
    return int(math.prod(a.shape[:-1]))


def _emit_head_stream(R: int, d: int, chunk: int, n_chunks: int,
                      gumbel: bool = False) -> None:
    """Trace hook for the streamed-head chunk loop (the lax.scan bodies
    below trace once regardless of trip count, so the per-chunk op group is
    emitted here, from the real chunk grid, and the scan runs under
    ``trace_lib.suppress()``).  One vocab chunk = weight slab burst into
    VMEM, MXU logit tile, online (max+idx, exp, sum) reduction, carry
    rescale; the slab and logit tile are alloc/freed every chunk so the
    simulator's allocator observes the in-place reuse."""
    trace_lib.emit("HBM_RD", (R, d), "bf16", "stream", "hidden")
    trace_lib.emit("SRAM_ALLOC", (3, R), "fp32", "stream", "carry")
    for _ in range(n_chunks):
        trace_lib.emit("SRAM_ALLOC", (d, chunk), TRACE_W_FMT, "stream",
                       "w_slab")
        trace_lib.emit("HBM_RD", (d, chunk), TRACE_W_FMT, "stream", "head_w")
        trace_lib.emit("SRAM_ALLOC", (isa_lib.TILE_R, chunk), "fp32",
                       "stream", "logit_tile")
        trace_lib.emit("GEMM_TILE", (R, d, chunk), stage="stream")
        trace_lib.emit("V_RED_MAX_IDX", (R, chunk), stage="stream")
        trace_lib.emit("V_EXP_V", (R, chunk), stage="stream")
        trace_lib.emit("V_RED_SUM", (R, chunk), stage="stream")
        if gumbel:
            trace_lib.emit("V_GUMBEL", (R, chunk), stage="stream")
            trace_lib.emit("V_ADD_VV", (R, chunk), stage="stream",
                           note="gumbel_score")
            trace_lib.emit("V_RED_MAX", (R, chunk), stage="stream",
                           note="best_score")
            trace_lib.emit("V_SELECT_INT", (3, R), stage="stream",
                           note="best_update")
        trace_lib.emit("V_ADD_VV", (R,), stage="stream",
                       note="online_rescale")
        trace_lib.emit("SRAM_FREE", stage="stream", note="logit_tile")
        trace_lib.emit("SRAM_FREE", stage="stream", note="w_slab")
    trace_lib.emit("SRAM_FREE", stage="stream", note="carry")


@dataclasses.dataclass(frozen=True)
class SamplingConfig:
    fmt: str = "mxfp8_e4m3"     # sampling precision: bf16 | mxfp8_e4m3 | none
    temperature: float = 0.0     # 0 => greedy (LLaDA reference)
    strategy: str = "stablemax"  # "stablemax" (low-confidence) | "random"
    suppress_mask_token: bool = True  # never sample the mask id itself


# ---------------------------------------------------------------------------
# Stable-Max confidence + argmax (reference; oracle for the Pallas kernel)
# ---------------------------------------------------------------------------

def stable_max(logits: jax.Array, fmt: str = "none",
               rng: Optional[jax.Array] = None, temperature: float = 0.0,
               suppress_id: Optional[int] = None
               ) -> Tuple[jax.Array, jax.Array]:
    """logits (..., V) -> (confidence (...), token (...) int32).

    With temperature > 0, tokens are Gumbel-max sampled and the confidence is
    the (un-tempered) softmax probability of the sampled token, matching the
    LLaDA reference sampler.  ``suppress_id`` excludes one token (the mask
    id) from the reductions *after* quantization — the hardware analogue is
    the comparator skipping that index, so the -inf must never enter the MX
    block scaling (it would zero its 31 neighbours).
    """
    if trace_lib.is_active():
        rows, V = _rows_of(logits), logits.shape[-1]
        trace_lib.emit("HBM_RD", (rows, V), fmt, "stream", "logits")
        trace_lib.emit("SRAM_ALLOC", (3, rows), "fp32", "stream", "carry")
        if temperature > 0.0 and rng is not None:
            trace_lib.emit("V_GUMBEL", (rows, V), stage="stream")
            trace_lib.emit("V_ADD_VV", (rows, V), stage="stream",
                           note="gumbel_score")
        trace_lib.emit("V_RED_MAX_IDX", (rows, V), stage="stream")
        trace_lib.emit("V_EXP_V", (rows, V), stage="stream")
        trace_lib.emit("V_RED_SUM", (rows, V), stage="stream")
        trace_lib.emit("SRAM_FREE", stage="stream", note="carry")
        trace_lib.emit("S_RECIP", (rows,), stage="tail")
        trace_lib.emit("S_ST", (2 * rows,), stage="tail", note="conf_idx_wb")
    z = mx.mx_fake_quant(logits, fmt).astype(jnp.float32)
    if suppress_id is not None:
        v = z.shape[-1]
        z = jnp.where(jnp.arange(v) == suppress_id, NEG_INF, z)
    m = jnp.max(z, axis=-1)
    s = jnp.sum(jnp.exp(z - m[..., None]), axis=-1)
    if temperature > 0.0 and rng is not None:
        g = jax.random.gumbel(rng, z.shape, jnp.float32)
        idx = jnp.argmax(z / temperature + g, axis=-1).astype(jnp.int32)
        z_at = jnp.take_along_axis(z, idx[..., None].astype(jnp.int32),
                                   axis=-1)[..., 0]
        conf = jnp.exp(z_at - m) / s
    else:
        idx = jnp.argmax(z, axis=-1).astype(jnp.int32)
        conf = 1.0 / s                      # numerator e^0 = 1 (Eq. 3)
    return conf, idx


def stable_max_two_pass(logits: jax.Array, fmt: str = "none"):
    """Paper-faithful phase structure: pass 1 = V_RED_MAX_IDX, pass 2 =
    V_EXP_V + V_RED_SUM, then S_RECIP.  Numerically identical to
    ``stable_max``; kept separate because the analytical model charges it
    2x logit reads (the beyond-paper single-pass kernel reads once)."""
    z = mx.mx_fake_quant(logits, fmt).astype(jnp.float32)
    m = jnp.max(z, axis=-1)                          # pass 1a
    idx = jnp.argmax(z, axis=-1).astype(jnp.int32)   # pass 1b (fused max+idx)
    s = jnp.sum(jnp.exp(z - m[..., None]), axis=-1)  # pass 2
    return 1.0 / s, idx


# ---------------------------------------------------------------------------
# Vocab-sharded combine (runs inside shard_map; axis 'model' shards V)
# ---------------------------------------------------------------------------

def local_partials(logits_shard: jax.Array, fmt: str = "none"):
    """Per-shard partials: (m_l, idx_l, s_l) with s_l relative to m_l."""
    z = mx.mx_fake_quant(logits_shard, fmt).astype(jnp.float32)
    m = jnp.max(z, axis=-1)
    idx = jnp.argmax(z, axis=-1).astype(jnp.int32)
    s = jnp.sum(jnp.exp(z - m[..., None]), axis=-1)
    return m, idx, s


def combine_partials(m: jax.Array, gidx: jax.Array, s: jax.Array,
                     axis_name: str) -> Tuple[jax.Array, jax.Array]:
    """Merge per-shard (m, global idx, s) Stable-Max partials over
    ``axis_name``:  m = max_i m_i, S = sum_i S_i * exp(m_i - m), idx from
    the shard owning the global max (lowest shard index breaks ties).
    One pmax + one psum + one pmin of scalars per position."""
    if trace_lib.is_active():
        trace_lib.emit_combine(int(math.prod(m.shape)))
    gm = jax.lax.pmax(m, axis_name)
    gs = jax.lax.psum(s * jnp.exp(m - gm), axis_name)
    big = jnp.int32(2 ** 30)
    cand = jnp.where(m >= gm, gidx, big)
    gi = jax.lax.pmin(cand, axis_name)
    return 1.0 / gs, gi.astype(jnp.int32)


def sharded_stable_max(logits_shard: jax.Array, axis_name: str,
                       fmt: str = "none") -> Tuple[jax.Array, jax.Array]:
    """Stable-Max over a vocab axis sharded on ``axis_name``.

    Combine rule (DESIGN.md §7.2): see ``combine_partials`` —
    O(V/n_shards) logit traffic per chip.
    """
    shard = jax.lax.axis_index(axis_name)
    vloc = logits_shard.shape[-1]
    m, idx, s = local_partials(logits_shard, fmt)
    return combine_partials(m, idx + shard * vloc, s, axis_name)


# ---------------------------------------------------------------------------
# Fused LM-head + Stable-Max (logits never materialized; docs/fused_sampling.md)
# ---------------------------------------------------------------------------

def _mix32(x: jax.Array) -> jax.Array:
    """splitmix-style uint32 finalizer (avalanching integer hash)."""
    x = x.astype(jnp.uint32)
    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def counter_gumbel(seed: jax.Array, rows: jax.Array, cols: jax.Array
                   ) -> jax.Array:
    """Deterministic counter-based Gumbel(0,1) noise g(seed, row, col).

    Shared by the fused-head oracle and the Pallas kernel so both draw the
    *same* per-(row, token) noise tile-by-tile without ever materializing a
    (R, V) noise tensor (a stateless analogue of jax's threefry draw)."""
    h = _mix32(rows.astype(jnp.uint32) * jnp.uint32(0x9E3779B9)
               ^ seed.astype(jnp.uint32))
    h = _mix32(h ^ cols.astype(jnp.uint32) * jnp.uint32(0x85EBCA6B))
    # the top 24 bits fit int32 exactly; Mosaic has no uint32 -> f32 cast
    u = ((h >> jnp.uint32(8)).astype(jnp.int32).astype(jnp.float32) + 0.5) \
        * (1.0 / (1 << 24))
    return -jnp.log(-jnp.log(u))


def gumbel_seed(rng: jax.Array) -> jax.Array:
    """Fold a PRNG key into the uint32 seed of the counter-Gumbel stream."""
    return jax.random.bits(jax.random.fold_in(rng, 0x5A11), (), jnp.uint32)


def head_logits(hidden: jax.Array, w_head: jax.Array, *,
                logit_scale: float = 1.0, quant=None) -> jax.Array:
    """hidden (..., d) @ w_head (d, V) -> logits (..., V) in hidden.dtype.

    Bit-for-bit mirror of the in-model LM head (layers.qdot + logit_scale):
    f32 accumulation, cast back to the activation dtype, then scale.  Used
    by the unfused block-sliced fallback and, chunk-by-chunk, by the fused
    oracle — chunking the N axis leaves each output element's K-reduction
    untouched, which is what keeps fused and unfused greedy tokens
    bit-identical."""
    if trace_lib.is_active():
        M, K, N = _rows_of(hidden), hidden.shape[-1], w_head.shape[-1]
        trace_lib.emit("HBM_RD", (M, K), "bf16", "head", "hidden")
        trace_lib.emit("HBM_RD", (K, N), TRACE_W_FMT, "head", "head_w")
        trace_lib.emit("GEMM_TILE", (M, K, N), stage="head")
        trace_lib.emit("HBM_WR", (M, N), "bf16", "head", "logits")
    if quant is not None and quant.enabled:
        hidden, w_head = quant.acts(hidden), quant.weights(w_head)
    z = jax.lax.dot_general(
        hidden, w_head.astype(hidden.dtype),
        (((hidden.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return z.astype(hidden.dtype) * logit_scale


def _chunk_grid(V: int, chunk_v: int) -> Tuple[int, int]:
    """(chunk, padded V): chunks are rounded down to multiples of the MX
    block (min one block) so per-chunk fake-quant sees the exact 32-wide
    blocks full-row fake-quant sees; shared by the jnp oracle and the
    Pallas kernel so both tile the vocab identically."""
    chunk_v = max(mx.MX_BLOCK, chunk_v - chunk_v % mx.MX_BLOCK)
    ceil32 = -(-V // mx.MX_BLOCK) * mx.MX_BLOCK
    chunk = min(chunk_v, ceil32)
    return chunk, -(-V // chunk) * chunk


def _prep_stream(hidden: jax.Array, w: jax.Array, chunk_v: int, quant):
    """Shared prologue of the streamed-head scans: chunk grid, zero-pad the
    vocab tail (zero weight columns -> exact-zero logits, masked later),
    apply the GEMM-boundary quant policy once."""
    V = w.shape[-1]
    chunk, Vp = _chunk_grid(V, chunk_v)
    if Vp != V:
        w = jnp.pad(w, ((0, 0), (0, Vp - V)))
    if quant is not None and quant.enabled:
        hidden, w = quant.acts(hidden), quant.weights(w)
    return hidden, w, V, chunk, Vp // chunk


def _stream_chunk(h, w_pad, c, chunk, V, fmt, logit_scale, suppress_id,
                  col_offset, col_limit=None):
    """One quantized f32 logit tile (R, chunk) + its local column ids —
    the single source of truth for the oracle scans' per-chunk math
    (pad-column masking and post-quant suppression included).
    ``col_limit`` masks *global* columns >= the true vocab size: under the
    SPMD mesh the head is zero-padded before sharding, so a shard's local
    width V may extend past the real vocabulary."""
    wc = jax.lax.dynamic_slice_in_dim(w_pad, c * chunk, chunk, axis=1)
    z = head_logits(h, wc, logit_scale=logit_scale)
    z = mx.mx_fake_quant(z, fmt).astype(jnp.float32)
    col = c * chunk + jnp.arange(chunk, dtype=jnp.int32)[None, :]
    z = jnp.where(col < V, z, NEG_INF)
    if col_limit is not None:
        z = jnp.where(col + col_offset < col_limit, z, NEG_INF)
    if suppress_id is not None:
        z = jnp.where(col + col_offset == suppress_id, NEG_INF, z)
    return z, col


def _online_ms(m, s, z):
    """Online-softmax rescale: fold one logit tile into (max, exp-sum)."""
    local_m = jnp.max(z, axis=-1)
    m_new = jnp.maximum(m, local_m)
    s_new = s * jnp.exp(m - m_new) + \
        jnp.sum(jnp.exp(z - m_new[:, None]), axis=-1)
    return m_new, s_new, local_m


def fused_head_local_partials(hidden: jax.Array, w_shard: jax.Array,
                              fmt: str = "none", *, logit_scale: float = 1.0,
                              col_offset=0, suppress_id: Optional[int] = None,
                              chunk_v: int = 4096, quant=None,
                              col_limit: Optional[int] = None):
    """Streamed-head Stable-Max partials over one vocab shard.

    hidden (R, d), w_shard (d, V_loc) -> (m (R,), gidx (R,), s (R,)) with s
    relative to m and gidx global (``col_offset`` = shard * V_loc).  The
    logit chunks live only inside the scan carry — never (R, V_loc) at once.
    """
    R = hidden.shape[0]
    hidden, w_shard, V, chunk, n_chunks = _prep_stream(hidden, w_shard,
                                                       chunk_v, quant)
    col_offset = jnp.asarray(col_offset, jnp.int32)
    if trace_lib.is_active():
        _emit_head_stream(R, hidden.shape[-1], chunk, n_chunks)

    def body(carry, c):
        m, idx, s = carry
        z, col = _stream_chunk(hidden, w_shard, c, chunk, V, fmt,
                               logit_scale, suppress_id, col_offset,
                               col_limit)
        m_new, s_new, local_m = _online_ms(m, s, z)
        big = jnp.int32(2 ** 30)
        local_i = jnp.min(jnp.where(z >= local_m[:, None], col, big), axis=-1)
        idx = jnp.where(local_m > m, local_i, idx)     # first chunk wins ties
        return (m_new, idx, s_new), None

    init = (jnp.full((R,), NEG_INF), jnp.zeros((R,), jnp.int32),
            jnp.zeros((R,), jnp.float32))
    # inside shard_map the carry turns varying over every manual axis the
    # operands vary over; the scan needs its init typed the same way
    vma = tuple(sorted(jax.typeof(hidden).vma | jax.typeof(w_shard).vma
                       | jax.typeof(col_offset).vma))
    if vma:
        init = jax.lax.pcast(init, vma, to="varying")
    with trace_lib.suppress():
        (m, idx, s), _ = jax.lax.scan(body, init,
                                      jnp.arange(n_chunks, dtype=jnp.int32))
    return m, idx + col_offset, s


def fused_head_stable_max(hidden: jax.Array, w_head: jax.Array,
                          fmt: str = "none", *, logit_scale: float = 1.0,
                          rng: Optional[jax.Array] = None,
                          temperature: float = 0.0,
                          suppress_id: Optional[int] = None,
                          chunk_v: int = 4096, quant=None
                          ) -> Tuple[jax.Array, jax.Array]:
    """Fused hidden (..., d) @ w_head (d, V) -> (conf (...), token (...)).

    Pure-jnp oracle for kernels/fused_head_sampling.py: lax.scan streams the
    head GEMM one (R, chunk_v) logit tile at a time into the online
    (m, argmax, exp-sum) reduction, so HBM traffic is O(R*d + d*V) instead
    of O(R*V).  Numerically this computes exactly what
    ``stable_max(head_logits(...), fmt, ...)`` computes for greedy decoding
    (identical per-element logits -> identical argmax tokens; the exp-sum
    differs only in accumulation order).  With temperature > 0 the Gumbel
    draw comes from the counter-based stream (``counter_gumbel``) rather
    than jax.random.gumbel, so tiles can regenerate their own noise.
    """
    *lead, d = hidden.shape
    h = hidden.reshape(-1, d)
    if not (temperature > 0.0 and rng is not None):
        # greedy: exactly the single-shard streamed partials, conf = 1/S
        m, idx, s = fused_head_local_partials(
            h, w_head, fmt, logit_scale=logit_scale,
            suppress_id=suppress_id, chunk_v=chunk_v, quant=quant)
        if trace_lib.is_active():
            trace_lib.emit("S_RECIP", (h.shape[0],), stage="tail")
            trace_lib.emit("S_ST", (2 * h.shape[0],), stage="tail",
                           note="conf_idx_wb")
        return (1.0 / s).reshape(lead), idx.reshape(lead)

    R = h.shape[0]
    h, w_head, V, chunk, n_chunks = _prep_stream(h, w_head, chunk_v, quant)
    if trace_lib.is_active():
        _emit_head_stream(R, h.shape[-1], chunk, n_chunks, gumbel=True)
        trace_lib.emit("S_RECIP", (R,), stage="tail")
        trace_lib.emit("S_ST", (2 * R,), stage="tail", note="conf_idx_wb")
    seed = gumbel_seed(rng)
    rows = jnp.arange(R, dtype=jnp.int32)[:, None]
    zero = jnp.int32(0)

    def body(carry, c):
        m, s, idx, best, z_at = carry
        z, col = _stream_chunk(h, w_head, c, chunk, V, fmt, logit_scale,
                               suppress_id, zero)
        m_new, s_new, _ = _online_ms(m, s, z)
        big = jnp.int32(2 ** 30)
        g = counter_gumbel(seed, jnp.broadcast_to(rows, z.shape), col)
        sc = z / temperature + g                       # Gumbel-max trick
        local_b = jnp.max(sc, axis=-1)
        li = jnp.min(jnp.where(sc >= local_b[:, None], col, big), axis=-1)
        z_li = jnp.take_along_axis(
            z, (li - c * chunk)[:, None], axis=-1)[:, 0]
        upd = local_b > best
        best = jnp.where(upd, local_b, best)
        idx = jnp.where(upd, li, idx)
        z_at = jnp.where(upd, z_li, z_at)
        return (m_new, s_new, idx, best, z_at), None

    init = (jnp.full((R,), NEG_INF), jnp.zeros((R,), jnp.float32),
            jnp.zeros((R,), jnp.int32), jnp.full((R,), NEG_INF),
            jnp.full((R,), NEG_INF))
    with trace_lib.suppress():
        (m, s, idx, _, z_at), _ = jax.lax.scan(
            body, init, jnp.arange(n_chunks, dtype=jnp.int32))
    conf = jnp.exp(z_at - m) / s
    return conf.reshape(lead), idx.reshape(lead)


def pad_head_for_mesh(w_head: jax.Array, n_shards: int) -> jax.Array:
    """Zero-pad the (d, V) LM head so it splits into ``n_shards`` equal
    vocab shards whose width is a multiple of the MX block.

    Shard boundaries on 32-column multiples keep per-shard fake-quant
    blocks aligned with full-row blocks (zero pad columns never raise a
    block's max-abs scale), so sharded greedy argmax stays bit-identical
    to the single-device fused stream; pad logits are masked out via the
    ``col_limit`` of ``fused_head_local_partials``.  No-op when already
    aligned — the serving engine pads once at construction."""
    step = n_shards * mx.MX_BLOCK
    V = w_head.shape[-1]
    Vp = -(-V // step) * step
    if Vp != V:
        w_head = jnp.pad(w_head, ((0, 0), (0, Vp - V)))
    return w_head


def sharded_fused_head_stable_max(hidden: jax.Array, w_shard: jax.Array,
                                  axis_name: str, fmt: str = "none", *,
                                  logit_scale: float = 1.0,
                                  suppress_id: Optional[int] = None,
                                  chunk_v: int = 4096, quant=None,
                                  col_limit: Optional[int] = None
                                  ) -> Tuple[jax.Array, jax.Array]:
    """Fused head + Stable-Max with the LM head sharded on ``axis_name``
    (runs inside shard_map): each chip streams its own (d, V/n) shard
    through ``fused_head_local_partials`` and the per-chip (m, idx, s)
    triples merge with the same tiny collective ``sharded_stable_max``
    uses — per-chip vocab traffic drops to O(R*d + d*V/n)."""
    shard = jax.lax.axis_index(axis_name)
    vloc = w_shard.shape[-1]
    m, gidx, s = fused_head_local_partials(
        hidden.reshape(-1, hidden.shape[-1]), w_shard, fmt,
        logit_scale=logit_scale, col_offset=shard * vloc,
        suppress_id=suppress_id, chunk_v=chunk_v, quant=quant,
        col_limit=col_limit)
    conf, idx = combine_partials(m, gidx, s, axis_name)
    if trace_lib.is_active():
        trace_lib.emit("S_ST", (2 * m.shape[0],), stage="tail",
                       note="conf_idx_wb")
    lead = hidden.shape[:-1]
    return conf.reshape(lead), idx.reshape(lead)


def sharded_fused_sampling_step_full(hidden: jax.Array, w_shard: jax.Array,
                                     x: jax.Array, mask_id: int,
                                     k: jax.Array, cfg: SamplingConfig,
                                     rng: Optional[jax.Array] = None, *,
                                     axis_name: str, logit_scale: float = 1.0,
                                     quant=None, chunk_v: int = 4096,
                                     col_limit: Optional[int] = None
                                     ) -> Tuple[jax.Array, jax.Array,
                                                jax.Array]:
    """``fused_sampling_step_full`` inside shard_map with the LM head
    column-sharded on ``axis_name``: per-shard streamed partials, the
    one-pmax/psum/pmin combine, then the (replicated-per-shard) transfer
    selection and commit.  Greedy only — the counter-Gumbel temperature
    path needs a second best-score combine and is not wired up yet."""
    if cfg.temperature > 0.0 and rng is not None:
        raise NotImplementedError(
            "vocab-sharded sampling supports greedy decoding only "
            "(temperature == 0)")
    m_idx = x == mask_id
    sup = mask_id if cfg.suppress_mask_token else None
    conf, x0 = sharded_fused_head_stable_max(
        hidden, w_shard, axis_name, cfg.fmt, logit_scale=logit_scale,
        suppress_id=sup, chunk_v=chunk_v, quant=quant, col_limit=col_limit)
    return _select_and_commit(conf, x0, x, m_idx, k, cfg, rng)


# ---------------------------------------------------------------------------
# Position-level top-k transfer mask (V_TOPK_MASK) + commit (V_SELECT_INT)
# ---------------------------------------------------------------------------

NEG_INF = -1e30  # python float: importing builds no device array


def topk_transfer_mask(conf: jax.Array, mask_idx: jax.Array,
                       k: jax.Array, use_kernel: Optional[bool] = None
                       ) -> jax.Array:
    """conf (B, L) float; mask_idx (B, L) bool (True = still masked);
    k (B,) int32 -> transfer mask (B, L) bool with exactly min(k, #masked)
    True entries per row, at the highest-confidence masked positions.

    One ``jax.lax.top_k`` (stable: ties break toward the lower index,
    matching the old argsort-of-argsort rank) + one scatter, instead of two
    full L*log(L) sorts per tick; on TPU the Pallas V_TOPK_MASK kernel
    (kernels/topk_mask.py) computes the rank entirely in VMEM."""
    B, L = conf.shape
    if trace_lib.is_active():
        trace_lib.emit("S_MAP_V_FP", (B * L,), stage="commit")
        trace_lib.emit("V_TOPK_MASK_PER_ELT", (B * L,), stage="commit")
    if use_kernel is None:
        # a Pallas kernel cannot be traced inside a vma-checked shard_map
        # (its body mixes varying refs with constants): varying inputs
        # take the jnp path
        use_kernel = (jax.default_backend() == "tpu"
                      and not jax.typeof(conf).vma)
    if use_kernel:
        from repro.kernels import ops                  # lazy: avoid cycle
        return ops.transfer_mask(conf.astype(jnp.float32), mask_idx, k)
    c = jnp.where(mask_idx, conf.astype(jnp.float32), NEG_INF)
    _, order = jax.lax.top_k(c, L)                     # descending, stable
    take = jnp.minimum(k[:, None], jnp.sum(mask_idx, axis=-1, keepdims=True))
    sel = jax.lax.broadcasted_iota(jnp.int32, (B, L), 1) < take
    transfer = jnp.zeros((B, L), bool).at[
        jnp.arange(B, dtype=jnp.int32)[:, None], order].set(sel)
    return transfer & mask_idx


def commit_tokens(x: jax.Array, x0: jax.Array, transfer: jax.Array
                  ) -> jax.Array:
    """Phase 4 integer masked update: commit sampled tokens where selected."""
    return jnp.where(transfer, x0, x)


def sampling_step_full(logits: jax.Array, x: jax.Array, mask_id: int,
                       k: jax.Array, cfg: SamplingConfig,
                       rng: Optional[jax.Array] = None
                       ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One full sampling stage (Alg. 2 phases 1-4) for the active block.

    logits (B, L, V), x (B, L) current tokens, k (B,) tokens to unmask.
    Returns (new tokens (B, L), transfer mask (B, L), conf (B, L)) where
    conf is always the model (Stable-Max) confidence of the sampled tokens —
    even under strategy='random', whose uniform draw only reorders the
    *transfer* selection — so schedulers can gate on it.
    """
    m_idx = x == mask_id
    sup = mask_id if cfg.suppress_mask_token else None
    conf, x0 = stable_max(logits, cfg.fmt, rng, cfg.temperature,
                          suppress_id=sup)
    return _select_and_commit(conf, x0, x, m_idx, k, cfg, rng)


def _select_and_commit(conf, x0, x, m_idx, k, cfg: SamplingConfig, rng
                       ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Shared tail of the (fused and unfused) sampling steps: transfer
    selection, top-k mask, masked commit."""
    select = conf
    if cfg.strategy == "random":
        if rng is None:
            raise ValueError(
                "strategy='random' requires an rng key: without one every "
                "call would reuse the identical PRNGKey(0) transfer order")
        select = jax.random.uniform(rng, conf.shape)
    x0 = jnp.where(m_idx, x0, x)                 # keep committed tokens
    transfer = topk_transfer_mask(select, m_idx, k)
    if trace_lib.is_active():
        trace_lib.emit("V_SELECT_INT", (2 * int(math.prod(x.shape)),),
                       stage="commit")
    return commit_tokens(x, x0, transfer), transfer, conf


def fused_sampling_step_full(hidden: jax.Array, w_head: jax.Array,
                             x: jax.Array, mask_id: int, k: jax.Array,
                             cfg: SamplingConfig,
                             rng: Optional[jax.Array] = None, *,
                             logit_scale: float = 1.0, quant=None,
                             chunk_v: int = 4096,
                             use_kernel: Optional[bool] = None
                             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``sampling_step_full`` fed by active-block *hidden states* instead of
    logits: hidden (B, L, d) + w_head (d, V) stream through the fused
    head + Stable-Max reduction (Pallas kernel on TPU, lax.scan oracle
    elsewhere) so the (B, L, V) logits never exist in HBM.  On the kernel
    path a format outside ``SUPPORTED_FMTS`` raises.  Greedy tokens
    are bit-identical to the unfused path (pinned by
    tests/test_fused_head.py); temperature > 0 draws from the counter-based
    Gumbel stream instead of jax.random.gumbel."""
    m_idx = x == mask_id
    sup = mask_id if cfg.suppress_mask_token else None
    # no rng => greedy, matching stable_max's gating — the kernel must not
    # fall back to a constant seed-0 Gumbel stream
    temp = cfg.temperature if rng is not None else 0.0
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if use_kernel:
        from repro.kernels import ops                  # lazy: avoid cycle
        seed = gumbel_seed(rng) if temp > 0.0 else jnp.uint32(0)
        conf, x0 = ops.fused_head_sampling(
            hidden, w_head, fmt=cfg.fmt, logit_scale=logit_scale,
            suppress_id=sup, temperature=temp, seed=seed,
            chunk_v=chunk_v, quant=quant)
    else:
        conf, x0 = fused_head_stable_max(
            hidden, w_head, cfg.fmt, logit_scale=logit_scale, rng=rng,
            temperature=temp, suppress_id=sup, chunk_v=chunk_v,
            quant=quant)
    return _select_and_commit(conf, x0, x, m_idx, k, cfg, rng)


def sampling_step(logits: jax.Array, x: jax.Array, mask_id: int,
                  k: jax.Array, cfg: SamplingConfig,
                  rng: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """As ``sampling_step_full`` without the confidence output."""
    new_x, transfer, _ = sampling_step_full(logits, x, mask_id, k, cfg, rng)
    return new_x, transfer


def full_softmax_reference(logits: jax.Array):
    """The naive Eq. 2 path (materializes the V-wide probability vector);
    used only to validate Stable-Max equivalence in tests."""
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    idx = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    conf = jnp.take_along_axis(p, idx[..., None], axis=-1)[..., 0]
    return conf, idx
