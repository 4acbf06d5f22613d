"""Fused LM-head + Stable-Max sampling kernel (paper §3.2 -> TPU Pallas).

The hottest loop of dLLM serving is the per-step sampling stage: project the
active-block hidden states through the (d, V) LM head and run Stable-Max
over the vocabulary.  The unfused path writes the (R, V) logits to HBM and
reads them back — exactly the vocab-wide traffic the paper identifies as up
to 70% of inference latency.  This kernel streams the head GEMM instead:

  grid (R / TILE_R, V / CHUNK_V), vocab innermost.  Each step loads the
  (TILE_R, d) hidden tile (revisited per vocab chunk) and one (d, CHUNK_V)
  weight slab into VMEM, computes the logit tile on the MXU, fake-quantizes
  it to the sampling precision (bf16 / MXFP8 per 32-wide OCP block), and
  folds it into the per-row running (max m, argmax i, exp-sum s) scratch
  with the online-softmax rescaling

      m' = max(m, m_c);  s' = s * e^(m - m') + sum_j e^(z_j - m')

  so the logits live only in VMEM.  HBM traffic: R*d + d*V instead of R*V
  (+ the R*V writeback the unfused head pays).  Mask-token suppression is a
  comparator skip on the global column id; temperature > 0 adds a Gumbel
  perturbation drawn from the shared counter-based stream
  (core/sampling.counter_gumbel) so the pure-jnp oracle
  (core/sampling.fused_head_stable_max) reproduces the draw bit-for-bit.

Outputs: confidence (R,) f32 and sampled token (R,) i32 — the L-sized
FP/Int "domains" of the paper, written once at the final vocab chunk.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import mx
from repro.core import sampling as sampling_lib

NEG = -1e30  # python float: pallas kernels cannot capture array constants

SUPPORTED_FMTS = ("none", "bf16", "mxfp8_e4m3")
_MX_BLOCK = mx.MX_BLOCK


def _block_amax(a: jax.Array, block: int) -> jax.Array:
    """Max of each aligned ``block``-lane group of ``a`` (r, c), broadcast
    back over the group's lanes.  A log2(block)-step XOR butterfly of lane
    rotations: Mosaic cannot lay out the (r, c/block, block) reshape the
    jnp formulation uses, but rotates lanes natively.  Exact (a max), so
    the shared scales match mx's reshape-based ones bit for bit."""
    c = a.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
    s = 1
    while s < block:
        below = pltpu.roll(a, s, 1)          # a[lane - s]
        above = pltpu.roll(a, c - s, 1)      # a[lane + s]
        a = jnp.maximum(a, jnp.where((lane & s) != 0, below, above))
        s *= 2
    return a


def _fake_quant_tile(z: jax.Array, fmt: str, model_dtype) -> jax.Array:
    """Per-tile mirror of core/mx.mx_fake_quant for the sampling formats.

    Reuses mx's shared-scale / element-grid helpers directly (the jitted
    mx_fake_quant wrapper cannot be called from a kernel body) so the
    quantization math has a single source of truth.  ``z`` is the f32 logit
    tile already cast through the model dtype; chunk widths are multiples
    of MX_BLOCK so the OCP shared-scale blocks line up exactly with a
    full-row quantization."""
    if fmt == "none":
        return z
    if fmt == "bf16":
        return z.astype(jnp.bfloat16).astype(model_dtype).astype(jnp.float32)
    if fmt == "mxfp8_e4m3":
        fmt_o = mx.FORMATS[fmt]
        q = mx._quant_blocks(z, _block_amax(jnp.abs(z), _MX_BLOCK), fmt_o)
        return q.astype(model_dtype).astype(jnp.float32)
    raise ValueError(f"unsupported sampling fmt for the fused kernel: {fmt}")


def _kernel(seed_ref, h_ref, w_ref, conf_ref, idx_ref,
            m_sc, s_sc, i_sc, b_sc, z_sc, *, tile_r: int, chunk_v: int,
            n_chunks: int, v_true: int, fmt: str, logit_scale: float,
            temperature: float, suppress_id: Optional[int]):
    r, c = pl.program_id(0), pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc[...], NEG)
        s_sc[...] = jnp.zeros_like(s_sc[...])
        i_sc[...] = jnp.zeros_like(i_sc[...])
        b_sc[...] = jnp.full_like(b_sc[...], NEG)
        z_sc[...] = jnp.full_like(z_sc[...], NEG)

    h = h_ref[...]                                       # (TILE_R, d)
    w = w_ref[...]                                       # (d, CHUNK_V)
    # LM head tile on the MXU: f32 accumulate, cast through the model dtype
    # (bit-mirror of layers.qdot + logit_scale), then sampling fake-quant.
    z = jnp.dot(h, w, preferred_element_type=jnp.float32)
    z = (z.astype(h.dtype) * logit_scale).astype(jnp.float32)
    z = _fake_quant_tile(z, fmt, h.dtype)

    col = jax.lax.broadcasted_iota(jnp.int32, z.shape, 1) + c * chunk_v
    z = jnp.where(col < v_true, z, NEG)                  # vocab pad columns
    if suppress_id is not None:
        z = jnp.where(col == suppress_id, NEG, z)        # V_RED skip

    # per-row state is (TILE_R, 1): Mosaic tiles a rank-1 block only when
    # it spans the array or a multiple of 128 rows
    local_m = jnp.max(z, axis=-1, keepdims=True)         # V_RED_MAX
    big = jnp.int32(2 ** 30)
    m_old, s_old = m_sc[...], s_sc[...]
    m_new = jnp.maximum(m_old, local_m)
    s_new = s_old * jnp.exp(m_old - m_new) + jnp.sum(   # V_EXP_V + V_RED_SUM
        jnp.exp(z - m_new), axis=-1, keepdims=True)
    m_sc[...], s_sc[...] = m_new, s_new

    if temperature > 0.0:
        rows = jax.lax.broadcasted_iota(jnp.int32, z.shape, 0) + r * tile_r
        g = sampling_lib.counter_gumbel(seed_ref[0, 0], rows, col)
        sc = z / temperature + g                         # Gumbel-max trick
        local_b = jnp.max(sc, axis=-1, keepdims=True)
        li = jnp.min(jnp.where(sc >= local_b, col, big), axis=-1,
                     keepdims=True)
        z_li = jnp.max(jnp.where(col == li, z, NEG), axis=-1, keepdims=True)
        upd = local_b > b_sc[...]
        b_sc[...] = jnp.where(upd, local_b, b_sc[...])
        i_sc[...] = jnp.where(upd, li, i_sc[...])
        z_sc[...] = jnp.where(upd, z_li, z_sc[...])
    else:
        # first-occurrence argmax (matches jnp.argmax tie-breaking)
        local_i = jnp.min(jnp.where(z >= local_m, col, big), axis=-1,
                          keepdims=True)
        i_sc[...] = jnp.where(local_m > m_old, local_i, i_sc[...])

    @pl.when(c == n_chunks - 1)
    def _fin():
        if temperature > 0.0:
            conf_ref[...] = jnp.exp(z_sc[...] - m_new) / s_new
        else:
            conf_ref[...] = 1.0 / s_new                  # S_RECIP (Eq. 3)
        idx_ref[...] = i_sc[...]


@functools.partial(jax.jit, static_argnames=(
    "tile_r", "chunk_v", "fmt", "logit_scale", "temperature", "suppress_id",
    "interpret"))
def fused_head_sampling(hidden: jax.Array, w_head: jax.Array,
                        seed: jax.Array, *, tile_r: int = 8,
                        chunk_v: int = 512, fmt: str = "none",
                        logit_scale: float = 1.0, temperature: float = 0.0,
                        suppress_id: Optional[int] = None,
                        interpret: bool = False
                        ) -> Tuple[jax.Array, jax.Array]:
    """hidden (R, d), w_head (d, V), seed uint32 scalar ->
    (conf (R,) f32, token (R,) i32).  Pads R and V (zero weight columns
    produce exact-zero logits, masked to -inf before the reductions)."""
    if fmt not in SUPPORTED_FMTS:
        raise ValueError(f"fmt {fmt!r} not in {SUPPORTED_FMTS}")
    R, d = hidden.shape
    V = w_head.shape[-1]
    # head weights join the GEMM in the activation dtype, exactly like
    # layers.qdot / sampling.head_logits — required for the bit-identity pin
    w_head = w_head.astype(hidden.dtype)
    chunk_v, _ = sampling_lib._chunk_grid(V, chunk_v)
    pad_r = (-R) % tile_r
    pad_v = (-V) % chunk_v
    if pad_r:
        hidden = jnp.pad(hidden, ((0, pad_r), (0, 0)))
    if pad_v:
        w_head = jnp.pad(w_head, ((0, 0), (0, pad_v)))
    Rp, Vp = hidden.shape[0], w_head.shape[-1]
    n_chunks = Vp // chunk_v

    conf, idx = pl.pallas_call(
        functools.partial(
            _kernel, tile_r=tile_r, chunk_v=chunk_v, n_chunks=n_chunks,
            v_true=V, fmt=fmt, logit_scale=logit_scale,
            temperature=temperature, suppress_id=suppress_id),
        grid=(Rp // tile_r, n_chunks),
        in_specs=[pl.BlockSpec((1, 1), lambda r, c: (0, 0),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec((tile_r, d), lambda r, c: (r, 0)),
                  pl.BlockSpec((d, chunk_v), lambda r, c: (0, c))],
        out_specs=[pl.BlockSpec((tile_r, 1), lambda r, c: (r, 0)),
                   pl.BlockSpec((tile_r, 1), lambda r, c: (r, 0))],
        out_shape=[jax.ShapeDtypeStruct((Rp, 1), jnp.float32),
                   jax.ShapeDtypeStruct((Rp, 1), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((tile_r, 1), jnp.float32),
                        pltpu.VMEM((tile_r, 1), jnp.float32),
                        pltpu.VMEM((tile_r, 1), jnp.int32),
                        pltpu.VMEM((tile_r, 1), jnp.float32),
                        pltpu.VMEM((tile_r, 1), jnp.float32)],
        interpret=interpret,
        # the profiler trace finds the head by this name
        name="fused_head_sampling",
    )(seed.reshape(1, 1).astype(jnp.uint32), hidden, w_head)
    return conf[:R, 0], idx[:R, 0]
