"""V_TOPK_MASK kernel: streaming top-k transfer mask over block positions.

DART implements an O(k)-area insertion comparator producing a boolean
transfer mask over the L active-block positions.  On TPU the natural
formulation is a rank computation over the (tiny) L-vector held entirely in
VMEM: stable rank r_i = #{j : c_j > c_i} + #{j < i : c_j == c_i}, then
transfer_i = (r_i < min(k, #masked)) & masked_i — identical output to the
argsort-of-argsort reference (core/sampling.topk_transfer_mask) including
tie handling.  L <= 64 so the O(L^2) comparison block is trivially
VMEM-resident; k is a per-row *runtime* input (the diffusion transfer
schedule varies per batch element).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG = -1e30  # python float: pallas kernels cannot capture array constants


def _kernel(conf_ref, mask_ref, k_ref, out_ref):
    c = conf_ref[...].astype(jnp.float32)            # (TILE_R, L)
    m = mask_ref[...] > 0                            # (TILE_R, L)
    k = k_ref[...]                                   # (TILE_R, 1)
    c = jnp.where(m, c, NEG)

    ci = c[:, :, None]                               # (R, L, 1) "self"
    cj = c[:, None, :]                               # (R, 1, L) "other"
    ii = jax.lax.broadcasted_iota(jnp.int32, ci.shape, 1)
    jj = jax.lax.broadcasted_iota(jnp.int32, cj.shape, 2)
    gt = (cj > ci) | ((cj == ci) & (jj < ii))        # stable descending rank
    rank = jnp.sum(gt.astype(jnp.int32), axis=2)     # (R, L)

    take = jnp.minimum(k, jnp.sum(m.astype(jnp.int32), axis=-1,
                                  keepdims=True))
    out = (rank < take) & m
    out_ref[...] = out.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("tile_r", "interpret"))
def topk_mask(conf: jax.Array, mask: jax.Array, k: jax.Array, *,
              tile_r: int = 8, interpret: bool = False) -> jax.Array:
    """conf (R, L) f32; mask (R, L) {0,1}; k (R,) i32 -> transfer (R, L) i32."""
    R, L = conf.shape
    pad_r = (-R) % tile_r
    if pad_r:
        conf = jnp.pad(conf, ((0, pad_r), (0, 0)))
        mask = jnp.pad(mask, ((0, pad_r), (0, 0)))
        k = jnp.pad(k, (0, pad_r))
    Rp = conf.shape[0]

    out = pl.pallas_call(
        _kernel,
        grid=(Rp // tile_r,),
        in_specs=[pl.BlockSpec((tile_r, L), lambda r: (r, 0)),
                  pl.BlockSpec((tile_r, L), lambda r: (r, 0)),
                  # (TILE_R, 1): Mosaic tiles a rank-1 block only when it
                  # spans the array or a multiple of 128 rows
                  pl.BlockSpec((tile_r, 1), lambda r: (r, 0))],
        out_specs=pl.BlockSpec((tile_r, L), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((Rp, L), jnp.int32),
        interpret=interpret,
    )(conf, mask.astype(jnp.int32), k.astype(jnp.int32)[:, None])
    return out[:R]
