"""Jit'd public wrappers for the Pallas kernels.

``interpret`` defaults to True off the TPU (kernels execute via the Pallas
interpreter for correctness validation) and False on TPU (compiled
Mosaic).  A CPU host can still compile them for a TPU it does not have:
tests/test_tpu_compile.py lowers the main-path kernels for a described
v5e topology, which is where Mosaic's layout and VMEM refusals surface.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import baos_mx_quant as _bq
from repro.kernels import flash_bidir as _fb
from repro.kernels import fused_head_sampling as _fh
from repro.kernels import stablemax_sampling as _ss
from repro.kernels import topk_mask as _tk


# Rows per fused-head tile at most: each weight byte then feeds 512
# multiply-adds, past a v5e's ridge of ~240 FLOP/B, so the MXU and not HBM
# sets the pace.
HEAD_ROW_TILE = 512
# Logit-tile elements per grid step at most.  Mosaic unrolls the kernel
# body over the tile, and its compile time grows faster than the tile: for
# a v5e, (512 x 4096) x (4096 x 512) tiles took ~4 s, 512 x 256 ~1.3 s.
HEAD_TILE_ELEMS = 512 * 256
# VMEM the fused head's tiles may take (_fh.vmem_bytes), inside the
# kernel's scoped limit _fh.VMEM_LIMIT_BYTES with room for Mosaic's own.
HEAD_VMEM_BUDGET = 32 * 1024 * 1024


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def head_tiles(R: int, d: int, chunk_v: int,
               itemsize: int) -> Tuple[int, int]:
    """(tile_r, chunk_v) of the fused head for R rows of width d.  R splits
    into the fewest equal row tiles of at most ``HEAD_ROW_TILE`` rows, each
    a multiple of 8 (so under 8 padded rows a tile); the head is streamed
    once per tile.  The vocab chunk is the caller's, cut where the tile
    would pass ``HEAD_TILE_ELEMS`` or ``HEAD_VMEM_BUDGET``, and rounded
    down to the 128 lanes a Mosaic block needs (a chunk under 128 stays:
    only the interpreter runs it)."""
    n = -(-R // HEAD_ROW_TILE)
    tile_r = -(-R // (8 * n)) * 8
    fixed = _fh.vmem_bytes(tile_r, 0, d, itemsize)
    per_col = _fh.vmem_bytes(tile_r, 1, d, itemsize) - fixed
    chunk = min(chunk_v, HEAD_TILE_ELEMS // tile_r,
                (HEAD_VMEM_BUDGET - fixed) // per_col)
    return tile_r, max(chunk // 128 * 128, min(chunk_v, 128))


def fused_head_sampling(hidden: jax.Array, w_head: jax.Array, *,
                        fmt: str = "none", logit_scale: float = 1.0,
                        suppress_id: Optional[int] = None,
                        temperature: float = 0.0,
                        seed: Optional[jax.Array] = None,
                        chunk_v: int = 512, quant=None,
                        interpret: Optional[bool] = None
                        ) -> Tuple[jax.Array, jax.Array]:
    """hidden (..., d) @ w_head (d, V) -> (conf (...), token (...)) without
    materializing the (..., V) logits.  Flattens leading dims; the optional
    MX ``quant`` boundary policy is applied outside the kernel (fake-quant
    emulation) so the kernel itself stays a pure streamed head."""
    interp = _default_interpret() if interpret is None else interpret
    batch_shape = hidden.shape[:-1]
    d = hidden.shape[-1]
    flat = hidden.reshape(-1, d)
    if quant is not None and quant.enabled:
        flat, w_head = quant.acts(flat), quant.weights(w_head)
    if seed is None:
        if temperature > 0.0:
            raise ValueError(
                "temperature > 0 requires a seed: without one every call "
                "would draw the identical counter-Gumbel noise stream")
        seed = jnp.uint32(0)
    # the oracle's lax.scan has no VMEM limit, so callers may pass much
    # larger chunks than the kernel's tiles hold
    tile_r, chunk_v = head_tiles(flat.shape[0], d, chunk_v,
                                 flat.dtype.itemsize)
    conf, idx = _fh.fused_head_sampling(
        flat, w_head, seed, tile_r=tile_r, chunk_v=chunk_v, fmt=fmt,
        logit_scale=logit_scale, temperature=temperature,
        suppress_id=suppress_id, interpret=interp)
    return conf.reshape(batch_shape), idx.reshape(batch_shape)


def fused_sampling(logits: jax.Array, suppress_id: Optional[int] = None,
                   tile_r: int = 8, chunk_v: int = 512,
                   interpret: Optional[bool] = None
                   ) -> Tuple[jax.Array, jax.Array]:
    """logits (..., V) -> (conf (...), idx (...)).  Flattens leading dims."""
    interp = _default_interpret() if interpret is None else interpret
    batch_shape = logits.shape[:-1]
    V = logits.shape[-1]
    flat = logits.reshape(-1, V)
    conf, idx = _ss.stablemax_sampling(
        flat, tile_r=tile_r, chunk_v=min(chunk_v, V),
        suppress_id=suppress_id, interpret=interp)
    return conf.reshape(batch_shape), idx.reshape(batch_shape)


def transfer_mask(conf: jax.Array, mask: jax.Array, k: jax.Array,
                  interpret: Optional[bool] = None) -> jax.Array:
    """conf/mask (B, L), k (B,) -> bool transfer mask (B, L)."""
    interp = _default_interpret() if interpret is None else interpret
    out = _tk.topk_mask(conf, mask.astype(jnp.int32), k, interpret=interp)
    return out.astype(bool)


def baos_quantize(x: jax.Array, center: jax.Array, scale: jax.Array,
                  fmt_name: str = "mxint4",
                  interpret: Optional[bool] = None) -> jax.Array:
    """x (B, S, H, D) + calib (B, 1, H, D) -> smoothed fake-quant cache."""
    interp = _default_interpret() if interpret is None else interpret
    B, S, H, D = x.shape
    xg = x.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    c = center.transpose(0, 2, 1, 3).reshape(B * H, 1, D)
    f = scale.transpose(0, 2, 1, 3).reshape(B * H, 1, D)
    out = _bq.baos_mx_quant(xg, c, f, fmt_name=fmt_name, interpret=interp)
    return out.reshape(B, H, S, D).transpose(0, 2, 1, 3)


def flash_attention(q, k, v, fk=None, fv=None, cv=None,
                    window: Optional[int] = None,
                    bq: int = 128, bk: int = 512,
                    interpret: Optional[bool] = None):
    """Bidirectional flash attention with optional BAOS fusion."""
    interp = _default_interpret() if interpret is None else interpret
    return _fb.flash_bidir(q, k, v, fk, fv, cv, bq=bq, bk=bk,
                           window=window, interpret=interp)
