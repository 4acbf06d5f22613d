"""Fused BAOS-smooth + MX-quantize kernel (paper §3.1.1 + §4.4 -> Pallas).

DART applies Block-Adaptive Online Smoothing and MX quantization on the KV
write-back path, *before* the tensors leave the Transformer Engine for HBM.
The TPU kernel fuses the two elementwise stages so smoothed values never
round-trip through HBM:

    x_s = (x - c) / f                    (BAOS, per-channel c/f)
    q   = MX_fake_quant(x_s)             (per-32-block shared E8M0 scale)

Layout: x (G, S, D) where G = B*H_kv "channel groups"; c, f are (G, 1, D).
Grid = (G, S / TILE_S); each step holds a (TILE_S, D) tile + its (1, D)
calibration rows in VMEM.  MX blocks run along D (the reduction axis of the
downstream QK^T / PV GEMMs), matching core/mx.py exactly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import mx as mx_lib


def _quant_block(xs: jax.Array, fmt: mx_lib.MXFormat, block: int):
    """xs (TILE_S, D) -> fake-quantized, blocks of `block` along D."""
    t, d = xs.shape
    xb = xs.reshape(t, d // block, block)
    q = mx_lib._quant_blocks(
        xb, jnp.max(jnp.abs(xb), axis=-1, keepdims=True), fmt)
    return q.reshape(t, d)


def _kernel(x_ref, c_ref, f_ref, out_ref, *, fmt: mx_lib.MXFormat,
            block: int):
    x = x_ref[0].astype(jnp.float32)          # (TILE_S, D)
    c = c_ref[0].astype(jnp.float32)          # (1, D)
    f = f_ref[0].astype(jnp.float32)
    xs = (x - c) / f
    out_ref[0] = _quant_block(xs, fmt, block).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("fmt_name", "block", "tile_s",
                                             "interpret"))
def baos_mx_quant(x: jax.Array, center: jax.Array, scale: jax.Array, *,
                  fmt_name: str = "mxint4", block: int = 32,
                  tile_s: int = 128, interpret: bool = False) -> jax.Array:
    """x (G, S, D); center/scale (G, 1, D) -> smoothed fake-quant (G, S, D)."""
    G, S, D = x.shape
    if D % block:
        raise ValueError(f"head_dim {D} must be a multiple of {block}")
    fmt = mx_lib.FORMATS[fmt_name]
    tile = min(tile_s, S)
    pad_s = (-S) % tile
    if pad_s:
        x = jnp.pad(x, ((0, 0), (0, pad_s), (0, 0)))
    Sp = x.shape[1]

    out = pl.pallas_call(
        functools.partial(_kernel, fmt=fmt, block=block),
        grid=(G, Sp // tile),
        in_specs=[pl.BlockSpec((1, tile, D), lambda g, s: (g, s, 0)),
                  pl.BlockSpec((1, 1, D), lambda g, s: (g, 0, 0)),
                  pl.BlockSpec((1, 1, D), lambda g, s: (g, 0, 0))],
        out_specs=pl.BlockSpec((1, tile, D), lambda g, s: (g, s, 0)),
        out_shape=jax.ShapeDtypeStruct((G, Sp, D), x.dtype),
        interpret=interpret,
    )(x, center, scale)
    return out[:, :S]
