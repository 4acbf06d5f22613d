"""Microscaling (MX) data-format emulation (OCP MX spec, Rouhani et al. 2023).

DART stores weights / KV / sampling logits in MX formats (MXINT4, MXINT8,
MXFP8, MXFP4): blocks of ``block_size`` contiguous elements along the
reduction axis share one power-of-two scale (E8M0 exponent byte).  On TPU we
emulate the formats bit-faithfully with quantize->dequantize ("fake quant")
so the accuracy path (paper's accuracy simulator) is exact, while the byte
counts feed the analytical/roofline model.

Element codings follow the OCP spec:
  * MXINT8 : 2's-complement, 1 sign + 1 integer + 6 fraction bits -> k/64,
             k in [-128, 127]  (values in [-2, 1.984375])
  * MXINT4 : 1 sign + 1 integer + 2 fraction bits -> k/4, k in [-8, 7]
  * MXFP8  : float8 e4m3 (emax = 8, max normal 448)
  * MXFP6  : e3m2 (emax = 4, max 28)
  * MXFP4  : e2m1 (emax = 2, grid {0, .5, 1, 1.5, 2, 3, 4, 6})
Shared scale: X = 2^(floor(log2 amax) - emax_elem), E8M0 (no mantissa).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

MX_BLOCK = 32  # OCP default block size


@dataclasses.dataclass(frozen=True)
class MXFormat:
    name: str
    element_bits: int
    emax: int           # exponent of the largest representable element magnitude
    is_int: bool
    frac_bits: int = 0  # for INT formats: fraction bits (OCP fixed-point coding)
    grid_max: float = 0.0   # largest representable element magnitude

    @property
    def bits_per_element(self) -> float:
        """Effective storage bits/element incl. the shared E8M0 scale byte."""
        return self.element_bits + 8.0 / MX_BLOCK


MXINT8 = MXFormat("mxint8", 8, 1, True, frac_bits=6, grid_max=127 / 64)
MXINT4 = MXFormat("mxint4", 4, 1, True, frac_bits=2, grid_max=7 / 4)
MXFP8 = MXFormat("mxfp8_e4m3", 8, 8, False, grid_max=448.0)
MXFP6 = MXFormat("mxfp6_e3m2", 6, 4, False, grid_max=28.0)
MXFP4 = MXFormat("mxfp4_e2m1", 4, 2, False, grid_max=6.0)
BF16 = MXFormat("bf16", 16, 127, False)   # bf16 rounding pseudo-format
NONE = MXFormat("none", 32, 127, False)   # exact passthrough (FP64 analogue)

FORMATS = {f.name: f for f in (MXINT8, MXINT4, MXFP8, MXFP6, MXFP4, BF16,
                               NONE)}
# Short aliases used in configs.
FORMATS.update({
    "int8": MXINT8, "int4": MXINT4, "fp8": MXFP8, "fp6": MXFP6,
    "fp4": MXFP4, "bf16": BF16, "fp64": NONE, "fp32": NONE,
})

_E2M1_GRID = np.array([0.0, 0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 6.0], np.float32)
_E3M2_GRID = np.array(
    sorted({0.0} | {m * 2.0 ** e for e in range(-2, 5) for m in (1.0, 1.25, 1.5, 1.75)}
           | {0.0625 * k for k in range(4)}),  # subnormals 2^-2 * {0,.25,.5,.75}
    np.float32)


def _round_half_away(x: jax.Array) -> jax.Array:
    return jnp.sign(x) * jnp.floor(jnp.abs(x) + 0.5)


def _quant_grid(x: jax.Array, grid: np.ndarray) -> jax.Array:
    """Round |x| to nearest grid point (half rounds up), keep sign."""
    mids = jnp.asarray((grid[1:] + grid[:-1]) / 2.0, x.dtype)
    idx = jnp.sum(jnp.abs(x)[..., None] >= mids, axis=-1)
    return jnp.sign(x) * jnp.asarray(grid, x.dtype)[idx]


def _pow2(e: jax.Array) -> jax.Array:
    """Exact f32 2**e for integer e in [-127, 127], built from the exponent
    bits: exp2 is not exact on every backend (XLA's CPU exp2(-17) is off
    in the last bits), and an MX scale has to be a power of two."""
    e = jnp.asarray(e, jnp.int32)
    normal = jax.lax.bitcast_convert_type(
        (jnp.maximum(e, -126) + 127) << 23, jnp.float32)
    return jnp.where(e >= -126, normal, 2.0 ** -127)


def _exponent(x: jax.Array) -> jax.Array:
    """Unbiased exponent field of f32 ``x`` (-127 for zero)."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    return ((bits >> 23) & 0xFF) - 127


def _quant_element(x: jax.Array, fmt: MXFormat) -> jax.Array:
    """Quantize scaled elements x (already divided by the shared scale)."""
    if fmt.is_int:
        lo = -(2 ** (fmt.element_bits - 1))
        hi = 2 ** (fmt.element_bits - 1) - 1
        q = jnp.clip(_round_half_away(x * (2 ** fmt.frac_bits)), lo, hi)
        return q * (2.0 ** -fmt.frac_bits)
    if fmt is MXFP8:
        # OCP MX requires *saturating* conversion, so clip to e4m3's 448
        # first.  The round-to-nearest-even onto the e4m3 grid (3 mantissa
        # bits, subnormal step 2^-9) is spelled out rather than cast:
        # XLA's f32 -> float8_e4m3fn convert on TPU v5e does not round like
        # ml_dtypes, while this form is exact on every backend and in
        # Pallas kernels.
        x = jnp.clip(x, -448.0, 448.0)
        step = jnp.maximum(_exponent(x), -6) - 3
        return jax.lax.round(x * _pow2(-step),
                             jax.lax.RoundingMethod.TO_NEAREST_EVEN) * \
            _pow2(step)
    if fmt is MXFP6:
        return _quant_grid(x, _E3M2_GRID)
    if fmt is MXFP4:
        return _quant_grid(x, _E2M1_GRID)
    raise ValueError(f"unknown element format {fmt}")


def _shared_exp(amax: jax.Array, fmt: MXFormat) -> jax.Array:
    """E8M0 exponent of the block scale: the smallest e with
    amax / 2^e <= grid_max, clipped to [-127, 127] (0 for an all-zero
    block).

    (ceil variant: the naive floor(log2 amax) - emax mapping can leave the
    block max up to 2x above the element grid -> saturation; ceil keeps
    every element representable and makes fake-quant idempotent.)  Read
    off the exponent and mantissa bits of amax and grid_max, so no log2
    or division rounds it."""
    g_mant, g_exp = math.frexp(fmt.grid_max)          # g = g_mant * 2^g_exp
    g_bits = int((2.0 * g_mant - 1.0) * (1 << 23))    # mantissa of 2*g_mant
    bits = jax.lax.bitcast_convert_type(amax.astype(jnp.float32), jnp.int32)
    e = _exponent(amax) - (g_exp - 1) + \
        ((bits & 0x7FFFFF) > g_bits).astype(jnp.int32)
    return jnp.where(amax > 0, jnp.clip(e, -127, 127), 0)


def _quant_blocks(xb: jax.Array, amax: jax.Array, fmt: MXFormat
                  ) -> jax.Array:
    """Fake-quantize ``xb`` against its (broadcastable) block maxima."""
    e = _shared_exp(amax, fmt)
    return _quant_element(xb * _pow2(-e), fmt) * _pow2(e)


def _blockize(x: jax.Array, block: int) -> Tuple[jax.Array, int]:
    """Reshape last axis into (nblocks, block), zero-padding the tail."""
    n = x.shape[-1]
    pad = (-n) % block
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x.reshape(*x.shape[:-1], -1, block), pad


@functools.partial(jax.jit, static_argnames=("fmt_name", "block"))
def _fake_quant_impl(x: jax.Array, fmt_name: str, block: int) -> jax.Array:
    fmt = FORMATS[fmt_name]
    orig_dtype = x.dtype
    n = x.shape[-1]
    xb, _ = _blockize(x.astype(jnp.float32), block)
    q = _quant_blocks(xb, jnp.max(jnp.abs(xb), axis=-1, keepdims=True), fmt)
    q = q.reshape(*x.shape[:-1], -1)[..., :n]
    return q.astype(orig_dtype)


def mx_fake_quant(x: jax.Array, fmt: MXFormat | str, block: int = MX_BLOCK,
                  axis: int = -1) -> jax.Array:
    """Quantize-dequantize ``x`` in MX format along ``axis``."""
    fmt = FORMATS[fmt] if isinstance(fmt, str) else fmt
    if fmt is NONE:
        return x
    if fmt is BF16:
        return x.astype(jnp.bfloat16).astype(x.dtype)
    if axis != -1 and axis != x.ndim - 1:
        x = jnp.moveaxis(x, axis, -1)
        out = _fake_quant_impl(x, fmt.name, block)
        return jnp.moveaxis(out, -1, axis)
    return _fake_quant_impl(x, fmt.name, block)


def mx_quantize(x: jax.Array, fmt: MXFormat | str, block: int = MX_BLOCK
                ) -> Tuple[jax.Array, jax.Array]:
    """Return (element codes as float, shared scales).  Last-axis blocks."""
    fmt = FORMATS[fmt] if isinstance(fmt, str) else fmt
    xb, _ = _blockize(x.astype(jnp.float32), block)
    e = _shared_exp(jnp.max(jnp.abs(xb), axis=-1, keepdims=True), fmt)
    return _quant_element(xb * _pow2(-e), fmt), _pow2(e)


def mx_dequantize(codes: jax.Array, scale: jax.Array, n: int | None = None,
                  dtype=jnp.float32) -> jax.Array:
    x = (codes * scale).reshape(*codes.shape[:-2], -1)
    if n is not None:
        x = x[..., :n]
    return x.astype(dtype)


def quant_error(x: jax.Array, fmt: MXFormat | str, block: int = MX_BLOCK):
    """Relative L2 quantization error (accuracy-simulator metric)."""
    q = mx_fake_quant(x, fmt, block)
    num = jnp.linalg.norm((q - x).astype(jnp.float32))
    den = jnp.linalg.norm(x.astype(jnp.float32)) + 1e-12
    return num / den


def storage_bytes(shape: Tuple[int, ...], fmt: MXFormat | str,
                  block: int = MX_BLOCK) -> int:
    """HBM bytes for a tensor stored in ``fmt`` (scales included)."""
    fmt = FORMATS[fmt] if isinstance(fmt, str) else fmt
    n = int(np.prod(shape))
    if fmt is NONE:
        return 4 * n
    if fmt is BF16:
        return 2 * n
    nblocks = -(-shape[-1] // block) * (n // shape[-1])
    return (n * fmt.element_bits) // 8 + nblocks  # +1 E8M0 byte per block
