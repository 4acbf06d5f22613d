"""MX format properties (core/mx.py) — hypothesis + targeted cases."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # optional `test` extra (see pyproject)
    from _hypothesis_fallback import given, settings, st

from repro.core import mx

FMTS = ["mxint8", "mxint4", "mxfp8_e4m3", "mxfp6_e3m2", "mxfp4_e2m1"]

# worst-case relative error per element for each format (values within a
# block span at most 2x the shared scale's headroom)
REL_TOL = {"mxint8": 0.02, "mxint4": 0.30, "mxfp8_e4m3": 0.10,
           "mxfp6_e3m2": 0.30, "mxfp4_e2m1": 0.60}


@pytest.mark.parametrize("fmt", FMTS)
def test_roundtrip_error_bounded(fmt):
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 128)) * 3.0
    err = float(mx.quant_error(x, fmt))
    assert err < REL_TOL[fmt], f"{fmt}: rel err {err}"


@pytest.mark.parametrize("fmt", FMTS)
def test_idempotent(fmt):
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 64))
    q1 = mx.mx_fake_quant(x, fmt)
    q2 = mx.mx_fake_quant(q1, fmt)
    np.testing.assert_allclose(q1, q2, rtol=0, atol=0)


def test_zero_block():
    x = jnp.zeros((4, 64))
    for fmt in FMTS:
        np.testing.assert_array_equal(mx.mx_fake_quant(x, fmt), x)


def test_none_is_identity():
    x = jax.random.normal(jax.random.PRNGKey(2), (5, 33))
    np.testing.assert_array_equal(mx.mx_fake_quant(x, "none"), x)


def test_scales_are_power_of_two():
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 64)) * 10
    _, scale = mx.mx_quantize(x, "mxint8")
    log = np.log2(np.asarray(scale).ravel())
    np.testing.assert_allclose(log, np.round(log), atol=1e-6)


def test_ragged_tail_padding():
    # non-multiple-of-32 trailing dim must round-trip shape exactly
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 45))
    q = mx.mx_fake_quant(x, "mxint8")
    assert q.shape == x.shape
    assert float(jnp.abs(q - x).max()) < 0.5


def test_axis_argument():
    x = jax.random.normal(jax.random.PRNGKey(5), (32, 8)) * 4
    q0 = mx.mx_fake_quant(x, "mxint8", axis=0)
    q1 = mx.mx_fake_quant(x.T, "mxint8", axis=-1).T
    np.testing.assert_allclose(q0, q1, rtol=1e-6)


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.sampled_from(FMTS),
       st.floats(0.01, 100.0))
def test_property_error_scale_invariant(seed, fmt, scale):
    """MX uses power-of-2 scales: quant noise is ~invariant to pow2 scaling
    and bounded for arbitrary positive scaling."""
    x = jax.random.normal(jax.random.PRNGKey(seed % 2**30), (4, 64)) * scale
    err = float(mx.quant_error(x, fmt))
    assert err < REL_TOL[fmt]


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_property_pow2_exact_equivariance(seed):
    """Scaling by exactly 2^k permutes block exponents: quantization
    commutes with power-of-two scaling bit-exactly."""
    x = jax.random.normal(jax.random.PRNGKey(seed % 2**30), (2, 64))
    q = mx.mx_fake_quant(x, "mxint8")
    q4 = mx.mx_fake_quant(x * 4.0, "mxint8")
    np.testing.assert_allclose(np.asarray(q) * 4.0, q4, rtol=1e-7)


def test_storage_bytes():
    assert mx.storage_bytes((64,), "mxint8") == 64 + 2
    assert mx.storage_bytes((64,), "mxint4") == 32 + 2
    assert mx.storage_bytes((4, 64), "bf16") == 512


def test_mxfp8_element_rounding_matches_float8_cast():
    """The spelled-out e4m3 rounding is ml_dtypes' saturating
    float8_e4m3fn cast bit for bit: normals, subnormals (step 2^-9),
    ties to even, saturation at 448, signs and zero."""
    import ml_dtypes
    rs = np.random.RandomState(0)
    grid = np.arange(256, dtype=np.uint8).view(ml_dtypes.float8_e4m3fn)
    grid = grid.astype(np.float32)
    grid = np.unique(grid[np.isfinite(grid)])
    mids = (grid[1:] + grid[:-1]) / 2                 # exact ties
    x = np.concatenate([
        grid, mids, np.nextafter(mids, np.float32(np.inf)),
        np.nextafter(mids, np.float32(-np.inf)),
        rs.uniform(-500, 500, 4096), rs.uniform(-0.03, 0.03, 4096),
        [0.0, -0.0, 447.9, 448.0, 460.0, -1e4]]).astype(np.float32)
    got = np.asarray(jax.jit(lambda v: mx._quant_element(v, mx.MXFP8))(x))
    ref = np.clip(x, -448, 448).astype(ml_dtypes.float8_e4m3fn)
    np.testing.assert_array_equal(got, ref.astype(np.float32))


@pytest.mark.parametrize("fmt", FMTS)
def test_shared_scale_is_smallest_exact_power_of_two(fmt):
    """scale = 2^e exactly, with e the smallest exponent for which
    amax / 2^e <= grid_max — also where amax is grid_max * 2^k exactly
    and one ulp either side of it."""
    f = mx.FORMATS[fmt]
    rs = np.random.RandomState(1)
    edge = np.float32(f.grid_max) * np.exp2(np.arange(-30, 30)).astype(
        np.float32)
    amax = np.concatenate([
        edge, np.nextafter(edge, np.float32(np.inf)),
        np.nextafter(edge, np.float32(0)),
        np.exp2(rs.uniform(-40, 40, 2048))]).astype(np.float32)
    scale = np.asarray(jax.jit(
        lambda a: mx._pow2(mx._shared_exp(a, f)))(amax))
    e = np.log2(scale.astype(np.float64))
    np.testing.assert_array_equal(e, np.round(e))     # exact powers of two
    a64, g = amax.astype(np.float64), float(f.grid_max)
    assert (a64 / scale <= g).all()
    assert (a64 / (scale / 2.0) > g).all()            # one step smaller fails
