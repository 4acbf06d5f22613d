"""Transformer building blocks: norms, RoPE, MX-boundary GEMMs, and
memory-efficient *bidirectional* attention with BAOS fusion.

dLLM attention has no causal mask (paper §2.1): every position attends to
every other, so there is no triangular sparsity — instead we bound peak
memory with an online-softmax chunked scan over the KV sequence (the XLA
analogue of DART's FlashAttention engine; the Pallas version lives in
kernels/flash_bidir.py and is numerically cross-checked against this).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro import sharding
from repro.core import baos as baos_lib
from repro.core import mx

# ---------------------------------------------------------------------------
# Quantization policy at GEMM boundaries (paper §3.1.1 asymmetric data path)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class QuantPolicy:
    enabled: bool = False
    weight_fmt: str = "mxint4"   # weights stored in HBM in MX format
    act_fmt: str = "mxint8"      # dynamic act quant at the systolic boundary

    def weights(self, w: jax.Array) -> jax.Array:
        if not self.enabled:
            return w
        # MX blocks run along the contraction (first) axis of (K, N) weights.
        return mx.mx_fake_quant(w, self.weight_fmt, axis=0)

    def acts(self, x: jax.Array) -> jax.Array:
        if not self.enabled:
            return x
        return mx.mx_fake_quant(x, self.act_fmt, axis=-1)


def qdot(x: jax.Array, w: jax.Array, policy: Optional[QuantPolicy],
         bias: Optional[jax.Array] = None) -> jax.Array:
    """x (..., K) @ w (K, N) with optional MX fake-quant at the boundary.
    Accumulation in f32 (the INT32-accumulate analogue), cast back to x.dtype."""
    if policy is not None and policy.enabled:
        x, w = policy.acts(x), policy.weights(w)
    y = jax.lax.dot_general(
        x, w.astype(x.dtype),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Norms & activations
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def layer_norm(x: jax.Array, w: jax.Array, b: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w + b


def swiglu(gate: jax.Array, up: jax.Array) -> jax.Array:
    return jax.nn.silu(gate) * up


# ---------------------------------------------------------------------------
# Rotary position embedding (GPT-NeoX half-split convention)
# ---------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0
         ) -> jax.Array:
    """x: (B, S, H, D); positions: (B, S) or (S,)."""
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.exp(-jnp.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Bidirectional chunked attention (online softmax over KV chunks)
# ---------------------------------------------------------------------------

NEG_INF = -1e30  # python float: importing builds no device array


def _mask_bias(q_pos: jax.Array, kv_pos: jax.Array, kv_valid: jax.Array,
               mode: str, window: Optional[int]) -> jax.Array:
    """(B, Sq, Skv) additive bias: 0 allowed / -inf disallowed."""
    ok = kv_valid[:, None, :]
    qp, kp = q_pos[:, :, None], kv_pos[:, None, :]
    if mode == "causal":
        ok = ok & (kp <= qp)
    if window is not None:
        ok = ok & (jnp.abs(qp - kp) < window) if mode != "causal" else \
             ok & (qp - kp < window)
    return jnp.where(ok, 0.0, NEG_INF)


def attention_partials(q: jax.Array, k: jax.Array, v: jax.Array, *,
                       q_pos: jax.Array, kv_pos: jax.Array,
                       kv_valid: jax.Array, mode: str = "bidir",
                       window: Optional[int] = None,
                       kv_chunk: int = 1024,
                       softmax_scale: Optional[float] = None,
                       unroll: bool = False,
                       score_dtype=jnp.float32):
    """Online-softmax partials: (m, l, o_unnorm), each (B, Hkv, G, Sq[, D]).

    Composable: partials from disjoint KV sources combine exactly (used by
    the split active-block cache).  ``score_dtype=bfloat16`` halves the
    materialized score/probability traffic (hillclimb option; max/sum
    accumulators stay f32)."""
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    G = Hq // Hkv
    scale = softmax_scale if softmax_scale is not None else D ** -0.5
    qg = (q * scale).reshape(B, Sq, Hkv, G, D)

    def chunk_scores(ks, kpos, kval):
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(score_dtype),
                       ks.astype(score_dtype),
                       preferred_element_type=score_dtype)
        bias = _mask_bias(q_pos, kpos, kval, mode, window)
        return s + bias[:, None, None, :, :].astype(score_dtype)

    def partial(ks, vs, kpos, kval):
        # every chunk-size tensor (s, p) stays in score_dtype; only the
        # (B,H,G,Sq)-sized accumulators are f32 (reductions use dtype= so
        # no full-size f32 copy is ever materialized)
        s = chunk_scores(ks, kpos, kval)
        m = jnp.maximum(jnp.max(s, axis=-1).astype(jnp.float32), NEG_INF)
        p = jnp.exp(s - m[..., None].astype(score_dtype))
        l = jnp.sum(p, axis=-1, dtype=jnp.float32)
        o = jnp.einsum("bhgqk,bkhd->bhgqd", p, vs.astype(score_dtype),
                       preferred_element_type=jnp.float32)
        return m, l, o.astype(jnp.float32)

    n_chunks = max(1, Skv // kv_chunk) if Skv % kv_chunk == 0 else 1
    if n_chunks <= 1:
        return partial(k, v, kv_pos, kv_valid)

    kc = k.reshape(B, n_chunks, kv_chunk, Hkv, D)
    vc = v.reshape(B, n_chunks, kv_chunk, Hkv, D)
    pc = kv_pos.reshape(B, n_chunks, kv_chunk)
    valc = kv_valid.reshape(B, n_chunks, kv_chunk)

    def body(carry, xs):
        ks, vs, kpos, kval = xs
        return combine_partials(carry, partial(ks, vs, kpos, kval)), None

    m0 = jnp.full((B, Hkv, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, Sq), jnp.float32)
    o0 = jnp.zeros((B, Hkv, G, Sq, D), jnp.float32)
    xs = (kc.swapaxes(0, 1), vc.swapaxes(0, 1), pc.swapaxes(0, 1),
          valc.swapaxes(0, 1))
    if unroll:
        carry = (m0, l0, o0)
        for i in range(n_chunks):
            carry, _ = body(carry, jax.tree.map(lambda t: t[i], xs))
        return carry
    (m, l, o), _ = jax.lax.scan(body, (m0, l0, o0), xs)
    return m, l, o


def combine_partials(a, b):
    """Exact online-softmax merge of two (m, l, o_unnorm) partials."""
    m_a, l_a, o_a = a
    m_b, l_b, o_b = b
    m = jnp.maximum(m_a, m_b)
    ca = jnp.exp(m_a - m)
    cb = jnp.exp(m_b - m)
    return m, l_a * ca + l_b * cb, o_a * ca[..., None] + o_b * cb[..., None]


def finalize_partials(p, B, Sq, Hq, D, dtype):
    m, l, o = p
    o = o / jnp.maximum(l, 1e-30)[..., None]
    return o.transpose(0, 3, 1, 2, 4).reshape(B, Sq, Hq, D).astype(dtype)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              q_pos: jax.Array, kv_pos: jax.Array, kv_valid: jax.Array,
              mode: str = "bidir", window: Optional[int] = None,
              baos_calib: Optional[baos_lib.BAOSCalib] = None,
              kv_chunk: int = 1024, softmax_scale: Optional[float] = None,
              unroll: bool = False, score_dtype=jnp.float32,
              extra_kv=None) -> jax.Array:
    """Memory-efficient GQA attention.

    q: (B, Sq, Hq, D); k, v: (B, Skv, Hkv, D); kv_valid: (B, Skv) bool.
    When ``baos_calib`` is given, k/v are the *smoothed* cache entries: the
    inverse K-scale is fused into q and the V-scale/center corrections are
    applied on the output (exact identities — DESIGN.md §7.3-7.4).
    ``extra_kv=(k2, v2, pos2, valid2)`` adds a second KV source (the split
    active-block buffer) whose entries must live in the same smoothed space;
    partials from both sources merge exactly.
    """
    B, Sq, Hq, D = q.shape
    if baos_calib is not None:
        q = baos_lib.scale_query(q, baos_calib, Hq)

    p = attention_partials(
        q, k, v, q_pos=q_pos, kv_pos=kv_pos, kv_valid=kv_valid, mode=mode,
        window=window, kv_chunk=kv_chunk, softmax_scale=softmax_scale,
        unroll=unroll, score_dtype=score_dtype)
    if extra_kv is not None:
        k2, v2, pos2, valid2 = extra_kv
        p2 = attention_partials(
            q, k2, v2, q_pos=q_pos, kv_pos=pos2, kv_valid=valid2, mode=mode,
            window=window, kv_chunk=max(kv_chunk, k2.shape[1]),
            softmax_scale=softmax_scale, unroll=unroll,
            score_dtype=score_dtype)
        p = combine_partials(p, p2)

    out = finalize_partials(p, B, Sq, Hq, D, q.dtype)
    if baos_calib is not None:
        out = baos_lib.correct_output(out, baos_calib, Hq)
    return out


# ---------------------------------------------------------------------------
# Parameter init helpers
# ---------------------------------------------------------------------------

def dense_init(key: jax.Array, d_in: int, d_out: int,
               dtype=jnp.float32) -> jax.Array:
    std = (2.0 / (d_in + d_out)) ** 0.5
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * std
            ).astype(dtype)


def embed_init(key: jax.Array, vocab: int, d: int, dtype=jnp.float32
               ) -> jax.Array:
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02
            ).astype(dtype)


def shard_act(x: jax.Array, *names) -> jax.Array:
    return sharding.shard(x, *names)
