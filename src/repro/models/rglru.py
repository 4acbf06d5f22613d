"""RecurrentGemma / Griffin hybrid: RG-LRU recurrent blocks + local attention.

Layer pattern is (rec, rec, attn) repeated (paper: "RG-LRU + local attn,
1:2") — 26 layers = 8 triples + 2 trailing recurrent layers.  The stack scans
over the 8 triples (one triple's HLO regardless of depth) and unrolls the
tail.

Blocked-diffusion semantics mirror the dense model for the *attention*
layers (windowed KV cache, BAOS-smoothed) and the SSM model for the
*recurrent* layers (warm step checkpoints the RG-LRU hidden state + conv
state at the active-block boundary; refinement replays the block from it).
`long_500k` runs on this arch: the local window (2048) and the fixed-size
recurrent state make it sub-quadratic.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import sharding
from repro.core import baos as baos_lib
from repro.models import layers
from repro.models.transformer import (ModelConfig, _norm_params, _norm_specs,
                                      _apply_norm)

RGLRU_C = 8.0


# ---------------------------------------------------------------------------
# RG-LRU recurrence
# ---------------------------------------------------------------------------

def rglru_scan(x: jax.Array, r: jax.Array, i: jax.Array, lam: jax.Array,
               h0: Optional[jax.Array] = None):
    """x, r, i: (B, S, D); lam: (D,) learnable Λ.
    h_t = a_t h_{t-1} + sqrt(1 - a_t^2) (i_t ⊙ x_t),
    a_t = exp(-c softplus(Λ) r_t).  Returns (h_all (B,S,D))."""
    log_a = -RGLRU_C * jax.nn.softplus(lam)[None, None, :] * \
        r.astype(jnp.float32)
    a = jnp.exp(log_a)
    b = jnp.sqrt(jnp.maximum(1.0 - a * a, 0.0)) * \
        (i.astype(jnp.float32) * x.astype(jnp.float32))

    def combine(lhs, rhs):
        a1, b1 = lhs
        a2, b2 = rhs
        return a2 * a1, a2 * b1 + b2

    sa, sb = jax.lax.associative_scan(combine, (a, b), axis=1)
    if h0 is not None:
        h = sb + sa * h0[:, None, :].astype(jnp.float32)
    else:
        h = sb
    return h


def rglru_ref(x, r, i, lam, h0=None):
    """Sequential oracle."""
    log_a = -RGLRU_C * jax.nn.softplus(lam)[None, :]
    def step(h, t):
        a = jnp.exp(log_a * r[:, t].astype(jnp.float32))
        b = jnp.sqrt(jnp.maximum(1 - a * a, 0)) * \
            (i[:, t] * x[:, t]).astype(jnp.float32)
        h = a * h + b
        return h, h
    B, S, D = x.shape
    h0 = jnp.zeros((B, D), jnp.float32) if h0 is None else h0
    _, hs = jax.lax.scan(step, h0, jnp.arange(S))
    return hs.transpose(1, 0, 2)


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def init_rec_block(key, cfg: ModelConfig):
    d, dr = cfg.d_model, cfg.d_rnn
    ks = jax.random.split(key, 6)
    dt = cfg.jdtype
    return {
        "w_y": layers.dense_init(ks[0], d, dr, dt),
        "w_gate": layers.dense_init(ks[1], d, dr, dt),
        "conv_w": (jax.random.normal(ks[2], (cfg.conv_width, dr)) * 0.1
                   ).astype(dt),
        "conv_b": jnp.zeros((dr,), dt),
        "w_a": layers.dense_init(ks[3], dr, dr, dt),
        "b_a": jnp.zeros((dr,), dt),
        "w_x": layers.dense_init(ks[4], dr, dr, dt),
        "b_x": jnp.zeros((dr,), dt),
        "lam": jnp.full((dr,), 0.7, jnp.float32),
        "w_out": layers.dense_init(ks[5], dr, d, dt),
    }


def rec_block_specs():
    return {"w_y": ("embed", "mlp"), "w_gate": ("embed", "mlp"),
            "conv_w": (None, "mlp"), "conv_b": ("mlp",),
            "w_a": ("mlp", None), "b_a": ("mlp",),
            "w_x": ("mlp", None), "b_x": ("mlp",),
            "lam": ("mlp",), "w_out": ("mlp", "embed")}


def _causal_conv1d(x, w, b, conv_state):
    W = w.shape[0]
    if conv_state is None:
        pad = jnp.zeros((x.shape[0], W - 1, x.shape[2]), x.dtype)
    else:
        pad = conv_state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)
    out = sum(xp[:, k:k + x.shape[1]] * w[k][None, None, :] for k in range(W))
    return out + b[None, None, :]


def rec_block(x, p, cfg: ModelConfig, h0=None, conv_state=None,
              capture_at: Optional[jax.Array] = None):
    """Griffin recurrent temporal block.  x: (B, S, d_model) (pre-normed).
    Returns (y, h_capture (B,Dr) | None, conv_capture | None)."""
    W = cfg.conv_width
    y = layers.qdot(x, p["w_y"], None)
    gate = jax.nn.gelu(layers.qdot(x, p["w_gate"], None))

    h_cap = conv_cap = None
    if capture_at is not None:
        start = jnp.maximum(capture_at - (W - 1), 0)
        conv_cap = jax.lax.dynamic_slice_in_dim(y, start, W - 1, axis=1)
        conv_cap = jnp.where(capture_at >= W - 1, conv_cap, 0.0)
    y = _causal_conv1d(y, p["conv_w"], p["conv_b"], conv_state)

    r = jax.nn.sigmoid(layers.qdot(y, p["w_a"], None, p["b_a"]))
    i = jax.nn.sigmoid(layers.qdot(y, p["w_x"], None, p["b_x"]))
    h = rglru_scan(y, r, i, p["lam"], h0)
    if capture_at is not None:
        idx = jnp.maximum(capture_at - 1, 0)
        h_cap = jax.lax.dynamic_index_in_dim(h, idx, axis=1, keepdims=False)
        h_cap = jnp.where(capture_at >= 1, h_cap, 0.0)
    out = layers.qdot((h.astype(x.dtype) * gate), p["w_out"], None)
    return out, h_cap, conv_cap


def init_attn_block(key, cfg: ModelConfig):
    d = cfg.d_model
    hq, hkv = cfg.n_heads * cfg.d_head, cfg.n_kv_heads * cfg.d_head
    ks = jax.random.split(key, 4)
    dt = cfg.jdtype
    return {"wq": layers.dense_init(ks[0], d, hq, dt),
            "wk": layers.dense_init(ks[1], d, hkv, dt),
            "wv": layers.dense_init(ks[2], d, hkv, dt),
            "wo": layers.dense_init(ks[3], hq, d, dt)}


def attn_block_specs():
    return {"wq": ("embed", "heads"), "wk": ("embed", "heads"),
            "wv": ("embed", "heads"), "wo": ("heads", "embed")}


def init_mlp(key, cfg: ModelConfig):
    ks = jax.random.split(key, 3)
    dt = cfg.jdtype
    return {"w_gate": layers.dense_init(ks[0], cfg.d_model, cfg.d_ff, dt),
            "w_up": layers.dense_init(ks[1], cfg.d_model, cfg.d_ff, dt),
            "w_down": layers.dense_init(ks[2], cfg.d_ff, cfg.d_model, dt)}


def _geglu_mlp(x, p):
    h = jax.nn.gelu(layers.qdot(x, p["w_gate"], None)) * \
        layers.qdot(x, p["w_up"], None)
    return layers.qdot(h, p["w_down"], None)


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------

class GriffinModel:
    """8 scanned (rec, rec, attn) triples + 2 tail rec layers (26 total)."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        if cfg.n_layers % 3 != 2:
            raise ValueError(
                f"expect 3k+2 layers (rec,rec,attn)*k + 2; "
                f"got n_layers={cfg.n_layers}")
        self.n_triples = cfg.n_layers // 3

    # -- params ------------------------------------------------------------
    def _init_sub(self, key, kind):
        cfg = self.cfg
        k1, k2, k3 = jax.random.split(key, 3)
        temporal = (init_rec_block(k1, cfg) if kind == "rec"
                    else init_attn_block(k1, cfg))
        return {"ln1": _norm_params(cfg.d_model, cfg.norm, cfg.jdtype),
                "ln2": _norm_params(cfg.d_model, cfg.norm, cfg.jdtype),
                "temporal": temporal, "mlp": init_mlp(k3, cfg)}

    def _sub_specs(self, kind):
        cfg = self.cfg
        t = rec_block_specs() if kind == "rec" else attn_block_specs()
        return {"ln1": _norm_specs(cfg.norm), "ln2": _norm_specs(cfg.norm),
                "temporal": t,
                "mlp": {"w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"),
                        "w_down": ("mlp", "embed")}}

    def init(self, key):
        cfg = self.cfg
        ke, kt, ktl, kh = jax.random.split(key, 4)
        tkeys = jax.random.split(kt, self.n_triples)

        def init_triple(k):
            ka, kb, kc = jax.random.split(k, 3)
            return {"rec1": self._init_sub(ka, "rec"),
                    "rec2": self._init_sub(kb, "rec"),
                    "attn": self._init_sub(kc, "attn")}

        tail_keys = jax.random.split(ktl, 2)
        return {
            "embed": layers.embed_init(ke, cfg.vocab, cfg.d_model, cfg.jdtype),
            "triples": jax.vmap(init_triple)(tkeys),
            "tail": jax.vmap(lambda k: self._init_sub(k, "rec"))(tail_keys),
            "final_norm": _norm_params(cfg.d_model, cfg.norm, cfg.jdtype),
            "lm_head": layers.dense_init(kh, cfg.d_model, cfg.vocab,
                                         cfg.jdtype),
        }

    def param_specs(self):
        def stack(tree):
            return jax.tree.map(lambda s: ("layers",) + s, tree,
                                is_leaf=lambda x: isinstance(x, tuple))
        return {
            "embed": ("vocab", "embed"),
            "triples": stack({"rec1": self._sub_specs("rec"),
                              "rec2": self._sub_specs("rec"),
                              "attn": self._sub_specs("attn")}),
            "tail": stack(self._sub_specs("rec")),
            "final_norm": _norm_specs(self.cfg.norm),
            "lm_head": ("embed", "vocab"),
        }

    # -- cache ---------------------------------------------------------------
    def init_cache(self, batch: int, s_tot: int, act_len=None):
        # act_len (split attention cache) not yet applied to the hybrid
        cfg = self.cfg
        nt = self.n_triples
        kv = (nt, batch, s_tot, cfg.n_kv_heads, cfg.d_head)
        cal = (nt, batch, 1, cfg.n_kv_heads, cfg.d_head)
        rec = (nt, 2, batch, cfg.d_rnn)
        cw = (nt, 2, batch, cfg.conv_width - 1, cfg.d_rnn)
        return {
            "k": jnp.zeros(kv, cfg.jdtype), "v": jnp.zeros(kv, cfg.jdtype),
            "k_center": jnp.zeros(cal, jnp.float32),
            "k_scale": jnp.ones(cal, jnp.float32),
            "v_center": jnp.zeros(cal, jnp.float32),
            "v_scale": jnp.ones(cal, jnp.float32),
            "rec_state": jnp.zeros(rec, jnp.float32),
            "rec_conv": jnp.zeros(cw, cfg.jdtype),
            "tail_state": jnp.zeros((2, batch, cfg.d_rnn), jnp.float32),
            "tail_conv": jnp.zeros((2, batch, cfg.conv_width - 1, cfg.d_rnn),
                                   cfg.jdtype),
        }

    def cache_specs(self, act_len=None):
        kv = ("layers", "batch", "kv_seq", "kv_heads", "head_dim")
        cal = ("layers", "batch", None, "kv_heads", "head_dim")
        return {"k": kv, "v": kv, "k_center": cal, "k_scale": cal,
                "v_center": cal, "v_scale": cal,
                "rec_state": ("layers", None, "batch", "mlp"),
                "rec_conv": ("layers", None, "batch", None, "mlp"),
                "tail_state": (None, "batch", "mlp"),
                "tail_conv": (None, "batch", None, "mlp")}

    # -- forward -------------------------------------------------------------
    def _rec_sub(self, x, p, st):
        """st: dict(h0, conv, capture_at) or None (stateless)."""
        cfg = self.cfg
        h = _apply_norm(x, p["ln1"], cfg)
        if st is None:
            y, hc, cc = rec_block(h, p["temporal"], cfg)
        else:
            y, hc, cc = rec_block(h, p["temporal"], cfg, st.get("h0"),
                                  st.get("conv"), st.get("capture_at"))
        x = x + y
        x = x + _geglu_mlp(_apply_norm(x, p["ln2"], cfg), p["mlp"])
        return x, hc, cc

    def _attn_sub(self, x, p, lk, lv, lcal, *, seg_start, positions, kv_pos,
                  kv_valid, baos_cfg, calibrate, calib_mask):
        cfg = self.cfg
        B, S, _ = x.shape
        h = _apply_norm(x, p["ln1"], cfg)
        ap = p["temporal"]
        q = layers.qdot(h, ap["wq"], None).reshape(B, S, cfg.n_heads,
                                                   cfg.d_head)
        k = layers.qdot(h, ap["wk"], None).reshape(B, S, cfg.n_kv_heads,
                                                   cfg.d_head)
        v = layers.qdot(h, ap["wv"], None).reshape(B, S, cfg.n_kv_heads,
                                                   cfg.d_head)
        q = layers.rope(q, positions, cfg.rope_theta)
        k = layers.rope(k, positions, cfg.rope_theta)

        if lk is not None:
            if calibrate:
                calib = baos_lib.calibrate(k, v, baos_cfg, calib_mask)
            else:
                calib = lcal
            if baos_cfg.enabled:
                ks, vs = baos_lib.smooth_quantize_kv(k, v, calib, baos_cfg)
                use_cal = calib
            else:
                ks, vs, use_cal = k, v, None
            zero = jnp.zeros((), jnp.int32)
            lk = jax.lax.dynamic_update_slice(
                lk, ks.astype(lk.dtype), (zero, seg_start, zero, zero))
            lv = jax.lax.dynamic_update_slice(
                lv, vs.astype(lv.dtype), (zero, seg_start, zero, zero))
            attn_out = layers.attention(
                q, lk, lv, q_pos=positions, kv_pos=kv_pos, kv_valid=kv_valid,
                mode="bidir", window=cfg.window, baos_calib=use_cal,
                kv_chunk=cfg.attn_chunk, unroll=cfg.unroll_layers)
        else:
            calib = None
            attn_out = layers.attention(
                q, k, v, q_pos=positions, kv_pos=positions,
                kv_valid=kv_valid, mode="bidir",
                window=cfg.window, kv_chunk=cfg.attn_chunk,
                unroll=cfg.unroll_layers)
        attn_out = attn_out.reshape(B, S, cfg.n_heads * cfg.d_head)
        x = x + layers.qdot(attn_out, ap["wo"], None)
        x = x + _geglu_mlp(_apply_norm(x, p["ln2"], cfg), p["mlp"])
        return x, lk, lv, calib

    def forward(self, params, tokens=None, *, embeds=None, cache=None,
                seg_start=0, kv_valid=None, baos_cfg=None, calibrate=False,
                calib_mask=None, quant=None, logits_slice=None, **_):
        cfg = self.cfg
        baos_cfg = baos_cfg or baos_lib.BAOSConfig(enabled=False)
        if embeds is None:
            embeds = params["embed"][tokens] * cfg.embed_scale
        x = embeds.astype(cfg.jdtype)
        B, S = x.shape[:2]
        if isinstance(seg_start, int):
            seg_start = jnp.int32(seg_start)
        positions = jnp.broadcast_to(
            seg_start + jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))

        warm = calibrate and cache is not None
        capture_at = (logits_slice[0] if (warm and logits_slice is not None)
                      else jnp.int32(0))

        if cache is not None:
            s_tot = cache["k"].shape[2]
            kv_pos = jnp.broadcast_to(
                jnp.arange(s_tot, dtype=jnp.int32)[None, :], (B, s_tot))
            if kv_valid is None:
                kv_valid = jnp.ones((B, s_tot), bool)
        else:
            kv_pos = positions
            if kv_valid is None:
                kv_valid = jnp.ones((B, S), bool)

        def rec_state(lstate, lconv):
            if cache is None:
                return None
            if warm:
                return {"capture_at": jnp.asarray(capture_at, jnp.int32)}
            return {"h0": lstate, "conv": lconv}

        def triple_fn(carry, xs):
            x, = carry
            tp, tc = xs
            new_tc = dict(tc) if tc is not None else None
            for j, name in enumerate(("rec1", "rec2")):
                st = rec_state(tc["rec_state"][j] if tc else None,
                               tc["rec_conv"][j] if tc else None)
                x, hc, cc = self._rec_sub(x, tp[name], st)
                if warm:
                    new_tc["rec_state"] = new_tc["rec_state"].at[j].set(hc)
                    new_tc["rec_conv"] = new_tc["rec_conv"].at[j].set(
                        cc.astype(new_tc["rec_conv"].dtype))
            if tc is not None:
                lcal = baos_lib.BAOSCalib(tc["k_center"], tc["k_scale"],
                                          tc["v_center"], tc["v_scale"])
                x, lk, lv, calib = self._attn_sub(
                    x, tp["attn"], tc["k"], tc["v"], lcal,
                    seg_start=seg_start, positions=positions, kv_pos=kv_pos,
                    kv_valid=kv_valid, baos_cfg=baos_cfg, calibrate=calibrate,
                    calib_mask=calib_mask)
                new_tc["k"], new_tc["v"] = lk, lv
                if calibrate and calib is not None:
                    new_tc.update({"k_center": calib.k_center,
                                   "k_scale": calib.k_scale,
                                   "v_center": calib.v_center,
                                   "v_scale": calib.v_scale})
            else:
                x, _, _, _ = self._attn_sub(
                    x, tp["attn"], None, None, None,
                    seg_start=seg_start, positions=positions, kv_pos=kv_pos,
                    kv_valid=kv_valid, baos_cfg=baos_cfg, calibrate=False,
                    calib_mask=None)
                new_tc = 0
            return (x,), new_tc

        tcache = None
        if cache is not None:
            tcache = {k: cache[k] for k in
                      ("k", "v", "k_center", "k_scale", "v_center", "v_scale",
                       "rec_state", "rec_conv")}
        if cfg.unroll_layers:
            new_ts = []
            for i in range(self.n_triples):
                tp = jax.tree.map(lambda t: t[i], params["triples"])
                tc = (jax.tree.map(lambda t: t[i], tcache)
                      if tcache is not None else None)
                (x,), ntc = triple_fn((x,), (tp, tc))
                new_ts.append(ntc)
            new_tcache = (jax.tree.map(lambda *ls: jnp.stack(ls), *new_ts)
                          if tcache is not None else 0)
        else:
            (x,), new_tcache = jax.lax.scan(
                triple_fn, (x,), (params["triples"], tcache))

        new_cache = None
        if cache is not None:
            new_cache = dict(new_tcache)
            new_cache["tail_state"] = cache["tail_state"]
            new_cache["tail_conv"] = cache["tail_conv"]

        for j in range(2):
            tp = jax.tree.map(lambda t: t[j], params["tail"])
            st = rec_state(cache["tail_state"][j] if cache is not None else None,
                           cache["tail_conv"][j] if cache is not None else None)
            x, hc, cc = self._rec_sub(x, tp, st)
            if warm:
                new_cache["tail_state"] = new_cache["tail_state"].at[j].set(hc)
                new_cache["tail_conv"] = new_cache["tail_conv"].at[j].set(
                    cc.astype(new_cache["tail_conv"].dtype))

        x = _apply_norm(x, params["final_norm"], cfg)
        if logits_slice is not None:
            start, length = logits_slice
            x = jax.lax.dynamic_slice_in_dim(x, start, length, axis=1)
        logits = layers.qdot(x, params["lm_head"], quant) * cfg.logit_scale
        logits = sharding.shard(logits, "batch", "seq", "vocab")
        return logits, new_cache, jnp.float32(0)
