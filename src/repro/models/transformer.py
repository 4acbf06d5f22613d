"""dLLM transformer stack (dense | MoE | decoder-with-cross-attention).

Design points:
  * **scan-over-layers** with stacked per-layer params: one layer's HLO is
    compiled once regardless of depth (essential for 512-device dry-runs).
  * **blocked-diffusion KV cache**: a full-length (B, S_tot, H_kv, D) buffer
    per layer, refreshed in place by `lax.dynamic_update_slice` — the
    non-append-only pattern the paper builds hardware for.
  * **BAOS** (paper §4.4): the cache stores *smoothed+MX-quantized* KV; the
    per-generation-block calibration is computed during the warm step and
    threaded through the cache pytree; attention consumes the smoothed cache
    with the Q-fusion identities.
  * bidirectional attention throughout (mask_mode="bidir"), optional local
    window and causal modes for the hybrid/AR-baseline paths.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import sharding
from repro.core import baos as baos_lib
from repro.models import layers
from repro.models.layers import QuantPolicy
from repro.models import moe as moe_lib


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                     # dense|moe|ssm|hybrid|audio|vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    d_ff: int
    vocab: int
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    norm: str = "rms"               # rms | ln
    ffn: str = "swiglu"             # swiglu | gelu
    mask_token_id: Optional[int] = None   # defaults to vocab - 1
    moe: Optional[moe_lib.MoEConfig] = None
    window: Optional[int] = None    # local attention window (all attn layers)
    attn_mode: str = "bidir"        # bidir | causal
    # hybrid (recurrentgemma): layer pattern, d_rnn; ssm (mamba2) extras
    block_pattern: Optional[Tuple[str, ...]] = None   # e.g. ("rec","rec","attn")
    d_rnn: int = 0
    ssm_state: int = 0
    ssm_head_dim: int = 64
    conv_width: int = 4
    # enc-dec (whisper)
    n_encoder_layers: int = 0
    n_audio_ctx: int = 1500
    # vlm
    n_image_tokens: int = 0
    # scaling knobs (minicpm mu-param)
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    logit_scale: float = 1.0
    # execution
    dtype: str = "bfloat16"
    attn_chunk: int = 1024
    remat: str = "none"             # none | full | dots
    sub_quadratic: bool = False     # long_500k eligibility
    unroll_layers: bool = False     # python-loop layers instead of lax.scan
    #                                 (XLA cost_analysis counts a while body
    #                                 once; the dry-run's cost variants
    #                                 unroll to get true per-layer costs)
    score_dtype: str = "float32"    # attention score/prob dtype (bfloat16 =
    #                                 §Perf hillclimb: halves score traffic)

    @property
    def jscore_dtype(self):
        return jnp.dtype(self.score_dtype)

    @property
    def mask_id(self) -> int:
        return self.mask_token_id if self.mask_token_id is not None \
            else self.vocab - 1

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    def param_count(self) -> int:
        d, h = self.d_model, self.n_heads * self.d_head
        hkv = self.n_kv_heads * self.d_head
        attn = d * h + 2 * d * hkv + h * d
        if self.moe is not None:
            m = self.moe
            ff = m.num_experts * 3 * d * m.d_ff_expert + d * m.num_experts
            ff += 3 * d * (m.d_ff_shared or m.num_shared_experts * m.d_ff_expert)
        else:
            ff = 3 * d * self.d_ff if self.ffn == "swiglu" else 2 * d * self.d_ff
        per_layer = attn + ff + 2 * d
        return (self.n_layers * per_layer + 2 * self.vocab * d + d)

    def active_param_count(self) -> int:
        if self.moe is None:
            return self.param_count()
        d = self.d_model
        m = self.moe
        ff_all = m.num_experts * 3 * d * m.d_ff_expert
        ff_act = m.top_k * 3 * d * m.d_ff_expert
        return self.param_count() - self.n_layers * (ff_all - ff_act)


# ---------------------------------------------------------------------------
# Parameter init / specs
# ---------------------------------------------------------------------------

def _norm_params(d: int, norm: str, dtype):
    if norm == "ln":
        return {"w": jnp.ones((d,), dtype), "b": jnp.zeros((d,), dtype)}
    return {"w": jnp.ones((d,), dtype)}


def _norm_specs(norm: str):
    if norm == "ln":
        return {"w": ("embed",), "b": ("embed",)}
    return {"w": ("embed",)}


def _apply_norm(x, p, cfg: ModelConfig):
    if cfg.norm == "ln":
        return layers.layer_norm(x, p["w"], p["b"], cfg.norm_eps)
    return layers.rms_norm(x, p["w"], cfg.norm_eps)


def init_layer_params(key: jax.Array, cfg: ModelConfig, cross_attn: bool = False):
    ks = jax.random.split(key, 12)
    d = cfg.d_model
    hq, hkv = cfg.n_heads * cfg.d_head, cfg.n_kv_heads * cfg.d_head
    dt = cfg.jdtype
    p: Dict[str, Any] = {
        "ln1": _norm_params(d, cfg.norm, dt),
        "ln2": _norm_params(d, cfg.norm, dt),
        "attn": {
            "wq": layers.dense_init(ks[0], d, hq, dt),
            "wk": layers.dense_init(ks[1], d, hkv, dt),
            "wv": layers.dense_init(ks[2], d, hkv, dt),
            "wo": layers.dense_init(ks[3], hq, d, dt),
        },
    }
    if cfg.qkv_bias:
        p["attn"]["bq"] = jnp.zeros((hq,), dt)
        p["attn"]["bk"] = jnp.zeros((hkv,), dt)
        p["attn"]["bv"] = jnp.zeros((hkv,), dt)
    if cross_attn:
        p["ln_x"] = _norm_params(d, cfg.norm, dt)
        p["xattn"] = {
            "wq": layers.dense_init(ks[8], d, hq, dt),
            "wk": layers.dense_init(ks[9], d, hkv, dt),
            "wv": layers.dense_init(ks[10], d, hkv, dt),
            "wo": layers.dense_init(ks[11], hq, d, dt),
        }
    if cfg.moe is not None:
        p["moe"] = moe_lib.init_moe_params(ks[4], d, cfg.moe, dt)
    elif cfg.ffn == "swiglu":
        p["mlp"] = {
            "w_gate": layers.dense_init(ks[5], d, cfg.d_ff, dt),
            "w_up": layers.dense_init(ks[6], d, cfg.d_ff, dt),
            "w_down": layers.dense_init(ks[7], cfg.d_ff, d, dt),
        }
    else:
        p["mlp"] = {
            "w_in": layers.dense_init(ks[5], d, cfg.d_ff, dt),
            "b_in": jnp.zeros((cfg.d_ff,), dt),
            "w_out": layers.dense_init(ks[7], cfg.d_ff, d, dt),
            "b_out": jnp.zeros((d,), dt),
        }
    return p


def layer_param_specs(cfg: ModelConfig, cross_attn: bool = False):
    p: Dict[str, Any] = {
        "ln1": _norm_specs(cfg.norm), "ln2": _norm_specs(cfg.norm),
        "attn": {"wq": ("embed", "heads"), "wk": ("embed", "heads"),
                 "wv": ("embed", "heads"), "wo": ("heads", "embed")},
    }
    if cfg.qkv_bias:
        p["attn"].update({"bq": ("heads",), "bk": ("heads",), "bv": ("heads",)})
    if cross_attn:
        p["ln_x"] = _norm_specs(cfg.norm)
        p["xattn"] = dict(p["attn"])
        p["xattn"] = {"wq": ("embed", "heads"), "wk": ("embed", "heads"),
                      "wv": ("embed", "heads"), "wo": ("heads", "embed")}
    if cfg.moe is not None:
        p["moe"] = moe_lib.moe_param_specs(cfg.moe)
    elif cfg.ffn == "swiglu":
        p["mlp"] = {"w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"),
                    "w_down": ("mlp", "embed")}
    else:
        p["mlp"] = {"w_in": ("embed", "mlp"), "b_in": ("mlp",),
                    "w_out": ("mlp", "embed"), "b_out": ("embed",)}
    return p


def init_params(key: jax.Array, cfg: ModelConfig, cross_attn: bool = False):
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    lkeys = jax.random.split(k_layers, cfg.n_layers)
    stacked = jax.vmap(lambda k: init_layer_params(k, cfg, cross_attn))(lkeys)
    return {
        "embed": layers.embed_init(k_embed, cfg.vocab, cfg.d_model, cfg.jdtype),
        "layers": stacked,
        "final_norm": _norm_params(cfg.d_model, cfg.norm, cfg.jdtype),
        "lm_head": layers.dense_init(k_head, cfg.d_model, cfg.vocab, cfg.jdtype),
    }


def param_specs(cfg: ModelConfig, cross_attn: bool = False):
    def stack(tree):
        return jax.tree.map(lambda s: ("layers",) + s, tree,
                            is_leaf=lambda x: isinstance(x, tuple))
    return {
        "embed": ("vocab", "embed"),
        "layers": stack(layer_param_specs(cfg, cross_attn)),
        "final_norm": _norm_specs(cfg.norm),
        "lm_head": ("embed", "vocab"),
    }


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, s_tot: int,
               act_len: Optional[int] = None):
    """Full-length refreshable KV buffer + stacked BAOS calibration.

    ``act_len`` enables the SPLIT layout (§Perf): refinement steps write a
    small replicated active-block buffer (k_act/v_act) instead of a
    dynamic-update-slice into the sharded full-length buffer — the DART
    'active block stays in SRAM' execution model.  The active buffer holds
    *smoothed-but-unquantized* KV so one softmax spans both sources exactly
    (same center/scale space; DESIGN.md §7).
    """
    shape = (cfg.n_layers, batch, s_tot, cfg.n_kv_heads, cfg.d_head)
    cal = (cfg.n_layers, batch, 1, cfg.n_kv_heads, cfg.d_head)
    cache = {
        "k": jnp.zeros(shape, cfg.jdtype),
        "v": jnp.zeros(shape, cfg.jdtype),
        "k_center": jnp.zeros(cal, jnp.float32),
        "k_scale": jnp.ones(cal, jnp.float32),
        "v_center": jnp.zeros(cal, jnp.float32),
        "v_scale": jnp.ones(cal, jnp.float32),
    }
    if act_len is not None:
        act = (cfg.n_layers, batch, act_len, cfg.n_kv_heads, cfg.d_head)
        cache["k_act"] = jnp.zeros(act, cfg.jdtype)
        cache["v_act"] = jnp.zeros(act, cfg.jdtype)
    return cache


def cache_specs(cfg: ModelConfig, act_len: Optional[int] = None):
    kv = ("layers", "batch", "kv_seq", "kv_heads", "head_dim")
    cal = ("layers", "batch", None, "kv_heads", "head_dim")
    spec = {"k": kv, "v": kv, "k_center": cal, "k_scale": cal,
            "v_center": cal, "v_scale": cal}
    if act_len is not None:
        act = ("layers", "batch", None, "kv_heads", "head_dim")
        spec["k_act"] = act
        spec["v_act"] = act
    return spec


# ---------------------------------------------------------------------------
# Forward pass
# ---------------------------------------------------------------------------

def _qkv(x, ap, cfg: ModelConfig, quant, positions):
    B, S, _ = x.shape
    q = layers.qdot(x, ap["wq"], quant, ap.get("bq"))
    k = layers.qdot(x, ap["wk"], quant, ap.get("bk"))
    v = layers.qdot(x, ap["wv"], quant, ap.get("bv"))
    q = q.reshape(B, S, cfg.n_heads, cfg.d_head)
    k = k.reshape(B, S, cfg.n_kv_heads, cfg.d_head)
    v = v.reshape(B, S, cfg.n_kv_heads, cfg.d_head)
    if cfg.rope_theta > 0:
        q = layers.rope(q, positions, cfg.rope_theta)
        k = layers.rope(k, positions, cfg.rope_theta)
    return q, k, v


def _ffn(x, lp, cfg: ModelConfig, quant):
    if cfg.moe is not None:
        return moe_lib.moe_ffn(x, lp["moe"], cfg.moe, quant)
    mp = lp["mlp"]
    if cfg.ffn == "swiglu":
        h = layers.swiglu(layers.qdot(x, mp["w_gate"], quant),
                          layers.qdot(x, mp["w_up"], quant))
        return layers.qdot(h, mp["w_down"], quant), jnp.float32(0)
    h = jax.nn.gelu(layers.qdot(x, mp["w_in"], quant, mp["b_in"]))
    return layers.qdot(h, mp["w_out"], quant, mp["b_out"]), jnp.float32(0)


def _layer(x, lp, lcache, cfg: ModelConfig, *, seg_start, positions,
           kv_valid, kv_pos, baos_cfg: baos_lib.BAOSConfig,
           calibrate: bool, calib_mask, quant, cross_kv=None, attn_mode=None,
           calib_start=None):
    """One transformer layer over a segment; returns (x, new_layer_cache, aux)."""
    B, S, _ = x.shape
    mode = attn_mode or cfg.attn_mode

    h = _apply_norm(x, lp["ln1"], cfg)
    h = sharding.shard(h, "batch", "seq", "embed")
    q, k_seg, v_seg = _qkv(h, lp["attn"], cfg, quant, positions)
    q = sharding.shard(q, "batch", "seq", "heads", None)

    aux = jnp.float32(0)
    if lcache is not None:
        split = "k_act" in lcache
        if calibrate:
            calib = baos_lib.calibrate(k_seg, v_seg, baos_cfg, calib_mask)
        else:
            calib = baos_lib.BAOSCalib(
                lcache["k_center"], lcache["k_scale"],
                lcache["v_center"], lcache["v_scale"])
        if baos_cfg.enabled:
            use_calib = calib
        else:
            use_calib = None
        zero = jnp.zeros((), jnp.int32)
        new_cache = {"k_center": calib.k_center, "k_scale": calib.k_scale,
                     "v_center": calib.v_center, "v_scale": calib.v_scale}

        if split and not calibrate:
            # SPLIT refinement: smoothed-unquantized active buffer only;
            # the sharded full-length buffer is read-only (no DUS).
            if baos_cfg.enabled:
                ks_act = (k_seg.astype(jnp.float32) - calib.k_center) / \
                    calib.k_scale
                vs_act = (v_seg.astype(jnp.float32) - calib.v_center) / \
                    calib.v_scale
            else:
                ks_act, vs_act = k_seg, v_seg
            L_act = lcache["k_act"].shape[1]
            act_pos = positions[:, :L_act]
            act_valid = jnp.ones(act_pos.shape, bool)
            # the stale copy of the active block inside the big buffer is
            # masked out of the softmax
            in_act = (kv_pos >= seg_start) & (kv_pos < seg_start + L_act)
            attn_out = layers.attention(
                q, lcache["k"], lcache["v"], q_pos=positions, kv_pos=kv_pos,
                kv_valid=kv_valid & ~in_act, mode=mode, window=cfg.window,
                baos_calib=use_calib, kv_chunk=cfg.attn_chunk,
                unroll=cfg.unroll_layers, score_dtype=cfg.jscore_dtype,
                extra_kv=(ks_act.astype(cfg.jdtype),
                          vs_act.astype(cfg.jdtype), act_pos, act_valid))
            new_cache.update({
                "k": lcache["k"], "v": lcache["v"],
                "k_act": ks_act.astype(lcache["k_act"].dtype),
                "v_act": vs_act.astype(lcache["v_act"].dtype)})
        else:
            if baos_cfg.enabled:
                ks, vs = baos_lib.smooth_quantize_kv(k_seg, v_seg, calib,
                                                     baos_cfg)
            else:
                ks, vs = k_seg, v_seg
            new_k = jax.lax.dynamic_update_slice(
                lcache["k"], ks.astype(lcache["k"].dtype),
                (zero, seg_start, zero, zero))
            new_v = jax.lax.dynamic_update_slice(
                lcache["v"], vs.astype(lcache["v"].dtype),
                (zero, seg_start, zero, zero))
            new_k = sharding.shard(new_k, "batch", "kv_seq", "kv_heads",
                                   None)
            new_v = sharding.shard(new_v, "batch", "kv_seq", "kv_heads",
                                   None)
            attn_out = layers.attention(
                q, new_k, new_v, q_pos=positions, kv_pos=kv_pos,
                kv_valid=kv_valid, mode=mode, window=cfg.window,
                baos_calib=use_calib, kv_chunk=cfg.attn_chunk,
                unroll=cfg.unroll_layers, score_dtype=cfg.jscore_dtype)
            new_cache.update({"k": new_k, "v": new_v})
            if split:
                # warm step also refreshes the active buffer from the
                # just-written smoothed KV at the active-block offset
                L_act = lcache["k_act"].shape[1]
                act_start = calib_start if calib_start is not None \
                    else seg_start
                k_act = jax.lax.dynamic_slice(
                    ks, (zero, act_start - seg_start, zero, zero),
                    (ks.shape[0], L_act, ks.shape[2], ks.shape[3]))
                v_act = jax.lax.dynamic_slice(
                    vs, (zero, act_start - seg_start, zero, zero),
                    (vs.shape[0], L_act, vs.shape[2], vs.shape[3]))
                new_cache.update({
                    "k_act": k_act.astype(lcache["k_act"].dtype),
                    "v_act": v_act.astype(lcache["v_act"].dtype)})
    else:
        # cache-free segment: the keys are this segment's own positions,
        # and kv_valid hides the canvas padding past each row's length
        attn_out = layers.attention(
            q, k_seg, v_seg, q_pos=positions, kv_pos=positions,
            kv_valid=kv_valid, mode=mode, window=cfg.window,
            kv_chunk=cfg.attn_chunk, unroll=cfg.unroll_layers,
            score_dtype=cfg.jscore_dtype)
        new_cache = None

    attn_out = attn_out.reshape(B, S, cfg.n_heads * cfg.d_head)
    x = x + layers.qdot(attn_out, lp["attn"]["wo"], quant) * cfg.residual_scale
    x = sharding.shard(x, "batch", "seq", "embed")

    if cross_kv is not None:
        hx = _apply_norm(x, lp["ln_x"], cfg)
        qx = layers.qdot(hx, lp["xattn"]["wq"], quant).reshape(
            B, S, cfg.n_heads, cfg.d_head)
        ck, cv = cross_kv
        s_enc = ck.shape[1]
        xout = layers.attention(
            qx, ck, cv, q_pos=positions,
            kv_pos=jnp.arange(s_enc)[None, :].repeat(B, 0),
            kv_valid=jnp.ones((B, s_enc), bool), mode="bidir",
            kv_chunk=cfg.attn_chunk, unroll=cfg.unroll_layers)
        xout = xout.reshape(B, S, cfg.n_heads * cfg.d_head)
        x = x + layers.qdot(xout, lp["xattn"]["wo"], quant) * cfg.residual_scale

    h2 = _apply_norm(x, lp["ln2"], cfg)
    ffn_out, aux_l = _ffn(h2, lp, cfg, quant)
    aux = aux + aux_l
    x = x + ffn_out * cfg.residual_scale
    return sharding.shard(x, "batch", "seq", "embed"), new_cache, aux


def forward(params, cfg: ModelConfig, tokens: Optional[jax.Array] = None, *,
            embeds: Optional[jax.Array] = None,
            prefix_embeds: Optional[jax.Array] = None,
            cache=None, seg_start=0,
            kv_valid: Optional[jax.Array] = None,
            baos_cfg: Optional[baos_lib.BAOSConfig] = None,
            calibrate: bool = False,
            calib_mask: Optional[jax.Array] = None,
            quant: Optional[QuantPolicy] = None,
            cross_kv=None, attn_mode: Optional[str] = None,
            logits_slice: Optional[Tuple[int, int]] = None,
            head_mode: str = "logits"):
    calib_start = None
    if calibrate and logits_slice is not None:
        calib_start = jnp.asarray(logits_slice[0], jnp.int32)
    """Segment forward (paper Alg. 1).

    tokens (B, S_seg) or precomputed ``embeds``; with ``prefix_embeds``
    (VLM/audio stub frontends) they are prepended to the token embeddings.
    Returns (logits, new_cache, aux_loss).  ``head_mode='hidden'`` stops
    before the LM head and returns the final-norm hidden states (B, S, d)
    instead of logits — the feed for the fused head + Stable-Max sampling
    path (core/sampling.fused_head_stable_max), which streams the (d, V)
    projection so (B, S, V) logits never reach HBM.
    """
    baos_cfg = baos_cfg or baos_lib.BAOSConfig(enabled=False)
    if embeds is None:
        embeds = params["embed"][tokens] * cfg.embed_scale
    if prefix_embeds is not None:
        embeds = jnp.concatenate(
            [prefix_embeds.astype(embeds.dtype), embeds], axis=1)
    x = embeds.astype(cfg.jdtype)
    x = sharding.shard(x, "batch", "seq", "embed")
    B, S = x.shape[:2]

    if isinstance(seg_start, int):
        seg_start = jnp.int32(seg_start)
    positions = seg_start + jnp.arange(S, dtype=jnp.int32)[None, :]
    positions = jnp.broadcast_to(positions, (B, S))

    if cache is not None:
        s_tot = cache["k"].shape[2]
        kv_pos = jnp.broadcast_to(jnp.arange(s_tot, dtype=jnp.int32)[None, :],
                                  (B, s_tot))
        if kv_valid is None:
            kv_valid = jnp.ones((B, s_tot), bool)
    else:
        kv_pos = positions
        if kv_valid is None:
            kv_valid = jnp.ones((B, S), bool)

    def layer_fn(carry, xs):
        x, aux = carry
        lp, lcache = xs
        x, new_lcache, aux_l = _layer(
            x, lp, lcache, cfg, seg_start=seg_start, positions=positions,
            kv_valid=kv_valid, kv_pos=kv_pos, baos_cfg=baos_cfg,
            calibrate=calibrate, calib_mask=calib_mask, quant=quant,
            cross_kv=None, attn_mode=attn_mode, calib_start=calib_start)
        if new_lcache is None:
            new_lcache = 0  # placeholder ys
        return (x, aux + aux_l), new_lcache

    if cfg.remat != "none":
        policy = (jax.checkpoint_policies.nothing_saveable
                  if cfg.remat == "full"
                  else jax.checkpoint_policies.checkpoint_dots)
        layer_fn = jax.checkpoint(layer_fn, policy=policy)

    if cfg.unroll_layers:
        aux = jnp.float32(0)
        new_lcaches = []
        for i in range(cfg.n_layers):
            lp = jax.tree.map(lambda t: t[i], params["layers"])
            lc = (jax.tree.map(lambda t: t[i], cache)
                  if cache is not None else None)
            ck = (jax.tree.map(lambda t: t[i], cross_kv)
                  if cross_kv is not None else None)
            x, nlc, aux_l = _layer(
                x, lp, lc, cfg, seg_start=seg_start, positions=positions,
                kv_valid=kv_valid, kv_pos=kv_pos, baos_cfg=baos_cfg,
                calibrate=calibrate, calib_mask=calib_mask, quant=quant,
                cross_kv=ck, attn_mode=attn_mode)
            aux = aux + aux_l
            new_lcaches.append(nlc)
        new_cache = (jax.tree.map(lambda *ls: jnp.stack(ls), *new_lcaches)
                     if cache is not None else None)
        x = _apply_norm(x, params["final_norm"], cfg)
        if logits_slice is not None:
            start, length = logits_slice
            x = jax.lax.dynamic_slice_in_dim(x, start, length, axis=1)
        if head_mode == "hidden":
            return sharding.shard(x, "batch", "seq", "embed"), new_cache, aux
        logits = layers.qdot(x, params["lm_head"], quant) * cfg.logit_scale
        logits = sharding.shard(logits, "batch", "seq", "vocab")
        return logits, new_cache, aux

    xs = (params["layers"], cache)
    if cross_kv is not None:
        # cross KV is per-layer stacked; fold into xs
        def layer_fn_x(carry, xs2):
            x, aux = carry
            lp, lcache, ck, cv = xs2
            x, new_lcache, aux_l = _layer(
                x, lp, lcache, cfg, seg_start=seg_start, positions=positions,
                kv_valid=kv_valid, kv_pos=kv_pos, baos_cfg=baos_cfg,
                calibrate=calibrate, calib_mask=calib_mask, quant=quant,
                cross_kv=(ck, cv), attn_mode=attn_mode,
                calib_start=calib_start)
            if new_lcache is None:
                new_lcache = 0
            return (x, aux + aux_l), new_lcache
        fn = layer_fn_x
        if cfg.remat != "none":
            fn = jax.checkpoint(fn, policy=(
                jax.checkpoint_policies.nothing_saveable if cfg.remat == "full"
                else jax.checkpoint_policies.checkpoint_dots))
        (x, aux), new_cache = jax.lax.scan(
            fn, (x, jnp.float32(0)), (params["layers"], cache,
                                      cross_kv[0], cross_kv[1]))
    else:
        (x, aux), new_cache = jax.lax.scan(layer_fn, (x, jnp.float32(0)), xs)

    x = _apply_norm(x, params["final_norm"], cfg)
    if logits_slice is not None:
        start, length = logits_slice
        x = jax.lax.dynamic_slice_in_dim(x, start, length, axis=1)
    if cache is None:
        new_cache = None
    if head_mode == "hidden":
        return sharding.shard(x, "batch", "seq", "embed"), new_cache, aux
    logits = layers.qdot(x, params["lm_head"], quant) * cfg.logit_scale
    logits = sharding.shard(logits, "batch", "seq", "vocab")
    return logits, new_cache, aux
