"""The served model's weights, its plain reference, and the control.

A configuration names its reference module (``"reference": "<module>"`` in
``chipbench/configs/<name>.json``, this module where the key is absent);
the harness finds it as ``chipbench/<module>.py``.  A new architecture adds
such a module and imports nothing of the program.  The contract:

``layout(m) -> dict``
    Shapes (tuples) of the parameter tree, in the program's layout, for the
    configuration's ``model`` dict ``m``.
``init_weights(m, init, seed) -> pytree``
    Random weights of that layout from the seed, made on the device in one
    jitted call in the served dtype (``init``: the configuration's
    ``init``).  The harness hands them to the program, and refuses a
    program whose parameter tree differs from them in structure, shape or
    dtype; after the window it makes them again for ``check_rows``.
``blocks(prompt_len, gen_len, L, T) -> [(start, end, ks), ...]``
    The blocks a request's generation commits, in order: each block's
    positions ``start <= p < end`` in the canvas of ``prompt_len + gen_len``
    positions, and ``ks``, the number of positions it commits at each step
    (``L``, ``T``: the configuration's block length and steps per block).
    A commit event breaks the grid when its block or step is out of order,
    it commits other than the step's count, or a position lies outside the
    block or was not masked before.
``canvases(prompt, gen, events, mask_id) -> (rows, final)``
    ``rows``: (canvas, positions, tokens) of each commit event, the canvas
    as the served path saw it before the commit; ``final``: the canvas
    after the last.  ``events``: (positions, tokens) of each.
``check_rows(params, m, rows, seq_len, block, control=False) -> dict``
    ``rows``: (canvas, positions, tokens, block start, block end, k) per
    commit; ``m`` adds ``mask_id``; ``block``: the configuration's block
    length, which no block exceeds.  Returns numpy arrays: ``gap``, the
    reference's logit gap of every committed token, and ``conf_gap``, the
    confidence shortfall of every commit; with ``control`` also
    ``control_gap`` and ``control_conf_gap``, the control's.

Optional, where the least work differs from ``chipbench/counts.py``'s dense
count: ``tick_flops(m, block, active)``, ``head_flops(m, rows)`` and
``head_bytes(m, rows, weight_itemsize)``, with the arguments and meaning of
the functions of that name there.  A module may take what it shares with
this one from it: ``random_weights`` for its own layout, ``canvases``, and
``check_rows`` with its own plain forward (``forward=``).

This module is the default: a bidirectional dense dLLM.  The benchmark
makes the weights itself (``init_weights``) and hands them to the program
in the program's parameter layout.  After the window it makes them again
from the seed for the reference, which imports nothing of the program.

``check_rows`` runs the plain forward of a bidirectional dLLM in float32 at
``highest`` matmul precision, with keys and values in the configuration's
KV cache format, over each canvas the served path committed from, and
reads two things.  At every committed position, how far the served token's
logit lies below the best logit (the mask id excluded).  And for every
commit, the Stable-Max confidence (the softmax probability of the best
token, over logits read in the stated MXFP8) of each still-masked position
of the active block: how far, in log-confidence, the lowest committed
position lies below the k-th most confident, k being what the block's
schedule commits at that step.  With
``control=True`` it also runs the control: the same forward with every
matmul in float8 e4m3 (one step below the bfloat16 the configuration states)
and its logits sampled in MXFP4 (one step below the stated MXFP8), and reads
the same two gaps for the token and the positions the control puts first.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key for any whole seed, beyond 32 bits too."""
    key = jax.random.PRNGKey(0)
    for word in (seed & 0x7FFFFFFF, (seed >> 31) & 0x7FFFFFFF, seed >> 62):
        key = jax.random.fold_in(key, word)
    return key


def layout(m: dict) -> dict:
    """Shapes of the parameter tree, in the program's layout."""
    d, L, V, ff = m["d_model"], m["n_layers"], m["vocab"], m["d_ff"]
    hq, hkv = m["n_heads"] * m["d_head"], m["n_kv_heads"] * m["d_head"]
    attn = {"wq": (L, d, hq), "wk": (L, d, hkv), "wv": (L, d, hkv),
            "wo": (L, hq, d)}
    if m["qkv_bias"]:
        attn.update({"bq": (L, hq), "bk": (L, hkv), "bv": (L, hkv)})
    return {"embed": (V, d),
            "layers": {"ln1": {"w": (L, d)}, "ln2": {"w": (L, d)},
                       "attn": attn,
                       "mlp": {"w_gate": (L, d, ff), "w_up": (L, d, ff),
                               "w_down": (L, ff, d)}},
            "final_norm": {"w": (d,)},
            "lm_head": (d, V)}


def init_weights(m: dict, init: dict, seed: int):
    """Random weights of ``layout(m)`` from the seed (``random_weights``)."""
    return random_weights(layout(m), m, init, seed)


def random_weights(shapes: dict, m: dict, init: dict, seed: int):
    """Random weights of the tree of ``shapes`` from the seed, made on the
    device in one jitted call.

    Matrices are N(0, 1/fan_in); norm weights (a leaf whose path holds
    ``ln`` or ``norm``) 1 + N(0, norm_jitter); biases (a leaf named ``b*``)
    N(0, bias_std); the embedding N(0, embed_std); an untied head
    N(0, logit_std^2 / d), so that logits spread by about ``logit_std``.
    A tied head is the embedding's transpose."""
    dt = jnp.dtype(m["dtype"])
    leaves, treedef = jax.tree_util.tree_flatten(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    names = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(
                 shapes, is_leaf=lambda s: isinstance(s, tuple))[0]]

    def std_of(name, shape):
        if "ln" in name or "norm" in name:
            return init["norm_jitter"]
        if "'b" in name:
            return init["bias_std"]
        if "embed" in name:
            return init["embed_std"]
        if "lm_head" in name:
            return init["logit_std"] / np.sqrt(m["d_model"])
        return 1.0 / np.sqrt(shape[-2])

    def make(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, name, shape in zip(keys, names, leaves):
            x = jax.random.normal(k, shape, jnp.float32) * std_of(name, shape)
            if "ln" in name or "norm" in name:
                x = x + 1.0
            out.append(x.astype(dt))
        params = jax.tree_util.tree_unflatten(treedef, out)
        if m["tie_word_embeddings"]:
            params["lm_head"] = params["embed"].T
        return params

    return jax.jit(make)(key_from_seed(seed))


# -- number formats of the control -----------------------------------------

def _round_grid(v, min_exp, mant_bits, vmax):
    """Round to a binary float grid (round half to even), saturating."""
    _, e = jnp.frexp(v)
    e = jnp.maximum(e - 1, min_exp)
    step = jnp.exp2((e - mant_bits).astype(jnp.float32))
    return jnp.clip(jnp.round(v / step) * step, -vmax, vmax)


def e4m3(x, axis):
    """float8 e4m3 with one float32 scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / 448.0, 1.0)
    return _round_grid(x / s, -6, 3, 448.0) * s


def mxfp4(x):
    """MXFP4 as the OCP MX spec defines it: e2m1 elements, one
    power-of-two scale 2^(floor(log2 amax) - 2) per 32 along the last
    axis."""
    shp = x.shape
    xb = x.reshape(shp[:-1] + (shp[-1] // 32, 32))
    amax = jnp.max(jnp.abs(xb), axis=-1, keepdims=True)
    _, e = jnp.frexp(jnp.where(amax > 0, amax, 1.0))
    scale = jnp.exp2((e - 1 - 2).astype(jnp.float32))
    return (_round_grid(xb / scale, 0, 1, 6.0) * scale).reshape(shp)


def mxfp8(x):
    """MXFP8 as the configuration's sampling stage reads the logits: e4m3
    elements, one power-of-two scale per 32 along the last axis, the
    smallest that keeps the block's largest magnitude within 448."""
    shp = x.shape
    xb = x.reshape(shp[:-1] + (shp[-1] // 32, 32))
    amax = jnp.max(jnp.abs(xb), axis=-1, keepdims=True)
    m, e = jnp.frexp(jnp.where(amax > 0, amax, 1.0))
    scale = jnp.exp2((e - 9 + (m > 0.875)).astype(jnp.float32))
    return (_round_grid(xb / scale, -6, 3, 448.0) * scale).reshape(shp)


# -- the plain forward ------------------------------------------------------

def mxint4(x):
    """MXINT4 as the configuration's KV cache stores it: elements k/4 with
    k in [-8, 7] (round half away from zero), one power-of-two scale per 32
    along the last axis, the smallest that keeps the block's largest
    magnitude within 1.75."""
    shp = x.shape
    xb = x.reshape(shp[:-1] + (-1, min(32, shp[-1])))
    amax = jnp.max(jnp.abs(xb), axis=-1, keepdims=True)
    m, e = jnp.frexp(jnp.where(amax > 0, amax, 1.0))
    e = e - 1 + (2 * m > 1.75)
    scale = jnp.exp2(e.astype(jnp.float32))
    k = jnp.sign(xb / scale) * jnp.floor(jnp.abs(xb / scale) * 4 + 0.5)
    return (jnp.clip(k, -8, 7) / 4 * scale).reshape(shp)


def kv_mxint4(x):
    """The configuration's KV cache format: each channel centred and
    scaled by its min and max over the whole canvas (BAOS, minmax), stored
    in MXINT4, read back unscaled.  x: (B, S, H, D)."""
    hi, lo = jnp.max(x, 1, keepdims=True), jnp.min(x, 1, keepdims=True)
    c = (hi + lo) / 2
    f = jnp.maximum(jnp.maximum(hi - c, c - lo), 1e-6)
    return mxint4((x - c) / f) * f + c


def _mm(a, w, low):
    if low:
        a = e4m3(a, -1).astype(jnp.bfloat16)
        w = e4m3(w, -2).astype(jnp.bfloat16)
        return jnp.matmul(a, w, preferred_element_type=jnp.float32)
    return jnp.matmul(a, w, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    S, D = x.shape[1], x.shape[-1]
    half = D // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def hidden(params, tokens, valid, m, low=False):
    """Final-norm hidden states (B, S, d) in float32 of a bidirectional
    transformer over ``tokens``; keys where ``valid`` is False are left out
    of every softmax.  Keys and values pass through the configuration's KV
    cache format (``kv_mxint4``) before attention reads them.  ``low``: every
    matmul in float8 e4m3 (the control)."""
    B, S = tokens.shape
    H, Hkv, D = m["n_heads"], m["n_kv_heads"], m["d_head"]
    eps = m["norm_eps"]
    x = params["embed"][tokens].astype(jnp.float32)
    bias = jnp.where(valid, 0.0, -jnp.inf)[:, None, None, :]

    def layer(x, lp):
        lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
        a = lp["attn"]
        h = _rms(x, lp["ln1"]["w"], eps)
        q, k, v = (_mm(h, a[w], low) + (a["b" + w[1]] if "bq" in a else 0.0)
                   for w in ("wq", "wk", "wv"))
        q = _rope(q.reshape(B, S, H, D), m["rope_theta"])
        k = _rope(k.reshape(B, S, Hkv, D), m["rope_theta"])
        v = v.reshape(B, S, Hkv, D)
        k, v = kv_mxint4(k), kv_mxint4(v)
        k, v = (jnp.repeat(t, H // Hkv, axis=2) for t in (k, v))
        prec = None if low else HIGHEST
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=prec) / np.sqrt(D)
        p = jax.nn.softmax(s + bias, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=prec)
        x = x + _mm(o.reshape(B, S, H * D), a["wo"], low)
        f = lp["mlp"]
        h = _rms(x, lp["ln2"]["w"], eps)
        g = jax.nn.silu(_mm(h, f["w_gate"], low)) * _mm(h, f["w_up"], low)
        return x + _mm(g, f["w_down"], low), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    return _rms(x, params["final_norm"]["w"].astype(jnp.float32), eps)


def _log_conf(z):
    """Stable-Max log-confidence of the best token: max - logsumexp."""
    return jnp.max(z, -1) - jax.nn.logsumexp(z, -1)


@functools.partial(jax.jit, static_argnames=("mk", "control", "forward"))
def _gaps(params, tokens, valid, pos, tok, blk, mk, control, forward):
    """Per row: the served tokens' logit gaps at ``pos`` and the
    log-confidence of every position in ``blk`` (the active block), taken
    as the configuration states it, over logits read in MXFP8; with
    ``control`` the control's token gaps and block log-confidences.
    ``forward``: the final-norm hidden states, as ``hidden`` gives them."""
    m = dict(mk)
    head = params["lm_head"].astype(jnp.float32)
    mask_id = m["mask_id"]

    def at(h, where):
        return jnp.take_along_axis(h, where[..., None], axis=1)  # (B, K, d)

    def logits(h, low, fmt=None):
        z = (mxfp4(_mm(h, head, True)) if low
             else jnp.matmul(h, head, precision=HIGHEST))
        z = fmt(z) if fmt else z
        return z.at[..., mask_id].set(-jnp.inf)

    h = forward(params, tokens, valid, m)
    z = logits(at(h, pos), False)

    def gap(t):
        return jnp.max(z, -1) - jnp.take_along_axis(z, t[..., None], -1)[
            ..., 0]

    out = {"gap": gap(tok),
           "conf": _log_conf(logits(at(h, blk), False, mxfp8))}
    if control:
        hc = forward(params, tokens, valid, m, True)
        out["control_gap"] = gap(jnp.argmax(logits(at(hc, pos), True), -1))
        out["control_conf"] = _log_conf(logits(at(hc, blk), True))
    return out


def blocks(prompt_len: int, gen_len: int, L: int, T: int) -> list:
    """Blocks of ``L`` positions from the end of the prompt; each commits
    the block length spread evenly over ``T`` steps, the remainder first."""
    ks = [L // T + (t < L % T) for t in range(T)]
    return [(prompt_len + b * L, prompt_len + (b + 1) * L, ks)
            for b in range(gen_len // L)]


def canvases(prompt, gen, events, mask_id):
    """Each commit event's canvas, as the served path saw it before the
    commit, with that event's (positions, tokens)."""
    row = np.concatenate([np.asarray(prompt, np.int32),
                          np.full(gen, mask_id, np.int32)])
    out = []
    for pos, tok in events:
        out.append((row.copy(), np.asarray(pos), np.asarray(tok)))
        row[np.asarray(pos, np.int64)] = tok
    return out, row


def conf_shortfall(conf, masked, picked, k: int) -> float:
    """How far the lowest-confidence picked position lies below the k-th
    most confident masked one (0 where it is among the top k)."""
    kth = np.sort(conf[masked])[::-1][min(k, int(masked.sum())) - 1]
    return max(0.0, float(kth - conf[picked].min()))


def check_rows(params, m: dict, rows, seq_len: int, block: int,
               batch: int = 8, control: bool = False,
               forward=hidden) -> dict:
    """``rows``: (canvas, positions, tokens, block start, block end, k) per
    commit.  Returns, as numpy arrays, the reference gap of every committed
    token and the confidence shortfall of every commit (and the
    control's).  ``block``: the configuration's block length, which no
    block exceeds; ``forward``: the plain forward (a reference module of
    another architecture passes its own)."""
    K = max(len(r[1]) for r in rows)
    mk = tuple(sorted({**m, "mask_id": m["mask_id"]}.items()))
    out = {"gap": [], "conf_gap": []}
    if control:
        out.update(control_gap=[], control_conf_gap=[])
    for i in range(0, len(rows), batch):
        chunk = rows[i:i + batch]
        tokens = np.full((batch, seq_len), m["mask_id"], np.int32)
        valid = np.zeros((batch, seq_len), bool)
        pos = np.zeros((batch, K), np.int32)
        tok = np.zeros((batch, K), np.int32)
        keep = np.zeros((batch, K), bool)
        blk = np.zeros((batch, block), np.int32)
        for j, (c, p, t, bs, _, _) in enumerate(chunk):
            tokens[j, :c.size] = c
            valid[j, :c.size] = True
            pos[j, :p.size], tok[j, :t.size] = p, t
            keep[j, :p.size] = True
            blk[j] = np.minimum(bs + np.arange(block), seq_len - 1)
        valid[len(chunk):, 0] = True           # filler rows: one valid key
        got = jax.device_get(_gaps(params, tokens, valid, pos, tok, blk, mk,
                                   control, forward))
        out["gap"].append(got["gap"][keep])
        if control:
            out["control_gap"].append(got["control_gap"][keep])
        for j, (c, p, _, bs, be, k) in enumerate(chunk):
            masked = c[np.minimum(blk[j], c.size - 1)] == m["mask_id"]
            masked &= bs + np.arange(block) < be
            inside = (p >= bs) & (p < be)
            if not masked.any() or not inside.any():
                continue        # no such commit: counted as a mismatch
            picked = np.zeros(block, bool)
            picked[p[inside] - bs] = True
            out["conf_gap"].append([conf_shortfall(
                got["conf"][j], masked, picked, k)])
            if control:
                cc = np.where(masked, got["control_conf"][j], -np.inf)
                mine = np.zeros(block, bool)
                mine[np.argsort(-cc, kind="stable")[:k]] = True
                out["control_conf_gap"].append([conf_shortfall(
                    got["conf"][j], masked, mine & masked, k)])
    return {k: np.concatenate(v) if v else np.zeros(0)
            for k, v in out.items()}
