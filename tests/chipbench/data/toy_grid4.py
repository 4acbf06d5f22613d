"""A toy architecture's plain reference, for the harness's tests: the default
reference's dense dLLM with one more parameter leaf (an RMSNorm weight over
each head's queries and keys) and block-causal attention on an absolute grid
of 4 positions.  A position sees every position of its own block and of the
blocks before it; the prompt's tail shares the first block with the
generation, and the canvas's end cuts the last.  Its work count takes a
block of 4 rows a tick.  ``CALLS`` records which parts of the contract the
harness reached."""
import jax
import jax.numpy as jnp
import numpy as np

import counts
import reference as base
from reference import canvases  # noqa: F401  (the default's, unchanged)

GRID = 4
CALLS = []


def layout(m):
    shapes = base.layout(m)
    shapes["layers"]["attn"]["qk_norm"] = {"w": (m["n_layers"], m["d_head"])}
    return shapes


def init_weights(m, init, seed):
    CALLS.append("init_weights")
    return base.random_weights(layout(m), m, init, seed)


def blocks(prompt_len, gen_len, L, T):
    """Cut at every multiple of the grid, at the prompt's end and at the
    canvas's end; a block of n positions commits over min(T, n) steps."""
    CALLS.append("blocks")
    end = prompt_len + gen_len
    cuts = ([prompt_len]
            + list(range((prompt_len // GRID + 1) * GRID, end, GRID)) + [end])
    out = []
    for a, b in zip(cuts, cuts[1:]):
        s = min(T, b - a)
        out.append((a, b, [(b - a) // s + (t < (b - a) % s)
                           for t in range(s)]))
    return out


def hidden(params, tokens, valid, m, low=False):
    """As ``reference.hidden``, with the per-head norm on queries and keys
    before RoPE, and keys of later blocks left out of every softmax."""
    B, S = tokens.shape
    H, Hkv, D = m["n_heads"], m["n_kv_heads"], m["d_head"]
    eps = m["norm_eps"]
    x = params["embed"][tokens].astype(jnp.float32)
    grid = jnp.arange(S) // GRID
    seen = (grid[None, :] <= grid[:, None])[None, None] & valid[:, None,
                                                                None, :]
    bias = jnp.where(seen, 0.0, -jnp.inf)
    prec = None if low else base.HIGHEST

    def layer(x, lp):
        lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
        a = lp["attn"]
        h = base._rms(x, lp["ln1"]["w"], eps)
        q, k, v = (base._mm(h, a[w], low)
                   + (a["b" + w[1]] if "bq" in a else 0.0)
                   for w in ("wq", "wk", "wv"))
        q = base._rms(q.reshape(B, S, H, D), a["qk_norm"]["w"], eps)
        k = base._rms(k.reshape(B, S, Hkv, D), a["qk_norm"]["w"], eps)
        q, k = base._rope(q, m["rope_theta"]), base._rope(k, m["rope_theta"])
        k, v = base.kv_mxint4(k), base.kv_mxint4(v.reshape(B, S, Hkv, D))
        k, v = (jnp.repeat(t, H // Hkv, axis=2) for t in (k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=prec) / np.sqrt(D)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s + bias, axis=-1),
                       v, precision=prec)
        x = x + base._mm(o.reshape(B, S, H * D), a["wo"], low)
        f = lp["mlp"]
        h = base._rms(x, lp["ln2"]["w"], eps)
        g = (jax.nn.silu(base._mm(h, f["w_gate"], low))
             * base._mm(h, f["w_up"], low))
        return x + base._mm(g, f["w_down"], low), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    return base._rms(x, params["final_norm"]["w"].astype(jnp.float32), eps)


def check_rows(params, m, rows, seq_len, block, control=False):
    CALLS.append("check_rows")
    return base.check_rows(params, m, rows, seq_len, block, control=control,
                           forward=hidden)


def tick_flops(m, block, active):
    CALLS.append("tick_flops")
    return counts.tick_flops(m, GRID, active)
