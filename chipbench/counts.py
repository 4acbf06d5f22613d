"""Operations and bytes the served algorithm requires, from shapes alone.

The count is the least work of the algorithm, whatever implements it: the
dual cache of Fast-dLLM.  Per tick and active request, a forward over the
request's L-position active block attending to its real context, and the
LM head over those L rows; per block, one forward over the request's real
(unpadded) positions.  Recomputed or padded work does not count, so a
program that removes it raises the share of the peak and never passes it.

This count is of a dense transformer that attends over the whole real
length.  A configuration's reference module (``ref``) that defines
``tick_flops``, ``head_flops`` or ``head_bytes`` counts its own
architecture's least work in their place, with the same arguments.
"""
from __future__ import annotations


def dense_flops_per_token(m: dict) -> int:
    """Matmul operations of one token through every layer (no attention
    scores, no head): 2 per multiply-add."""
    d, hq = m["d_model"], m["n_heads"] * m["d_head"]
    hkv = m["n_kv_heads"] * m["d_head"]
    per_layer = d * hq + 2 * d * hkv + hq * d + 3 * d * m["d_ff"]
    return 2 * m["n_layers"] * per_layer


def attn_flops(m: dict, queries: int, keys: int) -> int:
    """QK^T and PV over ``keys`` for ``queries`` tokens, every layer."""
    return 4 * m["n_layers"] * m["n_heads"] * m["d_head"] * queries * keys


def head_flops(m: dict, rows: int, ref=None) -> int:
    if hasattr(ref, "head_flops"):
        return ref.head_flops(m, rows)
    return 2 * rows * m["d_model"] * m["vocab"]


def head_bytes(m: dict, rows: int, weight_itemsize: int, ref=None) -> int:
    """The (d, V) head read once, the hidden rows in, (conf, token) out."""
    if hasattr(ref, "head_bytes"):
        return ref.head_bytes(m, rows, weight_itemsize)
    act = 4 if m["dtype"] == "float32" else 2
    return (m["d_model"] * m["vocab"] * weight_itemsize
            + rows * m["d_model"] * act + rows * 8)


def tick_flops(m: dict, block: int, active, ref=None) -> int:
    """Required operations of one tick.  ``active``: (real length, first
    tick of a block) of each active request."""
    if hasattr(ref, "tick_flops"):
        return ref.tick_flops(m, block, active)
    total = 0
    for length, block_start in active:
        total += block * dense_flops_per_token(m)
        total += attn_flops(m, block, length) + head_flops(m, block)
        if block_start:
            total += (length * dense_flops_per_token(m)
                      + attn_flops(m, length, length))
    return total


def traced_ticks(run) -> list:
    """Per engine tick inside the trace, each active request's (real
    length, first tick of its block), from the commit events the client
    saw.  One replica: tick numbers are that engine's."""
    (on, *_), (off, *_) = run.trace_ticks
    ticks = {}
    for r in run.records:
        length = len(r["prompt"]) + r["gen"]
        for e in r["events"]:
            if on < e[1] <= off:
                ticks.setdefault(e[1], []).append((length, e[3] == 0))
    return [ticks[t] for t in sorted(ticks)]
