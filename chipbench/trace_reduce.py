"""Reduce a JAX profiler trace to what the per-layer metrics read.

``load`` turns an ``.xplane.pb`` into a flat list of events
``[plane, line, name, start_ns, end_ns, detail]``, where ``detail`` joins
the event's short string stats (the HLO op and module names among them).
``reduce`` takes that list and the traced window and returns, per device
plane: the union of the intervals in which an operation ran (busy), the
operations that took most time, the idle gaps with the host event that
overlapped each most, every kernel's calls and device time (``kernels``,
by the stem of the op's name, so that a reader of a new kernel's roofline
finds its time by name), the head kernel's calls, and the tick programs:
the module runs that hold a head call (the jitted tick carries no name of
its own in the trace today).
The list is what a test keeps as its recorded trace.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# the fused LM-head + Stable-Max kernel: its jit name in the op metadata
HEAD = "fused_head_sampling"


def load(profile_dir: str) -> list:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {profile_dir}, "
                           f"found {len(paths)}")
    events = []
    for plane in ProfileData.from_file(paths[0]).planes:
        if not (DEVICE.match(plane.name) or plane.name.startswith("/host")):
            continue
        for line in plane.lines:
            for e in line.events:
                detail = " ".join(str(v) for _, v in e.stats
                                  if isinstance(v, str) and len(v) < 400)
                events.append([plane.name, line.name, e.name,
                               int(e.start_ns), int(e.end_ns), detail])
    return events


def union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(s, e, w0, w1):
    return max(s, w0), min(e, w1)


def _matches(ev, pattern):
    return pattern in ev[2] or pattern in ev[5]


def short(name: str) -> str:
    """An HLO op's name without its signature: ``%fusion.12 = ...`` ->
    ``%fusion.12``."""
    return name.split(" = ", 1)[0]


def stem(name: str) -> str:
    """An HLO op's name without its signature, ``%`` and instance number:
    ``%fused_head_sampling.1 = ...`` -> ``fused_head_sampling``."""
    return re.sub(r"\.\d+$", "", short(name).lstrip("%"))


def reduce(events: list, window_ns: tuple, top: int = 10) -> dict:
    """Per device plane, within [w0, w1): busy and idle seconds, the top
    operations by summed time, the longest idle gaps with what the host was
    doing, the head kernel's calls and the tick program's runs; and every
    kernel's calls and summed device seconds by the stem of its name, over
    every op of the trace, each whole, as the head's are counted."""
    w0, w1 = window_ns
    host = [(s, e, n) for p, ln, n, s, e, _ in events
            if p.startswith("/host") and e > s]
    devices = sorted({ev[0] for ev in events if DEVICE.match(ev[0])})
    out = {"window_s": (w1 - w0) / 1e9, "devices": {}}
    for dev in devices:
        ops = [ev for ev in events if ev[0] == dev and ev[1] == OPS_LINE]
        spans = [_clip(ev[3], ev[4], w0, w1) for ev in ops]
        busy = union([(s, e) for s, e in spans if e > s])
        busy_ns = sum(e - s for s, e in busy)
        by_op = {}
        for ev, (s, e) in zip(ops, spans):
            if e > s:
                n = short(ev[2])
                by_op[n] = by_op.get(n, 0) + (e - s)
        gaps, prev = [], w0
        for s, e in busy + [[w1, w1]]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        gaps.sort(key=lambda g: g[0] - g[1])
        named = []
        for g0, g1 in gaps[:top]:
            best, what = 0, "no host event"
            for s, e, n in host:
                ov = min(e, g1) - max(s, g0)
                if ov > best:
                    best, what = ov, n
            named.append([what, (g1 - g0) / 1e9])
        kernels = {}
        for ev in ops:
            n, ns = kernels.get(stem(ev[2]), (0, 0))
            kernels[stem(ev[2])] = (n + 1, ns + ev[4] - ev[3])
        head = [ev for ev in ops if _matches(ev, HEAD)]
        ticks = sorted((ev[3], ev[4]) for ev in events
                       if ev[0] == dev and ev[1] == MODULES_LINE
                       and ev[3] >= w0 and ev[4] <= w1
                       and any(ev[3] <= h[3] and h[4] <= ev[4]
                               for h in head))
        out["devices"][dev] = {
            "busy_s": busy_ns / 1e9,
            "idle_share": 1.0 - busy_ns / (w1 - w0),
            "top_ops": [[n, t / 1e9] for n, t in
                        sorted(by_op.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": named,
            "kernels": {k: {"calls": n, "s": ns / 1e9}
                        for k, (n, ns) in kernels.items()},
            "head_calls": len(head),
            "head_s": sum(e - s for _, _, _, s, e, _ in head) / 1e9,
            "ticks": len(ticks),
            "tick_span_s": (ticks[-1][1] - ticks[0][0]) / 1e9 if ticks else 0.0,
        }
    return out
