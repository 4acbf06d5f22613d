"""The program's own marks in a profiler trace: the ``forward`` /
``sampling`` / ``commit`` name scopes of the tick on the device, and the
``dllm.*`` host spans of the engine thread.

On a TPU v5e the ``XLA Ops`` events carry only the HLO instruction and its
device times, not the op metadata that names the scope.  So the scope of
an op is looked up by its HLO name in the compiled tick's HLO text
(``scope_map``), read once after the engine's warm-up (``tick_hlo``).

``split`` takes the event list of ``trace_reduce.load``, the traced
window and that map, and returns per device plane:

- ``scope_s``: device seconds of the operations inside the counted tick
  modules (``trace_reduce``'s ticks), by scope (``forward``,
  ``sampling``, ``commit``, else ``other``).  Only top-level ``XLA Ops``
  events count, so a ``%while`` and the body ops nested in its interval
  are not summed twice.
- ``tick_busy_s``: the union of those operations' intervals.
- ``idle_by_span``: each idle gap of the window cut at the engine spans'
  edges, every piece given to the innermost engine span over it, else to
  ``before_first_span`` / ``after_last_span`` (a span is recorded only if
  it opens after the profiler starts and closes before it stops, so the
  engine's spans at the window's two edges are missing) or
  ``unattributed``.
- ``host_idle_s``: the idle seconds that lie under an engine span.

Nothing here is wired into ``run.py`` yet.  Run as a script it drives one
traced cell through ``run.run_cell`` and prints the split, per device, to
standard error before the result line::

    python3 chipbench/trace_spans.py --workload <cell> --seed <n> \\
        --seconds <s>
"""
from __future__ import annotations

import re
import sys

import trace_reduce

SCOPES = ("forward", "sampling", "commit")
# the outermost of the tick's scopes in an op's name-scope path
SCOPE = re.compile(r"(?:^|/)(forward|sampling|commit)(?=/|$)")
# an HLO instruction and the name-scope path in its metadata
HLO_OP = re.compile(r'^\s*(?:ROOT )?(%\S+) = .*?op_name="([^"]*)"', re.M)
# the spans of the engine thread: tick stages and the worker loop around
# them (docs/observability.md)
ENGINE_SPANS = frozenset(
    "dllm." + n for n in (
        "host_prep", "admit", "paged_io", "dispatch", "forward", "sampling",
        "device_sync", "host_sync", "commit", "canvas_fetch", "replay",
        "engine_tick", "submit", "worker_sync", "worker_wait"))
BEFORE = "before_first_span"
AFTER = "after_last_span"
NONE = "unattributed"


def scope_map(hlo_text: str) -> dict:
    """``{'%op': scope}`` over a compiled module's HLO text."""
    out = {}
    for name, path in HLO_OP.findall(hlo_text):
        m = SCOPE.search(path)
        out[name] = m.group(1) if m else "other"
    return out


def tick_hlo(engine) -> str:
    """The HLO text of an engine's compiled K=1 tick, lowered with the
    arguments of its warm-up call (so the compilation cache has it)."""
    import jax
    import jax.numpy as jnp
    z = jnp.zeros((engine.num_slots,), jnp.int32)
    srng = jax.random.split(jax.random.PRNGKey(0))[1]
    cache = engine.pool.cache if engine.mode == "warm" else None
    return engine._tick_fn.lower(
        engine.params, engine.x, engine.kv_valid, z, z, srng, cache,
        **engine.fwd_kw).compile().as_text()


def top_level(ops: list) -> list:
    """The events not nested in another event's interval."""
    out, end = [], None
    for ev in sorted(ops, key=lambda ev: (ev[3], -ev[4])):
        if end is None or ev[4] > end:
            out.append(ev)
            end = ev[4]
    return out


def _attribute(gaps, spans):
    """Idle nanoseconds by innermost covering span (latest start, then
    shortest)."""
    first = spans[0][0] if spans else None
    last = max((e for _, e, _ in spans), default=None)
    out = {}
    for g0, g1 in gaps:
        near = [sp for sp in spans if sp[0] < g1 and sp[1] > g0]
        cuts = sorted({g0, g1} | {t for s, e, _ in near for t in (s, e)
                                  if g0 < t < g1})
        for a, b in zip(cuts, cuts[1:]):
            over = [sp for sp in near if sp[0] <= a and sp[1] >= b]
            if over:
                name = max(over, key=lambda sp: (sp[0], sp[0] - sp[1]))[2]
            elif first is None or b <= first:
                name = BEFORE
            elif a >= last:
                name = AFTER
            else:
                name = NONE
            out[name] = out.get(name, 0) + (b - a)
    return out


def split(events: list, window_ns: tuple, ops_scope: dict) -> dict:
    """Per device plane: ``scope_s``, ``tick_busy_s``, ``host_idle_s``
    and ``idle_by_span`` (seconds) within ``[w0, w1)``; ``ops_scope`` is
    ``scope_map`` of the tick."""
    w0, w1 = window_ns
    spans = sorted((max(s, w0), min(e, w1), n)
                   for p, _, n, s, e, _ in events
                   if p.startswith("/host") and n in ENGINE_SPANS
                   and min(e, w1) > max(s, w0))
    devices = sorted({ev[0] for ev in events
                      if trace_reduce.DEVICE.match(ev[0])})
    out = {}
    for dev in devices:
        ops = [ev for ev in events
               if ev[0] == dev and ev[1] == trace_reduce.OPS_LINE]
        head = [ev for ev in ops if trace_reduce.HEAD in ev[2]
                or trace_reduce.HEAD in ev[5]]
        ticks = [(ev[3], ev[4]) for ev in events
                 if ev[0] == dev and ev[1] == trace_reduce.MODULES_LINE
                 and ev[3] >= w0 and ev[4] <= w1
                 and any(ev[3] <= h[3] and h[4] <= ev[4] for h in head)]
        scope_s = dict.fromkeys(SCOPES + ("other",), 0.0)
        inside = []
        for ev in top_level(ops):
            if any(t0 <= ev[3] and ev[4] <= t1 for t0, t1 in ticks):
                sc = ops_scope.get(trace_reduce.short(ev[2]), "other")
                scope_s[sc] += (ev[4] - ev[3]) / 1e9
                inside.append((ev[3], ev[4]))
        busy = trace_reduce.union(
            [(max(ev[3], w0), min(ev[4], w1)) for ev in ops
             if min(ev[4], w1) > max(ev[3], w0)])
        gaps, prev = [], w0
        for s, e in busy + [[w1, w1]]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        idle = _attribute(gaps, spans)
        out[dev] = {
            "scope_s": scope_s,
            "tick_busy_s": sum(e - s for s, e in
                               trace_reduce.union(inside)) / 1e9,
            "host_idle_s": sum(v for k, v in idle.items()
                               if k not in (BEFORE, AFTER, NONE)) / 1e9,
            "idle_by_span": {k: v / 1e9 for k, v in sorted(
                idle.items(), key=lambda kv: -kv[1])},
        }
    return out


def report(red: dict, extra: dict, file=sys.stderr) -> None:
    """The split per device, per traced tick where that is its unit."""
    for dev, x in sorted(extra.items()):
        d = red["devices"].get(dev, {})
        n = d.get("ticks") or 0

        def per(v):
            return f"{1e3 * v / n:.3f}" if n else "-"

        sc = x["scope_s"]
        print(f"trace {dev} scopes over {n} ticks, ms per tick: "
              + ", ".join(f"{k} {per(v)}" for k, v in sc.items())
              + f"; forward+sampling+commit "
              f"{per(sum(sc[k] for k in SCOPES))} against tick busy "
              f"{per(x['tick_busy_s'])}, window busy "
              f"{per(d.get('busy_s', 0.0))}", file=file)
        idle = sum(x["idle_by_span"].values())
        print(f"trace {dev} idle {idle:.6f} s, under engine spans "
              f"{x['host_idle_s']:.6f} s ({per(x['host_idle_s'])} ms per "
              "tick): " + ", ".join(f"{k} {v:.6f}" for k, v in
                                   x["idle_by_span"].items()), file=file)


def main(argv=None) -> int:
    import argparse

    import run
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    a = ap.parse_args(argv)
    reduce, frontend, ops = trace_reduce.reduce, run.frontend, {}

    def frontend_and_map(*args, **kw):
        fe = frontend(*args, **kw)
        ops.update(scope_map(tick_hlo(fe.router.workers[0].engine)))
        return fe

    def reduce_and_split(events, window_ns, *args, **kw):
        red = reduce(events, window_ns, *args, **kw)
        report(red, split(events, window_ns, ops))
        return red

    trace_reduce.reduce, run.frontend = reduce_and_split, frontend_and_map
    try:
        return run.run_cell(a.workload, a.seed, a.seconds, True)
    finally:
        trace_reduce.reduce, run.frontend = reduce, frontend


if __name__ == "__main__":
    sys.exit(main())
