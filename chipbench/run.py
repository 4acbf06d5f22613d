"""One benchmark run of one cell on the chip(s) it names.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration
(``chipbench/configs/<config>.json``) and a traffic mix
(``chipbench/traffic/<mix>.json``); per-layer metrics are read by
``chipbench/metrics/<metric>.py``.  The run builds the weights on the device
from the seed, starts the program's streaming HTTP frontend at the program's
own engine defaults, and drives ``POST /v1/completions`` over SSE from a
client process that imports no JAX.  After a warm-up period of the same
traffic it measures for ``--seconds``; with ``--trace 1`` a profiler trace
covers a few seconds in the middle of the window and the per-layer metrics
are reported instead of the end-to-end ones.  Once the window has closed the
served tokens of a sample of finished requests are compared with the plain
reference that the configuration names (``chipbench/reference.py`` unless
its ``"reference"`` key names another module of ``chipbench/``).

The last line of standard output is the JSON result.  A machine whose first
JAX device is not a TPU, or that has fewer chips than the cell asks for,
exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import dataclasses  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

import client as client_lib  # noqa: E402
import trace_reduce  # noqa: E402

CACHE_DIR = os.path.join(ROOT, ".xla_cache")
WORK_DIR = os.path.join(ROOT, ".chipbench_run")
TRACE_S = 4.0            # profiled seconds, in the middle of the window


def load(kind: str, name: str, root: str = HERE) -> dict:
    with open(os.path.join(root, kind, name + ".json")) as f:
        return json.load(f)


def cell_spec(name: str, bench_path: str = os.path.join(ROOT,
                                                       "BENCHMARK.json"),
              root: str = HERE):
    """The cell, its configuration and traffic (under ``root``), and the
    metrics it reports (end-to-end, per-layer), all found by name."""
    with open(bench_path) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    cell = cells[name]

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return (cell, load("configs", cell["config"], root),
            load("traffic", cell["traffic"], root),
            mine(bench["end_to_end"]), mine(bench["per_layer"]))


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str):
    """``metrics/<metric>.py``; a metric split by the end-to-end metric it
    moves (``<quantity>.<suffix>``) falls back to the quantity's reader."""
    path = os.path.join(HERE, "metrics", metric + ".py")
    if not os.path.exists(path):
        path = os.path.join(HERE, "metrics", metric.split(".")[0] + ".py")
    return _module(path, f"metric_{metric}").read


@functools.cache
def _reference(name: str):
    return _module(os.path.join(HERE, name + ".py"), name)


def reference_of(config: dict):
    """The configuration's plain reference: ``<module>.py`` beside this
    file for its ``"reference": "<module>"`` key, ``reference.py`` without
    one (the contract is at the top of ``reference.py``).  Loaded once a
    process."""
    return _reference(config.get("reference", "reference"))


class CompileClock:
    """Backend compile seconds and count, from jax.monitoring."""

    def __init__(self):
        import jax
        self.seconds, self.count = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1


class GCPauses:
    """Collections of the garbage collector from now until ``stop``: how
    many, and the longest pause."""

    def __init__(self):
        self.n, self.longest, self._t = 0, 0.0, 0.0
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.n += 1
            self.longest = max(self.longest, time.perf_counter() - self._t)

    def stop(self):
        gc.callbacks.remove(self._cb)
        return self.n, self.longest


class StallWatch:
    """Watches the engines' tick counters from a thread of its own, every
    ``every`` seconds: the longest time without a tick, and for each pause
    longer than ``limit`` when it began, how long it lasted, where each
    engine thread stood once it had lasted ``limit``, and how late this
    thread itself woke (a late wake means the interpreter lock was held or
    the process did not run)."""

    def __init__(self, engines, limit: float = 0.5, every: float = 0.01):
        import threading
        self.engines, self.limit, self.every = engines, limit, every
        self.stalls, self.longest, self.late = [], 0.0, 0.0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._watch, daemon=True,
                                        name="chipbench-stallwatch")
        self._thread.start()

    def _where(self) -> list:
        import threading
        import traceback
        frames = sys._current_frames()
        out = []
        for t in threading.enumerate():
            if t.name.startswith("engine-") and t.ident in frames:
                stack = traceback.extract_stack(frames[t.ident])[-4:]
                out.append(f"{t.name}: " + " <- ".join(
                    f"{os.path.basename(f.filename)}:{f.lineno} {f.name}"
                    for f in reversed(stack)))
        return out

    def _watch(self):
        ticks = sum(e.ticks_total for e in self.engines)
        last = time.monotonic()
        stall = None
        while not self._stop.is_set():
            t_sleep = time.monotonic()
            time.sleep(self.every)
            now = time.monotonic()
            self.late = max(self.late, now - t_sleep - self.every)
            n = sum(e.ticks_total for e in self.engines)
            if n != ticks:
                ticks = n
                if stall is not None:
                    stall["s"] = now - last
                    self.stalls.append(stall)
                    stall = None
                self.longest = max(self.longest, now - last)
                last = now
            elif stall is None and now - last > self.limit:
                stall = {"at": last, "late": now - t_sleep - self.every,
                         "where": self._where()}

    def stop(self):
        self._stop.set()
        self._thread.join()
        self.engines = None             # the program's state may go now
        return self


def pctl(values, q: float) -> float:
    """Nearest-rank percentile."""
    v = sorted(values)
    return v[max(0, math.ceil(q * len(v)) - 1)]


# -- the served run ---------------------------------------------------------

def build(config: dict, seed: int):
    """The program's model object and the benchmark's weights for it."""
    import jax
    from repro.configs import base
    from repro.models.registry import build_model
    from repro.models.transformer import ModelConfig

    m = config["model"]
    ref = reference_of(config)
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    cfg = dataclasses.replace(base.get_config(config["arch"]),
                              **{k: v for k, v in m.items() if k in fields})
    model = build_model(cfg)
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    mine = jax.eval_shape(lambda: ref.init_weights(m, config["init"], seed))
    if (jax.tree_util.tree_structure(want)
            != jax.tree_util.tree_structure(mine)
            or any(a.shape != b.shape or a.dtype != b.dtype for a, b in zip(
                jax.tree_util.tree_leaves(want),
                jax.tree_util.tree_leaves(mine)))):
        raise RuntimeError("the program's parameter layout differs from "
                           f"{ref.__name__}.layout ({ref.__file__})")
    params = ref.init_weights(m, config["init"], seed)
    jax.block_until_ready(params)
    return model, params


def frontend(model, params, config: dict, seed: int):
    """The program's streaming frontend at serve.py's engine defaults; the
    configuration sets slots, canvas, block length and steps per block,
    and the head's vocabulary chunk where the default does not run."""
    from repro.launch import serve
    from repro.serving import get_policy
    from repro.serving.frontend import build_frontend

    s = config["serving"]
    a = serve.build_parser().parse_args([
        "--block-len", str(s["block_length"]),
        "--steps", str(s["steps_per_block"]),
        "--gen-len", str(s["block_length"])])
    dcfg = serve.make_dcfg(a)
    if "head_chunk" in s:
        dcfg = dataclasses.replace(dcfg, head_chunk=s["head_chunk"])
    return build_frontend(
        model, params, dcfg, model_name=config["name"],
        num_slots=s["num_slots"],
        max_seq_len=s["max_seq_len"], mode=a.mode,
        policy=get_policy(a.policy), seed=seed, megatick_k=a.megatick,
        pool=a.pool, page_size=a.page_size, num_pages=a.num_pages)


async def serve_and_drive(fe, config, traffic_path, seed, seconds, trace,
                          clock) -> dict:
    """Start the frontend, warm it with one request, run the client, and
    trace the middle of the window when asked.  The frontend is shut down
    (shedding what is left) however this ends."""
    await fe.start()
    try:
        return await _drive(fe, config, traffic_path, seed, seconds, trace,
                            clock)
    finally:
        await fe.shutdown(drain=False, timeout=60)


async def _drive(fe, config, traffic_path, seed, seconds, trace, clock):
    import jax

    m = config["model"]
    warm = {"events": []}
    task = asyncio.ensure_future(client_lib.complete(
        fe.url, [1] * 16, config["serving"]["block_length"], warm))
    waited = time.monotonic() + 30.0
    while not (warm["events"] or task.done()) and time.monotonic() < waited:
        await asyncio.sleep(0.01)
    # what set-up made lives as long as the process: keep it out of the
    # collector's sweeps, which would otherwise pause the server for
    # seconds at a time inside the window
    gc.collect()
    gc.freeze()
    pauses = GCPauses()
    proc = await asyncio.create_subprocess_exec(
        sys.executable, os.path.join(HERE, "client.py"), "--url", fe.url,
        "--traffic", traffic_path, "--seed", str(seed),
        "--vocab", str(m["vocab"]), "--mask-id", str(m["mask_token_id"]),
        "--window", str(seconds), "--scrape", str(int(trace)),
        stdout=asyncio.subprocess.PIPE, limit=1 << 30)
    try:
        t0 = json.loads(await proc.stdout.readline())["t0"]
        run = {"setup_s": t0 - T_START, "compiles_setup": clock.count,
               "compile_s": clock.seconds}
        with open(traffic_path) as f:
            ws = t0 + float(json.load(f)["warmup_s"])
        await asyncio.sleep(max(0.0, ws - time.monotonic()))
        run["compiles_warmup"] = clock.count - run["compiles_setup"]
        engines = [w.engine for w in fe.router.workers]
        watch = StallWatch(engines)
        if trace:
            tdir = os.path.join(WORK_DIR, "trace")
            shutil.rmtree(tdir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            await asyncio.sleep(max(0.0, ws + (seconds - TRACE_S) / 2
                                    - time.monotonic()))
            jax.profiler.start_trace(tdir, profiler_options=opts)
            t_on = time.monotonic()
            ticks_on = [e.ticks_total for e in engines]
            await asyncio.sleep(TRACE_S)
            ticks_off = [e.ticks_total for e in engines]
            t_off = time.monotonic()
            jax.profiler.stop_trace()
            run["trace"] = {"dir": tdir, "seconds": t_off - t_on,
                            "ticks": [ticks_on, ticks_off]}
        await asyncio.sleep(max(0.0, ws + seconds - time.monotonic()))
        run["compiles_window"] = (clock.count - run["compiles_setup"]
                                  - run["compiles_warmup"])
        run["gc"] = pauses.stop()
        run["stalls"] = watch.stop()
        out = json.loads(await proc.stdout.readline())
        await proc.wait()
    finally:
        task.cancel()
        if proc.returncode is None:
            proc.kill()
            await proc.wait()
    run["peak_bytes"] = max((d.memory_stats() or {}).get(
        "peak_bytes_in_use", 0) for d in jax.local_devices())
    run.update(out)
    return run


# -- metrics ----------------------------------------------------------------

def in_window(run, t) -> bool:
    ws, we = run["window"]
    return ws <= t < we


def end_to_end(run: dict) -> dict:
    recs = run["records"]
    # ticks by the time their first commit event arrived: the rate is the
    # tokens of the window's ticks after its first, over the time from the
    # first to the last, which a tick's 1% granularity does not quantize
    ticks = {}
    for r in recs:
        for e in r["events"]:
            t, n = ticks.get(e[1], (e[0], 0))
            ticks[e[1]] = (min(t, e[0]), n + len(e[5]))
    inside = sorted(v for v in ticks.values() if in_window(run, v[0]))
    toks = sum(n for _, n in inside[1:])
    span = inside[-1][0] - inside[0][0] if len(inside) > 1 else 0.0
    gaps = [b[0] - a[0] for r in recs
            for a, b in zip(r["events"], r["events"][1:])
            if in_window(run, b[0])]
    due = [r for r in recs if in_window(run, r["due"])]
    # a request that failed, was shed or never finished counts as a miss:
    # its time is the whole wait, up to when the client stopped following
    ttft = [r["events"][0][0] - r["due"] if r["events"]
            else run["stopped"] - r["due"] for r in due]
    lat = [r["done"] - r["due"] if r.get("status") == "ok"
           else run["stopped"] - r["due"] for r in due]
    vals = {"tokens_s": toks / span if span else 0.0,
            "gap_p99_s": pctl(gaps, 0.99) if gaps else 0.0,
            "ttft_p90_s": pctl(ttft, 0.90) if ttft else 0.0,
            "latency_p90_s": pctl(lat, 0.90) if lat else 0.0}
    print("end-to-end, declared or not: " + ", ".join(
        f"{k} {v:.6f}" for k, v in vals.items()), file=sys.stderr)
    print(f"samples: {len(inside)} ticks and {toks} tokens over "
          f"{span:.6f} s, {len(gaps)} commit gaps, {len(due)} requests due "
          "in the window", file=sys.stderr)
    return vals


def stalls(run: dict) -> None:
    w = run.pop("stalls")
    ws = run["window"][0]
    print(f"engine ticks in the window: longest pause {w.longest:.6f} s, "
          f"{len(w.stalls)} over {w.limit} s; the watching thread woke "
          f"at most {w.late:.6f} s late", file=sys.stderr)
    for st in w.stalls:
        print(f"stall: {st['s']:.6f} s from window +{st['at'] - ws:.3f} s, "
              f"watcher {st['late']:.6f} s late; "
              + " | ".join(st["where"]), file=sys.stderr)


def attempted_failed(run: dict, closed: bool):
    due = [r for r in run["records"] if in_window(run, r["due"])]
    # a closed loop cuts its last requests at the close: they did not fail
    bad = [r for r in due if r.get("status", "cut") != "ok"
           and not (closed and "status" not in r)]
    return len(due), len(bad)


def lateness(run: dict) -> str:
    late = sorted(r["sent"] - r["due"] for r in run["records"] if "sent" in r)
    if not late:
        return "generator lateness: no request sent"
    return (f"generator lateness over {len(late)} requests: median "
            f"{late[len(late) // 2]:.6f} s, max {late[-1]:.6f} s")


# -- correctness ------------------------------------------------------------

def commits(r: dict, config: dict):
    """The request's commit events with the block each belongs to, and how
    many of them break the reference's block grid (``blocks``): a block or
    step out of order, a commit of other than the step's k positions, or a
    position outside the active block or not masked before.  An event past
    the grid's last block breaks it."""
    ref = reference_of(config)
    mask_id = config["model"]["mask_token_id"]
    s = config["serving"]
    grid = ref.blocks(len(r["prompt"]), r["gen"], s["block_length"],
                      s["steps_per_block"])
    end = len(r["prompt"]) + r["gen"]
    evs, final = ref.canvases(
        r["prompt"], r["gen"], [(e[4], e[5]) for e in r["events"]], mask_id)

    def block(b):
        return grid[b] if b < len(grid) else (end, end, [])

    rows, bad, b, t = [], 0, 0, 0
    bs, be, ks = block(0)
    left = be - bs
    for e, (canvas, pos, tok) in zip(r["events"], evs):
        k = ks[t] if t < len(ks) else left
        bad += int(e[2] != b or e[3] != t or len(pos) != k
                   or not np.all((pos >= bs) & (pos < be))
                   or not np.all(canvas[pos] == mask_id))
        rows.append((canvas, pos, tok, bs, be, k))
        left -= len(pos)
        if left > 0:
            t += 1
            continue
        b, t = b + 1, 0
        bs, be, ks = block(b)
        left = be - bs
    return rows, bad, final


def served_rows(run: dict, config: dict, seed: int, closed: bool):
    """A sample drawn from the seed of the requests finished in the window,
    the longest first, until ``check.tokens`` served tokens: each commit's
    canvas, positions, tokens, block start and end, and scheduled k.  Also
    the number of requests whose stream disagrees with its own final
    answer, and the number of commits that break the block grid."""
    mask_id = config["model"]["mask_token_id"]
    ok = [r for r in run["records"] if r.get("status") == "ok"
          and (in_window(run, r["done"]) if closed
               else in_window(run, r["due"]))]
    if not ok:
        return [], 0, 0, 0
    rng = np.random.default_rng(seed + 2)
    order = [ok[i] for i in rng.permutation(len(ok))]
    order.sort(key=lambda r: -r["gen"])
    pick = [order[0]] + [order[i] for i in
                         rng.permutation(np.arange(1, len(order)))]
    rows, n_tok, n_req, broken, bad = [], 0, 0, 0, 0
    for r in pick:
        if n_tok >= config["check"]["tokens"]:
            break
        if not r["events"]:
            broken += 1
            continue
        evs, off, final = commits(r, config)
        if (final[len(r["prompt"]):].tolist() != r["tokens"]
                or (final == mask_id).any()):
            broken += 1
        bad += off
        rows += evs
        n_tok += sum(len(row[1]) for row in evs)
        n_req += 1
    return rows, n_req, broken, bad


def check_numbers(gap: np.ndarray, conf_gap: np.ndarray) -> dict:
    return {"max_gap": float(gap.max()), "mean_gap": float(gap.mean()),
            "conf_max_gap": float(conf_gap.max()) if conf_gap.size else 0.0,
            "conf_mean_gap": (float(conf_gap.mean()) if conf_gap.size
                              else 0.0)}


def judge(run, config, seed, closed, control=False):
    """The check's numbers against their limits, and with ``control`` the
    control's numbers too."""
    rows, n_req, broken, bad = served_rows(run, config, seed, closed)
    exact = {"stream_mismatch": {"value": broken, "limit": 0},
             "commit_mismatch": {"value": bad, "limit": 0}}
    if not rows:
        return {"served_requests": {"value": 0, "limit": -1}, **exact}, None
    ref = reference_of(config)
    m = {**config["model"], "mask_id": config["model"]["mask_token_id"]}
    params = ref.init_weights(config["model"], config["init"], seed)
    out = ref.check_rows(params, m, rows, config["serving"]["max_seq_len"],
                         config["serving"]["block_length"], control=control)
    got = check_numbers(out["gap"], out["conf_gap"])
    check = dict(exact)
    for k, lim in config["check"]["limits"].items():
        check[k] = {"value": got[k], "limit": lim}
    print(f"check: {n_req} requests, {out['gap'].size} served tokens, "
          f"{out['conf_gap'].size} commits; read, not compared: " + ", ".join(
              f"{k} {v}" for k, v in got.items() if k not in check),
          file=sys.stderr)
    return check, (check_numbers(out["control_gap"],
                                 out["control_conf_gap"])
                   if control else None)


# -- the run ----------------------------------------------------------------

def arm_cache():
    """JAX's persistent compilation cache, in the checkout."""
    import jax
    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def drive(config, traffic_path, seed, seconds, trace, clock) -> dict:
    """Set up, serve the traffic, and free the program's state."""
    model, params = build(config, seed)
    fe = frontend(model, params, config, seed)
    del params
    run = asyncio.run(serve_and_drive(fe, config, traffic_path, seed,
                                      seconds, trace, clock))
    del fe
    gc.unfreeze()               # the program's state is garbage now
    gc.collect()
    return run


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             bench_path: str = os.path.join(ROOT, "BENCHMARK.json"),
             root: str = HERE, need_tpu: bool = True) -> int:
    cell, config, traffic, e2e, per_layer = cell_spec(name, bench_path, root)
    traffic_path = os.path.join(root, "traffic", cell["traffic"] + ".json")
    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", file=sys.stderr)
    if need_tpu and (dev.platform != "tpu" or len(devices) < cell["chips"]):
        print(f"run: the cell needs {cell['chips']} TPU chip(s); JAX found "
              f"{len(devices)} {dev.platform} device(s)", file=sys.stderr)
        return 2
    arm_cache()
    run = drive(config, traffic_path, seed, seconds, trace, CompileClock())
    print(f"compiles: {run['compiles_setup']} in set-up "
          f"({run['compile_s']:.3f} s), {run['compiles_warmup']} in the "
          f"warm-up period, {run['compiles_window']} in the window",
          file=sys.stderr)
    print(lateness(run), file=sys.stderr)
    print(f"garbage collections in the warm-up period and the window: "
          f"{run['gc'][0]}, longest {run['gc'][1]:.6f} s", file=sys.stderr)
    stalls(run)
    closed = traffic["loop"] == "closed"
    if trace:
        metrics = layer_metrics(run, config, traffic, per_layer,
                                seconds, dev.device_kind)
    else:
        vals = end_to_end(run)
        vals["setup_s"] = run["setup_s"]
        metrics = {m["name"]: {"value": vals[m["name"]], "unit": m["unit"]}
                   for m in e2e}
    attempted, failed = attempted_failed(run, closed)
    check, _ = judge(run, config, seed, closed)
    correct = all(c["value"] <= c["limit"] for c in check.values())
    for k, c in check.items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics,
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devices),
                         "memory_peak_bytes": int(run["peak_bytes"])}}
    if trace and "busy_s" in run:
        result["device"].update(busy_s=run["busy_s"],
                                window_s=run["window_s"])
        result["breakdown"] = run["breakdown"]
    result["check"] = check
    print(json.dumps(result))
    return 0


class Peaks(dict):
    """The chip's published peaks; a device kind not in ``peaks.json`` is
    an error as soon as a metric asks for a peak."""

    def __init__(self, kind, table):
        super().__init__(table)
        self.kind = kind

    def __missing__(self, key):
        raise RuntimeError(f"no {key} for device kind {self.kind!r} in "
                           "chipbench/peaks.json")


def layer_metrics(run, config, traffic, per_layer, seconds, kind):
    """Reduce the trace and read every per-layer metric of the cell."""
    t = run.pop("trace")
    events = trace_reduce.load(t["dir"])
    red = trace_reduce.reduce(events, (0, int(t["seconds"] * 1e9)))
    shutil.rmtree(t["dir"], ignore_errors=True)
    devs = red["devices"]
    if devs:
        n = len(devs)
        run["busy_s"] = sum(d["busy_s"] for d in devs.values()) / n
        run["window_s"] = red["window_s"]
        first = devs[sorted(devs)[0]]
        run["breakdown"] = {"device_ops": first["top_ops"],
                            "idle_gaps": first["idle_gaps"]}
        for name, d in sorted(devs.items()):
            print(f"trace {name}: busy {d['busy_s']:.6f} s of "
                  f"{red['window_s']:.6f} s, idle share {d['idle_share']:.6f}"
                  f", {d['ticks']} ticks over {d['tick_span_s']:.6f} s, "
                  f"{d['head_calls']} head calls {d['head_s']:.6f} s",
                  file=sys.stderr)
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = Peaks(kind, json.load(f).get(kind, {}))
    ctx = types.SimpleNamespace(
        records=run["records"], window=run["window"], scrapes=run["scrapes"],
        trace=red, trace_ticks=t["ticks"], config=config, traffic=traffic,
        model=config["model"], reference=reference_of(config), peaks=peaks,
        seconds=seconds)
    out = {}
    for m in per_layer:
        v = reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    return run_cell(a.workload, a.seed, a.seconds, bool(a.trace))


if __name__ == "__main__":
    sys.exit(main())
