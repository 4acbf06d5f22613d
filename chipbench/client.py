"""Load generator: the traffic mix's requests over HTTP/SSE, timed from their
due times.  Imports no JAX, so it runs in a process of its own.

    python chipbench/client.py --url URL --traffic FILE --seed N \
        --vocab V --mask-id M --window S [--scrape 0|1]

Reads the traffic mix (``chipbench/traffic/<mix>.json``), draws every
request from the seed, prints one line ``{"t0": ...}`` when it starts (the
time.monotonic() at which the warm-up period begins), and at the end one
JSON line with every request's record and, unless ``--scrape 0``, two
``/metrics`` scrapes, taken when the window opens and when it closes.

The mix's sizes and arrivals are drawn once per run length, at fixed
quantiles; the seed only orders them and draws the prompt ids.  Every seed
thus offers the same work.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import math
import statistics
import sys
import time
import urllib.parse

import numpy as np

_READ_LIMIT = 8 << 20       # a `done` line carries all token ids


def quantiles(spec: dict, n: int) -> np.ndarray:
    """n values at the quantiles (i + 0.5) / n of a length distribution."""
    u = (np.arange(n) + 0.5) / n
    if "values" in spec:
        cum = np.cumsum(spec["probs"])
        idx = np.searchsorted(cum / cum[-1], u)
        return np.asarray(spec["values"])[idx]
    if spec["dist"] == "lognormal":
        z = np.asarray([statistics.NormalDist().inv_cdf(x) for x in u])
        v = np.round(spec["median"] * np.exp(spec["sigma"] * z))
        return np.clip(v, spec["min"], spec["max"]).astype(int)
    raise ValueError(f"unknown length distribution {spec}")


def draw_requests(traffic: dict, seed: int, vocab: int, mask_id: int,
                  n: int):
    """n requests: (prompt ids, gen length), sizes from the mix's quantiles
    in an order drawn from the seed; prompt ids never the mask id."""
    rng = np.random.default_rng(seed)
    plens = rng.permutation(quantiles(traffic["prompt_len"], n))
    gens = rng.permutation(quantiles(traffic["gen_len"], n))
    out = []
    for p, g in zip(plens, gens):
        ids = rng.integers(0, vocab - 1, size=int(p))
        ids[ids >= mask_id] += 1             # skip the mask id
        out.append((ids.tolist(), int(g)))
    return out


def arrivals(traffic: dict, seed: int, horizon: float) -> np.ndarray:
    """Poisson arrivals at the mix's rate over [0, horizon): exponential
    gaps at fixed quantiles, in an order drawn from the seed."""
    rate = float(traffic["rate"])
    n = int(math.ceil(rate * horizon))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gaps = np.random.default_rng(seed + 1).permutation(gaps)
    return np.cumsum(gaps) * horizon / gaps.sum()


async def _open(url):
    u = urllib.parse.urlsplit(url)
    return await asyncio.open_connection(u.hostname, u.port,
                                         limit=_READ_LIMIT)


async def get_text(url: str, path: str) -> str:
    reader, writer = await _open(url)
    writer.write(f"GET {path} HTTP/1.1\r\nHost: x\r\n"
                 "Connection: close\r\n\r\n".encode())
    await writer.drain()
    body = await reader.read()
    writer.close()
    return body.split(b"\r\n\r\n", 1)[1].decode()


async def complete(url: str, prompt, gen: int, rec: dict) -> None:
    """One streamed completion; fills ``rec`` with the arrival time of each
    ``block_committed`` event and the ``done`` answer."""
    rec["sent"] = time.monotonic()
    try:
        reader, writer = await _open(url)
        body = json.dumps({"prompt": prompt, "max_tokens": gen,
                           "stream": True}).encode()
        writer.write((f"POST /v1/completions HTTP/1.1\r\nHost: x\r\n"
                      "Content-Type: application/json\r\n"
                      f"Content-Length: {len(body)}\r\n"
                      "Connection: close\r\n\r\n").encode() + body)
        await writer.drain()
        status = int((await reader.readline()).split()[1])
        while (await reader.readline()) not in (b"\r\n", b"\n", b""):
            pass
        if status != 200:
            rec["status"] = "shed" if status == 429 else f"http {status}"
            writer.close()
            return
        name = None
        async for raw in reader:
            line = raw.decode().rstrip("\r\n")
            if line.startswith("event: "):
                name = line[7:]
            elif line.startswith("data: ") and line != "data: [DONE]":
                p = json.loads(line[6:])
                t = time.monotonic()
                if name == "block_committed":
                    rec["events"].append([t, p["tick"], p["block_idx"],
                                          p["step_in_block"], p["positions"],
                                          p["tokens"]])
                elif name == "done":
                    rec["done"] = t
                    rec["tokens"] = p["choices"][0]["token_ids"]
                    rec["status"] = "ok"
                elif name == "error":
                    rec["status"] = p["error"]["type"]
        writer.close()
    except (OSError, ValueError, IndexError) as e:
        rec["status"] = f"error {type(e).__name__}"
    rec.setdefault("status", "error: stream ended")


async def run(args, traffic: dict) -> dict:
    warm, window = float(traffic["warmup_s"]), float(args.window)
    follow = float(traffic.get("follow_s", 0.0))
    closed = traffic["loop"] == "closed"
    horizon = warm + window + follow
    if closed:
        per = int(math.ceil(horizon / traffic["min_service_s"])) + 1
        n = per * traffic["clients"]
    else:
        due = arrivals(traffic, args.seed, horizon)
        n = len(due)
    reqs = draw_requests(traffic, args.seed, args.vocab, args.mask_id, n)
    t0 = time.monotonic() + 0.2
    print(json.dumps({"t0": t0}), flush=True)
    ws, we = t0 + warm, t0 + warm + window
    records, tasks, scrapes = [], [], {}

    async def scrape(name, at):
        await asyncio.sleep(max(0.0, at - time.monotonic()))
        scrapes[name] = [time.monotonic(), await get_text(args.url,
                                                           "/metrics")]

    async def client(c):
        for j in range(c, n, traffic["clients"]):
            now = time.monotonic()
            if now >= we:
                return
            rec = {"i": j, "due": max(now, t0), "prompt": reqs[j][0],
                   "gen": reqs[j][1], "events": []}
            records.append(rec)
            await asyncio.sleep(max(0.0, rec["due"] - now))
            await complete(args.url, reqs[j][0], reqs[j][1], rec)

    async def fire(j):
        rec = {"i": j, "due": t0 + float(due[j]), "prompt": reqs[j][0],
               "gen": reqs[j][1], "events": []}
        records.append(rec)
        await asyncio.sleep(max(0.0, rec["due"] - time.monotonic()))
        await complete(args.url, reqs[j][0], reqs[j][1], rec)

    side = ([asyncio.ensure_future(scrape("open", ws)),
             asyncio.ensure_future(scrape("close", we))] if args.scrape
            else [asyncio.ensure_future(asyncio.sleep(we - time.monotonic()))])
    if closed:
        tasks = [asyncio.ensure_future(client(c))
                 for c in range(traffic["clients"])]
        # the window closes: requests still running need not finish
        await asyncio.sleep(max(0.0, we - time.monotonic()) + 0.5)
    else:
        tasks = [asyncio.ensure_future(fire(j)) for j in range(n)]
        # follow the window's requests to completion, up to the limit
        mine = [t for j, t in enumerate(tasks) if ws <= t0 + due[j] < we]
        await asyncio.wait(mine or side, timeout=max(0.0, we + follow
                                             - time.monotonic()))
    await asyncio.gather(*side)
    stopped = time.monotonic()
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    return {"t0": t0, "window": [ws, we], "stopped": stopped,
            "records": records, "scrapes": scrapes}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--url", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--vocab", type=int, required=True)
    ap.add_argument("--mask-id", type=int, required=True)
    ap.add_argument("--window", type=float, required=True)
    ap.add_argument("--scrape", type=int, choices=(0, 1), default=1,
                    help="take the /metrics scrapes at the window's edges")
    args = ap.parse_args(argv)
    with open(args.traffic) as f:
        traffic = json.load(f)
    out = asyncio.run(run(args, traffic))
    sys.stdout.write(json.dumps(out) + "\n")
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
