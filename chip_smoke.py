"""Run the serving engine end to end on a TPU at LLaDA-8B's published widths.

    python chip_smoke.py               # one chip: phases (a)-(g)
    python chip_smoke.py --four-chips  # four chips: mesh engines and HTTP
                                       # replicas, and their references

The model is ``llada-8b`` (d_model 4096, 32 heads of 128, d_ff 12288,
vocab 126464, bf16) with random weights from ``--seed``.  Depth is cut
from 32 to 16 layers: the 32-layer bf16 weights are ~16.0 GB and fill a
16 GB v5e on their own, 16 layers (~9.1 GB) leave room for KV and
activations.  Every phase checks its result against a reference; a failed
phase makes the script exit non-zero without the result line, and so does
a machine whose first JAX device is not a TPU.  The last line of standard
output is the JSON result.  Timings printed here are informational, not a
benchmark.  The persistent compilation cache is ``repro.deploy``'s.
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import functools
import gc
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import deploy  # noqa: E402
from repro.configs import base  # noqa: E402
from repro.core import diffusion, mx, sampling  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.models.registry import build_model  # noqa: E402
from repro.serving import (EngineConfig, Request, ServingEngine,  # noqa: E402
                           get_policy)

SMOKE_LAYERS = 16
HEAD_FMTS = ("none", "bf16", "mxfp8_e4m3")
# The kernel's MXU and XLA's dot accumulate the K=4096 reduction in
# different orders, so a logit may land one bf16 rounding step apart.  A
# row whose kernel token is not the oracle's is excused only when the
# token's oracle logit is within TIE_ULPS bf16 ulps of the row's best.
TIE_ULPS = 4
# conf = 1/sum(exp(z - max)): when the row's best logit lands one bf16
# step apart, every term scales by exp(that step).  So conf may differ by
# the same TIE_ULPS bf16 ulps of the best logit, in log space.


@dataclasses.dataclass(frozen=True)
class Sizes:
    slots: int = 16
    requests: int = 32
    prompt_len: int = 128
    gen_len: int = 64
    block_len: int = 32
    steps: int = 16
    max_seq_len: int = 256        # > prompt + gen: every row is padded
    head_rows: int = 512
    megatick_k: int = 8


class PhaseFailed(RuntimeError):
    pass


class CompileClock:
    """Seconds of XLA backend compilation (persistent-cache reads count
    only their retrieval time) and persistent-cache hits/misses, from
    jax.monitoring."""

    def __init__(self):
        self.seconds = 0.0
        self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


class Smoke:
    def __init__(self, cfg, sizes: Sizes, seed: int, clock: CompileClock):
        self.cfg, self.sz, self.seed, self.clock = cfg, sizes, seed, clock
        self.model = build_model(cfg)
        self.failures = []
        argv = ["--full", "--slots", str(sizes.slots),
                "--batch", str(sizes.slots),
                "--requests", str(sizes.requests // sizes.slots),
                "--prompt-len", str(sizes.prompt_len),
                "--gen-len", str(sizes.gen_len),
                "--block-len", str(sizes.block_len),
                "--steps", str(sizes.steps), "--seed", str(seed)]
        # serve.py's own defaults: warm ticks, mxfp8 sampling, BAOS mxint4
        # KV, fifo, slot pool, K=1
        self.args = serve.build_parser().parse_args(argv)
        self.dcfg = serve.make_dcfg(self.args)
        self.prompts = [np.asarray(r.prompt, np.int32) for r in
                        serve.make_requests(self.args, cfg, seed)]
        self.params = None
        self.tokens = None          # phase (c): uid -> generated tokens
        self.tok_per_s = None       # phase (c), informational

    # -- helpers -----------------------------------------------------------

    def init_params(self, sharding=None):
        """Weights from the seed, built on device (jitted, so the f32
        draws never sit in HBM next to the bf16 weights)."""
        init = jax.jit(self.model.init, out_shardings=sharding)
        params = init(jax.random.PRNGKey(self.seed))
        jax.block_until_ready(params)
        return params

    def engine_config(self, **over) -> EngineConfig:
        a = self.args
        kw = dict(num_slots=a.slots, max_seq_len=self.sz.max_seq_len,
                  mode=a.mode, policy=get_policy(a.policy),
                  rng=jax.random.PRNGKey(a.seed), megatick_k=a.megatick,
                  pool=a.pool, page_size=a.page_size,
                  num_pages=a.num_pages)
        kw.update(over)
        return EngineConfig(**kw)

    def requests(self, n=None):
        return [Request(uid=i + 1, prompt=p, gen_length=self.sz.gen_len)
                for i, p in enumerate(self.prompts[:n])]

    def run_engine(self, params, dcfg, config, n=None):
        """warmup + run; returns (uid -> generated tokens, run seconds)."""
        eng = ServingEngine(self.model, params, dcfg, config)
        eng.warmup()
        reqs = self.requests(n)
        t0 = time.perf_counter()
        done = eng.run(reqs)
        secs = time.perf_counter() - t0
        if len(done) != len(reqs):
            raise PhaseFailed(f"{len(done)} of {len(reqs)} requests "
                              "completed")
        out = {c.uid: np.asarray(c.tokens[c.prompt_len:]) for c in done}
        left = sum(int((t == self.cfg.mask_id).sum()) for t in out.values())
        if left:
            raise PhaseFailed(f"{left} mask tokens left in the output")
        del eng
        gc.collect()
        return out, secs

    def phase(self, name, fn, needs_tokens=False) -> None:
        if needs_tokens and self.tokens is None:
            self.failures.append(name)
            print(f"phase {name}: FAIL (needs the tokens of phase (c))")
            return
        t0, c0 = time.perf_counter(), self.clock.seconds
        try:
            detail = fn()
        except Exception as e:                       # recorded, exit != 0
            traceback.print_exc()
            self.failures.append(name)
            print(f"phase {name}: FAIL ({type(e).__name__}: "
                  f"{str(e)[:300]})", flush=True)
            return
        print(f"phase {name}: PASS  {detail}  [{time.perf_counter() - t0:.1f}"
              f" s, compile {self.clock.seconds - c0:.1f} s]", flush=True)

    # -- phases --------------------------------------------------------------

    def head_input(self):
        """(rows, d) hidden states from the seed, and the model's head."""
        cfg = self.cfg
        h = jax.random.normal(jax.random.PRNGKey(self.seed + 1),
                              (self.sz.head_rows, cfg.d_model), jnp.float32)
        return h.astype(cfg.jdtype), self.params["lm_head"]

    def kernel_vs_oracle(self) -> str:
        """(b) the fused LM-head kernel against the jnp oracle on the same
        (rows, d) hidden states and the model's (d, V) head."""
        mask_id = self.cfg.mask_id
        h, w = self.head_input()
        notes = []
        for fmt in HEAD_FMTS:
            kern = jax.jit(lambda h, w, fmt=fmt: ops.fused_head_sampling(
                h, w, fmt=fmt, suppress_id=mask_id))
            notes.append(_compare_heads(f"kernel fmt={fmt}", kern(h, w),
                                        h, w, fmt, mask_id))
        return (f"tolerance {TIE_ULPS} bf16 ulps of the best logit; "
                + "; ".join(notes))

    def engine_defaults(self) -> str:
        """(c) serve.py's default engine at the smoke's sizes."""
        self.tokens, secs = self.run_engine(self.params, self.dcfg,
                                            self.engine_config())
        n_tok = len(self.tokens) * self.sz.gen_len
        self.tok_per_s = n_tok / secs
        return (f"{len(self.tokens)} requests completed, no mask left; "
                f"{n_tok} tokens in {secs:.2f} s")

    def parity_with_generate(self) -> str:
        """(d) two requests through the mode-none engine == generate().

        The engine's canvas is prompt + gen here, the shape generate()
        runs, so both run the same program.  A padded canvas gives the
        same tokens on the CPU (tests/test_serving.py); on the chip a
        longer canvas changes the rounding of the forward, which a
        random-weight model's near-uniform confidences turn into other
        commits, so that comparison is printed, not required."""
        dcfg = dataclasses.replace(self.dcfg, cache_mode="none")
        prompt = jnp.asarray(np.stack(self.prompts[:2]))
        ref = np.asarray(diffusion.generate(
            self.model, self.params, prompt, dcfg,
            rng=jax.random.PRNGKey(self.seed)))[:, self.sz.prompt_len:]
        s_tot = self.sz.prompt_len + self.sz.gen_len
        got, _ = self.run_engine(self.params, dcfg, self.engine_config(
            mode="none", num_slots=2, max_seq_len=s_tot), n=2)
        for i in range(2):
            _require_equal(f"request {i + 1}", got[i + 1], ref[i])
        padded, _ = self.run_engine(self.params, dcfg, self.engine_config(
            mode="none", num_slots=2), n=2)
        same = [int((padded[i + 1] == ref[i]).sum()) for i in range(2)]
        return (f"2 requests bit-identical to generate(cache_mode='none') "
                f"at canvas {s_tot}; at canvas {self.sz.max_seq_len} "
                f"{same} of {self.sz.gen_len} tokens equal (information)")

    def megatick_paged(self) -> str:
        """(e) the same requests with megatick_k=K and the paged pool."""
        got, _ = self.run_engine(self.params, self.dcfg, self.engine_config(
            megatick_k=self.sz.megatick_k, pool="paged"))
        for uid, ref in self.tokens.items():
            _require_equal(f"request {uid}", got[uid], ref)
        return (f"{len(got)} requests bit-identical to phase (c) "
                f"(megatick_k={self.sz.megatick_k}, pool=paged)")

    def http_server(self) -> str:
        """(f) two streamed completions through an in-process server."""
        from repro.serving.frontend import build_frontend, loadgen

        async def go():
            fe = build_frontend(
                self.model, self.params, self.dcfg, model_name=self.cfg.name,
                replicas=1, num_slots=self.sz.slots,
                max_seq_len=self.sz.max_seq_len, mode=self.args.mode,
                policy=get_policy(self.args.policy), seed=self.seed,
                drift=False)
            await fe.start()
            try:
                return await asyncio.gather(*[
                    loadgen.complete(fe.url, p.tolist(), self.sz.gen_len)
                    for p in self.prompts[:2]])
            finally:
                await fe.shutdown()

        rows = asyncio.run(go())
        gc.collect()
        for i, row in enumerate(rows):
            if row.get("status") != "ok":
                raise PhaseFailed(f"request {i + 1}: {row}")
            _require_equal(f"streamed request {i + 1}",
                           np.asarray(row["token_ids"]), self.tokens[i + 1])
        return "2 streamed completions equal the offline engine's tokens"

    # -- the runs ------------------------------------------------------------

    def one_chip(self) -> None:
        self.params = self.init_params()
        print(f"weights: {_tree_bytes(self.params) / 1e9:.2f} GB "
              f"({SMOKE_LAYERS} layers)", flush=True)
        self.phase("(b) kernel at full width", self.kernel_vs_oracle)
        self.phase("(c) engine defaults", self.engine_defaults)
        self.phase("(d) parity with generate()", self.parity_with_generate)
        self.phase("(e) megatick + paged pool", self.megatick_paged,
                   needs_tokens=True)
        self.phase("(f) HTTP server", self.http_server, needs_tokens=True)

    def four_chips(self) -> None:
        """Four HTTP replicas against the single-device engine (the same
        program per chip, so the same tokens); the Stable-Max combine over
        LM-head shards against the single-device oracle; and the SPMD
        engine on (1,4) and (2,2) meshes against a (1,1) mesh.

        The mesh engines run one step per block: each tick then commits
        the whole block by its per-position argmax, which the column
        shards reproduce exactly.  With 16 steps a tick commits the most
        confident positions, and the combine's cross-shard exp-sum rounds
        differently from one device's, so a near-tie between a random-
        weight model's confidences can commit other positions."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.launch.mesh import make_debug_mesh

        meshes = {"(1,1)": make_debug_mesh(1, 1),
                  "(1,4)": make_debug_mesh(1, 4),
                  "(2,2)": make_debug_mesh(2, 2)}
        self.params = self.init_params()
        self.phase("(c) single-device engine", self.engine_defaults)
        self.phase("replicas: four HTTP replicas", self.http_replicas,
                   needs_tokens=True)
        for name in ("(1,4)", "(2,2)"):
            self.phase(f"head combine {name}",
                       lambda name=name: self.sharded_head(meshes[name]))
        self.params = None
        gc.collect()

        n = self.sz.requests
        dcfg = dataclasses.replace(self.dcfg, steps_per_block=1)
        ref = {}

        def mesh_run(name):
            if name != "(1,1)" and not ref:
                raise PhaseFailed("needs the (1,1) mesh's tokens")
            mesh = meshes[name]
            params = self.init_params(NamedSharding(mesh, P()))
            # every chip computes `slots` rows, as on the (1,1) mesh
            slots = self.sz.slots * mesh.shape["data"]
            eng = ServingEngine(self.model, params, dcfg,
                                self.engine_config(mesh=mesh,
                                                   num_slots=slots))
            del params                      # the engine holds its placement
            eng.warmup()
            done = eng.run(self.requests(n))
            got = {c.uid: np.asarray(c.tokens[c.prompt_len:]) for c in done}
            del eng
            gc.collect()
            if len(got) != n:
                raise PhaseFailed(f"{len(got)} of {n} requests completed")
            if name == "(1,1)":
                ref.update(got)
                return f"{n} requests, one step per block"
            for uid, r in ref.items():
                _require_equal(f"request {uid}", got[uid], r)
            return f"{n} requests bit-identical to the (1,1) mesh"

        for name in meshes:
            self.phase(f"mesh {name} engine", lambda name=name: mesh_run(name))

    def sharded_head(self, mesh) -> str:
        """The SPMD head: each chip streams its LM-head column shard and
        the per-shard Stable-Max partials merge with pmax/psum/pmin."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        fmt, mask_id = self.dcfg.sampling.fmt, self.cfg.mask_id
        h, w = self.head_input()
        w_sh = jax.device_put(
            sampling.pad_head_for_mesh(w, mesh.shape["model"]),
            NamedSharding(mesh, P(None, "model")))
        head = jax.jit(jax.shard_map(
            lambda h, w: sampling.sharded_fused_head_stable_max(
                h, w, "model", fmt, suppress_id=mask_id,
                col_limit=self.cfg.vocab),
            mesh=mesh, in_specs=(P(), P(None, "model")),
            out_specs=(P(), P())))
        got = head(jax.device_put(h, NamedSharding(mesh, P())), w_sh)
        got = tuple(np.asarray(a) for a in got)      # replicated result
        return _compare_heads(f"fmt={fmt}", got, h, w, fmt, mask_id)

    def http_replicas(self) -> str:
        from repro.serving.frontend import build_frontend, loadgen

        devices = jax.local_devices()
        n = self.sz.requests

        async def go():
            fe = build_frontend(
                self.model, self.params, self.dcfg, model_name=self.cfg.name,
                replicas=len(devices), num_slots=self.sz.slots,
                max_seq_len=self.sz.max_seq_len, mode=self.args.mode,
                policy=get_policy(self.args.policy), seed=self.seed,
                drift=False)
            await fe.start()
            try:
                rows = await asyncio.gather(*[
                    loadgen.complete(fe.url, p.tolist(), self.sz.gen_len,
                                     timeout=600.0)
                    for p in self.prompts[:n]])
            finally:
                await fe.shutdown()
            return fe, rows

        fe, rows = asyncio.run(go())
        placed, served = [], []
        for i, w in enumerate(fe.router.workers):
            devs = {d for leaf in jax.tree_util.tree_leaves(w.engine.params)
                    for d in leaf.devices()}
            if devs != {devices[i]}:
                raise PhaseFailed(f"{w.name}: params on {devs}, expected "
                                  f"{devices[i]}")
            placed.append(str(devices[i].id))
            served.append(w.completed)
        del fe
        gc.collect()
        for i, row in enumerate(rows):
            if row.get("status") != "ok":
                raise PhaseFailed(f"request {i + 1}: {row}")
            _require_equal(f"request {i + 1}", np.asarray(row["token_ids"]),
                           self.tokens[i + 1])
        if min(served) == 0:
            raise PhaseFailed(f"a replica served nothing: {served}")
        return (f"{len(rows)} streamed completions equal the single-device "
                f"engine; replicas on devices {placed} served {served}")


@functools.partial(jax.jit, static_argnames=("fmt", "mask_id"))
def _quantized_logits_at(h, w, tok, fmt, mask_id):
    """The oracle's sampling-precision logits (full rows, mask id
    suppressed): the top two per row, and the value at ``tok``."""
    z = mx.mx_fake_quant(sampling.head_logits(h, w), fmt).astype(jnp.float32)
    z = z.at[:, mask_id].set(sampling.NEG_INF)
    top2 = jax.lax.top_k(z, 2)[0]
    return top2, jnp.take_along_axis(z, tok[:, None], axis=-1)[:, 0]


def _compare_heads(what, got, h, w, fmt, mask_id) -> str:
    """Hold a head's (conf, token) per row to the jnp oracle
    (``fused_head_stable_max``) on the same inputs: the token's oracle
    logit within TIE_ULPS bf16 ulps of the row's best, conf within as much
    in log space.  Returns how many rows were excused as ties."""
    c_o, i_o = jax.jit(lambda h, w: sampling.fused_head_stable_max(
        h, w, fmt, suppress_id=mask_id))(h, w)
    top2, z_tok = _quantized_logits_at(h, w, got[1], fmt, mask_id)
    c_k, i_k, c_o, i_o, best, z_tok = (np.asarray(a) for a in (
        got[0], got[1], c_o, i_o, top2[:, 0], z_tok))
    tol = TIE_ULPS * np.exp2(np.floor(np.log2(np.abs(best) + 1e-30)) - 7)
    if not (z_tok >= best - tol).all():
        raise PhaseFailed(f"{what}: {int((z_tok < best - tol).sum())} rows' "
                          f"token is more than {TIE_ULPS} bf16 ulps below "
                          "the oracle's best logit")
    dlog = np.abs(np.log(c_k) - np.log(c_o))
    if not (dlog <= tol).all():
        raise PhaseFailed(f"{what}: {int((dlog > tol).sum())} rows' conf "
                          f"differs by more than {TIE_ULPS} bf16 ulps of "
                          "the best logit (log space)")
    return (f"{what}: {int((i_k != i_o).sum())}/{len(i_k)} rows excused as "
            f"ties, max |dlog conf| {dlog.max():.2e}")


def _require_equal(what, got, ref) -> None:
    got, ref = np.asarray(got), np.asarray(ref)
    if got.shape != ref.shape:
        raise PhaseFailed(f"{what}: shape {got.shape} != {ref.shape}")
    diff = np.nonzero(got != ref)[0]
    if diff.size:
        raise PhaseFailed(f"{what}: first differing generated position "
                          f"{int(diff[0])} ({int(got[diff[0]])} vs "
                          f"{int(ref[diff[0]])}), {diff.size} differ")


def _tree_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(tree))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip paths (a 2x2 host)")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    need = 4 if args.four_chips else 1
    print(f"(a) device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu" or len(devices) < need:
        print(f"chip_smoke: needs {need} TPU chip(s); JAX found "
              f"{len(devices)} {dev.platform} device(s)", file=sys.stderr)
        return 2
    print(f"compilation cache: {deploy.ensure_compilation_cache()}")
    clock = CompileClock()

    full = base.get_config("llada-8b")
    cfg = dataclasses.replace(full, n_layers=SMOKE_LAYERS)
    print(f"model: {cfg.name} d_model={cfg.d_model} heads={cfg.n_heads}x"
          f"{cfg.d_head} kv_heads={cfg.n_kv_heads} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab} dtype={cfg.dtype} n_layers={cfg.n_layers} "
          f"(cut from {full.n_layers}: 32-layer bf16 weights are ~16.0 GB, "
          f"one chip holds 16 GB)", flush=True)
    sz = Sizes(slots=8, requests=16) if args.four_chips else Sizes()
    smoke = Smoke(cfg, sz, args.seed, clock)
    a = smoke.args
    print(f"engine: mode={a.mode} sampling={a.sampling_fmt} "
          f"kv={a.kv_format} baos={not a.no_baos} policy={a.policy} "
          f"pool={a.pool} K={a.megatick} slots={sz.slots} "
          f"requests={len(smoke.prompts)} prompt={sz.prompt_len} "
          f"gen={sz.gen_len} block={sz.block_len} steps={sz.steps} "
          f"max_seq_len={sz.max_seq_len}", flush=True)

    if args.four_chips:
        smoke.four_chips()
    else:
        smoke.one_chip()

    print(f"(g) compile seconds: {clock.seconds:.1f} (persistent cache "
          f"hits {clock.hits}, misses {clock.misses})")
    if smoke.tok_per_s is not None:
        print(f"(g) steady tokens/s (informational, not a benchmark): "
              f"{smoke.tok_per_s:.1f}")
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        print(f"(g) device {d.id} peak_bytes_in_use: "
              f"{stats.get('peak_bytes_in_use', 'not reported')}")
    if smoke.failures:
        print(f"FAILED phases: {smoke.failures}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
