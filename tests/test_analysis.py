"""Static-analysis gate (docs/static_analysis.md): each pass must fire on
its seeded-violation fixture and stay quiet on clean code — and on the
repo itself, which pins the violation fixes that landed with the gate
(time.time() -> perf_counter, bare asserts -> ValueError, the reviewed
allowlist entry).  The recompilation-guard test replays the engine's
mixed-K megatick + mesh shape trace and bounds the compiled executables.
"""
import textwrap

import pytest

from repro.analysis import hotpath_lint, locks, registry, sram_budget
from repro.analysis.report import Allowlist, Violation, assemble, render

# a module path registered as fully hot ("*") — fixtures lint as if they
# lived there, so hot-path rules apply
HOT_PATH = "repro/core/sampling.py"
COLD_PATH = "repro/launch/dryrun.py"


def _lint(src, relpath=HOT_PATH):
    vs, _ = hotpath_lint.lint_source(relpath, textwrap.dedent(src))
    return vs


def _rules(vs):
    return {v.rule for v in vs}


# ---------------------------------------------------------------------------
# hotpath_lint: seeded fixtures
# ---------------------------------------------------------------------------

class TestHotpathLint:
    def test_hidden_item_fires(self):
        vs = _lint("""
            import jax.numpy as jnp
            def stable_max(conf):
                return conf.item()
        """)
        assert _rules(vs) == {"ANL-HOSTSYNC"}
        assert ".item()" in vs[0].detail

    def test_numpy_call_fires(self):
        vs = _lint("""
            import numpy as np
            def tick(x):
                return np.asarray(x)
        """)
        assert _rules(vs) == {"ANL-HOSTSYNC"}

    def test_device_get_and_block_until_ready_fire(self):
        vs = _lint("""
            import jax
            def tick(x):
                jax.block_until_ready(x)
                return jax.device_get(x)
        """)
        assert len([v for v in vs if v.rule == "ANL-HOSTSYNC"]) == 2

    def test_float_on_name_fires_attribute_does_not(self):
        vs = _lint("""
            def tick(x, cfg):
                a = float(x)
                b = float(cfg.logit_scale)
                c = int(len(cfg.items))
                return a + b + c
        """)
        assert len(vs) == 1 and "float(x)" in vs[0].detail

    def test_rng_reuse_fires(self):
        vs = _lint("""
            import jax
            def draw(rng, shape):
                a = jax.random.uniform(rng, shape)
                b = jax.random.gumbel(rng, shape)
                return a + b
        """)
        assert _rules(vs) == {"ANL-RNG"}

    def test_rng_split_between_draws_is_clean(self):
        vs = _lint("""
            import jax
            def draw(rng, shape):
                a = jax.random.uniform(rng, shape)
                rng, sub = jax.random.split(rng)
                b = jax.random.gumbel(rng, shape)
                c = jax.random.bits(sub)
                return a + b + c
        """)
        assert vs == []

    def test_time_time_fires_everywhere(self):
        vs = _lint("""
            import time
            def measure():
                return time.time()
        """, relpath=COLD_PATH)
        assert _rules(vs) == {"ANL-TIME"}

    def test_bare_assert_fires(self):
        vs = _lint("""
            def pack(d, block):
                assert d % block == 0
        """, relpath=COLD_PATH)
        assert _rules(vs) == {"ANL-ASSERT"}

    def test_clean_hot_code_is_quiet(self):
        vs = _lint("""
            import jax
            import jax.numpy as jnp
            def tick(x, rng):
                noise = jax.random.gumbel(rng, x.shape)
                return jnp.argmax(x + noise, axis=-1)
        """)
        assert vs == []

    def test_cold_module_skips_hot_rules(self):
        # host syncs are fine outside registered hot paths
        vs = _lint("""
            import numpy as np
            def drain(conf):
                return np.asarray(conf), conf.item()
        """, relpath=COLD_PATH)
        assert vs == []

    def test_emit_io_fires_in_registered_emit_path(self):
        # fixture lints as if it were the real EventLog.emit
        vs = _lint("""
            import json, os
            class EventLog:
                def emit(self, rec):
                    line = json.dumps(rec)
                    self._file.write(line)
                    os.fsync(self._file.fileno())
                def flush(self):
                    self._file.flush()      # flusher side: allowed
        """, relpath="repro/obs/events.py")
        emit = [v for v in vs if v.rule == "ANL-EMITIO"]
        assert len(emit) == 3               # dumps, .write, os.fsync
        assert all("repro/obs/events.py::EventLog.emit" == v.where
                   for v in emit)

    def test_emit_io_quiet_on_dict_build(self):
        vs = _lint("""
            class EventLog:
                def emit(self, event, uid=None):
                    rec = {"event": event, "uid": uid}
                    with self._lock:
                        self._pending.append(rec)
        """, relpath="repro/obs/events.py")
        assert [v for v in vs if v.rule == "ANL-EMITIO"] == []

    def test_repo_is_clean_and_fixes_are_pinned(self):
        """The gate lands at zero: no time.time(), no bare assert, no hot
        host-sync anywhere in src/ beyond the reviewed exceptions."""
        allow = Allowlist.load(registry.default_allowlist_path())
        res = hotpath_lint.run(allow)
        assert res.violations == []
        assert res.checked > 400
        # reviewed exceptions: the megatick builder prologue and the
        # OpenMetrics exemplar timestamp (wall-clock by spec)
        assert sorted(v.where for v in res.suppressed) == \
            ["repro/core/diffusion.py::get_megatick_fn",
             "repro/obs/registry.py::module"]


# ---------------------------------------------------------------------------
# locks: seeded fixtures
# ---------------------------------------------------------------------------

def _scan(src):
    vs, edges, _, _ = locks.scan_source("repro/serving/fixture.py",
                                        textwrap.dedent(src))
    return vs, edges


class TestLocks:
    def test_unguarded_field_write_fires(self):
        vs, _ = _scan("""
            import threading
            class Worker:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.queued = 0
                def safe(self, n):
                    with self._lock:
                        self.queued = n
                def racy(self):
                    self.queued += 1
        """)
        assert [v.rule for v in vs] == ["ANL-LOCK-MIXED"]
        assert "Worker.queued" in vs[0].where

    def test_consistent_disciplines_are_quiet(self):
        vs, _ = _scan("""
            import threading
            class Worker:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.queued = 0
                    self.ticks = 0
                def locked_write(self, n):
                    with self._lock:
                        self.queued = n
                def single_writer(self):
                    self.ticks += 1      # worker-thread-only, never locked
        """)
        assert vs == []

    def test_mutating_container_calls_are_writes(self):
        vs, _ = _scan("""
            import threading
            class Buf:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.events = []
                def emit(self, ev):
                    with self._lock:
                        self.events.append(ev)
                def drain_racy(self):
                    self.events.clear()
        """)
        assert [v.rule for v in vs] == ["ANL-LOCK-MIXED"]

    def test_closure_under_with_is_not_protected(self):
        vs, _ = _scan("""
            import threading
            class W:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.n = 0
                def sched(self, loop):
                    with self._lock:
                        def cb():
                            self.n += 1      # runs later, lock released
                        loop.call_soon(cb)
                def bump(self):
                    with self._lock:
                        self.n += 1
        """)
        assert [v.rule for v in vs] == ["ANL-LOCK-MIXED"]

    def test_lock_order_cycle_fires(self):
        vs, edges = _scan("""
            import threading
            class AB:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()
                def fwd(self):
                    with self._a:
                        with self._b:
                            pass
                def rev(self):
                    with self._b:
                        with self._a:
                            pass
        """)
        cycles = locks._find_cycles(edges)
        assert cycles, "AB/BA nesting must form a deadlock cycle"
        assert {"AB._a", "AB._b"} <= set(cycles[0])

    def test_reacquire_same_lock_fires(self):
        vs, _ = _scan("""
            import threading
            class W:
                def __init__(self):
                    self._lock = threading.Lock()
                def oops(self):
                    with self._lock:
                        with self._lock:
                            pass
        """)
        assert "ANL-LOCK-ORDER" in {v.rule for v in vs}

    def test_repo_lock_discipline_is_clean(self):
        res = locks.run(Allowlist())
        assert res.violations == []
        assert res.checked >= 20
        # the guard map proves extraction saw the real locked classes
        gm = res.info["guard_map"]
        assert any("EngineWorker" in k for k in gm)


# ---------------------------------------------------------------------------
# sram_budget: seeded overflow + real-kernel fit + allocator cross-check
# ---------------------------------------------------------------------------

class TestSramBudget:
    def test_synthetic_overflow_fires(self):
        huge = registry.KernelSpec(
            "synthetic_overflow", {"d": 8192, "chunk": 8192},
            {"w_slab": 8192 * 8192 * 2, "scratch": 1024},
            ("w_slab",))
        vs, table = sram_budget.check_budgets([huge])
        assert [v.rule for v in vs] == ["ANL-SRAM-BUDGET"]
        assert "w_slab" in vs[0].detail
        assert table["synthetic_overflow"]["utilization"] > 1.0

    def test_production_kernels_fit(self):
        vs, table = sram_budget.check_budgets()
        assert vs == []
        assert set(table) == {"fused_head_sampling", "stablemax_sampling",
                              "topk_mask", "flash_bidir", "baos_mx_quant"}
        for t in table.values():
            assert t["utilization"] < 1.0
        # the fused head's double-buffered ~4 MiB slab dominates
        fh = table["fused_head_sampling"]
        assert fh["buffers"]["w_slab"] == pytest.approx(8 * 2**20)

    def test_footprint_tracks_double_buffering(self):
        spec = registry.kernel_specs()[0]
        fp = spec.footprint()
        assert fp["w_slab"] == 2 * spec.buffers["w_slab"]
        assert fp["scratch"] == spec.buffers["scratch"]

    def test_crossval_agrees_with_cycle_allocator(self):
        """The SRAM pass's static fused-head footprint and sim/cycle.py's
        exact-fit allocator must agree within the asserted band at full
        LLaDA-8B scale (they are byte-identical today)."""
        vs, info = sram_budget.crossval_allocator()
        assert vs == []
        lo, hi = registry.SRAM_CROSSVAL_BAND
        assert lo <= info["ratio"] <= hi
        assert info["sram_ok"] is True
        # today the accounting is byte-exact; allow a hair of slack
        assert info["ratio"] == pytest.approx(1.0, abs=0.02)

    def test_band_is_discriminative(self):
        """A mis-modeled vocab chunk (the classic divergence: the kernel's
        BlockSpec changes but the sim's emission hook doesn't) moves the
        static peak far outside SRAM_CROSSVAL_BAND."""
        static = sram_budget.static_stream_peak(8, 32, 126464, 4096,
                                                chunk_v=512)
        full = sram_budget.static_stream_peak(8, 32, 126464, 4096)
        assert static < full * registry.SRAM_CROSSVAL_BAND[0]


# ---------------------------------------------------------------------------
# report / allowlist plumbing
# ---------------------------------------------------------------------------

class TestAllowlist:
    def test_filter_and_stale_detection(self, tmp_path):
        p = tmp_path / "allow.txt"
        p.write_text("# header\n"
                     "ANL-TIME:a.py::module  # reviewed wall-clock use\n"
                     "ANL-RNG:gone.py::fn    # no longer exists\n"
                     "ANL-ASSERT:b.py::module\n")
        allow = Allowlist.load(str(p))
        kept, supp = allow.filter([
            Violation("ANL-TIME", "a.py::module", "x"),
            Violation("ANL-HOSTSYNC", "c.py::f", "y"),
        ])
        assert [v.rule for v in kept] == ["ANL-HOSTSYNC"]
        assert [v.rule for v in supp] == ["ANL-TIME"]
        metas = allow.meta_violations()
        details = " | ".join(v.detail for v in metas)
        assert "stale" in details and "no justification" in details
        # partial runs must not report stale entries
        assert all("stale" not in v.detail
                   for v in allow.meta_violations(check_stale=False))

    def test_assemble_counts_meta_violations(self):
        allow = Allowlist({"ANL-X:nowhere": ""})
        payload = assemble([], allow)
        assert payload["violations"] == 2      # uncommented + stale
        assert payload["benchmark"] == "analysis"
        assert "FAIL" in render(payload)


# ---------------------------------------------------------------------------
# jaxpr audit: seeded fixtures + real entry points + recompilation guard
# ---------------------------------------------------------------------------

class TestJaxprAudit:
    def test_callback_primitive_fires(self):
        import jax
        import jax.numpy as jnp

        from repro.analysis import jaxpr_audit

        def leaky(x):
            return jax.pure_callback(
                lambda v: v, jax.ShapeDtypeStruct(x.shape, x.dtype), x)

        ep = registry.EntryPoint(
            "leaky", leaky, (jnp.ones((4,)),), resident_argnums=(),
            max_h2d=8, max_d2h=8)
        vs, _ = jaxpr_audit.audit_entry(ep)
        assert [v.rule for v in vs] == ["ANL-JAXPR-CALLBACK"]

    def test_transfer_budget_fires(self):
        import jax.numpy as jnp

        from repro.analysis import jaxpr_audit

        ep = registry.EntryPoint(
            "fat", lambda a, b: (a, b, a + b),
            (jnp.ones((2,)), jnp.ones((2,))),
            resident_argnums=(), max_h2d=1, max_d2h=2)
        vs, _ = jaxpr_audit.audit_entry(ep)
        assert {v.rule for v in vs} == {"ANL-JAXPR-TRANSFER"}
        assert len(vs) == 2                    # h2d and d2h both over

    def test_dropped_donation_fires(self):
        import jax
        import jax.numpy as jnp

        from repro.analysis import jaxpr_audit

        def step(x):
            return x + 1

        arg = jnp.ones((8,))
        kept = registry.EntryPoint(
            "donated", step, (arg,), resident_argnums=(0,),
            max_h2d=1, max_d2h=1,
            jitted=jax.jit(step, donate_argnums=(0,)), min_aliased=1)
        vs, info = jaxpr_audit.audit_entry(kept)
        assert vs == [] and info["aliased_buffers"] == 1

        dropped = registry.EntryPoint(
            "undonated", step, (arg,), resident_argnums=(0,),
            max_h2d=1, max_d2h=1,
            jitted=jax.jit(step), min_aliased=1)
        vs, _ = jaxpr_audit.audit_entry(dropped)
        assert [v.rule for v in vs] == ["ANL-JAXPR-DONATE"]

    def test_undeclared_collective_axis_fires(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from repro.analysis import jaxpr_audit
        from repro.launch.mesh import make_debug_mesh

        mesh = make_debug_mesh(1, 1)

        def red(x):
            return jax.shard_map(lambda v: jax.lax.psum(v, "model"),
                             mesh=mesh, in_specs=P(None, "model"),
                             out_specs=P())(x)

        ep = registry.EntryPoint(
            "stray_axis", red, (jnp.ones((2, 2)),), resident_argnums=(),
            max_h2d=8, max_d2h=8, mesh_axes=("data",))
        vs, _ = jaxpr_audit.audit_entry(ep)
        assert "ANL-JAXPR-COLLECTIVE" in {v.rule for v in vs}
        ep.mesh_axes = ("data", "model")
        vs, _ = jaxpr_audit.audit_entry(ep)
        assert vs == []

    def test_registered_entry_points_are_clean(self):
        """Every registered jitted entry point passes the abstract audit:
        no callbacks, donation lowered, budgets and axes respected."""
        from repro.analysis import jaxpr_audit

        res = jaxpr_audit.run(Allowlist(), recompile=False)
        assert res.violations == []
        eps = res.info["entry_points"]
        assert {"batched_tick", "spmd_tick", "megatick",
                "megatick_mesh"} <= set(eps)
        assert eps["megatick"]["aliased_buffers"] >= 1
        assert set(eps["megatick_mesh"]["collectives"].get("psum", [])) \
            <= {"data", "model"}

    def test_recompilation_guard_bounds_executables(self):
        """Satellite: replaying a mixed-K megatick + mesh engine trace
        (k_req 1/4/2, stop_on_release both ways, fresh rng, two batch
        shapes for the plain tick) compiles a bounded, enumerated set of
        executables — depth, stop flag, and rng are device operands,
        never static cache keys."""
        from repro.analysis import jaxpr_audit

        vs, info = jaxpr_audit.check_recompilation()
        assert vs == []
        sizes = info["cache_entries"]
        assert sizes["megatick"] == 1
        assert sizes["megatick_mesh"] == 1
        assert sizes["tick"] == 2              # one per live batch shape


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_cli_check_exits_zero_on_clean_repo(tmp_path, capsys):
    from repro.analysis.__main__ import main

    out = tmp_path / "report.json"
    rc = main(["--check", "--passes", "hotpath_lint,locks",
               "--json", str(out)])
    assert rc == 0
    text = capsys.readouterr().out
    assert "hotpath_lint" in text and "locks" in text
    import json
    payload = json.loads(out.read_text())
    assert payload["violations"] == 0
    assert payload["benchmark"] == "analysis"


def test_cli_check_exits_nonzero_on_violation(tmp_path, capsys):
    from repro.analysis.__main__ import main

    # an allowlist whose only entry is uncommented is itself a violation
    bad = tmp_path / "allow.txt"
    bad.write_text("ANL-TIME:nowhere.py::module\n")
    rc = main(["--check", "--passes", "locks",
               "--allowlist", str(bad)])
    assert rc == 1
    assert "no justification" in capsys.readouterr().out
