"""Jaxpr/HLO audit of every registered jitted entry point.

All checks run on abstract (shape-only) traces at smoke scale — no
weights are allocated except by the recompilation guard, which compiles
and runs the real (tiny) executables.

Rules:

  * ``ANL-JAXPR-CALLBACK`` — a callback/infeed primitive
    (``registry.FORBIDDEN_PRIMITIVES``) inside a jitted entry point:
    a host round-trip compiled into the hot loop.
  * ``ANL-JAXPR-DONATE`` — an entry point that declares donated buffers
    (canvas/KV) whose lowering carries fewer input/output aliases than
    declared: donation silently dropped means a second canvas allocation
    per megastep.
  * ``ANL-JAXPR-TRANSFER`` — per-call host<->device operand counts above
    the declared budget: a new per-tick upload or fetched output snuck
    into the signature.
  * ``ANL-JAXPR-COLLECTIVE`` — a collective primitive referencing an
    axis outside the entry point's declared mesh axes.
  * ``ANL-RECOMPILE`` — replaying a representative engine shape trace
    (mixed ``k_req`` depths, both stop-flag values, fresh rng, single
    and meshed megaticks, two live batch shapes) compiles more distinct
    executables than ``registry.RECOMPILE_BOUNDS`` allows: some operand
    became a static cache key.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Set, Tuple

from repro.analysis import registry
from repro.analysis.report import Allowlist, PassResult, Violation


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------

def iter_eqns(jaxpr) -> Iterable:
    """Every eqn in ``jaxpr`` and all nested sub-jaxprs (pjit bodies,
    while/cond/scan branches, shard_map bodies, custom_* calls)."""
    from jax._src.core import Jaxpr as _Jaxpr

    def subjaxprs(params: dict):
        for v in params.values():
            stack = [v]
            while stack:
                item = stack.pop()
                if isinstance(item, (list, tuple)):
                    stack.extend(item)
                elif hasattr(item, "jaxpr") and hasattr(item, "consts"):
                    yield item.jaxpr          # ClosedJaxpr
                elif isinstance(item, _Jaxpr):
                    yield item

    seen: Set[int] = set()
    stack = [jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr]
    while stack:
        j = stack.pop()
        if id(j) in seen:
            continue
        seen.add(id(j))
        for eqn in j.eqns:
            yield eqn
            stack.extend(subjaxprs(eqn.params))


def primitive_census(jaxpr) -> Dict[str, int]:
    census: Dict[str, int] = {}
    for eqn in iter_eqns(jaxpr):
        name = eqn.primitive.name
        census[name] = census.get(name, 0) + 1
    return census


_PRIMITIVE_ALIASES = {"psum_invariant": "psum"}


def collective_axes(jaxpr) -> Dict[str, Set[str]]:
    """primitive name -> set of *named* axes it reduces/permutes over.
    ``psum`` of a value that is invariant over its axes lowers to
    ``psum_invariant`` inside a checked shard_map; it is reported as
    ``psum``."""
    out: Dict[str, Set[str]] = {}
    for eqn in iter_eqns(jaxpr):
        name = _PRIMITIVE_ALIASES.get(eqn.primitive.name, eqn.primitive.name)
        if name not in registry.COLLECTIVE_PRIMITIVES:
            continue
        axes: Set[str] = set()
        for key in ("axes", "axis_name", "axis_index_groups_axes"):
            val = eqn.params.get(key)
            if val is None:
                continue
            vals = val if isinstance(val, (tuple, list, frozenset, set)) \
                else (val,)
            axes.update(str(a) for a in vals if isinstance(a, str))
        out.setdefault(name, set()).update(axes)
    return out


# ---------------------------------------------------------------------------
# per-entry-point checks
# ---------------------------------------------------------------------------

def audit_entry(ep: registry.EntryPoint) -> Tuple[List[Violation], dict]:
    import jax

    violations: List[Violation] = []
    jaxpr = jax.make_jaxpr(ep.fn)(*ep.args)
    census = primitive_census(jaxpr)
    info: dict = {"primitives": len(census)}

    forbidden = {p: n for p, n in census.items()
                 if p in registry.FORBIDDEN_PRIMITIVES
                 or "callback" in p}
    if forbidden:
        violations.append(Violation(
            "ANL-JAXPR-CALLBACK", ep.name,
            f"host-callback primitives compiled into the entry point: "
            f"{forbidden}"))

    if ep.kernel_only:
        return violations, info

    colls = collective_axes(jaxpr)
    info["collectives"] = {p: sorted(a) for p, a in colls.items()}
    declared = set(ep.mesh_axes)
    for prim, axes in colls.items():
        stray = axes - declared
        if stray:
            violations.append(Violation(
                "ANL-JAXPR-COLLECTIVE", ep.name,
                f"{prim} over undeclared axes {sorted(stray)} "
                f"(declared: {sorted(declared) or 'none'})"))

    leaves = jax.tree_util.tree_leaves
    h2d = sum(len(leaves(a)) for i, a in enumerate(ep.args)
              if i not in ep.resident_argnums)
    d2h = len(jaxpr.out_avals)
    info["h2d_leaves"], info["d2h_leaves"] = h2d, d2h
    info["budget"] = {"max_h2d": ep.max_h2d, "max_d2h": ep.max_d2h}
    if h2d > ep.max_h2d:
        violations.append(Violation(
            "ANL-JAXPR-TRANSFER", ep.name,
            f"{h2d} host-supplied operand leaves per call exceeds the "
            f"declared budget {ep.max_h2d} — a new per-tick upload"))
    if d2h > ep.max_d2h:
        violations.append(Violation(
            "ANL-JAXPR-TRANSFER", ep.name,
            f"{d2h} output leaves per call exceeds the declared budget "
            f"{ep.max_d2h} — a new per-tick fetchable output"))

    if ep.jitted is not None and ep.min_aliased > 0:
        txt = ep.jitted.lower(*ep.args).as_text()
        aliased = txt.count("tf.aliasing_output")
        info["aliased_buffers"] = aliased
        if aliased < ep.min_aliased:
            violations.append(Violation(
                "ANL-JAXPR-DONATE", ep.name,
                f"lowering aliases {aliased} buffer(s), declared minimum "
                f"{ep.min_aliased} — donation (donate_argnums) was "
                f"dropped, the canvas/KV copy is back"))
    return violations, info


# ---------------------------------------------------------------------------
# recompilation guard
# ---------------------------------------------------------------------------

def check_recompilation() -> Tuple[List[Violation], dict]:
    """Replay the engine's per-megastep call shapes against *fresh*
    jitted executables (``__wrapped__`` bypasses the lru_cache so prior
    in-process callers cannot skew the count) and bound the jit-cache
    entries per ``registry.RECOMPILE_BOUNDS``.  Mixed depths, stop flags,
    and rng are device operands — none of them may key a recompile."""
    import jax
    import jax.numpy as jnp

    from repro.configs import base
    from repro.core import diffusion
    from repro.launch.mesh import make_debug_mesh

    from repro.models.registry import build_model

    cfg = base.get_config("llada-8b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    dcfg = diffusion.DiffusionConfig(gen_length=16, block_length=8,
                                     steps_per_block=4, cache_mode="none",
                                     head_path="fused")
    mask_id = cfg.mask_id
    B, s_tot, k_max = 2, 24, 4

    def mega_args(b, seed):
        x = jnp.full((b, s_tot), mask_id, jnp.int32)
        kv = jnp.ones((b, s_tot), bool)
        state = diffusion.megatick_state(
            jnp.full((b,), 8, jnp.int32), jnp.full((b,), 2, jnp.int32),
            dcfg)
        return x, kv, state, jax.random.PRNGKey(seed)

    sizes: Dict[str, int] = {}
    violations: List[Violation] = []

    fns = {
        "megatick": diffusion.get_megatick_fn.__wrapped__(
            model, dcfg, mask_id, k_max, jit_steps=True),
        "megatick_mesh": diffusion.get_megatick_fn.__wrapped__(
            model, dcfg, mask_id, k_max, mesh=make_debug_mesh(1, 1),
            jit_steps=True),
    }
    for name, fn in fns.items():
        if not hasattr(fn, "_cache_size"):
            sizes[name] = -1            # introspection unavailable
            continue
        for seed, (k_req, stop) in enumerate(
                [(1, False), (4, False), (2, True), (4, False)]):
            x, kv, state, rng = mega_args(B, seed)
            out = fn(params, x, kv, state, rng, jnp.int32(k_req),
                     jnp.asarray(stop), None)
            jax.block_until_ready(out[0])
        sizes[name] = fn._cache_size()

    tick = diffusion.get_tick_fn.__wrapped__(model, dcfg, mask_id,
                                             jit_steps=True)
    if hasattr(tick, "_cache_size"):
        for b in (B, 2 * B):            # two live engine batch shapes
            x, kv, _, rng = mega_args(b, 7)
            bs = jnp.full((b,), 8, jnp.int32)
            k = jnp.ones((b,), jnp.int32)
            out = tick(params, x, kv, bs, k, rng, None)
            jax.block_until_ready(out[0])
        sizes["tick"] = tick._cache_size()
    else:
        sizes["tick"] = -1

    for name, bound in registry.RECOMPILE_BOUNDS.items():
        size = sizes.get(name)
        if size is not None and size > bound:
            violations.append(Violation(
                "ANL-RECOMPILE", name,
                f"{size} distinct executables compiled over the replayed "
                f"engine trace (bound {bound}) — an operand became a "
                f"static cache key"))
    info = {"cache_entries": sizes,
            "bounds": dict(registry.RECOMPILE_BOUNDS)}
    return violations, info


def run(allow: Allowlist, recompile: bool = True) -> PassResult:
    violations: List[Violation] = []
    info: dict = {"entry_points": {}}
    eps = registry.entry_points()
    for ep in eps:
        vs, ep_info = audit_entry(ep)
        violations.extend(vs)
        info["entry_points"][ep.name] = ep_info
    if recompile:
        vs, rc = check_recompilation()
        violations.extend(vs)
        info["recompilation"] = rc
    kept, suppressed = allow.filter(violations)
    return PassResult("jaxpr_audit", kept, suppressed, info=info,
                      checked=len(eps))
