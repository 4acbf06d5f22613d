"""Continuous-batching serving engine over the diffusion state machine.

Every engine tick advances *all* active requests by one denoising step with
a single fused forward + Stable-Max sampling call (core/diffusion
``batched_tick``), regardless of each request's block index or step within
the block.  Requests are packed into fixed padded batch slots backed by a
preallocated KV slot pool; a slot frees (and a queued request admits) the
moment its request's last block unmasks, so the batch stays full under
mixed prompt/generation lengths instead of serializing per request.

For head-mode-capable models the tick slices each row's active block at the
*hidden* level (B, block, d) and feeds the fused LM-head + Stable-Max path
(dcfg.head_path, docs/fused_sampling.md): vocab-wide logits never reach
HBM — the pre-PR behavior of materializing (B, S, V) logits every tick is
kept only as the explicit ``head_path='legacy'`` escape hatch.

Tick modes:
  * ``none``: cache-free full recompute per tick (Block Diffusion).  A
    one-slot engine in this mode runs the exact jitted computation
    ``generate(cache_mode='none')`` runs -> bit-identical greedy tokens.
  * ``warm``: every tick is a warm step through the pooled KV cache — all
    KV recomputed and rewritten via the BAOS smoothing/quantization path,
    so serving exercises the paper's quantized-cache attention each step.

With ``mesh=`` (a ``(data, model)`` mesh) every tick runs shard_mapped SPMD
(docs/sharded_serving.md): batch slots shard over the data axis, the LM-head
columns over the model axis — each chip streams only its (d, V/n) head shard
and the per-chip Stable-Max partials merge with one pmax/psum/pmin.  The
head param is resharded (and MX-block-pad-aligned) once at construction.
Call :meth:`warmup` before timed runs so jit compilation never pollutes the
virtual clock.

Online serving (docs/streaming_serving.md) layers on two hooks here:
``submit(request, on_commit=cb)`` registers a per-request commit callback —
every tick the engine diffs the request's row against its host-tracked mask
state and hands the callback a :class:`CommitEvent` with the positions and
tokens that committed on that tick (dLLM tokens commit *out of order*
within a block, so this is the streaming-native unit, not a suffix append).
The diff reuses the one post-tick host copy of ``x`` that request release
already needs, so streaming adds no extra device syncs.  ``cancel(uid)``
removes a still-queued request (the frontend's shed path).
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import diffusion, schedule as schedule_lib
from repro.obs.tracing import Spans
from repro.serving.cache_pool import CachePool, PagedCachePool, SpilledSlot
from repro.serving.metrics import MetricsTracker
from repro.serving.scheduler import (FIFOPolicy, Policy, SlowFastPolicy,
                                     get_policy)


@dataclasses.dataclass(eq=False)
class Request:
    """One single-sequence generation request.

    Identity equality (``eq=False``): requests hold ndarray prompts, so a
    generated value ``__eq__`` is ambiguous, and queue membership/removal
    is about *this* request, not value-equal twins.

    ``uid`` may be left None — :meth:`ServingEngine.submit` assigns the
    next free uid and returns it (explicit positive uids are still
    accepted, with the duplicate/non-positive validation).  ``policy``
    optionally names a per-request step policy (scheduler.get_policy,
    e.g. ``"slowfast"`` with ``policy_params={"threshold": 0.95}``),
    overriding the engine-global policy's ``step_k`` for this request.
    """
    prompt: np.ndarray            # (P,) int32
    gen_length: int
    uid: Optional[int] = None
    arrival_time: float = 0.0
    policy: Optional[str] = None
    policy_params: Optional[dict] = None
    # SLO tier (repro.obs.slo): deadlines are measured from
    # ``arrival_time`` — the *first* submit; preempt/restore never
    # re-stamps it, so a spilled request's deadlines keep ticking
    slo_class: str = "standard"
    # W3C trace id (32 hex chars) linking this request across the event
    # log, Perfetto spans, SSE stream, and /metrics exemplars; "" = none
    trace_id: str = ""

    @property
    def prompt_len(self) -> int:
        return int(np.asarray(self.prompt).shape[-1])

    @property
    def total_len(self) -> int:
        return self.prompt_len + self.gen_length


@dataclasses.dataclass
class CompletedRequest:
    uid: int
    tokens: np.ndarray            # (P + gen,) int32
    prompt_len: int
    gen_length: int
    arrival_time: float
    admitted_time: float
    completed_time: float
    ticks: int

    @property
    def latency(self) -> float:
        return self.completed_time - self.arrival_time


@dataclasses.dataclass
class CommitEvent:
    """Per-tick commit delta for one request (streaming unit).

    ``positions`` are absolute indices into the request's row (prompt at
    [0, prompt_len)); within a block they are generally *not* contiguous or
    left-to-right — dLLM commits are confidence-ordered.  ``done`` events
    additionally carry the full final row in ``final_tokens``.
    """
    uid: int
    tick: int                     # engine tick counter (monotone)
    now: float                    # engine virtual clock at commit
    block_idx: int
    step_in_block: int
    positions: np.ndarray         # (k,) int — committed this tick
    tokens: np.ndarray            # (k,) int32
    masks_left: int               # masks left in the active block after tick
    done: bool = False
    final_tokens: Optional[np.ndarray] = None   # (P + gen,) when done
    # perf_counter at creation on the engine thread: the frontend's
    # dllm_delivery_seconds measures from here to the SSE write
    created: float = dataclasses.field(default_factory=time.perf_counter)


@dataclasses.dataclass
class _Slot:
    """Host-side per-slot resume state (the scalar half of DiffusionState;
    the array half lives batched in the engine's canvas/pool rows)."""
    request: Request
    admitted_time: float
    block_idx: int = 0
    step_in_block: int = 0
    ticks: int = 0
    last_conf: float = float("-inf")
    block_masks_left: int = 0
    first_commit: bool = False
    first_commit_t: Optional[float] = None   # virtual clock at first commit
    # host mirror of still-masked positions, kept only for requests with a
    # commit callback (the per-tick streaming diff)
    masked: Optional[np.ndarray] = None
    # resolved per-request step policy (None -> engine-global policy)
    policy: Optional[Policy] = None


@dataclasses.dataclass
class EngineConfig:
    """Typed engine construction config (docs/serving.md).

    Collapses the historical ``ServingEngine(**12 kwargs)`` sprawl; the
    engine also still accepts those kwargs directly through a deprecation
    shim that builds an EngineConfig from them.  ``pool`` selects the
    storage backend: ``"slot"`` (one fixed region per batch slot) or
    ``"paged"`` (block pool + radix prefix cache, docs/paged_cache.md);
    ``page_size``/``num_pages``/``prefix_cache`` only apply to paged.
    """
    num_slots: int = 4
    max_seq_len: int = 128
    mode: str = "warm"
    policy: Optional[Policy] = None
    rng: Optional[jax.Array] = None
    jit_steps: bool = True
    breakdown: bool = False
    fwd_kw: Optional[dict] = None
    mesh: Any = None
    obs: Any = None
    megatick_k: int = 1
    pool: str = "slot"
    page_size: int = 16
    num_pages: Optional[int] = None
    prefix_cache: bool = True


class ServingEngine:
    """Continuous-batching engine: submit() requests, tick() until drained."""

    def __init__(self, model, params, dcfg: diffusion.DiffusionConfig,
                 config: Optional[EngineConfig] = None, **kwargs):
        if config is not None and kwargs:
            raise TypeError(
                "pass either an EngineConfig or individual kwargs, not both "
                f"(got config= and {sorted(kwargs)})")
        if config is None:
            if kwargs:
                warnings.warn(
                    "constructing ServingEngine from individual kwargs is "
                    "deprecated; pass an EngineConfig",
                    DeprecationWarning, stacklevel=2)
            config = EngineConfig(**kwargs)
        self.config = config
        num_slots, max_seq_len = config.num_slots, config.max_seq_len
        mode, policy, rng = config.mode, config.policy, config.rng
        jit_steps, breakdown = config.jit_steps, config.breakdown
        fwd_kw, mesh, obs = config.fwd_kw, config.mesh, config.obs
        megatick_k = config.megatick_k
        if mode not in ("warm", "none"):
            raise ValueError(f"unknown engine mode {mode!r}")
        if config.pool not in ("slot", "paged"):
            raise ValueError(f"unknown pool backend {config.pool!r}; "
                             "choose 'slot' or 'paged'")
        self.paged = config.pool == "paged"
        self.model = model
        self.params = params
        self.dcfg = dcfg
        self.mode = mode
        self.num_slots = num_slots
        self.max_seq_len = max_seq_len
        self.mask_id = int(model.cfg.mask_id)
        self.policy = policy or FIFOPolicy()
        self.breakdown = breakdown
        # optional repro.obs.ServingObs: per-stage tick histograms, spans,
        # request-lifecycle counters, drift gauges (docs/observability.md).
        # Every hook receives data the tick already computed, so obs=None
        # keeps the hot path identical and obs!=None adds only host-side
        # bookkeeping (bounded <2% by benchmarks/obs_overhead.py).
        self.obs = obs
        # structured event-log hook (repro.obs.events): one record per
        # request lifecycle edge.  ServingObs.event no-ops (one None
        # check) when no EventLog is wired, so the cached bound method
        # costs nothing on the hot path without events.
        self._event = obs.event if obs is not None \
            and hasattr(obs, "event") else None
        # dllm.* host spans of this engine's thread (tick stages and the
        # frontend worker loop): profiler annotations, the stage seconds
        # obs histograms, and --trace-out events (docs/observability.md)
        self.spans = Spans(getattr(obs, "trace", None), cat="engine")
        self._early_exits_seen = 0
        self.fwd_kw = dict(fwd_kw or {})
        # QuantPolicy is not a jax type: bind it statically into the jitted
        # tick fns rather than passing it as a runtime kwarg
        self._quant = self.fwd_kw.pop("quant", None)
        if self.paged:
            if breakdown:
                raise ValueError(
                    "the paged pool is incompatible with breakdown timing "
                    "(the paged tick is one fused gather/tick/scatter "
                    "executable)")
            if self.fwd_kw:
                raise ValueError(
                    "paged serving does not support extra forward kwargs")
        self.rng = rng if rng is not None else jax.random.PRNGKey(0)
        self.mesh = mesh
        if mesh is not None:
            if breakdown:
                raise ValueError(
                    "breakdown timing is not supported under a mesh (the "
                    "SPMD tick is one fused shard_map executable)")
            if self.fwd_kw:
                raise ValueError(
                    "mesh serving does not support extra forward kwargs")
            # validates mesh axes and model/dcfg (fused head path, greedy)
            # before any params["lm_head"] access; lru-cached, so the
            # re-fetch below is free
            diffusion.get_spmd_tick_fn(model, dcfg, self.mask_id, mesh,
                                       jit_steps=jit_steps,
                                       quant=self._quant)
            if num_slots % mesh.shape["data"]:
                raise ValueError(
                    f"num_slots {num_slots} must be divisible by the data "
                    f"axis size {mesh.shape['data']}")
            # one-time resharding: LM-head columns over 'model' (zero-padded
            # to MX-aligned shard boundaries), everything else replicated —
            # ticks then never move params again
            from jax.sharding import NamedSharding, PartitionSpec as P
            self.params = diffusion.place_spmd_params(params, mesh)
            self._row_sharding = NamedSharding(mesh, P("data", None))
        else:
            self._row_sharding = None

        if self.paged:
            self.pool = PagedCachePool(
                model, num_slots, max_seq_len,
                page_size=config.page_size, num_pages=config.num_pages,
                with_cache=(mode == "warm"), mask_id=self.mask_id,
                prefix_cache=config.prefix_cache)
        else:
            self.pool = CachePool(model, num_slots, max_seq_len,
                                  with_cache=(mode == "warm"))
            if mesh is not None and self.pool.cache is not None:
                self.pool.cache = jax.device_put(
                    self.pool.cache, NamedSharding(mesh, P(None, "data")))
        if self.paged and self._event is not None:
            # pool-internal edges (spill/restore/prefix_hit/evict) flow
            # through the same event hook, uid-less (the pool tracks
            # slots and pages, not request identities)
            self.pool.event_cb = self._event
        self.slots: List[Optional[_Slot]] = [None] * num_slots
        self.slot_of_uid: Dict[int, int] = {}
        self.queue: List[Request] = []
        self._preempted: Dict[int, Tuple[_Slot, SpilledSlot]] = {}
        self._req_policy: Dict[int, Policy] = {}
        self._next_uid = 1                  # next auto-assigned request uid
        self._early_exits_released = 0      # from released per-request policies
        self.completed: List[CompletedRequest] = []
        self.metrics = MetricsTracker(num_slots)
        self.now = 0.0                      # virtual clock (seconds)
        self.ticks_total = 0
        self._commit_cbs: Dict[int, Callable[[CommitEvent], None]] = {}

        L, T = dcfg.block_length, dcfg.steps_per_block
        self._ksched = np.asarray(
            schedule_lib.linear_unmask_schedule(L, T))        # (T,)
        self.x = self._put_rows(
            jnp.full((num_slots, max_seq_len), self.mask_id, jnp.int32))
        pos = np.arange(max_seq_len)
        # idle rows keep one valid key so their (discarded) attention rows
        # never produce an all-masked softmax
        self._valid_np = np.tile(pos < 1, (num_slots, 1))
        self.kv_valid = self._put_rows(jnp.asarray(self._valid_np))
        self._kv_dirty = False
        self.kv_valid_uploads = 0           # host->device refreshes (1/tick)
        # mask-mirror-diff fetches (and, with megatick, per-tick device
        # syncs) skipped because no streaming sink needed them — exported
        # as dllm_host_syncs_elided_total (docs/megatick.md)
        self.host_syncs_elided = 0

        # --- device-resident megatick (docs/megatick.md): fuse K ticks
        # into one jitted while_loop dispatch; host state replays from the
        # drained on-device commit buffers at megastep boundaries
        self.megatick_k = int(megatick_k)
        if self.megatick_k < 1:
            raise ValueError(f"megatick_k must be >= 1, got {megatick_k}")
        self._megatick_fn = None
        self._sf_threshold: Optional[float] = None
        if self.megatick_k > 1:
            if breakdown:
                raise ValueError(
                    "megatick_k > 1 is incompatible with breakdown timing "
                    "(the megastep is one fused while_loop executable)")
            if self.fwd_kw:
                raise ValueError(
                    "megatick serving does not support extra forward "
                    "kwargs")
            if isinstance(self.policy, SlowFastPolicy):
                # step_k moves on device: the loop applies the confidence
                # early-exit per tick without a host round-trip
                self._sf_threshold = float(self.policy.threshold)
            elif type(self.policy).step_k is not Policy.step_k:
                raise ValueError(
                    f"policy {self.policy.name!r} overrides step_k; only "
                    "the default schedule and SlowFastPolicy run on "
                    "device inside a megatick")
            if self.paged:
                self._megatick_fn = diffusion.get_paged_megatick_fn(
                    model, dcfg, self.mask_id, self.megatick_k,
                    config.page_size, max_seq_len,
                    with_cache=(mode == "warm"), mesh=mesh,
                    jit_steps=jit_steps, quant=self._quant,
                    slowfast_threshold=self._sf_threshold)
            else:
                self._megatick_fn = diffusion.get_megatick_fn(
                    model, dcfg, self.mask_id, self.megatick_k, mesh=mesh,
                    jit_steps=jit_steps, quant=self._quant,
                    slowfast_threshold=self._sf_threshold)

        if self.paged:
            self._tick_fn = diffusion.get_paged_tick_fn(
                model, dcfg, self.mask_id, config.page_size, max_seq_len,
                with_cache=(mode == "warm"), mesh=mesh, jit_steps=jit_steps,
                quant=self._quant)
        elif mesh is not None:
            self._tick_fn = diffusion.get_spmd_tick_fn(
                model, dcfg, self.mask_id, mesh, jit_steps=jit_steps,
                quant=self._quant)
        elif breakdown:
            self._fwd_fn, self._smp_fn = diffusion.get_tick_stage_fns(
                model, dcfg, self.mask_id, jit_steps, quant=self._quant)
            self._tick_fn = None
        else:
            self._tick_fn = diffusion.get_tick_fn(
                model, dcfg, self.mask_id, jit_steps, quant=self._quant)

    def _put_rows(self, a: jax.Array) -> jax.Array:
        """Pin a (num_slots, ...) array to the data-axis sharding (no-op
        without a mesh)."""
        return a if self._row_sharding is None \
            else jax.device_put(a, self._row_sharding)

    # -- request lifecycle --------------------------------------------------

    def submit(self, request: Request,
               on_commit: Optional[Callable[[CommitEvent], None]] = None
               ) -> int:
        """Queue a request and return its uid; ``on_commit`` (if given)
        receives a CommitEvent after every tick that touches it, including
        the final done event.  A request with ``uid=None`` gets the next
        unused uid assigned (and written back onto the request)."""
        uid = request.uid
        if uid is None:
            uid = self._next_uid
            while uid in self.metrics.seen_uids:
                uid += 1
            request.uid = uid
        elif not isinstance(uid, (int, np.integer)) or uid <= 0:
            raise ValueError(f"request uid must be a positive int, "
                             f"got {uid!r}")
        elif uid in self.metrics.seen_uids:
            # a duplicate would silently overwrite the slot_of_uid and
            # metrics entries of the live/finished request with this uid
            # (seen_uids survives metrics compaction: uids never recycle)
            raise ValueError(f"duplicate request uid {uid}")
        uid = int(uid)
        self._next_uid = max(self._next_uid, uid + 1)
        pol: Optional[Policy] = None
        if request.policy is not None:
            # resolve (and validate) the per-request step policy now, so a
            # bad name/params fails at submit time, not mid-tick
            pol = get_policy(request.policy, **(request.policy_params or {}))
            if self.megatick_k > 1 and not self._policy_matches(pol):
                raise ValueError(
                    f"per-request policy {request.policy!r} must match the "
                    f"engine policy {self.policy.name!r} under megatick "
                    "(step_k runs on device inside the fused loop)")
        L = self.dcfg.block_length
        if request.gen_length <= 0 or request.gen_length % L:
            raise ValueError(
                f"gen_length {request.gen_length} must be a positive "
                f"multiple of block_length {L}")
        if request.total_len > self.max_seq_len:
            raise ValueError(
                f"request length {request.total_len} exceeds engine "
                f"max_seq_len {self.max_seq_len}")
        self.queue.append(request)
        if pol is not None:
            self._req_policy[uid] = pol
        if on_commit is not None:
            self._commit_cbs[uid] = on_commit
        self.metrics.request_arrived(request.uid, request.arrival_time,
                                     request.gen_length)
        if self.obs is not None:
            self.obs.request_queued(uid, trace=request.trace_id,
                                    cls=request.slo_class)
        if self._event is not None:
            self._event("submit", uid=uid, trace=request.trace_id,
                        cls=request.slo_class, t=request.arrival_time,
                        prompt_len=request.prompt_len,
                        gen_length=request.gen_length)
        return uid

    def _policy_matches(self, pol: Policy) -> bool:
        """Whether a per-request policy resolves to the same on-device
        step behavior as the engine policy (the megatick constraint)."""
        if type(pol) is not type(self.policy):
            return False
        if isinstance(pol, SlowFastPolicy):
            return pol.threshold == self.policy.threshold
        return True

    def cancel(self, uid: int, reason: str = "shed") -> bool:
        """Remove a still-*queued* request (the frontend's max_queue_wait
        shed path).  Returns False when the uid is unknown or already
        admitted to a slot — admitted work is never interrupted.
        ``reason="deadline"`` marks a queue-deadline expiry: the shed
        counts as an SLO violation for the request's class."""
        for i, r in enumerate(self.queue):
            if r.uid == uid:
                del self.queue[i]
                self._commit_cbs.pop(uid, None)
                self._req_policy.pop(uid, None)
                self.metrics.request_shed(uid, self.now)
                if self.obs is not None:
                    self.obs.request_shed(uid, cls=r.slo_class,
                                          trace=r.trace_id,
                                          deadline=(reason == "deadline"))
                if self._event is not None:
                    self._event(
                        "shed", uid=uid, trace=r.trace_id,
                        cls=r.slo_class, t=self.now, reason=reason,
                        queue_wait_s=round(
                            max(0.0, self.now - r.arrival_time), 6))
                return True
        return False

    def _admit(self) -> None:
        if self.paged:
            self._restore_preempted()
        while self.pool.free_slots:
            arrived = [r for r in self.queue if r.arrival_time <= self.now]
            if not arrived:
                break
            pick = arrived[self.policy.select(arrived, self.now)]
            if self.paged and not self.pool.can_admit(
                    np.asarray(pick.prompt, np.int32), pick.total_len):
                # footprint-blocked: the slot exists but the projected
                # pages don't fit.  Ask the policy for a victim to spill;
                # with no preemption hook the request waits in queue
                victim = self.policy.preempt(self.slots, pick, self.now)
                if victim is None or self.slots[victim] is None:
                    break
                if self._event is not None:
                    self._event("policy_decision", uid=pick.uid,
                                trace=pick.trace_id, cls=pick.slo_class,
                                t=self.now, kind="preempt_victim",
                                victim=int(self.slots[victim].request.uid),
                                policy=self.policy.name)
                self.preempt(self.slots[victim].request.uid)
                if not self.pool.can_admit(
                        np.asarray(pick.prompt, np.int32), pick.total_len):
                    break
            self.queue.remove(pick)
            slot = self.pool.acquire()
            self.slots[slot] = _Slot(
                request=pick, admitted_time=self.now,
                block_masks_left=self.dcfg.block_length,
                policy=self._req_policy.pop(pick.uid, None))
            if pick.uid in self._commit_cbs:
                m = np.zeros((pick.total_len,), bool)
                m[pick.prompt_len:] = True
                self.slots[slot].masked = m
            self.slot_of_uid[pick.uid] = slot
            row = np.full((self.max_seq_len,), self.mask_id, np.int32)
            row[:pick.prompt_len] = np.asarray(pick.prompt, np.int32)
            if self.paged:
                # prompt pages dedup through the radix cache; uploads are
                # staged and flushed once per tick (PagedCachePool.flush)
                self.pool.bind_row(slot, row, pick.prompt_len,
                                   pick.total_len)
            else:
                # pinned to the tick's P('data', None) spec: a drifting
                # output sharding would retrigger a jit compile on the
                # first timed tick after warmup()
                self.x = self.x.at[slot].set(
                    jnp.asarray(row), out_sharding=self._row_sharding)
            self._valid_np[slot] = np.arange(self.max_seq_len) < pick.total_len
            self._kv_dirty = True      # uploaded once per tick, not per admit
            self.metrics.request_admitted(pick.uid, self.now)
            pol = self.slots[slot].policy or self.policy
            if self.obs is not None:
                self.obs.request_admitted(
                    pick.uid, max(0.0, self.now - pick.arrival_time))
                self.obs.request_policy(pol.name)
            if self._event is not None:
                self._event(
                    "admit", uid=pick.uid, trace=pick.trace_id,
                    cls=pick.slo_class, t=self.now, slot=slot,
                    queue_wait_s=round(
                        max(0.0, self.now - pick.arrival_time), 6))
                self._event("policy_decision", uid=pick.uid,
                            trace=pick.trace_id, cls=pick.slo_class,
                            t=self.now, kind="admit", policy=pol.name)

    # -- preemption (paged pool only) ---------------------------------------

    def preempt(self, uid: int) -> bool:
        """Spill an admitted request to host memory and free its slot +
        pages; it transparently re-admits (bit-identical state) once pages
        free up.  Returns False for unknown/unadmitted uids."""
        if not self.paged:
            raise RuntimeError("preempt() requires the paged pool "
                               "(EngineConfig(pool='paged'))")
        slot = self.slot_of_uid.get(uid)
        if slot is None:
            return False
        s = self.slots[slot]
        sp = self.pool.spill(slot)
        sp.prompt_len = s.request.prompt_len
        self._preempted[uid] = (s, sp)
        self.slots[slot] = None
        del self.slot_of_uid[uid]
        self._valid_np[slot] = np.arange(self.max_seq_len) < 1
        self._kv_dirty = True
        if self.obs is not None:
            self.obs.request_preempted(uid)
        if self._event is not None:
            self._event("preempt", uid=uid, trace=s.request.trace_id,
                        cls=s.request.slo_class, t=self.now, slot=slot,
                        total_len=sp.total_len)
        return True

    def _restore_preempted(self) -> None:
        """Re-admit spilled requests (oldest first) while slots and pages
        allow — they resume exactly where they left off, so they outrank
        the queue."""
        for uid in list(self._preempted):
            if not self.pool.free_slots:
                break
            s, sp = self._preempted[uid]
            if not self.pool.can_restore(sp):
                break
            slot = self.pool.acquire()
            self.pool.restore(slot, sp)
            self.slots[slot] = s
            self.slot_of_uid[uid] = slot
            self._valid_np[slot] = np.arange(self.max_seq_len) < sp.total_len
            self._kv_dirty = True
            del self._preempted[uid]
            if self.obs is not None:
                self.obs.request_restored(uid)
            if self._event is not None:
                self._event("restore", uid=uid,
                            trace=s.request.trace_id,
                            cls=s.request.slo_class, t=self.now,
                            slot=slot, total_len=sp.total_len)

    def _release(self, slot: int, x_host: np.ndarray) -> None:
        s = self.slots[slot]
        req = s.request
        self.completed.append(CompletedRequest(
            uid=req.uid, tokens=x_host[:req.total_len].copy(),
            prompt_len=req.prompt_len, gen_length=req.gen_length,
            arrival_time=req.arrival_time, admitted_time=s.admitted_time,
            completed_time=self.now, ticks=s.ticks))
        self.metrics.request_completed(req.uid, self.now, s.ticks)
        if s.policy is not None:
            # fold the dying per-request policy's early-exit count into the
            # released accumulator so the obs total stays monotone
            self._early_exits_released += getattr(s.policy, "early_exits", 0)
        latency_s = max(0.0, self.now - req.arrival_time)
        ttft_s = (None if s.first_commit_t is None
                  else max(0.0, s.first_commit_t - req.arrival_time))
        kinds: Tuple[str, ...] = ()
        if self.obs is not None:
            # obs owns the SLO class table; it returns the deadline kinds
            # this request missed so the done event can carry them
            kinds = self.obs.request_done(
                req.uid, latency_s, s.ticks, ttft_s=ttft_s,
                cls=req.slo_class, trace=req.trace_id,
                tokens=req.gen_length) or ()
        if self._event is not None:
            self._event(
                "done", uid=req.uid, trace=req.trace_id,
                cls=req.slo_class, t=self.now,
                latency_s=round(latency_s, 6),
                ttft_s=None if ttft_s is None else round(ttft_s, 6),
                ticks=s.ticks, tokens=req.gen_length,
                violations=list(kinds))
        self.slots[slot] = None
        del self.slot_of_uid[req.uid]
        self._valid_np[slot] = np.arange(self.max_seq_len) < 1
        self._kv_dirty = True          # uploaded once per tick, not per free
        self.pool.release(slot)

    def _emit_commit(self, req: Request, cb, tick: int, block_idx: int,
                     step_in_block: int, positions, tokens,
                     masks_left: int, block_masks_before: int) -> None:
        """Event-log record for one tick's commit activity on a request.

        Streaming requests (``cb`` set) get one record per tick with the
        exact ``block_committed`` SSE payload fields — the event log and
        the SSE stream stay bit-for-bit consistent.  Non-streaming
        requests get one summary record per completed block (no
        positions: the mask-mirror diff never ran, by design — keeping
        the host-sync elision)."""
        if self._event is None:
            return
        if cb is not None:
            self._event("block_commit", uid=req.uid, trace=req.trace_id,
                        cls=req.slo_class, t=self.now, tick=tick,
                        block_idx=block_idx, step_in_block=step_in_block,
                        positions=positions, tokens=tokens,
                        masks_left=masks_left)
        elif masks_left == 0:
            self._event("block_commit", uid=req.uid, trace=req.trace_id,
                        cls=req.slo_class, t=self.now, tick=tick,
                        block_idx=block_idx, step_in_block=step_in_block,
                        committed=block_masks_before, masks_left=0)

    # -- stepping -----------------------------------------------------------

    @property
    def active_slots(self) -> int:
        return sum(s is not None for s in self.slots)

    @property
    def pending(self) -> int:
        return len(self.queue) + self.active_slots + len(self._preempted)

    def _early_exits_total(self) -> int:
        """Early exits across the engine policy, live per-request
        policies, and already-released per-request policies."""
        tot = getattr(self.policy, "early_exits", 0)
        tot += self._early_exits_released
        for s in self.slots:
            if s is not None and s.policy is not None:
                tot += getattr(s.policy, "early_exits", 0)
        return tot

    def _next_arrival(self) -> Optional[float]:
        return min((r.arrival_time for r in self.queue), default=None)

    def _flush_kv_valid(self) -> None:
        """One batched host->device refresh of the (num_slots, max_seq_len)
        validity mask after admission/release settles — admitting or
        releasing N requests in a tick costs one upload, not N."""
        if self._kv_dirty:
            self.kv_valid = self._put_rows(jnp.asarray(self._valid_np))
            self._kv_dirty = False
            self.kv_valid_uploads += 1
            if self.obs is not None:
                self.obs.kv_valid_upload()

    def warmup(self) -> "ServingEngine":
        """Compile the tick executable(s) with a dummy zero-commit tick,
        leaving the virtual clock, rng chain, metrics, canvas, and KV pool
        untouched — so the first *timed* tick charges no jit compile time
        to ``now`` (latency percentiles / tokens_per_s stay clean).

        Where the entry point armed the persistent compilation cache
        (repro.deploy, docs/megatick.md), compiles land there and later
        processes warm up from disk.  With ``megatick_k > 1`` both the K=1
        tick *and* the
        configured megatick shape pre-compile, and the megatick warmup
        runs on throwaway *copies* of the canvas/cache — its jitted
        executable donates those buffers, and warmup must leave engine
        state untouched."""
        self._flush_kv_valid()
        B = self.num_slots
        bs = jnp.zeros((B,), jnp.int32)
        k = jnp.zeros((B,), jnp.int32)           # commits nothing
        # the K=1 tick path splits the rng chain eagerly every tick: warm
        # that executable too, or the first timed tick pays its compile
        srng = jax.random.split(jax.random.PRNGKey(0))[1]
        cache = self.pool.cache if self.mode == "warm" else None
        if self.paged:
            # the paged K=1 tick is not donated, so warming it on the live
            # page stores is safe (outputs discarded; a k=0 tick scatters
            # back exactly what it gathered)
            self.pool.flush()
            out = self._tick_fn(self.params, self.pool.canvas_pages, cache,
                                self.pool.canvas_table, self.pool.kv_table,
                                self.kv_valid, bs, k, srng)
        elif self.breakdown:
            feats, _ = self._fwd_fn(self.params, self.x, self.kv_valid, bs,
                                    cache, **self.fwd_kw)
            out = self._smp_fn(self.params, feats, self.x, bs, k, srng)
        else:
            out = self._tick_fn(self.params, self.x, self.kv_valid, bs, k,
                                srng, cache, **self.fwd_kw)
        jax.block_until_ready(out)               # outputs discarded
        if self._megatick_fn is not None:
            zeros = np.zeros((B,), np.int32)
            state = diffusion.megatick_state(
                zeros, zeros, self.dcfg, active=np.zeros((B,), bool))
            if self.paged:
                # the paged megatick donates its page stores: run the
                # warmup compile on throwaway copies
                canvas_copy = jnp.copy(self.pool.canvas_pages)
                cache_copy = (None if cache is None
                              else jax.tree.map(jnp.copy, cache))
                out = self._megatick_fn(
                    self.params, canvas_copy, cache_copy,
                    self.pool.canvas_table, self.pool.kv_table,
                    self.kv_valid, state, jax.random.PRNGKey(0),
                    jnp.int32(1), jnp.asarray(False))
            else:
                x_copy = jnp.copy(self.x)        # donated + discarded
                cache_copy = (None if cache is None
                              else jax.tree.map(jnp.copy, cache))
                out = self._megatick_fn(self.params, x_copy, self.kv_valid,
                                        state, jax.random.PRNGKey(0),
                                        jnp.int32(1), jnp.asarray(False),
                                        cache_copy)
            jax.block_until_ready(out)
        return self

    def _admit_ready(self) -> bool:
        """Admit what has arrived (fast-forwarding the virtual clock to
        the next arrival when no slot is active) and upload the validity
        mask.  False when there is nothing left to run."""
        self._admit()
        if self.active_slots == 0:
            nxt = self._next_arrival()
            if nxt is None:
                return False
            self.now = max(self.now, nxt)     # fast-forward through idle gap
            self._admit()
        self._flush_kv_valid()
        return True

    def _advance(self, i: int, s: _Slot, cb, masks_left: int, conf: float,
                 positions, tokens, rows: Optional[np.ndarray]) -> None:
        """One tick of slot ``i``'s host state machine once the device has
        reported its ``masks_left`` and ``conf``: first commit, event-log
        record, block boundary (releasing the request with its row of
        ``rows`` when it is done) or next step, and the commit callback.
        ``positions``/``tokens`` are the streaming diff (None without a
        callback)."""
        obs = self.obs
        uid = s.request.uid
        L = self.dcfg.block_length
        if not s.first_commit and masks_left < L:
            s.first_commit = True
            s.first_commit_t = self.now
            self.metrics.request_first_commit(uid, self.now)
            if obs is not None:
                obs.request_first_commit(
                    uid, max(0.0, self.now - s.request.arrival_time))
        block_idx, step_in_block = s.block_idx, s.step_in_block
        # event-log commit record precedes any done record _release
        # emits this tick (lifecycle order: block_commit, then done)
        self._emit_commit(s.request, cb, self.ticks_total, block_idx,
                          step_in_block, positions, tokens, masks_left,
                          s.block_masks_left)
        done = False
        final: Optional[np.ndarray] = None
        if masks_left == 0:                   # block fully committed
            if obs is not None:
                obs.block_committed(
                    uid, block_idx, self.ticks_total,
                    len(positions) if positions is not None
                    else s.block_masks_left,
                    positions, tokens)
            s.block_idx += 1
            s.step_in_block = 0
            s.last_conf = float("-inf")
            s.block_masks_left = L
            if s.block_idx * L >= s.request.gen_length:
                done = True
                if cb is not None:
                    final = rows[i, :s.request.total_len].copy()
                self._release(i, rows[i])
        else:
            s.step_in_block += 1
            s.last_conf = conf
            s.block_masks_left = masks_left
        if cb is not None:
            cb(CommitEvent(
                uid=uid, tick=self.ticks_total, now=self.now,
                block_idx=block_idx, step_in_block=step_in_block,
                positions=positions, tokens=tokens,
                masks_left=masks_left, done=done, final_tokens=final))
            if done:
                del self._commit_cbs[uid]

    def _tick_obs(self, stages: Dict[str, float], committed: int,
                  ticks: List[Tuple[float, float, int]]) -> None:
        """Per-call obs: tokens, early exits, pool gauges, and one
        ``obs.tick`` per denoising tick ``(start_s, seconds, active)``
        with its share of the call's stage seconds."""
        obs = self.obs
        obs.tokens_committed(committed)
        ee = self._early_exits_total()
        if ee > self._early_exits_seen:
            obs.policy_early_exit(ee - self._early_exits_seen)
            if self._event is not None:
                self._event("early_exit", t=self.now,
                            n=ee - self._early_exits_seen)
            self._early_exits_seen = ee
        if self.paged:
            obs.pool_pages(self.pool)
        per_tick = {name: s_sec / len(ticks) for name, s_sec in stages.items()}
        queued = len(self.queue)
        for t0, dt, active in ticks:
            obs.tick(per_tick, dt, active, queued, t_start_us=t0 * 1e6)

    def tick(self, max_ticks: Optional[int] = None) -> bool:
        """Admit, run one fused batched step, advance slot states.

        Returns False when there is nothing to do (drained).  With
        ``megatick_k > 1`` a tick() call runs one *megastep* of up to
        megatick_k fused denoising ticks (fewer under queue pressure or
        early release); ``max_ticks`` caps the productive ticks this call
        may run — the ``--profile-ticks`` contract (profile exactly N
        ticks regardless of K).  Callers observing progress should diff
        ``ticks_total``, which counts denoising ticks in both modes."""
        if self.megatick_k > 1:
            return self._megastep(max_ticks)
        sp = self.spans
        L = self.dcfg.block_length
        T = self.dcfg.steps_per_block
        # tick stages (docs/observability.md), each a dllm.* span: host_prep
        # is admission and the k schedule; everything that talks to the
        # runtime — the eager rng split (an XLA computation of its own),
        # the bs/k host->device puts, and the tick call — is dispatch, and
        # device_sync is the wait on results.  That dispatch/device_sync
        # pair is the per-tick host tax the megatick amortizes over K
        # ticks (docs/megatick.md); with ``breakdown`` the dispatch window
        # instead splits into blocking forward / sampling stages.
        with sp.stage("dllm.host_prep") as prep:
            with sp.span("dllm.admit"):
                ready = self._admit_ready()
            bs_np = np.zeros((self.num_slots,), np.int32)
            k_np = np.zeros((self.num_slots,), np.int32)
            for i, s in enumerate(self.slots):
                if s is None:
                    continue
                bs_np[i] = s.request.prompt_len + s.block_idx * L
                t = s.step_in_block
                default_k = (int(self._ksched[t]) if t < T
                             else s.block_masks_left)
                pol = s.policy or self.policy
                k_np[i] = min(pol.step_k(s, default_k), L)
        if not ready:
            sp.take()                         # an idle call is no tick
            return False
        if self.paged:
            # staged canvas uploads + dirty tables: a stage of its own, so
            # the drift monitor can compare the measured paged overhead
            # against the analytical page_io term
            with sp.stage("dllm.paged_io"):
                self.pool.flush()
        cache = self.pool.cache if self.mode == "warm" else None
        with sp.stage("dllm.forward" if self.breakdown
                      else "dllm.dispatch") as first:
            bs_vec = jnp.asarray(bs_np)
            k_vec = jnp.asarray(k_np)
            self.rng, srng = jax.random.split(self.rng)
            if self.breakdown:
                feats, new_cache = self._fwd_fn(
                    self.params, self.x, self.kv_valid, bs_vec, cache,
                    **self.fwd_kw)
                jax.block_until_ready(feats)
            elif self.paged:
                # one fused gather -> tick -> scatter call; x_new is the
                # dense post-tick canvas view (the same array the slot tick
                # returns), so streaming diffs and release reads are
                # unchanged
                canvas, new_cache, x_new, conf_min, masks_left = \
                    self._tick_fn(self.params, self.pool.canvas_pages, cache,
                                  self.pool.canvas_table, self.pool.kv_table,
                                  self.kv_valid, bs_vec, k_vec, srng)
                self.pool.canvas_pages = canvas
            else:
                x_new, new_cache, conf_min, masks_left = self._tick_fn(
                    self.params, self.x, self.kv_valid, bs_vec, k_vec, srng,
                    cache, **self.fwd_kw)
        if self.breakdown:
            # feats = pre-head hidden states for head-capable models: the
            # sampling stage owns the LM head (the paper's Fig. 1 split
            # charges vocab traffic to sampling, not the model forward)
            with sp.stage("dllm.sampling"):
                x_new, conf_min, masks_left = self._smp_fn(
                    self.params, feats, self.x, bs_vec, k_vec, srng)
                jax.block_until_ready(x_new)
        with sp.stage("dllm.host_sync" if self.breakdown
                      else "dllm.device_sync") as synced:
            conf_np = np.asarray(conf_min)    # device sync point
            masks_np = np.asarray(masks_left)
        dt = synced.t1 - first.t0
        self.x = x_new
        if self.mode == "warm":
            self.pool.update(new_cache)

        n_active = self.active_slots
        self.now += dt
        self.ticks_total += 1
        self.metrics.record_tick(dt, n_active)
        with sp.stage("dllm.commit"):
            committed_total = 0
            x_host: Optional[np.ndarray] = None
            for i, s in enumerate(self.slots):
                if s is None:
                    continue
                s.ticks += 1
                cb = self._commit_cbs.get(s.request.uid)
                masks_left = int(masks_np[i])
                committed_total += max(0, s.block_masks_left - masks_left)
                # host copy only when someone will read it: a streaming
                # diff, or a request completing this tick (release needs the
                # row); intermediate block boundaries without callbacks stay
                # on device, matching the pre-streaming sync behavior
                if x_host is None and (cb is not None or (
                        masks_left == 0
                        and (s.block_idx + 1) * L >= s.request.gen_length)):
                    with sp.span("dllm.canvas_fetch"):
                        x_host = np.asarray(self.x)   # one copy, all rows
                positions = tokens = None
                if cb is not None:
                    # streaming diff: what unmasked on this tick, against
                    # the host-tracked mask mirror (no extra device sync —
                    # x_host is the copy the release path fetches anyway)
                    row = x_host[i, :s.request.total_len]
                    newly = s.masked & (row != self.mask_id)
                    positions = np.nonzero(newly)[0]
                    tokens = row[positions].copy()
                    s.masked &= ~newly
                self._advance(i, s, cb, masks_left, float(conf_np[i]),
                              positions, tokens, x_host)
            if x_host is None and n_active:
                # no streaming sink and no release needed the canvas this
                # tick: the mask-mirror-diff host fetch was skipped entirely
                self.host_syncs_elided += 1
                if self.obs is not None:
                    self.obs.host_syncs_elided(1)
        stages = sp.take()
        for name, s_sec in stages.items():
            self.metrics.record_stage(name, s_sec)
        if self.obs is not None:
            self._tick_obs(stages, committed_total,
                           [(prep.t0, dt, self.active_slots)])
        return True

    # -- device-resident megatick (docs/megatick.md) ------------------------

    def _choose_megatick_k(self, max_ticks: Optional[int]) -> tuple:
        """Adaptive megastep depth from queue pressure: admission happens
        only at megastep boundaries, so a deep megastep must not starve
        queued work.  With requests queued, the loop stops at the first
        release (``stop_on_release``) so freed slots refill immediately;
        if slots are *already* free (the queued work just hasn't arrived
        on the virtual clock yet), depth drops to 1 so the next arrival
        admits at most one tick late — exactly the K=1 admission cadence.
        """
        k = self.megatick_k
        if max_ticks is not None:
            k = max(1, min(k, int(max_ticks)))
        if self.queue:
            if self.pool.free_slots:
                k = 1
            return k, True
        return k, False

    def _megastep(self, max_ticks: Optional[int] = None) -> bool:
        """One megastep: admit at the boundary, run up to K fused ticks in
        a single on-device while_loop dispatch, then drain the commit
        buffers and replay them tick-by-tick through the host state
        machine — metrics, streaming callbacks, and obs hooks see the
        identical per-tick event sequence the K=1 path produces, with
        contiguous tick numbering and one device sync per megastep
        instead of per tick.  The stages are the K=1 tick's; each
        replayed tick is a ``dllm.replay`` span inside ``dllm.commit``."""
        sp = self.spans
        L = self.dcfg.block_length
        B = self.num_slots
        with sp.stage("dllm.host_prep") as prep:
            with sp.span("dllm.admit"):
                ready = self._admit_ready()
            k_req, stop_on_release = self._choose_megatick_k(max_ticks)
            pl = np.zeros((B,), np.int32)
            gb = np.zeros((B,), np.int32)
            bi = np.zeros((B,), np.int32)
            ti = np.zeros((B,), np.int32)
            bml = np.zeros((B,), np.int32)
            lc = np.full((B,), -np.inf, np.float32)
            act = np.zeros((B,), bool)
            for i, s in enumerate(self.slots):
                if s is None:
                    continue
                pl[i] = s.request.prompt_len
                gb[i] = s.request.gen_length // L
                bi[i] = s.block_idx
                ti[i] = s.step_in_block
                bml[i] = s.block_masks_left
                lc[i] = s.last_conf
                act[i] = True
        if not ready:
            sp.take()                         # an idle call is no tick
            return False
        if self.paged:
            # tables are constant across the megastep; a stage of its own
            # (per-tick share = paged_io / n, like dispatch)
            with sp.stage("dllm.paged_io"):
                self.pool.flush()
        cache = self.pool.cache if self.mode == "warm" else None
        # dispatch window mirrors the K=1 path: the state host->device
        # puts plus the single fused call.  x and cache are *donated*
        # into the loop (the engine rebinds both from the outputs below)
        with sp.stage("dllm.dispatch") as first:
            state = diffusion.megatick_state(
                pl, gb, self.dcfg, block_idx=bi, step_in_block=ti,
                block_masks_left=bml, last_conf=lc, active=act)
            if self.paged:
                # page stores are donated into the fused loop; rebind both
                canvas, new_cache, x_new, rng_new, _, bufs, n_dev = \
                    self._megatick_fn(
                        self.params, self.pool.canvas_pages, cache,
                        self.pool.canvas_table, self.pool.kv_table,
                        self.kv_valid, state, self.rng, jnp.int32(k_req),
                        jnp.asarray(bool(stop_on_release)))
                self.pool.canvas_pages = canvas
            else:
                x_new, new_cache, rng_new, _, bufs, n_dev = \
                    self._megatick_fn(
                        self.params, self.x, self.kv_valid, state, self.rng,
                        jnp.int32(k_req), jnp.asarray(bool(stop_on_release)),
                        cache)
        with sp.stage("dllm.device_sync") as synced:
            n = int(n_dev)                    # THE device sync point
            masks_b = np.asarray(bufs["masks_left"])
            conf_b = np.asarray(bufs["conf"])
            early_b = (np.asarray(bufs["early"])
                       if self._sf_threshold is not None else None)
            sinks = any(s is not None and s.request.uid in self._commit_cbs
                        for s in self.slots)
            xa_b = np.asarray(bufs["xa"]) if sinks else None
        dt = synced.t1 - first.t0
        self.x = x_new
        self.rng = rng_new
        if self.mode == "warm":
            self.pool.update(new_cache)
        elided = (n - 1) + (0 if sinks else 1)
        if elided > 0:
            self.host_syncs_elided += elided
            if self.obs is not None:
                self.obs.host_syncs_elided(elided)

        now0 = self.now
        committed_total = 0
        x_final: Optional[np.ndarray] = None
        ticks: List[Tuple[float, float, int]] = []
        with sp.stage("dllm.commit"):
            for j in range(n):
                with sp.span("dllm.replay"):
                    self.now = now0 + dt * (j + 1) / n
                    self.ticks_total += 1
                    ticks.append((prep.t0 + j * (dt / n), dt / n,
                                  self.active_slots))
                    self.metrics.record_tick(dt / n, self.active_slots)
                    for i, s in enumerate(self.slots):
                        if s is None:
                            continue
                        s.ticks += 1
                        cb = self._commit_cbs.get(s.request.uid)
                        masks_left = int(masks_b[j, i])
                        committed_total += max(
                            0, s.block_masks_left - masks_left)
                        positions = tokens = None
                        if cb is not None:
                            bs = s.request.prompt_len + s.block_idx * L
                            xa = xa_b[j, i]
                            newly = (s.masked[bs:bs + L]
                                     & (xa != self.mask_id))
                            local = np.nonzero(newly)[0]
                            positions = bs + local
                            tokens = xa[local].copy()
                            s.masked[bs:bs + L] &= ~newly
                        if x_final is None and masks_left == 0 and (
                                (s.block_idx + 1) * L
                                >= s.request.gen_length):
                            # released rows tick with k=0 afterwards, so
                            # the final canvas still holds their rows
                            with sp.span("dllm.canvas_fetch"):
                                x_final = np.asarray(self.x)
                        self._advance(i, s, cb, masks_left,
                                      float(conf_b[j, i]), positions,
                                      tokens, x_final)
            if early_b is not None:
                self.policy.early_exits += int(early_b[:n].sum())
        stages = sp.take()
        for name, s_sec in stages.items():
            self.metrics.record_stage(name, s_sec)
        if self.obs is not None:
            # per-megastep stages with per-tick attribution: every
            # replayed tick carries 1/n of the megastep's stage seconds,
            # so the dispatch/device_sync histograms directly show the
            # amortization (and the drift monitor compares against
            # host_overhead_per_tick(host, K))
            self._tick_obs(stages, committed_total, ticks)
            self.obs.megastep(n, k_req, dt, t_start_us=prep.t0 * 1e6)
        return True

    def run(self, requests: Optional[Sequence[Request]] = None
            ) -> List[CompletedRequest]:
        """Submit ``requests`` (if given) and tick until fully drained."""
        for r in requests or ():
            self.submit(r)
        while self.pending:
            if not self.tick():
                break
        self.metrics.elapsed = self.now
        return self.completed
