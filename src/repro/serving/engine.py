"""Request-level MoE serving engine with pluggable expert residency and a
paged, prefix-shared KV cache.

The unit of work is a **request**, not a batch: ``submit(request)`` returns a
handle, ``step()`` advances every in-flight request — by one sampled token,
or by a whole accepted draft burst when self-speculative decoding is on
(``EngineConfig(spec_k > 0)``, see ``repro.serving.spec``) — and ``drain()``
runs until the queue empties. Tokens are drawn host-side by each request's
own ``SamplingParams`` (``repro.serving.sampler``; greedy default is exact
argmax). The engine implements continuous batching over a fixed pool of
``max_slots`` batch rows:

* **admission** — queued requests are batched into a padded, masked prefill:
  prompt lengths round up a small geometric bucket ladder
  (``bucket_base``·2^i, capped at ``max_len``), up to ``prefill_rows``
  same-bucket requests prefill in ONE forward (per-row true lengths mask
  padding out of attention-cache writes, MoE dispatch and router counts).
  XLA therefore compiles at most one prefill executable per bucket
  — O(#buckets), not O(#distinct prompt lengths) — and admission cost
  amortizes over the batch at high arrival rates;
* **decode** — one jitted step advances *all* occupied slots together, with
  a per-slot position vector (each request decodes at its own offset) and a
  per-slot validity mask: vacant slots still ride along for shape stability
  but are masked out of MoE dispatch and every router count;
* **eviction/refill** — a finished request frees its slot at the end of the
  step; the next ``step()`` admits queued work into it mid-stream.

KV residency (``paged=True``, the default) is a **block pool**
(``repro.serving.kvpool``): attention caches live as fixed-size physical
blocks leased to requests through per-slot block tables, with a token-prefix
trie (``repro.serving.prefix``) mapping shared prompt prefixes (system
prompts, few-shot headers) onto the SAME physical blocks — admission adopts
trie hits and prefills only the suffix, skipping recompute entirely; decode
appends lazily and copy-on-writes shared blocks on divergence. KV block
bytes are reserved from the same ``BudgetTracker`` the expert hi-tier
promotes against, so KV admission and DynaExq promotions genuinely contend
for one HBM envelope (``hbm_budget_bytes``): KV pressure defers promotions,
demotions free headroom for admission. ``paged=False`` keeps the dense
per-slot rows — the parity reference. (Parity caveat: with a TIGHT MoE
``capacity_factor`` the router may drop overflow tokens, and the drop set
is a function of the compute batch — prefix skipping changes that batch,
exactly like batching itself does. Token-identity between the shared and
dense paths is therefore guaranteed for drop-free capacity settings.)

Where expert weights live — dense fp16, static PTQ, DynaExq mixed precision,
or host-offloaded with an LRU device cache — is entirely the
``ResidencyBackend``'s business (see ``repro.serving.backends``). The engine
calls exactly the backend protocol: ``materialize_banks`` at build time
(receiving the POOL's byte accounting and the shared budget),
``observe(counts, compute_s, prefill, row_valid)`` after every forward with
per-row (slot-resolved) router counts plus the row-validity mask — so no
backend ever accounts phantom traffic from padding or vacant slots — and
``tick()`` at step boundaries. There is no mode switch and no per-backend
branch anywhere in this loop.

Per-request routing telemetry falls out of the same signal: every
``RequestHandle`` accumulates its own row's expert counts
(``handle.expert_counts``: MoE position → (nsb, E)), attributing router
traffic to the request that caused it (prefix-skipped tokens are attributed
to the request that originally computed them).

``generate(batch, n_tokens)`` survives as a thin compat shim over
submit + drain for the whole-batch callers (benchmarks, launchers).
"""
from __future__ import annotations

import dataclasses
import enum
import functools
import itertools
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.budget import UNBOUNDED, BudgetTracker
from repro.kernels import ops as kops
from repro.launch.dist import dist_ctx, place_for_serving
from repro.models import (attn_logical_capacity, decode_step,
                          decode_step_paged, init_caches, init_paged_caches,
                          prefill, prefill_paged)
from repro.models.config import ArchConfig
from repro.models.moe import RAGGED_BM, moe_capacity
from repro.models.model import DecodeCaches
from repro.obs.trace import span
from repro.serving.backends import ResidencyBackend
from repro.serving.kvpool import KVBlockPool, KVLease
from repro.serving.prefix import PrefixTrie
from repro.serving.requests import Request
from repro.serving.sampler import RequestSampler
from repro.serving.spec import (_gather_paged_lanes, _restore_paged_lanes,
                                all_lo_banks)
from repro.serving.scheduler import (Scheduler, SchedulerConfig,
                                     SlotSnapshot, TieredQueue)

#: Engine-level keys ``InferenceEngine.stats()`` adds on top of the
#: backend's ``STAT_KEYS`` + per-class ``STAT_EXTRAS`` (plus the
#: speculative decoder's live overwrites of schema keys). Pinned here so
#: the stats schema is a checked contract (tests/test_obs.py), not an
#: accretion: a new engine gauge must be added to this tuple or the
#: contract test fails.
ENGINE_STAT_KEYS = (
    "steps", "prefills", "admitted", "finished", "prefill_tokens",
    "prefix_hit_tokens", "kv_cow_copies", "preemptions", "resumes",
    "shed_requests", "downgraded", "chunk_prefills",
    "prefill_compiles", "kv_blocks_in_use", "kv_bytes_in_use",
    "prefix_trie_nodes", "spec_row_rounds", "watchdog_cancels")

#: Keys ``load_snapshot()`` returns — the shed policy's input schema,
#: pinned for the same reason.
LOAD_SNAPSHOT_KEYS = ("queue_depth", "tpot_ema_s", "est_wait_s",
                      "budget_headroom_frac", "residency_ready_frac")


# Module-level jitted entry points with the (frozen, hashable) ArchConfig as
# a static argument: the XLA compile cache is keyed on the function identity,
# so every engine built for the same config shares compilations — a warm-up
# engine genuinely warms the measured one (benchmarks rely on this).
#
# ``ep`` is an unused static cache key: the ambient DistContext is read at
# TRACE time (``moe_apply`` → ``get_dist()``), so an engine serving under an
# expert-parallel mesh must not share a cache entry with a single-device
# engine of identical shapes — the engine passes its token-shard count (0
# when no mesh) to force distinct compilations per distribution regime.

@functools.partial(jax.jit, static_argnames=("cfg", "capacity_factor",
                                             "moe_dispatch", "row_capacity",
                                             "ep"))
def _prefill_jit(params, batch, caches, banks, lengths, *, cfg,
                 capacity_factor, moe_dispatch=None, row_capacity=None,
                 ep=0):
    return prefill(params, cfg, batch, caches, bank=banks,
                   capacity_factor=capacity_factor, lengths=lengths,
                   per_row_counts=True, moe_dispatch=moe_dispatch,
                   row_capacity=row_capacity)


@functools.partial(jax.jit, static_argnames=("cfg", "capacity_factor",
                                             "moe_dispatch", "row_capacity",
                                             "ep"))
def _decode_jit(params, token, pos, caches, banks, row_valid, *, cfg,
                capacity_factor, moe_dispatch=None, row_capacity=None,
                ep=0):
    return decode_step(params, cfg, token, pos, caches, bank=banks,
                       capacity_factor=capacity_factor, row_valid=row_valid,
                       per_row_counts=True, moe_dispatch=moe_dispatch,
                       row_capacity=row_capacity)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "capacity_factor", "has_prefix",
                                    "moe_dispatch", "row_capacity", "ep"),
                   donate_argnums=(2,))
def _prefill_paged_jit(params, batch, caches, banks, table, start, lengths,
                       *, cfg, capacity_factor, has_prefix,
                       moe_dispatch=None, row_capacity=None, ep=0):
    return prefill_paged(params, cfg, batch, caches, table, start, lengths,
                         bank=banks, capacity_factor=capacity_factor,
                         per_row_counts=True, has_prefix=has_prefix,
                         moe_dispatch=moe_dispatch, row_capacity=row_capacity)


@functools.partial(jax.jit, static_argnames=("cfg", "capacity_factor",
                                             "moe_dispatch", "row_capacity",
                                             "ep"),
                   donate_argnums=(3,))
def _decode_paged_jit(params, token, pos, caches, banks, row_valid, table,
                      write_blk, write_off, *, cfg, capacity_factor,
                      moe_dispatch=None, row_capacity=None, ep=0):
    return decode_step_paged(params, cfg, token, pos, caches, table,
                             write_blk, write_off, bank=banks,
                             capacity_factor=capacity_factor,
                             row_valid=row_valid, per_row_counts=True,
                             moe_dispatch=moe_dispatch,
                             row_capacity=row_capacity)


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_rows(pool, rows, slots):
    """Write the first ``len(slots)`` prefilled rows of a bucket cache into
    the batch rows named by ``slots``. The pool is donated so XLA updates
    the (large) cache buffers in place."""
    n = slots.shape[0]
    return jax.tree_util.tree_map(
        lambda m, o: m.at[:, slots].set(o[:, :n]), pool, rows)


@jax.jit
def _merge_rows(new_sub, old_sub, mask):
    """Row-masked cache merge for tier-split dispatch: keep the freshly
    computed state only for rows in ``mask`` ((B,) bool); every other row
    keeps its pre-dispatch state. Needed for RECURRENT (mamba) leaves —
    a decode forward advances SSM state for masked rows too, so when one
    engine step dispatches several QoS groups, each group's forward must
    not clobber the live state of rows belonging to the others. (Attention
    caches need no merge: a masked row's garbage write lands at that row's
    next-write position, which its own group overwrites before any read.)
    Leaves are (nsb, B, ...)."""
    def one(nv, ov):
        m = mask.reshape((1, -1) + (1,) * (nv.ndim - 2))
        return jnp.where(m, nv, ov)
    return jax.tree_util.tree_map(one, new_sub, old_sub)


@functools.partial(jax.jit, donate_argnums=(0,))
def _copy_blocks(pools, src, dst):
    """Batched physical block copies (COW resolution): block ``src[i]`` →
    ``dst[i]`` in every attention pool leaf ((nsb, N, ...)). Sources are
    all gathered before any scatter, so same-step chains (A→B while A is
    reallocated as another copy's destination) read pre-step contents.
    Padding lanes are trash→trash self-copies."""
    return jax.tree_util.tree_map(
        lambda a: a.at[:, dst].set(a[:, src]), pools)


@dataclasses.dataclass
class EngineConfig:
    max_slots: int = 4               # concurrent requests (batch rows)
    max_len: int = 512               # per-slot sequence budget
    capacity_factor: float = 2.0
    pad_token_id: int = 0            # fed to never-yet-occupied decode rows
    bucket_base: int = 32            # smallest prefill length bucket
    # Rows per batched prefill (compile-time constant so the prefill compile
    # count stays O(#buckets)); None → min(4, max_slots).
    prefill_rows: Optional[int] = None
    # ---- paged KV pool ------------------------------------------------
    paged: bool = True               # block-pool KV (False = dense rows)
    block_tokens: int = 16           # cache positions per physical block
    # Physical blocks in the pool; None → exactly enough for max_slots full
    # sequences plus the trash block (sharing then only ADDS headroom).
    kv_blocks: Optional[int] = None
    prefix_sharing: bool = True      # trie-based cross-request prefix reuse
    # Unified HBM envelope shared by KV block reservations and the expert
    # hi tier (None = unbounded: per-subsystem caps still apply).
    hbm_budget_bytes: Optional[int] = None
    # ---- self-speculative decoding -----------------------------------
    # Max draft depth per round (0 = off). Drafting runs decode with the
    # backend's all-lo expert banks (no extra weights); every verify round
    # emits 1..spec_k+1 tokens. Token-identical to spec-off at
    # temperature=0 under drop-free MoE capacity (see serving.spec).
    spec_k: int = 0
    # Adapt the per-round draft depth from an acceptance-rate EMA over a
    # power-of-two ladder (False = always draft spec_k).
    spec_adaptive: bool = True
    # ---- MoE dispatch ------------------------------------------------
    # Token layout for every MoE layer of the serving forwards: "padded"
    # (fixed-capacity (E, C, d) scatter, reference), "ragged" (compacted
    # activations + fused mixed-precision kernel — only active experts'
    # weights stream), or None → kernels.ops.moe_dispatch_default()
    # (ragged on TPU, padded on CPU; REPRO_MOE_DISPATCH overrides).
    # Resolved ONCE at engine construction.
    moe_dispatch: Optional[str] = None
    # Per-row MoE capacity normalization: the drop rule under tight
    # capacity_factor becomes per-request-row (see moe._row_capacity_keep),
    # so whether a token's assignment drops no longer depends on which
    # other requests share the compute batch — prefix sharing and
    # spec-verify token identity then hold even in drop regimes.
    row_capacity_norm: bool = False
    # ---- SLO-tiered QoS scheduling -----------------------------------
    # Policy knobs for the tiered scheduler (queue aging, shed policy,
    # preemption, chunked prefill). None → SchedulerConfig() defaults,
    # which reproduce the untiered engine exactly for default-class
    # traffic. See repro.serving.scheduler.
    scheduler: Optional[SchedulerConfig] = None
    # ---- fault tolerance (repro.fault) -------------------------------
    # Cancel hi promotions stuck in flight longer than this (engine-clock
    # seconds since copy issue): slot freed, reservation refunded, expert
    # keeps serving lo. None = no promotion watchdog.
    promo_deadline_s: Optional[float] = None
    # Preempt-and-requeue RUNNING requests that appended no token for this
    # long (bit-exact snapshot resume). None = no request watchdog.
    watchdog_no_progress_s: Optional[float] = None


class RequestState(enum.Enum):
    QUEUED = "queued"
    PREFILLING = "prefilling"     # chunked prefill in flight (owns a slot)
    RUNNING = "running"
    FINISHED = "finished"
    SHED = "shed"                 # refused by the load-shedding policy


class EngineStallError(RuntimeError):
    """The engine went fully idle with queued work it could not admit and
    no in-flight transfers left to free bytes — no future step can change
    anything. Carries a structured ``snapshot`` (queue depths per QoS
    tier, pending promotions with ages, budget headroom, residency
    readiness) so operators see *why* admission wedged instead of a bare
    "stalled" string."""

    def __init__(self, snapshot: Dict[str, object]):
        self.snapshot = snapshot
        depths = snapshot.get("queue_depths", {})
        pend = snapshot.get("pending_promotions", [])
        super().__init__(
            f"admission stalled: {snapshot.get('queued_total', 0)} queued "
            f"request(s) cannot reserve KV under the shared HBM envelope "
            f"and no in-flight work remains to free bytes "
            f"(queue depths {depths}, envelope used "
            f"{snapshot.get('budget_used', 0)}/"
            f"{snapshot.get('budget_cap', 0)}, "
            f"ready_frac {snapshot.get('residency_ready_frac', 1.0):.3f}, "
            f"{len(pend)} pending promotion(s))")


class RequestHandle:
    """Mutable per-request view returned by ``submit``."""

    def __init__(self, rid: int, request: Request):
        self.id = rid
        self.request = request
        self.state = RequestState.QUEUED
        self.slot: Optional[int] = None
        self.tokens: List[int] = []      # generated tokens
        # Per-request sampling state (counter-based PRNG keyed by the
        # request's seed; greedy when the request carries no params).
        self.sampler = RequestSampler(request.sampling)
        self._eos_scanned = 0            # tokens already checked for EOS
        # Per-REQUEST speculative acceptance EMA: draft depth adapts from
        # this request's own history only, so its burst boundaries (and
        # therefore its PRNG stream consumption) never depend on which
        # other requests share the batch — bit-reproducibility survives
        # adaptive speculation.
        self.spec_ema = 0.75
        self.submit_s: float = 0.0       # engine clock at submit
        self.stall_at_submit: float = 0.0  # engine stall-clock at submit
        self.ttft_s: float = 0.0         # submit → first token (incl. queue)
        self.first_token_s: float = 0.0  # engine clock at first token
        self.finish_s: float = 0.0       # engine clock at finish
        self.step_times: List[float] = []
        # ---- QoS (repro.serving.scheduler) ---------------------------
        self.qos: str = "standard"       # resolved SLO class
        self.exec_qos: str = "standard"  # execution tier (after downgrades)
        self.enqueue_s: float = 0.0      # queue-aging reference time
        self.preempts = 0                # times this request was evicted
        self._snapshot = None            # SlotSnapshot while evicted
        self._chunk_pos = 0              # prompt tokens prefilled so far
        self.lease: Optional[KVLease] = None   # paged-mode KV block lease
        self.prefix_hit_tokens: int = 0  # prompt tokens served from the trie
        # Modeled stall seconds of forwards this request was RESIDENT for
        # (prefill + decode + spec rounds): host-tier demand fetches and
        # offload misses attributed to the requests they actually delayed.
        # Exposure, not an exclusive share — concurrent residents each
        # record the full stall their step suffered.
        self.stall_exposure_s: float = 0.0
        # Per-request routing telemetry: MoE position → (nsb, E) int64
        # router selections attributed to THIS request's row (prompt tokens
        # at prefill + one per decode step). Populated at admission.
        self.expert_counts: Optional[Dict[str, np.ndarray]] = None
        # ---- fault tolerance -----------------------------------------
        # True once any forward of this request routed through a
        # quarantined (host-served, degraded-quality) expert cell — such
        # requests complete but are excluded from bit-parity guarantees.
        self.degraded = False
        # Engine clock at the last appended token (watchdog progress
        # stamp; 0.0 until the first token).
        self.last_progress_s: float = 0.0

    @property
    def workload(self) -> str:
        return self.request.workload

    def token_array(self) -> np.ndarray:
        return np.asarray(self.tokens, np.int32)

    def __repr__(self):
        return (f"RequestHandle(id={self.id}, state={self.state.value}, "
                f"slot={self.slot}, n_generated={len(self.tokens)})")


class InferenceEngine:
    """Continuous-batching serving loop over a ``ResidencyBackend``."""

    def __init__(self, cfg: ArchConfig, params: Dict,
                 backend: ResidencyBackend,
                 ecfg: Optional[EngineConfig] = None, dist=None, obs=None):
        if cfg.is_encoder_decoder:
            raise NotImplementedError(
                "InferenceEngine serves decoder-only stacks; encoder-decoder "
                "architectures go through the batch prefill/decode entry "
                "points in repro.models directly.")
        self.cfg = cfg
        self.params = params
        self.backend = backend
        self.ecfg = ecfg if ecfg is not None else EngineConfig()
        # Optional DistContext (expert-parallel / data-parallel serving):
        # every jitted forward traces under it — see ``_dist_wrap``.
        self.dist = dist

        n = self.ecfg.max_slots
        sb = cfg.superblock_or_default()
        self._attn_pos = [str(p) for p, k in enumerate(sb) if k == "attn"]
        self._mamba_pos = [str(p) for p, k in enumerate(sb) if k != "attn"]

        # ---- unified HBM envelope + paged KV pool ----------------------
        # The pool is the single source of truth for KV bytes: both modes
        # size KV from the same block math, and in paged mode every block
        # is reserved against the shared budget the expert hi tier also
        # draws from (see repro.core.budget).
        cap = self.ecfg.hbm_budget_bytes
        self.budget = BudgetTracker(UNBOUNDED if cap is None else cap)
        self.pool: Optional[KVBlockPool] = None
        self.trie: Optional[PrefixTrie] = None
        self._bt = self.ecfg.block_tokens
        if self._attn_pos:
            self._C_attn = self.ecfg.max_len \
                if cfg.attn.sliding_window is None \
                else min(self.ecfg.max_len, cfg.attn.sliding_window)
            self._C_pad = attn_logical_capacity(cfg, self.ecfg.max_len,
                                                self._bt)
            self._nb_per_slot = self._C_pad // self._bt
        else:
            self._C_attn = self._C_pad = self._nb_per_slot = 0
        n_blocks = self.ecfg.kv_blocks if self.ecfg.kv_blocks is not None \
            else 1 + n * self._nb_per_slot
        block_bytes = self._block_bytes()
        if self.ecfg.paged and self._attn_pos:
            if self._nb_per_slot > n_blocks - 1:
                raise ValueError(
                    f"kv_blocks={n_blocks} cannot hold even one sequence "
                    f"({self._nb_per_slot} logical blocks + the trash "
                    f"block); raise kv_blocks or shrink max_len")
            self.pool = KVBlockPool(n_blocks, self._bt, block_bytes,
                                    budget=self.budget.view("kv"),
                                    reclaim=self._reclaim_blocks)
            # Prefix skipping needs leasable sequence state; recurrent
            # (mamba) positions cannot be restored from a cache, so mixed
            # stacks run the pool without the trie.
            if self.ecfg.prefix_sharing and not self._mamba_pos:
                self.trie = PrefixTrie(self.pool)
        # KV bytes reported to the backend = what is actually allocated:
        # the pool's capacity (trash + rounding included) in paged mode,
        # the dense per-slot rows otherwise.
        if self.pool is not None:
            kv_bytes = self.pool.capacity_bytes
        elif self._attn_pos:
            kv_bytes = (block_bytes // self._bt) * n * self._C_attn
        else:
            kv_bytes = 0

        # ---- observability (repro.obs) ---------------------------------
        # The flight recorder's clock is rebound to the ENGINE clock, so
        # virtual-clock replays (``replay(realtime=False)``) stamp events
        # deterministically and traces compare byte-identical in CI. With
        # ``obs=None`` (default) every instrumentation site below is a
        # single pointer check — the decode hot path is untouched.
        self.obs = obs
        self.tracer = obs.tracer if obs is not None else None
        self.metrics = obs.metrics if obs is not None else None
        self._sample_every = max(1, obs.cfg.sample_every) \
            if obs is not None else 1
        self._obs_prev = (0.0, 0.0, 0)   # dispatch-gauge snapshot per step
        if self.tracer is not None:
            self.tracer.clock = self._now
        if obs is not None:
            attach = getattr(backend, "attach_obs", None)
            if attach is not None:
                attach(self.tracer, self.metrics)

        self.banks = backend.materialize_banks(cfg, params, kv_bytes,
                                               budget=self.budget)
        if self.dist is not None:
            place_for_serving(self.dist, self.params, self.banks)
        # ---- fault tolerance (repro.fault) ------------------------------
        # Rebind the transfer plane's clocks to the engine clock (virtual
        # under replay) so promotion ages — the watchdog's input — share
        # the time base of every other engine metric.
        bind = getattr(backend, "bind_clock", None)
        if bind is not None:
            bind(self._now)
        self._watchdog = None
        if self.ecfg.promo_deadline_s is not None or \
                self.ecfg.watchdog_no_progress_s is not None:
            from repro.fault.watchdog import Watchdog, WatchdogConfig
            self._watchdog = Watchdog(WatchdogConfig(
                promo_deadline_s=self.ecfg.promo_deadline_s,
                no_progress_s=self.ecfg.watchdog_no_progress_s),
                tracer=self.tracer)
        # Quarantine-degradation marking: a single method call per step
        # when the backend exposes it, skipped entirely otherwise.
        self._degraded_fn = getattr(backend, "degraded_cells", None)
        # MoE dispatch layout + per-row capacity normalization, resolved
        # ONCE here (env changes after construction cannot disagree with
        # already-compiled executables). The decode row cap is static; the
        # prefill cap depends on the length bucket and rides per call.
        self.moe_dispatch = self.ecfg.moe_dispatch \
            if self.ecfg.moe_dispatch is not None \
            else kops.moe_dispatch_default()
        if self.moe_dispatch not in ("padded", "ragged"):
            raise ValueError(f"moe_dispatch={self.moe_dispatch!r}; "
                             f"one of padded|ragged")
        if self.tracer is not None:
            # Run metadata exported with the trace: backend, dispatch mode,
            # router shape.
            self.tracer.meta.update(
                moe_dispatch=self.moe_dispatch,
                num_experts=cfg.moe.num_experts if cfg.is_moe else 0,
                top_k=cfg.moe.top_k if cfg.is_moe else 1,
                backend=backend.name)
        norm = self.ecfg.row_capacity_norm and cfg.is_moe
        self._row_cap_decode = moe_capacity(
            1, cfg.moe, self.ecfg.capacity_factor) if norm else None
        self._row_cap_norm = norm
        ep_key = 0 if self.dist is None else self.dist.n_token_shards
        self._decode_kw = dict(cfg=cfg,
                               capacity_factor=self.ecfg.capacity_factor,
                               moe_dispatch=self.moe_dispatch,
                               row_capacity=self._row_cap_decode, ep=ep_key)
        self._jit_prefill = self._dist_wrap(functools.partial(
            _prefill_jit, cfg=cfg,
            capacity_factor=self.ecfg.capacity_factor,
            moe_dispatch=self.moe_dispatch, ep=ep_key))
        self._jit_decode = self._dist_wrap(functools.partial(
            _decode_jit, **self._decode_kw))
        self._jit_prefill_paged = self._dist_wrap(functools.partial(
            _prefill_paged_jit, cfg=cfg,
            capacity_factor=self.ecfg.capacity_factor,
            moe_dispatch=self.moe_dispatch, ep=ep_key))
        self._jit_decode_paged = self._dist_wrap(functools.partial(
            _decode_paged_jit, **self._decode_kw))
        # Optional observer of every forward's logits, called as
        # ``logits_hook(kind, logits, rows)``: kind "prefill", "decode" or
        # "verify", the device logits, and the (row, handle) pairs the
        # forward served — row indexes the logits' batch dim (the second
        # dim of verify's (k+1, B, V)). None costs one check per forward.
        self.logits_hook = None
        self._jit_scatter = _scatter_rows
        # Dispatch-efficiency gauges (host mirror of MoEAux telemetry).
        self._disp_active_sum = 0.0
        self._disp_pad_sum = 0.0
        self._disp_layers = 0

        if self.pool is not None:
            self.caches = init_paged_caches(cfg, n, self.ecfg.max_len,
                                            self._bt, self.pool.n_blocks)
        else:
            self.caches = init_caches(cfg, n, self.ecfg.max_len)
        self.slots: List[Optional[RequestHandle]] = [None] * n
        self.pos = np.zeros(n, np.int32)        # next write position per slot
        self.tokens = np.full(n, self.ecfg.pad_token_id, np.int32)
        # ---- SLO-tiered scheduling ----------------------------------
        # The scheduler is pure policy; the admission queue is the tiered
        # weighted-aging queue (deque-compatible — FIFO for uniform-class
        # traffic, so the defaults reproduce the untiered engine exactly).
        self.sched = Scheduler(self.ecfg.scheduler)
        self._clock: Optional[float] = None     # virtual clock (replay)
        self.queue: TieredQueue = TieredQueue(self._now,
                                              self.sched.cfg.aging_s)
        self._lo_owner_cache: Dict = {}         # all-lo bank derivation memo
        self._tpot_ema = 0.0                    # per-token latency EMA
        self.last_counts: Dict = {}             # (nsb, E) counts, last forward
        self.last_row_counts: Dict = {}         # (nsb, R, E), last forward
        self.decode_times: List[float] = []     # per-step latency incl. stall
        # Per-TOKEN decode latency accounting: a speculative round's
        # dispatch latency amortizes over every token the round emits, so
        # tpot stays time-per-OUTPUT-token whether or not speculation runs.
        self._tpot_sum = 0.0                    # Σ row-rounds × latency
        self._tpot_tokens = 0                   # decode-emitted tokens
        self.ttfts: List[float] = []            # per-request submit→first-tok
        # Cumulative modeled stall seconds (backend-returned, never slept):
        # a virtual clock running alongside perf_counter, so queue-inclusive
        # latencies charge the stalls of work that ran ahead of a request.
        self._stall_clock = 0.0
        self._ids = itertools.count()
        self.counters = {"steps": 0, "prefills": 0, "admitted": 0,
                         "finished": 0, "prefill_tokens": 0,
                         "prefix_hit_tokens": 0, "kv_cow_copies": 0,
                         "preemptions": 0, "resumes": 0,
                         "shed_requests": 0, "downgraded": 0,
                         "chunk_prefills": 0, "watchdog_cancels": 0}
        # ---- length-bucket ladder -----------------------------------
        # SSD prefill requires sequence length divisible by the chunk size,
        # so for stacks with mamba layers every bucket is a chunk multiple.
        self._seq_mult = cfg.ssm.chunk if self._mamba_pos else 1
        m = self._seq_mult
        cap = (self.ecfg.max_len // m) * m
        if cap <= 0:
            raise ValueError(
                f"max_len={self.ecfg.max_len} below the SSD chunk multiple "
                f"{m}; no prefill bucket fits")
        base = max(1, -(-self.ecfg.bucket_base // m) * m)
        ladder: List[int] = []
        v = base
        while v < cap:
            ladder.append(v)
            v *= 2
        ladder.append(cap)
        self.buckets = tuple(ladder)            # ascending, last == cap
        self._max_prompt = cap
        self._prefill_rows = self.ecfg.prefill_rows \
            if self.ecfg.prefill_rows is not None else min(4, n)
        self.prefill_shapes: set = set()        # (rows, bucket) traced
        # ---- chunked prefill ----------------------------------------
        # Effective chunk size: the largest block-aligned ladder bucket
        # not above the knob, so every chunk prefill hits a bucket shape
        # the normal admission path already compiles (compile count stays
        # O(#buckets)). Chunking needs the paged suffix-prefill path and
        # restartable sequence state: attention-only stacks (SSD prefill
        # takes no initial state, so mamba rows must prefill in one shot),
        # and sliding-window prompts only while they fit the window.
        self._chunk_tokens = 0
        pc = self.sched.cfg.prefill_chunk
        if pc > 0 and self.pool is not None and not self._mamba_pos:
            fits = [b for b in self.buckets
                    if b <= pc and b % self._bt == 0]
            if fits:
                self._chunk_tokens = fits[-1]
        # ---- self-speculative decoding ------------------------------
        self._spec = None
        if self.ecfg.spec_k > 0:
            from repro.serving.spec import SpecDecoder
            self._spec = SpecDecoder(self)

    # ------------------------------------------------------------------
    def _now(self) -> float:
        """The engine's accounting clock. Wall time normally; the replay
        loop installs a VIRTUAL clock for ``realtime=False`` runs so every
        queue-time metric (submit_s, ttft_s, finish_s, queue aging,
        deadlines) is computed by the same code against deterministic
        timestamps — virtual-clock runs report the same submit-inclusive
        accounting realtime ones do, machine speed be damned. Compute
        latencies (decode dt, stalls) always use perf_counter."""
        return time.perf_counter() if self._clock is None else self._clock

    # ------------------------------------------------------------------
    def _dist_wrap(self, fn):
        """Run a jitted forward under the engine's DistContext: the MoE
        layer reads the ambient context at trace time to decide its
        sharding regime (single-device / dp shard_map / expert-parallel
        all-to-all), so every trace — including the speculative decoder's,
        which calls through these same partials — happens inside it. The
        ``ep`` static passed alongside keeps distribution regimes from
        sharing a compile-cache entry."""
        if self.dist is None:
            return fn

        def wrapped(*a, **kw):
            with dist_ctx(self.dist):
                return fn(*a, **kw)
        return wrapped

    def decode_program(self):
        """The single-token decode program this engine runs, lowered
        (``jax.stages.Lowered``) for its current state with every row
        vacant: ``.compile().as_text()`` is its optimized HLO."""
        n = self.ecfg.max_slots
        args = [self.params, jnp.asarray(self.tokens), jnp.asarray(self.pos),
                self.caches, self.banks, jnp.zeros(n, bool)]
        fn = _decode_jit
        if self.pool is not None:
            zeros = jnp.zeros(n, jnp.int32)
            args += [jnp.asarray(self._block_tables()), zeros, zeros]
            fn = _decode_paged_jit
        with dist_ctx(self.dist):
            return fn.lower(*args, **self._decode_kw)

    def decode_program_text(self) -> str:
        """StableHLO text of ``decode_program`` — where a check looks for
        the kernels the step holds. Locations are kept, so the text names
        each op's ``jax.named_scope`` (``attn``, ``moe/ffn``, …)."""
        return self.decode_program().as_text(debug_info=True)

    # ------------------------------------------------------------------
    def _row_cap_prefill(self, bucket: int) -> Optional[int]:
        """Per-row MoE capacity for a prefill at this length bucket (None
        when normalization is off). Bucket-derived so it is a static compile
        constant per bucket and depends only on the request's own length —
        never on which rows share the batch."""
        if not self._row_cap_norm:
            return None
        return moe_capacity(bucket, self.cfg.moe, self.ecfg.capacity_factor)

    def _note_dispatch(self, counts_np: Dict) -> None:
        """Host mirror of the MoEAux dispatch telemetry: per-layer active
        expert counts and the pad ratio of the layout actually configured
        (padding rows of the (E, C) buffer, or intra-tile slack of the
        bm-aligned ragged layout) — the uniform ``active_experts`` /
        ``dispatch_pad_ratio`` gauges in ``stats()``."""
        if not self.cfg.is_moe or not counts_np:
            return
        E = self.cfg.moe.num_experts
        if self._row_cap_decode is not None:
            C = self.ecfg.max_slots * self._row_cap_decode
        else:
            C = moe_capacity(self.ecfg.max_slots, self.cfg.moe,
                             self.ecfg.capacity_factor)
        for v in counts_np.values():
            v = np.asarray(v)
            if v.ndim == 4:                       # (W, nsb, B, E) spec steps
                per = v.sum(axis=2).reshape(-1, E)
            elif v.ndim == 3:                     # (nsb, B, E) per-row
                per = v.sum(axis=1).reshape(-1, E)
            else:                                 # (nsb, E) aggregated
                per = v.reshape(-1, E)
            per = per.astype(np.float64)
            routed = per.sum(axis=1)
            live = routed > 0
            if not live.any():
                continue
            per = per[live]
            routed = routed[live]
            active = (per > 0).sum(axis=1)
            if self.moe_dispatch == "ragged":
                tiles = np.ceil(per / RAGGED_BM).sum(axis=1)
                pad = 1.0 - routed / np.maximum(tiles * RAGGED_BM, 1.0)
            else:
                kept = np.minimum(per, C).sum(axis=1)
                pad = 1.0 - kept / max(E * C, 1)
            self._disp_active_sum += float(active.sum())
            self._disp_pad_sum += float(pad.sum())
            self._disp_layers += int(active.shape[0])

    def _block_bytes(self) -> int:
        """Bytes of ONE physical block across every attention layer of the
        stack (k+v, bf16). The pool's block math is the only KV size
        accounting in the system."""
        cfg = self.cfg
        if not self._attn_pos:
            return 0
        n_attn = len(self._attn_pos) * cfg.n_superblocks()
        return (2 * self._bt * cfg.attn.n_kv_heads * cfg.attn.head_dim *
                2 * n_attn)

    def _reclaim_blocks(self, need: int) -> int:
        return self.trie.evict(need) if self.trie is not None else 0

    def _quota_blocks(self, plen: int, start: int, max_new: int) -> int:
        """Worst-case physical blocks a request can ever allocate.

        Full attention (positions only grow): exactly the logical blocks
        from the (block-aligned) prefix hit ``start`` to the sequence cap —
        adopted prefix blocks and registered chunks are never rewritten, so
        they can never COW. Sliding-window rings can wrap a write onto ANY
        logical block: one allocation per logical block (lazy append or COW
        of an adopted block) plus one per trie-registrable prompt chunk (a
        block this lease computes, shares, then COWs on a later wrap)."""
        seq_cap = min(self.ecfg.max_len, plen + max_new)
        if self.cfg.attn.sliding_window is None:
            return -(-seq_cap // self._bt) - start // self._bt
        n_write = -(-min(self._C_pad, seq_cap) // self._bt)
        n_reg = plen // self._bt \
            if (self.trie is not None and plen <= self._C_attn) else 0
        return n_write + n_reg

    # ------------------------------------------------------------------
    def submit(self, request: Request) -> RequestHandle:
        """Queue a request; it is admitted on a later ``step()`` as soon as
        a cache slot frees up. Returns immediately with a handle.

        The prompt must fit the largest prefill bucket (``max_len`` rounded
        down to the engine's sequence multiple). A generation budget that
        overruns the slot is fine — common for eos-bounded requests — the
        request is truncated at the sequence capacity (finishes with fewer
        than ``max_new_tokens`` tokens).

        QoS: the request's class (or the scheduler default) is resolved and
        validated here — unknown classes and non-positive deadlines fail
        loudly. Under an active shed policy an overloaded engine may return
        the handle in state ``SHED`` (batch tier, ``reject`` policy) or
        downgrade its execution tier to the all-lo banks — premium is never
        touched."""
        qos = self.sched.resolve(request.qos)
        if request.deadline_ms is not None and request.deadline_ms <= 0:
            raise ValueError(
                f"deadline_ms={request.deadline_ms} must be > 0 (or None)")
        plen = int(np.asarray(request.tokens).shape[-1])
        if plen > self._max_prompt:
            raise ValueError(
                f"prompt of {plen} tokens exceeds the largest prefill "
                f"bucket {self._max_prompt} (max_len={self.ecfg.max_len})")
        if request.sampling is not None:
            # Malformed sampling params fail at the door, not mid-decode.
            request.sampling.validate()
        if self.pool is not None:
            # Loud infeasibility instead of an unbounded queue spin: a
            # request whose worst-case KV quota (no prefix hits) plus the
            # trash block can NEVER fit the envelope — or whose live block
            # footprint exceeds the pool's physical blocks — would block
            # the queue head forever.
            worst = ((1 + self._quota_blocks(plen, 0, request.max_new_tokens))
                     * self.pool.block_bytes)
            if worst > self.budget.cap:
                raise ValueError(
                    f"request needs {worst} bytes of KV worst-case but the "
                    f"HBM envelope caps at {self.budget.cap}; raise "
                    f"hbm_budget_bytes or shorten the request")
        handle = RequestHandle(next(self._ids), request)
        handle.qos = handle.exec_qos = qos
        handle.submit_s = self._now()
        handle.enqueue_s = handle.submit_s
        handle.stall_at_submit = self._stall_clock
        if self.tracer is not None:
            self.tracer.instant("submit", cat="sched", rid=handle.id,
                                qos=qos, prompt=plen)
        action = self.sched.admit_action(qos, self.load_snapshot())
        if action == "shed":
            handle.state = RequestState.SHED
            self.counters["shed_requests"] += 1
            if self.tracer is not None:
                self.tracer.instant("shed", cat="sched", rid=handle.id,
                                    qos=qos, reason="overload")
            return handle
        if action == "downgrade" and handle.exec_qos != "batch":
            handle.exec_qos = "batch"
            self.counters["downgraded"] += 1
            if self.tracer is not None:
                self.tracer.instant("downgrade", cat="sched", rid=handle.id,
                                    qos=qos)
        self.queue.append(handle)
        self._queued(handle)
        return handle

    def _queued(self, handle: RequestHandle) -> None:
        """Open the request's ``sched.queue`` span (submit or requeue)."""
        if self.tracer is not None:
            self.tracer.async_begin("queue", handle.id, cat="sched",
                                    qos=handle.exec_qos)

    def _dequeued(self, handle: RequestHandle, how: str) -> None:
        """Close the request's ``sched.queue`` span: it left the queue for
        a prefill group, a chunked admission or a resume (or was shed)."""
        if self.tracer is not None:
            self.tracer.async_end("queue", handle.id, cat="sched", how=how)

    def _bucket_len(self, plen: int) -> int:
        """Smallest ladder bucket that fits ``plen`` tokens."""
        for b in self.buckets:
            if b >= plen:
                return b
        raise ValueError(f"prompt of {plen} tokens exceeds the largest "
                         f"bucket {self.buckets[-1]}")

    @staticmethod
    def _prompt_len(handle: RequestHandle) -> int:
        return int(np.asarray(handle.request.tokens).reshape(-1).shape[0])

    # -- paged-mode helpers --------------------------------------------
    def _apply_copies(self, cows: List[Tuple[int, int]]) -> None:
        """Run the batched (src, dst) block copies on-device; lane count
        padded to a power of two (trash self-copies) to bound compiles."""
        if not cows:
            return
        n = 1 << max(0, len(cows) - 1).bit_length()
        src = np.zeros(n, np.int32)
        dst = np.zeros(n, np.int32)
        for i, (s, d) in enumerate(cows):
            src[i], dst[i] = s, d
        attn_sub = {p: self.caches.blocks[p] for p in self._attn_pos}
        new_sub = _copy_blocks(attn_sub, jnp.asarray(src), jnp.asarray(dst))
        self.caches = DecodeCaches(
            blocks={**self.caches.blocks, **new_sub}, cross=None)
        self.counters["kv_cow_copies"] += len(cows)

    def _block_tables(self) -> np.ndarray:
        """(max_slots, nb) physical block table rows (vacant rows -1)."""
        nb = max(1, self._nb_per_slot)
        out = np.full((self.ecfg.max_slots, nb), -1, np.int32)
        for i, h in enumerate(self.slots):
            if h is not None and h.lease is not None:
                out[i] = h.lease.table
        return out

    def _ensure_write(self, lease: KVLease, pos: int,
                      cows: List[Tuple[int, int]]) -> Tuple[int, int]:
        """Resolve the physical (block, offset) for a write at absolute
        position ``pos``, collecting any COW obligation."""
        s = pos % self._C_pad
        phys, cow = lease.ensure(s // self._bt)
        if cow >= 0:
            cows.append((cow, phys))
        return phys, s % self._bt

    def _write_span_blocks(self, start: int, end: int) -> List[int]:
        """Logical blocks whose ring slots the position span
        ``[start, end)`` writes (ring wrap included). O(#blocks), not
        O(#tokens): the written ring-slot span is contiguous mod C_pad."""
        if end - start >= self._C_pad:
            return list(range(self._nb_per_slot))
        s0 = start % self._C_pad
        s1 = (end - 1) % self._C_pad
        if s0 <= s1:
            return list(range(s0 // self._bt, s1 // self._bt + 1))
        return sorted(set(range(0, s1 // self._bt + 1)) |
                      set(range(s0 // self._bt, self._nb_per_slot)))

    # -- load signals (shedding / benchmark telemetry) ------------------
    def load_snapshot(self) -> Dict[str, float]:
        """The uniform load signals the shed policy keys on: queue depth,
        the decode TPOT EMA, the estimated queue wait they imply (queued
        decode tokens at the measured per-token latency, spread over the
        slots), and the shared HBM envelope's headroom fraction."""
        queued_tokens = sum(
            h.request.max_new_tokens +
            max(0, self._prompt_len(h) - h._chunk_pos)
            for h in self.queue)
        est_wait = (queued_tokens * self._tpot_ema /
                    max(1, self.ecfg.max_slots))
        frac = getattr(self.backend, "ready_frac", None)
        return {"queue_depth": float(len(self.queue)),
                "tpot_ema_s": float(self._tpot_ema),
                "est_wait_s": float(est_wait),
                "budget_headroom_frac": float(self.budget.headroom_frac()),
                "residency_ready_frac":
                    float(frac()) if frac is not None else 1.0}

    def _shed_expired(self) -> None:
        """Drop queued batch-tier work whose deadline already passed —
        serving it late burns decode steps premium traffic is waiting on.
        Only the batch tier is dropped; standard/premium deadlines are
        reported (SLO attainment) but never enforced by discard."""
        if not self.sched.cfg.drop_expired_batch or not self.queue:
            return
        now = self._now()

        def expired(h):
            d = h.request.deadline_ms
            return (h.qos == "batch" and d is not None and
                    (now - h.submit_s) * 1e3 > d)

        for h in self.queue.prune(expired):
            self._dequeued(h, "shed")
            h.state = RequestState.SHED
            self.counters["shed_requests"] += 1
            if self.tracer is not None:
                self.tracer.instant("shed", cat="sched", rid=h.id,
                                    qos=h.qos, reason="deadline")

    # ------------------------------------------------------------------
    def _admit(self, finished: List[RequestHandle]) -> None:
        """Fill free slots from the queue with batched, length-bucketed
        masked prefills: the queue head picks the bucket, same-bucket
        requests behind it join (up to ``prefill_rows`` and the free-slot
        count), the batch right-pads to (prefill_rows, bucket), and each
        prefilled row scatters into its slot of the batched caches. Batch
        rows beyond the group are ``lengths == 0`` pads, so every prefill
        compiles at one of O(#buckets) shapes.

        In paged mode the bucket is chosen by the SUFFIX length (prompt
        minus trie-hit prefix) and admission additionally passes the KV
        quota gate: a request whose worst-case block bytes do not fit the
        shared budget waits in the queue — expert demotions or finishing
        requests free the headroom that admits it. (Stacks without
        attention positions have no KV to page and always take the dense
        path.)"""
        with span(self.tracer, "admit", "sched"):
            if self.pool is not None:
                self._admit_paged(finished)
            else:
                self._admit_dense(finished)

    def _admit_dense(self, finished: List[RequestHandle]) -> None:
        while self.queue:
            free = [i for i, h in enumerate(self.slots) if h is None]
            if not free:
                return
            head_peek = self.queue.peek()
            if head_peek is not None and head_peek._snapshot is not None:
                # Preempted request at the queue head: resume is a direct
                # cache-row upload, not a prefill.
                self.queue.popleft()
                self._dequeued(head_peek, "resume")
                self._resume_dense(head_peek, free[0])
                continue
            R = self._prefill_rows
            limit = min(len(free), R)
            head = self.queue.popleft()
            bucket = self._bucket_len(self._prompt_len(head))
            group = [head]
            skipped: List[RequestHandle] = []
            while self.queue and len(group) < limit:
                h = self.queue.popleft()
                if h._snapshot is None and \
                        self._bucket_len(self._prompt_len(h)) == bucket:
                    group.append(h)
                else:
                    skipped.append(h)
            self.queue.extendleft(reversed(skipped))
            for h in group:
                self._dequeued(h, "prefill")

            G = len(group)
            lengths = np.zeros(R, np.int32)
            batch_toks = np.full((R, bucket), self.ecfg.pad_token_id,
                                 np.int32)
            for r, h in enumerate(group):
                p = np.asarray(h.request.tokens, np.int32).reshape(-1)
                lengths[r] = p.shape[0]
                batch_toks[r, :p.shape[0]] = p
            row_caches = init_caches(self.cfg, R, self.ecfg.max_len)
            with span(self.tracer, "prefill", "engine", rows=G,
                      bucket=bucket):
                t0 = time.perf_counter()
                logits, row_caches, counts = self._jit_prefill(
                    self.params, {"tokens": jnp.asarray(batch_toks)},
                    row_caches, self.banks, jnp.asarray(lengths),
                    row_capacity=self._row_cap_prefill(bucket))
                logits.block_until_ready()
                dt = time.perf_counter() - t0
            self.prefill_shapes.add((R, bucket))
            slots_arr = np.asarray(free[:G], np.int32)
            # Scatter the prefilled rows into their slots' batch rows.
            self.caches = DecodeCaches(
                blocks=self._jit_scatter(self.caches.blocks,
                                         row_caches.blocks,
                                         jnp.asarray(slots_arr)),
                cross=None)
            self._post_prefill(group, slots_arr, lengths, counts, dt,
                               logits,
                               [int(x) for x in lengths[:G]], finished)

    def _chunk_eligible(self, handle: RequestHandle) -> bool:
        """Chunked prefill applies to prompts longer than the chunk size on
        stacks where suffix prefill can restart mid-prompt (see the chunk
        resolution in ``__init__``); sliding-window prompts only while the
        whole prompt fits the attention window (a mid-prompt ring wrap
        would change which positions a later chunk may overwrite)."""
        if not self._chunk_tokens:
            return False
        plen = self._prompt_len(handle)
        if plen <= self._chunk_tokens:
            return False
        return (self.cfg.attn.sliding_window is None or
                plen <= self._C_attn)

    def _admit_paged(self, finished: List[RequestHandle]) -> None:
        while self.queue:
            free = [i for i, h in enumerate(self.slots) if h is None]
            if not free:
                return
            head_peek = self.queue.peek()
            if head_peek is not None and head_peek._snapshot is not None:
                self.queue.popleft()
                if not self._resume_paged(head_peek, free[0]):
                    # Blocked on quota/headroom — back to the head; a
                    # finishing request or expert demotion unblocks it.
                    self.queue.appendleft(head_peek)
                    return
                self._dequeued(head_peek, "resume")
                continue
            if head_peek is not None and self._chunk_eligible(head_peek):
                self.queue.popleft()
                if not self._begin_chunked(head_peek, free[0]):
                    self.queue.appendleft(head_peek)
                    return
                self._dequeued(head_peek, "chunk")
                continue
            R = self._prefill_rows
            limit = min(len(free), R)
            group: List[Tuple[RequestHandle, KVLease, int]] = []
            skipped: List[RequestHandle] = []
            bucket = None
            while self.queue and len(group) < limit:
                h = self.queue.popleft()
                if h._snapshot is not None or self._chunk_eligible(h):
                    # Resumes and chunked admissions only happen from the
                    # head position — requeue and let a later iteration
                    # (or step) take them.
                    skipped.append(h)
                    continue
                plen = self._prompt_len(h)
                toks = np.asarray(h.request.tokens, np.int32).reshape(-1)
                hits: List[int] = []
                if self.trie is not None:
                    max_hit = min((plen - 1) // self._bt, self._nb_per_slot)
                    hits = self.trie.match(toks, max_blocks=max_hit)
                    # Pin the hits NOW: the quota reservation below may
                    # reclaim trie-exclusive blocks under byte pressure,
                    # and a bare match() holds no reference.
                    for blk in hits:
                        self.pool.retain(blk)
                start = len(hits) * self._bt
                b = self._bucket_len(plen - start)
                if bucket is None:
                    bucket = b
                elif b != bucket:
                    for blk in hits:
                        self.pool.release(blk)
                    skipped.append(h)
                    continue
                # Physical headroom: live lease footprints are bounded by
                # nb_per_slot each (release-before-alloc keeps COW from
                # pinning extras), so admission defers when an UNDERSIZED
                # pool (explicit kv_blocks) cannot physically host one more
                # sequence alongside the running ones — instead of crashing
                # a mid-stream alloc. Default sizing never defers here.
                running = sum(s is not None for s in self.slots) + len(group)
                if (running + 1) * self._nb_per_slot > self.pool.n_blocks - 1:
                    for blk in hits:
                        self.pool.release(blk)
                    skipped.append(h)
                    if not group:
                        break       # wait for a running request to finish
                    continue
                quota = self._quota_blocks(plen, start,
                                           h.request.max_new_tokens)
                if not self.pool.try_reserve_quota(quota):
                    # Shared-envelope backpressure: the request waits for
                    # expert demotions / finishing requests to free bytes.
                    for blk in hits:
                        self.pool.release(blk)
                    skipped.append(h)
                    if not group:
                        break       # head blocked — retry next step
                    continue
                lease = KVLease(self.pool, self._nb_per_slot, quota)
                if hits:
                    lease.adopt_prefix(hits, retained=True)
                    h.prefix_hit_tokens = start
                group.append((h, lease, start))
                self._dequeued(h, "prefill")
            self.queue.extendleft(reversed(skipped))
            if not group:
                return
            G = len(group)
            nb = max(1, self._nb_per_slot)
            lengths = np.zeros(R, np.int32)       # TOTAL prompt lengths
            starts = np.zeros(R, np.int32)
            tables = np.full((R, nb), -1, np.int32)
            batch_toks = np.full((R, bucket), self.ecfg.pad_token_id,
                                 np.int32)
            cows: List[Tuple[int, int]] = []
            for r, (h, lease, start) in enumerate(group):
                toks = np.asarray(h.request.tokens, np.int32).reshape(-1)
                plen = toks.shape[0]
                lengths[r], starts[r] = plen, start
                batch_toks[r, :plen - start] = toks[start:]
                # Resolve every block the suffix will write (ring wrap
                # included): fresh allocation or COW of shared blocks.
                for j in self._write_span_blocks(start, plen):
                    phys, cow = lease.ensure(j)
                    if cow >= 0:
                        cows.append((cow, phys))
                tables[r] = lease.table
            self._apply_copies(cows)
            has_prefix = bool((starts > 0).any())
            mamba_rows = init_caches(self.cfg, R, self.ecfg.max_len,
                                     positions=self._mamba_pos).blocks \
                if self._mamba_pos else {}
            call_caches = DecodeCaches(blocks={
                **{p: self.caches.blocks[p] for p in self._attn_pos},
                **mamba_rows}, cross=None)
            with span(self.tracer, "prefill", "engine", rows=G,
                      bucket=bucket):
                t0 = time.perf_counter()
                logits, new_caches, counts = self._jit_prefill_paged(
                    self.params, {"tokens": jnp.asarray(batch_toks)},
                    call_caches, self.banks, jnp.asarray(tables),
                    jnp.asarray(starts), jnp.asarray(lengths),
                    has_prefix=has_prefix,
                    row_capacity=self._row_cap_prefill(bucket))
                logits.block_until_ready()
                dt = time.perf_counter() - t0
            self.prefill_shapes.add((R, bucket))
            slots_arr = np.asarray(free[:G], np.int32)
            blocks = {p: new_caches.blocks[p] for p in self._attn_pos}
            if self._mamba_pos:
                mamba_new = self._jit_scatter(
                    {p: self.caches.blocks[p] for p in self._mamba_pos},
                    {p: new_caches.blocks[p] for p in self._mamba_pos},
                    jnp.asarray(slots_arr))
                blocks.update(mamba_new)
            self.caches = DecodeCaches(blocks=blocks, cross=None)
            # Register newly computed prompt chunks for future sharing (only
            # prompts that fit the logical cache wholly — ring overwrites
            # would otherwise leave stale chunks in the trie).
            for (h, lease, start) in group:
                plen = self._prompt_len(h)
                if self.trie is not None and plen <= self._C_attn:
                    toks = np.asarray(h.request.tokens,
                                      np.int32).reshape(-1)
                    chain = [int(lease.table[j])
                             for j in range(plen // self._bt)]
                    self.trie.insert(toks, chain)
            for (h, lease, _) in group:
                h.lease = lease
            self._post_prefill([h for h, _, _ in group], slots_arr, lengths,
                               counts, dt, logits,
                               [int(lengths[r] - starts[r])
                                for r in range(G)], finished)

    def _post_prefill(self, group: List[RequestHandle],
                      slots_arr: np.ndarray, lengths: np.ndarray, counts,
                      dt: float, logits,
                      computed: List[int],
                      finished: List[RequestHandle]) -> None:
        """Shared post-prefill bookkeeping: counts → backend, TTFT, slot
        assignment, telemetry. ``logits`` ((R, V) f32, device) are the
        last-token logits each row's sampler draws its FIRST token from
        (emission index 0); an all-greedy group ships only the device
        argmax to host. ``computed[r]`` is the number of prompt tokens this
        prefill actually computed for row r (suffix length in paged mode —
        the prefix-share saving shows up here)."""
        R = self._prefill_rows
        G = len(group)
        if self.logits_hook is not None:
            self.logits_hook("prefill", logits, list(enumerate(group)))
        with span(self.tracer, "sample", "engine"):
            amax = np.asarray(jnp.argmax(logits, -1), np.int32)
            samp = self._gather_sampling_rows(
                logits, [r for r, h in enumerate(group)
                         if not h.sampler.greedy])
        counts_np = {k: np.asarray(v) for k, v in counts.items()}
        self.last_row_counts = counts_np
        self.last_counts = {k: v.sum(axis=1) if v.ndim == 3 else v
                            for k, v in counts_np.items()}
        row_valid = np.zeros(R, bool)
        row_valid[:G] = True
        with span(self.tracer, "observe", "residency"):
            stall = self.backend.observe(counts_np, dt, prefill=True,
                                         row_valid=row_valid)
        self._stall_clock += stall
        self._note_degraded(counts_np, list(enumerate(group)))
        for r, handle in enumerate(group):
            slot = int(slots_arr[r])
            handle.stall_exposure_s += stall
            if self.tracer is not None:
                self.tracer.instant("admit", cat="sched", rid=handle.id,
                                    slot=slot, qos=handle.exec_qos)
            tok = int(amax[r]) if r not in samp else \
                handle.sampler.next_token(samp[r], 0)
            handle.tokens.append(tok)
            # Serving TTFT: submit → first token. The engine clock covers
            # queue wait and the prefills admitted ahead of it (virtual
            # under replay(realtime=False) — same accounting, deterministic
            # timestamps); the stall-clock delta charges every MODELED
            # stall since submit (predecessors' demand misses and this
            # forward's own) that wall time never slept. The backend's own
            # ttft_s tracks per-prefill latency.
            handle.first_token_s = self._now()
            handle.ttft_s = (handle.first_token_s - handle.submit_s +
                             self._stall_clock - handle.stall_at_submit)
            self.ttfts.append(handle.ttft_s)
            handle.state = RequestState.RUNNING
            handle.last_progress_s = handle.first_token_s
            handle.slot = slot
            # Per-request attribution needs row-resolved counts; under
            # shard_map expert parallelism only aggregates exist.
            handle.expert_counts = {
                k: v[:, r].astype(np.int64)
                for k, v in counts_np.items() if v.ndim == 3}
            self.slots[slot] = handle
            self.pos[slot] = int(lengths[r])
            self.tokens[slot] = tok
            self.counters["admitted"] += 1
            self.counters["prefill_tokens"] += computed[r]
            self.counters["prefix_hit_tokens"] += handle.prefix_hit_tokens
            if self._done(handle):
                self._finish(handle, finished)
        self.counters["prefills"] += 1

    @staticmethod
    def _gather_sampling_rows(logits, rows: List[int]) -> Dict[int,
                                                               np.ndarray]:
        """Ship the (·, V) f32 logits of only the given batch rows to host
        (device-side gather first): row index → (V,) np array."""
        if not rows:
            return {}
        sub = np.asarray(logits[jnp.asarray(rows, jnp.int32)])
        return {i: sub[j] for j, i in enumerate(rows)}

    def _done(self, handle: RequestHandle) -> bool:
        req = handle.request
        if req.eos_token_id is not None:
            # A speculative verify step can accept a burst with EOS in the
            # MIDDLE: scan every not-yet-checked token (not just the tail)
            # and truncate the output at the first occurrence.
            toks = handle.tokens
            for t in range(handle._eos_scanned, len(toks)):
                if toks[t] == req.eos_token_id:
                    del toks[t + 1:]
                    handle._eos_scanned = len(toks)
                    return True
            handle._eos_scanned = len(toks)
        if len(handle.tokens) >= req.max_new_tokens:
            return True
        # Out of sequence budget: the slot's cache row is full.
        return int(self.pos[handle.slot]) >= self.ecfg.max_len

    def _finish(self, handle: RequestHandle,
                finished: List[RequestHandle]) -> None:
        handle.state = RequestState.FINISHED
        handle.finish_s = self._now()
        self.slots[handle.slot] = None
        if handle.lease is not None:
            # Release block refs + unspent quota; trie-registered blocks
            # keep the trie's own reference and stay warm for future hits.
            handle.lease.close()
        # The vacated row keeps replaying its last token through the batched
        # decode (shape stability), but row_valid masks it out of MoE
        # dispatch and every router count — vacancy is invisible to hotness
        # and residency accounting.
        self.counters["finished"] += 1
        if self.tracer is not None:
            self.tracer.instant("finish", cat="sched", rid=handle.id,
                                tokens=len(handle.tokens))
        finished.append(handle)

    # ------------------------------------------------------------------
    # Preemption: evict-and-resume under budget pressure. Preempting a
    # request snapshots its sequence state HOST-side and genuinely frees
    # its HBM (the KVLease closes, blocks and quota return to the shared
    # envelope); resume re-admits through the normal admission path,
    # adopting prefix-trie hits where the preempted blocks survived and
    # re-uploading only the lanes that did not. Bit-exactness needs no
    # recompute anywhere: the cache-position invariant (cached tokens =
    # seq[:pos], next input = tokens[-1]) plus counter-keyed per-request
    # sampling make the resumed continuation identical to an
    # uninterrupted run.
    # ------------------------------------------------------------------
    def preempt(self, handle: RequestHandle) -> None:
        """Evict a RUNNING request and re-queue it at the front of its QoS
        tier (original queue age preserved — it keeps climbing)."""
        if handle.state is not RequestState.RUNNING:
            raise ValueError(
                f"preempt of a {handle.state.value} request (only RUNNING "
                f"requests hold evictable slot state)")
        slot = handle.slot
        pos = int(self.pos[slot])
        span_start = max(0, pos - self._C_attn) if self._attn_pos else 0
        snap = SlotSnapshot(pos=pos, span_start=span_start)
        if self._attn_pos:
            if self.pool is not None:
                # Valid lanes only: [pos - C_attn, pos) covers everything
                # attention can still read; ring slots in that span are
                # distinct (span <= C_attn <= C_pad), so each position maps
                # to exactly one (block, offset) lane. Lane count pads to a
                # power of two (trash lanes) to bound gather compiles.
                span = np.arange(span_start, pos, dtype=np.int64)
                s = span % self._C_pad
                blk = np.asarray(
                    [int(handle.lease.table[int(x) // self._bt])
                     for x in s], np.int32)
                off = (s % self._bt).astype(np.int32)
                P = 1 << max(0, int(span.size) - 1).bit_length()
                blk_p = np.zeros(P, np.int32)
                off_p = np.zeros(P, np.int32)
                blk_p[:span.size], off_p[:span.size] = blk, off
                attn_now = {p: self.caches.blocks[p]
                            for p in self._attn_pos}
                lanes = _gather_paged_lanes(attn_now,
                                            jnp.asarray(blk_p[None]),
                                            jnp.asarray(off_p[None]))
                snap.attn_lanes = jax.tree_util.tree_map(
                    lambda v: np.asarray(v)[:, :span.size], lanes)
            else:
                snap.attn_rows = jax.tree_util.tree_map(
                    lambda a: np.asarray(a[:, slot]),
                    {p: self.caches.blocks[p] for p in self._attn_pos})
        if self._mamba_pos:
            snap.mamba_rows = jax.tree_util.tree_map(
                lambda a: np.asarray(a[:, slot]),
                {p: self.caches.blocks[p] for p in self._mamba_pos})
        if handle.lease is not None:
            # Register the full prompt+generated chunks before closing the
            # lease: the trie keeps those blocks warm (its own reference),
            # so an early resume adopts them and skips the host re-upload
            # entirely. Correctness never depends on trie survival — the
            # host snapshot covers every lane.
            if self.trie is not None and pos <= self._C_attn:
                seq = np.concatenate([
                    np.asarray(handle.request.tokens,
                               np.int32).reshape(-1),
                    np.asarray(handle.tokens, np.int32)])
                chain = [int(handle.lease.table[j])
                         for j in range(pos // self._bt)]
                if chain:
                    self.trie.insert(seq[:pos], chain)
            handle.lease.close()
            handle.lease = None
        self.slots[slot] = None
        self.tokens[slot] = self.ecfg.pad_token_id
        handle.slot = None
        handle.state = RequestState.QUEUED
        handle._snapshot = snap
        handle.preempts += 1
        self.counters["preemptions"] += 1
        if self.tracer is not None:
            self.tracer.instant("preempt", cat="sched", rid=handle.id,
                                slot=slot, pos=pos)
        self.queue.appendleft(handle)
        self._queued(handle)

    def _maybe_preempt(self) -> None:
        """After admission: if a higher-class request is still queued while
        strictly lower-class work runs, evict one victim (lowest class
        first, most remaining work first) so the head admits next step.
        Eviction counts are capped per request — aged batch work cannot be
        preempted forever."""
        if not self.sched.cfg.preemption or not self.queue:
            return
        head = self.queue.peek()
        if head is None:
            return
        running = [(i, h) for i, h in enumerate(self.slots)
                   if h is not None and h.state is RequestState.RUNNING]
        victim = self.sched.pick_victim(running, head.qos)
        if victim is not None:
            self.preempt(victim[1])

    def _finish_resume(self, handle: RequestHandle, slot: int,
                       snap: SlotSnapshot) -> None:
        self.slots[slot] = handle
        handle.slot = slot
        handle.state = RequestState.RUNNING
        handle._snapshot = None
        self.pos[slot] = snap.pos
        self.tokens[slot] = handle.tokens[-1]
        self.counters["resumes"] += 1
        if self.tracer is not None:
            self.tracer.instant("resume", cat="sched", rid=handle.id,
                                slot=slot, pos=snap.pos)

    def _scatter_snapshot_rows(self, rows: Dict[str, np.ndarray],
                               slot: int) -> None:
        """Upload whole per-slot cache rows (dense attention / mamba state)
        from a host snapshot into ``slot``'s batch row."""
        sub_old = {p: self.caches.blocks[p] for p in rows}
        sub_new = self._jit_scatter(
            sub_old,
            jax.tree_util.tree_map(lambda a: jnp.asarray(a)[:, None], rows),
            jnp.asarray(np.asarray([slot], np.int32)))
        self.caches = DecodeCaches(
            blocks={**self.caches.blocks, **sub_new}, cross=None)

    def _resume_dense(self, handle: RequestHandle, slot: int) -> None:
        """Dense-mode resume: scatter the snapshot rows back (any free
        slot — row contents are position-indexed, not slot-bound). Cannot
        fail: dense rows are preallocated, there is no quota."""
        snap = handle._snapshot
        rows: Dict[str, np.ndarray] = {}
        if snap.attn_rows:
            rows.update(snap.attn_rows)
        if snap.mamba_rows:
            rows.update(snap.mamba_rows)
        if rows:
            self._scatter_snapshot_rows(rows, slot)
        self._finish_resume(handle, slot, snap)

    def _resume_paged(self, handle: RequestHandle, slot: int) -> bool:
        """Paged-mode resume: the same admission discipline as a fresh
        request (trie match → pin, physical headroom, quota gate), then
        scatter the host lanes the trie could not cover. False = blocked
        (quota/headroom) — the caller requeues the handle at the head."""
        snap = handle._snapshot
        pos = snap.pos
        hits: List[int] = []
        if self.trie is not None and pos <= self._C_attn:
            seq = np.concatenate([
                np.asarray(handle.request.tokens, np.int32).reshape(-1),
                np.asarray(handle.tokens, np.int32)])
            max_hit = min(pos // self._bt, self._nb_per_slot)
            hits = self.trie.match(seq[:pos], max_blocks=max_hit)
            for blk in hits:
                self.pool.retain(blk)
        start = len(hits) * self._bt
        running = sum(s is not None for s in self.slots)
        if (running + 1) * self._nb_per_slot > self.pool.n_blocks - 1:
            for blk in hits:
                self.pool.release(blk)
            return False
        remaining = handle.request.max_new_tokens - len(handle.tokens)
        quota = self._quota_blocks(pos, start, remaining)
        if not self.pool.try_reserve_quota(quota):
            for blk in hits:
                self.pool.release(blk)
            return False
        lease = KVLease(self.pool, self._nb_per_slot, quota)
        if hits:
            lease.adopt_prefix(hits, retained=True)
        lo = max(start, snap.span_start)
        span = np.arange(lo, pos, dtype=np.int64)
        if span.size:
            cows: List[Tuple[int, int]] = []
            s = span % self._C_pad
            for j in sorted({int(x) // self._bt for x in s}):
                phys, cow = lease.ensure(j)
                if cow >= 0:
                    cows.append((cow, phys))
            self._apply_copies(cows)
            blk = np.asarray([int(lease.table[int(x) // self._bt])
                              for x in s], np.int32)
            off = (s % self._bt).astype(np.int32)
            sel = (span - snap.span_start).astype(np.int64)
            P = 1 << max(0, int(span.size) - 1).bit_length()
            mask = np.zeros((1, P), bool)
            mask[0, :span.size] = True
            blk_p = np.zeros(P, np.int32)
            off_p = np.zeros(P, np.int32)
            blk_p[:span.size], off_p[:span.size] = blk, off
            def _lane(v):
                lane = v[:, sel]
                pad = np.zeros((1, P - span.size) + lane.shape[2:],
                               lane.dtype)
                return jnp.asarray(np.concatenate([lane, pad], axis=1))

            lanes = jax.tree_util.tree_map(_lane, snap.attn_lanes)
            attn_sub = {p: self.caches.blocks[p] for p in self._attn_pos}
            attn_sub = _restore_paged_lanes(attn_sub, lanes,
                                            jnp.asarray(blk_p[None]),
                                            jnp.asarray(off_p[None]),
                                            jnp.asarray(mask))
            self.caches = DecodeCaches(
                blocks={**self.caches.blocks, **attn_sub}, cross=None)
        if self._mamba_pos and snap.mamba_rows:
            self._scatter_snapshot_rows(snap.mamba_rows, slot)
        handle.lease = lease
        self._finish_resume(handle, slot, snap)
        return True

    # ------------------------------------------------------------------
    # Chunked prefill: long prompts admit immediately (slot + lease +
    # full quota) but prefill one chunk per engine step, interleaved with
    # everyone else's decode — a single long admission stops inflating
    # neighbors' TPOT by the whole prompt's prefill latency. Each chunk is
    # a suffix prefill through the PR-3 paged path (cached prefix ⊕
    # suffix), at an existing ladder-bucket shape.
    # ------------------------------------------------------------------
    def _begin_chunked(self, handle: RequestHandle, slot: int) -> bool:
        """Admit a long prompt for chunked prefill: trie match + quota
        gate exactly like normal admission, but no forward yet — the
        handle enters PREFILLING and ``_advance_chunk_prefills`` feeds it
        chunk by chunk. False = blocked on quota/headroom."""
        toks = np.asarray(handle.request.tokens, np.int32).reshape(-1)
        plen = toks.shape[0]
        hits: List[int] = []
        if self.trie is not None:
            max_hit = min((plen - 1) // self._bt, self._nb_per_slot)
            hits = self.trie.match(toks, max_blocks=max_hit)
            for blk in hits:
                self.pool.retain(blk)
        start = len(hits) * self._bt
        running = sum(s is not None for s in self.slots)
        if (running + 1) * self._nb_per_slot > self.pool.n_blocks - 1:
            for blk in hits:
                self.pool.release(blk)
            return False
        quota = self._quota_blocks(plen, start,
                                   handle.request.max_new_tokens)
        if not self.pool.try_reserve_quota(quota):
            for blk in hits:
                self.pool.release(blk)
            return False
        lease = KVLease(self.pool, self._nb_per_slot, quota)
        if hits:
            lease.adopt_prefix(hits, retained=True)
            handle.prefix_hit_tokens = start
        handle.lease = lease
        handle._chunk_pos = start
        handle.state = RequestState.PREFILLING
        handle.slot = slot
        self.slots[slot] = handle
        self.pos[slot] = 0
        self.tokens[slot] = self.ecfg.pad_token_id
        self.counters["admitted"] += 1
        self.counters["prefix_hit_tokens"] += start
        return True

    def _advance_chunk_prefills(self, finished: List[RequestHandle]) -> None:
        """Advance chunked prefills by ONE chunk this step (one batched
        suffix-prefill forward over same-bucket chunk rows — per-step cost
        stays bounded by one prefill dispatch). The final chunk emits the
        request's first token and flips it to RUNNING, so it decodes with
        everyone else from this very step."""
        chunking = [(i, h) for i, h in enumerate(self.slots)
                    if h is not None and
                    h.state is RequestState.PREFILLING]
        if not chunking:
            return

        def next_chunk(h: RequestHandle) -> int:
            return min(self._chunk_tokens,
                       self._prompt_len(h) - h._chunk_pos)

        R = self._prefill_rows
        bucket = self._bucket_len(next_chunk(chunking[0][1]))
        group = [(i, h) for i, h in chunking
                 if self._bucket_len(next_chunk(h)) == bucket][:R]
        G = len(group)
        nb = max(1, self._nb_per_slot)
        lengths = np.zeros(R, np.int32)      # prefix + chunk (total so far)
        starts = np.zeros(R, np.int32)
        tables = np.full((R, nb), -1, np.int32)
        batch_toks = np.full((R, bucket), self.ecfg.pad_token_id, np.int32)
        cows: List[Tuple[int, int]] = []
        for r, (i, h) in enumerate(group):
            toks = np.asarray(h.request.tokens, np.int32).reshape(-1)
            cpos = h._chunk_pos
            clen = next_chunk(h)
            starts[r], lengths[r] = cpos, cpos + clen
            batch_toks[r, :clen] = toks[cpos:cpos + clen]
            for j in self._write_span_blocks(cpos, cpos + clen):
                phys, cow = h.lease.ensure(j)
                if cow >= 0:
                    cows.append((cow, phys))
            tables[r] = h.lease.table
        self._apply_copies(cows)
        call_caches = DecodeCaches(
            blocks={p: self.caches.blocks[p] for p in self._attn_pos},
            cross=None)
        with span(self.tracer, "prefill", "engine", rows=G, bucket=bucket):
            t0 = time.perf_counter()
            logits, new_caches, counts = self._jit_prefill_paged(
                self.params, {"tokens": jnp.asarray(batch_toks)},
                call_caches, self.banks, jnp.asarray(tables),
                jnp.asarray(starts), jnp.asarray(lengths),
                has_prefix=True, row_capacity=self._row_cap_prefill(bucket))
            logits.block_until_ready()
            dt = time.perf_counter() - t0
        self.prefill_shapes.add((R, bucket))
        self.caches = DecodeCaches(
            blocks={**self.caches.blocks,
                    **{p: new_caches.blocks[p] for p in self._attn_pos}},
            cross=None)
        counts_np = {k: np.asarray(v) for k, v in counts.items()}
        self.last_row_counts = counts_np
        self.last_counts = {k: v.sum(axis=1) if v.ndim == 3 else v
                            for k, v in counts_np.items()}
        row_valid = np.zeros(R, bool)
        row_valid[:G] = True
        with span(self.tracer, "observe", "residency"):
            stall = self.backend.observe(counts_np, dt, prefill=True,
                                         row_valid=row_valid)
        self._stall_clock += stall
        for _, h in group:
            h.stall_exposure_s += stall
        self._note_degraded(counts_np, [(r, h) for r, (i, h)
                                        in enumerate(group)])
        if self.logits_hook is not None:
            self.logits_hook("prefill", logits,
                             [(r, h) for r, (i, h) in enumerate(group)])
        with span(self.tracer, "sample", "engine"):
            amax = np.asarray(jnp.argmax(logits, -1), np.int32)
            samp = self._gather_sampling_rows(
                logits, [r for r, (i, h) in enumerate(group)
                         if not h.sampler.greedy and
                         int(lengths[r]) == self._prompt_len(h)])
        for r, (i, h) in enumerate(group):
            clen = int(lengths[r] - starts[r])
            h._chunk_pos = int(lengths[r])
            self.counters["prefill_tokens"] += clen
            sub = {k: v[:, r].astype(np.int64)
                   for k, v in counts_np.items() if v.ndim == 3}
            if h.expert_counts is None:
                h.expert_counts = sub
            else:
                for k, v in sub.items():
                    if k in h.expert_counts:
                        h.expert_counts[k] += v
            plen = self._prompt_len(h)
            if h._chunk_pos < plen:
                continue                     # more chunks to go
            # Final chunk: register the whole prompt for sharing, emit the
            # first token, flip to RUNNING.
            if self.trie is not None and plen <= self._C_attn:
                toks = np.asarray(h.request.tokens, np.int32).reshape(-1)
                chain = [int(h.lease.table[j])
                         for j in range(plen // self._bt)]
                self.trie.insert(toks, chain)
            tok = int(amax[r]) if r not in samp else \
                h.sampler.next_token(samp[r], 0)
            h.tokens.append(tok)
            h.first_token_s = self._now()
            h.ttft_s = (h.first_token_s - h.submit_s +
                        self._stall_clock - h.stall_at_submit)
            self.ttfts.append(h.ttft_s)
            h.state = RequestState.RUNNING
            h.last_progress_s = h.first_token_s
            self.pos[i] = plen
            self.tokens[i] = tok
            if self._done(h):
                self._finish(h, finished)
        self.counters["prefills"] += 1
        self.counters["chunk_prefills"] += 1

    # ------------------------------------------------------------------
    def step(self) -> List[RequestHandle]:
        """One engine step: drop expired batch work, admit queued requests
        into free slots (resumes and chunked admissions included), preempt
        for a blocked higher class, advance chunked prefills by one chunk,
        then advance every running request grouped by execution tier —
        premium/standard on the mixed-precision banks (with speculative
        bursts when enabled), batch tier on the all-lo banks. One group —
        uniform-class traffic — is exactly the untiered engine. Returns
        the handles that finished this step."""
        with span(self.tracer, "step", "engine"):
            finished: List[RequestHandle] = []
            if self._watchdog is not None:
                # Scan BEFORE this step makes progress: a request that wedged
                # during prior steps still carries its stale stamp here, and a
                # cancelled promotion's slot is re-admittable this same step.
                self._watchdog.scan(self)
            ready = getattr(self.backend, "serving_ready", None)
            if ready is not None and not ready():
                # Streaming cold start: the residency ladder is still
                # materializing — keep the backend's staging windows running
                # and hold admission (requests queue; no forward may observe
                # a partially materialized expert).
                with span(self.tracer, "tick", "residency"):
                    self.backend.tick()
                return finished
            self._shed_expired()
            self._admit(finished)
            self._maybe_preempt()
            self._advance_chunk_prefills(finished)
            active = [(i, h) for i, h in enumerate(self.slots)
                      if h is not None and h.state is RequestState.RUNNING]
            if active:
                groups = self.sched.decode_groups(active,
                                                  self._spec is not None)
                guard = len(groups) > 1 and bool(self._mamba_pos)
                for kind, rows in groups:
                    # The speculative round falls back to the single-token
                    # step when no row has draft headroom (e.g. one token
                    # remaining).
                    if kind == "spec" and self._spec.round(rows, finished):
                        continue
                    self._decode_one(rows, finished, lo=(kind == "lo"),
                                     guard_ssm=guard)
            with span(self.tracer, "tick", "residency"):
                self.backend.tick()
            if self.obs is not None:
                self._step_obs()
            return finished

    def _step_obs(self) -> None:
        """Step-boundary observability: one ``step`` trace instant with the
        per-step gauges, plus the metrics sampling cadence. Every value is
        count-derived or modeled (never a wall-clock duration), so
        virtual-clock replays trace byte-identically."""
        a0, p0, l0 = self._obs_prev
        self._obs_prev = (self._disp_active_sum, self._disp_pad_sum,
                          self._disp_layers)
        d_lay = self._disp_layers - l0
        active = (self._disp_active_sum - a0) / d_lay if d_lay else 0.0
        pad = (self._disp_pad_sum - p0) / d_lay if d_lay else 0.0
        mix_fn = getattr(self.backend, "residency_mix", None)
        mix = mix_fn() if mix_fn is not None else {"hi": 0, "lo": 0,
                                                   "host": 0}
        headroom = float(self.budget.headroom_frac())
        depths = self.queue.depths()
        running = sum(h is not None for h in self.slots)
        step = self.counters["steps"]
        if self.tracer is not None:
            self.tracer.instant(
                "step", cat="engine", step=step,
                active_experts=round(active, 4), pad_ratio=round(pad, 4),
                hi=mix["hi"], lo=mix["lo"], host=mix["host"],
                headroom=round(headroom, 6), queued=len(self.queue),
                running=running)
        if self.metrics is not None and step % self._sample_every == 0:
            m = self.metrics
            m.gauge("engine_active_experts",
                    "mean experts with routed tokens per layer-step").set(
                        active)
            m.gauge("engine_dispatch_pad_ratio",
                    "padding fraction of the MoE dispatch layout").set(pad)
            m.gauge("residency_hi_cells").set(mix["hi"])
            m.gauge("residency_lo_cells").set(mix["lo"])
            m.gauge("residency_host_cells").set(mix["host"])
            m.gauge("budget_headroom_frac",
                    "shared HBM envelope headroom").set(headroom)
            for q, d in depths.items():
                m.gauge(f"queue_depth_{q}").set(d)
            if self._spec is not None:
                m.gauge("spec_accept_rate").set(
                    self._spec.accepted_total /
                    max(1, self._spec.draft_total))
            m.sample(step=step, active_experts=round(active, 4),
                     pad_ratio=round(pad, 4), hi=mix["hi"], lo=mix["lo"],
                     host=mix["host"], headroom=round(headroom, 6),
                     **{f"queued_{q}": d for q, d in depths.items()})

    def _decode_one(self, active, finished: List[RequestHandle],
                    lo: bool = False, guard_ssm: bool = False) -> None:
        """Advance the given active rows by exactly one sampled token.
        ``lo=True`` dispatches on the all-lo expert banks (batch tier):
        the same buffers with every hi slot disowned — same pytree, so the
        already-compiled decode executables serve both tiers. Rows of
        other groups ride along masked out of dispatch and counts;
        ``guard_ssm`` protects their recurrent state (see _merge_rows)."""
        row_valid = np.zeros(self.ecfg.max_slots, bool)
        for i, _ in active:
            row_valid[i] = True
        banks = all_lo_banks(self.banks, self._lo_owner_cache) if lo \
            else self.banks
        # The decode dispatch advances recurrent (SSM/conv) state for every
        # row, valid or not — copy the pre-step leaves so rows belonging to
        # *other* tier groups can be merged back afterwards. (Copy, not
        # alias: the decode jits donate the cache argument.)
        ssm_old = {p: jnp.array(self.caches.blocks[p])
                   for p in self._mamba_pos} if guard_ssm else None
        with span(self.tracer, "decode", "engine", rows=len(active)):
            t0 = time.perf_counter()
            if self.pool is not None:
                n = self.ecfg.max_slots
                wblk = np.zeros(n, np.int32)     # vacant rows → trash block
                woff = np.zeros(n, np.int32)
                cows: List[Tuple[int, int]] = []
                for i, h in active:
                    wblk[i], woff[i] = self._ensure_write(
                        h.lease, int(self.pos[i]), cows)
                self._apply_copies(cows)
                logits, self.caches, counts = self._jit_decode_paged(
                    self.params, jnp.asarray(self.tokens),
                    jnp.asarray(self.pos), self.caches, banks,
                    jnp.asarray(row_valid),
                    jnp.asarray(self._block_tables()),
                    jnp.asarray(wblk), jnp.asarray(woff))
            else:
                logits, self.caches, counts = self._jit_decode(
                    self.params, jnp.asarray(self.tokens),
                    jnp.asarray(self.pos), self.caches, banks,
                    jnp.asarray(row_valid))
            logits.block_until_ready()
            dt = time.perf_counter() - t0
        if self.logits_hook is not None:
            self.logits_hook("decode", logits, list(active))
        if ssm_old is not None:
            sub_new = {p: self.caches.blocks[p] for p in self._mamba_pos}
            merged = _merge_rows(sub_new, ssm_old, jnp.asarray(row_valid))
            self.caches = DecodeCaches(
                blocks={**self.caches.blocks, **merged}, cross=None)
        counts_np = {k: np.asarray(v) for k, v in counts.items()}
        self.last_row_counts = counts_np
        self.last_counts = {k: v.sum(axis=1) if v.ndim == 3 else v
                            for k, v in counts_np.items()}
        self._note_dispatch(counts_np)
        with span(self.tracer, "observe", "residency"):
            stall = self.backend.observe(counts_np, dt, prefill=False,
                                         row_valid=row_valid)
        self._stall_clock += stall
        if stall:
            for _, h in active:
                h.stall_exposure_s += stall
        latency = dt + stall
        self.decode_times.append(latency)
        self._tpot_sum += latency * len(active)
        self._tpot_tokens += len(active)
        self._tpot_ema = latency if self._tpot_ema == 0.0 else \
            0.9 * self._tpot_ema + 0.1 * latency
        self._note_degraded(counts_np, active)
        with span(self.tracer, "sample", "engine"):
            # Greedy fast path: only the (B,) device argmax crosses to host;
            # full (·, V) logits rows ship only for requests that sample
            # (device-gathered, so greedy neighbors stay off the transfer).
            amax = np.asarray(jnp.argmax(logits, -1), np.int32)
            samp = self._gather_sampling_rows(
                logits, [i for i, h in active if not h.sampler.greedy])
            for i, handle in active:
                tok = int(amax[i]) if i not in samp else \
                    handle.sampler.next_token(samp[i], len(handle.tokens))
                handle.tokens.append(tok)
                handle.step_times.append(latency)
                if self._watchdog is not None:
                    handle.last_progress_s = self._now()
                for k, v in counts_np.items():
                    if v.ndim == 3 and k in handle.expert_counts:
                        handle.expert_counts[k] += v[:, i]
                self.tokens[i] = tok
                self.pos[i] += 1
                if self._done(handle):
                    self._finish(handle, finished)
        self.counters["steps"] += 1

    def _note_degraded(self, counts_np: Dict[str, np.ndarray],
                       rows) -> None:
        """Flag requests whose forward routed through a quarantined expert
        cell (host-served after repeated staging failures): they complete,
        but at degraded quality — the chaos-parity contract excludes them.
        ``rows``: (row index into the counts' row dim, handle) pairs."""
        if self._degraded_fn is None:
            return
        cells = self._degraded_fn()
        if not cells:
            return
        for pos, q in cells.items():
            v = counts_np.get(pos)
            if v is None or v.ndim != 3:       # (nsb, R, E)
                continue
            hit = ((v > 0) & q[:, None, :]).any(axis=(0, 2))
            for r, h in rows:
                if hit[r]:
                    h.degraded = True

    def drain(self) -> List[RequestHandle]:
        """Run ``step()`` until no request is queued or running; returns the
        handles finished during the drain, in completion order.

        A queued request blocked on the shared HBM envelope normally waits
        for in-flight work (finishing requests, expert demotions) to free
        bytes. If the engine goes fully idle and hundreds of consecutive
        steps (each of which ticks the backend, so pending transitions and
        demotions do get their chance) admit nothing, no future step can
        change anything — raise instead of busy-spinning forever."""
        done: List[RequestHandle] = []
        stalled = 0
        while self.queue or any(h is not None for h in self.slots):
            before = len(self.queue)
            done.extend(self.step())
            stalled = self._check_admission_stall(stalled, before)
        return done

    def _check_admission_stall(self, stalled: int, queue_before: int) -> int:
        """Post-step progress accounting for the serving loops: bump (and
        eventually trip) the stall counter when the engine sits fully idle
        with queued work it could not admit."""
        ready = getattr(self.backend, "serving_ready", None)
        if ready is not None and not ready():
            return 0    # cold start still staging — queueing is progress
        idle = not any(h is not None for h in self.slots)
        if self.queue and idle and len(self.queue) == queue_before:
            stalled += 1
            if stalled > 256:
                raise EngineStallError(self._stall_snapshot())
            return stalled
        return 0

    def _stall_snapshot(self) -> Dict[str, object]:
        """Diagnostic state for ``EngineStallError``: everything an
        operator needs to tell a budget wedge from a stuck transfer from
        a cold start that never finished."""
        now = self._now()
        frac = getattr(self.backend, "ready_frac", None)
        pend_fn = getattr(self.backend, "pending_promotions", None)
        pending = []
        if pend_fn is not None:
            pending = [{"pos": str(pos), "layer": int(l), "expert": int(e),
                        "age_s": float(age)}
                       for pos, l, e, age in pend_fn(now)]
        return {
            "queued_total": len(self.queue),
            "queue_depths": self.queue.depths(),
            "running": sum(1 for h in self.slots if h is not None),
            "budget_used": int(self.budget.used),
            "budget_cap": int(self.budget.cap),
            "budget_headroom_frac": float(self.budget.headroom_frac()),
            "residency_ready_frac":
                float(frac()) if frac is not None else 1.0,
            "pending_promotions": pending,
            "counters": dict(self.counters),
        }

    def replay(self, stream, realtime: bool = True,
               virtual_step_s: float = 2e-3) -> List[RequestHandle]:
        """Serve an arrival-timed request stream (e.g. ``RequestStream``).

        ``realtime=True`` (benchmarks): each request is submitted once the
        wall clock — measured from replay start — passes its ``arrival_s``
        offset, so queueing delay and TTFT reflect the offered load. When
        the engine goes idle before the next arrival it skips ahead instead
        of spinning.

        ``realtime=False`` (CI / tests): a **virtual clock** replaces
        ``perf_counter`` — it advances ``virtual_step_s`` per engine step
        and fast-forwards across idle gaps — so the interleaving of
        arrivals with admissions (and therefore every generated token) is
        fully deterministic, machine speed be damned.

        Returns handles in arrival order; all are FINISHED on return."""
        requests = list(stream)
        handles: List[RequestHandle] = []
        i = 0
        now = 0.0
        stalled = 0
        t0 = time.perf_counter()
        try:
            if not realtime:
                # Route ALL engine time accounting (submit/enqueue stamps,
                # ttft, finish, queue aging, deadline expiry) through the
                # virtual clock, so virtual-clock runs report the same
                # accounting semantics realtime ones do.
                self._clock = now
            while i < len(requests) or self.queue or \
                    any(h is not None for h in self.slots):
                if realtime:
                    now = time.perf_counter() - t0
                while i < len(requests) and requests[i].arrival_s <= now:
                    handles.append(self.submit(requests[i]))
                    i += 1
                if i < len(requests) and not self.queue and \
                        all(h is None for h in self.slots):
                    # Idle gap until the next arrival — fast-forward.
                    if not realtime:
                        now = requests[i].arrival_s
                        self._clock = now
                    handles.append(self.submit(requests[i]))
                    i += 1
                before = len(self.queue)
                self.step()
                if i >= len(requests):
                    # All arrivals in: the same dead-admission detection as
                    # drain() (a permanently envelope-blocked head would
                    # otherwise spin this loop forever).
                    stalled = self._check_admission_stall(stalled, before)
                if not realtime:
                    now += virtual_step_s
                    self._clock = now
        finally:
            self._clock = None
        return handles

    def flush(self) -> None:
        """Barrier on the backend's in-flight residency transitions."""
        self.backend.flush()

    # ------------------------------------------------------------------
    def generate(self, batch: Dict, n_tokens: int, sampling=None,
                 qos=None, deadline_ms=None):
        """Whole-batch compat shim over submit + drain.

        ``batch``: ``{"tokens": (B, S)}`` with B ≤ ``max_slots``.
        ``sampling``: optional ``SamplingParams`` applied to every row
        (default greedy — bit-identical to the pre-sampler shim); validated
        at ``submit`` like any request. Returns ``(tokens (B, n_tokens),
        ttft_s, per_step_s)`` token-for-token identical to driving
        submit/step/drain directly.
        Token-only: multimodal batches (``image_embeds``/``audio_embeds``)
        are not supported by the request path and are rejected loudly.
        """
        extra = set(batch) - {"tokens"}
        if extra:
            raise NotImplementedError(
                f"InferenceEngine serves token-only requests; unsupported "
                f"batch keys: {sorted(extra)}. Use repro.models.prefill/"
                f"decode_step directly for multimodal batches.")
        toks = np.asarray(batch["tokens"])
        B = toks.shape[0]
        if B > self.ecfg.max_slots:
            raise ValueError(f"batch {B} > max_slots={self.ecfg.max_slots}")
        if toks.shape[1] + n_tokens - 1 > self.ecfg.max_len:
            # The shim stacks a dense (B, n_tokens) grid — truncation would
            # break it, so the whole batch must fit the slot budget.
            raise ValueError(
                f"{toks.shape[1]}-token prompts + {n_tokens} new tokens "
                f"exceed max_len={self.ecfg.max_len}")
        handles = [self.submit(Request(tokens=toks[i],
                                       max_new_tokens=n_tokens,
                                       sampling=sampling, qos=qos,
                                       deadline_ms=deadline_ms))
                   for i in range(B)]
        n_before = len(self.decode_times)
        self.drain()
        out = jnp.asarray(np.stack([h.token_array() for h in handles], 0))
        ttft = float(np.mean([h.ttft_s for h in handles]))
        return out, ttft, self.decode_times[n_before:]

    # ------------------------------------------------------------------
    def stats(self) -> Dict[str, float]:
        """Backend's uniform serving stats merged with engine counters.
        ``ttft_s`` is the request-level submit→first-token mean (queue wait
        included); the backend's per-prefill latency stays available via
        ``backend.stats()``. Paged engines add the KV-pool gauges:
        ``kv_blocks_in_use`` / ``kv_bytes_in_use`` (pool accounting, quota
        included) and the prefix-sharing meters ``prefix_hit_tokens`` /
        ``prefill_tokens`` (prompt tokens served from the trie vs actually
        computed)."""
        out = dict(self.backend.stats())
        if self.ttfts:
            out["ttft_s"] = float(np.mean(self.ttfts))
        if self._tpot_tokens:
            # Time per OUTPUT token: a speculative round's latency spreads
            # over every token it emitted (the backend's own tpot_s stays
            # per-forward — per-dispatch latency).
            out["tpot_s"] = self._tpot_sum / self._tpot_tokens
        out.update({k: float(v) for k, v in self.counters.items()})
        out["prefill_compiles"] = float(len(self.prefill_shapes))
        if self._disp_layers:
            out["active_experts"] = self._disp_active_sum / self._disp_layers
            out["dispatch_pad_ratio"] = self._disp_pad_sum / \
                self._disp_layers
        out["spec_row_rounds"] = 0.0
        if self._spec is not None:
            out.update(self._spec.stats())
        # ENGINE_STAT_KEYS are emitted unconditionally (zeros where N/A) so
        # the stats schema is configuration-independent.
        if self.pool is not None:
            out["kv_blocks_in_use"] = float(self.pool.blocks_in_use)
            out["kv_bytes_in_use"] = float(self.pool.bytes_in_use)
        else:
            out["kv_blocks_in_use"] = 0.0
            out["kv_bytes_in_use"] = 0.0
        out["prefix_trie_nodes"] = float(self.trie.n_nodes) \
            if self.trie is not None else 0.0
        return out

    def device_bytes(self) -> int:
        return self.backend.device_bytes()
