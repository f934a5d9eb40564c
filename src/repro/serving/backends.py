"""Pluggable expert-residency backends for the serving engine.

The paper's DynaExq controller is one point in a family of budget-constrained
residency strategies (static PTQ, offloading/prefetch, dense fp16). Each
strategy is a ``ResidencyBackend``: the engine owns requests, caches and the
jitted forward closures; the backend owns *where expert weights live* and
what moving them costs. All four backends run through literally the same
``InferenceEngine.step()`` loop, so the DynaExq-vs-offload comparison is
structural, not an artifact of two different serving loops.

Protocol (one backend instance per engine):

* ``materialize_banks(cfg, params, kv_bytes, budget=None)`` — build the
  device-resident weight tiers; returns the per-MoE-position bank mapping
  the engine passes into the jitted forward (``None`` ⇒ dense bf16 experts
  from ``params``). ``kv_bytes`` is the KV pool's own accounting (the
  engine's block math — no backend re-derives KV sizes); ``budget`` is the
  engine's shared ``BudgetTracker``: residency strategies that gate byte
  movement (DynaExq's hi tier) reserve through account-scoped views of it,
  so expert promotions and KV block admission contend for ONE HBM envelope.
* ``observe(counts, compute_s, prefill, row_valid)`` — per-forward
  router-trace hook; returns modeled *stall seconds* to charge to the
  step's critical path (non-zero only for demand-fetch strategies like
  offloading). ``counts`` values are either pre-masked (L, E) aggregates or
  row-resolved (L, R, E) arrays, in which case ``row_valid`` ((R,) bool)
  masks vacant/padding rows before they reach hotness or residency
  accounting — no backend ever sees phantom traffic.
* ``tick()`` — window boundary: run policies, publish completed transitions.
* ``device_bytes()`` — resident expert bytes under this strategy's budget.
* ``stats()`` — uniform serving stats: ``{ttft_s, tpot_s, stall_s,
  bytes_moved, promotions, demotions}`` (zeros where N/A), plus
  backend-specific extras.
* ``flush()`` — barrier on in-flight transitions (shutdown / tests).
"""
from __future__ import annotations

import dataclasses
import math
import os
import time
from collections import OrderedDict, deque
from typing import (Dict, Iterable, Optional, Protocol, Tuple,
                    runtime_checkable)

import jax
import numpy as np

from repro.core import (ControllerConfig, DynaExqController, build_bank,
                        expert_hi_nbytes, expert_lo_nbytes, plan_budget)
from repro.core.allocator import AllocatorConfig, GlobalAllocator
from repro.core.budget import BudgetTracker
from repro.core.controller import EPCoordinator, RebalanceConfig
from repro.core.hotness import mask_row_counts
from repro.core.ver import build_bank_empty
from repro.fault.inject import FaultInjector, FaultPlan
from repro.fault.retry import RetryExhausted, RetryPolicy
from repro.models.config import ArchConfig
from repro.quant.sensitivity import load_sensitivity, normalize
from repro.serving.hoststore import FetchModel, HostExpertStore
from repro.serving.streaming import ShardSource, hotness_stage_order

GiB = 1 << 30

#: Keys every backend's ``stats()`` must return (zeros where N/A). The
#: speculative-decoding meters (``accept_rate``/``draft_tokens``/
#: ``verified_tokens``/``spec_rounds``) are part of the uniform schema so
#: every benchmark row is machine-comparable whether or not speculation ran;
#: the engine overwrites them with live values when its SpecDecoder is on.
#: Likewise the MoE dispatch gauges: ``active_experts`` (mean experts with
#: ≥1 routed token per layer-step) and ``dispatch_pad_ratio`` (fraction of
#: expert-GEMM rows that were padding under the configured layout) — the
#: engine fills them from its per-forward router counts.
#: The QoS-scheduler meters (``preemptions``/``resumes``/``shed_requests``/
#: ``downgraded``) join the schema the same way: zeros from every backend,
#: overwritten by the engine's live scheduler counters.
#: ``host_fetches`` counts demand reads from the host tier — OffloadBackend's
#: cache misses and DynaExq's routed-but-host-resident experts land in the
#: same column, so "how often did the critical path touch host memory" is
#: directly comparable across residency strategies.
#: The fault-tolerance meters (``retries``: transfer attempts retried under
#: the shared backoff policy; ``fault_cancels``: promotions/migrations
#: cancelled by a fault, timeout, or publish-time integrity check) join the
#: uniform schema: zeros everywhere the transfer plane is fault-free.
STAT_KEYS = ("ttft_s", "tpot_s", "stall_s", "bytes_moved",
             "promotions", "demotions",
             "accept_rate", "draft_tokens", "verified_tokens", "spec_rounds",
             "active_experts", "dispatch_pad_ratio",
             "preemptions", "resumes", "shed_requests", "downgraded",
             "host_fetches", "retries", "fault_cancels")

#: The schema contract: ``backend.stats()`` returns EXACTLY
#: ``STAT_KEYS + type(backend).STAT_EXTRAS`` — extras are declared per
#: class, not leaked ad hoc, so downstream consumers (benchmark JSON,
#: metrics export, report tables) can pin columns. Enforced by
#: ``tests/test_obs.py``.


def _param_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(tree)
               if hasattr(x, "dtype"))


@runtime_checkable
class ResidencyBackend(Protocol):
    """Structural interface the engine programs against (no isinstance /
    mode-string branching anywhere in the serving loop)."""

    name: str

    def materialize_banks(self, cfg: ArchConfig, params: Dict,
                          kv_bytes: int, budget=None) -> Optional[Dict]: ...

    def observe(self, counts: Dict, compute_s: float = 0.0,
                prefill: bool = False,
                row_valid: Optional[np.ndarray] = None) -> float: ...

    def tick(self) -> None: ...

    def device_bytes(self) -> int: ...

    def stats(self) -> Dict[str, float]: ...

    def flush(self) -> None: ...


class LRUSet:
    """O(1) LRU set over expert ids (OrderedDict: ``move_to_end`` on hit,
    ``popitem(last=False)`` on eviction). Replaces the earlier O(n)
    list-based LRU in the offload path."""

    def __init__(self, size: int, init: Optional[Iterable[int]] = None):
        self.size = size
        self._od: OrderedDict[int, None] = OrderedDict()
        if init is not None:
            for e in init:
                self.add(int(e))

    def __contains__(self, e: int) -> bool:
        return e in self._od

    def __len__(self) -> int:
        return len(self._od)

    def hit(self, e: int) -> bool:
        """Refresh ``e`` if cached; returns whether it was a hit."""
        if e in self._od:
            self._od.move_to_end(e)
            return True
        return False

    def add(self, e: int) -> None:
        """Insert ``e`` as most-recent, evicting the LRU entry on overflow."""
        self._od[e] = None
        self._od.move_to_end(e)
        while len(self._od) > self.size:
            self._od.popitem(last=False)

    def touch(self, e: int) -> bool:
        """Hit-or-insert; returns True on hit (classic LRU access)."""
        if self.hit(e):
            return True
        self.add(e)
        return False

    def order(self) -> list[int]:
        """Entries LRU-first (introspection/tests)."""
        return list(self._od)


class _BackendBase:
    """Shared accounting: latency aggregation (TTFT/TPOT as observed by the
    engine) and router-count accumulation (the uniform hotness signal)."""

    name = "base"

    #: Stats keys this class emits beyond the uniform ``STAT_KEYS``.
    STAT_EXTRAS: Tuple[str, ...] = ()

    def __init__(self):
        self._ttft: list[float] = []
        self._tpot: list[float] = []
        self._counts_sum: Dict[str, np.ndarray] = {}
        self.cfg: Optional[ArchConfig] = None
        self.budget = None                  # engine's shared BudgetTracker
        self.moe_positions: list[int] = []
        self.tracer = None                  # obs.FlightRecorder | None
        self.metrics = None                 # obs.MetricsRegistry | None

    # -- observability ---------------------------------------------------
    def attach_obs(self, tracer=None, metrics=None) -> None:
        """Wire the engine's flight recorder / metrics registry in.
        Subclasses propagate to owned components (TransitionManager,
        EPCoordinator, HostExpertStore). ``None`` detaches — every
        instrumentation site is a pointer check, so detached backends
        compile to the pre-obs behavior."""
        self.tracer = tracer
        self.metrics = metrics

    # -- lifecycle -------------------------------------------------------
    def materialize_banks(self, cfg: ArchConfig, params: Dict,
                          kv_bytes: int, budget=None) -> Optional[Dict]:
        self.cfg = cfg
        self.budget = budget
        sb = cfg.superblock_or_default()
        self.moe_positions = [p for p, _ in enumerate(sb)
                              if cfg.ffn_kind(p) == "moe"] if cfg.is_moe \
            else []
        return self._materialize(cfg, params, kv_bytes)

    def _materialize(self, cfg: ArchConfig, params: Dict,
                     kv_bytes: int) -> Optional[Dict]:
        return None

    # -- per-forward hook ------------------------------------------------
    def observe(self, counts: Dict, compute_s: float = 0.0,
                prefill: bool = False,
                row_valid: Optional[np.ndarray] = None) -> float:
        """Accumulate one forward's router counts and run residency
        accounting. Values may be (L, E) aggregates (accumulated as-is) or
        row-resolved (L, R, E), in which case ``row_valid`` masks vacant/
        padding rows before the sum (``core.hotness.mask_row_counts`` — the
        one scrub rule every residency strategy shares)."""
        cleaned: Dict[str, np.ndarray] = {}
        for k, c in counts.items():
            c = mask_row_counts(c, row_valid)
            cleaned[k] = c
            acc = self._counts_sum.get(k)
            self._counts_sum[k] = c.copy() if acc is None else acc + c
        stall = self._observe_residency(cleaned, compute_s)
        if self.tracer is not None:
            # The per-forward traffic record: routed assignments plus the
            # active-cell tier split at THIS forward's residency. Args are
            # counts only (no wall-clock durations), so virtual-clock
            # replays trace byte-identically.
            hi, lo, host, pub = self._tier_counts(cleaned)
            self.tracer.instant(
                "moe_forward", cat="engine",
                routed=int(sum(int(c.sum()) for c in cleaned.values())),
                layers=int(sum(c.shape[0] for c in cleaned.values())),
                active_hi=hi, active_lo=lo, active_host=host,
                published_hi=pub, prefill=int(prefill))
        (self._ttft if prefill else self._tpot).append(compute_s + stall)
        return stall

    def _observe_residency(self, counts: Dict, compute_s: float) -> float:
        return 0.0

    def _tier_counts(self, cleaned: Dict) -> Tuple[int, int, int, int]:
        """One forward's ``(active_hi, active_lo, active_host,
        published_hi)`` cell counts. Base strategy: everything serves from
        an always-resident lo tier (StaticPTQ's truth; overridden where the
        ladder is richer)."""
        act = sum(int((c > 0).sum()) for c in cleaned.values())
        return 0, act, 0, 0

    def tick(self) -> None:
        pass

    def flush(self) -> None:
        pass

    # -- cold-start readiness --------------------------------------------
    def serving_ready(self) -> bool:
        """Whether forwards may run (False only mid-streaming-cold-start —
        the engine idles admission and keeps ticking the backend)."""
        return True

    def ready_frac(self) -> float:
        """Residency build-out progress in [0, 1] (1.0 once serving)."""
        return 1.0

    # -- introspection ---------------------------------------------------
    def router_counts(self) -> Dict[str, np.ndarray]:
        """Accumulated router-selection counts per MoE position, (L, E)."""
        return dict(self._counts_sum)

    def residency_mix(self) -> Dict[str, int]:
        """Current (layer, expert)-cell residency census:
        ``{"hi", "lo", "host"}`` counts (the per-step gauge the metrics
        sampler records)."""
        return {"hi": 0, "lo": 0, "host": 0}

    def device_bytes(self) -> int:
        raise NotImplementedError

    def stats(self) -> Dict[str, float]:
        out = {k: 0.0 for k in STAT_KEYS}
        if self._ttft:
            out["ttft_s"] = float(np.mean(self._ttft))
        if self._tpot:
            out["tpot_s"] = float(np.mean(self._tpot))
        out.update(self._residency_stats())
        return out

    def _residency_stats(self) -> Dict[str, float]:
        return {}


class Fp16Backend(_BackendBase):
    """Dense bf16 experts, fully device-resident — the quality/latency
    reference (and the compute substrate the offload model prices)."""

    name = "fp16"

    def __init__(self):
        super().__init__()
        self._dense_bytes = 0
        self._cells = 0

    def _materialize(self, cfg, params, kv_bytes):
        self._dense_bytes = sum(
            _param_bytes(params["blocks"][str(p)]["moe"]["experts"])
            for p in self.moe_positions)
        self._cells = sum(
            int(np.prod(params["blocks"][str(p)]["moe"]["experts"]
                        ["w_gate"].shape[:2]))
            for p in self.moe_positions)
        return None        # forward uses the dense experts in params

    def _tier_counts(self, cleaned):
        # Dense experts: every active cell streams at full precision.
        act = sum(int((c > 0).sum()) for c in cleaned.values())
        cells = sum(int(c.size) for c in cleaned.values())
        return act, 0, 0, cells

    def residency_mix(self) -> Dict[str, int]:
        return {"hi": self._cells, "lo": 0, "host": 0}

    def device_bytes(self) -> int:
        return self._dense_bytes


class StaticPTQBackend(_BackendBase):
    """Uniform static PTQ (the paper's static baseline): every expert serves
    from the always-resident lo tier; no hi pool, no transfers, ever."""

    name = "static"

    def __init__(self, lo_bits: int = 4, group_size: int = 64):
        super().__init__()
        self.lo_bits = lo_bits
        self.group_size = group_size
        self.banks: Dict = {}
        self._lo_bytes = 0
        self._cells = 0

    def _materialize(self, cfg, params, kv_bytes):
        for pos in self.moe_positions:
            experts = params["blocks"][str(pos)]["moe"]["experts"]
            shapes = {k: tuple(v.shape) for k, v in experts.items()}
            L, E = experts["w_gate"].shape[:2]
            self._lo_bytes += expert_lo_nbytes(
                shapes, self.lo_bits, self.group_size) * L * E
            self._cells += L * E
            self.banks[str(pos)] = build_bank(
                experts, n_hi=0, lo_bits=self.lo_bits,
                group_size=self.group_size)
            # Free the dense copies — the bank is the only residency now.
            params["blocks"][str(pos)]["moe"]["experts"] = None
        return self.banks

    def residency_mix(self) -> Dict[str, int]:
        return {"hi": 0, "lo": self._cells, "host": 0}

    def device_bytes(self) -> int:
        return self._lo_bytes


class DynaExqBackend(_BackendBase):
    """The paper's system, extended to the full residency ladder: a hi-bf16
    pool, the always-materializable lo tier, and (optionally) a host-DRAM
    third tier — governed by ONE ``GlobalAllocator`` knapsack across every
    layer of every MoE position. Promotions ride the migration stream (off
    the critical path) — ``observe`` only feeds hotness; ``tick`` runs the
    allocation window.

    ``global_alloc`` (default on for single-shard serving): replaces the L
    independent per-layer top-n policies with one cross-layer allocation —
    a hot layer may hold more hi slots than a cold layer at the same total
    byte budget. Each bank's physical per-layer slot pool is built with
    ``slots_slack`` headroom over the uniform share so the allocator has
    room to skew. ``global_alloc=False`` restores the paper's per-layer
    rule (and is forced under expert parallelism, where hi slots are
    shard-local and cannot be reassigned across layers).

    ``lo_resident_total`` enables the host tier: only that many (layer,
    expert) cells count as device-lo-resident; the rest live in host DRAM
    and pay a ``fetch``-modeled demand stall when routed. ``sensitivity``
    (dict or ``.npz`` path from ``quant.sensitivity``) reweights hotness so
    fragile experts win hi slots at lower traffic.

    ``stream`` (a ``streaming.ShardSource`` or its path) turns on the
    streaming cold start: banks are built EMPTY, ``serving_ready()`` stays
    False while ``tick`` backfills lo rows from the checkpoint shards
    (``stream_experts_per_tick`` per window, hottest-first when a
    ``hotness_path`` snapshot from a previous run exists), and the hi/host
    tiers materialize lazily behind promotions — the dense experts never
    need to exist in device memory all at once.

    Expert parallelism (``ep_shards > 1``): every MoE position's hi-slot
    pool is split into per-shard slot ranges with per-shard budget accounts
    (shard j's promotions bill shard j's local HBM, never a neighbour's),
    and an ``EPCoordinator`` periodically rebalances expert *ownership*
    across shards from the globally-psum'd hotness (``tick`` drives its
    window alongside the per-position controllers)."""

    name = "dynaexq"

    STAT_EXTRAS = ("deferred", "lo_resident_frac", "hi_loads",
                   "residency_ready_frac", "migrations", "quarantined")

    def __init__(self, lo_bits: int = 4, hi_bits: int = 16,
                 group_size: int = 64,
                 n_hi_per_layer: Optional[int] = None,
                 hbm_gb: Optional[float] = None,
                 activation_slack_bytes: int = 64 << 20,
                 controller: Optional[ControllerConfig] = None,
                 ep_shards: int = 1,
                 rebalance: Optional[RebalanceConfig] = None,
                 global_alloc: Optional[bool] = None,
                 slots_slack: float = 2.0,
                 sensitivity=None,
                 lo_resident_total: Optional[int] = None,
                 fetch: Optional[FetchModel] = None,
                 hotness_path: Optional[str] = None,
                 stream=None,
                 stream_experts_per_tick: int = 16,
                 fault=None,
                 retry: Optional[RetryPolicy] = None):
        super().__init__()
        if ep_shards < 1:
            raise ValueError("ep_shards must be >= 1")
        if global_alloc is None:
            global_alloc = ep_shards == 1
        if global_alloc and ep_shards > 1:
            raise ValueError(
                "global_alloc requires ep_shards == 1: hi slots are "
                "shard-local HBM under expert parallelism and cannot be "
                "reassigned across layers by a global knapsack")
        if (stream is not None or lo_resident_total) and not global_alloc:
            raise ValueError(
                "the host tier and streaming cold start require the "
                "global allocator (single-shard serving)")
        if slots_slack < 1.0:
            raise ValueError("slots_slack must be >= 1.0")
        if lo_resident_total is not None and lo_resident_total < 1:
            raise ValueError("lo_resident_total must be >= 1")
        self.lo_bits = lo_bits
        self.hi_bits = hi_bits
        self.group_size = group_size
        self.n_hi_per_layer = n_hi_per_layer
        self.hbm_gb = hbm_gb
        self.activation_slack_bytes = activation_slack_bytes
        self.controller_cfg = controller
        self.ep_shards = int(ep_shards)
        self.coordinator: Optional[EPCoordinator] = \
            EPCoordinator(self.ep_shards, rebalance) if ep_shards > 1 else None
        self.controllers: Dict[str, DynaExqController] = {}
        self.banks: Dict = {}
        # -- residency-ladder configuration --------------------------------
        self.global_alloc = bool(global_alloc)
        self.slots_slack = float(slots_slack)
        self.sensitivity = sensitivity      # dict pos→(L,E) | .npz path
        self.lo_resident_total = lo_resident_total
        self.fetch = fetch if fetch is not None else FetchModel()
        self.hotness_path = hotness_path
        self.stream = stream                # ShardSource | path | None
        self.stream_experts_per_tick = int(stream_experts_per_tick)
        self.stores: Dict[str, HostExpertStore] = {}
        self.allocator: Optional[GlobalAllocator] = None
        self._global_root: Optional[BudgetTracker] = None
        self._row_caps: Optional[np.ndarray] = None
        self._row_pos: list = []            # global row → (pos, layer)
        self._row_offsets: Dict[str, int] = {}
        self._sens: Dict[str, np.ndarray] = {}
        self._lo_b: Dict[str, int] = {}
        self._pump_queue: deque = deque()
        self._lo_quota_left = lo_resident_total or 0
        self._serving_ready = True
        self._last_global = time.monotonic()
        self._cadence = (controller.update_interval_s if controller
                         is not None else ControllerConfig().update_interval_s)
        self._host_acct = {"host_fetches": 0, "host_fetch_bytes": 0,
                           "hotness_restored": 0}
        # -- fault tolerance ------------------------------------------------
        # ``fault``: a FaultPlan, a prebuilt FaultInjector, or a JSON
        # string/path (the launcher's --fault-plan). None = zero overhead:
        # every site is a single pointer check.
        if fault is None or isinstance(fault, FaultInjector):
            self.injector = fault
        elif isinstance(fault, FaultPlan):
            self.injector = fault.injector()
        else:
            self.injector = FaultPlan.parse(fault).injector()
        self.retry = retry if retry is not None else RetryPolicy()
        self._fault_acct = {"retries": 0}

    # -- materialization ---------------------------------------------------
    def _derive_n_hi(self, params, kv_bytes, shapes, L, E, hi_b, lo_b):
        ep = self.ep_shards
        if self.n_hi_per_layer is not None:
            n_hi = self.n_hi_per_layer
            if ep > 1 and n_hi % ep:
                raise ValueError(
                    f"n_hi_per_layer={n_hi} not divisible by "
                    f"ep_shards={ep} (each shard owns n_hi/ep slots)")
            return n_hi
        if self.hbm_gb is not None:
            nonexp = _param_bytes({k: v for k, v in params.items()
                                   if k != "blocks"})
            plan = plan_budget(
                m_total=int(self.hbm_gb * GiB),
                m_fixed=nonexp + kv_bytes + self.activation_slack_bytes,
                lo_bytes_total=lo_b * L * E,
                hi_bytes_per_expert_layer=hi_b,
                n_layers=L, num_experts=E, align=ep)
            return plan.n_hi_per_layer
        n_hi = max(1, E // 8)
        if ep > 1:
            # round to a shard-divisible count (≥ one slot per shard)
            n_hi = max(ep, n_hi // ep * ep)
        return n_hi

    def _materialize(self, cfg, params, kv_bytes):
        src = None
        if self.stream is not None:
            src = self.stream if hasattr(self.stream, "lo_layer") \
                else ShardSource(self.stream)
            self.stream = src
        sens = self.sensitivity
        if isinstance(sens, str):
            sens = load_sensitivity(sens)
        # Phase 1 — metadata prepass: slot counts and byte prices for every
        # position BEFORE building anything, so the global envelope and the
        # knapsack budget are sums over the whole model, not one position.
        metas = []
        for pos in self.moe_positions:
            pos = str(pos)
            experts = params["blocks"][pos]["moe"]["experts"]
            if experts is not None:
                shapes = {k: tuple(v.shape) for k, v in experts.items()}
            elif src is not None:
                shapes = src.shapes(pos)
            else:
                raise ValueError(
                    f"position {pos}: experts are None and no stream "
                    f"source configured")
            hi_b = expert_hi_nbytes(shapes, hi_bits=self.hi_bits,
                                    group_size=self.group_size)
            lo_b = expert_lo_nbytes(shapes, self.lo_bits, self.group_size)
            L, E = next(iter(shapes.values()))[:2]
            if self.ep_shards > 1 and E % self.ep_shards:
                raise ValueError(f"num_experts={E} not divisible by "
                                 f"ep_shards={self.ep_shards}")
            n_hi = self._derive_n_hi(params, kv_bytes, shapes, L, E,
                                     hi_b, lo_b)
            metas.append((pos, experts, shapes, L, E, hi_b, lo_b, n_hi))
        self._build_global_structures(metas, sens)
        for pos, experts, shapes, L, E, hi_b, lo_b, n_hi in metas:
            self._lo_b[pos] = lo_b
            slots = n_hi
            if self.global_alloc and n_hi > 0:
                # Physical per-layer pool ceiling: headroom over the
                # uniform share so the knapsack can skew slots toward hot
                # layers. Byte accounting stays at n_hi·L·hi_b — extra
                # slots are capacity, not budget.
                slots = min(E, max(n_hi,
                                   math.ceil(n_hi * self.slots_slack)))
            streaming = experts is None
            if streaming:
                bank = build_bank_empty(shapes, n_hi=slots,
                                        lo_bits=self.lo_bits,
                                        group_size=self.group_size)
                store = HostExpertStore(
                    shapes,
                    hi_loader=lambda l, e, p=pos: src.hi_expert(p, l, e),
                    lo_loader=lambda l, p=pos: src.lo_layer(p, l),
                    lo_valid_init=False)
            else:
                bank = build_bank(experts, n_hi=slots, lo_bits=self.lo_bits,
                                  group_size=self.group_size,
                                  hi_bits=self.hi_bits)
                store = HostExpertStore(
                    shapes, hi={k: np.asarray(v)
                                for k, v in experts.items()})
            self.banks[pos] = bank
            self.stores[pos] = store
            if n_hi > 0:
                ctl = DynaExqController(
                    bank, store, n_hi_per_layer=n_hi,
                    hi_bytes_per_expert=hi_b, cfg=self.controller_cfg,
                    tracker=self._tracker_for(pos, n_hi, L, hi_b),
                    ep_shards=self.ep_shards,
                    shard_trackers=self._shard_trackers_for(
                        pos, n_hi, L, hi_b))
                self.controllers[pos] = ctl
                self._restore_hotness(pos, ctl)
                if self.coordinator is not None:
                    # The moe params dict outlives the experts=None free
                    # below — the coordinator swaps its router leaf in
                    # place on migration.
                    self.coordinator.register(
                        ctl, params["blocks"][pos]["moe"])
            if streaming:
                self._serving_ready = False
            params["blocks"][pos]["moe"]["experts"] = None
        if not self._serving_ready:
            self._build_pump_queue()
        self._propagate_faults()
        self._propagate_obs()   # components built after attach_obs
        return self.banks

    # -- fault tolerance ---------------------------------------------------
    def _propagate_faults(self) -> None:
        """Push the injector + retry policy into every transfer-plane
        component (transition managers, host stores, the shard source, the
        EP coordinator)."""
        for ctl in self.controllers.values():
            ctl.tm.injector = self.injector
            ctl.tm.retry = self.retry
        for store in self.stores.values():
            store.injector = self.injector
            store.retry = self.retry
        if self.coordinator is not None:
            self.coordinator.injector = self.injector
        if self.stream is not None and hasattr(self.stream, "lo_layer"):
            self.stream.injector = self.injector

    def bind_clock(self, clock) -> None:
        """Rebind the transfer plane to the engine clock (virtual under
        replay) — promotion issue timestamps feed the watchdog."""
        for ctl in self.controllers.values():
            ctl.tm.clock = clock

    def cancel_stuck_promotions(self, now: float, deadline_s: float) -> int:
        """Watchdog hook: cancel promotions in flight past the deadline
        (slot freed, reservation refunded, expert keeps serving lo)."""
        n = 0
        for ctl in self.controllers.values():
            n += ctl.tm.cancel_stuck(now, deadline_s)
        return n

    def pending_promotions(self, now: float) -> list:
        """(pos, layer, expert, age_s) for every in-flight promotion —
        the stall-diagnostic snapshot."""
        out = []
        for pos, ctl in self.controllers.items():
            out += [(pos, l, e, a) for l, e, a in ctl.tm.pending_ages(now)]
        return out

    def degraded_cells(self) -> Dict[str, np.ndarray]:
        """pos → (L, E) quarantine mask, positions with none omitted —
        the engine flags requests routed through these as degraded."""
        return {pos: s.quarantined for pos, s in self.stores.items()
                if s.quarantined.any()}

    def _heal_quarantined(self, per_tick: int = 2) -> None:
        """Opportunistically re-stage quarantined cells (a bounded number
        per window); a staging that finally lands clears the flag at
        publish. Repeated failures just keep the cell quarantined."""
        healed = 0
        for pos, store in self.stores.items():
            if not store.quarantined.any():
                continue
            for l, e in zip(*np.nonzero(store.quarantined)):
                if healed >= per_tick:
                    return
                resident = True
                if self.lo_resident_total is not None:
                    resident = self._lo_quota_left > 0
                    if resident:
                        self._lo_quota_left -= 1
                try:
                    store.stage_lo(self.banks[pos], int(l), int(e),
                                   resident=resident)
                except RetryExhausted:
                    if resident and self.lo_resident_total is not None:
                        self._lo_quota_left += 1
                    continue
                healed += 1

    # -- observability -----------------------------------------------------
    def attach_obs(self, tracer=None, metrics=None) -> None:
        super().attach_obs(tracer, metrics)
        self._propagate_obs()

    def _propagate_obs(self) -> None:
        """Push the recorder/registry into owned components. Idempotent and
        order-independent: runs both at attach time and at the end of
        ``_materialize`` (whichever comes second sees everything)."""
        hist = self.metrics.histogram(
            "promotion_publish_latency_seconds",
            "copy issue -> publish latency of hi promotions") \
            if self.metrics is not None else None
        for ctl in self.controllers.values():
            ctl.tm.tracer = self.tracer
            ctl.tm.publish_hist = hist
        if self.coordinator is not None:
            self.coordinator.tracer = self.tracer
        for store in self.stores.values():
            store.tracer = self.tracer
        if self.injector is not None:
            self.injector.tracer = self.tracer
        if self.tracer is not None:
            # Promotion issue timestamps and publish latencies on ONE clock
            # (the engine rebinds the recorder's clock to its own).
            for ctl in self.controllers.values():
                ctl.tm.clock = self.tracer.clock

    def _tier_counts(self, cleaned):
        hi = lo = host = pub = 0
        for k, c in cleaned.items():
            act = c > 0
            ctl = self.controllers.get(k)
            hi_mask = ctl.tm.slot_map_h >= 0 if ctl is not None \
                else np.zeros(c.shape, bool)
            store = self.stores.get(k)
            if store is not None and self.lo_resident_total:
                host_mask = ~store.lo_resident & store.lo_valid
            else:
                host_mask = np.zeros(c.shape, bool)
            if store is not None:
                host_mask = host_mask | store.quarantined
            pub += int(hi_mask.sum())
            hi += int((act & hi_mask).sum())
            host += int((act & ~hi_mask & host_mask).sum())
            lo += int((act & ~hi_mask & ~host_mask).sum())
        return hi, lo, host, pub

    def residency_mix(self) -> Dict[str, int]:
        hi = lo = host = 0
        for ctl in self.controllers.values():
            hi += int((ctl.tm.slot_map_h >= 0).sum())
        for store in self.stores.values():
            lo += int(store.lo_resident.sum())
            host += int((~store.lo_resident & store.lo_valid).sum())
        return {"hi": hi, "lo": lo, "host": host}

    def _build_global_structures(self, metas, sens) -> None:
        """Global-mode scaffolding: the cross-layer knapsack (row = one
        layer of one position), its per-row slot ceilings, the shared byte
        envelope, and the normalized sensitivity weights."""
        if not self.global_alloc:
            return
        rows = [(pos, L, E, n_hi, hi_b)
                for pos, _, _, L, E, hi_b, _, n_hi in metas if n_hi > 0]
        if not rows:
            return
        Es = {E for _, _, E, _, _ in rows}
        if len(Es) != 1:
            raise ValueError(
                f"global allocation needs a uniform expert count across "
                f"MoE positions, got {sorted(Es)}")
        total_hi = sum(n_hi * L for _, L, _, n_hi, _ in rows)
        total_cap = sum(n_hi * L * hi_b for _, L, _, n_hi, hi_b in rows)
        caps = []
        for pos, L, E, n_hi, _ in rows:
            self._row_offsets[pos] = len(self._row_pos)
            slots = min(E, max(n_hi, math.ceil(n_hi * self.slots_slack)))
            for l in range(L):
                self._row_pos.append((pos, l))
                caps.append(slots)
        self._row_caps = np.asarray(caps, np.int64)
        ctl_cfg = self.controller_cfg if self.controller_cfg is not None \
            else ControllerConfig()
        max_tr = ctl_cfg.max_transitions_per_layer * len(self._row_pos) \
            if ctl_cfg.max_transitions_per_layer else 0
        self.allocator = GlobalAllocator(AllocatorConfig(
            total_hi=total_hi,
            slots_per_layer=int(self._row_caps.max()),
            margin=ctl_cfg.margin,
            max_transitions=max_tr,
            lo_resident_total=self.lo_resident_total or 0,
            lo_margin=ctl_cfg.margin))
        # One byte envelope for the whole hi tier: either the engine's
        # shared tracker (promotions contend with KV admission) or a
        # private global tracker at the classic summed cap. Per-position
        # accounts carry NO own cap — the global slot budget is the
        # allocator's to spend across layers and positions.
        self._global_root = self.budget if self.budget is not None \
            else BudgetTracker(total_cap)
        if sens:
            for pos, L, E, _, _ in rows:
                s = sens.get(pos)
                if s is None:
                    continue
                s = np.asarray(s, np.float64)
                if s.shape != (L, E):
                    raise ValueError(
                        f"sensitivity for position {pos} has shape "
                        f"{s.shape}, expected ({L}, {E})")
                self._sens[pos] = normalize(s)

    def _tracker_for(self, pos, n_hi, L, hi_b):
        if self.global_alloc and self.allocator is not None:
            return self._global_root.view(f"hi:{pos}")
        if self.budget is not None:
            # Under an engine-shared budget each position's hi tier is an
            # account-scoped view: its own cap is the classic n_hi·L·hi_b
            # pool, but every reservation also passes through the ONE
            # envelope KV blocks draw from — KV pressure defers
            # promotions, demotions free admission headroom.
            return self.budget.view(f"hi:{pos}", cap=n_hi * L * hi_b)
        return None

    def _shard_trackers_for(self, pos, n_hi, L, hi_b):
        ep = self.ep_shards
        if ep <= 1:
            return None
        # One account per shard: a shard's promotions reserve against ITS
        # slice of the pool (its local HBM), so a hot shard saturating its
        # slots cannot starve — or borrow from — a neighbour's budget.
        per_cap = (n_hi // ep) * L * hi_b
        if self.budget is not None:
            return [self.budget.view(f"hi:{pos}:s{j}", cap=per_cap)
                    for j in range(ep)]
        return [BudgetTracker(per_cap) for _ in range(ep)]

    def _restore_hotness(self, pos, ctl) -> None:
        if not self.hotness_path:
            return
        path = f"{self.hotness_path}_p{pos}.npz"
        if not os.path.exists(path):
            return
        try:
            ctl.hotness.load(path)
            self._host_acct["hotness_restored"] += 1
        except ValueError:
            pass    # resized model: a stale prior must not crash serving

    def _build_pump_queue(self) -> None:
        """Round-robin merge of per-position staging orders (hottest-first
        under a restored hotness prior, row-major otherwise) — positions
        backfill evenly instead of position 0 hogging the early windows."""
        per_pos = []
        for pos, store in self.stores.items():
            ctl = self.controllers.get(pos)
            scores = ctl.hotness.scores if ctl is not None else None
            order = hotness_stage_order(scores, store.L, store.E)
            per_pos.append([(pos, l, e) for l, e in order])
        for group in zip(*per_pos) if per_pos else []:
            self._pump_queue.extend(group)

    # -- per-forward hook --------------------------------------------------
    def _observe_residency(self, counts, compute_s):
        stall = 0.0
        for k, ctl in self.controllers.items():
            c = counts.get(k)
            if c is None:
                continue
            c = np.asarray(c)
            ctl.observe(c)
            store = self.stores.get(k)
            if store is None:
                continue
            # Routed experts whose lo residency was ceded to the host tier
            # pay a demand fetch on the critical path (their device rows
            # are valid — the stall models the configuration where a
            # host-resident row would not be kept on device). Quarantined
            # cells are ALWAYS host-served (their device rows are unreal),
            # regardless of whether the host tier is enabled.
            miss = np.zeros(c.shape, bool)
            if self.lo_resident_total:
                miss = ~store.lo_resident & store.lo_valid
            miss = (c > 0) & (miss | store.quarantined)
            n = int(miss.sum())
            if n:
                demand = n * self._lo_b[k]
                self._host_acct["host_fetches"] += n
                self._host_acct["host_fetch_bytes"] += demand
                s = self.fetch.stall_s(demand)
                if self.injector is not None:
                    f = self.injector.fire("host_fetch", pos=k, experts=n)
                    if f is not None:
                        # A failed (or slow) demand fetch is retried
                        # synchronously on the critical path: one extra
                        # full transfer plus any injected stall —
                        # availability is never lost, only latency.
                        extra = s + (f.stall_s if f.kind == "stall" else 0.0)
                        s += extra
                        self._fault_acct["retries"] += 1
                        if self.tracer is not None:
                            self.tracer.instant("retry", cat="fault",
                                                site="host_fetch", pos=k,
                                                backoff_s=round(extra, 9))
                stall += s
                if self.tracer is not None:
                    # stall_s is modeled from bytes (deterministic), safe
                    # for byte-identical replay traces.
                    self.tracer.instant("host_fetch", cat="host", pos=k,
                                        experts=n, bytes=demand, stall_s=s)
        return stall

    # -- windows -----------------------------------------------------------
    def tick(self) -> None:
        if not self._serving_ready:
            self._pump()
            return
        if self.allocator is not None:
            self._global_tick()
        else:
            for ctl in self.controllers.values():
                ctl.maybe_update()
        if self.coordinator is not None:
            self.coordinator.maybe_rebalance()
        self._heal_quarantined()
        for store in self.stores.values():
            store.publish_lo()

    def _pump(self) -> None:
        """One streaming-cold-start window: stage up to
        ``stream_experts_per_tick`` experts' lo rows, publish completed
        copies, and open serving once every cell is materialized."""
        staged = 0
        batch: Dict[Tuple[str, int], Tuple[list, list]] = {}
        while self._pump_queue and staged < self.stream_experts_per_tick:
            pos, l, e = self._pump_queue.popleft()
            if self.stores[pos].lo_valid[l, e]:
                continue
            resident = True
            if self.lo_resident_total is not None:
                resident = self._lo_quota_left > 0
                if resident:
                    self._lo_quota_left -= 1
            ex, res = batch.setdefault((pos, l), ([], []))
            ex.append(e)
            res.append(resident)
            staged += 1
        for (pos, l), (ex, res) in batch.items():
            # One scatter per (layer, leaf): the pump is dispatch-bound on
            # tiny rows, so cell-at-a-time writes would dominate TTFT.
            try:
                self.stores[pos].stage_lo_batch(self.banks[pos], l, ex, res)
            except RetryExhausted:
                # The staging source exhausted its retries: quarantine the
                # batch (served from host, healed by later re-stages) so
                # one unreadable shard can never hold ``serving_ready()``
                # hostage; refund the residency quota it reserved.
                self.stores[pos].quarantine(l, ex)
                if self.lo_resident_total is not None:
                    self._lo_quota_left += sum(res)
        for store in self.stores.values():
            store.publish_lo()
        if not self._pump_queue:
            for store in self.stores.values():
                store.publish_lo(wait=True)
            if all(s.lo_complete for s in self.stores.values()):
                self._serving_ready = True

    def _global_tick(self, now: Optional[float] = None) -> bool:
        now = now if now is not None else time.monotonic()
        # Read the cadence live from the controllers (not the construction-
        # time snapshot): callers freeze/retune policy by mutating ctl.cfg,
        # exactly as the per-layer maybe_update path honors it.
        cadence = min((ctl.cfg.update_interval_s
                       for ctl in self.controllers.values()),
                      default=self._cadence)
        if now - self._last_global < cadence:
            # Still publish any copies that completed since last window.
            for ctl in self.controllers.values():
                ctl.tm.publish_ready()
            return False
        self._last_global = now
        self._global_update()
        return True

    def _global_update(self) -> None:
        """One global allocation window: fold every position's hotness,
        weight by sensitivity, stack all layers into one (R, E) value
        matrix, solve the knapsack ONCE, then hand each position's
        controller its slice of the plan (globally ordered, so under a
        rate limit the hottest promotions anywhere in the model go
        first)."""
        R = len(self._row_pos)
        if R == 0:
            return
        E = self.stores[self._row_pos[0][0]].E
        value = np.zeros((R, E))
        cur_hi = [set() for _ in range(R)]
        use_lo = bool(self.lo_resident_total)
        cur_lo = [set() for _ in range(R)] if use_lo else None
        for pos, off in self._row_offsets.items():
            ctl = self.controllers[pos]
            w = ctl.folded_scores()     # fold + failure-decay penalty
            s = self._sens.get(pos)
            if s is not None:
                w = w * s
            L = ctl.tm.state.shape[0]
            value[off:off + L] = w
            store = self.stores[pos]
            for l in range(L):
                cur_hi[off + l] = ctl.tm.hi_set(l) | \
                    ctl.tm.pending_experts(l)
                if use_lo:
                    cur_lo[off + l] = set(
                        np.nonzero(store.lo_resident[l])[0].tolist())
        asn = self.allocator.allocate(value, cur_hi, cur_lo,
                                      row_caps=self._row_caps)
        if use_lo:
            for r, e in asn.lo_demotions:
                pos, l = self._row_pos[r]
                self.stores[pos].lo_resident[l, e] = False
            for r, e in asn.lo_promotions:
                pos, l = self._row_pos[r]
                store = self.stores[pos]
                if store.lo_valid[l, e]:
                    store.lo_resident[l, e] = True
                else:
                    try:
                        store.stage_lo(self.banks[pos], l, e, resident=True)
                    except RetryExhausted:
                        # Failed lo staging falls back to the host demand
                        # path: the cell stays host-resident (paying the
                        # modeled fetch stall when routed) and the allocator
                        # re-candidates it next window.
                        continue
        promos: Dict[str, list] = {p: [] for p in self.controllers}
        demos: Dict[str, list] = {p: [] for p in self.controllers}
        for r, e in asn.promotions:
            pos, l = self._row_pos[r]
            promos[pos].append((l, e))
        for r, e in asn.demotions:
            pos, l = self._row_pos[r]
            demos[pos].append((l, e))
        for pos, ctl in self.controllers.items():
            ctl.apply_plan(promos[pos], demos[pos])

    def force_update(self) -> None:
        if not self._serving_ready:
            self.flush()
        if self.allocator is not None:
            self._global_update()
        else:
            for ctl in self.controllers.values():
                ctl.update()

    def flush(self) -> None:
        while not self._serving_ready:
            self._pump()
        for ctl in self.controllers.values():
            ctl.flush()
        for store in self.stores.values():
            store.publish_lo(wait=True)
            store.check_invariants()

    # -- readiness ---------------------------------------------------------
    def serving_ready(self) -> bool:
        return self._serving_ready

    def ready_frac(self) -> float:
        if self._serving_ready or not self.stores:
            return 1.0
        return float(np.mean([s.lo_valid.mean()
                              for s in self.stores.values()]))

    def save_hotness(self, path: Optional[str] = None) -> None:
        """Persist every position's traffic history (``hotness_path``
        prefix by default) — the next cold start stages hottest-first and
        the allocator opens with a warm prior instead of uniform."""
        prefix = path if path is not None else self.hotness_path
        if not prefix:
            raise ValueError("no hotness path configured")
        for pos, ctl in self.controllers.items():
            ctl.hotness.save(f"{prefix}_p{pos}.npz")

    # -- introspection -----------------------------------------------------
    def hi_sets(self) -> Dict[str, list]:
        out = {}
        for k, ctl in self.controllers.items():
            L = ctl.tm.slot_map_h.shape[0]
            out[k] = [sorted(ctl.tm.hi_set(l)) for l in range(L)]
        return out

    def device_bytes(self) -> int:
        total = 0
        for pos, bank in self.banks.items():
            shapes = {n: tuple(q.shape) for n, q in bank.lo.items()}
            L, E = bank.slot_map.shape
            per_lo = expert_lo_nbytes(shapes, self.lo_bits, self.group_size)
            per_hi = expert_hi_nbytes(shapes, hi_bits=self.hi_bits,
                                      group_size=self.group_size)
            store = self.stores.get(pos)
            n_lo = int(store.lo_resident.sum()) \
                if store is not None and self.lo_resident_total else L * E
            n_hi_res = int((np.asarray(bank.slot_owner) >= 0).sum())
            total += per_lo * n_lo + n_hi_res * per_hi
        return total

    def _residency_stats(self):
        # Every STAT_EXTRAS key gets a default so the emitted schema is
        # exactly STAT_KEYS + STAT_EXTRAS regardless of configuration.
        agg = {"stall_s": 0.0, "bytes_moved": 0.0,
               "promotions": 0.0, "demotions": 0.0, "deferred": 0.0,
               "lo_resident_frac": 1.0, "hi_loads": 0.0, "migrations": 0.0,
               "host_fetches": float(self._host_acct["host_fetches"]),
               "retries": float(self._fault_acct["retries"]),
               "fault_cancels": 0.0, "quarantined": 0.0}
        for ctl in self.controllers.values():
            agg["bytes_moved"] += ctl.tm.stats["bytes_moved"]
            agg["promotions"] += ctl.tm.stats["promoted"]
            agg["demotions"] += ctl.tm.stats["demoted"]
            agg["deferred"] += ctl.tm.stats["deferred"]
            agg["retries"] += ctl.tm.stats["retries"]
            agg["fault_cancels"] += ctl.tm.stats["fault_cancels"]
        agg["bytes_moved"] += self._host_acct["host_fetch_bytes"]
        if self.stores:
            agg["lo_resident_frac"] = float(np.mean(
                [s.lo_resident.mean() for s in self.stores.values()]))
            agg["hi_loads"] = float(sum(
                s.stats["hi_loads"] for s in self.stores.values()))
            agg["bytes_moved"] += sum(
                s.stats["lo_bytes_staged"] for s in self.stores.values())
            agg["retries"] += sum(
                s.stats["retries"] for s in self.stores.values())
            # Live gauge (not a counter): cells currently host-served
            # because their staging source kept failing.
            agg["quarantined"] = float(sum(
                int(s.quarantined.sum()) for s in self.stores.values()))
        agg["residency_ready_frac"] = self.ready_frac()
        if self.coordinator is not None:
            agg["migrations"] = float(self.coordinator.stats["migrations"])
            agg["bytes_moved"] += self.coordinator.stats["bytes_moved"]
            agg["fault_cancels"] += \
                self.coordinator.stats["aborted_migrations"]
        return agg


@dataclasses.dataclass
class OffloadConfig:
    cache_experts_per_layer: int = 16
    pcie_gbps: float = 16.0          # PCIe gen4 x16 — the paper's A6000
    prefetch: bool = True


class OffloadBackend(_BackendBase):
    """ExpertFlow-like offloading/prefetch baseline (paper §5.3 comparator).

    Experts live in host memory; the device keeps an LRU cache of
    ``cache_experts_per_layer`` experts per layer in bf16. Each forward the
    router's activated set is compared against the cache: misses must be
    fetched over PCIe *on the critical path* (minus whatever an optimistic
    prefetcher overlapped) — exactly the structural cost the paper's Fig. 1
    measures. The transfer cost is a deterministic model
    (bytes / pcie_gbps) layered on the measured compute time, so the
    DynaExq-vs-offload comparison reflects transfer volume, not CPU noise.

    Prefetch model: before each step the predictor prefetches the previous
    step's activated set (a strong next-step predictor for decode — routing
    is temporally correlated); prefetched bytes overlap with compute up to
    ``compute_s × pcie`` bytes per step, the rest spills into the stall.
    """

    name = "offload"

    STAT_EXTRAS = ("hits", "misses")

    def __init__(self, ocfg: Optional[OffloadConfig] = None):
        super().__init__()
        self.ocfg = ocfg if ocfg is not None else OffloadConfig()
        # The transfer-cost model is the residency ladder's FetchModel —
        # the offload baseline and DynaExq's host tier price host↔device
        # bytes identically, so their stall columns are comparable.
        self.fetch = FetchModel(gbps=self.ocfg.pcie_gbps)
        self.expert_bytes = 0
        self.n_moe_layers = 0
        self.lru: Dict[int, LRUSet] = {}
        self.prev_active: Dict[int, set] = {}
        self._acct = {"hits": 0, "misses": 0, "stall_s": 0.0,
                      "bytes_moved": 0}

    def _materialize(self, cfg, params, kv_bytes):
        # Per-expert bf16 bytes (w_gate + w_up + w_down).
        self.expert_bytes = 3 * cfg.d_model * cfg.moe.d_ff_expert * 2
        self.n_moe_layers = len(self.moe_positions) * cfg.n_superblocks()
        self.lru = {l: LRUSet(self.ocfg.cache_experts_per_layer)
                    for l in range(self.n_moe_layers)}
        self.prev_active = {l: set() for l in range(self.n_moe_layers)}
        return None        # computes dense; residency is modeled

    def _observe_residency(self, counts, compute_s):
        activated: Dict[int, np.ndarray] = {}
        li = 0
        for pos in self.moe_positions:
            c = np.asarray(counts[str(pos)])       # (nsb, E)
            for sbi in range(c.shape[0]):
                activated[li] = np.nonzero(c[sbi] > 0)[0]
                li += 1
        miss_bytes = 0
        prefetched_bytes = 0
        for l, acts in activated.items():
            lru = self.lru[l]
            if self.ocfg.prefetch:
                for e in self.prev_active[l]:
                    if e not in lru:
                        prefetched_bytes += self.expert_bytes
                    lru.touch(int(e))
            for e in acts:
                if lru.touch(int(e)):
                    self._acct["hits"] += 1
                else:
                    self._acct["misses"] += 1
                    miss_bytes += self.expert_bytes
            self.prev_active[l] = set(int(x) for x in acts)
        # Prefetches overlap with compute; anything beyond the overlap
        # window spills into the critical path with the demand misses.
        stall = self.fetch.stall_s(miss_bytes, prefetched_bytes, compute_s)
        self._acct["stall_s"] += stall
        self._acct["bytes_moved"] += miss_bytes + prefetched_bytes
        return stall

    def _tier_counts(self, cleaned):
        # Computes dense: every active cell streams full-precision rows.
        act = sum(int((c > 0).sum()) for c in cleaned.values())
        cells = sum(int(c.size) for c in cleaned.values())
        return act, 0, 0, cells

    def residency_mix(self) -> Dict[str, int]:
        hi = sum(len(lru) for lru in self.lru.values())
        E = self.cfg.moe.num_experts if self.cfg is not None and \
            self.cfg.moe is not None else 0
        total = self.n_moe_layers * E
        return {"hi": hi, "lo": 0, "host": max(0, total - hi)}

    def device_bytes(self) -> int:
        """Device-resident cache footprint under the offload budget."""
        return (self.n_moe_layers * self.ocfg.cache_experts_per_layer *
                self.expert_bytes)

    def _residency_stats(self):
        return {"stall_s": self._acct["stall_s"],
                "bytes_moved": float(self._acct["bytes_moved"]),
                "hits": float(self._acct["hits"]),
                "misses": float(self._acct["misses"]),
                "host_fetches": float(self._acct["misses"])}


BACKENDS = {
    "fp16": Fp16Backend,
    "static": StaticPTQBackend,
    "dynaexq": DynaExqBackend,
    "offload": OffloadBackend,
}


def make_backend(name: str, **kwargs) -> ResidencyBackend:
    """Registry factory: ``make_backend("dynaexq", n_hi_per_layer=2)``."""
    try:
        cls = BACKENDS[name]
    except KeyError:
        raise KeyError(f"unknown backend {name!r}; "
                       f"one of {sorted(BACKENDS)}") from None
    return cls(**kwargs)
