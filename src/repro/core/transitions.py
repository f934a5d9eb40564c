"""Non-blocking transition pipeline (paper §3.4), JAX realization.

Queues + admission control + publish-then-switch:

* ``request_promotion/request_demotion`` enqueue candidates (from the policy).
* ``drain()`` processes demotions first (reclaiming capacity enlarges the
  feasible set — the paper's eviction priority), then admits promotions that
  pass BOTH gates: the byte budget (``BudgetTracker.try_reserve``) and the
  per-window migration-rate limit (bounded interference).
* An admitted promotion allocates a slot from the layer's ``SlotPool`` and
  issues the hi-weight copy (``write_hi_slot``). JAX dispatch is async — this
  is the migration-stream analogue: the host goes on serving while the copy
  lands, and no forward reads the slot because it is unpublished. The copy
  writes the pool in place (donation), so on the device it is ordered after
  the forwards already dispatched against the pool.
* ``publish_ready()`` — called at a window boundary — publishes completed
  copies by writing ``slot_map``/``slot_owner``. A copy is "complete" when
  its result array is ready (the CUDA-event analogue).

The forward pass never observes a partially-materialized version: ``slot_map``
only ever points at slots whose copies completed.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.budget import BudgetTracker
from repro.core.pools import ShardedSlotPool
from repro.core.ver import ExpertBankQ, Residency, write_hi_slot
from repro.fault.inject import TransferFault
from repro.fault.retry import RetryExhausted, RetryPolicy, retry_call


@dataclasses.dataclass
class PendingPromotion:
    layer: int
    expert: int
    slot: int
    nbytes: int
    # THIS copy's result arrays (one per bank leaf). Readiness must be
    # probed on these — ``bank.hi`` is overwritten by every later
    # ``_issue_copy``, so peeking the bank would let an older promotion
    # publish on a newer copy's completion (and vice versa). A later copy
    # donates them (``write_hi_slot`` writes in place); see ``_is_ready``.
    arrays: tuple = ()
    # Flight-recorder lifecycle: async-span correlation id and the engine-
    # clock issue timestamp (publish latency = publish ts − issue ts).
    seq: int = 0
    issue_ts: float = 0.0
    # Fault state: an injected DMA stall keeps the copy "in flight" until
    # the clock passes ``stall_until``; a corrupt payload is caught by the
    # publish-time integrity check and never published; ``cancelled`` makes
    # the refund idempotent no matter which path cancels first.
    stall_until: float = 0.0
    corrupt: bool = False
    cancelled: bool = False


class TransitionManager:
    def __init__(self, bank: ExpertBankQ,
                 host_hi: Dict[str, np.ndarray],
                 tracker: BudgetTracker,
                 hi_bytes_per_expert: int,
                 migration_bytes_per_window: int = 0,
                 n_shards: int = 1,
                 shard_trackers: Optional[Sequence[BudgetTracker]] = None):
        """``host_hi``: name → (L, E, K, N) host copies of the hi tier (the
        paper's pre-packed pinned-host source). ``migration_bytes_per_window``
        0 = unlimited. Under expert parallelism (``n_shards > 1``) the hi
        pool's slot dim is sharded: expert ``e`` lives on shard
        ``e // (E/n_shards)`` and may only occupy that shard's slots;
        ``shard_trackers`` (one per shard) price each shard's hi slots
        against its LOCAL HBM — without them all shards bill ``tracker``."""
        self.bank = bank
        self.host_hi = host_hi
        self.tracker = tracker
        self.hi_bytes = hi_bytes_per_expert
        self.rate_limit = migration_bytes_per_window
        L, n_hi = bank.slot_owner.shape
        E = bank.num_experts
        if n_shards > 1 and E % n_shards:
            raise ValueError(f"num_experts={E} not divisible by n_shards={n_shards}")
        if shard_trackers is not None and len(shard_trackers) != n_shards:
            raise ValueError("need one shard tracker per shard")
        self.n_shards = n_shards
        self.e_per_shard = E // n_shards
        self.shard_trackers = list(shard_trackers) if shard_trackers else None
        self.pools = [ShardedSlotPool(n_hi, n_shards) for _ in range(L)]
        self.state = np.full((L, bank.num_experts), Residency.RESIDENT_LO.value,
                             np.int8)
        self.update_q: deque[tuple[int, int]] = deque()
        self.evict_q: deque[tuple[int, int]] = deque()
        self._pending: List[PendingPromotion] = []
        # Host mirrors of the published device maps (authoritative copies —
        # reading device arrays back every window would sync the stream).
        self.slot_map_h = np.asarray(bank.slot_map).copy()
        self.slot_owner_h = np.asarray(bank.slot_owner).copy()
        # One per-window transfer meter shared by promotion admission AND
        # EP ownership migrations (relabeling bytes) — both ride the same
        # interconnect, so they contend for the same budget. ``drain()``
        # opens a fresh window; migrations spend whatever the window's
        # promotions left.
        self._window_used = 0
        self.stats = {"promoted": 0, "demoted": 0, "deferred": 0,
                      "bytes_moved": 0, "retries": 0, "fault_cancels": 0}
        # Observability (attached by the backend, None by default): every
        # hook below guards on ``tracer is not None`` — with observability
        # off the pipeline allocates nothing extra.
        self.tracer = None                  # repro.obs.trace.FlightRecorder
        self.publish_hist = None            # metrics Histogram (publish lat)
        # Fault tolerance (same pointer-check discipline as obs). ``clock``
        # is rebound to the engine clock so promotion ages — the watchdog's
        # input — ride the virtual clock under replay.
        self.injector = None                # repro.fault.inject.FaultInjector
        self.retry = RetryPolicy()
        self.clock = time.monotonic
        self.fail_cb = None                 # controller failure-decay hook
        # Sum of reservations issued but neither published nor cancelled.
        # ``check_invariants`` pins this to the open promotion spans —
        # the exactly-once-refund audit.
        self.inflight_bytes = 0

    # -- shard plumbing ---------------------------------------------------
    def shard_of_expert(self, expert: int) -> int:
        return expert // self.e_per_shard

    def _tracker_for(self, shard: int) -> BudgetTracker:
        return self.shard_trackers[shard] if self.shard_trackers else self.tracker

    # -- queue side ------------------------------------------------------
    def request_promotion(self, layer: int, expert: int) -> None:
        if self.state[layer, expert] == Residency.RESIDENT_LO.value:
            self.state[layer, expert] = Residency.PROMOTING.value
            self.update_q.append((layer, expert))
            if self.tracer is not None:
                self.tracer.instant("promo_request", cat="residency",
                                    layer=layer, expert=expert)

    def request_demotion(self, layer: int, expert: int) -> None:
        if self.state[layer, expert] == Residency.RESIDENT_HI.value:
            self.state[layer, expert] = Residency.DEMOTING.value
            self.evict_q.append((layer, expert))
            if self.tracer is not None:
                self.tracer.instant("demo_request", cat="residency",
                                    layer=layer, expert=expert)

    def try_consume_window(self, nbytes: int) -> bool:
        """Charge ``nbytes`` against the current window's transfer budget
        (always succeeds when no rate limit is configured). The EP
        coordinator prices its relabeling bytes here, so rebalancing and
        promotions genuinely contend for one per-window budget."""
        if not self.rate_limit:
            return True
        if self._window_used + nbytes > self.rate_limit:
            return False
        self._window_used += nbytes
        return True

    # -- worker side -----------------------------------------------------
    def drain(self) -> None:
        """Process evictions, then admit promotions under both gates.
        Opens a fresh transfer window: promotions spend first, and any
        coordinator migrations until the next drain spend the remainder."""
        while self.evict_q:
            l, e = self.evict_q.popleft()
            if self.state[l, e] != Residency.DEMOTING.value:
                continue
            self._demote(l, e)
        self._window_used = 0
        deferred = deque()
        while self.update_q:
            l, e = self.update_q.popleft()
            if self.state[l, e] != Residency.PROMOTING.value:
                continue
            if self.rate_limit and \
                    self._window_used + self.hi_bytes > self.rate_limit:
                deferred.append((l, e))
                continue
            shard = self.shard_of_expert(e)
            if (self.pools[l].n_free_in(shard) == 0
                    or not self._tracker_for(shard).try_reserve(self.hi_bytes)):
                deferred.append((l, e))   # backpressure: stay queued
                self.stats["deferred"] += 1
                if self.tracer is not None:
                    self.tracer.instant("promo_deferred", cat="residency",
                                        layer=l, expert=e)
                continue
            slot = self.pools[l].alloc(e, shard)
            if self._issue_copy(l, e, slot):
                self._window_used += self.hi_bytes
        self.update_q = deferred

    def _issue_copy(self, layer: int, expert: int, slot: int) -> bool:
        """Async hi-weight copy into the (unpublished) pool slot. When the
        host side is a ``HostExpertStore`` (duck-typed via ``ensure_hi``),
        the expert's host rows are materialized first — on a streaming cold
        start that is the lazy read from the checkpoint shard.

        Fault path: injected ``promo_copy`` failures are retried under
        ``self.retry``; if the copy (or the host-side load underneath it)
        exhausts its retries, the admission is aborted — slot freed,
        reservation refunded, expert back to RESIDENT_LO, controller
        notified via ``fail_cb`` — and the expert keeps serving lo.
        Returns True iff the copy was issued."""
        fault = [None]

        def attempt():
            if self.injector is not None:
                f = self.injector.fire("promo_copy", layer=layer,
                                       expert=expert)
                if f is not None:
                    if f.kind == "fail":
                        raise TransferFault("promo_copy", seq=f.seq)
                    fault[0] = f        # stall / corrupt ride the copy
            ensure = getattr(self.host_hi, "ensure_hi", None)
            if ensure is not None:
                ensure(layer, expert)
            rows = {name: jnp.asarray(self.host_hi[name][layer, expert])
                    for name in self.bank.hi}
            return write_hi_slot(self.bank.hi, jnp.int32(layer),
                                 jnp.int32(slot), rows)

        seed = self.injector.seed if self.injector is not None else 0
        try:
            new_hi, retries, _ = retry_call(
                attempt, self.retry, seed=seed, key=(layer << 16) | expert,
                site="promo_copy", tracer=self.tracer)
        except (RetryExhausted, TransferFault) as e:
            self._abort_issue(layer, expert, slot, e)
            return False
        if retries:
            self.stats["retries"] += retries
        self.bank.hi = new_hi  # dispatched, not yet waited on
        p = PendingPromotion(layer, expert, slot, self.hi_bytes,
                             arrays=tuple(new_hi.values()))
        p.issue_ts = self.clock()
        f = fault[0]
        if f is not None:
            if f.kind == "stall":
                p.stall_until = p.issue_ts + f.stall_s
            elif f.kind == "corrupt":
                p.corrupt = True
        if self.tracer is not None:
            # Lifecycle span: opens at copy issue, closes at publish (or
            # cancellation) — per-phase timestamps on the engine clock.
            p.seq = self.tracer.next_id()
            self.tracer.async_begin("promotion", p.seq, cat="residency",
                                    layer=layer, expert=expert, slot=slot,
                                    bytes=self.hi_bytes)
        self._pending.append(p)
        self.inflight_bytes += self.hi_bytes
        self.stats["bytes_moved"] += self.hi_bytes
        return True

    def _abort_issue(self, layer: int, expert: int, slot: int,
                     err: Exception) -> None:
        """Unwind an admission whose copy never issued: the slot and the
        reservation go back, the expert stays lo, and the controller's
        failure-decay penalty keeps a flapping expert from livelocking the
        promotion budget."""
        self.pools[layer].free(slot)
        self._tracker_for(self.pools[layer].shard_of(slot)).release(
            self.hi_bytes)
        self.state[layer, expert] = Residency.RESIDENT_LO.value
        self.stats["fault_cancels"] += 1
        if self.fail_cb is not None:
            self.fail_cb(layer, expert)
        if self.tracer is not None:
            self.tracer.instant("fault_cancel", cat="fault", layer=layer,
                                expert=expert, site="promo_copy",
                                reason=type(err).__name__)

    def _demote(self, layer: int, expert: int) -> None:
        """Publish-then-reclaim: redirect the handle to lo, then free."""
        slot = int(self.slot_map_h[layer, expert])
        self.slot_map_h[layer, expert] = -1
        if slot >= 0:
            self.slot_owner_h[layer, slot] = -1
            self.pools[layer].free(slot)
            self._tracker_for(self.pools[layer].shard_of(slot)).release(
                self.hi_bytes)
        self.state[layer, expert] = Residency.RESIDENT_LO.value
        self.stats["demoted"] += 1
        if self.tracer is not None:
            self.tracer.instant("demotion", cat="residency", layer=layer,
                                expert=expert, slot=slot)

    def publish_ready(self, wait: bool = False) -> int:
        """Publish completed copies (window boundary). ``wait=True`` blocks on
        all in-flight copies (used at shutdown / in tests). Each pending
        promotion is probed on ITS OWN result arrays (``p.arrays``), never
        on the bank's current leaves — the bank only reflects the most
        recently issued copy."""
        if not self._pending:
            self._flush_maps()
            return 0
        still = []
        published = 0
        for p in self._pending:
            if not wait and p.stall_until > self.clock():
                # Injected DMA stall: the copy is "still on the wire" until
                # the deadline passes (the watchdog may cancel it first).
                still.append(p)
                continue
            ready = wait or all(_is_ready(a) for a in p.arrays)
            if ready and wait:
                for a in p.arrays:
                    if not a.is_deleted():
                        jax.block_until_ready(a)
            if not ready:
                still.append(p)
                continue
            if self.state[p.layer, p.expert] == Residency.PROMOTING.value:
                if p.corrupt:
                    # Modeled publish-time integrity check: the copy landed
                    # but its payload is bad — cancel instead of publishing,
                    # so the forward never sees the corrupt version.
                    self._cancel_pending(p, "corrupt")
                    continue
                self.slot_map_h[p.layer, p.expert] = p.slot
                self.slot_owner_h[p.layer, p.slot] = p.expert
                self.state[p.layer, p.expert] = Residency.RESIDENT_HI.value
                self.inflight_bytes -= p.nbytes
                published += 1
                self.stats["promoted"] += 1
                if self.tracer is not None:
                    # ``published=1`` certifies the publish-then-switch
                    # discipline: this span only closes published after its
                    # own result arrays probed ready — no forward can have
                    # observed a half-materialized expert.
                    self.tracer.async_end("promotion", p.seq,
                                          cat="residency", published=1)
                    if self.publish_hist is not None:
                        self.publish_hist.observe(
                            self.tracer.clock() - p.issue_ts)
            else:
                # Demoted while promoting — reclaim without publishing.
                self._cancel_pending(p, "demoted", fault=False)
        self._pending = still
        self._flush_maps()
        return published

    def _cancel_pending(self, p: PendingPromotion, reason: str,
                        fault: bool = True) -> None:
        """Cancel an in-flight promotion through the async-span cancel path.
        Idempotent: the slot frees and the reservation refunds exactly once
        no matter how many paths (publish, watchdog, demote) race to cancel."""
        if p.cancelled:
            return
        p.cancelled = True
        self.pools[p.layer].free(p.slot)
        self._tracker_for(self.pools[p.layer].shard_of(p.slot)).release(
            p.nbytes)
        self.state[p.layer, p.expert] = Residency.RESIDENT_LO.value
        self.inflight_bytes -= p.nbytes
        if fault:
            self.stats["fault_cancels"] += 1
            if self.fail_cb is not None:
                self.fail_cb(p.layer, p.expert)
        if self.tracer is not None:
            self.tracer.async_end("promotion", p.seq, cat="residency",
                                  published=0, reason=reason)

    def cancel_stuck(self, now: float, deadline_s: float) -> int:
        """Watchdog hook: cancel promotions in flight longer than
        ``deadline_s`` (engine-clock age since issue). The expert keeps
        serving lo and the controller re-candidates it next window."""
        n = 0
        still = []
        for p in self._pending:
            age = now - p.issue_ts
            if age > deadline_s:
                if self.tracer is not None:
                    self.tracer.instant("promo_timeout", cat="fault",
                                        layer=p.layer, expert=p.expert,
                                        age_s=round(age, 6))
                self._cancel_pending(p, "timeout")
                n += 1
            else:
                still.append(p)
        self._pending = still
        return n

    def refund_window(self, nbytes: int) -> None:
        """Return bytes charged via ``try_consume_window`` for a transfer
        that was subsequently aborted (e.g. an EP migration that rolled
        back) — the window budget should only price transfers that landed."""
        if self.rate_limit:
            self._window_used = max(0, self._window_used - nbytes)

    def pending_ages(self, now: float) -> List[tuple]:
        """(layer, expert, age_s) for every in-flight promotion — the
        stall-diagnostic snapshot's view of the transfer plane."""
        return [(p.layer, p.expert, round(now - p.issue_ts, 6))
                for p in self._pending]

    def _flush_maps(self) -> None:
        """Push the host-side handle table to the device arrays (tiny)."""
        self.bank.slot_map = jnp.asarray(self.slot_map_h)
        self.bank.slot_owner = jnp.asarray(self.slot_owner_h)

    # -- introspection ----------------------------------------------------
    def hi_set(self, layer: int) -> set[int]:
        return {int(e) for e in np.nonzero(self.slot_map_h[layer] >= 0)[0]}

    def pending_experts(self, layer: int) -> set[int]:
        """Experts with an in-flight (issued, unpublished) promotion on
        ``layer`` — the policy must treat these as already hi."""
        return {int(p.expert) for p in self._pending if p.layer == layer}

    def check_invariants(self) -> None:
        """VER invariants (tested property-based): every published handle
        resolves to a slot owned by that expert; budget counts match."""
        L, E = self.slot_map_h.shape
        n_used = 0
        used_shard = np.zeros(self.n_shards, np.int64)
        for l in range(L):
            for e in range(E):
                s = self.slot_map_h[l, e]
                if s >= 0:
                    assert self.slot_owner_h[l, s] == e, (l, e, s)
                    # sharded placement: expert's slot lives on its shard
                    assert self.pools[l].shard_of(s) == self.shard_of_expert(e), \
                        (l, e, s)
                    n_used += 1
                    used_shard[self.shard_of_expert(e)] += 1
        owners = (self.slot_owner_h >= 0).sum()
        assert owners == n_used, (owners, n_used)
        in_flight = len(self._pending)
        # Exactly-once refund audit: bytes reserved-but-unpublished must
        # equal the sum of OPEN promotion spans — a double refund (or a
        # leaked reservation) after an injected fault breaks this first.
        open_bytes = sum(p.nbytes for p in self._pending)
        assert self.inflight_bytes == open_bytes, \
            (self.inflight_bytes, open_bytes)
        assert not any(p.cancelled for p in self._pending)
        for p in self._pending:
            used_shard[self.pools[p.layer].shard_of(p.slot)] += 1
        if self.shard_trackers:
            for j, trk in enumerate(self.shard_trackers):
                assert trk.used == used_shard[j] * self.hi_bytes, \
                    (j, trk.used, used_shard[j])
        else:
            assert self.tracker.used == (n_used + in_flight) * self.hi_bytes, \
                (self.tracker.used, n_used, in_flight)


def _is_ready(arr) -> bool:
    """A copy's result is ready once computed — or once a later slot write
    consumed it by donation: that write runs after it, and every forward
    reads the live leaf the later write produces."""
    return arr.is_deleted() or arr.is_ready()
