"""Pinned host-DRAM expert store — the third tier of the residency ladder.

The ladder is hi-bf16 ↔ lo-int4/int2 ↔ host-DRAM, all governed by the global
allocator (``core.allocator``). This module owns everything host-side:

* the **hi source** rows ``TransitionManager`` copies from on promotion —
  either materialized upfront (``np.asarray`` of the dense experts, the
  classic path) or lazily from checkpoint shards via ``hi_loader``
  (streaming cold start: the host tier itself backfills in hotness order,
  so a large model never needs to fully materialize);
* the **lo staging pipeline**: host→lo promotion and cold-start backfill
  issue real async H2D writes of the packed lo rows
  (``ver.write_lo_expert``) and publish by flipping the residency masks
  only once the copy's own result arrays are ready — the same
  publish-then-switch discipline ``TransitionManager`` uses for hi slots,
  so a forward pass never observes a partially materialized expert;
* the residency masks: ``lo_valid`` (device lo rows hold real weights —
  monotone under serving, the cold-start gate) and ``lo_resident``
  (the allocator's accounting: a valid-but-nonresident cell has been
  demoted to the host tier and pays a modeled demand-fetch stall when
  routed);
* the ``FetchModel`` transfer-cost model shared with ``OffloadBackend``
  (absorbed into the ladder rather than sitting beside it).

The store duck-types the ``host_hi`` mapping interface (``items`` /
``__getitem__`` / ``__setitem__``) that ``TransitionManager`` and
``EPCoordinator`` already speak, plus ``ensure_hi`` for lazy shard loads.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.core.ver import ExpertBankQ, write_lo_expert, write_lo_rows
from repro.fault.inject import TransferFault
from repro.fault.retry import RetryExhausted, RetryPolicy, retry_call


@dataclasses.dataclass
class FetchModel:
    """Deterministic host↔device transfer-cost model (PCIe gen4 x16 by
    default — the paper's A6000). Layered on measured compute so backend
    comparisons reflect transfer volume, not CPU noise."""

    gbps: float = 16.0

    @property
    def bytes_per_s(self) -> float:
        return self.gbps * 1e9

    def stall_s(self, demand_bytes: int, overlap_bytes: int = 0,
                compute_s: float = 0.0) -> float:
        """Critical-path seconds: demand fetches always stall; overlapped
        (prefetch) bytes hide under ``compute_s`` and only their spill
        stalls."""
        spill = max(0.0, overlap_bytes - compute_s * self.bytes_per_s)
        return (demand_bytes + spill) / self.bytes_per_s


@dataclasses.dataclass
class _PendingLo:
    layer: int
    expert: int
    resident: bool            # reserve-accounted (vs transient cold-stage)
    nbytes: int
    arrays: tuple             # THIS copy's result arrays (probe these —
                              # the bank's leaves track only the newest
                              # staged copy, same hazard as hi promotions)


class HostExpertStore:
    def __init__(self, shapes: Dict[str, tuple],
                 hi: Optional[Dict[str, np.ndarray]] = None,
                 hi_loader: Optional[Callable[[int, int],
                                              Dict[str, np.ndarray]]] = None,
                 lo_loader: Optional[Callable[[int],
                                              Dict[str, np.ndarray]]] = None,
                 lo_valid_init: bool = True):
        """``shapes``: name → (L, E, K, N) dense shapes. ``hi``: fully
        materialized host rows (classic path). ``hi_loader(l, e)``: lazy
        per-expert source (streaming). ``lo_loader(l)``: per-layer packed
        lo rows, keys ``f"{name}.packed"``/``f"{name}.scales"`` with
        leading dim E (streaming cold start + host→lo staging)."""
        first = next(iter(shapes.values()))
        self.L, self.E = int(first[0]), int(first[1])
        self.shapes = dict(shapes)
        if hi is None and hi_loader is None:
            raise ValueError("need materialized hi rows or a hi_loader")
        self.hi: Dict[str, np.ndarray] = hi if hi is not None else {
            n: np.zeros(tuple(s), np.float32)
            for n, s in sorted(shapes.items())}
        self.hi_present = np.full((self.L, self.E), hi is not None, bool)
        self._hi_loader = hi_loader
        self._lo_loader = lo_loader
        self._lo_cache: Tuple[Optional[int], Optional[Dict]] = (None, None)
        self.lo_valid = np.full((self.L, self.E), lo_valid_init, bool)
        self.lo_resident = self.lo_valid.copy()
        self._staging: List[_PendingLo] = []
        self.stats = {"hi_loads": 0, "hi_bytes_loaded": 0,
                      "lo_staged": 0, "lo_bytes_staged": 0,
                      "retries": 0, "retry_stall_s": 0.0, "quarantines": 0}
        self.tracer = None   # FlightRecorder, attached by the serving layer
        # Fault tolerance: host loads and staging retry under ``retry``;
        # a cell whose staging source exhausts its retries is quarantined —
        # served from host (demand-fetch stall, zero-weight device rows
        # never referenced as valid) instead of blocking ``lo_complete``
        # forever. Healing: a later successful re-stage clears the flag.
        self.injector = None  # repro.fault.inject.FaultInjector
        self.retry = RetryPolicy()
        self.quarantined = np.zeros((self.L, self.E), bool)

    # -- host_hi mapping interface (TransitionManager / EPCoordinator) ----
    def items(self):
        return self.hi.items()

    def __getitem__(self, name: str) -> np.ndarray:
        return self.hi[name]

    def __setitem__(self, name: str, arr: np.ndarray) -> None:
        self.hi[name] = arr

    def swap_experts(self, layer: int, e: int, f: int) -> None:
        """EP relabeling: the residency/presence masks follow their expert
        (the hi row swap itself runs through the mapping interface)."""
        for m in (self.hi_present, self.lo_valid, self.lo_resident,
                  self.quarantined):
            m[layer, [e, f]] = m[layer, [f, e]]

    # -- fault plumbing ---------------------------------------------------
    def _seed(self) -> int:
        return self.injector.seed if self.injector is not None else 0

    def _fire(self, site: str, **ctx) -> None:
        """Evaluate the fault plan at a host-transfer site. ``stall`` is
        absorbed as modeled stall seconds; ``fail`` raises a retryable
        `TransferFault`; ``corrupt`` is a failed checksum — also retried."""
        if self.injector is None:
            return
        f = self.injector.fire(site, **ctx)
        if f is None:
            return
        if f.kind == "stall":
            self.stats["retry_stall_s"] += f.stall_s
            return
        raise TransferFault(site, kind=f.kind, seq=f.seq)

    def _retry(self, fn, site: str, key: int):
        """Run one host transfer under the shared retry policy, accounting
        retries + modeled backoff. `RetryExhausted` propagates to the
        caller's graceful-degradation path."""
        try:
            out, retries, waited = retry_call(
                fn, self.retry, seed=self._seed(), key=key, site=site,
                tracer=self.tracer)
        except RetryExhausted as e:
            # The attempts were still made (and their backoff modeled) —
            # account them before the degradation path takes over.
            self.stats["retries"] += e.attempts - 1
            self.stats["retry_stall_s"] += e.waited_s
            raise
        if retries:
            self.stats["retries"] += retries
            self.stats["retry_stall_s"] += waited
        return out

    # -- hi tier (host side) ----------------------------------------------
    def ensure_hi(self, layer: int, expert: int) -> None:
        """Materialize one expert's host hi rows (lazy shard read). Called
        by ``TransitionManager._issue_copy`` right before the H2D copy —
        hi backfill therefore follows promotion order, i.e. hotness."""
        if self.hi_present[layer, expert]:
            return
        if self._hi_loader is None:
            raise RuntimeError(
                f"expert ({layer}, {expert}) absent from the host store "
                f"and no hi_loader configured")

        def attempt():
            self._fire("host_hi", layer=layer, expert=expert)
            return self._hi_loader(layer, expert)

        rows = self._retry(attempt, "host_hi", (layer << 16) | expert)
        nbytes = 0
        for name, arr in self.hi.items():
            r = np.asarray(rows[name])
            arr[layer, expert] = r.astype(arr.dtype)
            nbytes += r.nbytes
        self.hi_present[layer, expert] = True
        self.stats["hi_loads"] += 1
        self.stats["hi_bytes_loaded"] += nbytes

    # -- lo tier (device staging) -----------------------------------------
    def _lo_rows(self, layer: int) -> Dict[str, np.ndarray]:
        if self._lo_loader is None:
            raise RuntimeError("no lo_loader configured for lo staging")
        cl, rows = self._lo_cache
        if cl != layer:
            def attempt():
                self._fire("host_lo", layer=layer)
                return self._lo_loader(layer)
            rows = self._retry(attempt, "host_lo", layer)
            self._lo_cache = (layer, rows)
        return rows

    def stage_lo(self, bank: ExpertBankQ, layer: int, expert: int,
                 resident: bool = True) -> int:
        """Issue the async H2D write of one expert's packed lo rows into
        the bank; returns the bytes in flight. The rows stay unreferenced
        (``lo_valid`` unflipped) until ``publish_lo`` sees the copy's own
        result arrays ready."""
        def fetch():
            self._fire("stage_lo", layer=layer, experts=1)
            return self._lo_rows(layer)
        rows = self._retry(fetch, "stage_lo", (layer << 16) | expert)
        arrays = []
        nbytes = 0
        li, ei = np.int32(layer), np.int32(expert)
        for name, qt in bank.lo.items():
            packed = write_lo_expert(qt.packed, li, ei,
                                     rows[f"{name}.packed"][expert])
            scales = write_lo_expert(qt.scales, li, ei,
                                     rows[f"{name}.scales"][expert])
            bank.lo[name] = dataclasses.replace(qt, packed=packed,
                                                scales=scales)
            arrays += [packed, scales]
            nbytes += (packed.nbytes + scales.nbytes) // (self.L * self.E)
        self._staging.append(_PendingLo(layer, expert, resident, nbytes,
                                        tuple(arrays)))
        self.stats["lo_staged"] += 1
        self.stats["lo_bytes_staged"] += nbytes
        if self.tracer is not None:
            self.tracer.instant("host_stage", cat="host", layer=layer,
                                experts=1, bytes=nbytes)
        return nbytes

    def stage_lo_batch(self, bank: ExpertBankQ, layer: int, experts,
                       resident) -> int:
        """Bulk-stage several experts of one layer: ONE device write per
        bank leaf instead of one per expert cell — the cold-start pump's
        fast path (dispatch overhead, not bytes, dominates tiny rows).
        ``resident`` is a per-expert bool sequence; publish semantics are
        identical to issuing ``stage_lo`` per cell."""
        idx = np.asarray(list(experts), np.int32)
        res = np.asarray(list(resident), bool)

        def fetch():
            self._fire("stage_lo", layer=layer, experts=int(idx.size))
            return self._lo_rows(layer)

        rows = self._retry(fetch, "stage_lo", layer)
        arrays = []
        nbytes = 0
        li = np.int32(layer)
        for name, qt in bank.lo.items():
            packed = write_lo_rows(qt.packed, li, idx,
                                   rows[f"{name}.packed"][idx])
            scales = write_lo_rows(qt.scales, li, idx,
                                   rows[f"{name}.scales"][idx])
            bank.lo[name] = dataclasses.replace(qt, packed=packed,
                                                scales=scales)
            arrays += [packed, scales]
            nbytes += (packed.nbytes + scales.nbytes) * idx.size \
                // (self.L * self.E)
        self._staging.append(_PendingLo(layer, idx, res, nbytes,
                                        tuple(arrays)))
        self.stats["lo_staged"] += int(idx.size)
        self.stats["lo_bytes_staged"] += nbytes
        if self.tracer is not None:
            self.tracer.instant("host_stage", cat="host", layer=layer,
                                experts=int(idx.size), bytes=nbytes)
        return nbytes

    def publish_lo(self, wait: bool = False) -> int:
        """Flip residency masks for completed staging copies (window
        boundary). Each pending entry is probed on ITS OWN result arrays."""
        if not self._staging:
            return 0
        still: List[_PendingLo] = []
        published = 0
        for p in self._staging:
            ready = wait or all(a.is_ready() for a in p.arrays)
            if ready and wait:
                for a in p.arrays:
                    jax.block_until_ready(a)
            if not ready:
                still.append(p)
                continue
            ex = np.atleast_1d(np.asarray(p.expert))
            res = np.broadcast_to(np.atleast_1d(np.asarray(p.resident)),
                                  ex.shape)
            self.lo_valid[p.layer, ex] = True
            self.lo_resident[p.layer, ex[res]] = True
            # Healing: real rows just landed for these cells — any
            # quarantine from an earlier failed staging is lifted.
            self.quarantined[p.layer, ex] = False
            published += int(ex.size)
        self._staging = still
        if published and self.tracer is not None:
            self.tracer.instant("lo_publish", cat="host", experts=published)
        return published

    @property
    def staging_inflight(self) -> int:
        return len(self._staging)

    @property
    def lo_complete(self) -> bool:
        """Every expert's device lo rows hold real weights (or the cell is
        quarantined and served from host) — the serving gate on a streaming
        cold start. Quarantine keeps one unreadable shard from blocking
        ``serving_ready()`` forever."""
        return bool((self.lo_valid | self.quarantined).all()) \
            and not self._staging

    def quarantine(self, layer: int, experts) -> int:
        """Mark cells whose staging source exhausted its retries: they are
        served from the host tier (demand-fetch pricing, requests routed to
        them flagged degraded) and re-staged opportunistically until a copy
        lands and heals them."""
        ex = np.atleast_1d(np.asarray(experts, np.int64))
        ex = ex[~self.lo_valid[layer, ex]]      # valid cells need no rescue
        fresh = ex[~self.quarantined[layer, ex]]
        self.quarantined[layer, fresh] = True
        n = int(fresh.size)
        self.stats["quarantines"] += n
        if n and self.tracer is not None:
            self.tracer.instant("quarantine", cat="fault", layer=layer,
                                experts=n)
        return n

    def check_invariants(self) -> None:
        """Residency-ladder invariants: a lo-resident cell must be valid
        (accounting never outruns materialization), and a staged-but-
        unpublished cell is never already marked valid by that staging."""
        assert (self.lo_valid | ~self.lo_resident).all(), \
            "lo_resident cell with invalid device rows"
        # A quarantined cell is by definition not materialized on device:
        # never valid (healing clears the flag at publish) and never
        # counted resident by the allocator.
        assert not (self.quarantined & self.lo_valid).any(), \
            "quarantined cell marked lo_valid"
        assert not (self.quarantined & self.lo_resident).any(), \
            "quarantined cell counted lo_resident"
        if self._hi_loader is None:
            assert self.hi_present.all()
