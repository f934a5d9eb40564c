"""Flight recorder: a bounded ring buffer of typed serving events.

The recorder is the serving stack's black box. Every interesting host-side
transition — engine steps, admissions, preemptions, the promotion pipeline's
``issue → copy → publish`` phases, EP ownership migrations, host-tier demand
fetches, speculative rounds, shed/downgrade decisions — lands here as a
typed event stamped on the **engine clock** (``InferenceEngine._now``):
wall time normally, the virtual clock under ``replay(realtime=False)``, so
CI replays produce byte-identical trace files while realtime runs produce
perfetto-viewable timelines.

Design constraints, in order:

* **Zero cost when absent.** No recorder instance ⇒ no event objects, no
  dict building, nothing — every instrumentation site guards on
  ``tracer is not None`` before touching arguments. The decode hot path is
  identical with observability disabled.
* **Bounded.** The buffer is a ``deque(maxlen=capacity)``; overflow drops
  the oldest events and counts them (``dropped``) instead of growing.
* **Deterministic export.** ``save()`` emits Chrome trace-event JSON with
  sorted keys and no wall-clock metadata, so two runs with identical event
  streams write identical bytes.

Event vocabulary (``name`` / ``cat``):

========================  ==========  =========================================
name                      cat         args
========================  ==========  =========================================
``step``                  engine      step, active, queued, active_experts,
                                      hi/lo/host residency cells, headroom
``moe_forward``           moe         routed, layers, active, active_hi,
                                      active_lo, active_host, published_hi,
                                      tokens, prefill
``submit``/``shed``/      sched       rid, qos
``downgrade``/
``shed_expired``
``admit``/``finish``/     sched       rid, slot (…)
``preempt``/``resume``
``promo_request``/        residency   layer, expert (…)
``demo_request``/
``demotion``/
``promo_deferred``
``promotion``             residency   async span: begin at copy issue
                                      (layer/expert/slot/bytes), end at
                                      publish (published=1) or cancellation
``ep_migration``          residency   layer, e, f, bytes
``host_fetch``            host        pos, n, bytes, stall_s
``host_stage``/           host        layer(s), n, bytes
``lo_publish``
``spec_round``            spec        rows, drafted, accepted
``fault_injected``        fault       site, kind, seq (injector fired)
``retry``                 fault       site, attempt, backoff_s — includes
                                      host demand-fetch re-reads
``fault_cancel``          fault       layer, expert, reason (promotion or
                                      migration aborted after retries)
``promo_timeout``         fault       layer, expert, age_s (watchdog
                                      cancelled a stuck promotion)
``watchdog_cancel``       fault       rid, idle_s (no-progress request
                                      preempted and requeued)
``quarantine``            fault       layer, n, experts served from host
                                      until their lo rows re-stage
========================  ==========  =========================================

Duration spans (``span``): ``B``/``E`` pairs in the ring, each ``B`` with
its span ``id`` and ``parent`` (the innermost span open when it began), and
the same span written into the JAX profiler's host plane as
``jax.profiler.TraceAnnotation("<cat>.<name>", id=…, …)`` — on the clock
the device operations share, so a profile names every idle gap by the
innermost span it falls in. ``sched.queue`` is an async span (``b``/``e``,
id = request id): it waits across steps and has no host-thread extent.

========================  ==========  =========================================
name                      cat         where, args
========================  ==========  =========================================
``step``                  engine      ``InferenceEngine.step``, whole body
``queue``                 sched       async: ``submit`` (or a preemption's
                                      requeue) → the handle leaves the queue
                                      for a prefill group, a chunked
                                      admission or a resume; rid
``admit``                 sched       ``_admit``: admission bookkeeping plus
                                      the prefills it runs
``prefill``               engine      one prefill or chunk forward, dispatch
                                      → ``block_until_ready``; rows, bucket
``decode``                engine      one ``_decode_one`` forward, dispatch →
                                      ``block_until_ready``; rows
``sample``                engine      argmax / sampling rows to host, tokens
                                      appended
``observe``/``tick``      residency   ``backend.observe`` / ``backend.tick``
``promote``               residency   ``TransitionManager._issue_copy``: host
                                      row read + ``write_hi_slot`` dispatch;
                                      layer, expert, bytes
========================  ==========  =========================================

Named scopes inside the jitted programs (``jax.named_scope``; compile-time
metadata, no op changes): ``attn`` (QKV projection, RoPE, attention, output
projection), ``kv_write``, ``moe/router``, ``moe/dispatch``, ``moe/ffn``
(the expert GEMMs: the ragged Pallas kernels on the chip), ``moe/combine``,
``lm_head``, and ``residency/promote`` on ``write_hi_slot``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import json
import time
from collections import deque
from typing import Callable, ContextManager, Dict, List, Optional, Tuple

import jax
import numpy as np


#: The begin phase each end phase closes.
_BEGIN_OF = {"e": "b", "E": "B"}
#: What ``span`` returns with no recorder: one shared context that does
#: nothing, so an untraced call site builds no span and formats no args.
_NO_SPAN = contextlib.nullcontext()


@dataclasses.dataclass
class TraceEvent:
    """One flight-recorder entry. ``ph`` follows the Chrome trace-event
    phase vocabulary: ``i`` instant, ``B``/``E`` duration span,
    ``b``/``e`` async span (paired by ``id``)."""

    ts: float                       # seconds on the engine clock
    ph: str
    name: str
    cat: str = ""
    id: Optional[int] = None        # async-span correlation id
    args: Optional[Dict] = None


class FlightRecorder:
    """Bounded typed-event ring buffer with a span API and Chrome export.

    ``clock`` is injected by the engine (``engine._now``) so replay runs
    under the virtual clock produce deterministic timestamps; standalone
    use falls back to ``time.perf_counter``.
    """

    def __init__(self, capacity: int = 1 << 16,
                 clock: Optional[Callable[[], float]] = None):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = int(capacity)
        self.clock: Callable[[], float] = clock or time.perf_counter
        self._events: deque[TraceEvent] = deque(maxlen=self.capacity)
        self.dropped = 0
        self._ids = itertools.count(1)
        self._open: List[int] = []          # ids of the open ``span``s
        #: Run-level metadata (backend, dispatch, router shape) exported
        #: with the trace.
        self.meta: Dict = {}

    # -- recording --------------------------------------------------------
    def _push(self, ev: TraceEvent) -> None:
        if len(self._events) == self.capacity:
            self.dropped += 1
        self._events.append(ev)

    def instant(self, name: str, cat: str = "", **args) -> None:
        self._push(TraceEvent(self.clock(), "i", name, cat,
                              args=args or None))

    def begin(self, name: str, cat: str = "", **args) -> None:
        self._push(TraceEvent(self.clock(), "B", name, cat,
                              args=args or None))

    def end(self, name: str, cat: str = "", **args) -> None:
        self._push(TraceEvent(self.clock(), "E", name, cat,
                              args=args or None))

    def next_id(self) -> int:
        """Fresh correlation id for an async span (promotion lifecycle)."""
        return next(self._ids)

    @contextlib.contextmanager
    def span(self, name: str, cat: str, parent: Optional[int] = None,
             **args):
        """Duration span: ``B``/``E`` in the ring on the recorder's clock,
        the ``B`` carrying a fresh span ``id`` and its ``parent`` (default:
        the innermost span still open), and the same region entered as a
        ``jax.profiler.TraceAnnotation`` named ``<cat>.<name>`` with the id
        and ``args``, so the profiler's trace holds it beside the device
        operations. Yields the span id."""
        sid = self.next_id()
        if parent is None and self._open:
            parent = self._open[-1]
        self._push(TraceEvent(self.clock(), "B", name, cat, id=sid,
                              args=dict(args, parent=parent)))
        self._open.append(sid)
        try:
            with jax.profiler.TraceAnnotation(f"{cat}.{name}", id=sid,
                                              **args):
                yield sid
        finally:
            self._open.pop()
            self._push(TraceEvent(self.clock(), "E", name, cat, id=sid))

    def async_begin(self, name: str, span_id: int, cat: str = "",
                    **args) -> None:
        self._push(TraceEvent(self.clock(), "b", name, cat, id=span_id,
                              args=args or None))

    def async_end(self, name: str, span_id: int, cat: str = "",
                  **args) -> None:
        self._push(TraceEvent(self.clock(), "e", name, cat, id=span_id,
                              args=args or None))

    # -- introspection ----------------------------------------------------
    def __len__(self) -> int:
        return len(self._events)

    def events(self) -> List[TraceEvent]:
        return list(self._events)

    def instants(self, name: str) -> List[TraceEvent]:
        return [e for e in self._events if e.ph == "i" and e.name == name]

    def spans(self, name: str, cat: Optional[str] = None
              ) -> List[Tuple[TraceEvent, TraceEvent]]:
        """Completed spans of ``name`` (and ``cat``, when given), async
        ``b``/``e`` and duration ``B``/``E`` alike, paired by id, in end
        order. Unmatched begins (still open, or whose partner fell off the
        ring) are omitted."""
        begins: Dict[Tuple[str, int], TraceEvent] = {}
        out: List[Tuple[TraceEvent, TraceEvent]] = []
        for e in self._events:
            if e.name != name or e.id is None or \
                    (cat is not None and e.cat != cat):
                continue
            if e.ph in ("b", "B"):
                begins[(e.ph, e.id)] = e
            elif e.ph in _BEGIN_OF and (_BEGIN_OF[e.ph], e.id) in begins:
                out.append((begins.pop((_BEGIN_OF[e.ph], e.id)), e))
        return out

    # -- export -----------------------------------------------------------
    def to_chrome(self) -> Dict:
        """Chrome trace-event JSON object (perfetto / chrome://tracing).
        Timestamps convert to microseconds; the category doubles as the
        track (pid=0, tid=cat) so each subsystem gets its own lane."""
        evs = []
        for e in self._events:
            d: Dict = {"name": e.name, "ph": e.ph, "cat": e.cat or "misc",
                       "ts": round(e.ts * 1e6, 3), "pid": 0,
                       "tid": e.cat or "misc"}
            if e.id is not None:
                d["id"] = e.id
            if e.args:
                d["args"] = e.args
            evs.append(d)
        return {"traceEvents": evs, "displayTimeUnit": "ms",
                "otherData": dict(self.meta, dropped_events=self.dropped)}

    def save(self, path: str) -> None:
        """Write the Chrome trace JSON. Deterministic: sorted keys, fixed
        separators, no wall-clock metadata — under the virtual clock two
        identical replays produce byte-identical files."""
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f, sort_keys=True,
                      separators=(",", ":"))
            f.write("\n")


def span(tracer: Optional[FlightRecorder], name: str, cat: str,
         **args) -> ContextManager:
    """``tracer.span(name, cat, **args)``, or with no recorder the shared
    no-op context: the call sites' one pointer check."""
    return _NO_SPAN if tracer is None else tracer.span(name, cat, **args)


def load_chrome_trace(path: str) -> Dict:
    """Read a trace written by ``FlightRecorder.save`` (or any Chrome
    trace-event JSON object with a ``traceEvents`` list)."""
    with open(path) as f:
        return json.load(f)


def promotion_report(trace) -> Dict:
    """Promotion publish-latency percentiles from the lifecycle spans
    (copy issue → publish) plus the half-materialization audit: every
    publish event must carry ``published`` ∈ {0, 1} — a span that ended
    published implies its copy's result arrays were ready, i.e. no forward
    observed a half-materialized expert. ``trace``: a live recorder, a
    Chrome trace object or its path."""
    if isinstance(trace, FlightRecorder):
        spans = [(b.ts, e.ts, (e.args or {}))
                 for b, e in trace.spans("promotion")]
    else:
        obj = load_chrome_trace(trace) if isinstance(trace, str) else trace
        begins: Dict[int, float] = {}
        spans = []
        for ev in obj.get("traceEvents", []):
            if ev.get("name") != "promotion":
                continue
            if ev.get("ph") == "b":
                begins[ev["id"]] = ev["ts"] / 1e6
            elif ev.get("ph") == "e" and ev.get("id") in begins:
                spans.append((begins.pop(ev["id"]), ev["ts"] / 1e6,
                              ev.get("args", {})))
    lat = [e - b for b, e, a in spans if a.get("published")]
    cancelled = sum(1 for _, _, a in spans if not a.get("published"))
    arr = np.asarray(lat) if lat else np.zeros(0)
    return {
        "n_published": len(lat),
        "n_cancelled": cancelled,
        "publish_latency_p50_s": float(np.percentile(arr, 50)) if lat
        else 0.0,
        "publish_latency_p95_s": float(np.percentile(arr, 95)) if lat
        else 0.0,
        "publish_latency_max_s": float(arr.max()) if lat else 0.0,
    }
