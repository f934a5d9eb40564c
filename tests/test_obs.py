"""Observability layer: flight recorder and its spans, metrics registry,
promotion report, stats-schema contract, stall attribution, named scopes
in the decode program, and trace determinism.

The schema tests are CONTRACTS, not snapshots: ``backend.stats()`` must
return exactly ``STAT_KEYS + type(backend).STAT_EXTRAS`` and the engine adds
exactly ``ENGINE_STAT_KEYS`` — independent of configuration or what happened
during the run, so downstream benchmark tables never grow holes when a
feature sits idle.
"""
from __future__ import annotations

import contextlib
import json
import re

import jax
import numpy as np
import pytest

from repro.obs import (FlightRecorder, MetricsRegistry, ObsConfig,
                       Observability, load_chrome_trace, report, span)
from repro.serving import (EngineConfig, InferenceEngine, OffloadConfig,
                           Request, RequestStream, STAT_KEYS, make_backend)
from repro.serving.backends import (DynaExqBackend, Fp16Backend,
                                    OffloadBackend, StaticPTQBackend)
from repro.serving.engine import ENGINE_STAT_KEYS, LOAD_SNAPSHOT_KEYS
from repro.serving.scheduler import SchedulerConfig


# ---------------------------------------------------------------------------
# FlightRecorder unit behavior
# ---------------------------------------------------------------------------

def test_ring_buffer_bounds_and_drop_count():
    tr = FlightRecorder(capacity=4, clock=lambda: 0.0)
    for i in range(10):
        tr.instant("e", cat="t", i=i)
    assert len(tr) == 4
    assert tr.dropped == 6
    # Oldest dropped, newest kept.
    assert [e.args["i"] for e in tr.events()] == [6, 7, 8, 9]
    assert tr.to_chrome()["otherData"]["dropped_events"] == 6


def test_async_span_pairing():
    t = iter(np.arange(0.0, 10.0, 0.5))
    tr = FlightRecorder(clock=lambda: float(next(t)))
    a, b = tr.next_id(), tr.next_id()
    assert a != b
    tr.async_begin("promotion", a, cat="residency", layer=0)
    tr.async_begin("promotion", b, cat="residency", layer=1)
    tr.async_end("promotion", b, published=1)
    tr.async_end("promotion", a, published=0)
    # An unmatched begin stays open and is omitted.
    tr.async_begin("promotion", tr.next_id())
    spans = tr.spans("promotion")
    assert len(spans) == 2
    for bg, en in spans:
        assert bg.id == en.id and bg.ts < en.ts
    # Pairs are keyed by id, not arrival order: b ended first.
    assert spans[0][1].args["published"] == 1
    assert spans[1][1].args["published"] == 0


def test_chrome_export_round_trip(tmp_path):
    tr = FlightRecorder(clock=lambda: 1.0)
    tr.meta.update(num_experts=4, top_k=2)
    tr.instant("moe_forward", cat="engine", routed=8)
    path = str(tmp_path / "t.trace.json")
    tr.save(path)
    obj = load_chrome_trace(path)
    (ev,) = obj["traceEvents"]
    assert ev["name"] == "moe_forward" and ev["ph"] == "i"
    assert ev["ts"] == 1e6 and ev["tid"] == "engine"   # µs + cat lane
    assert ev["args"] == {"routed": 8}
    assert obj["otherData"]["num_experts"] == 4
    # Determinism: a second save writes identical bytes.
    path2 = str(tmp_path / "t2.trace.json")
    tr.save(path2)
    assert open(path, "rb").read() == open(path2, "rb").read()


# ---------------------------------------------------------------------------
# MetricsRegistry
# ---------------------------------------------------------------------------

def test_metrics_registry_kinds_and_values():
    m = MetricsRegistry()
    m.counter("c").inc()
    m.counter("c").inc(2.5)
    assert m.counter("c").value == 3.5
    with pytest.raises(ValueError):
        m.counter("c").inc(-1)
    m.gauge("g").set(7)
    m.gauge("g").set(4)
    with pytest.raises(TypeError):
        m.gauge("c")          # kind mismatch on an existing name
    h = m.histogram("h")
    for v in np.linspace(0.001, 0.1, 100):
        h.observe(v)
    snap = m.snapshot()
    assert snap["c"] == 3.5 and snap["g"] == 4.0
    assert snap["h_count"] == 100
    assert snap["h_p50"] == pytest.approx(np.percentile(
        np.linspace(0.001, 0.1, 100), 50))
    assert snap["h_p50"] <= snap["h_p95"]


def test_prometheus_exposition_and_jsonl_sink(tmp_path):
    path = str(tmp_path / "m.jsonl")
    m = MetricsRegistry(jsonl_path=path)
    m.counter("reqs", "total requests").inc(3)
    m.gauge("depth").set(2)
    m.histogram("lat").observe(0.002)
    m.sample(step=1, depth=2)
    m.sample(step=2, depth=0)
    m.close()
    text = m.to_prometheus()
    assert "# HELP reqs total requests" in text
    assert "# TYPE reqs counter" in text
    assert "reqs 3" in text
    assert "# TYPE lat histogram" in text
    assert 'lat_bucket{le="+Inf"} 1' in text
    assert "lat_count 1" in text
    rows = [json.loads(ln) for ln in open(path)]
    assert rows == [{"step": 1, "depth": 2}, {"step": 2, "depth": 0}]
    m.close()                  # idempotent


class _Annotations:
    """Stand-in for ``jax.profiler.TraceAnnotation`` that records every
    annotation entered: (name, args)."""

    def __init__(self):
        self.entered = []

    def __call__(self, name, **args):
        @contextlib.contextmanager
        def enter():
            self.entered.append((name, args))
            yield
        return enter()


@pytest.fixture()
def annotations(monkeypatch):
    rec = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", rec)
    return rec


def test_span_writes_begin_end_with_parent_and_one_annotation(annotations):
    t = iter(np.arange(0.0, 10.0, 1.0))
    tr = FlightRecorder(clock=lambda: float(next(t)))
    with tr.span("step", "engine") as outer:
        with tr.span("decode", "engine", rows=3) as inner:
            pass
        with tr.span("tick", "residency", parent=99):
            pass
    b_out, b_in, e_in, b_tick, e_tick, e_out = tr.events()
    assert [e.ph for e in tr.events()] == ["B", "B", "E", "B", "E", "E"]
    assert (b_out.id, b_in.id) == (outer, inner) and outer != inner
    assert b_out.args == {"parent": None}
    assert b_in.args == {"rows": 3, "parent": outer}
    assert b_tick.args["parent"] == 99          # an explicit parent wins
    assert e_in.id == inner and e_out.id == outer
    assert [e.ts for e in tr.events()] == [0.0, 1.0, 2.0, 3.0, 4.0, 5.0]
    # One profiler annotation per span, named <cat>.<name>, with its id.
    assert annotations.entered == [
        ("engine.step", {"id": outer}),
        ("engine.decode", {"id": inner, "rows": 3}),
        ("residency.tick", {"id": b_tick.id})]
    (pair,) = tr.spans("decode", cat="engine")
    assert pair == (b_in, e_in)


def test_span_without_recorder_is_one_shared_noop(annotations):
    a = span(None, "step", "engine", rows=1)
    b = span(None, "decode", "engine")
    assert a is b
    with a:
        pass
    assert annotations.entered == []


def test_promotion_report_live_and_saved(tmp_path):
    t = iter(np.arange(0.0, 100.0, 0.25))
    tr = FlightRecorder(clock=lambda: float(next(t)))
    sid = tr.next_id()
    tr.async_begin("promotion", sid, cat="residency", layer=0, expert=3)
    tr.async_end("promotion", sid, cat="residency", published=1)
    sid = tr.next_id()
    tr.async_begin("promotion", sid, cat="residency", layer=1, expert=5)
    tr.async_end("promotion", sid, cat="residency", published=0)
    rep = report(tr)
    assert set(rep) == {"promotions"}
    prom = rep["promotions"]
    assert prom["n_published"] == 1 and prom["n_cancelled"] == 1
    assert prom["publish_latency_p50_s"] == pytest.approx(0.25)
    # Identical numbers read back from the saved file.
    path = str(tmp_path / "t.trace.json")
    tr.save(path)
    assert report(path) == rep


# ---------------------------------------------------------------------------
# Stats-schema contract
# ---------------------------------------------------------------------------

_BACKEND_CLASSES = {"fp16": Fp16Backend, "static": StaticPTQBackend,
                    "dynaexq": DynaExqBackend, "offload": OffloadBackend}


def test_stat_extras_pinned():
    """The per-class extras are part of the public schema — changing them
    must be a deliberate act that also updates this pin."""
    assert _BackendExtras("fp16") == ()
    assert _BackendExtras("static") == ()
    assert _BackendExtras("dynaexq") == (
        "deferred", "lo_resident_frac", "hi_loads", "residency_ready_frac",
        "migrations", "quarantined")
    assert {"retries", "fault_cancels"} <= set(STAT_KEYS)
    assert "watchdog_cancels" in ENGINE_STAT_KEYS
    assert _BackendExtras("offload") == ("hits", "misses")
    assert len(STAT_KEYS) == len(set(STAT_KEYS))
    assert len(ENGINE_STAT_KEYS) == len(set(ENGINE_STAT_KEYS))
    # The overlap is exactly the scheduler counters the engine overwrites
    # on top of the backends' uniform defaults.
    assert set(STAT_KEYS) & set(ENGINE_STAT_KEYS) == {
        "preemptions", "resumes", "shed_requests", "downgraded"}


def _BackendExtras(kind):
    return _BACKEND_CLASSES[kind].STAT_EXTRAS


@pytest.mark.parametrize("kind", sorted(_BACKEND_CLASSES))
def test_stats_schema_exact(engine_factory, serving_setup, kind):
    """After a real run, ``engine.stats()`` contains exactly the uniform
    keys + the backend's declared extras + the engine's keys — no more, no
    fewer — regardless of which features the run exercised."""
    cfg, _ = serving_setup
    kw = {"ocfg": OffloadConfig(cache_experts_per_layer=1)} \
        if kind == "offload" else {}
    eng = engine_factory(kind, **kw)
    rng = np.random.default_rng(0)
    for _ in range(3):
        eng.submit(Request(tokens=rng.integers(0, cfg.vocab_size, size=10),
                           max_new_tokens=4))
    eng.drain()
    eng.flush()
    st = eng.stats()
    expect = set(STAT_KEYS) | set(_BackendExtras(kind)) \
        | set(ENGINE_STAT_KEYS)
    assert set(st) == expect, (
        f"{kind}: stats schema drift — extra {sorted(set(st) - expect)}, "
        f"missing {sorted(expect - set(st))}")
    assert all(isinstance(v, float) for v in st.values())


def test_load_snapshot_schema(engine_factory):
    eng = engine_factory("static")
    assert set(eng.load_snapshot()) == set(LOAD_SNAPSHOT_KEYS)


# ---------------------------------------------------------------------------
# Engine integration: events, meta, sampling, stall attribution
# ---------------------------------------------------------------------------

def _run(engine_factory, cfg, kind, obs, n=4, new=6, **kw):
    eng = engine_factory(kind, obs=obs, **kw)
    rng = np.random.default_rng(7)
    handles = [eng.submit(Request(
        tokens=rng.integers(0, cfg.vocab_size, size=12), max_new_tokens=new))
        for _ in range(n)]
    eng.drain()
    eng.flush()
    return eng, handles


def test_engine_emits_lifecycle_and_forward_events(engine_factory,
                                                   serving_setup):
    cfg, _ = serving_setup
    obs = Observability(ObsConfig())
    eng, handles = _run(engine_factory, cfg, "dynaexq", obs)
    tr = obs.tracer
    names = {e.name for e in tr.events()}
    assert {"submit", "admit", "finish", "step", "moe_forward"} <= names
    assert len(tr.instants("submit")) == len(handles)
    assert len(tr.instants("finish")) == len(handles)
    assert tr.meta["backend"] == "dynaexq"
    # Metrics sampled at step cadence.
    snap = obs.metrics.snapshot()
    assert "engine_active_experts" in snap
    assert snap["residency_hi_cells"] > 0


def test_promotion_lifecycle_spans(engine_factory, serving_setup):
    """Every completed promotion span ends with ``published`` ∈ {0, 1};
    a published end means the copy's result arrays were ready before any
    forward referenced the slot — the half-materialization audit."""
    cfg, _ = serving_setup
    obs = Observability(ObsConfig())
    eng, _ = _run(engine_factory, cfg, "dynaexq", obs)
    spans = obs.tracer.spans("promotion")
    assert spans, "dynaexq run produced no promotion lifecycle spans"
    assert any(e.args["published"] == 1 for _, e in spans)
    for b, e in spans:
        assert e.args["published"] in (0, 1)
        assert e.ts >= b.ts
        assert b.args["layer"] >= 0 and b.args["bytes"] > 0
    # Published count in the trace matches the backend's own accounting.
    n_pub = sum(e.args["published"] for _, e in spans)
    assert n_pub <= eng.stats()["promotions"] + len(spans)
    # Publish-latency histogram fed by the same spans.
    snap = obs.metrics.snapshot()
    assert snap["promotion_publish_latency_seconds_count"] == n_pub


def test_stall_exposure_attribution(engine_factory, serving_setup):
    """Offload demand misses stall the step; every handle active during a
    stalled step accrues the stall in its ``stall_exposure_s`` (exposure,
    not exclusive share — concurrent handles each saw the full wait)."""
    cfg, _ = serving_setup
    eng, handles = _run(engine_factory, cfg, "offload", None,
                        ocfg=OffloadConfig(cache_experts_per_layer=1))
    st = eng.stats()
    assert st["stall_s"] > 0
    exposed = [h.stall_exposure_s for h in handles]
    assert max(exposed) > 0
    # Exposure is bounded by the total stalled wall each handle could see.
    assert max(exposed) <= st["stall_s"] + 1e-9
    # A stall-free backend attributes nothing.
    eng2, handles2 = _run(engine_factory, cfg, "static", None)
    assert all(h.stall_exposure_s == 0.0 for h in handles2)


def test_disabled_tracer_records_nothing(engine_factory, serving_setup):
    cfg, _ = serving_setup
    obs = Observability(ObsConfig(trace=False, metrics=True))
    assert obs.tracer is None
    eng, _ = _run(engine_factory, cfg, "dynaexq", obs)
    assert eng.tracer is None
    assert "engine_active_experts" in obs.metrics.snapshot()
    with pytest.raises(ValueError):
        obs.save_trace("/tmp/never.json")


def test_obs_none_leaves_engine_bare(engine_factory, serving_setup):
    cfg, _ = serving_setup
    eng, _ = _run(engine_factory, cfg, "static", None)
    assert eng.obs is None and eng.tracer is None and eng.metrics is None
    assert eng.backend.tracer is None


# ---------------------------------------------------------------------------
# Spans at the engine's layer boundaries
# ---------------------------------------------------------------------------

#: (cat, name) of every duration span the engine and the residency backend
#: open; each nests under an ``engine.step``.
ENGINE_SPANS = {("engine", "step"), ("sched", "admit"),
                ("engine", "prefill"), ("engine", "decode"),
                ("engine", "sample"), ("residency", "observe"),
                ("residency", "tick"), ("residency", "promote")}


def test_engine_spans_nest_under_step(engine_factory, serving_setup,
                                      annotations):
    cfg, _ = serving_setup
    obs = Observability(ObsConfig(metrics=False))
    eng, handles = _run(engine_factory, cfg, "dynaexq", obs, n=6,
                        max_slots=2)
    evs = obs.tracer.events()
    begins = {e.id: e for e in evs if e.ph == "B"}
    assert {(e.cat, e.name) for e in begins.values()} == ENGINE_SPANS
    assert len(begins) == sum(e.ph == "E" for e in evs)
    for b in begins.values():
        if b.name == "step":
            assert b.args["parent"] is None
            continue
        chain = [b]
        while chain[-1].args["parent"] is not None:
            chain.append(begins[chain[-1].args["parent"]])
        assert (chain[-1].cat, chain[-1].name) == ("engine", "step"), \
            [(c.cat, c.name) for c in chain]
    decodes = [b for b in begins.values() if b.name == "decode"]
    assert len(decodes) == eng.counters["steps"]
    assert all(1 <= b.args["rows"] <= 2 for b in decodes)
    prefills = [b for b in begins.values() if b.name == "prefill"]
    assert len(prefills) == eng.counters["prefills"]
    assert all(b.args["bucket"] in eng.buckets for b in prefills)
    assert all(begins[b.args["parent"]].name == "admit" for b in prefills)
    promos = [b for b in begins.values() if b.name == "promote"]
    assert all(b.args["bytes"] > 0 for b in promos)
    # Every span entered the profiler once, under <cat>.<name>.
    assert sorted(n for n, _ in annotations.entered) == sorted(
        f"{b.cat}.{b.name}" for b in begins.values())
    # Each request queued once and left the queue once, for a prefill.
    queue = obs.tracer.spans("queue", cat="sched")
    assert sorted(b.id for b, _ in queue) == sorted(h.id for h in handles)
    assert all(e.args["how"] == "prefill" for _, e in queue)


def test_obs_none_serving_enters_no_annotation(engine_factory,
                                               serving_setup, annotations):
    cfg, _ = serving_setup
    eng, handles = _run(engine_factory, cfg, "dynaexq", None)
    assert all(h.tokens for h in handles)
    assert annotations.entered == []


def _queue_spans_engine(serving_setup, obs, **sched):
    cfg, params = serving_setup
    clone = jax.tree_util.tree_map(lambda x: x, params)
    return InferenceEngine(
        cfg, clone, make_backend("static", lo_bits=4),
        EngineConfig(max_slots=2, max_len=64,
                     scheduler=SchedulerConfig(**sched)), obs=obs)


@pytest.mark.parametrize("path", ["paged", "chunked", "resume"])
def test_queue_span_closes_once_per_admission(serving_setup, path):
    """``sched.queue`` opens at submit (and again at a preemption's
    requeue) and closes exactly once per admission: a prefill group, a
    chunked admission or a resume."""
    cfg, _ = serving_setup
    obs = Observability(ObsConfig(metrics=False))
    rng = np.random.default_rng(3)
    if path == "chunked":
        eng = _queue_spans_engine(serving_setup, obs, prefill_chunk=32)
        plen = 48
    else:
        eng = _queue_spans_engine(serving_setup, obs)
        plen = 12
    handles = [eng.submit(Request(
        tokens=rng.integers(0, cfg.vocab_size, size=plen),
        max_new_tokens=5)) for _ in range(3)]
    if path == "resume":
        eng.step()
        victim = next(h for h in handles if h.state.value == "running")
        eng.preempt(victim)
    eng.drain()
    assert all(h.state.value == "finished" for h in handles)
    tr = obs.tracer
    queue = tr.spans("queue", cat="sched")
    opened = [e for e in tr.events() if e.name == "queue" and e.ph == "b"]
    assert len(queue) == len(opened)           # none left open
    how = {}
    for _, e in queue:
        how.setdefault(e.id, []).append(e.args["how"])
    first = {"paged": "prefill", "chunked": "chunk", "resume": "prefill"}
    assert set(how) == {h.id for h in handles}
    for h in handles:
        want = [first[path]] + (["resume"] if h.preempts else [])
        assert how[h.id] == want
    assert sum(h.preempts for h in handles) == (path == "resume")


# ---------------------------------------------------------------------------
# Named scopes in the decode program
# ---------------------------------------------------------------------------

def _op_list(text):
    """The program's ops in order, locations left out."""
    return re.findall(r"\b(?:stablehlo|chlo|func|tpu|sdy|mhlo)\.[a-z_]+",
                      re.sub(r"^#loc.*$", "", text, flags=re.M))


def test_decode_program_scopes_change_no_op(engine_factory, monkeypatch):
    eng = engine_factory("dynaexq", max_slots=2)
    scoped = eng.decode_program_text()
    for scope in ("attn/", "kv_write/", "moe/router/", "moe/dispatch/",
                  "moe/ffn/", "moe/combine/", "lm_head/"):
        assert f'"{scope}' in scoped or f'/{scope}' in scoped, scope
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    jax.clear_caches()          # trace again, without the scopes
    try:
        bare = eng.decode_program_text()
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert "moe/ffn" not in bare and "attn/" not in bare
    ops = _op_list(scoped)
    assert len(ops) > 100 and ops == _op_list(bare)


# ---------------------------------------------------------------------------
# Trace determinism under the virtual clock
# ---------------------------------------------------------------------------

def test_virtual_clock_replay_traces_byte_identical(engine_factory,
                                                    serving_setup, tmp_path):
    """Two ``replay(realtime=False)`` runs of the same stream write
    byte-identical trace files: every event arg is count-derived and every
    timestamp comes off the virtual clock. Static backend — its residency
    never depends on wall-clock cadence."""
    cfg, _ = serving_setup

    def one(tag):
        obs = Observability(ObsConfig(metrics=False))
        eng = engine_factory("static", obs=obs)
        stream = RequestStream(cfg.vocab_size, phases=[("text", 5)],
                               prompt_len=10, prompt_len_jitter=3,
                               max_new_tokens=5, arrival_rate_rps=200.0,
                               seed=11)
        handles = eng.replay(stream, realtime=False)
        assert all(h.tokens for h in handles)
        path = str(tmp_path / f"{tag}.trace.json")
        obs.save_trace(path)
        return open(path, "rb").read()

    a, b = one("a"), one("b")
    assert len(a) > 200
    assert a == b
    # The engine's duration and queue spans are part of what replays
    # byte-identically.
    phases = {(e["ph"], e["name"]) for e in json.loads(a)["traceEvents"]}
    assert {("B", "step"), ("E", "decode"), ("b", "queue"),
            ("e", "queue")} <= phases
    # And the events are genuinely virtual-clock stamped: the first event
    # sits near t=0, not at perf_counter's epoch.
    first = json.loads(a)["traceEvents"][0]
    assert first["ts"] < 60e6
