"""Observability tax: serve the same decode workload with the flight
recorder + metrics registry attached and detached, and assert the attached
run keeps ≥95% of the detached throughput (the obs layer must stay off the
jit path — everything it records is host-side Python on already-fetched
counters).

The obs-on run's trace is saved to ``experiments/obs.trace.json`` (Chrome
trace-event JSON, viewable in Perfetto) and read back through
``repro.obs.report`` so the artifact also carries the promotion
publish-latency percentiles, regenerated on every benchmark run.

``BENCH_SMOKE=1`` shrinks reps/tokens for CI.
"""
from __future__ import annotations

import json
import os
import time

from benchmarks.common import (BENCH_SMOKE, bench_backend, clone,
                               trained_model)
from repro.core import ControllerConfig
from repro.serving import EngineConfig, InferenceEngine, Request

# Even the smoke run needs a measurable wall: at ~80 tok/s a 4-token decode
# finishes in ~0.2 s and scheduler jitter alone reads as >5% "overhead".
N_NEW = 8 if BENCH_SMOKE else 12
BATCH = 4
PROMPT = 32
REPS = 3 if BENCH_SMOKE else 4
MAX_OVERHEAD = 0.05
JSON_OUT = os.path.join(os.path.dirname(__file__), "..", "experiments",
                        "BENCH_obs.json")
TRACE_OUT = os.path.join(os.path.dirname(__file__), "..", "experiments",
                         "obs.trace.json")


def _engine(cfg, params, obs):
    return InferenceEngine(
        cfg, clone(params),
        bench_backend("dynaexq", controller=ControllerConfig(
            update_interval_s=0.0)),
        EngineConfig(max_slots=BATCH, max_len=64), obs=obs)


def _serve_once(cfg, params, toks, obs):
    eng = _engine(cfg, params, obs)
    t0 = time.perf_counter()
    handles = [eng.submit(Request(tokens=t, max_new_tokens=N_NEW))
               for t in toks]
    eng.drain()
    wall = time.perf_counter() - t0
    eng.flush()
    return sum(len(h.tokens) for h in handles) / wall


def run(report):
    from repro.obs import Observability, ObsConfig
    from repro.obs import report as obs_report
    cfg, params, task = trained_model()
    toks = list(task.sample(BATCH, PROMPT, seed=3))
    _serve_once(cfg, params, toks, None)               # warm-up compile
    tps = {"off": 0.0, "on": 0.0}
    last_obs = None
    for _ in range(REPS):                              # interleaved reps so
        tps["off"] = max(tps["off"],                   # drift hits both arms
                         _serve_once(cfg, params, toks, None))
        obs = Observability(ObsConfig())
        tps["on"] = max(tps["on"], _serve_once(cfg, params, toks, obs))
        last_obs = obs
    overhead = 1.0 - tps["on"] / tps["off"]

    last_obs.tracer.save(TRACE_OUT)
    prom = obs_report(last_obs.tracer)["promotions"]

    report("obs/tokens_per_s/off", 0.0, round(tps["off"], 2))
    report("obs/tokens_per_s/on", 0.0, round(tps["on"], 2))
    report("obs/overhead_frac", 0.0, round(overhead, 4))
    report("obs/promotion_publish_p95_ms", 0.0,
           round(prom["publish_latency_p95_s"] * 1e3, 2))
    print(f"obs overhead: {overhead*100:+.1f}% "
          f"({tps['off']:.1f} -> {tps['on']:.1f} tok/s, best of {REPS}); "
          f"{prom['n_published']} promotions published "
          f"(p95 {prom['publish_latency_p95_s']*1e3:.1f} ms)")

    results = {"obs": {
        "tokens_per_s_off": tps["off"], "tokens_per_s_on": tps["on"],
        "overhead_frac": overhead, "max_overhead_frac": MAX_OVERHEAD,
        "reps": REPS, "smoke": BENCH_SMOKE,
        "trace_events": len(last_obs.tracer),
        "promotions": prom,
    }}
    os.makedirs(os.path.dirname(JSON_OUT), exist_ok=True)
    merged = {}
    if os.path.exists(JSON_OUT):
        try:
            with open(JSON_OUT) as f:
                merged = json.load(f)
        except Exception:
            merged = {}
    merged.update(results)
    with open(JSON_OUT, "w") as f:
        json.dump(merged, f, indent=2, sort_keys=True)
    print(f"# wrote {os.path.normpath(JSON_OUT)} and "
          f"{os.path.normpath(TRACE_OUT)}")

    if overhead > MAX_OVERHEAD:
        raise AssertionError(
            f"observability overhead {overhead*100:.1f}% exceeds the "
            f"{MAX_OVERHEAD*100:.0f}% budget — something crept onto the "
            f"hot path (check _step_obs / observe instrumentation)")
