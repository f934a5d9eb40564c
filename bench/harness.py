"""One run of one benchmark cell: build the engine through the program's
own entry points, warm up, serve an open-loop schedule for the window,
read the metrics, and decide ``correct`` against the plain reference.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its configuration in ``bench/configs/<config>.json``, its traffic in
``bench/traffic/<mix>.json``, each metric's reader in
``bench/metrics/<metric>.py`` and the correctness limits and control in
``bench/checks/<cell>.json``. Adding any of them is adding files and
entries.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import shutil
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH_DIR = Path(__file__).resolve().parent
CHECKOUT = BENCH_DIR.parent
TRACE_DIR = CHECKOUT / ".bench_trace"
#: Seconds of the window the traced run hands to the profiler.
TRACE_SECONDS = 5.0
#: The two Pallas kernels of the ragged MoE FFN in the profiler's trace.
#: The program gives its kernels no names of their own, so they are found
#: by their custom-call target; in these cells the gate/up and down
#: kernels of the decode and prefill programs are the step's only Pallas
#: calls (a trace of granite-shift on a v5e shows exactly those four).
KERNELS = {"moe_ffn": ("tpu_custom_call",)}
#: Served tokens the correctness sample aims for (the longest finished
#: request, then others drawn from the seed), and its most requests.
SAMPLE_TOKENS = 384
SAMPLE_MAX_REQUESTS = 8
#: Longest the engine keeps serving after the window so that sampled
#: requests still running at the close can finish (no new submissions).
FINISH_LIMIT_S = 90.0
#: Rows per reference call (the sample is padded up to a multiple).
REF_ROWS = 4


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


# ---------------------------------------------------------------------------
# Finding a cell's parts by name
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    conf: Dict                      # the configuration file
    mix: Dict                       # the traffic file
    end_to_end: List[Dict]          # BENCHMARK.json metric entries
    per_layer: List[Dict]
    limits: Dict                    # number -> {"limit": ..., readings}
    control: Dict = dataclasses.field(default_factory=dict)


def load_cell(name: str, bench_file: Path = CHECKOUT / "BENCHMARK.json",
              bench_dir: Path = BENCH_DIR) -> Cell:
    from bench import model
    from bench.traffic import generator
    spec = json.loads(bench_file.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {bench_file} "
                       f"(known: {sorted(cells)})")
    w = cells[name]
    confs = {c["name"]: c for c in spec["configs"]}
    conf_file = bench_file.parent / confs[w["config"]]["file"]
    conf = model.load_config(conf_file.stem, conf_file.parent)
    mix = generator.load_mix(w["traffic"], bench_dir / "traffic")

    def applies(m):
        return "workloads" not in m or name in m["workloads"]
    checks_file = bench_dir / "checks" / f"{name}.json"
    checks = json.loads(checks_file.read_text()) if checks_file.is_file() \
        else {}
    return Cell(name=name, chips=int(w["chips"]), conf=conf, mix=mix,
                end_to_end=[m for m in spec["end_to_end"] if applies(m)],
                per_layer=[m for m in spec["per_layer"] if applies(m)],
                limits={k: v for k, v in checks.items()
                        if isinstance(v, dict) and "limit" in v},
                control=checks.get("control", {}))


def with_serve_args(cell: Cell, extra: List[str]) -> Cell:
    """The cell with ``extra`` launcher flags after its configuration's
    (a later flag overrides an earlier one): how the control runs the
    program at its own lower precision."""
    conf = json.loads(json.dumps(cell.conf))
    conf["serving"]["serve_args"] = list(conf["serving"]["serve_args"]) + \
        [str(a) for a in extra]
    return dataclasses.replace(cell, conf=conf)


def metric_reader(name: str, bench_dir: Path = BENCH_DIR) -> Callable:
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# Run record: what the readers read
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Record:
    due_s: float
    prompt_len: int
    max_new: int
    workload: str
    prompt: np.ndarray
    submit_t: Optional[float] = None
    first_token_t: Optional[float] = None
    token_t: List[float] = dataclasses.field(default_factory=list)
    handle: object = None
    finished_t: Optional[float] = None


@dataclasses.dataclass
class Run:
    window_s: float
    setup_s: float
    requests: List[Record]
    dims: Dict
    peaks: Dict
    chips: int
    lo_bits: int
    group: int
    counters: Dict[str, float]      # engine counters over the window
    stats: Dict[str, float]         # backend stats over the window
    disp: Dict[str, float]          # dispatch gauges over the window
    prefill_s: List[float]
    decode_s: List[float]
    flight: Optional[List[Dict]] = None      # moe_forward args + "t"
    traced: Optional[tuple] = None           # (t0, t1) window seconds
    device_trace: Optional[Dict] = None

    def measured(self) -> List[Record]:
        return [r for r in self.requests if 0.0 <= r.due_s < self.window_s]

    def counter_delta(self, key: str) -> float:
        return self.counters.get(key, 0.0)

    def stat_delta(self, key: str) -> Optional[float]:
        return self.stats.get(key)

    def disp_delta(self, key: str) -> float:
        return self.disp.get(key, 0.0)

    def moe_forwards(self, traced: bool = False) -> Optional[List[Dict]]:
        if self.flight is None:
            return None
        lo, hi = (self.traced if traced and self.traced is not None
                  else (0.0, self.window_s))
        return [e for e in self.flight if lo <= e["t"] <= hi]


# ---------------------------------------------------------------------------
# The program under test
# ---------------------------------------------------------------------------

def program_arch(conf: Dict, d: Dict):
    """The program's ArchConfig for this configuration: its registry entry
    with every size the file states."""
    from repro.configs import get_config
    base = get_config(conf["serving"]["program_arch"])
    attn = dataclasses.replace(base.attn, n_heads=d["n_heads"],
                               n_kv_heads=d["n_kv_heads"],
                               head_dim=d["head_dim"],
                               rope_theta=float(d["rope_theta"]),
                               qk_norm=bool(d["qk_norm"]))
    moe = dataclasses.replace(base.moe, num_experts=d["num_experts"],
                              top_k=d["top_k"], d_ff_expert=d["d_ff_expert"],
                              norm_topk_prob=bool(d["norm_topk_prob"]))
    arch = dataclasses.replace(base, n_layers=d["n_layers"],
                               d_model=d["d_model"],
                               vocab_size=d["vocab_size"], attn=attn, moe=moe,
                               norm_eps=float(d["norm_eps"]),
                               tie_embeddings=bool(d["tie_embeddings"]))
    if abs(d["attn_scale"] - d["head_dim"] ** -0.5) > 1e-9:
        raise ValueError("the program scales attention by 1/sqrt(head_dim); "
                         f"the configuration states {d['attn_scale']}")
    return arch


def check_layout(arch, d: Dict) -> None:
    """The benchmark's weights have the program's tree, shapes and dtypes."""
    import jax
    from bench import model
    from repro.models import init_params
    want = jax.eval_shape(lambda k: init_params(k, arch),
                          jax.random.PRNGKey(0))
    flat = {tuple(getattr(p, "key", str(p)) for p in path): (tuple(v.shape),
                                                              str(v.dtype))
            for path, v in jax.tree_util.tree_flatten_with_path(want)[0]}
    mine = {tuple(p): (tuple(s), t) for p, s, t, _ in model.param_spec(d)}
    if flat != mine:
        raise ValueError(f"weight layout differs from the program's: "
                         f"{sorted(set(flat.items()) ^ set(mine.items()))}")


def build_engine(conf: Dict, arch, params: Dict, obs=None):
    """The engine, through the launcher's own functions (``build_backend``,
    ``build_engine_config``), ``make_backend``, ``EngineConfig`` and
    ``InferenceEngine``."""
    from repro.launch.serve import (build_backend, build_engine_config,
                                    build_parser)
    from repro.serving import InferenceEngine
    sv = conf["serving"]
    args = build_parser().parse_args(list(sv["serve_args"]))
    backend = build_backend(args)
    ecfg = build_engine_config(args)
    if sv.get("capacity_factor") == "drop_free":
        ecfg = dataclasses.replace(
            ecfg, capacity_factor=arch.moe.num_experts / arch.moe.top_k)
    return InferenceEngine(arch, params, backend, ecfg, obs=obs), args


class CompileMeter:
    """Backend compilations (persistent-cache reads included) from JAX's
    monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


# ---------------------------------------------------------------------------
# Serving loop
# ---------------------------------------------------------------------------

def _annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def serve(engine, records: List[Record], origin: float, until_s: float,
          on_tick: Optional[Callable[[float], None]] = None) -> int:
    """Open loop: submit each record when it falls due (seconds after
    ``origin`` on ``perf_counter``), step the engine while it has work,
    wait for the next due time when it has none, and stamp every token as
    the step that produced it returns. Stops at ``until_s``. Returns the
    most KV blocks the engine's pool held after a step."""
    from repro.serving import Request
    pool = getattr(engine, "pool", None)
    kv_max = 0
    nxt = 0
    live: List[Record] = []
    clock = time.perf_counter
    while True:
        now = clock() - origin
        if on_tick is not None:
            on_tick(now)
        if now >= until_s:
            return kv_max
        if nxt < len(records) and records[nxt].due_s <= now:
            with _annotate("bench.submit"):
                while nxt < len(records) and records[nxt].due_s <= now:
                    r = records[nxt]
                    r.handle = engine.submit(Request(
                        tokens=r.prompt, max_new_tokens=r.max_new,
                        workload=r.workload))
                    r.submit_t = clock() - origin
                    live.append(r)
                    nxt += 1
        if engine.queue or any(s is not None for s in engine.slots):
            with _annotate("bench.step"):
                engine.step()
            t = clock() - origin
            if pool is not None:
                kv_max = max(kv_max, pool.blocks_in_use)
            keep = []
            for r in live:
                n = len(r.handle.tokens)
                if n > len(r.token_t):
                    if r.first_token_t is None:
                        r.first_token_t = t
                    r.token_t.extend([t] * (n - len(r.token_t)))
                if r.handle.state.value in ("finished", "shed"):
                    r.finished_t = t
                else:
                    keep.append(r)
            live = keep
        else:
            wake = min(records[nxt].due_s if nxt < len(records) else until_s,
                       until_s)
            with _annotate("bench.idle"):
                while clock() - origin < wake:
                    time.sleep(min(0.002, max(0.0, wake - (clock() - origin))))


def warm_up(engine, mix: Dict, reqs, vocab: int, seed: int) -> Dict:
    """Compile every program the cell's traffic uses: one prefill per
    prompt-length bucket the schedule holds, the decode step, and the
    residency backend's promotion and demotion writes. Two passes over
    different workloads, each followed by an allocation window, so that
    the second demotes what the first promoted."""
    from bench.traffic import generator
    from repro.serving import Request
    src = generator.TokenSource(vocab, mix["zipf_s"])
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 7]))
    by_bucket: Dict[int, int] = {}
    for n in sorted({r.prompt_len for r in reqs}):
        by_bucket.setdefault(engine._bucket_len(n), n)
    first = mix["phases"][0]
    passes = [first, next(w for w in generator.WORKLOADS if w != first)]
    backend = engine.backend
    times = []
    for w in passes:
        t = time.perf_counter()
        for n in by_bucket.values():
            engine.submit(Request(tokens=src.draw(rng, w, n),
                                  max_new_tokens=2, workload=w))
        engine.drain()
        t1 = time.perf_counter()
        if hasattr(backend, "force_update"):
            backend.force_update()
            backend.flush()
        times.append([w, round(t1 - t, 3), round(time.perf_counter() - t1,
                                                 3)])
    return {"buckets": sorted(by_bucket), "passes": times}


# ---------------------------------------------------------------------------
# Correctness
# ---------------------------------------------------------------------------

def pick_sample(records: List[Record], seed: int) -> List[Record]:
    """Requests to check: among those finished when the window closed,
    the one that served the most tokens, then others drawn from the seed,
    until ``SAMPLE_TOKENS`` served tokens or ``SAMPLE_MAX_REQUESTS``
    requests; where the finished ones fall short, requests still running
    at the close join, drawn from the seed, to be served to their end."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 11]))
    done = [r for r in records if r.finished_t is not None
            and r.handle.state.value == "finished"]
    running = [r for r in records if r.finished_t is None
               and r.first_token_t is not None]
    out: List[Record] = []
    if done:
        out.append(max(done, key=lambda r: len(r.handle.tokens)))
    pool = [done[i] for i in rng.permutation(len(done))] + \
        [running[i] for i in rng.permutation(len(running))]
    total = sum(r.max_new for r in out)
    for r in pool:
        if total >= SAMPLE_TOKENS or len(out) >= SAMPLE_MAX_REQUESTS:
            break
        if r not in out:
            out.append(r)
            total += r.max_new
    return out


def finish(engine, sample: List[Record], limit_s: float) -> float:
    """Step the engine, submitting nothing new, until every sampled
    request has finished or ``limit_s`` has passed. Returns the seconds
    it took."""
    t0 = time.perf_counter()
    while any(r.handle.state.value != "finished" for r in sample) and \
            time.perf_counter() - t0 < limit_s:
        engine.step()
    return time.perf_counter() - t0


def reference_gaps(d: Dict, seed: int, sample: List[Dict],
                   seq_len: int) -> Dict:
    """Over every served position of the sample, the gap by which the
    served token's reference logit lies below the reference's best: the
    widest, the mean and the 90th percentile, the share of positions where
    the two agree, and whether every reference logit was finite."""
    import jax
    import jax.numpy as jnp
    from bench import model
    params = model.make_params(d, seed)
    P = max(len(s["served"]) for s in sample)
    rows = -(-len(sample) // REF_ROWS) * REF_ROWS
    toks = np.zeros((rows, seq_len), np.int32)
    pos = np.zeros((rows, P), np.int32)
    nxt = np.zeros((rows, P), np.int32)
    valid = np.zeros((rows, P), bool)
    for i, s in enumerate(sample):
        seq = np.concatenate([s["prompt"], s["served"]]).astype(np.int32)
        n, m = len(s["prompt"]), len(s["served"])
        toks[i, :len(seq)] = seq
        pos[i, :m] = np.arange(n - 1, n - 1 + m)
        pos[i, m:] = n - 1 + m - 1
        nxt[i, :m] = s["served"]
        valid[i, :m] = True
    ref = model.Reference(d, params)
    parts, finite = [], True
    for b0 in range(0, rows, REF_ROWS):
        sl = slice(b0, b0 + REF_ROWS)
        base = ref.logits_at(toks[sl], pos[sl])
        served = jnp.take_along_axis(base, jnp.asarray(nxt[sl])[..., None],
                                     -1)[..., 0]
        parts.append(np.asarray(jnp.max(base, -1) - served))
        finite = finite and bool(jnp.isfinite(base).all())
        del base
    gaps = np.concatenate(parts)[valid]
    del params, ref
    gc.collect()
    jax.clear_caches()
    return {"widest_logit_gap": float(gaps.max()),
            "mean_logit_gap": float(gaps.mean()),
            "p90_logit_gap": float(np.percentile(gaps, 90)),
            "agree": float(np.mean(gaps == 0.0)),
            "finite": finite, "positions": int(gaps.size)}


def judge(limits: Dict, readings: Optional[Dict]) -> tuple:
    """Each number compared beside its limit, and whether every one is
    within it (no sample, or a reference logit that is not finite, is not
    correct)."""
    checks = {name: {"value": None if readings is None
                     else readings.get(name), "limit": spec["limit"]}
              for name, spec in limits.items()}
    ok = bool(checks and readings is not None and readings["finite"]
              and all(c["value"] is not None and c["value"] <= c["limit"]
                      for c in checks.values()))
    return checks, ok


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

def device_info(jax, chips: int) -> Dict:
    devs = jax.devices()[:chips]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes(jax, chips: int) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:chips])


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, rate: Optional[float] = None) -> Dict:
    """Set up, serve the window, read the metrics and check the outputs.
    Returns the result object (the contract's last line) plus, under
    ``"_extra"``, what the log lines and the calibration need. ``rate``
    replaces the mix's offered load (the knee sweep and the calibration;
    ``bench/run.py`` never sets it)."""
    import jax
    from bench import flops, model
    from bench.traffic import generator
    from repro.obs import Observability, ObsConfig

    d = model.dims(cell.conf)
    dev = device_info(jax, cell.chips)
    peaks = flops.load_peaks(dev["kind"])
    meter = CompileMeter()
    marks = {}

    reqs = generator.schedule(cell.mix, d["vocab_size"], seed, seconds,
                              rate_rps=rate)
    records = [Record(r.due_s, r.prompt_len, r.max_new_tokens, r.workload,
                      r.tokens) for r in reqs]
    log(f"traffic {json.dumps(generator.describe(reqs))}")

    arch = program_arch(cell.conf, d)
    check_layout(arch, d)
    params = model.make_params(d, seed)
    jax.block_until_ready(params)
    marks["weights"] = time.perf_counter()
    obs = Observability(ObsConfig(metrics=False, trace_capacity=1 << 20)) \
        if trace else None
    engine, args = build_engine(cell.conf, arch, params, obs=obs)
    del params
    marks["engine"] = time.perf_counter()
    c0 = (meter.seconds, meter.count, meter.hits)
    warm = warm_up(engine, cell.mix, reqs, d["vocab_size"], seed)
    marks["warm_up"] = time.perf_counter()
    c1 = (meter.seconds, meter.count, meter.hits)

    lead = float(cell.mix["lead_in_s"])
    origin = time.perf_counter() + lead       # the window opens here
    serve(engine, [r for r in records if r.due_s < 0], origin, 0.0)
    setup_s = time.perf_counter() - t_start
    marks["lead_in"] = time.perf_counter()
    log(f"setup {setup_s:.3f} s: weights "
        f"{marks['weights'] - t_start:.3f}, engine "
        f"{marks['engine'] - marks['weights']:.3f}, warm-up "
        f"{marks['warm_up'] - marks['engine']:.3f} (compile "
        f"{c1[0] - c0[0]:.3f} s in {c1[1] - c0[1]} programs, "
        f"{c1[2] - c0[2]} cache hits; buckets {warm['buckets']}; "
        f"[workload, serve s, allocate s] {warm['passes']}), lead-in "
        f"{marks['lead_in'] - marks['warm_up']:.3f}; compile before the "
        f"window {meter.seconds:.3f} s in {meter.count} programs")

    # -- the window -----------------------------------------------------
    backend = engine.backend
    cnt0 = dict(engine.counters)
    st0 = backend.stats()
    disp0 = (engine._disp_pad_sum, engine._disp_layers)
    n_dec0, n_pre0 = len(engine.decode_times), len(backend._ttft)
    comp0 = meter.count
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        tr_len = min(TRACE_SECONDS, seconds / 2)
        tr_at = max(0.0, seconds / 2 - tr_len / 2)
        state = {"on": False, "t0": None, "t1": None}

        def on_tick(now):
            if not state["on"] and state["t0"] is None and now >= tr_at:
                jax.profiler.start_trace(str(TRACE_DIR))
                state["on"], state["t0"] = True, time.perf_counter() - origin
            elif state["on"] and now >= tr_at + tr_len:
                jax.profiler.stop_trace()
                state["on"], state["t1"] = False, \
                    time.perf_counter() - origin
    else:
        on_tick = None
    measured = [r for r in records if r.due_s >= 0]
    kv_max = serve(engine, measured, origin, seconds, on_tick=on_tick)
    if trace and state["on"]:
        jax.profiler.stop_trace()
        state["on"], state["t1"] = False, time.perf_counter() - origin
    compiles_in_window = meter.count - comp0

    cnt1 = engine.counters
    st1 = backend.stats()
    run = Run(
        window_s=float(seconds), setup_s=setup_s, requests=records, dims=d,
        peaks=peaks, chips=cell.chips, lo_bits=int(args.lo_bits),
        group=int(getattr(backend, "group_size", 64)),
        counters={k: float(cnt1[k] - cnt0.get(k, 0)) for k in cnt1},
        stats={k: float(st1[k] - st0.get(k, 0.0)) for k in
               ("promotions", "demotions", "bytes_moved") if k in st1},
        disp={"pad": engine._disp_pad_sum - disp0[0],
              "layers": engine._disp_layers - disp0[1]},
        prefill_s=list(backend._ttft[n_pre0:]),
        decode_s=list(engine.decode_times[n_dec0:]))
    if obs is not None:
        run.flight = [dict(e.args, t=e.ts - origin)
                      for e in obs.tracer.events()
                      if e.name == "moe_forward" and e.args
                      and e.ts >= origin]
    device = dict(dev, memory_peak_bytes=peak_bytes(jax, cell.chips))

    breakdown = None
    if trace and state["t0"] is not None:
        from bench import devtrace
        raw = devtrace.extract(devtrace.find_xplane(str(TRACE_DIR)))
        red = devtrace.reduce(raw, kernels=KERNELS)
        if red is not None:
            run.device_trace = red
            run.traced = (state["t0"], state["t1"])
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            breakdown = {"device_ops": red["device_ops"],
                         "idle_gaps": red["idle_gaps"]}
            log(f"trace: {json.dumps(red)}")
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    # -- metrics --------------------------------------------------------
    entries = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in entries:
        v = metric_reader(m["name"])(run)
        if v is not None and math.isfinite(v):
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    measured_reqs = run.measured()
    pool = getattr(engine, "pool", None)
    attempted = len(measured_reqs)
    failed = sum(r.handle is not None and r.handle.state.value == "shed"
                 for r in measured_reqs)
    log(f"window {seconds} s: {attempted} requests due, "
        f"{sum(r.first_token_t is not None for r in measured_reqs)} with a "
        f"first token, {sum(r.finished_t is not None for r in measured_reqs)}"
        f" finished; {run.counter_delta('steps'):.0f} decode steps, "
        f"{run.counter_delta('prefills'):.0f} prefills; promotions "
        f"{run.stat_delta('promotions')}; compiles in the window "
        f"{compiles_in_window}; peak {device['memory_peak_bytes']} B; KV "
        f"blocks in use at most {kv_max} of {getattr(pool, 'n_blocks', 0)}"
        f" ({kv_max * getattr(pool, 'block_bytes', 0)} B)")

    # -- correctness ----------------------------------------------------
    picked = pick_sample(records, seed)
    finish_s = finish(engine, picked, FINISH_LIMIT_S)
    sample = [{"prompt": np.asarray(r.prompt), "served":
               np.asarray(r.handle.tokens, np.int32)}
              for r in picked if r.handle.state.value == "finished"]
    del engine, backend, obs, run, pool
    for r in records:
        r.handle = None
    gc.collect()
    jax.clear_caches()
    extra = {"setup": {k: v - t_start for k, v in marks.items()},
             "compiles_in_window": compiles_in_window,
             "kv_blocks_max": kv_max,
             "sample_tokens": int(sum(len(s["served"]) for s in sample)),
             "finish_s": finish_s,
             "sample_requests": len(sample)}
    readings = None
    if sample:
        seq_len = int(cell.mix["prompt_len"]["max"]
                      + cell.mix["output_len"]["max"])
        t_ref = time.perf_counter()
        readings = reference_gaps(d, seed, sample, seq_len)
        extra["reference_s"] = time.perf_counter() - t_ref
        extra["readings"] = readings
    checks, correct = judge(cell.limits, readings)
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    result["_extra"] = extra
    return result
