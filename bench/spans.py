#!/usr/bin/env python3
"""Read the program's own spans and named scopes back from a traced run.

    python3 bench/spans.py --workload granite-shift --seed 7 --seconds 50

The program writes each engine span twice: ``B``/``E`` in its flight
recorder on the engine clock, and a ``jax.profiler.TraceAnnotation``
named ``<cat>.<name>`` (with the span's ``id``) into the profiler's host
plane, on the clock the device operations share. Its jitted programs
carry ``jax.named_scope`` paths (``attn``, ``kv_write``, ``moe/ffn``, …)
in each operation's metadata. This module keeps both when it reduces a
profile (``extract``, ``reduce``), relates the two clocks through the
spans that appear in both (``clock_offset``), and computes the per-layer
numbers they support: queue wait, residency host time per step, and the
device time of attention, MoE and promotion writes.

Run as a script it serves one cell as ``bench/run.py --trace 1`` does
(the harness's own set-up, warm-up, lead-in and open-loop window, with
the profiler on for ``harness.TRACE_SECONDS`` in the middle), then
prints one JSON object: the reduction, the numbers above, the
generator's lag, and how the stats of a few device operations of each
program look, so the source of the scope paths can be checked by hand.
``--fixture`` writes one traced engine step for the tests. It runs only
on a TPU; ``bench/run.py`` and its metrics are unchanged by it.

This module is temporary. Its ``extract`` and ``reduce`` are a superset
of ``bench/devtrace.py``'s, and ``main`` repeats the traced window of
``harness.run_cell``. The ``benchmark`` change that wires the five
numbers into metrics moves ``extract``/``reduce`` into ``devtrace``,
reads the numbers from ``harness.run_cell``, and deletes ``main``, so
that the harness alone runs traced windows.
"""
from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

#: Host spans kept from the profile: the harness's own and the program's.
HOST_PREFIXES = ("bench.", "engine.", "sched.", "residency.")
PROGRAM_PREFIXES = HOST_PREFIXES[1:]
#: The program's named scopes. An op is attributed to the innermost of
#: them on its scope path; ``moe`` holds MoE work outside the four parts.
SCOPES = ("attn", "kv_write", "moe", "moe/router", "moe/dispatch",
          "moe/ffn", "moe/combine", "lm_head", "residency/promote")
UNSCOPED = "(none)"
#: Ops whose scope path could not be found: no program text holds them,
#: or the variants of their program that fit disagree.
UNMATCHED = "(unmatched)"
#: Scopes whose device time ``decode_attention_ms`` and ``decode_moe_ms``
#: count, in programs whose name holds ``DECODE``.
ATTENTION = ("attn", "kv_write")
DECODE = "decode"

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_HLO_LINE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")


# ---------------------------------------------------------------------------
# Names, scopes and shapes of device operations
# ---------------------------------------------------------------------------

def scope_of(path: str) -> str:
    """The innermost named scope of ``SCOPES`` on an op's scope path
    (``jit(f)/while/body/closed_call/moe/ffn/pallas_call`` → ``moe/ffn``),
    or ``UNSCOPED``."""
    parts = path.split("/")
    best, key = UNSCOPED, (0, 0)
    for sc in SCOPES:
        sp = sc.split("/")
        for i in range(len(parts) - len(sp) + 1):
            if parts[i:i + len(sp)] == sp and (i + len(sp), len(sp)) > key:
                best, key = sc, (i + len(sp), len(sp))
    return best


def shape_of(long_name: str) -> str:
    """The result shape in an op's HLO text, layout dropped:
    ``%copy.5 = bf16[24,8,64]{2,1,0:T(8,128)} copy(...)`` →
    ``bf16[24,8,64]``; '' when the name holds no HLO text."""
    if " = " not in long_name:
        return ""
    rhs = long_name.split(" = ", 1)[1]
    if rhs.startswith("("):
        depth = 0
        for i, c in enumerate(rhs):
            depth += (c == "(") - (c == ")")
            if depth == 0:
                rhs = rhs[:i + 1]
                break
    else:
        rhs = rhs.split(" ", 1)[0]
    return re.sub(r"\{[^{}]*\}", "", rhs)[:120]


def op_names(hlo_text: str) -> Dict[str, Tuple[str, str]]:
    """HLO instruction name → (result shape, metadata ``op_name``, '' where
    the instruction has none) in a compiled program's text
    (``Compiled.as_text()``)."""
    out: Dict[str, Tuple[str, str]] = {}
    for line in hlo_text.splitlines():
        m = _HLO_LINE.match(line)
        if m:
            op = _OP_NAME.search(line)
            out[m.group(1)] = (shape_of(line), op.group(1) if op else "")
    return out


def program_of(module: str) -> str:
    """``jit__decode_paged_jit(123)`` or ``..._123`` → the program name."""
    return re.sub(r"(\(\d+\)|_\d{6,})$", "", module)


def _key(ev_name: str) -> Tuple[str, str]:
    """(instruction name, result shape) of a profile op."""
    return ev_name.split(" = ", 1)[0].strip().lstrip("%"), shape_of(ev_name)


def _holds(variant: Dict[str, Tuple[str, str]], key: Tuple[str, str]
           ) -> Optional[str]:
    got = variant.get(key[0])
    if got is not None and (not key[1] or got[0] == key[1]):
        return got[1]
    return None


def fitting_variants(keys: Iterable[Tuple[str, str]],
                     variants: Sequence[Dict[str, Tuple[str, str]]]
                     ) -> List[Dict[str, Tuple[str, str]]]:
    """The compiled variants of a program (one per bucket and prefix
    flag) that hold the most of one profiled module's ops, by name and
    result shape. The profile names a module by program and fingerprint;
    its text is not in the profile, so the variant is told by its ops."""
    keys = set(keys)
    score = [sum(_holds(v, k) is not None for k in keys) for v in variants]
    best = max(score, default=0)
    return [v for v, n in zip(variants, score) if best and n == best]


def _op_path(ev_name: str, stats: Dict,
             variants: Sequence[Dict[str, Tuple[str, str]]]
             ) -> Optional[str]:
    """An op's scope path: the ``tf_op`` stat where the profile has one,
    else the ``op_name`` in its HLO text, else the metadata of the same
    instruction (name and result shape) in the compiled texts of its
    module (``fitting_variants``; a v5e profile read through
    ``ProfileData`` carries neither). None when no text holds it or the
    texts disagree."""
    path = stats.get("tf_op")
    if isinstance(path, str) and path:
        return path
    m = _OP_NAME.search(ev_name)
    if m:
        return m.group(1)
    key = _key(ev_name)
    found = {_holds(v, key) for v in variants} - {None}
    return found.pop() if len(found) == 1 else None


# ---------------------------------------------------------------------------
# Extraction from the profiler's .xplane.pb
# ---------------------------------------------------------------------------

def extract(xplane_path: str, programs: Sequence[str] = (),
            sample: int = 3) -> Dict:
    """Like ``devtrace.extract``, plus what the program's spans and scopes
    need: {"devices": {plane: [[op, start_ns, dur_ns, target, scope path
    (None where none was found), shape], ...]}, "modules": as
    ``devtrace``, "host": [[name, start_ns, dur_ns, args], ...] for every
    ``HOST_PREFIXES`` span, "stats_seen": {program: [[op, {stat: value}],
    ...]} (the first ``sample`` ops of each program, to look at by hand)}.
    ``programs`` are compiled HLO texts, read where an op carries no scope
    of its own; each profiled module is matched to the texts of its
    program that fit it best (``fitting_variants``)."""
    from jax.profiler import ProfileData
    from bench import devtrace
    names: Dict[str, List] = {}
    for text in programs:
        m = re.search(r"^HloModule ([\w.\-]+)", text, re.M)
        if m:
            names.setdefault(program_of(m.group(1)), []).append(
                op_names(text))
    pd = ProfileData.from_file(xplane_path)
    devices: Dict[str, List] = {}
    modules: Dict[str, List] = {}
    host: List = []
    seen: Dict[str, List] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            lines = {ln.name: ln for ln in plane.lines}
            line = next((lines[n] for n in devtrace.OP_LINES if n in lines),
                        None)
            if line is None:
                continue
            mods = sorted([[ev.name, float(ev.start_ns),
                            float(ev.duration_ns)]
                           for ev in lines[devtrace.MODULE_LINE].events]
                          if devtrace.MODULE_LINE in lines else [],
                          key=lambda m: m[1])
            modules[plane.name] = mods
            starts = [m[1] for m in mods]
            evs = [(ev.name, float(ev.start_ns), float(ev.duration_ns),
                    dict(ev.stats)) for ev in line.events]
            at = [devtrace._module_at(mods, starts, e[1]) for e in evs]
            keys: Dict[str, set] = {}
            for e, mod in zip(evs, at):
                keys.setdefault(mod, set()).add(_key(e[0]))
            fits = {mod: fitting_variants(k, names.get(program_of(mod), []))
                    for mod, k in keys.items()}
            ops = []
            for (name, start, dur, stats), mod in zip(evs, at):
                got = seen.setdefault(program_of(mod), [])
                if len(got) < sample:
                    got.append([devtrace._short(name),
                                {k: str(v)[:160] for k, v in stats.items()}])
                ops.append([devtrace._short(name), start, dur,
                            devtrace._target(name),
                            _op_path(name, stats, fits[mod]),
                            shape_of(name)])
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIXES):
                        host.append([ev.name, float(ev.start_ns),
                                     float(ev.duration_ns),
                                     {k: v for k, v in ev.stats
                                      if isinstance(v, (int, float))}])
    host.sort(key=lambda h: h[1])
    return {"devices": devices, "modules": modules, "host": host,
            "stats_seen": seen}


# ---------------------------------------------------------------------------
# Reduction
# ---------------------------------------------------------------------------

def _depths(spans: Sequence[Sequence]) -> List[int]:
    """Nesting depth of each span (sorted by start) among those holding
    it: the program's spans nest on their thread."""
    out, stack = [], []
    for _, s, d, *_ in spans:
        while stack and stack[-1] <= s:
            stack.pop()
        out.append(len(stack))
        stack.append(s + d)
    return out


def label_gap(gap_s: float, gap_e: float, host: Sequence[Sequence],
              depth: Sequence[int]) -> str:
    """The innermost program span that overlaps the gap (the most
    overlap among equals), else the harness span that overlaps it most,
    else ``host:none``."""
    best, key = "host:none", None
    for (name, s, dur, *_), dep in zip(host, depth):
        ov = min(gap_e, s + dur) - max(gap_s, s)
        if ov <= 0:
            continue
        k = (name.startswith(PROGRAM_PREFIXES), dep, ov)
        if key is None or k > key:
            best, key = name, k
    return best


def reduce(trace: Dict, t0_ns: float, t1_ns: float, top: int = 12
           ) -> Optional[Dict]:
    """Over [t0_ns, t1_ns]: device busy time; ``scope_s``, the device self
    time of each (program, scope) pair (``UNSCOPED`` for ops outside every
    scope, ``UNMATCHED`` for ops whose path was not found);
    ``unmatched_share``, the share of device self time whose path was
    not found; ``span_counts``, the program spans that began in the interval;
    ``device_ops``, the ops that took most self time, each named by scope,
    shape and program; ``idle_gaps``, the longest gaps, each named by the
    innermost span over it. Times are averaged over the devices traced.
    None when no device operation was traced."""
    from bench import devtrace
    devs = {k: v for k, v in trace["devices"].items() if v}
    if not devs:
        return None
    host = sorted((h for h in trace.get("host", [])
                   if h[1] < t1_ns and h[1] + h[2] > t0_ns),
                  key=lambda h: h[1])
    depth = _depths(host)
    scope_t: Dict[Tuple[str, str], float] = {}
    op_t: Dict[str, float] = {}
    busy = 0.0
    gaps: List = []
    for plane, evs in devs.items():
        mods = trace.get("modules", {}).get(plane, [])
        starts = [m[1] for m in mods]
        kept, spans = [], []
        for name, s, dur, _target, path, shape in evs:
            a, b = max(s, t0_ns), min(s + dur, t1_ns)
            if b > a:
                kept.append((name, path, shape, a))
                spans.append((a, b))
        for (name, path, shape, a), st in zip(kept,
                                              devtrace._self_times(spans)):
            prog = program_of(devtrace._module_at(mods, starts, a))
            sc = UNMATCHED if path is None else scope_of(path)
            scope_t[(prog, sc)] = scope_t.get((prog, sc), 0.0) + st * 1e-9
            label = f"{name} {shape} [{sc}] in {prog}"
            op_t[label] = op_t.get(label, 0.0) + st * 1e-9
        u = devtrace._union(spans)
        busy += sum(e - s for s, e in u) * 1e-9
        edges = [t0_ns] + [x for iv in u for x in iv] + [t1_ns]
        for i in range(0, len(edges), 2):
            if edges[i + 1] > edges[i]:
                gaps.append((edges[i + 1] - edges[i], edges[i],
                             edges[i + 1]))
    n = len(devs)
    gaps.sort(reverse=True)
    counts: Dict[str, int] = {}
    for name, s, *_ in host:
        if name.startswith(PROGRAM_PREFIXES) and t0_ns <= s < t1_ns:
            counts[name] = counts.get(name, 0) + 1
    return {
        "devices": n,
        "window_s": (t1_ns - t0_ns) * 1e-9,
        "busy_s": busy / n,
        "scope_s": {f"{p}|{sc}": v / n
                    for (p, sc), v in sorted(scope_t.items())},
        "unmatched_share": sum(v for (_, sc), v in scope_t.items()
                               if sc == UNMATCHED)
        / max(sum(scope_t.values()), 1e-30),
        "span_counts": counts,
        "device_ops": [[k, v / n] for k, v in sorted(
            op_t.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[label_gap(s, e, host, depth), g * 1e-9]
                      for g, s, e in gaps[:top]],
    }


def clock_offset(ring_begins: Iterable[Tuple[int, float]],
                 host: Sequence[Sequence]) -> Optional[float]:
    """Trace clock minus engine clock, in seconds: the median over the
    spans found in both (same ``id``) of the profiler's start less the
    recorder's ``B`` stamp. None when no span is in both."""
    ring = dict(ring_begins)
    diffs = [s * 1e-9 - ring[int(a["id"])] for name, s, _, a in host
             if name.startswith(PROGRAM_PREFIXES) and "id" in a
             and int(a["id"]) in ring]
    return float(np.median(diffs)) if diffs else None


# ---------------------------------------------------------------------------
# The per-layer numbers
# ---------------------------------------------------------------------------

def ring_spans(events: Iterable, name: str, cat: str, t0: float, t1: float
               ) -> List[Tuple[int, float, float]]:
    """(id, begin, end) of the recorder's spans ``cat.name`` that began in
    [t0, t1] (engine clock), async or duration; a span still open at
    ``t1`` ends there. ``events`` are ``TraceEvent``s or
    ``[ph, name, cat, id, ts]`` lists."""
    opened: Dict[int, float] = {}
    out = []
    for e in events:
        ph, nm, ct, sid, ts = (e if isinstance(e, (list, tuple)) else
                               (e.ph, e.name, e.cat, e.id, e.ts))
        if nm != name or ct != cat or sid is None:
            continue
        if ph in ("b", "B"):
            if t0 <= ts <= t1:
                opened[sid] = ts
        elif ph in ("e", "E") and sid in opened:
            b = opened.pop(sid)
            out.append((sid, b, min(ts, t1)))
    out += [(sid, b, t1) for sid, b in opened.items()]
    return out


def queue_wait_p95_s(events: Iterable, t0: float, t1: float
                     ) -> Optional[float]:
    """p95 over the requests queued in [t0, t1] of their ``sched.queue``
    time; a request still queued at ``t1`` counts its wait so far."""
    per: Dict[int, float] = {}
    for rid, b, e in ring_spans(events, "queue", "sched", t0, t1):
        per[rid] = per.get(rid, 0.0) + (e - b)
    return float(np.percentile(list(per.values()), 95)) if per else None


def residency_host_ms(events: Iterable, t0: float, t1: float
                      ) -> Optional[float]:
    """Host time of ``residency.observe`` and ``residency.tick`` spans in
    [t0, t1] per ``engine.step`` span there, in ms."""
    evs = list(events)
    steps = len(ring_spans(evs, "step", "engine", t0, t1))
    if not steps:
        return None
    host = sum(e - b for n in ("observe", "tick")
               for _, b, e in ring_spans(evs, n, "residency", t0, t1))
    return host / steps * 1e3


def _scope_time(red: Dict, scopes: Sequence[str], program: str = "") -> float:
    return sum(v for k, v in red["scope_s"].items()
               if program in k.split("|")[0]
               and any(k.split("|")[1] == s or
                       k.split("|")[1].startswith(s + "/") for s in scopes))


def _per_span_ms(seconds: float, red: Dict, span: str) -> Optional[float]:
    n = red["span_counts"].get(span, 0)
    return seconds / n * 1e3 if n else None


def decode_attention_ms(red: Dict) -> Optional[float]:
    """Device time of ``attn`` and ``kv_write`` ops in decode programs per
    ``engine.decode`` span, in ms."""
    return _per_span_ms(_scope_time(red, ATTENTION, DECODE), red,
                        "engine.decode")


def decode_moe_ms(red: Dict) -> Optional[float]:
    """Device time of ``moe/*`` ops in decode programs per
    ``engine.decode`` span, in ms."""
    return _per_span_ms(_scope_time(red, ("moe",), DECODE), red,
                        "engine.decode")


def promotion_write_ms(red: Dict) -> Optional[float]:
    """Device time of ``residency/promote`` ops per ``residency.promote``
    span, in ms."""
    return _per_span_ms(_scope_time(red, ("residency/promote",)), red,
                        "residency.promote")


def span_ms(events: Iterable, name: str, cat: str, t0: float, t1: float,
            stat=np.mean) -> Optional[float]:
    """``stat`` of the durations of the recorder's ``cat.name`` spans that
    began in [t0, t1], in ms."""
    d = [e - b for _, b, e in ring_spans(events, name, cat, t0, t1)]
    return float(stat(d)) * 1e3 if d else None


def split(red: Dict, events: Iterable, t0: float, t1: float) -> Dict:
    """The five numbers over the traced interval [t0, t1] (engine clock
    for ``events``), beside the engine's own forward spans there."""
    evs = list(events)
    return {"queue_wait_p95_s": queue_wait_p95_s(evs, t0, t1),
            "residency_host_ms": residency_host_ms(evs, t0, t1),
            "decode_attention_ms": decode_attention_ms(red),
            "decode_moe_ms": decode_moe_ms(red),
            "promotion_write_ms": promotion_write_ms(red),
            "decode_span_ms_mean": span_ms(evs, "decode", "engine", t0, t1),
            "prefill_span_ms_median": span_ms(evs, "prefill", "engine", t0,
                                              t1, np.median)}


# ---------------------------------------------------------------------------
# One traced run
# ---------------------------------------------------------------------------

def _ring(obs) -> List[List]:
    return [[e.ph, e.name, e.cat, e.id, e.ts] for e in obs.tracer.events()
            if e.ph in "bBeE" and e.id is not None]


def _program_texts(engine, buckets: Sequence[int]) -> List[str]:
    """Compiled HLO of the programs a granite cell runs, lowered from the
    engine's own state: the decode step, a prefill per warmed bucket with
    and without a shared prefix (chunks and prefix hits run the latter),
    and the promotion write. Each compile is a cache hit of the program
    the window ran, where the shapes agree."""
    import jax.numpy as jnp
    from repro.core.ver import write_hi_slot
    from repro.models.model import DecodeCaches
    texts = [engine.decode_program().compile().as_text()]
    pre = engine._jit_prefill_paged            # partial(_prefill_paged_jit)
    R, nb = engine._prefill_rows, max(1, engine._nb_per_slot)
    caches = DecodeCaches(blocks={p: engine.caches.blocks[p]
                                  for p in engine._attn_pos}, cross=None)
    for b in buckets:
        for has_prefix in (False, True):
            texts.append(pre.func.lower(
                engine.params, {"tokens": jnp.zeros((R, b), jnp.int32)},
                caches, engine.banks, jnp.full((R, nb), -1, jnp.int32),
                jnp.zeros(R, jnp.int32), jnp.zeros(R, jnp.int32),
                has_prefix=has_prefix, row_capacity=engine._row_cap_prefill(b),
                **pre.keywords).compile().as_text())
    bank = next(iter(engine.banks.values()))
    if getattr(bank, "hi", None):
        rows = {n: jnp.zeros(v.shape[2:], v.dtype) for n, v in bank.hi.items()}
        texts.append(write_hi_slot.lower(bank.hi, jnp.int32(0), jnp.int32(0),
                                         rows).compile().as_text())
    return texts


def _ttft_split(records, queued: Dict[int, Tuple[float, float]]) -> Dict:
    """p50 and p95 over the requests due in the window that have a first
    token: due → submit (the harness submits between steps), the queue
    span, and queue end → first token (the prefill and the rest of its
    step). ``queued``: request id → its queue span, in window seconds."""
    rows = []
    for r in records:
        if r.first_token_t is None or r.handle is None or r.due_s < 0:
            continue
        q = queued.get(r.handle.id)
        if q is None:
            continue
        rows.append((r.submit_t - r.due_s, q[1] - q[0],
                     r.first_token_t - q[1], r.first_token_t - r.due_s))
    if not rows:
        return {}
    a = np.asarray(rows)
    return {k: [float(np.percentile(a[:, i], 50)),
                float(np.percentile(a[:, i], 95))]
            for i, k in enumerate(("due_to_submit_s", "queue_s",
                                   "queue_end_to_first_token_s", "ttft_s"))}


def main(argv=None) -> None:
    import argparse
    import json
    import shutil
    import time
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fixture", action="store_true",
                    help="write one traced engine step that decodes and "
                         "promotes to chiprun_out/spans_fixture.json")
    args = ap.parse_args(argv)
    import run as entry
    entry.setup_environment()
    import jax
    from bench import devtrace, harness
    from bench.traffic import generator
    from repro.obs import Observability, ObsConfig
    if jax.devices()[0].platform != "tpu":
        entry.fail("bench/spans.py runs only on a TPU", 3)
    cell = harness.load_cell(args.workload)
    d = cell.arch.dims(cell.conf)
    reqs = generator.schedule(cell.mix, d["vocab_size"], args.seed,
                              args.seconds)
    records = [harness.Record(r.due_s, r.prompt_len, r.max_new_tokens,
                              r.workload, r.tokens) for r in reqs]
    cfg = cell.arch.program_arch(cell.conf, d)
    harness.check_layout(cell.arch, cfg, d)
    obs = Observability(ObsConfig(metrics=False, trace_capacity=1 << 20))
    engine, _ = harness.build_engine(cell.conf, cfg,
                                     cell.arch.make_params(d, args.seed),
                                     obs=obs)
    warm = harness.warm_up(engine, cell.mix, reqs, d["vocab_size"],
                           args.seed)
    texts = _program_texts(engine, warm["buckets"])
    origin = time.perf_counter() + float(cell.mix["lead_in_s"])
    harness.serve(engine, [r for r in records if r.due_s < 0], origin, 0.0)
    harness.log(f"set-up {time.perf_counter() - t_start:.1f} s")

    tdir = str(harness.TRACE_DIR)
    shutil.rmtree(tdir, ignore_errors=True)
    tr_len = min(harness.TRACE_SECONDS, args.seconds / 2)
    tr_at = max(0.0, args.seconds / 2 - tr_len / 2)
    state = {"on": False, "t0": None, "t1": None, "stop_s": None}

    def stop():
        state["on"], state["t1"] = False, time.perf_counter()
        jax.profiler.stop_trace()
        state["stop_s"] = time.perf_counter() - state["t1"]

    def on_tick(now):
        if not state["on"] and state["t0"] is None and now >= tr_at:
            jax.profiler.start_trace(tdir)
            state["on"], state["t0"] = True, time.perf_counter()
        elif state["on"] and now >= tr_at + tr_len:
            stop()

    n0 = engine.counters["steps"]
    harness.serve(engine, [r for r in records if r.due_s >= 0], origin,
                  args.seconds, on_tick=on_tick)
    if state["on"]:
        stop()
    ring = _ring(obs)
    w0, w1 = origin, origin + args.seconds
    lags = [r.submit_t - r.due_s for r in records
            if 0 <= r.due_s < args.seconds and r.submit_t is not None]
    queued = {sid: (b - origin, e - origin)
              for sid, b, e in ring_spans(ring, "queue", "sched", w0, w1)}
    out = {"workload": cell.name, "seed": args.seed,
           "device": jax.devices()[0].device_kind,
           "generator_lag_p95_s": float(np.percentile(lags, 95))
           if lags else None,
           "stop_trace_s": state["stop_s"],
           "decode_steps_in_window": engine.counters["steps"] - n0,
           "ttft_split": _ttft_split(records, queued),
           "window": {
               "queue_wait_p95_s": queue_wait_p95_s(ring, w0, w1),
               "residency_host_ms": residency_host_ms(ring, w0, w1),
               "decode_span_ms_mean": span_ms(ring, "decode", "engine",
                                              w0, w1),
               "prefill_span_ms_median": span_ms(ring, "prefill", "engine",
                                                 w0, w1, np.median),
               "step_span_ms_mean": span_ms(ring, "step", "engine", w0, w1),
               "sample_span_ms_mean": span_ms(ring, "sample", "engine", w0,
                                              w1),
               "admit_span_ms_mean": span_ms(ring, "admit", "sched", w0,
                                             w1)}}
    raw = extract(devtrace.find_xplane(tdir), texts)
    offset = clock_offset(((sid, ts) for ph, _, _, sid, ts in ring
                           if ph == "B"), raw["host"])
    out.update(clock_offset_s=offset, stats_seen=raw["stats_seen"])
    if offset is not None:
        t0_ns = (state["t0"] + offset) * 1e9
        t1_ns = (state["t1"] + offset) * 1e9
        red = reduce(raw, t0_ns, t1_ns, top=25)
        out["reduce"] = red
        if red is not None:
            out["split"] = split(red, ring, state["t0"], state["t1"])
        if args.fixture:
            out["fixture"] = _write_fixture(
                raw, ring, state, offset,
                f"{cell.name} on one {out['device']} (bench/spans.py "
                f"--seed {args.seed})")
    shutil.rmtree(tdir, ignore_errors=True)
    print(json.dumps(out), flush=True)


def _write_fixture(raw: Dict, ring: List, state: Dict, offset: float,
                   what: str) -> bool:
    """The first whole engine step of the traced interval that decodes and
    promotes, as ``extract`` keeps it but with each op's scope path cut
    to its scope (what ``reduce`` reads), with the recorder's spans of the
    traced interval (engine clock). False when no such step was traced."""
    import json
    from bench import harness
    t0_ns = (state["t0"] + offset) * 1e9
    t1_ns = (state["t1"] + offset) * 1e9
    host = raw["host"]

    def holds(step, name):
        return any(h[0] == name and step[1] <= h[1] < step[1] + step[2]
                   for h in host)
    step = next((h for h in host if h[0] == "engine.step"
                 and t0_ns <= h[1] and h[1] + h[2] <= t1_ns
                 and holds(h, "engine.decode")
                 and holds(h, "residency.promote")), None)
    if step is None:
        return False
    a, b = step[1], step[1] + step[2]

    def cut(op):
        path = op[4]
        return op[:4] + [None if path is None else
                         "" if scope_of(path) == UNSCOPED else
                         scope_of(path)] + op[5:]
    keep = {"source": f"{what}: one engine step of a traced window, as "
                      "spans.extract keeps it, each op's scope path cut "
                      "to its scope; ring: the recorder's spans of the "
                      "traced interval, engine clock",
            "t0_ns": a, "t1_ns": b, "clock_offset_s": offset,
            "ring_t0": state["t0"], "ring_t1": state["t1"],
            "devices": {k: [cut(o) for o in v if a <= o[1] < b]
                        for k, v in raw["devices"].items()},
            "modules": {k: [m for m in v if m[1] < b and m[1] + m[2] > a]
                        for k, v in raw["modules"].items()},
            "host": [h for h in host if h[1] < b and h[1] + h[2] > a],
            "ring": [e for e in ring
                     if state["t0"] - 5 <= e[4] <= state["t1"]]}
    path = harness.CHECKOUT / "chiprun_out" / "spans_fixture.json"
    path.parent.mkdir(exist_ok=True)
    path.write_text(json.dumps(keep))
    return True


if __name__ == "__main__":
    main()
