"""Reduce a JAX profiler trace to device busy time, kernel time and the
idle gaps between device operations, each gap labelled by the host span it
falls in.

``extract`` reads the ``.xplane.pb`` the profiler writes and keeps only
what the reduction needs, as plain lists (this form is what the tests
record); ``reduce`` does the arithmetic. Times are nanoseconds on the
trace's own clock, on which the host spans and the device operations
share one base.
"""
from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, List, Optional, Sequence

#: Device lines whose events are single operations, in order of preference,
#: and the line of whole programs.
OP_LINES = ("XLA Ops",)
MODULE_LINE = "XLA Modules"
#: Host spans the harness writes (``jax.profiler.TraceAnnotation``).
HOST_PREFIX = "bench."


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def _short(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` → ``%fusion.12``."""
    return name.split(" = ", 1)[0]


def _target(name: str) -> str:
    """The custom-call target named in an op's HLO text, or ''."""
    i = name.find('custom_call_target="')
    if i < 0:
        return ""
    j = i + len('custom_call_target="')
    return name[j:name.find('"', j)]


def extract(xplane_path: str) -> Dict:
    """{"devices": {plane: [[op, start_ns, dur_ns, target], ...]},
    "modules": {plane: [[program, start_ns, dur_ns], ...]},
    "host": [[name, start_ns, dur_ns], ...], "layout": {plane: [line]}}
    — each device plane's operations (short HLO name, custom-call target)
    and programs, the harness's own host spans, and every plane's lines."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(xplane_path)
    devices: Dict[str, List] = {}
    modules: Dict[str, List] = {}
    host: List = []
    layout: Dict[str, List[str]] = {}
    for plane in pd.planes:
        layout[plane.name] = [ln.name for ln in plane.lines][:40]
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            lines = {ln.name: ln for ln in plane.lines}
            line = next((lines[n] for n in OP_LINES if n in lines), None)
            if line is None:
                continue
            devices[plane.name] = [
                [_short(ev.name), float(ev.start_ns), float(ev.duration_ns),
                 _target(ev.name)] for ev in line.events]
            if MODULE_LINE in lines:
                modules[plane.name] = [
                    [ev.name, float(ev.start_ns), float(ev.duration_ns)]
                    for ev in lines[MODULE_LINE].events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append([ev.name, float(ev.start_ns),
                                     float(ev.duration_ns)])
    return {"devices": devices, "modules": modules, "host": host,
            "layout": layout}


def _union(intervals: Sequence[Sequence[float]]) -> List[List[float]]:
    out: List[List[float]] = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _label(gap_s: float, gap_e: float, host: Sequence[Sequence]) -> str:
    """The host span that overlaps the gap most (``host:none`` if none)."""
    best, best_ov = "host:none", 0.0
    for name, s, dur in host:
        ov = min(gap_e, s + dur) - max(gap_s, s)
        if ov > best_ov:
            best, best_ov = name, ov
    return best


def _module_at(mods: Sequence[Sequence], starts: Sequence[float],
               t: float) -> str:
    """Name of the program (``mods`` sorted by start) running at ``t``."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t < mods[i][1] + mods[i][2]:
        return mods[i][0]
    return ""


def _self_times(ops: Sequence[Sequence]) -> List[float]:
    """Each op's duration less its nested ops' (a ``while`` holds the ops
    of its body on the same line)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1]))
    self_t = [e - s for s, e in ops]
    stack: List[int] = []
    for i in order:
        s, e = ops[i]
        while stack and ops[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            self_t[stack[-1]] -= e - s
        stack.append(i)
    return self_t


def reduce(trace: Dict, t0_ns: Optional[float] = None,
           t1_ns: Optional[float] = None,
           kernels: Optional[Dict[str, Sequence[str]]] = None,
           top: int = 10) -> Optional[Dict]:
    """Busy and idle time of the devices over [t0_ns, t1_ns] (default: the
    extent of the host spans), averaged over the devices traced; the time
    of each named kernel (ops whose name or custom-call target contains
    one of its patterns), averaged likewise; the ops that took most self
    time, named with their program; and the longest idle gaps, named by
    the host span they fall in. None when no device operation was traced."""
    devs = {k: v for k, v in trace["devices"].items() if v}
    if not devs:
        return None
    host = trace.get("host", [])
    if t0_ns is None or t1_ns is None:
        if host:
            t0_ns = min(s for _, s, _ in host)
            t1_ns = max(s + d for _, s, d in host)
        else:
            evs = [e for v in devs.values() for e in v]
            t0_ns = min(e[1] for e in evs)
            t1_ns = max(e[1] + e[2] for e in evs)
    window = (t1_ns - t0_ns) * 1e-9
    busy_total = 0.0
    op_time: Dict[str, float] = {}
    kern_time = {k: 0.0 for k in (kernels or {})}
    gaps: List = []
    for plane, evs in devs.items():
        mods = sorted(trace.get("modules", {}).get(plane, []),
                      key=lambda m: m[1])
        starts = [m[1] for m in mods]
        kept, spans = [], []
        for name, s, dur, target in evs:
            a, b = max(s, t0_ns), min(s + dur, t1_ns)
            if b > a:
                kept.append((name, target, a))
                spans.append((a, b))
        for (name, target, a), (s, e), st in zip(kept, spans,
                                                 _self_times(spans)):
            mod = _module_at(mods, starts, a)
            label = f"{name} in {mod}" if mod else name
            op_time[label] = op_time.get(label, 0.0) + st * 1e-9
            for k, pats in (kernels or {}).items():
                if any(p in name or p in target for p in pats):
                    kern_time[k] += (e - s) * 1e-9
        u = _union(spans)
        busy_total += sum(e - s for s, e in u) * 1e-9
        edges = [t0_ns] + [x for iv in u for x in iv] + [t1_ns]
        for i in range(0, len(edges), 2):
            s, e = edges[i], edges[i + 1]
            if e > s:
                gaps.append((e - s, s, e))
    n = len(devs)
    gaps.sort(reverse=True)
    return {
        "devices": n,
        "window_s": window,
        "busy_s": busy_total / n,
        "kernel_s": {k: v / n for k, v in kern_time.items()},
        "device_ops": [[k, v / n] for k, v in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_label(s, e, host), g * 1e-9]
                      for g, s, e in gaps[:top]],
    }
