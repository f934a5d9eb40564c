"""The trace reduction: busy and idle time, kernel time and labelled idle
gaps, on a hand-made trace and on a short one recorded on a v5e."""
import json
from pathlib import Path

import pytest

from bench import devtrace, harness

DATA = Path(__file__).resolve().parent / "data"


def test_hand_made_trace():
    trace = {"devices": {"/device:TPU:0": [
        ["fusion.1", 0.0, 100.0, ""],
        ["custom-call.7", 150.0, 50.0, "long_name=_ragged_gateup_kernel"],
        ["fusion.2", 180.0, 40.0, ""],
    ]}, "host": [["bench.step", 0.0, 160.0], ["bench.idle", 160.0, 200.0]]}
    red = devtrace.reduce(trace, 0.0, 300.0,
                          kernels={"moe_ffn": ("ragged_gateup",)})
    assert red["busy_s"] == pytest.approx(170e-9)
    assert red["window_s"] == pytest.approx(300e-9)
    assert red["kernel_s"]["moe_ffn"] == pytest.approx(50e-9)
    assert red["idle_gaps"] == [["bench.idle", pytest.approx(80e-9)],
                                ["bench.step", pytest.approx(50e-9)]]
    assert red["device_ops"][0] == ["fusion.1", pytest.approx(100e-9)]


def test_no_device_ops_reads_nothing():
    assert devtrace.reduce({"devices": {}, "host": []}) is None


def _busy_by_sweep(events, t0, t1):
    """Busy time by a sweep over event boundaries (an independent count)."""
    marks = []
    for _, s, d, _ in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            marks += [(a, 1), (b, -1)]
    marks.sort()
    depth, last, busy = 0, None, 0.0
    for t, step in marks:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy * 1e-9


def test_recorded_v5e_trace():
    path = DATA / "trace_granite_v5e.json"
    trace = json.loads(path.read_text())
    (events,) = trace["devices"].values()
    t0 = min(s for _, s, _ in trace["host"])
    t1 = max(s + d for _, s, d in trace["host"])
    red = devtrace.reduce(trace, kernels=harness.KERNELS)
    assert red["busy_s"] == pytest.approx(_busy_by_sweep(events, t0, t1),
                                          rel=1e-9)
    kern = sum(min(s + d, t1) - max(s, t0) for n, s, d, x in events
               if any(p in n or p in x for p in
                      harness.KERNELS["moe_ffn"])
               and min(s + d, t1) > max(s, t0)) * 1e-9
    assert red["kernel_s"]["moe_ffn"] == pytest.approx(kern, rel=1e-9)
    assert red["kernel_s"]["moe_ffn"] > 0
    assert 0 < red["busy_s"] < red["window_s"]
    gaps = [g for _, g in red["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert all(lbl.startswith("bench.") or lbl == "host:none"
               for lbl, _ in red["idle_gaps"])
