"""A cell, configuration, traffic mix or metric added as files and entries
is found by name, with no edit to the harness."""
import json
import shutil
from pathlib import Path

from bench import harness

ROOT = Path(__file__).resolve().parents[2]


def test_every_declared_part_exists():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.chips == w["chips"]
        assert cell.limits, f"no correctness limits for {w['name']}"
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))


def test_added_cell_is_found_by_name(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench = root / "bench"
    # A new configuration, traffic mix, metric and limit: files only.
    conf = json.loads((bench / "configs" /
                       "granite-moe-1b-a400m.json").read_text())
    conf["name"] = "granite-moe-1b-a400m-b16"
    conf["serving"]["serve_args"] += ["--n-hi", "16"]
    (bench / "configs" / "granite-moe-1b-a400m-b16.json").write_text(
        json.dumps(conf))
    mix = json.loads((bench / "traffic" / "shift_granite.json").read_text())
    mix["rate_rps"] = 1.25
    (bench / "traffic" / "burst_new.json").write_text(json.dumps(mix))
    (bench / "metrics" / "queue_wait_p50_s.py").write_text(
        "def read(run):\n    return 0.5\n")
    (bench / "checks" / "granite-b16-burst.json").write_text(
        json.dumps({"widest_logit_gap": {"limit": 1.0}}))
    spec["configs"].append({"name": "granite-moe-1b-a400m-b16",
                            "source": "x",
                            "file": "bench/configs/"
                                    "granite-moe-1b-a400m-b16.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "granite-b16-burst",
                              "config": "granite-moe-1b-a400m-b16",
                              "traffic": "burst_new", "chips": 1,
                              "why": "x"})
    spec["per_layer"].append({"name": "queue_wait_p50_s", "unit": "s",
                              "better": "lower", "source": "host_clock",
                              "layer": "scheduler and engine loop",
                              "moves": "ttft_p95_s",
                              "workloads": ["granite-b16-burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.load_cell("granite-b16-burst",
                             bench_file=root / "BENCHMARK.json",
                             bench_dir=bench)
    assert cell.mix["rate_rps"] == 1.25
    assert cell.conf["name"] == "granite-moe-1b-a400m-b16"
    assert cell.limits["widest_logit_gap"]["limit"] == 1.0
    names = [m["name"] for m in cell.per_layer]
    assert "queue_wait_p50_s" in names
    assert harness.metric_reader("queue_wait_p50_s", bench)(None) == 0.5
    # The metric scoped to the new cell stays out of the others.
    old = harness.load_cell("granite-shift",
                            bench_file=root / "BENCHMARK.json",
                            bench_dir=bench)
    assert "queue_wait_p50_s" not in [m["name"] for m in old.per_layer]
