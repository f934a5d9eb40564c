#!/usr/bin/env python3
"""Readings that a cell's correctness limits are set from: the program's
as the configuration states it, and the control's, on many seeds in one
process.

    python3 bench/calibrate.py --workload granite-shift --seconds 50 \
        --seeds 11 12 13 --control-seeds 11 12 13 [--rate R]

For every seed it makes one whole run of the cell (set-up, the window at
the cell's own load, the sample compared with the reference) and judges
the readings by the cell's limits, as ``bench/run.py`` does. The control
is the program with its own lower-precision path switched on: the
launcher flags under ``control.serve_args`` in ``bench/checks/<cell>.json``
(for dynaexq, the lo tier one width down). A control run that comes out
correct is reported as a failed control. ``--variant NAME=FLAGS`` adds
further readings of the program under other flags, judged the same way.
One JSON line per run; the benchmark's own runs never run the control.
It runs only on a TPU.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))
import run as entry  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--variant", action="append", default=[],
                    metavar="NAME=FLAGS",
                    help="e.g. all_int4='--backend static' (first seed)")
    ap.add_argument("--rate", type=float, default=None)
    args = ap.parse_args()
    entry.setup_environment()
    import jax
    from bench import harness
    if jax.devices()[0].platform != "tpu":
        entry.fail("calibration runs only on a TPU", 3)
    cell = harness.load_cell(args.workload)
    ctl = harness.with_serve_args(cell, cell.control.get("serve_args", []))
    # Program and control runs alternate, so that a cut call leaves both.
    pairs = [[("program", cell, s)] for s in args.seeds]
    for i, s in enumerate(args.control_seeds):
        if i == len(pairs):
            pairs.append([])
        pairs[i].append(("control", ctl, s))
    runs = [r for pair in pairs for r in pair]
    for v in args.variant:
        name, flags = v.split("=", 1)
        runs += [(name, harness.with_serve_args(cell, flags.split()), s)
                 for s in args.seeds[:1]]
    for kind, c, seed in runs:
        res = harness.run_cell(c, seed, args.seconds, False,
                               time.perf_counter(), rate=args.rate)
        extra = res.pop("_extra")
        out = {"kind": kind, "seed": seed, "correct": res["correct"],
               "checks": res["checks"],
               "metrics": {k: v["value"] for k, v in res["metrics"].items()},
               "readings": extra.get("readings"),
               "sample_requests": extra["sample_requests"],
               "sample_tokens": extra["sample_tokens"],
               "finish_s": extra["finish_s"],
               "reference_s": extra.get("reference_s"),
               "kv_blocks_max": extra["kv_blocks_max"],
               "compiles_in_window": extra["compiles_in_window"],
               "peak": res["device"]["memory_peak_bytes"]}
        if kind == "control":
            out["control_failed_the_limits"] = not res["correct"]
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
