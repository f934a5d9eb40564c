#!/usr/bin/env python3
"""Find a cell's knee once: serve its traffic at a ladder of offered rates
from one engine, and report whether the backlog grows at each.

    python3 bench/sweep.py --workload granite-shift --seconds 50 \
        --seed 5 --rates 0.2 0.4 0.6 0.8

The engine is built and warmed once; each rate, in the order given, then
serves the mix's lead-in and a window of ``--seconds``, and the engine
drains before the next rate. After two rates that are not sustained the
ladder ends. The knee is the highest rate whose backlog does
not grow: by
the window's end every request due in its first half has its first token,
the queue holds at most ``--max-queue`` requests, and the TTFT median of
the window's last third is at most twice that of its first third. A cell
runs at a fixed rate written into its traffic file; this tool is how that
rate was found, not part of a run. It runs only on a TPU.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))
import run as entry  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--max-queue", type=int, default=4)
    args = ap.parse_args()
    entry.setup_environment()
    import jax
    from bench import harness, model
    from bench.traffic import generator
    if jax.devices()[0].platform != "tpu":
        entry.fail("the sweep runs only on a TPU", 3)
    cell = harness.load_cell(args.workload)
    d = model.dims(cell.conf)
    arch = harness.program_arch(cell.conf, d)
    harness.check_layout(arch, d)
    engine, _ = harness.build_engine(cell.conf, arch,
                                     model.make_params(d, args.seed))
    reqs = generator.schedule(cell.mix, d["vocab_size"], args.seed,
                              args.seconds)
    harness.warm_up(engine, cell.mix, reqs, d["vocab_size"], args.seed)
    harness.log(f"engine ready after {time.perf_counter() - T0:.1f} s")
    readers = {n: harness.metric_reader(n) for n in
               ("ttft_p95_s", "tpot_p95_s", "output_tokens_per_s")}
    missed = 0
    for i, rate in enumerate(args.rates):
        sched = generator.schedule(cell.mix, d["vocab_size"],
                                   args.seed + i + 1, args.seconds,
                                   rate_rps=rate)
        recs = [harness.Record(r.due_s, r.prompt_len, r.max_new_tokens,
                               r.workload, r.tokens) for r in sched]
        origin = time.perf_counter() + cell.mix["lead_in_s"]
        kv_max = harness.serve(engine, recs, origin, args.seconds)
        queued = len(engine.queue)
        run = harness.Run(window_s=args.seconds, setup_s=0.0, requests=recs,
                          dims=d, peaks={}, chips=1, lo_bits=4, group=64,
                          counters={}, stats={}, disp={}, prefill_s=[],
                          decode_s=[])
        win = run.measured()
        third = args.seconds / 3

        def ttft_med(lo, hi):
            v = [(r.first_token_t if r.first_token_t is not None
                  else args.seconds) - r.due_s
                 for r in win if lo <= r.due_s < hi]
            return float(np.median(v)) if v else None
        first_half = [r for r in win if r.due_s < args.seconds / 2]
        early, late = ttft_med(0, third), ttft_med(2 * third, args.seconds)
        sustained = (all(r.first_token_t is not None for r in first_half)
                     and queued <= args.max_queue and early is not None
                     and late is not None and late <= 2 * max(early, 0.5))
        out = {"rate_rps": rate, "due": len(win), "queued_at_end": queued,
               "kv_blocks_max": kv_max,
               "ttft_p50_first_third": early, "ttft_p50_last_third": late,
               "sustained": sustained}
        out.update({n: f(run) for n, f in readers.items()})
        print(json.dumps(out), flush=True)
        missed += not sustained
        if missed >= 2:
            break
        engine.drain()


if __name__ == "__main__":
    main()
