#!/usr/bin/env python3
"""Find a cell's knee once: serve its traffic at a ladder of offered rates
from one engine, and report at each whether the backlog grows and whether
the window emits the tokens its requests ask for.

    python3 bench/sweep.py --workload granite-shift --seconds 50 \
        --seed 5 --rates 0.4 0.6 0.8 1.0

The engine is built and warmed once; each rate, in the order given, then
serves the mix's lead-in and a window of ``--seconds``, and the engine
drains before the next rate. After two rates that are not sustained the
ladder ends. A rate is sustained when it passes two tests, both printed:

- by queue: by the window's end every request due in its first half has
  its first token, the queue holds at most ``--max-queue`` requests, and
  the TTFT median of the window's last third is at most twice that of its
  first third;
- by tokens: the requests due in the window emit inside it at least
  ``TOKEN_SHARE`` of the output tokens they ask for, each request's ask
  capped by the window's end: no more than it could emit between its due
  time and the close at the pace of the ladder's first rate (its median
  prefill forward, then one token a mean decode forward). A backlog held
  in the slots, which the queue test misses, fails this one.

The knee is the highest sustained rate. A cell runs at a fixed rate
written into its traffic file; this tool is how that rate was found, not
part of a run. It runs only on a TPU.
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

#: Share of the capped ask that a sustained rate emits in the window.
TOKEN_SHARE = 0.9


def token_test(win, window_s: float, prefill_s: float,
               decode_s: float) -> dict:
    """Tokens the requests due in the window emitted inside it, against
    what they ask for: each request's ``max_new``, capped at what it could
    emit by the close when served from its due time at ``prefill_s`` for
    the first token and ``decode_s`` for each further one."""
    asked = emitted = 0
    for r in win:
        left = window_s - r.due_s - prefill_s
        cap = 0 if left < 0 else 1 + int(left // decode_s)
        asked += min(r.max_new, cap)
        emitted += sum(1 for t in r.token_t if 0.0 <= t <= window_s)
    share = emitted / asked if asked else None
    return {"tokens_asked": asked, "tokens_emitted": emitted,
            "token_share": share,
            "tokens_ok": share is not None and share >= TOKEN_SHARE}


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
    from bench import harness
    from bench.traffic import generator
    if jax.devices()[0].platform != "tpu":
        entry.fail("the sweep runs only on a TPU", 3)
    cell = harness.load_cell(args.workload)
    d = cell.arch.dims(cell.conf)
    cfg = cell.arch.program_arch(cell.conf, d)
    harness.check_layout(cell.arch, cfg, d)
    engine, _ = harness.build_engine(cell.conf, cfg,
                                     cell.arch.make_params(d, args.seed))
    reqs = generator.schedule(cell.mix, d["vocab_size"], args.seed,
                              args.seconds)
    harness.warm_up(engine, cell.mix, reqs, d["vocab_size"], args.seed)
    harness.log(f"engine ready after {time.perf_counter() - T0:.1f} s")
    readers = {n: harness.metric_reader(n) for n in
               ("ttft_p95_s", "tpot_p95_s", "output_tokens_per_s")}
    missed = 0
    pace = None                 # (prefill, decode) s at the first rate
    for i, rate in enumerate(args.rates):
        sched = generator.schedule(cell.mix, d["vocab_size"],
                                   args.seed + i + 1, args.seconds,
                                   rate_rps=rate)
        recs = [harness.Record(r.due_s, r.prompt_len, r.max_new_tokens,
                               r.workload, r.tokens) for r in sched]
        origin = time.perf_counter() + cell.mix["lead_in_s"]
        harness.serve(engine, [r for r in recs if r.due_s < 0], origin, 0.0)
        n_pre, n_dec = len(engine.backend._ttft), len(engine.decode_times)
        kv_max = harness.serve(engine, [r for r in recs if r.due_s >= 0],
                               origin, args.seconds)
        queued = len(engine.queue)
        prefill_s = list(engine.backend._ttft[n_pre:])
        decode_s = list(engine.decode_times[n_dec:])
        if pace is None and prefill_s and decode_s:
            pace = (float(np.median(prefill_s)), float(np.mean(decode_s)))
        run = harness.Run(window_s=args.seconds, setup_s=0.0, requests=recs,
                          arch=cell.arch, dims=d, peaks={}, chips=1,
                          lo_bits=4, group=64, counters={}, stats={},
                          disp={}, prefill_s=prefill_s, decode_s=decode_s)
        win = run.measured()
        third = args.seconds / 3

        def ttft_med(lo, hi):
            v = [(r.first_token_t if r.first_token_t is not None
                  else args.seconds) - r.due_s
                 for r in win if lo <= r.due_s < hi]
            return float(np.median(v)) if v else None
        first_half = [r for r in win if r.due_s < args.seconds / 2]
        early, late = ttft_med(0, third), ttft_med(2 * third, args.seconds)
        queue_ok = (all(r.first_token_t is not None for r in first_half)
                    and queued <= args.max_queue and early is not None
                    and late is not None and late <= 2 * max(early, 0.5))
        tokens = token_test(win, args.seconds, *pace) if pace else \
            {"tokens_ok": False}
        sustained = queue_ok and tokens["tokens_ok"]
        out = {"rate_rps": rate, "due": len(win), "queued_at_end": queued,
               "kv_blocks_max": kv_max,
               "ttft_p50_first_third": early, "ttft_p50_last_third": late,
               "queue_ok": queue_ok, **tokens, "sustained": sustained,
               "pace_s": pace,
               "prefill_ms_p50": float(np.median(prefill_s)) * 1e3
               if prefill_s else None,
               "decode_ms_mean": float(np.mean(decode_s)) * 1e3
               if decode_s else None}
        out.update({n: f(run) for n, f in readers.items()})
        print(json.dumps(out), flush=True)
        missed += not sustained
        if missed >= 2:
            break
        engine.drain()


if __name__ == "__main__":
    main()
