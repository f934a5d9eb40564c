#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator and print its result.

    python3 bench/run.py --workload granite-shift --seed 7 --seconds 30 --trace 0

The cell, its model configuration, its traffic mix, its metrics and its
correctness limit are all found by name (see ``bench/harness.py``). With
``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, read from the engine's counters, the
flight recorder and a profiler trace of a few seconds of the window.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced), then ``checks``: each number compared with its limit. The same
numbers are the last lines of standard error. Earlier lines log the set-up
(weights, engine, compilation, warm-up), the traffic and the window.

The run refuses to start without a TPU, or with fewer chips than the cell
asks for, and then prints no result. Compiled programs go to JAX's
persistent cache: ``JAX_COMPILATION_CACHE_DIR`` when set, else
``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parent.parent


def fail(msg: str, code: int = 2) -> None:
    print(f"[bench] error: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    return args


def setup_environment() -> str:
    """Runtime logs and the compile cache inside the checkout."""
    os.environ.setdefault("TPU_LOG_DIR", str(CHECKOUT / ".tpu_logs"))
    for p in (CHECKOUT, CHECKOUT / "src"):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    import jax
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache:
        cache = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache


def main(argv=None) -> None:
    args = parse(argv)
    if not (CHECKOUT / "BENCHMARK.json").is_file():
        fail(f"no BENCHMARK.json at {CHECKOUT}")
    if not (CHECKOUT / "src" / "repro").is_dir():
        fail(f"no program beside the benchmark ({CHECKOUT / 'src'})")
    cache = setup_environment()
    import jax
    from bench import harness

    cell = harness.load_cell(args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"JAX found no TPU (device 0 is {devs[0].platform}: "
             f"{devs[0].device_kind}); this benchmark runs only on the chip",
             3)
    if len(devs) < cell.chips:
        fail(f"cell {cell.name} needs {cell.chips} chips, JAX found "
             f"{len(devs)}", 3)
    harness.log(f"{cell.name}: {devs[0].device_kind} x{len(devs)}, jax "
                f"{jax.__version__}, compile cache {cache}, seed {args.seed}"
                f", window {args.seconds} s, trace {args.trace}")
    res = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           T_START)
    extra = res.pop("_extra")
    harness.log(f"detail {json.dumps(extra)}")
    for name, c in res["checks"].items():
        print(f"check {name}: value {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(res), flush=True)


if __name__ == "__main__":
    main()
