#!/usr/bin/env python3
"""End-to-end check that the serving path runs on a TPU at published widths.

    python chip_smoke.py            # one chip: kernel vs oracle, four backends
    python chip_smoke.py --ep 4     # four chips: expert-parallel dynaexq
                                    # against the single-device engine

Serves granite-moe-1b-a400m at its published widths (24 layers, d_model
1024, 32 experts top-8, d_ff_expert 512, 16/8 heads of 64, vocab 49155)
with random weights made from ``--seed``, through the entry points a user
calls: ``repro.launch.serve``'s builders, ``make_backend``,
``EngineConfig`` and ``InferenceEngine``. One process; it starts no child.

Phases on one chip:

1. the ragged mixed-precision FFN kernel (Pallas) against its jnp oracle at
   granite widths, int4, with hi slots held;
2. ``dynaexq`` serving: 4 requests whose prompts span two prefill buckets,
   32 new tokens each, the launcher's default speculation, and a
   controller window short enough that promotions publish mid-run;
3. ``static``, ``fp16`` and ``offload``: 2 requests each, speculation off.

Each phase prints one line (widths, device, compile seconds, tokens, TTFT
and TPOT as the engine reports them, every device's bytes in use before and
after the engine is built, and device 0's peak after building and after
serving: a process maximum, never reset). Any failed check exits non-zero.
The last line of stdout is a JSON object naming the device. Without a TPU,
or without the repository's ``src/`` beside it, the script exits non-zero
before any phase and prints no result.

Compiled programs go to JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR``
when set, else ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import functools
import gc
import json
import math
import os
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

ARCH = "granite-moe-1b-a400m"
NEW_TOKENS = 32
PROMPT_LENS = (100, 700)        # prefill buckets 128 and 740 (= max_len)

#: Kernel vs oracle: the largest |pallas − jnp| over the largest |jnp|. Both
#: sides take bf16 inputs and accumulate in f32 (the oracle at "highest"
#: matmul precision), then round the gate/up products, the SiLU·up hidden
#: state and the output to bf16 at the same points; they differ in the order
#: of the f32 sums only. A flipped bf16 rounding of a hidden element moves
#: an output by one bf16 ulp (2^-8 relative) times one weight, so the
#: scale-normalized error stays well under one percent; 2e-2 leaves room for
#: a few such flips landing on the same output and nothing more.
KERNEL_TOL = 2e-2

#: EP vs single device. Both engines serve the same prompts greedily from
#: the same weights at drop-free MoE capacity with the residency policy
#: frozen, so request i's logits row j has identical inputs on both while
#: the two emitted the same first j tokens; every such row is compared
#: (relative L2 error). The EP step routes each token through the same
#: experts and kernel, but other rows share its kernel tiles and its gate-
#: weighted sum is formed in another order: a few bf16 roundings (2^-8
#: each) of the MoE output. At depth 1 (the published widths, one layer)
#: that moves a logits row by well under EP_TOL. A token whose router
#: top-8 holds a near tie may take another expert when its router input
#: rounds differently; its row then moves by about one expert's share of
#: the output, so up to EP_FLIP_SHARE of the rows may exceed EP_TOL.
EP_TOL = 2e-2
EP_FLIP_SHARE = 0.05
#: Both depths also run a control: the single-device engine against itself
#: with layer 0's MoE input-norm scale moved by one bf16 ulp (1 → 1 +
#: 2^-7), a change of the size of one rounding. At the published depth of
#: 24 layers the random-weight model turns such roundings into other expert
#: choices in later layers and other argmaxes, so there the bound is
#: relative to the control: the EP engine's median row error may be at
#: most EP_CTRL_FACTOR times the control's. A wrong expert mapping or a
#: lost shard gives an unrelated row, a relative error near sqrt(2).
EP_CTRL_FACTOR = 2.0


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


# ---------------------------------------------------------------------------
# Instrumentation: compile time, logits and device memory
# ---------------------------------------------------------------------------

class CompileMeter:
    """Seconds spent in backend compilation (persistent-cache reads
    included: a hit replaces the compile), and the cache hits, from JAX's
    own monitoring events."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return self.seconds, self.hits


class LogitLog:
    """An ``InferenceEngine.logits_hook``: counts forwards by kind, keeps
    one on-device all-finite flag per forward and, with ``keep``, the host
    copy of the logits row each request drew its j-th token from, keyed by
    (request index, j)."""

    def __init__(self, keep: bool = False):
        self.keep = keep
        self.index = {}                  # RequestHandle.id → request index
        self.forwards = collections.Counter()
        self.flags = []
        self.rows = {}

    def __call__(self, kind, logits, rows):
        import jax.numpy as jnp
        import numpy as np
        self.forwards[kind] += 1
        self.flags.append(jnp.isfinite(logits).all())
        if self.keep and kind != "verify":
            host = np.asarray(logits, np.float32)
            for r, h in rows:
                self.rows[(self.index[h.id], len(h.tokens))] = host[r]

    def all_finite(self) -> bool:
        return all(bool(f) for f in self.flags)


def device_bytes() -> list:
    """``bytes_in_use`` of every device, in device order."""
    import jax
    return [int((d.memory_stats() or {}).get("bytes_in_use", -1))
            for d in jax.devices()]


def peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def phase_kernel(seed: int, *, E: int, K: int, F: int, bits: int = 4,
                 group: int = 64, bm: int = 8, n_tiles: int = 64,
                 n_hi: int = 4) -> dict:
    """The ragged FFN kernel against its jnp oracle on the same seeded
    inputs: tiles over random experts, ``n_hi`` of them held in hi slots."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops as kops
    from repro.quant import quantize

    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    dense = {name: jax.random.normal(k, (E, kk, nn), jnp.float32) * kk ** -0.5
             for k, (name, kk, nn) in zip(keys[:3], [("w_gate", K, F),
                                                     ("w_up", K, F),
                                                     ("w_down", F, K)])}
    lo = {n: quantize(w, bits=bits, group_size=group) for n, w in dense.items()}
    rng = np.random.default_rng(seed)
    hi_experts = rng.choice(E, n_hi, replace=False)
    hi = {n: w[hi_experts].astype(jnp.bfloat16) for n, w in dense.items()}
    tile_eid = np.sort(rng.integers(0, E, n_tiles)).astype(np.int32)
    slot_of = {int(e): s for s, e in enumerate(hi_experts)}
    tile_slot = np.asarray([slot_of.get(int(e), -1) for e in tile_eid],
                           np.int32)
    xs = jax.random.normal(keys[3], (n_tiles * bm, K), jnp.bfloat16)

    def run(backend):
        fn = jax.jit(functools.partial(kops.ragged_quant_ffn_op, bits=bits,
                                       group=group, bm=bm, backend=backend))
        return np.asarray(fn(xs, jnp.asarray(tile_eid), jnp.asarray(tile_slot),
                             lo, hi), np.float32)

    got = run("pallas")
    with jax.default_matmul_precision("highest"):
        want = run("jnp")
    err = float(np.max(np.abs(got - want)))
    scale = float(np.max(np.abs(want)))
    return {"max_abs_err": err, "max_rel_err": err / max(scale, 1e-30),
            "scale": scale, "hi_tiles": int((tile_slot >= 0).sum()),
            "tiles": n_tiles, "finite": bool(np.isfinite(got).all())}


def prompts(vocab: int, lens, seed: int):
    from repro.serving import make_prompts
    return [make_prompts("text", vocab, 1, n, seed=seed + i)[0]
            for i, n in enumerate(lens)]


def build_engine(cfg, seed: int, argv, dist=None, tune=None,
                 capacity_factor=None, nudge=None):
    """Engine for ``repro.launch.serve`` arguments ``argv``, built by the
    launcher's own builders over fresh seeded weights (backends take
    ownership of the expert weights, so every engine gets its own).
    ``tune(backend)`` adjusts the backend's policy config before the engine
    materializes it; ``capacity_factor`` replaces the engine's MoE one;
    ``nudge(params)`` edits the weights in place before the engine sees
    them."""
    import jax

    from repro.launch.serve import (build_backend, build_engine_config,
                                    build_parser)
    from repro.models import init_params
    from repro.serving import InferenceEngine

    args = build_parser().parse_args(argv)
    backend = build_backend(args)
    if tune is not None:
        tune(backend)
    ecfg = build_engine_config(args)
    if capacity_factor is not None:
        ecfg = dataclasses.replace(ecfg, capacity_factor=capacity_factor)
    params = init_params(jax.random.PRNGKey(seed), cfg)
    if nudge is not None:
        nudge(params)
    return InferenceEngine(cfg, params, backend, ecfg, dist=dist)


def serve_argv(backend: str, batch: int, extra=()) -> list:
    return ["--backend", backend, "--batch", str(batch),
            "--prompt-len", str(max(PROMPT_LENS)),
            "--new-tokens", str(NEW_TOKENS), *extra]


def serve(engine, toks, keep: bool = False):
    """Submit every prompt, drain, and report. Returns (handles, report);
    with ``keep``, ``report["rows"]`` holds the host logits row behind each
    emitted token (see ``LogitLog``)."""
    from repro.serving import Request

    log = LogitLog(keep)
    engine.logits_hook = log
    t0 = time.perf_counter()
    handles = []
    for i, t in enumerate(toks):
        h = engine.submit(Request(tokens=t, max_new_tokens=NEW_TOKENS))
        log.index[h.id] = i
        handles.append(h)
    engine.drain()
    wall = time.perf_counter() - t0
    engine.logits_hook = None
    st = engine.stats()           # before flush: promotions published mid-run
    engine.flush()
    return handles, {"wall_s": wall, "stats": st,
                     "forwards": dict(log.forwards),
                     "finite": log.all_finite(), "rows": log.rows}


def widths(cfg) -> str:
    a, m = cfg.attn, cfg.moe
    return (f"{cfg.name} L={cfg.n_layers} d={cfg.d_model} E={m.num_experts} "
            f"top{m.top_k} F={m.d_ff_expert} heads={a.n_heads}/{a.n_kv_heads}"
            f"x{a.head_dim} V={cfg.vocab_size}")


def check_served(name: str, handles, rep) -> None:
    short = [len(h.tokens) for h in handles if len(h.tokens) != NEW_TOKENS]
    if short or any(h.state.value != "finished" for h in handles):
        fail(f"{name}: not every request finished with {NEW_TOKENS} tokens "
             f"({[len(h.tokens) for h in handles]})")
    if not rep["finite"]:
        fail(f"{name}: non-finite logits")


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------

def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    ap.add_argument("--ep", type=int, default=0,
                    help="run only the expert-parallel comparison over this "
                         "many chips")
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        fail(f"no repository source beside this script ({SRC})")
    sys.path.insert(0, str(SRC))
    # The TPU runtime logs to /tmp unless told otherwise.
    os.environ.setdefault("TPU_LOG_DIR", str(SRC.parent / ".tpu_logs"))

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"JAX found no TPU (device 0 is {dev.platform}: "
             f"{dev.device_kind}); this check runs only on the chip")
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    meter = CompileMeter(jax)
    print(f"[chip_smoke] {dev.device_kind} x{jax.device_count()}  "
          f"jax {jax.__version__}  compile cache {cache_dir}")

    from repro.configs import get_config
    cfg = get_config(ARCH)
    if args.ep:
        run_ep(cfg, args.seed, args.ep, meter, dev)
    else:
        run_one_chip(cfg, args.seed, meter, dev)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))


def run_phase(name, cfg, seed, argv, toks, meter, dev, keep=False, **kw):
    """Build one engine, serve ``toks`` through it, print the phase line.
    Returns (engine, handles, report)."""
    c0 = meter.snapshot()
    before = device_bytes()
    eng = build_engine(cfg, seed, argv, **kw)
    built, peak_built = device_bytes(), peak_bytes(dev)
    handles, rep = serve(eng, toks, keep=keep)
    st = rep["stats"]
    print(f"[{name}] {widths(cfg)} | {dev.device_kind} | compile "
          f"{meter.seconds - c0[0]:.2f} s ({meter.hits - c0[1]} cache hits) | "
          f"tokens {sum(len(h.tokens) for h in handles)} in "
          f"{rep['wall_s']:.2f} s | ttft {st.get('ttft_s', 0.0):.6f} s "
          f"tpot {st.get('tpot_s', 0.0):.6f} s | forwards {rep['forwards']} "
          f"| dispatch={eng.moe_dispatch} promotions "
          f"{st['promotions']:.0f} | bytes in use before {before} built "
          f"{built} | peak after build {peak_built} after serving "
          f"{peak_bytes(dev)} B")
    check_served(name, handles, rep)
    if eng.moe_dispatch != "ragged":
        fail(f"{name}: moe_dispatch={eng.moe_dispatch}, expected ragged")
    return eng, handles, rep


def run_one_chip(cfg, seed: int, meter: CompileMeter, dev) -> None:
    # 1 — kernel against oracle
    c0 = meter.snapshot()
    k = phase_kernel(seed, E=cfg.moe.num_experts, K=cfg.d_model,
                     F=cfg.moe.d_ff_expert)
    print(f"[kernel] ragged_quant_ffn int4 g64 bm8 E={cfg.moe.num_experts} "
          f"K={cfg.d_model} F={cfg.moe.d_ff_expert} tiles={k['tiles']} "
          f"hi_tiles={k['hi_tiles']} | {dev.device_kind} | compile "
          f"{meter.seconds - c0[0]:.2f} s | max_abs_err {k['max_abs_err']:.6g} "
          f"max_rel_err {k['max_rel_err']:.6g} (tol {KERNEL_TOL}) | peak "
          f"{peak_bytes(dev)} B")
    if not k["finite"] or not k["max_rel_err"] <= KERNEL_TOL:
        fail(f"kernel vs oracle: {k}")

    # 2 — dynaexq, launcher defaults (int4 lo, speculation on)
    def short_window(backend):
        # Every tick may open an allocation window, so promotions publish
        # while the requests are still decoding.
        backend.controller_cfg.update_interval_s = 0.0

    toks = prompts(cfg.vocab_size, PROMPT_LENS * 2, seed)
    eng, handles, rep = run_phase(
        "dynaexq", cfg, seed, serve_argv("dynaexq", 4, ["--n-hi", "4"]),
        toks, meter, dev, tune=short_window)
    custom = "tpu_custom_call" in eng.decode_program_text()
    print(f"[dynaexq] spec_k={eng.ecfg.spec_k} decode step "
          f"tpu_custom_call={custom}")
    if rep["stats"]["promotions"] < 1:
        fail("dynaexq: no promotion published while serving")
    if not custom:
        fail("dynaexq: the decode step holds no Pallas kernel")
    del eng, handles
    gc.collect()

    # 3 — the baselines, speculation off
    for name in ("static", "fp16", "offload"):
        eng, handles, _ = run_phase(name, cfg, seed,
                                    serve_argv(name, 2, ["--no-spec"]),
                                    toks[:2], meter, dev)
        del eng, handles
        gc.collect()


def nudge_ulp(params) -> None:
    """The control's perturbation: layer 0's MoE input-norm scale moved up
    by one bf16 ulp."""
    import jax.numpy as jnp
    norm = params["blocks"]["0"]["norm2"]
    s = norm["scale"]
    norm["scale"] = s.at[0].set(jnp.nextafter(s[0], jnp.asarray(jnp.inf,
                                                                s.dtype)))


def compare(ref, got):
    """Relative L2 errors of the logits rows whose inputs agree (request
    i's row j while both emitted the same first j tokens), and the number
    of equal tokens. ``ref``/``got``: (token lists, ``LogitLog.rows``)."""
    import numpy as np

    (t_ref, rows_ref), (t_got, rows_got) = ref, got
    errs = []
    for i, (a, b) in enumerate(zip(t_ref, t_got)):
        for j in range(len(a)):
            if a[:j] != b[:j]:
                break
            x, y = rows_ref[(i, j)], rows_got[(i, j)]
            errs.append(float(np.linalg.norm(y - x) / np.linalg.norm(x)))
    match = sum(int(x == y) for a, b in zip(t_ref, t_got)
                for x, y in zip(a, b))
    return np.asarray(errs), match


def run_ep(cfg, seed: int, n: int, meter: CompileMeter, dev) -> None:
    """Expert-parallel dynaexq over ``make_ep_mesh(n)`` against the
    single-device engine on the same weights and prompts, both greedy, at
    drop-free MoE capacity, with the residency policy frozen (no promotion
    or migration may fire, so both serve the identical all-lo bank). Runs
    at depth 1 and at the published depth; see EP_TOL and EP_CTRL_FACTOR."""
    import jax
    import numpy as np

    from repro.launch.dist import ep_context
    from repro.launch.mesh import make_ep_mesh

    if jax.device_count() < n:
        fail(f"--ep {n} needs {n} devices, found {jax.device_count()}")
    if cfg.moe.num_experts % n:
        fail(f"{cfg.moe.num_experts} experts do not split over {n} chips")
    n_hi = 2 * n
    # MoE capacity at which no assignment drops: any expert may take every
    # token. Under a tighter factor EP splits each expert's capacity over
    # the sending shards (``models.moe.ep_cap_shard``), so which overflow
    # assignments drop depends on where the tokens sit, by design.
    drop_free = cfg.moe.num_experts / cfg.moe.top_k

    def frozen(backend):
        backend.controller_cfg.update_interval_s = math.inf
        if backend.coordinator is not None:
            backend.coordinator.cfg.interval_s = math.inf

    toks = prompts(cfg.vocab_size, PROMPT_LENS * 2, seed)
    ep = f"ep{n}"
    for depth in (1, cfg.n_layers):
        c = dataclasses.replace(cfg, n_layers=depth)
        runs = {}
        for tag, shards, nudge in (("single", 1, None), (ep, n, None),
                                   ("single+ulp", 1, nudge_ulp)):
            extra = ["--no-spec", "--n-hi", str(n_hi), "--moe-dispatch",
                     "ragged", "--ep-shards", str(shards)]
            dist = ep_context(make_ep_mesh(n)) if shards > 1 else None
            eng, handles, rep = run_phase(
                f"{tag} L={depth}", c, seed,
                serve_argv("dynaexq", len(toks), extra), toks, meter, dev,
                keep=True, dist=dist, tune=frozen,
                capacity_factor=drop_free, nudge=nudge)
            if rep["stats"]["promotions"] or rep["stats"].get("migrations", 0):
                fail(f"{tag}: the frozen policy moved experts")
            runs[tag] = ([list(h.tokens) for h in handles], rep["rows"])
            del eng, handles, rep
            gc.collect()

        total = sum(len(t) for t in runs["single"][0])
        errs, match = compare(runs["single"], runs[ep])
        over = float(np.mean(errs > EP_TOL))
        print(f"[{ep}-vs-single L={depth}] {errs.size} logits rows with "
              f"equal inputs (prefill and decode): relative error median "
              f"{np.median(errs):.6g} max {errs.max():.6g}; share over "
              f"{EP_TOL}: {over:.6g} | tokens match {match}/{total}")
        ctrl, ctrl_match = compare(runs["single"], runs["single+ulp"])
        print(f"[single+ulp-vs-single L={depth}] {ctrl.size} logits rows "
              f"with equal inputs: relative error median "
              f"{np.median(ctrl):.6g} max {ctrl.max():.6g} | tokens match "
              f"{ctrl_match}/{total}")
        if not np.isfinite(errs).all():
            fail(f"L={depth}: non-finite logits difference")
        if depth == 1:
            if over > EP_FLIP_SHARE:
                fail(f"L=1: {over:.3g} of the EP logits rows differ from "
                     f"the single-device ones by more than {EP_TOL}")
        elif np.median(errs) > EP_CTRL_FACTOR * np.median(ctrl):
            fail(f"L={depth}: EP logits differ from the single-device ones "
                 f"by more than {EP_CTRL_FACTOR}x a one-ulp perturbation")


if __name__ == "__main__":
    main()
