"""Serving launcher.

With no ``--full`` it serves the reduced config of ``--arch`` (CPU-sized:
narrower widths, fewer layers and experts); ``--full`` serves the published
widths, which needs an accelerator (one TPU v5e holds
granite-moe-1b-a400m). It drives the same ``InferenceEngine`` loop either
way, on one device, or over ``--ep-shards`` devices of an expert-parallel
mesh:

    python -m repro.launch.serve --arch granite-moe-1b-a400m \
        --backend dynaexq --batch 4 --prompt-len 32 --new-tokens 16 [--full]

``chip_smoke.py`` at the repository root is the one-chip end-to-end check
of the full-width path.
"""
import argparse
import time

import jax

from repro.configs import ARCH_IDS, get_config
from repro.core import ControllerConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.dist import ep_context
from repro.launch.mesh import make_ep_mesh
from repro.models import init_params
from repro.serving import (BACKENDS, EngineConfig, InferenceEngine,
                           OffloadConfig, Request, SamplingParams,
                           SchedulerConfig, load_streaming_params,
                           make_backend, make_prompts, save_expert_shards)


def build_backend(args):
    """CLI name → ResidencyBackend construction (builder code — the engine
    itself is backend-agnostic)."""
    if args.backend == "dynaexq":
        return make_backend(
            "dynaexq", lo_bits=args.lo_bits,
            n_hi_per_layer=None if args.hbm_gb else args.n_hi,
            hbm_gb=args.hbm_gb,
            controller=ControllerConfig(update_interval_s=0.25),
            ep_shards=args.ep_shards,
            global_alloc=False if args.per_layer_alloc else None,
            sensitivity=args.sensitivity,
            lo_resident_total=args.lo_resident_total,
            hotness_path=args.hotness_path,
            stream=args.stream_from,
            fault=_fault_plan(args))
    if args.backend == "static":
        return make_backend("static", lo_bits=args.lo_bits)
    if args.backend == "offload":
        return make_backend("offload", ocfg=OffloadConfig(
            cache_experts_per_layer=args.n_hi * 2))
    return make_backend(args.backend)


def _fault_plan(args):
    """``--fault-plan`` (JSON string or path) → FaultPlan, with
    ``--fault-seed`` overriding the plan's seed when given."""
    if not getattr(args, "fault_plan", None):
        return None
    from repro.fault import FaultPlan
    return FaultPlan.parse(args.fault_plan, seed=args.fault_seed)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-moe-1b-a400m", choices=ARCH_IDS)
    ap.add_argument("--backend", "--mode", dest="backend", default="dynaexq",
                    choices=sorted(BACKENDS))
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--lo-bits", type=int, default=4, choices=[2, 4, 8])
    ap.add_argument("--n-hi", type=int, default=2)
    ap.add_argument("--hbm-gb", type=float, default=None,
                    help="derive n_hi from a device envelope instead")
    ap.add_argument("--full", action="store_true",
                    help="serve the published widths instead of the "
                         "reduced config (needs an accelerator)")
    ap.add_argument("--workload", default="text")
    ap.add_argument("--dense-kv", action="store_true",
                    help="dense per-slot KV rows instead of the paged pool")
    ap.add_argument("--block-tokens", type=int, default=16,
                    help="KV positions per paged block")
    ap.add_argument("--no-prefix-sharing", action="store_true",
                    help="disable trie-based cross-request prefix reuse")
    ap.add_argument("--hbm-budget-gb", type=float, default=None,
                    help="unified envelope shared by KV blocks and the "
                         "expert hi tier (promotion backpressure under KV "
                         "pressure)")
    ap.add_argument("--moe-dispatch", default=None,
                    choices=["padded", "ragged"],
                    help="MoE token layout: padded (E,C,d) reference vs "
                         "ragged compacted dispatch + fused mixed-precision "
                         "kernel (default: ragged on TPU, padded on CPU)")
    ap.add_argument("--row-capacity", action="store_true",
                    help="normalize MoE capacity drops per request row "
                         "(batch-shape-independent token drops)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="max speculative draft depth per round (drafts on "
                         "the all-lo expert tier, verifies against the "
                         "mixed-precision banks)")
    ap.add_argument("--no-spec", action="store_true",
                    help="disable speculative decoding (one token per step)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy)")
    ap.add_argument("--top-k", type=int, default=None,
                    help="sample only from the k most probable tokens")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass (1.0 = full vocab)")
    ap.add_argument("--seed", type=int, default=0,
                    help="per-request sampling seed base (request b uses "
                         "seed+b)")
    ap.add_argument("--qos-default", default="standard",
                    choices=["batch", "standard", "premium"],
                    help="QoS class for requests that carry none (batch "
                         "decodes on the all-lo banks, premium keeps the "
                         "hi tier + speculative bursts)")
    ap.add_argument("--shed-policy", default="none",
                    choices=["none", "downgrade", "reject"],
                    help="overload response: downgrade batch/standard "
                         "execution to the lo tier, or also reject "
                         "batch-tier submissions outright")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="split prompts longer than this many tokens into "
                         "chunked prefills interleaved with decode "
                         "(0 = single-shot; rounded down to a "
                         "block-aligned prefill bucket)")
    ap.add_argument("--per-layer-alloc", action="store_true",
                    help="use the paper's per-layer top-n policy instead "
                         "of the default global cross-layer knapsack "
                         "allocator (dynaexq, single-shard)")
    ap.add_argument("--sensitivity", default=None,
                    help=".npz of per-expert quantization sensitivity "
                         "(quant.sensitivity.save_sensitivity) — weights "
                         "the global allocator's hotness ranking")
    ap.add_argument("--lo-resident-total", type=int, default=None,
                    help="enable the host-DRAM third tier: only this many "
                         "(layer, expert) cells stay device-lo-resident; "
                         "the rest pay a modeled demand-fetch stall when "
                         "routed")
    ap.add_argument("--hotness-path", default=None,
                    help="prefix for hotness snapshots: restored at "
                         "startup (warm allocator prior + hottest-first "
                         "streaming) and saved after the run")
    ap.add_argument("--stream-from", default=None,
                    help="expert-sharded checkpoint dir (save_expert_"
                         "shards): stream the lo tier in at startup and "
                         "serve before the model fully materializes")
    ap.add_argument("--save-shards", default=None,
                    help="write the expert-sharded serving checkpoint to "
                         "this dir and exit (streaming cold-start source)")
    ap.add_argument("--trace-out", default=None,
                    help="write the flight-recorder trace here as Chrome "
                         "trace-event JSON (open in Perfetto / "
                         "chrome://tracing)")
    ap.add_argument("--metrics-jsonl", default=None,
                    help="append per-step metric samples to this JSONL file")
    ap.add_argument("--no-obs", action="store_true",
                    help="disable the observability layer entirely (no "
                         "tracer, no metrics, no shutdown summary)")
    ap.add_argument("--fault-plan", default=None,
                    help="fault-injection plan for chaos runs: a JSON "
                         "string or a path to one, e.g. "
                         '\'{"seed": 7, "rules": [{"site": "host_lo", '
                         '"prob": 0.1}]}\' (dynaexq backend only)')
    ap.add_argument("--fault-seed", type=int, default=None,
                    help="override the fault plan's Philox seed")
    ap.add_argument("--promo-deadline-s", type=float, default=None,
                    help="watchdog: cancel promotions still unpublished "
                         "after this many seconds (refund + keep serving "
                         "lo)")
    ap.add_argument("--ep-shards", type=int, default=1,
                    help="expert-parallel serving over this many devices: "
                         "tokens and experts shard over the model axis, MoE "
                         "layers run the ragged all-to-all pipeline, and "
                         "the dynaexq hi pool splits into per-shard slot "
                         "ranges with per-shard budgets (requires "
                         "num_experts and --n-hi divisible by the shard "
                         "count; 1 = single-device)")
    return ap


def build_engine_config(args) -> EngineConfig:
    """CLI arguments → EngineConfig (sequence budget: prompt + new tokens
    + 8 positions of slack)."""
    return EngineConfig(
        max_slots=args.batch,
        max_len=args.prompt_len + args.new_tokens + 8,
        paged=not args.dense_kv,
        block_tokens=args.block_tokens,
        prefix_sharing=not args.no_prefix_sharing,
        hbm_budget_bytes=None if args.hbm_budget_gb is None
        else int(args.hbm_budget_gb * (1 << 30)),
        spec_k=0 if args.no_spec else max(0, args.spec_k),
        moe_dispatch=args.moe_dispatch,
        row_capacity_norm=args.row_capacity,
        promo_deadline_s=args.promo_deadline_s,
        scheduler=SchedulerConfig(
            qos_default=args.qos_default,
            shed_policy=args.shed_policy,
            prefill_chunk=args.prefill_chunk))


def main():
    args = build_parser().parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch, reduced=not args.full)
    ecfg = build_engine_config(args)
    dist = None
    if args.ep_shards > 1:
        if args.ep_shards > jax.device_count():
            raise SystemExit(
                f"--ep-shards {args.ep_shards} > visible devices "
                f"{jax.device_count()} (set XLA_FLAGS="
                f"--xla_force_host_platform_device_count=N to emulate)")
        dist = ep_context(make_ep_mesh(args.ep_shards))
    print(f"[serve] {cfg.name} backend={args.backend} "
          f"devices={jax.device_count()} spec_k={ecfg.spec_k} "
          f"ep_shards={args.ep_shards}")
    if args.stream_from:
        # Streaming cold start: only the base (non-expert) params load
        # synchronously; the lo tier backfills behind the engine.
        params = load_streaming_params(args.stream_from)
    else:
        params = init_params(jax.random.PRNGKey(0), cfg)
    if args.save_shards:
        positions = [p for p, _ in enumerate(cfg.superblock_or_default())
                     if cfg.ffn_kind(p) == "moe"] if cfg.is_moe else []
        save_expert_shards(args.save_shards, params, positions,
                           lo_bits=args.lo_bits)
        print(f"[serve] expert-sharded checkpoint -> {args.save_shards}")
        return
    obs = None
    if not args.no_obs:
        from repro.obs import Observability, ObsConfig
        obs = Observability(ObsConfig(metrics_jsonl=args.metrics_jsonl))
    engine = InferenceEngine(cfg, params, build_backend(args), ecfg,
                             dist=dist, obs=obs)
    toks = make_prompts(args.workload, cfg.vocab_size,
                        args.batch, args.prompt_len)
    use_sampling = (args.temperature > 0 or args.top_k is not None or
                    args.top_p < 1.0)
    t0 = time.perf_counter()
    handles = [engine.submit(Request(
        tokens=toks[b], max_new_tokens=args.new_tokens,
        sampling=SamplingParams(temperature=args.temperature,
                                top_k=args.top_k, top_p=args.top_p,
                                seed=args.seed + b)
        if use_sampling else None))
               for b in range(args.batch)]
    engine.drain()
    engine.flush()
    wall = time.perf_counter() - t0
    tput = sum(len(h.tokens) for h in handles) / wall
    st = engine.stats()
    print(f"[serve] TTFT {st['ttft_s']*1e3:.1f} ms  TPOT "
          f"{st['tpot_s']*1e3:.1f} ms  throughput {tput:.2f} tok/s")
    print(f"[serve] moe dispatch={engine.moe_dispatch}: "
          f"active_experts {st.get('active_experts', 0.0):.1f}"
          f"/{cfg.moe.num_experts if cfg.is_moe else 0}  "
          f"pad_ratio {st.get('dispatch_pad_ratio', 0.0):.2f}")
    if ecfg.spec_k:
        row_rounds = max(1.0, st.get("spec_row_rounds", 0.0))
        print(f"[serve] spec: accept_rate {st['accept_rate']:.2f}  "
              f"tokens/row-round {st['verified_tokens']/row_rounds:.2f} "
              f"(1.0 = no speculation; {st['draft_tokens']:.0f} drafted "
              f"over {st['spec_rounds']:.0f} rounds)")
    print(f"[serve] resident expert bytes: {engine.device_bytes():,}")
    if obs is None:
        # No obs layer: fall back to the raw uniform stats dump.
        print(f"[serve] uniform stats: "
              f"{ {k: round(float(v), 4) for k, v in st.items()} }")
    else:
        summ = obs.summary()
        roof, prom = summ["roofline"], summ["promotions"]
        resid = max((abs(b["rel_residual"]) for b in roof["buckets"]),
                    default=0.0)
        stall = sum(h.stall_exposure_s for h in handles)
        print(f"[serve] obs: {summ['trace_events']} events "
              f"({summ['trace_dropped']} dropped)  "
              f"promotions {prom['n_published']} published / "
              f"{prom['n_cancelled']} cancelled "
              f"publish p95 {prom['publish_latency_p95_s']*1e3:.1f} ms  "
              f"bytes/token residual max {resid:.3f} "
              f"over {roof['n_steps']} decode steps  "
              f"stall exposure {stall*1e3:.1f} ms  "
              f"shed {st.get('shed_requests', 0.0):.0f}")
        if args.trace_out:
            obs.save_trace(args.trace_out)
            print(f"[serve] trace -> {args.trace_out}")
        obs.close()
    if args.hotness_path and hasattr(engine.backend, "save_hotness"):
        engine.backend.save_hotness()
        print(f"[serve] hotness snapshot -> {args.hotness_path}_p*.npz")


if __name__ == "__main__":
    main()
