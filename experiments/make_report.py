"""Generate EXPERIMENTS.md §Dry-run and §Roofline tables from the JSONL
records (last record wins per (arch, shape, mesh))."""
import json
import sys


def load(path):
    recs = {}
    try:
        for line in open(path):
            r = json.loads(line)
            recs[(r["arch"], r["shape"], r["mesh"])] = r
    except FileNotFoundError:
        pass
    return recs


def fmt_roofline(recs):
    head = ("| arch | shape | t_comp (s) | t_mem (s) | t_coll (s) | bottleneck "
            "| HBM/dev (GB) | fits 16GB | useful FLOPs | note |\n"
            "|---|---|---|---|---|---|---|---|---|---|")
    rows = [head]
    order = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]
    archs = sorted({a for a, _, _ in recs})
    for a in archs:
        for s in order:
            r = recs.get((a, s, "16x16"))
            if r is None:
                continue
            if "skipped" in r:
                rows.append(f"| {a} | {s} | — | — | — | — | — | — | — | "
                            f"SKIP: {r['skipped']} |")
                continue
            if "error" in r:
                rows.append(f"| {a} | {s} | — | — | — | — | — | — | — | "
                            f"ERROR: {r['error'][:60]} |")
                continue
            rl = r["roofline"]
            note = _move_note(rl)
            rows.append(
                f"| {a} | {s} | {rl['t_compute_s']:.4g} | "
                f"{rl['t_memory_s']:.4g} | {rl['t_collective_s']:.4g} | "
                f"**{rl['bottleneck']}** | {r.get('per_device_hbm_gb', '?')} | "
                f"{'yes' if r.get('fits_16gb_hbm') else 'NO'} | "
                f"{rl['useful_flops_ratio']:.3f} | {note} |")
    return "\n".join(rows)


def _move_note(rl):
    b = rl["bottleneck"]
    if b == "memory":
        return "reduce bytes/step (see per-pair analysis, §Perf)"
    if b == "collective":
        return "reduce collective volume (see per-pair analysis, §Perf)"
    return "increase per-chip work (larger per-device batch)"


def fmt_dryrun(recs, mesh):
    rows = [f"| arch | shape | kind | compile (s) | HBM/dev (GB) | collectives (MB/chip) |",
            "|---|---|---|---|---|---|"]
    order = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]
    for a in sorted({a for a, _, _ in recs}):
        for s in order:
            r = recs.get((a, s, mesh))
            if r is None:
                continue
            if "skipped" in r:
                rows.append(f"| {a} | {s} | — | — | — | SKIP ({r['skipped']}) |")
                continue
            if "error" in r:
                rows.append(f"| {a} | {s} | — | — | — | ERROR |")
                continue
            coll = sum(r.get("collectives", {}).values()) / 1e6
            rows.append(f"| {a} | {s} | {r['kind']} | {r['compile_s']} | "
                        f"{r.get('per_device_hbm_gb', '?')} | {coll:.1f} |")
    return "\n".join(rows)


def fmt_obs(path="experiments/BENCH_obs.json"):
    """§Observability table from the obs_overhead benchmark artifact:
    the tax of the obs layer and promotion publish-latency percentiles."""
    try:
        with open(path) as f:
            obs = json.load(f)["obs"]
    except (FileNotFoundError, KeyError):
        return None
    prom = obs["promotions"]
    rows = [
        "### Observability tax + promotion latency",
        "",
        f"| tok/s (obs off) | tok/s (obs on) | overhead | trace events |",
        "|---|---|---|---|",
        f"| {obs['tokens_per_s_off']:.1f} | {obs['tokens_per_s_on']:.1f} | "
        f"{obs['overhead_frac']*100:+.1f}% (budget "
        f"{obs['max_overhead_frac']*100:.0f}%) | {obs['trace_events']} |",
        "",
        f"Promotions: {prom['n_published']} published, "
        f"{prom['n_cancelled']} cancelled; publish latency p50 "
        f"{prom['publish_latency_p50_s']*1e3:.1f} ms, p95 "
        f"{prom['publish_latency_p95_s']*1e3:.1f} ms, max "
        f"{prom['publish_latency_max_s']*1e3:.1f} ms.",
    ]
    return "\n".join(rows)


if __name__ == "__main__":
    single = load("experiments/dryrun_single.jsonl")
    multi = load("experiments/dryrun_multi.jsonl")
    opt = load("experiments/dryrun_single_opt.jsonl")
    if opt:
        with open("experiments/roofline_table_optimized.md", "w") as f:
            f.write(fmt_roofline(opt))
    n_ok_s = sum(1 for r in single.values() if "roofline" in r)
    n_ok_m = sum(1 for r in multi.values() if "roofline" in r)
    print(f"single-pod OK: {n_ok_s}, multi-pod OK: {n_ok_m}")
    with open("experiments/roofline_table.md", "w") as f:
        f.write(fmt_roofline(single))
    with open("experiments/dryrun_single_table.md", "w") as f:
        f.write(fmt_dryrun(single, "16x16"))
    with open("experiments/dryrun_multi_table.md", "w") as f:
        f.write(fmt_dryrun(multi, "2x16x16"))
    obs_md = fmt_obs()
    if obs_md is not None:
        with open("experiments/obs_table.md", "w") as f:
            f.write(obs_md + "\n")
    print("tables written to experiments/*.md")
