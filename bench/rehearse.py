"""CPU rehearsal of a benchmark run, for the tests only.

It drives the harness's own functions end to end at a tiny size: a small
granite-shaped configuration, a short schedule, and the Pallas kernels in
interpret mode. ``bench/run.py`` itself still refuses any device but a
TPU; numbers from here are never device metrics.
"""
from __future__ import annotations

import copy
import os
import time
from typing import Dict

TINY_CONF: Dict = {
    "name": "tiny-moe",
    "source": "tiny granite-shaped rehearsal configuration",
    "hidden_size": 128, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_local_experts": 8, "num_experts_per_tok": 2, "vocab_size": 512,
    "rms_norm_eps": 1e-6, "rope_theta": 10000, "tie_word_embeddings": True,
    "reduced": [],
    "arch_module": "moe_gqa",
    "arch": {
        "n_layers": "num_hidden_layers", "d_model": "hidden_size",
        "vocab_size": "vocab_size", "n_heads": "num_attention_heads",
        "n_kv_heads": "num_key_value_heads", "head_dim": 32,
        "num_experts": "num_local_experts", "top_k": "num_experts_per_tok",
        "d_ff_expert": "intermediate_size", "norm_eps": "rms_norm_eps",
        "rope_theta": "rope_theta", "tie_embeddings": "tie_word_embeddings",
        "qk_norm": False, "norm_topk_prob": True,
        "attn_scale": 32 ** -0.5},
    "serving": {
        "program_arch": "granite-moe-1b-a400m",
        "serve_args": ["--backend", "dynaexq", "--batch", "4",
                       "--prompt-len", "64", "--new-tokens", "8",
                       "--lo-bits", "4", "--n-hi", "2", "--no-spec",
                       "--moe-dispatch", "ragged", "--block-tokens", "16"],
        "capacity_factor": "drop_free"},
}

TINY_MIX: Dict = {
    "arrivals": "poisson", "rate_rps": 4.0,
    "prompt_len": {"dist": "lognormal", "median": 24, "sigma": 0.6,
                   "min": 8, "max": 64},
    "output_len": {"dist": "lognormal", "median": 6, "sigma": 0.3,
                   "min": 4, "max": 8},
    "phases": ["text", "math", "code"], "phase_seconds": 1.0,
    "lead_in_s": 0.5, "zipf_s": 1.2, "order_seed": 1,
}


#: The tiny cell's limit on the mean logit gap and its control, the
#: program with an int2 lo tier. Readings on the CPU (interpret-mode
#: kernels, seeds 31-36): the program 0.0028-0.0210, the control
#: 0.143-0.209. At this size the widest gap does not separate them
#: (0.071-0.434 against 0.489-0.892).
TINY_LIMITS: Dict = {"mean_logit_gap": {"limit": 0.08},
                     "control": {"serve_args": ["--lo-bits", "2"]}}


def tiny_cell():
    """A Cell over the tiny configuration, with every metric the
    benchmark declares."""
    import json
    from bench import arch, harness
    spec = json.loads((harness.CHECKOUT / "BENCHMARK.json").read_text())
    checks = copy.deepcopy(TINY_LIMITS)
    return harness.Cell(
        name="tiny", chips=1, conf=copy.deepcopy(TINY_CONF),
        mix=copy.deepcopy(TINY_MIX),
        end_to_end=spec["end_to_end"], per_layer=spec["per_layer"],
        limits={k: v for k, v in checks.items() if "limit" in v},
        arch=arch.load(TINY_CONF), control=checks["control"])


def rehearse(seed: int, seconds: float, trace: bool = False,
             control: bool = False) -> Dict:
    """One run of the tiny cell on this CPU (``control``: with the
    control's flags). Peaks are the v5e row: the arithmetic runs, the
    numbers mean nothing."""
    from bench import harness
    from bench.arch import common
    os.environ["REPRO_MOE_GEMM"] = "pallas"      # kernels in interpret mode
    real = common.load_peaks
    common.load_peaks = lambda kind, *a, **k: real("TPU v5 lite")
    cell = tiny_cell()
    if control:
        cell = harness.with_serve_args(cell, cell.control["serve_args"])
    try:
        return harness.run_cell(cell, seed, seconds, trace,
                                time.perf_counter())
    finally:
        common.load_peaks = real
