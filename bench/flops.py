"""Operations, bytes and peaks: the arithmetic behind the roofline and
utilization metrics, kept with the benchmark so that every PR counts them
the same way.

Peaks come only from ``peaks.json`` beside this module, keyed by the
``device_kind`` JAX reports; a device that is not in the table is an error.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Iterable

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def load_peaks(device_kind: str, path: Path = PEAKS_FILE) -> Dict[str, float]:
    table = json.loads(path.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path} "
                       f"(known: {sorted(table)})")
    return table[device_kind]


# ---------------------------------------------------------------------------
# The ragged MoE FFN (gate/up kernel, then down kernel)
# ---------------------------------------------------------------------------

def expert_matrices(d: Dict):
    """(K, N) of the three matrices of one expert: gate, up, down."""
    D, F = d["d_model"], d["d_ff_expert"]
    return ((D, F), (D, F), (F, D))


def expert_hi_bytes(d: Dict) -> int:
    """Bytes of one expert of one layer in bf16."""
    return sum(2 * k * n for k, n in expert_matrices(d))


def expert_lo_bytes(d: Dict, bits: int, group: int) -> int:
    """Bytes of one expert of one layer at ``bits``-bit codes with one bf16
    scale per ``group`` rows of K."""
    return sum(k * n * bits // 8 + (k // group) * n * 2
               for k, n in expert_matrices(d))


def moe_ffn_work(d: Dict, forwards: Iterable[Dict], lo_bits: int,
                 group: int) -> Dict[str, float]:
    """Least FLOPs and bytes of the ragged expert FFN over some forwards.

    Each forward is a ``moe_forward`` record: ``routed`` token-expert
    assignments and ``active_hi``/``active_lo`` (layer, expert) cells that
    received tokens, summed over layers. Every active expert's weights are
    read once in the tier it is served from; every assignment's
    activations are read and written once at bf16 (x in, h out and back
    in, y out); every assignment costs the three matmuls of its expert."""
    D, F = d["d_model"], d["d_ff_expert"]
    hi_b, lo_b = expert_hi_bytes(d), expert_lo_bytes(d, lo_bits, group)
    act_b = 2 * (2 * D + 2 * F)
    flops = nbytes = 0.0
    for f in forwards:
        flops += f["routed"] * 2.0 * (2 * D * F + F * D)
        nbytes += (f["active_hi"] * hi_b + f["active_lo"] * lo_b
                   + f["routed"] * act_b)
    return {"flops": flops, "bytes": nbytes}


def least_seconds(work: Dict[str, float], peaks: Dict[str, float]) -> Dict:
    """The roofline's least time for some work, and which bound sets it."""
    t_c = work["flops"] / peaks["bf16_flops"]
    t_m = work["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_c, t_m),
            "bound": "compute" if t_c >= t_m else "memory"}


# ---------------------------------------------------------------------------
# Whole-model FLOPs per token (for model_mfu)
# ---------------------------------------------------------------------------

def flops_per_token(d: Dict, ctx: float) -> float:
    """Forward FLOPs of one token that attends to ``ctx`` positions
    (itself included): attention projections and scores, router, the
    top-k experts, and the LM head. Norms and softmaxes are left out."""
    D, H, Hkv, hd = d["d_model"], d["n_heads"], d["n_kv_heads"], d["head_dim"]
    E, k, F = d["num_experts"], d["top_k"], d["d_ff_expert"]
    proj = 2 * (D * H * hd + 2 * D * Hkv * hd + H * hd * D)
    scores = 2 * 2 * ctx * H * hd
    router = 2 * D * E
    experts = k * 2 * 3 * D * F
    return d["n_layers"] * (proj + scores + router + experts) \
        + 2 * D * d["vocab_size"]


def prompt_flops(d: Dict, start: int, end: int) -> float:
    """FLOPs of prefilling positions [start, end) of one sequence."""
    n = end - start
    # Position p attends to p + 1 positions; sum over the span.
    ctx_sum = (start + 1 + end) * n / 2.0
    return n * flops_per_token(d, 0) + (flops_per_token(d, 1)
                                        - flops_per_token(d, 0)) * ctx_sum
