"""Roofline analysis from compiled dry-run artifacts.

Three terms per (arch × shape × mesh), all in seconds-per-step per chip:

    compute    = HLO_FLOPs / (chips × peak)          peak = 197 TFLOP/s bf16
    memory     = HLO_bytes / (chips × hbm_bw)        hbm  = 819 GB/s
    collective = collective_bytes / (chips × links)  link = 50 GB/s/link ICI

FLOPs/bytes come from ``compiled.cost_analysis()``. Collective bytes are NOT
in cost_analysis — we parse the optimized HLO text and sum the result-shape
bytes of every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute op (a per-chip measure, since post-SPMD HLO shapes are
per-device).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict

PEAK_FLOPS = 197e12         # bf16 per chip, TPU v5e
HBM_BW = 819e9              # bytes/s per chip
ICI_BW = 50e9               # bytes/s per link (~1 effective link per axis)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "tuple": 0, "token": 0,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g. "bf16[16,2048,768]{2,1,0} all-gather(...)"  (also matches tuple elems)
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    b = _DTYPE_BYTES.get(dtype)
    if b is None:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * b


_CONVERT_RE = re.compile(r"= (\w+)\[([\d,]*)\]\S* convert\(")

# When a convert is elided (fused on TPU), we also save reading its input:
# input bytes = out_elems × src_size; src inferred from the usual CPU
# legalization pairs (bf16→f32 for dots, f32→bf16 results).
_CONVERT_SRC_BYTES = {"f32": 2, "bf16": 4, "u32": 1, "s32": 1, "s8": 1}


_COMP_HEADER_RE = re.compile(r"^(%?[\w.\-]+)\s*(?:\([^)]*\))?\s*.*->.*\{\s*$")


def convert_bytes(hlo_text: str) -> int:
    """Bytes attributable to TOP-LEVEL dtype converts in the optimized HLO
    (converts inside fusion bodies never touch HBM and are skipped).

    XLA:CPU legalizes bf16 dots by upcasting operands to f32 and the SPMD
    partitioner's masked fallbacks run in f32 — on TPU these are native (MXU
    bf16 inputs) or fused. Subtracting convert traffic gives the
    TPU-faithful memory term; both raw and adjusted values are reported."""
    total = 0
    in_fusion = False
    for line in hlo_text.splitlines():
        h = _COMP_HEADER_RE.match(line.strip())
        if h and line.rstrip().endswith("{"):
            in_fusion = "fused" in h.group(1)
            continue
        if in_fusion:
            continue
        m = _CONVERT_RE.search(line)
        if not m:
            continue
        dt, dims = m.group(1), m.group(2)
        out_b = _shape_bytes(dt, dims)
        if not out_b:
            continue
        elems = out_b // max(_DTYPE_BYTES.get(dt, 1), 1)
        total += out_b + elems * _CONVERT_SRC_BYTES.get(dt, 0)
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum result bytes per collective kind from optimized HLO text."""
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        # result shape appears before "opname(", e.g. "%x = bf16[..] all-gather("
        for kind in _COLLECTIVES:
            if f" {kind}(" in s or s.startswith(f"{kind}("):
                if f"%{kind}" in s or f"= {kind}" in s or f" {kind}(" in s:
                    lhs = s.split(f" {kind}(")[0]
                    total = sum(_shape_bytes(m.group(1), m.group(2))
                                for m in _SHAPE_RE.finditer(lhs))
                    out[kind] += total
                    counts[kind] += 1
                break
    out["_counts"] = counts
    return out


@dataclasses.dataclass
class Roofline:
    flops: float                # per-chip HLO flops
    hbm_bytes: float            # per-chip bytes accessed
    coll_bytes: float           # per-chip collective bytes
    coll_detail: Dict[str, int]
    chips: int
    model_flops: float          # 6·N·D (train) or 2·N_active·D (inference)

    @property
    def t_compute(self) -> float:
        return self.flops / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / ICI_BW

    @property
    def bottleneck(self) -> str:
        t = {"compute": self.t_compute, "memory": self.t_memory,
             "collective": self.t_collective}
        return max(t, key=t.get)

    @property
    def useful_flops_ratio(self) -> float:
        total = self.flops * self.chips
        return self.model_flops / total if total else 0.0

    def row(self) -> dict:
        return {
            "t_compute_s": round(self.t_compute, 6),
            "t_memory_s": round(self.t_memory, 6),
            "t_collective_s": round(self.t_collective, 6),
            "bottleneck": self.bottleneck,
            "hlo_gflops_per_chip": round(self.flops / 1e9, 2),
            "hbm_gb_per_chip": round(self.hbm_bytes / 1e9, 3),
            "coll_mb_per_chip": round(self.coll_bytes / 1e6, 3),
            "model_gflops_total": round(self.model_flops / 1e9, 2),
            "useful_flops_ratio": round(self.useful_flops_ratio, 4),
        }


def model_flops(cfg, kind: str, tokens: int) -> float:
    """6·N·D for training, 2·N_active·D for inference forward."""
    n_active = cfg.active_param_count()
    n_total = cfg.param_count()
    if kind == "train":
        return 6.0 * n_active * tokens
    return 2.0 * n_active * tokens


def analyze(compiled, hlo_text: str, cfg, kind: str, tokens: int,
            chips: int) -> Roofline:
    ca = compiled.cost_analysis()
    if isinstance(ca, list):   # older API returned [dict]
        ca = ca[0]
    flops = float(ca.get("flops", 0.0))
    # bytes accessed: sum the explicit operand/output accounting if present.
    hbm = float(ca.get("bytes accessed", 0.0))
    coll = collective_bytes(hlo_text)
    detail = {k: v for k, v in coll.items() if k != "_counts"}
    total_coll = float(sum(detail.values()))
    return Roofline(flops=flops, hbm_bytes=hbm, coll_bytes=total_coll,
                    coll_detail=coll, chips=chips,
                    model_flops=model_flops(cfg, kind, tokens))
