"""Open-loop request schedule for one traffic mix, drawn from a seed.

A mix is a JSON file beside this module (``<mix>.json``) holding only
parameters; this one generator reads every mix. The token content follows
the program's own shifting-mix scheme (``repro.serving.requests``, copied
here so the yardstick does not move with the program): each workload draws
Zipf-distributed tokens over its own third of the vocabulary, so the router
sees a different hot expert set per workload.

Every seed gets the same amount of work: the multiset of prompt lengths,
output lengths and inter-arrival gaps is fixed by the mix (quantiles of its
distributions) and ordered by the mix's own ``order_seed``, so every run
serves the same arrivals; the run's seed draws the tokens (and, in the
harness, the weights).

Arrival times are seconds relative to the start of the measured window.
Requests due before 0 are the lead-in: traffic that brings the engine to
its steady state during set-up and is not measured.
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from typing import Dict, List

import numpy as np

MIX_DIR = Path(__file__).resolve().parent
WORKLOADS = ("text", "math", "code")
#: Fixed permutation of the vocabulary that splits it into per-workload
#: slices (the same constant the program's generator uses).
_VOCAB_PERM_SEED = 42


@dataclasses.dataclass
class Req:
    due_s: float            # seconds from the window's start (< 0: lead-in)
    workload: str
    tokens: np.ndarray      # (prompt_len,) int32
    max_new_tokens: int

    @property
    def prompt_len(self) -> int:
        return int(self.tokens.shape[0])


def load_mix(name: str, directory: Path = MIX_DIR) -> Dict:
    path = directory / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    mix = json.loads(path.read_text())
    validate(mix)
    return mix


def validate(mix: Dict) -> None:
    for key in ("rate_rps", "prompt_len", "output_len", "phases",
                "phase_seconds", "lead_in_s", "zipf_s", "order_seed"):
        if key not in mix:
            raise ValueError(f"traffic mix lacks {key!r}")
    for key in ("prompt_len", "output_len"):
        d = mix[key]
        if d.get("dist") != "lognormal":
            raise ValueError(f"{key}: only lognormal lengths are supported")
        if not 1 <= d["min"] <= d["median"] <= d["max"]:
            raise ValueError(f"{key}: need 1 <= min <= median <= max")
    unknown = set(mix["phases"]) - set(WORKLOADS)
    if unknown or not mix["phases"]:
        raise ValueError(f"phases must be drawn from {WORKLOADS}")
    if mix["rate_rps"] <= 0 or mix["phase_seconds"] <= 0:
        raise ValueError("rate_rps and phase_seconds must be positive")


def _quantiles_lognormal(d: Dict, n: int) -> np.ndarray:
    """n lengths at the mid-quantiles of a clipped lognormal."""
    from statistics import NormalDist
    z = np.asarray([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = d["median"] * np.exp(d["sigma"] * z)
    return np.clip(np.round(x), d["min"], d["max"]).astype(np.int64)


def _exp_gaps(n: int, total_s: float) -> np.ndarray:
    """n inter-arrival gaps at the mid-quantiles of an exponential, scaled
    so that they sum to ``total_s`` exactly."""
    q = (np.arange(n) + 0.5) / n
    g = -np.log1p(-q)
    return g * (total_s / g.sum())


def phase_of(t: float, mix: Dict) -> str:
    phases = mix["phases"]
    return phases[int(math.floor(t / mix["phase_seconds"])) % len(phases)]


def _zipf_probs(n: int, s: float) -> np.ndarray:
    p = 1.0 / np.arange(1, n + 1) ** s
    return p / p.sum()


class TokenSource:
    """Zipf tokens over a per-workload third of the vocabulary."""

    def __init__(self, vocab_size: int, zipf_s: float):
        perm = np.random.default_rng(_VOCAB_PERM_SEED).permutation(vocab_size)
        self.slices = {}
        for wi, w in enumerate(WORKLOADS):
            ids = perm[wi * vocab_size // 3:(wi + 1) * vocab_size // 3]
            self.slices[w] = (ids, np.cumsum(_zipf_probs(len(ids), zipf_s)))

    def draw(self, rng: np.random.Generator, workload: str,
             n: int) -> np.ndarray:
        ids, cdf = self.slices[workload]
        idx = np.searchsorted(cdf, rng.random(n) * cdf[-1], side="right")
        return ids[np.minimum(idx, len(ids) - 1)].astype(np.int32)


def _span(mix: Dict, start_s: float, length_s: float, order, rng,
          src: TokenSource, rate: float) -> List[Req]:
    """Requests due in [start_s, start_s + length_s): a fixed count and a
    fixed multiset of lengths and gaps, ordered by ``order``, with tokens
    drawn from ``rng``."""
    n = max(1, int(round(rate * length_s)))
    prompts = order.permutation(_quantiles_lognormal(mix["prompt_len"], n))
    outputs = order.permutation(_quantiles_lognormal(mix["output_len"], n))
    gaps = order.permutation(_exp_gaps(n, length_s))
    # The first request is due at the span's start; the gap after the last
    # one closes the span.
    due = start_s + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    reqs = []
    for t, p, o in zip(due, prompts, outputs):
        w = phase_of(float(t), mix)
        reqs.append(Req(float(t), w, src.draw(rng, w, int(p)), int(o)))
    return reqs


def schedule(mix: Dict, vocab_size: int, seed: int, window_s: float,
             rate_rps: float | None = None) -> List[Req]:
    """The whole schedule of one run, sorted by due time: the lead-in
    (due in [-lead_in_s, 0)) and the measured window (due in
    [0, window_s)). ``rate_rps`` overrides the mix's rate (rate sweeps)."""
    validate(mix)
    rate = float(rate_rps if rate_rps is not None else mix["rate_rps"])
    rng = np.random.default_rng(np.random.SeedSequence(int(seed)))
    order = np.random.default_rng(np.random.SeedSequence(
        int(mix["order_seed"])))
    src = TokenSource(vocab_size, mix["zipf_s"])
    lead = float(mix["lead_in_s"])
    reqs = _span(mix, -lead, lead, order, rng, src, rate) if lead > 0 else []
    reqs += _span(mix, 0.0, float(window_s), order, rng, src, rate)
    return reqs


def describe(reqs: List[Req]) -> Dict:
    """Counts per phase and the spread of lengths (for the log lines)."""
    win = [r for r in reqs if r.due_s >= 0]
    out: Dict = {"requests": len(win), "lead_in": len(reqs) - len(win)}
    for w in WORKLOADS:
        k = sum(r.workload == w for r in win)
        if k:
            out[f"phase_{w}"] = k
    for name, vals in (("prompt", [r.prompt_len for r in win]),
                       ("output", [r.max_new_tokens for r in win])):
        if vals:
            q = np.percentile(vals, [0, 50, 95, 100])
            out[name] = {"min": int(q[0]), "p50": float(q[1]),
                         "p95": float(q[2]), "max": int(q[3]),
                         "sum": int(sum(vals))}
    return out
