"""What no architecture owns: configuration files, the seeded weight maker,
the norm and rotary helpers every reference uses, and the chip's peaks
with the roofline's least time.

Nothing here imports the program. Peaks come only from ``peaks.json`` in
``bench/``, keyed by the ``device_kind`` JAX reports; a device that is not
in the table is an error.
"""
from __future__ import annotations

import functools
import json
from pathlib import Path
from typing import Dict, List, Tuple

import numpy as np

BENCH_DIR = Path(__file__).resolve().parent.parent
CONFIG_DIR = BENCH_DIR / "configs"
PEAKS_FILE = BENCH_DIR / "peaks.json"


def load_config(name: str, directory: Path = CONFIG_DIR) -> Dict:
    path = directory / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no configuration {name!r} at {path}")
    return json.loads(path.read_text())


# ---------------------------------------------------------------------------
# Seeded weights
# ---------------------------------------------------------------------------

def seed_key(seed: int):
    """A PRNG key from any non-negative integer seed (wider than 32 bits
    included). The ``rbg`` generator draws on the chip's own random-bit
    unit, which makes a billion weights in a fraction of the time threefry
    takes; it is deterministic for a key on a given backend, and the
    reference remakes the weights on the same device as the program."""
    import jax
    s = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.fold_in(
        jax.random.key(int(s[0]) & 0x7FFFFFFF, impl="rbg"),
        int(s[1]) & 0x7FFFFFFF)


def _nest(flat: Dict[Tuple[str, ...], object]) -> Dict:
    out: Dict = {}
    for path, v in flat.items():
        node = out
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = v
    return out


@functools.lru_cache(maxsize=None)
def _maker(spec_key):
    import jax
    import jax.numpy as jnp
    spec = json.loads(spec_key)

    @jax.jit
    def make(key):
        flat = {}
        for i, (path, shape, dtype, scale) in enumerate(spec):
            dt = jnp.dtype(dtype)
            if scale == 0.0:
                flat[tuple(path)] = jnp.ones(shape, dt)
            else:
                k = jax.random.fold_in(key, i)
                flat[tuple(path)] = (jax.random.normal(k, shape, jnp.float32)
                                     * scale).astype(dt)
        return _nest(flat)
    return make


def make_weights(spec: List[Tuple[Tuple[str, ...], tuple, str, float]],
                 seed: int) -> Dict:
    """Every weight of ``spec`` ((path, shape, dtype, init scale), scale 0
    for ones), made on the default device in one jitted call from the
    seed, in the dtype it is served in. The i-th weight draws from the
    seed's key folded with i."""
    spec = [(list(p), list(s), t, c) for p, s, t, c in spec]
    return _maker(json.dumps(spec))(seed_key(seed))


# ---------------------------------------------------------------------------
# Reference helpers
# ---------------------------------------------------------------------------

def rmsnorm(x, scale, eps):
    import jax.numpy as jnp
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) \
        * scale


def rope(x, theta):
    """x: (B, S, H, hd), positions 0..S-1, split-halves rotation."""
    import jax.numpy as jnp
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


# ---------------------------------------------------------------------------
# Peaks and the roofline
# ---------------------------------------------------------------------------

def load_peaks(device_kind: str, path: Path = PEAKS_FILE) -> Dict[str, float]:
    table = json.loads(path.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path} "
                       f"(known: {sorted(table)})")
    return table[device_kind]


def least_seconds(work: Dict[str, float], peaks: Dict[str, float]) -> Dict:
    """The roofline's least time for some work, and which bound sets it."""
    t_c = work["flops"] / peaks["bf16_flops"]
    t_m = work["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_c, t_m),
            "bound": "compute" if t_c >= t_m else "memory"}
