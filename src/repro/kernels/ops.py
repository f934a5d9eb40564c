"""Jitted public wrappers for the Pallas kernels + the serving-path
dispatchers that pick an execution backend per environment.

On TPU the kernels compile natively (Mosaic) and never fall back: a shape
the kernel cannot tile raises, and no path swaps in the ``ref.py`` oracle
behind the caller's back. Everywhere else (CPU tests) they execute in
``interpret=True`` mode, which runs the kernel body in Python for
correctness validation against ``ref.py``.

Two dispatch axes for the MoE decode hot path, each overridable by env:

* ``REPRO_MOE_GEMM``      ∈ {auto, jnp, pallas} — how quantized expert
  GEMMs execute. ``auto``: native Pallas on TPU, the (bit-identical) jnp
  group-blocked expression on CPU. ``pallas`` off-TPU runs the kernels in
  interpret mode (slow; used by CI to exercise the kernel code paths).
* ``REPRO_MOE_DISPATCH``  ∈ {auto, padded, ragged} — token dispatch layout.
  ``padded``: the fixed-capacity (E, C, d) scatter + grouped GEMM over ALL
  experts (the reference path). ``ragged``: sorted, bm-aligned compacted
  activations + active-expert tile maps — only experts that received
  tokens stream their weights (see ``moe._dispatch_ragged``). ``auto``:
  ragged on TPU, padded on CPU.

The dispatch layout is resolved ONCE at engine construction
(``EngineConfig.moe_dispatch``) and threaded as a static jit argument, so a
changed env var cannot disagree with an already-compiled executable. The
GEMM backend is read at trace time per compilation: changing
``REPRO_MOE_GEMM`` mid-process only affects shapes traced afterwards —
callers that need a pinned backend pass ``backend=``/``gemm=`` explicitly.
"""
from __future__ import annotations

import math
import os
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.quant_matmul import (grouped_quant_matmul, quant_matmul,
                                        ragged_quant_ffn)
from repro.kernels.flash_decode import flash_decode, flash_decode_paged
from repro.quant.qtensor import QuantizedTensor


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def moe_gemm_backend() -> str:
    """Resolved quantized-GEMM backend: 'jnp' or 'pallas'."""
    v = os.environ.get("REPRO_MOE_GEMM", "auto")
    if v not in ("auto", "jnp", "pallas"):
        raise ValueError(f"REPRO_MOE_GEMM={v!r}; one of auto|jnp|pallas")
    if v == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    return v


def moe_dispatch_default() -> str:
    """Resolved MoE dispatch layout: 'padded' or 'ragged'."""
    v = os.environ.get("REPRO_MOE_DISPATCH", "auto")
    if v not in ("auto", "padded", "ragged"):
        raise ValueError(
            f"REPRO_MOE_DISPATCH={v!r}; one of auto|padded|ragged")
    if v == "auto":
        return "ragged" if jax.default_backend() == "tpu" else "padded"
    return v


def grouped_lo_matmul(xg: jax.Array, packed: jax.Array, scales: jax.Array,
                      bits: int, group: int, *,
                      backend: str | None = None) -> jax.Array:
    """THE grouped lo-tier GEMM of the padded MoE path: xg (B, C, K) ×
    packed (B, K//epb, N) → (B, C, N). One dispatcher over the two
    re-expressions of the same group-blocked math — ``ref``'s jnp einsum
    chain and the Pallas kernel (interpret-mode off TPU) — which a parity
    test holds bit-identical."""
    be = backend if backend is not None else moe_gemm_backend()
    if be == "jnp":
        return _ref.grouped_lo_gemm_jnp(xg, packed, scales, bits, group)
    return grouped_quant_matmul(xg, packed, scales, bits=bits, group=group,
                                interpret=_interpret_default())


def _hold_last(vals: jax.Array) -> jax.Array:
    """Forward-fill negatives with the last non-negative value (and clip
    the leading run to 0): turns a sparse index sequence into a DMA hold
    map — repeated block indices make Pallas skip the refetch."""
    filled = jax.lax.associative_scan(
        lambda a, b: jnp.where(b < 0, a, b), vals)
    return jnp.maximum(filled, 0).astype(jnp.int32)


def ragged_quant_ffn_op(xs: jax.Array, tile_eid: jax.Array,
                        tile_slot: jax.Array, lo: dict, hi,
                        *, bits: int, group: int, bm: int,
                        backend: str | None = None) -> jax.Array:
    """Ragged mixed-precision expert FFN dispatcher. ``xs``: (Tt·bm, K)
    compacted activations; ``tile_eid``/``tile_slot``: (Tt,) per-tile
    expert id and hi-pool slot (−1 ⇒ lo). ``lo``: name → arrays with
    ``.packed``/``.scales`` (QuantizedTensor or shard-local view); ``hi``:
    name → (n_hi, K, N) bf16 or None. Returns (Tt·bm, D)."""
    be = backend if backend is not None else moe_gemm_backend()
    n_hi = 0 if hi is None else hi["w_gate"].shape[0]
    if be == "jnp":
        return _ref.ragged_quant_ffn_ref(
            xs, tile_eid, tile_slot,
            lo["w_gate"].packed, lo["w_gate"].scales,
            lo["w_up"].packed, lo["w_up"].scales,
            lo["w_down"].packed, lo["w_down"].scales,
            None if n_hi == 0 else hi["w_gate"],
            None if n_hi == 0 else hi["w_up"],
            None if n_hi == 0 else hi["w_down"],
            bits=bits, group=group, bm=bm)
    is_hi = (tile_slot >= 0) & (n_hi > 0)
    # DMA hold maps: the tier a tile does NOT compute with re-addresses the
    # previous tile's block, so only the resident tier streams per tile.
    tile_lo = _hold_last(jnp.where(is_hi, -1, tile_eid))
    tile_hi = _hold_last(jnp.where(is_hi, tile_slot, -1))
    return ragged_quant_ffn(
        xs, tile_lo, tile_hi, is_hi.astype(jnp.int32),
        lo["w_gate"].packed, lo["w_gate"].scales,
        lo["w_up"].packed, lo["w_up"].scales,
        lo["w_down"].packed, lo["w_down"].scales,
        None if n_hi == 0 else hi["w_gate"],
        None if n_hi == 0 else hi["w_up"],
        None if n_hi == 0 else hi["w_down"],
        bits=bits, group=group, bm=bm,
        interpret=_interpret_default())


def ragged_dense_ffn_op(xs: jax.Array, tile_eid: jax.Array, bank: dict,
                        *, bm: int, backend: str | None = None) -> jax.Array:
    """Ragged DENSE expert FFN dispatcher (fp16/offload banks — no
    quantized tier to fall back on, so inactive experts are skipped by the
    tile map alone). ``bank``: {'w_gate','w_up','w_down'} → (E, K, N).
    The Pallas backend reuses the fused mixed-precision kernel in all-hi
    mode — every tile reads its expert's dense weights through the hi-pool
    operand while a placeholder lo tier holds one zero expert and is never
    streamed (the per-tile DMA hold maps pin it to block 0).
    Returns (Tt·bm, D)."""
    be = backend if backend is not None else moe_gemm_backend()
    w_gate, w_up, w_down = bank["w_gate"], bank["w_up"], bank["w_down"]
    if be == "pallas":
        K, F = w_gate.shape[1], w_gate.shape[2]
        group = math.gcd(math.gcd(K, F), 64)
        zero_lo = lambda k, n: (jnp.zeros((1, k, n), jnp.uint8),
                                jnp.zeros((1, k // group, n), w_gate.dtype))
        gp, gs = zero_lo(K, F)
        dp_, ds = zero_lo(F, K)
        return ragged_quant_ffn(
            xs, jnp.zeros_like(tile_eid), tile_eid, jnp.ones_like(tile_eid),
            gp, gs, gp, gs, dp_, ds,
            w_gate, w_up, w_down,
            bits=8, group=group, bm=bm, interpret=_interpret_default())
    return _ref.ragged_dense_ffn_ref(xs, tile_eid, w_gate, w_up, w_down,
                                     bm=bm)


@partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def quant_matmul_op(x: jax.Array, qt: QuantizedTensor, bm: int = 128,
                    bn: int = 128,
                    interpret: bool | None = None) -> jax.Array:
    interpret = _interpret_default() if interpret is None else interpret
    return quant_matmul(x, qt.packed, qt.scales, bits=qt.bits,
                        group=qt.group_size, bm=bm, bn=bn,
                        interpret=interpret)


@partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def grouped_quant_matmul_op(xg: jax.Array, qt: QuantizedTensor, bm: int = 128,
                            bn: int = 128,
                            interpret: bool | None = None) -> jax.Array:
    interpret = _interpret_default() if interpret is None else interpret
    return grouped_quant_matmul(xg, qt.packed, qt.scales, bits=qt.bits,
                                group=qt.group_size, bm=bm, bn=bn,
                                interpret=interpret)


@partial(jax.jit, static_argnames=("bs", "interpret"))
def flash_decode_op(q: jax.Array, k: jax.Array, v: jax.Array,
                    valid: jax.Array, bs: int = 512,
                    interpret: bool | None = None) -> jax.Array:
    interpret = _interpret_default() if interpret is None else interpret
    return flash_decode(q, k, v, valid, bs=bs, interpret=interpret)


@partial(jax.jit, static_argnames=("interpret",))
def flash_decode_paged_op(q: jax.Array, k: jax.Array, v: jax.Array,
                          table: jax.Array, valid: jax.Array,
                          interpret: bool | None = None) -> jax.Array:
    """Block-table flash decode over the paged KV pool (see
    ``flash_decode_paged``); k/v are (N, Hkv, bt, hd) physical blocks —
    the ``PagedKVCache`` layout, one superblock slice."""
    interpret = _interpret_default() if interpret is None else interpret
    return flash_decode_paged(q, k, v, table, valid, interpret=interpret)
