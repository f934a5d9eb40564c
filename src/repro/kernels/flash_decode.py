"""Pallas TPU kernel: single-token flash attention over a long KV cache.

The decode_32k / long_500k hot spot: one query row per (batch, head) against
S cached keys. Online-softmax accumulation over KV tiles keeps the working
set at O(bs·hd) VMEM regardless of S; GQA is handled in the BlockSpec index
map (q head → kv head), so kv tiles are fetched once per kv head group.

Grid: (B, H, S/bs), S innermost/sequential with running (m, l, acc) scratch.

``flash_decode_paged`` is the gather-by-block-table variant for the paged
KV pool (``repro.serving.kvpool``): K/V come as (N, Hkv, bt, hd) physical
blocks — the ``repro.models.layers.PagedKVCache`` pool, head-major — and
each sequence's (B, nb) block table rides in as a scalar-prefetch
argument, so the BlockSpec index map DMAs exactly the blocks the row owns
— no materialized logical copy of the cache.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)


def _fd_kernel(q_ref, k_ref, v_ref, valid_ref, o_ref,
               m_ref, l_ref, acc_ref, *, ns, scale):
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                 # (1, hd) via block
    k = k_ref[0, :, 0].astype(jnp.float32)              # (bs, hd)
    v = v_ref[0, :, 0].astype(jnp.float32)              # (bs, hd)
    logits = (q @ k.T) * scale                          # (1, bs)
    logits = jnp.where(valid_ref[0][None, :], logits, -jnp.inf)

    m_prev = m_ref[...]                                 # (1, 1)
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
    # All-masked tiles keep m at -inf; exp(-inf - -inf) is nan — guard.
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_new), 0.0)
    p = jnp.exp(logits - m_new)                         # (1, bs), 0 where -inf
    p = jnp.where(jnp.isfinite(logits), p, 0.0)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + p @ v         # (1, hd)
    m_ref[...] = m_new

    @pl.when(s == ns - 1)
    def _done():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom)[0].astype(o_ref.dtype)


def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array, valid: jax.Array,
                 *, bs: int = 512, interpret: bool = False) -> jax.Array:
    """q: (B, H, hd); k/v: (B, S, Hkv, hd); valid: (B, S) bool → (B, H, hd)."""
    B, H, hd = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    rep = H // Hkv
    bs = min(bs, S)
    if S % bs:
        raise ValueError(f"S={S} not tileable by bs={bs}")
    ns = S // bs
    grid = (B, H, ns)
    return pl.pallas_call(
        functools.partial(_fd_kernel, ns=ns, scale=hd ** -0.5),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, hd), lambda b, h, s: (b, h, 0)),
            pl.BlockSpec((1, bs, 1, hd), lambda b, h, s: (b, s, h // rep, 0)),
            pl.BlockSpec((1, bs, 1, hd), lambda b, h, s: (b, s, h // rep, 0)),
            pl.BlockSpec((1, bs), lambda b, h, s: (b, s)),
        ],
        out_specs=pl.BlockSpec((1, 1, hd), lambda b, h, s: (b, h, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        scratch_shapes=[_vmem((1, 1), jnp.float32),
                        _vmem((1, 1), jnp.float32),
                        _vmem((1, hd), jnp.float32)],
        interpret=interpret,
    )(q, k, v, valid)


def _fd_paged_kernel(table_ref, q_ref, k_ref, v_ref, valid_ref, o_ref,
                     m_ref, l_ref, acc_ref, *, ns, scale):
    # Online-softmax accumulation, one KV tile per physical block. The
    # block table only acts in the index maps (table_ref is the
    # scalar-prefetch operand); tiles arrive in the pool's head-major
    # (1, 1, bt, hd) layout.
    del table_ref
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                 # (1, hd) via block
    k = k_ref[0, 0].astype(jnp.float32)                 # (bt, hd)
    v = v_ref[0, 0].astype(jnp.float32)                 # (bt, hd)
    logits = (q @ k.T) * scale                          # (1, bt)
    logits = jnp.where(valid_ref[0][None, :], logits, -jnp.inf)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_new), 0.0)
    p = jnp.exp(logits - m_new)
    p = jnp.where(jnp.isfinite(logits), p, 0.0)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + p @ v
    m_ref[...] = m_new

    @pl.when(s == ns - 1)
    def _done():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom)[0].astype(o_ref.dtype)


def flash_decode_paged(q: jax.Array, k: jax.Array, v: jax.Array,
                       table: jax.Array, valid: jax.Array,
                       *, interpret: bool = False) -> jax.Array:
    """Paged flash decode over the pool's blocks: q: (B, H, hd); k/v:
    (N, Hkv, bt, hd) physical block pools (``repro.models.layers.
    PagedKVCache``, one superblock slice, head-major); table:
    (B, nb) int32 physical block ids per logical block (-1 = unallocated,
    routed to block 0 — mask those slots out via ``valid``); valid:
    (B, nb·bt) bool over the logical view. Returns (B, H, hd), numerically
    identical to ``flash_decode`` over the gathered logical cache. One KV
    tile per block: the scalar-prefetched table IS the gather."""
    from jax.experimental.pallas import tpu as pltpu

    B, H, hd = q.shape
    Hkv, bt = k.shape[1], k.shape[2]
    nb = table.shape[1]
    rep = H // Hkv
    if valid.shape != (B, nb * bt):
        raise ValueError(f"valid {valid.shape} != (B, nb*bt)="
                         f"{(B, nb * bt)}")
    table = jnp.clip(table.astype(jnp.int32), 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H, nb),
        in_specs=[
            pl.BlockSpec((1, 1, hd), lambda b, h, s, t: (b, h, 0)),
            pl.BlockSpec((1, 1, bt, hd),
                         lambda b, h, s, t: (t[b, s], h // rep, 0, 0)),
            pl.BlockSpec((1, 1, bt, hd),
                         lambda b, h, s, t: (t[b, s], h // rep, 0, 0)),
            pl.BlockSpec((1, bt), lambda b, h, s, t: (b, s)),
        ],
        out_specs=pl.BlockSpec((1, 1, hd), lambda b, h, s, t: (b, h, 0)),
        scratch_shapes=[_vmem((1, 1), jnp.float32),
                        _vmem((1, 1), jnp.float32),
                        _vmem((1, hd), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_fd_paged_kernel, ns=nb, scale=hd ** -0.5),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        interpret=interpret,
    )(table, q, k, v, valid)
