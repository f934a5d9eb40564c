"""Pallas TPU kernels: fused dequant + matmul for int4/int2/int8 weights.

The DynaExq lo-tier GEMMs. The packed codes stream HBM→VMEM at ``bits``/8
bytes per element — the entire memory-footprint benefit of the lo tier —
and are expanded *in VMEM* right before feeding the MXU, so no dequantized
copy ever exists in HBM.

Three kernels:

* ``quant_matmul``          — plain (M, K) × q(K, N), dequant-tile-then-dot.
* ``grouped_quant_matmul``  — batched-over-experts (E, C, K) × q(E, K, N),
  the PADDED MoE path. Uses the group-blocked formulation (per-group partial
  dot, scales applied after) so its arithmetic matches the jnp expression
  ``ref.grouped_lo_gemm_jnp`` bit for bit — the two are collapsed behind one
  dispatcher (``ops.grouped_lo_matmul``) with a parity test.
* ``ragged_quant_ffn``      — the decode hot path: ONE fused mixed-precision
  SwiGLU FFN over a bm-aligned ragged layout. Tokens arrive compacted
  (sorted by expert, segments padded to the row-tile bm — no (E, C, d)
  zero-padded buffer), scalar-prefetched tile→expert maps drive the weight
  BlockSpecs, and each tile streams ONLY its expert's resident tier: hi
  (bf16 slot) or lo (packed int codes dequantized in VMEM). Inactive
  (zero-token) experts never appear in the tile maps, so their weights are
  never read; tail tiles past the ragged extent repeat the previous tile's
  weight block index, which Pallas recognizes as "no new DMA". w_gate and
  w_up fuse into one grid sweep with the SiLU·mul epilogue in VMEM; the
  grouped w_down GEMM rides the same tile maps.

Tiling: grid (tiles, N/bn, K/bk); K is the innermost (sequential) axis with
f32 VMEM accumulators. The contraction tile bk is ``_k_tile``'s: 16 scale
groups, or the whole K when that does not divide it — a bf16 scales block
must span a multiple of 16 rows or the whole K/group axis, or the TPU
lowering refuses it (at group 64 and K ≤ 1024, one K step covers K).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


#: Second-minor block rows Mosaic accepts for a bf16 operand (its sublane
#: tile). The scales block (bk // group, bn) must span a multiple of this
#: many rows, or the whole K // group axis.
_SCALE_ROWS = 16


def _k_tile(K: int, group: int) -> int:
    """Contraction tile: ``_SCALE_ROWS`` scale groups when that divides
    ``K``, else the whole of ``K``. Either way each K tile sees whole scale
    groups and its scales block is one the TPU lowering accepts."""
    bk = _SCALE_ROWS * group
    return bk if K % bk == 0 else K


def _unpack_tile(wp: jax.Array, bits: int) -> jax.Array:
    """wp: (rows, bn) uint8 packed → (rows · 8//bits, bn) centered int32."""
    if bits == 8:
        return wp.astype(jnp.int32) - 128
    epb = 8 // bits
    bkp, bn = wp.shape
    shifts = (jnp.arange(epb, dtype=jnp.uint32) * bits)[None, :, None]
    u = (wp.astype(jnp.uint32)[:, None, :] >> shifts) & ((1 << bits) - 1)
    return u.reshape(bkp * epb, bn).astype(jnp.int32) - (1 << (bits - 1))


def _dequant_tile(wp: jax.Array, s: jax.Array, bits: int, group: int) -> jax.Array:
    """wp: (bk//epb, bn) uint8; s: (bk//g, bn) → (bk, bn) f32 (in VMEM)."""
    q = _unpack_tile(wp, bits)
    scale = jnp.repeat(s.astype(jnp.float32), group, axis=0)  # (bk, bn)
    return q.astype(jnp.float32) * scale


def _group_blocked_matmul(x: jax.Array, wp: jax.Array, s: jax.Array,
                          bits: int, group: int) -> jax.Array:
    """x: (bm, bk) × packed (bk//epb, bn) / scales (bk//g, bn) → (bm, bn)
    f32, computed as Σ_g scale_g · (x_g @ q_g): per-group partial dots in
    the input dtype with f32 accumulation, scales applied AFTER — the exact
    decomposition of ``ref.grouped_lo_gemm_jnp`` (bit-parity by
    construction on a given backend)."""
    epb = 8 // bits
    bk = wp.shape[0] * epb
    rpg = group // epb                     # packed rows per scale group
    acc = jnp.zeros((x.shape[0], wp.shape[1]), jnp.float32)
    for g in range(bk // group):
        q = _unpack_tile(wp[g * rpg:(g + 1) * rpg], bits)     # (group, bn)
        part = jnp.dot(x[:, g * group:(g + 1) * group],
                       q.astype(x.dtype),
                       preferred_element_type=jnp.float32)
        acc = acc + part * s[g][None, :].astype(jnp.float32)
    return acc


def _qmm_kernel(x_ref, wp_ref, s_ref, o_ref, acc_ref, *, bits, group, nk):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = _dequant_tile(wp_ref[...], s_ref[...], bits, group)
    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32), w,
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def quant_matmul(x: jax.Array, packed: jax.Array, scales: jax.Array, *,
                 bits: int, group: int, bm: int = 128, bn: int = 128,
                 interpret: bool = False) -> jax.Array:
    """x: (M, K) bf16 × packed (K//epb, N) uint8 / scales (K//g, N) → (M, N)."""
    M, K = x.shape
    epb = 8 // bits
    N = packed.shape[1]
    bm, bn, bk = min(bm, M), min(bn, N), _k_tile(K, group)
    if M % bm or N % bn or K % bk:
        raise ValueError(f"shape ({M},{K})x({K},{N}) not tileable by "
                         f"({bm},{bn},{bk})")
    nk = K // bk
    grid = (M // bm, N // bn, nk)
    return pl.pallas_call(
        functools.partial(_qmm_kernel, bits=bits, group=group, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk // epb, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bk // group, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[_vmem_scratch((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, packed, scales)


def _vmem_scratch(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)


def _gqmm_kernel(x_ref, wp_ref, s_ref, o_ref, acc_ref, *, bits, group, nk):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _group_blocked_matmul(x_ref[0], wp_ref[0], s_ref[0],
                                          bits, group)

    @pl.when(k == nk - 1)
    def _done():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def grouped_quant_matmul(xg: jax.Array, packed: jax.Array, scales: jax.Array,
                         *, bits: int, group: int, bm: int = 128,
                         bn: int = 128,
                         interpret: bool = False) -> jax.Array:
    """xg: (E, C, K) × packed (E, K//epb, N) → (E, C, N)."""
    E, C, K = xg.shape
    epb = 8 // bits
    N = packed.shape[2]
    bm, bn, bk = min(bm, C), min(bn, N), _k_tile(K, group)
    if C % bm or N % bn or K % bk:
        raise ValueError(f"({E},{C},{K})x({K},{N}) not tileable by "
                         f"({bm},{bn},{bk})")
    nk = K // bk
    grid = (E, C // bm, N // bn, nk)
    return pl.pallas_call(
        functools.partial(_gqmm_kernel, bits=bits, group=group, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda e, i, j, k: (e, i, k)),
            pl.BlockSpec((1, bk // epb, bn), lambda e, i, j, k: (e, k, j)),
            pl.BlockSpec((1, bk // group, bn), lambda e, i, j, k: (e, k, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda e, i, j, k: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, C, N), xg.dtype),
        scratch_shapes=[_vmem_scratch((bm, bn), jnp.float32)],
        interpret=interpret,
    )(xg, packed, scales)


# ---------------------------------------------------------------------------
# Ragged mixed-precision expert FFN — the decode megakernel
# ---------------------------------------------------------------------------

def _fit_tile(n: int, pref: int) -> int:
    """Largest of ``pref`` / whole-dim that tiles ``n`` exactly."""
    return pref if n % pref == 0 else n


def _ragged_gateup_kernel(lo_ref, hi_ref, ih_ref, x_ref,
                          gp_ref, gs_ref, up_ref, us_ref, hg_ref, hu_ref,
                          h_ref, accg_ref, accu_ref,
                          *, bits, group, nk, has_hi):
    """Fused w_gate∥w_up GEMM + SiLU·mul epilogue for one (tile, n, k) grid
    step. Scalar-prefetched maps: ``lo_ref``/``hi_ref`` are the DMA hold
    maps (which lo expert / hi slot this tile's weight blocks come from —
    repeated indices on the unused tier and on tail tiles suppress
    refetches), ``ih_ref`` selects which tier actually computes."""
    t = pl.program_id(0)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        accg_ref[...] = jnp.zeros_like(accg_ref)
        accu_ref[...] = jnp.zeros_like(accu_ref)

    x = x_ref[...]
    if has_hi:
        is_hi = ih_ref[t] > 0

        @pl.when(jnp.logical_not(is_hi))
        def _lo():
            accg_ref[...] += _group_blocked_matmul(x, gp_ref[0], gs_ref[0],
                                                   bits, group)
            accu_ref[...] += _group_blocked_matmul(x, up_ref[0], us_ref[0],
                                                   bits, group)

        @pl.when(is_hi)
        def _hi():
            accg_ref[...] += jnp.dot(x, hg_ref[0],
                                     preferred_element_type=jnp.float32)
            accu_ref[...] += jnp.dot(x, hu_ref[0],
                                     preferred_element_type=jnp.float32)
    else:
        accg_ref[...] += _group_blocked_matmul(x, gp_ref[0], gs_ref[0],
                                               bits, group)
        accu_ref[...] += _group_blocked_matmul(x, up_ref[0], us_ref[0],
                                               bits, group)

    @pl.when(k == nk - 1)
    def _done():
        # Epilogue in VMEM, matching the jnp contract of the padded path:
        # both accumulators round to the activation dtype, SiLU evaluates
        # in f32, and the product rounds once more.
        g16 = accg_ref[...].astype(h_ref.dtype)
        u16 = accu_ref[...].astype(h_ref.dtype)
        h_ref[...] = (jax.nn.silu(g16.astype(jnp.float32))
                      .astype(h_ref.dtype) * u16)


def _ragged_down_kernel(lo_ref, hi_ref, ih_ref, h_ref,
                        dp_ref, ds_ref, hd_ref,
                        y_ref, acc_ref, *, bits, group, nk, has_hi):
    t = pl.program_id(0)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    h = h_ref[...]
    if has_hi:
        is_hi = ih_ref[t] > 0

        @pl.when(jnp.logical_not(is_hi))
        def _lo():
            acc_ref[...] += _group_blocked_matmul(h, dp_ref[0], ds_ref[0],
                                                  bits, group)

        @pl.when(is_hi)
        def _hi():
            acc_ref[...] += jnp.dot(h, hd_ref[0],
                                    preferred_element_type=jnp.float32)
    else:
        acc_ref[...] += _group_blocked_matmul(h, dp_ref[0], ds_ref[0],
                                              bits, group)

    @pl.when(k == nk - 1)
    def _done():
        y_ref[...] = acc_ref[...].astype(y_ref.dtype)


def _prefetch_grid_spec(num_scalar_prefetch, grid, in_specs, out_specs,
                        scratch_shapes):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=num_scalar_prefetch, grid=grid,
        in_specs=in_specs, out_specs=out_specs,
        scratch_shapes=scratch_shapes)


def ragged_quant_ffn(xs: jax.Array, tile_lo: jax.Array, tile_hi: jax.Array,
                     tile_is_hi: jax.Array,
                     gate_packed, gate_scales, up_packed, up_scales,
                     down_packed, down_scales,
                     hi_gate=None, hi_up=None, hi_down=None, *,
                     bits: int, group: int, bm: int, bn: int = 128,
                     interpret: bool = False) -> jax.Array:
    """One fused mixed-precision SwiGLU FFN over the ragged token layout.

    ``xs``: (R = Tt·bm, K) compacted activations — tokens sorted by expert,
    per-expert segments padded up to the row tile ``bm`` (the ONLY padding
    in the ragged path). ``tile_lo``/``tile_hi``: (Tt,) int32 DMA hold maps
    (lo expert id / hi slot id to stream for each row tile; the unused
    tier's index repeats the previous tile so no fresh block is fetched).
    ``tile_is_hi``: (Tt,) int32 — 1 where the tile computes with its hi
    slot. Lo weights: packed (E, K//epb, F) / scales (E, K//g, F) per
    matrix; hi weights: (n_hi, K, F) bf16 (``None`` ⇒ an all-lo bank, e.g.
    the static-PTQ backend or the speculative draft tier — the kernel then
    compiles without hi operands at all).

    Returns y (R, D). Rows of tail/padding tiles hold garbage — callers
    gather only real assignment rows back out (``moe._dispatch_ragged``)."""
    R, K = xs.shape
    Tt = tile_lo.shape[0]
    if R != Tt * bm:
        raise ValueError(f"xs rows {R} != tiles {Tt} × bm {bm}")
    epb = 8 // bits
    F = gate_packed.shape[-1]
    D = down_packed.shape[-1]
    has_hi = hi_gate is not None and hi_gate.shape[0] > 0
    bn_f = _fit_tile(F, bn)
    bn_d = _fit_tile(D, bn)
    bk_k = _k_tile(K, group)
    bk_f = _k_tile(F, group)
    if K % bk_k or K % group or F % bn_f or F % bk_f or F % group or D % bn_d:
        raise ValueError(f"(K={K}, F={F}, D={D}) not tileable by "
                         f"(bk={bk_k}/{bk_f}, bn={bn_f}/{bn_d}, g={group})")
    nk1 = K // bk_k
    nk2 = F // bk_f
    if not has_hi:
        # Zero-size placeholders keep one call signature; the kernel is
        # compiled without hi refs (static ``has_hi``), so nothing streams.
        hi_gate = jnp.zeros((1, K, F), xs.dtype)
        hi_up = jnp.zeros((1, K, F), xs.dtype)
        hi_down = jnp.zeros((1, F, D), xs.dtype)

    gu_specs = [
        pl.BlockSpec((bm, bk_k), lambda t, j, k, lo, hi, ih: (t, k)),
        pl.BlockSpec((1, bk_k // epb, bn_f),
                     lambda t, j, k, lo, hi, ih: (lo[t], k, j)),
        pl.BlockSpec((1, bk_k // group, bn_f),
                     lambda t, j, k, lo, hi, ih: (lo[t], k, j)),
        pl.BlockSpec((1, bk_k // epb, bn_f),
                     lambda t, j, k, lo, hi, ih: (lo[t], k, j)),
        pl.BlockSpec((1, bk_k // group, bn_f),
                     lambda t, j, k, lo, hi, ih: (lo[t], k, j)),
        pl.BlockSpec((1, bk_k, bn_f),
                     lambda t, j, k, lo, hi, ih: (hi[t], k, j)),
        pl.BlockSpec((1, bk_k, bn_f),
                     lambda t, j, k, lo, hi, ih: (hi[t], k, j)),
    ]
    h = pl.pallas_call(
        functools.partial(_ragged_gateup_kernel, bits=bits, group=group,
                          nk=nk1, has_hi=has_hi),
        grid_spec=_prefetch_grid_spec(
            3, (Tt, F // bn_f, nk1), gu_specs,
            pl.BlockSpec((bm, bn_f), lambda t, j, k, lo, hi, ih: (t, j)),
            [_vmem_scratch((bm, bn_f), jnp.float32),
             _vmem_scratch((bm, bn_f), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((R, F), xs.dtype),
        interpret=interpret,
    )(tile_lo, tile_hi, tile_is_hi, xs, gate_packed, gate_scales,
      up_packed, up_scales, hi_gate, hi_up)

    dn_specs = [
        pl.BlockSpec((bm, bk_f), lambda t, j, k, lo, hi, ih: (t, k)),
        pl.BlockSpec((1, bk_f // epb, bn_d),
                     lambda t, j, k, lo, hi, ih: (lo[t], k, j)),
        pl.BlockSpec((1, bk_f // group, bn_d),
                     lambda t, j, k, lo, hi, ih: (lo[t], k, j)),
        pl.BlockSpec((1, bk_f, bn_d),
                     lambda t, j, k, lo, hi, ih: (hi[t], k, j)),
    ]
    return pl.pallas_call(
        functools.partial(_ragged_down_kernel, bits=bits, group=group,
                          nk=nk2, has_hi=has_hi),
        grid_spec=_prefetch_grid_spec(
            3, (Tt, D // bn_d, nk2), dn_specs,
            pl.BlockSpec((bm, bn_d), lambda t, j, k, lo, hi, ih: (t, j)),
            [_vmem_scratch((bm, bn_d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((R, D), xs.dtype),
        interpret=interpret,
    )(tile_lo, tile_hi, tile_is_hi, h, down_packed, down_scales, hi_down)
