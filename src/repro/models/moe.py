"""Mixture-of-Experts layer with capacity-based dispatch and a pluggable
expert bank (dense bf16 for training, DynaExq mixed-precision for serving).

Two dispatch layouts, selected per call (``dispatch=``, default from
``kernels.ops.moe_dispatch_default``):

* **padded** (reference): sort the token→expert assignments, scatter into a
  fixed-capacity (E, C, d) buffer, run the batched expert GEMM over ALL E
  experts, combine with the router gates. Simple, shardable, and the
  bit-parity oracle — but at decode batch sizes most of (E, C) is padding,
  so every step pays the weight-read bytes of every expert.
* **ragged** (serving decode hot path): sort + compact into a (Tt·bm, d)
  buffer whose per-expert segments are aligned to the row tile ``bm``, and
  hand per-tile expert/slot maps to ONE fused mixed-precision kernel
  (``kernels.ops.ragged_quant_ffn_op``). Only experts that received tokens
  this step stream their weights, and each streams its *resident tier only*
  (hi bf16 slot or packed lo codes dequantized in VMEM) — the bytes/token
  the lo tier was built to save are actually saved.

Execution regimes:

* Single device (tests, CPU serving, benchmarks): both layouts available.
* Distributed (via ``repro.launch.dist``): inside ``shard_map``, two
  regimes. The padded body — each data shard routes its own tokens, each
  model shard computes only its local E/n experts
  (``e_offset``/``e_local``), partial token outputs reduce with a single
  psum over the model axis. And the first-class expert-parallel serving
  path (``DistContext.tokens_ep_sharded`` + ragged dispatch): tokens shard
  over data AND model axes, each shard compacts its kept assignments per
  destination expert-shard and exchanges a statically-bounded bm-aligned
  payload with one ``lax.all_to_all`` each way, so per-MoE-layer
  interconnect bytes scale with the payload budget ``ep_payload_rows``
  instead of the full activation psum. Both are formulations GSPMD cannot
  derive on its own (data-dependent sort/scatter) and the reason dispatch
  is explicit here.

Per-(layer, expert) selection counts — the hotness signal the DynaExq
scheduler consumes (paper §3.5) — fall out of dispatch for free, as do the
dispatch-efficiency gauges (``MoEAux.active_experts`` /
``dispatch_pad_ratio``) the serving stats surface.
"""
from __future__ import annotations

import os
from typing import Dict, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
from jax import shard_map

from repro.core.ver import ExpertBankQ
from repro.kernels import ops as kops
from repro.models.config import MoEConfig
from repro.models.layers import _init
from repro.models.mlp import init_swiglu, swiglu
from repro.quant.qtensor import dequantize

#: Row-tile height of the ragged layout: each active expert's token segment
#: is padded up to a multiple of this (the ONLY padding the ragged path
#: pays). 8 matches the f32 sublane on TPU and keeps CPU tests cheap.
RAGGED_BM = int(os.environ.get("REPRO_MOE_RAGGED_BM", "8"))


class MoEAux(NamedTuple):
    counts: jax.Array     # (E,) int32 — router selections this call
    aux_loss: jax.Array   # scalar f32 — load-balance loss
    dropped: jax.Array    # scalar f32 — fraction of assignments dropped
    # (R, E) int32 — selections segment-summed per row (request/slot), only
    # when ``moe_apply(..., n_rows=R)`` asks for it. Rows whose tokens are
    # all masked by ``token_valid`` contribute zeros, which is what lets the
    # serving engine keep vacant continuous-batching slots and prompt
    # padding out of the hotness signal.
    row_counts: Optional[jax.Array] = None
    # Dispatch-efficiency telemetry (None on the sharded path): number of
    # experts that received ≥1 assignment this call, and the fraction of
    # GEMM rows that were padding — (E·C − kept)/(E·C) for the padded
    # layout, (Tt·bm − routed)/(Tt·bm) for the ragged layout.
    active_experts: Optional[jax.Array] = None
    dispatch_pad_ratio: Optional[jax.Array] = None


def init_moe(key, d_model: int, cfg: MoEConfig) -> Dict:
    ks = jax.random.split(key, 5)
    E, f = cfg.num_experts, cfg.d_ff_expert
    p = {
        "router": _init(ks[0], (d_model, E), scale=d_model ** -0.5,
                        dtype=jnp.float32),
        "experts": {
            "w_gate": _init(ks[1], (E, d_model, f)),
            "w_up": _init(ks[2], (E, d_model, f)),
            "w_down": _init(ks[3], (E, f, d_model)),
        },
    }
    if cfg.n_shared_experts:
        p["shared"] = init_swiglu(ks[4], d_model,
                                  cfg.d_ff_shared * cfg.n_shared_experts)
    return p


def effective_expert_weights(bank: Union[Dict, ExpertBankQ],
                             e_offset: int = 0,
                             e_local: Optional[int] = None,
                             slot_lo: int = 0,
                             n_slot_local: Optional[int] = None
                             ) -> Dict[str, jax.Array]:
    """Materialize per-expert weights (E_local, K, N) in bf16.

    Dense bank: identity. DynaExq bank: dequantize the lo tier then scatter
    the published hi versions over their owners — experts whose stable handle
    points at a hi slot compute with hi weights, the rest with lo. Under
    expert parallelism the bank leaves arrive pre-sliced to the local expert
    (and hi-slot) ranges; ``slot_owner`` stays global, so owners are shifted
    by ``e_offset`` and out-of-range owners drop out of the scatter.
    (The Pallas serving kernel performs the same selection in-kernel without
    materializing; this jnp path is the oracle + dry-run path.)
    """
    if isinstance(bank, ExpertBankQ):
        owner = bank.slot_owner            # (n_hi,) global, after scan slicing
        E = bank.slot_map.shape[-1]
        e_local = e_local if e_local is not None else E
        if n_slot_local is not None:
            owner = jax.lax.dynamic_slice_in_dim(owner, slot_lo, n_slot_local)
        owner = owner - e_offset
        safe_owner = jnp.where((owner >= 0) & (owner < e_local),
                               owner, e_local)          # OOB ⇒ dropped
        out = {}
        for name, qt in bank.lo.items():
            w = dequantize(qt)             # (E_local, K, N)
            out[name] = w.at[safe_owner].set(bank.hi[name], mode="drop")
        return out
    return bank


def route(router_w: jax.Array, x: jax.Array, cfg: MoEConfig):
    """x: (T, d) → gates (T, k), idx (T, k), probs (T, E)."""
    logits = (x.astype(jnp.float32) @ router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return gates, idx, probs


def _sort_routing(idx: jax.Array, e_local: int):
    """Shared dispatch prologue — the ONE place the assignment order, the
    per-expert counts and positions, and therefore the padded↔ragged
    bit-identity contract are defined. idx: (T, k) local expert ids with
    ``e_local`` as the out-of-range sentinel. Returns ``(order, sorted_eid,
    counts (e_local,), pos_in_e, tok)`` over the stable sort-by-expert of
    the flattened assignments."""
    k = idx.shape[1]
    fidx = idx.reshape(-1)                                   # (T*k,)
    order = jnp.argsort(fidx, stable=True)
    sorted_eid = fidx[order]
    counts_all = jnp.bincount(fidx, length=e_local + 1)
    counts = counts_all[:e_local]
    starts = jnp.cumsum(counts_all) - counts_all
    pos_in_e = jnp.arange(fidx.shape[0], dtype=jnp.int32) - \
        starts[sorted_eid]
    tok = order // k                                         # source token
    return order, sorted_eid, counts, pos_in_e, tok


def _keep_mask(sorted_eid: jax.Array, pos_in_e: jax.Array, tok: jax.Array,
               e_local: int, capacity: int, row_capacity: Optional[int],
               n_rows: Optional[int], n_tokens: int) -> jax.Array:
    """The ONE drop rule both layouts share: global per-expert capacity, or
    the per-row normalization when ``row_capacity`` is set."""
    if row_capacity is None:
        return (pos_in_e < capacity) & (sorted_eid < e_local)
    return _row_capacity_keep(sorted_eid, tok, e_local, n_rows, n_tokens,
                              row_capacity) & (sorted_eid < e_local)


def _row_capacity_keep(sorted_eid: jax.Array, tok: jax.Array, e_local: int,
                       n_rows: int, n_tokens: int,
                       row_capacity: int) -> jax.Array:
    """Per-row drop rule: an assignment survives iff its rank among ITS OWN
    row's assignments to the same expert is < ``row_capacity``. Whether a
    token's assignment drops then depends only on that row's routing —
    never on which other rows share the compute batch (the batch-shape
    independence prefix sharing and spec-verify token-identity need in drop
    regimes). Assumes ``sorted_eid``/``tok`` come from the stable
    sort-by-expert (same-(expert, row) entries are contiguous and in token
    order)."""
    tpr = n_tokens // n_rows
    rid = tok // tpr
    key = jnp.where(sorted_eid < e_local, sorted_eid * n_rows + rid,
                    e_local * n_rows)
    cnt = jnp.zeros((e_local * n_rows + 1,), jnp.int32).at[key].add(1)
    kstart = jnp.cumsum(cnt) - cnt
    pos_re = jnp.arange(key.shape[0], dtype=jnp.int32) - kstart[key]
    return pos_re < row_capacity


def dispatch_compute(bank, x: jax.Array, idx: jax.Array, gates: jax.Array,
                     e_local: int, capacity: int, e_offset: int = 0,
                     n_slot_local: Optional[int] = None, slot_lo: int = 0,
                     ff_axis=None, row_capacity: Optional[int] = None,
                     n_rows: Optional[int] = None, gemm: Optional[str] = None):
    """Padded sort-scatter dispatch + batched expert GEMM + gated combine.

    x: (T, d); idx: (T, k) LOCAL expert ids with ``e_local`` as the
    out-of-range sentinel; gates: (T, k) with zeros on sentinel entries.
    ``row_capacity`` (with ``n_rows``) switches the drop rule from the
    global per-expert capacity to the per-row rule (see
    ``_row_capacity_keep``); ``capacity`` must then be the physical bound
    the caller derived (``n_rows · row_capacity`` makes overflow
    impossible). Returns (y (T, d), counts (e_local,), dropped scalar).
    """
    T, d = x.shape
    with jax.named_scope("dispatch"):
        order, sorted_eid, counts, pos_in_e, tok = _sort_routing(idx, e_local)
        valid = _keep_mask(sorted_eid, pos_in_e, tok, e_local, capacity,
                           row_capacity, n_rows, T)
        if row_capacity is None:
            scat_pos = pos_in_e
        else:
            # Scatter by rank among KEPT assignments of the expert so the
            # physical buffer only ever holds survivors.
            kept_i = valid.astype(jnp.int32)
            inc = jnp.cumsum(kept_i)
            kept_e = jnp.zeros((e_local + 1,), jnp.int32) \
                .at[sorted_eid].add(kept_i)
            kstart = jnp.cumsum(kept_e) - kept_e
            scat_pos = jnp.where(valid, inc - 1 - kstart[sorted_eid], capacity)

        xg = jnp.zeros((e_local, capacity, d), x.dtype)
        xg = xg.at[sorted_eid, scat_pos].set(x[tok], mode="drop")

    with jax.named_scope("ffn"):
        if isinstance(bank, ExpertBankQ):
            yg = _quant_expert_ffn(bank, xg, e_offset=e_offset,
                                   e_local=e_local, slot_lo=slot_lo,
                                   n_slot_local=n_slot_local,
                                   ff_axis=ff_axis, gemm=gemm)
        else:
            w = bank
            h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xg, w["w_gate"])
                            .astype(jnp.float32)).astype(x.dtype)
            h = h * jnp.einsum("ecd,edf->ecf", xg, w["w_up"])
            yg = jnp.einsum("ecf,efd->ecd", h, w["w_down"])

    with jax.named_scope("combine"):
        pos_safe = jnp.minimum(scat_pos, capacity - 1)
        eid_safe = jnp.minimum(sorted_eid, e_local - 1)
        y_sorted = yg[eid_safe, pos_safe]
        gate_sorted = gates.reshape(-1)[order].astype(x.dtype)
        contrib = jnp.where(valid[:, None], y_sorted * gate_sorted[:, None], 0)
        # yg's output-feature dim may be data-sliced under 2-D expert sharding.
        y = jnp.zeros((T, yg.shape[-1]), x.dtype).at[tok].add(contrib)

        routed = jnp.sum(jnp.where(sorted_eid < e_local, 1.0, 0.0))
        kept = jnp.sum(jnp.where(valid, 1.0, 0.0))
        dropped = 1.0 - kept / jnp.maximum(routed, 1.0)
    return y, counts.astype(jnp.int32), dropped


def _quant_expert_ffn(bank: ExpertBankQ, xg: jax.Array, e_offset=0,
                      e_local: Optional[int] = None, slot_lo: int = 0,
                      n_slot_local: Optional[int] = None,
                      ff_axis=None, gemm: Optional[str] = None) -> jax.Array:
    """SwiGLU expert FFN on the lo tier (group-blocked quantized GEMMs via
    the ``kernels.ops.grouped_lo_matmul`` dispatcher — jnp expression or
    Pallas kernel, one math) with the published hi-precision experts
    overlaid: hi slots compute in bf16 and their outputs replace the lo
    outputs of the experts they own — numerically identical to swapping the
    weights, without materializing per-expert dense weights."""
    E_, C, d = xg.shape
    lo = bank.lo
    g1 = kops.grouped_lo_matmul(xg, lo["w_gate"].packed, lo["w_gate"].scales,
                                lo["w_gate"].bits, lo["w_gate"].group_size,
                                backend=gemm)
    up = kops.grouped_lo_matmul(xg, lo["w_up"].packed, lo["w_up"].scales,
                                lo["w_up"].bits, lo["w_up"].group_size,
                                backend=gemm)
    h = (jax.nn.silu(g1.astype(jnp.float32)).astype(xg.dtype) * up)
    if ff_axis is not None:
        # 2-D expert sharding for token-replicated decode (batch-1 long
        # context): gate/up are FF-sliced over the otherwise-idle data axis,
        # so each rank dequantized/read only F/|data| of every expert. The
        # activations are tiny at decode — gathering h costs ~100 KB.
        h = jax.lax.all_gather(h, ff_axis, axis=2, tiled=True)
    y = kops.grouped_lo_matmul(h, lo["w_down"].packed, lo["w_down"].scales,
                               lo["w_down"].bits, lo["w_down"].group_size,
                               backend=gemm)

    owner = bank.slot_owner
    if n_slot_local is not None:
        owner = jax.lax.dynamic_slice_in_dim(owner, slot_lo, n_slot_local)
    hi = bank.hi
    n_slots = owner.shape[0]
    if n_slots == 0:
        return y
    owner_l = owner - e_offset
    valid = (owner_l >= 0) & (owner_l < E_)
    safe = jnp.where(valid, owner_l, 0)
    xh = xg[safe]                                     # (n_hi, C, d)
    hh = jax.nn.silu(jnp.einsum("scd,sdf->scf", xh, hi["w_gate"])
                     .astype(jnp.float32)).astype(xg.dtype)
    hh = hh * jnp.einsum("scd,sdf->scf", xh, hi["w_up"])
    if ff_axis is not None:
        hh = jax.lax.all_gather(hh, ff_axis, axis=2, tiled=True)
    yh = jnp.einsum("scf,sfd->scd", hh, hi["w_down"])
    sentinel = jnp.where(valid, owner_l, E_)          # OOB ⇒ dropped
    return y.at[sentinel].set(yh, mode="drop")


def ragged_tile_map(counts: jax.Array, bm: int, n_assign: int):
    """bm-aligned ragged layout over per-expert assignment ``counts``
    ((E,) int32; ``n_assign`` = static total assignment budget T·k).

    Returns ``(astart (E,), tile_eid (Tt,), n_tiles scalar)``: expert e's
    segment starts at compact row ``astart[e]``; row tile t computes with
    expert ``tile_eid[t]``. Experts with zero tokens never appear in the
    live prefix ``tile_eid[:n_tiles]`` — their weights are never streamed.
    Σ ceil(c_e/bm) tiles ≤ n_assign//bm + #active, so the static tile
    budget Tt covers every routing; tail tiles (t ≥ n_tiles) repeat the
    last active expert — no fresh weight DMA, and their garbage rows are
    never gathered back."""
    e_local = counts.shape[0]
    aligned = ((counts + bm - 1) // bm) * bm
    astart = jnp.cumsum(aligned) - aligned
    ntile = aligned // bm
    cum_t = jnp.cumsum(ntile)
    n_tiles = cum_t[-1]
    Tt = n_assign // bm + min(e_local, n_assign) + 1
    t_range = jnp.arange(Tt, dtype=jnp.int32)
    tile_eid = jnp.searchsorted(cum_t, t_range, side="right") \
        .astype(jnp.int32)
    e_last = jnp.maximum(
        jnp.max(jnp.where(counts > 0, jnp.arange(e_local), -1)), 0)
    tile_eid = jnp.clip(jnp.where(t_range < n_tiles, tile_eid, e_last),
                        0, e_local - 1)
    return astart, tile_eid, n_tiles


def _dispatch_ragged(bank: Union[Dict, ExpertBankQ], x: jax.Array,
                     idx: jax.Array, gates: jax.Array, e_local: int,
                     capacity: int, row_capacity: Optional[int] = None,
                     n_rows: Optional[int] = None,
                     gemm: Optional[str] = None):
    """Padding-free ragged dispatch + ONE fused mixed-precision kernel.

    Same routing contract as ``dispatch_compute`` (idx sorted stably by
    expert, identical drop rule, identical gate-weighted combine — the two
    layouts are bit-identical per token on a given backend), but tokens
    compact into a (Tt·bm, d) buffer whose per-expert segments are aligned
    to the row tile ``RAGGED_BM`` instead of scattering into (E, C, d).
    The tile→expert map visits only experts that received tokens this
    step; per tile the kernel streams the expert's resident tier only (hi
    slot derived from ``slot_owner`` — the same stable handles the padded
    overlay scatters through, so an all-lo draft bank stays all-lo here
    too). A dense dict bank (fp16/offload serving, which has no quantized
    tier) takes the same layout through ``ragged_dense_ffn_op``: inactive
    experts still skip their weight reads. Dropped-by-capacity assignments
    still occupy compact rows (the layout depends only on routing) but are
    zeroed at combine, exactly like the padded path never computing them.

    Returns (y (T, D), counts (E,), dropped, pad_ratio)."""
    T, d = x.shape
    with jax.named_scope("dispatch"):
        Tk = T * idx.shape[1]
        bm = RAGGED_BM
        order, sorted_eid, counts, pos_in_e, tok = _sort_routing(idx, e_local)
        kept = _keep_mask(sorted_eid, pos_in_e, tok, e_local, capacity,
                          row_capacity, n_rows, T)
        astart, tile_eid, n_tiles = ragged_tile_map(counts, bm, Tk)
        R = tile_eid.shape[0] * bm
        safe_e = jnp.minimum(sorted_eid, e_local - 1)
        rowpos = jnp.where(sorted_eid < e_local,              # sentinel
                           astart[safe_e] + pos_in_e, R)    # → drop
        xs = jnp.zeros((R, d), x.dtype).at[rowpos].set(x[tok], mode="drop")

    with jax.named_scope("ffn"):
        if isinstance(bank, ExpertBankQ):
            # Stable handles: expert → hi slot derived from slot_owner
            # (NOT slot_map), matching the padded overlay's semantics — a
            # draft bank that disowns every slot is all-lo under both
            # layouts.
            owner = bank.slot_owner                              # (n_hi,)
            n_hi = owner.shape[0]
            if n_hi > 0:
                eff_map = jnp.full((e_local + 1,), -1, jnp.int32).at[
                    jnp.where(owner >= 0, owner, e_local)].set(
                    jnp.arange(n_hi, dtype=jnp.int32), mode="drop")[:e_local]
                tile_slot = eff_map[tile_eid]
            else:
                tile_slot = jnp.full_like(tile_eid, -1)

            y_rows = kops.ragged_quant_ffn_op(
                xs, tile_eid, tile_slot, bank.lo, bank.hi if n_hi else None,
                bits=bank.lo["w_gate"].bits,
                group=bank.lo["w_gate"].group_size, bm=bm, backend=gemm)
        else:
            y_rows = kops.ragged_dense_ffn_op(xs, tile_eid, bank, bm=bm,
                                              backend=gemm)

    with jax.named_scope("combine"):
        y_asn = y_rows[jnp.minimum(rowpos, R - 1)]
        gate_sorted = gates.reshape(-1)[order].astype(x.dtype)
        contrib = jnp.where(kept[:, None], y_asn * gate_sorted[:, None], 0)
        y = jnp.zeros((T, y_rows.shape[-1]), x.dtype).at[tok].add(contrib)

        routed = jnp.sum(jnp.where(sorted_eid < e_local, 1.0, 0.0))
        kept_f = jnp.sum(jnp.where(kept, 1.0, 0.0))
        dropped = 1.0 - kept_f / jnp.maximum(routed, 1.0)
        pad_ratio = 1.0 - routed / jnp.maximum(n_tiles * bm,
                                               1).astype(jnp.float32)
    return y, counts.astype(jnp.int32), dropped, pad_ratio


def _moe_local(params: Dict, bank, x: jax.Array, cfg: MoEConfig,
               capacity: int, e_offset, e_local: int,
               slot_lo=0, n_slot_local: Optional[int] = None, ff_axis=None,
               token_valid: Optional[jax.Array] = None,
               n_rows: Optional[int] = None,
               row_capacity: Optional[int] = None,
               dispatch: Optional[str] = None, gemm: Optional[str] = None):
    """Route + dispatch for one shard (e_offset may be traced).

    ``token_valid`` ((T,) bool) drops masked tokens from dispatch entirely:
    they route to the sentinel expert (zero output, no capacity consumed)
    and vanish from every count — the per-row validity signal prefill
    padding and vacant decode slots ride in on. ``n_rows`` additionally
    returns (n_rows, E) counts segment-summed over T/n_rows-token rows.
    ``row_capacity`` switches the drop rule to the per-row normalization
    (see ``_row_capacity_keep``); ``dispatch``/``gemm`` select the token
    layout and GEMM backend (see ``kernels.ops``).
    """
    E, k = cfg.num_experts, cfg.top_k
    T = x.shape[0]
    with jax.named_scope("router"):
        gates, idx, probs = route(params["router"], x, cfg)
    sel = (idx >= e_offset) & (idx < e_offset + e_local)
    if token_valid is not None:
        sel = sel & token_valid[:, None]
    idx_l = jnp.where(sel, idx - e_offset, e_local)          # sentinel
    gates_l = jnp.where(sel, gates, 0.0)
    if row_capacity is not None:
        if n_rows is None:
            raise ValueError("row_capacity needs n_rows")
        # Physical capacity covering the per-row rule's worst case (all
        # surviving assignments on one expert) — overflow-free, so drops
        # come from the row rule alone.
        capacity = n_rows * row_capacity
    # Ragged layout: full-expert-range bodies only — shifted expert windows
    # (traced e_offset), sliced slot pools, and FF-split experts keep the
    # padded reference body. Quantized AND dense dict banks both qualify
    # (the dense variant routes through ``ragged_dense_ffn_op``).
    use_ragged = (dispatch == "ragged"
                  and isinstance(e_offset, int) and e_offset == 0
                  and n_slot_local is None and ff_axis is None)
    if use_ragged:
        y, counts_l, dropped, pad_ratio = _dispatch_ragged(
            bank, x, idx_l, gates_l, e_local, capacity,
            row_capacity=row_capacity, n_rows=n_rows, gemm=gemm)
    else:
        y, counts_l, dropped = dispatch_compute(
            bank, x, idx_l, gates_l, e_local, capacity,
            e_offset=e_offset, slot_lo=slot_lo, n_slot_local=n_slot_local,
            ff_axis=ff_axis, row_capacity=row_capacity, n_rows=n_rows,
            gemm=gemm)
        kept_rows = jnp.sum(jnp.clip(counts_l, 0, capacity))
        pad_ratio = 1.0 - kept_rows.astype(jnp.float32) / \
            jnp.float32(max(e_local * capacity, 1))
    active_experts = jnp.sum((counts_l > 0).astype(jnp.int32))

    # Load-balance aux on the full (replicated) router distribution,
    # restricted to valid tokens so padding cannot skew the balance target.
    if token_valid is None:
        full_idx = jnp.clip(idx.reshape(-1), 0, E)
        n_assign = x.shape[0] * k
        mean_prob = jnp.mean(probs, axis=0)
    else:
        full_idx = jnp.where(token_valid[:, None], jnp.clip(idx, 0, E),
                             E).reshape(-1)
        n_assign = jnp.maximum(jnp.sum(token_valid), 1) * k
        tv = token_valid[:, None].astype(jnp.float32)
        mean_prob = jnp.sum(probs * tv, axis=0) / \
            jnp.maximum(jnp.sum(tv), 1.0)
    full_counts = jnp.zeros((E + 1,), jnp.int32).at[full_idx].add(1)[:E]
    frac_routed = full_counts.astype(jnp.float32) / jnp.maximum(n_assign, 1)
    aux_loss = cfg.router_aux_coef * E * jnp.sum(frac_routed * mean_prob)

    row_counts = None
    if n_rows is not None:
        # Segment-sum the valid assignments per row: row r covers tokens
        # [r·T/R, (r+1)·T/R). Uses GLOBAL expert ids (telemetry is shard-
        # agnostic); masked/out-of-shard assignments fall into the E bucket.
        tpr = T // n_rows
        rid = jnp.arange(T, dtype=jnp.int32) // tpr
        eid = jnp.where(sel, idx, E)
        row_counts = jnp.zeros((n_rows, E + 1), jnp.int32).at[
            jnp.broadcast_to(rid[:, None], (T, k)), eid].add(1)[:, :E]
    return y, counts_l, full_counts.astype(jnp.int32), aux_loss, dropped, \
        row_counts, active_experts, pad_ratio


def moe_apply(params: Dict, bank: Union[Dict, ExpertBankQ], x: jax.Array,
              cfg: MoEConfig, capacity: int,
              token_valid: Optional[jax.Array] = None,
              n_rows: Optional[int] = None,
              row_capacity: Optional[int] = None,
              dispatch: Optional[str] = None,
              gemm: Optional[str] = None) -> tuple[jax.Array, MoEAux]:
    """Single-device path. params: {'router', ['shared']}; x: (T, d).

    ``token_valid``/``n_rows``: see ``_moe_local`` — masked tokens are
    excluded from dispatch, capacity and every count; ``n_rows`` requests
    per-row (R, E) counts in ``MoEAux.row_counts``. ``row_capacity``
    normalizes the drop rule per row (batch-shape-independent drops;
    requires ``n_rows``). ``dispatch`` ∈ {padded, ragged} picks the token
    layout (None → ``kernels.ops.moe_dispatch_default()``); ``gemm`` ∈
    {jnp, pallas} the quantized-GEMM backend.
    """
    dist = _get_dist()
    if dist is not None:
        return _moe_apply_sharded(params, bank, x, cfg, capacity, dist,
                                  token_valid=token_valid, n_rows=n_rows,
                                  row_capacity=row_capacity,
                                  dispatch=dispatch, gemm=gemm)
    if dispatch is None:
        dispatch = kops.moe_dispatch_default()
    y, counts, _full, aux_loss, dropped, row_counts, active, padr = \
        _moe_local(params, bank, x, cfg, capacity, 0, cfg.num_experts,
                   token_valid=token_valid, n_rows=n_rows,
                   row_capacity=row_capacity, dispatch=dispatch, gemm=gemm)
    if "shared" in params:
        y = y + swiglu(params["shared"], x)
    return y, MoEAux(counts=counts, aux_loss=aux_loss, dropped=dropped,
                     row_counts=row_counts, active_experts=active,
                     dispatch_pad_ratio=padr)


def _get_dist():
    try:
        from repro.launch.dist import get_dist
        return get_dist()
    except ImportError:  # pragma: no cover
        return None


def _moe_apply_sharded(params, bank, x, cfg: MoEConfig, capacity, dist,
                       token_valid=None, n_rows=None, row_capacity=None,
                       dispatch=None, gemm=None):
    """shard_map expert parallelism (see module docstring).

    Two sharded regimes, chosen statically at trace time:

    * **EP ragged** (``dist.tokens_ep_sharded`` + ragged dispatch): tokens
      shard over the data AND model axes (every device owns a token slice
      plus its E/n experts). Each shard routes its local tokens, compacts
      the kept assignments per destination expert-shard (the stable
      sort-by-expert already groups destinations contiguously), exchanges a
      statically-bounded bm-aligned payload with ONE ``all_to_all`` each
      way (per-(dest, expert) counts ride a second tiny one), runs the
      grouped ragged kernel on its local experts at their resident tier
      (local hi-slot slice), and combines with the router gates back on the
      sender — the same per-token scatter-add order and dtype as the
      single-device ragged path, so drop-free regimes (decode, and any
      ``row_capacity`` run) are bit-identical per token. When the global
      per-expert ``capacity`` binds, drops apply per (expert, sender) at
      ``ep_cap_shard`` — the same 1/n slicing the padded dp body already
      does — so heavy prefill overflow degrades the same way it always has.
    * **padded** (everything else): each data shard routes its own tokens,
      each model shard computes its local experts into the fixed (E, C, d)
      buffer, partial outputs psum over the model axis. The reference — and
      the fallback whenever the EP layout can't hold statically (tokens not
      divisible over the token shards, unsharded hi pool, padded dispatch).

    ``token_valid`` shards alongside ``x`` and masks dispatch exactly like
    the single-device path. ``n_rows`` produces ``MoEAux.row_counts`` with
    the row dim sharded like the tokens (EP: data×model; padded: data when
    the rows divide, else replicated) — the engine's hotness/telemetry
    signal no longer goes dark under a mesh. ``row_capacity`` keeps its
    per-row drop rule exactly: rows never straddle a token shard.

    The bank is decomposed into plain dicts around the shard_map boundary
    (PartitionSpec trees must structurally match the args; custom pytree
    metadata like QuantizedTensor's logical shape changes under slicing)."""
    from jax.sharding import PartitionSpec as P

    mesh = dist.mesh
    mn = dist.model_size
    E = cfg.num_experts
    k = cfg.top_k
    T = x.shape[0]
    if dispatch is None:
        dispatch = kops.moe_dispatch_default()
    if E % mn:
        # Cannot expert-shard — run replicated (noted by the planner).
        y, counts, _f, aux, dropped, rc, act, padr = _moe_local(
            params, bank, x, cfg, capacity, 0, E, token_valid=token_valid,
            n_rows=n_rows, row_capacity=row_capacity, dispatch=dispatch,
            gemm=gemm)
        if "shared" in params:
            y = y + swiglu(params["shared"], x)
        return y, MoEAux(counts, aux, dropped, row_counts=rc,
                         active_experts=act, dispatch_pad_ratio=padr)
    e_local = E // mn
    is_q = isinstance(bank, ExpertBankQ)
    n_hi = bank.n_hi if is_q else 0
    hi_shard = n_hi > 0 and n_hi % mn == 0
    nh_local = n_hi // mn if hi_shard else None

    dp_n = 1
    for a in dist.dp_axes:
        dp_n *= mesh.shape[a]
    n_tok = dp_n * mn if dist.tokens_ep_sharded else dp_n

    # ---- EP ragged eligibility (static) ---------------------------------
    use_ep = (dist.tokens_ep_sharded and dispatch == "ragged"
              and T % n_tok == 0 and (T // n_tok) > 0
              and (n_hi == 0 or hi_shard))
    if row_capacity is not None and n_rows is None:
        raise ValueError("row_capacity needs n_rows")
    if use_ep and n_rows is not None:
        # Rows must tile exactly over the token shards for the per-row
        # drop rule / row_counts to stay local.
        use_ep = (T % n_rows == 0 and n_rows % n_tok == 0
                  and (T // n_tok) % (T // n_rows) == 0)

    # capacity was computed for global T and global E; the local shard keeps
    # the same per-expert expectation: T_loc·k·cf / E = capacity / dp_n.
    cap_local = max(8, (capacity // dp_n + 7) // 8 * 8) \
        if dist.tokens_dp_sharded else capacity

    # FF-slice over the idle data axis when tokens are replicated (batch-1
    # long-context decode) and every sliced dim divides: 2-D expert sharding.
    dp1 = None if not dist.dp_axes else \
        (dist.dp_axes if len(dist.dp_axes) > 1 else dist.dp_axes[0])
    ff_axis = None
    if is_q and not dist.tokens_dp_sharded and dp_n > 1 and not use_ep:
        f_dim = bank.lo["w_gate"].packed.shape[-1]
        d_dim = bank.lo["w_down"].packed.shape[-1]
        if f_dim % dp_n == 0 and d_dim % dp_n == 0:
            ff_axis = dp1

    # ---- flatten bank to plain dicts + spec trees -----------------------
    eshard = P("model")          # prefix spec: shard dim 0 (E / n_hi)
    repl = P()
    if is_q:
        flat = {f"lo_packed.{n}": qt.packed for n, qt in bank.lo.items()}
        flat.update({f"lo_scales.{n}": qt.scales for n, qt in bank.lo.items()})
        flat.update({f"hi.{n}": a for n, a in bank.hi.items()})
        flat["slot_owner"] = bank.slot_owner
        flat["slot_map"] = bank.slot_map
        meta = {n: (qt.bits, qt.group_size) for n, qt in bank.lo.items()}

        def spec_of(kk):
            he = eshard if hi_shard else repl
            if kk.startswith("slot"):
                return repl
            base = eshard if kk.startswith("lo_") else he
            if ff_axis is not None:   # slice the last (F or D-out) dim
                return P(*(tuple(base) + (None,) * (2 - len(tuple(base))) + (dp1,)))
            return base
        bank_spec = {kk: spec_of(kk) for kk in flat}
    else:
        flat = dict(bank)
        meta = None
        bank_spec = {kk: eshard for kk in flat}

    def rebuild(flat_l):
        if not is_q:
            return flat_l
        lo = {n: QuantizedTensorLike(flat_l[f"lo_packed.{n}"],
                                     flat_l[f"lo_scales.{n}"], *meta[n])
              for n in bank.lo}
        return ExpertBankQ(lo=lo, hi={n: flat_l[f"hi.{n}"] for n in bank.hi},
                           slot_owner=flat_l["slot_owner"],
                           slot_map=flat_l["slot_map"])

    params_spec = jax.tree_util.tree_map(lambda _: repl, params)

    if use_ep:
        return _moe_local_ep(params, flat, rebuild, x, cfg, capacity, dist,
                             token_valid, n_rows, row_capacity, gemm, mesh,
                             mn, n_tok, e_local, nh_local, is_q, params_spec,
                             bank_spec)

    x_spec = P(dist.dp_axes) if dist.tokens_dp_sharded else repl
    tv_spec = None if token_valid is None else x_spec

    # Row split for row_counts / row_capacity: rows follow the tokens, so
    # they only shard when they tile exactly over the dp shards.
    rows_split = dist.tokens_dp_sharded and dp_n > 1
    n_rows_loc = None
    if n_rows is not None:
        if not rows_split:
            n_rows_loc = n_rows
        elif (T % n_rows == 0 and n_rows % dp_n == 0
                and (T // dp_n) % (T // n_rows) == 0):
            n_rows_loc = n_rows // dp_n
        elif row_capacity is not None:
            raise ValueError(
                f"row_capacity requires rows to tile over the {dp_n} data "
                f"shards (T={T}, n_rows={n_rows})")
    want_rc = n_rows_loc is not None
    rc_spec = P(dist.dp_axes, None) if (want_rc and rows_split) else repl

    def body(params_l, flat_l, x_l, tv_l):
        j = jax.lax.axis_index(dist.model_axis)
        e_off = j * e_local
        slot_lo = (j * nh_local) if hi_shard else 0
        y, counts_l, _full, aux, dropped, rc, _a, padr = _moe_local(
            params_l, rebuild(flat_l), x_l, cfg, cap_local, e_off, e_local,
            slot_lo=slot_lo, n_slot_local=nh_local, ff_axis=ff_axis,
            token_valid=tv_l, n_rows=n_rows_loc, row_capacity=row_capacity,
            dispatch=dispatch, gemm=gemm)
        y = jax.lax.psum(y, dist.model_axis)
        if ff_axis is not None:   # y is D-sliced over data: gather (tiny)
            y = jax.lax.all_gather(y, ff_axis, axis=1, tiled=True)
        if "shared" in params_l:
            y = y + swiglu(params_l["shared"], x_l)
        # Global hotness counts: place the local expert slice, reduce over
        # model (expert partition) and data (token partition).
        counts = jnp.zeros((cfg.num_experts,), jnp.int32)
        counts = jax.lax.dynamic_update_slice(counts, counts_l, (e_off,))
        counts = jax.lax.psum(counts, dist.model_axis)
        if dist.tokens_dp_sharded and dist.dp_axes:
            counts = jax.lax.psum(counts, dist.dp_axes)
            aux = jax.lax.pmean(aux, dist.dp_axes)
            dropped = jax.lax.pmean(dropped, dist.dp_axes)
            padr = jax.lax.pmean(padr, dist.dp_axes)
        dropped = jax.lax.pmean(dropped, dist.model_axis)
        padr = jax.lax.pmean(padr, dist.model_axis)
        if not want_rc:
            return y, counts, aux, dropped, padr
        # Each model shard only sees its own experts' assignments — the
        # psum fills in the rest; rows stay local to their dp shard.
        rc = jax.lax.psum(rc, dist.model_axis)
        if not rows_split and dist.dp_axes and dist.tokens_dp_sharded:
            rc = jax.lax.psum(rc, dist.dp_axes)
        return y, counts, aux, dropped, padr, rc

    out_specs = (x_spec, repl, repl, repl, repl) + \
        ((rc_spec,) if want_rc else ())
    res = shard_map(
        body, mesh=mesh,
        in_specs=(params_spec, bank_spec, x_spec, tv_spec),
        out_specs=out_specs,
        check_vma=False,
    )(params, flat, x, token_valid)
    y, counts, aux, dropped, padr = res[:5]
    rc = res[5] if want_rc else None
    active = jnp.sum((counts > 0).astype(jnp.int32))
    return y, MoEAux(counts=counts, aux_loss=aux, dropped=dropped,
                     row_counts=rc, active_experts=active,
                     dispatch_pad_ratio=padr)


def _moe_local_ep(params, flat, rebuild, x, cfg: MoEConfig, capacity, dist,
                  token_valid, n_rows, row_capacity, gemm, mesh, mn, n_tok,
                  e_local, nh_local, is_q, params_spec, bank_spec):
    """The EP ragged all-to-all pipeline (see ``_moe_apply_sharded``).

    Wire protocol per shard pair: a (mn·S, d) row payload — block ``s`` of
    the send buffer holds the rows destined for shard ``s``, bm-aligned
    budget ``S`` rows each (``ep_payload_rows``) — and an (mn, e_local)
    count matrix whose row ``s`` says how many of those rows belong to each
    of shard ``s``'s experts, in expert order. After the exchange the
    receiver rebuilds per-expert segments with the SAME ``_sort_routing``
    contract the single-device path compiles (stable by (expert, source,
    send order)), feeds the grouped kernel, and the result rows ride the
    inverse route home."""
    from jax.sharding import PartitionSpec as P

    E, kk = cfg.num_experts, cfg.top_k
    T, _d = x.shape
    bm = RAGGED_BM
    T_l = T // n_tok
    n_rows_l = None if n_rows is None else n_rows // n_tok
    cap_shard = None if row_capacity is not None else ep_cap_shard(capacity,
                                                                   n_tok)
    S = ep_payload_rows(T, kk, e_local, capacity, n_tok, bm=bm,
                        n_rows=n_rows, row_capacity=row_capacity)
    tok_axes = tuple(dist.dp_axes) + (dist.model_axis,)
    x_spec = P(tok_axes)
    tv_spec = None if token_valid is None else x_spec
    want_rc = n_rows is not None
    repl = P()

    def body(params_l, flat_l, x_l, tv_l):
        bank_l = rebuild(flat_l)
        j = jax.lax.axis_index(dist.model_axis)
        d = x_l.shape[1]
        gates, idx, probs = route(params_l["router"], x_l, cfg)
        if tv_l is not None:
            idx_v = jnp.where(tv_l[:, None], idx, E)
            gates_v = jnp.where(tv_l[:, None], gates, 0.0)
        else:
            idx_v, gates_v = idx, gates

        # -- sender: sort by GLOBAL expert id (= grouped by destination
        # shard, experts ascending within each destination) and compact the
        # kept assignments into the per-destination payload blocks.
        order, sorted_eid, counts_l, pos_in_e, tok = _sort_routing(idx_v, E)
        kept = _keep_mask(sorted_eid, pos_in_e, tok, E,
                          cap_shard if cap_shard is not None else 0,
                          row_capacity, n_rows_l, T_l)
        dest = jnp.where(sorted_eid < E, sorted_eid // e_local, mn)
        kept_i = kept.astype(jnp.int32)
        inc = jnp.cumsum(kept_i)
        kept_d = jnp.zeros((mn + 1,), jnp.int32).at[dest].add(kept_i)
        dstart = jnp.cumsum(kept_d) - kept_d
        offs = inc - 1 - dstart[dest]          # rank among kept, within dest
        send_row = jnp.where(kept, dest * S + offs, mn * S)  # OOB ⇒ dropped
        send = jnp.zeros((mn * S, d), x_l.dtype).at[send_row].set(
            x_l[tok], mode="drop")
        cnt_send = jnp.zeros((E + 1,), jnp.int32).at[
            jnp.where(kept, sorted_eid, E)].add(1)[:E].reshape(mn, e_local)

        def a2a(v):
            return jax.lax.all_to_all(v, dist.model_axis, 0, 0, tiled=True)

        recv = a2a(send)          # (mn·S, d): block s ← source shard s
        cnt_recv = a2a(cnt_send)  # (mn, e_local): row s ← source shard s

        # -- receiver: per-row local expert id from the count boundaries
        # (payload rows past a block's total → e_local sentinel), then the
        # standard ragged compaction over the local experts.
        r = jnp.arange(mn * S, dtype=jnp.int32)
        src = r // S
        cum = jnp.cumsum(cnt_recv, axis=1)
        eid_r = jnp.sum(((r % S)[:, None] >= cum[src]).astype(jnp.int32),
                        axis=1)
        order_r, sorted_re, cnt_e, pos_re, rrow = _sort_routing(
            eid_r[:, None], e_local)
        astart, tile_eid, n_tiles = ragged_tile_map(cnt_e, bm, mn * S)
        R = tile_eid.shape[0] * bm
        safe_e = jnp.minimum(sorted_re, e_local - 1)
        rowpos = jnp.where(sorted_re < e_local, astart[safe_e] + pos_re, R)
        xs = jnp.zeros((R, d), x_l.dtype).at[rowpos].set(recv[rrow],
                                                         mode="drop")
        if is_q:
            if nh_local:
                # Local hi-slot slice: slot g = j·nh_local + s lives here;
                # owners are global expert positions.
                owner = jax.lax.dynamic_slice_in_dim(
                    bank_l.slot_owner, j * nh_local, nh_local)
                owner_l = owner - j * e_local
                eff = jnp.full((e_local + 1,), -1, jnp.int32).at[
                    jnp.where((owner_l >= 0) & (owner_l < e_local),
                              owner_l, e_local)].set(
                    jnp.arange(nh_local, dtype=jnp.int32),
                    mode="drop")[:e_local]
                tile_slot = eff[tile_eid]
                hi_l = bank_l.hi
            else:
                tile_slot = jnp.full_like(tile_eid, -1)
                hi_l = None
            y_rows = kops.ragged_quant_ffn_op(
                xs, tile_eid, tile_slot, bank_l.lo, hi_l,
                bits=bank_l.lo["w_gate"].bits,
                group=bank_l.lo["w_gate"].group_size, bm=bm, backend=gemm)
        else:
            y_rows = kops.ragged_dense_ffn_op(xs, tile_eid, bank_l, bm=bm,
                                              backend=gemm)
        D = y_rows.shape[-1]
        back = jnp.where((sorted_re < e_local)[:, None],
                         y_rows[jnp.minimum(rowpos, R - 1)], 0)
        y_recv = jnp.zeros((mn * S, D), x_l.dtype).at[rrow].set(back)

        # -- home: block d of the return exchange is MY rows' results from
        # shard d, at the offsets I sent them at.
        y_ret = a2a(y_recv)
        y_asn = y_ret[jnp.minimum(send_row, mn * S - 1)]
        gate_sorted = gates_v.reshape(-1)[order].astype(x_l.dtype)
        contrib = jnp.where(kept[:, None], y_asn * gate_sorted[:, None], 0)
        y = jnp.zeros((T_l, D), x_l.dtype).at[tok].add(contrib)
        if "shared" in params_l:
            y = y + swiglu(params_l["shared"], x_l)

        # -- exact global telemetry (counts keyed by global expert already)
        counts = jax.lax.psum(counts_l.astype(jnp.int32), tok_axes)
        routed = jax.lax.psum(
            jnp.sum((sorted_eid < E).astype(jnp.float32)), tok_axes)
        kept_g = jax.lax.psum(jnp.sum(kept.astype(jnp.float32)), tok_axes)
        dropped = 1.0 - kept_g / jnp.maximum(routed, 1.0)
        padr = jax.lax.pmean(
            1.0 - jnp.sum(cnt_e).astype(jnp.float32)
            / jnp.maximum(n_tiles * bm, 1).astype(jnp.float32), tok_axes)
        # Load-balance aux from globally psum'd routing stats — same value
        # the single-device formula produces.
        if tv_l is None:
            full_idx = jnp.clip(idx.reshape(-1), 0, E)
            n_val = jnp.float32(T_l)
            sum_prob = jnp.sum(probs, axis=0)
        else:
            full_idx = jnp.where(tv_l[:, None], jnp.clip(idx, 0, E),
                                 E).reshape(-1)
            n_val = jnp.sum(tv_l).astype(jnp.float32)
            sum_prob = jnp.sum(probs * tv_l[:, None].astype(jnp.float32),
                               axis=0)
        full_counts = jax.lax.psum(
            jnp.zeros((E + 1,), jnp.int32).at[full_idx].add(1)[:E], tok_axes)
        n_val = jax.lax.psum(n_val, tok_axes)
        sum_prob = jax.lax.psum(sum_prob, tok_axes)
        mean_prob = sum_prob / jnp.maximum(n_val, 1.0)
        frac = full_counts.astype(jnp.float32) / jnp.maximum(n_val * kk, 1.0)
        aux = cfg.router_aux_coef * E * jnp.sum(frac * mean_prob)
        if not want_rc:
            return y, counts, aux, dropped, padr
        tpr = T // n_rows
        rid = jnp.arange(T_l, dtype=jnp.int32) // tpr
        rc = jnp.zeros((n_rows_l, E + 1), jnp.int32).at[
            jnp.broadcast_to(rid[:, None], (T_l, kk)), idx_v].add(1)[:, :E]
        return y, counts, aux, dropped, padr, rc

    out_specs = (x_spec, repl, repl, repl, repl) + \
        ((P(tok_axes, None),) if want_rc else ())
    res = shard_map(
        body, mesh=mesh,
        in_specs=(params_spec, bank_spec, x_spec, tv_spec),
        out_specs=out_specs,
        check_vma=False,
    )(params, flat, x, token_valid)
    y, counts, aux, dropped, padr = res[:5]
    rc = res[5] if want_rc else None
    active = jnp.sum((counts > 0).astype(jnp.int32))
    return y, MoEAux(counts=counts, aux_loss=aux, dropped=dropped,
                     row_counts=rc, active_experts=active,
                     dispatch_pad_ratio=padr)


class QuantizedTensorLike(NamedTuple):
    """Local-shard view of a QuantizedTensor inside shard_map (plain tuple:
    no global-shape metadata to go stale)."""
    packed: jax.Array
    scales: jax.Array
    bits: int
    group_size: int


def moe_capacity(n_tokens: int, cfg: MoEConfig, factor: float | None = None) -> int:
    f = factor if factor is not None else cfg.capacity_factor
    cap = int(n_tokens * cfg.top_k * f / cfg.num_experts) + 1
    # Round up to a multiple of 8 for friendlier tiling/sharding.
    return max(8, (cap + 7) // 8 * 8)


def ep_cap_shard(capacity: int, n_token_shards: int) -> int:
    """Per-(expert, sender) capacity slice under EP token sharding: the
    global per-expert ``capacity`` split evenly over the senders, floored
    at 8 so small-batch decode (where a sender holds ≤ a handful of tokens)
    is always drop-free — the same 1/n scaling (and floor) the padded dp
    body applies to its local capacity."""
    return max(8, (-(-capacity // n_token_shards) + 7) // 8 * 8)


def ep_payload_rows(n_tokens: int, top_k: int, e_local: int, capacity: int,
                    n_token_shards: int, bm: int = RAGGED_BM,
                    n_rows: Optional[int] = None,
                    row_capacity: Optional[int] = None) -> int:
    """Static per-destination row budget ``S`` of the EP all-to-all payload.

    A sender can forward at most min(its local assignments, what one
    destination can keep) rows to any one shard: ``T_l·k`` assignments
    total, and per destination ``e_local`` experts × the per-sender keep
    bound (``ep_cap_shard``, or ``rows_l·row_capacity`` under the per-row
    rule). The bm round-up keeps the exchanged buffer tile-aligned for the
    grouped kernel on the receiver. This is also the bytes-moved model the
    ``ep_scaling`` benchmark reports: each shard moves ``2·(mn−1)·S·d``
    payload elements per MoE layer (out and back), independent of the
    global batch — vs. the replicated baseline's ``2·(mn−1)/mn·T·d`` psum."""
    t_l = n_tokens // n_token_shards
    if row_capacity is not None:
        per_dest = e_local * (n_rows // n_token_shards) * row_capacity
    else:
        per_dest = e_local * ep_cap_shard(capacity, n_token_shards)
    s = min(t_l * top_k, per_dest)
    return max(bm, -(-s // bm) * bm)
