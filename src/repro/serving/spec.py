"""Self-speculative decoding on the low-precision fallback tier.

DynaExq keeps an always-resident lo-precision copy of every expert — the
fallback the hi pool demotes onto. That tier is also a free draft model:
running the whole MoE with **all-lo expert banks** is exactly the cheap
approximate forward speculative decoding needs, so quantization buys
throughput, not just footprint. No draft weights are materialized anywhere:
the draft bank reuses the target ``ExpertBankQ`` buffers with every
``slot_owner`` pointed at -1 (lo fallback), which keeps the same pytree
structure and therefore reuses the already-compiled decode executables.

One speculative round per engine step:

1. **draft** — ``k`` greedy tokens per row from ONE dispatch
   (``models.spec_draft``: chained decode steps under a ``lax.scan``) with
   the all-lo banks;
2. **verify** — all ``k+1`` positions in ONE multi-token dispatch
   (``models.spec_verify``) against the mixed-precision banks. Each verify
   position runs the *decode-step math itself* (same attention reduction,
   same per-step MoE capacity), so a verified prefix is bit-identical to
   what the non-speculative engine would have computed — token parity by
   construction, the same way paged attention shares ``_attend_cache`` with
   the dense path;
3. **accept** — standard rejection sampling against each request's
   ``SamplingParams`` (greedy draft ⇒ accept probability ``p(d)``, residual
   ``p`` with ``d`` removed), so the output distribution provably matches
   the target model; ``temperature == 0`` degenerates to exact
   argmax-agreement and the emitted tokens equal the non-speculative
   greedy path's;
4. **rewind** — rejected positions roll back: per-lease write positions
   retreat, paged blocks that only ever held rejected positions return to
   the pool (``KVLease.unwind``, COW-safe), sliding-window ring slots
   restore their pre-burst contents from a snapshot, and mamba recurrent
   state rolls back to the last accepted step via the per-step states the
   verify scan stacked (snapshot/restore around the draft burst keeps
   mixed mamba+attention stacks exact).

Hotness hygiene: ONLY verify-pass router counts for ACCEPTED steps reach
``backend.observe`` — draft traffic and rejected positions never distort
promotion decisions.

Draft depth adapts from each REQUEST's own acceptance-rate EMA over a
power-of-two ladder (compiles stay O(log k_max), the bucket idiom admission
uses). Row-local adaptation is a determinism guarantee, not just a tuning
choice: a request's burst boundaries — and therefore which counter-keyed
PRNG draws its sampled decode consumes — depend only on its own history,
never on which neighbors share the batch.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.ver import ExpertBankQ
from repro.models import spec_draft, spec_verify
from repro.models.model import DecodeCaches
from repro.serving.sampler import (STREAM_ACCEPT, STREAM_BONUS,
                                   STREAM_RESIDUAL, RequestSampler,
                                   categorical, sampling_probs)


# Module-level jits with the frozen ArchConfig static, like the engine's
# decode wrappers: every engine for the same config shares compilations.
# None of these donate their cache operands — the round holds live
# references (the SSM snapshot aliases the pre-draft caches, the engine's
# ``self.caches`` still points at them until the round commits), and a
# donated buffer dies even while referenced.

@functools.partial(jax.jit, static_argnames=("cfg", "capacity_factor",
                                             "moe_dispatch", "row_capacity"))
def _draft_jit(params, token, pos, caches, banks, row_valid, *, cfg,
               capacity_factor, moe_dispatch=None, row_capacity=None):
    return spec_draft(params, cfg, token, pos, caches, row_valid, bank=banks,
                      capacity_factor=capacity_factor,
                      moe_dispatch=moe_dispatch, row_capacity=row_capacity)


@functools.partial(jax.jit, static_argnames=("cfg", "capacity_factor",
                                             "moe_dispatch", "row_capacity"))
def _draft_paged_jit(params, token, pos, caches, banks, row_valid, table,
                     wblk, woff, *, cfg, capacity_factor,
                     moe_dispatch=None, row_capacity=None):
    return spec_draft(params, cfg, token, pos, caches, row_valid, bank=banks,
                      capacity_factor=capacity_factor,
                      paged={"table": table, "write_blk": wblk,
                             "write_off": woff},
                      moe_dispatch=moe_dispatch, row_capacity=row_capacity)


@functools.partial(jax.jit, static_argnames=("cfg", "capacity_factor",
                                             "moe_dispatch", "row_capacity"))
def _verify_jit(params, tokens, pos, caches, banks, row_valid, *, cfg,
                capacity_factor, moe_dispatch=None, row_capacity=None):
    return spec_verify(params, cfg, tokens, pos, caches, row_valid,
                       bank=banks, capacity_factor=capacity_factor,
                       moe_dispatch=moe_dispatch, row_capacity=row_capacity)


@functools.partial(jax.jit, static_argnames=("cfg", "capacity_factor",
                                             "moe_dispatch", "row_capacity"))
def _verify_paged_jit(params, tokens, pos, caches, banks, row_valid, table,
                      wblk, woff, *, cfg, capacity_factor,
                      moe_dispatch=None, row_capacity=None):
    return spec_verify(params, cfg, tokens, pos, caches, row_valid,
                       bank=banks, capacity_factor=capacity_factor,
                       paged={"table": table, "write_blk": wblk,
                              "write_off": woff},
                       moe_dispatch=moe_dispatch, row_capacity=row_capacity)


# ---- cache-slot snapshot / restore ---------------------------------------
# A draft/verify burst writes cache slots for positions the round may
# REJECT. In a ring cache those writes clobber still-valid old positions
# (slot = pos % C); in a DENSE full cache a row riding past its own depth
# (or its sequence cap) can wrap ``(pos + j) % C`` onto live low slots the
# same way. So every dense attention cache (full and ring alike) snapshots
# the lanes the burst will write and restores (a) ALL lanes between draft
# and verify — verify must read pre-burst contents through its per-step
# validity masks — and (b) every non-accepted lane after acceptance. Paged
# mode only needs this for sliding-window stacks: full-attention paged
# writes go to fresh private blocks (beyond-depth lanes target the trash
# block), and slots past the accepted position are masked out of every
# later read until their rightful token overwrites them.

@jax.jit
def _gather_dense_slots(blocks: Dict, slots):
    """blocks: {pos: KVCache((nsb, B, Hkv, C, hd))}; slots: (B, W) →
    snapshots (nsb, B, Hkv, W, hd) per leaf."""
    def one(a):
        nsb, B, Hkv, _, hd = a.shape
        idx = jnp.broadcast_to(slots[None, :, None, :, None],
                               (nsb, B, Hkv, slots.shape[1], hd))
        return jnp.take_along_axis(a, idx, axis=3)
    return jax.tree_util.tree_map(one, blocks)


@jax.jit
def _restore_dense_slots(blocks: Dict, snap: Dict, slots, mask):
    """Write ``snap`` back into ``slots`` where ``mask`` ((B, W) bool);
    unmasked lanes keep the cache's current value."""
    def one(a, s):
        nsb, B, Hkv, _, hd = a.shape
        W = slots.shape[1]
        idx = jnp.broadcast_to(slots[None, :, None, :, None],
                               (nsb, B, Hkv, W, hd))
        cur = jnp.take_along_axis(a, idx, axis=3)
        vals = jnp.where(mask[None, :, None, :, None], s, cur)
        x = jnp.transpose(vals, (1, 3, 0, 2, 4))        # (B, W, nsb, Hkv, hd)
        b = jnp.arange(B)[:, None]
        return a.at[:, b, :, slots].set(x)
    return jax.tree_util.tree_map(one, blocks, snap)


@jax.jit
def _gather_paged_lanes(blocks: Dict, blk, off):
    """blocks: {pos: PagedKVCache((nsb, N, Hkv, bt, hd))}; blk/off: (B, W)
    physical lanes → snapshots (B, W, nsb, Hkv, hd) per leaf."""
    return jax.tree_util.tree_map(lambda a: a[:, blk, :, off], blocks)


@jax.jit
def _restore_paged_lanes(blocks: Dict, snap: Dict, blk, off, mask):
    def one(a, s):
        cur = a[:, blk, :, off]
        vals = jnp.where(mask[:, :, None, None, None], s, cur)
        return a.at[:, blk, :, off].set(vals)
    return jax.tree_util.tree_map(one, blocks, snap)


@jax.jit
def _select_ssm(stacked: Dict, sel):
    """Per-row rollback of recurrent state: stacked leaves (S, nsb, B, ...)
    from the verify scan, ``sel`` (B,) the per-row accepted step index →
    (nsb, B, ...) leaves holding each row's state after its last accepted
    token."""
    def one(st):
        out = st[sel, :, jnp.arange(sel.shape[0])]       # (B, nsb, ...)
        return jnp.moveaxis(out, 0, 1)
    return jax.tree_util.tree_map(one, stacked)


def all_lo_banks(banks, cache: Dict):
    """Derive the draft banks: the SAME lo/hi buffers with every hi slot
    disowned, so every expert serves from the always-resident lo tier.
    ``cache`` memoizes the constant all(-1) owner arrays per MoE position
    (bank objects are mutated in place by the transition manager, so the
    derivation re-reads them every round — it is a handful of array refs)."""
    if banks is None:
        return None
    out = {}
    for k, b in banks.items():
        if isinstance(b, ExpertBankQ):
            neg = cache.get(k)
            if neg is None:
                neg = cache[k] = jnp.full_like(b.slot_owner, -1)
            out[k] = dataclasses.replace(b, slot_owner=neg)
        else:
            out[k] = b
    return out


def accept_burst(sampler: RequestSampler, drafts: np.ndarray,
                 target_logits: Optional[np.ndarray],
                 target_top: Optional[np.ndarray] = None
                 ) -> Tuple[int, List[int]]:
    """Rejection-sample one row's burst. ``drafts``: (d,) draft tokens;
    ``target_logits``: (d+1, V) f32 verify logits (``target_logits[j]`` is
    the target distribution for the token after consuming ``drafts[:j]``).
    A greedy request only needs ``target_top`` ((d+1,) device-side argmax
    of the verify logits) — the engine then never ships the full (W, B, V)
    logits to host on the greedy fast path.

    Returns ``(n_accepted, emitted)`` where ``emitted`` is the accepted
    prefix plus exactly one target-sampled token (the correction on
    rejection, the bonus on full acceptance) — so every round emits at
    least one token and the output distribution matches sampling from the
    target one token at a time. The draft proposal is greedy (a point mass
    at ``d``): accept with probability ``p(d)``; the residual is ``p`` with
    ``d`` removed, renormalized."""
    sp = sampler.sp
    d = int(drafts.shape[0])
    out: List[int] = []
    a = 0
    if sp.greedy:
        if target_top is None:
            target_top = np.argmax(target_logits, axis=-1)
        for j in range(d):
            t = int(target_top[j])
            out.append(t)
            if t != int(drafts[j]):
                return a, out                      # correction token
            a += 1
        out.append(int(target_top[d]))             # bonus token
        return a, out
    rnd = sampler.spec_round
    for j in range(d):
        p = sampling_probs(target_logits[j], sp)
        dj = int(drafts[j])
        if sampler.uniform(STREAM_ACCEPT, rnd, j) < p[dj]:
            out.append(dj)
            a += 1
            continue
        q = p.copy()
        q[dj] = 0.0
        s = q.sum()
        if s <= 0.0:                               # p was (numerically) 1_d
            masked = np.array(target_logits[j], np.float64)
            masked[dj] = -np.inf
            out.append(int(np.argmax(masked)))
        else:
            out.append(categorical(q / s,
                                   sampler.uniform(STREAM_RESIDUAL, rnd, j)))
        return a, out
    out.append(categorical(sampling_probs(target_logits[d], sp),
                           sampler.uniform(STREAM_BONUS, rnd)))
    return a, out


class SpecDecoder:
    """Per-engine speculative-decoding orchestrator (built by the engine
    when ``EngineConfig.spec_k > 0``). Owns the adaptive draft depth, the
    draft-bank derivation, and the round statistics the engine surfaces
    through ``stats()``."""

    def __init__(self, engine):
        self.eng = engine
        k_max = int(engine.ecfg.spec_k)
        if engine._attn_pos and engine.cfg.attn.sliding_window is not None:
            # A burst that wraps the ring would overwrite its own accepted
            # slots; keep the whole burst inside one window.
            k_max = min(k_max, engine._C_attn - 1)
        self.k_max = max(1, k_max)
        ladder, v = [], 1
        while v < self.k_max:
            ladder.append(v)
            v *= 2
        ladder.append(self.k_max)
        self.ladder = ladder                       # power-of-two k buckets
        self.adaptive = bool(engine.ecfg.spec_adaptive)
        self.ema_alpha = 0.25
        self.ema = 0.75                            # aggregate (telemetry)
        self._neg_owner_cache: Dict = {}
        self.rounds = 0
        self.row_rounds = 0              # (round, active row) pairs
        self.draft_total = 0
        self.accepted_total = 0
        self.verified_total = 0

    # ------------------------------------------------------------------
    def _pick_k(self, ema: float) -> int:
        """Largest ladder depth an acceptance EMA supports: the expected
        accepted run of a per-token acceptance rate r is r/(1-r) — there is
        no point drafting much deeper than the run that survives. Depth is
        chosen from each REQUEST's own EMA (``handle.spec_ema``): row-local
        adaptation keeps a request's burst boundaries — and therefore its
        sampling-PRNG stream consumption — independent of batch
        composition."""
        target = ema / max(1e-6, 1.0 - ema)
        k = 1
        for v in self.ladder:
            if v <= max(1.0, target):
                k = v
        return min(k, self.k_max)

    def stats(self) -> Dict[str, float]:
        return {
            "spec_rounds": float(self.rounds),
            "spec_row_rounds": float(self.row_rounds),
            "draft_tokens": float(self.draft_total),
            "verified_tokens": float(self.verified_total),
            "accept_rate": (self.accepted_total / self.draft_total)
            if self.draft_total else 0.0,
        }

    # ------------------------------------------------------------------
    def round(self, active, finished) -> bool:
        """Run one draft/verify round over the active rows. Returns False
        (caller falls back to the plain single-token step) when no row has
        speculation headroom — e.g. every request needs just one more
        token, or sits one position from its sequence cap."""
        eng = self.eng
        B = eng.ecfg.max_slots
        depth = np.zeros(B, np.int64)
        for i, h in active:
            rem = h.request.max_new_tokens - len(h.tokens)
            k_h = self._pick_k(h.spec_ema) if self.adaptive else self.k_max
            depth[i] = max(0, min(k_h, rem - 1,
                                  eng.ecfg.max_len - 1 - int(eng.pos[i])))
        k = int(depth.max())
        if k <= 0:
            return False
        # Round the scan length UP to the ladder so the draft/verify
        # executables only ever compile at O(log k_max) shapes — per-row
        # clamps (a request nearing its token budget) would otherwise leak
        # arbitrary k values into fresh whole-model compilations. The
        # step-validity mask neutralizes the padded steps, the same way
        # admission bucketing pads prompts.
        k = next(v for v in self.ladder if v >= k)
        W = k + 1
        # The round may cover a SUBSET of occupied rows (the scheduler's
        # spec tier): row_valid masks dispatch/counts to the rows this
        # round advances, while ``occupied`` — every slot holding a request,
        # active in this round or not — drives the snapshot/restore masks:
        # a burst lane that wraps the ring can clobber ANOTHER group's live
        # slot, so non-active occupied rows restore all their lanes.
        row_valid = np.zeros(B, bool)
        for i, _ in active:
            row_valid[i] = True
        occupied = np.asarray([h is not None for h in eng.slots], bool)
        # Step j of the burst is real for row i iff j <= depth[i]; rows past
        # their depth (and vacant rows) ride along masked out of MoE
        # dispatch and every count.
        step_valid = row_valid[None, :] & \
            (np.arange(W)[:, None] <= depth[None, :])
        t0 = time.perf_counter()
        pos0 = eng.pos.copy()

        # ---- resolve paged write lanes up front (alloc + COW) ----------
        table = wblk = woff = None
        fresh: Dict[int, List[int]] = {}
        if eng.pool is not None:
            wblk = np.zeros((W, B), np.int32)      # beyond-depth → trash
            woff = np.zeros((W, B), np.int32)
            cows: List[Tuple[int, int]] = []
            for i, h in active:
                seen: List[int] = []
                for j in range(int(depth[i]) + 1):
                    p = int(pos0[i]) + j
                    jb = (p % eng._C_pad) // eng._bt
                    was_free = int(h.lease.table[jb]) < 0
                    wblk[j, i], woff[j, i] = eng._ensure_write(
                        h.lease, p, cows)
                    if was_free and jb not in seen:
                        seen.append(jb)
                fresh[i] = seen
            eng._apply_copies(cows)
            table = eng._block_tables()

        # ---- snapshots --------------------------------------------------
        # Dense caches always snapshot (a burst lane can wrap onto a live
        # slot whenever a row rides past its own depth or sequence cap);
        # paged pools only for sliding-window rings (full-attention paged
        # lanes target fresh private blocks or the trash block).
        restore = bool(eng._attn_pos) and \
            (eng.pool is None or eng.cfg.attn.sliding_window is not None)
        snap = slots_bw = blk_bw = off_bw = None
        if restore:
            attn_now = {p: eng.caches.blocks[p] for p in eng._attn_pos}
            if eng.pool is not None:
                blk_bw = jnp.asarray(np.ascontiguousarray(wblk.T))
                off_bw = jnp.asarray(np.ascontiguousarray(woff.T))
                snap = _gather_paged_lanes(attn_now, blk_bw, off_bw)
            else:
                C = eng._C_attn
                slots_bw = jnp.asarray(
                    ((pos0[:, None] + np.arange(W)[None, :]) % C)
                    .astype(np.int32))
                snap = _gather_dense_slots(attn_now, slots_bw)
        # Mamba state snapshot is free: jax arrays are immutable, holding
        # the pre-burst references IS the snapshot.
        ssm_snap = {p: eng.caches.blocks[p] for p in eng._mamba_pos}

        # ---- draft: k chained greedy steps, all-lo banks, one dispatch --
        # The draft rides the SAME dispatch layout as the target decode:
        # under "ragged" every draft step streams only active experts' lo
        # codes through the fused kernel — no separate all-lo GEMM path.
        dbanks = all_lo_banks(eng.banks, self._neg_owner_cache)
        cf = eng.ecfg.capacity_factor
        md = eng.moe_dispatch
        rc = eng._row_cap_decode
        if eng.pool is not None:
            drafted_dev, caches = _draft_paged_jit(
                eng.params, jnp.asarray(eng.tokens), jnp.asarray(pos0),
                eng.caches, dbanks, jnp.asarray(step_valid[1:]),
                jnp.asarray(table), jnp.asarray(wblk[:k]),
                jnp.asarray(woff[:k]), cfg=eng.cfg, capacity_factor=cf,
                moe_dispatch=md, row_capacity=rc)
        else:
            drafted_dev, caches = _draft_jit(
                eng.params, jnp.asarray(eng.tokens), jnp.asarray(pos0),
                eng.caches, dbanks, jnp.asarray(step_valid[1:]),
                cfg=eng.cfg, capacity_factor=cf,
                moe_dispatch=md, row_capacity=rc)
        drafted = np.asarray(drafted_dev)          # (k, B)

        # ---- rewind the draft's side effects before verify --------------
        blocks = dict(caches.blocks)
        blocks.update(ssm_snap)                    # restore recurrent state
        caches = DecodeCaches(blocks=blocks, cross=None)
        if restore:
            all_mask = jnp.asarray(
                np.broadcast_to(occupied[:, None], (B, W)).copy())
            attn_sub = {p: caches.blocks[p] for p in eng._attn_pos}
            if eng.pool is not None:
                attn_sub = _restore_paged_lanes(attn_sub, snap, blk_bw,
                                                off_bw, all_mask)
            else:
                attn_sub = _restore_dense_slots(attn_sub, snap, slots_bw,
                                                all_mask)
            caches = DecodeCaches(blocks={**caches.blocks, **attn_sub},
                                  cross=None)

        # ---- verify: k+1 positions, target banks, one dispatch ----------
        vtoks = np.concatenate([eng.tokens[None, :], drafted], axis=0)
        if eng.pool is not None:
            logits_dev, caches, counts_dev, ssm_stack = _verify_paged_jit(
                eng.params, jnp.asarray(vtoks), jnp.asarray(pos0), caches,
                eng.banks, jnp.asarray(step_valid), jnp.asarray(table),
                jnp.asarray(wblk), jnp.asarray(woff), cfg=eng.cfg,
                capacity_factor=cf, moe_dispatch=md, row_capacity=rc)
        else:
            logits_dev, caches, counts_dev, ssm_stack = _verify_jit(
                eng.params, jnp.asarray(vtoks), jnp.asarray(pos0), caches,
                eng.banks, jnp.asarray(step_valid), cfg=eng.cfg,
                capacity_factor=cf, moe_dispatch=md, row_capacity=rc)
        logits_dev.block_until_ready()
        dt = time.perf_counter() - t0
        if eng.logits_hook is not None:
            eng.logits_hook("verify", logits_dev, list(active))
        # Greedy fast path: only the (W, B) device-side argmax crosses to
        # host; full (W, ·, V) f32 logits ship only for the rows that
        # genuinely sample (gathered on device first, so greedy neighbors
        # in a mixed batch stay off the transfer).
        top = np.asarray(jnp.argmax(logits_dev, -1), np.int32)   # (W, B)
        samp_rows = [i for i, h in active if not h.sampler.greedy]
        samp_logits: Dict[int, np.ndarray] = {}
        if samp_rows:
            sub = np.asarray(
                logits_dev[:, jnp.asarray(samp_rows, jnp.int32)])
            samp_logits = {i: sub[:, j] for j, i in enumerate(samp_rows)}

        # ---- rejection sampling per row ---------------------------------
        # -1 for rows outside this round: the occupied-row restore mask
        # (lane j restored iff j > accepts) then covers ALL their lanes.
        accepts = np.full(B, -1, np.int32)
        emitted: Dict[int, List[int]] = {}
        n_draft = 0
        n_accept = 0
        for i, h in active:
            d = int(depth[i])
            row_logits = samp_logits.get(i)
            a, toks = accept_burst(
                h.sampler, drafted[:d, i],
                None if row_logits is None else row_logits[:d + 1],
                target_top=top[:d + 1, i])
            h.sampler.end_round()
            accepts[i] = a
            emitted[i] = toks
            n_draft += d
            n_accept += a

        # ---- hotness: verify-pass counts of ACCEPTED steps only ---------
        counts_np = {kk: np.asarray(v) for kk, v in counts_dev.items()}
        eng._note_dispatch(counts_np)          # per-verify-step gauges
        accept_mask = row_valid[None, :] & \
            (np.arange(W)[:, None] <= accepts[None, :])        # (W, B)
        obs: Dict[str, np.ndarray] = {}
        for kk, v in counts_np.items():
            if v.ndim == 4:                        # (W, nsb, B, E)
                obs[kk] = (v * accept_mask[:, None, :, None]).sum(axis=0)
            else:                                  # aggregated fallback
                obs[kk] = v.sum(axis=0)
        stall = eng.backend.observe(obs, dt, prefill=False,
                                    row_valid=row_valid)
        eng._stall_clock += stall
        if stall:
            for _, h in active:
                h.stall_exposure_s += stall
        latency = dt + stall
        eng.decode_times.append(latency)
        eng._tpot_ema = latency if eng._tpot_ema == 0.0 else \
            0.9 * eng._tpot_ema + 0.1 * latency
        eng.last_row_counts = obs
        eng.last_counts = {kk: v.sum(axis=1) if v.ndim == 3 else v
                           for kk, v in obs.items()}

        # ---- roll recurrent state back to the last accepted step --------
        if eng._mamba_pos:
            sub = _select_ssm({p: ssm_stack[p] for p in eng._mamba_pos},
                              jnp.asarray(np.maximum(accepts, 0)))
            if bool(np.any(occupied & ~row_valid)):
                # Rows outside this round rode through the verify scan
                # masked — their recurrent state must come back from the
                # pre-round snapshot, not from any scan step.
                act = jnp.asarray(row_valid)
                sub = {
                    p: jnp.where(
                        act.reshape((1, -1) + (1,) * (sub[p].ndim - 2)),
                        sub[p], ssm_snap[p])
                    for p in eng._mamba_pos}
            caches = DecodeCaches(blocks={**caches.blocks, **sub},
                                  cross=None)

        # ---- restore non-accepted lanes ---------------------------------
        if restore:
            rej = jnp.asarray(occupied[:, None] &
                              (np.arange(W)[None, :] > accepts[:, None]))
            attn_sub = {p: caches.blocks[p] for p in eng._attn_pos}
            if eng.pool is not None:
                attn_sub = _restore_paged_lanes(attn_sub, snap, blk_bw,
                                                off_bw, rej)
            else:
                attn_sub = _restore_dense_slots(attn_sub, snap, slots_bw,
                                                rej)
            caches = DecodeCaches(blocks={**caches.blocks, **attn_sub},
                                  cross=None)
        eng.caches = caches

        # ---- release blocks that only held rejected positions -----------
        if eng.pool is not None and eng.cfg.attn.sliding_window is None:
            for i, h in active:
                new_pos = int(pos0[i]) + int(accepts[i]) + 1
                for jb in fresh.get(i, ()):
                    if jb * eng._bt >= new_pos:
                        h.lease.unwind(jb)

        # ---- emit + bookkeeping -----------------------------------------
        eng._tpot_sum += latency * len(active)
        kept_total = 0
        for i, h in active:
            toks = emitted[i]
            n_before = len(h.tokens)
            h.tokens.extend(toks)
            eng.tokens[i] = toks[-1]
            eng.pos[i] += int(accepts[i]) + 1
            # _done may TRUNCATE at a mid-burst EOS: only tokens that
            # survive count toward latency amortization and spec meters.
            done = eng._done(h)
            kept = len(h.tokens) - n_before
            kept_total += kept
            # The round's latency amortizes over every token it emitted
            # for this row — step_times stays per-TOKEN.
            h.step_times.extend([latency / max(1, kept)] * kept)
            if h.expert_counts is not None:
                for kk, v in counts_np.items():
                    if v.ndim == 4 and kk in h.expert_counts:
                        h.expert_counts[kk] += (
                            v[:, :, i].astype(np.int64) *
                            accept_mask[:, i][:, None, None]).sum(axis=0)
            d = int(depth[i])
            if d:
                # Row-local acceptance EMA → row-local draft depth.
                r = int(accepts[i]) / d
                h.spec_ema = (1 - self.ema_alpha) * h.spec_ema + \
                    self.ema_alpha * r
            if done:
                eng._finish(h, finished)
        eng._tpot_tokens += kept_total
        eng.counters["steps"] += 1
        self.rounds += 1
        self.row_rounds += len(active)
        self.draft_total += n_draft
        self.accepted_total += n_accept
        self.verified_total += kept_total
        if eng.tracer is not None:
            eng.tracer.instant("spec_round", cat="engine",
                               rows=len(active), drafted=int(n_draft),
                               accepted=int(n_accept),
                               emitted=int(kept_total))
        if n_draft:
            r = n_accept / n_draft
            self.ema = (1 - self.ema_alpha) * self.ema + self.ema_alpha * r
        return True
