"""Unified model builder covering all six assigned families.

A model is a stack of repeating *super-blocks* (one layer-kind pattern, e.g.
jamba = 7×mamba + 1×attn with MoE FFN on odd positions). Parameters for each
super-block position are stacked over the number of super-blocks so the whole
stack runs under one ``lax.scan`` — keeping the lowered HLO small enough to
compile 40 (arch × shape) × 2 meshes on this container.

Three entry points:
* ``forward_train``  — full-sequence causal forward (training / quality eval)
* ``prefill``        — full forward writing KV/SSM caches, last-token logits;
  ``lengths=`` turns it into a padded, masked prefill (per-row true lengths,
  logits gathered at ``lengths - 1``) so the serving engine can batch
  variable-length prompts into a handful of length buckets
* ``decode_step``    — ONE token against the caches (the serving hot path);
  ``row_valid=`` masks vacant continuous-batching rows out of MoE dispatch
  and router counts

Both serving entry points accept ``per_row_counts=True`` to return router
counts per ROW ((nsb, B, E)) instead of aggregated — the per-request routing
telemetry the engine attributes to request handles and the residency
backends use to keep phantom traffic out of hotness.

MoE layers accept an optional DynaExq ``ExpertBankQ`` override (serving in
mixed precision); without it they use the dense bf16 experts in ``params``.
Every MoE layer emits its router-selection counts — the hotness signal.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models.config import ArchConfig
from repro.models import layers as L
from repro.models import mlp as M
from repro.models import moe as X
from repro.models import ssm as S
from repro.models.layers import KVCache
from repro.models.ssm import MambaCache

PyTree = Any

# Roofline instrumentation: when True, layer scans fully unroll so XLA's
# cost_analysis (which counts while-loop bodies once) sees every iteration.
# Enabled only by the dry-run's reduced-depth variant compiles.
_SCAN_UNROLL = False


@contextlib.contextmanager
def unrolled_scans():
    global _SCAN_UNROLL
    prev = _SCAN_UNROLL
    _SCAN_UNROLL = True
    try:
        yield
    finally:
        _SCAN_UNROLL = prev


def _scan(body, carry, xs, length=None):
    return jax.lax.scan(body, carry, xs, length=length,
                        unroll=True if _SCAN_UNROLL else 1)


# --------------------------------------------------------------------------
# Init
# --------------------------------------------------------------------------

def _init_block(key, cfg: ArchConfig, kind: str, ffn: str) -> Dict:
    ks = jax.random.split(key, 4)
    p: Dict = {"norm1": L.init_rmsnorm(cfg.d_model),
               "norm2": L.init_rmsnorm(cfg.d_model)}
    if kind == "attn":
        p["attn"] = L.init_attention(ks[0], cfg.d_model, cfg.attn)
    elif kind == "mamba":
        p["mamba"] = S.init_mamba(ks[0], cfg.d_model, cfg.ssm)
    else:
        raise ValueError(kind)
    if ffn == "moe":
        p["moe"] = X.init_moe(ks[1], cfg.d_model, cfg.moe)
    elif cfg.d_ff:
        p["mlp"] = M.init_swiglu(ks[1], cfg.d_model, cfg.d_ff)
    if cfg.is_encoder_decoder and kind == "attn":
        p["norm_cross"] = L.init_rmsnorm(cfg.d_model)
        p["cross"] = L.init_cross_attention(ks[2], cfg.d_model, cfg.attn)
    return p


def _init_enc_block(key, cfg: ArchConfig) -> Dict:
    ks = jax.random.split(key, 2)
    return {"norm1": L.init_rmsnorm(cfg.d_model),
            "attn": L.init_attention(ks[0], cfg.d_model, cfg.attn),
            "norm2": L.init_rmsnorm(cfg.d_model),
            "mlp": M.init_gelu_mlp(ks[1], cfg.d_model, cfg.d_ff or 4 * cfg.d_model)}


def init_params(key, cfg: ArchConfig) -> Dict:
    sb = cfg.superblock_or_default()
    nsb = cfg.n_superblocks()
    keys = jax.random.split(key, 4 + len(sb))
    params: Dict = {
        "embed": (jax.random.normal(keys[0], (cfg.vocab_size, cfg.d_model),
                                    jnp.float32) * 0.02).astype(jnp.bfloat16),
        "final_norm": L.init_rmsnorm(cfg.d_model),
        "blocks": {},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (jax.random.normal(
            keys[1], (cfg.d_model, cfg.vocab_size), jnp.float32)
            * cfg.d_model ** -0.5).astype(jnp.bfloat16)
    for pos, kind in enumerate(sb):
        ffn = cfg.ffn_kind(pos)
        pos_keys = jax.random.split(keys[4 + pos], nsb)
        params["blocks"][str(pos)] = jax.vmap(
            lambda k: _init_block(k, cfg, kind, ffn))(pos_keys)
    if cfg.is_encoder_decoder:
        enc_keys = jax.random.split(keys[2], cfg.n_encoder_layers)
        params["encoder"] = jax.vmap(
            lambda k: _init_enc_block(k, cfg))(enc_keys)
        params["enc_final_norm"] = L.init_rmsnorm(cfg.d_model)
    return params


# --------------------------------------------------------------------------
# Caches
# --------------------------------------------------------------------------

class DecodeCaches(NamedTuple):
    """Per super-block-position stacked caches + (audio) cross-attn KV."""
    blocks: Dict[str, Any]       # pos → KVCache | MambaCache (leading nsb)
    cross: Optional[Dict[str, jax.Array]]  # {'k','v'}: (nsb, B, Senc, Hkv, hd)


def init_caches(cfg: ArchConfig, batch: int, max_len: int,
                dtype=jnp.bfloat16,
                positions: Optional[list] = None) -> DecodeCaches:
    """``positions``: optional subset of super-block position keys (str) to
    build caches for — e.g. only the mamba positions when the attention KV
    lives in a shared paged pool (allocating dense rows to throw away would
    waste device memory on every admission)."""
    sb = cfg.superblock_or_default()
    nsb = cfg.n_superblocks()
    blocks = {}
    for pos, kind in enumerate(sb):
        if positions is not None and str(pos) not in positions:
            continue
        if kind == "attn":
            cap = max_len if cfg.attn.sliding_window is None \
                else min(max_len, cfg.attn.sliding_window)
            c = L.init_kv_cache(batch, cap, cfg.attn, dtype)
        else:
            c = S.init_mamba_cache(batch, cfg.d_model, cfg.ssm, dtype)
        blocks[str(pos)] = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (nsb,) + a.shape).copy(), c)
    cross = None
    if cfg.is_encoder_decoder:
        shape = (nsb, batch, cfg.encoder_seq, cfg.attn.n_kv_heads,
                 cfg.attn.head_dim)
        cross = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    return DecodeCaches(blocks=blocks, cross=cross)


def attn_logical_capacity(cfg: ArchConfig, max_len: int,
                          block_tokens: int) -> int:
    """Per-sequence logical KV capacity under paging: the dense capacity
    (``max_len``, or the sliding window) rounded UP to a whole number of
    blocks. Extra padded slots are never valid, so attention results match
    the dense cache exactly."""
    cap = max_len if cfg.attn.sliding_window is None \
        else min(max_len, cfg.attn.sliding_window)
    return -(-cap // block_tokens) * block_tokens


def init_paged_caches(cfg: ArchConfig, batch: int, max_len: int,
                      block_tokens: int, n_blocks: int,
                      dtype=jnp.bfloat16) -> DecodeCaches:
    """Decode caches for the paged engine: attention positions hold ONE
    shared (nsb, N, bt, Hkv·hd) physical block pool (batch-independent —
    requests lease blocks out of it via block tables), while mamba
    positions keep their per-slot recurrent state rows (O(1) per slot, so
    paging them buys nothing)."""
    if cfg.is_encoder_decoder:
        raise NotImplementedError("paged caches are decoder-only")
    sb = cfg.superblock_or_default()
    nsb = cfg.n_superblocks()
    blocks = {}
    for pos, kind in enumerate(sb):
        if kind == "attn":
            c = L.init_paged_kv_cache(n_blocks, block_tokens, cfg.attn,
                                      dtype)
        else:
            c = S.init_mamba_cache(batch, cfg.d_model, cfg.ssm, dtype)
        blocks[str(pos)] = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (nsb,) + a.shape).copy(), c)
    return DecodeCaches(blocks=blocks, cross=None)


# --------------------------------------------------------------------------
# Block application
# --------------------------------------------------------------------------

def _apply_ffn(bp: Dict, cfg: ArchConfig, pos: int, x2d: jax.Array,
               capacity: int, bank, token_valid=None, n_rows=None,
               row_capacity=None, moe_dispatch=None):
    """x2d: (T, d) → (y, MoEAux | None)."""
    ffn = cfg.ffn_kind(pos)
    if ffn == "moe":
        b = bank[str(pos)] if bank is not None else bp["moe"]["experts"]
        with jax.named_scope("moe"):
            return X.moe_apply(bp["moe"], b, x2d, cfg.moe, capacity,
                               token_valid=token_valid, n_rows=n_rows,
                               row_capacity=row_capacity,
                               dispatch=moe_dispatch)
    if "mlp" in bp:
        return M.swiglu(bp["mlp"], x2d), None
    return jnp.zeros_like(x2d), None


def _block_train(bp: Dict, cfg: ArchConfig, pos: int, kind: str, x: jax.Array,
                 capacity: int, bank, enc_out: Optional[jax.Array]):
    B, Sq, d = x.shape
    h = L.rmsnorm(bp["norm1"], x, cfg.norm_eps)
    if kind == "attn":
        attn_out = L.attention_full(bp["attn"], cfg.attn, h)
        if cfg.is_encoder_decoder:
            x = x + attn_out
            hc = L.rmsnorm(bp["norm_cross"], x, cfg.norm_eps)
            ek, ev = L.encode_cross_kv(bp["cross"], cfg.attn, enc_out)
            attn_out = L.cross_attention(bp["cross"], cfg.attn, hc, ek, ev)
    else:
        attn_out, _ = S.ssd_forward(bp["mamba"], cfg.ssm, cfg.d_model, h)
    x = x + attn_out
    h = L.rmsnorm(bp["norm2"], x, cfg.norm_eps)
    y, aux = _apply_ffn(bp, cfg, pos, h.reshape(B * Sq, d), capacity, bank)
    counts = aux.counts if aux is not None else None
    aux_loss = aux.aux_loss if aux is not None else jnp.float32(0)
    return x + y.reshape(B, Sq, d), counts, aux_loss


def _block_step(bp: Dict, cfg: ArchConfig, pos: int, kind: str, x: jax.Array,
                cache, pos_idx, capacity: int, bank,
                cross_kv, prefill: bool, lengths=None, token_valid=None,
                n_rows=None, paged: Optional[Dict] = None,
                row_capacity=None, moe_dispatch=None):
    """Shared prefill/decode body. x: (B, S, d) (S=1 for decode).

    ``lengths``/``token_valid``/``n_rows`` carry the per-row validity
    signal: masked cache writes for padded prefill, masked MoE dispatch,
    and optional per-row router counts (see ``prefill``/``decode_step``).
    ``paged`` switches attention positions to the block-table path
    (gather/scatter against the shared ``PagedKVCache`` pool): a dict with
    ``table`` (B, nb) and either ``layer``/``write_blk``/``write_off``
    (decode: ``cache`` is then the whole stacked pool, read and written at
    ``layer``) or ``start``/``has_prefix`` (prefill: ``cache`` is the
    layer's slice). Mamba positions are unaffected —
    their per-slot state is not paged. ``row_capacity``/``moe_dispatch``
    select the MoE drop rule and token layout (see ``moe.moe_apply``).
    Returns (x, cache, counts) where counts is (E,) or (n_rows, E)."""
    B, Sq, d = x.shape
    h = L.rmsnorm(bp["norm1"], x, cfg.norm_eps)
    if kind == "attn":
        with jax.named_scope("attn"):
            if paged is not None:
                if prefill:
                    # paged["lengths"] are TOTAL prompt lengths (prefix +
                    # the suffix being computed); the ``lengths`` param
                    # carries the suffix lengths for the non-paged (mamba)
                    # positions.
                    attn_out, cache = L.attention_prefill_paged(
                        bp["attn"], cfg.attn, h, cache, paged["table"],
                        paged["start"], paged["lengths"],
                        has_prefix=paged["has_prefix"])
                else:
                    attn_out, cache = L.attention_decode_paged(
                        bp["attn"], cfg.attn, h, pos_idx, cache,
                        paged["layer"], paged["table"], paged["write_blk"],
                        paged["write_off"])
            elif prefill:
                attn_out, cache = L.attention_prefill(
                    bp["attn"], cfg.attn, h, cache, lengths=lengths)
            else:
                attn_out, cache = L.attention_decode(
                    bp["attn"], cfg.attn, h, pos_idx, cache)
            if cfg.is_encoder_decoder:
                x = x + attn_out
                hc = L.rmsnorm(bp["norm_cross"], x, cfg.norm_eps)
                attn_out = L.cross_attention(bp["cross"], cfg.attn, hc,
                                             cross_kv["k"], cross_kv["v"])
    else:
        if prefill:
            attn_out, cache = S.ssd_forward(bp["mamba"], cfg.ssm,
                                            cfg.d_model, h, lengths=lengths)
        else:
            attn_out, cache = S.ssd_decode_step(bp["mamba"], cfg.ssm,
                                                cfg.d_model, h, cache)
    x = x + attn_out
    h = L.rmsnorm(bp["norm2"], x, cfg.norm_eps)
    # Per-row capacity needs the row count even when per-row counts were
    # not requested; the counts selection below still keys on the caller's
    # ``n_rows`` so the emitted telemetry shape is unchanged.
    n_rows_ffn = n_rows if n_rows is not None \
        else (B if row_capacity is not None else None)
    y, aux = _apply_ffn(bp, cfg, pos, h.reshape(B * Sq, d), capacity, bank,
                        token_valid=token_valid, n_rows=n_rows_ffn,
                        row_capacity=row_capacity, moe_dispatch=moe_dispatch)
    if aux is None:
        counts = None
    elif n_rows is not None and aux.row_counts is not None:
        counts = aux.row_counts
    else:
        # Per-row counts unavailable (shard_map expert parallelism) — fall
        # back to the aggregated (E,) counts rather than dropping the
        # hotness signal entirely. Consumers must branch on ndim.
        counts = aux.counts
    return x + y.reshape(B, Sq, d), cache, counts


# --------------------------------------------------------------------------
# Encoder (audio)
# --------------------------------------------------------------------------

def encode(params: Dict, cfg: ArchConfig, frames: jax.Array) -> jax.Array:
    """frames: (B, Senc, d) stub frontend output → encoder hidden states."""
    def body(x, bp):
        h = L.rmsnorm(bp["norm1"], x, cfg.norm_eps)
        x = x + L.attention_full(bp["attn"], cfg.attn, h, causal=False)
        h = L.rmsnorm(bp["norm2"], x, cfg.norm_eps)
        return x + M.gelu_mlp(bp["mlp"], h), None
    x, _ = _scan(body, frames, params["encoder"])
    return L.rmsnorm(params["enc_final_norm"], x, cfg.norm_eps)


# --------------------------------------------------------------------------
# Embedding / head
# --------------------------------------------------------------------------

def _embed_inputs(params: Dict, cfg: ArchConfig, batch: Dict) -> jax.Array:
    x = params["embed"][batch["tokens"]]  # (B, S, d)
    if cfg.family == "vlm" and "image_embeds" in batch:
        x = jnp.concatenate(
            [batch["image_embeds"].astype(x.dtype), x], axis=1)
    return x


def _lm_logits(params: Dict, cfg: ArchConfig, x: jax.Array) -> jax.Array:
    with jax.named_scope("lm_head"):
        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        return (x @ head).astype(jnp.float32)


# --------------------------------------------------------------------------
# Entry points
# --------------------------------------------------------------------------

def forward_train(params: Dict, cfg: ArchConfig, batch: Dict,
                  capacity_factor: Optional[float] = None,
                  remat: bool = True):
    """Full causal forward. Returns (logits (B,S,V) f32, aux dict)."""
    sb = cfg.superblock_or_default()
    x = _embed_inputs(params, cfg, batch)
    B, Stot, d = x.shape
    cap = X.moe_capacity(B * Stot, cfg.moe, capacity_factor) if cfg.is_moe else 0
    enc_out = encode(params, cfg, batch["audio_embeds"]) \
        if cfg.is_encoder_decoder else None

    def sb_body(carry, bp_sliced):
        x, aux_sum = carry
        counts_out = {}
        for pos, kind in enumerate(sb):
            x, counts, aux = _block_train(bp_sliced[str(pos)], cfg, pos, kind,
                                          x, cap, None, enc_out)
            aux_sum = aux_sum + aux
            if counts is not None:
                counts_out[str(pos)] = counts
        return (x, aux_sum), counts_out

    body = jax.checkpoint(sb_body) if remat else sb_body
    (x, aux_sum), counts = _scan(body, (x, jnp.float32(0)),
                                        params["blocks"])
    logits = _lm_logits(params, cfg, x)
    return logits, {"aux_loss": aux_sum, "counts": counts}


def prefill(params: Dict, cfg: ArchConfig, batch: Dict, caches: DecodeCaches,
            bank=None, capacity_factor: Optional[float] = None,
            lengths: Optional[jax.Array] = None,
            per_row_counts: bool = False,
            row_capacity: Optional[int] = None,
            moe_dispatch: Optional[str] = None):
    """Full forward writing caches. Returns (last-token logits (B,V),
    caches, counts).

    ``lengths`` ((B,) int32) enables padded, masked prefill: each row's true
    length within the (right-padded) batch. Logits are gathered at
    ``lengths - 1`` per row, padded positions are excluded from MoE dispatch
    and every router count, attention/SSM cache writes stop at each row's
    last real token, and a ``lengths == 0`` row is fully inert (a batch-pad
    row). Padding must be on the right; causal masking then keeps it out of
    every valid position's attention for free.

    ``per_row_counts=True`` returns counts values of shape (nsb, B, E)
    (per-row routing telemetry) instead of the aggregated (nsb, E).
    ``row_capacity``/``moe_dispatch``: MoE drop rule and token layout
    (``moe.moe_apply``); ``row_capacity`` requires per-row counts.
    """
    sb = cfg.superblock_or_default()
    x = _embed_inputs(params, cfg, batch)
    B, Stot, d = x.shape
    cap = X.moe_capacity(B * Stot, cfg.moe, capacity_factor) if cfg.is_moe else 0
    token_valid = None
    if lengths is not None:
        lengths = jnp.asarray(lengths, jnp.int32)
        token_valid = (jnp.arange(Stot)[None, :] <
                       lengths[:, None]).reshape(-1)
    n_rows = B if per_row_counts else None

    cross = caches.cross
    if cfg.is_encoder_decoder:
        enc_out = encode(params, cfg, batch["audio_embeds"])

        def fill_cross(bp_sliced):
            out = {}
            for pos, kind in enumerate(sb):
                if kind == "attn":
                    k, v = L.encode_cross_kv(bp_sliced[str(pos)]["cross"],
                                             cfg.attn, enc_out)
                    out = {"k": k, "v": v}
            return out
        cross = jax.vmap(fill_cross)(params["blocks"])

    def sb_body(x, xs):
        if bank is not None:
            bp_sliced, cache_sliced, cross_sliced, bank_sliced = xs
        else:
            bp_sliced, cache_sliced, cross_sliced = xs
            bank_sliced = None
        counts_out, new_caches = {}, {}
        for pos, kind in enumerate(sb):
            x, c, counts = _block_step(bp_sliced[str(pos)], cfg, pos, kind, x,
                                       cache_sliced[str(pos)], None, cap,
                                       bank_sliced, cross_sliced,
                                       prefill=True, lengths=lengths,
                                       token_valid=token_valid,
                                       n_rows=n_rows,
                                       row_capacity=row_capacity,
                                       moe_dispatch=moe_dispatch)
            new_caches[str(pos)] = c
            if counts is not None:
                counts_out[str(pos)] = counts
        return x, (new_caches, counts_out)

    xs = (params["blocks"], caches.blocks, cross)
    if bank is not None:
        xs = xs + (bank,)
    x, (new_blocks, counts) = _scan(sb_body, x, xs)
    if lengths is None:
        x_last = x[:, -1:, :]
    else:
        last = jnp.clip(lengths - 1, 0, Stot - 1)
        x_last = x[jnp.arange(B), last][:, None, :]
    logits = _lm_logits(params, cfg, x_last)[:, 0]
    return logits, DecodeCaches(blocks=new_blocks, cross=cross), counts


def decode_step(params: Dict, cfg: ArchConfig, token: jax.Array,
                pos_idx: jax.Array, caches: DecodeCaches, bank=None,
                capacity_factor: float = 2.0,
                row_valid: Optional[jax.Array] = None,
                per_row_counts: bool = False,
                row_capacity: Optional[int] = None,
                moe_dispatch: Optional[str] = None):
    """One-token decode. token: (B,) int32; pos_idx: scalar int32 position,
    or a (B,) int32 vector of per-sequence positions (continuous batching —
    each KV-cache slot advances at its own request's offset).
    Returns (logits (B,V), caches, counts).

    ``row_valid`` ((B,) bool) marks which rows carry real requests: invalid
    (vacant continuous-batching) rows are dropped from MoE dispatch,
    capacity and all router counts, so their replayed tokens cannot
    contaminate hotness or offload accounting. Their logits are garbage and
    must not be read. ``per_row_counts=True`` returns counts values shaped
    (nsb, B, E) instead of the aggregated (nsb, E). ``row_capacity``
    normalizes MoE drops per row; ``moe_dispatch`` picks the token layout
    — ``"ragged"`` routes every MoE layer of this step through the
    padding-free compacted dispatch + fused mixed-precision kernel."""
    sb = cfg.superblock_or_default()
    x = params["embed"][token][:, None, :]  # (B, 1, d)
    B = x.shape[0]
    cap = X.moe_capacity(B, cfg.moe, capacity_factor) if cfg.is_moe else 0
    token_valid = None if row_valid is None \
        else jnp.asarray(row_valid, bool).reshape(-1)
    n_rows = B if per_row_counts else None

    def sb_body(x, xs):
        if bank is not None:
            bp_sliced, cache_sliced, cross_sliced, bank_sliced = xs
        else:
            bp_sliced, cache_sliced, cross_sliced = xs
            bank_sliced = None
        counts_out, new_caches = {}, {}
        for pos, kind in enumerate(sb):
            x, c, counts = _block_step(bp_sliced[str(pos)], cfg, pos, kind, x,
                                       cache_sliced[str(pos)], pos_idx, cap,
                                       bank_sliced, cross_sliced,
                                       prefill=False,
                                       token_valid=token_valid,
                                       n_rows=n_rows,
                                       row_capacity=row_capacity,
                                       moe_dispatch=moe_dispatch)
            new_caches[str(pos)] = c
            if counts is not None:
                counts_out[str(pos)] = counts
        return x, (new_caches, counts_out)

    xs = (params["blocks"], caches.blocks, caches.cross)
    if bank is not None:
        xs = xs + (bank,)
    x, (new_blocks, counts) = _scan(sb_body, x, xs)
    logits = _lm_logits(params, cfg, x)[:, 0]
    return logits, DecodeCaches(blocks=new_blocks, cross=caches.cross), counts


# --------------------------------------------------------------------------
# Paged entry points (block-table KV, see repro.serving.kvpool)
# --------------------------------------------------------------------------

def prefill_paged(params: Dict, cfg: ArchConfig, batch: Dict,
                  caches: DecodeCaches, block_table: jax.Array,
                  start: jax.Array, lengths: jax.Array, bank=None,
                  capacity_factor: Optional[float] = None,
                  per_row_counts: bool = False, has_prefix: bool = False,
                  row_capacity: Optional[int] = None,
                  moe_dispatch: Optional[str] = None):
    """Masked prefill of prompt SUFFIXES into the paged KV pool.

    ``batch["tokens"]``: (R, S) rows holding tokens ``start[r]`` ..
    ``lengths[r]-1`` right-padded to the bucket width S; ``lengths`` are
    TOTAL prompt lengths, so ``lengths - start`` are the per-row suffix
    lengths (0 ⇒ inert batch-pad row). ``block_table``: (R, nb) physical
    block ids (the engine pre-resolves allocation and copy-on-write).
    ``has_prefix=True`` (static) additionally attends each suffix over its
    row's cached prefix blocks — the prefix-sharing fast path that skips
    recomputing trie-hit tokens entirely. Prefix skips are only valid for
    attention-state stacks: rows of stacks with mamba positions must have
    ``start == 0`` (their recurrent state cannot be leased from a cache).

    Returns (suffix-last-token logits (R, V), caches, counts); attention
    leaves of ``caches`` are the UPDATED shared pools."""
    sb = cfg.superblock_or_default()
    if cfg.is_encoder_decoder:
        raise NotImplementedError("paged prefill is decoder-only")
    x = _embed_inputs(params, cfg, batch)
    R, Stot, d = x.shape
    cap = X.moe_capacity(R * Stot, cfg.moe, capacity_factor) if cfg.is_moe \
        else 0
    start = jnp.asarray(start, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    suffix_lens = lengths - start
    token_valid = (jnp.arange(Stot)[None, :] <
                   suffix_lens[:, None]).reshape(-1)
    n_rows = R if per_row_counts else None
    paged = {"table": block_table, "start": start, "lengths": lengths,
             "has_prefix": has_prefix}

    def sb_body(x, xs):
        if bank is not None:
            bp_sliced, cache_sliced, bank_sliced = xs
        else:
            bp_sliced, cache_sliced = xs
            bank_sliced = None
        counts_out, new_caches = {}, {}
        for pos, kind in enumerate(sb):
            x, c, counts = _block_step(bp_sliced[str(pos)], cfg, pos, kind, x,
                                       cache_sliced[str(pos)], None, cap,
                                       bank_sliced, None,
                                       prefill=True, lengths=suffix_lens,
                                       token_valid=token_valid,
                                       n_rows=n_rows, paged=paged,
                                       row_capacity=row_capacity,
                                       moe_dispatch=moe_dispatch)
            new_caches[str(pos)] = c
            if counts is not None:
                counts_out[str(pos)] = counts
        return x, (new_caches, counts_out)

    xs = (params["blocks"], caches.blocks)
    if bank is not None:
        xs = xs + (bank,)
    x, (new_blocks, counts) = _scan(sb_body, x, xs)
    last = jnp.clip(suffix_lens - 1, 0, Stot - 1)
    x_last = x[jnp.arange(R), last][:, None, :]
    logits = _lm_logits(params, cfg, x_last)[:, 0]
    return logits, DecodeCaches(blocks=new_blocks, cross=None), counts


def decode_step_paged(params: Dict, cfg: ArchConfig, token: jax.Array,
                      pos_idx: jax.Array, caches: DecodeCaches,
                      block_table: jax.Array, write_blk: jax.Array,
                      write_off: jax.Array, bank=None,
                      capacity_factor: float = 2.0,
                      row_valid: Optional[jax.Array] = None,
                      per_row_counts: bool = False,
                      row_capacity: Optional[int] = None,
                      moe_dispatch: Optional[str] = None):
    """One-token decode against the paged KV pool: ``decode_step`` with the
    attention cache addressed through per-row block tables. ``write_blk``/
    ``write_off`` ((B,) int32) name each row's pre-resolved physical write
    target (vacant rows point at the trash block). Semantics otherwise
    identical to ``decode_step`` — the gathered logical view equals the
    dense per-slot cache bit for bit.

    The stacked pools ride in the layer scan's carry: each layer writes its
    one new entry per row and gathers its blocks in place at the scan's
    layer index, so no pool-sized array enters or leaves the scan and the
    (donated) pools come back where they were. Mamba positions scan their
    per-slot state as ``xs``/``ys``."""
    sb = cfg.superblock_or_default()
    x = params["embed"][token][:, None, :]  # (B, 1, d)
    B = x.shape[0]
    cap = X.moe_capacity(B, cfg.moe, capacity_factor) if cfg.is_moe else 0
    token_valid = None if row_valid is None \
        else jnp.asarray(row_valid, bool).reshape(-1)
    n_rows = B if per_row_counts else None
    pools = {k: c for k, c in caches.blocks.items()
             if isinstance(c, L.PagedKVCache)}
    state = {k: c for k, c in caches.blocks.items() if k not in pools}

    def sb_body(carry, xs):
        x, pools = carry
        if bank is not None:
            layer, bp_sliced, state_sliced, bank_sliced = xs
        else:
            layer, bp_sliced, state_sliced = xs
            bank_sliced = None
        paged = {"layer": layer, "table": block_table,
                 "write_blk": write_blk, "write_off": write_off}
        pools = dict(pools)
        counts_out, new_state = {}, {}
        for pos, kind in enumerate(sb):
            key = str(pos)
            cache = pools[key] if key in pools else state_sliced[key]
            x, c, counts = _block_step(bp_sliced[key], cfg, pos, kind, x,
                                       cache, pos_idx, cap,
                                       bank_sliced, None,
                                       prefill=False,
                                       token_valid=token_valid,
                                       n_rows=n_rows, paged=paged,
                                       row_capacity=row_capacity,
                                       moe_dispatch=moe_dispatch)
            if key in pools:
                pools[key] = c
            else:
                new_state[key] = c
            if counts is not None:
                counts_out[key] = counts
        return (x, pools), (new_state, counts_out)

    nsb = cfg.n_superblocks()
    xs = (jnp.arange(nsb, dtype=jnp.int32), params["blocks"], state)
    if bank is not None:
        xs = xs + (bank,)
    (x, pools), (new_state, counts) = _scan(sb_body, (x, pools), xs)
    logits = _lm_logits(params, cfg, x)[:, 0]
    return logits, DecodeCaches(blocks={**new_state, **pools}, cross=None), \
        counts


# --------------------------------------------------------------------------
# Speculative decoding entry points (multi-token draft / verify)
# --------------------------------------------------------------------------
#
# Both run S chained single-token decode steps under ONE ``lax.scan`` — one
# device dispatch advances every row by S positions. Each scan iteration IS
# ``decode_step``/``decode_step_paged``, so every per-position computation
# (attention reduction order, MoE capacity = moe_capacity(B), masked cache
# writes) is identical to the engine's sequential decode — token parity with
# the non-speculative path holds by construction, the same way the paged
# attention shares ``_attend_cache`` with the dense path. (A width-S fused
# verify forward — the arithmetic-intensity win on real accelerators — is a
# kernel follow-up; it would trade this bit-parity for throughput.)

def _mamba_position_keys(cfg: ArchConfig) -> tuple:
    sb = cfg.superblock_or_default()
    return tuple(str(p) for p, k in enumerate(sb) if k != "attn")


def spec_draft(params: Dict, cfg: ArchConfig, token: jax.Array,
               pos: jax.Array, caches: DecodeCaches, row_valid: jax.Array,
               bank=None, capacity_factor: float = 2.0,
               paged: Optional[Dict] = None,
               row_capacity: Optional[int] = None,
               moe_dispatch: Optional[str] = None):
    """Draft ``S = row_valid.shape[0]`` greedy tokens per row by chaining
    decode steps (each step's argmax feeds the next step's embedding).

    ``token``: (B,) the last emitted token per row; ``pos``: (B,) the first
    write position; ``row_valid``: (S, B) per-STEP validity (a row past its
    own draft depth is masked out of MoE dispatch and counts but still rides
    for shape stability). ``paged``: ``{"table": (B, nb), "write_blk"/
    "write_off": (S, B)}`` pre-resolved physical write lanes (the engine
    routes beyond-depth and vacant lanes to the trash block).

    Passing an all-lo ``bank`` (every ``slot_owner`` = -1) turns the
    always-resident low-precision fallback tier into the draft model — no
    extra weights exist, the lo tier IS the speculator; under
    ``moe_dispatch="ragged"`` each draft step runs the same padding-free
    fused kernel as the target decode (the slot derivation reads the
    disowned handles, so every tile streams lo — no separate all-lo GEMM
    path). Returns ``(drafted (S, B) int32, caches)``; counts are not
    emitted (draft traffic must never feed hotness)."""
    S = row_valid.shape[0]

    def body(carry, xs):
        tok, c = carry
        if paged is not None:
            j, rv, wb, wo = xs
            logits, c, _ = decode_step_paged(
                params, cfg, tok, pos + j, c, paged["table"], wb, wo,
                bank=bank, capacity_factor=capacity_factor, row_valid=rv,
                row_capacity=row_capacity, moe_dispatch=moe_dispatch)
        else:
            j, rv = xs
            logits, c, _ = decode_step(
                params, cfg, tok, pos + j, c, bank=bank,
                capacity_factor=capacity_factor, row_valid=rv,
                row_capacity=row_capacity, moe_dispatch=moe_dispatch)
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        return (nxt, c), nxt

    xs = (jnp.arange(S, dtype=jnp.int32), row_valid)
    if paged is not None:
        xs = xs + (paged["write_blk"], paged["write_off"])
    (_, caches), drafted = jax.lax.scan(body, (token, caches), xs)
    return drafted, caches


def spec_verify(params: Dict, cfg: ArchConfig, tokens: jax.Array,
                pos: jax.Array, caches: DecodeCaches, row_valid: jax.Array,
                bank=None, capacity_factor: float = 2.0,
                paged: Optional[Dict] = None,
                row_capacity: Optional[int] = None,
                moe_dispatch: Optional[str] = None):
    """Verify ``S`` positions in one dispatch: chained decode steps over the
    given tokens (row r, step j consumes ``tokens[j, r]`` at position
    ``pos[r] + j``) under the TARGET (mixed-precision) bank.

    Returns ``(logits (S, B, V), caches, counts, ssm_states)``:

    * ``logits[j]`` is the next-token distribution after consuming
      ``tokens[:j+1]`` — position j's draft is judged against
      ``logits[j-1]`` and ``logits[a]`` supplies the bonus token;
    * ``counts`` values are per-step stacked ((S, nsb, B, E)) so the engine
      can keep REJECTED positions out of the hotness signal;
    * ``ssm_states`` maps each mamba position to its per-step stacked cache
      ((S, nsb, B, ...)) — rejection rolls a row's recurrent state back to
      exactly the last accepted step, no recompute."""
    mkeys = _mamba_position_keys(cfg)

    def body(c, xs):
        if paged is not None:
            tok, j, rv, wb, wo = xs
            logits, c, counts = decode_step_paged(
                params, cfg, tok, pos + j, c, paged["table"], wb, wo,
                bank=bank, capacity_factor=capacity_factor, row_valid=rv,
                per_row_counts=True, row_capacity=row_capacity,
                moe_dispatch=moe_dispatch)
        else:
            tok, j, rv = xs
            logits, c, counts = decode_step(
                params, cfg, tok, pos + j, c, bank=bank,
                capacity_factor=capacity_factor, row_valid=rv,
                per_row_counts=True, row_capacity=row_capacity,
                moe_dispatch=moe_dispatch)
        ssm = {p: c.blocks[p] for p in mkeys}
        return c, (logits, counts, ssm)

    S = tokens.shape[0]
    xs = (tokens, jnp.arange(S, dtype=jnp.int32), row_valid)
    if paged is not None:
        xs = xs + (paged["write_blk"], paged["write_off"])
    caches, (logits, counts, ssm) = jax.lax.scan(body, caches, xs)
    return logits, caches, counts, ssm


