"""Core layers: norms, RoPE, GQA attention (full / sliding-window / cross)
with prefill + single-token decode against a KV cache.

All functions are pure; parameters are dict pytrees created by the matching
``init_*`` functions. Compute dtype is bf16 with f32 softmax/norm accumulation.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models.config import AttnConfig

Param = dict


def _init(key, shape, scale=None, dtype=jnp.bfloat16):
    fan_in = shape[0] if len(shape) > 1 else shape[-1]
    scale = scale if scale is not None else fan_in ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------

def init_rmsnorm(d: int) -> Param:
    return {"scale": jnp.ones((d,), jnp.bfloat16)}


def rmsnorm(p: Param, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * p["scale"]


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[..., None] * freqs  # (..., S, half)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# Attention
# --------------------------------------------------------------------------

class KVCache(NamedTuple):
    """KV cache, laid out (B, Hkv, C, hd) — head-major so the decode GQA dot
    is a SINGLE-batch-dim matmul after a free (B·Hkv) reshape. (The seq-major
    layout forced XLA to upcast the full cache to f32 every layer: the
    multi-batch-dim bf16 dot is unsupported and the GQA grouping put (b, g)
    in the batch dims.) Full cache: ``capacity == max_len``; sliding window
    uses a ring buffer of ``capacity == window`` slots addressed
    ``pos % window`` on axis 2."""
    k: jax.Array  # (B, Hkv, C, hd)
    v: jax.Array  # (B, Hkv, C, hd)

    @property
    def capacity(self) -> int:
        return self.k.shape[2]


def init_kv_cache(batch: int, capacity: int, cfg: AttnConfig,
                  dtype=jnp.bfloat16) -> KVCache:
    shape = (batch, cfg.n_kv_heads, capacity, cfg.head_dim)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


class PagedKVCache(NamedTuple):
    """Physical KV block pool, laid out (N, bt, Hkv·hd): ``N`` fixed-size
    blocks of ``bt`` cache positions each, shared by every request; one
    position's K (or V) for every KV head is one row. A request's logical
    cache is named by a *block table* row ((nb,) int32 of physical block
    ids, -1 = unallocated): gathering the table recovers the exact
    (Hkv, nb·bt, hd) head-major view the dense ``KVCache`` stores per batch
    row, so both layouts run the same attention math. Block 0 is the pool's
    trash block (vacant-row writes land there; see
    ``repro.serving.kvpool``).

    A row holds all heads so that it is at least 128 wide: a TPU lays out
    an array whose last dim is narrower (``hd`` = 64) with its block dim
    innermost, and then every gather of blocks, and every write of a
    position, first relayouts the pool. Here a block is whole in the
    device layout and a position's write is one row."""
    k: jax.Array  # (N, bt, Hkv·hd)
    v: jax.Array  # (N, bt, Hkv·hd)

    @property
    def n_blocks(self) -> int:
        return self.k.shape[0]

    @property
    def block_tokens(self) -> int:
        return self.k.shape[1]


def init_paged_kv_cache(n_blocks: int, block_tokens: int, cfg: AttnConfig,
                        dtype=jnp.bfloat16) -> PagedKVCache:
    shape = (n_blocks, block_tokens, cfg.n_kv_heads * cfg.head_dim)
    return PagedKVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


def paged_view(cache: PagedKVCache, table: jax.Array, hd: int,
               layer: Optional[jax.Array] = None
               ) -> tuple[jax.Array, jax.Array]:
    """Gather-by-block-table: (B, nb) table → (B, Hkv, nb·bt, hd) logical
    K/V views (slot order = logical cache order). Unallocated (-1) entries
    read the trash block; callers mask those slots out of attention. With
    ``layer`` the cache is the stacked (nsb, N, bt, Hkv·hd) pool and the
    blocks are gathered from that layer where they lie."""
    B, nb = table.shape
    at = jnp.clip(table, 0)
    if layer is not None:
        at = (layer, at)
    k = cache.k[at]                            # (B, nb, bt, Hkv·hd)
    v = cache.v[at]
    F = k.shape[-1]
    # (B, C, Hkv·hd) → (B, Hkv·hd, C) → (B, Hkv, hd, C) → (B, Hkv, C, hd):
    # a v5e compiles this to one transpose of the gathered rows into the
    # C-innermost layout the attention dots read, without first splitting
    # the rows into hd-wide (lane-padded) pieces.
    k = jnp.swapaxes(k.reshape(B, -1, F), 1, 2).reshape(B, F // hd, hd, -1)
    v = jnp.swapaxes(v.reshape(B, -1, F), 1, 2).reshape(B, F // hd, hd, -1)
    return jnp.swapaxes(k, 2, 3), jnp.swapaxes(v, 2, 3)


def init_attention(key, d_model: int, cfg: AttnConfig) -> Param:
    ks = jax.random.split(key, 4)
    p = {
        "wq": _init(ks[0], (d_model, cfg.q_dim)),
        "wk": _init(ks[1], (d_model, cfg.kv_dim)),
        "wv": _init(ks[2], (d_model, cfg.kv_dim)),
        "wo": _init(ks[3], (cfg.q_dim, d_model)),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_rmsnorm(cfg.head_dim)
        p["k_norm"] = init_rmsnorm(cfg.head_dim)
    return p


def _project_qkv(p: Param, cfg: AttnConfig, x: jax.Array, positions):
    B, S, _ = x.shape
    q = (x @ p["wq"]).reshape(B, S, cfg.n_heads, cfg.head_dim)
    k = (x @ p["wk"]).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = (x @ p["wv"]).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q)
        k = rmsnorm(p["k_norm"], k)
    if cfg.use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def _sdpa(q, k, v, mask, n_heads):
    """q: (B,Sq,H,hd)  k,v: (B,Skv,Hkv,hd)  mask: (B|1, Sq, Skv) bool.

    Grouped-GQA form: K/V are never head-repeated (materializing the repeat
    forced an extra full-cache copy per layer at decode), and the QK einsum
    accumulates bf16*bf16->f32 on the MXU instead of upcasting K/V."""
    B, Sq, H, hd = q.shape
    Hkv = k.shape[2]
    rep = n_heads // Hkv
    qg = q.reshape(B, Sq, Hkv, rep, hd)
    scale = hd ** -0.5
    logits = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k).astype(jnp.float32) * scale
    logits = jnp.where(mask[:, None, None, :, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", probs, v)
    return out.reshape(B, Sq, H * hd)


def _seq_parallel_constraint(q, k, v, n_heads):
    """Sequence parallelism for architectures whose head count does not
    divide the model axis (24H, 56H, 6H vs 16): attention params stay
    replicated (FSDP handles their storage) and each model rank computes ALL
    heads for S/16 of the query positions. K/V are gathered per layer — a
    268 MB-scale all-gather instead of the TB-scale all-reduces (or
    replication fallbacks) that contraction / uneven head sharding caused."""
    try:
        from repro.launch.dist import get_dist
    except ImportError:  # pragma: no cover
        return q, k, v
    ctx = get_dist()
    if ctx is None or n_heads % ctx.model_size == 0:
        return q, k, v
    if q.shape[1] % ctx.model_size:
        return q, k, v
    from jax.sharding import NamedSharding, PartitionSpec as P
    dp = ctx.dp_axes if ctx.tokens_dp_sharded else None
    qs = NamedSharding(ctx.mesh, P(dp, "model", None, None))
    kvs = NamedSharding(ctx.mesh, P(dp, None, None, None))
    return (jax.lax.with_sharding_constraint(q, qs),
            jax.lax.with_sharding_constraint(k, kvs),
            jax.lax.with_sharding_constraint(v, kvs))


def attention_full(p: Param, cfg: AttnConfig, x: jax.Array,
                   causal: bool = True,
                   positions: Optional[jax.Array] = None) -> jax.Array:
    """Training / encoder attention over a full sequence (no cache)."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)[None, :]
    q, k, v = _project_qkv(p, cfg, x, positions)
    q, k, v = _seq_parallel_constraint(q, k, v, cfg.n_heads)
    if causal and S > PREFILL_CHUNK_THRESHOLD and S % PREFILL_CHUNK == 0:
        out = _sdpa_chunked(q, k, v, cfg.n_heads, cfg.sliding_window,
                            PREFILL_CHUNK)
        return out @ p["wo"]
    qpos = positions[..., :, None]
    kpos = positions[..., None, :]
    if causal:
        mask = kpos <= qpos
        if cfg.sliding_window is not None:
            mask &= kpos > qpos - cfg.sliding_window
    else:
        mask = jnp.ones((1, S, S), bool)
    mask = jnp.broadcast_to(mask, (B, S, S)) if mask.shape[0] != B else mask
    out = _sdpa(q, k, v, mask, cfg.n_heads)
    return out @ p["wo"]


def _sdpa_chunked(q, k, v, n_heads, sliding_window, chunk: int):
    """Causal attention via a q-chunk scan — never materializes (S, S)
    logits; per-chunk working set is (B, H, chunk, S). Used for long
    prefill (and train at long S)."""
    B, S, H, hd = q.shape[0], q.shape[1], q.shape[2], q.shape[3]
    Hkv = k.shape[2]
    rep = n_heads // Hkv
    scale = hd ** -0.5
    nq = S // chunk
    qc = q.reshape(B, nq, chunk, Hkv, rep, hd)
    kpos = jnp.arange(S)

    def body(_, qi_i):
        qi, i = qi_i                      # (B, chunk, Hkv, rep, hd), scalar
        qpos = i * chunk + jnp.arange(chunk)
        logits = jnp.einsum("bqgrd,bkgd->bgrqk", qi, k).astype(jnp.float32) * scale
        m = kpos[None, :] <= qpos[:, None]
        if sliding_window is not None:
            m &= kpos[None, :] > qpos[:, None] - sliding_window
        logits = jnp.where(m[None, None, None, :, :], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(qi.dtype)
        return None, jnp.einsum("bgrqk,bkgd->bqgrd", probs, v)

    from repro.models import model as _model_mod
    _, out = jax.lax.scan(body, None,
                          (jnp.moveaxis(qc, 1, 0), jnp.arange(nq)),
                          unroll=True if _model_mod._SCAN_UNROLL else 1)
    out = jnp.moveaxis(out, 0, 1)          # (B, nq, chunk, Hkv, rep, hd)
    return out.reshape(B, S, H * hd)


PREFILL_CHUNK_THRESHOLD = 2048
PREFILL_CHUNK = 256


def attention_prefill(p: Param, cfg: AttnConfig, x: jax.Array,
                      cache: KVCache, lengths: Optional[jax.Array] = None
                      ) -> tuple[jax.Array, KVCache]:
    """Causal prefill writing the cache. Sequence starts at position 0.

    For a sliding-window ring cache (capacity < S) only the last ``capacity``
    keys land in the cache, which is exactly the window semantics.

    ``lengths`` ((B,) int32) marks each row's true prompt length for padded
    (length-bucketed) prefill. Causal masking already keeps end-of-row
    padding out of every valid position's attention; what needs care is the
    cache write: ring slot i must hold each ROW's largest real position
    p < length with p % C == i (not the batch tail, which for a short row
    in a long bucket is pure padding), and slots no real position maps to
    keep their previous contents. Attention outputs at padded positions are
    garbage and must not be read.
    """
    B, S, _ = x.shape
    positions = jnp.arange(S)[None, :]
    q, k, v = _project_qkv(p, cfg, x, positions)
    if S > PREFILL_CHUNK_THRESHOLD and S % PREFILL_CHUNK == 0:
        out = _sdpa_chunked(q, k, v, cfg.n_heads, cfg.sliding_window,
                            PREFILL_CHUNK)
    else:
        qpos = positions[..., :, None]
        kpos = positions[..., None, :]
        mask = kpos <= qpos
        if cfg.sliding_window is not None:
            mask &= kpos > qpos - cfg.sliding_window
        mask = jnp.broadcast_to(mask, (B, S, S))
        out = _sdpa(q, k, v, mask, cfg.n_heads)

    with jax.named_scope("kv_write"):
        C = cache.capacity
        kc = k.transpose(0, 2, 1, 3)       # (B, Hkv, S, hd) — cache layout
        vc = v.transpose(0, 2, 1, 3)
        if lengths is not None:
            # Per-row masked write (full and ring caches alike): slot i takes
            # the row's largest real position p < length with p % C == i; slots
            # with no real owner keep their previous contents.
            last = lengths[:, None] - 1 - jnp.mod(          # (B, C)
                lengths[:, None] - 1 - jnp.arange(C)[None, :], C)
            has_owner = (last >= 0) & (lengths[:, None] > 0)
            src = jnp.clip(last, 0, S - 1)[:, None, :, None]
            gk = jnp.take_along_axis(kc, src, axis=2)        # (B, Hkv, C, hd)
            gv = jnp.take_along_axis(vc, src, axis=2)
            keep = has_owner[:, None, :, None]
            new_k = jnp.where(keep, gk, cache.k)
            new_v = jnp.where(keep, gv, cache.v)
        elif C >= S:
            new_k = jax.lax.dynamic_update_slice(cache.k, kc, (0, 0, 0, 0))
            new_v = jax.lax.dynamic_update_slice(cache.v, vc, (0, 0, 0, 0))
        else:  # ring buffer: keep last C positions, slot = pos % C
            tail_k, tail_v = kc[:, :, S - C:], vc[:, :, S - C:]
            slots = (jnp.arange(S - C, S)) % C
            new_k = cache.k.at[:, :, slots].set(tail_k)
            new_v = cache.v.at[:, :, slots].set(tail_v)
    return out @ p["wo"], KVCache(new_k, new_v)


def _attend_cache(q: jax.Array, k_all: jax.Array, v_all: jax.Array,
                  pos_b: jax.Array, cfg: AttnConfig) -> jax.Array:
    """Single-token attention over a written cache view. ``q``: (B,1,H,hd);
    ``k_all``/``v_all``: (B, Hkv, C, hd) head-major views (dense rows or
    block-table gathers — same math either way); ``pos_b``: (B,) positions
    just written. Slot i of a row's view holds the largest position
    p <= pos with p % C == i (full cache ⇒ slot == position)."""
    B, C = q.shape[0], k_all.shape[2]
    idx = jnp.arange(C)[None, :]
    if cfg.sliding_window is None:
        valid = idx <= pos_b[:, None]                             # (B, C)
    else:
        # slot i holds the largest position p' <= pos with p' % C == i.
        slot_pos = pos_b[:, None] - jnp.mod(pos_b[:, None] - idx, C)
        valid = (slot_pos >= 0) & \
            (slot_pos > pos_b[:, None] - cfg.sliding_window)

    H, hd = cfg.n_heads, cfg.head_dim
    Hkv = cfg.n_kv_heads
    rep = H // Hkv
    # q head order H = g·rep + r matches the (B,1,H,hd) projection reshape.
    qg = q.reshape(B, Hkv, rep, hd).reshape(B * Hkv, rep, hd)
    kf = k_all.reshape(B * Hkv, C, hd)
    vf = v_all.reshape(B * Hkv, C, hd)
    # bf16 dot (TPU MXU accumulates f32 natively; requesting f32 out here
    # makes the CPU lowering convert the ENTIRE cache to f32 every layer,
    # which would poison the roofline bytes and the real TPU layout alike).
    logits = jnp.einsum("brd,bkd->brk", qg, kf).astype(jnp.float32) * hd ** -0.5
    # valid (B, C) → rows of the (B·Hkv) flattened batch, b-major like kf.
    logits = jnp.where(jnp.repeat(valid, Hkv, axis=0)[:, None, :],
                       logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("brk,bkd->brd", probs, vf)        # (B·Hkv, rep, hd)
    return out.reshape(B, 1, H * hd)


def attention_decode(p: Param, cfg: AttnConfig, x: jax.Array, pos: jax.Array,
                     cache: KVCache) -> tuple[jax.Array, KVCache]:
    """One-token decode. ``x``: (B, 1, d); ``pos``: scalar int32 or (B,)
    int32 vector of per-sequence positions (continuous batching: every slot
    tracks its own request's position). Works for both full and ring caches.
    All dots are single-batch-dim bf16 matmuls on the head-major cache (see
    KVCache)."""
    B = x.shape[0]
    C = cache.capacity
    pos = jnp.asarray(pos, jnp.int32)
    pos_b = jnp.broadcast_to(pos, (B,)) if pos.ndim == 0 else pos  # (B,)
    positions = pos_b[:, None]                                    # (B, 1)
    q, k, v = _project_qkv(p, cfg, x, positions)       # k/v: (B, 1, Hkv, hd)
    slot = jnp.mod(pos_b, C)                                      # (B,)
    # Masked update instead of dynamic_update_slice: SPMD partitions a
    # dynamic-index DUS over the sharded seq axis through an f32 masked
    # fallback (measured ~4x the bytes); the explicit where-mask stays bf16
    # and costs exactly one cache read+write.
    with jax.named_scope("kv_write"):
        slot_mask = (jnp.arange(C)[None, :] ==
                     slot[:, None])[:, None, :, None]
        new_k = jnp.where(slot_mask, k.transpose(0, 2, 1, 3), cache.k)
        new_v = jnp.where(slot_mask, v.transpose(0, 2, 1, 3), cache.v)
    out = _attend_cache(q, new_k, new_v, pos_b, cfg)
    return out @ p["wo"], KVCache(new_k, new_v)


def attention_decode_paged(p: Param, cfg: AttnConfig, x: jax.Array,
                           pos: jax.Array, pool: PagedKVCache,
                           layer: jax.Array, table: jax.Array,
                           write_blk: jax.Array, write_off: jax.Array
                           ) -> tuple[jax.Array, PagedKVCache]:
    """One-token decode against the stacked (nsb, N, bt, Hkv·hd) pool, in
    place: the layer's new K/V row is written at ``(layer, write_blk,
    write_off)`` and its blocks are gathered from ``layer`` where they lie,
    so no layer-sized slice of the pool is made. ``table``: (B, nb) block
    tables; ``write_blk``/``write_off``: (B,) physical block + in-block
    offset for each row's write (COW already resolved host-side — the
    engine routes vacant rows to the trash block). After the write the
    gathered logical view equals the dense cache row bit for bit, so decode
    shares ``_attend_cache`` with the contiguous path."""
    B = x.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    pos_b = jnp.broadcast_to(pos, (B,)) if pos.ndim == 0 else pos
    q, k, v = _project_qkv(p, cfg, x, pos_b[:, None])
    with jax.named_scope("kv_write"):
        at = (layer, write_blk, write_off)
        pool = PagedKVCache(pool.k.at[at].set(k.reshape(B, -1)),
                            pool.v.at[at].set(v.reshape(B, -1)))
    k_all, v_all = paged_view(pool, table, cfg.head_dim, layer)
    out = _attend_cache(q, k_all, v_all, pos_b, cfg)
    return out @ p["wo"], pool


def attention_prefill_paged(p: Param, cfg: AttnConfig, x: jax.Array,
                            cache: PagedKVCache, table: jax.Array,
                            start: jax.Array, lengths: jax.Array,
                            has_prefix: bool = False
                            ) -> tuple[jax.Array, PagedKVCache]:
    """Masked prefill of a prompt SUFFIX into pool blocks.

    ``x``: (B, S, d) embeds of tokens ``start[b] .. lengths[b]-1`` (right-
    padded to the bucket S); ``start``: (B,) int32 prefix-hit offsets (0 =
    whole prompt); ``lengths``: (B,) TOTAL prompt lengths. ``table``:
    (B, nb) block tables covering logical slots 0..nb·bt — for a prefix hit
    the leading entries alias trie-shared blocks whose contents were written
    by an earlier request (any block this call writes was COWed or freshly
    allocated by the engine first).

    Writes use the same per-slot last-owner rule as the dense masked
    prefill (ring wrap included), restricted to positions >= start so
    shared prefix slots are never touched. With ``has_prefix`` the suffix
    queries additionally attend over the gathered prefix K/V (read before
    the write), giving exact continuation semantics without recomputing a
    single prefix token. Outputs at padded positions are garbage and must
    not be read."""
    B, S, _ = x.shape
    bt = cache.block_tokens
    C = table.shape[1] * bt
    start = jnp.asarray(start, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    positions = start[:, None] + jnp.arange(S)[None, :]           # (B, S)
    q, k, v = _project_qkv(p, cfg, x, positions)
    if has_prefix:
        # Prefix view BEFORE the suffix write (the data dependency keeps
        # the gather ordered ahead of the scatter inside one jit).
        pk, pv = paged_view(cache, table, cfg.head_dim)  # (B, Hkv, C, hd)

    with jax.named_scope("kv_write"):
        # ---- scatter suffix K/V: slot s takes the row's largest real position
        # p in [start, length) with p % C == s; all other lanes hit the trash
        # block (never a live one).
        idx = jnp.arange(C)[None, :]
        last = lengths[:, None] - 1 - jnp.mod(lengths[:, None] - 1 - idx, C)
        own = (last >= start[:, None]) & (lengths[:, None] > start[:, None])
        src = jnp.clip(last - start[:, None], 0, S - 1)
        kc = k.reshape(B, S, -1)                          # (B, S, Hkv·hd)
        vc = v.reshape(B, S, -1)
        gk = jnp.take_along_axis(kc, src[:, :, None], axis=1)
        gv = jnp.take_along_axis(vc, src[:, :, None], axis=1)
        blk = jnp.take_along_axis(table, jnp.broadcast_to(idx // bt, (B, C)),
                                  axis=1)
        phys = jnp.where(own, jnp.clip(blk, 0), 0)
        offs = jnp.broadcast_to(idx % bt, (B, C))
        new_k = cache.k.at[phys, offs].set(gk)
        new_v = cache.v.at[phys, offs].set(gv)

    # ---- suffix queries over [cached prefix ⊕ suffix] ------------------
    qpos = positions[:, :, None]                          # (B, S, 1)
    kpos = positions[:, None, :]                          # (B, 1, S)
    mask = (kpos <= qpos) & (kpos < lengths[:, None, None])
    if cfg.sliding_window is not None:
        mask = mask & (kpos > qpos - cfg.sliding_window)
    if has_prefix:
        # Slot s of the pre-write view holds prefix position
        # p_s = largest p < start with p % C == s (ring and full alike).
        ppos = start[:, None] - 1 - jnp.mod(start[:, None] - 1 - idx, C)
        pmask = jnp.broadcast_to((ppos >= 0)[:, None, :], (B, S, C))
        if cfg.sliding_window is not None:
            pmask = pmask & (ppos[:, None, :] > qpos - cfg.sliding_window)
        k_cat = jnp.concatenate([pk.transpose(0, 2, 1, 3), k], axis=1)
        v_cat = jnp.concatenate([pv.transpose(0, 2, 1, 3), v], axis=1)
        mask = jnp.concatenate([pmask, jnp.broadcast_to(mask, (B, S, S))],
                               axis=-1)
        out = _sdpa(q, k_cat, v_cat, mask, cfg.n_heads)
    else:
        out = _sdpa(q, k, v, jnp.broadcast_to(mask, (B, S, S)), cfg.n_heads)
    return out @ p["wo"], PagedKVCache(new_k, new_v)


# --------------------------------------------------------------------------
# Cross attention (encoder-decoder)
# --------------------------------------------------------------------------

def init_cross_attention(key, d_model: int, cfg: AttnConfig) -> Param:
    p = init_attention(key, d_model, cfg)
    return p


def cross_attention(p: Param, cfg: AttnConfig, x: jax.Array,
                    enc_k: jax.Array, enc_v: jax.Array) -> jax.Array:
    """x: (B, Sq, d); enc_k/enc_v: (B, Senc, Hkv, hd) precomputed."""
    B, Sq, _ = x.shape
    q = (x @ p["wq"]).reshape(B, Sq, cfg.n_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q)
    mask = jnp.ones((B, Sq, enc_k.shape[1]), bool)
    out = _sdpa(q, enc_k, enc_v, mask, cfg.n_heads)
    return out @ p["wo"]


def encode_cross_kv(p: Param, cfg: AttnConfig, enc_out: jax.Array):
    B, Senc, _ = enc_out.shape
    k = (enc_out @ p["wk"]).reshape(B, Senc, cfg.n_kv_heads, cfg.head_dim)
    v = (enc_out @ p["wv"]).reshape(B, Senc, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        k = rmsnorm(p["k_norm"], k)
    return k, v
