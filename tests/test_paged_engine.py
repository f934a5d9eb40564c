"""Paged KV-cache subsystem, end to end: the paged engine is token-identical
to the dense-slot engine on mixed-length request streams (full-attention,
sliding-window ring and mamba stacks, with and without prefix sharing),
prefix reuse measurably skips prefill compute, KV block reservations and
expert hi-tier promotions draw from ONE BudgetTracker (promotion
backpressure under KV pressure), and the paged flash-decode kernel matches
the gathered dense reference."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import ControllerConfig
from repro.models import (attn_logical_capacity, decode_step,
                          decode_step_paged, init_caches, init_paged_caches,
                          init_params)
from repro.models import layers as L
from repro.models.model import DecodeCaches
from repro.serving import (EngineConfig, InferenceEngine, Request,
                           RequestStream, make_backend, make_prompts)
from repro.serving.engine import _prefill_paged_jit


def _engine(cfg, params, backend_name="fp16", paged=True, sharing=True,
            max_slots=2, max_len=64, prefill_rows=None, hbm=None, **bkw):
    clone = jax.tree_util.tree_map(lambda x: x, params)
    if backend_name == "dynaexq":
        bkw.setdefault("lo_bits", 4)
        bkw.setdefault("n_hi_per_layer", 2)
        bkw.setdefault("controller", ControllerConfig(update_interval_s=0.0))
    return InferenceEngine(
        cfg, clone, make_backend(backend_name, **bkw),
        EngineConfig(max_slots=max_slots, max_len=max_len, paged=paged,
                     prefix_sharing=sharing, prefill_rows=prefill_rows,
                     hbm_budget_bytes=hbm))


def _serve(eng, prompts, news, flush_each_step=False):
    """``flush_each_step``: barrier the backend's async transitions at
    every window boundary. DynaExq publishes a promotion whenever its
    device copy happens to report ready relative to the host loop, so two
    engines (or two runs) legitimately serve a given step at different
    expert precisions; flushing pins publication to the issuing window,
    making token streams comparable across engines."""
    handles = [eng.submit(Request(tokens=p, max_new_tokens=n))
               for p, n in zip(prompts, news)]
    if flush_each_step:
        while eng.queue or any(s is not None for s in eng.slots):
            eng.step()
            eng.backend.flush()
    else:
        eng.drain()
    return [h.tokens for h in handles]


# ---------------------------------------------------------------------------
# Parity: paged == dense, token for token
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend_name", ["fp16", "dynaexq"])
def test_paged_matches_dense_mixed_lengths(serving_setup, backend_name):
    cfg, params = serving_setup
    lens, news = (9, 13, 30, 7, 21), (3, 6, 5, 8, 4)
    prompts = [make_prompts("text", cfg.vocab_size, 1, ln, seed=ln)[0]
               for ln in lens]
    sync = backend_name == "dynaexq"         # pin async publish timing
    dense = _serve(_engine(cfg, params, backend_name, paged=False),
                   prompts, news, flush_each_step=sync)
    eng = _engine(cfg, params, backend_name, paged=True)
    paged = _serve(eng, prompts, news, flush_each_step=sync)
    assert dense == paged
    if sync:
        assert eng.stats()["promotions"] > 0  # parity WITH promotions live
    eng.pool.check_invariants()
    st = eng.stats()
    assert st["kv_blocks_in_use"] >= 0 and "prefix_hit_tokens" in st


def test_paged_matches_dense_sliding_window(serving_setup):
    """Ring (sliding-window) caches: wrap during prefill AND decode, with
    prefix hits whose shared blocks get copy-on-written when the ring wraps
    back over them."""
    cfg, params0 = serving_setup
    cfg = dataclasses.replace(
        cfg, name="granite-sw32",
        attn=dataclasses.replace(cfg.attn, sliding_window=32))
    params = init_params(jax.random.PRNGKey(0), cfg)
    sysp = make_prompts("text", cfg.vocab_size, 1, 16, seed=5)[0]
    prompts = [np.concatenate(
        [sysp, make_prompts("code", cfg.vocab_size, 1, 4, seed=i)[0]])
        for i in range(3)]
    prompts.append(make_prompts("math", cfg.vocab_size, 1, 40, seed=9)[0])
    news = (20, 20, 20, 6)
    dense = _serve(_engine(cfg, params, paged=False, max_slots=3,
                           prefill_rows=1), prompts, news)
    eng = _engine(cfg, params, paged=True, max_slots=3, prefill_rows=1)
    paged = _serve(eng, prompts, news)
    assert dense == paged
    st = eng.stats()
    # decode wrapped past the window onto trie-shared blocks → COW fired
    assert st["kv_cow_copies"] > 0 and st["prefix_hit_tokens"] > 0
    eng.pool.check_invariants()


def test_paged_matches_dense_mamba_and_mixed_stack(serving_setup):
    """Pure-SSM stacks have no KV to page (engine falls back to dense rows
    even under paged=True); mixed mamba+attn stacks page their attention
    caches with the trie auto-disabled (recurrent state cannot be leased)."""
    cfgm = dataclasses.replace(get_config("mamba2-130m", reduced=True),
                               n_layers=2)
    pm = init_params(jax.random.PRNGKey(0), cfgm)
    prompts = [make_prompts("text", cfgm.vocab_size, 1, ln, seed=ln)[0]
               for ln in (5, 19, 40)]
    dense = _serve(_engine(cfgm, pm, paged=False), prompts, (2, 2, 2))
    eng = _engine(cfgm, pm, paged=True)
    assert eng.pool is None                       # nothing to page
    assert dense == _serve(eng, prompts, (2, 2, 2))

    cfgj = get_config("jamba-v0_1-52b", reduced=True)
    pj = init_params(jax.random.PRNGKey(0), cfgj)
    prompts = [make_prompts("text", cfgj.vocab_size, 1, ln, seed=ln)[0]
               for ln in (5, 19, 33)]
    dense = _serve(_engine(cfgj, pj, paged=False), prompts, (3, 3, 3))
    eng = _engine(cfgj, pj, paged=True)
    assert eng.pool is not None and eng.trie is None
    assert dense == _serve(eng, prompts, (3, 3, 3))
    eng.pool.check_invariants()


# ---------------------------------------------------------------------------
# Model level: one paged decode step == one dense decode step, bit for bit
# ---------------------------------------------------------------------------

def _stack(serving_setup, stack):
    """(cfg, params, max_len, block_tokens) of a full-attention stack, a
    sliding-window ring (capacity 8 in two blocks, so decode wraps) and a
    mixed mamba/attention stack of two superblocks."""
    cfg, params = serving_setup
    if stack == "full":
        return cfg, params, 32, 4
    if stack == "sliding":
        cfg = dataclasses.replace(
            cfg, name="granite-sw8",
            attn=dataclasses.replace(cfg.attn, sliding_window=8))
        return cfg, params, 32, 4
    cfg = dataclasses.replace(get_config("jamba-v0_1-52b", reduced=True),
                              n_layers=16)
    return cfg, init_params(jax.random.PRNGKey(0), cfg), 16, 4


def _random_like(tree, seed):
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree_util.tree_unflatten(treedef, [
        jax.random.normal(k, a.shape, jnp.float32).astype(a.dtype)
        for k, a in zip(keys, leaves)])


def _row_view(pool, table_row, slots, hkv):
    """Row's K (or V) at ``slots`` read from a (nsb, N, bt, Hkv·hd) pool
    leaf by hand: (nsb, Hkv, len(slots), hd)."""
    bt = pool.shape[2]
    rows = np.stack([pool[:, table_row[s // bt], s % bt] for s in slots], 1)
    return rows.reshape(rows.shape[:2] + (hkv, -1)).transpose(0, 2, 1, 3)


@pytest.mark.parametrize("stack", ["full", "sliding", "mamba"])
def test_decode_step_paged_matches_dense_bit_for_bit(serving_setup, stack):
    """Two chained ``decode_step_paged`` steps against a scrambled pool give
    the dense ``decode_step``'s logits, cache rows and recurrent state bit
    for bit. Row 0 is deep in its table (its ring wraps in the sliding
    stack); row 1's table ends in -1 entries and its second step lands in
    a freshly allocated block; row 2 is vacant and writes the trash block.
    The pool changes at the live rows' write targets and the trash
    block's, nowhere else."""
    cfg, params, max_len, bt = _stack(serving_setup, stack)
    B, N = 3, 40
    dense = init_caches(cfg, B, max_len)
    dense = DecodeCaches(blocks=_random_like(dense.blocks, 1), cross=None)
    paged = init_paged_caches(cfg, B, max_len, bt, N)
    blocks = _random_like(paged.blocks, 2)
    attn = [k for k, c in blocks.items() if isinstance(c, L.PagedKVCache)]
    assert attn
    C = dense.blocks[attn[0]].k.shape[3]
    nb, hkv = C // bt, cfg.attn.n_kv_heads
    assert attn_logical_capacity(cfg, max_len, bt) == C
    rng = np.random.default_rng(0)
    free = list(rng.permutation(np.arange(1, N)))
    pos = np.array([C + 3, 3, 0] if stack == "sliding" else [14, 7, 0],
                   np.int32)
    table = np.full((B, nb), -1, np.int32)
    for b in (0, 1):
        for j in range(min(nb, pos[b] // bt + 1)):
            table[b, j] = free.pop()
    # the pool holds the dense rows' slots, wherever the tables put them
    for k in blocks:
        if k not in attn:
            blocks[k] = dense.blocks[k]
            continue
        leaves = []
        for pool, rows in zip(blocks[k], dense.blocks[k]):
            pool, rows = np.array(pool), np.asarray(rows)
            for b, j in zip(*np.nonzero(table >= 0)):
                seg = rows[:, b, :, j * bt:(j + 1) * bt]
                pool[:, table[b, j]] = seg.transpose(0, 2, 1, 3).reshape(
                    pool.shape[0], bt, -1)
            leaves.append(jnp.asarray(pool))
        blocks[k] = L.PagedKVCache(*leaves)
    paged = DecodeCaches(blocks=blocks, cross=None)
    valid = jnp.asarray([True, True, False])

    for step in range(2):
        slot = pos % C
        for b in (0, 1):
            if table[b, slot[b] // bt] < 0:
                table[b, slot[b] // bt] = free.pop()
        wblk = np.where([True, True, False], table[np.arange(B), slot // bt],
                        0).astype(np.int32)
        woff = np.where([True, True, False], slot % bt, 0).astype(np.int32)
        tok = jnp.asarray(rng.integers(0, cfg.vocab_size, B), jnp.int32)
        before = {k: [np.asarray(a) for a in paged.blocks[k]] for k in attn}
        want, dense, _ = decode_step(params, cfg, tok, jnp.asarray(pos),
                                     dense, row_valid=valid)
        got, paged, _ = decode_step_paged(
            params, cfg, tok, jnp.asarray(pos), paged, jnp.asarray(table),
            jnp.asarray(wblk), jnp.asarray(woff), row_valid=valid)
        np.testing.assert_array_equal(np.asarray(got)[:2],
                                      np.asarray(want)[:2])
        for k in attn:
            for old, pool, rows in zip(before[k], paged.blocks[k],
                                       dense.blocks[k]):
                pool, rows = np.asarray(pool), np.asarray(rows)
                for b in (0, 1):
                    live = np.arange(max(0, pos[b] + 1 - C), pos[b] + 1) % C
                    np.testing.assert_array_equal(
                        _row_view(pool, table[b], live, hkv),
                        rows[:, b][:, :, live])
                changed = np.argwhere((pool != old).any(axis=(0, 3)))
                assert {tuple(x) for x in changed} <= set(zip(wblk, woff))
                assert (0, 0) in {tuple(x) for x in changed}  # vacant row
        for k in blocks:
            if k not in attn:
                for got_s, want_s in zip(paged.blocks[k], dense.blocks[k]):
                    np.testing.assert_array_equal(np.asarray(got_s),
                                                  np.asarray(want_s))
        pos = pos + np.array([1, 1, 0], np.int32)


def test_decode_step_paged_moves_no_pool_slice(serving_setup,
                                              paged_decode_program_check):
    """The paged decode program keeps the stacked pool out of the layer
    scan's ``xs``/``ys`` (compiled for the CPU here; for a described TPU
    in ``test_tpu_compile.py``)."""
    paged_decode_program_check(serving_setup[0])


# ---------------------------------------------------------------------------
# Prefix sharing: measurable reuse
# ---------------------------------------------------------------------------

def test_prefix_sharing_skips_prefill_compute(serving_setup):
    """Shared-prefix workload: token-identical to dense, strictly fewer
    prompt tokens computed, hits reported per request and in stats()."""
    cfg, params = serving_setup
    sysp = make_prompts("text", cfg.vocab_size, 1, 32, seed=999)[0]
    prompts = [np.concatenate(
        [sysp, make_prompts("math", cfg.vocab_size, 1, 8, seed=i)[0]])
        for i in range(4)]
    news = (4,) * 4
    dense_eng = _engine(cfg, params, paged=False)
    dense = _serve(dense_eng, prompts, news)
    eng = _engine(cfg, params, paged=True, sharing=True)
    shared = _serve(eng, prompts, news)
    assert dense == shared
    st, st_d = eng.stats(), dense_eng.stats()
    assert st["prefill_tokens"] < st_d["prefill_tokens"]
    assert st["prefix_hit_tokens"] > 0
    assert st["kv_blocks_in_use"] > 0             # trie keeps prefixes warm
    # every prompt token was either computed or served from the trie
    assert sum(st[k] for k in ("prefill_tokens", "prefix_hit_tokens")) == \
        sum(len(p) for p in prompts)
    eng.pool.check_invariants()


def test_prefix_sharing_compile_count(serving_setup):
    """Bucketed admission survives paging: a mixed-length stream compiles
    at most #buckets paged-prefill executables per has_prefix variant."""
    cfg, params = serving_setup
    eng = _engine(cfg, params, paged=True, max_slots=4, prefill_rows=4)
    before = _prefill_paged_jit._cache_size()
    lens = (4, 7, 9, 13, 18, 23, 29, 33, 41, 55)
    prompts = [make_prompts("text", cfg.vocab_size, 1, ln, seed=ln)[0]
               for ln in lens]
    _serve(eng, prompts, (2,) * len(lens))
    n_buckets = len(eng.buckets)
    assert len(eng.prefill_shapes) <= n_buckets
    assert _prefill_paged_jit._cache_size() - before <= 2 * n_buckets
    assert eng.counters["prefills"] < len(lens)


# ---------------------------------------------------------------------------
# One budget: KV admission vs expert promotions
# ---------------------------------------------------------------------------

def test_kv_and_promotions_share_one_budget(serving_setup):
    """KV block reservations and hi-tier promotions draw from the same
    BudgetTracker: under KV pressure promotions defer (backpressure), and
    finished requests' freed KV bytes are exactly what lets the deferred
    promotions proceed. check_invariants stays green on the account-scoped
    views throughout."""
    cfg, params = serving_setup
    probe = _engine(cfg, params, "dynaexq", paged=True, max_slots=8)
    hi_b = next(iter(probe.backend.controllers.values())).tm.hi_bytes
    bb = probe.pool.block_bytes
    # 8 in-flight requests × 4 blocks (+ trash) of live KV, an envelope
    # with headroom for exactly ONE hi expert on top: while the requests
    # run, at most one promotion fits; their release frees > hi_b.
    kv_live = (1 + 8 * 4) * bb
    assert kv_live - bb > hi_b, "config drifted: KV must outweigh one expert"
    eng = _engine(cfg, params, "dynaexq", paged=True, sharing=False,
                  max_slots=8, hbm=kv_live + hi_b)
    prompts = [make_prompts("text", cfg.vocab_size, 1, 56, seed=i)[0]
               for i in range(8)]
    handles = [eng.submit(Request(tokens=p, max_new_tokens=16))
               for p in prompts]
    eng.step()                                # admit all 8 → KV fully live
    eng.step()
    ctls = list(eng.backend.controllers.values())
    assert eng.pool.bytes_in_use == kv_live
    deferred_mid = sum(c.tm.stats["deferred"] for c in ctls)
    promoted_mid = sum(c.tm.stats["promoted"] for c in ctls)
    assert deferred_mid > 0                   # KV pressure deferred hi work
    assert promoted_mid <= 1                  # only the headroom expert fit
    for c in ctls:
        c.tm.check_invariants()               # per-account books stay exact
    eng.drain()
    eng.flush()
    assert all(len(h.tokens) > 0 for h in handles)
    # requests done → KV bytes returned to the shared envelope → the same
    # promotions now fit (drive a few policy windows on the warm hotness)
    assert eng.pool.quota_blocks == 0 and eng.pool.blocks_in_use == 0
    for _ in range(3):
        eng.backend.force_update()
    eng.flush()
    assert sum(c.tm.stats["promoted"] for c in ctls) > promoted_mid
    for c in ctls:
        c.tm.check_invariants()
    eng.pool.check_invariants()


def test_kv_admission_waits_for_budget(serving_setup):
    """A request whose KV quota cannot be reserved waits QUEUED (no crash,
    no partial admission) and is admitted once earlier requests finish."""
    cfg, params = serving_setup
    probe = _engine(cfg, params, paged=True, max_slots=2)
    one_req_blocks = probe._quota_blocks(24, 0, 4)
    block_bytes = probe.pool.block_bytes
    # envelope fits the trash block + exactly one in-flight request
    eng = _engine(cfg, params, paged=True, sharing=False, max_slots=2,
                  hbm=(1 + one_req_blocks) * block_bytes)
    prompts = [make_prompts("text", cfg.vocab_size, 1, 24, seed=i)[0]
               for i in range(3)]
    handles = [eng.submit(Request(tokens=p, max_new_tokens=4))
               for p in prompts]
    eng.step()
    running = [h for h in handles if h.slot is not None]
    assert len(running) == 1                  # budget admits exactly one
    eng.drain()
    assert all(len(h.tokens) == 4 for h in handles)
    assert eng.pool.stats["quota_denied"] > 0
    eng.pool.check_invariants()


def test_undersized_pool_defers_instead_of_crashing(serving_setup):
    """An explicitly undersized pool (kv_blocks < max_slots·nb + 1)
    serializes admissions against the physical block supply; a pool too
    small for even ONE sequence is rejected at engine construction."""
    cfg, params = serving_setup
    clone = jax.tree_util.tree_map(lambda x: x, params)
    with pytest.raises(ValueError, match="kv_blocks"):
        InferenceEngine(cfg, clone, make_backend("fp16"),
                        EngineConfig(max_slots=2, max_len=64, kv_blocks=3))
    # room for exactly one sequence (4 blocks) + trash: 3 requests on 2
    # slots must run one at a time, never exhausting the pool
    eng = InferenceEngine(
        cfg, jax.tree_util.tree_map(lambda x: x, params),
        make_backend("fp16"),
        EngineConfig(max_slots=2, max_len=64, kv_blocks=5,
                     prefix_sharing=False))
    prompts = [make_prompts("text", cfg.vocab_size, 1, 30, seed=i)[0]
               for i in range(3)]
    handles = [eng.submit(Request(tokens=p, max_new_tokens=4))
               for p in prompts]
    eng.step()
    assert sum(h.slot is not None for h in handles) == 1
    eng.drain()
    assert all(len(h.tokens) == 4 for h in handles)
    eng.pool.check_invariants()


def test_submit_rejects_never_satisfiable_request(serving_setup):
    """A request whose worst-case KV quota can never fit the envelope is
    rejected loudly at submit() instead of blocking the queue forever."""
    cfg, params = serving_setup
    probe = _engine(cfg, params, paged=True, max_slots=2)
    bb = probe.pool.block_bytes
    eng = _engine(cfg, params, paged=True, sharing=False, max_slots=2,
                  hbm=2 * bb)                 # trash + ONE block, ever
    with pytest.raises(ValueError, match="envelope"):
        eng.submit(Request(tokens=make_prompts(
            "text", cfg.vocab_size, 1, 40, seed=0)[0], max_new_tokens=8))
    # a request that fits still serves
    h = eng.submit(Request(tokens=make_prompts(
        "text", cfg.vocab_size, 1, 8, seed=1)[0], max_new_tokens=4))
    eng.drain()
    assert len(h.tokens) == 4
    eng.pool.check_invariants()


# ---------------------------------------------------------------------------
# Virtual-clock replay (deterministic CI streams)
# ---------------------------------------------------------------------------

def test_replay_virtual_clock_deterministic(serving_setup):
    """replay(realtime=False) decouples arrival pacing from the machine: two
    replays of the same stream produce identical admission interleavings
    and token streams, and arrival order is preserved."""
    cfg, params = serving_setup

    def run():
        eng = _engine(cfg, params, paged=True, max_slots=2)
        stream = RequestStream(cfg.vocab_size,
                               phases=[("text", 3), ("math", 3)],
                               prompt_len=10, prompt_len_jitter=3,
                               max_new_tokens=3, arrival_rate_rps=200.0,
                               seed=3)
        handles = eng.replay(stream, realtime=False, virtual_step_s=2e-3)
        order = [h.id for h in handles]
        return order, [h.tokens for h in handles], eng.counters.copy()

    o1, t1, c1 = run()
    o2, t2, c2 = run()
    assert o1 == o2 and t1 == t2
    # the full engine schedule (admission groups, step count) repeats too
    for k in ("steps", "prefills", "admitted", "finished"):
        assert c1[k] == c2[k], (k, c1, c2)


def test_replay_realtime_still_default(serving_setup):
    cfg, params = serving_setup
    eng = _engine(cfg, params, paged=True, max_slots=2)
    stream = RequestStream(cfg.vocab_size, phases=[("text", 3)],
                           prompt_len=8, max_new_tokens=2,
                           arrival_rate_rps=500.0, seed=1)
    handles = eng.replay(stream)              # wall-clock path unchanged
    assert all(len(h.tokens) == 2 for h in handles)
