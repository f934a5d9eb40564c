"""VER + transition pipeline: the paper's execution contract.

Property tested: whatever the workload does, (i) the published handle table
is always consistent (slot_map↔slot_owner bijective on resident experts),
(ii) the byte budget is never exceeded, (iii) the forward pass always sees a
fully-materialized version (hi slots referenced by slot_map hold exactly the
host-side hi weights).
"""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import (ControllerConfig, DynaExqController, build_bank,
                        expert_hi_nbytes)
from repro.core.ver import Residency


def make_controller(L=2, E=8, K=64, N=32, n_hi=3, margin=0.0,
                    rate_experts=0):
    key = jax.random.PRNGKey(0)
    w = {"w": jax.random.normal(key, (L, E, K, N), jnp.float32)
         .astype(jnp.bfloat16)}
    bank = build_bank(w, n_hi=n_hi, lo_bits=4)
    host = {k: np.asarray(v) for k, v in w.items()}
    hib = expert_hi_nbytes({k: v.shape for k, v in w.items()})
    ctl = DynaExqController(
        bank, host, n_hi_per_layer=n_hi, hi_bytes_per_expert=hib,
        cfg=ControllerConfig(update_interval_s=0.0, alpha=0.5, margin=margin,
                             migration_bytes_per_window=rate_experts * hib))
    return ctl, host, hib


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2 ** 16), windows=st.integers(1, 8))
def test_invariants_under_random_workload(seed, windows):
    ctl, host, hib = make_controller()
    rng = np.random.default_rng(seed)
    for _ in range(windows):
        counts = rng.integers(0, 50, size=(2, 8))
        ctl.observe(counts)
        ctl.update()
        ctl.tm.check_invariants()
    ctl.flush()
    ctl.tm.check_invariants()
    # published hi slots contain exactly the host hi bytes
    sm = ctl.tm.slot_map_h
    hi = np.asarray(ctl.bank.hi["w"])
    for l in range(2):
        for e in range(8):
            if sm[l, e] >= 0:
                np.testing.assert_array_equal(hi[l, sm[l, e]], host["w"][l, e])


def test_hot_experts_become_resident():
    ctl, _, _ = make_controller()
    counts = np.zeros((2, 8), np.int64)
    counts[:, [1, 4, 6]] = [100, 80, 60]
    for _ in range(3):
        ctl.observe(counts)
        ctl.update()
    ctl.flush()
    for l in range(2):
        assert ctl.tm.hi_set(l) == {1, 4, 6}


def test_workload_shift_swaps_residency():
    ctl, _, _ = make_controller(n_hi=2)
    a = np.zeros((2, 8), np.int64); a[:, [0, 1]] = 100
    b = np.zeros((2, 8), np.int64); b[:, [6, 7]] = 100
    for _ in range(3):
        ctl.observe(a); ctl.update()
    ctl.flush()
    assert ctl.tm.hi_set(0) == {0, 1}
    for _ in range(8):   # EMA needs a few windows to cross over
        ctl.observe(b); ctl.update()
    ctl.flush()
    assert ctl.tm.hi_set(0) == {6, 7}
    assert ctl.tm.stats["demoted"] >= 4


def test_migration_rate_limit_defers():
    """Bounded interference: with a rate limit of one expert per window,
    promotions trickle instead of bursting."""
    ctl, _, hib = make_controller(n_hi=3, rate_experts=1)
    counts = np.zeros((2, 8), np.int64)
    counts[:, [1, 4, 6]] = [100, 80, 60]
    ctl.observe(counts)
    ctl.update()
    promoted_after_one = ctl.tm.stats["promoted"]
    assert promoted_after_one <= 2  # ≤ 1 admitted per layer window
    for _ in range(10):
        ctl.observe(counts); ctl.update()
    ctl.flush()
    assert ctl.tm.hi_set(0) == {1, 4, 6}   # eventually converges


def test_budget_accounting_exact():
    ctl, _, hib = make_controller(n_hi=2)
    counts = np.zeros((2, 8), np.int64)
    counts[:, [2, 3]] = 50
    ctl.observe(counts); ctl.update(); ctl.flush()
    resident = int((ctl.tm.slot_map_h >= 0).sum())
    assert ctl.tracker.used == int(resident) * hib
    assert ctl.tracker.used <= ctl.tracker.cap


def test_publish_ready_probes_each_copy_not_the_bank(monkeypatch):
    """Regression: readiness used to be probed on one leaf of the CURRENT
    ``bank.hi`` — which every later ``_issue_copy`` overwrites — so an older
    pending promotion could publish based on a newer copy's readiness. Each
    ``PendingPromotion`` now carries its own result arrays: with only the
    FIRST copy's arrays reporting ready, exactly that promotion publishes."""
    from repro.core import transitions as T
    ctl, _, _ = make_controller(n_hi=3)
    ctl.tm.request_promotion(0, 1)
    ctl.tm.drain()                            # pending A (issued first)
    ctl.tm.request_promotion(0, 4)
    ctl.tm.drain()                            # pending B overwrites bank.hi
    pend = ctl.tm._pending
    assert len(pend) == 2
    assert all(p.arrays for p in pend)
    assert set(map(id, pend[0].arrays)).isdisjoint(map(id, pend[1].arrays))
    ready_ids = {id(a) for a in pend[0].arrays}
    monkeypatch.setattr(T, "_is_ready", lambda a: id(a) in ready_ids)
    published = ctl.tm.publish_ready()
    assert published == 1
    assert ctl.tm.hi_set(0) == {1}            # A published, B still pending
    assert ctl.tm.pending_experts(0) == {4}
    monkeypatch.undo()
    ctl.tm.publish_ready(wait=True)
    assert ctl.tm.hi_set(0) == {1, 4}
    ctl.tm.check_invariants()


def test_promotion_burst_writes_the_pool_in_place():
    """A burst of promotions keeps one live copy of the hi pool: each slot
    write donates the leaf the previous one produced (an undonated write
    allocated a whole pool per promotion and ran a full-width model out of
    device memory). Donated results still count as landed."""
    ctl, host, _ = make_controller(n_hi=3)
    for e in (1, 4, 6):
        ctl.tm.request_promotion(0, e)
    ctl.tm.drain()
    pend = ctl.tm._pending
    assert len(pend) == 3
    assert all(a.is_deleted() for p in pend[:-1] for a in p.arrays)
    assert not any(a.is_deleted() for a in pend[-1].arrays)
    assert ctl.tm.publish_ready(wait=True) == 3
    hi = np.asarray(ctl.bank.hi["w"])
    for e in (1, 4, 6):
        np.testing.assert_array_equal(hi[0, ctl.tm.slot_map_h[0, e]],
                                      host["w"][0, e])
    ctl.tm.check_invariants()


def test_demote_while_promoting_reclaims():
    ctl, _, _ = make_controller(n_hi=1)
    a = np.zeros((2, 8), np.int64); a[:, 0] = 100
    ctl.observe(a)
    ctl.tm.request_promotion(0, 0)
    ctl.tm.drain()
    # demote before publish
    ctl.tm.state[0, 0] = Residency.DEMOTING.value
    ctl.tm.evict_q.append((0, 0))
    ctl.tm.drain()
    ctl.tm.publish_ready(wait=True)
    assert ctl.tm.slot_map_h[0, 0] == -1
    assert ctl.tm.pools[0].n_free == 1
    assert ctl.tracker.used == 0
