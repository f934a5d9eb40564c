"""Unified serving API: ResidencyBackend protocol conformance, continuous
batching (slot reuse / mid-stream admission), the generate() compat shim,
arrival-timed replay, the OrderedDict LRU, and the public transition
accessors. Engines come from the shared ``engine_factory`` fixture."""
import jax.numpy as jnp
import numpy as np

from repro.core import (BudgetTracker, DynaExqController, TransitionManager,
                        build_bank, expert_hi_nbytes)
from repro.serving import (BACKENDS, LRUSet, Request, RequestState,
                           RequestStream, ResidencyBackend, STAT_KEYS,
                           make_prompts)


# ---------------------------------------------------------------------------
# Backend protocol / parity
# ---------------------------------------------------------------------------

def test_backend_parity_shapes_and_footprint(serving_setup, engine_factory):
    """All backends produce the same-shaped greedy output through the SAME
    engine loop; device_bytes orders static < dynaexq < fp16."""
    cfg, _ = serving_setup
    toks = np.asarray(make_prompts("text", cfg.vocab_size, 3, 20))
    bytes_by = {}
    for name in ("fp16", "static", "dynaexq", "offload"):
        eng = engine_factory(name, max_slots=3)
        assert isinstance(eng.backend, ResidencyBackend)
        out, ttft, times = eng.generate({"tokens": toks}, 4)
        eng.flush()
        assert out.shape == (3, 4)
        assert out.dtype == jnp.int32
        assert ttft > 0 and len(times) == 3
        bytes_by[name] = eng.device_bytes()
    assert bytes_by["static"] < bytes_by["dynaexq"] < bytes_by["fp16"]


def test_stats_schema_uniform(serving_setup, engine_factory):
    """Every backend's stats() carries the full uniform key set (zeros where
    the concept does not apply)."""
    cfg, _ = serving_setup
    toks = np.asarray(make_prompts("text", cfg.vocab_size, 2, 12))
    for name in BACKENDS:
        eng = engine_factory(name, max_slots=2)
        eng.generate({"tokens": toks}, 3)
        st = eng.backend.stats()
        assert set(STAT_KEYS) <= set(st), (name, st)
        assert st["ttft_s"] > 0 and st["tpot_s"] > 0
        if name in ("fp16", "static"):
            assert st["stall_s"] == 0 and st["bytes_moved"] == 0
            assert st["promotions"] == 0 and st["demotions"] == 0
        if name == "offload":
            assert st["promotions"] == 0 and st["demotions"] == 0


def test_logits_hook_sees_every_emitting_forward(serving_setup,
                                                 engine_factory):
    """The logits hook is called once per forward with the rows it served:
    under greedy sampling each request's j-th token is the argmax of the
    row the hook saw when that request held j tokens."""
    cfg, _ = serving_setup
    eng = engine_factory("static", max_slots=2)
    seen, kinds = {}, []

    def hook(kind, logits, rows):
        kinds.append(kind)
        host = np.asarray(logits)
        for r, h in rows:
            seen[(h.id, len(h.tokens))] = int(host[r].argmax())

    eng.logits_hook = hook
    hs = [eng.submit(Request(tokens=p, max_new_tokens=4))
          for p in make_prompts("text", cfg.vocab_size, 3, 12)]
    eng.drain()
    assert kinds.count("prefill") >= 2 and "decode" in kinds
    for h in hs:
        assert [seen[(h.id, j)] for j in range(4)] == list(h.tokens)


def test_decode_program_text_holds_the_decode_step(engine_factory):
    """The engine lowers its own decode program on request, without
    serving a step."""
    eng = engine_factory("static", max_slots=2)
    text = eng.decode_program_text()
    assert "func.func public @main" in text
    assert eng.counters["steps"] == 0


# ---------------------------------------------------------------------------
# Continuous batching
# ---------------------------------------------------------------------------

def test_slot_reuse_mid_stream(serving_setup, engine_factory):
    """A queued request is admitted into a freed slot while another request
    is still mid-decode — the continuous-batching property."""
    cfg, _ = serving_setup
    eng = engine_factory("static", max_slots=2)
    p = [make_prompts("text", cfg.vocab_size, 1, ln, seed=s)[0]
         for s, ln in enumerate((10, 14, 12))]
    short = eng.submit(Request(tokens=p[0], max_new_tokens=3))
    long = eng.submit(Request(tokens=p[1], max_new_tokens=7))
    waiting = eng.submit(Request(tokens=p[2], max_new_tokens=3))

    eng.step()                       # admits short+long; both decode once
    assert short.state == RequestState.RUNNING
    assert waiting.state == RequestState.QUEUED   # no free slot yet
    eng.step()                       # short finishes (3 tokens), frees slot
    assert short.state == RequestState.FINISHED
    eng.step()                       # waiting admitted into the freed slot
    assert waiting.state == RequestState.RUNNING
    assert waiting.slot == short.slot             # literally the same slot
    assert long.state == RequestState.RUNNING     # still mid-stream

    done = eng.drain()
    assert {h.id for h in done} == {long.id, waiting.id}
    for h in (short, long, waiting):
        assert h.state == RequestState.FINISHED
        assert len(h.tokens) == h.request.max_new_tokens
        assert h.ttft_s > 0 and not np.isnan(h.token_array()).any()


def test_variable_length_prompts_same_engine(serving_setup, engine_factory):
    cfg, _ = serving_setup
    eng = engine_factory("dynaexq", max_slots=3)
    handles = [eng.submit(Request(
        tokens=make_prompts("math", cfg.vocab_size, 1, ln, seed=ln)[0],
        max_new_tokens=3)) for ln in (6, 17, 11)]
    eng.drain()
    eng.flush()
    assert all(len(h.tokens) == 3 for h in handles)


def test_continuous_batching_matches_solo_decode(serving_setup,
                                                 engine_factory):
    """Reference parity for the per-slot position vectorization: requests
    served through staggered continuous batching (mixed lengths, slot reuse
    mid-stream) produce token-for-token the same greedy output as each
    request decoded alone in a batch-1 engine."""
    cfg, _ = serving_setup
    prompts = [make_prompts("text", cfg.vocab_size, 1, ln, seed=ln)[0]
               for ln in (9, 13, 11)]
    eng = engine_factory("fp16", max_slots=2)
    handles = [eng.submit(Request(tokens=p, max_new_tokens=n))
               for p, n in zip(prompts, (3, 6, 4))]
    eng.drain()
    for p, h in zip(prompts, handles):
        solo = engine_factory("fp16", max_slots=1, max_len=64)
        ref = solo.submit(Request(tokens=p,
                                  max_new_tokens=h.request.max_new_tokens))
        solo.drain()
        assert ref.tokens == h.tokens, (ref.tokens, h.tokens)


def test_generate_shim_matches_submit_step(serving_setup, engine_factory):
    """The whole-batch generate() compat shim is token-for-token identical
    to driving submit + step + drain by hand."""
    cfg, _ = serving_setup
    toks = np.asarray(make_prompts("code", cfg.vocab_size, 3, 16))
    n_new = 5

    eng_a = engine_factory("static", max_slots=3)
    out_a, _, _ = eng_a.generate({"tokens": toks}, n_new)

    eng_b = engine_factory("static", max_slots=3)
    handles = [eng_b.submit(Request(tokens=toks[i], max_new_tokens=n_new))
               for i in range(3)]
    while eng_b.queue or any(s is not None for s in eng_b.slots):
        eng_b.step()
    out_b = np.stack([h.token_array() for h in handles], 0)

    np.testing.assert_array_equal(np.asarray(out_a), out_b)


def test_request_stream_replay(serving_setup, engine_factory):
    """RequestStream arrival times are consumed by engine.replay(): requests
    enter in arrival order and every handle completes."""
    cfg, _ = serving_setup
    stream = RequestStream(cfg.vocab_size,
                           phases=[("text", 2), ("math", 2)],
                           prompt_len=10, prompt_len_jitter=3,
                           max_new_tokens=2, arrival_rate_rps=200.0, seed=3)
    reqs = list(stream)
    assert len(reqs) == len(stream) == 4
    assert [r.workload for r in reqs] == ["text", "text", "math", "math"]
    arrivals = [r.arrival_s for r in reqs]
    assert arrivals == sorted(arrivals) and arrivals[-1] > 0
    eng = engine_factory("fp16", max_slots=2)
    handles = eng.replay(stream)
    assert [h.request.arrival_s for h in handles] == arrivals
    assert all(h.state == RequestState.FINISHED for h in handles)
    assert all(len(h.tokens) == 2 for h in handles)
    # the engine saw router traffic for every request (counts accumulated)
    assert eng.backend.router_counts()["0"].sum() > 0


# ---------------------------------------------------------------------------
# Satellites: LRU, controller config sharing, public transition accessor
# ---------------------------------------------------------------------------

def test_lru_hit_and_evict_order():
    lru = LRUSet(3)
    assert not lru.touch(1) and not lru.touch(2) and not lru.touch(3)
    assert lru.order() == [1, 2, 3]
    assert lru.touch(1)                  # hit refreshes recency
    assert lru.order() == [2, 3, 1]
    assert not lru.touch(4)              # evicts LRU entry: 2
    assert lru.order() == [3, 1, 4]
    assert 2 not in lru and 1 in lru and len(lru) == 3
    assert lru.hit(3) and lru.order() == [1, 4, 3]
    lru.add(5)                           # explicit insert evicts 1
    assert lru.order() == [4, 3, 5]
    warm = LRUSet(2, init=[7, 8, 9])
    assert warm.order() == [8, 9]


def _mini_bank(L=2, E=4, n_hi=2):
    w = {n: jnp.ones((L, E, 8, 8), jnp.bfloat16)
         for n in ("w_gate", "w_up", "w_down")}
    bank = build_bank(w, n_hi=n_hi, lo_bits=4, group_size=8)
    host = {n: np.asarray(v) for n, v in w.items()}
    hi_b = expert_hi_nbytes({n: tuple(v.shape) for n, v in w.items()})
    return bank, host, hi_b


def test_controller_configs_not_shared():
    """Regression: a dataclass-instance default arg would be one shared
    (mutable) config across all controllers."""
    (b1, h1, hb), (b2, h2, _) = _mini_bank(), _mini_bank()
    c1 = DynaExqController(b1, h1, n_hi_per_layer=2, hi_bytes_per_expert=hb)
    c2 = DynaExqController(b2, h2, n_hi_per_layer=2, hi_bytes_per_expert=hb)
    assert c1.cfg is not c2.cfg
    c1.cfg.update_interval_s = 123.0
    assert c2.cfg.update_interval_s != 123.0


def test_pending_experts_public_accessor():
    bank, host, hi_b = _mini_bank()
    tm = TransitionManager(bank, host, BudgetTracker(4 * hi_b), hi_b)
    tm.request_promotion(0, 1)
    tm.request_promotion(1, 3)
    tm.drain()                            # issue copies, not yet published
    assert tm.pending_experts(0) == {1}
    assert tm.pending_experts(1) == {3}
    tm.publish_ready(wait=True)
    assert tm.pending_experts(0) == set()
    assert tm.hi_set(0) == {1} and tm.hi_set(1) == {3}
    tm.check_invariants()
