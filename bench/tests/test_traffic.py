"""The traffic generator: deterministic per seed, the same work and the
same arrivals for every seed, lengths inside the mix's clips, phases on
the mix's schedule."""
import numpy as np
import pytest

from bench.traffic import generator

MIXES = ("shift_granite", "shift_qwen3")


def _sig(reqs):
    return [(r.due_s, r.workload, r.max_new_tokens, r.tokens.tobytes())
            for r in reqs]


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_schedule(mix):
    m = generator.load_mix(mix)
    a = generator.schedule(m, 49155, 2 ** 33 + 5, 30)
    b = generator.schedule(m, 49155, 2 ** 33 + 5, 30)
    c = generator.schedule(m, 49155, 2 ** 33 + 6, 30)
    assert _sig(a) == _sig(b)
    assert _sig(a) != _sig(c)


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_gets_the_same_work(mix):
    m = generator.load_mix(mix)
    runs = [generator.schedule(m, 49155, s, 30) for s in (1, 2, 3)]
    for key in (lambda r: r.prompt_len, lambda r: r.max_new_tokens):
        sets = [sorted(key(r) for r in reqs) for reqs in runs]
        assert sets[0] == sets[1] == sets[2]
    n_win = [sum(r.due_s >= 0 for r in reqs) for reqs in runs]
    assert len(set(n_win)) == 1
    assert n_win[0] == round(m["rate_rps"] * 30)


@pytest.mark.parametrize("mix", MIXES)
def test_order_seed_fixes_the_arrivals(mix):
    # Every run seed serves the same arrivals and lengths, in the order
    # the mix's order seed gives them; the run seed draws the tokens.
    m = generator.load_mix(mix)
    runs = [generator.schedule(m, 49155, s, 30) for s in (1, 2 ** 33 + 7)]
    sig = [[(r.due_s, r.workload, r.prompt_len, r.max_new_tokens)
            for r in reqs] for reqs in runs]
    assert sig[0] == sig[1]
    assert runs[0][0].tokens.tobytes() != runs[1][0].tokens.tobytes()
    other = generator.schedule(dict(m, order_seed=m["order_seed"] + 1),
                               49155, 1, 30)
    assert [r.prompt_len for r in other] != [r.prompt_len for r in runs[0]]


@pytest.mark.parametrize("mix", MIXES)
def test_clips_and_window(mix):
    m = generator.load_mix(mix)
    reqs = generator.schedule(m, 151936, 9, 40)
    p, o = m["prompt_len"], m["output_len"]
    assert all(p["min"] <= r.prompt_len <= p["max"] for r in reqs)
    assert all(o["min"] <= r.max_new_tokens <= o["max"] for r in reqs)
    assert all(-m["lead_in_s"] <= r.due_s < 40 for r in reqs)
    assert [r.due_s for r in reqs] == sorted(r.due_s for r in reqs)
    med = np.median([r.prompt_len for r in reqs if r.due_s >= 0])
    assert abs(med - p["median"]) / p["median"] < 0.1
    assert all(r.tokens.dtype == np.int32 and r.tokens.max() < 151936
               for r in reqs)


def test_phase_schedule():
    m = generator.load_mix("shift_granite")
    reqs = generator.schedule(m, 49155, 4, 60)
    for r in reqs:
        k = int(np.floor(r.due_s / m["phase_seconds"])) % 3
        assert r.workload == m["phases"][k]
    win = [r.workload for r in reqs if r.due_s >= 0]
    assert set(win) == {"text", "math", "code"}
    # The lead-in is the phase before the window's first.
    assert {r.workload for r in reqs if r.due_s < 0} == {"code"}
    steady = dict(m, phases=["text"])
    assert {r.workload for r in generator.schedule(steady, 49155, 4, 60)} \
        == {"text"}


def test_workloads_draw_from_disjoint_vocabulary():
    src = generator.TokenSource(49155, 1.2)
    rng = np.random.default_rng(0)
    seen = {w: set(src.draw(rng, w, 4000).tolist())
            for w in generator.WORKLOADS}
    assert not seen["text"] & seen["math"]
    assert not seen["math"] & seen["code"]


def test_bad_mix_is_refused():
    m = generator.load_mix("shift_granite")
    m["phases"] = ["chat"]
    with pytest.raises(ValueError):
        generator.validate(m)
