"""The knee sweep's test by tokens: each request's ask is capped by what
it could emit between its due time and the window's close."""
import pytest

from bench import harness, sweep


def _rec(due, max_new, token_t):
    r = harness.Record(due, 8, max_new, "text", None)
    r.token_t = list(token_t)
    return r


def test_ask_is_capped_by_the_close():
    # Prefill 1 s, then 0.5 s a token, window 10 s. Due at 0: asks 4
    # (cap 1 + 18 = 19). Due at 6: 3 s left after prefill, cap 1 + 6 = 7,
    # asks 7 of 20. Due at 9.5: no time for a prefill, asks 0.
    win = [_rec(0.0, 4, [1.0, 1.5, 2.0, 2.5]),
           _rec(6.0, 20, [7.0 + 0.5 * i for i in range(6)] + [10.5]),
           _rec(9.5, 16, [])]
    out = sweep.token_test(win, 10.0, 1.0, 0.5)
    assert out["tokens_asked"] == 4 + 7 + 0
    # Tokens after the close do not count.
    assert out["tokens_emitted"] == 4 + 6
    assert out["token_share"] == pytest.approx(10 / 11)
    assert out["tokens_ok"]


def test_backlog_in_the_slots_fails():
    # Every request got its first token (the queue test passes), but each
    # emits at a quarter of the first rate's pace.
    win = [_rec(float(t), 40, [t + 1.0 + 2.0 * i for i in range(10)])
           for t in range(0, 20, 2)]
    out = sweep.token_test(win, 60.0, 1.0, 0.5)
    assert out["tokens_asked"] == 400
    assert out["tokens_emitted"] == 100
    assert not out["tokens_ok"]
