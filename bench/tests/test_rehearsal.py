"""A whole run of the harness on the CPU at a tiny size (kernels in
interpret mode): sound, it comes out correct; with the control's lower
precision in the program's place, or with the timed path broken
underneath, it comes out not correct."""
import jax
import jax.numpy as jnp
import pytest

from bench import rehearse

LIMIT = rehearse.TINY_LIMITS["mean_logit_gap"]["limit"]


def test_sound_run_is_correct_and_reports_every_metric():
    res = rehearse.rehearse(21, 3.0)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"ttft_p95_s", "tpot_p95_s",
                                   "output_tokens_per_s", "setup_s"}
    assert res["attempted"] == 12 and res["failed"] == 0
    assert list(res)[-2:] == ["checks", "_extra"]
    assert res["_extra"]["compiles_in_window"] == 0


def test_traced_run_reads_the_host_side_layers():
    res = rehearse.rehearse(22, 3.0, trace=True)
    assert res["correct"], res["checks"]
    for name in ("generator_lag_p95_s", "decode_rows_mean", "prefill_ms",
                 "decode_step_ms", "hi_route_share", "dispatch_pad_ratio",
                 "model_mfu"):
        assert name in res["metrics"], name
    # No device trace on the CPU: its readers read nothing, and say so by
    # leaving their metrics out.
    assert "device_idle_share" not in res["metrics"]
    assert "moe_ffn_roofline" not in res["metrics"]


@pytest.mark.parametrize("seed", [31, 32])
def test_control_at_int2_fails_the_limit(seed):
    # The control is the program with its int2 lo tier, judged by the
    # same limits as every run.
    res = rehearse.rehearse(seed, 3.0, control=True)
    assert res["_extra"]["readings"]["mean_logit_gap"] > LIMIT
    assert not res["correct"], res["checks"]


class _ArgminNumpy:
    """jax.numpy with argmax replaced by argmin: every served token becomes
    the worst one, where the engine draws it."""

    def __getattr__(self, name):
        return getattr(jnp, name)

    @staticmethod
    def argmax(x, axis=None):
        return jnp.argmin(x, axis)


def _wrap_decode(monkeypatch, change):
    from repro.serving import engine as eng
    orig = eng._decode_paged_jit
    statics = ("cfg", "capacity_factor", "moe_dispatch", "row_capacity",
               "ep")

    def broken(params, token, pos, caches, banks, *rest, **kw):
        logits, new_caches, counts = orig.__wrapped__(
            params, token, pos, caches, banks, *rest, **kw)
        return change(logits, caches, new_caches, counts)
    monkeypatch.setattr(eng, "_decode_paged_jit",
                        jax.jit(broken, static_argnames=statics))


def test_fault_token_altered(monkeypatch):
    from repro.serving import engine as eng
    monkeypatch.setattr(eng, "jnp", _ArgminNumpy())
    res = rehearse.rehearse(23, 3.0)
    assert not res["correct"]


def test_fault_step_returns_state_unchanged(monkeypatch):
    # The decode step leaves the engine's state as it found it: no KV
    # write, no position or input token advanced. Every step then serves
    # the same logits again.
    from repro.serving import engine as eng
    _wrap_decode(monkeypatch, lambda lg, old, new, c: (lg, old, c))
    orig = eng.InferenceEngine._decode_one

    def frozen(self, active, finished, **kw):
        pos, tok = self.pos.copy(), self.tokens.copy()
        orig(self, active, finished, **kw)
        live = [i for i, h in active if h.state.value == "running"]
        self.pos[live], self.tokens[live] = pos[live], tok[live]
    monkeypatch.setattr(eng.InferenceEngine, "_decode_one", frozen)
    res = rehearse.rehearse(24, 3.0)
    assert not res["correct"]


def test_fault_half_the_batch_left_out(monkeypatch):
    # Every other decode row is left out: it gets its neighbour's logits.
    def half(lg, old, new, c):
        return lg.at[1::2].set(lg[0::2]), new, c
    _wrap_decode(monkeypatch, half)
    res = rehearse.rehearse(25, 3.0)
    assert not res["correct"]
