"""The program's spans and named scopes read back from a profile: scope
paths, the reduction by scope, the innermost-span gap labels, the clock
offset, and the per-layer numbers, on hand-made inputs and on a short
trace recorded on a v5e."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import spans

DATA = Path(__file__).resolve().parent / "data"

DECODE = "jit(_decode_paged_jit)/while/body/closed_call"


@pytest.mark.parametrize("path,scope", [
    (f"{DECODE}/attn/dot_general", "attn"),
    (f"{DECODE}/attn/kv_write/scatter", "kv_write"),
    (f"{DECODE}/moe/ffn/pallas_call", "moe/ffn"),
    (f"{DECODE}/moe/router/top_k", "moe/router"),
    (f"{DECODE}/moe/add", "moe"),
    ("jit(_decode_paged_jit)/lm_head/dot_general", "lm_head"),
    ("jit(write_hi_slot)/residency/promote/dynamic_update_slice",
     "residency/promote"),
    ("jit(_decode_paged_jit)/while", spans.UNSCOPED),
    ("", spans.UNSCOPED),
    ("jit(f)/attention/add", spans.UNSCOPED),
])
def test_scope_of(path, scope):
    assert spans.scope_of(path) == scope


def test_shape_and_program_names():
    assert spans.shape_of("%copy.5 = bf16[24,8,64]{2,1,0:T(8,128)(2,1)} "
                          "copy(bf16[24,8,64]{2,1,0} %p)") == "bf16[24,8,64]"
    assert spans.shape_of("%t = (f32[2]{0}, s32[]) tuple(%a, %b)") == \
        "(f32[2], s32[])"
    assert spans.shape_of("%fusion.1") == ""
    assert spans.program_of("jit__decode_paged_jit(11861842777592704838)") \
        == "jit__decode_paged_jit"
    assert spans.program_of("jit__decode_paged_jit_11861842777592704838") \
        == "jit__decode_paged_jit"


def test_op_names_from_compiled_text():
    """Where an op carries no scope of its own, its name is looked up in
    the compiled program's text: the metadata there holds the path."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("attn"):
            y = jnp.sin(x) @ x
        with jax.named_scope("moe"), jax.named_scope("ffn"):
            return jnp.cos(y) @ y

    text = f.lower(jnp.ones((8, 8))).compile().as_text()
    names = spans.op_names(text)
    assert {spans.scope_of(p) for _, p in names.values()} >= {"attn",
                                                               "moe/ffn"}
    assert all(shape == "f32[8,8]" for shape, _ in names.values()
               if shape.startswith("f32[8"))
    # The op of a profile is found by its module's texts, name and shape.
    variants = [{"dot_general.9": ("f32[8,8]", "jit(f)/moe/ffn/dot")}]
    op = "%dot_general.9 = f32[8,8]{1,0} dot(%a, %b)"
    assert spans._op_path(op, {}, variants) == "jit(f)/moe/ffn/dot"
    assert spans._op_path(op.replace("f32[8,8]", "f32[4,8]"), {},
                          variants) is None
    assert spans._op_path("%x", {"tf_op": "jit(f)/attn/add"}, []) == \
        "jit(f)/attn/add"
    assert spans._op_path('%y = f32[2]{0} add(%a), metadata={op_name='
                          '"jit(f)/kv_write/add"}', {}, []) == \
        "jit(f)/kv_write/add"
    # An instruction with no metadata is found, and has no scope.
    bare = spans.op_names("HloModule jit_f\n\nENTRY %main {\n"
                          "  %copy.3 = f32[8]{0} copy(f32[8]{0} %p)\n}")
    assert bare == {"copy.3": ("f32[8]", "")}
    assert spans._op_path("%copy.3 = f32[8]{0} copy(%p)", {}, [bare]) == ""


def test_colliding_variants_are_told_apart_or_left_unmatched():
    """Two buckets of one program reuse an instruction name for different
    ops. A profiled module is read against the variant that holds the
    most of its ops; where the variants that fit disagree, the op is
    left unmatched rather than filed under the wrong scope."""
    small = {"fusion.1": ("bf16[4,128]", "jit(p)/attn/dot"),
             "copy.2": ("bf16[8]", "jit(p)/moe/combine/copy"),
             "add.3": ("f32[]", "jit(p)/attn/add")}
    large = {"fusion.1": ("bf16[4,2048]", "jit(p)/attn/dot"),
             "copy.2": ("bf16[8]", "jit(p)/kv_write/copy"),
             "add.3": ("f32[]", "jit(p)/attn/add")}
    ops = ["%fusion.1 = bf16[4,2048]{1,0} fusion(%a)",
           "%copy.2 = bf16[8]{0} copy(%b)", "%add.3 = f32[] add(%c, %d)"]
    fits = spans.fitting_variants([spans._key(o) for o in ops],
                                  [small, large])
    assert fits == [large]
    assert [spans.scope_of(spans._op_path(o, {}, fits)) for o in ops] == \
        ["attn", "kv_write", "attn"]
    # Without the bucket-shaped op the two fit equally: the shared
    # instruction whose scopes differ is unmatched, the one that agrees
    # is kept.
    tied = spans.fitting_variants([spans._key(o) for o in ops[1:]],
                                  [small, large])
    assert len(tied) == 2
    assert spans._op_path(ops[1], {}, tied) is None
    assert spans._op_path(ops[2], {}, tied) == "jit(p)/attn/add"
    assert spans.fitting_variants([("nothing.1", "")], [small]) == []


def _hand_made():
    """One decode program and one promotion write on a 500 ns window,
    under nested program spans inside the harness's ``bench.step``."""
    ops = [
        ["%while.1", 0.0, 240.0, "", "jit(_decode_paged_jit)/while", ""],
        ["%fusion.1", 0.0, 100.0, "", f"{DECODE}/attn/dot_general",
         "bf16[4,1024]"],
        ["%scatter.2", 100.0, 20.0, "", f"{DECODE}/attn/kv_write/scatter",
         "bf16[24,64,8,16,64]"],
        ["%custom-call.3", 150.0, 50.0, "tpu_custom_call",
         f"{DECODE}/moe/ffn/pallas_call", "bf16[256,1024]"],
        ["%copy.4", 200.0, 30.0, "", "", "bf16[24,64,8,16,64]"],
        ["%fusion.9", 400.0, 10.0, "",
         "jit(write_hi_slot)/residency/promote/dynamic_update_slice",
         "bf16[24,8,1024,512]"],
        ["%fusion.12", 420.0, 5.0, "", None, "f32[8]"],
    ]
    host = [["bench.step", 0.0, 500.0, {}],
            ["engine.step", 5.0, 480.0, {"id": 1}],
            ["engine.decode", 10.0, 235.0, {"id": 2, "rows": 3}],
            ["engine.sample", 250.0, 20.0, {"id": 3}],
            ["residency.tick", 300.0, 150.0, {"id": 4}],
            ["residency.promote", 350.0, 40.0, {"id": 5, "layer": 0}]]
    return {"devices": {"/device:TPU:0": ops},
            "modules": {"/device:TPU:0": [
                ["jit__decode_paged_jit(123)", 0.0, 240.0],
                ["jit_write_hi_slot(7)", 400.0, 25.0]]},
            "host": host}


def test_reduce_hand_made_trace():
    red = spans.reduce(_hand_made(), 0.0, 500.0)
    # [0,240] ∪ [400,410] ∪ [420,425]
    assert red["busy_s"] == pytest.approx(255e-9)
    dec = "jit__decode_paged_jit"
    assert red["scope_s"] == pytest.approx({
        f"{dec}|attn": 100e-9, f"{dec}|kv_write": 20e-9,
        f"{dec}|moe/ffn": 50e-9,
        f"{dec}|(none)": 70e-9,          # the copy + the while's self time
        "jit_write_hi_slot|residency/promote": 10e-9,
        "jit_write_hi_slot|(unmatched)": 5e-9})
    assert red["unmatched_share"] == pytest.approx(5 / 255)
    assert red["span_counts"] == {"engine.step": 1, "engine.decode": 1,
                                  "engine.sample": 1, "residency.tick": 1,
                                  "residency.promote": 1}
    # Each gap goes to the innermost span over it, never to bench.step.
    assert red["idle_gaps"] == [
        ["residency.promote", pytest.approx(160e-9)],
        ["residency.tick", pytest.approx(75e-9)],
        ["residency.tick", pytest.approx(10e-9)]]
    top = red["device_ops"][0]
    assert top[0] == f"%fusion.1 bf16[4,1024] [attn] in {dec}"
    assert any(name.startswith("%copy.4 bf16[24,64,8,16,64] [(none)]")
               for name, _ in red["device_ops"])
    assert spans.decode_attention_ms(red) == pytest.approx(120e-9 * 1e3)
    assert spans.decode_moe_ms(red) == pytest.approx(50e-9 * 1e3)
    assert spans.promotion_write_ms(red) == pytest.approx(10e-9 * 1e3)


def test_gap_label_falls_back_to_the_harness_span():
    trace = _hand_made()
    trace["host"] = [["bench.step", 0.0, 300.0, {}]]
    red = spans.reduce(trace, 0.0, 600.0)
    assert {lbl for lbl, _ in red["idle_gaps"]} == {"bench.step",
                                                    "host:none"}
    assert red["span_counts"] == {}
    assert spans.decode_attention_ms(red) is None
    assert spans.promotion_write_ms(red) is None


def test_no_device_ops_reads_nothing():
    assert spans.reduce({"devices": {}, "host": []}, 0.0, 1.0) is None


def test_clock_offset_from_spans_in_both():
    host = _hand_made()["host"]
    ring = [(1, 10.0), (2, 10.000000005), (9, 3.0)]
    off = spans.clock_offset(ring, host)
    assert off == pytest.approx(5e-9 - 10.0)
    assert spans.clock_offset([(42, 1.0)], host) is None


def _ring():
    """[ph, name, cat, id, ts] rows as ``bench/spans.py`` keeps them."""
    return [
        ["b", "queue", "sched", 3, 0.5],                   # before t0
        ["b", "queue", "sched", 0, 1.0], ["e", "queue", "sched", 0, 1.5],
        ["B", "step", "engine", 10, 1.6],
        ["B", "observe", "residency", 11, 1.7],
        ["E", "observe", "residency", 11, 1.701],
        ["B", "tick", "residency", 12, 1.8],
        ["E", "tick", "residency", 12, 1.803],
        ["E", "step", "engine", 10, 1.9],
        ["b", "queue", "sched", 1, 2.0],
        ["B", "step", "engine", 13, 2.1],
        ["B", "observe", "residency", 14, 2.2],
        ["E", "observe", "residency", 14, 2.202],
        ["E", "step", "engine", 13, 2.3],
        ["b", "queue", "sched", 2, 3.0],                   # open at t1
        ["e", "queue", "sched", 1, 4.0],
        ["e", "queue", "sched", 3, 4.5],
    ]


def test_queue_wait_counts_open_requests_to_the_close():
    # Waits: rid 0 0.5 s, rid 1 2.0 s, rid 2 still queued: 5.0 - 3.0.
    assert spans.queue_wait_p95_s(_ring(), 0.9, 5.0) == pytest.approx(2.0)
    # At t1 = 2.5, rid 1 has waited 0.5 s of its 2.0.
    assert spans.queue_wait_p95_s(_ring(), 0.9, 2.5) == pytest.approx(0.5)
    assert spans.queue_wait_p95_s(_ring(), 5.0, 6.0) is None


def test_residency_host_time_per_step():
    # (1 + 3 + 2) ms of observe and tick over two steps.
    assert spans.residency_host_ms(_ring(), 0.9, 5.0) == pytest.approx(3.0)
    assert spans.residency_host_ms(_ring(), 3.0, 5.0) is None
    assert spans.span_ms(_ring(), "step", "engine", 0.9, 5.0) == \
        pytest.approx(250.0)


def test_split_reads_every_number():
    red = spans.reduce(_hand_made(), 0.0, 500.0)
    out = spans.split(red, _ring(), 0.9, 5.0)
    assert set(out) >= {"queue_wait_p95_s", "residency_host_ms",
                        "decode_attention_ms", "decode_moe_ms",
                        "promotion_write_ms"}
    assert all(out[k] is not None and out[k] > 0 for k in
               ("queue_wait_p95_s", "residency_host_ms",
                "decode_attention_ms", "decode_moe_ms",
                "promotion_write_ms"))


def test_recorded_v5e_spans():
    """Whole engine steps of granite-shift on one v5e, as
    ``spans.extract`` keeps them: every number reads, the idle gaps go to
    the program's spans, and the decode split fits in the decode span."""
    trace = json.loads((DATA / "trace_granite_v5e_spans.json").read_text())
    red = spans.reduce(trace, trace["t0_ns"], trace["t1_ns"])
    # Nearly every op's scope was found in its module's own program text.
    assert red["unmatched_share"] < 0.01
    counts = red["span_counts"]
    assert counts["engine.decode"] >= 1
    assert counts["engine.step"] == counts["engine.decode"]
    assert counts["residency.promote"] >= 1
    assert all(lbl.startswith(spans.PROGRAM_PREFIXES)
               for lbl, _ in red["idle_gaps"])
    decode = {k.split("|")[1] for k in red["scope_s"] if "decode" in k}
    assert {"attn", "kv_write", "moe/router", "moe/dispatch", "moe/ffn",
            "moe/combine", "lm_head"} <= decode
    att, moe = spans.decode_attention_ms(red), spans.decode_moe_ms(red)
    span_ms = np.mean([d for n, _, d, _ in trace["host"]
                       if n == "engine.decode"]) * 1e-6
    assert 0 < att and 0 < moe and att + moe <= span_ms
    assert 0 < spans.promotion_write_ms(red) < 1.0
    # The copies that dominate decode are named by shape and scope.
    assert any(name.startswith("%copy.") and "[(none)]" in name
               and "in jit__decode_paged_jit" in name
               for name, _ in red["device_ops"])
    ring, t0, t1 = trace["ring"], trace["ring_t0"], trace["ring_t1"]
    assert spans.queue_wait_p95_s(ring, t0, t1) >= 0
    assert spans.residency_host_ms(ring, t0, t1) > 0
    off = spans.clock_offset(((e[3], e[4]) for e in ring if e[0] == "B"),
                             trace["host"])
    assert off == pytest.approx(trace["clock_offset_s"], abs=1e-3)
