"""Architecture modules.

``moe_gqa`` reproduces, bit for bit, the weights, reference logits and
operation counts recorded from the benchmark's code before it moved into
``bench/arch/`` (``data/moe_gqa_pin.json``: CPU, two small sizes, one tied
without q/k norm and one untied with it). An architecture added as files
alone (a module in a ``bench/arch``-style directory and a configuration
naming it) goes through the harness with no edit to it, and a
configuration that names no module, or one that does not exist, is
refused with the key named."""
import hashlib
import json
import shutil
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

import bench.arch
from bench import harness
from bench.arch import common

ROOT = Path(__file__).resolve().parents[2]
PIN = json.loads((Path(__file__).parent / "data" /
                  "moe_gqa_pin.json").read_text())
CASES = sorted(PIN["cases"])
MOE_GQA = bench.arch.load({"name": "pin", "arch_module": "moe_gqa"})


def _leaves(params):
    return {"/".join(getattr(k, "key", str(k)) for k in path): v
            for path, v in jax.tree_util.tree_flatten_with_path(params)[0]}


@pytest.mark.parametrize("case", CASES)
def test_param_spec_and_weights_are_pinned(case):
    pin = PIN["cases"][case]
    d = MOE_GQA.dims(pin["conf"])
    assert d == pin["dims"]
    spec = MOE_GQA.param_spec(d)
    assert [["/".join(p), list(s), t, c] for p, s, t, c in spec] == \
        [[w["path"], w["shape"], w["dtype"], w["scale"]]
         for w in pin["weights"]]
    flat = _leaves(MOE_GQA.make_params(d, PIN["seed"]))
    assert sorted(flat) == sorted(w["path"] for w in pin["weights"])
    for w in pin["weights"]:
        got = hashlib.sha256(np.asarray(flat[w["path"]]).tobytes())
        assert got.hexdigest() == w["sha256"], w["path"]


@pytest.mark.parametrize("case", CASES)
def test_reference_logits_are_pinned(case):
    pin = PIN["cases"][case]
    ref = pin["reference"]
    d = MOE_GQA.dims(pin["conf"])
    params = MOE_GQA.make_params(d, PIN["seed"])
    logits = np.asarray(MOE_GQA.Reference(d, params).logits_at(
        np.asarray(ref["tokens"], np.int32),
        np.asarray(ref["positions"], np.int32)), np.float32)
    assert list(logits.shape) == ref["shape"]
    assert logits.argmax(-1).tolist() == ref["argmax"]
    assert logits.max(-1).tolist() == ref["max"]
    assert hashlib.sha256(logits.tobytes()).hexdigest() == ref["sha256"]


@pytest.mark.parametrize("case", CASES)
def test_operation_counts_are_pinned(case):
    pin = PIN["cases"][case]
    d = MOE_GQA.dims(pin["conf"])
    for ctx, want in pin["flops_per_token"].items():
        assert MOE_GQA.flops_per_token(d, int(ctx)) == want
    for span, want in pin["prompt_flops"].items():
        start, end = map(int, span.split("-"))
        assert MOE_GQA.prompt_flops(d, start, end) == want
    assert [list(m) for m in MOE_GQA.expert_matrices(d)] == \
        pin["expert_matrices"]
    assert MOE_GQA.expert_hi_bytes(d) == pin["expert_hi_bytes"]
    for bits, want in pin["expert_lo_bytes"].items():
        assert MOE_GQA.expert_lo_bytes(d, int(bits), 64) == want
    work = pin["moe_ffn_work"]
    assert MOE_GQA.moe_ffn_work(d, work["forwards"], 4, 64) == work["work"]


# ---------------------------------------------------------------------------
# An architecture added as files alone
# ---------------------------------------------------------------------------

#: A dense GQA block with a SwiGLU MLP in every layer, mapped onto the
#: program's llama3.2 entry. It is written to a temporary directory that
#: joins ``bench.arch``'s search path, as a new file of ``bench/arch/``
#: would be found.
DENSE_MODULE = '''
"""Dense GQA with a SwiGLU MLP in every layer (test fixture)."""
import dataclasses
import functools

from bench.arch.common import make_weights, rmsnorm, rope

KEYS = ("n_layers", "d_model", "vocab_size", "n_heads", "n_kv_heads",
        "head_dim", "d_ff", "norm_eps", "rope_theta")


def dims(conf):
    return {k: conf[conf["arch"][k]] if isinstance(conf["arch"][k], str)
            else conf["arch"][k] for k in KEYS}


def param_spec(d):
    L, D, V, F = d["n_layers"], d["d_model"], d["vocab_size"], d["d_ff"]
    H, Hkv, hd = d["n_heads"], d["n_kv_heads"], d["head_dim"]
    b = ("blocks", "0")
    return [
        (("embed",), (V, D), "bfloat16", 0.02),
        (("final_norm", "scale"), (D,), "bfloat16", 0.0),
        (("lm_head",), (D, V), "bfloat16", D ** -0.5),
        (b + ("norm1", "scale"), (L, D), "bfloat16", 0.0),
        (b + ("norm2", "scale"), (L, D), "bfloat16", 0.0),
        (b + ("attn", "wq"), (L, D, H * hd), "bfloat16", D ** -0.5),
        (b + ("attn", "wk"), (L, D, Hkv * hd), "bfloat16", D ** -0.5),
        (b + ("attn", "wv"), (L, D, Hkv * hd), "bfloat16", D ** -0.5),
        (b + ("attn", "wo"), (L, H * hd, D), "bfloat16", (H * hd) ** -0.5),
        (b + ("mlp", "w_gate"), (L, D, F), "bfloat16", D ** -0.5),
        (b + ("mlp", "w_up"), (L, D, F), "bfloat16", D ** -0.5),
        (b + ("mlp", "w_down"), (L, F, D), "bfloat16", F ** -0.5),
    ]


def make_params(d, seed):
    return make_weights(param_spec(d), seed)


def program_arch(conf, d):
    from repro.configs import get_config
    base = get_config(conf["serving"]["program_arch"])
    attn = dataclasses.replace(base.attn, n_heads=d["n_heads"],
                               n_kv_heads=d["n_kv_heads"],
                               head_dim=d["head_dim"],
                               rope_theta=float(d["rope_theta"]))
    return dataclasses.replace(base, n_layers=d["n_layers"],
                               d_model=d["d_model"], d_ff=d["d_ff"],
                               vocab_size=d["vocab_size"], attn=attn,
                               norm_eps=float(d["norm_eps"]))


def _forward(d, params, tokens, positions):
    import jax
    import jax.numpy as jnp
    f32 = lambda a: a.astype(jnp.float32)
    blk = params["blocks"]["0"]
    x = f32(params["embed"][tokens])
    B, S, D = x.shape
    H, Hkv, hd = d["n_heads"], d["n_kv_heads"], d["head_dim"]
    mask = jnp.tril(jnp.ones((S, S), bool))
    for l in range(d["n_layers"]):
        a = blk["attn"]
        h = rmsnorm(x, f32(blk["norm1"]["scale"][l]), d["norm_eps"])
        q = rope((h @ f32(a["wq"][l])).reshape(B, S, H, hd), d["rope_theta"])
        k = rope((h @ f32(a["wk"][l])).reshape(B, S, Hkv, hd),
                 d["rope_theta"])
        v = (h @ f32(a["wv"][l])).reshape(B, S, Hkv, hd)
        k, v = jnp.repeat(k, H // Hkv, 2), jnp.repeat(v, H // Hkv, 2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, S, H * hd)
        x = x + o @ f32(a["wo"][l])
        m = blk["mlp"]
        h = rmsnorm(x, f32(blk["norm2"]["scale"][l]), d["norm_eps"])
        x = x + (jax.nn.silu(h @ f32(m["w_gate"][l]))
                 * (h @ f32(m["w_up"][l]))) @ f32(m["w_down"][l])
    xs = jnp.take_along_axis(x, positions[..., None], axis=1)
    xs = rmsnorm(xs, f32(params["final_norm"]["scale"]), d["norm_eps"])
    return xs @ f32(params["lm_head"])


class Reference:
    def __init__(self, d, params):
        import jax
        self.params = params
        self._fwd = jax.jit(functools.partial(_forward, d))

    def logits_at(self, tokens, positions):
        import jax
        with jax.default_matmul_precision("highest"):
            return self._fwd(self.params, tokens, positions)


def flops_per_token(d, ctx):
    D, H, Hkv, hd = d["d_model"], d["n_heads"], d["n_kv_heads"], d["head_dim"]
    proj = 2 * (D * H * hd + 2 * D * Hkv * hd + H * hd * D)
    return d["n_layers"] * (proj + 4 * ctx * H * hd + 6 * D * d["d_ff"]) \\
        + 2 * D * d["vocab_size"]


def prompt_flops(d, start, end):
    return sum(flops_per_token(d, p + 1) for p in range(start, end))


def moe_ffn_work(d, forwards, lo_bits, group):
    return {"flops": 0.0, "bytes": 0.0}
'''

DENSE_CONF = {
    "name": "tiny-dense", "source": "test fixture",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 96,
    "rms_norm_eps": 1e-5, "rope_theta": 500000.0,
    "arch_module": "dense_gqa_fixture",
    "arch": {"n_layers": "num_hidden_layers", "d_model": "hidden_size",
             "vocab_size": "vocab_size", "n_heads": "num_attention_heads",
             "n_kv_heads": "num_key_value_heads", "head_dim": 16,
             "d_ff": "intermediate_size", "norm_eps": "rms_norm_eps",
             "rope_theta": "rope_theta"},
    "serving": {"program_arch": "llama3_2-3b", "serve_args": []},
}


def _checkout(tmp_path, conf):
    """A checkout whose only cell runs ``conf`` under granite's traffic."""
    root = tmp_path / "checkout"
    bench_dir = root / "bench"
    shutil.copytree(ROOT / "bench", bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    (bench_dir / "configs" / f"{conf['name']}.json").write_text(
        json.dumps(conf))
    (bench_dir / "checks" / "fixture-cell.json").write_text(
        json.dumps({"widest_logit_gap": {"limit": 1.0}}))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": conf["name"], "source": "x",
                        "file": f"bench/configs/{conf['name']}.json",
                        "reduced": [], "why": "x"}]
    spec["workloads"] = [{"name": "fixture-cell", "config": conf["name"],
                          "traffic": "shift_granite", "chips": 1,
                          "why": "x"}]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def _load(root):
    return harness.load_cell("fixture-cell",
                             bench_file=root / "BENCHMARK.json",
                             bench_dir=root / "bench")


@pytest.fixture
def dense_module(tmp_path, monkeypatch):
    """``dense_gqa_fixture`` written to a directory on ``bench.arch``'s
    search path; it leaves the path and ``sys.modules`` as they were."""
    arch_dir = tmp_path / "arch"
    arch_dir.mkdir()
    (arch_dir / "dense_gqa_fixture.py").write_text(DENSE_MODULE)
    monkeypatch.setattr(bench.arch, "__path__",
                        list(bench.arch.__path__) + [str(arch_dir)])
    yield "bench.arch.dense_gqa_fixture"
    sys.modules.pop("bench.arch.dense_gqa_fixture", None)


def test_architecture_added_as_files_runs_through_the_harness(
        tmp_path, dense_module):
    cell = _load(_checkout(tmp_path, DENSE_CONF))
    assert cell.arch.__name__ == dense_module
    d = cell.arch.dims(cell.conf)
    assert d["d_ff"] == 128 and d["head_dim"] == 16
    cfg = cell.arch.program_arch(cell.conf, d)
    harness.check_layout(cell.arch, cfg, d)        # the program's own tree
    params = cell.arch.make_params(d, 2**33 + 5)
    assert params["blocks"]["0"]["mlp"]["w_gate"].shape == (2, 64, 128)
    # The harness's reference pass, over a served sample it is handed.
    rng = np.random.default_rng(0)
    sample = [{"prompt": rng.integers(0, 96, n).astype(np.int32),
               "served": rng.integers(0, 96, m).astype(np.int32)}
              for n, m in ((9, 4), (20, 3), (5, 6))]
    gaps = harness.reference_gaps(cell.arch, d, 2**33 + 5, sample, 32)
    assert gaps["finite"] and gaps["positions"] == 13
    assert gaps["widest_logit_gap"] >= gaps["mean_logit_gap"] >= 0.0
    # The program's tree differs from what a wrong module states.
    with pytest.raises(ValueError, match="weight layout differs"):
        harness.check_layout(cell.arch, cfg, dict(d, d_ff=64))


def test_configuration_without_arch_module_is_refused(tmp_path):
    conf = json.loads((ROOT / "bench" / "configs" /
                       "granite-moe-1b-a400m.json").read_text())
    conf.pop("arch_module")
    conf["name"] = "granite-no-module"
    with pytest.raises(KeyError, match="names no 'arch_module'"):
        _load(_checkout(tmp_path, conf))


def test_configuration_naming_a_missing_module_is_refused(tmp_path):
    conf = dict(DENSE_CONF, arch_module="mla_moe")
    with pytest.raises(KeyError, match=r"arch_module 'mla_moe'.*moe_gqa"):
        _load(_checkout(tmp_path, conf))


def test_module_without_part_of_the_interface_is_refused(tmp_path,
                                                         monkeypatch):
    (tmp_path / "partial_fixture.py").write_text(
        DENSE_MODULE + "\ndel prompt_flops, moe_ffn_work\n")
    monkeypatch.setattr(bench.arch, "__path__",
                        list(bench.arch.__path__) + [str(tmp_path)])
    try:
        with pytest.raises(AttributeError,
                           match="lacks.*prompt_flops.*moe_ffn_work"):
            bench.arch.load({"name": "x", "arch_module": "partial_fixture"})
    finally:
        sys.modules.pop("bench.arch.partial_fixture", None)


def test_every_configuration_names_a_module():
    for path in sorted((ROOT / "bench" / "configs").glob("*.json")):
        conf = common.load_config(path.stem)
        arch = bench.arch.load(conf)
        assert arch.dims(conf)["vocab_size"] == conf["vocab_size"]
