"""Distribution correctness: the shard_map expert-parallel MoE must compute
EXACTLY what the single-device path computes. Runs in a subprocess with 8
host devices (jax locks the device count at first init, and the rest of the
suite runs single-device)."""
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np, json
from repro.configs import get_config
from repro.launch.dist import DistContext, dist_ctx
from repro.launch.mesh import make_debug_mesh
from repro.launch.sharding import ShardingPlanner
from repro.models import decode_step, init_caches, init_params, prefill

mesh = make_debug_mesh(2, 4)
cfg = get_config("granite-moe-1b-a400m", reduced=True)
# reduced: E=4 experts over model=4 → 1 expert/rank
key = jax.random.PRNGKey(0)

# f32 params AND caches: the once-xfailed divergence here was bf16
# reduction-order noise (GSPMD contraction-sharded dense projections plus
# the bf16 MoE combine accumulate in different orders across shards)
# flipping near-tie router top-k picks. In f32 the two paths agree to
# float rounding — the sharded formulation itself is exact.
def _f32(t):
    return jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32)
        if hasattr(x, "dtype") and x.dtype == jnp.bfloat16 else x, t)

params = _f32(init_params(key, cfg))
B, S = 4, 16
toks = jax.random.randint(key, (B, S + 2), 0, cfg.vocab_size)

# single-device reference
caches = _f32(init_caches(cfg, B, 64))
lg_ref, caches_ref, counts_ref = prefill(
    params, cfg, {"tokens": toks[:, :S]}, caches, capacity_factor=8.0)
tok = toks[:, S]
lg2_ref, _, _ = decode_step(params, cfg, tok, jnp.int32(S), caches_ref,
                            capacity_factor=8.0)

# sharded: same computation under mesh + dist ctx + planner shardings
dctx = DistContext(mesh=mesh, dp_axes=("data",), tokens_dp_sharded=True)
planner = ShardingPlanner(cfg, mesh)
params_sh = planner.tree_shardings(params, "param")
caches0 = _f32(init_caches(cfg, B, 64))
caches_sh = planner.tree_shardings(caches0, "cache")

def pf(p, b, c):
    with dist_ctx(dctx):
        return prefill(p, cfg, b, c, capacity_factor=8.0)

def dc(p, t, i, c):
    with dist_ctx(dctx):
        return decode_step(p, cfg, t, i, c, capacity_factor=8.0)

with mesh:
    params_d = jax.device_put(params, params_sh)
    caches_d = jax.device_put(caches0, caches_sh)
    lg, caches1, counts = jax.jit(pf)(params_d, {"tokens": toks[:, :S]}, caches_d)
    lg2, _, counts2 = jax.jit(dc)(params_d, tok, jnp.int32(S), caches1)

out = {
  "prefill_max_err": float(jnp.max(jnp.abs(lg - lg_ref))),
  "decode_max_err": float(jnp.max(jnp.abs(lg2 - lg2_ref))),
  "counts_equal": bool((np.asarray(counts["0"]) == np.asarray(counts_ref["0"])).all()),
  "prefill_scale": float(jnp.max(jnp.abs(lg_ref))),
}
print("RESULT " + json.dumps(out))
"""


@pytest.mark.slow
def test_shard_map_moe_matches_single_device():
    """The GSPMD-sharded MoE forward matches single-device to float
    rounding. Run in f32 so reduction-order noise cannot flip near-tie
    router picks (the root cause of the historical xfail here)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=560,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, r.stderr[-3000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")][-1]
    out = json.loads(line[len("RESULT "):])
    scale = max(out["prefill_scale"], 1.0)
    assert out["prefill_max_err"] <= 1e-4 * scale, out
    assert out["decode_max_err"] <= 1e-4 * scale, out
    assert out["counts_equal"], out


DRYRUN_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, json
import repro.launch.shapes as shapes
import repro.launch.dryrun as dr
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
# shrink the shape set for an 8-device smoke of the REAL dry-run machinery
shapes.SHAPES["tiny_decode"] = dict(kind="decode", seq=128, batch=8)
shapes.SHAPES["tiny_train"] = dict(kind="train", seq=64, batch=8)
import repro.configs as C
import dataclasses
orig = C.get_config
def patched(name, reduced=False):
    cfg = orig(name, reduced=True)
    return cfg
C.get_config = patched
shapes.get_config = patched
for shape in ("tiny_decode", "tiny_train"):
    spec = shapes.build_dryrun("granite-moe-1b-a400m", shape, mesh)
    jitted = jax.jit(spec.step_fn, in_shardings=spec.in_shardings,
                     donate_argnums=spec.donate_argnums)
    with mesh:
        compiled = jitted.lower(*spec.args).compile()
    print("COMPILED", shape, dr.cost_analysis(compiled).get("flops", 0) > 0)
"""


@pytest.mark.slow
def test_dryrun_machinery_small_multipod_mesh():
    """The real build_dryrun/planner path lowers+compiles on a (2,2,2)
    multi-pod debug mesh — including the MoE serving bank and train step."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", DRYRUN_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=560,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-3000:])
    assert r.stdout.count("COMPILED") == 2
    assert "False" not in r.stdout
