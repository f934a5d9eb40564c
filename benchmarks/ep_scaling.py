"""Expert-parallel scaling: tokens/s, bytes-moved/token, rebalance count.

The structural claim (ISSUE 7 / DESIGN §EP): under expert parallelism each
shard exchanges a fixed per-destination payload — ``2·(n−1)·S·d`` elements
per MoE layer, out and back, with ``S`` the static all-to-all row budget
(``ep_payload_rows``) — while the replicated baseline psums the full
activation, ``2·(n−1)/n·T·d`` elements per shard. Per token the EP exchange
is **batch-independent** (``S`` is capped by per-destination capacity), so
from 4 shards up it moves strictly fewer bytes per token than the psum; at
2 shards the capacity slice is still wide enough that it legitimately
loses. Both models are reported per shard count alongside the measured
layer throughput on a forced host-device mesh, plus the hotness
rebalancer's migration count under a canned skew.

Each shard count runs in a subprocess (jax pins the device count at first
init). ``BENCH_SMOKE=1`` shrinks the timing loop. Rows land in
``experiments/BENCH_dist.json``.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np

from benchmarks.common import BENCH_SMOKE

D_MODEL = 256
N_TOKENS = 512
N_EXPERTS = 16
BYTES_EL = 2                       # bf16 payload
SHARD_COUNTS = (1, 2, 4, 8)
ITERS = 3 if BENCH_SMOKE else 20
JSON_OUT = os.path.join(os.path.dirname(__file__), "..", "experiments",
                        "BENCH_dist.json")

SCRIPT = r"""
import os, json, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from repro.models.config import MoEConfig
from repro.models import moe as M
from repro.launch.dist import dist_ctx, ep_context
from repro.launch.mesh import make_ep_mesh

n, iters, d, T = %(n)d, %(iters)d, %(d)d, %(T)d
cfg = MoEConfig(num_experts=%(E)d, top_k=2, d_ff_expert=512,
                n_shared_experts=0, capacity_factor=1.25,
                norm_topk_prob=True)
params = M.init_moe(jax.random.PRNGKey(0), d, cfg)
dense = dict(params["experts"])
x = jax.random.normal(jax.random.PRNGKey(1), (T, d), jnp.bfloat16)
cap = M.moe_capacity(T, cfg)

jf = jax.jit(lambda p, b, xx: M.moe_apply(p, b, xx, cfg, cap,
                                          dispatch="ragged", gemm="jnp"))
if n > 1:
    ctx = ep_context(make_ep_mesh(n))
    def call():
        with dist_ctx(ctx):
            return jf(params, dense, x)
else:
    def call():
        return jf(params, dense, x)
y, _ = call()
y.block_until_ready()                          # compile outside the timing
t0 = time.perf_counter()
for _ in range(iters):
    y, _ = call()
y.block_until_ready()
wall = time.perf_counter() - t0
S = M.ep_payload_rows(T, cfg.top_k, cfg.num_experts // n, cap, n) \
    if n > 1 else 0
print("RESULT " + json.dumps({"wall_s": wall, "capacity": cap, "S": S,
                              "tokens_per_s": T * iters / wall}))
"""


def _time_shards(n):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    # Emulated CPU devices: the child must never reach for an accelerator
    # the parent process may already hold.
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    src = SCRIPT % dict(n=n, iters=ITERS, d=D_MODEL, T=N_TOKENS, E=N_EXPERTS)
    r = subprocess.run([sys.executable, "-c", src], env=env,
                       capture_output=True, text=True, timeout=560)
    if r.returncode != 0:
        raise RuntimeError(f"ep_scaling subprocess n={n} failed:\n"
                           f"{r.stderr[-3000:]}")
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def _rebalance_count(n):
    """Exercise the EP coordinator (host-side, no mesh) under a canned
    two-hot-experts-on-one-shard skew; returns migrations admitted."""
    import jax
    import jax.numpy as jnp

    from repro.core import (ControllerConfig, DynaExqController, build_bank,
                            expert_hi_nbytes)
    from repro.core.controller import EPCoordinator, RebalanceConfig

    w = {"w": jax.random.normal(jax.random.PRNGKey(0),
                                (1, N_EXPERTS, 64, 32), jnp.float32)
         .astype(jnp.bfloat16)}
    bank = build_bank(w, n_hi=0, lo_bits=4)
    host = {k: np.asarray(v) for k, v in w.items()}
    hib = expert_hi_nbytes({k: v.shape for k, v in w.items()})
    ctl = DynaExqController(bank, host, n_hi_per_layer=0,
                            hi_bytes_per_expert=hib,
                            cfg=ControllerConfig(update_interval_s=1e9),
                            ep_shards=n)
    coord = EPCoordinator(n, RebalanceConfig(interval_s=1e9))
    coord.register(ctl, {"router": jnp.zeros((1, 16, N_EXPERTS),
                                             jnp.float32)})
    ctl.hotness.counts[:, 0] += 100
    ctl.hotness.counts[:, 1] += 100
    return coord.maybe_rebalance(force=True)


def run(report):
    results = {"smoke": BENCH_SMOKE, "d_model": D_MODEL,
               "n_tokens": N_TOKENS, "n_experts": N_EXPERTS,
               "iters": ITERS, "shards": {}}
    for n in SHARD_COUNTS:
        row = _time_shards(n)
        if n > 1:
            # per-shard interconnect models, bytes per (global) token
            row["bytes_per_token_ep"] = (2 * (n - 1) * row["S"] * D_MODEL *
                                         BYTES_EL / N_TOKENS)
            row["bytes_per_token_replicated"] = (2 * (n - 1) / n * D_MODEL *
                                                 BYTES_EL)
            row["rebalance_migrations"] = _rebalance_count(n)
        else:
            row["bytes_per_token_ep"] = 0.0
            row["bytes_per_token_replicated"] = 0.0
            row["rebalance_migrations"] = 0
        results["shards"][str(n)] = row
        report(f"ep_scaling/tokens_per_s/{n}shard",
               1e6 * row["wall_s"] / ITERS, round(row["tokens_per_s"], 1))
        report(f"ep_scaling/bytes_per_token/{n}shard", 0.0,
               round(row["bytes_per_token_ep"], 1))
    # The claim that makes EP worth serving: at 4+ shards the all-to-all
    # moves strictly fewer bytes/token per shard than the replicated psum.
    for n in (4, 8):
        row = results["shards"][str(n)]
        if not row["bytes_per_token_ep"] < row["bytes_per_token_replicated"]:
            raise AssertionError(
                f"EP exchange at {n} shards moved "
                f"{row['bytes_per_token_ep']:.0f} B/token, not below the "
                f"replicated {row['bytes_per_token_replicated']:.0f} — "
                "payload sizing regressed")
        if row["rebalance_migrations"] < 1:
            raise AssertionError(
                f"rebalancer admitted no migration at {n} shards under a "
                "canned skew — coordinator policy regressed")
    os.makedirs(os.path.dirname(JSON_OUT), exist_ok=True)
    with open(JSON_OUT, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)
    print(f"# wrote {os.path.normpath(JSON_OUT)}")
