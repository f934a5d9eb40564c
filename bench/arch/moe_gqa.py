"""Grouped-query attention with sparse SwiGLU experts in every layer
(granite-moe, qwen3-moe): its sizes, seeded weights, plain reference,
mapping onto the program, and operation and byte counts.

The reference is the architecture written out in ``jax.numpy`` at float32
with ``highest`` matmul precision: RMSNorm, RoPE (split halves), grouped-
query causal attention with optional per-head q/k RMSNorm, a softmax router
with top-k gates renormalized, SwiGLU experts computed densely and weighted
by the gates (no capacity, so no token is dropped), a final RMSNorm and the
(tied or untied) LM head. It runs layer by layer over padded sequences so
that one compiled program serves every request of a run.

Nothing here imports the program except ``program_arch``, which builds the
program's own configuration for these sizes.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Iterable, List, Tuple

import numpy as np

from bench.arch.common import make_weights, rmsnorm, rope

#: Keys every configuration's ``arch`` block maps (to a key of the file, or
#: to a literal value).
ARCH_KEYS = ("n_layers", "d_model", "vocab_size", "n_heads", "n_kv_heads",
             "head_dim", "num_experts", "top_k", "d_ff_expert", "norm_eps",
             "rope_theta", "tie_embeddings", "qk_norm", "norm_topk_prob",
             "attn_scale")


def dims(conf: Dict) -> Dict:
    """The configuration's sizes under the benchmark's own names."""
    out = {}
    for k in ARCH_KEYS:
        v = conf["arch"][k]
        out[k] = conf[v] if isinstance(v, str) else v
    return out


# ---------------------------------------------------------------------------
# Seeded weights
# ---------------------------------------------------------------------------

def param_spec(d: Dict) -> List[Tuple[Tuple[str, ...], tuple, str, float]]:
    """(path, shape, dtype, init scale) of every weight, in a fixed order.
    Scale 0 marks a norm scale (ones). Layers are stacked on axis 0."""
    L, D, V = d["n_layers"], d["d_model"], d["vocab_size"]
    H, Hkv, hd = d["n_heads"], d["n_kv_heads"], d["head_dim"]
    E, F = d["num_experts"], d["d_ff_expert"]
    b = ("blocks", "0")
    spec = [
        (("embed",), (V, D), "bfloat16", 0.02),
        (("final_norm", "scale"), (D,), "bfloat16", 0.0),
        (b + ("norm1", "scale"), (L, D), "bfloat16", 0.0),
        (b + ("norm2", "scale"), (L, D), "bfloat16", 0.0),
        (b + ("attn", "wq"), (L, D, H * hd), "bfloat16", D ** -0.5),
        (b + ("attn", "wk"), (L, D, Hkv * hd), "bfloat16", D ** -0.5),
        (b + ("attn", "wv"), (L, D, Hkv * hd), "bfloat16", D ** -0.5),
        (b + ("attn", "wo"), (L, H * hd, D), "bfloat16", (H * hd) ** -0.5),
        (b + ("moe", "router"), (L, D, E), "float32", D ** -0.5),
        (b + ("moe", "experts", "w_gate"), (L, E, D, F), "bfloat16",
         D ** -0.5),
        (b + ("moe", "experts", "w_up"), (L, E, D, F), "bfloat16", D ** -0.5),
        (b + ("moe", "experts", "w_down"), (L, E, F, D), "bfloat16",
         F ** -0.5),
    ]
    if d["qk_norm"]:
        spec += [(b + ("attn", "q_norm", "scale"), (L, hd), "bfloat16", 0.0),
                 (b + ("attn", "k_norm", "scale"), (L, hd), "bfloat16", 0.0)]
    if not d["tie_embeddings"]:
        spec.append((("lm_head",), (D, V), "bfloat16", D ** -0.5))
    return spec


def make_params(d: Dict, seed: int) -> Dict:
    return make_weights(param_spec(d), seed)


# ---------------------------------------------------------------------------
# The program's configuration
# ---------------------------------------------------------------------------

def program_arch(conf: Dict, d: Dict):
    """The program's ArchConfig for this configuration: its registry entry
    with every size the file states."""
    from repro.configs import get_config
    base = get_config(conf["serving"]["program_arch"])
    attn = dataclasses.replace(base.attn, n_heads=d["n_heads"],
                               n_kv_heads=d["n_kv_heads"],
                               head_dim=d["head_dim"],
                               rope_theta=float(d["rope_theta"]),
                               qk_norm=bool(d["qk_norm"]))
    moe = dataclasses.replace(base.moe, num_experts=d["num_experts"],
                              top_k=d["top_k"], d_ff_expert=d["d_ff_expert"],
                              norm_topk_prob=bool(d["norm_topk_prob"]))
    arch = dataclasses.replace(base, n_layers=d["n_layers"],
                               d_model=d["d_model"],
                               vocab_size=d["vocab_size"], attn=attn, moe=moe,
                               norm_eps=float(d["norm_eps"]),
                               tie_embeddings=bool(d["tie_embeddings"]))
    if abs(d["attn_scale"] - d["head_dim"] ** -0.5) > 1e-9:
        raise ValueError("the program scales attention by 1/sqrt(head_dim); "
                         f"the configuration states {d['attn_scale']}")
    return arch


# ---------------------------------------------------------------------------
# Reference forward
# ---------------------------------------------------------------------------

def _layer(d: Dict, x, w, token_block: int):
    """One decoder layer at float32. x: (B, S, D)."""
    import jax
    import jax.numpy as jnp
    B, S, D = x.shape
    H, Hkv, hd = d["n_heads"], d["n_kv_heads"], d["head_dim"]
    E, k = d["num_experts"], d["top_k"]
    eps = d["norm_eps"]
    f32 = lambda a: a.astype(jnp.float32)

    h = rmsnorm(x, f32(w["norm1"]), eps)
    q = (h @ f32(w["wq"])).reshape(B, S, H, hd)
    kk = (h @ f32(w["wk"])).reshape(B, S, Hkv, hd)
    v = (h @ f32(w["wv"])).reshape(B, S, Hkv, hd)
    if d["qk_norm"]:
        q = rmsnorm(q, f32(w["q_norm"]), 1e-6)
        kk = rmsnorm(kk, f32(w["k_norm"]), 1e-6)
    q, kk = rope(q, d["rope_theta"]), rope(kk, d["rope_theta"])
    rep = H // Hkv
    qg = q.reshape(B, S, Hkv, rep, hd)
    mask = jnp.tril(jnp.ones((S, S), bool))

    def attend(qc, c0):
        # qc: (B, C, Hkv, rep, hd) queries at positions c0.. c0+C-1
        C = qc.shape[1]
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qc, kk) * d["attn_scale"]
        m = jax.lax.dynamic_slice_in_dim(mask, c0, C, 0)
        s = jnp.where(m[None, None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, -1)
        return jnp.einsum("bgrqk,bkgd->bqgrd", p, v)

    C = min(S, 512)
    chunks = [attend(qg[:, c:c + C], c) for c in range(0, S, C)]
    o = jnp.concatenate(chunks, 1).reshape(B, S, H * hd)
    x = x + o @ f32(w["wo"])

    h = rmsnorm(x, f32(w["norm2"]), eps).reshape(B * S, D)
    probs = jax.nn.softmax(h @ w["router"].astype(jnp.float32), -1)
    top, idx = jax.lax.top_k(probs, k)
    if d["norm_topk_prob"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    # (T, E) gate of every expert for every token; zero off the top-k.
    gates = jnp.zeros((B * S, E), jnp.float32).at[
        jnp.arange(B * S)[:, None], idx].set(top)

    def moe_block(args):
        hb, gb = args                              # (T, D), (T, E)

        def one(acc, e_w):
            e, wg, wu, wd = e_w
            y = (jax.nn.silu(hb @ f32(wg)) * (hb @ f32(wu))) @ f32(wd)
            return acc + gb[:, e, None] * y, None
        acc, _ = jax.lax.scan(
            one, jnp.zeros_like(hb),
            (jnp.arange(E), w["w_gate"], w["w_up"], w["w_down"]))
        return acc

    T = B * S
    blk = min(token_block, T)
    pad = -T % blk
    h = jnp.pad(h, ((0, pad), (0, 0)))
    gates = jnp.pad(gates, ((0, pad), (0, 0)))      # zero gates: no output
    y = jax.lax.map(moe_block, (h.reshape(-1, blk, D),
                                gates.reshape(-1, blk, E)))
    return x + y.reshape(-1, D)[:T].reshape(B, S, D)


def _layer_weights(params: Dict, l: int):
    blk = params["blocks"]["0"]
    w = {"norm1": blk["norm1"]["scale"][l], "norm2": blk["norm2"]["scale"][l],
         "router": blk["moe"]["router"][l]}
    for n in ("wq", "wk", "wv", "wo"):
        w[n] = blk["attn"][n][l]
    for n in ("q_norm", "k_norm"):
        if n in blk["attn"]:
            w[n] = blk["attn"][n]["scale"][l]
    for n in ("w_gate", "w_up", "w_down"):
        w[n] = blk["moe"]["experts"][n][l]
    return w


class Reference:
    """Reference logits at chosen positions of padded token rows."""

    def __init__(self, d: Dict, params: Dict, token_block: int = 1024):
        import jax
        self.d, self.params = d, params
        self._layer = jax.jit(functools.partial(
            _layer, d, token_block=token_block))
        self._head = jax.jit(functools.partial(_head, d))

    def logits_at(self, tokens: np.ndarray, positions: np.ndarray):
        """tokens (B, S) int32, positions (B, P) int32 → (B, P, V)
        float32 logits predicting the token after each position."""
        import jax
        import jax.numpy as jnp
        with jax.default_matmul_precision("highest"):
            x = self.params["embed"][jnp.asarray(tokens)].astype(jnp.float32)
            for l in range(self.d["n_layers"]):
                w = _layer_weights(self.params, l)
                x = self._layer(x, w)
            return self._head(self.params, x, jnp.asarray(positions))


def _head(d: Dict, params: Dict, x, positions):
    import jax.numpy as jnp
    xs = jnp.take_along_axis(x, positions[..., None], axis=1)   # (B, P, D)
    xs = rmsnorm(xs, params["final_norm"]["scale"].astype(jnp.float32),
                 d["norm_eps"])
    head = params["embed"].T if d["tie_embeddings"] else params["lm_head"]
    return xs @ head.astype(jnp.float32)


# ---------------------------------------------------------------------------
# The ragged MoE FFN (gate/up kernel, then down kernel)
# ---------------------------------------------------------------------------

def expert_matrices(d: Dict):
    """(K, N) of the three matrices of one expert: gate, up, down."""
    D, F = d["d_model"], d["d_ff_expert"]
    return ((D, F), (D, F), (F, D))


def expert_hi_bytes(d: Dict) -> int:
    """Bytes of one expert of one layer in bf16."""
    return sum(2 * k * n for k, n in expert_matrices(d))


def expert_lo_bytes(d: Dict, bits: int, group: int) -> int:
    """Bytes of one expert of one layer at ``bits``-bit codes with one bf16
    scale per ``group`` rows of K."""
    return sum(k * n * bits // 8 + (k // group) * n * 2
               for k, n in expert_matrices(d))


def moe_ffn_work(d: Dict, forwards: Iterable[Dict], lo_bits: int,
                 group: int) -> Dict[str, float]:
    """Least FLOPs and bytes of the ragged expert FFN over some forwards.

    Each forward is a ``moe_forward`` record: ``routed`` token-expert
    assignments and ``active_hi``/``active_lo`` (layer, expert) cells that
    received tokens, summed over layers. Every active expert's weights are
    read once in the tier it is served from; every assignment's
    activations are read and written once at bf16 (x in, h out and back
    in, y out); every assignment costs the three matmuls of its expert."""
    D, F = d["d_model"], d["d_ff_expert"]
    hi_b, lo_b = expert_hi_bytes(d), expert_lo_bytes(d, lo_bits, group)
    act_b = 2 * (2 * D + 2 * F)
    flops = nbytes = 0.0
    for f in forwards:
        flops += f["routed"] * 2.0 * (2 * D * F + F * D)
        nbytes += (f["active_hi"] * hi_b + f["active_lo"] * lo_b
                   + f["routed"] * act_b)
    return {"flops": flops, "bytes": nbytes}


# ---------------------------------------------------------------------------
# Whole-model FLOPs per token (for model_mfu)
# ---------------------------------------------------------------------------

def flops_per_token(d: Dict, ctx: float) -> float:
    """Forward FLOPs of one token that attends to ``ctx`` positions
    (itself included): attention projections and scores, router, the
    top-k experts, and the LM head. Norms and softmaxes are left out."""
    D, H, Hkv, hd = d["d_model"], d["n_heads"], d["n_kv_heads"], d["head_dim"]
    E, k, F = d["num_experts"], d["top_k"], d["d_ff_expert"]
    proj = 2 * (D * H * hd + 2 * D * Hkv * hd + H * hd * D)
    scores = 2 * 2 * ctx * H * hd
    router = 2 * D * E
    experts = k * 2 * 3 * D * F
    return d["n_layers"] * (proj + scores + router + experts) \
        + 2 * D * d["vocab_size"]


def prompt_flops(d: Dict, start: int, end: int) -> float:
    """FLOPs of prefilling positions [start, end) of one sequence."""
    n = end - start
    # Position p attends to p + 1 positions; sum over the span.
    ctx_sum = (start + 1 + end) * n / 2.0
    return n * flops_per_token(d, 0) + (flops_per_token(d, 1)
                                        - flops_per_token(d, 0)) * ctx_sum
