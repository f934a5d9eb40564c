"""The benchmark's side of the model: configuration files, seeded weights
and the plain reference forward pass.

Nothing here imports the program. The weights are made from the seed by
this module, in the layout the program's parameter tree uses (the harness
checks the layout against the program before it hands them over), and the
reference recomputes them from the same seed after the program has been
freed, so it takes nothing the program made.

The reference is the architecture written out in ``jax.numpy`` at float32
with ``highest`` matmul precision: RMSNorm, RoPE (split halves), grouped-
query causal attention with optional per-head q/k RMSNorm, a softmax router
with top-k gates renormalized, SwiGLU experts computed densely and weighted
by the gates (no capacity, so no token is dropped), a final RMSNorm and the
(tied or untied) LM head. It runs layer by layer over padded sequences so
that one compiled program serves every request of a run.
"""
from __future__ import annotations

import functools
import json
from pathlib import Path
from typing import Dict, List, Tuple

import numpy as np

CONFIG_DIR = Path(__file__).resolve().parent / "configs"

#: Keys every configuration's ``arch`` block maps (to a key of the file, or
#: to a literal value).
ARCH_KEYS = ("n_layers", "d_model", "vocab_size", "n_heads", "n_kv_heads",
             "head_dim", "num_experts", "top_k", "d_ff_expert", "norm_eps",
             "rope_theta", "tie_embeddings", "qk_norm", "norm_topk_prob",
             "attn_scale")


def load_config(name: str, directory: Path = CONFIG_DIR) -> Dict:
    path = directory / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no configuration {name!r} at {path}")
    return json.loads(path.read_text())


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


def seed_key(seed: int):
    """A PRNG key from any non-negative integer seed (wider than 32 bits
    included). The ``rbg`` generator draws on the chip's own random-bit
    unit, which makes a billion weights in a fraction of the time threefry
    takes; it is deterministic for a key on a given backend, and the
    reference remakes the weights on the same device as the program."""
    import jax
    s = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.fold_in(
        jax.random.key(int(s[0]) & 0x7FFFFFFF, impl="rbg"),
        int(s[1]) & 0x7FFFFFFF)


def _nest(flat: Dict[Tuple[str, ...], object]) -> Dict:
    out: Dict = {}
    for path, v in flat.items():
        node = out
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = v
    return out


@functools.lru_cache(maxsize=None)
def _maker(spec_key):
    import jax
    import jax.numpy as jnp
    spec = json.loads(spec_key)

    @jax.jit
    def make(key):
        flat = {}
        for i, (path, shape, dtype, scale) in enumerate(spec):
            dt = jnp.dtype(dtype)
            if scale == 0.0:
                flat[tuple(path)] = jnp.ones(shape, dt)
            else:
                k = jax.random.fold_in(key, i)
                flat[tuple(path)] = (jax.random.normal(k, shape, jnp.float32)
                                     * scale).astype(dt)
        return _nest(flat)
    return make


def make_params(d: Dict, seed: int) -> Dict:
    """Every weight, made on the default device in one jitted call from
    the seed, in the dtype it is served in."""
    spec = [(list(p), list(s), t, c) for p, s, t, c in param_spec(d)]
    return _maker(json.dumps(spec))(seed_key(seed))


# ---------------------------------------------------------------------------
# Reference forward
# ---------------------------------------------------------------------------

def _rmsnorm(x, scale, eps):
    import jax.numpy as jnp
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) \
        * scale


def _rope(x, theta):
    """x: (B, S, H, hd), positions 0..S-1, split-halves rotation."""
    import jax.numpy as jnp
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(d: Dict, x, w, token_block: int):
    """One decoder layer at float32. x: (B, S, D)."""
    import jax
    import jax.numpy as jnp
    B, S, D = x.shape
    H, Hkv, hd = d["n_heads"], d["n_kv_heads"], d["head_dim"]
    E, k = d["num_experts"], d["top_k"]
    eps = d["norm_eps"]
    f32 = lambda a: a.astype(jnp.float32)

    h = _rmsnorm(x, f32(w["norm1"]), eps)
    q = (h @ f32(w["wq"])).reshape(B, S, H, hd)
    kk = (h @ f32(w["wk"])).reshape(B, S, Hkv, hd)
    v = (h @ f32(w["wv"])).reshape(B, S, Hkv, hd)
    if d["qk_norm"]:
        q = _rmsnorm(q, f32(w["q_norm"]), 1e-6)
        kk = _rmsnorm(kk, f32(w["k_norm"]), 1e-6)
    q, kk = _rope(q, d["rope_theta"]), _rope(kk, d["rope_theta"])
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

    h = _rmsnorm(x, f32(w["norm2"]), eps).reshape(B * S, D)
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
    xs = _rmsnorm(xs, params["final_norm"]["scale"].astype(jnp.float32),
                  d["norm_eps"])
    head = params["embed"].T if d["tie_embeddings"] else params["lm_head"]
    return xs @ head.astype(jnp.float32)
