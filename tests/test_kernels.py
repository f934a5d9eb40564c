"""Pallas kernels vs pure-jnp oracles (interpret=True on CPU): shape/dtype/
bit-width sweeps per the deliverable."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.ops import (flash_decode_op, flash_decode_paged_op,
                               grouped_quant_matmul_op, quant_matmul_op)
from repro.quant import quantize


@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("m,k,n", [(128, 256, 128), (256, 512, 256),
                                   (128, 1024, 384)])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_quant_matmul_sweep(bits, m, k, n, dtype):
    key = jax.random.PRNGKey(m + k + n + bits)
    x = jax.random.normal(key, (m, k), dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (k, n), jnp.float32)
    qt = quantize(w, bits=bits, group_size=64)
    out = quant_matmul_op(x, qt, bm=128, bn=128)
    want = ref.quant_matmul_ref(x, qt.packed, qt.scales, bits, 64)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=4e-2, atol=4e-1)


@pytest.mark.parametrize("bits", [2, 4])
@pytest.mark.parametrize("e,c,k,n", [(4, 128, 256, 128), (8, 256, 128, 256)])
def test_grouped_quant_matmul_sweep(bits, e, c, k, n):
    key = jax.random.PRNGKey(e + c + bits)
    xg = jax.random.normal(key, (e, c, k), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(2), (e, k, n), jnp.float32)
    qt = quantize(w, bits=bits, group_size=64)
    out = grouped_quant_matmul_op(xg, qt, bm=128, bn=128)
    want = ref.grouped_quant_matmul_ref(xg, qt.packed, qt.scales, bits, 64)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=4e-2, atol=4e-1)


@pytest.mark.parametrize("h,hkv", [(8, 8), (8, 2), (4, 1)])
@pytest.mark.parametrize("s,bs", [(1024, 256), (2048, 512)])
def test_flash_decode_sweep(h, hkv, s, bs):
    B, hd = 2, 64
    key = jax.random.PRNGKey(h * s)
    q = jax.random.normal(key, (B, h, hd), jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(3), (B, s, hkv, hd), jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(4), (B, s, hkv, hd), jnp.bfloat16)
    # ragged validity incl. one very short row (stresses the -inf guards)
    valid = jnp.arange(s)[None, :] < jnp.array([[17], [s]])
    out = flash_decode_op(q, k, v, valid, bs=bs)
    want = ref.flash_decode_ref(q, k, v, valid)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_flash_decode_matches_model_attention():
    """Kernel semantics == the model's decode attention (full cache)."""
    from repro.models.config import AttnConfig
    from repro.models import layers as L
    cfg = AttnConfig(n_heads=4, n_kv_heads=2, head_dim=64, use_rope=False)
    key = jax.random.PRNGKey(0)
    p = L.init_attention(key, 128, cfg)
    B, S, pos = 2, 256, 100
    cache = L.init_kv_cache(B, S, cfg)
    ks = jax.random.normal(key, (B, 2, S, 64), jnp.bfloat16)
    vs = jax.random.normal(jax.random.PRNGKey(1), (B, 2, S, 64), jnp.bfloat16)
    cache = L.KVCache(ks, vs)
    x = jax.random.normal(jax.random.PRNGKey(2), (B, 1, 128), jnp.bfloat16)
    out_model, cache2 = L.attention_decode(p, cfg, x, jnp.int32(pos), cache)
    # reproduce with the kernel: q from the same projection path; the kernel
    # takes the seq-major (B, S, Hkv, hd) layout
    q = (x @ p["wq"]).reshape(B, 1, 4, 64)[:, 0]
    valid = (jnp.arange(S)[None, :] <= pos) * jnp.ones((B, 1), bool)
    out_kernel = flash_decode_op(q, cache2.k.transpose(0, 2, 1, 3),
                                 cache2.v.transpose(0, 2, 1, 3), valid, bs=64)
    want = out_kernel.reshape(B, 1, 256) @ p["wo"]
    np.testing.assert_allclose(np.asarray(out_model, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2, atol=3e-1)


@pytest.mark.parametrize("h,hkv,bt,nb", [(4, 2, 16, 4), (8, 2, 32, 3),
                                         (4, 4, 64, 2)])
def test_flash_decode_paged_matches_gathered_dense(h, hkv, bt, nb):
    """Block-table flash decode == dense flash decode over the logical view
    the (scrambled, partially unallocated) tables gather — and it consumes
    the ``PagedKVCache`` (N, Hkv, bt, hd) pool layout directly, matching
    ``layers.paged_view``."""
    from repro.models import layers as L
    from repro.models.config import AttnConfig

    B, hd = 2, 64
    S = nb * bt
    N = 1 + B * nb                      # trash block + B full tables
    acfg = AttnConfig(n_heads=h, n_kv_heads=hkv, head_dim=hd)
    pool = L.init_paged_kv_cache(N, bt, acfg)
    kp = jax.random.normal(jax.random.PRNGKey(1), pool.k.shape, jnp.bfloat16)
    vp = jax.random.normal(jax.random.PRNGKey(2), pool.v.shape, jnp.bfloat16)
    pool = L.PagedKVCache(kp, vp)
    q = jax.random.normal(jax.random.PRNGKey(h * bt + nb), (B, h, hd),
                          jnp.bfloat16)
    rng = np.random.default_rng(0)
    table = rng.permutation(np.arange(1, N, dtype=np.int32)).reshape(B, nb)
    table[0, -1] = -1                   # one unallocated logical block
    valid = np.zeros((B, S), bool)
    valid[0, :S - bt - 3] = True        # stays clear of the -1 block
    valid[1, :S - 1] = True
    # dense reference over the same logical view the model gathers
    k_log, v_log = L.paged_view(pool, jnp.asarray(table))  # (B,Hkv,S,hd)
    want = flash_decode_op(q, jnp.moveaxis(k_log, 1, 2),
                           jnp.moveaxis(v_log, 1, 2),
                           jnp.asarray(valid), bs=bt)
    out = flash_decode_paged_op(q, pool.k, pool.v, jnp.asarray(table),
                                jnp.asarray(valid))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_quant_matmul_rejects_bad_tiling():
    x = jnp.ones((100, 256), jnp.bfloat16)
    w = jnp.ones((256, 128), jnp.float32)
    qt = quantize(w, bits=4, group_size=64)
    with pytest.raises(ValueError):
        quant_matmul_op(x, qt, bm=64)  # 100 % 64 != 0
