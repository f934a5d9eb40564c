"""The serving path's Pallas kernels compile for a TPU v5e at the published
widths of granite-moe-1b-a400m.

Interpret mode (every other kernel test) cannot see what the TPU compiler
refuses: misaligned blocks, too much VMEM. These tests compile the kernels
through the same ``kernels.ops`` dispatchers the MoE layer calls, for one
chip of a described ``v5e:2x2`` topology, with no chip attached. The
topology is described inside a fixture, so importing this file touches no
TPU library and only the worker that runs these tests loads it.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops as kops
from repro.quant.qtensor import QuantizedTensor

# granite-moe-1b-a400m: experts, d_model, d_ff_expert; the lo tier's group.
E, D, F, GROUP = 32, 1024, 512, 64
BM = 8                 # models.moe.RAGGED_BM
N_HI = 8               # hi slots per layer (n_hi 4 with the 2x pool slack)
# Row tiles of a decode step at batch 4, top-8: ragged_tile_map's static
# budget 32 // 8 + min(32, 32) + 1.
TILES = 37


@pytest.fixture(scope="module")
def one_chip(tmp_path_factory):
    with pytest.MonkeyPatch.context() as mp:
        # The TPU runtime logs to /tmp unless told otherwise.
        mp.setenv("TPU_LOG_DIR", str(tmp_path_factory.mktemp("tpu_logs")))
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:     # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip_compile(one_chip, monkeypatch):
    """Compile ``fn`` for the described chip and return its HLO text. The
    dispatchers run their kernels natively (as on a TPU host), and JAX's
    persistent cache stays off: a described-chip entry cannot be read back
    without a chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    monkeypatch.setattr(kops, "_interpret_default", lambda: False)
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()

    def is_shape(a):      # a (shape, dtype) pair
        return (isinstance(a, tuple) and len(a) == 2
                and isinstance(a[0], tuple)
                and all(isinstance(d, int) for d in a[0]))

    def compile_(fn, *args):
        args = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(*a, sharding=one_chip), args,
            is_leaf=is_shape)
        return jax.jit(fn).lower(*args).compile().as_text()

    yield compile_
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


def _lo(bits, k, n):
    """Abstract int-``bits`` lo tier of one (E, k, n) expert matrix."""
    return QuantizedTensor(packed=((E, k * bits // 8, n), jnp.uint8),
                           scales=((E, k // GROUP, n), jnp.bfloat16),
                           bits=bits, group_size=GROUP, shape=(E, k, n))


def _ffn_shapes(bits, n_hi):
    lo = {"w_gate": _lo(bits, D, F), "w_up": _lo(bits, D, F),
          "w_down": _lo(bits, F, D)}
    hi = None if n_hi == 0 else {
        "w_gate": ((n_hi, D, F), jnp.bfloat16),
        "w_up": ((n_hi, D, F), jnp.bfloat16),
        "w_down": ((n_hi, F, D), jnp.bfloat16)}
    return (((TILES * BM, D), jnp.bfloat16), ((TILES,), jnp.int32),
            ((TILES,), jnp.int32), lo, hi)


@pytest.mark.parametrize("n_hi", [0, N_HI], ids=["all_lo", "hi_slots"])
@pytest.mark.parametrize("bits", [2, 4, 8])
def test_ragged_quant_ffn_compiles(chip_compile, bits, n_hi):
    """The decode hot path of every quantized backend (dynaexq with hi
    slots; static PTQ and the speculative draft tier all-lo), at every lo
    bit width the launcher offers."""
    xs, eid, slot, lo, hi = _ffn_shapes(bits, n_hi)

    def ffn(xs, eid, slot, lo, hi=None):
        return kops.ragged_quant_ffn_op(xs, eid, slot, lo, hi, bits=bits,
                                        group=GROUP, bm=BM, backend="pallas")
    args = (xs, eid, slot, lo) + (() if hi is None else (hi,))
    assert "tpu_custom_call" in chip_compile(ffn, *args)


def test_ragged_dense_ffn_compiles(chip_compile):
    """The fp16/offload banks' ragged FFN (the fused kernel in all-hi
    mode over the dense bf16 experts)."""
    bank = {"w_gate": ((E, D, F), jnp.bfloat16),
            "w_up": ((E, D, F), jnp.bfloat16),
            "w_down": ((E, F, D), jnp.bfloat16)}

    def ffn(xs, eid, bank):
        return kops.ragged_dense_ffn_op(xs, eid, bank, bm=BM,
                                        backend="pallas")
    text = chip_compile(ffn, ((TILES * BM, D), jnp.bfloat16),
                        ((TILES,), jnp.int32), bank)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("bits", [2, 4, 8])
def test_grouped_quant_matmul_compiles(chip_compile, bits):
    """The padded dispatch's grouped lo-tier GEMM, (E, C, d) × int codes."""
    qt = _lo(bits, D, F)

    def gemm(xg, qt):
        return kops.grouped_lo_matmul(xg, qt.packed, qt.scales, bits, GROUP,
                                      backend="pallas")
    text = chip_compile(gemm, ((E, 16, D), jnp.bfloat16), qt)
    assert "tpu_custom_call" in text


def test_quant_matmul_compiles(chip_compile):
    qt = QuantizedTensor(packed=((D // 2, F), jnp.uint8),
                         scales=((D // GROUP, F), jnp.bfloat16),
                         bits=4, group_size=GROUP, shape=(D, F))
    text = chip_compile(lambda x, qt: kops.quant_matmul_op(x, qt),
                        ((128, D), jnp.bfloat16), qt)
    assert "tpu_custom_call" in text

