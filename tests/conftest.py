import os
import sys
import types

# Tests run single-device: the multi-device dry-run tests spawn subprocesses
# with their own XLA_FLAGS (jax locks device count at first init).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

jax.config.update("jax_enable_x64", False)


# ---------------------------------------------------------------------------
# Minimal deterministic stand-in for `hypothesis` when it is not installed
# (this container bakes in jax but not hypothesis, and installing packages is
# not an option). The property tests only use a tiny strategy surface —
# integers / floats / sampled_from / lists — so a seeded-RNG driver that runs
# each property `max_examples` times preserves the coverage. With the real
# hypothesis available (e.g. in CI) this block is inert.
# ---------------------------------------------------------------------------

def _install_hypothesis_shim():
    try:
        import hypothesis  # noqa: F401
        return
    except ImportError:
        pass

    import numpy as np

    class _Strategy:
        def __init__(self, draw):
            self.draw = draw

    def integers(min_value=0, max_value=(1 << 32) - 1):
        return _Strategy(
            lambda rng: int(rng.integers(min_value, max_value + 1)))

    def floats(min_value=0.0, max_value=1.0, **_kw):
        return _Strategy(
            lambda rng: float(rng.uniform(min_value, max_value)))

    def sampled_from(seq):
        items = list(seq)
        return _Strategy(lambda rng: items[int(rng.integers(len(items)))])

    def lists(elements, min_size=0, max_size=10):
        def draw(rng):
            n = int(rng.integers(min_size, max_size + 1))
            return [elements.draw(rng) for _ in range(n)]
        return _Strategy(draw)

    def booleans():
        return _Strategy(lambda rng: bool(rng.integers(2)))

    def given(**strategies):
        def deco(fn):
            def wrapper(*args, **kwargs):
                n = getattr(wrapper, "_max_examples", 20)
                rng = np.random.default_rng(0xD15EA5E)
                for _ in range(n):
                    drawn = {k: s.draw(rng) for k, s in strategies.items()}
                    fn(*args, **kwargs, **drawn)
            # NOT functools.wraps: pytest must see the wrapper's empty
            # signature, not the property's drawn parameters (which would
            # otherwise be collected as missing fixtures).
            wrapper.__name__ = fn.__name__
            wrapper.__doc__ = fn.__doc__
            wrapper.__module__ = fn.__module__
            wrapper._hypothesis_shim = True
            return wrapper
        return deco

    def settings(max_examples=20, **_kw):
        def deco(fn):
            fn._max_examples = max_examples
            return fn
        return deco

    st_mod = types.ModuleType("hypothesis.strategies")
    st_mod.integers = integers
    st_mod.floats = floats
    st_mod.sampled_from = sampled_from
    st_mod.lists = lists
    st_mod.booleans = booleans

    hyp = types.ModuleType("hypothesis")
    hyp.given = given
    hyp.settings = settings
    hyp.strategies = st_mod
    hyp.__is_shim__ = True
    sys.modules["hypothesis"] = hyp
    sys.modules["hypothesis.strategies"] = st_mod


_install_hypothesis_shim()


# ---------------------------------------------------------------------------
# Shared serving fixtures: one reduced MoE + a canonical engine builder, so
# every serving suite exercises the SAME backend settings.
# ---------------------------------------------------------------------------

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def serving_setup():
    """(cfg, params) for the reduced granite MoE used by the serving tests."""
    from repro.configs import get_config
    from repro.models import init_params
    cfg = get_config("granite-moe-1b-a400m", reduced=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture()
def engine_factory(serving_setup):
    """Build an InferenceEngine over a fresh clone of the shared params with
    the canonical test backend settings (int4 lo, n_hi=2, T_u=0)."""
    from repro.core import ControllerConfig
    from repro.serving import EngineConfig, InferenceEngine, make_backend

    cfg, params = serving_setup

    def build(name, max_slots=4, max_len=64, obs=None, **kw):
        if name in ("static", "dynaexq"):
            kw.setdefault("lo_bits", 4)
        if name == "dynaexq":
            kw.setdefault("n_hi_per_layer", 2)
            kw.setdefault("controller",
                          ControllerConfig(update_interval_s=0.0))
        clone = jax.tree_util.tree_map(lambda x: x, params)
        return InferenceEngine(cfg, clone, make_backend(name, **kw),
                               EngineConfig(max_slots=max_slots,
                                            max_len=max_len), obs=obs)

    return build


@pytest.fixture(scope="session")
def paged_decode_program_check():
    """``check(cfg, sharding=None, ...)``: compile the paged engine's
    decode step (``_decode_paged_jit``) from shapes, for ``sharding``'s
    device when given, and assert that the stacked KV pools stay out of the
    layer scan's ``xs``/``ys``: no instruction of a loop (its body and all
    it calls) makes an array of one layer's pool shape, no whole pool is
    copied anywhere, and each returned pool aliases its donated input."""
    import re

    from repro.models import init_paged_caches, init_params
    from repro.models.layers import PagedKVCache
    from repro.serving.engine import _decode_paged_jit

    def check(cfg, sharding=None, batch=2, max_len=32, block_tokens=4,
              n_blocks=41):
        import jax.numpy as jnp

        def spec(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
        params = jax.eval_shape(lambda k: init_params(k, cfg),
                                jax.random.PRNGKey(0))
        caches = jax.eval_shape(lambda: init_paged_caches(
            cfg, batch, max_len, block_tokens, n_blocks))
        i32 = jax.ShapeDtypeStruct((batch,), jnp.int32)
        args = jax.tree_util.tree_map(spec, (
            params, i32, i32, caches, None,
            jax.ShapeDtypeStruct((batch,), jnp.bool_),
            jax.ShapeDtypeStruct((batch, max_len // block_tokens),
                                 jnp.int32), i32, i32))
        lowered = _decode_paged_jit.lower(*args, cfg=cfg,
                                          capacity_factor=2.0)
        hlo = lowered.compile().as_text()

        comps = {}
        for block in hlo.split("\n\n"):
            head = block.strip().split("\n", 1)[0].split()
            if head:
                name = head[1] if head[0] == "ENTRY" else head[0]
                comps[name.lstrip("%")] = block

        def reach(name, seen):
            if name in comps and name not in seen:
                seen.add(name)
                for callee in re.findall(
                        r"(?:calls|to_apply|body|condition|"
                        r"branch_computations)=\{?%?([\w.\-]+)",
                        comps[name]):
                    reach(callee, seen)
            return seen

        loops = set()
        for body in re.findall(r" while\(.*?body=%?([\w.\-]+)", hlo):
            loops |= reach(body, set())
        assert loops
        leaves = [a for c in caches.blocks.values()
                  if isinstance(c, PagedKVCache) for a in c]
        assert leaves
        layer = {",".join(map(str, s)) for a in leaves
                 for s in (a.shape[1:], (1,) + a.shape[1:])}
        made = re.compile(r"= \w+\[(%s)\]" % "|".join(map(re.escape, layer)))
        found = [ln.strip()[:160] for c in loops
                 for ln in comps[c].splitlines() if made.search(ln)]
        assert not found, found
        whole = ",".join(map(str, leaves[0].shape))
        assert not re.findall(r".*= \w+\[%s\]\S* copy\(.*" % whole, hlo)

        # flattened argument and output positions of the pool leaves
        flat = jax.tree_util.tree_leaves(caches)
        first = len(jax.tree_util.tree_leaves(args[:3]))
        ids = {first + i for i, a in enumerate(flat)
               if any(a is leaf for leaf in leaves)}
        out = jax.tree_util.tree_leaves(lowered.out_info)
        outs = {i for i, a in enumerate(out) if a.shape == leaves[0].shape}
        alias = hlo.split("\n", 1)[0].split("input_output_alias=", 1)[1]
        alias = alias.split("entry_computation_layout", 1)[0]
        pairs = {(int(o), int(p)) for o, p in
                 re.findall(r"\{(\d+)\}: \((\d+),", alias)}
        assert len(ids) == len(outs) == len(leaves)
        assert {(o, p) for o, p in pairs if p in ids} == \
            set(zip(sorted(outs), sorted(ids))), (alias, ids, outs)

    return check
