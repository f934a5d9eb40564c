"""Distribution context: lets model code (notably the MoE layer) opt into
shard_map expert parallelism when a mesh is active, while staying pure jnp on
a single device.

GSPMD auto-sharding handles every dense layer well, but MoE dispatch is
data-dependent (sort/scatter by expert id): the partitioner cannot shard a
global argsort and replicates the (tokens×top_k, d_model) gather — a ~68 GB
buffer at train_4k scale. The production formulation makes dispatch LOCAL:
each data shard routes its own tokens, each model shard computes only its
E/16 experts, and partial outputs reduce with one psum over 'model' per MoE
layer. ``dist_ctx`` carries the mesh + axis names into the model layers.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Optional, Tuple

_STATE = threading.local()


@dataclasses.dataclass(frozen=True)
class DistContext:
    mesh: object
    dp_axes: Tuple[str, ...]      # ('pod', 'data') or ('data',)
    model_axis: str = "model"
    tokens_dp_sharded: bool = True   # False for batch-1 long-context decode
    # Expert-parallel serving mode: tokens shard over dp_axes AND the model
    # axis (every device owns T/n_token_shards tokens plus E/model_size
    # experts), and the MoE layer runs the ragged all-to-all pipeline —
    # route locally, exchange compacted rows to the owning expert shard,
    # compute with the shard's resident tier, exchange results back. See
    # ``models.moe._moe_local_ep``.
    tokens_ep_sharded: bool = False

    @property
    def model_size(self) -> int:
        return self.mesh.shape[self.model_axis]

    @property
    def dp_size(self) -> int:
        n = 1
        for a in self.dp_axes:
            n *= self.mesh.shape[a]
        return n

    @property
    def n_token_shards(self) -> int:
        """Shards the token dim splits over (EP: data × model; else data)."""
        return self.dp_size * (self.model_size if self.tokens_ep_sharded
                               else 1)


def ep_context(mesh, model_axis: str = "model") -> DistContext:
    """Expert-parallel serving context over ``mesh``: every non-model axis
    data-shards tokens, the model axis owns experts AND a token slice."""
    dp = tuple(a for a in mesh.axis_names if a != model_axis)
    return DistContext(mesh=mesh, dp_axes=dp, model_axis=model_axis,
                       tokens_dp_sharded=True, tokens_ep_sharded=True)


def place_for_serving(dist: DistContext, params: dict, banks) -> None:
    """Commit serving state to ``dist.mesh`` once, in place: parameters
    replicated, and each quantized expert bank split over the model axis
    along its expert dim (lo tier) and hi-slot dim (hi tier, when the slots
    divide) — the layout the MoE ``shard_map`` reads, so no step re-sends
    the weights from device 0. Leaves are swapped inside their containers
    because the residency backend and the EP coordinator keep references
    to those dicts and banks."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.ver import ExpertBankQ

    repl = NamedSharding(dist.mesh, P())
    split = NamedSharding(dist.mesh, P(None, dist.model_axis))

    def put(d: dict) -> None:
        for k, v in d.items():
            if isinstance(v, dict):
                put(v)
            else:
                d[k] = jax.tree_util.tree_map(
                    lambda a: jax.device_put(a, repl), v)

    put(params)
    for bank in (banks or {}).values():
        if not isinstance(bank, ExpertBankQ):
            continue
        for name, qt in bank.lo.items():
            bank.lo[name] = dataclasses.replace(
                qt, packed=jax.device_put(qt.packed, split),
                scales=jax.device_put(qt.scales, split))
        n_hi = bank.slot_owner.shape[-1]
        hi = split if n_hi and n_hi % dist.model_size == 0 else repl
        for name, a in bank.hi.items():
            bank.hi[name] = jax.device_put(a, hi)


def get_dist() -> Optional[DistContext]:
    return getattr(_STATE, "ctx", None)


@contextlib.contextmanager
def dist_ctx(ctx: Optional[DistContext]):
    prev = get_dist()
    _STATE.ctx = ctx
    try:
        yield
    finally:
        _STATE.ctx = prev
