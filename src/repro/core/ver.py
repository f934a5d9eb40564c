"""Versioned Expert Residency (VER) — paper §3.2, adapted to JAX/TPU.

The paper's pointer-indirection handle table becomes two small device arrays:

* ``slot_map[L, E]``  : expert → hi-pool slot (−1 ⇒ lo fallback). This is the
  "stable handle": the MoE kernel always gathers through it, so *publishing*
  a new version is a single int32 store, and the forward pass always sees a
  fully-materialized version (the hi slot is only referenced after its weight
  copy completed — publish-then-switch).
* ``slot_owner[L, n_hi]`` : hi slot → expert id (−1 ⇒ free). Used by the
  weight-scatter formulation (jnp path) and by eviction.

Weight versions live in two preallocated pools (paper §3.3):

* lo pool  — packed int4/int2 ``QuantizedTensor``s for ALL experts, always
  resident (the guaranteed fallback).
* hi pool  — ``n_hi`` bf16 (or higher-bit) expert slots per layer. Fixed
  granularity = one expert ⇒ no fragmentation by construction.

Residency states (host-side mirror, per expert): RESIDENT_LO, PROMOTING,
RESIDENT_HI, DEMOTING. The device arrays only ever reflect *published*
states; PROMOTING/DEMOTING exist host-side while a transition is in flight.
"""
from __future__ import annotations

import dataclasses
import enum
import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.quant.qtensor import QuantizedTensor, quantize, quantized_nbytes


class Residency(enum.Enum):
    RESIDENT_LO = 0
    PROMOTING = 1
    RESIDENT_HI = 2
    DEMOTING = 3
    EVICTING = 4


@jax.tree_util.register_pytree_with_keys_class
@dataclasses.dataclass
class ExpertBankQ:
    """Mixed-precision expert bank for one MoE stack (all layers, stacked).

    ``lo``: dict name → QuantizedTensor with leading dims (L, E, …).
    ``hi``: dict name → bf16 array with leading dims (L, n_hi, …).
    ``slot_owner``: (L, n_hi) int32, −1 = free slot.
    ``slot_map``: (L, E) int32, −1 = serve from lo pool.
    """

    lo: Dict[str, QuantizedTensor]
    hi: Dict[str, jax.Array]
    slot_owner: jax.Array
    slot_map: jax.Array

    def tree_flatten_with_keys(self):
        lo_names = tuple(sorted(self.lo))
        hi_names = tuple(sorted(self.hi))
        K = jax.tree_util.GetAttrKey
        children = tuple((K(f"lo.{n}"), self.lo[n]) for n in lo_names) + \
            tuple((K(f"hi.{n}"), self.hi[n]) for n in hi_names) + \
            ((K("slot_owner"), self.slot_owner), (K("slot_map"), self.slot_map))
        return children, (lo_names, hi_names)

    def tree_flatten(self):
        children, aux = self.tree_flatten_with_keys()
        return tuple(c for _, c in children), aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        lo_names, hi_names = aux
        nl, nh = len(lo_names), len(hi_names)
        lo = dict(zip(lo_names, children[:nl]))
        hi = dict(zip(hi_names, children[nl:nl + nh]))
        slot_owner, slot_map = children[nl + nh:]
        return cls(lo=lo, hi=hi, slot_owner=slot_owner, slot_map=slot_map)

    @property
    def n_hi(self) -> int:
        return self.slot_owner.shape[-1]

    @property
    def num_experts(self) -> int:
        return self.slot_map.shape[-1]


def build_bank(expert_weights: Dict[str, jax.Array], n_hi: int,
               lo_bits: int, group_size: int = 64,
               hi_bits: int = 16) -> ExpertBankQ:
    """Prepare the two weight tiers from dense bf16 expert weights.

    ``expert_weights``: name → (L, E, K, N). The hi pool starts EMPTY
    (all experts serve from lo) — the online policy fills it.

    ``hi_bits``: 16 keeps bf16 hi versions (paper's FP16 tier). A value in
    {4, 8} builds an int-hi tier (the paper's Qwen3-80B Int4-hi/Int2-lo
    configuration); those are stored dequantized in the pool (pool bytes are
    then accounted at ``hi_bits`` by the budget model, matching a real
    deployment where the pool stores packed int4).
    """
    names = sorted(expert_weights)
    first = expert_weights[names[0]]
    L = first.shape[0]
    E = first.shape[1]
    lo, hi = {}, {}
    for n in names:
        w = expert_weights[n]
        lo[n] = quantize(w, bits=lo_bits, group_size=group_size)
        if hi_bits < 16:
            # Simulate the int-hi tier numerically (store its dequantized
            # values); budget accounting uses hi_bits.
            w = quantize(w, bits=hi_bits, group_size=group_size).dequantize()
        hi[n] = jnp.zeros((L, n_hi) + w.shape[2:], jnp.bfloat16)
    slot_owner = jnp.full((L, n_hi), -1, jnp.int32)
    slot_map = jnp.full((L, E), -1, jnp.int32)
    return ExpertBankQ(lo=lo, hi=hi, slot_owner=slot_owner, slot_map=slot_map)


def build_bank_empty(expert_weights_shapes: Dict[str, tuple], n_hi: int,
                     lo_bits: int, group_size: int = 64) -> ExpertBankQ:
    """A bank whose lo rows are NOT yet materialized (streaming cold start):
    packed codes and scales are zero until ``write_lo_expert`` stages each
    expert's rows from the checkpoint shards. Callers gate serving on the
    store's ``lo_valid`` mask — a forward pass must never read a zero row.

    ``expert_weights_shapes``: name → (L, E, K, N) logical dense shapes."""
    from repro.quant.qtensor import _elems_per_byte   # layout contract
    lo, hi = {}, {}
    first = next(iter(expert_weights_shapes.values()))
    L, E = first[0], first[1]
    for n, shape in sorted(expert_weights_shapes.items()):
        l4, e4, k, nn = shape
        lo[n] = QuantizedTensor(
            packed=jnp.zeros((l4, e4, k // _elems_per_byte(lo_bits), nn),
                             jnp.uint8),
            scales=jnp.zeros((l4, e4, k // group_size, nn), jnp.bfloat16),
            bits=lo_bits, group_size=group_size, shape=tuple(shape))
        hi[n] = jnp.zeros((l4, n_hi, k, nn), jnp.bfloat16)
    slot_owner = jnp.full((L, n_hi), -1, jnp.int32)
    slot_map = jnp.full((L, E), -1, jnp.int32)
    return ExpertBankQ(lo=lo, hi=hi, slot_owner=slot_owner,
                       slot_map=slot_map)


def expert_hi_nbytes(expert_weights_shapes: Dict[str, tuple], hi_bits: int = 16,
                     group_size: int = 64) -> int:
    """Device bytes of ONE expert's hi-precision version (per layer)."""
    total = 0
    for shape in expert_weights_shapes.values():
        per = shape[2:]  # (K, N)
        if hi_bits >= 16:
            total += int(np.prod(per)) * 2
        else:
            total += quantized_nbytes(per, hi_bits, group_size)
    return total


def expert_lo_nbytes(expert_weights_shapes: Dict[str, tuple], lo_bits: int,
                     group_size: int = 64) -> int:
    total = 0
    for shape in expert_weights_shapes.values():
        total += quantized_nbytes(shape[2:], lo_bits, group_size)
    return total


# ---------------------------------------------------------------------------
# Published-state updates. These are the ONLY functions that touch the device
# arrays; both are donated in the jitted controller path so promotion writes
# happen in place (the TPU analogue of copying into a preallocated pool slot).
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, donate_argnums=0)
def write_hi_slot(hi: Dict[str, jax.Array], layer: jax.Array,
                  slot: jax.Array, w: Dict[str, jax.Array]
                  ) -> Dict[str, jax.Array]:
    """Copy one expert's hi weights (name → (K, N)) into pool slot (layer,
    slot) of every leaf of ``hi`` (name → (L, slots, K, N)), in place.

    The leaves are donated together, in one program: a burst of promotions
    never holds more than one copy of the pool (an undonated write allocates
    each whole leaf anew per promotion), and a write that fails consumes
    none of them. Donation orders the write after every dispatched forward
    that reads the pool, so the copy does not overlap the serve step in
    flight; it still runs asynchronously to the host, which dispatches the
    next step without waiting for it.
    """
    return {n: jax.lax.dynamic_update_slice(
                leaf, w[n].astype(leaf.dtype)[None, None],
                (layer, slot) + (0,) * w[n].ndim)
            for n, leaf in hi.items()}


@jax.jit
def write_lo_expert(leaf: jax.Array, layer: jax.Array, expert: jax.Array,
                    row: jax.Array) -> jax.Array:
    """Copy one expert's lo-tier rows (packed codes OR scales) into an
    (L, E, …) bank leaf — the H2D staging write of host→lo promotion and
    streaming cold start. Same publish-then-switch discipline as
    ``write_hi_slot``: the row is unreferenced until its residency mask
    flips, so XLA overlaps the copy with in-flight serve steps."""
    return jax.lax.dynamic_update_slice(
        leaf, row.astype(leaf.dtype)[None, None],
        (layer, expert) + (0,) * row.ndim)


@jax.jit
def write_lo_rows(leaf: jax.Array, layer: jax.Array, idx: jax.Array,
                  vals: jax.Array) -> jax.Array:
    """Bulk variant of :func:`write_lo_expert`: stage several experts of one
    layer in a single scatter — the cold-start pump issues one device write
    per (layer, leaf) instead of one per expert cell."""
    return leaf.at[layer, idx].set(vals.astype(leaf.dtype))


@jax.jit
def swap_expert_rows(leaf: jax.Array, layer: jax.Array, e: jax.Array,
                     f: jax.Array) -> jax.Array:
    """Swap experts ``e`` and ``f`` at one layer of an (L, E, ...) leaf.

    This is the device half of expert-ownership migration under expert
    parallelism: "expert id" IS the position in every bank/router array, so
    moving an expert to another shard relabels the pair — swap the lo rows
    (this helper), the router columns (``swap_router_cols``), and the host
    mirrors; the forward pass is invariant and needs no changes."""
    a, b = leaf[layer, e], leaf[layer, f]
    return leaf.at[layer, e].set(b).at[layer, f].set(a)


@jax.jit
def swap_router_cols(router: jax.Array, layer: jax.Array, e: jax.Array,
                     f: jax.Array) -> jax.Array:
    """Swap two expert columns of an (L, d_model, E) router at ``layer`` —
    the compensating half of relabeling migration: tokens that routed to
    position ``e`` now route to ``f`` (which holds the same weights)."""
    a, b = router[layer, :, e], router[layer, :, f]
    return router.at[layer, :, e].set(b).at[layer, :, f].set(a)


@jax.jit
def publish(slot_map: jax.Array, slot_owner: jax.Array, layer: jax.Array,
            expert: jax.Array, slot: jax.Array):
    """Atomically publish expert→slot (promotion). slot = −1 demotes: the
    handle falls back to the always-resident lo version first; the hi slot is
    reclaimed afterwards (publish-then-switch, paper §3.2)."""
    old_owner = slot_owner[layer, slot]
    # Demote whoever owned the slot (no-op if free).
    slot_map = slot_map.at[layer, jnp.where(old_owner >= 0, old_owner, 0)].set(
        jnp.where(old_owner >= 0, -1, slot_map[layer, jnp.where(old_owner >= 0, old_owner, 0)]))
    slot_map = slot_map.at[layer, expert].set(slot)
    slot_owner = slot_owner.at[layer, slot].set(
        jnp.where(slot >= 0, expert, slot_owner[layer, slot]))
    return slot_map, slot_owner


@jax.jit
def unpublish(slot_map: jax.Array, slot_owner: jax.Array, layer: jax.Array,
              expert: jax.Array):
    """Demotion: redirect the handle to the lo version and free the slot."""
    slot = slot_map[layer, expert]
    slot_map = slot_map.at[layer, expert].set(-1)
    safe_slot = jnp.where(slot >= 0, slot, 0)
    slot_owner = slot_owner.at[layer, safe_slot].set(
        jnp.where(slot >= 0, -1, slot_owner[layer, safe_slot]))
    return slot_map, slot_owner
