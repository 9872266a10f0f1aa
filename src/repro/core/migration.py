"""Data-plane execution of controller migration decisions (paper §5.1).

The controller (control plane) decides *what* moves; this module is the
shim-layer data mover (paper §3 "handling TurboKV controller's data
migration requests between the storage nodes").  All movers are jittable,
static-shape array programs over :class:`~repro.core.store.StoreState`.
The ``repro.cluster`` metrics charge each executed plan as migration
traffic (entries counted on the source before the move).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.core import keys as K
from repro.core.store import (StoreState, _compact_sorted, delete_range,
                              in_range, put_sorted)

EMPTY = K.EMPTY_KEY


@dataclasses.dataclass(frozen=True)
class MigrationOp:
    """One controller decision: move/copy [lo, hi] from src to dst.

    kind: 'move' (migration — delete at src afterwards),
          'copy' (replica repair / chain widening — src keeps its data), or
          'reclaim' (chain narrowing — delete [lo, hi] at src, no copy;
          dst is ignored).
    """

    lo: int
    hi: int
    src: int
    dst: int
    kind: str = "move"


def _extract_range(slab_keys: jnp.ndarray, slab_vals: jnp.ndarray, lo, hi):
    """All entries with key in [lo, hi], EMPTY-padded to capacity."""
    hit = in_range(slab_keys, lo, hi)
    ex_keys = jnp.where(hit, slab_keys, EMPTY)
    # the hits are a sorted subsequence of the sorted slab: gather-compact
    # them to a prefix instead of re-sorting the whole slab
    return _compact_sorted(ex_keys, slab_vals, hit)


@partial(jax.jit, static_argnames=("move",))
def apply_migration(store: StoreState, lo, hi, src: jnp.ndarray, dst: jnp.ndarray, *, move: bool) -> StoreState:
    """Execute one migration/copy op (jitted; lo/hi/src/dst are traced, so
    every op of a plan reuses one compiled program per store shape)."""
    lo = jnp.asarray(lo, jnp.uint32)
    hi = jnp.asarray(hi, jnp.uint32)
    ex_keys, ex_vals = _extract_range(store.keys[src], store.values[src], lo, hi)

    # the extracted range is sorted and distinct: no dedupe sort (a sort
    # of a whole slab is minutes of TPU compile at a million slots)
    dst_keys, dst_vals, dropped = put_sorted(store.keys[dst], store.values[dst], ex_keys, ex_vals)
    keys = store.keys.at[dst].set(dst_keys)
    values = store.values.at[dst].set(dst_vals)

    if move:
        src_keys, src_vals = delete_range(keys[src], values[src], lo, hi)
        keys = keys.at[src].set(src_keys)
        values = values.at[src].set(src_vals)

    return StoreState(keys=keys, values=values, overflow=store.overflow.at[dst].add(dropped))


@jax.jit
def apply_reclaim(store: StoreState, lo, hi, node: jnp.ndarray) -> StoreState:
    """Delete [lo, hi] at ``node`` (chain-narrowing space reclamation)."""
    lo = jnp.asarray(lo, jnp.uint32)
    hi = jnp.asarray(hi, jnp.uint32)
    new_keys, new_vals = delete_range(store.keys[node], store.values[node], lo, hi)
    return StoreState(
        keys=store.keys.at[node].set(new_keys),
        values=store.values.at[node].set(new_vals),
        overflow=store.overflow,
    )


def execute(store: StoreState, ops: list[MigrationOp]) -> StoreState:
    """Run a controller migration plan (host loop over jitted movers)."""
    for op in ops:
        # spans are uint32 (up to 0xFFFFFFFE): cast before the jit boundary
        # so python ints never canonicalize to (overflowing) int32
        lo, hi = jnp.uint32(op.lo), jnp.uint32(op.hi)
        if op.kind == "reclaim":
            store = apply_reclaim(store, lo, hi, jnp.int32(op.src))
        else:
            store = apply_migration(
                store, lo, hi, jnp.int32(op.src), jnp.int32(op.dst),
                move=(op.kind == "move"),
            )
    return store
