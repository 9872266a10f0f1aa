"""Pallas TPU kernel: the switch data-plane match-action stage.

This is the paper's per-packet hot path (§4.2): match the matching value
against the sub-range table, fetch the chain action data, pick head/tail by
opcode.  A P4 switch does this in TCAM; the TPU-native formulation
(DESIGN.md §2) is **masked interval matching over the slot pool** — the
table is ``Spad`` physical slots with inclusive per-slot spans
``[lo_i, hi_i]`` (dead/padding slots carry ``lo > hi`` and can never hit),
and the record index of value v is

    ridx(v) = min_i { i : lo_i <= v <= hi_i }          (clamped to S-1)

an O(S) broadcast-compare + min-reduce that is perfectly lane-parallel on
the VPU and needs no gather (TPU gathers from dynamic vectors are slow; the
span tiles live wholly in VMEM).  Unlike the earlier sorted-bounds
compare-and-sum, this tolerates *holes*: the controller kills and
reallocates slots in place (split/merge) without re-sorting the table, so
the data plane never changes shape.  Chain fetch is a one-hot contraction
against the chain table — an MXU matmul for free.

Tiling: the packet batch is reshaped to (B/128, 128) and tiled (Bb, 128)
rows per grid step; the span / chain tables are small (S <= few K) and are
mapped whole into VMEM every step (grid-invariant index_map).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
DEFAULT_BLOCK_ROWS = 8  # sublane-aligned f32/i32 tile height

_NO_HIT = 0x7FFFFFFF  # min-reduce identity for the slot-match
_EMPTY_KEY = 0xFFFFFFFF  # core.constants.EMPTY_KEY: sorted-slab tail padding


def _slot_match_tile(mvals, lo, hi, num_slots: int):
    """(Bb, 128) mvals vs (1, Spad) spans -> (Bb, 128) slot ids.

    Dead/padding slots (lo > hi) lose every lookup; a (malformed-table)
    total miss clamps to slot num_slots - 1, exactly like the jnp oracle.
    """
    spad = lo.shape[-1]
    hit = (mvals[:, :, None] >= lo[0][None, None, :]) & (
        mvals[:, :, None] <= hi[0][None, None, :]
    )
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, 1, spad), 2)
    ridx = jnp.min(jnp.where(hit, iota, jnp.int32(_NO_HIT)), axis=-1)
    return jnp.minimum(ridx, num_slots - 1)


def _gather_rows_tile(ridx, rows):
    """One-hot contraction: (Bb, 128) slot ids vs each (Spad,) row of a
    (R, Spad) register table -> list of R (Bb, 128) per-packet values.
    Shared by every kernel's chain/clen/dirty fetch (TPU gathers from
    dynamic vectors are slow; the one-hot contraction is MXU-friendly)."""
    spad = rows.shape[-1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, 1, spad), 2)
    onehot = (ridx[:, :, None] == iota).astype(jnp.int32)
    return [jnp.sum(onehot * rows[p][None, None, :], axis=-1)
            for p in range(rows.shape[0])]


def _select_pos_tile(cols, pos):
    """cols[pos] over static chain positions (r_max small): the tile-level
    take_along_axis all three kernels share."""
    out = cols[0]
    for p in range(1, len(cols)):
        out = jnp.where(pos == p, cols[p], out)
    return out


def _load_gather_tile(n, loads):
    """(Bb, 128) node ids -> their (1, Npad) load-register values (one-hot
    contraction over the node axis; negative ids clamp to node 0)."""
    npad = loads.shape[-1]
    niota = jax.lax.broadcasted_iota(jnp.int32, (1, 1, npad), 2)
    return jnp.sum((jnp.maximum(n, 0)[:, :, None] == niota).astype(jnp.int32)
                   * loads[0][None, None, :], axis=-1)


def _p2c_tile(chain_cols, clen_b, u1, u2, loads):
    """The power-of-two-choices pick, shared by the spread and the dirty
    (CRAQ) kernels — the bit-parity contract with ``routing._p2c_pick``
    and the jnp refs hangs on this one formula.  Returns
    ``(picked, ppos, p1, p2, first_wins)``."""
    c = jnp.maximum(clen_b, 1)
    p1 = u1 % c
    p2 = u2 % c
    n1 = _select_pos_tile(chain_cols, p1)
    n2 = _select_pos_tile(chain_cols, p2)
    l1 = _load_gather_tile(n1, loads)
    l2 = _load_gather_tile(n2, loads)
    first_wins = l1 <= l2
    return (jnp.where(first_wins, n1, n2), jnp.where(first_wins, p1, p2),
            p1, p2, first_wins)


def _slab_lookup_tile(qkeys, target, slabs, slab_len: int, gather_rows: bool):
    """(Bb, 128) query keys + serving nodes vs the (N, Cpad) sorted-slab
    table -> ``(slot, found)`` per packet.

    ``searchsorted(slab, qkey, side="left")`` computed as a rank count —
    lane-parallel sums instead of a binary search.  Two bit-identical
    formulations, chosen per backend by the launcher:

    * ``gather_rows=False`` (compiled TPU): walk the slab in static
      128-lane chunks, materialising the per-packet node row by a static
      N-way select (N = node count, small) — TPU gathers from dynamic
      vectors are slow, broadcast-select is lane-parallel VPU work.
    * ``gather_rows=True`` (interpret / CPU emulation): a branchless
      vectorised bisect — log2(Cpad) rounds, each gathering one probe
      key per packet (``slabs[node, mid]``).  Gather is the right
      primitive where the body lowers to XLA:CPU; O(log C) probes beat
      the O(C) rank count there, and no (B, Cpad) row ever materialises.

    EMPTY tail padding is inert either way: the slab stays globally
    sorted (EMPTY is the maximum key), so bisect-left over the padded
    row equals the rank count over it, and an EMPTY probe only equals an
    (already-masked) EMPTY query key.  The slot clamps into
    ``[0, slab_len)`` exactly like ``store.slab_get``; ``found`` masks
    EMPTY queries and unrouted (negative-node) packets.
    """
    n_nodes, cpad = slabs.shape
    t_safe = jnp.clip(target, 0, n_nodes - 1)
    qk = qkeys[:, :, None]                                 # (Bb, 128, 1)
    if gather_rows:
        # bisect_left(slabs[t], qk) with per-packet [lo, hi) intervals,
        # all lanes stepping in lock-step for ceil(log2(cpad)) + 1 rounds
        lo = jnp.zeros(qkeys.shape, dtype=jnp.int32)
        hi = jnp.full(qkeys.shape, cpad, dtype=jnp.int32)
        for _ in range(cpad.bit_length()):
            active = lo < hi
            mid = (lo + hi) // 2
            v = slabs[t_safe, jnp.minimum(mid, cpad - 1)]  # (Bb, 128)
            less = v < qkeys
            lo = jnp.where(active & less, mid + 1, lo)
            hi = jnp.where(active & ~less, mid, hi)
        slot = lo
        probe = slabs[t_safe, jnp.minimum(slot, cpad - 1)]
        found = probe == qkeys
    else:
        slot = jnp.zeros(qkeys.shape, dtype=jnp.int32)
        found = jnp.zeros(qkeys.shape, dtype=jnp.bool_)
        for c in range(cpad // LANES):
            chunk = slabs[:, c * LANES:(c + 1) * LANES]    # (N, 128)
            row = jnp.broadcast_to(
                chunk[0][None, None, :], qkeys.shape + (LANES,)
            )
            for n in range(1, n_nodes):
                row = jnp.where(
                    t_safe[:, :, None] == n, chunk[n][None, None, :], row
                )
            slot = slot + jnp.sum((row < qk).astype(jnp.int32), axis=-1)
            found = found | jnp.any(row == qk, axis=-1)
    slot = jnp.minimum(slot, slab_len - 1)
    found = found & (qkeys != jnp.uint32(_EMPTY_KEY)) & (target >= 0)
    return slot, found


def _kernel(mvals_ref, opcodes_ref, lo_ref, hi_ref, chains_ref, clen_ref,
            ridx_ref, target_ref, chain_ref, *, num_slots: int, r_max: int):
    mvals = mvals_ref[...]            # (Bb, 128) uint32
    opcodes = opcodes_ref[...]        # (Bb, 128) int32
    lo = lo_ref[...]                  # (1, Spad) uint32 span starts, dead-masked
    hi = hi_ref[...]                  # (1, Spad) uint32 span ends, dead-masked
    chains = chains_ref[...]          # (r_max, Spad) int32
    clen = clen_ref[...]              # (1, Spad) int32

    # --- masked interval match over the slot pool ---
    ridx = _slot_match_tile(mvals, lo, hi, num_slots)   # (Bb, 128)

    # --- one-hot chain fetch (action-data registers) ---
    chain_cols = _gather_rows_tile(ridx, chains)
    chain = jnp.stack(chain_cols, axis=0)                       # (r, Bb, 128)
    (clen_b,) = _gather_rows_tile(ridx, clen)                   # (Bb, 128)

    # --- opcode action: PUT/DEL -> head, GET/SCAN -> tail ---
    is_write = (opcodes == 1) | (opcodes == 2)
    head = chain[0]
    tail = _select_pos_tile(chain_cols, clen_b - 1)
    target = jnp.where(is_write, head, tail)

    ridx_ref[...] = ridx
    target_ref[...] = target
    chain_ref[...] = chain


def _kernel_spread(mvals_ref, opcodes_ref, u1_ref, u2_ref, lo_ref, hi_ref,
                   chains_ref, clen_ref, loads_ref,
                   ridx_ref, target_ref, chain_ref,
                   *, num_slots: int, r_max: int):
    """Match-action stage with power-of-two-choices read spreading.

    Mirrors ``core.routing.route_load_aware``: writes -> chain head; reads
    pick two live chain positions (from the pre-drawn uniforms u1/u2) and
    go to the replica with the smaller load register.  ``loads_ref`` is
    the (1, Npad) per-node load register tile, whole in VMEM.
    """
    mvals = mvals_ref[...]
    opcodes = opcodes_ref[...]
    u1 = u1_ref[...]                  # (Bb, 128) int32 raw uniform draws
    u2 = u2_ref[...]
    lo = lo_ref[...]
    hi = hi_ref[...]
    chains = chains_ref[...]
    clen = clen_ref[...]
    loads = loads_ref[...]            # (1, Npad) int32 load registers

    ridx = _slot_match_tile(mvals, lo, hi, num_slots)
    chain_cols = _gather_rows_tile(ridx, chains)
    chain = jnp.stack(chain_cols, axis=0)
    (clen_b,) = _gather_rows_tile(ridx, clen)

    picked, _ppos, _p1, _p2, _fw = _p2c_tile(chain_cols, clen_b, u1, u2, loads)

    is_write = (opcodes == 1) | (opcodes == 2)
    target = jnp.where(is_write, chain[0], picked)

    ridx_ref[...] = ridx
    target_ref[...] = target
    chain_ref[...] = chain


def _kernel_spread_dirty(mvals_ref, opcodes_ref, u1_ref, u2_ref, lo_ref, hi_ref,
                         chains_ref, clen_ref, loads_ref, dirty_ref,
                         ridx_ref, target_ref, chain_ref, picked_ref, bounced_ref,
                         *, num_slots: int, r_max: int):
    """Match-action stage with CRAQ apportioned reads.

    The p2c pick of ``_kernel_spread`` plus the dirty-bit serving rule:
    ``dirty_ref`` is the (r_max, Spad) per-(position, slot) dirty table
    (``repro.replication.state.dirty_bits``, transposed like the chain
    registers); a read whose picked position is dirty and not the tail
    bounces to the tail.  Emits the picked replica and the bounce mask so
    the DES hop planner can charge the extra hop.
    """
    mvals = mvals_ref[...]
    opcodes = opcodes_ref[...]
    u1 = u1_ref[...]
    u2 = u2_ref[...]
    lo = lo_ref[...]
    hi = hi_ref[...]
    chains = chains_ref[...]
    clen = clen_ref[...]
    loads = loads_ref[...]
    dirty = dirty_ref[...]            # (r_max, Spad) int32 dirty bits

    ridx = _slot_match_tile(mvals, lo, hi, num_slots)
    chain_cols = _gather_rows_tile(ridx, chains)
    dirty_cols = _gather_rows_tile(ridx, dirty)
    chain = jnp.stack(chain_cols, axis=0)
    (clen_b,) = _gather_rows_tile(ridx, clen)

    picked, ppos, p1, p2, first_wins = _p2c_tile(
        chain_cols, clen_b, u1, u2, loads
    )
    d1 = _select_pos_tile(dirty_cols, p1)
    d2 = _select_pos_tile(dirty_cols, p2)
    d_pick = jnp.where(first_wins, d1, d2)
    tail = _select_pos_tile(chain_cols, clen_b - 1)

    is_write = (opcodes == 1) | (opcodes == 2)
    bounced = (
        (~is_write) & (d_pick != 0) & (ppos != clen_b - 1) & (picked >= 0)
    )
    read_target = jnp.where(bounced, tail, picked)
    target = jnp.where(is_write, chain[0], read_target)

    ridx_ref[...] = ridx
    target_ref[...] = target
    chain_ref[...] = chain
    picked_ref[...] = picked
    bounced_ref[...] = bounced.astype(jnp.int32)


def _kernel_stale(mvals_ref, opcodes_ref, sw_ref, lo_ref, hi_ref, chains_ref,
                  clen_ref, version_ref, committed_ref,
                  sridx_ref, server_ref, divergent_ref,
                  *, num_slots: int, r_max: int, n_switches: int):
    """Replicated-tier match-action stage: each packet matches against its
    ingress switch's private table copy and carries the divergence bit.

    The per-switch tables ride whole in VMEM (``W`` is the fabric's switch
    count — a handful); rather than gathering a (Bb, 128, Spad) per-packet
    row (dynamic-vector gathers are slow on TPU), the tile runs the
    interval match against *every* switch's table and broadcast-selects by
    the packet's switch id — bit-identical to the gathered-row jnp oracle
    because each packet's result only ever reads its own switch's rows.
    The switches are a ``fori_loop``, not an unrolled loop, so only one
    switch's (Bb, 128, Spad) intermediates are live in VMEM at a time.
    Per-switch tables arrive as (W, rows, Spad) and are indexed on the
    leading axis.
    """
    mvals = mvals_ref[...]            # (Bb, 128) uint32
    opcodes = opcodes_ref[...]        # (Bb, 128) int32
    sw = sw_ref[...]                  # (Bb, 128) int32 ingress switch ids
    committed = committed_ref[...]    # (1, Spad) int32 (u32 bit-cast)
    is_write = (opcodes == 1) | (opcodes == 2)

    def match(w):
        # lo/hi (1, Spad) uint32 live/dead-masked; chains (r_max, Spad);
        # clen, version (1, Spad) int32
        ridx_w = _slot_match_tile(mvals, lo_ref[w], hi_ref[w], num_slots)
        cols_w = _gather_rows_tile(ridx_w, chains_ref[w])
        (clen_w,) = _gather_rows_tile(ridx_w, clen_ref[w])
        tail_w = _select_pos_tile(cols_w, clen_w - 1)
        server_w = jnp.where(is_write, cols_w[0], tail_w)
        (ver_w,) = _gather_rows_tile(ridx_w, version_ref[w])
        (com_w,) = _gather_rows_tile(ridx_w, committed)
        # int32, not bool: Mosaic cannot select between i1 vectors
        return ridx_w, server_w, (ver_w != com_w).astype(jnp.int32)

    def fold(w, acc):
        here = sw == w
        return tuple(jnp.where(here, new, old)
                     for new, old in zip(match(w), acc))

    # switch 0 seeds the select, so a packet outside [0, W) reads switch 0
    sridx, server, divergent = jax.lax.fori_loop(1, n_switches, fold,
                                                 match(0))
    sridx_ref[...] = sridx
    server_ref[...] = server
    divergent_ref[...] = divergent


def _kernel_apply(mvals_ref, opcodes_ref, u1_ref, u2_ref, qkeys_ref,
                  lo_ref, hi_ref, chains_ref, clen_ref, loads_ref, dirty_ref,
                  slabs_ref,
                  ridx_ref, target_ref, chain_ref, picked_ref, bounced_ref,
                  slot_ref, found_ref,
                  *, num_slots: int, r_max: int, slab_len: int,
                  gather_rows: bool):
    """The fused route→apply hot path: ``_kernel_spread_dirty`` plus the
    slab-slot scatter, one pass over the packet tile.

    Routing emits the serving node; the apply stage then needs each
    packet's slot in that node's sorted slab.  Running both in one kernel
    keeps the tile's ridx/chain/target live in VMEM between the stages —
    the two-kernel path writes them to HBM and reads them straight back.
    ``slabs_ref`` is the (N, Cpad) per-node sorted key table (EMPTY-tail
    padded to a lane multiple), whole in VMEM like the span tables.
    """
    mvals = mvals_ref[...]
    opcodes = opcodes_ref[...]
    u1 = u1_ref[...]
    u2 = u2_ref[...]
    qkeys = qkeys_ref[...]            # (Bb, 128) uint32 raw query keys
    lo = lo_ref[...]
    hi = hi_ref[...]
    chains = chains_ref[...]
    clen = clen_ref[...]
    loads = loads_ref[...]
    dirty = dirty_ref[...]
    slabs = slabs_ref[...]            # (N, Cpad) uint32 sorted slab keys

    ridx = _slot_match_tile(mvals, lo, hi, num_slots)
    chain_cols = _gather_rows_tile(ridx, chains)
    dirty_cols = _gather_rows_tile(ridx, dirty)
    chain = jnp.stack(chain_cols, axis=0)
    (clen_b,) = _gather_rows_tile(ridx, clen)

    picked, ppos, p1, p2, first_wins = _p2c_tile(
        chain_cols, clen_b, u1, u2, loads
    )
    d1 = _select_pos_tile(dirty_cols, p1)
    d2 = _select_pos_tile(dirty_cols, p2)
    d_pick = jnp.where(first_wins, d1, d2)
    tail = _select_pos_tile(chain_cols, clen_b - 1)

    is_write = (opcodes == 1) | (opcodes == 2)
    bounced = (
        (~is_write) & (d_pick != 0) & (ppos != clen_b - 1) & (picked >= 0)
    )
    read_target = jnp.where(bounced, tail, picked)
    target = jnp.where(is_write, chain[0], read_target)

    slot, found = _slab_lookup_tile(qkeys, target, slabs, slab_len, gather_rows)

    ridx_ref[...] = ridx
    target_ref[...] = target
    chain_ref[...] = chain
    picked_ref[...] = picked
    bounced_ref[...] = bounced.astype(jnp.int32)
    slot_ref[...] = slot
    found_ref[...] = found.astype(jnp.int32)


def _kernel_lookup(qkeys_ref, target_ref, slabs_ref, slot_ref, found_ref,
                   *, slab_len: int, gather_rows: bool):
    """Standalone slab-slot lookup (the second kernel of the two-kernel
    route→apply baseline): reads the routed targets back from HBM."""
    qkeys = qkeys_ref[...]
    target = target_ref[...]
    slabs = slabs_ref[...]
    slot, found = _slab_lookup_tile(qkeys, target, slabs, slab_len, gather_rows)
    slot_ref[...] = slot
    found_ref[...] = found.astype(jnp.int32)


def range_match_stale_pallas(
    mvals: jnp.ndarray,            # (B,) uint32 matching values
    opcodes: jnp.ndarray,          # (B,) int32
    sw: jnp.ndarray,               # (B,) int32 ingress switch ids
    lo_w: jnp.ndarray,             # (W, Spad) uint32 dead-masked span starts
    hi_w: jnp.ndarray,             # (W, Spad) uint32 dead-masked span ends
    chains_w: jnp.ndarray,         # (W * r_max, Spad) int32
    clen_w: jnp.ndarray,           # (W, Spad) int32
    version_w: jnp.ndarray,        # (W, Spad) int32 (u32 bit-cast)
    committed: jnp.ndarray,        # (Spad,) int32 (u32 bit-cast)
    *,
    num_slots: int,
    r_max: int,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool,
):
    """Launch the replicated-directory (stale-table) match-action kernel.

    Contract of :func:`repro.kernels.range_match.ref.range_match_stale_ref`
    (``chains_w`` arrives switch-major flattened to (W*r_max, Spad));
    returns ``(sridx, server, divergent)`` with divergent an int32 0/1
    mask.
    """
    B = mvals.shape[0]
    rows = B // LANES
    n_switches, spad = lo_w.shape

    grid = (rows // block_rows,)
    kernel = functools.partial(
        _kernel_stale, num_slots=num_slots, r_max=r_max,
        n_switches=n_switches,
    )

    out_shapes = (
        jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
    )
    whole = lambda i: (0, 0)
    whole_w = lambda i: (0, 0, 0)
    tile = lambda i: (i, 0)
    per_switch = pl.BlockSpec((n_switches, 1, spad), whole_w)
    sridx, server, divergent = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, LANES), tile),
            pl.BlockSpec((block_rows, LANES), tile),
            pl.BlockSpec((block_rows, LANES), tile),
            per_switch,
            per_switch,
            pl.BlockSpec((n_switches, r_max, spad), whole_w),
            per_switch,
            per_switch,
            pl.BlockSpec((1, spad), whole),
        ],
        out_specs=(
            pl.BlockSpec((block_rows, LANES), tile),
            pl.BlockSpec((block_rows, LANES), tile),
            pl.BlockSpec((block_rows, LANES), tile),
        ),
        out_shape=out_shapes,
        interpret=interpret,
    )(
        mvals.reshape(rows, LANES),
        opcodes.reshape(rows, LANES),
        sw.reshape(rows, LANES),
        lo_w.reshape(n_switches, 1, spad),
        hi_w.reshape(n_switches, 1, spad),
        chains_w.reshape(n_switches, r_max, spad),
        clen_w.reshape(n_switches, 1, spad),
        version_w.reshape(n_switches, 1, spad),
        committed.reshape(1, spad),
    )
    return sridx.reshape(B), server.reshape(B), divergent.reshape(B)


def range_match_apply_pallas(
    mvals: jnp.ndarray,            # (B,) uint32 matching values
    opcodes: jnp.ndarray,          # (B,) int32
    u1: jnp.ndarray,               # (B,) int32 nonneg uniform draws
    u2: jnp.ndarray,               # (B,) int32
    qkeys: jnp.ndarray,            # (B,) uint32 raw query keys
    slot_lo: jnp.ndarray,          # (Spad,) uint32 dead-masked span starts
    slot_hi: jnp.ndarray,          # (Spad,) uint32 dead-masked span ends
    chains: jnp.ndarray,           # (r_max, Spad) int32
    chain_len: jnp.ndarray,        # (Spad,) int32
    loads: jnp.ndarray,            # (Npad,) int32 per-node load registers
    dirty: jnp.ndarray,            # (r_max, Spad) int32 dirty bits
    slabs: jnp.ndarray,            # (N, Cpad) uint32 sorted slab keys
    *,
    num_slots: int,
    slab_len: int,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool,
    gather_rows: bool | None = None,
):
    """Launch the fused route→apply kernel.

    Contract of :func:`range_match_spread_dirty_pallas` plus the slab
    lookup of ``store.slab_get`` against the serving node's slab; returns
    ``(ridx, target, chain, picked, bounced, slot, found)`` with found an
    int32 0/1 mask.  ``gather_rows`` picks the lookup formulation
    (``None``: gather under interpret, N-way select when compiled — see
    :func:`_slab_lookup_tile`); both are bit-identical.
    """
    B = mvals.shape[0]
    rows = B // LANES
    r_max, spad = chains.shape
    npad = loads.shape[0]
    n_nodes, cpad = slabs.shape
    if gather_rows is None:
        gather_rows = interpret

    grid = (rows // block_rows,)
    kernel = functools.partial(
        _kernel_apply, num_slots=num_slots, r_max=r_max, slab_len=slab_len,
        gather_rows=gather_rows,
    )

    out_shapes = (
        jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        jax.ShapeDtypeStruct((r_max, rows, LANES), jnp.int32),
        jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
    )
    whole = lambda i: (0, 0)
    tile = lambda i: (i, 0)
    ridx, target, chain, picked, bounced, slot, found = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, LANES), tile),
            pl.BlockSpec((block_rows, LANES), tile),
            pl.BlockSpec((block_rows, LANES), tile),
            pl.BlockSpec((block_rows, LANES), tile),
            pl.BlockSpec((block_rows, LANES), tile),
            pl.BlockSpec((1, spad), whole),
            pl.BlockSpec((1, spad), whole),
            pl.BlockSpec((r_max, spad), lambda i: (0, 0)),
            pl.BlockSpec((1, spad), whole),
            pl.BlockSpec((1, npad), whole),
            pl.BlockSpec((r_max, spad), lambda i: (0, 0)),
            pl.BlockSpec((n_nodes, cpad), lambda i: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((block_rows, LANES), tile),
            pl.BlockSpec((block_rows, LANES), tile),
            pl.BlockSpec((r_max, block_rows, LANES), lambda i: (0, i, 0)),
            pl.BlockSpec((block_rows, LANES), tile),
            pl.BlockSpec((block_rows, LANES), tile),
            pl.BlockSpec((block_rows, LANES), tile),
            pl.BlockSpec((block_rows, LANES), tile),
        ),
        out_shape=out_shapes,
        interpret=interpret,
    )(
        mvals.reshape(rows, LANES),
        opcodes.reshape(rows, LANES),
        u1.reshape(rows, LANES),
        u2.reshape(rows, LANES),
        qkeys.reshape(rows, LANES),
        slot_lo.reshape(1, spad),
        slot_hi.reshape(1, spad),
        chains,
        chain_len.reshape(1, spad),
        loads.reshape(1, npad),
        dirty,
        slabs,
    )
    return (ridx.reshape(B), target.reshape(B), chain.reshape(r_max, B),
            picked.reshape(B), bounced.reshape(B),
            slot.reshape(B), found.reshape(B))


def slab_lookup_pallas(
    qkeys: jnp.ndarray,            # (B,) uint32 raw query keys
    target: jnp.ndarray,           # (B,) int32 serving nodes
    slabs: jnp.ndarray,            # (N, Cpad) uint32 sorted slab keys
    *,
    slab_len: int,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool,
    gather_rows: bool | None = None,
):
    """Launch the standalone slab-lookup kernel (two-kernel baseline's
    second stage).  Returns ``(slot, found)``, found an int32 0/1 mask."""
    B = qkeys.shape[0]
    rows = B // LANES
    n_nodes, cpad = slabs.shape
    if gather_rows is None:
        gather_rows = interpret

    grid = (rows // block_rows,)
    kernel = functools.partial(_kernel_lookup, slab_len=slab_len,
                               gather_rows=gather_rows)

    out_shapes = (
        jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
    )
    tile = lambda i: (i, 0)
    slot, found = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, LANES), tile),
            pl.BlockSpec((block_rows, LANES), tile),
            pl.BlockSpec((n_nodes, cpad), lambda i: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((block_rows, LANES), tile),
            pl.BlockSpec((block_rows, LANES), tile),
        ),
        out_shape=out_shapes,
        interpret=interpret,
    )(
        qkeys.reshape(rows, LANES),
        target.reshape(rows, LANES),
        slabs,
    )
    return slot.reshape(B), found.reshape(B)


def range_match_spread_dirty_pallas(
    mvals: jnp.ndarray,            # (B,) uint32 matching values
    opcodes: jnp.ndarray,          # (B,) int32
    u1: jnp.ndarray,               # (B,) int32 nonneg uniform draws
    u2: jnp.ndarray,               # (B,) int32
    slot_lo: jnp.ndarray,          # (Spad,) uint32 dead-masked span starts
    slot_hi: jnp.ndarray,          # (Spad,) uint32 dead-masked span ends
    chains: jnp.ndarray,           # (r_max, Spad) int32
    chain_len: jnp.ndarray,        # (Spad,) int32
    loads: jnp.ndarray,            # (Npad,) int32 per-node load registers
    dirty: jnp.ndarray,            # (r_max, Spad) int32 dirty bits
    *,
    num_slots: int,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool,
):
    """Launch the CRAQ apportioned-read match-action kernel.

    Same contract as :func:`range_match_spread_pallas` plus the dirty
    table; returns ``(ridx, target, chain, picked, bounced)`` with
    ``target`` the serving node (tail for bounced dirty reads).
    """
    B = mvals.shape[0]
    rows = B // LANES
    r_max, spad = chains.shape
    npad = loads.shape[0]

    grid = (rows // block_rows,)
    kernel = functools.partial(
        _kernel_spread_dirty, num_slots=num_slots, r_max=r_max
    )

    out_shapes = (
        jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        jax.ShapeDtypeStruct((r_max, rows, LANES), jnp.int32),
        jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
    )
    whole = lambda i: (0, 0)
    tile = lambda i: (i, 0)
    ridx, target, chain, picked, bounced = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, LANES), tile),
            pl.BlockSpec((block_rows, LANES), tile),
            pl.BlockSpec((block_rows, LANES), tile),
            pl.BlockSpec((block_rows, LANES), tile),
            pl.BlockSpec((1, spad), whole),
            pl.BlockSpec((1, spad), whole),
            pl.BlockSpec((r_max, spad), lambda i: (0, 0)),
            pl.BlockSpec((1, spad), whole),
            pl.BlockSpec((1, npad), whole),
            pl.BlockSpec((r_max, spad), lambda i: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((block_rows, LANES), tile),
            pl.BlockSpec((block_rows, LANES), tile),
            pl.BlockSpec((r_max, block_rows, LANES), lambda i: (0, i, 0)),
            pl.BlockSpec((block_rows, LANES), tile),
            pl.BlockSpec((block_rows, LANES), tile),
        ),
        out_shape=out_shapes,
        interpret=interpret,
    )(
        mvals.reshape(rows, LANES),
        opcodes.reshape(rows, LANES),
        u1.reshape(rows, LANES),
        u2.reshape(rows, LANES),
        slot_lo.reshape(1, spad),
        slot_hi.reshape(1, spad),
        chains,
        chain_len.reshape(1, spad),
        loads.reshape(1, npad),
        dirty,
    )
    return (ridx.reshape(B), target.reshape(B), chain.reshape(r_max, B),
            picked.reshape(B), bounced.reshape(B))


def range_match_spread_pallas(
    mvals: jnp.ndarray,            # (B,) uint32 matching values
    opcodes: jnp.ndarray,          # (B,) int32
    u1: jnp.ndarray,               # (B,) int32 nonneg uniform draws
    u2: jnp.ndarray,               # (B,) int32
    slot_lo: jnp.ndarray,          # (Spad,) uint32 dead-masked span starts
    slot_hi: jnp.ndarray,          # (Spad,) uint32 dead-masked span ends
    chains: jnp.ndarray,           # (r_max, Spad) int32
    chain_len: jnp.ndarray,        # (Spad,) int32
    loads: jnp.ndarray,            # (Npad,) int32 per-node load registers
    *,
    num_slots: int,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool,
):
    """Launch the load-aware match-action kernel (p2c read spreading).

    Same contract as :func:`range_match_pallas` plus the pre-drawn p2c
    uniforms and the node load registers; Npad must be a lane multiple.
    """
    B = mvals.shape[0]
    rows = B // LANES
    r_max, spad = chains.shape
    npad = loads.shape[0]

    grid = (rows // block_rows,)
    kernel = functools.partial(_kernel_spread, num_slots=num_slots, r_max=r_max)

    out_shapes = (
        jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        jax.ShapeDtypeStruct((r_max, rows, LANES), jnp.int32),
    )
    whole = lambda i: (0, 0)
    tile = lambda i: (i, 0)
    ridx, target, chain = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, LANES), tile),
            pl.BlockSpec((block_rows, LANES), tile),
            pl.BlockSpec((block_rows, LANES), tile),
            pl.BlockSpec((block_rows, LANES), tile),
            pl.BlockSpec((1, spad), whole),
            pl.BlockSpec((1, spad), whole),
            pl.BlockSpec((r_max, spad), lambda i: (0, 0)),
            pl.BlockSpec((1, spad), whole),
            pl.BlockSpec((1, npad), whole),
        ],
        out_specs=(
            pl.BlockSpec((block_rows, LANES), tile),
            pl.BlockSpec((block_rows, LANES), tile),
            pl.BlockSpec((r_max, block_rows, LANES), lambda i: (0, i, 0)),
        ),
        out_shape=out_shapes,
        interpret=interpret,
    )(
        mvals.reshape(rows, LANES),
        opcodes.reshape(rows, LANES),
        u1.reshape(rows, LANES),
        u2.reshape(rows, LANES),
        slot_lo.reshape(1, spad),
        slot_hi.reshape(1, spad),
        chains,
        chain_len.reshape(1, spad),
        loads.reshape(1, npad),
    )
    return ridx.reshape(B), target.reshape(B), chain.reshape(r_max, B)


def range_match_pallas(
    mvals: jnp.ndarray,        # (B,) uint32 matching values
    opcodes: jnp.ndarray,      # (B,) int32
    slot_lo: jnp.ndarray,      # (Spad,) uint32 dead-masked span starts
    slot_hi: jnp.ndarray,      # (Spad,) uint32 dead-masked span ends
    chains: jnp.ndarray,       # (r_max, Spad) int32
    chain_len: jnp.ndarray,    # (Spad,) int32
    *,
    num_slots: int,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool,
):
    """Launch the match-action kernel.  B must be a multiple of 128*block_rows
    (ops.py pads).  Returns (ridx (B,), target (B,), chain (r_max, B))."""
    B = mvals.shape[0]
    rows = B // LANES
    r_max, spad = chains.shape

    grid = (rows // block_rows,)
    kernel = functools.partial(_kernel, num_slots=num_slots, r_max=r_max)

    out_shapes = (
        jax.ShapeDtypeStruct((rows, LANES), jnp.int32),        # ridx
        jax.ShapeDtypeStruct((rows, LANES), jnp.int32),        # target
        jax.ShapeDtypeStruct((r_max, rows, LANES), jnp.int32),  # chain
    )
    whole = lambda i: (0, 0)
    ridx, target, chain = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, spad), whole),
            pl.BlockSpec((1, spad), whole),
            pl.BlockSpec((r_max, spad), lambda i: (0, 0)),
            pl.BlockSpec((1, spad), whole),
        ],
        out_specs=(
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((r_max, block_rows, LANES), lambda i: (0, i, 0)),
        ),
        out_shape=out_shapes,
        interpret=interpret,
    )(
        mvals.reshape(rows, LANES),
        opcodes.reshape(rows, LANES),
        slot_lo.reshape(1, spad),
        slot_hi.reshape(1, spad),
        chains,
        chain_len.reshape(1, spad),
    )
    return ridx.reshape(B), target.reshape(B), chain.reshape(r_max, B)
