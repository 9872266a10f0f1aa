"""The distributed data plane: TurboKV over a JAX device mesh (shard_map).

This is the in-mesh coordination path (DESIGN.md §2): the store is sharded
one storage node per device along a mesh axis; the directory is replicated
(every "switch" holds the same match-action table, like every ToR on the
query path); queries are injected sharded (each device fronts a slice of the
client aggregation servers) and are *routed by key* to the owning shard with
collectives standing in for switch hops.

Two routing strategies, both bit-identical to the single-program oracle
(``store.apply_routed``):

  * ``allgather`` — every shard sees the whole batch and filters what it
    owns (one all-gather + one psum).  Simple, collective-heavy; the
    faithful baseline whose cost mirrors "replicate the directory lookup
    everywhere".
  * ``bucket_a2a`` — each source buckets queries by target shard into
    bounded per-target queues and a single ``all_to_all`` delivers them
    (then the inverse all_to_all returns replies).  Bounded buckets model
    switch queue capacity: overflowing queries are dropped and counted, the
    client retries — this is also the straggler bound (no shard can be
    handed more than ``N * cap`` ops per step).  Writes propagate along the
    replica chain in ``r`` sequential all_to_all rounds — the literal chain
    replication dataflow of paper Fig 9(a).

The serving engine reuses ``bucket_a2a`` for KV-cache page routing, and
the ``repro.cluster`` epoch driver uses this module as its ``dist``
backend (``DistConfig.read_spread`` turns on the load-aware p2c read
path, ``return_decision`` feeds the DES hop planner).  Slab mutations go
through ``store.shard_apply`` -> ``slab_put``/``slab_delete``, so the
PR-4 searchsorted rank merge applies here verbatim and oracle/dist
parity stays bit-exact.

Two entry points share one per-device data plane (``_make_bucket_plane``):

  * :func:`make_dist_apply` — ONE epoch per shard_map dispatch (the
    per-epoch reference path; the fused driver used to step this with
    deferred host syncs).
  * :func:`make_dist_period` — the whole control period as ONE shard_map
    program with a ``lax.scan`` over the epochs *inside* it: the a2a
    bucketing rounds run in the scan body, the directory / load / repl /
    overload registers scan exactly like the single-host donated
    buffers, and the per-epoch routing decision is ``all_gather``-ed so
    the observe stage (node ops, sketch, overload step, hop plans, span
    sampling — all global-batch-order dependent) runs replicated on
    every device.  Bit-identical to stepping :func:`make_dist_apply`
    per epoch, compiled once per scenario.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import keys as K
from repro.core import routing as R
from repro.core.directory import Directory
from repro.core import store as S
from repro.core.store import StoreState, Responses, shard_apply

DROP = -1  # bucket slot for dead/overflowed queries
# third-level scopes under ``apply/exchange``: the read round and the
# chain's write rounds
READ_ROUND, WRITE_ROUND = "read", "write"


class ExchangeCounts(NamedTuple):
    """What one batch sent through the query all_to_all rounds, each an
    (R,) int32 over the R = 1 + r_max rounds: round 0 is the read round,
    round k the write round of chain position k - 1.  ``a2a_rows``
    counts the live slots sent, ``a2a_rows_remote`` those whose target
    is another device, ``a2a_slots`` every slot sent, live or not;
    ``bucket_peak`` is the largest fill of any (source, target) bucket,
    against ``DistConfig.bucket_cap``.  Over a mesh the rows and slots
    are sums over the devices and the peak their maximum (see
    :attr:`peaks`).  The replies ride back in the same slots."""

    a2a_rows: jnp.ndarray
    a2a_rows_remote: jnp.ndarray
    a2a_slots: jnp.ndarray
    bucket_peak: jnp.ndarray

    # fields combined by their maximum, over devices and over epochs
    peaks = frozenset({"bucket_peak"})


def _round_counts(slot: jnp.ndarray, me, n_shards: int, cap: int):
    """(live rows, remote rows, slots, peak bucket fill) of one round's
    bucket slots on this device."""
    live = slot >= 0
    target = jnp.where(live, slot // cap, n_shards)
    fill = jnp.zeros((n_shards,), jnp.int32).at[target].add(1, mode="drop")
    return (jnp.sum(live, dtype=jnp.int32),
            jnp.sum(live & (target != me), dtype=jnp.int32),
            jnp.int32(n_shards * cap), jnp.max(fill))


def _combine_exchange_counts(counts: ExchangeCounts, axis: str):
    """One device's :class:`ExchangeCounts` combined over the mesh axis."""
    return ExchangeCounts(*(
        (jax.lax.pmax if f in counts.peaks else jax.lax.psum)(x, axis)
        for f, x in zip(ExchangeCounts._fields, counts)))


# ---------------------------------------------------------------------------
# bounded bucketing (per-device helper, runs inside shard_map)
# ---------------------------------------------------------------------------


def bucketize(target: jnp.ndarray, n_shards: int, cap: int):
    """Group local queries by target shard into (n_shards, cap) slots.

    target: (Bl,) int32 in [0, n_shards) or DROP for dead queries.
    Returns (slot (Bl,) flat bucket slot or DROP, overflow_count).
    Deterministic: earlier queries (in batch order) win bucket slots.
    """
    Bl = target.shape[0]
    valid = (target >= 0) & (target < n_shards)
    tkey = jnp.where(valid, target, n_shards)  # dead queries sort last
    order = jnp.argsort(tkey, stable=True)
    sorted_t = tkey[order]
    group_start = jnp.searchsorted(sorted_t, jnp.arange(n_shards + 1), side="left")
    pos_in_group = jnp.arange(Bl) - group_start[jnp.minimum(sorted_t, n_shards)]
    keep = (sorted_t < n_shards) & (pos_in_group < cap)
    slot_sorted = jnp.where(keep, sorted_t * cap + pos_in_group, DROP)
    # map back to original order
    slot = jnp.zeros((Bl,), jnp.int32).at[order].set(slot_sorted.astype(jnp.int32))
    overflow = jnp.sum((sorted_t < n_shards) & (pos_in_group >= cap))
    return slot, overflow


def scatter_to_buckets(slot: jnp.ndarray, payload: jnp.ndarray, n_slots: int, fill):
    """payload (Bl, ...) -> buckets (n_slots, ...); DROP slots are discarded
    (out-of-bounds scatter indices drop in JAX)."""
    idx = jnp.where(slot >= 0, slot, n_slots)  # OOB -> dropped by scatter
    out = jnp.full((n_slots,) + payload.shape[1:], fill, payload.dtype)
    return out.at[idx].set(payload, mode="drop")


def gather_from_buckets(slot: jnp.ndarray, buckets: jnp.ndarray, fill):
    """Inverse of scatter: fetch each query's reply from its bucket slot."""
    idx = jnp.maximum(slot, 0)
    out = buckets[idx]
    dead = slot < 0
    return jnp.where(jnp.reshape(dead, dead.shape + (1,) * (out.ndim - 1)), fill, out)


# ---------------------------------------------------------------------------
# the distributed apply
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DistConfig:
    axis: str = "data"          # mesh axis carrying the storage nodes
    strategy: str = "bucket_a2a"  # or "allgather"
    bucket_cap: int = 64          # per-(source,target) queue bound
    max_scan_results: int = 8
    # power-of-two-choices read spreading over chain replicas
    # (routing.route_load_aware; the repro.cluster adaptive read path).
    # Changes the apply signature: (store, directory, load_reg, q, rng)
    #   -> (store, responses, directory', load_reg', metrics)
    read_spread: bool = False
    # include the routing decision (ridx/target/chain/chain_len, sharded)
    # in the metrics dict so a caller can build DES hop plans and advance
    # the replication version registers without re-routing
    return_decision: bool = False
    # consistency mode over the replica chains (repro.replication).  The
    # write path already broadcasts along the whole chain (the r_max
    # sequential all_to_all rounds of Fig 9a — literal chain replication);
    # "craq" additionally threads the (S, r_max) dirty table into the
    # in-mesh routing: the apply signature gains a replicated ``dirty``
    # input after load_reg, reads whose p2c pick is dirty are served by
    # the chain tail, and metrics carry the sharded picked/bounced
    # vectors.  "chain" needs no dist-side change (tail reads == the
    # read_spread=False path); "eventual" is the unchanged default.
    replication_mode: str = "eventual"
    # admission-queue penalty (repro.overload): the spread/craq apply
    # signatures gain a replicated (N,) ``queue_pen`` input after
    # load_reg, added to the load registers in the p2c comparison only
    # (routing.route_load_aware queue_pen — raw registers still bump),
    # so deep-queued nodes shed read traffic in-mesh too.  Ignored for
    # the deterministic tail-read path.
    queue_pen: bool = False


def _local_slab(store: StoreState):
    return store.keys[0], store.values[0]


def _make_bucket_plane(cfg: DistConfig, n_shards: int):
    """The per-device ``bucket_a2a`` data plane, shared verbatim by the
    per-epoch apply (:func:`make_dist_apply`) and the fused period program
    (:func:`make_dist_period`) so the two are the same dataflow: route the
    local batch slice (psum-delta keeps counters/load registers globally
    consistent), one read all_to_all round, ``r_max`` sequential write
    rounds along the chain (Fig 9a), local slab mutation.

    Returns ``plane(store, directory, q_local, load_reg, rng, dirty,
    queue_pen) -> (store', resp, directory', load_reg', decision, picked,
    bounced, bucket_overflow, counts, xcounts)``, ``counts`` this
    device's :class:`~repro.core.store.ApplyCounts` over its read and
    write rounds and ``xcounts`` its :class:`ExchangeCounts`;
    ``load_reg``/``rng``/``dirty``/``queue_pen`` ride through untouched
    on the paths that ignore them.
    """
    axis = cfg.axis
    spread = cfg.read_spread
    craq = cfg.replication_mode == "craq"

    def plane(store: StoreState, directory: Directory, q: R.QueryBatch,
              load_reg, rng, dirty, queue_pen):
        me = jax.lax.axis_index(axis)
        slab_keys, slab_vals = _local_slab(store)
        picked = bounced = None
        with jax.named_scope(S.ROUTE):
            base_dir = directory
            if craq:
                base_load = load_reg
                decision, directory, load_reg, picked, bounced = (
                    R.route_load_aware_dirty(
                        directory, q, load_reg, dirty, jax.random.fold_in(rng, me),
                        queue_pen=queue_pen,
                    )
                )
                load_reg = base_load + jax.lax.psum(load_reg - base_load, axis)
            elif spread:
                base_load = load_reg
                # distinct draws per device (each routes its own batch slice)
                decision, directory, load_reg = R.route_load_aware(
                    directory, q, load_reg, jax.random.fold_in(rng, me),
                    queue_pen=queue_pen,
                )
                load_reg = base_load + jax.lax.psum(load_reg - base_load, axis)
            else:
                decision, directory = R.route(directory, q)
            # counters were bumped from the *local* slice only; make the
            # statistics registers globally consistent (replicated out_spec)
            directory = dataclasses.replace(
                directory,
                read_count=base_dir.read_count
                + jax.lax.psum(directory.read_count - base_dir.read_count, axis),
                write_count=base_dir.write_count
                + jax.lax.psum(directory.write_count - base_dir.write_count, axis),
            )
        with jax.named_scope(S.APPLY):
            new_store, resp, bucket_ovf, counts, xcounts = _apply(
                store, q, decision, slab_keys, slab_vals)
        return (new_store, resp, directory, load_reg, decision, picked,
                bounced, bucket_ovf, counts, xcounts)

    @contextlib.contextmanager
    def exchange(part: str):
        with jax.named_scope(S.EXCHANGE), jax.named_scope(part):
            yield

    def _apply(store, q, decision, slab_keys, slab_vals):
        """The read round, the ``r_max`` write rounds and the local slab
        mutations: ``(store', resp, bucket_overflow, counts, xcounts)``."""
        me = jax.lax.axis_index(axis)
        is_write = (q.opcode == K.OP_PUT) | (q.opcode == K.OP_DEL)
        cap = cfg.bucket_cap
        n_slots = n_shards * cap

        # --- reads: one a2a round to the tail, replies via inverse a2a ---
        with exchange(READ_ROUND):
            read_target = jnp.where(
                ~is_write & (q.key != K.EMPTY_KEY), decision.target, DROP
            )
            slot, ovf_r = bucketize(read_target, n_shards, cap)
            rounds = [_round_counts(slot, me, n_shards, cap)]
            bkeys = scatter_to_buckets(slot, q.key, n_slots, K.EMPTY_KEY)
            bop = scatter_to_buckets(slot, q.opcode, n_slots,
                                     jnp.int32(K.OP_GET))
            bend = scatter_to_buckets(slot, q.end_key, n_slots, jnp.uint32(0))
            bkeys, bop, bend = (_a2a(x, axis, n_shards)
                                for x in (bkeys, bop, bend))

        inbound = R.QueryBatch(
            opcode=bop, key=bkeys, end_key=bend,
            value=jnp.zeros((n_slots, q.value.shape[1]), q.value.dtype),
        )
        read_mine = (inbound.opcode == K.OP_GET) | (inbound.opcode == K.OP_SCAN)
        read_mine &= inbound.key != K.EMPTY_KEY
        slab_keys, slab_vals, _, resp_in, counts = shard_apply(
            slab_keys, slab_vals, inbound, read_mine,
            jnp.zeros_like(read_mine),  # no writes in the read round
            max_scan_results=cfg.max_scan_results,
        )
        with exchange(READ_ROUND):
            back = _return_replies(resp_in, axis, n_shards)
            resp = Responses(
                value=gather_from_buckets(slot, back.value, 0.0),
                found=gather_from_buckets(slot, back.found, False),
                scan_values=gather_from_buckets(slot, back.scan_values, 0.0),
                scan_keys=gather_from_buckets(slot, back.scan_keys,
                                              K.EMPTY_KEY),
                scan_count=gather_from_buckets(slot, back.scan_count,
                                               jnp.int32(0)),
            )

        # --- writes: r sequential a2a rounds along the chain (Fig 9a) ---
        ovf_w = jnp.zeros((), ovf_r.dtype)
        r_max = decision.chain.shape[1]
        for pos in range(r_max):
            with exchange(WRITE_ROUND):
                live = (is_write & (pos < decision.chain_len)
                        & (q.key != K.EMPTY_KEY))
                wt = jnp.where(live, decision.chain[:, pos], DROP)
                wslot, ovf = bucketize(wt, n_shards, cap)
                ovf_w += ovf
                rounds.append(_round_counts(wslot, me, n_shards, cap))
                wkeys = scatter_to_buckets(wslot, q.key, n_slots, K.EMPTY_KEY)
                wop = scatter_to_buckets(wslot, q.opcode, n_slots,
                                         jnp.int32(K.OP_GET))
                wval = scatter_to_buckets(wslot, q.value, n_slots, 0.0)
                wkeys, wop, wval = (_a2a(x, axis, n_shards)
                                    for x in (wkeys, wop, wval))
            wq = R.QueryBatch(
                opcode=wop, key=wkeys, end_key=jnp.zeros_like(wkeys), value=wval
            )
            write_mine = ((wq.opcode == K.OP_PUT) | (wq.opcode == K.OP_DEL)) & (
                wq.key != K.EMPTY_KEY
            )
            slab_keys, slab_vals, dropped, wresp, wcounts = shard_apply(
                slab_keys, slab_vals, wq, jnp.zeros_like(write_mine), write_mine,
                max_scan_results=1,
            )
            counts = jax.tree.map(jnp.add, counts, wcounts)
            if pos == 0:
                put_dropped = dropped
            else:
                put_dropped = put_dropped + dropped
            # tail replies: DEL found flag returns from the last chain pos
            with exchange(WRITE_ROUND):
                wback = _a2a(wresp.found, axis, n_shards)
                at_tail = is_write & (pos == decision.chain_len - 1)
                got = gather_from_buckets(wslot, wback, False)
            resp = dataclasses.replace(
                resp, found=jnp.where(at_tail, got, resp.found)
            )

        new_store = StoreState(
            keys=slab_keys[None], values=slab_vals[None],
            overflow=store.overflow + put_dropped,
        )
        xcounts = ExchangeCounts(*(jnp.stack(c) for c in zip(*rounds)))
        return (new_store, resp, (ovf_r + ovf_w).astype(jnp.int32), counts,
                xcounts)

    return plane


def _a2a(x: jnp.ndarray, axis: str, n: int) -> jnp.ndarray:
    """(n, cap, ...) buckets -> transposed across the mesh axis."""
    return jax.lax.all_to_all(x, axis, split_axis=0, concat_axis=0, tiled=True)


def _return_replies(resp: Responses, axis: str, n: int) -> Responses:
    """The read round's replies, still in bucket slots, back to the
    devices that sent the queries: the inverse all_to_all."""
    return jax.tree.map(lambda x: _a2a(x, axis, n), resp)


def make_dist_apply(mesh, directory_template: Directory, cfg: DistConfig):
    """Build the jitted distributed batch-apply.

    Signature of the returned fn:
      (store_sharded, directory_replicated, q_sharded)
        -> (store, responses_sharded, directory', metrics)

    With ``cfg.read_spread`` (load-aware p2c reads, ``repro.cluster``):
      (store, directory, load_reg, q, rng)
        -> (store, responses, directory', load_reg', metrics)
    where ``load_reg`` is the replicated (N,) node load register and the
    same psum-delta trick used for the statistics counters keeps it
    globally consistent.  ``cfg.return_decision`` adds the sharded routing
    decision (target/chain/chain_len) to ``metrics`` so the caller can
    build DES hop plans without routing a second time.  ``metrics`` also
    holds under ``counts`` the batch's count parts, combined over the
    devices: ``(ApplyCounts,)``, and under ``bucket_a2a``
    ``(ApplyCounts, ExchangeCounts)``.
    """
    n_shards = mesh.shape[cfg.axis]
    axis = cfg.axis
    spread = cfg.read_spread
    craq = cfg.replication_mode == "craq"
    if cfg.replication_mode not in ("eventual", "chain", "craq"):
        raise ValueError(
            f"unknown replication_mode {cfg.replication_mode!r}"
        )
    if craq and not spread:
        raise ValueError("replication_mode='craq' needs read_spread=True "
                         "(apportioned reads are the protocol)")
    bucket_plane = _make_bucket_plane(cfg, n_shards)

    def per_device(store: StoreState, directory: Directory, q: R.QueryBatch,
                   load_reg=None, rng=None, dirty=None, queue_pen=None):
        me = jax.lax.axis_index(axis)
        slab_keys, slab_vals = _local_slab(store)
        picked = bounced = None

        if cfg.strategy == "allgather":
            gq = jax.tree.map(lambda x: _ag(x, axis), q)
            if craq:
                # identical rng on every device -> identical global decision
                decision, directory, load_reg, picked, bounced = (
                    R.route_load_aware_dirty(directory, gq, load_reg, dirty,
                                             rng, queue_pen=queue_pen)
                )
            elif spread:
                decision, directory, load_reg = R.route_load_aware(
                    directory, gq, load_reg, rng, queue_pen=queue_pen
                )
            else:
                decision, directory = R.route(directory, gq)
            new_keys, new_vals, dropped, resp, counts = _apply_full(
                slab_keys, slab_vals, gq, decision, me, cfg.max_scan_results
            )
            # each read answered by exactly one shard -> psum combines
            resp = jax.tree.map(lambda x: jax.lax.psum(_mask_resp(x), axis), resp)
            resp = Responses(
                value=resp.value,
                found=resp.found > 0,
                scan_values=resp.scan_values,
                scan_keys=resp.scan_keys.astype(jnp.uint32),
                scan_count=resp.scan_count.astype(jnp.int32),
            )
            # return this device's slice of the replies
            Bl = q.opcode.shape[0]
            resp = jax.tree.map(
                lambda x: jax.lax.dynamic_slice_in_dim(x, me * Bl, Bl, axis=0), resp
            )
            overflow = jnp.zeros((), jnp.int32)
            new_store = StoreState(
                keys=new_keys[None], values=new_vals[None], overflow=store.overflow + dropped
            )
            metrics = {
                "bucket_overflow": overflow,
                "a2a_rounds": jnp.zeros((), jnp.int32),
                "counts": (jax.lax.psum(counts, axis),),
            }
            if cfg.return_decision:
                metrics.update(_slice_decision(decision, me, q.opcode.shape[0]))
                if craq:
                    Bl = q.opcode.shape[0]
                    sl = lambda x: jax.lax.dynamic_slice_in_dim(
                        x, me * Bl, Bl, axis=0
                    )
                    metrics["picked"] = sl(picked)
                    metrics["bounced"] = sl(bounced)
            # counters were bumped identically everywhere; keep one copy
            if spread:
                return new_store, resp, directory, load_reg, metrics
            return new_store, resp, directory, metrics

        # ---- bucket_a2a (the shared per-device data plane) ----
        (new_store, resp, directory, load_reg, decision, picked, bounced,
         bucket_ovf, counts, xcounts) = bucket_plane(
            store, directory, q, load_reg, rng, dirty, queue_pen
        )
        metrics = {
            "bucket_overflow": bucket_ovf,
            "a2a_rounds": jnp.int32(1 + decision.chain.shape[1]),
            "counts": (jax.lax.psum(counts, axis),
                       _combine_exchange_counts(xcounts, axis)),
        }
        if cfg.return_decision:
            metrics.update({
                "ridx": decision.ridx,
                "target": decision.target,
                "chain": decision.chain,
                "chain_len": decision.chain_len,
            })
            if craq:
                metrics["picked"] = picked
                metrics["bounced"] = bounced
        if spread:
            return new_store, resp, directory, load_reg, metrics
        return new_store, resp, directory, metrics

    def _slice_decision(decision, me, Bl):
        sl = lambda x: jax.lax.dynamic_slice_in_dim(x, me * Bl, Bl, axis=0)
        return {
            "ridx": sl(decision.ridx),
            "target": sl(decision.target),
            "chain": sl(decision.chain),
            "chain_len": sl(decision.chain_len),
        }

    def _ag(x, ax):
        return jax.lax.all_gather(x, ax, axis=0, tiled=True)

    def _mask_resp(x):
        if x.dtype == jnp.uint32:  # scan_keys sentinel: use min so EMPTY loses
            return x
        return x.astype(jnp.float32) if x.dtype == jnp.bool_ else x

    def _apply_full(slab_keys, slab_vals, gq, decision, me, max_scan):
        is_write = (gq.opcode == K.OP_PUT) | (gq.opcode == K.OP_DEL)
        r_max = decision.chain.shape[1]
        member_live = jnp.arange(r_max)[None, :] < decision.chain_len[:, None]
        read_mine = (decision.target == me) & ~is_write
        write_mine = is_write & jnp.any((decision.chain == me) & member_live, axis=1)
        new_keys, new_vals, dropped, resp, counts = shard_apply(
            slab_keys, slab_vals, gq, read_mine, write_mine, max_scan_results=max_scan
        )
        # zero out non-owned replies so psum combines cleanly; keys use min
        owner = read_mine
        resp = Responses(
            value=jnp.where(owner[:, None], resp.value, 0.0),
            found=jnp.where(owner, resp.found, False),
            scan_values=jnp.where(owner[:, None, None], resp.scan_values, 0.0),
            scan_keys=jnp.where(owner[:, None], resp.scan_keys, 0).astype(jnp.uint32),
            scan_count=jnp.where(owner, resp.scan_count, 0),
        )
        return new_keys, new_vals, dropped, resp, counts

    store_spec = StoreState(keys=P(axis), values=P(axis), overflow=P(axis))
    dir_spec = jax.tree.map(lambda _: P(), directory_template)
    q_spec = R.QueryBatch(opcode=P(axis), key=P(axis), end_key=P(axis), value=P(axis))
    resp_spec = Responses(
        value=P(axis), found=P(axis), scan_values=P(axis),
        scan_keys=P(axis), scan_count=P(axis),
    )
    metric_spec = {"bucket_overflow": P(), "a2a_rounds": P(), "counts": P()}
    if cfg.return_decision:
        metric_spec.update({"ridx": P(axis), "target": P(axis),
                            "chain": P(axis), "chain_len": P(axis)})
        if craq:
            metric_spec.update({"picked": P(axis), "bounced": P(axis)})

    if craq:
        if cfg.queue_pen:
            def entry(store, directory, load_reg, qpen, dirty, q, rng):
                return per_device(store, directory, q, load_reg, rng, dirty,
                                  qpen)

            in_specs = (store_spec, dir_spec, P(), P(), P(), q_spec, P())
        else:
            def entry(store, directory, load_reg, dirty, q, rng):
                return per_device(store, directory, q, load_reg, rng, dirty)

            in_specs = (store_spec, dir_spec, P(), P(), q_spec, P())
        out_specs = (store_spec, resp_spec, dir_spec, P(), metric_spec)
    elif spread:
        if cfg.queue_pen:
            def entry(store, directory, load_reg, qpen, q, rng):
                return per_device(store, directory, q, load_reg, rng, None,
                                  qpen)

            in_specs = (store_spec, dir_spec, P(), P(), q_spec, P())
        else:
            def entry(store, directory, load_reg, q, rng):
                return per_device(store, directory, q, load_reg, rng)

            in_specs = (store_spec, dir_spec, P(), q_spec, P())
        out_specs = (store_spec, resp_spec, dir_spec, P(), metric_spec)
    else:
        def entry(store, directory, q):
            return per_device(store, directory, q)

        in_specs = (store_spec, dir_spec, q_spec)
        out_specs = (store_spec, resp_spec, dir_spec, metric_spec)

    fn = _shard_map(entry, mesh, in_specs, out_specs)
    return jax.jit(fn)


def make_dist_period(mesh, directory_template: Directory, cfg: DistConfig,
                     *, pre, observe, fold_ovl: bool, on_trace=None):
    """Build the whole-period dist program: ONE shard_map whose per-device
    body runs a ``lax.scan`` over the period's epochs, each scan step
    executing the bounded-bucket a2a data plane on the local batch slice
    and then the *replicated* observe stage on the all_gathered decision.

    The observe stage (per-node op counts, the count-min sketch, the
    overload admission step, DES hop planning, replication-register
    advance, span sampling) is global-batch-order dependent — admission
    ranks and span slots are cumsums over the whole batch — so it cannot
    run shard-local.  Gathering the per-epoch decision (a few (B,) int
    vectors) and recomputing it identically on every device keeps it
    bit-identical to the per-epoch path's host-level observe at the cost
    of one tiled all_gather per epoch.

    ``pre(repl, ovl) -> (dirty, queue_pen)`` derives the routing inputs
    from the carried state exactly as the per-epoch driver does between
    steps; ``observe(q, ridx, target, chain, chain_len, sketch, r_plan,
    repl, picked, bounced, ovl, r_ovl, eid, coord, metrics) -> (sketch,
    plan, node_ops, repl, ovl, coord, metrics, ostats, cstats, spans)``
    is the per-epoch observe body verbatim (``coord`` the replicated
    coordination-tier carry, ``metrics`` the replicated fleet metrics
    ring — each an empty pytree / None when its plane is off).
    ``fold_ovl`` mirrors the driver's overload-rng fold (a fold_in, not
    a wider split, so the disabled path's rng streams are untouched).
    ``on_trace(qs, rngs, live, eids)``, if given, is called once per
    trace with the staged inputs (their shapes; it runs at trace time).

    Signature of the returned jitted fn (donated like the oracle period
    scan — store slabs, load/sketch/repl/overload registers, the
    coordination tier's switch tables and the metrics ring; the
    directory is NOT donated, see ``EpochDriver._build_oracle_period``):

      (store, directory, load_reg, sketch, repl, ovl, coord, metrics,
       qs, rngs, live, eids)
        -> (store, directory, load_reg, sketch, repl, ovl, coord, metrics,
            plans, node_ops, bucket_overflow, overflow_totals, bounced,
            ostats, cstats, spans, get_digests, (counts, xcounts))

    with ``get_digests`` the (P,) :func:`~repro.core.store.get_digest` of
    each epoch's GET replies, ``counts`` its
    :class:`~repro.core.store.ApplyCounts` and ``xcounts`` its
    :class:`ExchangeCounts`, all over all devices, ``qs`` the period's
    (P, B, ...) query pytree REPLICATED (each
    device slices its share for the data plane and keeps the whole batch
    for observe), ``live`` the (P,) real-epoch mask (dead padding epochs
    compute but do not commit), ``eids`` the (P,) absolute epoch ids.
    """
    n_shards = mesh.shape[cfg.axis]
    axis = cfg.axis
    spread = cfg.read_spread
    craq = cfg.replication_mode == "craq"
    plane = _make_bucket_plane(cfg, n_shards)
    if cfg.strategy != "bucket_a2a":
        raise ValueError(
            "make_dist_period fuses the bucket_a2a data plane only "
            f"(strategy={cfg.strategy!r}); use make_dist_apply per epoch"
        )

    def period_device(store, directory, load_reg, sketch, repl, ovl, coord,
                      metrics, qs, rngs, live, eids):
        if on_trace is not None:
            on_trace(qs, rngs, live, eids)
        me = jax.lax.axis_index(axis)

        def scan_body(carry, xs):
            (store, directory, load_reg, sketch, repl, ovl, coord,
             metrics) = carry
            q, rng, lv, eid = xs
            B = q.opcode.shape[0]
            Bl = B // n_shards
            # the same rng discipline as the per-epoch driver step
            r_ovl = jax.random.fold_in(rng, 0x0F10AD) if fold_ovl else rng
            r_route, r_plan = jax.random.split(rng)
            with jax.named_scope(S.ROUTE):
                dirty, queue_pen = pre(repl, ovl)
            q_local = jax.tree.map(
                lambda x: jax.lax.dynamic_slice_in_dim(x, me * Bl, Bl, 0), q
            )
            (store2, resp, directory2, load_reg2, decision, picked,
             bounced, bucket_ovf, counts, xcounts) = plane(
                store, directory, q_local, load_reg, r_route, dirty,
                queue_pen,
            )
            with jax.named_scope(S.APPLY), jax.named_scope(S.GET):
                # the slices' digests add up to the whole batch's
                digest = jax.lax.psum(S.get_digest(
                    q_local.opcode, q_local.key, resp.value, resp.found,
                    base=me * Bl), axis)
            counts = (jax.lax.psum(counts, axis),
                      _combine_exchange_counts(xcounts, axis))
            # reconstruct the global decision for the replicated observe
            with jax.named_scope(S.OBSERVE):
                ag = lambda x: jax.lax.all_gather(x, axis, axis=0, tiled=True)
                ridx, target = ag(decision.ridx), ag(decision.target)
                chain, clen = ag(decision.chain), ag(decision.chain_len)
                if craq:
                    picked_g, bounced_g = ag(picked), ag(bounced)
                else:
                    # placeholders keep observe's signature mode-independent
                    # (exactly the per-epoch step's substitution)
                    picked_g = target
                    bounced_g = jnp.zeros((B,), jnp.bool_)
            (sketch2, plan, node_ops, repl2, ovl2, coord2, metrics2,
             ostats, cstats, spans) = observe(
                q, ridx, target, chain, clen, sketch, r_plan, repl,
                picked_g, bounced_g, ovl, r_ovl, eid, coord, metrics,
            )
            if not spread:
                # tail-read path: registers tracked for parity (same units)
                with jax.named_scope(S.APPLY):
                    load_reg2 = load_reg2 + node_ops.astype(jnp.uint32)
            with jax.named_scope(S.COMMIT):
                keep = lambda new, old: jnp.where(lv, new, old)
                store2 = jax.tree.map(keep, store2, store)
                carry2 = (store2, jax.tree.map(keep, directory2, directory),
                          keep(load_reg2, load_reg), keep(sketch2, sketch),
                          jax.tree.map(keep, repl2, repl),
                          jax.tree.map(keep, ovl2, ovl),
                          jax.tree.map(keep, coord2, coord),
                          jax.tree.map(keep, metrics2, metrics))
                # global overflow total (the store is sharded, one node
                # per device — psum of the local sum is
                # jnp.sum(store.overflow))
                ovf = jax.lax.psum(jnp.sum(store2.overflow), axis)
            return carry2, (plan, node_ops, bucket_ovf, ovf, bounced_g,
                            ostats, cstats, spans, digest, counts)

        carry, outs = jax.lax.scan(
            scan_body,
            (store, directory, load_reg, sketch, repl, ovl, coord,
             metrics),
            (qs, rngs, live, eids),
        )
        return (*carry, *outs)

    store_spec = StoreState(keys=P(axis), values=P(axis), overflow=P(axis))
    # everything except the store is replicated state: the directory and
    # registers scan like the single-host donated buffers, the staged
    # queries stay whole on every device (the observe stage needs the
    # full batch; the data plane slices its share by axis index)
    in_specs = (store_spec, P(), P(), P(), P(), P(), P(), P(), P(), P(),
                P(), P())
    out_specs = (store_spec, P(), P(), P(), P(), P(), P(), P(),
                 P(), P(), P(), P(), P(), P(), P(), P(), P(), P())
    fn = _shard_map(period_device, mesh, in_specs, out_specs)
    return jax.jit(fn, donate_argnums=(0, 2, 3, 4, 5, 6, 7))


def make_mesh(shape: tuple[int, ...],
              axis_names: tuple[str, ...] = ("data",), *, devices=None):
    """The one mesh builder of the repo (dist store, benches, tests),
    over the first devices of ``devices`` (default ``jax.devices()``).

    Axes are ``AxisType.Auto``: the data plane partitions by hand inside
    ``shard_map`` and keeps the control state replicated, and under JAX's
    default ``Explicit`` axes the replicated ops on sharding-typed values
    (``_node_ops``' scatter, the per-epoch step's slicing) are rejected."""
    devices = list(jax.devices() if devices is None else devices)
    n = 1
    for d in shape:
        n *= d
    if n > len(devices):
        raise ValueError(f"mesh {shape} needs {n} devices, have {len(devices)}")
    return jax.make_mesh(
        tuple(shape), tuple(axis_names),
        axis_types=(jax.sharding.AxisType.Auto,) * len(shape),
        devices=devices[:n],
    )


def _shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)
