"""The storage-node layer: a sorted-slab key-value store in pure JAX.

Paper §3/§4.1.1: each storage node runs LevelDB (range mode: keys sorted in
SSTs) or a hash table (hash mode) behind a thin shim that turns TurboKV
packets into store API calls.  The JAX-native stand-in (DESIGN.md §2) is a
**sorted slab**: each shard holds a fixed-capacity array of keys kept in
ascending order (``EMPTY_KEY = 0xFFFFFFFF`` padding at the tail) plus a
parallel value array.  Sorted order gives O(log C) batched lookups
(``searchsorted``), natural range scans, and static-shape insert/delete via
a searchsorted **rank merge** of the two already-sorted runs (the slab and
the deduped batch) — the moral equivalent of an SST memtable merge, at
O(C+B) gather work (plus O(B log) binary searches) instead of a full
O((C+B) log(C+B)) sort of the concatenation; the only scatter is the
B-row in-place overwrite of keys a PUT batch already holds.  The merge
reproduces the old
sort-and-truncate layout exactly on the live prefix (asserted in
``tests/test_store_merge.py``); dead tail slots now hold zeroed values
instead of stale garbage — a deliberate tightening.  The jnp oracle
(``apply_routed``), the ``shard_apply`` twin inside
``dist_store.make_dist_apply`` and the migration movers all share these
primitives, so oracle/dist parity stays bit-exact.

Batch semantics: GET/SCAN observe the *pre-batch* state; DELs apply next;
PUTs apply last (a PUT and DEL of the same key in one batch resolves to the
PUT).  Within the PUT set, the last write in batch order wins.  Queries in
one batch are independent YCSB ops, so this is the natural vectorization.

Capacity overflow (more live keys than ``capacity`` after a PUT batch) drops
the largest keys of the slab and reports a per-shard ``overflow`` count —
the controller reacts by splitting the hot sub-range and migrating half of
it (paper §4.1.1 "divided into two smaller sub-ranges").

What a batch cost and served is visible from outside: ``shard_apply``
counts the slab rows its delete and put branches rewrite and the PUT rows
it applies (:class:`ApplyCounts`), :func:`get_digest` folds a batch's GET
replies into one uint32, and the stages run under the named scopes of
:data:`PERIOD_SCOPES` / :data:`APPLY_SCOPES`, so a profile's device ops
map to them.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import keys as K
from repro.core.routing import QueryBatch, RoutingDecision

EMPTY = K.EMPTY_KEY

# Named scopes (``jax.named_scope``) of the period program, so that a
# profile's device ops map to the stage that issued them.  The top-level
# stages of one epoch, and the second-level scopes inside ``apply``:
# GET lookups and their reply pick, range scans, deletes, the PUT batch's
# last-write dedupe, the slab merge, and (on a mesh) the all-to-all
# rounds.  Readers import these tuples; the strings live only here.
ROUTE, APPLY, OBSERVE, COMMIT = "route", "apply", "observe", "commit"
PERIOD_SCOPES = (ROUTE, APPLY, OBSERVE, COMMIT)
GET, SCAN, DELETE, DEDUPE, MERGE, EXCHANGE = (
    "get", "scan", "delete", "dedupe", "merge", "exchange")
APPLY_SCOPES = (GET, SCAN, DELETE, DEDUPE, MERGE, EXCHANGE)


@partial(
    jax.tree_util.register_dataclass,
    data_fields=("keys", "values", "overflow"),
    meta_fields=(),
)
@dataclasses.dataclass(frozen=True)
class StoreState:
    """All shards' slabs, leading axis = storage node (shardable).

    keys:     (N, C) uint32, ascending per shard, EMPTY-padded
    values:   (N, C, V) float32
    overflow: (N,) int32 cumulative dropped-entry count (capacity pressure)
    """

    keys: jnp.ndarray
    values: jnp.ndarray
    overflow: jnp.ndarray

    @property
    def num_shards(self) -> int:
        return self.keys.shape[0]

    @property
    def capacity(self) -> int:
        return self.keys.shape[1]

    @property
    def value_dim(self) -> int:
        return self.values.shape[2]


@partial(
    jax.tree_util.register_dataclass,
    data_fields=("value", "found", "scan_values", "scan_keys", "scan_count"),
    meta_fields=(),
)
@dataclasses.dataclass(frozen=True)
class Responses:
    """Per-query replies (the payload of the node->client packet).

    value:       (B, V) GET result (zeros if miss)
    found:       (B,) bool GET/DEL hit
    scan_values: (B, S, V) SCAN results
    scan_keys:   (B, S) uint32 keys of SCAN results (EMPTY beyond count)
    scan_count:  (B,) int32 number of live SCAN results
    """

    value: jnp.ndarray
    found: jnp.ndarray
    scan_values: jnp.ndarray
    scan_keys: jnp.ndarray
    scan_count: jnp.ndarray


class ApplyCounts(NamedTuple):
    """What applying one batch cost the slabs, summed over the shards:
    ``slab_rows_rewritten`` counts the slab rows a delete or put branch
    rewrote (the whole capacity of each shard whose branch ran), and
    ``put_rows`` the PUT rows applied at chain members.  Both () int32."""

    slab_rows_rewritten: jnp.ndarray
    put_rows: jnp.ndarray


def make_store(num_shards: int, capacity: int, value_dim: int) -> StoreState:
    return StoreState(
        keys=jnp.full((num_shards, capacity), EMPTY, dtype=jnp.uint32),
        values=jnp.zeros((num_shards, capacity, value_dim), dtype=jnp.float32),
        overflow=jnp.zeros((num_shards,), dtype=jnp.int32),
    )


# ---------------------------------------------------------------------------
# per-shard slab primitives (operate on one (C,)/(C,V) slab)
# ---------------------------------------------------------------------------


_CUMSUM_BLOCK = 1024


def _cumsum(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive prefix sum of a 1-D integer array, in blocks of
    ``_CUMSUM_BLOCK``: a row-wise cumsum plus a cumsum of the row totals.
    XLA's TPU compiler spends ~30 s on one ``jnp.cumsum`` over a
    million-slot slab and about a second on this form; integer sums make
    the two bit-identical."""
    n = x.shape[0]
    if n <= _CUMSUM_BLOCK:
        return jnp.cumsum(x)
    m = -(-n // _CUMSUM_BLOCK) * _CUMSUM_BLOCK
    rows = jnp.cumsum(jnp.pad(x, (0, m - n)).reshape(-1, _CUMSUM_BLOCK), axis=1)
    tot = rows[:, -1]
    return (rows + (jnp.cumsum(tot) - tot)[:, None]).reshape(-1)[:n]


def _compact_sorted(keys: jnp.ndarray, vals: jnp.ndarray, live: jnp.ndarray):
    """Gather the ``live`` entries (a sorted-in-index-order subsequence) to
    a sorted prefix; EMPTY keys / zero values beyond.

    Scatter-free compaction: destination ``d`` pulls the (d+1)-th live
    index, found by a binary search over the inclusive liveness prefix sum
    — O(n log n) binary searches, no sort, no scatter.
    """
    n = keys.shape[0]
    cum = _cumsum(live.astype(jnp.int32))
    d = jnp.arange(n, dtype=jnp.int32)
    src = jnp.minimum(jnp.searchsorted(cum, d + 1, side="left"), n - 1)
    in_live = d < cum[-1]
    out_k = jnp.where(in_live, keys[src], EMPTY)
    out_v = jnp.where(in_live[:, None], vals[src], 0.0)
    return out_k, out_v


def _dedupe_last_write(qkeys: jnp.ndarray, qvals: jnp.ndarray):
    """Sort a PUT batch by key; last write in batch order wins.

    Returns (sorted_keys, sorted_vals) with duplicate keys' earlier writes
    dropped: live entries are a sorted prefix, EMPTY/zero beyond.
    """
    B = qkeys.shape[0]
    # one stable sort on the key (equal keys keep batch order, so a run's
    # last entry is the last write): a lexsort on (key, -index) takes the
    # TPU compiler minutes at 64K entries
    sk, perm = jax.lax.sort((qkeys, jnp.arange(B, dtype=jnp.int32)),
                            num_keys=1, is_stable=True)
    sv = qvals[perm]
    last = jnp.concatenate([sk[1:] != sk[:-1], jnp.ones((1,), bool)])
    sk = jnp.where(last, sk, EMPTY)
    return _compact_sorted(sk, sv, sk != EMPTY)


def _member_sorted(sorted_keys: jnp.ndarray, probe: jnp.ndarray) -> jnp.ndarray:
    """probe ∈ sorted_keys (EMPTY never matches)."""
    pos = jnp.searchsorted(sorted_keys, probe)
    pos = jnp.minimum(pos, sorted_keys.shape[0] - 1)
    return (sorted_keys[pos] == probe) & (probe != EMPTY)


def slab_get(slab_keys: jnp.ndarray, slab_vals: jnp.ndarray, qkeys: jnp.ndarray):
    """Batched point lookup. Returns (values (B,V), found (B,))."""
    pos = jnp.searchsorted(slab_keys, qkeys)
    pos = jnp.minimum(pos, slab_keys.shape[0] - 1)
    found = (slab_keys[pos] == qkeys) & (qkeys != EMPTY)
    vals = jnp.where(found[:, None], slab_vals[pos], 0.0)
    return vals, found


def pad_slab(slab_keys: jnp.ndarray, slab_vals: jnp.ndarray, max_results: int):
    """Append ``max_results`` EMPTY/zero entries so every scan's
    ``dynamic_slice`` stays in bounds.  Hoisted out of the per-query path:
    one pad covers the whole vmapped scan batch in
    :func:`_slab_scan_padded`."""
    pad_k = jnp.concatenate(
        [slab_keys, jnp.full((max_results,), EMPTY, slab_keys.dtype)]
    )
    pad_v = jnp.concatenate(
        [slab_vals, jnp.zeros((max_results, slab_vals.shape[1]), slab_vals.dtype)]
    )
    return pad_k, pad_v


def _slab_scan_padded(
    pad_k: jnp.ndarray,
    pad_v: jnp.ndarray,
    k0: jnp.ndarray,
    k1: jnp.ndarray,
    max_results: int,
):
    """Scan core over a pre-padded slab (see :func:`pad_slab`)."""
    C = pad_k.shape[0] - max_results
    live_keys = jax.lax.slice(pad_k, (0,), (C,))
    lo = jnp.searchsorted(live_keys, k0)                      # (B,)
    hi = jnp.searchsorted(live_keys, k1, side="right")
    count = jnp.minimum(hi - lo, max_results).astype(jnp.int32)

    def one(lo_i, cnt_i):
        ks = jax.lax.dynamic_slice(pad_k, (lo_i,), (max_results,))
        vs = jax.lax.dynamic_slice(pad_v, (lo_i, 0), (max_results, pad_v.shape[1]))
        live = jnp.arange(max_results) < cnt_i
        return jnp.where(live, ks, EMPTY), jnp.where(live[:, None], vs, 0.0)

    ks, vs = jax.vmap(one)(lo, count)
    return ks, vs, count


def slab_scan(
    slab_keys: jnp.ndarray,
    slab_vals: jnp.ndarray,
    k0: jnp.ndarray,
    k1: jnp.ndarray,
    max_results: int,
):
    """Batched range scan of [k0, k1] (inclusive), up to ``max_results`` each.

    Returns (keys (B,S), values (B,S,V), count (B,)).
    """
    pad_k, pad_v = pad_slab(slab_keys, slab_vals, max_results)
    return _slab_scan_padded(pad_k, pad_v, k0, k1, max_results)


def slab_delete(slab_keys: jnp.ndarray, slab_vals: jnp.ndarray, del_keys: jnp.ndarray):
    """Delete a key set (del_keys need not be sorted; EMPTY entries ignored).

    Hit entries become EMPTY holes and the survivors (already a sorted
    subsequence) are gather-compacted back to a sorted prefix — no re-sort
    of the slab, no scatter."""
    sorted_del = jnp.sort(del_keys)
    hit = _member_sorted(sorted_del, slab_keys)
    new_keys = jnp.where(hit, EMPTY, slab_keys)
    return _compact_sorted(new_keys, slab_vals, new_keys != EMPTY)


def in_range(slab_keys: jnp.ndarray, lo, hi) -> jnp.ndarray:
    """Live slab entries with key in [lo, hi]."""
    return (slab_keys >= lo) & (slab_keys <= hi) & (slab_keys != EMPTY)


def delete_range(slab_keys: jnp.ndarray, slab_vals: jnp.ndarray, lo, hi):
    """:func:`slab_delete` of every key in [lo, hi]: the hits are known
    without a sort of the deleted keys."""
    live = (slab_keys != EMPTY) & ~in_range(slab_keys, lo, hi)
    return _compact_sorted(jnp.where(live, slab_keys, EMPTY), slab_vals, live)


def _merge_sorted_runs(ak, av, bk, bv, out_len: int):
    """Gather-style stable merge of two sorted runs (EMPTY tails sink, run-a
    holes ahead of run-b holes, matching the old stable concat-argsort).

    ``searchsorted(a, b, 'right') + arange`` gives every b element's
    merged position — strictly increasing, so the *inverse* permutation
    needs no scatter: destination ``d`` binary-searches that position
    vector to learn how many b elements landed before it (and whether it
    is itself a b slot), then gathers from the right run.
    """
    B = bk.shape[0]
    C = ak.shape[0]
    idx_b = jnp.searchsorted(ak, bk, side="right") + jnp.arange(B, dtype=jnp.int32)
    d = jnp.arange(out_len, dtype=jnp.int32)
    cb = jnp.searchsorted(idx_b, d, side="left")       # b elements before d
    cb_c = jnp.minimum(cb, B - 1)
    from_b = idx_b[cb_c] == d
    ai = jnp.clip(d - cb, 0, C - 1)
    out_k = jnp.where(from_b, bk[cb_c], ak[ai])
    out_v = jnp.where(from_b[:, None], bv[cb_c], av[ai])
    return out_k, out_v


def slab_put(slab_keys: jnp.ndarray, slab_vals: jnp.ndarray, put_keys: jnp.ndarray, put_vals: jnp.ndarray):
    """Insert/overwrite a batch. Returns (keys, vals, dropped_count).

    The slab must be compact (live keys a sorted prefix, EMPTY tail), as
    every store operation leaves it.  Keys already in the slab take their
    new value in place (a B-row scatter); the new keys form a second
    sorted run, and a searchsorted rank merge (:func:`_merge_sorted_runs`)
    produces the combined sorted slab in O(C+B) gather work — no
    log-factor sort of the concatenation and no O(C) compaction of the
    slab.  Capacity overflow drops the largest keys and reports the
    dropped count.
    """
    pk, pv = _dedupe_last_write(put_keys, put_vals)
    return put_sorted(slab_keys, slab_vals, pk, pv)


def put_sorted(slab_keys: jnp.ndarray, slab_vals: jnp.ndarray,
               pk: jnp.ndarray, pv: jnp.ndarray):
    """:func:`slab_put` of a batch that is already sorted with distinct
    keys, live keys a prefix (as :func:`_dedupe_last_write` or a
    range extracted from a slab leaves it): no sort."""
    C = slab_keys.shape[0]
    pos = jnp.minimum(jnp.searchsorted(slab_keys, pk), C - 1)
    hit = (slab_keys[pos] == pk) & (pk != EMPTY)
    slab_vals = slab_vals.at[jnp.where(hit, pos, C)].set(pv, mode="drop")
    nk, nv = _compact_sorted(jnp.where(hit, EMPTY, pk), pv, ~hit & (pk != EMPTY))
    # only the C smallest merged entries survive truncation: merge those
    out_keys, out_vals = _merge_sorted_runs(slab_keys, slab_vals, nk, nv, C)
    # dead tail slots hold zeros, whatever the slab's tail held
    out_vals = jnp.where((out_keys != EMPTY)[:, None], out_vals, 0.0)
    n_live = (jnp.sum((slab_keys != EMPTY).astype(jnp.int32))
              + jnp.sum((nk != EMPTY).astype(jnp.int32)))
    dropped = jnp.maximum(n_live - C, 0)
    return out_keys, out_vals, dropped


# ---------------------------------------------------------------------------
# the digest of a batch's GET replies
# ---------------------------------------------------------------------------

_MIX_INDEX = 0x9E3779B1
_MIX_FOUND = 0x85EBCA77
_MIX_WORD = 0xC2B2AE3D


def _fmix32(h, xp):
    """MurmurHash3's 32-bit finalizer on uint32 values."""
    h = h ^ (h >> 16)
    h = h * xp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * xp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def _digest(xp, opcode, keys, words, found, index):
    """Sum mod 2**32, over the GET rows, of a mix of (batch index, key,
    found, value words); every operand uint32 but ``opcode``/``found``."""
    col = np.arange(1, 2 * words.shape[1], 2, dtype=np.uint32) * np.uint32(
        _MIX_WORD)
    vsum = xp.sum(words * col, axis=1, dtype=xp.uint32)
    h = _fmix32(index * xp.uint32(_MIX_INDEX) + keys, xp)
    h = _fmix32((h ^ found.astype(xp.uint32) * xp.uint32(_MIX_FOUND)) + vsum,
                xp)
    return xp.sum(xp.where(opcode == K.OP_GET, h, xp.uint32(0)),
                  dtype=xp.uint32)


def get_digest(opcode: jnp.ndarray, keys: jnp.ndarray, value: jnp.ndarray,
               found: jnp.ndarray, base=0) -> jnp.ndarray:
    """() uint32 digest of a batch's GET replies: the sum mod 2**32, over
    the GETs, of a fixed mix of (batch index, key, found, the value's
    float32 bits).  ``base`` is the batch index of row 0, so the digests
    of a batch's slices add up to the whole batch's.  Twin:
    :func:`get_digest_np`."""
    index = (jnp.arange(keys.shape[0], dtype=jnp.uint32)
             + jnp.asarray(base).astype(jnp.uint32))
    words = jax.lax.bitcast_convert_type(value.astype(jnp.float32),
                                         jnp.uint32)
    return _digest(jnp, opcode, keys.astype(jnp.uint32), words, found, index)


def get_digest_np(opcode, keys, value, found) -> int:
    """:func:`get_digest` of a whole batch, in numpy."""
    keys = np.asarray(keys, np.uint32)
    words = np.ascontiguousarray(value, np.float32).view(np.uint32)
    index = np.arange(keys.shape[0], dtype=np.uint32)
    return int(_digest(np, np.asarray(opcode), keys, words,
                       np.asarray(found, bool), index))


# ---------------------------------------------------------------------------
# shard-level mixed-opcode batch application
# ---------------------------------------------------------------------------


def shard_apply(
    slab_keys: jnp.ndarray,
    slab_vals: jnp.ndarray,
    q: QueryBatch,
    read_mine: jnp.ndarray,
    write_mine: jnp.ndarray,
    *,
    max_scan_results: int,
):
    """Apply the batch slice owned by one shard.

    read_mine:  (B,) this shard serves the GET/SCAN (it is the chain tail)
    write_mine: (B,) this shard applies the PUT/DEL (it is a chain member)

    Returns ``(slab_keys, slab_vals, dropped, resp, counts)`` with
    ``counts`` this shard's :class:`ApplyCounts`.
    """
    is_get = (q.opcode == K.OP_GET) & read_mine
    is_scan = (q.opcode == K.OP_SCAN) & read_mine
    is_del = (q.opcode == K.OP_DEL) & write_mine
    is_put = (q.opcode == K.OP_PUT) & write_mine
    B, V = q.value.shape
    C = slab_keys.shape[0]
    rewrote = jnp.int32(C)
    untouched = jnp.zeros((), jnp.int32)

    # --- reads against pre-batch state ---
    with jax.named_scope(GET):
        get_vals, get_found = slab_get(slab_keys, slab_vals,
                                       jnp.where(is_get, q.key, EMPTY))

    # scans, deletes and puts cost O(C) per shard (slab pad copy,
    # compaction, merge): each is skipped when the batch holds none.  The
    # predicates read the whole batch, so they stay a cond also where a
    # caller vmaps over shards (a per-shard predicate would turn into a
    # select of both branches there).
    def scan(_):
        return slab_scan(
            slab_keys,
            slab_vals,
            jnp.where(is_scan, q.key, EMPTY),
            jnp.where(is_scan, q.end_key, jnp.zeros_like(q.end_key)),
            max_scan_results,
        )

    def no_scan(_):
        return (jnp.full((B, max_scan_results), EMPTY, slab_keys.dtype),
                jnp.zeros((B, max_scan_results, V), slab_vals.dtype),
                jnp.zeros((B,), jnp.int32))

    with jax.named_scope(SCAN):
        sk, sv, scount = jax.lax.cond(jnp.any(q.opcode == K.OP_SCAN), scan,
                                      no_scan, None)
        scount = jnp.where(is_scan, scount, 0)
        sk = jnp.where(is_scan[:, None], sk, EMPTY)
        sv = jnp.where(is_scan[:, None, None], sv, 0.0)

    # --- deletes ---
    with jax.named_scope(DELETE):
        del_keys = jnp.where(is_del, q.key, EMPTY)

        def delete(kv):
            # the hits are looked up in the branch: a batch with no DEL
            # pays no search of the slab for them
            return (*slab_delete(*kv, del_keys), rewrote,
                    _member_sorted(kv[0], del_keys))

        slab_keys, slab_vals, del_rows, del_found = jax.lax.cond(
            jnp.any(q.opcode == K.OP_DEL), delete,
            lambda kv: (*kv, untouched, jnp.zeros((B,), bool)),
            (slab_keys, slab_vals))

    # --- puts ---
    put_keys = jnp.where(is_put, q.key, EMPTY)
    put_vals = jnp.where(is_put[:, None], q.value, 0.0)

    def put(kv):
        with jax.named_scope(DEDUPE):
            pk, pv = _dedupe_last_write(put_keys, put_vals)
        with jax.named_scope(MERGE):
            return (*put_sorted(*kv, pk, pv), rewrote)

    slab_keys, slab_vals, dropped, put_rewrote = jax.lax.cond(
        jnp.any(q.opcode == K.OP_PUT), put,
        lambda kv: (*kv, jnp.zeros((), jnp.int32), untouched),
        (slab_keys, slab_vals))

    resp = Responses(
        value=get_vals,
        found=get_found | (del_found & is_del),
        scan_values=sv,
        scan_keys=sk,
        scan_count=scount,
    )
    counts = ApplyCounts(slab_rows_rewritten=del_rows + put_rewrote,
                         put_rows=jnp.sum(is_put.astype(jnp.int32)))
    return slab_keys, slab_vals, dropped, resp, counts


def apply_routed(
    store: StoreState,
    q: QueryBatch,
    decision: RoutingDecision,
    *,
    max_scan_results: int = 8,
) -> tuple[StoreState, Responses]:
    """Apply a routed batch to every shard (single-program simulation path).

    The distributed twin lives in ``repro.core.dist_store`` (shard_map); this
    form is bit-identical and is the oracle for it.  Reads are served
    by the routed target (the chain tail); writes are applied by every live
    chain member — the end state chain replication converges to (§4.1.2).
    """
    store, resp, _ = apply_routed_counted(
        store, q, decision, max_scan_results=max_scan_results)
    return store, resp


def apply_routed_counted(
    store: StoreState,
    q: QueryBatch,
    decision: RoutingDecision,
    *,
    max_scan_results: int = 8,
) -> tuple[StoreState, Responses, ApplyCounts]:
    """:func:`apply_routed`, also returning its :class:`ApplyCounts`
    summed over the shards."""
    N = store.num_shards
    is_write = (q.opcode == K.OP_PUT) | (q.opcode == K.OP_DEL)
    r_max = decision.chain.shape[1]
    member_live = jnp.arange(r_max)[None, :] < decision.chain_len[:, None]  # (B, r)

    shard_ids = jnp.arange(N, dtype=jnp.int32)

    def one_shard(slab_keys, slab_vals, shard_id):
        read_mine = (decision.target == shard_id) & ~is_write
        write_mine = is_write & jnp.any((decision.chain == shard_id) & member_live, axis=1)
        return shard_apply(
            slab_keys, slab_vals, q, read_mine, write_mine, max_scan_results=max_scan_results
        )

    # one shard at a time, not vmapped: a slab merge's temporaries are
    # O(capacity).  At 8 x 1M slots of 128-byte values and a 4,096-op
    # batch, a v5e compile's memory_analysis gives 17.31 GiB for the
    # vmapped form (over a 16 GB chip) and 4.53 GiB for this one
    new_keys, new_vals, dropped, resps, counts = jax.lax.map(
        lambda a: one_shard(*a), (store.keys, store.values, shard_ids))

    # combine per-shard responses: each read is answered by exactly one
    # shard, the routed target.  A gather, not a one-hot matmul: on a TPU
    # an f32 matmul at default precision rounds the values through bf16.
    # Unrouted queries (target NO_NODE) read as a miss, as before.
    with jax.named_scope(GET):
        routed = (decision.target >= 0) & (decision.target < N)
        owner = jnp.where(routed, decision.target, 0).astype(jnp.int32)
        b = jnp.arange(owner.shape[0])

        def pick(x):
            hit = routed.reshape(routed.shape + (1,) * (x.ndim - 2))
            return jnp.where(hit, x[owner, b], jnp.zeros((), x.dtype))

        value = pick(resps.value)
        found = pick(resps.found)
        scan_values = pick(resps.scan_values)
        scan_count = pick(resps.scan_count)
        scan_keys = jnp.take_along_axis(
            resps.scan_keys, decision.target[None, :, None].astype(jnp.int32), axis=0
        )[0]

    new_store = StoreState(
        keys=new_keys, values=new_vals, overflow=store.overflow + dropped
    )
    resp = Responses(
        value=value, found=found, scan_values=scan_values, scan_keys=scan_keys, scan_count=scan_count
    )
    return new_store, resp, jax.tree.map(jnp.sum, counts)


def store_fill(store: StoreState) -> jnp.ndarray:
    """(N,) live entries per shard (controller capacity signal)."""
    return jnp.sum((store.keys != EMPTY).astype(jnp.int32), axis=1)
