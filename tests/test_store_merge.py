"""The O(C+B) slab rank-merge vs the old sort-and-truncate oracle.

PR 4 replaced the full ``argsort`` of the ``capacity + B`` concatenation
in ``slab_put``/``slab_delete`` with a gather-style searchsorted rank
merge of the two already-sorted runs.  These tests pin the contract:

* live prefix (keys AND values) identical to the old argsort path;
* dead tail: EMPTY keys with **zeroed** values (a deliberate tightening —
  the old path left stale garbage values behind);
* overflow accounting identical;
* the migration movers (which share ``_compact_sorted``) round-trip;
* a PUT batch that inserts no new key skips the merge and still gives,
  bit for bit, what the merge path gives.
"""

import zlib

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.store import (
    EMPTY,
    _CUMSUM_BLOCK,
    _compact_sorted,
    _cumsum,
    _dedupe_last_write,
    _member_sorted,
    _merge_sorted_runs,
    delete_range,
    make_store,
    put_sorted,
    slab_delete,
    slab_get,
    slab_put,
)

# ---------------------------------------------------------------------------
# the pre-PR-4 implementations, kept verbatim as the semantic oracle
# ---------------------------------------------------------------------------


def _dedupe_ref(qkeys, qvals):
    B = qkeys.shape[0]
    perm = jnp.lexsort((-jnp.arange(B, dtype=jnp.int32), qkeys))
    sk, sv = qkeys[perm], qvals[perm]
    first = jnp.concatenate([jnp.ones((1,), bool), sk[1:] != sk[:-1]])
    sk = jnp.where(first, sk, EMPTY)
    p2 = jnp.argsort(sk)
    return sk[p2], sv[p2]


def slab_put_ref(slab_keys, slab_vals, put_keys, put_vals):
    C = slab_keys.shape[0]
    pk, pv = _dedupe_ref(put_keys, put_vals)
    overwritten = _member_sorted(pk, slab_keys)
    base_keys = jnp.where(overwritten, EMPTY, slab_keys)
    all_keys = jnp.concatenate([base_keys, pk])
    all_vals = jnp.concatenate([slab_vals, pv])
    perm = jnp.argsort(all_keys)
    all_keys, all_vals = all_keys[perm], all_vals[perm]
    live = jnp.sum((all_keys != EMPTY).astype(jnp.int32))
    return all_keys[:C], all_vals[:C], jnp.maximum(live - C, 0)


def slab_delete_ref(slab_keys, slab_vals, del_keys):
    sorted_del = jnp.sort(del_keys)
    hit = _member_sorted(sorted_del, slab_keys)
    new_keys = jnp.where(hit, EMPTY, slab_keys)
    perm = jnp.argsort(new_keys)
    return new_keys[perm], slab_vals[perm]


def _random_slab(rng, C, V, keyspace, fill=None):
    n_live = int(rng.integers(0, C + 1)) if fill is None else fill
    n_live = min(n_live, keyspace)
    keys = np.full(C, EMPTY, np.uint32)
    keys[:n_live] = np.sort(
        rng.choice(keyspace, size=n_live, replace=False).astype(np.uint32)
    )
    vals = rng.normal(size=(C, V)).astype(np.float32)
    # zero values on the EMPTY tail, as make_store and every store
    # operation leave a slab (a PUT batch that inserts nothing relies on it)
    vals[n_live:] = 0.0
    return keys, vals


def _check_put(sk, sv, pkeys, pvals):
    got = slab_put(jnp.asarray(sk), jnp.asarray(sv),
                   jnp.asarray(pkeys), jnp.asarray(pvals))
    ref = slab_put_ref(jnp.asarray(sk), jnp.asarray(sv),
                       jnp.asarray(pkeys), jnp.asarray(pvals))
    gk, gv, gd = map(np.asarray, got)
    rk, rv, rd = map(np.asarray, ref)
    nl = int((rk != EMPTY).sum())
    assert np.array_equal(gk, rk)
    assert np.array_equal(gv[:nl], rv[:nl])
    assert (gv[nl:] == 0).all()          # tightened: no stale tail values
    assert int(gd) == int(rd)


def _check_delete(sk, sv, dkeys):
    got = slab_delete(jnp.asarray(sk), jnp.asarray(sv), jnp.asarray(dkeys))
    ref = slab_delete_ref(jnp.asarray(sk), jnp.asarray(sv), jnp.asarray(dkeys))
    gk, gv = map(np.asarray, got)
    rk, rv = map(np.asarray, ref)
    nl = int((rk != EMPTY).sum())
    assert np.array_equal(gk, rk)
    assert np.array_equal(gv[:nl], rv[:nl])
    assert (gv[nl:] == 0).all()


def test_slab_put_matches_argsort_oracle_randomized():
    rng = np.random.default_rng(0)
    C, B, V = 48, 32, 3
    for _ in range(60):
        keyspace = int(rng.integers(40, 200))
        sk, sv = _random_slab(rng, C, V, keyspace)
        pkeys = rng.integers(0, keyspace, B).astype(np.uint32)
        pkeys[rng.random(B) < 0.15] = EMPTY    # masked batch slots
        pvals = rng.normal(size=(B, V)).astype(np.float32)
        _check_put(sk, sv, pkeys, pvals)


def test_slab_put_overflow_drops_largest_keys():
    rng = np.random.default_rng(1)
    C, B, V = 16, 16, 2
    sk, sv = _random_slab(rng, C, V, keyspace=1000, fill=C)  # slab full
    pkeys = (2000 + np.arange(B) * 3).astype(np.uint32)      # all fresh
    pvals = rng.normal(size=(B, V)).astype(np.float32)
    _check_put(sk, sv, pkeys, pvals)
    k, v, d = slab_put(jnp.asarray(sk), jnp.asarray(sv),
                       jnp.asarray(pkeys), jnp.asarray(pvals))
    assert int(d) == B                          # C live + B fresh - C kept
    assert (np.asarray(k) != EMPTY).all()
    assert (np.diff(np.asarray(k).astype(np.int64)) > 0).all()  # sorted


def test_slab_put_duplicate_batch_last_write_wins():
    sk = np.full(8, EMPTY, np.uint32)
    sv = np.zeros((8, 2), np.float32)
    pkeys = np.array([5, 5, 5, 9], np.uint32)
    pvals = np.arange(8, dtype=np.float32).reshape(4, 2)
    _check_put(sk, sv, pkeys, pvals)
    k, v, _ = slab_put(jnp.asarray(sk), jnp.asarray(sv),
                       jnp.asarray(pkeys), jnp.asarray(pvals))
    vals, found = slab_get(k, v, jnp.asarray([5, 9], jnp.uint32))
    assert bool(found.all())
    np.testing.assert_array_equal(np.asarray(vals), [[4.0, 5.0], [6.0, 7.0]])


def test_slab_put_empty_and_degenerate_batches():
    rng = np.random.default_rng(2)
    C, V = 12, 2
    sk, sv = _random_slab(rng, C, V, keyspace=50, fill=6)
    # all-EMPTY batch is an identity on the live prefix
    pkeys = np.full(8, EMPTY, np.uint32)
    pvals = np.zeros((8, V), np.float32)
    _check_put(sk, sv, pkeys, pvals)
    # pure overwrite batch (every key already resident)
    live = sk[sk != EMPTY][:4]
    pk2 = np.concatenate([live, np.full(4, EMPTY, np.uint32)])
    _check_put(sk, sv, pk2, rng.normal(size=(8, V)).astype(np.float32))
    # empty slab
    empty_k = np.full(C, EMPTY, np.uint32)
    _check_put(empty_k, np.zeros((C, V), np.float32),
               np.array([3, 1, 2, EMPTY], np.uint32),
               rng.normal(size=(4, V)).astype(np.float32))


def test_slab_delete_matches_argsort_oracle_randomized():
    rng = np.random.default_rng(3)
    C, B, V = 40, 24, 2
    for _ in range(60):
        keyspace = int(rng.integers(30, 150))
        sk, sv = _random_slab(rng, C, V, keyspace)
        dkeys = rng.integers(0, keyspace, B).astype(np.uint32)
        dkeys[rng.random(B) < 0.2] = EMPTY
        _check_delete(sk, sv, dkeys)


def test_compact_sorted_prefix_and_zero_tail():
    keys = np.array([2, 5, 7, 11, 13], np.uint32)
    vals = np.arange(10, dtype=np.float32).reshape(5, 2)
    live = np.array([True, False, True, False, True])
    k, v = _compact_sorted(jnp.asarray(keys), jnp.asarray(vals),
                           jnp.asarray(live))
    np.testing.assert_array_equal(np.asarray(k),
                                  [2, 7, 13, EMPTY, EMPTY])
    np.testing.assert_array_equal(np.asarray(v)[:3],
                                  [[0, 1], [4, 5], [8, 9]])
    assert (np.asarray(v)[3:] == 0).all()


@pytest.mark.parametrize("n", [
    1, _CUMSUM_BLOCK, _CUMSUM_BLOCK + 1, 3 * _CUMSUM_BLOCK, 5000,
])
def test_blocked_cumsum_matches_cumsum(n):
    x = np.random.default_rng(n).integers(0, 3, n).astype(np.int32)
    got = _cumsum(jnp.asarray(x))
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.cumsum(x))
    # under lax.map, as apply_routed runs the slab primitives per shard
    xs = np.stack([x, x[::-1]])
    np.testing.assert_array_equal(np.asarray(jax.lax.map(_cumsum, xs)),
                                  np.cumsum(xs, axis=1))


def test_dedupe_last_write_zeroes_dead_slots():
    pk, pv = _dedupe_last_write(
        jnp.asarray([7, 3, 7, EMPTY], jnp.uint32),
        jnp.arange(8, dtype=jnp.float32).reshape(4, 2),
    )
    np.testing.assert_array_equal(np.asarray(pk), [3, 7, EMPTY, EMPTY])
    np.testing.assert_array_equal(np.asarray(pv)[:2], [[2, 3], [4, 5]])
    assert (np.asarray(pv)[2:] == 0).all()


def test_migration_roundtrip_on_rank_merge():
    """move + reclaim still round-trip exactly on the new merge."""
    from repro.core.migration import MigrationOp, execute
    from repro.core.store import store_fill

    rng = np.random.default_rng(4)
    store = make_store(3, 64, 2)
    keys = np.sort(rng.choice(1000, 40, replace=False).astype(np.uint32))
    vals = rng.normal(size=(40, 2)).astype(np.float32)
    k0, v0, _ = slab_put(store.keys[0], store.values[0],
                         jnp.asarray(keys), jnp.asarray(vals))
    store = type(store)(
        keys=store.keys.at[0].set(k0), values=store.values.at[0].set(v0),
        overflow=store.overflow,
    )
    fill0 = int(np.asarray(store_fill(store)).sum())
    lo, hi = int(keys[10]), int(keys[29])
    span = int(((keys >= lo) & (keys <= hi)).sum())
    store = execute(store, [MigrationOp(lo=lo, hi=hi, src=0, dst=1, kind="move")])
    fills = np.asarray(store_fill(store))
    assert fills[1] == span and int(fills.sum()) == fill0
    # values intact after the move
    moved = keys[(keys >= lo) & (keys <= hi)]
    got, found = slab_get(store.keys[1], store.values[1],
                          jnp.asarray(moved))
    assert bool(np.asarray(found).all())
    np.testing.assert_allclose(
        np.asarray(got), vals[(keys >= lo) & (keys <= hi)], atol=0)
    # reclaim erases the copy
    store = execute(store, [MigrationOp(lo=lo, hi=hi, src=1, dst=1,
                                        kind="reclaim")])
    assert int(np.asarray(store_fill(store))[1]) == 0


def test_slab_put_large_uint32_spans():
    """keys near the uint32 ceiling (0xFFFFFFFE is a legal key)."""
    sk = np.full(8, EMPTY, np.uint32)
    sv = np.zeros((8, 1), np.float32)
    pkeys = np.array([0xFFFFFFFE, 0, 0x80000000], np.uint32)
    pvals = np.arange(3, dtype=np.float32)[:, None]
    k, v, d = slab_put(jnp.asarray(sk), jnp.asarray(sv),
                       jnp.asarray(pkeys), jnp.asarray(pvals))
    np.testing.assert_array_equal(
        np.asarray(k)[:3], [0, 0x80000000, 0xFFFFFFFE])
    assert int(d) == 0


# ---------------------------------------------------------------------------
# the merge skip: a batch that inserts no new key is written in place
# ---------------------------------------------------------------------------


def _merge_path(sk, sv, pk, pv):
    """The O(C) path of ``put_sorted`` called directly, whatever the
    batch: the hit rows written in place, then ``_compact_sorted`` of the
    new keys, ``_merge_sorted_runs`` and the tail zeroing."""
    C = sk.shape[0]
    pos = jnp.minimum(jnp.searchsorted(sk, pk), C - 1)
    hit = (sk[pos] == pk) & (pk != EMPTY)
    sv = sv.at[jnp.where(hit, pos, C)].set(pv, mode="drop")
    new = ~hit & (pk != EMPTY)
    nk, nv = _compact_sorted(jnp.where(hit, EMPTY, pk), pv, new)
    k, v = _merge_sorted_runs(sk, sv, nk, nv, C)
    v = jnp.where((k != EMPTY)[:, None], v, 0.0)
    n_live = jnp.sum(sk != EMPTY) + jnp.sum(new)
    return k, v, jnp.maximum(n_live - C, 0)


def _bits(x):
    x = np.asarray(x)
    return x.view(np.uint32) if x.dtype == np.float32 else x


def _put_case(name, rng, C=32, V=3, B=16):
    """(slab keys, slab values, raw PUT keys, PUT values, whether the batch
    inserts a key the slab lacks)."""
    fill = C if name.startswith("full") else 20
    sk, sv = _random_slab(rng, C, V, keyspace=1000, fill=fill)
    live = sk[sk != EMPTY]
    fresh = np.setdiff1d(np.arange(1000, dtype=np.uint32), live)
    old = rng.choice(live, 6, replace=False)
    new = rng.choice(fresh, 6, replace=False)
    keys = {
        "update_only": old,
        "insert_only": new,
        "mixed": np.concatenate([old[:3], new[:3]]),
        "duplicates_update": np.concatenate([old[:3], old[:3], old[:2]]),
        "duplicates_mixed": np.concatenate([old[:3], new[:2], old[:3],
                                            new[:2]]),
        "all_empty": np.zeros(0, np.uint32),
        "full_update": old,
        "full_overflow": new,
    }[name]
    pkeys = np.full(B, EMPTY, np.uint32)
    pkeys[:len(keys)] = keys
    rng.shuffle(pkeys)
    pvals = rng.normal(size=(B, V)).astype(np.float32)
    return sk, sv, pkeys, pvals, bool(np.isin(keys, live, invert=True).any())


@pytest.mark.parametrize("name", [
    "update_only", "insert_only", "mixed", "duplicates_update",
    "duplicates_mixed", "all_empty", "full_update", "full_overflow",
])
def test_put_skip_equals_full_merge(name):
    """``put_sorted`` (and ``slab_put``, which dedupes first)
    against the merge path called directly: keys, values and ``dropped``
    bit for bit, the merge run only for a batch with a new key."""
    sk, sv, pkeys, pvals, inserts = _put_case(
        name, np.random.default_rng(zlib.crc32(name.encode())))
    sk, sv = jnp.asarray(sk), jnp.asarray(sv)
    pk, pv = _dedupe_last_write(jnp.asarray(pkeys), jnp.asarray(pvals))
    want = _merge_path(sk, sv, pk, pv)
    k, v, dropped, merged, rows = put_sorted(sk, sv, pk, pv)
    for got in ((k, v, dropped),
                slab_put(sk, sv, jnp.asarray(pkeys), jnp.asarray(pvals))):
        for g, w in zip(got, want):
            np.testing.assert_array_equal(_bits(g), _bits(w))
    assert bool(merged) == inserts
    slab = np.asarray(sk)
    hits = int(np.isin(np.asarray(pk), slab[slab != EMPTY]).sum())
    assert int(rows) == (sk.shape[0] if inserts else hits)
    if name == "full_overflow":
        assert int(dropped) == 6
    if not inserts:
        assert int(dropped) == 0
        np.testing.assert_array_equal(np.asarray(k), np.asarray(sk))


def test_migration_into_a_slab_that_holds_the_range():
    """``apply_migration`` whose destination already holds every key of
    the range (a copy, then a move): the values arrive in place, with the
    slab as the merge path leaves it."""
    from repro.core.migration import apply_migration
    from repro.core.store import StoreState

    rng = np.random.default_rng(6)
    C, V = 48, 2
    keys = np.sort(rng.choice(1000, 30, replace=False).astype(np.uint32))
    store = make_store(3, C, V)
    slabs_k, slabs_v = [], []
    for shard, vals in enumerate((rng.normal(size=(30, V)),
                                  rng.normal(size=(30, V)))):
        k, v, _ = slab_put(store.keys[shard], store.values[shard],
                           jnp.asarray(keys), jnp.asarray(vals, np.float32))
        slabs_k.append(k)
        slabs_v.append(v)
    store = StoreState(keys=jnp.stack([*slabs_k, store.keys[2]]),
                       values=jnp.stack([*slabs_v, store.values[2]]),
                       overflow=store.overflow)
    lo, hi = int(keys[5]), int(keys[20])
    ex_live = (store.keys[0] >= lo) & (store.keys[0] <= hi)
    ex_k, ex_v = _compact_sorted(jnp.where(ex_live, store.keys[0], EMPTY),
                                 store.values[0], ex_live)
    dst_k, dst_v, _ = _merge_path(store.keys[1], store.values[1], ex_k, ex_v)
    for move in (False, True):
        out = apply_migration(store, lo, hi, jnp.int32(0), jnp.int32(1),
                              move=move)
        np.testing.assert_array_equal(np.asarray(out.keys[1]), dst_k)
        np.testing.assert_array_equal(_bits(out.values[1]), _bits(dst_v))
        src_k, src_v = ((store.keys[0], store.values[0]) if not move else
                        delete_range(store.keys[0], store.values[0], lo, hi))
        np.testing.assert_array_equal(np.asarray(out.keys[0]), src_k)
        np.testing.assert_array_equal(_bits(out.values[0]), _bits(src_v))
        np.testing.assert_array_equal(np.asarray(out.overflow), 0)
        # the range now carries the source's values at the destination
        got, found = slab_get(out.keys[1], out.values[1],
                              jnp.asarray(keys[5:21]))
        assert bool(np.asarray(found).all())
        np.testing.assert_array_equal(_bits(got), _bits(slabs_v[0][5:21]))


def test_store_operations_keep_slabs_compact_with_zero_tails():
    """The precondition of the merge skip: every operation leaves each
    slab's live keys a strictly increasing prefix and zero values on its
    EMPTY tail, through mixed PUT/DEL/GET batches on the served path and
    every kind of migration."""
    from repro import core as C
    from repro.core import keys as K
    from repro.core.migration import MigrationOp, execute
    from repro.core.store import apply_routed

    def assert_compact(store):
        keys, vals = np.asarray(store.keys), np.asarray(store.values)
        for k, v in zip(keys, vals):
            n = int((k != EMPTY).sum())
            assert (k[n:] == EMPTY).all()
            assert (np.diff(k[:n].astype(np.int64)) > 0).all()
            assert (v[n:] == 0).all()

    rng = np.random.default_rng(8)
    N, cap, V, B = 4, 96, 2, 64
    store = make_store(N, cap, V)
    directory = C.make_directory(8, N, 3, r_max=4, n_slots=16)
    pool = rng.choice(2**31, 120, replace=False).astype(np.uint32)
    ops = np.array([K.OP_PUT, K.OP_PUT, K.OP_DEL, K.OP_GET], np.int32)
    for step in range(6):
        # the first batch loads; later ones mix updates, inserts, deletes
        opcodes = (np.full(B, K.OP_PUT, np.int32) if step == 0
                   else rng.choice(ops, B).astype(np.int32))
        q = C.make_queries(jnp.asarray(rng.choice(pool, B)),
                           jnp.asarray(opcodes),
                           jnp.asarray(rng.normal(size=(B, V)), jnp.float32))
        decision, directory = C.route(directory, q)
        store, _ = apply_routed(store, q, decision)
        assert_compact(store)
    live = np.asarray(store.keys[0])
    live = live[live != EMPTY]
    lo, hi = int(live[len(live) // 4]), int(live[3 * len(live) // 4])
    # the second copy finds the range already at its destination
    for src, dst, kind in ((0, 1, "copy"), (0, 1, "copy"), (0, 2, "move"),
                           (1, 1, "reclaim")):
        store = execute(store, [MigrationOp(lo, hi, src, dst, kind)])
        assert_compact(store)
