"""The O(C+B) slab rank-merge vs the old sort-and-truncate oracle.

PR 4 replaced the full ``argsort`` of the ``capacity + B`` concatenation
in ``slab_put``/``slab_delete`` with a gather-style searchsorted rank
merge of the two already-sorted runs.  These tests pin the contract:

* live prefix (keys AND values) identical to the old argsort path;
* dead tail: EMPTY keys with **zeroed** values (a deliberate tightening —
  the old path left stale garbage values behind);
* overflow accounting identical;
* the migration movers (which share ``_compact_sorted``) round-trip.
"""

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
    make_store,
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
