"""repro.cluster: closed-loop adaptive balancing + its core-layer hooks."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core as C
from repro.core import directory as D
from repro.core import keys as K
from repro.core.coordination import NO_HOP
from repro.core.dist_store import make_mesh
from repro.kernels.range_match.ops import range_match_spread

from repro.cluster import (
    ClusterConfig,
    EpochDriver,
    ScenarioConfig,
    make_policy,
    make_scenario,
    summarize,
)


def _query_mix(n=256, seed=0, write_frac=0.2):
    rng = np.random.default_rng(seed)
    keys = jnp.asarray(rng.integers(0, 2**32 - 2, n), jnp.uint32)
    ops = jnp.asarray(
        np.where(rng.random(n) < write_frac, K.OP_PUT, K.OP_GET), jnp.int32
    )
    return C.make_queries(keys, ops, value_dim=2)


# ---------------------------------------------------------------------------
# load-aware routing (p2c read spreading)
# ---------------------------------------------------------------------------


def test_route_load_aware_targets_are_live_chain_members():
    d = C.make_directory(16, 8, 3, r_max=5)
    q = _query_mix()
    load = jnp.zeros((8,), jnp.uint32)
    dec, d2, load2 = C.route_load_aware(d, q, load, jax.random.PRNGKey(0))
    chain = np.asarray(dec.chain)
    clen = np.asarray(dec.chain_len)
    target = np.asarray(dec.target)
    is_write = np.asarray(q.opcode) != K.OP_GET
    # writes at the head, reads at some live member
    assert (target[is_write] == chain[is_write, 0]).all()
    for i in np.where(~is_write)[0]:
        assert target[i] in chain[i, : clen[i]]
    # registers bumped: one unit per read + one per live member per write
    expected = (~is_write).sum() + (clen[is_write]).sum()
    assert int(np.asarray(load2).sum()) == expected


def test_route_load_aware_spreads_reads_off_the_tail():
    d = C.make_directory(8, 8, 3)
    q = _query_mix(n=512, write_frac=0.0)
    dec_tail, _ = C.route(d, q)
    dec, _, _ = C.route_load_aware(
        d, q, jnp.zeros((8,), jnp.uint32), jax.random.PRNGKey(1)
    )
    # tail-only routing uses <= 8 distinct targets; p2c must not collapse
    # onto the tails (some reads land on non-tail members)
    assert (np.asarray(dec.target) != np.asarray(dec_tail.target)).mean() > 0.3


def test_route_load_aware_prefers_less_loaded_replica():
    # two nodes, one chain [0, 1]; node 0 heavily loaded -> reads go to 1
    d = C.make_directory(1, 2, 2)
    q = _query_mix(n=256, write_frac=0.0)
    load = jnp.asarray([1000, 0], jnp.uint32)
    dec, _, _ = C.route_load_aware(d, q, load, jax.random.PRNGKey(2))
    target = np.asarray(dec.target)
    # p2c picks node 1 whenever it is a candidate (~3/4 of draws)
    assert (target == 1).mean() > 0.6


def test_range_match_spread_matches_routing_oracle():
    d = C.make_directory(16, 8, 3, r_max=5)
    q = _query_mix(n=300, seed=3)
    load = jnp.asarray(np.random.default_rng(4).integers(0, 50, 8), jnp.uint32)
    rng = jax.random.PRNGKey(7)
    dec, _, _ = C.route_load_aware(d, q, load, rng)
    for use_pallas in (False, True):
        ridx, target, chain = range_match_spread(
            d, q.key, q.opcode, load, rng, use_pallas=use_pallas
        )
        assert np.array_equal(np.asarray(ridx), np.asarray(dec.ridx))
        assert np.array_equal(np.asarray(target), np.asarray(dec.target))
        assert np.array_equal(np.asarray(chain).T, np.asarray(dec.chain))


def test_apply_routed_serves_spread_reads():
    """Any replica a spread read targets must actually hold the data."""
    d = C.make_directory(8, 6, 3)
    store = C.make_store(6, 256, 2)
    rng = np.random.default_rng(5)
    keys = jnp.asarray(rng.choice(2**32 - 2, 100, replace=False), jnp.uint32)
    vals = jnp.asarray(rng.normal(size=(100, 2)), jnp.float32)
    qp = C.make_queries(keys, jnp.full((100,), C.OP_PUT), vals)
    dec, d = C.route(d, qp)
    store, _ = C.apply_routed(store, qp, dec)

    qg = C.make_queries(keys, jnp.full((100,), C.OP_GET), value_dim=2)
    dec, d, _ = C.route_load_aware(
        d, qg, jnp.zeros((6,), jnp.uint32), jax.random.PRNGKey(9)
    )
    _, resp = C.apply_routed(store, qg, dec)
    assert bool(resp.found.all())
    np.testing.assert_allclose(np.asarray(resp.value), np.asarray(vals),
                               atol=1e-6)


def test_plan_hops_write_chain_cap():
    d = C.make_directory(4, 8, 2, r_max=4)
    # widen every chain to 4
    ctl = C.Controller(d)
    for r in range(4):
        ctl.widen_chain(r, np.zeros(8))
        ctl.widen_chain(r, np.zeros(8))
    d = ctl.refresh(d)
    q = _query_mix(n=64, write_frac=1.0)
    dec, _ = C.route(d, q)
    full = C.plan_hops(q, dec, C.IN_SWITCH, C.LatencyModel(),
                       rng=jax.random.PRNGKey(0), num_nodes=8)
    capped = C.plan_hops(q, dec, C.IN_SWITCH, C.LatencyModel(),
                         rng=jax.random.PRNGKey(0), num_nodes=8,
                         write_chain_cap=2)
    hops_full = (np.asarray(full.nodes) != NO_HOP).sum(1)
    hops_capped = (np.asarray(capped.nodes) != NO_HOP).sum(1)
    assert (hops_full == 4).all()
    assert (hops_capped == 2).all()


# ---------------------------------------------------------------------------
# counters survive control updates (pull_report is the only reset path)
# ---------------------------------------------------------------------------


def test_counters_survive_chain_widening():
    d = C.make_directory(16, 8, 2, r_max=4)
    q = _query_mix(n=400, seed=6)
    dec, d = C.route(d, q)
    reads = np.asarray(d.read_count).copy()
    writes = np.asarray(d.write_count).copy()
    load_before = np.asarray(D.node_load(d)).copy()
    assert reads.sum() > 0 and writes.sum() > 0

    ctl = C.Controller(d)
    op = ctl.widen_chain(int(reads.argmax()), load_before)
    assert op is not None and op.kind == "copy"
    d2 = ctl.refresh(d)

    # the control update changed the chain but not one counter bit
    assert (np.asarray(d2.read_count) == reads).all()
    assert (np.asarray(d2.write_count) == writes).all()
    assert int(np.asarray(d2.chain_len)[reads.argmax()]) == 3
    # node_load derives from the surviving counters: still consistent
    assert np.asarray(D.node_load(d2)).sum() >= load_before.sum() - 1e-6

    # ... and pull_report is the reset path
    report, d3 = C.pull_report(d2, period=0)
    assert (report.read_count == reads).all()
    assert int(np.asarray(d3.read_count).sum()) == 0
    assert int(np.asarray(d3.write_count).sum()) == 0


def test_refresh_rejects_shape_change():
    d = C.make_directory(8, 8, 2)
    ctl = C.Controller(d)
    ctl.split_overflowed(0, np.zeros(8))  # R: 8 -> 9
    with pytest.raises(ValueError, match="shape changed"):
        ctl.refresh(d)


# ---------------------------------------------------------------------------
# controller edge cases: widen/narrow, split, switch failure
# ---------------------------------------------------------------------------


def test_widen_chain_at_r_max_is_noop():
    d = C.make_directory(4, 8, 3, r_max=3)  # no headroom
    ctl = C.Controller(d)
    assert ctl.widen_chain(0, np.zeros(8)) is None
    assert (ctl.chain_lengths() == 3).all()


def test_widen_narrow_roundtrip_reclaims_space():
    d = C.make_directory(4, 6, 2, r_max=3)
    store = C.make_store(6, 128, 2)
    rng = np.random.default_rng(8)
    keys = jnp.asarray(rng.choice(2**32 - 2, 60, replace=False), jnp.uint32)
    vals = jnp.asarray(rng.normal(size=(60, 2)), jnp.float32)
    qp = C.make_queries(keys, jnp.full((60,), C.OP_PUT), vals)
    dec, d = C.route(d, qp)
    store, _ = C.apply_routed(store, qp, dec)
    fill0 = int(C.store_fill(store).sum())

    ctl = C.Controller(d)
    op = ctl.widen_chain(0, np.zeros(6))
    store = C.execute_migrations(store, [op])
    assert int(C.store_fill(store).sum()) >= fill0

    op2 = ctl.narrow_chain(0, 2)
    assert op2 is not None and op2.kind == "reclaim" and op2.src == op.dst
    store = C.execute_migrations(store, [op2])
    assert int(C.store_fill(store).sum()) == fill0
    # narrowing below base replication refuses
    assert ctl.narrow_chain(0, 2) is None
    # data still fully readable through the narrowed directory
    d2 = ctl.refresh(d)
    qg = C.make_queries(keys, jnp.full((60,), C.OP_GET), value_dim=2)
    decg, _ = C.route(d2, qg)
    _, resp = C.apply_routed(store, qg, decg)
    assert bool(resp.found.all())


def test_repeated_failure_of_same_node_is_idempotent():
    d = C.make_directory(16, 8, 3)
    ctl = C.Controller(d)
    ops1 = ctl.handle_node_failure(2, np.zeros(8))
    chains_after = ctl._dir["chains"].copy()
    ops2 = ctl.handle_node_failure(2, np.zeros(8))
    assert ops1 and not ops2  # second failure: nothing left to splice
    assert (ctl._dir["chains"] == chains_after).all()


def test_switch_failure_takes_out_whole_rack():
    d = C.make_directory(24, 9, 3, num_pods=3)
    ctl = C.Controller(d)
    rack = [0, 1, 2]  # pod 0
    ops = ctl.handle_switch_failure(rack)
    chains = ctl._dir["chains"]
    clen = ctl._dir["chain_len"]
    for i in range(24):
        live = set(chains[i][: clen[i]].tolist())
        assert not live & set(rack)
        assert clen[i] == 3  # replication restored from survivors
    assert all(op.dst not in rack for op in ops if op.kind == "copy")


def test_switch_failure_repeated_rack_is_idempotent():
    d = C.make_directory(8, 6, 2, num_pods=3)
    ctl = C.Controller(d)
    ctl.handle_switch_failure([0, 1])
    chains_after = ctl._dir["chains"].copy()
    ops = ctl.handle_switch_failure([0, 1])
    assert not ops
    assert (ctl._dir["chains"] == chains_after).all()


def test_split_of_saturated_last_range():
    d = C.make_directory(8, 8, 2)  # no slot headroom: split must grow the pool
    ctl = C.Controller(d)
    assert int(ctl._dir["slot_hi"][7]) == 0xFFFFFFFF
    ops = ctl.split_overflowed(7, np.zeros(8))
    assert ctl.num_ranges == 9          # live records
    assert ctl.num_slots == 16          # pool doubled (shape change)
    hi = ctl._dir["slot_hi"]
    live = ctl._dir["live"]
    assert int(hi[live].astype(np.uint64).max()) == 0xFFFFFFFF
    # every key still matches exactly one live record in the rebuilt directory
    d2 = ctl.directory()
    probes = jnp.asarray([0, 1, 2**31, 0xFFFFFFFE, 0xFFFFFFFF], jnp.uint32)
    ridx = np.asarray(C.lookup_range(d2, probes))
    assert bool(np.asarray(d2.live)[ridx].all())
    lo2 = np.asarray(d2.slot_lo).astype(np.uint64)
    hi2 = np.asarray(d2.slot_hi).astype(np.uint64)
    for k, r in zip(np.asarray(probes, np.uint64), ridx):
        assert lo2[r] <= k <= hi2[r]
    if ops:
        assert ops[0].hi == 0xFFFFFFFF


def test_split_of_tiny_range_refuses():
    d = C.make_directory(8, 8, 2)
    ctl = C.Controller(d)
    # shrink range 0 to width 1: [0, 0]
    ctl._dir["slot_hi"][0] = np.uint32(0)
    assert ctl.split_overflowed(0, np.zeros(8)) == []
    assert ctl.num_ranges == 8


def test_split_range_uses_pool_without_shape_change():
    d = C.make_directory(8, 8, 2, n_slots=16)
    ctl = C.Controller(d)
    lo, hi = ctl.range_span(2)
    child = ctl.split_range(2, lo + (hi - lo) // 2)
    assert child is not None and child >= 8       # allocated from the pool
    assert ctl.num_slots == 16                    # no shape change
    assert ctl.num_ranges == 9
    d2 = ctl.refresh(d)                           # graft works: shapes agree
    # child covers the upper half, parent the lower; chains identical
    clo, chi = ctl.range_span(child)
    plo, phi = ctl.range_span(2)
    assert plo == lo and chi == hi and phi + 1 == clo
    assert (ctl.chain_nodes(child) == ctl.chain_nodes(2)).all()
    # lookups land on the right halves
    probes = jnp.asarray([plo, phi, clo, chi], jnp.uint32)
    ridx = np.asarray(C.lookup_range(d2, probes))
    assert list(ridx) == [2, 2, child, child]


def test_merge_range_roundtrip_and_ops():
    d = C.make_directory(4, 8, 2, n_slots=8)
    ctl = C.Controller(d)
    before = {k: v.copy() for k, v in ctl._dir.items()}
    lo, hi = ctl.range_span(1)
    child = ctl.split_range(1, lo + (hi - lo) // 2)
    # move the child's head elsewhere so the merge has to emit data ops
    old_head = int(ctl.chain_nodes(child)[0])
    new_head = (old_head + 3) % 8
    ctl._dir["chains"][child, 0] = new_head
    ops = ctl.merge_range(child)
    assert ops is not None
    kinds = sorted(o.kind for o in ops)
    assert "copy" in kinds and "reclaim" in kinds  # converge + free child copy
    for o in ops:
        assert o.lo >= lo and o.hi <= hi           # priced by the child span
    # directory round-trips exactly (slot tables identical to pre-split)
    for k in ("slot_lo", "slot_hi", "live", "chain_len", "parent",
              "generation", "chains"):
        assert (ctl._dir[k] == before[k]).all(), k


def test_merge_refuses_non_adjacent_child():
    d = C.make_directory(4, 8, 2, n_slots=8)
    ctl = C.Controller(d)
    lo, hi = ctl.range_span(0)
    c1 = ctl.split_range(0, lo + (hi - lo) // 2)
    # parent re-splits: c1 is no longer adjacent to its parent
    plo, phi = ctl.range_span(0)
    c2 = ctl.split_range(0, plo + (phi - plo) // 2)
    assert c1 is not None and c2 is not None
    assert ctl.merge_range(c1) is None            # spans drifted apart
    assert ctl.merge_range(c2) is not None        # still adjacent


def test_merge_credits_live_counters_to_parent():
    d = C.make_directory(4, 8, 2, n_slots=8)
    ctl = C.Controller(d)
    lo, hi = ctl.range_span(1)
    child = ctl.split_range(1, lo + (hi - lo) // 2)
    d_live = ctl.refresh(d)
    # traffic lands on the child mid-period
    clo, chi = ctl.range_span(child)
    keys = jnp.asarray(
        np.linspace(clo, chi, 64, dtype=np.uint64).astype(np.uint32))
    q = C.make_queries(keys, jnp.zeros((64,), jnp.int32), value_dim=1)
    _, d_live = C.route(d_live, q)
    child_reads = int(np.asarray(d_live.read_count)[child])
    assert child_reads > 0
    total = int(np.asarray(d_live.read_count).sum())
    # merge, then refresh: the dead child's unreported hits move to parent
    assert ctl.merge_range(child) is not None
    d2 = ctl.refresh(d_live)
    rc = np.asarray(d2.read_count)
    assert int(rc[child]) == 0
    assert int(rc.sum()) == total                  # no heat lost
    assert int(rc[1]) >= child_reads


# ---------------------------------------------------------------------------
# scenarios
# ---------------------------------------------------------------------------


def test_scenarios_fixed_shapes_and_valid_probs():
    cfg = ScenarioConfig(n_epochs=4, epoch_ops=128, n_records=256, value_dim=2)
    for name in ("shifting_hotspot", "flash_crowd", "diurnal", "node_failure",
                 "rack_failure_hotspot"):
        scen = make_scenario(name, cfg)
        for e in range(cfg.n_epochs):
            p = scen.record_probs(e)
            assert p.shape == (cfg.n_records,)
            np.testing.assert_allclose(p.sum(), 1.0, atol=1e-9)
            opcodes, keys, end_keys, values = scen.epoch(e)
            assert opcodes.shape == keys.shape == end_keys.shape == (128,)
            assert values.shape == (128, 2)
            assert 0.0 <= scen.read_ratio(e) <= 1.0


def test_shifting_hotspot_actually_shifts():
    cfg = ScenarioConfig(n_epochs=6, epoch_ops=512, n_records=1024)
    scen = make_scenario("shifting_hotspot", cfg, theta=1.2, shift_every=2)
    hot0 = scen.record_probs(0).argmax()
    hot2 = scen.record_probs(2).argmax()
    assert hot0 != hot2


def test_node_failure_scenario_emits_events():
    cfg = ScenarioConfig(n_epochs=6)
    scen = make_scenario("node_failure", cfg, fail_epoch=2, fail_node=3,
                         recover_epoch=4)
    assert scen.events(2) == [("fail", 3)]
    assert scen.events(4) == [("recover", 3)]
    assert scen.events(1) == []


# ---------------------------------------------------------------------------
# the epoch driver (closed loop)
# ---------------------------------------------------------------------------

TINY_SCFG = ScenarioConfig(n_epochs=4, epoch_ops=256, n_records=512,
                           value_dim=2, seed=3)
TINY_CCFG = ClusterConfig(num_nodes=8, num_ranges=32, replication=2, r_max=4,
                          n_clients=16, imbalance_threshold=1.1,
                          max_moves_per_round=6)


def test_epoch_step_compiles_once():
    scen = make_scenario("shifting_hotspot", TINY_SCFG, shift_every=2)
    drv = EpochDriver(scen, make_policy("full_adaptive"), TINY_CCFG)
    rows = drv.run()
    assert drv.traces == 1
    assert len(rows) == TINY_SCFG.n_epochs
    for r in rows:
        assert r.throughput > 0 and r.makespan > 0
        assert r.p99 >= r.p50 > 0
        assert r.imbalance >= 1.0


def test_adaptive_beats_frozen_on_imbalance():
    results = {}
    for pol in ("frozen", "full_adaptive"):
        scen = make_scenario("shifting_hotspot", TINY_SCFG, theta=1.2,
                             shift_every=2)
        drv = EpochDriver(scen, make_policy(pol), TINY_CCFG)
        results[pol] = summarize(drv.run())
        assert drv.traces == 1
    assert (results["full_adaptive"]["mean_imbalance"]
            < results["frozen"]["mean_imbalance"])
    assert (results["full_adaptive"]["mean_throughput"]
            > results["frozen"]["mean_throughput"])


def test_migration_traffic_accounted():
    scen = make_scenario("shifting_hotspot", TINY_SCFG, theta=1.2,
                         shift_every=2)
    drv = EpochDriver(scen, make_policy("full_adaptive"), TINY_CCFG)
    rows = drv.run()
    s = summarize(rows)
    assert s["total_migration_bytes"] > 0
    assert s["total_migration_entries"] > 0
    # frozen policy moves nothing
    scen = make_scenario("shifting_hotspot", TINY_SCFG, theta=1.2,
                         shift_every=2)
    drv = EpochDriver(scen, make_policy("frozen"), TINY_CCFG)
    assert summarize(drv.run())["total_migration_bytes"] == 0


def test_node_failure_mid_load_keeps_serving():
    scen = make_scenario("node_failure", TINY_SCFG, fail_epoch=1, fail_node=0,
                         recover_epoch=3)
    drv = EpochDriver(scen, make_policy("full_adaptive"), TINY_CCFG)
    rows = drv.run()
    assert any("fail:0" in r.events for r in rows)
    assert any("recover:0" in r.events for r in rows)
    # after the failure epoch no chain references the dead node while failed
    for r in rows:
        assert r.throughput > 0
    chains = np.asarray(drv.directory.chains)
    clen = np.asarray(drv.directory.chain_len)
    live = np.asarray(drv.directory.live)
    # node 0 recovered at epoch 3, may be back; but during failure the
    # store kept answering (throughput > 0 asserted above).  Every *live*
    # record keeps a live chain (dead pool slots legitimately hold 0).
    assert (clen[live] >= 1).all()


def test_driver_rejects_bad_backend():
    scen = make_scenario("stationary", TINY_SCFG)
    with pytest.raises(ValueError, match="backend"):
        EpochDriver(scen, make_policy("frozen"), TINY_CCFG, backend="nope")
    with pytest.raises(ValueError, match="mesh"):
        EpochDriver(scen, make_policy("frozen"), TINY_CCFG, backend="dist")


def test_dist_backend_single_device_mesh():
    mesh = make_mesh((1,))
    scfg = ScenarioConfig(n_epochs=2, epoch_ops=128, n_records=256,
                          value_dim=2, seed=4)
    ccfg = ClusterConfig(num_nodes=1, num_ranges=8, replication=1, r_max=1,
                         n_clients=8, max_moves_per_round=0)
    scen = make_scenario("stationary", scfg)
    drv = EpochDriver(scen, make_policy("frozen"), ccfg,
                      backend="dist", mesh=mesh)
    rows = drv.run()
    assert all(r.throughput > 0 for r in rows)


@pytest.mark.parametrize("n_records,capacity", [
    (512, None),    # a whole number of chunks
    (1000, None),   # a padded tail chunk
    (700, 64),      # slabs overflow: drops must match too
])
def test_chunked_preload_matches_one_batch(monkeypatch, n_records, capacity):
    """The driver's preload, in chunks of 256 records, leaves the store
    bit-identical to PUTting every record in one batch."""
    from repro.cluster import epoch
    from repro.core.store import apply_routed, make_store

    monkeypatch.setattr(epoch, "PRELOAD_CHUNK", 256)

    scfg = ScenarioConfig(n_epochs=2, epoch_ops=256, n_records=n_records,
                          value_dim=4, seed=3)
    ccfg = ClusterConfig(num_nodes=8, num_ranges=32, replication=3, r_max=4,
                         capacity=capacity)
    drv = EpochDriver(make_scenario("shifting_hotspot", scfg),
                      make_policy("full_adaptive"), ccfg)
    keys, vals = drv.scenario.load()
    q = C.make_queries(jnp.asarray(keys), jnp.full((len(keys),), K.OP_PUT),
                       jnp.asarray(vals))
    ref, _ = apply_routed(
        make_store(8, drv.store.capacity, 4), q, C.route(drv.directory, q)[0],
        max_scan_results=ccfg.max_scan_results)
    for f in ("keys", "values", "overflow"):
        np.testing.assert_array_equal(np.asarray(getattr(drv.store, f)),
                                      np.asarray(getattr(ref, f)), err_msg=f)
    assert (int(np.asarray(ref.overflow).sum()) > 0) == (capacity is not None)


def test_dist_backend_rejects_nodes_per_device_mismatch():
    """The dist data plane holds one storage node per device; more nodes
    than devices would run, with wrong migration accounting."""
    scen = make_scenario("stationary", TINY_SCFG)
    with pytest.raises(ValueError, match="one storage node per device"):
        EpochDriver(scen, make_policy("frozen"), TINY_CCFG, backend="dist",
                    mesh=make_mesh((1,)))


def test_policy_registry():
    from repro.cluster import POLICIES
    assert set(POLICIES) == {
        "frozen", "migrate", "replicate", "split_hot", "full_adaptive",
        "overload_adaptive",
    }
    assert make_policy("replicate").read_spread
    assert not make_policy("migrate").read_spread
    assert not make_policy("split_hot").read_spread
    with pytest.raises(ValueError):
        make_policy("nope")
