"""The device-resident epoch pipeline (PR 4).

Pins the tentpole contract:

* the fused period scan reproduces the per-epoch driver's
  ``EpochMetrics`` stream AND final store state **bit for bit** on
  shifting_hotspot and multi_hotspot (policies only act on
  period-boundary reports, so fusing within a period is observationally
  equivalent);
* the scan compiles exactly once per scenario — including scenarios
  whose control events cut segments short (masked no-op padding, not a
  second program);
* the store slabs / load registers / sketch are **donated** into the
  scan: the pre-call buffers are deleted (no second live copy) and jax
  emits no donation warnings;
* the incremental key-window dedupe matches one-shot ``np.unique`` and
  respects its cap;
* the batch metric helpers are row-identical to their scalar forms;
* the correlated-failure scenario (rack + hotspot) drives the
  switch-failure splice through the driver event loop.
"""

import dataclasses
import warnings

import numpy as np
import pytest

from repro.cluster import (
    ClusterConfig,
    EpochDriver,
    ScenarioConfig,
    imbalance_stats,
    imbalance_stats_batch,
    latency_percentiles,
    latency_percentiles_batch,
    make_policy,
    make_scenario,
)
from repro.cluster.epoch import _merge_unique

SCFG = ScenarioConfig(n_epochs=6, epoch_ops=256, n_records=512,
                      value_dim=2, seed=3)


def _ccfg(period=2, **kw):
    return ClusterConfig(num_nodes=8, num_ranges=32, replication=2, r_max=4,
                         n_clients=16, report_every=period,
                         imbalance_threshold=1.1, max_moves_per_round=6, **kw)


def _run_pair(scen_name, pol, period=2, scen_kw=None, scfg=SCFG):
    out = {}
    for fused in (False, True):
        scen = make_scenario(scen_name, scfg, **(scen_kw or {}))
        drv = EpochDriver(scen, make_policy(pol), _ccfg(period), fused=fused)
        rows = drv.run()
        out[fused] = (drv, rows)
    return out


def _assert_bitident(out):
    (drv_r, rows_r), (drv_f, rows_f) = out[False], out[True]
    assert len(rows_r) == len(rows_f)
    for a, b in zip(rows_r, rows_f):
        assert dataclasses.asdict(a) == dataclasses.asdict(b), (
            f"metrics diverge at epoch {a.epoch}")
    for field in ("keys", "values", "overflow"):
        assert np.array_equal(
            np.asarray(getattr(drv_r.store, field)),
            np.asarray(getattr(drv_f.store, field)),
        ), f"final store {field} diverges"
    # the control state converged identically too
    assert np.array_equal(np.asarray(drv_r.directory.chains),
                          np.asarray(drv_f.directory.chains))
    assert drv_r.controller.failed == drv_f.controller.failed


# ---------------------------------------------------------------------------
# bit-identity: the tentpole equivalence gate
# ---------------------------------------------------------------------------


def test_fused_bitident_shifting_hotspot_full_adaptive():
    out = _run_pair("shifting_hotspot", "full_adaptive", period=2,
                    scen_kw=dict(theta=1.2, shift_every=2))
    _assert_bitident(out)
    assert out[True][0].traces == 1
    # the whole point: strictly fewer host round-trips per run
    assert out[True][0].host_syncs < out[False][0].host_syncs


def test_fused_bitident_multi_hotspot_split_hot():
    out = _run_pair("multi_hotspot", "split_hot", period=3,
                    scen_kw=dict(theta=1.3, n_hotspots=2, shift_every=2))
    _assert_bitident(out)
    assert out[True][0].traces == 1


def test_fused_bitident_with_mid_period_events():
    """node_failure fires mid-period: segments are cut short + padded, and
    the stream must still match the per-epoch driver exactly."""
    out = _run_pair("node_failure", "migrate", period=4,
                    scen_kw=dict(fail_epoch=3, fail_node=0, recover_epoch=5))
    _assert_bitident(out)
    assert out[True][0].traces == 1   # masked padding, not a second program


def test_fused_bitident_whole_run_single_period():
    out = _run_pair("shifting_hotspot", "replicate", period=SCFG.n_epochs,
                    scen_kw=dict(theta=1.2, shift_every=2))
    _assert_bitident(out)
    assert out[True][0].traces == 1


def test_fused_bitident_telemetry_buffer_leaves():
    """The PR-7 extension of the equivalence gate: with tracing enabled,
    every telemetry buffer leaf (span tables, counts, latencies, bucket
    components, issue times) must ALSO match the per-epoch driver bit
    for bit — the span plane rides the same scan, so fusing may not
    reorder, drop or re-derive a single recorded value."""
    from repro.telemetry import TelemetryConfig

    tcfg = TelemetryConfig(sample_rate=1 / 4)
    out = {}
    for fused in (False, True):
        scen = make_scenario("shifting_hotspot", SCFG, theta=1.2,
                             shift_every=2)
        drv = EpochDriver(scen, make_policy("full_adaptive"),
                          _ccfg(2, telemetry=tcfg), fused=fused)
        rows = drv.run()
        out[fused] = (drv, rows)
    _assert_bitident(out)
    assert out[True][0].traces == 1
    er = out[False][0].telemetry.epochs
    ef = out[True][0].telemetry.epochs
    assert len(er) == len(ef) == SCFG.n_epochs
    assert out[True][0].telemetry.span_count > 0
    for a, b in zip(er, ef):
        assert a["epoch"] == b["epoch"]
        assert a["n_sampled"] == b["n_sampled"]
        assert a["makespan"] == b["makespan"]
        for leaf in ("span_i", "span_f", "lat", "comps", "issue"):
            np.testing.assert_array_equal(
                a[leaf], b[leaf],
                err_msg=f"telemetry leaf {leaf} diverges at epoch "
                        f"{a['epoch']}")


# ---------------------------------------------------------------------------
# donation + trace stability
# ---------------------------------------------------------------------------


def test_fused_scan_donates_store_and_registers():
    scen = make_scenario("shifting_hotspot", SCFG, shift_every=2)
    drv = EpochDriver(scen, make_policy("frozen"), _ccfg(3), fused=True)
    keys0, vals0 = drv.store.keys, drv.store.values
    load0, sketch0 = drv.load_reg, drv.sketch
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        drv.run()
    donation_warnings = [
        str(w.message) for w in caught if "donat" in str(w.message).lower()
    ]
    assert donation_warnings == []       # every donated buffer was usable
    # the pre-scan buffers were consumed in place: no second live copy
    assert keys0.is_deleted() and vals0.is_deleted()
    assert load0.is_deleted() and sketch0.is_deleted()
    assert drv.traces == 1


def test_fused_compiles_once_across_segment_lengths():
    """Same driver sees full segments, event-shortened segments and the
    run-end stub — all through ONE compiled program."""
    scfg = ScenarioConfig(n_epochs=7, epoch_ops=128, n_records=256,
                          value_dim=2, seed=5)
    scen = make_scenario("node_failure", scfg, fail_epoch=3, fail_node=1)
    drv = EpochDriver(scen, make_policy("full_adaptive"), _ccfg(2),
                      fused=True)
    rows = drv.run()
    assert len(rows) == 7
    assert drv.traces == 1
    assert all(r.compiled_steps == 1 for r in rows)


def test_per_epoch_unavailable_on_fused_driver():
    scen = make_scenario("stationary", SCFG)
    drv = EpochDriver(scen, make_policy("frozen"), _ccfg(2), fused=True)
    with pytest.raises(RuntimeError, match="fused"):
        drv.run_epoch(0)


# ---------------------------------------------------------------------------
# key-window dedupe + batch metric helpers
# ---------------------------------------------------------------------------


def test_merge_unique_matches_np_unique():
    rng = np.random.default_rng(0)
    acc = np.empty(0, np.uint32)
    seen = []
    for _ in range(10):
        chunk = rng.integers(0, 500, 200).astype(np.uint32)
        seen.append(chunk)
        acc = _merge_unique(acc, np.unique(chunk))
        np.testing.assert_array_equal(acc, np.unique(np.concatenate(seen)))


def test_key_window_cap_thins_uniformly():
    scen = make_scenario("stationary", SCFG)
    drv = EpochDriver(scen, make_policy("frozen"),
                      _ccfg(2, key_window_cap=64), fused=True)
    drv._note_keys(np.arange(1000, dtype=np.uint32))
    assert drv._key_window.size <= 64
    assert (np.diff(drv._key_window.astype(np.int64)) > 0).all()  # still sorted


def test_batch_metric_helpers_row_identical():
    rng = np.random.default_rng(1)
    lat = rng.exponential(50.0, size=(5, 333))
    p50s, p99s = latency_percentiles_batch(lat)
    for i in range(5):
        p50, p99 = latency_percentiles(lat[i])
        assert p50s[i] == p50 and p99s[i] == p99
    ops = rng.integers(0, 100, size=(5, 8)).astype(np.float64)
    live = np.array([True] * 6 + [False] * 2)
    imbs, covs = imbalance_stats_batch(ops, live)
    for i in range(5):
        imb, cov = imbalance_stats(ops[i], live)
        assert imbs[i] == imb and covs[i] == cov
    # degenerate: all-dead mask and zero ops
    imbs, covs = imbalance_stats_batch(np.zeros((2, 4)), np.zeros(4, bool))
    assert (imbs == 1.0).all() and (covs == 0.0).all()


# ---------------------------------------------------------------------------
# correlated-failure scenario (rack + hotspot)
# ---------------------------------------------------------------------------


def test_rack_failure_hotspot_events_and_recovery():
    scen = make_scenario("rack_failure_hotspot", SCFG, fail_epoch=2,
                         rack=(0, 1), recover_epoch=4)
    assert scen.events(2) == [("rack_fail", (0, 1))]
    assert scen.events(4) == [("recover", 0), ("recover", 1)]
    assert scen.events(1) == []
    # the heat still rotates (it composes the shifting hotspot)
    assert scen.record_probs(0).argmax() != scen.record_probs(5).argmax()


def test_rack_failure_hotspot_driver_splices_whole_rack():
    scen = make_scenario("rack_failure_hotspot", SCFG, fail_epoch=2,
                         rack=(0, 1))
    drv = EpochDriver(scen, make_policy("full_adaptive"), _ccfg(2),
                      fused=True)
    rows = drv.run()
    assert any("rack_fail:0+1" in r.events for r in rows)
    assert drv.controller.failed == {0, 1}
    # no live chain references a dead rack member after the splice
    chains = np.asarray(drv.directory.chains)
    clen = np.asarray(drv.directory.chain_len)
    live = np.asarray(drv.directory.live)
    for r in np.where(live)[0]:
        members = set(chains[r][: clen[r]].tolist())
        assert not members & {0, 1}
    # the repair moved actual data and service never stopped
    assert any(r.migration_entries > 0 for r in rows)
    assert all(r.throughput > 0 for r in rows)
    assert drv.traces == 1


def test_rack_failure_bitident_fused_vs_epoch():
    out = _run_pair("rack_failure_hotspot", "migrate", period=2,
                    scen_kw=dict(fail_epoch=3, rack=(2, 3), recover_epoch=5))
    _assert_bitident(out)


# ---------------------------------------------------------------------------
# dist backend: deferred-sync segments must match per-epoch stepping too
# ---------------------------------------------------------------------------


def test_dist_fused_bitident_vs_per_epoch():
    """The dist fused path steps per-epoch but defers every host sync to
    the period boundary, stacking plans/metrics on device — the stream
    must still match per-epoch dist stepping exactly (ordering of the
    stacked epochs, pull boundaries, overflow diffs)."""
    from repro.core.dist_store import make_mesh

    mesh = make_mesh((1,))
    scfg = ScenarioConfig(n_epochs=4, epoch_ops=128, n_records=256,
                          value_dim=2, seed=4)
    ccfg_kw = dict(num_nodes=1, num_ranges=8, replication=1, r_max=1,
                   n_clients=8, max_moves_per_round=0, report_every=2)
    rows = {}
    for fused in (False, True):
        scen = make_scenario("stationary", scfg)
        drv = EpochDriver(scen, make_policy("frozen"),
                          ClusterConfig(**ccfg_kw),
                          backend="dist", mesh=mesh, fused=fused)
        rows[fused] = (drv, drv.run())
    (drv_r, rows_r), (drv_f, rows_f) = rows[False], rows[True]
    for a, b in zip(rows_r, rows_f):
        assert dataclasses.asdict(a) == dataclasses.asdict(b), (
            f"dist metrics diverge at epoch {a.epoch}")
    assert np.array_equal(np.asarray(drv_r.store.keys),
                          np.asarray(drv_f.store.keys))
    assert np.array_equal(np.asarray(drv_r.store.values),
                          np.asarray(drv_f.store.values))
    assert drv_f.host_syncs < drv_r.host_syncs
