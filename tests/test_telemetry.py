"""The observability plane (PR 7): repro.telemetry.

Pins the tentpole contracts:

* **pure observer** — with telemetry enabled the ``EpochMetrics`` stream
  is bit-identical to the telemetry-off run (no-PRNG sampling means
  tracing perturbs nothing it observes), and the fused scan still
  compiles exactly once;
* **exact attribution** — every sampled span's DES closed-loop latency
  reconstructs bit-for-bit from its five bucket components, under random
  fail / park (defer/shed + retry orbit) / bounce (CRAQ dirty read)
  interleavings (the property-test matrix);
* **deterministic sampling** — ``hash(key, epoch) < rate`` with no RNG
  stream, first-``max_spans`` slot selection, truncation *reported*
  (``counts``) instead of silent;
* the satellite fixes: vectorized ``masked_p99_batch`` bit-identical to
  its per-row loop oracle, ``EpochMetrics`` row round-trip,
  ``summarize`` key order;
* the export/profiler/flight-recorder halves: span trees, Chrome-trace
  structure, stage timers and counters, postmortem dumps.
"""

import dataclasses
import json

import jax
import numpy as np
import pytest

from repro.cluster import (
    ClusterConfig,
    EpochDriver,
    EpochMetrics,
    ScenarioConfig,
    TelemetryConfig,
    make_policy,
    make_scenario,
    masked_p99_batch,
    masked_p99_batch_loop,
    summarize,
)
from repro.cluster.policies import PolicyConfig
from repro.core.coordination import LatencyModel
from repro.launch import mesh as MESH
from repro.overload import OverloadConfig
from repro.telemetry import (
    BUCKETS,
    SF,
    SI,
    SPAN_F_FIELDS,
    SPAN_I_FIELDS,
    FlightRecorder,
    StageTimers,
    decompose,
    rate_threshold,
    reconstruct,
    sample_mask,
    tail_attribution,
)
from repro.telemetry.attribution import (
    B_BOUNCE,
    B_INFLATION,
    B_QUEUE,
    B_RETRY,
    B_SERVICE,
)

SCFG = ScenarioConfig(n_epochs=6, epoch_ops=256, n_records=512,
                      value_dim=2, seed=3)


def _ccfg(**kw):
    base = dict(num_nodes=8, num_ranges=32, replication=2, r_max=4,
                n_clients=16, report_every=2,
                imbalance_threshold=1.1, max_moves_per_round=6)
    base.update(kw)
    return ClusterConfig(**base)


def _run(scen_name, pol, tel, *, scen_kw=None, ccfg_kw=None, pol_cfg=None,
         scfg=SCFG, fused=True):
    scen = make_scenario(scen_name, scfg, **(scen_kw or {}))
    policy = make_policy(pol, pol_cfg) if pol_cfg else make_policy(pol)
    drv = EpochDriver(scen, policy, _ccfg(telemetry=tel, **(ccfg_kw or {})),
                      fused=fused)
    rows = drv.run()
    return drv, rows


@pytest.fixture(scope="module")
def traced_run():
    """One shared traced run for the export/profiler structure tests."""
    tel = TelemetryConfig(sample_rate=1 / 2, max_spans=64)
    return _run("shifting_hotspot", "full_adaptive", tel,
                scen_kw=dict(theta=1.2, shift_every=2))


# ---------------------------------------------------------------------------
# sampling: deterministic, PRNG-free, slot-capped but never silent
# ---------------------------------------------------------------------------


def test_sample_mask_deterministic_and_rate_extremes():
    import jax.numpy as jnp

    keys = jnp.arange(1000, dtype=jnp.uint32)
    thr = rate_threshold(0.25)
    m1 = np.asarray(sample_mask(keys, 3, thr))
    assert np.array_equal(m1, np.asarray(sample_mask(keys, 3, thr)))
    # rate 1 samples everything, rate 0 nothing
    assert np.asarray(sample_mask(keys, 3, rate_threshold(1.0))).all()
    assert not np.asarray(sample_mask(keys, 3, rate_threshold(0.0))).any()
    # the epoch term re-mixes: a different epoch samples a different set
    assert (m1 != np.asarray(sample_mask(keys, 4, thr))).any()
    # the hash is roughly uniform at this rate
    assert 0.15 < m1.mean() < 0.35
    with pytest.raises(ValueError):
        rate_threshold(1.5)
    with pytest.raises(ValueError):
        rate_threshold(-0.1)


def test_slot_cap_truncates_but_reports():
    tel = TelemetryConfig(sample_rate=1.0, max_spans=8)
    drv, rows = _run("stationary", "frozen", tel)
    s = drv.telemetry.summary()
    # rate 1.0: every query of every epoch is sampled...
    assert s["spans_sampled"] == SCFG.n_epochs * SCFG.epoch_ops
    # ...but only the first max_spans per epoch get slots
    assert s["spans"] == SCFG.n_epochs * 8
    for rec in drv.telemetry.epochs:
        assert rec["span_i"].shape == (8, len(SPAN_I_FIELDS))
        assert rec["span_f"].shape == (8, len(SPAN_F_FIELDS))
        assert (rec["span_i"][:, SI["qid"]] >= 0).all()   # every slot live
        assert rec["n_sampled"] == SCFG.epoch_ops
    assert drv.telemetry.verify_exact() == 0.0


# ---------------------------------------------------------------------------
# the pure-observer contract: off-mode bit-parity + one compiled step
# ---------------------------------------------------------------------------


def test_telemetry_off_on_bit_parity_single_trace():
    base_drv, base = _run("shifting_hotspot", "full_adaptive", None,
                          scen_kw=dict(theta=1.2, shift_every=2))
    tel = TelemetryConfig(sample_rate=1 / 4)
    drv, rows = _run("shifting_hotspot", "full_adaptive", tel,
                     scen_kw=dict(theta=1.2, shift_every=2))
    assert [r.to_row() for r in base] == [r.to_row() for r in rows]
    assert base_drv.traces == 1
    assert drv.traces == 1            # tracing adds no second program
    assert drv.telemetry.span_count > 0
    assert drv.telemetry.verify_exact() == 0.0
    # the off-mode driver carries no recorder at all
    assert base_drv.telemetry is None


def test_telemetry_parity_with_overload_plane():
    """Same contract with the admission/retry plane in the loop — the
    span block reads the PRE-step overload state and must not perturb
    the queue dynamics."""
    ovl = OverloadConfig(queue_cap=24, service_rate=40, inflation=3.0,
                         max_level=3, backoff_base=1, jitter_span=2,
                         queue_weight=2)
    kw = dict(ccfg_kw=dict(overload=ovl, standby_nodes=(6, 7),
                           num_ranges=16),
              pol_cfg=PolicyConfig(scale_patience=1))
    base_drv, base = _run("retry_storm", "overload_adaptive", None, **kw)
    tel = TelemetryConfig(sample_rate=1 / 2, max_spans=128)
    drv, rows = _run("retry_storm", "overload_adaptive", tel, **kw)
    assert [r.to_row() for r in base] == [r.to_row() for r in rows]
    assert drv.traces == 1
    assert drv.telemetry.verify_exact() == 0.0


# ---------------------------------------------------------------------------
# exact reconstruction: the property-test matrix over fail/park/bounce
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_reconstruction_exact_under_retry_storm(seed):
    """Park interleavings: admission defers, queue-full sheds, retry
    orbits — every sampled span must still reconstruct exactly, and the
    storm must actually produce rejected + orbiting spans to attribute."""
    scfg = dataclasses.replace(SCFG, seed=seed, n_epochs=8)
    # service below the per-node epoch share: queues STAND across epochs,
    # so sampled spans see nonzero entry depth (service_rate >= queue_cap
    # would drain fully between epochs and every pre-epoch depth reads 0)
    ovl = OverloadConfig(queue_cap=48, service_rate=24, inflation=3.0,
                         max_level=3, backoff_base=1, jitter_span=2,
                         queue_weight=2)
    tel = TelemetryConfig(sample_rate=1 / 2, max_spans=256)
    drv, rows = _run("retry_storm", "overload_adaptive", tel, scfg=scfg,
                     ccfg_kw=dict(overload=ovl, standby_nodes=(6, 7),
                                  num_ranges=16),
                     pol_cfg=PolicyConfig(scale_patience=1))
    assert drv.telemetry.span_count > 0
    assert drv.telemetry.verify_exact() == 0.0
    si = np.concatenate([r["span_i"] for r in drv.telemetry.epochs])
    comps = drv.telemetry.all_comps()
    lat = drv.telemetry.all_latency()
    rejected = np.isin(si[:, SI["outcome"]], (1, 2))
    assert rejected.any(), "storm produced no deferred/shed spans"
    # a rejected span's whole latency is retry-storm cost, nothing else
    assert np.array_equal(comps[rejected, B_RETRY], lat[rejected])
    assert (comps[rejected][:, [B_QUEUE, B_INFLATION, B_BOUNCE,
                                B_SERVICE]] == 0.0).all()
    # queue pressure showed up in the recorded entry state
    assert (si[:, SI["queue_depth"]] > 0).any()


def test_reconstruction_exact_under_rack_failure():
    """Fail interleavings: a rack dies mid-run, chains splice, traffic
    piles onto survivors — reconstruction stays exact through it."""
    tel = TelemetryConfig(sample_rate=1 / 2, max_spans=128)
    drv, rows = _run("rack_failure_hotspot", "migrate", tel,
                     scen_kw=dict(fail_epoch=2, rack=(0, 1),
                                  recover_epoch=4))
    assert any("rack_fail" in e for r in rows for e in r.events)
    assert drv.telemetry.span_count > 0
    assert drv.telemetry.verify_exact() == 0.0


def test_reconstruction_exact_under_craq_bounces():
    """Bounce interleavings: CRAQ dirty reads detour through the version
    check + tail link; the bounce bucket must carry exactly that."""
    tel = TelemetryConfig(sample_rate=1.0, max_spans=SCFG.epoch_ops)
    drv, rows = _run("ycsb_a", "frozen", tel,
                     ccfg_kw=dict(replication_mode="craq"))
    assert sum(r.dirty_reads for r in rows) > 0
    si = np.concatenate([r["span_i"] for r in drv.telemetry.epochs])
    comps = drv.telemetry.all_comps()
    bounced = si[:, SI["bounced"]] == 1
    assert bounced.any(), "craq writes produced no sampled bounces"
    model = drv.cfg.latency
    expected = float(np.float32(model.lookup)) + float(np.float32(model.link))
    assert np.allclose(comps[bounced, B_BOUNCE], expected)
    assert (comps[~bounced, B_BOUNCE] == 0.0).all()
    assert drv.telemetry.verify_exact() == 0.0


def test_decompose_reconstruct_synthetic_rows():
    """Unit-level: hand-built spans hit each bucket exactly."""
    model = LatencyModel()
    link = float(np.float32(model.link))
    lookup = float(np.float32(model.lookup))
    n = 4
    si = np.full((n, len(SPAN_I_FIELDS)), -1, np.int32)
    sf = np.zeros((n, len(SPAN_F_FIELDS)), np.float32)
    si[:, SI["outcome"]] = (0, 0, 0, 2)
    si[:, SI["bounced"]] = (0, 0, 1, 0)
    #                      svc_total          links  svc_store        svc_base scale
    sf[0] = (10.0, 4.0, 10.0, 10.0, 1.0)            # plain admitted
    sf[1] = (30.0, 4.0, 30.0, 10.0, 3.0)            # 3x inflated
    sf[2] = (12.0 + lookup, 6.0, 12.0, 12.0, 1.0)   # craq bounce
    sf[3] = (0.0, 1.0, 0.0, 0.0, 1.0)               # shed: one-link NACK
    lat = np.array([20.0, 40.0, 25.0, 50.0])
    comps = decompose(si, sf, lat, model)
    assert comps.shape == (n, len(BUCKETS))
    np.testing.assert_array_equal(reconstruct(comps), lat)
    assert comps[0, B_QUEUE] == 6.0 and comps[0, B_SERVICE] == 14.0
    assert comps[1, B_INFLATION] == 20.0
    assert comps[2, B_BOUNCE] == lookup + link
    assert (comps[3] == (0, 0, 0, 50.0, 0)).all()


def test_tail_attribution_shares():
    rng = np.random.default_rng(11)
    lat = rng.exponential(40.0, 500)
    # decompose-shaped components: queue residual + flat service
    comps = np.zeros((500, len(BUCKETS)))
    comps[:, B_SERVICE] = 10.0
    comps[:, B_QUEUE] = lat - 10.0
    out = tail_attribution(lat, comps, q=99.0)
    assert out["n"] == 500 and out["n_tail"] >= 1
    assert out["threshold"] == pytest.approx(np.percentile(lat, 99.0))
    assert sum(out["share"].values()) == pytest.approx(1.0)
    assert sum(out["share_overall"].values()) == pytest.approx(1.0)
    assert out["mass"]["queue"] > out["mass"]["service"]  # tail is queueing
    empty = tail_attribution(np.zeros(0), np.zeros((0, len(BUCKETS))))
    assert empty["n"] == 0 and empty["mass"] == {}


# ---------------------------------------------------------------------------
# satellites: masked_p99 vectorization, row round-trip, summarize order
# ---------------------------------------------------------------------------


def test_masked_p99_batch_matches_loop_bitwise():
    rng = np.random.default_rng(7)
    lat = rng.exponential(50.0, size=(13, 257))
    mask = rng.random((13, 257)) < rng.random((13, 1))
    mask[3] = False                       # empty row -> 0.0
    mask[4] = True                        # full row
    mask[5] = False
    mask[5, 17] = True                    # single-element row
    np.testing.assert_array_equal(masked_p99_batch(lat, mask),
                                  masked_p99_batch_loop(lat, mask))
    assert masked_p99_batch(lat, mask)[3] == 0.0
    assert masked_p99_batch(lat, mask)[5] == lat[5, 17]
    # zero-width matrix
    np.testing.assert_array_equal(
        masked_p99_batch(np.zeros((3, 0)), np.zeros((3, 0), bool)),
        np.zeros(3))
    with pytest.raises(ValueError):
        masked_p99_batch(lat, mask[:, :5])


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_masked_p99_batch_property(seed):
    rng = np.random.default_rng(seed)
    P, B = rng.integers(1, 9), rng.integers(1, 400)
    lat = rng.lognormal(3.0, 1.0, size=(P, B))
    mask = rng.random((P, B)) < rng.random()
    np.testing.assert_array_equal(masked_p99_batch(lat, mask),
                                  masked_p99_batch_loop(lat, mask))


def test_epoch_metrics_row_round_trip():
    m = EpochMetrics(epoch=3, scenario="s", policy="p", ops=10,
                     throughput=1.5, p50=1.0, p99=2.0, makespan=9.0,
                     imbalance=1.2, cov=0.3, migration_entries=5,
                     migration_bytes=100, drops=1, retries=2,
                     compiled_steps=1, events=["rack_fail:0+1"], deferred=1,
                     shed=2, requeued=3, lost=0, queue_peak=7, p999=3.25,
                     read_p99=2.5, clean_read_p99=2.4, dirty_reads=4,
                     replication="craq")
    row = m.to_row()
    assert EpochMetrics.from_row(row) == m
    # survives an actual JSON round trip (the bench artifact path)
    assert EpochMetrics.from_row(json.loads(json.dumps(row))) == m
    # events list is copied, not aliased
    assert EpochMetrics.from_row(row).events is not row["events"]


def test_summarize_key_order_and_uniqueness():
    m = EpochMetrics(epoch=0, scenario="s", policy="p", ops=1,
                     throughput=1.0, p50=1.0, p99=2.0, makespan=1.0,
                     imbalance=1.0, cov=0.0, migration_entries=0,
                     migration_bytes=0, drops=0, retries=0,
                     compiled_steps=1, p999=7.5)
    s = summarize([m])
    keys = list(s)
    assert len(keys) == len(set(keys))
    # the duplicate-key fix: max_p999 sits beside mean_p999, not stranded
    assert keys.index("max_p999") == keys.index("mean_p999") + 1
    assert s["max_p999"] == 7.5


# ---------------------------------------------------------------------------
# exports: span trees + Chrome trace
# ---------------------------------------------------------------------------


def test_span_tree_structure(traced_run):
    drv, _ = traced_run
    rec = next(r for r in drv.telemetry.epochs if r["span_i"].shape[0] > 0)
    from repro.telemetry import span_tree

    tree = span_tree(rec, 0, drv.cfg.latency)
    for key in ("epoch", "qid", "key", "op", "target", "chain", "outcome",
                "start", "latency", "components", "hops"):
        assert key in tree
    # components are the exact decomposition of this query's latency
    assert sum(tree["components"].values()) == pytest.approx(
        tree["latency"], abs=1e-9)
    assert set(tree["components"]) == set(BUCKETS)
    if tree["outcome"] == "admitted" or tree["outcome"] == "n/a":
        assert any(h["kind"] == "service" for h in tree["hops"])
    json.dumps(tree)                      # JSON-serializable as-is


def test_chrome_trace_and_jsonl_exports(traced_run, tmp_path):
    drv, _ = traced_run
    n_spans = drv.telemetry.span_count
    trace = drv.telemetry.chrome_trace()
    events = trace["traceEvents"]
    roots = [e for e in events if e["cat"] == "query"]
    assert len(roots) == n_spans
    assert len(events) > n_spans          # hop child slices exist
    assert all(e["ph"] == "X" for e in events)
    assert all(e["dur"] >= 0 for e in events)
    # epochs are laid end to end: per-epoch min root ts is nondecreasing
    by_epoch = {}
    for e in roots:
        ep = e["args"]["epoch"]
        by_epoch[ep] = min(by_epoch.get(ep, np.inf), e["ts"])
    eps = sorted(by_epoch)
    assert all(by_epoch[a] <= by_epoch[b] for a, b in zip(eps, eps[1:]))

    path = drv.telemetry.write_chrome_trace(str(tmp_path / "trace.json"))
    assert json.load(open(path))["otherData"]["scenario"] == "shifting_hotspot"
    jpath = drv.telemetry.write_jsonl(str(tmp_path / "spans.jsonl"))
    lines = [json.loads(l) for l in open(jpath)]
    assert len(lines) == n_spans


# ---------------------------------------------------------------------------
# profiler: stage timers and counters
# ---------------------------------------------------------------------------


def test_stage_timers_unit():
    t = StageTimers(enabled=True)
    with t.stage("a"):
        pass
    with t.stage("a"):
        pass
    with t.stage("b"):
        pass
    s = t.summary()
    assert s["stage_calls"] == {"a": 2, "b": 1}
    assert s["total_s"] >= 0.0
    assert sum(s["stage_share"].values()) == pytest.approx(1.0, abs=1e-3)
    off = StageTimers(enabled=False)
    with off.stage("a"):
        pass
    assert off.summary()["stage_calls"] == {}


def test_driver_stage_timers_fire(traced_run):
    drv, _ = traced_run
    calls = drv.telemetry.timers.calls
    for stage in ("inject", "route_apply", "des", "host_sync", "control",
                  "telemetry"):
        assert calls.get(stage, 0) > 0, f"stage {stage} never timed"
    # the recorder summary folds the timers in
    assert "stage_share" in drv.telemetry.summary()


def test_kernel_roofline_rejects_unknown_device_kind():
    """Peaks are keyed by device kind: a device with no entry (the CPU
    here) is an error, never silently priced as a v5e."""
    kind = jax.devices()[0].device_kind
    assert kind not in MESH.PEAKS
    with pytest.raises(ValueError, match="no peak table"):
        MESH.device_peaks(kind)
    assert MESH.device_peaks("TPU v5 lite") is MESH.PEAKS["TPU v5 lite"]


# ---------------------------------------------------------------------------
# flight recorder: bounded ring, dedupe, breach dumps
# ---------------------------------------------------------------------------


def test_flight_recorder_ring_and_dedupe(tmp_path):
    fr = FlightRecorder(3, str(tmp_path), tag="t")
    for i in range(10):
        fr.record({"epoch": i, "arr": np.arange(2), "f": np.float32(1.5)})
    assert len(fr.ring) == 3                       # bounded
    assert [e["epoch"] for e in fr.ring] == [7, 8, 9]
    p1 = fr.dump("slo_p999:epoch 9")
    assert p1 and json.load(open(p1))["epochs_recorded"] == 3
    # same reason kind -> deduped; new kind -> new artifact; force wins
    assert fr.dump("slo_p999:epoch 10") is None
    assert fr.dump("conservation:gap 2") is not None
    assert fr.dump("slo_p999:epoch 11", force=True) is not None
    assert len(fr.dumps) == 3
    # numpy payloads were made JSON-safe at record time
    assert json.load(open(p1))["epochs"][0]["arr"] == [0, 1]


def test_flight_dump_dedup_across_mixed_reasons(tmp_path):
    # interleaved breach kinds each dump exactly once — the dedup key is
    # the kind prefix, not the full reason, and kinds don't shadow each
    # other no matter the arrival order
    fr = FlightRecorder(4, str(tmp_path), tag="mix")
    fr.record({"epoch": 0})
    seq = ["slo_p999:epoch 1", "conservation:gap 3", "slo_p999:epoch 2",
           "slo_burn:p999_fleet:epoch 2", "conservation:gap 4",
           "slo_burn:p999_fleet:epoch 3", "slo_p999:epoch 5"]
    paths = [fr.dump(r) for r in seq]
    assert [p is not None for p in paths] == [
        True, True, False, True, False, False, False]
    assert len(fr.dumps) == 3
    kinds = [json.load(open(p))["reason"].split(":", 1)[0]
             for p in fr.dumps]
    assert kinds == ["slo_p999", "conservation", "slo_burn"]
    # artifacts are distinct files, numbered in dump order
    assert len(set(fr.dumps)) == 3


def test_flight_ring_wrap_at_exactly_window(tmp_path):
    # epoch window boundary: after exactly `window` records the ring is
    # full but nothing has been evicted; record window+1 and the oldest
    # entry (and only it) falls out
    w = 5
    fr = FlightRecorder(w, str(tmp_path), tag="wrap")
    for i in range(w):
        fr.record({"epoch": i})
    assert [e["epoch"] for e in fr.ring] == list(range(w))
    p_full = fr.dump("at_window:full")
    assert json.load(open(p_full))["epochs_recorded"] == w
    fr.record({"epoch": w})
    assert len(fr.ring) == w
    assert [e["epoch"] for e in fr.ring] == list(range(1, w + 1))
    p_wrap = fr.dump("post_wrap:one past")
    assert json.load(open(p_wrap))["epochs"][0]["epoch"] == 1


def test_masked_p99_batch_all_masked_row():
    # an entirely masked-out matrix: every row reports 0.0, bitwise equal
    # to the per-row loop oracle, and the +inf padding never leaks a
    # warning or a NaN through the discarded lanes
    lat = np.linspace(1.0, 2.0, 4 * 8).reshape(4, 8)
    mask = np.zeros((4, 8), bool)
    with np.errstate(invalid="raise", over="raise"):
        got = masked_p99_batch(lat, mask)
    np.testing.assert_array_equal(got, np.zeros(4))
    np.testing.assert_array_equal(got, masked_p99_batch_loop(lat, mask))
    # one live row among all-masked rows keeps its exact percentile
    mask[2, :3] = True
    got2 = masked_p99_batch(lat, mask)
    np.testing.assert_array_equal(
        got2, masked_p99_batch_loop(lat, mask))
    assert got2[2] == np.percentile(lat[2, :3], 99)
    assert got2[0] == got2[1] == got2[3] == 0.0


def test_slo_breach_dumps_flight_ring(tmp_path):
    tel = TelemetryConfig(sample_rate=1 / 4, slo_p999=1e-3,
                          flight_dir=str(tmp_path), flight_epochs=4)
    drv, rows = _run("stationary", "frozen", tel)
    assert rows[0].p999 > 1e-3                     # the breach is real
    assert drv.telemetry.breaches
    assert len(drv.telemetry.flight.dumps) == 1    # deduped per kind
    data = json.load(open(drv.telemetry.flight.dumps[0]))
    assert data["reason"].startswith("slo_p999")
    assert 1 <= len(data["epochs"]) <= 4
    entry = data["epochs"][0]
    assert "metrics" in entry and "spans" in entry and "state" in entry
