"""Overload survival (PR 6): bounded admission queues, retry storms,
backpressure, autoscale — `repro.overload` end to end.

Pins the tentpole contracts:

* **conservation** — every injected query is admitted, deferred, lost,
  or still in the retry backlog: ``conservation_gap == 0`` on every
  driver run, every backend, every interleaving;
* **bit-compat off** — ``overload=None`` drivers produce the same rows
  as before the subsystem existed (the existing parity/gate tests pin
  that globally; here we pin the zero-valued overload columns);
* **fused ≡ per-epoch with overload ON** — metrics rows *and* the final
  ``OverloadState`` pytree match bit for bit;
* **one compiled program** — the overload plane rides the fused scan
  without adding a trace; pool growth (``split_overflow``) recompiles
  exactly once per growth event (``traces == 1 + growth_events``);
* **queue-aware routing parity** — the `route_load_aware(queue_pen=)`
  effective-load fold equals the kernel ops-layer fold bit for bit;
* **control plane** — AIMD admission direction, retry budgeting,
  standby autoscale up/down, cadence-scaled budgets (S2).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core as C
from repro import overload as OVL
from repro.cluster import (
    ClusterConfig,
    EpochDriver,
    ScenarioConfig,
    make_policy,
    make_scenario,
)
from repro.cluster.policies import OverloadAdaptivePolicy, PolicyConfig
from repro.core import keys as K
from repro.core.coordination import plan_hops
from repro.core.dist_store import make_mesh
from repro.core.stats import StatsReport

SCFG = ScenarioConfig(n_epochs=6, epoch_ops=256, n_records=512,
                      value_dim=2, seed=3)
OCFG = OVL.OverloadConfig(queue_cap=32, service_rate=24, inflation=3.0,
                          max_level=3, queue_weight=2)


def _ccfg(**kw):
    kw.setdefault("num_nodes", 6)
    kw.setdefault("num_ranges", 12)
    kw.setdefault("report_every", 2)
    return ClusterConfig(**kw)


# ---------------------------------------------------------------------------
# state-level dynamics
# ---------------------------------------------------------------------------

def _drive(cfg, n_nodes, batches, admit_prob=None, retry_budget=None,
           seed=0):
    """Feed a list of (B,) target arrays through OVL.step; return the
    final state and stacked stats."""
    st = OVL.make_state(n_nodes, cfg)
    if admit_prob is not None:
        st = dataclasses.replace(
            st, admit_prob=jnp.asarray(admit_prob, jnp.float32))
    if retry_budget is not None:
        st = dataclasses.replace(
            st, retry_budget=jnp.asarray(retry_budget, jnp.int32))
    rng = jax.random.PRNGKey(seed)
    step = jax.jit(OVL.step, static_argnums=(3,))
    rows = []
    for i, t in enumerate(batches):
        st, rej, scale, outcome, stats = step(
            st, jnp.asarray(t, jnp.int32), jax.random.fold_in(rng, i), cfg)
        rows.append(np.asarray(stats))
    return st, np.stack(rows)


def test_conservation_random_streams():
    rng = np.random.default_rng(0)
    for trial in range(3):
        n = int(rng.integers(2, 7))
        cfg = OVL.OverloadConfig(
            queue_cap=int(rng.integers(4, 40)),
            service_rate=int(rng.integers(2, 30)),
            max_level=int(rng.integers(1, 5)),
            backoff_base=int(rng.integers(1, 3)),
            jitter_span=int(rng.integers(0, 3)),
        )
        batches = [rng.integers(-1, n, size=64) for _ in range(12)]
        st, rows = _drive(cfg, n, batches, seed=trial)
        assert OVL.conservation_gap(st) == 0, (trial, OVL.summary(st))
        # per-epoch stats are consistent with the lifetime counters
        s = OVL.summary(st)
        assert rows[:, 0].sum() == s["injected"]
        assert rows[:, 5].sum() == s["lost"]


def test_negative_targets_outside_the_plane():
    cfg = OVL.OverloadConfig(queue_cap=8, service_rate=4)
    st, rows = _drive(cfg, 4, [np.full(32, -1)])
    assert OVL.summary(st)["injected"] == 0
    assert rows[0].sum() == 0


def test_closed_admission_defers_everything():
    cfg = OVL.OverloadConfig(queue_cap=8, service_rate=4)
    st, rows = _drive(cfg, 2, [np.zeros(32, np.int64)] * 3,
                      admit_prob=np.zeros(2))
    s = OVL.summary(st)
    assert s["deferred"] == s["injected"] == 96
    assert s["admitted"] == s["shed"] == 0


def test_overrun_sheds_then_loses():
    """A single node hammered far past capacity escalates retries through
    every backoff level and eventually loses queries out the top."""
    cfg = OVL.OverloadConfig(queue_cap=4, service_rate=1, max_level=2,
                             backoff_base=1, jitter_span=0)
    batches = [np.zeros(64, np.int64) for _ in range(10)]
    st, rows = _drive(cfg, 2, batches)
    s = OVL.summary(st)
    assert s["shed"] > 0
    assert s["lost"] > 0          # level-2 re-sheds escape
    assert OVL.conservation_gap(st) == 0
    # node 1 was never targeted: its registers stay empty
    assert int(np.asarray(st.queue)[1]) == 0
    assert int(np.asarray(st.retry)[1].sum()) == 0


def test_retry_budget_caps_reentry():
    """With a huge shed backlog, the per-epoch requeue rate is bounded by
    retry_budget (the storm smoother)."""
    cfg = OVL.OverloadConfig(queue_cap=64, service_rate=64, max_level=4,
                             backoff_base=1, jitter_span=0)
    # epoch 0: flood one node to build a backlog; later epochs: no new
    # arrivals, watch the drain rate
    batches = [np.zeros(256, np.int64)] + [np.full(256, -1)] * 6
    st, rows = _drive(cfg, 2, batches, retry_budget=np.full(2, 5))
    assert rows[1:, 4].max() <= 5          # requeued <= budget each epoch
    assert OVL.conservation_gap(st) == 0


def test_service_scale_inflates_with_occupancy():
    cfg = OVL.OverloadConfig(queue_cap=10, service_rate=2, inflation=3.0)
    st = OVL.make_state(1, cfg)
    rng = jax.random.PRNGKey(0)
    st, _, scale0, _, _ = OVL.step(st, jnp.zeros(8, jnp.int32), rng, cfg)
    # queue now non-empty -> next epoch's admitted queries pay more
    st, _, scale1, _, _ = OVL.step(st, jnp.zeros(8, jnp.int32), rng, cfg)
    assert float(np.asarray(scale0).max()) == pytest.approx(1.0)
    assert float(np.asarray(scale1).max()) > 1.0


# ---------------------------------------------------------------------------
# hop-plan integration
# ---------------------------------------------------------------------------

def test_plan_hops_shed_and_scale():
    lat = C.LatencyModel()
    d = C.make_directory(8, 4, 2)
    keys = jnp.arange(16, dtype=jnp.uint32) * 1000 + 5
    q = C.make_queries(keys, jnp.full((16,), C.OP_GET), value_dim=2)
    dec, d = C.route(d, q)
    rng = jax.random.PRNGKey(1)
    base = plan_hops(q, dec, "in_switch", lat, rng=rng, num_nodes=4)
    shed = jnp.zeros((16,), bool).at[3].set(True)
    scale = jnp.ones((16,), jnp.float32).at[5].set(4.0)
    p = plan_hops(q, dec, "in_switch", lat, rng=rng, num_nodes=4,
                  shed=shed, service_scale=scale)
    # shed query: no node visits, zero storage service, minimal links
    from repro.core.coordination import NO_HOP
    assert int(np.asarray(p.nodes)[3].max()) == NO_HOP
    assert float(np.asarray(p.service)[3].sum()) == 0.0
    assert (float(np.asarray(p.reply_links)[3])
            <= float(np.asarray(base.reply_links)[3]))
    # scaled query: service inflated exactly 4x, others untouched
    assert np.allclose(np.asarray(p.service)[5],
                       np.asarray(base.service)[5] * 4.0)
    mask = np.ones(16, bool)
    mask[[3, 5]] = False
    assert np.array_equal(np.asarray(p.service)[mask],
                          np.asarray(base.service)[mask])
    # no-kwargs call is the old function bit for bit
    again = plan_hops(q, dec, "in_switch", lat, rng=rng, num_nodes=4)
    for fld in ("nodes", "service", "reply_links"):
        assert np.array_equal(np.asarray(getattr(again, fld)),
                              np.asarray(getattr(base, fld)))


def test_queue_pen_routing_matches_kernel_fold():
    """routing.route_load_aware(queue_pen=) ≡ folding the penalty into
    load_reg before the kernel spread path — the parity the dist backend
    relies on."""
    from repro.core.routing import route_load_aware

    d = C.make_directory(16, 8, 3)
    rng0 = np.random.default_rng(7)
    keys = jnp.asarray(rng0.choice(2**32 - 2, 64, replace=False), jnp.uint32)
    q = C.make_queries(keys, jnp.full((64,), C.OP_GET), value_dim=2)
    load = jnp.asarray(rng0.integers(0, 50, 8), jnp.uint32)
    qpen = jnp.asarray(rng0.integers(0, 30, 8), jnp.uint32)
    rng = jax.random.PRNGKey(3)
    a, _, _ = route_load_aware(d, q, load, rng, queue_pen=qpen)
    b, _, _ = route_load_aware(d, q, load + qpen, rng)
    assert np.array_equal(np.asarray(a.target), np.asarray(b.target))
    # and queue_pen=None is exactly the plain call
    c, _, _ = route_load_aware(d, q, load, rng)
    c2, _, _ = route_load_aware(d, q, load, rng, queue_pen=None)
    assert np.array_equal(np.asarray(c.target), np.asarray(c2.target))


# ---------------------------------------------------------------------------
# driver integration
# ---------------------------------------------------------------------------

def _run(scen="cascade_failure", pol="overload_adaptive", ocfg=OCFG,
         fused=True, pcfg=None, scen_kw=None, **ccfg_kw):
    scen = make_scenario(scen, SCFG, **(scen_kw or {}))
    drv = EpochDriver(scen, make_policy(pol, pcfg),
                      _ccfg(overload=ocfg, **ccfg_kw), fused=fused)
    rows = drv.run()
    return drv, rows


def test_disabled_plane_reports_zeros():
    drv, rows = _run(ocfg=None, scen="shifting_hotspot", pol="full_adaptive")
    assert drv.ovl is None
    assert drv.overload_summary() == {}
    for r in rows:
        assert (r.deferred, r.shed, r.requeued, r.lost, r.queue_peak) \
            == (0, 0, 0, 0, 0)
    assert drv.traces == 1


def test_driver_conservation_and_traces():
    drv, rows = _run()
    assert drv.traces == 1                       # one program, overload on
    assert OVL.conservation_gap(drv.ovl) == 0
    s = drv.overload_summary()
    assert s["injected"] == sum(r.ops for r in rows)
    assert sum(r.shed for r in rows) == s["shed"]
    assert sum(r.lost for r in rows) == s["lost"]


@pytest.mark.parametrize("scen", ["cascade_failure", "retry_storm"])
def test_fused_matches_per_epoch_with_overload(scen):
    out = {}
    for fused in (False, True):
        drv, rows = _run(scen=scen, fused=fused)
        out[fused] = (drv, rows)
    (drv_r, rows_r), (drv_f, rows_f) = out[False], out[True]
    for a, b in zip(rows_r, rows_f):
        da, db = dataclasses.asdict(a), dataclasses.asdict(b)
        da.pop("compiled_steps"), db.pop("compiled_steps")
        assert da == db, f"metrics diverge at epoch {a.epoch}"
    for leaf_a, leaf_b in zip(jax.tree.leaves(drv_r.ovl),
                              jax.tree.leaves(drv_f.ovl)):
        assert np.array_equal(np.asarray(leaf_a), np.asarray(leaf_b))


def test_overload_survives_cascade_with_standby():
    """The headline closed loop: rack failure under load, AIMD sheds,
    standby capacity is recruited, nothing is permanently lost."""
    drv, rows = _run(
        num_nodes=8, standby_nodes=(6, 7),
        pcfg=PolicyConfig(scale_patience=1),
        scen_kw=dict(rack=(0, 1)),
    )
    evs = [e for r in rows for e in r.events]
    assert any(e.startswith("autoscale_up:") for e in evs)
    assert drv.controller.standby == set() or len(drv.controller.standby) < 2
    assert drv.overload_summary()["lost"] == 0
    assert OVL.conservation_gap(drv.ovl) == 0
    # admission control actually bit: some node's probability came down
    assert float(np.asarray(drv.ovl.admit_prob).min()) < 1.0


def test_autoscale_down_parks_idle_capacity():
    """Light load + empty backlog parks the least-loaded node back into
    the reserve, draining its data through the repair path."""
    ocfg = OVL.OverloadConfig(queue_cap=4096, service_rate=4096)
    drv, rows = _run(
        scen="stationary", ocfg=ocfg, num_nodes=6,
        pcfg=PolicyConfig(scale_patience=1, min_serving=2),
    )
    evs = [e for r in rows for e in r.events]
    assert any(e.startswith("autoscale_down:") for e in evs)
    assert len(drv.controller.standby) >= 1
    # parked nodes serve nothing and head no chains
    d = drv.controller.directory()
    chains = np.asarray(d.chains)
    clen = np.asarray(d.chain_len)
    for node in drv.controller.standby:
        for i in range(chains.shape[0]):
            assert node not in chains[i][: clen[i]]


def test_standby_nodes_start_parked():
    scen = make_scenario("stationary", SCFG)
    drv = EpochDriver(scen, make_policy("overload_adaptive"),
                      _ccfg(overload=OCFG, num_nodes=8,
                            standby_nodes=(5, 6, 7)))
    # at construction the reserve is parked and heads nothing
    assert drv.controller.standby == {5, 6, 7}
    d0 = drv.controller.directory()
    chains, clen = np.asarray(d0.chains), np.asarray(d0.chain_len)
    live = {int(n) for i in range(chains.shape[0])
            for n in chains[i][: clen[i]]}
    assert not (live & {5, 6, 7})
    # the run may recruit them (that's the point of a reserve), but the
    # plane stays conserved throughout
    drv.run()
    assert OVL.conservation_gap(drv.ovl) == 0


# ---------------------------------------------------------------------------
# control plane units
# ---------------------------------------------------------------------------

def _report(n=4, depth=None, load=None, **kw):
    kw.setdefault("queue_limit", 32)
    kw.setdefault("service_limit", 24)
    return StatsReport(
        read_count=np.zeros(8), write_count=np.zeros(8),
        node_load=np.asarray(load if load is not None else np.ones(n)),
        period=1,
        queue_depth=np.asarray(depth if depth is not None else np.zeros(n)),
        retry_backlog=np.zeros(n, np.int64), **kw,
    )


def test_aimd_admission_direction():
    pol = OverloadAdaptivePolicy(PolicyConfig())
    ctl = C.Controller(C.make_directory(8, 4, 2))
    cfg = pol.config
    # hot node 0 -> multiplicative cut; cold nodes recover toward 1.0
    pol._backpressure(ctl, _report(depth=np.array([32, 0, 0, 0])))
    ap1 = pol.admit_prob.copy()
    assert ap1[0] == pytest.approx(cfg.admit_decrease)
    assert np.all(ap1[1:] == 1.0)
    pol._backpressure(ctl, _report(depth=np.array([32, 0, 0, 0])))
    ap2 = pol.admit_prob.copy()
    assert ap2[0] == pytest.approx(
        max(cfg.admit_floor, ap1[0] * cfg.admit_decrease))
    # cooled off -> additive recovery, clipped at 1.0
    pol._backpressure(ctl, _report(depth=np.zeros(4)))
    assert pol.admit_prob[0] == pytest.approx(
        ap2[0] + cfg.admit_increase)
    # budget follows the service rate
    assert pol.retry_budget[0] == max(
        1, int(cfg.retry_frac * 24))


def test_backpressure_noop_without_plane():
    pol = OverloadAdaptivePolicy(PolicyConfig())
    ctl = C.Controller(C.make_directory(8, 4, 2))
    ops = pol._backpressure(ctl, _report(queue_limit=0))
    assert ops == [] and pol.admit_prob is None


def test_budget_scale_multiplies_move_budget():
    """S2: a k-x-longer auto period grants k rounds of migration budget
    (scale 1.0 is bit-identical to the unscaled loop)."""
    rng = np.random.default_rng(0)
    load = rng.permutation(np.arange(8, dtype=np.float64) * 100)

    def moves(scale):
        d = C.make_directory(64, 8, 2)
        ctl = C.Controller(d, C.ControllerConfig(
            imbalance_threshold=1.01, max_moves_per_round=2))
        rep = StatsReport(
            read_count=rng.integers(1, 100, 64).astype(np.float64),
            write_count=np.zeros(64), node_load=load.copy(),
            period=1, budget_scale=scale,
        )
        return len(ctl.balance(rep))

    assert moves(1.0) <= 2
    assert moves(4.0) > moves(1.0)


def test_auto_period_sets_budget_scale():
    ocfg = OVL.OverloadConfig(queue_cap=64, service_rate=64)
    scen = make_scenario("shifting_hotspot", SCFG)
    seen = []

    class Probe(OverloadAdaptivePolicy):
        def on_report(self, controller, report):
            seen.append(report.budget_scale)
            return super().on_report(controller, report)

    drv = EpochDriver(scen, Probe(),
                      _ccfg(overload=ocfg, report_every="auto",
                            auto_band=(2, 4)))
    drv.run()
    assert seen and all(s >= 1.0 for s in seen)
    # fixed-cadence drivers always report the neutral scale
    seen.clear()
    drv = EpochDriver(make_scenario("shifting_hotspot", SCFG), Probe(),
                      _ccfg(overload=ocfg, report_every=2))
    drv.run()
    assert seen and all(s == 1.0 for s in seen)


# ---------------------------------------------------------------------------
# S3: pool growth in the loop
# ---------------------------------------------------------------------------

def test_split_overflow_grows_pool_and_recompiles_once():
    scfg = ScenarioConfig(n_epochs=10, epoch_ops=512, n_records=2048,
                          read_ratio=0.3, value_dim=2)
    scen = make_scenario("keyspace_growth", scfg)
    drv = EpochDriver(
        scen, make_policy("full_adaptive"),
        ClusterConfig(num_nodes=4, num_ranges=8, n_slots=8, capacity=128,
                      split_overflow=True, report_every=2))
    rows = drv.run()
    evs = [e for r in rows for e in r.events]
    grows = [e for e in evs if e.startswith("grow_pool:")]
    assert grows, "pool never grew under capacity pressure"
    assert drv.growth_events == len(grows)
    # the no-silent-retrace gate, growth-aware: exactly one compile per
    # scenario plus one per growth
    assert drv.traces == 1 + drv.growth_events
    assert drv.controller.num_slots > 8
    # overflow pressure was actually relieved by the splits: the final
    # directory serves every genesis range from live slots
    assert set(drv.controller.live_ranges())


def test_split_overflow_grows_pool_on_dist_backend():
    """PR 8 lifted the `split_overflow x dist` rejection: the dist
    programs re-specialize on the grown directory/repl shapes by
    themselves, so growth costs exactly one recompile there too
    (``traces == 1 + growth_events``) and the fused period program
    stays bit-identical to per-epoch stepping across the growth."""
    mesh = make_mesh((1,))
    scfg = ScenarioConfig(n_epochs=10, epoch_ops=512, n_records=2048,
                          read_ratio=0.3, value_dim=2)
    out = {}
    for fused in (False, True):
        scen = make_scenario("keyspace_growth", scfg)
        drv = EpochDriver(
            scen, make_policy("full_adaptive"),
            ClusterConfig(num_nodes=1, num_ranges=8, n_slots=8,
                          replication=1, r_max=2, capacity=128,
                          split_overflow=True, report_every=2),
            backend="dist", mesh=mesh, fused=fused)
        out[fused] = (drv, drv.run())
    (drv_r, rows_r), (drv_f, rows_f) = out[False], out[True]
    grows = [e for r in rows_f for e in r.events
             if e.startswith("grow_pool:")]
    assert grows, "pool never grew under capacity pressure"
    assert drv_f.growth_events == len(grows)
    assert drv_f.traces == 1 + drv_f.growth_events
    assert drv_r.traces == 1 + drv_r.growth_events
    for a, b in zip(rows_r, rows_f):
        assert dataclasses.asdict(a) == dataclasses.asdict(b), (
            f"dist growth metrics diverge at epoch {a.epoch}")
    assert np.array_equal(np.asarray(drv_r.store.keys),
                          np.asarray(drv_f.store.keys))


def test_scenario_registry_has_overload_stressors():
    from repro.cluster import SCENARIOS
    assert {"cascade_failure", "retry_storm"} <= set(SCENARIOS)
    cs = make_scenario("cascade_failure", SCFG, fail_epoch=2, rack=(0, 1))
    assert cs.events(2) == [("rack_fail", (0, 1))]
    assert cs.events(3) == []
    rs = make_scenario("retry_storm", SCFG, fail_epoch=1, recover_epoch=3,
                       rack=(2,))
    assert rs.events(1) == [("rack_fail", (2,))]
    assert rs.events(3) == [("recover", 2)]
