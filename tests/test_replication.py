"""The repro.replication subsystem: versioned chains + CRAQ apportioned reads.

Pins the tentpole contract:

* the ReplState register file advances per the protocol rounds (writes
  bump committed versions; the ack round clears everything committed
  before the epoch) and control events edit it conservatively (split
  children inherit, membership changes dirty the slot);
* the dirty-aware routing bounces exactly the dirty non-tail picks to the
  tail, bit-identically across the jnp path, the kernel oracle and the
  Pallas kernel, and collapses to route_load_aware when everything is
  clean;
* hop plans charge the bounce correctly (version-check lookup at the
  replica, full service at the tail, one extra link);
* **safety refinement** (hypothesis): against an independent write-id-SET
  model of CRAQ message passing, the uint-version implementation never
  serves a read locally from a replica the model says is missing a
  committed write — across random write interleavings, splits, widens,
  narrows and failure splices;
* the fused epoch driver runs chain/craq bit-identically to the
  per-epoch reference, compiles once, and donates the version/dirty
  buffers;
* the drift-adaptive pull cadence stays inside its band and still
  compiles once.
"""

import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core as C
from repro import replication as RPL
from repro.core import keys as K
from repro.core import routing as R
from repro.core.controller import Controller
from repro.cluster import (
    ClusterConfig,
    EpochDriver,
    ScenarioConfig,
    make_policy,
    make_scenario,
)

SCFG = ScenarioConfig(n_epochs=6, epoch_ops=256, n_records=512,
                      value_dim=2, seed=3, read_ratio=0.7)


def _ccfg(mode="craq", period=2, **kw):
    return ClusterConfig(num_nodes=8, num_ranges=32, replication=2, r_max=4,
                         n_clients=16, report_every=period,
                         imbalance_threshold=1.1, max_moves_per_round=6,
                         replication_mode=mode, **kw)


# ---------------------------------------------------------------------------
# register-file semantics
# ---------------------------------------------------------------------------


def test_advance_marks_written_slots_dirty_for_one_round():
    st = RPL.make_state(8, 3)
    assert not np.asarray(RPL.dirty_bits(st)).any()
    ridx = jnp.asarray([2, 2, 5, 1], jnp.int32)
    is_write = jnp.asarray([True, True, True, False])
    st1 = RPL.advance(st, ridx, is_write)
    v = np.asarray(st1.version)
    assert v[2] == 2 and v[5] == 1 and v[1] == 0
    d = np.asarray(RPL.dirty_bits(st1))
    assert d[2].all() and d[5].all() and not d[1].any()
    # the next ack round clears everything not re-written
    st2 = RPL.advance(st1, ridx, jnp.zeros((4,), bool))
    assert not np.asarray(RPL.dirty_bits(st2)).any()
    assert np.array_equal(np.asarray(st2.version), v)


def test_apply_events_inherit_merge_reset_kill_grow():
    st = RPL.ReplState(
        version=jnp.asarray([5, 0, 3, 0], jnp.uint32),
        acked=jnp.asarray([[5, 2], [0, 0], [3, 3], [0, 0]], jnp.uint32),
    )
    out = RPL.apply_events(st, [("inherit", 0, 1)])
    assert np.asarray(out.version)[1] == 5
    assert np.array_equal(np.asarray(out.acked)[1], [5, 2])

    out = RPL.apply_events(st, [("merge", 0, 2), ("kill", 0)])
    assert np.asarray(out.version)[2] == 5          # max(3, 5)
    assert np.asarray(out.acked)[2].max() == 0      # conservatively dirty
    assert np.asarray(out.version)[0] == 0

    out = RPL.apply_events(st, [("reset", 2)])
    assert np.asarray(out.acked)[2].max() == 0
    assert np.asarray(out.version)[2] == 3

    out = RPL.apply_events(st, [("grow", 6)])
    assert out.num_slots == 6
    assert np.asarray(out.version)[4:].max() == 0
    # empty journal is a no-op (same object)
    assert RPL.apply_events(st, []) is st


def test_controller_journals_membership_and_lineage_events():
    d = C.make_directory(8, 8, 2, r_max=4, n_slots=16)
    ctl = Controller(d)
    nl = np.zeros(8)
    ctl.widen_chain(0, nl)
    lo, hi = ctl.range_span(1)
    child = ctl.split_range(1, lo + (hi - lo) // 2)
    assert child is not None
    ctl.narrow_chain(0, 2)
    ctl.handle_node_failure(0)
    events = ctl.drain_repl_log()
    kinds = [e[0] for e in events]
    assert kinds.count("inherit") == 1
    assert ("inherit", 1, child) in events
    assert "reset" in kinds
    assert ctl.drain_repl_log() == []   # drained


def test_split_child_inherits_parent_dirty_state():
    d = C.make_directory(4, 8, 2, r_max=3, n_slots=8)
    ctl = Controller(d)
    st = RPL.make_state(8, 3)
    st = RPL.advance(st, jnp.asarray([1, 1], jnp.int32),
                     jnp.asarray([True, True]))
    lo, hi = ctl.range_span(1)
    child = ctl.split_range(1, (lo + hi) // 2)
    st = RPL.apply_events(st, ctl.drain_repl_log())
    assert np.asarray(st.version)[child] == np.asarray(st.version)[1] == 2
    d_bits = np.asarray(RPL.dirty_bits(st))
    assert d_bits[child].all() and d_bits[1].all()


# ---------------------------------------------------------------------------
# dirty-aware routing + hop planning
# ---------------------------------------------------------------------------


def _query_batch(B, seed=0, write_frac=0.3):
    rng = np.random.default_rng(seed)
    keys = jnp.asarray(rng.integers(0, 2**32 - 2, B), jnp.uint32)
    ops = jnp.asarray(
        np.where(rng.random(B) < write_frac, K.OP_PUT, K.OP_GET), jnp.int32
    )
    return C.make_queries(keys, ops, value_dim=2)


def test_dirty_routing_bounces_to_tail_only_when_dirty():
    d = C.make_directory(16, 8, 3, r_max=5, n_slots=24)
    q = _query_batch(256, seed=1)
    load = jnp.zeros((8,), jnp.uint32)
    rng = jax.random.PRNGKey(5)

    all_dirty = jnp.ones((24, 5), bool)
    dec, _, _, picked, bounced = R.route_load_aware_dirty(
        d, q, load, all_dirty, rng
    )
    tgt = np.asarray(dec.target)
    ch = np.asarray(dec.chain)
    cl = np.asarray(dec.chain_len)
    pk = np.asarray(picked)
    b = np.asarray(bounced)
    w = np.asarray(q.opcode) == K.OP_PUT
    assert not b[w].any()
    for i in np.where(~w)[0]:
        tail = ch[i, cl[i] - 1]
        if b[i]:
            assert tgt[i] == tail and pk[i] != tail
        else:
            # with everything dirty, an unbounced read picked the tail
            assert pk[i] == tail and tgt[i] == tail
    assert b.sum() > 0

    clean = jnp.zeros((24, 5), bool)
    dec0, _, _ = R.route_load_aware(d, q, load, rng)
    decC, _, _, pickedC, bouncedC = R.route_load_aware_dirty(
        d, q, load, clean, rng
    )
    assert np.array_equal(np.asarray(dec0.target), np.asarray(decC.target))
    assert not np.asarray(bouncedC).any()


def test_dirty_routing_kernel_parity():
    from repro.kernels.range_match.ops import range_match_spread_dirty

    d = C.make_directory(16, 8, 3, r_max=5, n_slots=24)
    rng0 = np.random.default_rng(0)
    q = _query_batch(300, seed=0)
    load = jnp.asarray(rng0.integers(0, 50, 8), jnp.uint32)
    dirty = jnp.asarray(rng0.random((24, 5)) < 0.4)
    rng = jax.random.PRNGKey(7)
    dec, _, _, picked, bounced = R.route_load_aware_dirty(
        d, q, load, dirty, rng
    )
    for use_pallas in (False, True):
        ridx, target, chain, pk, bc = range_match_spread_dirty(
            d, q.key, q.opcode, load, dirty, rng, use_pallas=use_pallas
        )
        assert np.array_equal(np.asarray(ridx), np.asarray(dec.ridx))
        assert np.array_equal(np.asarray(target), np.asarray(dec.target))
        assert np.array_equal(np.asarray(chain).T, np.asarray(dec.chain))
        assert np.array_equal(np.asarray(pk), np.asarray(picked))
        assert np.array_equal(np.asarray(bc), np.asarray(bounced))


def test_plan_hops_charges_the_bounce():
    d = C.make_directory(8, 8, 3, r_max=4)
    q = _query_batch(128, seed=2)
    load = jnp.zeros((8,), jnp.uint32)
    dec, _, _, picked, bounced = R.route_load_aware_dirty(
        d, q, load, jnp.ones((8, 4), bool), jax.random.PRNGKey(3)
    )
    model = C.LatencyModel()
    plan = C.plan_hops(q, dec, C.IN_SWITCH, model, rng=jax.random.PRNGKey(9),
                       num_nodes=8, read_via=picked, read_bounce=bounced)
    plain = C.plan_hops(q, dec, C.IN_SWITCH, model, rng=jax.random.PRNGKey(9),
                        num_nodes=8)
    nodes = np.asarray(plan.nodes)
    svc = np.asarray(plan.service)
    links = np.asarray(plan.reply_links)
    b = np.asarray(bounced)
    w = np.asarray(q.opcode) == K.OP_PUT
    assert b.any()
    # bounced reads: picked replica pays the version check, tail the read
    assert ((nodes[b] >= 0).sum(axis=1) == 2).all()
    assert np.allclose(svc[b][:, 0], model.lookup)
    assert np.allclose(svc[b][:, 1], model.service)
    assert np.allclose(links[b], 3.0 * model.link)
    assert (nodes[b][:, 0] == np.asarray(picked)[b]).all()
    assert (nodes[b][:, 1] == np.asarray(dec.target)[b]).all()
    # unbounced queries are planned exactly as without the arguments
    nb = ~b
    assert np.array_equal(nodes[nb], np.asarray(plain.nodes)[nb])
    assert np.array_equal(svc[nb], np.asarray(plain.service)[nb])
    assert np.array_equal(nodes[w], np.asarray(plain.nodes)[w])

    with pytest.raises(ValueError, match="together"):
        C.plan_hops(q, dec, C.IN_SWITCH, model, rng=jax.random.PRNGKey(9),
                    num_nodes=8, read_bounce=bounced)


# ---------------------------------------------------------------------------
# safety refinement (hypothesis): clean implies fully-known
# ---------------------------------------------------------------------------


def test_craq_never_serves_stale_hypothesis():
    """The uint-version dirty bits must be *conservative* against an
    independent set-of-write-ids model of CRAQ message passing.

    Model: every write gets a unique id; the tail commits it in the epoch
    it arrives; ack messages deliver one epoch later, teaching every
    member the commit set as of the epoch start; any chain-membership
    change wipes a member's knowledge; a split child's members know what
    the parent's members knew; a merge wipes the survivor's knowledge.
    Invariant: whenever the implementation calls (slot, position) clean,
    the model says that position knows EVERY committed write of the slot
    — so a locally-served read can never observe a missing commit.
    """
    pytest.importorskip("hypothesis", reason="property tests need hypothesis")
    from hypothesis import given, settings, strategies as st

    S, RMAX, N = 8, 3, 6

    op = st.one_of(
        st.tuples(st.just("epoch"),
                  st.lists(st.integers(0, S - 1), min_size=0, max_size=6)),
        st.tuples(st.just("split"), st.integers(0, S - 1)),
        st.tuples(st.just("widen"), st.integers(0, S - 1)),
        st.tuples(st.just("narrow"), st.integers(0, S - 1)),
        st.tuples(st.just("fail"), st.integers(0, N - 1)),
    )

    @settings(max_examples=20, deadline=None)
    @given(ops=st.lists(op, min_size=1, max_size=12))
    def run(ops):
        d = C.make_directory(4, N, 2, r_max=RMAX, n_slots=S)
        ctl = Controller(d)
        state = RPL.make_state(S, RMAX)
        committed = [set() for _ in range(S)]       # model: committed ids
        known = [[set() for _ in range(RMAX)] for _ in range(S)]
        next_id = 0

        def check():
            dirty = np.asarray(RPL.dirty_bits(state))
            for s in range(S):
                for j in range(RMAX):
                    if not dirty[s, j]:
                        assert known[s][j] >= committed[s], (
                            f"slot {s} pos {j} clean but model says it is "
                            f"missing {committed[s] - known[s][j]}"
                        )

        for kind, arg in ops:
            if kind == "epoch":
                writes = [s for s in arg if ctl.is_live(s)]
                # reads this epoch observe the pre-epoch state
                check()
                snapshot = [set(c) for c in committed]
                for s in writes:
                    committed[s].add(next_id)
                    next_id += 1
                # ack round: commits as of the epoch start are now known
                for s in range(S):
                    for j in range(RMAX):
                        known[s][j] = set(snapshot[s])
                ridx = jnp.asarray(writes if writes else [0], jnp.int32)
                is_w = jnp.asarray([True] * len(writes) if writes else [False])
                state = RPL.advance(state, ridx, is_w)
            else:
                if kind == "split" and ctl.is_live(arg):
                    lo, hi = ctl.range_span(arg)
                    if hi - lo >= 2:
                        ctl.split_range(arg, lo + (hi - lo) // 2)
                elif kind == "widen" and ctl.is_live(arg):
                    ctl.widen_chain(arg, np.zeros(N))
                elif kind == "narrow" and ctl.is_live(arg):
                    ctl.narrow_chain(arg, 2)
                elif kind == "fail" and arg not in ctl.failed:
                    if len(ctl.live_nodes()) > 2:
                        ctl.handle_node_failure(arg)
                # the journal is the ground truth of WHAT was reconfigured;
                # the model replays it at the write-id-set level while the
                # implementation replays it at the uint-version level —
                # the refinement must survive both replays
                events = ctl.drain_repl_log()
                for ev in events:
                    if ev[0] == "reset":
                        known[ev[1]] = [set() for _ in range(RMAX)]
                    elif ev[0] == "inherit":
                        p_, c_ = ev[1], ev[2]
                        committed[c_] = set(committed[p_])
                        known[c_] = [set(k) for k in known[p_]]
                    elif ev[0] == "merge":
                        c_, p_ = ev[1], ev[2]
                        committed[p_] |= committed[c_]
                        known[p_] = [set() for _ in range(RMAX)]
                    elif ev[0] == "kill":
                        committed[ev[1]] = set()
                        known[ev[1]] = [set() for _ in range(RMAX)]
                state = RPL.apply_events(state, events)
            check()

    run()


# ---------------------------------------------------------------------------
# driver integration
# ---------------------------------------------------------------------------


def _run_pair(mode, scen_name="shifting_hotspot", pol="full_adaptive",
              scen_kw=None, period=2):
    out = {}
    for fused in (False, True):
        scen = make_scenario(scen_name, SCFG,
                             **(scen_kw or dict(theta=1.2, shift_every=2)))
        drv = EpochDriver(scen, make_policy(pol), _ccfg(mode, period),
                          fused=fused)
        out[fused] = (drv, drv.run())
    return out


@pytest.mark.parametrize("mode", ["chain", "craq"])
def test_fused_bitident_replication_modes(mode):
    out = _run_pair(mode)
    (dr, rows_r), (df, rows_f) = out[False], out[True]
    for a, b in zip(rows_r, rows_f):
        assert dataclasses.asdict(a) == dataclasses.asdict(b), (
            f"{mode}: metrics diverge at epoch {a.epoch}")
    assert np.array_equal(np.asarray(dr.store.keys), np.asarray(df.store.keys))
    assert np.array_equal(np.asarray(dr.repl.version),
                          np.asarray(df.repl.version))
    assert np.array_equal(np.asarray(dr.repl.acked), np.asarray(df.repl.acked))
    assert df.traces == 1
    assert df.host_syncs < dr.host_syncs


def test_craq_bounces_under_writes_and_not_without():
    # write-bearing mix: the dirty window opens, some reads bounce
    scen = make_scenario("ycsb_a", SCFG)
    drv = EpochDriver(scen, make_policy("full_adaptive"), _ccfg("craq"))
    rows = drv.run()
    assert sum(r.dirty_reads for r in rows) > 0
    assert all(r.replication == "craq" for r in rows)
    assert drv.traces == 1
    # clean reads are a subset of reads: clean p99 <= read p99 per epoch
    for r in rows:
        if r.dirty_reads:
            assert r.clean_read_p99 <= r.read_p99 + 1e-9

    # read-only stream after the load phase: nothing is ever dirty
    ro = ScenarioConfig(n_epochs=4, epoch_ops=256, n_records=512,
                        value_dim=2, seed=3, read_ratio=1.0)
    scen = make_scenario("stationary", ro)
    drv = EpochDriver(scen, make_policy("replicate"), _ccfg("craq"))
    rows = drv.run()
    assert sum(r.dirty_reads for r in rows) == 0


def test_craq_read_only_matches_eventual_spread():
    """On a read-only stream the consistency choice is invisible: under
    the same spreading policy craq makes the identical p2c picks (same
    rng), never bounces (nothing is ever dirty), and the write-cap
    difference has no writes to act on — the whole EpochMetrics stream
    must match eventual's exactly, mode label aside."""
    ro = ScenarioConfig(n_epochs=4, epoch_ops=256, n_records=512,
                        value_dim=2, seed=3, read_ratio=1.0)
    rows = {}
    for mode in ("eventual", "craq"):
        scen = make_scenario("stationary", ro)
        drv = EpochDriver(scen, make_policy("replicate"), _ccfg(mode))
        rows[mode] = drv.run()
    for a, b in zip(rows["eventual"], rows["craq"]):
        da, db = dataclasses.asdict(a), dataclasses.asdict(b)
        da.pop("replication"), db.pop("replication")
        assert da == db, f"epoch {a.epoch} diverges"
    assert all(r.dirty_reads == 0 for r in rows["craq"])


def test_chain_mode_reads_at_tail_writes_full_chain():
    scen = make_scenario("ycsb_a", SCFG)
    drv = EpochDriver(scen, make_policy("replicate"), _ccfg("chain"))
    rows = drv.run()
    assert drv.traces == 1
    assert all(r.dirty_reads == 0 for r in rows)
    # version registers advanced (chain tracks commit versions too)
    assert int(np.asarray(drv.repl.version).sum()) > 0


def test_fused_scan_donates_replication_registers():
    scen = make_scenario("shifting_hotspot", SCFG, shift_every=2)
    drv = EpochDriver(scen, make_policy("frozen"), _ccfg("craq", period=3),
                      fused=True)
    version0, acked0 = drv.repl.version, drv.repl.acked
    keys0 = drv.store.keys
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        drv.run()
    donation_warnings = [
        str(w.message) for w in caught if "donat" in str(w.message).lower()
    ]
    assert donation_warnings == []
    assert version0.is_deleted() and acked0.is_deleted()
    assert keys0.is_deleted()
    assert drv.traces == 1


def test_auto_cadence_stays_in_band_and_compiles_once():
    scen = make_scenario("stationary", SCFG)
    cfg = _ccfg("craq", period=None)
    cfg = dataclasses.replace(cfg, report_every="auto", auto_band=(1, 4))
    drv = EpochDriver(scen, make_policy("full_adaptive"), cfg, fused=True)
    rows = drv.run()
    assert len(rows) == SCFG.n_epochs
    assert drv.traces == 1
    assert drv.period_history, "auto cadence never pulled"
    assert all(1 <= p <= 4 for p in drv.period_history)
    # a stationary workload must eventually relax the cadence — at a
    # batch size where per-period sampling noise sits under the drift
    # floor (tiny 256-op epochs are all noise, and staying tight there
    # is the right call); the spread path's drift signal differences out
    # the halved-register floor, so the decayed tail of earlier periods
    # cannot keep it pinned
    scfg2 = dataclasses.replace(SCFG, n_epochs=12, epoch_ops=1024)
    scen2 = make_scenario("stationary", scfg2)
    drv2 = EpochDriver(scen2, make_policy("full_adaptive"),
                       dataclasses.replace(cfg), fused=True)
    drv2.run()
    assert max(drv2.period_history) > 1
    assert drv2.traces == 1


def test_dist_craq_write_broadcast_matches_single_host():
    """Forced-8-device mesh (subprocess: jax pins the device count at
    first init): the dist craq data plane — dirty-aware routing inside
    the shard_map, write broadcast along the chain via the sequential
    all_to_all rounds — must leave the store bit-identical to the
    single-host ``apply_routed`` path, serve every read correctly even
    with dirty bits forcing tail bounces, and report the bounce mask."""
    import os
    import subprocess
    import sys
    import textwrap

    env = {
        **os.environ,
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "PYTHONPATH": os.path.join(os.path.dirname(__file__), "..", "src"),
    }
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro import core as C
        from repro.core.dist_store import make_mesh

        mesh = make_mesh((8,))
        d = C.make_directory(16, 8, 3, r_max=5)
        store = C.make_store(8, 64, 4)
        rng0 = np.random.default_rng(0)
        B = 64
        keys = jnp.asarray(rng0.integers(0, 2**32-2, B), jnp.uint32)
        vals = jnp.asarray(rng0.normal(size=(B,4)), jnp.float32)
        qput = C.make_queries(keys, jnp.full((B,), C.OP_PUT), vals)
        qget = C.make_queries(keys, jnp.full((B,), C.OP_GET), value_dim=4)
        dirty = jnp.asarray(rng0.random((16,5)) < 0.5)
        for strat in ("allgather", "bucket_a2a"):
            cfg = C.DistConfig(strategy=strat, bucket_cap=32,
                               read_spread=True, return_decision=True,
                               replication_mode="craq")
            apply_fn = C.make_dist_apply(mesh, d, cfg)
            load = jnp.zeros((8,), jnp.uint32)
            s1, _, d1, load, m = apply_fn(
                store, d, load, dirty, qput, jax.random.PRNGKey(1))
            s2, resp, d2, load, m = apply_fn(
                s1, d1, load, dirty, qget, jax.random.PRNGKey(2))
            # reads are all served (tail bounces included) with the data
            assert bool(resp.found.all()), strat
            assert bool(jnp.allclose(resp.value, vals, atol=1e-6)), strat
            # write broadcast left every chain member converged exactly
            # like the single-host oracle
            dec, dd = C.route(d, qput)
            so, _ = C.apply_routed(store, qput, dec)
            assert jnp.array_equal(jnp.sort(s1.keys, axis=1),
                                   jnp.sort(so.keys, axis=1)), strat
            assert (np.asarray(d1.write_count)
                    == np.asarray(dd.write_count)).all(), strat
            assert m["bounced"].shape == (B,), strat
            assert int(jnp.sum(m["bounced"])) > 0, strat
        # the dist epoch driver runs craq end to end and compiles once
        from repro.cluster import (ClusterConfig, EpochDriver,
                                   ScenarioConfig, make_policy, make_scenario)
        scfg = ScenarioConfig(n_epochs=4, epoch_ops=256, n_records=512,
                              value_dim=2, seed=3)
        scen = make_scenario("ycsb_a", scfg)
        ccfg = ClusterConfig(num_nodes=8, num_ranges=32, replication=2,
                             r_max=4, n_clients=16, report_every=2,
                             replication_mode="craq")
        drv = EpochDriver(scen, make_policy("full_adaptive"), ccfg,
                          backend="dist", mesh=mesh,
                          dist_cfg=C.DistConfig(bucket_cap=64))
        rows = drv.run()
        assert drv.traces == 1, drv.traces
        assert sum(r.dirty_reads for r in rows) > 0
        print("ok")
    """)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"


def test_policy_pull_every_auto_is_honored():
    pol = make_policy("frozen")
    pol.pull_every = "auto"
    scen = make_scenario("stationary", SCFG)
    cfg = dataclasses.replace(_ccfg("eventual"), report_every=None)
    drv = EpochDriver(scen, pol, cfg, fused=True)
    assert drv.auto_period
    drv.run()
    assert drv.traces == 1
    # a timing re-drive (balance_bench steady-state measurement) starts
    # from epoch 0 with a stale _next_pull: segments must clamp to the
    # compiled scan length instead of crashing
    drv.run()
    assert drv.traces == 1
