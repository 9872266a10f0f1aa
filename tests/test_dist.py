"""Multi-device integration tests (8 forced host devices, subprocess).

jax pins the device count at first init, so these run in subprocesses with
XLA_FLAGS set; each subprocess asserts internally and exits nonzero on
failure.
"""

import os
import subprocess
import sys
import textwrap

import pytest

ENV = {**os.environ, "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
       "PYTHONPATH": os.path.join(os.path.dirname(__file__), "..", "src")}


PRELUDE = "from repro.core.dist_store import make_mesh\n"


def run_sub(code: str):
    r = subprocess.run([sys.executable, "-c", PRELUDE + textwrap.dedent(code)],
                       env=ENV, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    return r.stdout


def test_dist_store_matches_oracle():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro import core as C

        mesh = make_mesh((8,))
        d = C.make_directory(16, 8, 3)
        store = C.make_store(8, 64, 4)
        rng = np.random.default_rng(0)
        B = 64
        keys = jnp.asarray(rng.integers(0, 2**32-2, B), jnp.uint32)
        vals = jnp.asarray(rng.normal(size=(B,4)), jnp.float32)
        qput = C.make_queries(keys, jnp.full((B,), C.OP_PUT), vals)
        qget = C.make_queries(keys, jnp.full((B,), C.OP_GET), value_dim=4)
        for strat in ("allgather", "bucket_a2a"):
            apply_fn = C.make_dist_apply(mesh, d, C.DistConfig(strategy=strat, bucket_cap=32))
            s1, _, d1, _ = apply_fn(store, d, qput)
            s2, resp, d2, m = apply_fn(s1, d1, qget)
            assert bool(resp.found.all()), strat
            assert bool(jnp.allclose(resp.value, vals, atol=1e-6)), strat
            dec, dd = C.route(d, qput)
            so, _ = C.apply_routed(store, qput, dec)
            assert jnp.array_equal(jnp.sort(s1.keys, axis=1), jnp.sort(so.keys, axis=1)), strat
            assert (np.asarray(d1.write_count) == np.asarray(dd.write_count)).all(), strat
        print("ok")
    """)


def test_dist_store_bucket_overflow_counted():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro import core as C

        mesh = make_mesh((8,))
        d = C.make_directory(16, 8, 1)
        store = C.make_store(8, 256, 1)
        # aim every query at one key -> one target shard; cap tiny -> overflow
        B = 64
        keys = jnp.full((B,), 123, jnp.uint32)
        q = C.make_queries(keys, jnp.full((B,), C.OP_GET), value_dim=1)
        apply_fn = C.make_dist_apply(mesh, d, C.DistConfig(strategy="bucket_a2a", bucket_cap=2))
        _, resp, _, m = apply_fn(store, d, q)
        assert int(jnp.sum(m["bucket_overflow"])) > 0
        print("ok")
    """)


def test_dist_store_read_spread_matches_tail_reads():
    """p2c read spreading: same PUT/GET results, targets spread, load
    registers and decision metrics globally consistent."""
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro import core as C

        mesh = make_mesh((8,))
        d = C.make_directory(16, 8, 3, r_max=5)
        store = C.make_store(8, 64, 4)
        rng = np.random.default_rng(0)
        B = 64
        keys = jnp.asarray(rng.integers(0, 2**32-2, B), jnp.uint32)
        vals = jnp.asarray(rng.normal(size=(B,4)), jnp.float32)
        qput = C.make_queries(keys, jnp.full((B,), C.OP_PUT), vals)
        qget = C.make_queries(keys, jnp.full((B,), C.OP_GET), value_dim=4)
        for strat in ("allgather", "bucket_a2a"):
            cfg = C.DistConfig(strategy=strat, bucket_cap=32,
                               read_spread=True, return_decision=True)
            apply_fn = C.make_dist_apply(mesh, d, cfg)
            load = jnp.zeros((8,), jnp.uint32)
            s1, _, d1, load, m = apply_fn(store, d, load, qput, jax.random.PRNGKey(1))
            s2, resp, d2, load, m = apply_fn(s1, d1, load, qget, jax.random.PRNGKey(2))
            assert bool(resp.found.all()), strat
            assert bool(jnp.allclose(resp.value, vals, atol=1e-6)), strat
            # decision metrics cover the whole batch
            assert m["target"].shape == (B,), strat
            assert m["chain"].shape[0] == B, strat
            # reads spread beyond the 8 tails: register sum == B reads
            assert int(jnp.sum(load)) >= B, strat
        print("ok")
    """)


def test_dist_preload_is_shard_local_and_matches_oracle():
    """The dist load phase writes each slab on its own device (the store
    is made and stays sharded, one node per device) and loads the same
    store as the oracle backend, in chunks that do not divide the record
    count."""
    run_sub("""
        import numpy as np
        import repro.cluster.epoch as E
        from repro.cluster import (ClusterConfig, EpochDriver, ScenarioConfig,
                                   make_policy, make_scenario)

        E.PRELOAD_CHUNK = 300        # dist rounds it up to 304 (8 slices)
        mesh = make_mesh((8,))
        scfg = ScenarioConfig(n_epochs=1, epoch_ops=256, n_records=1001,
                              value_dim=2, seed=3)
        ccfg = ClusterConfig(num_nodes=8, num_ranges=32, replication=3,
                             r_max=4)
        drv = {b: EpochDriver(make_scenario("shifting_hotspot", scfg),
                              make_policy("frozen"), ccfg, backend=b,
                              mesh=mesh if b == "dist" else None)
               for b in ("oracle", "dist")}
        for f in ("keys", "values", "overflow"):
            assert np.array_equal(np.asarray(getattr(drv["oracle"].store, f)),
                                  np.asarray(getattr(drv["dist"].store, f))), f
        assert int((np.asarray(drv["dist"].store.keys) != 2**32 - 1).sum()) >= 1001
        for leaf in (drv["dist"].store.keys, drv["dist"].store.values):
            shards = leaf.addressable_shards
            assert len({s.device for s in shards}) == 8
            assert {s.data.shape[0] for s in shards} == {1}
        print("ok")
    """)


# Shared scaffold for the fused-dist ≡ per-epoch-dist parity tests: runs
# the same scenario through the dist backend with fused=False / fused=True
# and asserts every observable is bit-identical — the EpochMetrics stream,
# final store (keys/values/overflow), replication and overload state, and
# sampled telemetry spans — plus the fused driver compiling exactly once
# and never syncing the host more often than the per-epoch driver.
FUSED_PAIR = """
import dataclasses
import jax, numpy as np
from repro.cluster import (ClusterConfig, EpochDriver, ScenarioConfig,
                           make_policy, make_scenario)
from repro.overload import OverloadConfig
from repro.telemetry import TelemetryConfig

mesh = make_mesh((8,))
scfg = ScenarioConfig(n_epochs=6, epoch_ops=256, n_records=512,
                      value_dim=2, seed=3)
base = dict(num_nodes=8, num_ranges=32, replication=2, r_max=4,
            n_clients=16, report_every=2, imbalance_threshold=1.1,
            max_moves_per_round=6)

def pair(scen_name, pol, ccfg, scen_kw=None):
    rows = {}
    for fused in (False, True):
        scen = make_scenario(scen_name, scfg, **(scen_kw or {}))
        drv = EpochDriver(scen, make_policy(pol), ccfg,
                          backend="dist", mesh=mesh, fused=fused)
        rows[fused] = (drv, drv.run())
    (drv_r, rows_r), (drv_f, rows_f) = rows[False], rows[True]
    for a, b in zip(rows_r, rows_f):
        da, db = dataclasses.asdict(a), dataclasses.asdict(b)
        for k in da:
            assert da[k] == db[k], (scen_name, a.epoch, k, da[k], db[k])
    for f in ("keys", "values", "overflow"):
        assert np.array_equal(np.asarray(getattr(drv_r.store, f)),
                              np.asarray(getattr(drv_f.store, f))), (scen_name, f)
    for la, lb in zip(jax.tree.leaves(drv_r.repl), jax.tree.leaves(drv_f.repl)):
        assert np.array_equal(np.asarray(la), np.asarray(lb)), scen_name
    if drv_r.ovl is not None:
        for la, lb in zip(jax.tree.leaves(drv_r.ovl), jax.tree.leaves(drv_f.ovl)):
            assert np.array_equal(np.asarray(la), np.asarray(lb)), scen_name
    if drv_r.coord is not None:
        for la, lb in zip(jax.tree.leaves(drv_r.coord), jax.tree.leaves(drv_f.coord)):
            assert np.array_equal(np.asarray(la), np.asarray(lb)), scen_name
    if drv_r.metrics is not None:
        for la, lb in zip(jax.tree.leaves(drv_r.metrics),
                          jax.tree.leaves(drv_f.metrics)):
            assert np.array_equal(np.asarray(la), np.asarray(lb)), scen_name
    if drv_r.telemetry is not None:
        er, ef = drv_r.telemetry.epochs, drv_f.telemetry.epochs
        assert len(er) == len(ef)
        for a, b in zip(er, ef):
            for leaf in ("span_i", "span_f", "lat", "comps", "issue"):
                np.testing.assert_array_equal(a[leaf], b[leaf])
    assert drv_f.traces == 1, (scen_name, drv_f.traces)
    assert drv_f.host_syncs <= drv_r.host_syncs, scen_name
    print("ok", scen_name, pol, drv_f.host_syncs, drv_r.host_syncs)
    return rows_f
"""


def test_fused_dist_parity_shifting_hotspot_overload():
    """Whole-period fused scan ≡ per-epoch dist driver under p2c spread +
    overload backpressure + telemetry sampling."""
    run_sub(FUSED_PAIR + """
pair("shifting_hotspot", "overload_adaptive",
     ClusterConfig(**base,
                   overload=OverloadConfig(queue_cap=48, service_rate=80,
                                           inflation=3.0, queue_weight=2),
                   telemetry=TelemetryConfig(sample_rate=1 / 4)),
     scen_kw=dict(theta=1.2, shift_every=2))
""")


def test_fused_dist_parity_node_failure():
    """Fused ≡ per-epoch across a mid-period fail + recover transition."""
    run_sub(FUSED_PAIR + """
pair("node_failure", "migrate", ClusterConfig(**base),
     scen_kw=dict(fail_epoch=3, fail_node=0, recover_epoch=5))
""")


def test_fused_dist_parity_craq_ycsb_a():
    """Fused ≡ per-epoch with CRAQ apportioned reads on a write-heavy mix."""
    run_sub(FUSED_PAIR + """
pair("ycsb_a", "full_adaptive",
     ClusterConfig(**base, replication_mode="craq"))
""")


def test_fused_dist_parity_coordination_tier():
    """Fused ≡ per-epoch on the dist backend with the replicated switch
    tier live through a split-brain fault: the coord carry, redirect
    accounting and quorum safety are device-count invariant."""
    run_sub(FUSED_PAIR + """
from repro.coordination_tier import CoordConfig
rows = pair("split_brain", "full_adaptive",
            ClusterConfig(**base,
                          coordination=CoordConfig(n_switches=4, lag_per_hop=1)),
            scen_kw=dict(theta=1.2, shift_every=2, split_epoch=2,
                         heal_epoch=5, switch=1))
for r in rows:
    assert r.routed == r.direct + r.redirected, r.epoch
assert sum(r.mis_served for r in rows) == 0
assert sum(r.redirected for r in rows) > 0
""")


def test_fused_dist_metrics_plane_parity():
    """The PR-10 extension of the dist parity gate: with the fleet
    metrics ring carried (and donated) through the fused shard_map period
    scan, every ring leaf must match the per-epoch dist driver bit for
    bit, SLO burn evaluation included — and metrics=None must still
    produce the bit-identical EpochMetrics stream on the dist backend."""
    run_sub(FUSED_PAIR + """
from repro.telemetry.metrics import MetricsConfig
from repro.telemetry.slo import SLO
ovl = OverloadConfig(queue_cap=48, service_rate=80, inflation=3.0,
                     queue_weight=2)
mcfg = MetricsConfig(window=32, topk=4,
                     slos=(SLO(name="p999_fleet", series="p999", bound=50.0,
                               objective=0.9, fast_window=2, slow_window=4),))
rows_on = pair("shifting_hotspot", "overload_adaptive",
               ClusterConfig(**base, overload=ovl, metrics=mcfg),
               scen_kw=dict(theta=1.2, shift_every=2))
# pure-observer on the dist backend: metrics=None rows are bit-identical
scen = make_scenario("shifting_hotspot", scfg, theta=1.2, shift_every=2)
drv_off = EpochDriver(scen, make_policy("overload_adaptive"),
                      ClusterConfig(**base, overload=ovl, metrics=None),
                      backend="dist", mesh=mesh, fused=True)
rows_off = drv_off.run()
assert len(rows_off) == len(rows_on)
for a, b in zip(rows_off, rows_on):
    assert dataclasses.asdict(a) == dataclasses.asdict(b), a.epoch
assert drv_off.traces == 1
""")


def test_compressed_dp_train_step():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.configs.base import ShapeSpec
        from repro.data.pipeline import make_batch, DataConfig
        from repro.training.step import (TrainConfig, make_dp_train_step,
                                         init_train_state, init_dp_error_feedback)
        from repro.training.optimizer import OptConfig

        cfg = get_config("qwen2-1.5b").reduced()
        mesh = make_mesh((8,))
        tcfg = TrainConfig(opt=OptConfig(lr=1e-3, warmup_steps=2, total_steps=40),
                           remat=False, grad_compression=True, dp_axes=("data",))
        state = init_train_state(cfg, tcfg, jax.random.PRNGKey(0))
        state.pop("err")
        err = init_dp_error_feedback(cfg, state["params"], 8)
        shape = ShapeSpec("tiny", 32, 16, "train")
        batch0 = {k: jnp.asarray(v) for k, v in make_batch(cfg, shape, 0, DataConfig("copy")).items()}
        step = make_dp_train_step(cfg, tcfg, mesh, batch0)
        losses = []
        for i in range(8):
            b = {k: jnp.asarray(v) for k, v in make_batch(cfg, shape, i, DataConfig("copy")).items()}
            state, err, m = step(state, err, b)
            losses.append(float(m["loss"]))
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0], losses
        print("ok", losses[0], losses[-1])
    """)


def test_sharded_train_step_lowers_on_2x4():
    run_sub("""
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.configs.base import ShapeSpec
        from repro.distributed import sharding as SH
        from repro.training.step import TrainConfig, make_train_step, abstract_train_state
        from repro.training.optimizer import OptConfig
        from repro.launch.input_specs import batch_specs_for

        cfg = get_config("qwen2-1.5b").reduced()
        mesh = make_mesh((2, 4), ("data", "model"))
        tcfg = TrainConfig(opt=OptConfig(), remat=True, microbatches=2)
        state = abstract_train_state(cfg, tcfg)
        shape = ShapeSpec("tiny", 64, 8, "train")
        batch = batch_specs_for(cfg, shape, with_labels=True)
        ssp = SH.state_specs(state, mesh, dp_axes=("data",))
        bsp = SH.batch_specs(batch, ("data",))
        step = make_train_step(cfg, tcfg)
        j = jax.jit(step, in_shardings=(SH.to_named(ssp, mesh), SH.to_named(bsp, mesh)),
                    out_shardings=(SH.to_named(ssp, mesh), None))
        c = j.lower(state, batch).compile()
        assert c.memory_analysis().temp_size_in_bytes > 0
        print("ok")
    """)


def test_real_sharded_execution_matches_single_device():
    """Numerically execute a sharded step on 8 devices vs 1 device."""
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.configs.base import ShapeSpec
        from repro.data.pipeline import make_batch, DataConfig
        from repro.distributed import sharding as SH
        from repro.training.step import TrainConfig, make_train_step, init_train_state
        from repro.training.optimizer import OptConfig

        cfg = get_config("qwen2-1.5b").reduced()
        tcfg = TrainConfig(opt=OptConfig(lr=1e-3, warmup_steps=2, total_steps=10), remat=False)
        state = init_train_state(cfg, tcfg, jax.random.PRNGKey(0))
        shape = ShapeSpec("tiny", 32, 8, "train")
        batch = {k: jnp.asarray(v) for k, v in make_batch(cfg, shape, 0, DataConfig("copy")).items()}
        step = make_train_step(cfg, tcfg)

        # single-device reference
        s_ref, m_ref = jax.jit(step)(state, batch)

        mesh = make_mesh((2, 4), ("data", "model"))
        ssp = SH.state_specs(jax.eval_shape(lambda: state), mesh, dp_axes=("data",))
        bsp = SH.batch_specs(jax.eval_shape(lambda: batch), ("data",))
        j = jax.jit(step, in_shardings=(SH.to_named(ssp, mesh), SH.to_named(bsp, mesh)))
        s_sh, m_sh = j(state, batch)
        assert abs(float(m_ref["loss"]) - float(m_sh["loss"])) < 1e-3
        for a, b in zip(jax.tree.leaves(s_ref["params"]), jax.tree.leaves(s_sh["params"])):
            np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                                       atol=2e-3)
        print("ok")
    """)
