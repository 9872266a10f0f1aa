"""The exchange counters of the sharded data plane (``bucket_a2a``).

Each batch counts, per all-to-all round (the read round, then one write
round per chain position), the live slots the devices sent, those bound
for another device, every slot sent, and the fullest (source, target)
bucket.  Here they are recounted with numpy from the routing decision:
each device fronts its contiguous slice of the batch and keeps at most
``bucket_cap`` queries for each target.  With the stage timers on, a
segment's counters land in ``StageTimers.counts``, the rows and slots as
sums and the bucket peak as a maximum.  The sharded paths run on four
virtual CPU devices, in subprocesses (JAX fixes the device count when it
starts).
"""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.telemetry import StageTimers

ROOT = Path(__file__).resolve().parents[1]

RECOUNT = textwrap.dedent("""
    import numpy as np
    from repro.core import keys as K

    FIELDS = ("a2a_rows", "a2a_rows_remote", "a2a_slots", "bucket_peak")

    def recount(opcodes, keys, target, chain, chain_len, n, cap):
        # {field: (1 + r_max,) per round} of one batch
        B = len(keys)
        Bl = B // n
        real = keys != K.EMPTY_KEY
        write = (opcodes == K.OP_PUT) | (opcodes == K.OP_DEL)
        rounds = [np.where(~write & real, target, -1)]
        for pos in range(chain.shape[1]):
            rounds.append(np.where(write & real & (pos < chain_len),
                                   chain[:, pos], -1))
        out = {f: [] for f in FIELDS}
        for t in rounds:
            fill = np.zeros((n, n), np.int64)       # (source, target)
            for src in range(n):
                mine = t[src * Bl:(src + 1) * Bl]
                fill[src] = np.minimum(
                    np.bincount(mine[mine >= 0], minlength=n), cap)
            out["a2a_rows"].append(fill.sum())
            out["a2a_rows_remote"].append(fill.sum() - np.trace(fill))
            out["a2a_slots"].append(n * n * cap)
            out["bucket_peak"].append(fill.max())
        return {f: np.array(v) for f, v in out.items()}
""")


def _run(code: str):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": str(ROOT / "src")}
    r = subprocess.run([sys.executable, "-c", RECOUNT + textwrap.dedent(code)],
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0 and "ok" in r.stdout, r.stderr[-4000:]


def test_stage_timers_peak_is_a_maximum():
    t = StageTimers(enabled=True)
    t.peak("bucket_peak", 5)
    t.peak("bucket_peak", 3)
    t.count("a2a_rows", 3)
    assert t.counts == {"bucket_peak": 5, "a2a_rows": 3}
    t.peak("bucket_peak", 9)
    assert t.counts["bucket_peak"] == 9
    off = StageTimers(enabled=False)
    off.peak("bucket_peak", 5)
    assert off.counts == {}


@pytest.mark.parametrize("cap", [4, 32])
def test_apply_exchange_counters_match_numpy_recount(cap):
    """One batch through ``make_dist_apply``, per round: live rows, remote
    rows, slots and bucket peak.  At ``cap`` 4 buckets overflow and the
    rounds send fewer rows than the batch's live queries ask for."""
    _run(f"""
        import jax.numpy as jnp
        from repro import core as C
        from repro.core.dist_store import DistConfig, make_mesh

        n, cap, B, V = 4, {cap}, 128, 3
        mesh = make_mesh((n,))
        d = C.make_directory(16, n, 3, r_max=4)
        store = C.make_store(n, 512, V)
        rng = np.random.default_rng(7)
        keys = rng.integers(0, 2**32 - 2, B).astype(np.uint32)
        keys[::9] = K.EMPTY_KEY
        opcodes = rng.choice([K.OP_GET, K.OP_PUT], B).astype(np.int32)
        vals = rng.normal(size=(B, V)).astype(np.float32)
        q = C.make_queries(jnp.asarray(keys), jnp.asarray(opcodes),
                           jnp.asarray(vals))
        apply_fn = C.make_dist_apply(
            mesh, d, DistConfig(bucket_cap=cap, return_decision=True))
        _, _, _, m = apply_fn(store, d, q)
        want = recount(opcodes, keys, np.asarray(m["target"]),
                       np.asarray(m["chain"]), np.asarray(m["chain_len"]),
                       n, cap)
        apply_counts, xcounts = m["counts"]
        assert int(apply_counts.put_rows) > 0
        for f in FIELDS:
            np.testing.assert_array_equal(np.asarray(getattr(xcounts, f)),
                                          want[f], f)
        assert want["a2a_rows"][0] > 0 and want["a2a_rows"][1] > 0
        # chain length 3 of r_max 4: the last write round is empty
        assert want["a2a_rows"][-1] == 0
        # a live query fills one slot a round it takes part in, unless
        # its bucket is full
        sent = (int(((opcodes == K.OP_GET) & (keys != K.EMPTY_KEY)).sum())
                + 3 * int(((opcodes == K.OP_PUT)
                           & (keys != K.EMPTY_KEY)).sum()))
        if cap == 4:
            assert want["bucket_peak"].max() == cap
            assert want["a2a_rows"].sum() < sent
        else:
            assert want["a2a_rows"].sum() == sent
        print("ok")
    """)


@pytest.mark.parametrize("fused", [True, False])
def test_segment_exchange_counts_land_in_timers(fused):
    """Two segments of two epochs on the driver's dist backend: the timers
    hold the sums of the recounted rows and slots over the four epochs,
    and the largest bucket fill of any of them.  The fused period program
    keeps the read and write rounds apart in its op names."""
    _run(f"""
        import jax.numpy as jnp
        from repro import core as C
        from repro.cluster import (ClusterConfig, EpochDriver,
                                   ScenarioConfig, make_policy,
                                   make_scenario)
        from repro.core import routing as R
        from repro.core.dist_store import DistConfig, make_mesh
        from repro.telemetry import StageTimers

        n, cap = 4, 24
        scfg = ScenarioConfig(n_epochs=4, epoch_ops=128, n_records=384,
                              value_dim=3, seed=5)
        ccfg = ClusterConfig(num_nodes=n, num_ranges=8, replication=3,
                             r_max=4, n_clients=8, report_every=2,
                             replication_mode="chain")
        scen = make_scenario("shifting_hotspot", scfg)
        t = StageTimers(enabled=True)
        drv = EpochDriver(scen, make_policy("frozen"), ccfg, backend="dist",
                          mesh=make_mesh((n,)), timers=t, fused={fused},
                          dist_cfg=DistConfig(bucket_cap=cap))
        per_epoch = []
        for e in range(4):
            opcodes, keys, end_keys, values = scen.epoch(e)
            q = C.make_queries(jnp.asarray(keys), jnp.asarray(opcodes),
                               jnp.asarray(values), jnp.asarray(end_keys))
            dec, _ = R.route(drv.directory, q)
            per_epoch.append(recount(
                opcodes, keys, np.asarray(dec.target), np.asarray(dec.chain),
                np.asarray(dec.chain_len), n, cap))

        def want(epochs):
            got = {{f: int(sum(per_epoch[e][f].sum() for e in epochs))
                    for f in FIELDS}}
            got["bucket_peak"] = int(max(per_epoch[e]["bucket_peak"].max()
                                         for e in epochs))
            return got

        drv._run_segment(0, 4)
        assert {{f: t.counts[f] for f in FIELDS}} == want([0, 1]), t.counts
        drv._run_segment(2, 4)
        assert {{f: t.counts[f] for f in FIELDS}} == want(range(4)), t.counts
        assert t.counts["a2a_slots"] == 4 * 5 * n * n * cap
        assert 0 < t.counts["a2a_rows_remote"] < t.counts["a2a_rows"]
        assert t.counts["bucket_peak"] <= cap
        if {fused}:
            hlo = drv._dist_period.lower(
                *drv._dist_commit(drv.store, drv.directory, drv.load_reg,
                                  drv.sketch, drv.repl, drv.ovl, drv.coord,
                                  drv.metrics),
                *drv._period_inputs).compile().as_text()
            assert "apply/exchange/read/" in hlo
            assert "apply/exchange/write/" in hlo
        print("ok")
    """)
