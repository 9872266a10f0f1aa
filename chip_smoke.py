#!/usr/bin/env python3
"""Chip smoke test: drive the TurboKV epoch driver end to end on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: dist backend vs oracle

One chip: build ``EpochDriver`` on the ``shifting_hotspot`` scenario
under the ``full_adaptive`` policy with 1,000,000 records of 128-byte
values (``value_dim=32``) on 8 storage nodes, chain length 3,
``r_max=4``; serve 12 epochs of 4,096 ops with a controller pull every 4
epochs through ``driver.run()``; then replay the same op stream through
a plain dict store (:class:`DictStore`), GET a sample of 12,288 records
(hot and cold) through route -> ``apply_routed`` and require the values
bitwise equal to the reference, and require each sampled key's latest
value in the slab of every live chain member.

Four chips (``--chips 4``): only the sharded path, one storage node per
device (4 nodes, 250,000 records).  The fused dist backend
(``make_dist_period``) serves the same scenario as the oracle backend
under the ``migrate`` policy, and as the per-epoch dist driver under
``full_adaptive``; each pair must give an identical ``EpochMetrics``
stream and final store.

Without a TPU the script exits non-zero before it prints a result.
Earlier lines are smoke readings of one run, not benchmark numbers.  The
last line of standard output is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import sys
import time
from collections import Counter
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

SCENARIO = "shifting_hotspot"
POLICY = "full_adaptive"
N_RECORDS = 1_000_000
# the four-chip phase runs four whole drivers (oracle on one chip, three
# dist on four) at four times the chip cost; a quarter of the records
# keeps it short.  The dist store is sharded from its allocation on, so
# no device holds more than its own slab at any record count.
DIST_RECORDS = 250_000
VALUE_DIM = 32            # 128-byte values, the paper's (data/ycsb.py)
NUM_NODES = 8
REPLICATION = 3           # chain length
R_MAX = 4
EPOCH_OPS = 4096
N_EPOCHS = 12
REPORT_EVERY = 4
N_SAMPLE = 3 * EPOCH_OPS  # >= 10,000 records GET-checked
DIST_CHIPS = 4
# the policy of the four-chip dist-vs-oracle comparison: tail reads.  The
# dist backend folds the p2c read-spread rng per device, so under POLICY
# it matches its own per-epoch driver, not the oracle, bit for bit.
DIST_POLICY = "migrate"

# lowering + XLA compile of each top-level program (tracing is left out:
# its events nest, one per inner jit, and the persistent cache skips none)
_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


def log(msg: str) -> None:
    print(msg, flush=True)


def require_devices(n: int, platform: str = "tpu"):
    """The first ``n`` devices, all of ``platform``; exits non-zero
    otherwise (never falls back to another platform)."""
    import jax

    devs = jax.devices()
    if devs[0].platform != platform:
        raise SystemExit(
            f"chip_smoke: no {platform.upper()} found (jax.devices()[0] is "
            f"{devs[0].platform}:{devs[0].device_kind})"
        )
    if len(devs) < n:
        raise SystemExit(f"chip_smoke: needs {n} devices, found {len(devs)}")
    return devs[:n]


@contextlib.contextmanager
def compile_clock(out: dict):
    """Sum JAX's lowering + backend-compile durations into ``out["s"]``
    (a persistent-cache hit counts only its retrieval)."""
    import jax

    out["s"] = 0.0

    def listener(event, duration, **_):
        if event in _COMPILE_EVENTS:
            out["s"] += duration

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        yield out
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)


class DictStore:
    """Plain reference store with ``store.shard_apply``'s batch semantics:
    GETs see the state before the batch, DELs apply next, PUTs last; the
    last PUT of a key in batch order wins (a PUT beats a DEL of the same
    key in one batch)."""

    def __init__(self, keys: np.ndarray, values: np.ndarray):
        self.d = dict(zip(keys.tolist(), values))
        self.writes: Counter = Counter()
        self.accesses: Counter = Counter()

    def get(self, keys, value_dim: int):
        """(values (B, V), found (B,)) of ``keys`` in the current state;
        zeros where not found."""
        out = np.zeros((len(keys), value_dim), np.float32)
        found = np.zeros((len(keys),), bool)
        for i, k in enumerate(keys.tolist()):
            if k in self.d:
                found[i] = True
                out[i] = self.d[k]
        return out, found

    def apply(self, opcodes, keys, values):
        """Apply one batch; returns (values (B, V), found (B,)) of its
        GETs and DELs against the pre-batch state."""
        from repro.core import keys as K

        out, found = self.get(keys, values.shape[1])
        is_get = opcodes == K.OP_GET
        out[~is_get] = 0.0
        found &= is_get | (opcodes == K.OP_DEL)
        klist = keys.tolist()
        self.accesses.update(klist)
        for op, k in zip(opcodes.tolist(), klist):
            if op == K.OP_DEL:
                self.d.pop(k, None)
        for i, (op, k) in enumerate(zip(opcodes.tolist(), klist)):
            if op == K.OP_PUT:
                self.d[k] = values[i]
                self.writes[k] += 1
        return out, found


def replay(scenario, n_epochs: int) -> DictStore:
    """The reference after the load phase and ``n_epochs`` epochs of the
    scenario's op stream (regenerated from its seed)."""
    ref = DictStore(*scenario.load())
    for e in range(n_epochs):
        opcodes, keys, _end, values = scenario.epoch(e)
        ref.apply(opcodes, keys, values)
    return ref


def pick_sample(ref: DictStore, record_keys: np.ndarray, n: int,
                seed: int) -> np.ndarray:
    """``n`` distinct record keys: every key written while serving, then
    the most accessed, then cold records drawn at random."""
    written = sorted(ref.writes, key=lambda k: (-ref.writes[k], k))
    read_hot = [k for k, _ in ref.accesses.most_common()
                if k not in ref.writes]
    hot = (written + read_hot[: max(0, n // 2 - len(written))])[:n]
    taken = set(hot)
    cold = [k for k in np.random.default_rng(seed).permutation(
        record_keys).tolist() if k not in taken][: n - len(hot)]
    return np.asarray(hot + cold, np.uint32)


def _get_batch():
    import jax
    import jax.numpy as jnp

    from repro import core as C
    from repro.core import keys as K
    from repro.core import routing as R
    from repro.core.store import apply_routed

    @jax.jit
    def get(store, directory, keys):
        q = C.make_queries(keys, jnp.full(keys.shape, K.OP_GET, jnp.int32),
                           value_dim=store.value_dim)
        decision, _ = R.route(directory, q)
        _, resp = apply_routed(store, q, decision)
        return resp.value, resp.found, decision.chain, decision.chain_len

    return get


def _bitwise_equal(a: np.ndarray, b: np.ndarray) -> bool:
    return a.shape == b.shape and np.array_equal(
        np.ascontiguousarray(a, np.float32).view(np.uint32),
        np.ascontiguousarray(b, np.float32).view(np.uint32))


def check_store(driver, ref: DictStore, sample: np.ndarray,
                batch: int) -> dict:
    """GET ``sample`` through route -> apply_routed in batches of
    ``batch`` and compare with the reference bit for bit; then look up
    every sampled key in the slab of each live chain member.  Raises
    AssertionError on the first mismatch."""
    import jax.numpy as jnp

    get = _get_batch()
    n = -(-len(sample) // batch) * batch
    keys = np.concatenate([sample, sample[: n - len(sample)]])
    chains, clens = [], []
    for i in range(0, n, batch):
        kb = keys[i:i + batch]
        value, found, chain, clen = get(driver.store, driver.directory,
                                        jnp.asarray(kb))
        want, want_found = ref.get(kb, driver.store.value_dim)
        assert want_found.all(), "reference lost a sampled record"
        got_found = np.asarray(found)
        assert got_found.all(), (
            f"{int((~got_found).sum())} sampled records not found")
        assert _bitwise_equal(np.asarray(value), want), (
            "GET values differ from the reference")
        chains.append(np.asarray(chain))
        clens.append(np.asarray(clen))
    chain = np.concatenate(chains)[: len(sample)]
    clen = np.concatenate(clens)[: len(sample)]

    slab_keys = np.asarray(driver.store.keys)
    live = (np.arange(chain.shape[1])[None, :] < clen[:, None]) & (chain >= 0)
    qi, pos_in_chain = np.nonzero(live)
    nodes = chain[qi, pos_in_chain]
    want_keys = sample[qi]
    slots = np.array([np.searchsorted(slab_keys[m], k)
                      for m, k in zip(nodes.tolist(), want_keys.tolist())],
                     np.int64)
    slots = np.minimum(slots, slab_keys.shape[1] - 1)
    missing = slab_keys[nodes, slots] != want_keys
    assert not missing.any(), (
        f"{int(missing.sum())} (key, chain member) pairs missing a replica")
    got = np.asarray(driver.store.values[jnp.asarray(nodes),
                                         jnp.asarray(slots)])
    want = np.stack([ref.d[k] for k in want_keys.tolist()])
    assert _bitwise_equal(got, want), "a replica holds a stale value"
    return {"sampled": int(len(sample)), "replicas": int(len(nodes)),
            "written": int(len(ref.writes))}


def one_chip(scfg, ccfg, n_sample: int) -> dict:
    """Load, serve and check one store on the default device."""
    import jax

    from repro.cluster import EpochDriver, make_policy, make_scenario
    from repro.core import des

    backend, where = des.backend_info(ccfg.des_backend)
    log(f"des_backend: {backend} on {where}")
    clock: dict = {}
    with compile_clock(clock):
        scenario = make_scenario(SCENARIO, scfg)
        t0 = time.perf_counter()
        driver = EpochDriver(scenario, make_policy(POLICY), ccfg)
        jax.block_until_ready(driver.store)
        t_load = time.perf_counter() - t0
        compile_load = clock["s"]
        t0 = time.perf_counter()
        rows = driver.run()
        jax.block_until_ready(driver.store)
        t_run = time.perf_counter() - t0
        compile_run = clock["s"] - compile_load
    log(f"load: {scfg.n_records} records in {t_load:.3f} s "
        f"({compile_load:.3f} s compiling)")
    log(f"serve: {len(rows)} epochs of {scfg.epoch_ops} ops in "
        f"{t_run:.3f} s wall ({compile_run:.3f} s compiling)")
    log(f"compile_s: {clock['s']:.3f}")
    log(f"steady_wall_s_per_epoch: {(t_run - compile_run) / len(rows):.4f} "
        "(serve wall minus compile, per epoch)")
    expect = 1 + driver.growth_events
    log(f"traces: {driver.traces} (expected {expect})")
    assert driver.traces == expect, "the period program retraced"
    assert len(rows) == scfg.n_epochs
    assert all(r.throughput > 0 and np.isfinite(r.p99) for r in rows)
    overflow = int(np.asarray(driver.store.overflow).sum())
    assert overflow == 0, f"store dropped {overflow} entries (capacity)"
    moved = sum(r.migration_entries for r in rows)
    log(f"control: {sum(len(r.events) for r in rows)} events, "
        f"{moved} entries migrated")

    t0 = time.perf_counter()
    ref = replay(make_scenario(SCENARIO, scfg), scfg.n_epochs)
    sample = pick_sample(ref, scenario.record_keys, n_sample, scfg.seed)
    res = check_store(driver, ref, sample, scfg.epoch_ops)
    log(f"check: {res['sampled']} sampled records ({res['written']} written "
        f"while serving) bitwise equal to the reference; "
        f"{res['replicas']} replicas checked in {time.perf_counter() - t0:.3f} s")
    return {"driver": driver, "rows": rows, "ref": ref, "sample": sample,
            **res}


def _assert_same_run(a, b, what: str) -> None:
    """Two (driver, rows) runs: identical EpochMetrics stream and final
    store."""
    (d_a, rows_a), (d_b, rows_b) = a, b
    assert len(rows_a) == len(rows_b)
    for x, y in zip(rows_a, rows_b):
        dx, dy = dataclasses.asdict(x), dataclasses.asdict(y)
        diff = [k for k in dx if dx[k] != dy[k]]
        assert not diff, f"epoch {x.epoch}: {what} differ in {diff}"
    for f in ("keys", "values", "overflow"):
        assert np.array_equal(np.asarray(getattr(d_a.store, f)),
                              np.asarray(getattr(d_b.store, f))), (
            f"final store {f}: {what} differ")


def four_chips(scfg, ccfg, devices) -> None:
    """The fused dist backend on a mesh of ``devices``: under
    ``DIST_POLICY`` against the oracle backend, and under ``POLICY``
    (p2c read spreading, whose draws are made per device) against the
    per-epoch dist driver.  Each pair must give an identical
    EpochMetrics stream and final store."""
    import jax

    from repro.cluster import EpochDriver, make_policy, make_scenario
    from repro.core import DistConfig
    from repro.core.dist_store import make_mesh

    mesh = make_mesh((len(devices),), devices=devices)
    clock: dict = {}

    def run(backend, policy, fused=True):
        with compile_clock(clock):
            t0 = time.perf_counter()
            drv = EpochDriver(
                make_scenario(SCENARIO, scfg), make_policy(policy), ccfg,
                backend=backend, mesh=mesh if backend == "dist" else None,
                fused=fused,
                # bucket bound = a device's whole batch slice: no drops
                dist_cfg=DistConfig(bucket_cap=scfg.epoch_ops // len(devices)),
            )
            rows = drv.run()
            jax.block_until_ready(drv.store)
            wall = time.perf_counter() - t0
        name = f"{backend}{'' if fused else ' per-epoch'} / {policy}"
        log(f"{name}: load + {len(rows)} epochs in {wall:.3f} s "
            f"({clock['s']:.3f} s compiling), traces {drv.traces}")
        assert drv.traces == 1 + drv.growth_events
        assert len(rows) == scfg.n_epochs
        return drv, rows

    oracle = run("oracle", DIST_POLICY)
    dist = run("dist", DIST_POLICY)
    _assert_same_run(oracle, dist, "dist and oracle")
    shards = {s.device for s in dist[0].store.values.addressable_shards}
    log(f"dist == oracle ({DIST_POLICY}): {len(dist[1])} EpochMetrics rows "
        f"and the final store identical; store sharded over "
        f"{len(shards)} devices")
    del oracle, dist
    per_epoch = run("dist", POLICY, fused=False)
    fused = run("dist", POLICY)
    assert fused[0].mode_plan.spread, f"{POLICY} runs without read spreading"
    _assert_same_run(per_epoch, fused, "fused and per-epoch dist")
    log(f"fused dist == per-epoch dist ({POLICY}, p2c read spreading): "
        f"{len(fused[1])} EpochMetrics rows and the final store identical")


def configs(num_nodes: int, n_records: int):
    from repro.cluster import ClusterConfig, ScenarioConfig

    scfg = ScenarioConfig(n_epochs=N_EPOCHS, epoch_ops=EPOCH_OPS,
                          n_records=n_records, value_dim=VALUE_DIM, seed=0)
    ccfg = ClusterConfig(num_nodes=num_nodes, replication=REPLICATION,
                         r_max=R_MAX, report_every=REPORT_EVERY)
    return scfg, ccfg


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, DIST_CHIPS), default=1,
                    help="1: the one-chip smoke; 4: only the sharded "
                         "dist-vs-oracle phase")
    args = ap.parse_args(argv)
    devices = require_devices(args.chips)

    from repro.compile_cache import enable_compile_cache

    log(f"device: {devices[0].platform} {devices[0].device_kind} "
        f"x{len(devices)}")
    log(f"compile_cache: {enable_compile_cache()}")
    if args.chips == 1:
        scfg, ccfg = configs(NUM_NODES, N_RECORDS)
        one_chip(scfg, ccfg, N_SAMPLE)
    else:
        scfg, ccfg = configs(DIST_CHIPS, DIST_RECORDS)
        four_chips(scfg, ccfg, devices)
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", "not reported")
             for d in devices]
    log(f"peak_hbm_bytes: {', '.join(map(str, peaks))}"
        + (" (per device; the oracle runs on the first)" if len(peaks) > 1
           else ""))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
