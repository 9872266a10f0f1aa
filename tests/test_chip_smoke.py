"""chip_smoke.py's check logic, run on the CPU at a tiny size.

The script itself insists on a TPU; these tests point its platform
check at the CPU and drive the same phases, so the dict reference, the
bitwise read-back check and the replica check are themselves tested —
including that they catch a stale replica and a wrong read.
"""

import dataclasses
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core as C
from repro.core import keys as K
from repro.core.store import apply_routed, make_store
from repro.cluster import ClusterConfig, ScenarioConfig

ROOT = Path(__file__).resolve().parents[1]
TINY_SCFG = ScenarioConfig(n_epochs=6, epoch_ops=256, n_records=2048,
                           value_dim=4, seed=0)
TINY_CCFG = ClusterConfig(num_nodes=8, replication=3, r_max=4,
                          report_every=2)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def served(smoke):
    smoke.require_devices(1, platform="cpu")   # the CPU, in this test only
    return smoke.one_chip(TINY_SCFG, TINY_CCFG, 512)


def test_dict_store_matches_shard_apply_semantics(smoke):
    """One mixed batch with repeated keys: GET/DEL see the pre-batch
    state, DELs then PUTs apply, the last PUT of a key wins and beats a
    DEL of it — the reference and apply_routed agree on every reply and
    on the final contents."""
    rng = np.random.default_rng(0)
    V, n0 = 3, 64
    keys0 = np.sort(rng.choice(2**31, n0, replace=False)).astype(np.uint32)
    vals0 = rng.normal(size=(n0, V)).astype(np.float32)
    d = C.make_directory(8, 4, 2)
    store = make_store(4, 256, V)
    q0 = C.make_queries(jnp.asarray(keys0), jnp.full((n0,), K.OP_PUT),
                        jnp.asarray(vals0))
    store, _ = apply_routed(store, q0, C.route(d, q0)[0])
    ref = smoke.DictStore(keys0, vals0)

    B = 128
    fresh = rng.choice(2**31, 16).astype(np.uint32)
    keys = rng.choice(np.concatenate([keys0, fresh]), B).astype(np.uint32)
    ops = rng.choice([K.OP_GET, K.OP_PUT, K.OP_DEL], B).astype(np.int32)
    vals = rng.normal(size=(B, V)).astype(np.float32)
    q = C.make_queries(jnp.asarray(keys), jnp.asarray(ops), jnp.asarray(vals))
    store, resp = apply_routed(store, q, C.route(d, q)[0])
    want, want_found = ref.apply(ops, keys, vals)

    is_get = ops == K.OP_GET
    np.testing.assert_array_equal(np.asarray(resp.value)[is_get],
                                  want[is_get])
    is_rd = ops != K.OP_PUT
    np.testing.assert_array_equal(np.asarray(resp.found)[is_rd],
                                  want_found[is_rd])
    # final contents: every reference key is in its tail's slab
    probe = np.array(sorted(set(keys0.tolist()) | set(fresh.tolist())),
                     np.uint32)
    qg = C.make_queries(jnp.asarray(probe),
                        jnp.full((len(probe),), K.OP_GET), value_dim=V)
    _, got = apply_routed(store, qg, C.route(d, qg)[0])
    got_found = np.asarray(got.found)
    for i, k in enumerate(probe.tolist()):
        assert got_found[i] == (k in ref.d), k
        if k in ref.d:
            np.testing.assert_array_equal(np.asarray(got.value)[i], ref.d[k])


def test_one_chip_phase_checks_pass(served):
    assert served["sampled"] == 512
    assert served["written"] > 0
    # every sampled key sits on a chain of 3 or more live members
    assert served["replicas"] >= 3 * served["sampled"]
    assert len(served["rows"]) == TINY_SCFG.n_epochs


def test_check_store_catches_a_stale_replica(smoke, served):
    """Corrupt the key's copy at its chain head: GETs (served by the
    tail) still read right, only the replica check can see it."""
    drv, ref, sample = served["driver"], served["ref"], served["sample"]
    key = sample[:1]
    q = C.make_queries(jnp.asarray(key), jnp.full((1,), K.OP_GET))
    dec, _ = C.route(drv.directory, q)
    assert int(dec.chain_len[0]) >= 2
    head = int(dec.chain[0, 0])
    slot = int(np.searchsorted(np.asarray(drv.store.keys)[head], key[0]))
    good = drv.store
    drv.store = dataclasses.replace(
        good, values=good.values.at[head, slot].add(1.0))
    try:
        with pytest.raises(AssertionError, match="stale value"):
            smoke.check_store(drv, ref, sample, TINY_SCFG.epoch_ops)
    finally:
        drv.store = good


def test_check_store_catches_a_wrong_reference_value(smoke, served):
    drv, ref, sample = served["driver"], served["ref"], served["sample"]
    key = int(sample[-1])
    good = ref.d[key]
    ref.d[key] = good + np.float32(1.0)
    try:
        with pytest.raises(AssertionError, match="GET values"):
            smoke.check_store(drv, ref, sample, TINY_SCFG.epoch_ops)
    finally:
        ref.d[key] = good
    # restored: the same check passes again
    smoke.check_store(drv, ref, sample, TINY_SCFG.epoch_ops)


def test_pick_sample_is_hot_and_cold(smoke, served):
    ref, sample = served["ref"], served["sample"]
    assert len(set(sample.tolist())) == len(sample)
    written = set(ref.writes)
    assert written <= set(sample.tolist())
    assert any(k not in ref.accesses for k in sample.tolist())   # cold


def test_require_devices_refuses_another_platform(smoke):
    with pytest.raises(SystemExit, match="no TPU found"):
        smoke.require_devices(1)


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/jax-cache"])
def test_compile_cache_dir(monkeypatch, env_dir):
    """A set JAX_COMPILATION_CACHE_DIR wins and nothing is set in code;
    otherwise the cache is the fixed .jax_cache/ of the checkout."""
    from repro.compile_cache import enable_compile_cache

    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    was = jax.config.jax_compilation_cache_dir
    try:
        got = enable_compile_cache()
        if env_dir is None:
            assert got == str(ROOT / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        else:
            assert got == env_dir
            assert jax.config.jax_compilation_cache_dir == was
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_four_chip_phase_on_host_devices():
    """The ``--chips 4`` phase at a tiny size on four host devices: dist
    == oracle under migrate, fused == per-epoch dist under full_adaptive."""
    code = (
        "import sys; sys.path.insert(0, {root!r})\n"
        "import chip_smoke as CS\n"
        "from repro.cluster import ClusterConfig, ScenarioConfig\n"
        "devs = CS.require_devices(4, platform='cpu')\n"
        "CS.four_chips(ScenarioConfig(n_epochs=4, epoch_ops=256,\n"
        "                             n_records=2048, value_dim=4, seed=0),\n"
        "              ClusterConfig(num_nodes=4, replication=3, r_max=4,\n"
        "                            report_every=2), devs)\n"
    ).format(root=str(ROOT))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": str(ROOT / "src")}
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "dist == oracle (migrate)" in r.stdout
    assert "fused dist == per-epoch dist (full_adaptive" in r.stdout


def test_chip_smoke_exits_nonzero_without_tpu():
    """As a program on the CPU it fails and prints no result line."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    assert '"ok"' not in r.stdout
