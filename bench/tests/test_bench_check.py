"""The comparison that decides ``correct``, and the traffic it replays,
run on the CPU at a tiny size.

The harness's own build, warm-up, window and check run here with the
platform check pointed at the CPU.  A sound run comes out correct; the
control (the store's values carried in bfloat16) comes out not correct,
and so does each fault planted in the period program that the window
drives: a step that returns its state unchanged, half of each batch left
out, a value altered where it is written, and, on four virtual devices
(the sharded configuration under writes), the exchange between chips
left out.  A GET reply altered in the check's own read of the store
comes out not correct too.  The window's own GET replies never leave the
period program, so no fault planted in them could be seen.
"""

import dataclasses
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import reference as REF  # noqa: E402
import run as RUN  # noqa: E402
from workload import (OP_GET, OP_PUT, generator, load_mix,  # noqa: E402
                      make_traffic)

YCSB = generator("ycsb")
SPEC = RUN.load_spec()
SEED = 2**31 + 11
ONE_CHIP = "turbokv-8n-1m.ycsb_a"
# the sharded configuration under writes (configuration, traffic, chips):
# no cell of BENCHMARK.json yet, the harness runs it all the same
FOUR_CHIP = ("turbokv-4n-1m-4chip", "ycsb_a", 4)


def tiny_bench(cell, n_records: int = 2048, epoch_ops: int = 256,
               before_warm_up=None):
    """A built and warmed-up bench at a tiny size.  ``cell`` is a workload
    of BENCHMARK.json or a (configuration, traffic, chips) triple;
    ``before_warm_up(bench)`` runs before the programs first trace."""
    if isinstance(cell, str):
        entry, config, mix = RUN.find_cell(SPEC, cell)
        chips = entry["chips"]
    else:
        name, traffic, chips = cell
        config = json.loads((BENCH / "configs" / f"{name}.json").read_text())
        mix = load_mix(BENCH / "traffic" / f"{traffic}.json")
    config = dict(config, n_records=n_records, epoch_ops=epoch_ops)
    if config["dist"]:
        config["dist"] = dict(config["dist"], bucket_cap=epoch_ops // chips)
    devices = RUN.require_devices(chips, platform="cpu")
    bench = RUN.build(config, mix, SEED, devices)
    if before_warm_up is not None:
        before_warm_up(bench)
    RUN.warm_up(bench)
    return bench


def checked(bench, degrade=None):
    win = RUN.run_window(bench, 0.3)
    checks = RUN.check(bench, win, degrade=degrade)
    return checks, all(v <= lim for v, lim in checks.values())


# -- the traffic ------------------------------------------------------------


def test_fnvhash64_matches_ycsb():
    """The vectorized hash against YCSB's Utils.fnvhash64, written out."""
    def java(val):
        h = 0xCBF29CE484222325
        for _ in range(8):
            h ^= val & 0xFF
            val >>= 8
            h = (h * 1099511628211) & (2**64 - 1)
        return abs(h - 2**64 if h >= 2**63 else h)

    xs = np.array([0, 1, 2, 255, 256, 12345, 2**40 + 7], np.uint64)
    assert YCSB.fnvhash64(xs).tolist() == [java(int(x)) for x in xs]


def test_traffic_draws_follow_the_zipfian_ranks():
    mix = load_mix(BENCH / "traffic" / "ycsb_a.json")
    n, B = 1000, 50_000
    t = make_traffic(mix, n_records=n, value_dim=32, epoch_ops=B,
                     n_epochs=2, seed=SEED)
    r = t.ranks(0)
    freq = np.bincount(r, minlength=n) / B
    p = np.diff(np.concatenate([[0.0], t.cdf]))
    sd = np.sqrt(p * (1 - p) / B)
    # the ten hottest ranks sit within 5 standard deviations of zipf
    assert np.all(np.abs(freq[:10] - p[:10]) < 5 * sd[:10])
    assert freq[0] > freq[1] > freq[5] > freq[50]
    ops, keys, _, vals = t.epoch(0)
    np.testing.assert_array_equal(keys, t.record_keys[t.scramble[r]])
    assert abs((ops == OP_GET).mean() - 0.5) < 0.02
    assert set(np.unique(ops)) <= {OP_GET, OP_PUT}
    assert vals.shape == (B, 32) and vals.dtype == np.float32
    # the same seed regenerates the same stream; another seed draws
    # other keys at the same sizes
    again = make_traffic(mix, n_records=n, value_dim=32, epoch_ops=B,
                         n_epochs=2, seed=SEED)
    for a, b in zip(t.epoch(1), again.epoch(1)):
        np.testing.assert_array_equal(a, b)
    other = make_traffic(mix, n_records=n, value_dim=32, epoch_ops=B,
                         n_epochs=2, seed=SEED + 1)
    assert not np.array_equal(other.record_keys, t.record_keys)
    assert len(np.unique(t.record_keys)) == n


def test_dict_store_batch_semantics():
    """GETs see the pre-batch state; the last PUT of a key wins."""
    keys = np.array([10, 20, 30], np.uint32)
    vals = np.arange(6, dtype=np.float32).reshape(3, 2)
    ref = REF.DictStore(keys, vals)
    ops = np.array([OP_PUT, OP_GET, OP_PUT, OP_GET], np.int32)
    k = np.array([20, 20, 20, 40], np.uint32)
    v = np.array([[7, 7], [0, 0], [9, 9], [0, 0]], np.float32)
    out, found = ref.apply(ops, k, v)
    np.testing.assert_array_equal(out[1], vals[1])
    assert found.tolist() == [False, True, False, False]
    np.testing.assert_array_equal(ref.d[20], [9, 9])


# -- the check on a tiny run -------------------------------------------------


@pytest.fixture(scope="module")
def sound():
    bench = tiny_bench(ONE_CHIP)
    checks, ok = checked(bench)
    return bench, checks, ok


def test_sound_run_is_correct(sound):
    bench, checks, ok = sound
    assert ok, checks
    assert bench.next_epoch > RUN.WARM_SEGMENTS


def test_bf16_values_are_not_correct(sound):
    """The control: the same store with its values carried in bfloat16."""
    bench, _, _ = sound
    win = RUN.Window(seconds=1.0, seg_s=np.ones(1), seg_ops=np.ones(1, int),
                     failed=0, epochs=0, pulls=0, compiles={})
    checks = RUN.check(bench, win, degrade=RUN.bf16_values)
    assert checks["wrong_values"][0] > 0
    assert checks["get_mismatches"][0] > 0


def _wrap_period(bench, edit_store=None, edit_queries=None):
    drv = bench.driver
    period = drv._period_fn

    def broken(store, directory, load_reg, sketch, repl, ovl, coord,
               metrics, qs, *rest):
        if edit_queries is not None:
            qs = edit_queries(qs)
        out = period(jax.tree.map(jnp.copy, store), directory, load_reg,
                     sketch, repl, ovl, coord, metrics, qs, *rest)
        if edit_store is not None:
            out = (edit_store(store, out[0]), *out[1:])
        return out

    drv._period_fn = broken


def _half_batch(qs):
    B = qs.opcode.shape[1]
    return dataclasses.replace(qs, opcode=qs.opcode.at[:, B // 2:].set(OP_GET))


def _flip_values(qs):
    bits = jax.lax.bitcast_convert_type(qs.value, jnp.uint32) ^ jnp.uint32(1)
    return dataclasses.replace(
        qs, value=jax.lax.bitcast_convert_type(bits, jnp.float32))


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "value_altered"])
def test_fault_under_the_timed_path_is_not_correct(fault):
    bench = tiny_bench(ONE_CHIP)
    if fault == "state_unchanged":
        _wrap_period(bench, edit_store=lambda old, new: old)
    elif fault == "half_batch":
        _wrap_period(bench, edit_queries=_half_batch)
    else:
        _wrap_period(bench, edit_queries=_flip_values)
    checks, ok = checked(bench)
    assert not ok, checks


def test_reply_altered_in_the_check_read_is_not_correct():
    """A GET reply altered in the check's read of the store."""
    bench = tiny_bench(ONE_CHIP)
    get = bench.get

    def bad_get(store, directory, keys):
        v, f = get(store, directory, keys)
        return v.at[0, 0].add(1.0), f

    bench.get = bad_get
    checks, ok = checked(bench)
    assert not ok and checks["get_mismatches"][0] > 0, checks


def test_traffic_files_name_their_generator():
    """A mix is read by the generator it names; an unknown or malformed
    name is refused."""
    mix = load_mix(BENCH / "traffic" / "ycsb_c.json")
    assert generator(mix["generator"]) is YCSB
    for bad in ("no_such_generator", "../run", None):
        with pytest.raises(ValueError):
            generator(bad)


def test_four_chip_cell_and_exchange_left_out():
    """On four virtual CPU devices, the sharded configuration under YCSB-A
    is correct, and with the all_to_all exchange replaced by the identity
    in the period program alone (the check's GET program keeps it) it is
    not."""
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {str(BENCH / 'tests')!r})
        import test_bench_check as T
        from repro.core import dist_store
        b = T.tiny_bench(T.FOUR_CHIP, n_records=1024, epoch_ops=128)
        checks, ok = T.checked(b)
        assert ok, checks
        real, broken = dist_store._a2a, [False]
        dist_store._a2a = lambda x, axis, n: (
            x if broken[0] else real(x, axis, n))

        def period_only(bench):
            # programs trace at their first call: the period program in
            # the warm-up segments, the check's GET program after them
            get = bench.get

            def check_get(*args):
                broken[0] = False
                return get(*args)

            bench.get = check_get
            broken[0] = True

        b = T.tiny_bench(T.FOUR_CHIP, n_records=1024, epoch_ops=128,
                         before_warm_up=period_only)
        checks, ok = T.checked(b)
        assert not ok and checks["wrong_values"][0] > 0, checks
        print("OK")
    """)
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.pathsep.join(
               [str(ROOT / "src"), os.environ.get("PYTHONPATH", "")])}
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0 and "OK" in r.stdout, r.stderr[-4000:]
