"""The four-chip cell ``turbokv-4n-1m-4chip-16k.ycsb_a`` and its two
readers, checked without a chip.

The cell is built by its ``BENCHMARK.json`` name at a tiny size on four
virtual CPU devices, through the harness's own build, warm-up, window and
check: it comes out correct, and with the period program's all_to_all
exchange replaced by the identity (the check's GET program keeps it) it
does not.  The GET replies the window serves, which ``correct`` does not
compare yet, carry per-epoch digests equal to a ``DictStore`` replay's,
and unequal once the read round's replies alone skip their all_to_all.
``exchange_ms_per_epoch`` and ``allreduce_ms_per_epoch`` are read from
synthetic trace summaries named as on a v5e, and from the committed
one-chip trace, which has neither.
"""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import run as RUN  # noqa: E402
import trace_reduce as TR  # noqa: E402

CELL = "turbokv-4n-1m-4chip-16k.ycsb_a"
DATA = Path(__file__).resolve().parent / "data" / "small.xplane.pb"


def test_cell_is_declared_on_four_chips():
    spec = RUN.load_spec()
    cell, config, mix = RUN.find_cell(spec, CELL)
    assert cell["chips"] == config["chips"] == 4
    assert config["backend"] == "dist" and config["epoch_ops"] == 16384
    # a chip's whole batch slice fits one target's bucket: nothing drops
    assert config["dist"]["bucket_cap"] * cell["chips"] == config["epoch_ops"]
    assert mix["name"] == "ycsb_a"
    _, layer = RUN.cell_metrics(spec, CELL)
    names = {m["name"] for m in layer}
    assert {"exchange_ms_per_epoch", "allreduce_ms_per_epoch"} <= names
    _, one_chip = RUN.cell_metrics(spec, "turbokv-8n-1m.ycsb_a")
    assert not {"exchange_ms_per_epoch",
                "allreduce_ms_per_epoch"} & {m["name"] for m in one_chip}


def test_cell_is_correct_and_exchange_left_out_is_not():
    """On four virtual CPU devices the cell is correct, and with the
    all_to_all exchange replaced by the identity in the period program
    alone it is not."""
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {str(BENCH / 'tests')!r})
        import test_bench_check as T
        from repro.core import dist_store
        cell = {CELL!r}
        b = T.tiny_bench(cell, n_records=1024, epoch_ops=128)
        checks, ok = T.checked(b)
        assert ok, checks
        assert b.driver.backend == "dist"
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

        b = T.tiny_bench(cell, n_records=1024, epoch_ops=128,
                         before_warm_up=period_only)
        checks, ok = T.checked(b)
        assert not ok and checks["wrong_values"][0] > 0, checks
        print("OK")
    """)
    _run_on_four_devices(code)


def test_served_get_replies_match_the_replay_digests():
    """The digest of each window epoch's GET replies, as the period
    program served them, equals the digest of a ``DictStore`` replay's
    replies; with the read round's replies kept on the device that
    answered them (the reply all_to_all replaced by the identity, the
    write rounds untouched) the digests differ."""
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {str(BENCH / 'tests')!r})
        import test_bench_check as T
        import reference as REF
        import run as RUN
        from repro.core import dist_store
        from repro.core.store import get_digest_np
        cell = {CELL!r}

        def served_and_replayed(bench, n_segments=3):
            rows = [r for _ in range(n_segments)
                    for r in RUN.run_segment(bench)]
            ref = REF.DictStore(*bench.traffic.load())
            want = []
            for e in range(rows[-1].epoch + 1):
                opcodes, keys, _end, values = bench.traffic.epoch(e)
                v, f = ref.apply(opcodes, keys, values)
                want.append(get_digest_np(opcodes, keys, v, f))
            return ([r.get_digest for r in rows],
                    [want[r.epoch] for r in rows])

        b = T.tiny_bench(cell, n_records=1024, epoch_ops=128)
        served, want = served_and_replayed(b)
        assert served == want and all(want), (served, want)
        dist_store._return_replies = lambda resp, axis, n: resp
        b = T.tiny_bench(cell, n_records=1024, epoch_ops=128)
        served, want = served_and_replayed(b)
        assert all(s != w for s, w in zip(served, want)), (served, want)
        print("OK")
    """)
    _run_on_four_devices(code)


def _run_on_four_devices(code: str):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.pathsep.join(
               [str(ROOT / "src"), os.environ.get("PYTHONPATH", "")])}
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0 and "OK" in r.stdout, r.stderr[-4000:]


def _summary(op_s):
    devs = sorted(op_s)
    return TR.TraceSummary(window_s=1.0, devices=devs,
                           busy_s={d: 0.5 for d in devs},
                           module_s={d: {} for d in devs}, op_s=op_s,
                           collective_s={d: 0.0 for d in devs}, gaps=[])


def test_allreduce_reader_sums_the_period_all_reduces():
    read = RUN.load_reader("allreduce_ms_per_epoch")
    tr = _summary({
        # as a v5e names them: combined psums, an uncombined pmax, and
        # an asynchronous pair
        "/device:TPU:0": {"jit_period_device:all-reduce.3": 0.004,
                          "jit_period_device:pmax.9": 0.001,
                          "jit_period_device:all-reduce-start.1": 0.002,
                          "jit_period_device:all-reduce-done.1": 0.001,
                          "jit_period_device:all_to_all.2": 0.5,
                          "jit_period_device:all-gather.4": 0.5,
                          "jit_period_device:fusion.7": 0.9,
                          "jit_get:all-reduce.1": 3.0},
        "/device:TPU:1": {"jit_period_device:all-reduce.3": 0.010},
    })
    ctx = {"trace": tr, "traced_epochs": 5}
    # the slowest device: 10 ms over 5 epochs
    assert read(ctx) == pytest.approx(2.0)
    tr.op_s["/device:TPU:1"]["jit_period_device:all-reduce.3"] = 0.001
    assert read(ctx) == pytest.approx(8.0 / 5)
    assert read({"trace": tr, "traced_epochs": 0}) is None
    assert read({"trace": None, "traced_epochs": 5}) is None
    assert read({"trace": _summary({"/device:TPU:0": {
        "jit_period:fusion.1": 1.0}}), "traced_epochs": 5}) is None


def test_exchange_reader_sums_the_period_all_to_alls_and_all_gathers():
    read = RUN.load_reader("exchange_ms_per_epoch")
    tr = _summary({
        # as a v5e names them, with an asynchronous pair besides
        "/device:TPU:0": {"jit_period_device:all_to_all.213": 0.004,
                          "jit_period_device:all_to_all.252": 0.002,
                          "jit_period_device:all-gather.21": 0.003,
                          "jit_period_device:all-gather-start.2": 0.0005,
                          "jit_period_device:all-gather-done.2": 0.0005,
                          "jit_period_device:all-reduce.11": 0.5,
                          "jit_period_device:pmax.9": 0.5,
                          "jit_period_device:fusion.574": 0.9,
                          "jit_get:all_to_all.1": 3.0},
        "/device:TPU:1": {"jit_period_device:all_to_all.213": 0.005},
    })
    ctx = {"trace": tr, "traced_epochs": 5}
    # the slowest device: 10 ms over 5 epochs
    assert read(ctx) == pytest.approx(2.0)
    tr.op_s["/device:TPU:1"]["jit_period_device:all_to_all.213"] = 0.02
    assert read(ctx) == pytest.approx(4.0)
    assert read({"trace": tr, "traced_epochs": 0}) is None
    assert read({"trace": None, "traced_epochs": 5}) is None
    assert read({"trace": _summary({"/device:TPU:0": {
        "jit_period:fusion.1": 1.0}}), "traced_epochs": 5}) is None


def test_exchange_reader_reads_nothing_on_one_chip():
    ctx = {"trace": TR.reduce_trace(DATA), "traced_epochs": 4}
    assert RUN.load_reader("exchange_ms_per_epoch")(ctx) is None


def test_allreduce_reader_reads_nothing_on_one_chip():
    ctx = {"trace": TR.reduce_trace(DATA), "traced_epochs": 4}
    assert RUN.load_reader("allreduce_ms_per_epoch")(ctx) is None
