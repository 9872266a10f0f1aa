"""``bench/trace_reduce.py`` on a small trace recorded on a TPU v5e.

``data/small.xplane.pb`` comes from ``record_trace.py``: the harness's
own window at a tiny size (8 nodes, 4,096 records, 256 ops an epoch,
YCSB-A) with its annotated stage timers, on one chip.
"""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run as RUN  # noqa: E402
import trace_reduce as TR  # noqa: E402

DATA = Path(__file__).resolve().parent / "data" / "small.xplane.pb"
STAGES = {"inject", "route_apply", "des", "host_sync", "control",
          "host outside any stage"}


@pytest.fixture(scope="module")
def summary():
    return TR.reduce_trace(DATA)


def test_busy_and_idle_inside_the_window(summary):
    assert summary.devices == ["/device:TPU:0"]
    dev = summary.devices[0]
    assert 0 < summary.busy_s[dev] < summary.window_s
    # every busy second lies inside some module's execution
    assert summary.busy_s[dev] <= sum(summary.module_s[dev].values()) + 1e-9
    gaps = summary.gaps
    assert gaps and all(lab in STAGES for lab, _ in gaps)
    assert summary.top_gaps() == [list(g) for g in gaps]
    assert [s for _, s in gaps] == sorted((s for _, s in gaps), reverse=True)
    assert sum(s for _, s in gaps) <= summary.window_s - summary.busy_s[dev]


def test_modules_and_ops(summary):
    dev = summary.devices[0]
    period = summary.modules_matching(r"^jit_period")[dev]
    assert period > 0
    in_period = sum(s for k, s in summary.op_s[dev].items()
                    if k.startswith("jit_period:"))
    assert 0 < in_period <= period + 1e-9
    top = summary.top_ops()
    assert 0 < len(top) <= 10
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)
    assert all(":" in name for name, _ in top)
    assert summary.collective_s[dev] == 0.0     # one chip: no exchange


def test_readers_on_the_recorded_trace(summary):
    ctx = {"stages": {"inject": {"s": 0.01, "calls": 4},
                      "des": {"s": 0.02, "calls": 4},
                      "control": {"s": 0.04, "calls": 4}},
           "epochs": 4, "pulls": 4, "trace": summary,
           "traced_epochs": 4, "traced_pulls": 4}
    read = {m: RUN.load_reader(m)(ctx) for m in (
        "inject_ms_per_epoch", "des_ms_per_epoch", "control_ms_per_pull",
        "period_device_ms_per_epoch", "migration_device_ms_per_pull",
        "device_idle_share", "collective_ms_per_epoch")}
    assert read["inject_ms_per_epoch"] == pytest.approx(2.5)
    assert read["control_ms_per_pull"] == pytest.approx(10.0)
    assert read["period_device_ms_per_epoch"] > 0
    assert read["migration_device_ms_per_pull"] >= 0
    assert 0 < read["device_idle_share"] < 100
    assert read["collective_ms_per_epoch"] is None


def test_union_merges_overlaps():
    assert TR._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
