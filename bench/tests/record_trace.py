#!/usr/bin/env python3
"""Record the small chip trace that ``test_bench_trace.py`` reduces.

    python3 bench/tests/record_trace.py

Runs the harness's own window, with its annotated stage timers, on one
TPU at a tiny size (8 nodes, 4,096 records, 256 ops an epoch, YCSB-A)
for a few segments under the profiler, and writes the trace to
``bench/tests/data/small.xplane.pb``.  Needs a TPU: it exits non-zero on
any other platform.
"""

from __future__ import annotations

import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import run as RUN  # noqa: E402
import trace_reduce as TR  # noqa: E402

OUT = HERE / "data" / "small.xplane.pb"


def main() -> int:
    spec = RUN.load_spec()
    _, config, mix = RUN.find_cell(spec, "turbokv-8n-1m.ycsb_a")
    config = dict(config, n_records=4096, epoch_ops=256)
    devices = RUN.require_devices(1)
    RUN.enable_caches()
    bench = RUN.build(config, mix, 12, devices)
    RUN.warm_up(bench)
    bench.driver._timers = RUN.annotated_timers()
    tmp = Path(tempfile.mkdtemp(prefix="bench_trace_"))
    try:
        RUN.run_window(bench, 0.05, tmp)
        shutil.copyfile(TR.find_xplane(tmp), OUT)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    s = TR.reduce_trace(OUT)
    print(f"wrote {OUT} ({OUT.stat().st_size} bytes): window "
          f"{s.window_s:.6f} s, busy {s.busy_s}, modules "
          f"{sorted(next(iter(s.module_s.values())))}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
