#!/usr/bin/env python3
"""Readings of the comparison that decides ``correct``, for setting its
limits: the program's, and the control's, on several seeds in one
process.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 10

For each seed: the cell's set-up and a short window, as ``run.py`` makes
them; then the check of what the window left (the program's reading)
and the check of the same store with its values carried in bfloat16,
the precision below the configuration's float32 (the control's
reading).  Prints one JSON line per seed.  The benchmark's own runs do
not run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run as RUN  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one run each")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    spec = RUN.load_spec()
    cell, config, mix = RUN.find_cell(spec, args.workload)
    devices = RUN.require_devices(cell["chips"])
    RUN.device_peaks(devices[0].device_kind)
    sys.path.insert(0, str(RUN.ROOT / "src"))
    RUN.enable_caches()
    for seed in (int(s) for s in args.seeds.split(",")):
        bench = RUN.build(config, mix, seed, devices)
        RUN.warm_up(bench)
        win = RUN.run_window(bench, args.seconds)
        program = RUN.check(bench, win)
        control = RUN.check(bench, win, degrade=RUN.bf16_values)
        print(json.dumps({
            "seed": seed, "epochs": bench.next_epoch,
            "program": {k: v for k, (v, _) in program.items()},
            "control": {k: v for k, (v, _) in control.items()},
        }), flush=True)
        del bench, win
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
