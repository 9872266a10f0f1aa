"""Benchmark harness entry point — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  For the coordination-model
benchmarks us_per_call is the simulated mean latency per op (abstract ticks;
see benchmarks/paper_tables.py) and ``derived`` carries the reproduced
quantity (throughput / latency ratios vs server-driven coordination).  Run:

  PYTHONPATH=src python -m benchmarks.run [--quick] [--engine {reference,vectorized}]
                                          [--n-ops N] [--json BENCH_coordination.json]

``--engine`` selects the DES implementation (the vectorized engine is the
default; ``reference`` replays the heapq oracle).  ``--json`` additionally
writes every row plus engine wall-clock timings to a machine-readable file
so future changes have a perf trajectory to compare against.
"""

from __future__ import annotations

import argparse
import json
import time

from repro import core as C

_ROWS: list[tuple[str, float, str]] = []


def _emit(name: str, us: float, derived: str):
    _ROWS.append((name, us, derived))
    print(f"{name},{us:.2f},{derived}", flush=True)


def table_fig13a(n_ops: int, engine: str):
    from benchmarks.paper_tables import fig13a_throughput_vs_skew

    rows = fig13a_throughput_vs_skew(n_ops, engine=engine)
    base = {}
    for label, mode, thr in rows:
        base.setdefault(label, {})[mode] = thr
    for label, mode, thr in rows:
        rel = thr / base[label][C.SERVER_DRIVEN]
        _emit(f"fig13a/{label}/{mode}", 1e3 / max(thr, 1e-9),
              f"throughput={thr:.3f}ops_tick;vs_server={rel:.3f}x")


def table_fig13bc(n_ops: int, engine: str):
    from benchmarks.paper_tables import fig13bc_throughput_vs_write_ratio

    rows = fig13bc_throughput_vs_write_ratio(n_ops, engine=engine)
    base = {}
    for label, wr, mode, thr in rows:
        base.setdefault((label, wr), {})[mode] = thr
    for label, wr, mode, thr in rows:
        rel = thr / base[(label, wr)][C.SERVER_DRIVEN]
        _emit(f"fig13bc/{label}/wr{wr}/{mode}", 1e3 / max(thr, 1e-9),
              f"throughput={thr:.3f};vs_server={rel:.3f}x")


def tables_1_2(n_ops: int, engine: str):
    from benchmarks.paper_tables import tables12_latency

    out = tables12_latency(n_ops, engine=engine)
    for dist, modes in out.items():
        sv = modes[C.SERVER_DRIVEN]
        for mode, r in modes.items():
            _emit(
                f"table12/{dist}/{mode}/read", r.read_mean,
                f"p50={r.read_p50:.1f};p99={r.read_p99:.1f};vs_server_mean={r.read_mean / sv.read_mean:.3f}",
            )
            _emit(
                f"table12/{dist}/{mode}/write", r.write_mean,
                f"p50={r.write_p50:.1f};p99={r.write_p99:.1f};vs_server_mean={r.write_mean / sv.write_mean:.3f}",
            )
            _emit(
                f"table12/{dist}/{mode}/scan", r.scan_mean,
                f"p50={r.scan_p50:.1f};p99={r.scan_p99:.1f};vs_server_mean={r.scan_mean / sv.scan_mean:.3f}",
            )


def table_load_balance(n_ops: int):
    from benchmarks.paper_tables import load_balance_effect

    r = load_balance_effect(n_ops)
    _emit("load_balance/zipf1.2", r["max_load_before"],
          f"imb_before={r['imbalance_before']:.2f};imb_after={r['imbalance_after']:.2f};"
          f"migrations={r['migrations']}")


def table_hierarchy(n_ops: int):
    from benchmarks.paper_tables import hierarchy_stats

    r = hierarchy_stats(n_ops)
    _emit("hierarchy/2pods", 0.0,
          f"pod_crossing={r['pod_crossing_fraction']:.3f};"
          f"agreement={r['pod_table_agreement']:.3f}")


def table_kernels():
    from benchmarks.kernel_bench import (
        bench_range_match, bench_range_match_apply, bench_decode_attn, bench_ssd,
    )

    for name, us, derived in (bench_range_match() + bench_range_match_apply()
                              + bench_decode_attn() + bench_ssd()):
        _emit(name, us, derived)


def table_engine(n_ops: int, quick: bool):
    from benchmarks.coordination_bench import bench_engine

    rows, wall = bench_engine(
        n_ops, include_reference=not quick, include_1m=not quick)
    for name, us, derived in rows:
        _emit(name, us, derived)
    return wall


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="smaller op counts")
    ap.add_argument("--engine", choices=("reference", "vectorized"),
                    default="vectorized",
                    help="DES implementation for the coordination benchmarks")
    ap.add_argument("--n-ops", type=int, default=None,
                    help="ops per workload (default: 2048 quick, 8192 full)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write rows + engine wall-clock to PATH")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.n_ops is not None and args.n_ops < 1:
        ap.error("--n-ops must be >= 1")
    n = args.n_ops if args.n_ops is not None else (2048 if args.quick else 8192)

    t0 = time.perf_counter()
    print("name,us_per_call,derived")
    table_fig13a(n, args.engine)
    table_fig13bc(n, args.engine)
    tables_1_2(n, args.engine)
    table_load_balance(n)
    table_hierarchy(n)
    table_kernels()
    wall = table_engine(n, args.quick)
    total = time.perf_counter() - t0

    if args.json:
        payload = {
            "meta": {
                "n_ops": n,
                "engine": args.engine,
                "quick": args.quick,
                "backends": list(C.des.available_backends()),
                "suite_wall_clock_s": total,
            },
            "engine_wall_clock": wall,
            "rows": [
                {"name": name, "us_per_call": us, "derived": derived}
                for name, us, derived in _ROWS
            ],
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"# wrote {len(_ROWS)} rows -> {args.json}", flush=True)


if __name__ == "__main__":
    main()
