"""Adaptive-balancing benchmark: the policy zoo over the scenario library.

Runs the ``repro.cluster`` closed loop over the time-varying scenario
library and emits one JSON row per (scenario × policy) run — the numbers
behind BENCHMARKS.md §"Load balancing" and §"Hot-range splitting".  Two
acceptance gates are checked explicitly:

* **adaptive gate** (PR 2): on the Zipf-1.2 shifting hotspot,
  ``full_adaptive`` must beat the frozen directory on both mean load
  imbalance (max/mean) and mean DES p99 latency;
* **splitting gate** (PR 3): on the Zipf-1.3 multi-hotspot workload,
  ``split_hot`` must beat whole-range ``migrate`` on mean load imbalance
  at **equal or fewer** migrated entries (hot-subset moves are priced by
  the hot keys only), and every run's epoch step must compile exactly
  once.

Extras:

* ``--service lognormal|pareto`` re-runs the matrix under seeded per-hop
  service-time draws (``core.ServiceModel``) — the deterministic-service
  rows hide self-similar burstiness;
* ``--dist`` runs the dist-backend parity column (``make_dist_apply`` on
  a forced 8-device host mesh, in a subprocess because jax pins the
  device count at first init) and reports bucket-overflow retry rates
  under switch queue pressure;
* ``--period N`` sets the control-pull cadence (= the fused scan length;
  default 1 so the gate matrix's policy decisions stay comparable to the
  per-epoch PR-3 rows — raise it to trade control lag for throughput);
  ``--period auto`` turns on the drift-adaptive cadence
  (``Policy.pull_every="auto"``): each report picks the next period from
  report-to-report load drift inside ``ClusterConfig.auto_band``;
* ``--profile`` runs the epoch-pipeline comparison: fused vs per-epoch
  driver on the same scenario with the whole run fused into one period,
  reporting compile vs steady-state epochs/s and host-sync counts, and
  **gating** on the fused driver beating the per-epoch one (the CI smoke
  ratio + host-sync gates);
* ``--trace`` runs the telemetry parity column: the gate pair re-runs
  with ``repro.telemetry`` span sampling enabled, asserting the
  :class:`EpochMetrics` stream is **bit-identical** to the
  telemetry-off run, the step still compiles once, and every sampled
  span's latency decomposition reconstructs its DES latency exactly;
  emits a Chrome-trace artifact (``TRACE_balance.json`` by default)
  loadable in ``chrome://tracing`` / Perfetto;
* ``--replication`` runs the ``repro.replication`` three-mode comparison
  (eventual / chain / craq over diurnal, write-heavy flash-crowd and
  YCSB-A mixes) with its own gates: craq clean-read p99 must not exceed
  chain tail-read p99 on the read-heavy diurnal phase, only craq may
  (and must, under writes) report dirty-read bounces, and every step
  compiles once.  The gate matrix itself stays in ``eventual`` mode, so
  PR-2/3/4 comparisons are untouched.

Run: ``PYTHONPATH=src python -m benchmarks.balance_bench
[--quick] [--scenarios a,b] [--policies x,y] [--service kind] [--dist]
[--period N|auto] [--profile] [--trace] [--replication]
[--json BENCH_balance.json]``
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

DEFAULT_POLICIES = ("frozen", "migrate", "replicate", "split_hot", "full_adaptive")
DEFAULT_SCENARIOS = (
    "shifting_hotspot", "flash_crowd", "diurnal", "node_failure",
    "multi_hotspot", "keyspace_growth", "rack_failure_hotspot",
)
DIST_SCENARIO = "flash_crowd"                 # switch queue pressure case
DIST_POLICIES = ("frozen", "full_adaptive")
# gate-matrix pull cadence: 1 keeps every policy decision identical to the
# per-epoch PR-3 rows, so the adaptive/splitting gates compare unchanged
# behaviour; the pipeline win is measured by --profile (which fuses whole
# periods) and by exploratory --period runs
DEFAULT_PERIOD = 1
# the --profile comparison pair (the tentpole's acceptance scenario)
PROFILE_SCENARIO = "shifting_hotspot"
PROFILE_POLICIES = ("frozen", "full_adaptive")
# fused steady-state epochs/s vs the per-epoch driver, gated at two
# deliberately generous levels.  Full size measures >1.5x; quick sizes
# (4 epochs x 512 ops on a 2-core CI box) measure ~1.1-1.5x with
# run-to-run noise that straddles 1.0, so the quick gate only requires
# "not materially slower" — it still catches a real pipeline regression
# (a broken scan measures ~0.3x) without flaking CI.  host-sync counts
# gate deterministically alongside it.
PROFILE_RATIO_GATE = 1.2
PROFILE_RATIO_GATE_QUICK = 0.9


# the acceptance-gate cluster geometry: fine ranges so a Zipf hot block
# spans several chains, headroom for selective replication and splitting
def cluster_config(quick: bool, service: str = "fixed",
                   period=DEFAULT_PERIOD):
    from repro.cluster import ClusterConfig
    from repro.core import ServiceModel

    return ClusterConfig(
        num_nodes=8,
        num_ranges=32 if quick else 128,
        replication=2,
        r_max=4 if quick else 5,
        n_clients=32,
        report_every=period,
        imbalance_threshold=1.1,
        max_moves_per_round=8,
        service_model=ServiceModel(kind=service),
    )


def scenario_config(quick: bool):
    from repro.cluster import ScenarioConfig

    if quick:
        return ScenarioConfig(n_epochs=4, epoch_ops=512, n_records=1024,
                              value_dim=4, seed=1, read_ratio=0.95)
    return ScenarioConfig(n_epochs=10, epoch_ops=1024, n_records=2048,
                          value_dim=4, seed=1, read_ratio=0.95)


def scenario_kwargs(name: str, scfg) -> dict:
    mid = scfg.n_epochs // 2
    return {
        "shifting_hotspot": dict(theta=1.2, shift_every=max(scfg.n_epochs // 3, 1)),
        "flash_crowd": dict(t0=mid // 2, t1=mid + 1),
        "diurnal": {},
        "node_failure": dict(fail_epoch=mid, fail_node=0),
        "multi_hotspot": dict(theta=1.3, n_hotspots=3,
                              shift_every=max(scfg.n_epochs // 3, 1)),
        "keyspace_growth": {},
        "rack_failure_hotspot": dict(
            theta=1.2, shift_every=max(scfg.n_epochs // 3, 1),
            fail_epoch=mid, rack=(0, 1),
            recover_epoch=mid + 2 if mid + 2 < scfg.n_epochs else None,
        ),
        "ycsb_a": {},
        "stationary": {},
    }[name]


def _steady_epochs_per_s(drv, n_epochs: int, repeats: int = 1) -> float:
    """Steady-state epochs/s: re-drive the (already compiled) driver over
    the scenario's epochs via its real ``run()`` path, wall-clocked
    without trace/compile.  Best of ``repeats`` passes (per-pass noise on
    small CI boxes is large)."""
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        drv.run()
        best = min(best, time.perf_counter() - t0)
    return n_epochs / best


def run_matrix(scenarios, policies, quick: bool, *, service: str = "fixed",
               backend: str = "oracle", mesh=None, dist_cfg=None,
               period=DEFAULT_PERIOD, fused: bool = True,
               measure_steady: bool = False, verbose: bool = True):
    from repro.cluster import EpochDriver, make_policy, make_scenario, summarize

    rows = []
    for sname in scenarios:
        scfg = scenario_config(quick)
        for pname in policies:
            scen = make_scenario(sname, scfg, **scenario_kwargs(sname, scfg))
            drv = EpochDriver(scen, make_policy(pname),
                              cluster_config(quick, service, period),
                              backend=backend, mesh=mesh, dist_cfg=dist_cfg,
                              fused=fused)
            t0 = time.perf_counter()
            epochs = drv.run()
            wall = time.perf_counter() - t0
            row = summarize(epochs)
            row["wall_s"] = round(wall, 3)
            row["traces"] = drv.traces
            row["service"] = service
            row["backend"] = backend
            row["period"] = period
            row["fused"] = fused
            row["host_syncs"] = drv.host_syncs
            row["growth_events"] = drv.growth_events
            if drv.period_history:
                row["auto_periods"] = list(drv.period_history)
            if measure_steady and backend == "oracle":
                # the re-drive mutates driver state (fine for timing) but
                # runs AFTER the row's metrics are captured
                row["steady_eps"] = round(
                    _steady_epochs_per_s(drv, scfg.n_epochs), 2
                )
            rows.append(row)
            if verbose:
                eps = row.get("steady_eps")
                print(
                    f"{sname:20s} {pname:14s} imb {row['mean_imbalance']:5.2f} "
                    f"p99 {row['mean_p99']:6.1f} p50 {row['mean_p50']:6.1f} "
                    f"thr {row['mean_throughput']:.3f} "
                    f"ent {row['total_migration_entries']:6d} "
                    f"retries {row['total_retries']:4d} "
                    f"traces {row['traces']}"
                    + (f" steady {eps:7.2f} ep/s" if eps else "")
                )
    return rows


def check_acceptance(rows, *, quick: bool = False) -> list[str]:
    """The cluster-subsystem acceptance gates (see ISSUE/BENCHMARKS.md).

    ``quick`` (CI smoke sizes: 4 epochs) relaxes the splitting gate's
    imbalance comparison to "no worse" — at smoke scale a couple of
    control rounds cannot reliably separate the policies' imbalance
    means, but the keys-moved advantage and the compile-once property
    must hold at any size.
    """
    by = {(r["scenario"], r["policy"]): r for r in rows
          if r.get("backend", "oracle") == "oracle" and not r.get("profile")
          and not r.get("trace")
          and r.get("bench") not in ("replication", "replication_filter")}
    problems = []
    f = by.get(("shifting_hotspot", "frozen"))
    a = by.get(("shifting_hotspot", "full_adaptive"))
    if f and a:
        if not a["mean_imbalance"] < f["mean_imbalance"]:
            problems.append(
                f"full_adaptive imbalance {a['mean_imbalance']:.2f} !< "
                f"frozen {f['mean_imbalance']:.2f}"
            )
        if not a["mean_p99"] < f["mean_p99"]:
            problems.append(
                f"full_adaptive p99 {a['mean_p99']:.1f} !< "
                f"frozen {f['mean_p99']:.1f}"
            )
    # splitting gate: hot-subset control beats whole-range migration on
    # imbalance without moving more data
    m = by.get(("multi_hotspot", "migrate"))
    s = by.get(("multi_hotspot", "split_hot"))
    if m and s:
        ok = (s["mean_imbalance"] <= m["mean_imbalance"] if quick
              else s["mean_imbalance"] < m["mean_imbalance"])
        if not ok:
            problems.append(
                f"split_hot imbalance {s['mean_imbalance']:.2f} !< "
                f"migrate {m['mean_imbalance']:.2f}"
            )
        if not s["total_migration_entries"] <= m["total_migration_entries"]:
            problems.append(
                f"split_hot moved {s['total_migration_entries']} entries "
                f"!<= migrate {m['total_migration_entries']}"
            )
    for r in rows:
        expect = 1 + r.get("growth_events", 0)
        if r["traces"] != expect:
            problems.append(
                f"{r['scenario']}/{r['policy']}: epoch step traced "
                f"{r['traces']}x (expected {expect})"
            )
    return problems


def run_profile(quick: bool) -> tuple[list[dict], list[str]]:
    """The epoch-pipeline profile: fused vs per-epoch driver, same scenario,
    same config — compile vs steady-state wall clock and host-sync counts.

    The comparison fuses the **whole run into one control period**
    (``period = n_epochs``) for both drivers: policy decisions and pull
    costs are then identical on both sides, so the measured delta is
    purely the device-resident pipeline (scan + donated buffers + one
    host sync per period vs one per epoch).

    Returns (rows, problems): the ratio gate (fused steady-state epochs/s
    ``>= PROFILE_RATIO_GATE x`` per-epoch) plus a deterministic host-sync
    gate (fused must make strictly fewer device->host round-trips) are
    the CI smoke assertions for the device-resident pipeline.
    """
    from repro.cluster import EpochDriver, make_policy, make_scenario

    scfg = scenario_config(quick)
    period = scfg.n_epochs
    rows, problems = [], []
    for pname in PROFILE_POLICIES:
        measured = {}
        for fused in (True, False):
            scen = make_scenario(
                PROFILE_SCENARIO, scfg,
                **scenario_kwargs(PROFILE_SCENARIO, scfg))
            drv = EpochDriver(scen, make_policy(pname),
                              cluster_config(quick, period=period),
                              fused=fused)
            t0 = time.perf_counter()
            drv.run()
            wall = time.perf_counter() - t0
            syncs_run = drv.host_syncs
            steady = _steady_epochs_per_s(drv, scfg.n_epochs, repeats=3)
            row = {
                "profile": True,
                "scenario": PROFILE_SCENARIO,
                "policy": pname,
                "fused": fused,
                "period": period,
                "epochs": scfg.n_epochs,
                "wall_s": round(wall, 3),
                "compile_s": round(wall - scfg.n_epochs / steady, 3),
                "steady_eps": round(steady, 2),
                "host_syncs": syncs_run,
                "host_syncs_per_epoch": round(syncs_run / scfg.n_epochs, 2),
                "traces": drv.traces,
            }
            measured[fused] = row
            rows.append(row)
            print(
                f"[profile] {pname:14s} {'fused' if fused else 'epoch':5s} "
                f"P={period} wall {row['wall_s']:6.2f}s "
                f"(compile ~{row['compile_s']:5.2f}s) "
                f"steady {row['steady_eps']:8.2f} epochs/s "
                f"syncs/epoch {row['host_syncs_per_epoch']:5.2f} "
                f"traces {row['traces']}"
            )
        gate = PROFILE_RATIO_GATE_QUICK if quick else PROFILE_RATIO_GATE
        ratio = measured[True]["steady_eps"] / max(measured[False]["steady_eps"], 1e-9)
        if ratio < gate:
            problems.append(
                f"profile: fused steady epochs/s only {ratio:.2f}x the "
                f"per-epoch driver on {PROFILE_SCENARIO}/{pname} "
                f"(gate {gate}x)"
            )
        if not measured[True]["host_syncs"] < measured[False]["host_syncs"]:
            problems.append(
                f"profile: fused driver made {measured[True]['host_syncs']} "
                f"host syncs !< per-epoch {measured[False]['host_syncs']} "
                f"on {PROFILE_SCENARIO}/{pname}"
            )
    return rows, problems


# the --trace pair: the adaptive-gate scenario under its winning policy
TRACE_SCENARIO = "shifting_hotspot"
TRACE_POLICY = "full_adaptive"
TRACE_ARTIFACT = "TRACE_balance.json"


def run_trace(quick: bool, out: str = TRACE_ARTIFACT
              ) -> tuple[list[dict], list[str]]:
    """The telemetry parity column (PR 7 acceptance assertions).

    Runs the adaptive-gate pair twice — ``telemetry=None`` and with span
    sampling on — and asserts the three hard telemetry contracts:

    * **off-mode bit-parity**: the telemetry-on run's ``EpochMetrics``
      rows equal the telemetry-off rows field-for-field (tracing is a
      pure observer — it may not perturb the metric stream);
    * **one compiled step**: span collection lives inside the fused scan
      body, so ``drv.traces`` must stay 1;
    * **exact reconstruction**: every sampled span's latency bucket
      decomposition sums back to its DES closed-loop latency with zero
      float64 error (``TelemetryRecorder.verify_exact() == 0.0``).

    Writes the Chrome-trace artifact to ``out`` and returns
    (rows, problems).
    """
    import dataclasses

    from repro.cluster import (
        EpochDriver, TelemetryConfig, make_policy, make_scenario,
    )

    scfg = scenario_config(quick)
    kw = scenario_kwargs(TRACE_SCENARIO, scfg)
    # sample aggressively at smoke sizes so the parity run records >0
    # spans; full size uses the default 1/64 production rate
    tcfg = TelemetryConfig(sample_rate=1 / 4 if quick else 1 / 64)

    def drive(tel):
        scen = make_scenario(TRACE_SCENARIO, scfg, **kw)
        drv = EpochDriver(scen, make_policy(TRACE_POLICY),
                          dataclasses.replace(cluster_config(quick),
                                              telemetry=tel))
        return drv, drv.run()

    _, base = drive(None)
    drv, traced = drive(tcfg)

    problems = []
    if [r.to_row() for r in base] != [r.to_row() for r in traced]:
        problems.append(
            "trace: telemetry-on EpochMetrics rows differ from the "
            "telemetry-off run (tracing perturbed the metric stream)")
    if drv.traces != 1:
        problems.append(
            f"trace: epoch step traced {drv.traces}x with sampling on "
            "(expected 1)")
    err = drv.telemetry.verify_exact()
    if err != 0.0:
        problems.append(
            f"trace: span latency reconstruction off by {err!r} "
            "(must be exactly 0.0)")
    n_spans = drv.telemetry.span_count
    if n_spans == 0:
        problems.append("trace: sampling enabled but zero spans recorded")

    path = drv.telemetry.write_chrome_trace(out)
    summ = drv.telemetry.summary()
    row = {
        "trace": True,
        "scenario": TRACE_SCENARIO,
        "policy": TRACE_POLICY,
        "sample_rate": tcfg.sample_rate,
        "spans": n_spans,
        "n_sampled": summ["spans_sampled"],
        "reconstruction_max_err": err,
        "traces": drv.traces,
        "parity": not problems,
        "artifact": path,
    }
    print(
        f"[trace] {TRACE_SCENARIO}/{TRACE_POLICY} spans {n_spans} "
        f"(sampled {summ['spans_sampled']}) reconstruction err {err!r} "
        f"traces {drv.traces} -> {path}"
    )
    return [row], problems


def run_dist_parity(quick: bool) -> list[dict]:
    """Dist-backend parity column in a subprocess (forced 8-device mesh).

    jax pins the host device count at first init, so the parent process
    (which already initialized jax for the oracle matrix) cannot host the
    mesh itself.
    """
    env = {
        **os.environ,
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8 "
        + os.environ.get("XLA_FLAGS", ""),
        "JAX_PLATFORMS": "cpu",
    }
    cmd = [sys.executable, "-m", "benchmarks.balance_bench", "--dist-worker"]
    if quick:
        cmd.append("--quick")
    r = subprocess.run(cmd, env=env, capture_output=True, text=True)
    if r.returncode != 0:
        print(r.stdout)
        print(r.stderr, file=sys.stderr)
        raise RuntimeError("dist parity worker failed")
    payload = json.loads(r.stdout.splitlines()[-1])
    return payload["rows"]


def dist_worker(quick: bool) -> int:
    from repro.core import DistConfig
    from repro.core.dist_store import make_mesh

    mesh = make_mesh((8,))
    # a tight per-(source,target) queue bound so the flash crowd actually
    # exercises switch queue pressure: overflowing queries are dropped
    # and counted as client retries (the quantity this column reports)
    dist_cfg = DistConfig(bucket_cap=16 if quick else 24)
    rows = run_matrix([DIST_SCENARIO], list(DIST_POLICIES), quick,
                      backend="dist", mesh=mesh, dist_cfg=dist_cfg,
                      verbose=False)
    rows.append(_dist_growth_row(mesh, quick))
    print(json.dumps({"rows": rows}))
    return 0


def _dist_growth_row(mesh, quick: bool) -> dict:
    """keyspace_growth under capacity pressure on the dist backend: the
    pool must actually grow mid-run, and growth must cost exactly one
    re-specialization of the fused period program
    (``traces == 1 + growth_events`` — checked by the --dist gate)."""
    from repro.cluster import (ClusterConfig, EpochDriver, ScenarioConfig,
                               make_policy, make_scenario, summarize)

    scfg = ScenarioConfig(n_epochs=6 if quick else 10, epoch_ops=512,
                          n_records=2048, read_ratio=0.3, value_dim=2,
                          seed=1)
    scen = make_scenario("keyspace_growth", scfg)
    drv = EpochDriver(
        scen, make_policy("full_adaptive"),
        ClusterConfig(num_nodes=8, num_ranges=8, n_slots=8, replication=1,
                      r_max=2, capacity=64, split_overflow=True,
                      report_every=2),
        backend="dist", mesh=mesh)
    epochs = drv.run()
    row = summarize(epochs)
    row.update({
        "scenario": "keyspace_growth",
        "bench": "dist_growth",
        "backend": "dist",
        "fused": True,
        "traces": drv.traces,
        "growth_events": drv.growth_events,
        "host_syncs": drv.host_syncs,
    })
    return row


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="tiny sizes (CI smoke)")
    ap.add_argument("--scenarios", default=",".join(DEFAULT_SCENARIOS))
    ap.add_argument("--policies", default=",".join(DEFAULT_POLICIES))
    ap.add_argument("--service", default="fixed",
                    choices=("fixed", "lognormal", "pareto"),
                    help="per-hop service-time distribution (ServiceModel)")
    ap.add_argument("--dist", action="store_true",
                    help="also run the dist-backend parity column "
                         "(8-device host mesh subprocess)")
    ap.add_argument("--dist-worker", action="store_true",
                    help=argparse.SUPPRESS)  # internal: the forked mesh run
    ap.add_argument("--period", default=str(DEFAULT_PERIOD),
                    help="control-pull cadence = fused scan length "
                         f"(default {DEFAULT_PERIOD}); 'auto' adapts the "
                         "cadence to report-to-report load drift")
    ap.add_argument("--per-epoch", action="store_true",
                    help="run the per-epoch reference driver instead of "
                         "the fused period pipeline")
    ap.add_argument("--profile", action="store_true",
                    help="also run the fused vs per-epoch pipeline profile "
                         "(steady-state epochs/s + host-sync counts, with "
                         "the ratio gate)")
    ap.add_argument("--replication", action="store_true",
                    help="also run the three-mode replication comparison "
                         "(eventual/chain/craq tail latencies + gates)")
    ap.add_argument("--trace", action="store_true",
                    help="also run the telemetry parity column and emit a "
                         "Chrome-trace artifact (see --trace-out)")
    ap.add_argument("--trace-out", default=TRACE_ARTIFACT,
                    help=f"Chrome-trace artifact path (default "
                         f"{TRACE_ARTIFACT})")
    ap.add_argument("--json", default=None, help="write rows to this path")
    ap.add_argument("--no-check", action="store_true",
                    help="skip the acceptance gate (exploratory runs)")
    args = ap.parse_args(argv)
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.dist_worker:
        return dist_worker(args.quick)

    period = args.period if args.period == "auto" else int(args.period)
    scenarios = [s for s in args.scenarios.split(",") if s]
    policies = [p for p in args.policies.split(",") if p]
    rows = run_matrix(scenarios, policies, args.quick, service=args.service,
                      period=period, fused=not args.per_epoch,
                      measure_steady=True)

    profile_problems: list[str] = []
    if args.profile:
        profile_rows, profile_problems = run_profile(args.quick)
        rows.extend(profile_rows)

    trace_problems: list[str] = []
    if args.trace:
        trace_rows, trace_problems = run_trace(args.quick, args.trace_out)
        rows.extend(trace_rows)

    replication_problems: list[str] = []
    if args.replication:
        from repro.replication.bench import (
            check_filter_arm, check_replication, run_filter_arm,
            run_replication_matrix,
        )
        repl_rows = run_replication_matrix(args.quick)
        replication_problems = check_replication(repl_rows)
        filter_rows = run_filter_arm(args.quick)
        replication_problems += check_filter_arm(filter_rows)
        rows.extend(repl_rows)
        rows.extend(filter_rows)

    dist_problems: list[str] = []
    if args.dist:
        dist_rows = run_dist_parity(args.quick)
        for r in dist_rows:
            print(
                f"[dist] {r['scenario']:14s} {r['policy']:14s} "
                f"imb {r['mean_imbalance']:5.2f} p99 {r['mean_p99']:6.1f} "
                f"retries {r['total_retries']:4d} "
                f"({r['total_retries'] / max(r['epochs'], 1):.1f}/epoch) "
                f"traces {r['traces']} grows {r.get('growth_events', 0)}"
            )
            expect = 1 + r.get("growth_events", 0)
            if r["traces"] != expect:
                dist_problems.append(
                    f"dist {r['scenario']}/{r['policy']}: traces "
                    f"{r['traces']} != 1 + growth_events ({expect})"
                )
        grow = [r for r in dist_rows if r.get("bench") == "dist_growth"]
        if grow and grow[0]["growth_events"] < 1:
            dist_problems.append(
                "keyspace_growth --dist never grew the pool under "
                "capacity pressure"
            )
        rows.extend(dist_rows)

    if args.json:
        with open(args.json, "w") as f:
            json.dump({"quick": args.quick, "service": args.service,
                       "rows": rows}, f, indent=1)
        print(f"wrote {args.json} ({len(rows)} rows)")
        from benchmarks import history
        history.append("balance", {"quick": args.quick,
                                   "service": args.service, "rows": rows})

    if not args.no_check:
        problems = (check_acceptance(rows, quick=args.quick)
                    + profile_problems + trace_problems
                    + replication_problems + dist_problems)
        if problems:
            print("ACCEPTANCE FAILED:")
            for p in problems:
                print("  -", p)
            return 1
        gates = []
        if "shifting_hotspot" in scenarios:
            gates.append("full_adaptive < frozen on imbalance AND p99")
        if "multi_hotspot" in scenarios:
            gates.append("split_hot < migrate on imbalance at <= entries moved")
        gates.append("all steps compiled once")
        if args.dist:
            gates.append("dist: traces == 1 + growth_events, pool grows "
                         "under keyspace_growth capacity pressure")
        if args.profile:
            g = PROFILE_RATIO_GATE_QUICK if args.quick else PROFILE_RATIO_GATE
            gates.append(
                f"fused steady epochs/s >= {g}x per-epoch at fewer syncs")
        if args.trace:
            gates.append(
                "telemetry: off-mode bit-parity, one compiled step, "
                "exact span reconstruction")
        if args.replication:
            gates.append(
                "craq clean-read p99 <= chain tail-read p99 on read-heavy "
                "diurnal; dirty bounces only (and always) under craq writes")
        print("acceptance: " + "; ".join(gates))
    return 0


if __name__ == "__main__":
    sys.exit(main())
