"""Fleet-metrics-plane benchmark: parity gate + forced-breach incident.

Two arms, both CI-gated (the ``metrics-smoke`` job):

* **parity** — shifting_hotspot / full_adaptive, three drivers: metrics
  plane OFF (fused), ON (fused), ON (per-epoch).  Gates: the
  ``EpochMetrics`` stream is bit-identical with the ring on vs off (the
  plane is a pure observer), every ring leaf is bitwise equal between
  the fused scan and the per-epoch reference loop, and the fused step
  still compiles exactly once.
* **breach** — retry_storm on the *plain* (uncontrolled) arm with the
  overload queue physics on: admission stays open, so the storm drives
  the fleet p999 through the declared SLO bound.  Gates: the burn-rate
  alert's firing epochs match :func:`repro.telemetry.slo.reference_alerts`
  (an independent numpy oracle over the same f32 series) **exactly**;
  the rising edge triggered a flight-recorder dump; and
  ``incident.report()`` emits a complete postmortem (alert timeline,
  breach list, flight dump paths, p999 attribution shares, retry
  orbits, stage timers).  Artifacts: ``INCIDENT_metrics_smoke.{json,md}``,
  ``METRICS_view.json`` (the dashboard input), ``DASH_metrics.txt`` (the
  rendered terminal snapshot), plus the OpenMetrics exposition check.

Run: ``PYTHONPATH=src python -m benchmarks.metrics_bench
[--quick] [--json BENCH_metrics.json] [--no-check]``
"""

from __future__ import annotations

import argparse
import json
import sys
import time

SMOKE_TAG = "metrics_smoke"
VIEW_ARTIFACT = "METRICS_view.json"
DASH_ARTIFACT = "DASH_metrics.txt"


def scenario_config(quick: bool):
    from repro.cluster import ScenarioConfig

    if quick:
        return ScenarioConfig(n_epochs=16, epoch_ops=512, n_records=2048,
                              value_dim=4, seed=7)
    return ScenarioConfig(n_epochs=24, epoch_ops=2048, n_records=4096,
                          value_dim=8, seed=7)


def slo_spec(quick: bool):
    from repro.telemetry.slo import SLO

    # bound well under the storm's sustained tail so the breach is
    # forced, objective/windows tight enough that the burn alert fires
    # within the run
    return SLO(name="p999_fleet", series="p999",
               bound=120.0 if quick else 150.0,
               objective=0.9, fast_window=2, slow_window=4,
               fast_burn=2.0, slow_burn=1.0)


# ---------------------------------------------------------------------------
# arm 1: pure-observer parity
# ---------------------------------------------------------------------------

def run_parity(quick: bool) -> tuple[list[dict], list[str]]:
    import numpy as np

    from repro.cluster import (ClusterConfig, EpochDriver, make_policy,
                               make_scenario, summarize)
    from repro.telemetry.metrics import MetricsConfig

    scfg = scenario_config(quick)

    def ccfg(metrics):
        return ClusterConfig(num_nodes=8, num_ranges=32, replication=2,
                             r_max=4, n_clients=16, report_every=2,
                             imbalance_threshold=1.1, max_moves_per_round=6,
                             metrics=metrics)

    def drive(metrics, fused):
        scen = make_scenario("shifting_hotspot", scfg,
                             theta=1.2, shift_every=2)
        drv = EpochDriver(scen, make_policy("full_adaptive"),
                          ccfg(metrics), fused=fused)
        t0 = time.perf_counter()
        rows = drv.run()
        return drv, rows, time.perf_counter() - t0

    mcfg = MetricsConfig(window=64, topk=4)
    drv_off, rows_off, _ = drive(None, True)
    drv_on, rows_on, wall = drive(mcfg, True)
    drv_ref, rows_ref, _ = drive(mcfg, False)

    problems = []
    if [r.to_row() for r in rows_off] != [r.to_row() for r in rows_on]:
        problems.append(
            "parity: metrics-on EpochMetrics rows differ from metrics-off "
            "(the ring perturbed the stream it observes)")
    if [r.to_row() for r in rows_on] != [r.to_row() for r in rows_ref]:
        problems.append("parity: fused rows differ from per-epoch rows")
    if not np.array_equal(np.asarray(drv_on.metrics.ring),
                          np.asarray(drv_ref.metrics.ring)):
        problems.append(
            "parity: fused metrics ring != per-epoch ring (bitwise)")
    if int(drv_on.metrics.pos) != int(drv_ref.metrics.pos):
        problems.append("parity: ring pos diverged fused vs per-epoch")
    for tag, drv in (("off", drv_off), ("on", drv_on)):
        if drv.traces != 1:
            problems.append(
                f"parity: fused step (metrics {tag}) traced {drv.traces}x")

    row = summarize(rows_on)
    row.update(bench="metrics_parity", arm="parity", wall_s=round(wall, 3),
               traces=drv_on.traces, ring_pos=int(drv_on.metrics.pos),
               n_series=drv_on.met_layout.n_series)
    return [row], problems


# ---------------------------------------------------------------------------
# arm 2: forced SLO breach -> burn alert -> incident artifact
# ---------------------------------------------------------------------------

def run_breach(quick: bool, out_dir: str = "."
               ) -> tuple[list[dict], list[str]]:
    import numpy as np

    from repro.cluster import (ClusterConfig, EpochDriver, TelemetryConfig,
                               make_policy, make_scenario, summarize)
    from repro.overload import OverloadConfig
    from repro.telemetry import dashboard, incident
    from repro.telemetry import metrics as MTR
    from repro.telemetry import slo as SLOM
    from repro.telemetry.metrics import MetricsConfig

    spec = slo_spec(quick)
    ovl = (OverloadConfig(queue_cap=48, service_rate=80, inflation=3.0,
                          max_level=3, backoff_base=1, jitter_span=2,
                          queue_weight=2) if quick else
           OverloadConfig(queue_cap=192, service_rate=320, inflation=3.0,
                          max_level=3, backoff_base=1, jitter_span=2,
                          queue_weight=2))
    ccfg = ClusterConfig(
        num_nodes=10, num_ranges=20, replication=2, overload=ovl,
        standby_nodes=(8, 9), report_every=2,
        telemetry=TelemetryConfig(sample_rate=1 / 4 if quick else 1 / 64,
                                  flight_dir=out_dir, flight_epochs=4),
        metrics=MetricsConfig(window=64, slos=(spec,)),
    )
    scen = make_scenario("retry_storm", scenario_config(quick))
    drv = EpochDriver(scen, make_policy("full_adaptive"), ccfg, fused=True)
    t0 = time.perf_counter()
    rows = drv.run()
    wall = time.perf_counter() - t0

    problems = []
    # ground truth: the independent numpy oracle over the same f32 series
    vals = np.asarray([r.p999 for r in rows], np.float32)
    ref = SLOM.reference_alerts(vals, spec)
    fired = drv.met_engine.firing_epochs(spec.name)
    if not fired:
        problems.append("breach: the forced p999 SLO never fired")
    if fired != ref["fire_epochs"]:
        problems.append(
            f"breach: alert firing epochs {fired} != ground truth "
            f"{ref['fire_epochs']}")
    if not any(b.startswith("slo_burn:") for b in drv.telemetry.breaches):
        problems.append("breach: rising edge did not reach the recorder")
    if not drv.telemetry.flight.dumps:
        problems.append("breach: no flight-recorder dump was written")

    # one-command postmortem, checked for completeness
    doc = incident.report(drv, out_dir=out_dir, tag=SMOKE_TAG)
    for key in ("alerts", "slos", "metrics", "breaches", "flight_dumps",
                "p999_attribution", "stage_timers"):
        if not doc.get(key):
            problems.append(f"breach: incident report missing '{key}'")
    if "retry_orbits" not in doc:     # may legitimately be empty
        problems.append("breach: incident report missing 'retry_orbits'")
    if doc.get("alerts", {}).get("fires", 0) < 1:
        problems.append("breach: incident alert timeline has no fire")
    if "share" not in doc.get("p999_attribution", {}):
        problems.append("breach: attribution lacks bucket shares")

    # dashboard snapshot + OpenMetrics exposition over the same view
    view = drv.metrics_view()
    MTR.write_view(f"{out_dir}/{VIEW_ARTIFACT}", view,
                   alerts=drv.alert_timeline())
    with open(f"{out_dir}/{VIEW_ARTIFACT}") as f:
        snap = dashboard.render(json.load(f))
    with open(f"{out_dir}/{DASH_ARTIFACT}", "w") as f:
        f.write(snap)
    if "p999" not in snap or "fire" not in snap:
        problems.append("breach: dashboard snapshot lacks p999/alert rows")
    om = MTR.to_openmetrics(view)
    if "turbokv_p999" not in om or not om.endswith("# EOF\n"):
        problems.append("breach: OpenMetrics exposition malformed")

    row = summarize(rows)
    row.update(bench="metrics_breach", arm="breach", wall_s=round(wall, 3),
               traces=drv.traces, slo_bound=spec.bound,
               fire_epochs=fired, ref_fire_epochs=ref["fire_epochs"],
               alert_fires=doc["alerts"]["fires"],
               flight_dumps=len(drv.telemetry.flight.dumps),
               incident_paths=doc.get("paths", []))
    return [row], problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--json", default=None, help="write rows to this path")
    ap.add_argument("--out-dir", default=".")
    ap.add_argument("--no-check", action="store_true")
    args = ap.parse_args(argv)
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    rows_p, prob_p = run_parity(args.quick)
    rows_b, prob_b = run_breach(args.quick, args.out_dir)
    rows = rows_p + rows_b
    problems = prob_p + prob_b
    for r in rows:
        print(f"{r['bench']:16s} wall {r['wall_s']:7.2f}s "
              f"traces {r['traces']}")

    doc = {"quick": args.quick, "parity_ok": not prob_p,
           "alert_epoch_ok": not any("firing" in p or "never fired" in p
                                     for p in prob_b),
           "incident_complete": not any("incident" in p for p in prob_b),
           "rows": rows}
    if args.json:
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=1, default=str)
        print(f"wrote {args.json} ({len(rows)} rows)")
        from benchmarks import history
        history.append("metrics", doc)

    if not args.no_check and problems:
        print("\nGATE FAILURES:", file=sys.stderr)
        for p in problems:
            print(f"  - {p}", file=sys.stderr)
        return 1
    print("metrics gates: OK" if not problems else
          f"metrics gates: {len(problems)} problem(s) (unchecked)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
