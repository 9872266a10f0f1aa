"""Scale-out benchmark: the dist-backend period pipeline on 8 devices (PR 8).

Four drivers run the same shifting-hotspot scenario under the same
adaptive policy and report steady-state epochs/s, host syncs/epoch and
per-stage wall breakdowns:

* ``single_host``       — the oracle-backend pipeline at the repo's
  default control cadence (period=1): the production single-host path;
* ``single_host_fused`` — the same pipeline with the whole run fused
  into one control period: the single-host roofline;
* ``dist_epoch``        — the dist backend stepping shard_map once per
  epoch (the pre-PR-8 dist path), whole-run period;
* ``dist_fused``        — whole periods as ONE shard_map program with
  the epoch scan inside (PR 8's tentpole), whole-run period.

Because jax pins the host device count at first init, the measurement
runs in a subprocess with ``--xla_force_host_platform_device_count=8``.
Those devices are host threads: on a c-core box the 8 program instances
serialize ~8/c-fold, so the roofline ratio is environment-bound, not
program-bound (measured mesh-size scaling on one core: 80.5 / 52.1 /
27.5 / 21.9 epochs/s at 1 / 2 / 4 / 8 devices — near-linear in the
serialized instance count, i.e. the fused program itself adds almost
nothing over the oracle at mesh size 1).

Gates (skipped with ``--no-check``):

* **parity** — ``dist_fused`` must be bit-identical to ``dist_epoch``:
  the full :class:`EpochMetrics` stream and the final store
  (keys/values/overflow);
* **ratio**  — ``single_host`` steady-state epochs/s may beat
  ``dist_fused`` by at most ``RATIO_GATE`` (2x), i.e. scale-out keeps
  >= 0.5x the production single-host throughput even where the host
  serializes all 8 devices (on real parallel devices the ratio drops
  toward the collective cost alone);
* **syncs**  — ``dist_fused`` host syncs/epoch must not exceed
  ``dist_epoch``'s.

Run: ``PYTHONPATH=src python -m benchmarks.dist_bench
[--quick] [--json BENCH_dist.json] [--no-check]``
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import subprocess
import sys
import time

SCENARIO = "shifting_hotspot"
POLICY = "full_adaptive"
RATIO_GATE = 2.0


def _stage_breakdown(drv) -> dict:
    s = drv.telemetry.timers.summary()
    return {"stage_s": s["stage_s"], "stage_share": s["stage_share"]}


def worker(quick: bool) -> int:
    """Forced-8-device measurement (subprocess body)."""
    import numpy as np

    from benchmarks.balance_bench import (
        _steady_epochs_per_s, cluster_config, scenario_config,
        scenario_kwargs,
    )
    from repro.cluster import (EpochDriver, make_policy, make_scenario,
                               summarize)
    from repro.core import DistConfig
    from repro.core.dist_store import make_mesh
    from repro.telemetry import TelemetryConfig

    mesh = make_mesh((8,))
    scfg = scenario_config(quick)
    kw = scenario_kwargs(SCENARIO, scfg)
    # dist rows fuse the whole run into one control period (the
    # run_profile framing) so the one-shard_map-per-period structure
    # actually amortizes; the bucket bound matches balance_bench's
    # switch-queue pressure column (overflow drops count as retries)
    period = scfg.n_epochs
    dist_cfg = DistConfig(bucket_cap=16 if quick else 24)
    variants = (
        ("single_host", "oracle", True, 1),
        ("single_host_fused", "oracle", True, period),
        ("dist_epoch", "dist", False, period),
        ("dist_fused", "dist", True, period),
    )
    rows, finals = [], {}
    for name, backend, fused, per in variants:
        scen = make_scenario(SCENARIO, scfg, **kw)
        drv = EpochDriver(scen, make_policy(POLICY),
                          cluster_config(quick, period=per),
                          backend=backend,
                          mesh=mesh if backend == "dist" else None,
                          dist_cfg=dist_cfg if backend == "dist" else None,
                          fused=fused)
        t0 = time.perf_counter()
        epochs = drv.run()
        wall = time.perf_counter() - t0
        syncs_run = drv.host_syncs  # before the steady re-runs accumulate
        steady = _steady_epochs_per_s(drv, scfg.n_epochs, repeats=3)
        finals[name] = (drv, epochs)

        # separate profiled pass so the timed runs carry no telemetry
        scen_p = make_scenario(SCENARIO, scfg, **kw)
        ccfg_p = dataclasses.replace(
            cluster_config(quick, period=per),
            telemetry=TelemetryConfig(sample_rate=1.0 / 64.0))
        drv_p = EpochDriver(scen_p, make_policy(POLICY), ccfg_p,
                            backend=backend,
                            mesh=mesh if backend == "dist" else None,
                            dist_cfg=dist_cfg if backend == "dist" else None,
                            fused=fused)
        drv_p.run()

        row = summarize(epochs)
        row.update({
            "bench": "dist_scaleout",
            "variant": name,
            "backend": backend,
            "fused": fused,
            "period": per,
            "epochs": scfg.n_epochs,
            "wall_s": round(wall, 3),
            "steady_eps": round(steady, 2),
            "host_syncs": syncs_run,
            "host_syncs_per_epoch": round(syncs_run / scfg.n_epochs, 2),
            "traces": drv.traces,
            **_stage_breakdown(drv_p),
        })
        rows.append(row)

    # bit parity: fused dist vs per-epoch dist
    problems = []
    (drv_r, ep_r), (drv_f, ep_f) = finals["dist_epoch"], finals["dist_fused"]
    for a, b in zip(ep_r, ep_f):
        da, db = dataclasses.asdict(a), dataclasses.asdict(b)
        for k in da:
            if da[k] != db[k]:
                problems.append(
                    f"parity: epoch {a.epoch} field {k}: {da[k]} != {db[k]}")
    for f in ("keys", "values", "overflow"):
        if not np.array_equal(np.asarray(getattr(drv_r.store, f)),
                              np.asarray(getattr(drv_f.store, f))):
            problems.append(f"parity: final store field {f} differs")
    if drv_f.traces != 1:
        problems.append(f"dist_fused retraced: {drv_f.traces} != 1")

    print(json.dumps({"rows": rows, "problems": problems}))
    return 0


def check(rows: list[dict]) -> list[str]:
    by = {r["variant"]: r for r in rows if r.get("bench") == "dist_scaleout"}
    problems = []
    ratio = by["single_host"]["steady_eps"] / max(
        by["dist_fused"]["steady_eps"], 1e-9)
    roofline = by["single_host_fused"]["steady_eps"] / max(
        by["dist_fused"]["steady_eps"], 1e-9)
    print(f"ratio vs single_host {ratio:.2f}x (gate {RATIO_GATE}x); "
          f"vs fused roofline {roofline:.2f}x (informational — "
          f"host-serialized mesh)")
    if ratio > RATIO_GATE:
        problems.append(
            f"ratio: single-host is {ratio:.2f}x dist_fused steady epochs/s "
            f"(gate {RATIO_GATE}x)")
    if (by["dist_fused"]["host_syncs_per_epoch"]
            > by["dist_epoch"]["host_syncs_per_epoch"]):
        problems.append(
            f"syncs: dist_fused {by['dist_fused']['host_syncs_per_epoch']}"
            f"/epoch > dist_epoch "
            f"{by['dist_epoch']['host_syncs_per_epoch']}/epoch")
    return problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true", help="CI smoke sizes")
    ap.add_argument("--json", default=None, help="write rows to this path")
    ap.add_argument("--no-check", action="store_true",
                    help="skip the gates (exploratory runs)")
    ap.add_argument("--worker", action="store_true",
                    help=argparse.SUPPRESS)  # internal: the forked mesh run
    args = ap.parse_args(argv)
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.worker:
        return worker(args.quick)

    env = {
        **os.environ,
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8 "
        + os.environ.get("XLA_FLAGS", ""),
        "JAX_PLATFORMS": "cpu",
    }
    cmd = [sys.executable, "-m", "benchmarks.dist_bench", "--worker"]
    if args.quick:
        cmd.append("--quick")
    r = subprocess.run(cmd, env=env, capture_output=True, text=True)
    if r.returncode != 0:
        print(r.stdout)
        print(r.stderr, file=sys.stderr)
        raise RuntimeError("dist_bench worker failed")
    payload = json.loads(r.stdout.splitlines()[-1])
    rows, problems = payload["rows"], payload["problems"]

    for row in rows:
        shares = ", ".join(f"{k} {v:.0%}"
                           for k, v in sorted(row["stage_share"].items(),
                                              key=lambda kv: -kv[1]))
        print(f"{row['variant']:12s} steady {row['steady_eps']:8.2f} ep/s "
              f"wall {row['wall_s']:6.2f}s "
              f"syncs/epoch {row['host_syncs_per_epoch']:5.2f} "
              f"traces {row['traces']}  [{shares}]")

    if args.json:
        with open(args.json, "w") as f:
            json.dump({"quick": args.quick, "rows": rows}, f, indent=1)
        print(f"wrote {args.json} ({len(rows)} rows)")
        from benchmarks import history
        history.append("dist", {"quick": args.quick, "rows": rows})

    if not args.no_check:
        problems = problems + check(rows)
        if problems:
            print("ACCEPTANCE FAILED:")
            for p in problems:
                print(" -", p)
            return 1
        print("acceptance: dist_fused bit-identical to dist_epoch; "
              f"single-host <= {RATIO_GATE}x dist_fused steady epochs/s; "
              "fused syncs/epoch <= per-epoch")
    return 0


if __name__ == "__main__":
    sys.exit(main())
