"""Pipeline profiler: stage wall timers + per-kernel roofline rows.

Two halves, both feeding the ROADMAP's dist-fusion / roofline items:

* :class:`StageTimers` — cheap wall-clock accumulators the epoch driver
  wraps around its pipeline stages (inject / route+apply device step /
  DES / host-sync / control / telemetry).  Disabled they are a no-op
  context; enabled they also block on the device step's output so the
  timer measures execution, not dispatch (an explicit observer effect —
  values are unchanged, only wall time is).
* :func:`kernel_roofline_rows` — lower + compile the five routing hot
  kernels (``range_match`` / ``range_match_spread`` /
  ``range_match_spread_dirty`` / ``range_match_apply`` — PR 8's fused
  route→apply — / ``range_match_stale`` — PR 9's replicated-tier stale
  lookup), feed the compiled HLO through
  ``launch/hlo_stats.analyze_hlo`` and place each against the peaks of
  the running chip from the ``launch/mesh.PEAKS`` table, keyed by the
  ``device_kind`` of ``jax.devices()[0]`` (a kind with no entry is an
  error).  Pass ``use_pallas=True`` on the TPU for the kernel build; the
  reference (non-Pallas) implementation is the same math.

CLI: ``PYTHONPATH=src python -m repro.telemetry.profiler --json
BENCH_kernel_roofline.json`` writes the roofline table, priced against
the running chip; on a device with no peak table (the CPU) it exits
with an error.
"""

from __future__ import annotations

import contextlib
import time

import numpy as np

from repro.launch.hlo_stats import analyze_hlo
from repro.launch.mesh import device_peaks


class StageTimers:
    """Named wall-clock accumulators for the epoch pipeline stages."""

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self.totals: dict[str, float] = {}
        self.calls: dict[str, int] = {}

    @contextlib.contextmanager
    def stage(self, name: str):
        if not self.enabled:
            yield
            return
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            self.totals[name] = self.totals.get(name, 0.0) + dt
            self.calls[name] = self.calls.get(name, 0) + 1

    def summary(self) -> dict:
        total = sum(self.totals.values())
        return {
            "stage_s": {k: round(v, 6) for k, v in self.totals.items()},
            "stage_calls": dict(self.calls),
            "stage_share": {
                k: round(v / total, 4) if total > 0 else 0.0
                for k, v in self.totals.items()
            },
            "total_s": round(total, 6),
        }


# ---------------------------------------------------------------------------
# kernel roofline
# ---------------------------------------------------------------------------

KERNELS = ("range_match", "range_match_spread", "range_match_spread_dirty",
           "range_match_apply", "range_match_stale")


def _kernel_thunks(*, batch, num_ranges, num_nodes, replication, r_max,
                   n_slots, use_pallas, seed, capacity=1024, n_switches=4):
    import jax
    import jax.numpy as jnp

    from repro import core as C
    from repro.coordination_tier import state as CTS
    from repro.kernels.range_match import ops as KOPS

    directory = C.make_directory(num_ranges, num_nodes, replication,
                                 r_max=r_max, n_slots=n_slots)
    rng = jax.random.PRNGKey(seed)
    keys = jax.random.randint(
        rng, (batch,), 0, np.iinfo(np.int32).max, dtype=jnp.int32
    ).astype(jnp.uint32)
    opcodes = jnp.zeros((batch,), jnp.int32)          # GET hot path
    load_reg = jnp.zeros((num_nodes,), jnp.uint32)
    dirty = jnp.zeros((directory.num_slots, r_max), jnp.bool_)
    r2 = jax.random.fold_in(rng, 1)
    # PR 8's fused route->apply also binary-searches each serving node's
    # sorted slab: give it a populated (N, C) keys table.
    store_keys = jnp.sort(jax.random.randint(
        jax.random.fold_in(rng, 2), (num_nodes, capacity), 0,
        np.iinfo(np.int32).max, dtype=jnp.int32).astype(jnp.uint32), axis=1)
    # PR 9's replicated-tier stale lookup routes against per-switch table
    # copies; every switch starts at the controller's committed snapshot.
    tables = {k: np.asarray(getattr(directory, k)) for k in
              ("slot_lo", "slot_hi", "live", "chains", "chain_len")}
    coord = CTS.make_state(tables, n_switches)
    kw = dict(use_pallas=use_pallas)
    return {
        "range_match": lambda: KOPS.range_match(
            directory, keys, opcodes, **kw),
        "range_match_spread": lambda: KOPS.range_match_spread(
            directory, keys, opcodes, load_reg, r2, **kw),
        "range_match_spread_dirty": lambda: KOPS.range_match_spread_dirty(
            directory, keys, opcodes, load_reg, dirty, r2, **kw),
        "range_match_apply": lambda: KOPS.range_match_apply(
            directory, keys, opcodes, load_reg, dirty, store_keys, r2, **kw),
        "range_match_stale": lambda: KOPS.range_match_stale(
            coord, keys, opcodes, **kw),
    }


def kernel_roofline_rows(*, batch: int = 4096, num_ranges: int = 64,
                         num_nodes: int = 8, replication: int = 2,
                         r_max: int = 4, n_slots: int | None = None,
                         use_pallas: bool = False, seed: int = 0,
                         measure_iters: int = 5) -> list[dict]:
    """Compile each routing kernel and return its roofline row against
    the peaks of the running device's kind."""
    import jax

    kind = jax.devices()[0].device_kind
    peaks = device_peaks(kind)

    thunks = _kernel_thunks(
        batch=batch, num_ranges=num_ranges, num_nodes=num_nodes,
        replication=replication, r_max=r_max,
        n_slots=(2 * num_ranges if n_slots is None else n_slots),
        use_pallas=use_pallas, seed=seed,
    )
    rows = []
    for name in KERNELS:
        fn = jax.jit(thunks[name])
        compiled = fn.lower().compile()
        stats = analyze_hlo(compiled.as_text())
        flops = float(stats["flops_per_device"])
        bytes_ = float(stats["bytes_per_device"])
        # measured wall time: median of a few synced calls (first call
        # above already compiled, so no compile time leaks in)
        jax.block_until_ready(fn())
        times = []
        for _ in range(measure_iters):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            times.append(time.perf_counter() - t0)
        wall_us = float(np.median(times) * 1e6)
        t_compute_us = flops / peaks["flops_bf16"] * 1e6
        t_memory_us = bytes_ / peaks["hbm_bw"] * 1e6
        rows.append({
            "kernel": name,
            "impl": "pallas" if use_pallas else "ref",
            "device_kind": kind,
            "batch": batch,
            "n_slots": 2 * num_ranges if n_slots is None else n_slots,
            "flops": flops,
            "bytes": bytes_,
            "intensity_flop_per_byte": flops / bytes_ if bytes_ else 0.0,
            "t_compute_us": t_compute_us,
            "t_memory_us": t_memory_us,
            "bound": "memory" if t_memory_us >= t_compute_us else "compute",
            "roofline_us": max(t_compute_us, t_memory_us),
            "measured_us": wall_us,
            "queries_per_s_roofline": batch / (
                max(t_compute_us, t_memory_us) * 1e-6),
        })
    return rows


def fmt_roofline_md(rows: list[dict]) -> str:
    hdr = ("| kernel | impl | B | flops | bytes | FLOP/B | roofline µs "
           "| bound | measured µs |\n|---|---|---|---|---|---|---|---|---|")
    lines = [hdr]
    for r in rows:
        lines.append(
            f"| {r['kernel']} | {r['impl']} | {r['batch']} "
            f"| {r['flops']:.3g} | {r['bytes']:.3g} "
            f"| {r['intensity_flop_per_byte']:.3f} "
            f"| {r['roofline_us']:.2f} | {r['bound']} "
            f"| {r['measured_us']:.1f} |"
        )
    return "\n".join(lines)


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--batch", type=int, default=4096)
    ap.add_argument("--pallas", action="store_true",
                    help="analyze the Pallas build (TPU) instead of ref")
    ap.add_argument("--json", default=None)
    args = ap.parse_args(argv)
    rows = kernel_roofline_rows(batch=args.batch, use_pallas=args.pallas)
    missing = set(KERNELS) - {r["kernel"] for r in rows}
    assert not missing, f"roofline table missing kernels: {sorted(missing)}"
    print(fmt_roofline_md(rows))
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"rows": rows,
                       "device_kind": rows[0]["device_kind"],
                       "peaks": device_peaks(rows[0]["device_kind"])},
                      f, indent=1)
        print(f"wrote {args.json}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
