#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip, through ``EpochDriver``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``: it names a
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``), whose generator is
``bench/generators/<generator>.py``.  Per-layer metrics are the readers
in ``bench/metrics/<name>.py``.  Everything is found by name; a new
cell, mix, kind of traffic or metric is a new file and a new entry.

One run:

1. set-up: check the devices (a TPU, as many chips as the cell asks for,
   a kind in ``bench/peaks.json``; otherwise exit non-zero before any
   result), build the record set and the driver from ``--seed`` (the
   driver preloads every record through route -> apply), and warm up
   the cell's programs: a few segments of the period program, the
   migration mover on a span that holds no record, and the check's GET
   program;
2. the window: ``EpochDriver._run_segment`` back to back, one control
   period at a time, until ``--seconds`` have passed; it ends with the
   last segment's rows on the host and ``block_until_ready`` of the
   store.  Every layer runs inside it: traffic generation, route, store
   apply on the device, DES, controller pull and migrations;
3. the check: the reference replays the same op stream; every live slab
   entry of the store the window left (every chain member), every
   record's copies, and a sample of GETs read from that store through
   route -> apply are compared with it bit for bit.  The window's own GET
   replies never leave the period program, so they are not compared.

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` turns
on the driver's stage timers (each stage also a profiler annotation),
traces the first segments of the window and prints the per-layer
metrics.  Logs go to standard error, whose last lines are the numbers
compared with their limits; the last line of standard output is the
result, one JSON object.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))

import reference as REF  # noqa: E402
import trace_reduce as TR  # noqa: E402
from workload import OP_GET, load_mix, make_traffic  # noqa: E402

# scenario length: enough epochs to outlast any window (the driver reads
# it for its event table only)
N_EPOCHS = 200_000
# segments run in set-up: the period program, the control pull and its
# host-side programs compile there
WARM_SEGMENTS = 3
# records GET-checked through the served read path: three batches
SAMPLE_BATCHES = 3
# traced part of a --trace 1 window: at least this many segments and
# this many seconds
TRACE_MIN_SEGMENTS = 3
TRACE_MIN_S = 2.0
# a span above every record key (keys lie below 0xFFFFFFFE): a migration
# of it moves nothing, so the mover warms up on the live store
EMPTY_SPAN = 0xFFFFFFFE
_LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the spec: BENCHMARK.json and the files it names
# ---------------------------------------------------------------------------


def load_spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_cell(spec: dict, workload: str) -> tuple[dict, dict, dict]:
    """(cell, configuration file, traffic mix) of one workload name."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"bench: unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[workload]
    entry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    config = json.loads((ROOT / entry["file"]).read_text())
    mix = load_mix(HERE / "traffic" / f"{cell['traffic']}.json")
    return cell, config, mix


def cell_metrics(spec: dict, cell: str) -> tuple[list[dict], list[dict]]:
    """The (end-to-end, per-layer) metrics that ``cell`` reports."""
    applies = lambda m: "workloads" not in m or cell in m["workloads"]
    e2e = [m for m in spec["end_to_end"] if applies(m)]
    names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if applies(m) and m["moves"] in names]
    return e2e, layer


def load_reader(name: str):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def device_peaks(kind: str) -> dict:
    """The peaks of one chip of ``kind``; a kind with no entry is an
    error."""
    table = json.loads((HERE / "peaks.json").read_text())["peaks"]
    if kind not in table:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in "
                         f"bench/peaks.json (known: {sorted(table)})")
    return table[kind]


def require_devices(n: int, platform: str = "tpu"):
    """The first ``n`` devices, all of ``platform``; exits non-zero
    otherwise (never falls back to another platform)."""
    import jax

    devs = jax.devices()
    if devs[0].platform != platform:
        raise SystemExit(
            f"bench: no {platform.upper()} found (jax.devices()[0] is "
            f"{devs[0].platform}:{devs[0].device_kind})")
    if len(devs) < n:
        raise SystemExit(f"bench: the cell needs {n} devices, found "
                         f"{len(devs)}")
    return devs[:n]


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Bench:
    driver: object
    traffic: object       # the mix's generator's ``Traffic``
    config: dict
    get: object           # (store, directory, keys) -> (values, found)
    next_epoch: int = 0


def _get_program(config: dict, mesh, directory):
    """The check's GET program: a batch of keys through the same route ->
    apply functions the window's reads take (the sharded data plane on a
    mesh), compiled apart from the period program, whose GET replies are
    not among its outputs."""
    import jax
    import jax.numpy as jnp

    from repro import core as C
    from repro.core import routing as R
    from repro.core.dist_store import DistConfig, make_dist_apply
    from repro.core.store import apply_routed

    def queries(keys, value_dim):
        return C.make_queries(keys, jnp.full(keys.shape, OP_GET, jnp.int32),
                              value_dim=value_dim)

    if config["backend"] == "dist":
        apply = make_dist_apply(mesh, directory, DistConfig(**config["dist"]))

        def get(store, directory, keys):
            _, resp, _, _ = apply(store, directory,
                                  queries(keys, store.value_dim))
            return resp.value, resp.found

        return get

    @jax.jit
    def get(store, directory, keys):
        q = queries(keys, store.value_dim)
        decision, _ = R.route(directory, q)
        _, resp = apply_routed(store, q, decision)
        return resp.value, resp.found

    return get


def build(config: dict, mix: dict, seed: int, devices) -> Bench:
    """The record set, the traffic and the driver (which preloads every
    record) from ``seed``."""
    from repro.cluster import ClusterConfig, EpochDriver, make_policy
    from repro.core import des
    from repro.core.dist_store import DistConfig, make_mesh

    traffic = make_traffic(mix, n_records=config["n_records"],
                           value_dim=config["value_dim"],
                           epoch_ops=config["epoch_ops"], n_epochs=N_EPOCHS,
                           seed=seed)
    ccfg = ClusterConfig(**config["cluster"], seed=seed % (1 << 31))
    backend, where = des.backend_info(ccfg.des_backend)
    if backend != "native":
        raise SystemExit(f"bench: the DES runs on {backend} ({where}), "
                         "not the native C core")
    log(f"des_backend: {backend} on {where}")
    kw = {}
    mesh = None
    if config["backend"] == "dist":
        mesh = make_mesh((len(devices),), (config["dist"]["axis"],),
                         devices=devices)
        kw = dict(backend="dist", mesh=mesh,
                  dist_cfg=DistConfig(**config["dist"]))
    driver = EpochDriver(traffic, make_policy(config["policy"]), ccfg, **kw)
    return Bench(driver=driver, traffic=traffic, config=config,
                 get=_get_program(config, mesh, driver.directory))


def run_segment(bench: Bench) -> list:
    """One control period (or less, up to an event) of the driver."""
    rows = bench.driver._run_segment(bench.next_epoch, N_EPOCHS)
    bench.next_epoch = rows[-1].epoch + 1
    return rows


def warm_up(bench: Bench) -> None:
    """Compile and run every program the window and the check use, on the
    cell's own shapes and state."""
    import jax
    import jax.numpy as jnp

    from repro.core.migration import MigrationOp, execute

    drv = bench.driver
    for _ in range(WARM_SEGMENTS):
        run_segment(bench)
    drv.store = execute(drv.store, [MigrationOp(
        lo=EMPTY_SPAN, hi=EMPTY_SPAN, src=0, dst=1, kind="move")])
    keys = jnp.asarray(bench.traffic.record_keys[:bench.config["epoch_ops"]])
    jax.block_until_ready(bench.get(drv.store, drv.directory, keys))
    jax.block_until_ready(drv.store)


@contextlib.contextmanager
def compile_counter(out: dict):
    """Count lowerings and backend compiles while the block runs."""
    import jax

    out.update(lowerings=0, compiles=0)

    def listener(event, duration, **_):
        if event == _LOWERING:
            out["lowerings"] += 1
        elif event == _BACKEND_COMPILE:
            out["compiles"] += 1

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        yield out
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)


def annotated_timers():
    """The driver's stage timers, each stage also a profiler annotation
    (``stage:<name>``) so host stages sit on the device trace's clock."""
    import jax

    from repro.telemetry.profiler import StageTimers

    class AnnotatedStageTimers(StageTimers):
        @contextlib.contextmanager
        def stage(self, name: str):
            with jax.profiler.TraceAnnotation(TR.STAGE_PREFIX + name):
                with super().stage(name):
                    yield

    return AnnotatedStageTimers(enabled=True)


class _Profiler:
    """The profiler over the first segments of a window: device ops and
    the host's annotations, no Python call tracer."""

    def __init__(self, trace_dir: Path, bench: Bench):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        self._window = jax.profiler.TraceAnnotation(TR.WINDOW)
        self._window.__enter__()
        self._e0, self._p0 = bench.next_epoch, bench.driver._period

    def stop(self, bench: Bench) -> tuple[int, int]:
        """Close the traced part; returns its (epochs, pulls)."""
        import jax

        jax.block_until_ready(bench.driver.store)
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        return bench.next_epoch - self._e0, bench.driver._period - self._p0


@dataclasses.dataclass
class Window:
    seconds: float
    seg_s: np.ndarray          # wall seconds of each segment
    seg_ops: np.ndarray        # ops each segment carried
    failed: int
    epochs: int
    pulls: int
    compiles: dict
    moves: int = 0
    moved_entries: int = 0
    trace: TR.TraceSummary | None = None
    traced_epochs: int = 0
    traced_pulls: int = 0


def run_window(bench: Bench, seconds: float, trace_dir: Path | None = None
               ) -> Window:
    """Segments back to back until ``seconds`` have passed (whole segments
    only); with ``trace_dir``, the first of them under the profiler."""
    import jax

    drv = bench.driver
    seg_s, seg_ops = [], []
    failed = moves = moved = 0
    e0, p0 = bench.next_epoch, drv._period
    traced = None
    counts: dict = {}
    with compile_counter(counts):
        prof = _Profiler(trace_dir, bench) if trace_dir is not None else None
        t_start = time.perf_counter()
        while True:
            t0 = time.perf_counter()
            rows = run_segment(bench)
            t1 = time.perf_counter()
            seg_s.append(t1 - t0)
            seg_ops.append(sum(r.ops for r in rows))
            failed += sum(r.drops + r.lost for r in rows)
            moves += sum(ev.startswith("move:") for r in rows
                         for ev in r.events)
            moved += sum(r.migration_entries for r in rows)
            if prof and len(seg_s) >= TRACE_MIN_SEGMENTS and (
                    t1 - t_start >= TRACE_MIN_S):
                traced, prof = prof.stop(bench), None
            if t1 - t_start >= seconds:
                break
        jax.block_until_ready(drv.store)
        t_end = time.perf_counter()
    if prof:   # the window ended before the traced part did
        traced = prof.stop(bench)
    seg_s[-1] += t_end - t1
    win = Window(seconds=t_end - t_start, seg_s=np.asarray(seg_s),
                 seg_ops=np.asarray(seg_ops), failed=int(failed),
                 epochs=bench.next_epoch - e0, pulls=drv._period - p0,
                 compiles=counts, moves=moves, moved_entries=moved)
    if trace_dir is not None:
        win.trace = TR.reduce_trace(TR.find_xplane(trace_dir))
        win.traced_epochs, win.traced_pulls = traced
    return win


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------


def bf16_values(store):
    """The control's store: the same slabs with every value carried in
    bfloat16, the next precision below the configuration's float32."""
    import jax.numpy as jnp

    return dataclasses.replace(
        store, values=store.values.astype(jnp.bfloat16).astype(jnp.float32))


def check(bench: Bench, win: Window, degrade=None) -> dict:
    """Compare what the window left behind with the reference replay of
    the same op stream: the store that the period program wrote (every
    live entry, every record's copies), GETs read from it through the
    check's GET program, the ops the window failed, and its retraces.
    ``degrade``, for the control only, rewrites the store's values before
    the comparison.  Returns ``{name: (value, limit)}``; every limit is 0
    (exact comparison)."""
    import jax
    import jax.numpy as jnp

    drv, traffic = bench.driver, bench.traffic
    B = bench.config["epoch_ops"]
    t0 = time.perf_counter()
    ref = REF.replay(traffic, bench.next_epoch)
    sample = REF.pick_sample(ref, traffic.record_keys, SAMPLE_BATCHES * B,
                             traffic.seed)
    if degrade is not None:
        drv.store = degrade(drv.store)
    values, found = [], []
    for i in range(0, len(sample), B):
        v, f = bench.get(drv.store, drv.directory,
                         jnp.asarray(sample[i:i + B]))
        values.append(np.asarray(v))
        found.append(np.asarray(f))
    gets = REF.check_gets(sample, np.concatenate(values),
                          np.concatenate(found), ref)
    jax.block_until_ready(drv.store)
    slab_keys = np.asarray(drv.store.keys)
    slab_vals = np.asarray(drv.store.values)
    slabs = REF.check_slabs(slab_keys, slab_vals, ref,
                            bench.config["cluster"]["replication"])
    retraces = drv.traces - (1 + drv.growth_events)
    log(f"check: {slabs['entries_checked']} slab entries of "
        f"{slabs['records']} records, {gets['gets_checked']} GETs "
        f"({len(ref.writes)} records written while serving), "
        f"{bench.next_epoch} epochs replayed, in "
        f"{time.perf_counter() - t0:.3f} s")
    return {
        "wrong_values": (slabs["wrong_values"], 0),
        "under_replicated": (slabs["under_replicated"], 0),
        "get_mismatches": (gets["get_mismatches"], 0),
        "failed_ops": (win.failed, 0),
        "retraces": (retraces, 0),
    }


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------


def end_to_end(win: Window, setup_s: float) -> dict:
    # every op of a segment waits from the segment's start until its
    # results are on the host: the per-op latency is the segment's wall
    per_op = np.repeat(win.seg_s, win.seg_ops)
    return {
        "ops_per_s": float(win.seg_ops.sum() / win.seconds),
        "op_p95_ms": float(np.percentile(per_op, 95) * 1e3),
        "setup_s": float(setup_s),
    }


def reader_context(bench: Bench, win: Window) -> dict:
    t = bench.driver._timers
    return {
        "stages": {k: {"s": t.totals[k], "calls": t.calls[k]}
                   for k in t.totals},
        "epochs": win.epochs,
        "pulls": win.pulls,
        "trace": win.trace,
        "traced_epochs": win.traced_epochs,
        "traced_pulls": win.traced_pulls,
    }


def device_info(devices) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(max(peaks))}


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


def enable_caches() -> str:
    """The program's persistent compile cache (inside the checkout, or
    where ``JAX_COMPILATION_CACHE_DIR`` says), holding every program."""
    import jax

    from repro.compile_cache import enable_compile_cache

    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def report(result: dict, checks: dict) -> None:
    """The compared numbers as the last lines of standard error, and the
    result as the last line of standard output (``checks`` last)."""
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        log(f"check {k}: {v} (limit {lim})")
    print(json.dumps(result), flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    spec = load_spec()
    cell, config, mix = find_cell(spec, args.workload)
    devices = require_devices(cell["chips"])
    peaks = device_peaks(devices[0].device_kind)
    sys.path.insert(0, str(ROOT / "src"))
    log(f"device: {devices[0].platform} {devices[0].device_kind} "
        f"x{len(devices)}")
    log(f"compile_cache: {enable_caches()}")

    bench = build(config, mix, args.seed, devices)
    warm_up(bench)
    setup_s = time.perf_counter() - T_PROCESS
    log(f"setup_s: {setup_s:.3f} ({bench.next_epoch} warm-up epochs)")

    trace_dir = None
    if args.trace:
        bench.driver._timers = annotated_timers()
        trace_dir = Path(tempfile.mkdtemp(prefix="bench_trace_"))
    try:
        win = run_window(bench, args.seconds, trace_dir)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    log(f"compiles in the window: {win.compiles['compiles']} backend "
        f"compiles, {win.compiles['lowerings']} lowerings")
    log(f"window: {win.epochs} epochs in {len(win.seg_s)} segments, "
        f"{win.pulls} pulls, {win.moves} range moves ({win.moved_entries} "
        f"entries), {win.seconds:.3f} s")
    dev = device_info(devices)
    log(f"memory_peak_bytes: {dev['memory_peak_bytes']} "
        f"({dev['memory_peak_bytes'] / peaks['hbm_bytes']:.1%} of a chip)")

    e2e_specs, layer_specs = cell_metrics(spec, cell["name"])
    metrics = {}
    result: dict = {}
    if args.trace:
        ctx = reader_context(bench, win)
        for m in layer_specs:
            v = load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        dev["busy_s"] = win.trace.mean_busy_s
        dev["window_s"] = win.trace.window_s
        result["breakdown"] = {"device_ops": win.trace.top_ops(),
                               "idle_gaps": win.trace.top_gaps()}
    else:
        values = end_to_end(win, setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in e2e_specs}
    for name, m in metrics.items():
        log(f"metric {name}: {m['value']} {m['unit']}")

    checks = check(bench, win)
    correct = all(v <= lim for v, lim in checks.values())
    report({"correct": correct, "attempted": int(win.seg_ops.sum()),
            "failed": win.failed, "metrics": metrics, "device": dev,
            **result}, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
