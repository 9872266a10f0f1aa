"""What the period program tells a profile about itself.

* **named stages** — every device op of the period program carries the
  ``jax.named_scope`` of its stage (route, apply and its second-level
  scopes, observe, commit); ``EpochDriver.period_op_scopes`` reads them
  from the compiled program without compiling again, and the scopes are
  metadata only;
* **per-scope device time** — ``scope_seconds`` over a recorded chip
  trace adds up to the period module's op time;
* **slab work counters** — the rows the delete/put branches rewrite and
  the PUT rows applied at chain members, counted inside the program;
* **GET-reply digest** — every epoch row carries the digest of the
  replies the program served, equal to a dict replay's, on the fused and
  per-epoch drivers and on the sharded data plane;
* **stage timers** on their own, with counters and profiler annotations.
"""

import contextlib
import dataclasses
import importlib.util
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core as C
from repro.cluster import (
    ClusterConfig,
    EpochDriver,
    ScenarioConfig,
    make_policy,
    make_scenario,
)
from repro.cluster.epoch import APPLY_SCOPES, PERIOD_SCOPES
from repro.cluster.scenarios import Scenario
from repro.core import keys as K
from repro.core import routing as R
from repro.core.store import (APPLY, MERGE, get_digest, get_digest_np,
                              put_sorted)
from repro.telemetry import OpScopes, StageTimers, hlo_op_scopes, scope_seconds
from repro.telemetry.profiler import STAGE_PREFIX, scope_path

BENCH = Path(__file__).resolve().parents[1] / "bench"
SMALL_TRACE = BENCH / "tests" / "data" / "small.xplane.pb"

SCFG = ScenarioConfig(n_epochs=4, epoch_ops=128, n_records=384,
                      value_dim=3, seed=5)


def _ccfg(**kw):
    base = dict(num_nodes=4, num_ranges=8, replication=3, r_max=4,
                n_clients=8, report_every=2, replication_mode="chain")
    return ClusterConfig(**{**base, **kw})


class _Scripted(Scenario):
    """Zipf traffic whose epochs follow a script of read shares (1.0: a
    GET-only epoch), with every fifth op a GET of a key no record has;
    with ``insert``, every seventh op of the epochs it names is a PUT of a
    key no record has."""

    name = "scripted"

    def __init__(self, cfg, read_shares, insert=()):
        super().__init__(cfg)
        self.read_shares = read_shares
        self.insert = insert

    def read_ratio(self, epoch):
        return self.read_shares[epoch % len(self.read_shares)]

    def epoch(self, e):
        opcodes, keys, end_keys, values = super().epoch(e)
        miss = np.arange(len(keys)) % 5 == 4
        absent = np.setdiff1d(self.record_keys + np.uint32(1),
                              self.record_keys)
        keys = np.where(miss, absent[e % len(absent)], keys)
        opcodes = np.where(miss, K.OP_GET, opcodes)
        if e in self.insert:
            fresh = np.arange(len(keys)) % 7 == 3
            new = np.setdiff1d(self.record_keys + np.uint32(2),
                               np.concatenate([self.record_keys, absent]))
            keys = np.where(fresh, new[np.arange(len(keys)) % len(new)], keys)
            opcodes = np.where(fresh, K.OP_PUT, opcodes)
        return (opcodes.astype(np.int32), keys.astype(np.uint32), end_keys,
                values)


def _replay_digests(scen, n_epochs):
    """Per-epoch digests of a plain dict replay: GETs see the pre-batch
    state, then the PUTs apply, the last write of a key winning."""
    keys, vals = scen.load()
    d = dict(zip(keys.tolist(), vals))
    out = []
    for e in range(n_epochs):
        opcodes, qkeys, _, qvals = scen.epoch(e)
        V = qvals.shape[1]
        rv = np.zeros((len(qkeys), V), np.float32)
        rf = np.zeros((len(qkeys),), bool)
        for i, (op, k) in enumerate(zip(opcodes.tolist(), qkeys.tolist())):
            if op == K.OP_GET and k in d:
                rv[i], rf[i] = d[k], True
        out.append(get_digest_np(opcodes, qkeys, rv, rf))
        for i, (op, k) in enumerate(zip(opcodes.tolist(), qkeys.tolist())):
            if op == K.OP_PUT:
                d[k] = qvals[i]
    return out


@contextlib.contextmanager
def _compile_events(out):
    out.update(lowerings=0, compiles=0)

    def listener(event, duration, **_):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            out["lowerings"] += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            out["compiles"] += 1

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        yield out
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)


@pytest.fixture(scope="module")
def ran_driver():
    drv = EpochDriver(make_scenario("shifting_hotspot", SCFG),
                      make_policy("migrate"), _ccfg())
    drv._run_segment(0, SCFG.n_epochs)
    return drv


# ---------------------------------------------------------------------------
# named stages and the op-to-scope map
# ---------------------------------------------------------------------------


def test_period_op_scopes_name_every_stage_without_compiling(ran_driver):
    events: dict = {}
    with _compile_events(events):
        scopes = ran_driver.period_op_scopes()
    assert events == {"lowerings": 0, "compiles": 0}
    tops = {p.split("/")[0] for p in scopes.scope.values() if p}
    assert tops == set(PERIOD_SCOPES)
    subs = {p.split("/")[1] for p in scopes.scope.values() if "/" in p}
    # YCSB-style traffic issues no SCAN and the oracle has no exchange,
    # but the get lookups, the delete branch and the put branch are there
    assert {"get", "delete", "dedupe", "merge"} <= subs <= set(APPLY_SCOPES)
    for fusion, spans in scopes.fused.items():
        assert spans <= set(PERIOD_SCOPES), fusion


def test_period_op_scopes_needs_a_traced_program():
    drv = EpochDriver(make_scenario("shifting_hotspot", SCFG),
                      make_policy("frozen"), _ccfg())
    with pytest.raises(RuntimeError, match="run a segment first"):
        drv.period_op_scopes()


def _period_hlo(drv):
    state = (drv.store, drv.directory, drv.load_reg, drv.sketch, drv.repl,
             drv.ovl, drv.coord, drv.metrics)
    text = drv._period_fn.lower(*state, *drv._period_inputs).compile().as_text()
    # the instructions without their metadata (and without the tables of
    # source locations that come before the computations)
    text = re.sub(r"\n(?:FileNames|FunctionNames|FileLocations|StackFrames)"
                  r"\n(?:\d+ .*\n)*", "\n", text)
    return re.sub(r", metadata=\{[^}]*\}", "", text)


def test_period_scopes_are_metadata_only(ran_driver, monkeypatch):
    scoped = _period_hlo(ran_driver)
    assert " conditional(" in scoped and "FileNames" not in scoped
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    drv = EpochDriver(make_scenario("shifting_hotspot", SCFG),
                      make_policy("migrate"), _ccfg())
    drv._run_segment(0, SCFG.n_epochs)
    assert _period_hlo(drv) == scoped


def _merge_conditionals(text):
    """The ``conditional`` instructions of an optimized HLO module that
    the put branch's merge skip emits: (op_name, the instructions of each
    branch computation)."""
    comps, cur = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) .*\{$", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif line.strip() == "}":
            cur = None
        elif cur is not None:
            cur.append(line)
    out = []
    for ins in (i for body in comps.values() for i in body):
        op = re.search(r'op_name="([^"]*)"', ins)
        if " conditional(" in ins and op and op.group(1).endswith(
                f"/{MERGE}/cond"):
            names = re.search(r"branch_computations=\{([^}]*)\}", ins)
            out.append((op.group(1), [comps[b.strip().lstrip("%")]
                                      for b in names.group(1).split(",")]))
    return out


def test_merge_skip_is_a_conditional_inside_the_shard_map(ran_driver):
    """The per-shard merge skip survives XLA as a ``conditional`` inside
    the ``lax.map`` over shards: one branch holds the merge's search
    loops, the other none.  A vmap over slabs would turn it into a
    select of both branches, and the check sees that."""
    state = (ran_driver.store, ran_driver.directory, ran_driver.load_reg,
             ran_driver.sketch, ran_driver.repl, ran_driver.ovl,
             ran_driver.coord, ran_driver.metrics)
    text = ran_driver._period_fn.lower(
        *state, *ran_driver._period_inputs).compile().as_text()
    (op_name, branches), = _merge_conditionals(text)
    assert f"/{APPLY}/while/body/" in op_name  # inside the map over shards
    loops = sorted(sum(" while(" in i for i in b) for b in branches)
    assert loops[0] == 0 and loops[1] > 0, loops

    cap, v = ran_driver.store.capacity, ran_driver.store.value_dim
    slabs = (jax.ShapeDtypeStruct((2, cap), jnp.uint32),
             jax.ShapeDtypeStruct((2, cap, v), jnp.float32),
             jax.ShapeDtypeStruct((2, 16), jnp.uint32),
             jax.ShapeDtypeStruct((2, 16, v), jnp.float32))

    def vmapped(*a):
        with jax.named_scope(MERGE):
            return jax.vmap(put_sorted)(*a)

    assert _merge_conditionals(
        jax.jit(vmapped).lower(*slabs).compile().as_text()) == []


@pytest.mark.parametrize("op_name,path", [
    ("jit(period)/while/body/closed_call/apply/while/body/merge/add",
     "apply/merge"),
    ("jit(period)/while/body/apply/cond/branch_1_fun/dedupe/sort",
     "apply/dedupe"),
    ("jit(period)/while/body/route/gather", "route"),
    ("jit(period)/while/body/commit/select_n", "commit"),
    # the last component is the operation's own name, never a scope
    ("jit(period)/while/body/apply/scan", "apply"),
    ("jit(period)/while/body/closed_call", ""),
    ("", ""),
])
def test_scope_path(op_name, path):
    assert scope_path(op_name, PERIOD_SCOPES, APPLY_SCOPES) == path


def test_fusion_takes_its_root_scope_and_lists_its_span():
    def f(x, y):
        with jax.named_scope("route"):
            a = x * y
        with jax.named_scope("apply"):
            return a + 1.0

    x = jnp.arange(8.0)
    text = jax.jit(f).lower(x, x).compile().as_text()
    scopes = hlo_op_scopes(text, PERIOD_SCOPES, APPLY_SCOPES)
    fusions = [n for n in scopes.fused if scopes.fused[n]]
    assert len(fusions) == 1
    assert scopes.scope[fusions[0]] == "apply"
    assert scopes.fused[fusions[0]] == {"route", "apply"}


def test_hlo_op_scopes_reads_every_computation():
    """Computation headers with tuple-typed parameters carry ``=`` in
    their index comments; their instructions are mapped all the same."""
    text = "\n".join([
        "HloModule jit_period, entry_computation_layout={(u32[4])->u32[4]}",
        "",
        "%body.sunk (arg: (s32[], u32[4], /*index=2*/s32[])) -> "
        "(s32[], u32[4], /*index=2*/s32[]) {",
        '  %fusion.7 = u32[4]{0} fusion(%a), kind=kLoop, calls=%fc.7, '
        'metadata={op_name="jit(period)/while/body/apply/get/gather"}',
        '  ROOT %copy.3 = u32[4]{0} copy(%fusion.7)',
        "}",
        "",
        "%fc.7 (p: u32[4]) -> u32[4] {",
        '  ROOT %add.1 = u32[4]{0} add(%p, %p), '
        'metadata={op_name="jit(period)/while/body/commit/add"}',
        "}",
        "",
        "ENTRY %main.9 (a: u32[4]) -> u32[4] {",
        '  ROOT %while.2 = u32[4]{0} while(%a), body=%body.sunk, '
        'metadata={op_name="jit(period)/while"}',
        "}",
    ])
    scopes = hlo_op_scopes(text, PERIOD_SCOPES, APPLY_SCOPES)
    assert scopes.scope["fusion.7"] == "apply/get"
    assert scopes.scope["copy.3"] == ""
    assert scopes.scope["while.2"] == ""
    assert scopes.scope["add.1"] == "commit"
    assert scopes.fused == {"fusion.7": {"commit"}}


def _trace_reduce():
    spec = importlib.util.spec_from_file_location(
        "bench_trace_reduce", BENCH / "trace_reduce.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def test_scope_seconds_add_up_to_the_period_module():
    summary = _trace_reduce().reduce_trace(SMALL_TRACE)
    op_s = next(iter(summary.op_s.values()))
    module = "jit_period"
    ops = sorted(k.split(":", 1)[1] for k in op_s
                 if k.startswith(module + ":"))
    assert ops
    paths = ["route", "apply/merge", "apply/get", "apply", "observe",
             "commit", ""]
    scopes = OpScopes(scope={op: paths[i % len(paths)]
                             for i, op in enumerate(ops)}, fused={})
    total = sum(s for k, s in op_s.items() if k.startswith(module + ":"))
    top = scope_seconds(op_s, scopes, module)
    assert set(top) <= set(PERIOD_SCOPES) | {""}
    assert sum(top.values()) == pytest.approx(total, rel=1e-12)
    two = scope_seconds(op_s, scopes, module, depth=2)
    assert "apply/merge" in two
    assert sum(two.values()) == pytest.approx(total, rel=1e-12)
    assert two["apply/merge"] + two["apply/get"] + two["apply"] == (
        pytest.approx(top["apply"], rel=1e-12))
    # an op the map does not know counts as unscoped
    assert scope_seconds(op_s, OpScopes({}, {}), module) == {
        "": pytest.approx(total, rel=1e-12)}


# ---------------------------------------------------------------------------
# slab work counters
# ---------------------------------------------------------------------------


def _expected_counts(store_keys, opcodes, keys, decision):
    """The :class:`~repro.core.store.ApplyCounts` of one epoch, from the
    slabs before it: a shard whose PUTs bring a key it lacks merges
    (its whole capacity rewritten), one whose PUTs all update keys it
    holds writes its distinct PUT keys in place."""
    store_keys = np.asarray(store_keys)
    chain = np.asarray(decision.chain)
    member = np.arange(chain.shape[1])[None, :] < np.asarray(
        decision.chain_len)[:, None]
    is_put = opcodes == K.OP_PUT
    out = dict(slab_rows_rewritten=0, put_merges=0, put_in_place=0,
               put_rows=int(member[is_put].sum()))
    for s, slab in enumerate(store_keys):
        mine = np.unique(keys[is_put & ((chain == s) & member).any(axis=1)])
        if not len(mine):
            continue
        if np.isin(mine, slab, invert=True).any():
            out["put_merges"] += 1
            out["slab_rows_rewritten"] += slab.shape[0]
        else:
            out["put_in_place"] += 1
            out["slab_rows_rewritten"] += len(mine)
    return out


@pytest.mark.parametrize("kind", ["update", "insert", "get_only"])
@pytest.mark.parametrize("fused", [True, False])
def test_slab_rewrite_counters(fused, kind):
    """An epoch whose PUTs all update held keys writes their rows in place
    on every shard; one that inserts rewrites the whole slab of each shard
    that receives a new key; a GET-only epoch rewrites nothing.  The PUT
    rows are the PUTs times their live chain members."""
    scen = _Scripted(SCFG, read_shares=[1.0 if kind == "get_only" else 0.5],
                     insert=(0,) if kind == "insert" else ())
    timers = StageTimers(enabled=True)
    drv = EpochDriver(scen, make_policy("frozen"), _ccfg(report_every=1),
                      fused=fused, timers=timers)
    N, Cap = drv.store.num_shards, drv.store.capacity
    opcodes, keys, end_keys, values = scen.epoch(0)
    q = C.make_queries(jnp.asarray(keys), jnp.asarray(opcodes),
                       jnp.asarray(values), jnp.asarray(end_keys))
    decision, _ = R.route(drv.directory, q)
    want = _expected_counts(drv.store.keys, opcodes, keys, decision)
    if fused:
        drv._run_segment(0, SCFG.n_epochs)
    else:
        drv.run_epoch(0)
    assert timers.counts == want
    if kind == "update":
        assert want["put_in_place"] == N and want["put_merges"] == 0
        assert 0 < want["slab_rows_rewritten"] <= want["put_rows"]
    elif kind == "insert":
        assert want["put_merges"] > 0
        assert want["slab_rows_rewritten"] >= want["put_merges"] * Cap
    else:
        assert want == dict(slab_rows_rewritten=0, put_rows=0, put_merges=0,
                            put_in_place=0)
    assert timers.summary()["counts"] == timers.counts


def test_counters_pull_nothing_with_timers_off():
    drv = EpochDriver(_Scripted(SCFG, read_shares=[0.5]),
                      make_policy("frozen"), _ccfg(report_every=1))
    syncs = drv.host_syncs
    drv._run_segment(0, SCFG.n_epochs)
    on = EpochDriver(_Scripted(SCFG, read_shares=[0.5]),
                     make_policy("frozen"), _ccfg(report_every=1),
                     timers=StageTimers(enabled=True))
    syncs_on = on.host_syncs
    on._run_segment(0, SCFG.n_epochs)
    assert drv._timers.counts == {}
    assert on._timers.counts["put_rows"] > 0
    # the counters ride the segment's existing round trip
    assert drv.host_syncs - syncs == on.host_syncs - syncs_on


# ---------------------------------------------------------------------------
# the GET-reply digest
# ---------------------------------------------------------------------------


def test_get_digest_matches_numpy_twin_and_splits_by_base():
    rng = np.random.default_rng(1)
    B, V = 96, 5
    opcodes = rng.choice([K.OP_GET, K.OP_PUT], size=B).astype(np.int32)
    keys = rng.integers(0, 2**32 - 1, size=B, dtype=np.uint32)
    value = rng.normal(size=(B, V)).astype(np.float32)
    found = rng.random(B) < 0.7
    want = get_digest_np(opcodes, keys, value, found)
    args = [jnp.asarray(a) for a in (opcodes, keys, value, found)]
    assert int(get_digest(*args)) == want
    h = B // 3
    parts = [get_digest(*(a[i:i + h] for a in args), base=i)
             for i in range(0, B, h)]
    assert int(sum(int(p) for p in parts)) % 2**32 == want
    # every field moves it: a value bit, a found flag, a key, the order
    g = int(np.flatnonzero(opcodes == K.OP_GET)[0])
    flipped = value.copy()
    flipped.view(np.uint32)[g, 2] ^= 1
    assert get_digest_np(opcodes, keys, flipped, found) != want
    assert get_digest_np(opcodes, keys, value, ~found) != want
    assert get_digest_np(opcodes, keys ^ np.uint32(1), value, found) != want
    perm = np.r_[1, 0, 2:B]
    if opcodes[0] == opcodes[1] == K.OP_GET:
        assert get_digest_np(opcodes, keys[perm], value[perm],
                             found[perm]) != want
    # non-GET rows do not count
    assert get_digest_np(np.full(B, K.OP_PUT), keys, value, found) == 0


@pytest.mark.parametrize("fused", [True, False])
def test_get_digest_matches_dict_replay(fused):
    scen = _Scripted(SCFG, read_shares=[0.5, 0.8, 1.0])
    drv = EpochDriver(scen, make_policy("migrate"), _ccfg(), fused=fused)
    rows = drv.run()
    assert [r.get_digest for r in rows] == _replay_digests(scen,
                                                           SCFG.n_epochs)
    assert all(r.get_digest != 0 for r in rows)


def test_get_digest_fused_rows_equal_per_epoch_rows():
    rows = {}
    for fused in (False, True):
        drv = EpochDriver(make_scenario("shifting_hotspot", SCFG),
                          make_policy("full_adaptive"),
                          _ccfg(replication_mode="eventual"), fused=fused)
        rows[fused] = drv.run()
    assert [r.get_digest for r in rows[True]] == [
        r.get_digest for r in rows[False]]
    assert [dataclasses.asdict(r) for r in rows[True]] == [
        dataclasses.asdict(r) for r in rows[False]]


def test_dist_period_digest_counters_and_scopes():
    """Chain mode on 4 virtual devices: the sharded period program serves
    the oracle's GET replies and applies the same PUT rows, and its ops
    map to the same stages, the all-to-all rounds under apply/exchange."""
    import os
    import subprocess
    import textwrap

    code = textwrap.dedent("""
        import jax
        import numpy as np
        from repro.cluster import (ClusterConfig, EpochDriver, ScenarioConfig,
                                   make_policy, make_scenario)
        from repro.cluster.epoch import PERIOD_SCOPES
        from repro.core.dist_store import DistConfig, make_mesh
        from repro.telemetry import StageTimers

        mesh = make_mesh((4,))
        scfg = ScenarioConfig(n_epochs=4, epoch_ops=128, n_records=384,
                              value_dim=3, seed=5)
        ccfg = ClusterConfig(num_nodes=4, num_ranges=8, replication=3,
                             r_max=4, n_clients=8, report_every=2,
                             replication_mode="chain")
        out = {}
        for backend in ("oracle", "dist"):
            kw = (dict(mesh=mesh, dist_cfg=DistConfig(bucket_cap=128))
                  if backend == "dist" else {})
            t = StageTimers(enabled=True)
            drv = EpochDriver(make_scenario("shifting_hotspot", scfg),
                              make_policy("migrate"), ccfg, backend=backend,
                              timers=t, **kw)
            out[backend] = ([r.get_digest for r in drv.run()], t.counts)
        compiles = []
        jax.monitoring.register_event_duration_secs_listener(
            lambda e, d, **_: compiles.append(e) if e.endswith(
                ("backend_compile_duration",
                 "jaxpr_to_mlir_module_duration")) else None)
        paths = set(drv.period_op_scopes().scope.values())
        assert not compiles, compiles
        assert {p.split("/")[0] for p in paths if p} == set(PERIOD_SCOPES)
        assert "apply/exchange" in paths and "apply/merge" in paths
        (od, oc), (dd, dc) = out["oracle"], out["dist"]
        assert od == dd, (od, dd)
        assert all(od)
        assert oc["put_rows"] == dc["put_rows"] > 0, (oc, dc)
        # every PUT updates a record its chain members hold: no shard
        # merges, each writes its distinct PUT keys in place, on a device
        # in one write round per chain position it holds in the batch
        assert oc["put_merges"] == dc["put_merges"] == 0, (oc, dc)
        assert oc["put_in_place"] == 4 * 4, oc
        assert dc["put_in_place"] >= oc["put_in_place"], (oc, dc)
        assert (oc["slab_rows_rewritten"] == dc["slab_rows_rewritten"]
                <= oc["put_rows"]), (oc, dc)
        print("ok")
    """)
    env = {**os.environ,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": str(Path(__file__).resolve().parents[1] / "src")}
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]


# ---------------------------------------------------------------------------
# stage timers on their own
# ---------------------------------------------------------------------------


def test_stage_timers_count_and_summarize():
    t = StageTimers(enabled=True)
    t.count("put_rows", 3)
    t.count("put_rows", np.int64(4))
    t.count("slab_rows_rewritten", 0)
    assert t.counts == {"put_rows": 7, "slab_rows_rewritten": 0}
    assert t.summary()["counts"] == t.counts
    off = StageTimers(enabled=False)
    off.count("put_rows", 3)
    assert off.counts == {} and off.summary()["counts"] == {}


def test_stage_timers_annotate_the_profiler_clock(tmp_path):
    from jax.profiler import ProfileData

    timers = StageTimers(enabled=True, annotate=True)
    drv = EpochDriver(make_scenario("shifting_hotspot", SCFG),
                      make_policy("migrate"), _ccfg(report_every=1),
                      timers=timers)
    assert drv.telemetry is None and drv._timers is timers
    with jax.profiler.trace(str(tmp_path)):
        drv._run_segment(0, SCFG.n_epochs)
    (path,) = tmp_path.rglob("*.xplane.pb")
    host = set()
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            host |= {ev.name for line in plane.lines for ev in line.events}
    for stage in ("inject", "des", "control"):
        assert STAGE_PREFIX + stage in host
        assert timers.calls[stage] >= 1
