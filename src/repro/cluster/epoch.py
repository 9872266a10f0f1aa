"""The closed-loop epoch driver (paper §5.1 made to actually run).

One *epoch* = one device-side batch step; one *control period* =
``period`` consecutive epochs between controller pulls.  The device step
is a single fused, jitted program —

    inject workload slice
    -> route (counter + load-register + count-min sketch updates)
    -> apply to the store (``apply_routed``, or ``make_dist_apply`` on a
       mesh backend)
    -> build the DES hop plan

— and the host side closes the loop: pull the statistics report, run the
balancing policy, execute the migration plan, graft the refreshed
control tables back onto the live directory (``Controller.refresh`` —
counters survive; ``stats.pull_report`` is the only reset path), and
time the period's traffic on the PR-1 vectorized DES engine
(:mod:`repro.core.des`).

**Device-resident period pipeline** (the default, ``fused=True``): the
whole control period runs as ONE jitted ``lax.scan`` over the period's
pre-staged query batches, with the store slabs, load registers, sketch
and the replication version/dirty register file
(:mod:`repro.replication`) **donated** into the call (the slabs are the
big allocation; no
second live copy exists during the scan; the directory is deliberately
NOT donated — its freshly-grafted zeroed counter tables can alias one
constant buffer, which XLA rejects as a double donation, and it is tiny
next to the slabs).  Per-epoch
observables (hop plans, per-node ops, retries, overflow totals) come
back as stacked device arrays, so the host syncs **once per period**
instead of once per epoch: one batched DES engine call over the stacked
(P, B, H) plans (``stack_plans`` semantics, see
``des.simulate_closed_loop``), percentiles and imbalance vectorized over
the period.  NetCache/DistCache-style designs work precisely because
the data plane runs many intervals between control-plane pulls; so does
this driver.

The fused driver is **observationally equivalent** to per-epoch stepping
(``fused=False``): policies only ever act on period-boundary reports, so
fusing the epochs between two pulls changes no policy input, and the
``run()``/:class:`EpochMetrics` stream and final store state are
bit-identical — asserted in ``tests/test_epoch_fused.py``.  Scenario
control events (fail/recover/rack_fail) only ever fire at epoch
boundaries; a segment simply ends early at the next event epoch, and the
scan's fixed length is padded with masked (no-op) epochs so the program
still compiles exactly once per scenario.

Shape discipline: scenario batches, directory tables, the sketch, and
the load registers all keep fixed shapes across control updates (chain
widening only rewrites ``chain_len`` values; hot-subset splits allocate
pre-reserved directory slots — ``make_directory(r_max=, n_slots=)``
reserves both kinds of headroom), so the period scan traces **once per
scenario** — asserted via :attr:`EpochDriver.traces` (the jit cache
size, which also catches dist-backend retraces) in tests and recorded
per bench row.

What the device step does is visible from outside it.  Its stages run
under the named scopes of :data:`PERIOD_SCOPES` (``route``, ``apply``
with the second-level :data:`APPLY_SCOPES`, ``observe``, ``commit``),
and :meth:`EpochDriver.period_op_scopes` maps each op of the compiled
period program to its stage.  Each epoch returns the digest of the GET
replies it served (``EpochMetrics.get_digest``) and counts the slab rows
it rewrote and the PUT rows it applied, and on a mesh the rows its
all-to-all rounds sent, which enabled stage timers add up
(``StageTimers.counts``).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro import core as C
from repro.core import directory as D
from repro.core import keys as K
from repro.core import routing as R
from repro.core.controller import Controller, ControllerConfig
from repro.core.coordination import LatencyModel, plan_hops
from repro.core.dist_store import (
    DistConfig,
    make_dist_apply,
    make_dist_period,
)
from repro.core.migration import execute as execute_migrations
from repro.core.stats import make_sketch, pull_report, sketch_query, sketch_update
from repro.core import store as S
from repro.core.store import (
    APPLY_SCOPES,
    PERIOD_SCOPES,
    apply_routed,
    apply_routed_counted,
    get_digest,
    make_store,
)
from repro import coordination_tier as CT
from repro import overload as OVL
from repro import replication as RPL
from repro import telemetry as TEL
from repro.telemetry import metrics as MTR
from repro.telemetry import slo as SLOM

from repro.cluster.metrics import (
    EpochMetrics,
    imbalance_stats_batch,
    latency_percentiles_batch,
    masked_p99_batch,
    migration_traffic,
    p999_batch,
)
from repro.cluster.policies import Policy
from repro.cluster.scenarios import Scenario


@dataclasses.dataclass
class ClusterConfig:
    """Cluster geometry + timing knobs for a driver run."""

    num_nodes: int = 8
    num_ranges: int = 64
    replication: int = 2
    r_max: int = 4                 # chain-slot headroom for widening
    # range-slot pool size; None -> 2x num_ranges (headroom for hot-subset
    # splits, the slot-pool analogue of the r_max chain headroom)
    n_slots: int | None = None
    capacity: int | None = None    # per-shard slots; None -> sized from scenario
    mode: str = C.IN_SWITCH
    n_clients: int = 32            # DES closed-loop client count
    # consistency mode over the replica chains (repro.replication):
    # "eventual" (pre-subsystem behaviour, bit-identical), "chain"
    # (CR: tail reads, full-chain writes) or "craq" (apportioned reads
    # with dirty-bit tail bounces)
    replication_mode: str = "eventual"
    # epochs per controller pull == the fused scan's period length;
    # None -> the policy's declared ``pull_every`` cadence; "auto" ->
    # adaptive cadence: the next period is picked from report-to-report
    # load drift inside ``auto_band`` (the fused scan is sized at the
    # band maximum and shorter periods run as masked-padded segments,
    # so the program still compiles once)
    report_every: int | str | None = None
    auto_band: tuple = (1, 8)
    auto_drift_lo: float = 0.1     # drift below this doubles the period
    auto_drift_hi: float = 0.4     # drift above this halves it
    sketch_width: int = 512
    sketch_depth: int = 4
    # distinct-key window cap for the sketch pull view; uniform thinning
    # beyond this (the split policies' quantile consumers are robust to it)
    key_window_cap: int = 1 << 16
    latency: LatencyModel = dataclasses.field(default_factory=LatencyModel)
    # per-hop service-time distribution (fixed | lognormal | pareto)
    service_model: C.ServiceModel = dataclasses.field(
        default_factory=C.ServiceModel
    )
    # intra-epoch p2c freshness: route the batch in this many sub-chunks
    # with load-register updates between them (oracle backend, spread
    # policies; still one compiled step — the chunk loop unrolls)
    p2c_chunks: int = 1
    des_backend: str | None = None
    max_scan_results: int = 8
    imbalance_threshold: float = 1.3   # Controller.balance trigger
    max_moves_per_round: int = 4
    # the overload plane (repro.overload): None disables it and the run
    # is bit-identical to pre-overload behaviour; an OverloadConfig
    # carries bounded per-node admission queues + retry-storm dynamics
    # through the device step (donated through the fused scan)
    overload: OVL.OverloadConfig | None = None
    # capacity-autoscale reserve: nodes parked into Controller.standby
    # at init (before the preload, so they never hold data); the
    # backpressure policies activate/park them as utilization crosses
    # their bands
    standby_nodes: tuple = ()
    # capacity-driven splitting in the loop: at each control pull, split
    # the hottest range headed at any node whose store overflowed since
    # the last pull (Controller.split_overflowed) and — when the slot
    # pool is exhausted — grow the pool and recompile (oracle rebuilds
    # its step; the dist programs re-specialize on the grown shapes by
    # themselves; `traces` then counts 1 + growth_events either way)
    split_overflow: bool = False
    # the trace plane (repro.telemetry): None disables it and the run is
    # bit-identical to pre-telemetry behaviour; a TelemetryConfig samples
    # per-query spans inside the device step (hash-based, no PRNG
    # consumed — the metric stream is bit-identical with tracing on OR
    # off), decomposes tail latency exactly, and times pipeline stages
    telemetry: TEL.TelemetryConfig | None = None
    # the coordination tier (repro.coordination_tier): None disables it
    # and the run is bit-identical to pre-tier behaviour; a CoordConfig
    # replicates the directory onto per-switch table copies that lag the
    # controller's commits along the switch chain, resolving stale routes
    # with versioned redirects.  Accounting plane: store effects, counters
    # and PRNG draws always follow the TRUE routing decision, so a
    # zero-lag tier is also bit-identical to None
    coordination: CT.CoordConfig | None = None
    # the fleet metrics plane (repro.telemetry.metrics): None disables it
    # and the run is bit-identical to pre-metrics behaviour; a
    # MetricsConfig carries a fixed-shape (window, n_series) time-series
    # ring through the device step (donated through the fused scan, like
    # the overload/coordination registers), with SLO burn-rate alerting
    # evaluated on-device at each segment boundary.  Pure observer: no
    # PRNG consumed, no store/counter effects — the EpochMetrics stream
    # is bit-identical with the ring on OR off
    metrics: MTR.MetricsConfig | None = None
    # hashed per-key CRAQ dirty filter width (repro.replication): a craq
    # replica bounces only reads whose key *collides* with an uncommitted
    # write instead of every read of a dirty range.  0 (the default)
    # keeps slot-granular bouncing bit-identically; oracle backend only
    craq_filter_bits: int = 0
    seed: int = 0


def _node_ops(decision: C.RoutingDecision, opcode: jnp.ndarray, num_nodes: int
              ) -> jnp.ndarray:
    """(N,) ops served per node this epoch: reads at their routed target,
    writes at every live chain member (same units as directory.node_load)."""
    is_write = (opcode == K.OP_PUT) | (opcode == K.OP_DEL)
    r_max = decision.chain.shape[1]
    live = (jnp.arange(r_max)[None, :] < decision.chain_len[:, None]) & (
        decision.chain != D.NO_NODE
    )
    w_hit = live & is_write[:, None]
    ops = jnp.zeros((num_nodes,), jnp.int32)
    ops = ops.at[jnp.where(w_hit, decision.chain, 0).reshape(-1)].add(
        w_hit.reshape(-1).astype(jnp.int32)
    )
    # mode="drop": reads against a fully-spliced chain (target NO_NODE)
    # are unserved and must not show up as phantom load on node 0
    ops = ops.at[decision.target].add(
        jnp.where(is_write, 0, 1).astype(jnp.int32), mode="drop"
    )
    return ops


def _merge_unique(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Merge two sorted-unique uint32 arrays in linear time (no re-sort of
    the accumulated window — the incremental key-window dedupe)."""
    if a.size == 0:
        return b
    if b.size == 0:
        return a
    pos = np.searchsorted(a, b)
    hit = (pos < a.size) & (a[np.minimum(pos, a.size - 1)] == b)
    fresh = b[~hit]
    if fresh.size == 0:
        return a
    out = np.empty(a.size + fresh.size, a.dtype)
    at_b = np.searchsorted(a, fresh) + np.arange(fresh.size)
    mask = np.zeros(out.size, bool)
    mask[at_b] = True
    out[mask] = fresh
    out[~mask] = a
    return out


# Records per preload batch.  Each batch costs O(capacity) per shard (the
# slab merge), whatever its size: at 1M slots a shard's put is ~0.2 s on
# a TPU v5e, so chunks of one 4,096-op epoch would take minutes to load a
# million records.  Peak memory depends on this size and the capacity,
# never on the record count.
PRELOAD_CHUNK = 1 << 16


@partial(jax.jit, static_argnames=("max_scan_results",), donate_argnums=(0,))
def _preload_chunk(store, directory, keys, opcodes, values, *,
                   max_scan_results: int):
    """One preload batch through route -> apply_routed (the directory's
    counter bumps are discarded: the load phase is not traffic)."""
    q = C.make_queries(keys, opcodes, values)
    decision, _ = R.route(directory, q)
    store, _ = apply_routed(store, q, decision,
                            max_scan_results=max_scan_results)
    return store


def _jit_cache_size(fn, default: int = 0) -> int:
    cs = getattr(fn, "_cache_size", None)
    return cs() if callable(cs) else default


class EpochDriver:
    """Run a scenario under a policy, one control period at a time.

    ``backend='oracle'`` (default) uses the single-program
    ``apply_routed`` path; ``backend='dist'`` shards the store over a
    mesh axis and goes through ``make_dist_apply`` (the bounded-bucket
    all_to_all data plane) — pass ``mesh``.

    ``fused=True`` (default) runs each control period as one donated
    ``lax.scan`` (oracle) or one deferred-sync step loop (dist) with a
    single host round-trip per period; ``fused=False`` is the per-epoch
    reference loop the fused pipeline is asserted bit-identical against.

    ``timers`` is the :class:`~repro.telemetry.StageTimers` the driver
    times its stages and counts the store's work with (default: the span
    plane's when ``cfg.telemetry`` is set, else disabled ones).  Enabled,
    each segment also adds its period's
    :class:`~repro.core.store.ApplyCounts` to ``timers.counts``: the slab
    rows it rewrote (``slab_rows_rewritten``: a whole slab for a delete
    or a merge, the hit rows for PUTs written in place), the PUT rows it
    applied at chain members (``put_rows``), and the shard batches whose
    PUTs ran the O(capacity) merge (``put_merges``) or skipped it
    because they inserted no new key (``put_in_place``).
    """

    def __init__(
        self,
        scenario: Scenario,
        policy: Policy,
        cfg: ClusterConfig | None = None,
        *,
        backend: str = "oracle",
        mesh=None,
        dist_cfg: DistConfig | None = None,
        fused: bool = True,
        timers: TEL.StageTimers | None = None,
    ):
        self.scenario = scenario
        self.policy = policy
        self.cfg = cfg = cfg or ClusterConfig()
        if backend not in ("oracle", "dist"):
            raise ValueError(f"unknown backend {backend!r}")
        if backend == "dist" and mesh is None:
            raise ValueError("backend='dist' needs a mesh")
        if backend == "dist":
            n_dev = mesh.shape[(dist_cfg or DistConfig()).axis]
            if n_dev != cfg.num_nodes:
                # one storage node per device: the shard-local data plane
                # treats its slab as one node
                raise ValueError(
                    f"backend='dist' places one storage node per device: "
                    f"num_nodes={cfg.num_nodes} on a mesh axis of {n_dev}"
                )
        self.backend = backend
        self.fused = fused
        # consistency mode wiring: how routing / hop planning / the
        # version register file behave (repro.replication.resolve_mode)
        self.mode_plan = RPL.resolve_mode(
            cfg.replication_mode, policy.read_spread, cfg.replication
        )
        # pull cadence: explicit config wins, else the policy declares it.
        # "auto" picks each period from report-to-report load drift within
        # cfg.auto_band; the fused scan is sized at the band maximum.
        pe = (cfg.report_every if cfg.report_every is not None
              else policy.pull_every)
        self.period_history: list[int] = []
        if pe == "auto":
            lo, hi = int(cfg.auto_band[0]), int(cfg.auto_band[1])
            if not (1 <= lo <= hi):
                raise ValueError(f"bad auto_band {cfg.auto_band}")
            self.auto_period = True
            self.period = hi               # scan length = band maximum
            self._cur_period = lo          # start controlling tightly
            self._next_pull = lo
            self._prev_load: np.ndarray | None = None
            self._last_pull_epoch = 0
            # spread modes: load registers are halved (not reset) at each
            # pull, so drift must difference out the post-halving floor
            # or a decayed tail of prior periods pollutes the signal
            self._reg_floor = np.zeros((cfg.num_nodes,), np.float64)
        else:
            self.auto_period = False
            self.period = int(pe)

        scfg = scenario.cfg
        # keep the policy's notion of base replication honest
        policy.config.base_replication = cfg.replication
        if cfg.p2c_chunks > 1 and scfg.epoch_ops % cfg.p2c_chunks != 0:
            raise ValueError(
                f"epoch_ops {scfg.epoch_ops} not divisible by "
                f"p2c_chunks {cfg.p2c_chunks}"
            )

        n_slots = 2 * cfg.num_ranges if cfg.n_slots is None else cfg.n_slots
        directory = C.make_directory(
            cfg.num_ranges, cfg.num_nodes, cfg.replication, r_max=cfg.r_max,
            n_slots=n_slots,
        )
        self.controller = Controller(
            directory,
            ControllerConfig(
                imbalance_threshold=cfg.imbalance_threshold,
                max_moves_per_round=cfg.max_moves_per_round,
            ),
        )
        # capacity autoscale: park the configured reserve BEFORE the
        # preload, so standby nodes never hold data (the drain is free on
        # an empty store) and the YCSB load phase routes around them
        if cfg.standby_nodes:
            for node in cfg.standby_nodes:
                self.controller.park_node(int(node))
            directory = self.controller.directory()
            # fresh register file below: the park resets are no-ops on it
            self.controller.drain_repl_log()
        capacity = cfg.capacity
        if capacity is None:
            # every record on up to r_max chains, plus 2x headroom for skewed
            # placement and widen copies
            capacity = max(256, 2 * scfg.n_records * cfg.r_max // cfg.num_nodes)
        if backend == "dist":
            # each device allocates only its own slab: the whole store
            # never sits on one device
            from jax.sharding import NamedSharding, PartitionSpec

            self.store = jax.jit(
                partial(make_store, cfg.num_nodes, capacity, scfg.value_dim),
                out_shardings=NamedSharding(
                    mesh, PartitionSpec((dist_cfg or DistConfig()).axis)),
            )()
        else:
            self.store = make_store(cfg.num_nodes, capacity, scfg.value_dim)
        self.directory = directory
        self.load_reg = jnp.zeros((cfg.num_nodes,), jnp.uint32)
        self.sketch = make_sketch(cfg.sketch_width, cfg.sketch_depth)
        if backend == "dist" and cfg.craq_filter_bits:
            raise ValueError(
                "craq_filter_bits is an oracle-backend measurement "
                "feature; the dist data plane keeps slot-granular "
                "bouncing"
            )
        # the (n_slots, r_max) version/dirty register file, device-resident
        # next to the load registers; carried (and donated) through the
        # fused period scan for chain/craq, inert zeros under eventual
        self.repl = RPL.make_state(n_slots, cfg.r_max, cfg.craq_filter_bits)
        # the coordination tier: per-switch replicated table copies +
        # version registers, carried (and donated) through the fused
        # scan; the host-side CoordManager stages control writes along
        # the switch chain between segments.  None == empty pytree slot,
        # same discipline as the overload plane
        self.coord_cfg = cfg.coordination
        if self.coord_cfg is not None:
            self.coord_mgr = CT.CoordManager(
                self.coord_cfg, self.controller.table_snapshot(),
                num_nodes=cfg.num_nodes,
            )
            self.coord = self.coord_mgr.make_state()
        else:
            self.coord_mgr = None
            self.coord = None
        # previous period's redirect share (redirected / routed) — the
        # policy-facing convergence signal behind redirect_backoff
        self._last_redirect_share = 0.0
        # the overload plane: device-resident per-node queue/retry
        # registers, carried (and donated) through the fused scan; None
        # when disabled — an empty pytree slot, so the step signatures
        # stay uniform and the disabled path compiles the same program
        # as before the subsystem existed
        self.ovl_cfg = cfg.overload
        # the orbit-identity register (cross-epoch retry linking) sizes
        # off the trace plane's knob but lives with the retry orbit it
        # identifies — 0 bits keeps the (1,) placeholder leaf
        _lb = (cfg.telemetry.link_retries
               if cfg.telemetry is not None else 0)
        self.ovl = (OVL.make_state(cfg.num_nodes, cfg.overload,
                                   link_bits=_lb)
                    if cfg.overload is not None else None)
        # the trace plane: spans are assembled inside the device step (no
        # extra sync — they ride the one period round-trip), attributed
        # and archived by the host-side recorder.  None compiles the
        # identical program and produces the identical metric stream.
        self.tel_cfg = cfg.telemetry
        if self.tel_cfg is not None:
            self._tel_threshold = TEL.rate_threshold(
                self.tel_cfg.sample_rate
            )
            self.telemetry = TEL.TelemetryRecorder(
                self.tel_cfg, model=cfg.latency, scenario=scenario.name,
                policy=policy.name, n_clients=cfg.n_clients,
            )
        else:
            self._tel_threshold = 0
            self.telemetry = None
        # the stage timers: the caller's, else the span plane's, else off
        if timers is None:
            timers = (self.telemetry.timers if self.telemetry is not None
                      else TEL.StageTimers(enabled=False))
        self._timers = timers
        # the fleet metrics plane: a (window, n_series) f32 ring carried
        # (and donated) through the fused scan; None == empty pytree
        # slot, the same discipline as the overload/coordination planes
        self.met_cfg = cfg.metrics
        self._met_pos = 0   # host mirror of metrics.pos (fold positions)
        if self.met_cfg is not None:
            n_sw = (self.coord_mgr.n_switches
                    if self.coord_mgr is not None else 0)
            self.met_layout = MTR.build_layout(
                cfg.num_nodes, n_switches=n_sw,
                topk=min(self.met_cfg.topk, n_slots),
            )
            for s in self.met_cfg.slos:
                if s.series not in self.met_layout.index:
                    raise ValueError(
                        f"SLO {s.name!r} names unknown series "
                        f"{s.series!r}"
                    )
                need = s.slow_window + self.period
                if self.met_cfg.window < need:
                    raise ValueError(
                        f"metrics window {self.met_cfg.window} too "
                        f"short for SLO {s.name!r}: needs >= "
                        f"slow_window + period = {need} epochs of "
                        "retained history"
                    )
            self.metrics = MTR.make_state(
                self.met_cfg.window, self.met_layout.n_series
            )
            self.met_engine = SLOM.AlertEngine(
                self.met_cfg.slos, on_fire=self._on_slo_fire
            )
        else:
            self.met_layout = None
            self.metrics = None
            self.met_engine = None
        self.key = jax.random.PRNGKey(cfg.seed)

        self._traces = 0
        # compile counts carried across split_overflow step rebuilds: the
        # old program's jit cache size is banked here, so `traces` stays
        # exactly 1 + growth_events when recompiles only follow growth
        self._trace_base = 0
        self.growth_events = 0
        self._period = 0
        self._last_overflow = 0
        self.host_syncs = 0        # device->host round-trips (profile metric)
        # distinct keys seen since the last pull, deduped incrementally
        # (sorted-unique merge per epoch — pull cost no longer grows with
        # epoch_ops x period): queried against the count-min sketch at pull
        # time (StatsReport.key_sample/key_heat, the split policies'
        # boundary-quantile view)
        self._key_window: np.ndarray = np.empty(0, np.uint32)
        # scenario control events are deterministic: precompute the epochs
        # that force a host intervention (segment boundaries for the scan)
        self._event_epochs = {
            e for e in range(scfg.n_epochs) if scenario.events(e)
        }
        self._mesh = mesh
        self._step = None
        self._period_fn = None
        self._period_inputs = None
        if backend == "dist":
            base = dist_cfg or DistConfig()
            self._dist_cfg = dataclasses.replace(
                base,
                read_spread=self.mode_plan.spread,
                return_decision=True,
                replication_mode=cfg.replication_mode,
                max_scan_results=cfg.max_scan_results,
                queue_pen=(cfg.overload is not None
                           and cfg.overload.queue_weight > 0
                           and self.mode_plan.spread),
            )
            if fused:
                # the whole period inside ONE shard_map (a2a rounds in
                # the scan body) — compiled once, like the oracle scan
                self._dist_apply = None
                self._period_fn = self._build_dist_period()
            else:
                self._dist_apply = make_dist_apply(
                    mesh, directory, self._dist_cfg
                )
                self._step = self._build_dist_step()
        elif fused:
            self._period_fn = self._build_oracle_period(self.mode_plan)
        else:
            self._step = self._build_oracle_step(self.mode_plan)

        self._preload()

    # -- properties --------------------------------------------------------
    @property
    def traces(self) -> int:
        """How many distinct programs the epoch/period device step has
        compiled (the no-retracing acceptance gate: must be 1 after any
        number of epochs of one scenario).

        Counted from the jit compile-cache size wherever one exists — the
        python-side-effect counter under-reports a ``lax.scan`` body
        (traced more than once inside a single compile) and cannot see a
        dist-backend retrace at all, because ``make_dist_apply`` keys its
        own jit cache on input shardings.  Both caches are folded in so
        neither path can hide a retrace behind the other's count."""
        if self.backend == "oracle":
            if self.fused:
                return self._trace_base + _jit_cache_size(
                    self._period_fn, self._traces
                )
            return max(self._traces,
                       self._trace_base + _jit_cache_size(self._step, 0))
        t = self._traces
        if self.fused:
            # the fused dist period program: one cache entry per distinct
            # shape set (pool growth retraces it, counted like the oracle)
            return max(t, _jit_cache_size(self._dist_period, 0))
        return max(t, _jit_cache_size(self._dist_apply, 0))

    # -- setup -------------------------------------------------------------
    def _preload(self):
        """YCSB load phase: PUT every record through the normal data path,
        in chunks of at most ``PRELOAD_CHUNK`` records so peak device
        memory does not grow with the record count.  The tail chunk is
        padded with EMPTY-key GETs, which touch nothing.  Records are
        distinct keys, so the store ends bit-identical to a one-batch
        load.

        On the dist backend every device sees the whole chunk (the
        ``allgather`` data plane) and writes only its own slab, so the
        load runs shard-local, as the served path does."""
        keys, vals = self.scenario.load()
        if self.backend == "dist":
            axis = self._dist_cfg.axis
            n_dev = self._mesh.shape[axis]
            dist_load = make_dist_apply(
                self._mesh, self.directory,
                DistConfig(axis=axis, strategy="allgather",
                           max_scan_results=1),
            )

            def load(store, k, ops, v):
                return dist_load(store, self.directory,
                                 C.make_queries(k, ops, v))[0]
        else:
            n_dev = 1

            def load(store, k, ops, v):
                return _preload_chunk(
                    store, self.directory, k, ops, v,
                    max_scan_results=self.cfg.max_scan_results)
        # a whole number of batch slices per device
        B = -(-min(PRELOAD_CHUNK, len(keys)) // n_dev) * n_dev
        for i in range(0, len(keys), B):
            k, v = keys[i:i + B], vals[i:i + B]
            pad = B - len(k)
            ops = np.full((B,), K.OP_PUT, np.int32)
            if pad:
                ops[len(k):] = K.OP_GET
                k = np.concatenate([k, np.full((pad,), K.EMPTY_KEY, k.dtype)])
                v = np.concatenate([v, np.zeros((pad,) + v.shape[1:], v.dtype)])
            self.store = load(self.store, jnp.asarray(k), jnp.asarray(ops),
                              jnp.asarray(v))
        ovf = np.asarray(self.store.overflow).astype(np.int64)
        self._last_overflow = int(ovf.sum())
        # per-node overflow floor for capacity-driven splitting (which
        # node's store pushed past capacity since the last control pull)
        self._ovf_node_last = ovf

    # -- device step variants ----------------------------------------------
    def _make_oracle_body(self, mp: RPL.ModePlan):
        """One epoch's device math — shared verbatim by the per-epoch jit
        and the fused period scan so the two are the same program.

        ``mp`` wires the replication mode: p2c read spreading on or off,
        CRAQ dirty-bit tail bounces, the write path's client-visible
        chain cap, and whether the version register file advances."""
        cfg = self.cfg
        N = cfg.num_nodes
        spread = mp.spread
        # eventual mode under a spreading policy: widened members are
        # lazily-refreshed read replicas, the write's client-visible path
        # is the base chain only.  chain/craq broadcast down the whole
        # chain (see plan_hops / repro.replication.protocol).
        cap = mp.write_cap_spread
        # intra-epoch p2c freshness: sub-chunk the batch so the load
        # registers the p2c rule reads are at most 1/chunks of an epoch
        # stale.  The chunk loop unrolls inside the single jitted step —
        # the trace count stays 1.
        chunks = cfg.p2c_chunks if spread else 1
        # the overload plane (trace constants; None leaves every value
        # computed below bit-identical to the pre-overload program)
        ocfg = self.ovl_cfg
        # the trace plane (also trace constants; sampling consumes no
        # PRNG, so even the *enabled* path leaves every pre-existing
        # value bit-identical — only the extra span outputs are new)
        tcfg = self.tel_cfg
        tel_thr = self._tel_threshold
        # the coordination tier (trace constants; observe_epoch consumes
        # no PRNG and touches no store/counter state, so the disabled and
        # zero-lag paths are bit-identical — only the redirect pricing and
        # the new cstats output differ when the tables actually diverge)
        ccfg = self.coord_cfg
        hp = bool(getattr(self.directory, "hash_partitioned", False))
        fbits = cfg.craq_filter_bits
        # the metrics plane (trace constants; record_epoch consumes no
        # PRNG and the None path compiles the identical program)
        mcfg = self.met_cfg
        met_topk = self.met_layout.topk if mcfg is not None else 0

        def route_chunk(directory, load_reg, dirty, kf, qs, rng_c,
                        queue_pen):
            if mp.dirty_reads:
                dec, directory, load_reg, picked, bounced = (
                    R.route_load_aware_dirty(directory, qs, load_reg, dirty,
                                             rng_c, queue_pen=queue_pen,
                                             key_filter=kf)
                )
            elif spread:
                dec, directory, load_reg = R.route_load_aware(
                    directory, qs, load_reg, rng_c, queue_pen=queue_pen
                )
                picked = bounced = None
            else:
                dec, directory = R.route(directory, qs)
                picked = bounced = None
            return dec, directory, load_reg, picked, bounced

        def body(store, directory, load_reg, sketch, repl, ovl, coord,
                 metrics, q, rng, eid):
            if ocfg is not None:
                # fold_in (not a wider split) so the disabled path's
                # r_route/r_plan streams are untouched — routing and the
                # hop-plan service draws stay bit-identical either way
                r_ovl = jax.random.fold_in(rng, 0x0F10AD)
            r_route, r_plan = jax.random.split(rng)
            B = q.opcode.shape[0]
            with jax.named_scope(S.ROUTE):
                # deep queues repel p2c reads: the pre-epoch queue depth
                # joins the load registers in the pick comparison
                # (registers still bump raw, and the kernels fold the same
                # penalty at the ops layer — parity by construction)
                queue_pen = None
                if ocfg is not None and ocfg.queue_weight > 0 and spread:
                    queue_pen = ovl.queue.astype(jnp.uint32) * jnp.uint32(
                        ocfg.queue_weight
                    )
                # reads consult the PRE-epoch dirty state, exactly as they
                # observe the pre-batch store (repro.replication.state)
                dirty = RPL.dirty_bits(repl) if mp.dirty_reads else None
                kf = (repl.key_filter
                      if (mp.dirty_reads and fbits) else None)
                if spread and chunks > 1:
                    csize = B // chunks
                    decs, picks, bncs = [], [], []
                    for ci in range(chunks):
                        qs = jax.tree.map(
                            lambda x: x[ci * csize : (ci + 1) * csize], q
                        )
                        (dec, directory, load_reg, picked,
                         bounced) = route_chunk(
                            directory, load_reg, dirty, kf, qs,
                            jax.random.fold_in(r_route, ci), queue_pen,
                        )
                        decs.append(dec)
                        picks.append(picked)
                        bncs.append(bounced)
                    decision = jax.tree.map(
                        lambda *xs: jnp.concatenate(xs, axis=0), *decs
                    )
                    if mp.dirty_reads:
                        picked = jnp.concatenate(picks, axis=0)
                        bounced = jnp.concatenate(bncs, axis=0)
                else:
                    (decision, directory, load_reg, picked,
                     bounced) = route_chunk(
                        directory, load_reg, dirty, kf, q, r_route, queue_pen
                    )
            with jax.named_scope(S.APPLY):
                node_ops = _node_ops(decision, q.opcode, N)
                if not spread:
                    # tail-read path: registers tracked for parity (same
                    # units)
                    load_reg = load_reg + node_ops.astype(jnp.uint32)
            with jax.named_scope(S.OBSERVE):
                sketch = sketch_update(sketch, q.key)
            with jax.named_scope(S.APPLY):
                store, resp, counts = apply_routed_counted(
                    store, q, decision, max_scan_results=cfg.max_scan_results
                )
                with jax.named_scope(S.GET):
                    digest = get_digest(q.opcode, q.key, resp.value,
                                        resp.found)
            with jax.named_scope(S.OBSERVE):
                bounce_kw = (
                    dict(read_via=picked, read_bounce=bounced)
                    if mp.dirty_reads else {}
                )
                # overload step: queue/retry dynamics decide each query's
                # timing fate (the store above applied every op regardless —
                # accounting plane, see repro.overload).  The pre-step state
                # is the admission context the trace plane records: queue
                # depth at entry, exactly as routing observes the pre-epoch
                # store
                ovl_pre = ovl
                if ocfg is not None:
                    ovl, ovl_rej, ovl_scale, ovl_out, ostats = OVL.step(
                        ovl, decision.target, r_ovl, ocfg
                    )
                    ovl_kw = dict(shed=ovl_rej, service_scale=ovl_scale)
                    # cross-epoch retry linking: stamp/clear the hashed
                    # orbit-identity register (no-op at the (1,) placeholder)
                    ovl, first_epoch = OVL.link_orbit(
                        ovl, q.key, ovl_rej,
                        ovl_out == OVL.OUTCOME_ADMITTED, eid,
                    )
                else:
                    ostats = jnp.zeros((len(OVL.STAT_FIELDS),), jnp.int32)
                    ovl_kw = {}
                    first_epoch = None
                # the switch tier observes the batch against its (possibly
                # stale) per-switch table copies: versioned-redirect decision,
                # install of pending control writes, conservation counters.
                # Pure accounting — the decision above (and every store/
                # counter/PRNG effect) followed the TRUE tables, so the tier
                # only reprices hops and emits cstats
                if ccfg is not None:
                    coord, redirect, redirect_via, cstats = CT.observe_epoch(
                        coord, q, decision, eid, quorum=ccfg.quorum,
                        hash_partitioned=hp,
                    )
                    coord_kw = dict(redirect=redirect,
                                    redirect_via=redirect_via)
                else:
                    redirect = None
                    cstats = CT.empty_cstats()
                    coord_kw = {}
                plan = plan_hops(
                    q, decision, cfg.mode, cfg.latency, rng=r_plan, num_nodes=N,
                    write_chain_cap=cap, service_model=cfg.service_model,
                    **bounce_kw, **ovl_kw, **coord_kw,
                )
                if mp.track_state:
                    is_write = (q.opcode == K.OP_PUT) | (q.opcode == K.OP_DEL)
                    repl = RPL.advance(repl, decision.ridx, is_write,
                                       keys=q.key if fbits else None)
                retries = jnp.zeros((), jnp.int32)
                bounced_out = (bounced if mp.dirty_reads
                               else jnp.zeros((B,), jnp.bool_))
                # span attribution only: a versioned redirect rides the bounce
                # bucket of the trace plane (an extra pre-serve hop), while the
                # metric-stream bounced column stays CRAQ-only for parity
                span_bounced = (bounced_out if redirect is None
                                else bounced_out | redirect)
                if tcfg is not None:
                    if ocfg is not None:
                        t_safe = jnp.clip(decision.target, 0, N - 1)
                        qdepth = ovl_pre.queue[t_safe]
                        Lv = ovl_pre.retry.shape[1]
                        # deepest occupied backoff level at the target (how
                        # far its retry orbit has escalated); -1 when empty
                        orbit_node = jnp.max(
                            jnp.where(
                                ovl_pre.retry > 0,
                                jnp.arange(1, Lv + 1, dtype=jnp.int32)[None, :],
                                0,
                            ),
                            axis=1,
                        ) - 1
                        orbit = orbit_node[t_safe]
                        outcome = ovl_out
                        scale_rec = ovl_scale
                    else:
                        qdepth = jnp.zeros((B,), jnp.int32)
                        orbit = jnp.full((B,), -1, jnp.int32)
                        outcome = jnp.where(
                            decision.target >= 0,
                            jnp.int32(OVL.OUTCOME_ADMITTED),
                            jnp.int32(OVL.OUTCOME_INVALID),
                        )
                        scale_rec = jnp.ones((B,), jnp.float32)
                    pk = picked if mp.dirty_reads else decision.target
                    spans = TEL.collect_spans(
                        q, eid, decision, pk, span_bounced, outcome, qdepth,
                        orbit, scale_rec, plan,
                        threshold=tel_thr, k_slots=tcfg.max_spans,
                        lookup=cfg.latency.lookup, first_epoch=first_epoch,
                    )
                else:
                    spans = None
                if mcfg is not None:
                    # the fleet metrics row: post-step ovl, post-observe
                    # coord, post-advance repl — end-of-epoch state, like
                    # the flight ring's snapshots.  Pure observer.
                    metrics = MTR.record_epoch(
                        metrics, node_ops=node_ops, ovl=ovl, ostats=ostats,
                        cstats=cstats, coord=coord, repl=repl, sketch=sketch,
                        keys=q.key, ridx=decision.ridx, topk=met_topk,
                    )
            return (store, directory, load_reg, sketch, repl, ovl, coord,
                    metrics, plan, node_ops, retries, bounced_out, ostats,
                    cstats, spans, digest, counts)

        return body

    def _build_oracle_step(self, mp: RPL.ModePlan):
        body = self._make_oracle_body(mp)

        def step(store, directory, load_reg, sketch, repl, ovl, coord,
                 metrics, q, rng, eid):
            self._traces += 1  # python side effect: counts traces, not calls
            return body(store, directory, load_reg, sketch, repl, ovl,
                        coord, metrics, q, rng, eid)

        return jax.jit(step)

    def _build_oracle_period(self, mp: RPL.ModePlan):
        """The fused period program: ``period`` epoch bodies under one
        jitted ``lax.scan`` with the store/directory/load-register/sketch
        buffers **donated** (the store slabs are the big allocation — the
        scan updates them in place, no second live copy).

        Dead scan slots (segments cut short by a control event or the run
        end) compute but do not commit: the carry keeps its pre-step value
        and the host discards their output rows, so one fixed-length
        program covers every segment length — exactly one trace per
        scenario."""
        body = self._make_oracle_body(mp)

        def period(store, directory, load_reg, sketch, repl, ovl, coord,
                   metrics, qs, rngs, live, eids):
            self._note_period_inputs(qs, rngs, live, eids)

            def scan_body(carry, xs):
                (store, directory, load_reg, sketch, repl, ovl, coord,
                 metrics) = carry
                q, rng, lv, eid = xs
                (store2, directory2, load_reg2, sketch2, repl2, ovl2,
                 coord2, metrics2, plan, node_ops, retries, bounced,
                 ostats, cstats, spans, digest, counts) = body(
                    store, directory, load_reg, sketch, repl, ovl, coord,
                    metrics, q, rng, eid
                )
                with jax.named_scope(S.COMMIT):
                    keep = lambda new, old: jnp.where(lv, new, old)
                    store2 = jax.tree.map(keep, store2, store)
                    directory2 = jax.tree.map(keep, directory2, directory)
                    carry2 = (store2, directory2, keep(load_reg2, load_reg),
                              keep(sketch2, sketch),
                              jax.tree.map(keep, repl2, repl),
                              jax.tree.map(keep, ovl2, ovl),
                              jax.tree.map(keep, coord2, coord),
                              jax.tree.map(keep, metrics2, metrics))
                    ovf = jnp.sum(store2.overflow)
                # spans ride the ys stack (None == empty pytree when the
                # trace plane is off — the program is unchanged)
                return carry2, (plan, node_ops, retries, ovf, bounced,
                                ostats, cstats, spans, digest, counts)

            carry, outs = jax.lax.scan(
                scan_body,
                (store, directory, load_reg, sketch, repl, ovl, coord,
                 metrics),
                (qs, rngs, live, eids),
            )
            return (*carry, *outs)

        # donate the big buffers: store slabs, load registers, sketch, the
        # replication register file (version/dirty tables), the overload
        # queue/retry registers, the coordination tier's per-switch
        # table copies and the metrics ring (each an empty pytree when
        # disabled — donating one is then a no-op).
        # The directory is NOT donated — several of its freshly-grafted
        # tables (e.g. the zeroed read/write counters) can alias the same
        # constant buffer, which XLA rejects as a double donation; it is
        # also tiny next to the slabs, so nothing is lost.
        return jax.jit(period, donate_argnums=(0, 2, 3, 4, 5, 6, 7))

    def _make_dist_observe(self):
        """The dist observe stage — everything after the sharded apply,
        operating on the GLOBAL batch (per-node op counts, the sketch,
        the overload admission step, hop planning, replication-register
        advance, span sampling).  Shared verbatim by the per-epoch step
        (jitted at host level on the assembled decision) and the fused
        period program (run replicated inside the shard_map on the
        all_gathered decision), so the two are the same math."""
        cfg = self.cfg
        N = cfg.num_nodes
        mp = self.mode_plan
        ocfg = self.ovl_cfg
        tcfg = self.tel_cfg
        tel_thr = self._tel_threshold
        ccfg = self.coord_cfg
        hp = bool(getattr(self.directory, "hash_partitioned", False))
        mcfg = self.met_cfg
        met_topk = self.met_layout.topk if mcfg is not None else 0

        def observe(q, ridx, target, chain, chain_len, sketch, rng, repl,
                    picked, bounced, ovl, r_ovl, eid, coord, metrics):
            """Post-processing of the dist apply's decision."""
            with jax.named_scope(S.OBSERVE):
                B = target.shape[0]
                decision = C.RoutingDecision(
                    ridx=ridx,
                    target=target,
                    chain=chain,
                    chain_len=chain_len,
                    clength=jnp.zeros_like(target),
                )
                node_ops = _node_ops(decision, q.opcode, N)
                sketch = sketch_update(sketch, q.key)
                bounce_kw = (dict(read_via=picked, read_bounce=bounced)
                             if mp.dirty_reads else {})
                # overload step: same accounting-plane placement as the oracle
                # body — after the distributed apply, deciding timing fate only
                ovl_pre = ovl
                if ocfg is not None:
                    ovl, ovl_rej, ovl_scale, ovl_out, ostats = OVL.step(
                        ovl, target, r_ovl, ocfg
                    )
                    ovl_kw = dict(shed=ovl_rej, service_scale=ovl_scale)
                    ovl, first_epoch = OVL.link_orbit(
                        ovl, q.key, ovl_rej,
                        ovl_out == OVL.OUTCOME_ADMITTED, eid,
                    )
                else:
                    ostats = jnp.zeros((len(OVL.STAT_FIELDS),), jnp.int32)
                    ovl_kw = {}
                    first_epoch = None
                # the coordination tier observes the global batch (same
                # accounting-plane placement as the oracle body: redirects
                # reprice hops, nothing else changes)
                if ccfg is not None:
                    coord, redirect, redirect_via, cstats = CT.observe_epoch(
                        coord, q, decision, eid, quorum=ccfg.quorum,
                        hash_partitioned=hp,
                    )
                    coord_kw = dict(redirect=redirect,
                                    redirect_via=redirect_via)
                else:
                    redirect = None
                    cstats = CT.empty_cstats()
                    coord_kw = {}
                plan = plan_hops(
                    q, decision, cfg.mode, cfg.latency, rng=rng, num_nodes=N,
                    write_chain_cap=mp.write_cap_spread,
                    service_model=cfg.service_model, **bounce_kw, **ovl_kw,
                    **coord_kw,
                )
                if mp.track_state:
                    is_write = (q.opcode == K.OP_PUT) | (q.opcode == K.OP_DEL)
                    repl = RPL.advance(repl, ridx, is_write)
                span_bounced = (bounced if redirect is None
                                else bounced | redirect)
                if tcfg is not None:
                    if ocfg is not None:
                        t_safe = jnp.clip(target, 0, N - 1)
                        qdepth = ovl_pre.queue[t_safe]
                        Lv = ovl_pre.retry.shape[1]
                        orbit_node = jnp.max(
                            jnp.where(
                                ovl_pre.retry > 0,
                                jnp.arange(1, Lv + 1, dtype=jnp.int32)[None, :],
                                0,
                            ),
                            axis=1,
                        ) - 1
                        orbit = orbit_node[t_safe]
                        outcome = ovl_out
                        scale_rec = ovl_scale
                    else:
                        qdepth = jnp.zeros((B,), jnp.int32)
                        orbit = jnp.full((B,), -1, jnp.int32)
                        outcome = jnp.where(
                            target >= 0,
                            jnp.int32(OVL.OUTCOME_ADMITTED),
                            jnp.int32(OVL.OUTCOME_INVALID),
                        )
                        scale_rec = jnp.ones((B,), jnp.float32)
                    spans = TEL.collect_spans(
                        q, eid, decision, picked, span_bounced, outcome, qdepth,
                        orbit, scale_rec, plan,
                        threshold=tel_thr, k_slots=tcfg.max_spans,
                        lookup=cfg.latency.lookup, first_epoch=first_epoch,
                    )
                else:
                    spans = None
                if mcfg is not None:
                    # same end-of-epoch placement as the oracle body — the
                    # observe stage runs replicated on the global batch, so
                    # the ring row is identical on every device
                    metrics = MTR.record_epoch(
                        metrics, node_ops=node_ops, ovl=ovl, ostats=ostats,
                        cstats=cstats, coord=coord, repl=repl, sketch=sketch,
                        keys=q.key, ridx=ridx, topk=met_topk,
                    )
                return (sketch, plan, node_ops, repl, ovl, coord, metrics,
                        ostats, cstats, spans)

        return observe

    def _build_dist_step(self):
        from jax.sharding import NamedSharding, PartitionSpec

        cfg = self.cfg
        mp = self.mode_plan
        spread = mp.spread
        dist_apply = self._dist_apply
        # canonical layouts: replicated control state, node-sharded store.
        # Every call re-commits its inputs to these (a no-op at steady
        # state) — jit keys its cache on input commitment, so the mix of
        # committed step outputs and uncommitted host-built refresh tables
        # would otherwise compile the fused program twice (epoch 0 with
        # fresh host arrays, epoch 1 with device outputs: a hidden
        # retrace the `traces` gate now catches).
        rep = NamedSharding(self._mesh, PartitionSpec())
        shd = NamedSharding(self._mesh, PartitionSpec(self._dist_cfg.axis))
        ocfg = self.ovl_cfg
        use_qpen = self._dist_cfg.queue_pen
        observe_body = self._make_dist_observe()

        def observe(*args):
            self._traces += 1  # python side effect: counts traces
            return observe_body(*args)

        observe = jax.jit(observe)
        digest_of = jax.jit(lambda q, resp: get_digest(
            q.opcode, q.key, resp.value, resp.found))

        def step(store, directory, load_reg, sketch, repl, ovl, coord,
                 metrics, q, rng, eid):
            store = jax.device_put(store, shd)
            directory = jax.device_put(directory, rep)
            load_reg = jax.device_put(load_reg, rep)
            sketch = jax.device_put(sketch, rep)
            repl = jax.device_put(repl, rep)
            if coord is not None:
                coord = jax.device_put(coord, rep)
            if metrics is not None:
                metrics = jax.device_put(metrics, rep)
            if ovl is not None:
                ovl = jax.device_put(ovl, rep)
                r_ovl = jax.random.fold_in(rng, 0x0F10AD)
            else:
                r_ovl = rng  # unused placeholder, keeps observe uniform
            r_route, r_plan = jax.random.split(rng)
            B = q.opcode.shape[0]
            qp = ()
            if use_qpen:
                qp = (jax.device_put(
                    ovl.queue.astype(jnp.uint32)
                    * jnp.uint32(ocfg.queue_weight), rep
                ),)
            if mp.dirty_reads:
                dirty = jax.device_put(RPL.dirty_bits(repl), rep)
                store, resp, directory, load_reg, m = dist_apply(
                    store, directory, load_reg, *qp, dirty, q, r_route
                )
                picked, bounced = m["picked"], m["bounced"]
            elif spread:
                store, resp, directory, load_reg, m = dist_apply(
                    store, directory, load_reg, *qp, q, r_route
                )
                picked = bounced = None
            else:
                store, resp, directory, m = dist_apply(store, directory, q)
                picked = bounced = None
            if picked is None:
                # placeholders keep observe's signature mode-independent
                picked = m["target"]
                bounced = jnp.zeros((B,), jnp.bool_)
            (sketch, plan, node_ops, repl, ovl, coord, metrics, ostats,
             cstats, spans) = observe(
                q, m["ridx"], m["target"], m["chain"], m["chain_len"], sketch,
                r_plan, repl, picked, bounced, ovl, r_ovl, eid, coord,
                metrics,
            )
            if not spread:
                load_reg = load_reg + node_ops.astype(jnp.uint32)
            return (store, directory, load_reg, sketch, repl, ovl, coord,
                    metrics, plan, node_ops, m["bucket_overflow"], bounced,
                    ostats, cstats, spans, digest_of(q, resp), m["counts"])

        return step

    def _build_dist_period(self):
        """The fused dist period program (the scale-out tentpole): the
        whole control period runs as ONE shard_map program with the
        ``lax.scan`` over epochs *inside* it (``make_dist_period``) — one
        dispatch and one compile per scenario, like the oracle scan,
        instead of one shard_map program per epoch.  Wrapped with the
        same canonical-sharding re-commit as the per-epoch step (jit keys
        its cache on input commitment) and exposing the oracle period
        fn's exact signature so ``_scan_segment`` drives both backends."""
        from jax.sharding import NamedSharding, PartitionSpec

        mp = self.mode_plan
        ocfg = self.ovl_cfg
        use_qpen = self._dist_cfg.queue_pen

        def pre(repl, ovl):
            # the per-epoch routing inputs the driver derives from carried
            # state between steps, now derived inside the scan body —
            # identical math on identical (pre-epoch) state
            queue_pen = None
            if use_qpen:
                queue_pen = ovl.queue.astype(jnp.uint32) * jnp.uint32(
                    ocfg.queue_weight
                )
            dirty = RPL.dirty_bits(repl) if mp.dirty_reads else None
            return dirty, queue_pen

        self._dist_period = make_dist_period(
            self._mesh, self.directory, self._dist_cfg,
            pre=pre, observe=self._make_dist_observe(),
            fold_ovl=ocfg is not None, on_trace=self._note_period_inputs,
        )
        rep = NamedSharding(self._mesh, PartitionSpec())
        shd = NamedSharding(self._mesh, PartitionSpec(self._dist_cfg.axis))

        def commit(store, *state):
            return (jax.device_put(store, shd),
                    *(jax.device_put(x, rep) for x in state))

        def period(store, directory, load_reg, sketch, repl, ovl, coord,
                   metrics, qs, rngs, live, eids):
            return self._dist_period(
                *commit(store, directory, load_reg, sketch, repl, ovl,
                        coord, metrics),
                qs, rngs, live, eids,
            )

        self._dist_commit = commit
        return period

    # -- host-side helpers -------------------------------------------------
    def _sync(self, x):
        """Device->host transfer of an array or a pytree of arrays, in one
        round trip, with bookkeeping (the profile metric the fused
        pipeline exists to minimize)."""
        self.host_syncs += 1
        return jax.tree.map(np.asarray, jax.device_get(x))

    def _pull_apply(self, ovf, digest, counts):
        """The overflow totals and GET digests of a segment's epochs, in
        one round trip; with the timers on, the segment's count parts
        ride along into ``timers.counts``: each field summed, or taken at
        its maximum where the part names it among its ``peaks``.  The
        dist programs return a tuple of parts (``ApplyCounts`` and, under
        ``bucket_a2a``, ``ExchangeCounts``); the oracle programs their one
        ``ApplyCounts``, bare, so that their outputs stay as they were."""
        if not self._timers.enabled:
            ovf_h, digest_h = self._sync((ovf, digest))
        else:
            ovf_h, digest_h, counts_h = self._sync((ovf, digest, counts))
            parts = counts_h if self.backend == "dist" else (counts_h,)
            for part in parts:
                for name, n in part._asdict().items():
                    n = n.astype(np.int64)
                    if name in part.peaks:
                        self._timers.peak(name, int(n.max()))
                    else:
                        self._timers.count(name, int(n.sum()))
        return ovf_h.astype(np.int64), digest_h

    def _note_period_inputs(self, qs, rngs, live, eids) -> None:
        """Trace-time hook of the period program: keep the shapes of its
        staged inputs, which :meth:`period_op_scopes` lowers it with."""
        self._period_inputs = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            (qs, rngs, live, eids))

    def period_op_scopes(self) -> TEL.OpScopes:
        """The named scope of every operation of the period program as
        compiled for the driver's current shapes (see
        :func:`repro.telemetry.profiler.hlo_op_scopes`), read from the
        compiled program's text.  The program must have run: its compiled
        executable is reused, nothing is compiled again."""
        if self._period_fn is None:
            raise RuntimeError("period_op_scopes() reads the fused period "
                               "program; this driver steps per epoch")
        if self._period_inputs is None:
            raise RuntimeError("period_op_scopes() needs the period program "
                               "traced: run a segment first")
        state = (self.store, self.directory, self.load_reg, self.sketch,
                 self.repl, self.ovl, self.coord, self.metrics)
        fn = self._period_fn
        if self.backend == "dist":
            fn, state = self._dist_period, self._dist_commit(*state)
        hlo = fn.lower(*state, *self._period_inputs).compile().as_text()
        return TEL.hlo_op_scopes(hlo, PERIOD_SCOPES, APPLY_SCOPES)

    def _note_keys(self, keys) -> None:
        """Fold one epoch's keys into the distinct-key window (sorted-unique
        incremental merge; capped by uniform thinning)."""
        ek = np.unique(np.asarray(keys, np.uint32).ravel())
        self._key_window = _merge_unique(self._key_window, ek)
        cap = self.cfg.key_window_cap
        if cap and self._key_window.size > cap:
            stride = -(-self._key_window.size // cap)   # ceil div
            self._key_window = self._key_window[::stride]

    def _sketch_heat(self, sample: np.ndarray) -> np.ndarray:
        """Count-min estimates for the window, via a shape-stable padded
        query (per-epoch sample sizes vary; padding to a power-of-two
        bucket keeps the eager query from recompiling every pull — this
        was the single biggest per-epoch host cost before the fused
        pipeline)."""
        m = sample.size
        padded = 1 << max(6, (m - 1).bit_length())
        buf = np.full(padded, K.EMPTY_KEY, np.uint32)
        buf[:m] = sample
        heat = self._sync(sketch_query(self.sketch, jnp.asarray(buf)))
        return heat[:m].astype(np.float64)

    def _handle_events(self, e: int) -> tuple[list[str], int, int]:
        """Apply the scenario's control events for epoch ``e`` (host side;
        events only ever fire at epoch boundaries == segment starts)."""
        scfg = self.scenario.cfg
        events: list[str] = []
        mig_entries = mig_bytes = 0
        tables_changed = False
        for kind, node in self.scenario.events(e):
            if kind == "fail":
                # live node_load mid-period: counters are NOT reset here
                nl = self._sync(D.node_load(self.directory))
                ops = self.controller.handle_node_failure(node, nl)
                en, by = migration_traffic(self.store, ops, scfg.value_dim)
                self.store = execute_migrations(self.store, ops)
                self.directory = self.controller.refresh(self.directory)
                mig_entries += en
                mig_bytes += by
                tables_changed = True
                events.append(f"fail:{node}")
            elif kind == "rack_fail":
                # correlated failure: the switch fronting a rack dies and
                # every node behind it goes with it (paper §5.2); the
                # controller splices all of them before re-replicating so
                # repair copies never target a dead rack-mate
                rack = [int(n) for n in node]
                ops = self.controller.handle_switch_failure(rack)
                en, by = migration_traffic(self.store, ops, scfg.value_dim)
                self.store = execute_migrations(self.store, ops)
                self.directory = self.controller.refresh(self.directory)
                mig_entries += en
                mig_bytes += by
                tables_changed = True
                events.append("rack_fail:" + "+".join(map(str, rack)))
            elif kind == "recover":
                self.controller.recover_node(node)
                events.append(f"recover:{node}")
            elif kind in CT.EVENT_KINDS:
                # coordination-plane faults: meaningful only with the
                # tier on; the same scenario drives the no-tier baseline
                # arm, which simply ignores them
                if self.coord_mgr is not None:
                    with self._timers.stage("coord_control"):
                        self.coord, notes = self.coord_mgr.on_event(
                            kind, node, self.coord,
                            self.controller.table_snapshot(), now=e,
                        )
                    events.extend(notes)
        self._sync_repl()
        if self.coord_mgr is not None and tables_changed:
            # a failure splice is a control write like any other: it must
            # propagate along the switch chain (stale copies keep routing
            # to the spliced chain until their install lands — priced as
            # redirects, never served wrong under quorum reads)
            with self._timers.stage("coord_control"):
                self.coord, notes = self.coord_mgr.on_control(
                    self.coord, self.controller.table_snapshot(), now=e,
                )
            events.extend(notes)
        return events, mig_entries, mig_bytes

    def _sync_repl(self) -> None:
        """Replay the controller's reconfiguration journal onto the
        device-resident version/dirty register file (chain membership
        changes dirty conservatively, split children inherit — see
        ``repro.replication.state.apply_events``).  The journal is always
        drained (it must not grow unbounded) but only the tracking modes
        pay the host round-trip."""
        events = self.controller.drain_repl_log()
        if events and self.mode_plan.track_state:
            self.host_syncs += 1   # apply_events pulls the register file
            self.repl = RPL.apply_events(self.repl, events)

    def _control_pull(self, now: int) -> tuple[list[str], int, int]:
        """The period-boundary controller pull: harvest + reset counters,
        run the policy, execute its migration plan, graft the refreshed
        tables.  The ONLY counter/load-register reset path.  ``now`` is
        the epoch count at the pull (the boundary just completed)."""
        scfg = self.scenario.cfg
        self.host_syncs += 1   # pull_report harvests the device counters
        report, self.directory = pull_report(self.directory, self._period)
        self._period += 1
        if self._key_window.size:
            # count-min view of the period: distinct keys seen, with
            # their sketch heat estimates — the split policies place
            # boundaries at heat quantiles inside hot ranges
            sample = self._key_window
            heat = self._sketch_heat(sample)
            report = dataclasses.replace(
                report, key_sample=sample, key_heat=heat
            )
            self._key_window = np.empty(0, np.uint32)
        if self.mode_plan.spread:
            # directory.node_load charges every read to the chain tail;
            # under p2c spreading the data-plane load registers are the
            # truthful per-node picture — hand those to the policy so
            # widen/balance target selection doesn't chase tails
            report = dataclasses.replace(
                report,
                node_load=self._sync(self.load_reg).astype(np.float64),
            )
        if self.ovl is not None:
            # queue/retry view for the backpressure policies (host syncs
            # gated on the subsystem so the disabled path's sync count is
            # untouched)
            self.host_syncs += 1
            qd = np.asarray(self.ovl.queue).astype(np.int64)
            rb = np.asarray(self.ovl.retry).sum(axis=1).astype(np.int64)
            report = dataclasses.replace(
                report,
                queue_depth=qd,
                retry_backlog=rb,
                queue_limit=int(self.ovl_cfg.queue_cap),
                service_limit=int(self.ovl_cfg.service_rate),
            )
        if self.auto_period:
            # cadence-aware budgets: a period of k x the band minimum
            # gets k rounds' worth of per-round move/widen/split budget,
            # keeping the migration *rate* cadence-invariant
            span = max(now - self._last_pull_epoch, 1)
            report = dataclasses.replace(
                report,
                budget_scale=float(span) / float(self.cfg.auto_band[0]),
            )
        events: list[str] = []
        rb = getattr(self.policy.config, "redirect_backoff", 0.0)
        if rb > 0 and self._last_redirect_share > rb:
            # the switch fabric is still digesting the last
            # reconfiguration (redirect share above the policy's backoff
            # threshold): skip this round's policy consult entirely so
            # control churn stops widening the stale window
            ops = []
            events.append(
                f"redirect_backoff:{self._last_redirect_share:.3f}"
            )
        else:
            ops = self.policy.on_report(self.controller, report)
        # backpressure control channel: policies publish per-node
        # admission probabilities / retry budgets and free-form event
        # notes; graft them onto the device registers for the next period
        if self.ovl is not None:
            ap = getattr(self.policy, "admit_prob", None)
            if ap is not None:
                self.ovl = dataclasses.replace(
                    self.ovl, admit_prob=jnp.asarray(ap, jnp.float32)
                )
            rbud = getattr(self.policy, "retry_budget", None)
            if rbud is not None:
                self.ovl = dataclasses.replace(
                    self.ovl, retry_budget=jnp.asarray(rbud, jnp.int32)
                )
        notes = getattr(self.policy, "notes", None)
        if notes:
            events.extend(notes)
            notes.clear()
        mig_entries = mig_bytes = 0
        if ops:
            mig_entries, mig_bytes = migration_traffic(
                self.store, ops, scfg.value_dim
            )
            self.store = execute_migrations(self.store, ops)
            events.extend(f"{op.kind}:{op.src}->{op.dst}" for op in ops)
        if self.cfg.split_overflow:
            sops = self._capacity_splits(report)
            if sops:
                en, by = migration_traffic(self.store, sops, scfg.value_dim)
                self.store = execute_migrations(self.store, sops)
                mig_entries += en
                mig_bytes += by
                events.extend(f"{op.kind}:{op.src}->{op.dst}" for op in sops)
        grew = self.controller.num_slots != self.directory.chains.shape[0]
        if grew:
            # the slot pool grew under split_overflowed: shapes changed,
            # so refresh refuses by design — rebuild the device directory
            # and recompile the step.  The live counters were harvested
            # and reset by this very pull, so pending merge credits would
            # land on zeros; drop them with the old tables.
            self.controller.drop_credits()
            self.directory = self.controller.directory()
            self._rebuild_step()
            events.append(f"grow_pool:{self.controller.num_slots}")
        else:
            self.directory = self.controller.refresh(self.directory)
        self._sync_repl()
        if self.coord_mgr is not None:
            # the sync/stage/lease path is host control work like the
            # policy consult — timed under its own stage so the period
            # breakdown accounts for the coordination tier
            with self._timers.stage("coord_control"):
                snap = self.controller.table_snapshot()
                if grew:
                    # pool growth changes every table shape: full fabric
                    # resync at the new width (the step recompiles anyway
                    # — `traces` counts the growth, not a hidden retrace)
                    self.coord = self.coord_mgr.rebuild(snap)
                else:
                    # the period's control writes enter the switch chain:
                    # commit now, install per-switch with chain-position
                    # lag
                    self.coord, cnotes = self.coord_mgr.on_control(
                        self.coord, snap, now=now
                    )
                    events.extend(cnotes)
        if self.auto_period and now < self.scenario.cfg.n_epochs:
            # the pull at the final boundary has no next period to tune:
            # retuning there would append a period choice that never
            # executes (and, pre-fix, one computed without the realized
            # budget_scale) — drop it from period_history instead of
            # reporting a known-stale field
            nl = np.asarray(report.node_load, np.float64)
            if self.mode_plan.spread:
                # registers are cumulative-with-decay; the drift input is
                # this period's delta over the post-halving floor (the
                # non-spread path feeds pull_report counters, which ARE
                # reset per period — same semantics either way)
                self._auto_retune(nl - self._reg_floor, now)
                self._reg_floor = np.floor_divide(nl, 2)
            else:
                self._auto_retune(nl, now)
        # halve rather than zero: p2c needs *recent* load signal to keep
        # steering reads off write-busy heads; a hard reset degenerates
        # it to a uniform-random replica pick for the whole next period
        self.load_reg = self.load_reg // 2
        self.sketch = jnp.zeros_like(self.sketch)
        return events, mig_entries, mig_bytes

    def _auto_retune(self, node_load: np.ndarray, now: int) -> None:
        """Adaptive pull cadence: pick the next control period from
        report-to-report load drift, inside ``cfg.auto_band``.

        Drift is the L1 change of the *per-epoch-normalized* node-load
        vector relative to its previous mass (periods vary in length, so
        raw register sums are not comparable).  High drift (a moving
        hotspot) halves the period — control tightens; low drift doubles
        it — the data plane runs longer between host round-trips.  The
        fused scan is sized at the band maximum, so every period length
        in the band runs as a masked-padded segment of the one compiled
        program."""
        cfg = self.cfg
        lo, hi = int(cfg.auto_band[0]), int(cfg.auto_band[1])
        span = max(now - self._last_pull_epoch, 1)
        load = np.asarray(node_load, np.float64) / span
        prev = self._prev_load
        if prev is not None:
            mass = max(prev.sum(), 1e-9)
            drift = float(np.abs(load - prev).sum() / mass)
            if drift > cfg.auto_drift_hi:
                self._cur_period = max(lo, self._cur_period // 2)
            elif drift < cfg.auto_drift_lo:
                self._cur_period = min(hi, self._cur_period * 2)
        self._prev_load = load
        self._last_pull_epoch = now
        self._next_pull = now + self._cur_period
        self.period_history.append(self._cur_period)

    def _capacity_splits(self, report) -> list:
        """Capacity-driven splitting in the loop (paper §4.1.1): for each
        node whose store overflowed since the last pull, split the hottest
        live range it heads (``Controller.split_overflowed`` — which grows
        the slot pool when exhausted; the caller rebuilds the step)."""
        ovf = self._sync(self.store.overflow).astype(np.int64)
        delta = ovf - self._ovf_node_last
        self._ovf_node_last = ovf
        hot_nodes = [int(n) for n in np.argsort(-delta) if delta[n] > 0]
        if not hot_nodes:
            return []
        heat = (report.read_count + report.write_count).astype(np.float64)
        ctl = self.controller
        ops = []
        for node in hot_nodes:
            cands = [r for r in ctl.live_ranges()
                     if int(ctl.chain_nodes(r)[0]) == node]
            if not cands:
                continue
            # ranges born mid-loop (post-harvest) carry no heat yet
            ridx = max(cands,
                       key=lambda r: heat[r] if r < heat.size else 0.0)
            ops.extend(ctl.split_overflowed(ridx, report.node_load))
        return ops

    def _rebuild_step(self) -> None:
        """Recompile the device step after a pool growth (the one control
        action that changes array shapes).  The old program's compile
        count is banked in ``_trace_base`` so :attr:`traces` reports
        exactly ``1 + growth_events`` when recompiles only follow
        growth — the no-silent-retrace gate, now growth-aware."""
        if self.backend == "dist":
            # the dist programs close over no shapes: jit re-specializes
            # on the grown directory/repl arrays by itself, and the
            # traces property reads that cache — count the growth, keep
            # the program
            self.growth_events += 1
            return
        if self.fused:
            self._trace_base += _jit_cache_size(self._period_fn, 0)
            self._period_fn = self._build_oracle_period(self.mode_plan)
        else:
            self._trace_base += _jit_cache_size(self._step, 0)
            self._step = self._build_oracle_step(self.mode_plan)
        self.growth_events += 1

    # -- the per-epoch reference loop --------------------------------------
    def run_epoch(self, e: int) -> EpochMetrics:
        """One epoch, one host round-trip (the ``fused=False`` loop the
        period pipeline is asserted bit-identical against)."""
        if self._step is None:
            raise RuntimeError(
                "per-epoch stepping is unavailable on a fused driver; "
                "use run(), or construct with fused=False"
            )
        cfg = self.cfg
        scfg = self.scenario.cfg
        events, mig_entries, mig_bytes = self._handle_events(e)

        with self._timers.stage("inject"):
            opcodes, keys, end_keys, values = self.scenario.epoch(e)
            self._note_keys(keys)
            q = C.make_queries(
                jnp.asarray(keys), jnp.asarray(opcodes),
                jnp.asarray(values), jnp.asarray(end_keys),
            )
            rng = jax.random.fold_in(self.key, e)
        with self._timers.stage("route_apply"):
            out = self._step(
                self.store, self.directory, self.load_reg, self.sketch,
                self.repl, self.ovl, self.coord, self.metrics, q, rng,
                jnp.int32(e)
            )
            if self._timers.enabled:
                # profiling measures execution, not dispatch; values are
                # untouched (an explicit, wall-time-only observer effect)
                jax.block_until_ready(out)
        (self.store, self.directory, self.load_reg, self.sketch, self.repl,
         self.ovl, self.coord, self.metrics, plan, node_ops, retries,
         bounced, ostats, cstats, spans, digest, counts) = out

        self.host_syncs += 1   # the DES engine pulls the plan to the host
        issue = hops = None
        with self._timers.stage("des"):
            if self.telemetry is not None:
                latency, makespan, issue, hops = C.simulate_closed_loop(
                    plan,
                    n_clients=cfg.n_clients,
                    num_nodes=cfg.num_nodes,
                    link=cfg.latency.link,
                    backend=cfg.des_backend,
                    return_issue=True,
                    return_hops=True,
                )
            else:
                latency, makespan = C.simulate_closed_loop(
                    plan,
                    n_clients=cfg.n_clients,
                    num_nodes=cfg.num_nodes,
                    link=cfg.latency.link,
                    backend=cfg.des_backend,
                )
        lat = np.asarray(latency)[None]
        (p50,), (p99,) = latency_percentiles_batch(lat)
        (p999,) = p999_batch(lat)
        mk = float(np.asarray(makespan))

        is_read = ((opcodes == K.OP_GET) | (opcodes == K.OP_SCAN))[None]
        if self.mode_plan.dirty_reads:
            bounced_h = self._sync(bounced).astype(bool)[None]
        else:
            bounced_h = np.zeros_like(is_read)
        (read_p99,) = masked_p99_batch(lat, is_read)
        (clean_p99,) = masked_p99_batch(lat, is_read & ~bounced_h)
        dirty_reads = int(bounced_h.sum())

        live = self._live_mask()
        (imb,), (cov,) = imbalance_stats_batch(
            self._sync(node_ops)[None], live
        )

        # drops = pure store-capacity overflow delta; the overload plane's
        # shed/requeued/lost travel separately (the satellite fix for the
        # old conflation of capacity events with shed traffic)
        ovf_h, digest_h = self._pull_apply(self.store.overflow, digest,
                                           counts)
        overflow_now = int(ovf_h.sum())
        drops = overflow_now - self._last_overflow
        self._last_overflow = overflow_now
        if self.ovl is not None:
            ost = self._sync(ostats).astype(np.int64)
        else:
            ost = np.zeros((len(OVL.STAT_FIELDS),), np.int64)
        if self.coord is not None:
            cst = self._sync(cstats).astype(np.int64)
            if cst[0] > 0:
                self._last_redirect_share = float(cst[2]) / float(cst[0])
        else:
            cst = np.zeros((len(CT.CSTAT_FIELDS),), np.int64)

        # ---- control pull: the only counter/load-register reset path ----
        pull = ((e + 1) == self._next_pull if self.auto_period
                else (e + 1) % self.period == 0)
        if pull:
            pev, pen, pby = self._control_pull(e + 1)
            events.extend(pev)
            mig_entries += pen
            mig_bytes += pby

        row = EpochMetrics(
            epoch=e,
            scenario=self.scenario.name,
            policy=self.policy.name,
            ops=scfg.epoch_ops,
            throughput=scfg.epoch_ops / mk if mk > 0 else 0.0,
            p50=p50,
            p99=p99,
            makespan=mk,
            imbalance=imb,
            cov=cov,
            migration_entries=mig_entries,
            migration_bytes=mig_bytes,
            drops=drops,
            retries=int(self._sync(retries)),
            compiled_steps=self.traces,
            events=events,
            p999=float(p999),
            read_p99=float(read_p99),
            clean_read_p99=float(clean_p99),
            dirty_reads=dirty_reads,
            replication=cfg.replication_mode,
            deferred=int(ost[2]),
            shed=int(ost[3]),
            requeued=int(ost[4]),
            lost=int(ost[5]),
            queue_peak=int(ost[6]),
            routed=int(cst[0]),
            direct=int(cst[1]),
            redirected=int(cst[2]),
            mis_served=int(cst[3]),
            stale_switches=int(cst[4]),
            coordination=self._coord_label(),
            get_digest=int(digest_h),
        )
        if self.telemetry is not None:
            si, sf, cnt = spans
            self.host_syncs += 1   # span tables + state snapshot pull
            self.telemetry.on_segment(
                e, [row],
                np.asarray(si)[None], np.asarray(sf)[None],
                np.asarray(cnt)[None], lat,
                None if issue is None else np.asarray(issue)[None],
                np.asarray([mk]), self._state_snapshot(),
                hops=None if hops is None else np.asarray(hops)[None],
            )
        # fold the host-computed columns into the ring row the device
        # just wrote, then evaluate the SLO burn rates — L == 1 here, so
        # the cells and values are bitwise the fused path's (parity
        # contract on every ring leaf).  After on_segment: a burn alert's
        # flight dump must include this epoch's ring entry.
        self._fold_metrics(e, 1, [p50], [p99], [p999], [imb])
        return row

    def _state_snapshot(self) -> dict:
        """Host view of the carried state for the flight-recorder ring
        (telemetry-only path; its syncs are counted by the caller)."""
        snap: dict = {
            "load_reg": np.asarray(self.load_reg).astype(np.int64).tolist(),
        }
        if self.ovl is not None:
            snap["queue_depth"] = np.asarray(self.ovl.queue).tolist()
            snap["retry_backlog"] = int(np.asarray(self.ovl.retry).sum())
            snap["conservation_gap"] = OVL.conservation_gap(self.ovl)
        if self.mode_plan.track_state:
            snap["replication"] = RPL.summary(self.repl)
        if self.coord_mgr is not None:
            snap["coordination"] = self.coord_mgr.summary()
        return snap

    def _coord_label(self) -> str:
        """The metric-row coordination arm label ("none" when the tier is
        off — the pre-tier rows round-trip unchanged)."""
        if self.coord_cfg is None:
            return "none"
        return "quorum" if self.coord_cfg.quorum else "no-quorum"

    def _live_mask(self) -> np.ndarray:
        """(N,) bool serving mask: failed AND standby nodes are out of the
        imbalance denominator (a parked node's zero load is by design)."""
        out = self.controller.failed | self.controller.standby
        return np.array([n not in out for n in range(self.cfg.num_nodes)])

    def overload_summary(self) -> dict:
        """Host snapshot of the overload plane (empty when disabled)."""
        if self.ovl is None:
            return {}
        return OVL.summary(self.ovl)

    # -- the fleet metrics plane -------------------------------------------
    def _fold_metrics(self, e0: int, L: int, p50s, p99s, p999s, imbs
                      ) -> None:
        """Segment-boundary metrics work: fold the host-computed latency/
        imbalance columns into the ``L`` ring rows the device just wrote,
        then evaluate the SLO burn rates on device and feed the alert
        engine (one extra host sync, gated on the plane so the disabled
        path's sync count is untouched)."""
        if self.metrics is None:
            return
        with self._timers.stage("metrics"):
            vals = np.stack([
                np.asarray(p50s, np.float64).reshape(-1)[:L],
                np.asarray(p99s, np.float64).reshape(-1)[:L],
                np.asarray(p999s, np.float64).reshape(-1)[:L],
                np.asarray(imbs, np.float64).reshape(-1)[:L],
            ], axis=1)
            self.metrics = MTR.fold_host(
                self.metrics, self._met_pos, vals, self.met_layout.host_cols
            )
            self._met_pos += L
            if self.met_cfg.slos:
                res = SLOM.evaluate_segment(
                    self.metrics, self.met_layout, self.met_cfg.slos, L
                )
                self.host_syncs += 1   # the burn-rate arrays come home
                self.met_engine.observe(e0, res)

    def _on_slo_fire(self, spec, ev: dict) -> None:
        """Rising-edge hook: a burn alert is an invariant breach — dump
        the PR-7 flight ring with the SLO context in the reason."""
        if self.telemetry is not None:
            self.telemetry.breach(
                f"slo_burn:{spec.name}:epoch {ev['epoch']} "
                f"value {ev['value']:.2f} > {spec.bound} "
                f"fast {ev['fast_burn']:.2f} slow {ev['slow_burn']:.2f}"
            )

    def metrics_view(self) -> dict:
        """Chronological host view of the metrics ring (one sync)."""
        if self.metrics is None:
            raise ValueError("metrics plane disabled (metrics=None)")
        self.host_syncs += 1
        return MTR.series_view(self.metrics, self.met_layout)

    def alert_timeline(self) -> list[dict]:
        """The SLO alert timeline so far (empty when no SLOs fired)."""
        if self.met_engine is None:
            return []
        return list(self.met_engine.timeline)

    # -- the fused period loop ---------------------------------------------
    def _segment_len(self, e0: int, n: int) -> int:
        """Epochs until the next host intervention: the period boundary,
        the run end, or the next scenario control event."""
        if self.auto_period:
            next_pull = self._next_pull
        else:
            next_pull = ((e0 // self.period) + 1) * self.period
        # clamp to the scan length: a stale _next_pull (e.g. a timing
        # re-drive of an already-run auto-cadence driver) must never ask
        # for a segment longer than the compiled program
        end = min(next_pull, e0 + self.period, n)
        for e2 in range(e0 + 1, end):
            if e2 in self._event_epochs:
                return e2 - e0
        return max(end - e0, 1)

    def _scan_segment(self, e0: int, L: int):
        """Stage a segment's queries and run the donated period scan."""
        P = self.period
        with self._timers.stage("inject"):
            op_l, key_l, end_l, val_l = [], [], [], []
            for i in range(L):
                opcodes, keys, end_keys, values = self.scenario.epoch(e0 + i)
                self._note_keys(keys)
                op_l.append(opcodes)
                key_l.append(keys)
                end_l.append(end_keys)
                val_l.append(values)
            opcodes_h = np.stack(op_l)    # (L, B) host view for read masks
            for _ in range(L, P):   # pad with masked no-op epochs
                op_l.append(op_l[-1])
                key_l.append(key_l[-1])
                end_l.append(end_l[-1])
                val_l.append(val_l[-1])
            qs = C.make_queries(
                jnp.asarray(np.stack(key_l)), jnp.asarray(np.stack(op_l)),
                jnp.asarray(np.stack(val_l)), jnp.asarray(np.stack(end_l)),
            )
            rngs = jax.vmap(lambda i: jax.random.fold_in(self.key, i))(
                jnp.arange(e0, e0 + P)
            )
            live = jnp.asarray(np.arange(P) < L)
            eids = jnp.arange(e0, e0 + P, dtype=jnp.int32)
        with self._timers.stage("route_apply"):
            out = self._period_fn(
                self.store, self.directory, self.load_reg, self.sketch,
                self.repl, self.ovl, self.coord, self.metrics, qs, rngs,
                live, eids,
            )
            if self._timers.enabled:
                # profiling measures execution, not dispatch; values are
                # untouched (an explicit, wall-time-only observer effect)
                jax.block_until_ready(out)
        (self.store, self.directory, self.load_reg, self.sketch, self.repl,
         self.ovl, self.coord, self.metrics, plan, node_ops, retries, ovf,
         bounced, ostats, cstats, spans, digest, counts) = out
        return (jax.tree.map(lambda x: x[:L], plan),
                node_ops[:L], retries[:L], ovf[:L], bounced[:L], ostats[:L],
                cstats[:L],
                None if spans is None
                else jax.tree.map(lambda x: x[:L], spans),
                digest[:L], jax.tree.map(lambda x: x[:L], counts),
                opcodes_h)

    def _step_segment(self, e0: int, L: int):
        """Per-epoch dist segment (the ``fused=False`` reference loop):
        one shard_map program per epoch with all host syncs deferred to
        the period boundary — plans/metrics stay on device until then.
        The fused dist driver runs the same period through
        :meth:`_scan_segment` instead (scan inside the shard_map)."""
        (plans, nops_l, rtr_l, ovf_l, bnc_l, ost_l, cst_l, spn_l, dig_l,
         cnt_l, op_l) = ([], [], [], [], [], [], [], [], [], [], [])
        with self._timers.stage("route_apply"):
            for i in range(L):
                opcodes, keys, end_keys, values = self.scenario.epoch(e0 + i)
                self._note_keys(keys)
                op_l.append(opcodes)
                q = C.make_queries(
                    jnp.asarray(keys), jnp.asarray(opcodes),
                    jnp.asarray(values), jnp.asarray(end_keys),
                )
                rng = jax.random.fold_in(self.key, e0 + i)
                (self.store, self.directory, self.load_reg, self.sketch,
                 self.repl, self.ovl, self.coord, self.metrics, plan,
                 node_ops, retries, bounced, ostats, cstats, spans, digest,
                 counts) = self._step(
                    self.store, self.directory, self.load_reg, self.sketch,
                    self.repl, self.ovl, self.coord, self.metrics, q, rng,
                    jnp.int32(e0 + i)
                )
                plans.append(plan)
                nops_l.append(node_ops)
                rtr_l.append(retries)
                ovf_l.append(jnp.sum(self.store.overflow))
                bnc_l.append(bounced)
                ost_l.append(ostats)
                cst_l.append(cstats)
                spn_l.append(spans)
                dig_l.append(digest)
                cnt_l.append(counts)
        stack = lambda xs: jax.tree.map(lambda *x: jnp.stack(x), *xs)
        spans = None if spn_l[0] is None else stack(spn_l)
        return (stack(plans), jnp.stack(nops_l), jnp.stack(rtr_l),
                jnp.stack(ovf_l), jnp.stack(bnc_l), jnp.stack(ost_l),
                jnp.stack(cst_l), spans, jnp.stack(dig_l), stack(cnt_l),
                np.stack(op_l))

    def _run_segment(self, e0: int, n: int) -> list[EpochMetrics]:
        ev0, en0, by0 = self._handle_events(e0)
        L = self._segment_len(e0, n)
        segment = (self._scan_segment if self._period_fn is not None
                   else self._step_segment)
        (plan, node_ops, retries, ovf, bounced, ostats, cstats, spans,
         digest, counts, opcodes_h) = segment(e0, L)

        cfg = self.cfg
        scfg = self.scenario.cfg
        # ---- ONE host round-trip for the whole segment ----
        self.host_syncs += 1   # the DES engine pulls the stacked plans
        issue = hops = None
        with self._timers.stage("des"):
            if self.telemetry is not None:
                latency, makespan, issue, hops = C.simulate_closed_loop(
                    plan,
                    n_clients=cfg.n_clients,
                    num_nodes=cfg.num_nodes,
                    link=cfg.latency.link,
                    backend=cfg.des_backend,
                    return_issue=True,
                    return_hops=True,
                )
            else:
                latency, makespan = C.simulate_closed_loop(
                    plan,
                    n_clients=cfg.n_clients,
                    num_nodes=cfg.num_nodes,
                    link=cfg.latency.link,
                    backend=cfg.des_backend,
                )
        with self._timers.stage("host_sync"):
            lat = np.asarray(latency)
            mks = np.asarray(makespan)
            node_ops_h = self._sync(node_ops)
            retries_h = self._sync(retries)
            ovf_h, digest_h = self._pull_apply(ovf, digest, counts)

        p50s, p99s = latency_percentiles_batch(lat)
        p999s = p999_batch(lat)
        is_read = (opcodes_h == K.OP_GET) | (opcodes_h == K.OP_SCAN)
        if self.mode_plan.dirty_reads:
            bounced_h = self._sync(bounced).astype(bool)
        else:
            bounced_h = np.zeros_like(is_read)
        read_p99s = masked_p99_batch(lat, is_read)
        clean_p99s = masked_p99_batch(lat, is_read & ~bounced_h)
        dirty_counts = bounced_h.sum(axis=1)
        live = self._live_mask()
        imbs, covs = imbalance_stats_batch(node_ops_h, live)
        drops = np.diff(ovf_h, prepend=np.int64(self._last_overflow))
        self._last_overflow = int(ovf_h[-1])
        if self.ovl is not None:
            ost_h = self._sync(ostats).astype(np.int64)        # (L, 7)
        else:
            ost_h = np.zeros((L, len(OVL.STAT_FIELDS)), np.int64)
        if self.coord is not None:
            cst_h = self._sync(cstats).astype(np.int64)        # (L, 5)
            seg_routed = int(cst_h[:, 0].sum())
            if seg_routed > 0:
                # the redirect-backoff signal the NEXT pull's policy
                # consult reads — update before the pull below
                self._last_redirect_share = (
                    float(cst_h[:, 2].sum()) / seg_routed
                )
        else:
            cst_h = np.zeros((L, len(CT.CSTAT_FIELDS)), np.int64)

        pulled = ((e0 + L) == self._next_pull if self.auto_period
                  else (e0 + L) % self.period == 0)
        pev: list[str] = []
        pen = pby = 0
        if pulled:
            with self._timers.stage("control"):
                pev, pen, pby = self._control_pull(e0 + L)

        rows = []
        for i in range(L):
            mk = float(mks[i])
            events: list[str] = []
            mig_entries = mig_bytes = 0
            if i == 0:
                events.extend(ev0)
                mig_entries += en0
                mig_bytes += by0
            if i == L - 1 and pulled:
                events.extend(pev)
                mig_entries += pen
                mig_bytes += pby
            rows.append(EpochMetrics(
                epoch=e0 + i,
                scenario=self.scenario.name,
                policy=self.policy.name,
                ops=scfg.epoch_ops,
                throughput=scfg.epoch_ops / mk if mk > 0 else 0.0,
                p50=float(p50s[i]),
                p99=float(p99s[i]),
                makespan=mk,
                imbalance=float(imbs[i]),
                cov=float(covs[i]),
                migration_entries=mig_entries,
                migration_bytes=mig_bytes,
                drops=int(drops[i]),
                retries=int(retries_h[i]),
                compiled_steps=self.traces,
                events=events,
                p999=float(p999s[i]),
                read_p99=float(read_p99s[i]),
                clean_read_p99=float(clean_p99s[i]),
                dirty_reads=int(dirty_counts[i]),
                replication=cfg.replication_mode,
                deferred=int(ost_h[i, 2]),
                shed=int(ost_h[i, 3]),
                requeued=int(ost_h[i, 4]),
                lost=int(ost_h[i, 5]),
                queue_peak=int(ost_h[i, 6]),
                routed=int(cst_h[i, 0]),
                direct=int(cst_h[i, 1]),
                redirected=int(cst_h[i, 2]),
                mis_served=int(cst_h[i, 3]),
                stale_switches=int(cst_h[i, 4]),
                coordination=self._coord_label(),
                get_digest=int(digest_h[i]),
            ))
        if self.telemetry is not None:
            with self._timers.stage("telemetry"):
                si, sf, cnt = spans
                self.host_syncs += 1   # span tables + state snapshot pull
                self.telemetry.on_segment(
                    e0, rows, np.asarray(si), np.asarray(sf),
                    np.asarray(cnt), lat, issue, mks,
                    self._state_snapshot(), hops=hops,
                )
        # after on_segment: a burn alert firing in this segment dumps a
        # flight ring that already holds the segment's entries
        self._fold_metrics(e0, L, p50s, p99s, p999s, imbs)
        return rows

    def run(self) -> list[EpochMetrics]:
        tcfg = self.tel_cfg
        if tcfg is not None and tcfg.jax_trace_dir:
            # capture the whole run in a jax.profiler trace (TensorBoard/
            # Perfetto-loadable) alongside the span-plane artifacts
            with jax.profiler.trace(tcfg.jax_trace_dir):
                return self._run_all()
        return self._run_all()

    def _run_all(self) -> list[EpochMetrics]:
        n = self.scenario.cfg.n_epochs
        if not self.fused:
            return [self.run_epoch(e) for e in range(n)]
        rows: list[EpochMetrics] = []
        e = 0
        while e < n:
            rows.extend(self._run_segment(e, n))
            e = rows[-1].epoch + 1
        return rows
