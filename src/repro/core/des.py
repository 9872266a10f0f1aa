"""Vectorized discrete-event coordination engine (paper §8 timing model).

Drop-in replacement for the host-side Python ``heapq`` simulator kept in
:mod:`repro.core.coordination` as ``simulate_reference`` /
``simulate_closed_loop_reference``.  Same semantics, same signatures, same
bits — orders of magnitude faster, and able to sweep many scenarios
(coordination modes × workload configs) in one call.

Design
------
The per-node-FIFO queueing network serializes through a single event
order: events are processed by the unique key ``(time, qid)``, and each
service hop reads/writes one node's ``free`` time.  That dependency chain
cannot be data-parallelized per event without changing semantics, so the
engine instead

* **compacts** hop plans up front (argsort-based calendar build: NO_HOP
  slots squeezed out, per-query live hop counts, initial event calendar),
* **fuses scenarios**: plans stacked along a leading ``S`` axis are
  simulated in one engine call (``benchmarks/paper_tables.py`` runs its
  whole mode × workload sweep in a single pass).  This is also the
  **period-batched entry point** of the ``repro.cluster`` fused epoch
  driver: its donated ``lax.scan`` returns the control period's hop
  plans as one stacked (P, B, H) device array, and a single
  :func:`simulate_closed_loop` call times every epoch of the period —
  one plan transfer and one engine pass per controller pull, per-epoch
  results bit-identical to P separate calls (each scenario row carries
  its own queue/clock state),
* **folds finish events** into the last service hop (they carry no side
  effects beyond scheduling the successor, so times are unchanged), and
* runs the event loop itself in one of two exact backends:

  - ``native``: a ~100-line C core (``des_core.c``) compiled on first use
    with the system ``cc`` and driven via :mod:`ctypes` — no Python-level
    per-event work at all;
  - ``jax``: an XLA ``while_loop`` over the same event recurrence (used
    when no C compiler exists).  It always runs on the host CPU device,
    also where the default device is an accelerator: the loop is f64
    and strictly sequential.

Exactness contract
------------------
Both backends pop events in the identical ``(time, qid)`` order as the
reference heap (keys are unique: one pending event per query) and perform
the identical float64 ``max``/``add`` sequence, so latency and makespan
match the reference **bit for bit** — asserted for randomized plans in
``tests/test_des.py``.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
from jax import enable_x64

from repro.core import _des_native
from repro.core.coordination import NO_HOP, HopPlan

__all__ = [
    "simulate",
    "simulate_closed_loop",
    "stack_plans",
    "compact_plans",
    "available_backends",
    "backend_info",
]


# ---------------------------------------------------------------------------
# plan preparation: stacking + argsort-based calendar compaction
# ---------------------------------------------------------------------------


def stack_plans(plans: list[HopPlan]) -> HopPlan:
    """Stack per-scenario (B, H) hop plans into one (S, B, H) plan.

    Hop axes are right-padded with NO_HOP/0 to the widest plan so that
    e.g. server-driven plans (one extra coordinator hop) can be fused with
    in-switch ones.  All plans must share the batch size B.
    """
    if not plans:
        raise ValueError("stack_plans needs at least one plan")
    nodes = [np.asarray(p.nodes) for p in plans]
    service = [np.asarray(p.service) for p in plans]
    B = nodes[0].shape[0]
    if any(n.ndim != 2 or n.shape[0] != B for n in nodes):
        raise ValueError("all plans must be (B, H) with a common B")
    H = max(n.shape[1] for n in nodes)
    S = len(plans)
    nodes_s = np.full((S, B, H), NO_HOP, np.int32)
    service_s = np.zeros((S, B, H), np.float32)
    reply_s = np.zeros((S, B), np.float32)
    for i, (n, sv) in enumerate(zip(nodes, service)):
        nodes_s[i, :, : n.shape[1]] = n
        service_s[i, :, : sv.shape[1]] = sv
        reply_s[i] = np.asarray(plans[i].reply_links)
    return HopPlan(
        nodes=jnp.asarray(nodes_s),
        service=jnp.asarray(service_s),
        reply_links=jnp.asarray(reply_s),
    )


def compact_plans(
    plan: HopPlan, return_order: bool = False
) -> tuple[np.ndarray, ...]:
    """(S, B, H) plan -> (nodes, service, n_hops) with live hops first.

    The reference simulator skips NO_HOP slots at pop time with no cost,
    so squeezing them out (stable argsort on the dead mask — live hops
    keep their order) is semantics-preserving: exactly one link separates
    consecutive live hops either way.  ``return_order`` additionally
    returns the compaction permutation (``nodes_c[..., j] ==
    nodes[..., order[..., j]]``) so per-hop engine outputs can be
    scattered back to the original hop positions.
    """
    nodes = np.asarray(plan.nodes)
    service = np.asarray(plan.service, np.float32)
    squeeze = nodes.ndim == 2
    if squeeze:
        nodes, service = nodes[None], service[None]
    dead = nodes == NO_HOP
    order = np.argsort(dead, axis=-1, kind="stable")
    nodes_c = np.take_along_axis(nodes, order, axis=-1).astype(np.int32)
    service_c = np.take_along_axis(service, order, axis=-1)
    service_c = np.where(nodes_c == NO_HOP, np.float32(0.0), service_c)
    n_hops = (~dead).sum(-1).astype(np.int32)
    if return_order:
        return nodes_c, service_c, n_hops, order
    return nodes_c, service_c, n_hops


def _validate(nodes_c: np.ndarray, n_hops: np.ndarray, num_nodes: int) -> None:
    live = np.arange(nodes_c.shape[-1])[None, None, :] < n_hops[..., None]
    bad = live & ((nodes_c < 0) | (nodes_c >= num_nodes))
    if bad.any():
        raise ValueError(
            f"hop plan references nodes outside [0, {num_nodes}); "
            "pass the num_nodes the plan was built for"
        )


# ---------------------------------------------------------------------------
# backend selection
# ---------------------------------------------------------------------------


def available_backends() -> tuple[str, ...]:
    return ("native", "jax") if _des_native.available() else ("jax",)


def _resolve_backend(backend: str | None) -> str:
    if backend in (None, "auto"):
        env = os.environ.get("REPRO_DES_BACKEND", "auto").lower()
        if env in ("native", "jax"):
            backend = env
        elif env in ("", "auto"):
            backend = "native" if _des_native.available() else "jax"
        else:
            raise ValueError(
                f"REPRO_DES_BACKEND={env!r} not recognized; "
                "use 'native', 'jax', or 'auto'"
            )
    if backend == "native" and not _des_native.available():
        raise RuntimeError(
            "native DES backend requested but no C compiler is available; "
            "use backend='jax'"
        )
    if backend not in ("native", "jax"):
        raise ValueError(f"unknown DES backend {backend!r}")
    return backend


def _jax_device():
    return jax.devices("cpu")[0]


def backend_info(backend: str | None = None) -> tuple[str, str]:
    """``(backend, where it runs)`` for a backend selection, e.g.
    ``("native", "host C core")`` or ``("jax", "TFRT_CPU_0")``."""
    name = _resolve_backend(backend)
    if name == "native":
        _des_native.load()
        return name, "host C core"
    return name, str(_jax_device())


# ---------------------------------------------------------------------------
# native backend (ctypes -> des_core.c)
# ---------------------------------------------------------------------------


def _run_native(nodes_c, service_c, n_hops, arrivals, *, K, N, link, think,
                closed, want_hops=False):
    import ctypes

    lib = _des_native.load()
    S, B, H = nodes_c.shape
    nodes = np.ascontiguousarray(nodes_c, np.int32)
    service = np.ascontiguousarray(service_c, np.float32)
    nh = np.ascontiguousarray(n_hops, np.int32)
    arr = None
    if not closed:
        arr = np.ascontiguousarray(np.broadcast_to(arrivals, (S, B)), np.float64)
    finish = np.zeros((S, B), np.float64)
    issue = np.zeros((S, B), np.float64)
    hops = np.zeros((S, B, H), np.float64) if want_hops else None
    scratch_nf = np.zeros((N,), np.float64)
    scratch_hop = np.zeros((max(B, 1),), np.int32)
    scratch_heap = np.zeros((B + 1, 2), np.float64)
    p = lambda a: a.ctypes.data_as(ctypes.c_void_p)
    lib.des_simulate_batch(
        p(nodes), p(service), p(nh),
        None if arr is None else p(arr),
        S, B, H, int(K), int(N),
        float(link), float(think), 1 if closed else 0,
        p(scratch_nf), p(scratch_hop), p(scratch_heap), p(finish), p(issue),
        None if hops is None else p(hops),
    )
    return finish, issue, hops


# ---------------------------------------------------------------------------
# jax backend (XLA while_loop over the identical event recurrence)
# ---------------------------------------------------------------------------


@jax.jit
def _jax_open_one(nodes_c, service_c, n_hops, ev_time0, node_free0, link):
    B, H = nodes_c.shape

    def cond(st):
        return jnp.any(jnp.isfinite(st[0]))

    def body(st):
        ev_time, ev_hop, node_free, finish, hops = st
        q = jnp.argmin(ev_time)  # unique (time, qid): first-min == min qid
        t = ev_time[q]
        alive = jnp.isfinite(t)
        h = ev_hop[q]
        nh = n_hops[q]
        zero_hop = nh == 0
        hs = jnp.minimum(h, H - 1)
        n = nodes_c[q, hs]
        s = service_c[q, hs]
        sn = jnp.maximum(n, 0)
        nf = node_free[sn]
        start = jnp.maximum(t, nf)
        done = start + s
        serve = alive & ~zero_hop
        node_free = node_free.at[sn].set(jnp.where(serve, done, nf))
        hops = hops.at[q, hs].set(jnp.where(serve, done, hops[q, hs]))
        last = zero_hop | (h + 1 >= nh)
        fin_t = jnp.where(zero_hop, t, done + link)
        finish = finish.at[q].set(jnp.where(alive & last, fin_t, finish[q]))
        nxt = jnp.where(last, jnp.inf, done + link)
        ev_time = ev_time.at[q].set(jnp.where(alive, nxt, t))
        ev_hop = ev_hop.at[q].set(jnp.where(alive, h + 1, h))
        return ev_time, ev_hop, node_free, finish, hops

    state = (
        ev_time0,
        jnp.zeros((B,), jnp.int32),
        node_free0,
        jnp.zeros((B,), jnp.float64),
        jnp.zeros((B, H), jnp.float64),
    )
    st = jax.lax.while_loop(cond, body, state)
    return st[3], st[4]


@jax.jit
def _jax_closed_one(nodes_c, service_c, n_hops, ev_time0, cur_op0, node_free0,
                    K, link, think):
    B, H = nodes_c.shape
    KL = ev_time0.shape[0]
    INT_BIG = jnp.int32(2**31 - 1)

    def cond(st):
        return jnp.any(jnp.isfinite(st[0]))

    def body(st):
        ev_time, ev_hop, cur_op, node_free, finish, issue, hops = st
        t = jnp.min(ev_time)
        alive = jnp.isfinite(t)
        cand = ev_time == t
        lane = jnp.argmin(jnp.where(cand, cur_op, INT_BIG))
        q = cur_op[lane]
        h = ev_hop[lane]
        nh = n_hops[q]
        zero_hop = nh == 0
        hs = jnp.minimum(h, H - 1)
        n = nodes_c[q, hs]
        s = service_c[q, hs]
        sn = jnp.maximum(n, 0)
        nf = node_free[sn]
        start = jnp.maximum(t, nf)
        done = start + s
        serve = alive & ~zero_hop
        node_free = node_free.at[sn].set(jnp.where(serve, done, nf))
        hops = hops.at[q, hs].set(jnp.where(serve, done, hops[q, hs]))
        last = zero_hop | (h + 1 >= nh)
        fin_t = jnp.where(zero_hop, t, done + link)
        fin_now = alive & last
        finish = finish.at[q].set(jnp.where(fin_now, fin_t, finish[q]))
        nq = q + K
        snq = jnp.minimum(nq, B - 1)
        has_next = fin_now & (nq < B)
        issue = issue.at[snq].set(jnp.where(has_next, fin_t + think, issue[snq]))
        new_time = jnp.where(
            last, jnp.where(nq < B, fin_t + think + link, jnp.inf), done + link
        )
        ev_time = ev_time.at[lane].set(jnp.where(alive, new_time, t))
        ev_hop = ev_hop.at[lane].set(
            jnp.where(alive, jnp.where(last, 0, h + 1), h)
        )
        cur_op = cur_op.at[lane].set(jnp.where(alive, jnp.where(last, snq, q), q))
        return ev_time, ev_hop, cur_op, node_free, finish, issue, hops

    state = (
        ev_time0,
        jnp.zeros((KL,), jnp.int32),
        cur_op0,
        node_free0,
        jnp.zeros((B,), jnp.float64),
        jnp.zeros((B,), jnp.float64),
        jnp.zeros((B, H), jnp.float64),
    )
    st = jax.lax.while_loop(cond, body, state)
    return st[4], st[5], st[6]


def _run_jax(nodes_c, service_c, n_hops, arrivals, *, K, N, link, think,
             closed, want_hops=False):
    S, B, H = nodes_c.shape
    finish = np.zeros((S, B), np.float64)
    issue = np.zeros((S, B), np.float64)
    hops = np.zeros((S, B, H), np.float64) if want_hops else None
    with jax.default_device(_jax_device()), enable_x64():
        link64 = jnp.float64(link)
        think64 = jnp.float64(think)
        for s in range(S):
            nodes_d = jnp.asarray(nodes_c[s])
            service_d = jnp.asarray(service_c[s], jnp.float64)
            nh_d = jnp.asarray(n_hops[s])
            node_free0 = jnp.zeros((N,), jnp.float64)
            if closed:
                KK = min(K, B)
                lanes = np.arange(max(KK, 1), dtype=np.int32)
                ev0 = jnp.asarray(
                    np.where(lanes < KK, float(link), np.inf), jnp.float64
                )
                cur0 = jnp.asarray(np.minimum(lanes, B - 1), jnp.int32)
                f, i, hd = _jax_closed_one(
                    nodes_d, service_d, nh_d, ev0, cur0, node_free0,
                    jnp.int32(K), link64, think64,
                )
                finish[s] = np.asarray(f)
                issue[s] = np.asarray(i)
            else:
                arr64 = np.asarray(np.broadcast_to(arrivals, (S, B))[s], np.float64)
                ev0 = jnp.asarray(arr64 + float(link), jnp.float64)
                f, hd = _jax_open_one(
                    nodes_d, service_d, nh_d, ev0, node_free0, link64
                )
                finish[s] = np.asarray(f)
                issue[s] = arr64
            if hops is not None:
                hops[s] = np.asarray(hd)
    return finish, issue, hops


# ---------------------------------------------------------------------------
# public API — signatures match the reference simulator
# ---------------------------------------------------------------------------


def _finalize(finish, issue, stacked):
    latency = (finish - issue).astype(np.float32)
    if finish.shape[1] == 0:  # matches the reference's empty-batch makespan
        makespan = np.zeros((finish.shape[0],), np.float32)
    else:
        makespan = finish.max(axis=1).astype(np.float32)
    if not stacked:
        return jnp.asarray(latency[0]), jnp.asarray(makespan[0])
    return jnp.asarray(latency), jnp.asarray(makespan)


def _uncompact_hops(hops_c: np.ndarray, order: np.ndarray) -> np.ndarray:
    """Scatter compacted per-hop times back to original plan positions.

    ``hops_c[..., j]`` belongs to original hop ``order[..., j]``; dead
    slots carry 0 on both sides, so the scatter is exact.
    """
    out = np.zeros_like(hops_c)
    np.put_along_axis(out, order, hops_c, axis=-1)
    return out


def simulate(
    plan: HopPlan,
    arrivals,
    *,
    num_nodes: int,
    link: float = 1.0,
    backend: str | None = None,
    return_hops: bool = False,
):
    """Open-loop DES over a (B, H) plan — or an (S, B, H) scenario stack.

    Bit-identical to :func:`repro.core.coordination.simulate_reference`.
    For stacked plans ``arrivals`` may be (B,) (shared) or (S, B), and the
    result is (latency (S, B), makespan (S,)).

    ``return_hops=True`` additionally returns per-hop *completion* times
    as numpy float64 in the original plan's hop order (0 at dead slots) —
    exact engine timestamps, kept off-device like ``return_issue``.
    """
    stacked = np.asarray(plan.nodes).ndim == 3
    nodes_c, service_c, n_hops, order = compact_plans(plan, return_order=True)
    S, B, H = nodes_c.shape
    if B == 0:
        z = np.zeros((S, 0), np.float64)
        out = _finalize(z, z, stacked)
        if return_hops:
            zh = np.zeros((S, 0, H), np.float64)
            return (*out, zh if stacked else zh[0])
        return out
    _validate(nodes_c, n_hops, num_nodes)
    # float64 like the reference (which promotes arrivals before the loop):
    # f32 inputs convert exactly, f64 inputs keep their full precision
    arr = np.asarray(arrivals, np.float64)
    if arr.ndim == 1:
        arr = np.broadcast_to(arr[None], (S, B))
    run = _run_native if _resolve_backend(backend) == "native" else _run_jax
    finish, issue, hops = run(
        nodes_c, service_c, n_hops, arr,
        K=0, N=num_nodes, link=link, think=0.0, closed=False,
        want_hops=return_hops,
    )
    out = _finalize(finish, issue, stacked)
    if return_hops:
        hops = _uncompact_hops(hops, order)
        return (*out, hops if stacked else hops[0])
    return out


def simulate_closed_loop(
    plan: HopPlan,
    *,
    n_clients: int,
    num_nodes: int,
    link: float = 1.0,
    think: float = 0.0,
    backend: str | None = None,
    return_issue: bool = False,
    return_hops: bool = False,
):
    """Closed-loop DES (K clients replaying the stream back-to-back).

    Bit-identical to
    :func:`repro.core.coordination.simulate_closed_loop_reference`; accepts
    an (S, B, H) scenario stack like :func:`simulate`.

    With ``return_issue=True`` a third value is returned: the per-query
    issue times as **numpy float64** (the engine's exact internal clock —
    kept off-device because a jnp round-trip would downcast to f32).  The
    telemetry plane anchors span trees on it; latency/makespan are
    unchanged either way.

    With ``return_hops=True`` the last value returned is the per-hop
    completion-time array (numpy float64, original plan hop order, 0 at
    dead slots) — the exact interior timestamps the Chrome-trace exporter
    draws child slices from (the engine always computed them; this stops
    discarding them).
    """
    stacked = np.asarray(plan.nodes).ndim == 3
    nodes_c, service_c, n_hops, order = compact_plans(plan, return_order=True)
    S, B, H = nodes_c.shape
    if B == 0 or n_clients <= 0:
        z = np.zeros((S, B), np.float64)
        out = _finalize(z, z, stacked)
        if return_issue:
            out = (*out, z if stacked else z[0])
        if return_hops:
            zh = np.zeros((S, B, H), np.float64)
            out = (*out, zh if stacked else zh[0])
        return out
    _validate(nodes_c, n_hops, num_nodes)
    run = _run_native if _resolve_backend(backend) == "native" else _run_jax
    finish, issue, hops = run(
        nodes_c, service_c, n_hops, None,
        K=n_clients, N=num_nodes, link=link, think=think, closed=True,
        want_hops=return_hops,
    )
    out = _finalize(finish, issue, stacked)
    if return_issue:
        out = (*out, issue if stacked else issue[0])
    if return_hops:
        hops = _uncompact_hops(hops, order)
        out = (*out, hops if stacked else hops[0])
    return out
