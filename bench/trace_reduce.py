"""Reduce a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metric readers take.

What it reads, for each device plane (``/device:TPU:<n>``):

* the ``XLA Modules`` line: each compiled program's executions, summed
  per module name (the name without its ``(id)`` suffix).
* the ``XLA Ops`` line: every operation's interval, named by its HLO
  instruction (``%fusion.3 = ...`` gives ``fusion.3``) and by the module
  it ran in (``jit_period:fusion.3``).  Busy time is the union of these
  intervals inside the traced window.  Control-flow ops enclose their
  bodies' ops on the same line, so per-op totals are self times: they
  give the breakdown.  The collective time sums the instructions named
  ``all-to-all*`` or ``all-gather*``.

The idle gaps are the stretches of the window in which no device ran an
operation.

From the host planes it reads the benchmark's annotations: the traced
window (one event named :data:`WINDOW`) and the pipeline stages
(events named ``stage:<name>``), which label each idle gap with what the
host was doing in it.  Host and device events share the profiler's
clock.  Only intervals inside the window count.
"""

from __future__ import annotations

import bisect
import dataclasses
import re
from pathlib import Path

WINDOW = "bench:traced_window"
STAGE_PREFIX = "stage:"
_DEVICE = re.compile(r"^/device:[A-Z]+:\d+$")
_COLLECTIVE = re.compile(r"^(all-to-all|all-gather)")
_SUFFIX = re.compile(r"\(\d+\)$")
_INSTR = re.compile(r"^%?([\w.\-]+)")


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    devices: list[str]
    busy_s: dict[str, float]
    module_s: dict[str, dict[str, float]]
    op_s: dict[str, dict[str, float]]
    collective_s: dict[str, float]
    # the longest gaps in which no device was busy: (host stage, seconds)
    gaps: list[tuple[str, float]]

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s.values()) / len(self.busy_s)

    def modules_matching(self, pattern: str) -> dict[str, float]:
        """Seconds per device in the modules whose name matches."""
        rx = re.compile(pattern)
        return {d: sum(s for m, s in mods.items() if rx.search(m))
                for d, mods in self.module_s.items()}

    def top_ops(self, n: int = 10) -> list[list]:
        """The ``n`` operations with the most device time, summed over
        devices: ``[[name, seconds], ...]``."""
        tot: dict[str, float] = {}
        for ops in self.op_s.values():
            for name, s in ops.items():
                tot[name] = tot.get(name, 0.0) + s
        return [[k, v] for k, v in sorted(tot.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def top_gaps(self) -> list[list]:
        """The longest gaps in which no device ran an operation, each
        labelled with the host stage it fell in: ``[[label, seconds]]``."""
        return [[lab, sec] for lab, sec in self.gaps]


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(a: int, b: int, lo: int, hi: int):
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def _events(line):
    for ev in line.events:
        start = int(ev.start_ns)
        yield ev.name, start, start + int(ev.duration_ns)


def _self_times(line, lo: int, hi: int):
    """``(instruction, start, end, self ns)`` of each op on an ``XLA Ops``
    line, clipped to ``[lo, hi]``.  Control-flow ops (``while``,
    ``conditional``) enclose the ops of their bodies on the same line;
    an op's self time leaves out what its children cover."""
    if line is None:
        return []
    evs = []
    for name, a, b in _events(line):
        c = _clip(a, b, lo, hi)
        if c:
            m = _INSTR.match(name)
            evs.append([c[0], c[1], m.group(1) if m else name, 0])
    evs.sort(key=lambda e: (e[0], -e[1]))
    stack: list[list] = []
    for ev in evs:
        while stack and stack[-1][1] <= ev[0]:
            stack.pop()
        if stack:
            stack[-1][3] += ev[1] - ev[0]
        stack.append(ev)
    return [(key, a, b, (b - a) - child) for a, b, key, child in evs]


def _stage_at(stages: list[tuple[str, int, int]], t: int) -> str:
    """The innermost host stage that spans time ``t``."""
    best = None
    for name, a, b in stages:
        if a <= t < b and (best is None or b - a < best[1]):
            best = (name, b - a)
    return best[0] if best else "host outside any stage"


def reduce_trace(path: str | Path, n_gaps: int = 10) -> TraceSummary:
    """Reduce one ``.xplane.pb`` file (see the module docstring)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    window = None
    stages: list[tuple[str, int, int]] = []
    device_planes = []
    for plane in pd.planes:
        if _DEVICE.match(plane.name):
            device_planes.append(plane)
            continue
        for line in plane.lines:
            for name, a, b in _events(line):
                if name == WINDOW:
                    window = (a, b) if window is None else (
                        min(window[0], a), max(window[1], b))
                elif name.startswith(STAGE_PREFIX):
                    stages.append((name[len(STAGE_PREFIX):], a, b))
    if window is None:
        raise ValueError(f"{path}: no {WINDOW!r} annotation in the trace")
    if not device_planes:
        raise ValueError(f"{path}: no device plane in the trace")
    lo, hi = window
    busy, modules, ops, coll = {}, {}, {}, {}
    every_span: list[tuple[int, int]] = []
    for plane in device_planes:
        dev = plane.name
        lines = {line.name: line for line in plane.lines}
        mods: list[tuple[int, int, str]] = []
        per_mod: dict[str, float] = {}
        for name, a, b in (_events(lines["XLA Modules"])
                           if "XLA Modules" in lines else ()):
            base = _SUFFIX.sub("", name)
            mods.append((a, b, base))
            c = _clip(a, b, lo, hi)
            if c:
                per_mod[base] = per_mod.get(base, 0.0) + (c[1] - c[0]) * 1e-9
        mods.sort()
        starts = [m[0] for m in mods]
        spans: list[tuple[int, int]] = []
        per_op: dict[str, float] = {}
        collective = 0.0
        for key, a, b, self_ns in _self_times(
                lines["XLA Ops"] if "XLA Ops" in lines else None, lo, hi):
            spans.append((a, b))
            i = bisect.bisect_right(starts, a) - 1
            mod = mods[i][2] if i >= 0 and a < mods[i][1] else "?"
            name = f"{mod}:{key}"
            per_op[name] = per_op.get(name, 0.0) + self_ns * 1e-9
            if _COLLECTIVE.match(key):
                collective += (b - a) * 1e-9
        busy[dev] = sum(b - a for a, b in _union(spans)) * 1e-9
        every_span.extend(spans)
        modules[dev] = per_mod
        ops[dev] = per_op
        coll[dev] = collective
    idle, t = [], lo
    for a, b in _union(every_span) + [(hi, hi)]:
        if a > t:
            idle.append((t, a))
        t = max(t, b)
    idle.sort(key=lambda g: g[0] - g[1])
    gaps = [(_stage_at(stages, (a + b) // 2), (b - a) * 1e-9)
            for a, b in idle[:n_gaps]]
    return TraceSummary(
        window_s=(hi - lo) * 1e-9,
        devices=[p.name for p in device_planes],
        busy_s=busy, module_s=modules, op_s=ops, collective_s=coll,
        gaps=gaps,
    )


def find_xplane(trace_dir: str | Path) -> Path:
    """The one ``.xplane.pb`` a ``jax.profiler`` session wrote."""
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if len(found) != 1:
        raise ValueError(f"{trace_dir}: expected one .xplane.pb, found "
                         f"{len(found)}")
    return found[0]
