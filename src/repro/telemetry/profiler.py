"""Pipeline profiler: host stage timers and counters, and the map from
the period program's device operations to its named stages.

* :class:`StageTimers` — cheap wall-clock accumulators the epoch driver
  wraps around its pipeline stages (inject / route+apply device step /
  DES / host-sync / control / telemetry), plus integer counters of the
  work the device step did.  Disabled they are a no-op context;
  enabled they also block on the device step's output so the timer
  measures execution, not dispatch (an explicit observer effect —
  values are unchanged, only wall time is).  With ``annotate=True``
  each stage is also a ``jax.profiler.TraceAnnotation`` named
  ``stage:<name>``, so a profile carries the host stages on the device
  trace's clock.
* :func:`hlo_op_scopes` — read a compiled program's text and name the
  ``jax.named_scope`` stage (``route``, ``apply/merge``, ...) each of its
  operations ran under; :func:`scope_seconds` sums a profile's per-op
  device time by stage.  ``EpochDriver.period_op_scopes`` gives the map
  for the period program.
"""

from __future__ import annotations

import contextlib
import dataclasses
import re
import time

STAGE_PREFIX = "stage:"


class StageTimers:
    """Named wall-clock accumulators and counters for the epoch
    pipeline."""

    def __init__(self, enabled: bool = True, annotate: bool = False):
        self.enabled = enabled
        self.annotate = annotate
        self.totals: dict[str, float] = {}
        self.calls: dict[str, int] = {}
        self.counts: dict[str, int] = {}

    @contextlib.contextmanager
    def stage(self, name: str):
        if not self.enabled:
            yield
            return
        with contextlib.ExitStack() as stack:
            if self.annotate:
                import jax

                stack.enter_context(
                    jax.profiler.TraceAnnotation(STAGE_PREFIX + name))
            t0 = time.perf_counter()
            try:
                yield
            finally:
                dt = time.perf_counter() - t0
                self.totals[name] = self.totals.get(name, 0.0) + dt
                self.calls[name] = self.calls.get(name, 0) + 1

    def count(self, name: str, n: int) -> None:
        """Add ``n`` to the counter ``name`` (nothing when disabled)."""
        if self.enabled:
            self.counts[name] = self.counts.get(name, 0) + int(n)

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
            "counts": dict(self.counts),
        }


# ---------------------------------------------------------------------------
# device operations -> named stages
# ---------------------------------------------------------------------------

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_FUSION = re.compile(r"\sfusion\(")


@dataclasses.dataclass
class OpScopes:
    """The stages of one compiled program's operations.

    ``scope``: HLO instruction name (as a profile's op lines name it, e.g.
    ``fusion.152``) -> scope path: the top-level stage, then the
    second-level scope inside it, if any (``"apply/merge"``, ``"route"``);
    ``""`` for an operation under no stage.  A fusion takes the scope of
    its own metadata, which XLA takes from the fusion's root.
    ``fused``: fusion name -> the top-level stages among its fused
    instructions; more than one means the fusion's time spans stages.
    """

    scope: dict[str, str]
    fused: dict[str, frozenset[str]]


def scope_path(op_name: str, top: tuple[str, ...],
               sub: tuple[str, ...]) -> str:
    """The stage an ``op_name`` metadata string names: its first
    component in ``top``, then the first component in ``sub`` after it.
    The last component is the operation's own name, never a scope."""
    parts = op_name.split("/")[:-1]
    for i, p in enumerate(parts):
        if p in top:
            inner = next((q for q in parts[i + 1:] if q in sub), None)
            return p if inner is None else f"{p}/{inner}"
    return ""


def hlo_op_scopes(hlo_text: str, top: tuple[str, ...],
                  sub: tuple[str, ...]) -> OpScopes:
    """:class:`OpScopes` of a compiled program's text
    (``Compiled.as_text()``), from each instruction's ``op_name``
    metadata.  ``top`` and ``sub`` are the program's top-level and
    second-level scope names."""
    comps: dict[str, list[tuple[str, str, str | None, bool]]] = {}
    cur: list | None = None
    for line in hlo_text.splitlines():
        if not line[:1].isspace() and line.rstrip().endswith("{"):
            m = _COMPUTATION.match(line)
            cur = comps.setdefault(m.group(1), []) if m else None
            continue
        m = _INSTRUCTION.match(line)
        if m is None or cur is None:
            continue
        op = _OP_NAME.search(line)
        calls = _CALLS.search(line)
        cur.append((m.group(1), op.group(1) if op else "",
                    calls.group(1) if calls else None,
                    bool(_FUSION.search(line))))

    def fused_tops(comp: str, seen: set) -> set[str]:
        out: set[str] = set()
        if comp in seen:
            return out
        seen.add(comp)
        for _, op, calls, _ in comps.get(comp, ()):
            t = scope_path(op, top, sub).split("/")[0]
            if t:
                out.add(t)
            if calls:
                out |= fused_tops(calls, seen)
        return out

    # instruction names are unique within a module: the ops a profile
    # names are among them
    scope: dict[str, str] = {}
    fused: dict[str, frozenset[str]] = {}
    for ins in comps.values():
        for name, op, calls, is_fusion in ins:
            scope[name] = scope_path(op, top, sub)
            if is_fusion and calls:
                fused[name] = frozenset(fused_tops(calls, set()))
    return OpScopes(scope=scope, fused=fused)


def scope_seconds(op_s: dict[str, float], scopes: OpScopes, module: str,
                  depth: int = 1) -> dict[str, float]:
    """Sum a profile's per-operation seconds (``{"<module>:<instruction>":
    s}``, as ``bench/trace_reduce.py`` reduces a device plane) over the
    operations of ``module``, by their scope path cut to ``depth``
    components.  Key ``""`` holds the operations under no scope; the
    values add up to the module's operation time."""
    out: dict[str, float] = {}
    prefix = module + ":"
    for key, s in op_s.items():
        if not key.startswith(prefix):
            continue
        path = scopes.scope.get(key[len(prefix):], "")
        path = "/".join(path.split("/")[:depth]) if path else ""
        out[path] = out.get(path, 0.0) + s
    return out
