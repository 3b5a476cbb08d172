"""Per-phase wall-clock accounting for the analysis pipeline.

Round-2 verdict: "no counter splits host wall time into
step/fork/solve, so the states/sec can't be diagnosed — instrument
before optimizing." Originally this singleton kept its own defaultdict
accumulators; since the unified telemetry layer (PR 7) the BACKING
STORE is the process-wide metrics registry — one histogram
``mtpu_phase_wall_seconds{phase=...}`` per phase, scraped at /metrics
— and this class is a *delta view* over it: `reset()` takes a marker,
`wall`/`count`/`as_dict()` report what accumulated since. The -v4 log
lines and the per-contract result fields keep their exact shape; the
duplicate accumulation path is gone.

Phases and their relations:
  step         execute_state: one instruction on one path state
  feasibility  the post-step constraint filter (includes its solves)
  solve        every get_model call, wherever it came from
  concretize   get_transaction_sequence witness minimization
  prepass      the device symbolic exploration wall

"solve" is not a disjoint slice — it happens inside "feasibility" and
"concretize" — so the lines answer "where does the wall go" and "what
do solver calls cost" separately rather than summing to the total.
"""

from __future__ import annotations

import time
from typing import Dict, Tuple

from mythril_tpu.observe.registry import registry
from mythril_tpu.support.support_utils import Singleton

_METRIC_NAME = "mtpu_phase_wall_seconds"


class _Measure:
    """One timed block: a `perf_counter` pair around the body, observed
    into the phase's series at exit (exceptions included). A plain
    class rather than a generator context manager: LASER enters two of
    these per instruction step."""

    __slots__ = ("_child", "_t0")

    def __init__(self, child) -> None:
        self._child = child

    def __enter__(self) -> "_Measure":
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self._child.observe(time.perf_counter() - self._t0)


class PhaseProfile(object, metaclass=Singleton):
    """Delta view over the registry's per-phase wall histograms.

    Thread-safe now (the registry lock guards every update) — but the
    reset/report cycle is still scoped like every other engine
    singleton: one analysis per process at a time."""

    def __init__(self) -> None:
        self._backing_reg = None
        self._backing_hist = None
        #: phase -> the histogram's series handle, resolved once per
        #: backing registry (cleared by `_rebind`)
        self._children: Dict = {}
        self._marker: Dict[str, Tuple[float, int]] = {}
        self.reset()

    @property
    def _hist(self):
        """The backing registry histogram, re-resolved when the
        registry instance changes (reset_registry in tests) — this
        singleton outlives any one registry."""
        reg = registry()
        if self._backing_reg is not reg:
            self._rebind(reg)
        return self._backing_hist

    def _rebind(self, reg) -> None:
        self._backing_reg = reg
        self._backing_hist = reg.histogram(
            _METRIC_NAME,
            "host analysis wall seconds per pipeline phase",
        )
        self._children = {}

    # -- the backing totals (process-cumulative) -----------------------
    def _totals(self) -> Dict[str, Tuple[float, int]]:
        out: Dict[str, Tuple[float, int]] = {}
        with self._hist._lock:
            for key, row in self._hist._series.items():
                phase = dict(key).get("phase", "?")
                out[phase] = (row[1], row[2])
        return out

    def reset(self) -> None:
        """Start a fresh per-contract window: the registry keeps its
        cumulative series (the /metrics view), this view reports only
        what lands after the marker."""
        self._marker = self._totals()

    # -- the per-window views (shape-compatible with the original) ----
    @property
    def wall(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for phase, (total, _count) in self._totals().items():
            base = self._marker.get(phase, (0.0, 0))[0]
            delta = total - base
            if delta > 1e-12:
                out[phase] = delta
        return out

    @property
    def count(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for phase, (_total, total_n) in self._totals().items():
            base = self._marker.get(phase, (0.0, 0))[1]
            if total_n - base > 0:
                out[phase] = total_n - base
        return out

    def measure(self, phase: str) -> _Measure:
        """Time the `with` body into the phase's series. The series
        handle is resolved once per phase and backing registry."""
        reg = registry()  # `_hist`'s check inlined: LASER's per-step path
        if self._backing_reg is not reg:
            self._rebind(reg)
        child = self._children.get(phase)
        if child is None:
            child = self._children[phase] = self._backing_hist.labels(
                phase=phase
            )
        return _Measure(child)

    def add(self, phase: str, seconds: float, n: int = 1) -> None:
        self._hist.labels(phase=phase).add_raw(seconds, n)

    def as_dict(self) -> Dict[str, Dict[str, float]]:
        wall, count = self.wall, self.count
        return {
            phase: {
                "wall_s": round(wall.get(phase, 0.0), 3),
                "count": count.get(phase, 0),
            }
            for phase in sorted(set(wall) | set(count))
        }

    def __str__(self) -> str:
        wall, count = self.wall, self.count
        if not wall and not count:
            return "(no phases recorded)"
        lines = ["%-12s %10s %10s %12s" % ("phase", "wall s", "count", "avg ms")]
        for phase in sorted(wall, key=wall.get, reverse=True):
            n = max(1, count.get(phase, 0))
            lines.append(
                "%-12s %10.3f %10d %12.2f"
                % (phase, wall[phase], count.get(phase, 0),
                   1000.0 * wall[phase] / n)
            )
        return "\n".join(lines)
