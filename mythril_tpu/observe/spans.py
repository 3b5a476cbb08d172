"""Structured spans + the flight recorder + Perfetto export.

`trace(name, **attrs)` opens one span: monotonic start/end timestamps,
the caller's thread, an optional device-group *track*, and a parent
link (a thread-local stack gives nesting for free), recorded into a
bounded ring buffer — the **flight recorder**. The recorder is always
cheap (a deque append under a lock, nothing per step) and bounded, so
it can run in production and be dumped on demand:

- the service serves the recent tail at ``/trace``;
- ``myth analyze --trace-out trace.json`` exports the whole run;
- a ``MESH_GROUP_DEGRADED`` or deadline degradation triggers an
  automatic dump (``observe.configure(out_dir=...)``), so the
  flight recorder answers "what was in flight when it died".

The export format is Chrome/Perfetto trace-event JSON (`"X"` complete
events with microsecond timestamps): load it at https://ui.perfetto.dev
and a pipelined multi-device run renders as an actual timeline — one
track per device group / thread, wave execution against host harvest,
bubbles and compile stalls visible as gaps.

Each `trace()` span is also a JAX profiler annotation (a TraceMe) once
the process has imported jax: under `jax.profiler.trace` it lands on
the profiler's host plane, on the same clock as the device's `XLA Ops`,
so a device idle stretch can be matched to the host span that was open
across it. This module never imports jax itself (host-only CLI paths
and pool workers do not load it), and retrospective spans (`add`) stay
ring-only: the device plane already holds the device's side.

Span taxonomy (docs/observability.md has the diagram):

    job > contract > explore.run > phase > {wave.dispatch, wave.device,
    wave.harvest, wave.consume, flip.solve.host, flip.solve.device,
    kernel.compile, mesh.chunk, mesh.steal, service.wave}
    service.host.lock_wait, service.host.walk > contract.analyze
"""

from __future__ import annotations

import itertools
import json
import os
import sys
import threading
import time
from collections import deque
from typing import Dict, List, Optional

_IDS = itertools.count(1)
_TLS = threading.local()


class Span:
    """One closed span. Timestamps are `time.perf_counter()` seconds
    (monotonic, process-local)."""

    __slots__ = ("sid", "parent", "name", "t0", "t1", "tid", "track", "attrs")

    def __init__(self, sid, parent, name, t0, t1, tid, track, attrs) -> None:
        self.sid = sid
        self.parent = parent
        self.name = name
        self.t0 = t0
        self.t1 = t1
        self.tid = tid
        self.track = track
        self.attrs = attrs

    def as_dict(self) -> Dict:
        out = {
            "sid": self.sid,
            "parent": self.parent,
            "name": self.name,
            "t0": round(self.t0, 6),
            "t1": round(self.t1, 6),
            "dur_s": round(self.t1 - self.t0, 6),
            "thread": self.tid,
        }
        if self.track is not None:
            out["track"] = self.track
        if self.attrs:
            out["attrs"] = self.attrs
        return out


class FlightRecorder:
    """Bounded ring of closed spans (newest win; the recorder is a
    flight recorder, not an archive)."""

    def __init__(self, capacity: int = 8192) -> None:
        self._mu = threading.Lock()
        self._ring: "deque[Span]" = deque(maxlen=max(16, capacity))
        self.dropped = 0
        self.recorded = 0

    def record(self, span: Span) -> None:
        with self._mu:
            if len(self._ring) == self._ring.maxlen:
                self.dropped += 1
            self._ring.append(span)
            self.recorded += 1

    def add(
        self,
        name: str,
        t0: float,
        t1: float,
        track: Optional[str] = None,
        **attrs,
    ) -> None:
        """Record a RETROSPECTIVE span from explicit timestamps — the
        idiom for device execution, whose start (dispatch) and end
        (readback-ready) are observed on the host at different call
        sites."""
        from mythril_tpu import observe

        if not observe.enabled():
            return
        self.record(
            Span(
                next(_IDS), None, name, t0, t1,
                threading.current_thread().name, track, attrs or None,
            )
        )

    def tail(self, n: int = 512) -> List[Span]:
        with self._mu:
            spans = list(self._ring)
        return spans[-n:]

    def dump(self) -> List[Dict]:
        return [span.as_dict() for span in self.tail(len(self._ring))]

    def clear(self) -> None:
        with self._mu:
            self._ring.clear()

    def __len__(self) -> int:
        with self._mu:
            return len(self._ring)


_RECORDER = FlightRecorder()


def flight_recorder() -> FlightRecorder:
    return _RECORDER


def _stack() -> List[int]:
    stack = getattr(_TLS, "stack", None)
    if stack is None:
        stack = _TLS.stack = []
    return stack


_ANNOTATION = None


def _annotation_class():
    """`jax.profiler.TraceAnnotation` once jax is imported, else None.
    Looked up, never imported: a process that has not loaded jax pays
    nothing for it."""
    global _ANNOTATION
    if _ANNOTATION is None:
        jax = sys.modules.get("jax")
        profiler = getattr(jax, "profiler", None)
        _ANNOTATION = getattr(profiler, "TraceAnnotation", None)
    return _ANNOTATION


class _TraceCtx:
    """The `trace()` context manager: pushes a span id on the thread's
    stack at entry (so children see their parent), records the closed
    span at exit, and holds the span's profiler annotation open in
    between. Exceptions propagate; the span still closes and is marked
    with the exception type."""

    __slots__ = ("name", "track", "attrs", "sid", "t0", "annotation")

    def __init__(self, name: str, track: Optional[str], attrs: Dict) -> None:
        self.name = name
        self.track = track
        self.attrs = attrs
        self.annotation = None

    def __enter__(self) -> "_TraceCtx":
        self.sid = next(_IDS)
        cls = _annotation_class()
        if cls is not None:
            self.annotation = cls(self.name, **(self.attrs or {}))
            self.annotation.__enter__()
        self.t0 = time.perf_counter()
        _stack().append(self.sid)
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        t1 = time.perf_counter()
        if self.annotation is not None:
            self.annotation.__exit__(exc_type, exc, tb)
        stack = _stack()
        if stack and stack[-1] == self.sid:
            stack.pop()
        parent = stack[-1] if stack else None
        attrs = self.attrs
        if exc_type is not None:
            attrs = dict(attrs or {}, error=exc_type.__name__)
        _RECORDER.record(
            Span(
                self.sid, parent, self.name, self.t0, t1,
                threading.current_thread().name, self.track, attrs or None,
            )
        )


class _NullCtx:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None


_NULL = _NullCtx()


def trace(name: str, track: Optional[str] = None, **attrs):
    """Open a structured span. Near-zero-cost no-op while telemetry is
    disabled (one bool check, a shared null context)."""
    from mythril_tpu import observe

    if not observe.enabled():
        return _NULL
    return _TraceCtx(name, track, attrs or None)


# ---------------------------------------------------------------------------
# Perfetto / Chrome trace-event export
# ---------------------------------------------------------------------------
def to_perfetto(spans: Optional[List[Span]] = None) -> Dict:
    """Render spans as Chrome trace-event JSON (the `traceEvents`
    array form Perfetto loads directly): one complete ("ph": "X")
    event per span with microsecond timestamps, plus thread_name
    metadata so tracks are labeled. Spans with a device-group `track`
    render on that track (device timelines beside host threads)."""
    if spans is None:
        spans = _RECORDER.tail(len(_RECORDER))
    events: List[Dict] = []
    tids: Dict[str, int] = {}

    def tid_of(label: str) -> int:
        tid = tids.get(label)
        if tid is None:
            tid = tids[label] = len(tids) + 1
        return tid

    pid = os.getpid()
    base = min((s.t0 for s in spans), default=0.0)
    for span in spans:
        label = span.track if span.track is not None else span.tid
        events.append(
            {
                "name": span.name,
                "ph": "X",
                "ts": int((span.t0 - base) * 1e6),
                "dur": max(1, int((span.t1 - span.t0) * 1e6)),
                "pid": pid,
                "tid": tid_of(label),
                "args": dict(span.attrs or {}, sid=span.sid,
                             parent=span.parent),
            }
        )
    for label, tid in tids.items():
        events.append(
            {
                "name": "thread_name",
                "ph": "M",
                "pid": pid,
                "tid": tid,
                "args": {"name": label},
            }
        )
    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {"source": "mythril_tpu.observe"},
    }


def export_trace(path: str, spans: Optional[List[Span]] = None) -> str:
    """Write the Perfetto JSON to `path` (atomic tmp+rename, the
    checkpoint writer's idiom) and return the path."""
    doc = to_perfetto(spans)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as fp:
        json.dump(doc, fp)
    os.replace(tmp, path)
    return path


def overlap_fraction(
    spans: Optional[List[Span]] = None, name: str = "wave.device"
) -> float:
    """Fraction of the covered time that >= 2 spans named `name` were
    simultaneously open — the span-derived pipelining/mesh overlap
    figure bench.py reports as `trace_overlap_frac`. 0.0 when fewer
    than two such spans exist."""
    if spans is None:
        spans = _RECORDER.tail(len(_RECORDER))
    marks = []
    for span in spans:
        if span.name == name and span.t1 > span.t0:
            marks.append((span.t0, 1))
            marks.append((span.t1, -1))
    if len(marks) < 4:
        return 0.0
    marks.sort()
    covered = overlapped = 0.0
    depth = 0
    prev = marks[0][0]
    for t, d in marks:
        if depth >= 1:
            covered += t - prev
        if depth >= 2:
            overlapped += t - prev
        depth += d
        prev = t
    return round(overlapped / covered, 4) if covered > 0 else 0.0
