"""A short profiler slice of the measured window, and its reduction to
device busy time, kernel time by executable name, the top device
operations and the longest idle gaps, each labelled by what the
program's host spans were doing across it.

The slice runs on a timer thread beside the window: it starts `start_s`
after the window opens and lasts `length_s`. The reduction reads the
`.xplane.pb` the profiler writes with `jax.profiler.ProfileData`.
"""

from __future__ import annotations

import bisect
import shutil
import threading
import time
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

#: the line of a device plane that holds one event per executed
#: operation, and the one that holds one event per executable
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


class Slice:
    """Profile `length_s` seconds starting `start_s` into the window."""

    def __init__(self, out_dir: Path, start_s: float, length_s: float) -> None:
        self.out_dir = out_dir
        self.start_s = start_s
        self.length_s = length_s
        self.t0: Optional[float] = None
        self.t1: Optional[float] = None
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self.error: Optional[BaseException] = None

    def begin(self) -> None:
        """Called when the window opens."""
        shutil.rmtree(self.out_dir, ignore_errors=True)
        self._thread = threading.Thread(
            target=self._run, name="bench-trace", daemon=True
        )
        self._thread.start()

    def _run(self) -> None:
        import jax

        try:
            if self._stop.wait(self.start_s):
                return
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            jax.profiler.start_trace(str(self.out_dir), profiler_options=options)
            self.t0 = time.perf_counter()
            self._stop.wait(self.length_s)
            self.t1 = time.perf_counter()
            jax.profiler.stop_trace()
        except BaseException as why:  # reported by the run, not raised here
            self.error = why

    def end(self) -> None:
        """Called when the window closes: a slice still open stops."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join()

    @property
    def window_s(self) -> Optional[float]:
        if self.t0 is None or self.t1 is None:
            return None
        return self.t1 - self.t0

    def xplane(self) -> Optional[Path]:
        found = sorted(self.out_dir.glob("plugins/profile/*/*.xplane.pb"))
        return found[-1] if found else None


def _union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def device_lines(planes) -> List[Dict]:
    """Per device plane: its ops and modules events as (name, start_s,
    end_s) tuples, in start order."""
    out = []
    for plane in planes:
        if not plane["name"].startswith("/device:"):
            continue
        lines = {line["name"]: line["events"] for line in plane["lines"]}
        if OPS_LINE not in lines and MODULES_LINE not in lines:
            continue
        out.append({
            "name": plane["name"],
            "ops": sorted(lines.get(OPS_LINE) or [], key=lambda e: e[1]),
            "modules": sorted(lines.get(MODULES_LINE) or [], key=lambda e: e[1]),
        })
    return out


def read_planes(path: Path, host_spans: Iterable[str] = ()) -> List[Dict]:
    """The trace's planes as plain data: {name, lines: [{name, events:
    [(event name, start s, end s)]}]}. Device planes keep every event;
    host planes keep only the program's spans named in `host_spans`."""
    from jax.profiler import ProfileData

    keep = frozenset(host_spans)
    data = ProfileData.from_file(str(path))
    planes = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            events = []
            for e in line.events:
                name = e.name if device else _span_name(e.name)
                if device or name in keep:
                    events.append((name, e.start_ns * 1e-9,
                                   (e.start_ns + e.duration_ns) * 1e-9))
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def _span_name(name: str) -> str:
    """A host event's name without the `#key=value,...#` a TraceMe may
    carry its arguments in."""
    return name.split("#", 1)[0]


def host_spans(planes) -> List[Tuple[str, float, float]]:
    """Every event kept on the host planes, as (name, start_s, end_s)."""
    return [
        event
        for plane in planes if not plane["name"].startswith("/device:")
        for line in plane["lines"]
        for event in line["events"]
    ]


def _host_label(spans, start: float, end: float) -> str:
    """The host spans open at some point of [start, end], by name: how
    many, and the seconds of the stretch that they cover together, as
    "service.host.lock_wait x2 4.20s + service.host.walk x1 6.00s"."""
    inside: Dict[str, List[Tuple[float, float]]] = {}
    for name, s, e in spans:
        if s < end and e > start:
            inside.setdefault(name, []).append((max(s, start), min(e, end)))
    if not inside:
        return "no host span"
    return " + ".join(
        f"{name} x{len(cut)} {sum(e - s for s, e in _union(cut)):.2f}s"
        for name, cut in sorted(inside.items())
    )


def reduce(planes: List[Dict], window_s: float, kernels: Dict[str, List[str]]) -> Dict:
    """Busy seconds (union of operation intervals, averaged over the
    device planes), per-kernel device seconds and event counts
    (executables whose name contains one of the kernel's patterns), the
    ten device operations that took most time and the ten longest idle
    gaps, each labelled by the host spans open across it and by the
    executable that ran before it."""
    devs = device_lines(planes)
    if not devs:
        return {}
    busy_total = 0.0
    kernel_s = {k: 0.0 for k in kernels}
    kernel_n = {k: 0 for k in kernels}
    op_time: Dict[str, float] = {}
    module_time: Dict[str, float] = {}
    gaps: List[Tuple[str, float, float]] = []
    for dev in devs:
        events = dev["ops"] or dev["modules"]
        union = _union((s, e) for _n, s, e in events)
        busy_total += sum(e - s for s, e in union)
        for name, s, e in events:
            op_time[name] = op_time.get(name, 0.0) + (e - s)
        for name, s, e in dev["modules"]:
            module_time[name] = module_time.get(name, 0.0) + (e - s)
            for kernel, patterns in kernels.items():
                if any(p in name for p in patterns):
                    kernel_s[kernel] += e - s
                    kernel_n[kernel] += 1
        modules = dev["modules"]
        starts = [s for _n, s, _e in modules]
        # idle stretches between busy intervals, and from the slice's
        # start and to its end
        edges = [(None, 0.0)] + union + [(window_s, None)]
        for (_s0, e0), (s1, _e1) in zip(edges, edges[1:]):
            if s1 <= e0:
                continue
            i = bisect.bisect_right(starts, e0) - 1
            label = f"after {modules[i][0]}" if i >= 0 else "before first executable"
            gaps.append((label, e0, s1))
    n = len(devs)
    busy_s = min(busy_total / n, window_s)
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(gaps, key=lambda g: g[1] - g[2])[:10]
    spans = host_spans(planes)
    return {
        "chips": n,
        "busy_s": busy_s,
        "window_s": window_s,
        "kernel_s": {k: v / n for k, v in kernel_s.items()},
        "kernel_events": kernel_n,
        "device_ops": [[name, secs / n] for name, secs in top_ops],
        "executables": [
            [name, secs / n]
            for name, secs in sorted(module_time.items(), key=lambda kv: -kv[1])[:10]
        ],
        "idle_gaps": [
            [f"{_host_label(spans, e0, s1)} | {label}", s1 - e0]
            for label, e0, s1 in top_gaps
        ],
    }
