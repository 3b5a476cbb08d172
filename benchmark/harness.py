"""Shared pieces of a benchmark run: the definition files, the device,
the compile clock, percentiles and the result line."""

from __future__ import annotations

import importlib.util
import json
import os
import statistics
import sys
from pathlib import Path
from typing import Dict, List, Optional

BENCH = Path(__file__).resolve().parent


class BenchError(Exception):
    """A run that cannot produce a result (no chip, a missing file)."""


def load_json(path: Path) -> Dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError as why:
        raise BenchError(f"missing benchmark file {path}") from why


def definition(root: Path) -> Dict:
    return load_json(root / "BENCHMARK.json")


def find(items: List[Dict], name: str, what: str) -> Dict:
    for item in items:
        if item["name"] == name:
            return item
    raise BenchError(f"no {what} named {name!r} in BENCHMARK.json")


def load_module(path: Path, name: str):
    """Import one file of the benchmark by path (systems, readers)."""
    if not path.is_file():
        raise BenchError(f"missing benchmark file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def metrics_for(bench: Dict, workload: str, trace: bool) -> List[Dict]:
    """The metric entries a run of `workload` reports: the end-to-end
    ones without trace, the per-layer ones with it. An entry with a
    `workloads` key applies only to the cells it lists."""
    key = "per_layer" if trace else "end_to_end"
    return [
        m for m in bench[key]
        if "workloads" not in m or workload in m["workloads"]
    ]


def device_info(chips: int) -> Dict:
    """The device as JAX reports it; no TPU, or fewer chips than the
    cell asks for, is an error."""
    import jax

    devices = jax.devices()
    info = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if info["platform"] != "tpu":
        raise BenchError(f"no TPU: JAX found platform {info['platform']!r}")
    if info["count"] < chips:
        raise BenchError(f"{chips} chips wanted, JAX found {info['count']}")
    return info


def peaks_for(kind: str) -> Dict:
    """The published peaks of a device kind, from its own file under
    benchmark/peaks/ (the kind with spaces as `_`). A kind with no file
    is an error, never a default."""
    path = BENCH / "peaks" / (kind.replace(" ", "_") + ".json")
    if not path.is_file():
        raise BenchError(f"no peak table for device kind {kind!r} ({path})")
    peaks = load_json(path)
    if peaks.get("device_kind") != kind:
        raise BenchError(f"{path} is not the peak table of {kind!r}")
    return peaks


def memory_peak_bytes(chips: int) -> Optional[int]:
    """Peak bytes in use on the fullest chip this process used."""
    import jax

    peaks = []
    for device in jax.devices()[:chips]:
        stats = device.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def use_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache at a fixed directory inside
    the checkout, keeping every compile (no minimum compile time), so
    that only a cell's first run in a checkout compiles. Set before the
    program's own cache set-up, which takes the variable when set."""
    cache = str(root / ".jax_cache")
    # JAX does not create the directory on every platform: a missing
    # one fails each write and the next run compiles everything again
    os.makedirs(cache, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    import jax

    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache


class CompileClock:
    """Backend compiles and persistent-cache hits and misses, from
    JAX's own monitoring events (copied from the program's chip smoke).
    `mark()` and `since()` count what happened inside a window."""

    def __init__(self) -> None:
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self) -> Dict:
        return {"compiles": self.compiles, "seconds": self.seconds,
                "hits": self.hits, "misses": self.misses}

    def since(self, mark: Dict) -> Dict:
        now = self.mark()
        return {k: now[k] - mark[k] for k in now}


def quantile(values: List[float], q: float) -> Optional[float]:
    """The q-quantile (0 < q < 1), interpolated between the sorted
    values (Python's `inclusive` method); the single value when there
    is one."""
    if not values:
        return None
    if len(values) == 1:
        return float(values[0])
    cuts = statistics.quantiles(sorted(values), n=100, method="inclusive")
    return float(cuts[int(round(q * 100)) - 1])


def say(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
