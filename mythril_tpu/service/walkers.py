"""Walker processes: the serve engine's host walks side by side.

A host walk's symbolic state (the term arena, the CDCL session,
`PhaseProfile`, the `Args` flags) is process-global, so walks in one
process take turns on HOST_SYMBOLIC_LOCK (support/host_lock.py). A
walker is a process of its own, with singletons of its own: an engine
with `host_workers` W keeps min(W, effective CPUs - 1) walkers, one CPU
staying with the engine process (the wave thread, JAX dispatch and
`_finalize`), and a width of one or less walks in the engine's process
as before.

Each walker is a one-process spawn `ProcessPoolExecutor` pinned to the
CPU (`accel.pin_to_cpu`): it never opens the chip. A walker that dies
breaks only its own executor, which raises `BrokenProcessPool` for the
walk it held and is then started anew. A walk runs
`corpus._analyze_one` under the engine's `Args` flags and hands back,
beside its result, its own registry growth (`registry.growth`): the
solver attribution and phase series the engine folds into its own
registry, so that /metrics and the solver attribution stay whole.

On start a walker loads LASER and the native solver library and walks
a small built-in contract (`WARM_CODE`): a walk's first use in a
process costs seconds more than the next.
"""

from __future__ import annotations

import logging
import multiprocessing as mp
import os
import queue
from concurrent.futures import ProcessPoolExecutor
from concurrent.futures.process import BrokenProcessPool
from typing import Dict, List, Tuple

log = logging.getLogger(__name__)

#: the warm-up walk: a calldata-gated SSTORE and a CALLER SELFDESTRUCT
#: behind it, so that the walk steps, forks and solves a witness
WARM_CODE = "60003560e01c8063a9059cbb14601557005b60016000556000ff"


def walker_width(host_workers: int) -> int:
    """How many walker processes an engine with `host_workers` host
    threads keeps: one CPU of the affinity mask stays with the engine
    process. Below 2 the engine walks in its own process."""
    from mythril_tpu.analysis.corpus import _effective_cpus

    return min(host_workers, _effective_cpus() - 1)


def _warm() -> int:
    """Walker side: the warm-up walk, at the serve engine's default walk
    settings. Returns the walker's pid."""
    from mythril_tpu.analysis.corpus import _analyze_one
    from mythril_tpu.service.engine import DEFAULT_ADDRESS

    _analyze_one((WARM_CODE, "", "warm", DEFAULT_ADDRESS, "bfs", 2, 8, 10,
                  128, 3, None, None, False, None, None))
    return os.getpid()


def _walk(payload: Tuple, flags: Dict, observing: bool) -> Dict:
    """Walker side: one walk under the engine's `Args` flags and
    observe switch. The result carries the walker's `pid` and the
    walk's registry growth (`telemetry`)."""
    from mythril_tpu import observe
    from mythril_tpu.analysis.corpus import _analyze_one
    from mythril_tpu.support.support_args import args

    vars(args).update(flags)
    observe.set_enabled(observing)
    reg = observe.registry()
    marker = reg.marker()
    result = _analyze_one(payload)
    result["telemetry"] = reg.growth(marker)
    result["pid"] = os.getpid()
    return result


class Walker:
    """One walker process, walking one payload at a time."""

    def __init__(self, index: int) -> None:
        self.index = index
        self._start()

    def _start(self) -> None:
        from mythril_tpu.support.accel import pin_to_cpu

        self._executor = ProcessPoolExecutor(
            max_workers=1,
            mp_context=mp.get_context("spawn"),
            initializer=pin_to_cpu,
        )
        self._warm = self._executor.submit(_warm)

    def _restart(self) -> None:
        self._executor.shutdown(wait=False, cancel_futures=True)
        log.warning("walker %d died; starting it anew", self.index)
        self._start()

    @property
    def pid(self) -> int:
        """The walker's process id, once its warm-up walk is done."""
        return self._warm.result()

    def wait_warm(self) -> None:
        try:
            self._warm.result()
        except Exception:
            log.warning("walker %d warm-up failed", self.index, exc_info=True)

    def walk(self, payload: Tuple) -> Dict:
        """Walk `payload` in this walker. A walker already known dead is
        started anew first; one that dies before the walk ends raises
        `BrokenProcessPool`, and is started anew for the next walk."""
        from mythril_tpu import observe
        from mythril_tpu.support.support_args import args

        job = (_walk, payload, dict(vars(args)), observe.enabled())
        try:
            future = self._executor.submit(*job)
        except BrokenProcessPool:
            self._restart()
            future = self._executor.submit(*job)
        try:
            return future.result()
        except BrokenProcessPool:
            self._restart()
            raise

    def close(self) -> None:
        self._executor.shutdown(wait=True, cancel_futures=True)


class WalkerPool:
    """`width` walkers and the free ones among them: a host thread
    takes a free walker (`acquire`, blocking while all are walking),
    walks on it and gives it back (`release`)."""

    def __init__(self, width: int) -> None:
        self.walkers: List[Walker] = [Walker(i) for i in range(width)]
        self._free: "queue.SimpleQueue[Walker]" = queue.SimpleQueue()
        for walker in self.walkers:
            self._free.put(walker)

    def acquire(self) -> Walker:
        return self._free.get()

    def release(self, walker: Walker) -> None:
        self._free.put(walker)

    def wait_warm(self) -> None:
        for walker in self.walkers:
            walker.wait_warm()

    def close(self) -> None:
        for walker in self.walkers:
            walker.close()
