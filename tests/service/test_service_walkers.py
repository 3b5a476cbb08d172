"""Serve host walks in walker processes (service/walkers.py, tier-1
`service` marker): a walk in a walker gives what the same walk gives in
this process, its registry growth folds back into this process's
solver attribution, a walker killed mid-walk fails only its job and is
started anew, and `close()` leaves no walker behind. CPU-only; the
contracts are small."""

from __future__ import annotations

import os
import signal
import time
from concurrent.futures import ThreadPoolExecutor
from concurrent.futures.process import BrokenProcessPool
from pathlib import Path

import pytest

from mythril_tpu import observe
from mythril_tpu.analysis import corpus
from mythril_tpu.analysis.corpusgen import wide_contract
from mythril_tpu.observe.registry import MetricsRegistry, registry
from mythril_tpu.service.engine import (
    DEFAULT_ADDRESS,
    AnalysisEngine,
    ServiceConfig,
)
from mythril_tpu.service.jobs import Job
from mythril_tpu.service.walkers import Walker, WalkerPool

pytestmark = [pytest.mark.service]

FIXTURES = Path(__file__).resolve().parents[1] / "testdata" / "vendored" / "inputs"
#: CALLER; SELFDESTRUCT: one short walk
KILLABLE = "33ff"

WALK_CFG = dict(
    stripes=2,
    lanes_per_stripe=4,
    steps_per_wave=32,
    max_waves=1,
    queue_capacity=4,
    host_walk=True,
    host_workers=2,
    execution_timeout=10,
    coalesce_wait_s=0.02,
    idle_wait_s=0.02,
)


def payload(code: str, timeout: int = 8):
    return (code, "", "t", DEFAULT_ADDRESS, "bfs", 2, timeout, 10, 128, 3,
            None, None, False, None, None)


def found(result):
    return sorted((i["swc-id"], i["address"]) for i in result["issues"])


def gone(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return True
    return False


@pytest.fixture(scope="module")
def walker():
    one = Walker(0)
    yield one
    one.close()


def test_walker_and_in_process_walks_agree(walker):
    code = (FIXTURES / "exceptions.sol.o").read_text().strip()
    here = corpus._analyze_one(payload(code))
    there = walker.walk(payload(code))
    assert here["error"] is None and there["error"] is None
    assert found(there) == found(here) and found(here)
    assert there["states"] == here["states"]
    assert there["cut"] == here["cut"] is None
    assert there["pid"] == walker.pid != os.getpid()
    # the growth it hands back holds the walk's own phase split
    grown = there["telemetry"]["mtpu_phase_wall_seconds"]["series"]
    solves = [n for key, (_b, _s, n) in grown.items()
              if dict(key)["phase"] == "solve"]
    assert solves == [there["phases"]["solve"]["count"]]


def test_growth_fold_reproduces_in_process_attribution():
    code = (FIXTURES / "origin.sol.o").read_text().strip()
    marker = registry().marker()
    corpus._analyze_one(payload(code))
    direct = observe.solver_attribution(marker)
    phases = registry().since(marker)["mtpu_phase_wall_seconds"]
    grown = registry().growth(marker)
    assert sum(row["queries"] for row in direct.values()) >= 1

    target = MetricsRegistry()
    for times in (1, 2):
        target.fold(grown)
        folded = observe.solver_attribution(growth=target.growth({}))
        assert set(folded) == set(direct)
        for origin, row in direct.items():
            assert folded[origin]["queries"] == times * row["queries"]
            assert folded[origin]["verdicts"] == {
                v: times * n for v, n in row["verdicts"].items()
            }
            assert folded[origin]["escalations"] == times * row["escalations"]
            assert folded[origin]["wall_s"] == pytest.approx(
                times * row["wall_s"], abs=2e-3
            )
        hist = target.histogram("mtpu_phase_wall_seconds")
        for key, row in phases.items():
            child = hist.labels(**dict(key))
            assert child.count == times * row["count"]
            assert child.sum == pytest.approx(times * row["sum"])


def test_walker_started_anew_after_it_dies():
    one = Walker(0)
    try:
        first = one.pid
        with ThreadPoolExecutor(1) as thread:
            walking = thread.submit(one.walk, payload(wide_contract(8)))
            time.sleep(0.5)
            os.kill(first, signal.SIGKILL)
            with pytest.raises(BrokenProcessPool):
                walking.result(timeout=60.0)
        # the walk after it goes to a walker started anew
        result = one.walk(payload(KILLABLE))
        assert result["error"] is None and found(result)
        assert result["pid"] == one.pid != first
    finally:
        one.close()
    assert gone(first) and gone(result["pid"])


def locked_row(job: Job, timeout_s: float = 120.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        for row in observe.journey_log().events(job.journey_id):
            if row["tier"] == "host-walk" and row["event"] == "locked":
                return row
        time.sleep(0.02)
    raise AssertionError(f"job {job.id}'s walk never began")


def test_walker_killed_mid_walk_fails_only_its_job(monkeypatch):
    monkeypatch.setattr(corpus, "_effective_cpus", lambda: 8)
    engine = AnalysisEngine(ServiceConfig(**WALK_CFG)).start()
    try:
        assert engine._warm_done.wait(120.0)
        pool: WalkerPool = engine._walkers
        pids = [w.pid for w in pool.walkers]
        # a walk that runs to its budget, killed once it has begun
        doomed = engine.submit(Job(wide_contract(8)))
        index = locked_row(doomed)["attrs"]["walker"]
        os.kill(pids[index], signal.SIGKILL)
        settled = engine.queue.wait_terminal(doomed.id, 120.0)
        assert settled is not None and settled.state == "failed"
        assert "terminated abruptly" in settled.error
        assert settled.report["host"]["error"] == settled.error
        # the next job walks on the walker started anew: the other one
        # is held here
        other = pool.acquire()
        assert other.index != index
        try:
            job = engine.submit(Job(KILLABLE))
            settled = engine.queue.wait_terminal(job.id, 120.0)
        finally:
            pool.release(other)
        assert settled is not None and settled.state == "done"
        assert settled.report["issues"]
        walked = [r for r in observe.journey_log().events(job.journey_id)
                  if r["tier"] == "host-walk"]
        by_event = {r["event"]: r for r in walked}
        assert by_event["locked"]["attrs"]["walker"] == index
        reborn = by_event["done"]["attrs"]["pid"]
        assert reborn == pool.walkers[index].pid and reborn not in pids
    finally:
        engine.close()
    for pid in pids + [reborn]:
        assert gone(pid), pid


def test_one_host_worker_walks_in_process():
    engine = AnalysisEngine(
        ServiceConfig(**dict(WALK_CFG, host_workers=1))
    ).start()
    try:
        assert engine._walkers is None
        job = engine.submit(Job(KILLABLE))
        settled = engine.queue.wait_terminal(job.id, 120.0)
        assert settled is not None and settled.state == "done"
        done = [r for r in observe.journey_log().events(job.journey_id)
                if r["tier"] == "host-walk" and r["event"] == "done"]
        assert done[0]["attrs"]["pid"] == os.getpid()
        assert engine.stats()["host_pool"]["walkers"] == 0
        assert registry().value(
            "mtpu_serve_host_walks_total", engine=engine._eid,
            walker="inproc",
        ) == 1
    finally:
        engine.close()
