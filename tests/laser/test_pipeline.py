"""The pipelined wave engine (ISSUE 4): double-buffered async
dispatch, device-side evidence compaction, donated-arena reseed, the
background checkpoint writer, and the service's two pipeline slots.

The acceptance bar: the pipelined and lock-step (--no-pipeline)
schedules emit identical issue sets on the fault-suite contracts, an
XLA fault surfacing asynchronously on the in-flight wave N+1 is
attributed and retried correctly, and the compacted readback carries
exactly what the full-table harvest carried. Everything runs on CPU
JAX with the tiny hand-assembled fixtures the resilience suite uses.
"""

import numpy as np
import pytest

from mythril_tpu.laser.batch.arena import ArenaView
from mythril_tpu.laser.batch.explore import DeviceCorpusExplorer
from mythril_tpu.laser.batch.state import (
    make_batch,
    make_code_table,
    storage_dict_from,
)
from mythril_tpu.laser.batch.symbolic import make_sym_batch, sym_run
from mythril_tpu.support import resilience

# tests/laser is not a package: pytest's rootdir import mode puts this
# directory on sys.path, so the harness imports flat
from faultinject import device_faults  # noqa: E402

pytestmark = pytest.mark.pipeline

#: PUSH1 1; PUSH1 0; SSTORE; PUSH1 0; PUSH1 1; SSTORE; STOP
WRITER = "6001600055600060015500"
#: CALLDATALOAD(0) branches to a storage write — one symbolic JUMPI
BRANCHER = "600035600757005b600160005500"
#: CALLER; SELFDESTRUCT — banks trigger evidence in one wave
KILLABLE = "33ff"
#: SSTORE(0, 1) only when calldata byte 0 == 0x42 — covering the taken
#: direction needs a solver-derived flip witness
GATED = "60003560f81c604214600d57005b600160005500"


@pytest.fixture(autouse=True)
def _clean_supervisor():
    resilience.disarm_faults()
    resilience.DegradationLog().reset()
    yield
    resilience.disarm_faults()


def _explore(codes, pipeline, **kw):
    kw.setdefault("lanes_per_contract", 8)
    kw.setdefault("waves", 3)
    kw.setdefault("steps_per_wave", 64)
    kw.setdefault("transaction_count", 1)
    ex = DeviceCorpusExplorer(codes, pipeline=pipeline, **kw)
    return ex, ex.run()


def _fingerprint(contract):
    """The issue-bearing outcome of one contract: coverage, trigger
    pcs per kind, evidence (class, pc) pairs — everything issue
    synthesis (analysis/evidence.py) reads."""
    return (
        tuple(map(tuple, contract["covered_branches"])),
        {
            kind: tuple(sorted(t["pc"] for t in bucket))
            for kind, bucket in contract["triggers"].items()
        },
        tuple(sorted((e["class"], e["pc"]) for e in contract["evidence"])),
    )


# -- the differential (acceptance criterion) --------------------------------
def test_differential_issue_sets_match_on_fault_suite():
    """Pipelined and lock-step runs must report the SAME issue set on
    the fault-suite contracts — including the gated shape whose taken
    direction only a flip witness reaches."""
    codes = [KILLABLE, WRITER, BRANCHER, GATED]
    _, piped = _explore(codes, True, seed=7)
    _, lock = _explore(codes, False, seed=7)
    for p, s in zip(piped["contracts"], lock["contracts"]):
        assert _fingerprint(p) == _fingerprint(s)
    # and the differential is not trivially empty
    assert "selfdestruct" in piped["contracts"][0]["triggers"]
    covered_gate = {tuple(b) for b in piped["contracts"][3]["covered_branches"]}
    assert (11, True) in covered_gate and (11, False) in covered_gate


def test_differential_corpora_match_on_branchless_contracts():
    """Branchless contracts exhaust their frontier in the seed wave:
    both schedules bank identical (deterministic-seed) corpora entries
    for them — the corpus divergence budget of the pipeline is the
    extra warm-up stripe only."""
    _, piped = _explore([KILLABLE], True, waves=1, seed=5)
    _, lock = _explore([KILLABLE], False, waves=1, seed=5)
    assert (
        piped["contracts"][0]["corpus_size"]
        == lock["contracts"][0]["corpus_size"]
    )
    assert _fingerprint(piped["contracts"][0]) == _fingerprint(
        lock["contracts"][0]
    )


# -- overlap + accounting ----------------------------------------------------
def test_pipeline_keeps_two_waves_in_flight():
    ex, out = _explore([BRANCHER], True, waves=4)
    s = out["stats"]
    assert s["pipelined"] == 1
    assert s["waves_inflight_max"] == 2
    assert s["waves_overlapped"] >= 1
    assert 0.0 <= s["wave_overlap_ratio"] <= 1.0
    assert 0.0 <= s["device_idle_frac"] <= 1.0


def test_no_pipeline_is_lock_step():
    ex, out = _explore([BRANCHER], False, waves=3)
    s = out["stats"]
    assert s["pipelined"] == 0
    assert s["waves_overlapped"] == 0
    assert s["waves_inflight_max"] <= 1


def test_active_lane_steps_exclude_halted_tail():
    """KILLABLE lanes halt two instructions in while WRITER lanes run
    seven: the wave keeps stepping until the slowest lane halts, and
    the active count must exclude the already-halted stripe (the raw
    product steps x lanes counts it)."""
    _, out = _explore([WRITER, KILLABLE], True, waves=1)
    s = out["stats"]
    assert 0 < s["device_steps"] < s["device_steps_raw"]


# -- device-side evidence compaction ----------------------------------------
def test_compact_readback_equals_full_tables():
    """ArenaView's bucketed transfer must carry exactly what the
    full-table device_get carried: status, halt pc, gas bounds, and
    every storage journal row up to storage_cnt."""
    import jax

    table = make_code_table([bytes.fromhex(WRITER)])
    base = make_batch(4, calldata=[b"\x00" * 4] * 4)
    out, _steps, _active = sym_run(make_sym_batch(base), table, max_steps=64)
    view = ArenaView(out)
    status, pc, keys, vals, cnt = jax.device_get(
        (
            out.base.status,
            out.base.pc,
            out.base.storage_keys,
            out.base.storage_vals,
            out.base.storage_cnt,
        )
    )
    np.testing.assert_array_equal(view.status, status)
    np.testing.assert_array_equal(view.halt_pc, pc)
    for lane in range(4):
        assert storage_dict_from(view.storage_tables(), lane) == (
            storage_dict_from((keys, vals, cnt), lane)
        )
    assert view.bytes_fetched < view.bytes_full


def test_explorer_counts_compacted_evidence_bytes():
    _, out = _explore([WRITER], True, waves=1)
    s = out["stats"]
    assert s["evidence_bytes_per_wave"] > 0
    assert s["evidence_bytes"] < s["evidence_bytes_full"]


# -- donated-arena reseed ----------------------------------------------------
def test_device_reseed_matches_cold_rebuild():
    """From wave 1 on, the explorer reseeds the next wave on device
    out of the previous wave's buffers; the outcome must be identical
    to rebuilding every wave through make_batch."""

    class ColdExplorer(DeviceCorpusExplorer):
        def _dispatch_wave(self, payload):
            self._carcass = None  # force the cold path every wave
            return super()._dispatch_wave(payload)

    kw = dict(
        lanes_per_contract=8,
        waves=4,
        steps_per_wave=64,
        transaction_count=2,
        pipeline=False,
        seed=3,
    )
    warm = DeviceCorpusExplorer([BRANCHER], **kw).run()
    cold = ColdExplorer([BRANCHER], **kw).run()
    assert _fingerprint(warm["contracts"][0]) == _fingerprint(
        cold["contracts"][0]
    )
    assert (
        warm["contracts"][0]["corpus_size"]
        == cold["contracts"][0]["corpus_size"]
    )


# -- async fault containment -------------------------------------------------
def test_async_fault_on_wave_readback_is_attributed_and_retried():
    """A classified fault surfacing at the harvest (the async-dispatch
    readback point) is recorded against the faulted wave and retried
    cold — the exploration completes with full results."""
    with device_faults(times=1):
        _, out = _explore([BRANCHER], True, waves=3)
    counts = resilience.DegradationLog().counts
    assert counts.get("async-device-fault", 0) >= 1
    assert out["stats"]["device_faults"] == 0  # recovered, not abandoned
    covered = {tuple(b) for b in out["contracts"][0]["covered_branches"]}
    assert (5, False) in covered or (5, True) in covered


def test_fault_on_inflight_second_wave_recovers():
    """skip=1 lets wave 0's harvest through and faults the IN-FLIGHT
    wave 1 — the pipeline's retry must rebuild exactly that wave."""
    with device_faults(times=1, skip=1):
        _, out = _explore([BRANCHER], True, waves=3)
    counts = resilience.DegradationLog().counts
    assert counts.get("async-device-fault", 0) >= 1
    assert out["stats"]["device_faults"] == 0
    assert out["stats"]["waves"] >= 2


def test_exhausted_ladder_still_degrades_not_crashes():
    """Past the whole ladder the pipelined run degrades exactly like
    the lock-step one (resilience parity with test_resilience)."""
    with device_faults(times=99):
        ex = DeviceCorpusExplorer(
            [WRITER],
            lanes_per_contract=8,
            waves=2,
            steps_per_wave=64,
            transaction_count=1,
            pipeline=True,
        )
        out = ex.run()
    assert out["stats"]["device_faults"] == 1
    assert not out["contracts"][0]["device_complete"]
    assert resilience.DegradationLog().counts.get("wave-abandoned") == 1


# -- background checkpoint writer -------------------------------------------
def test_checkpoint_writer_flushes_replayable_frontier(tmp_path):
    from mythril_tpu.laser.batch.checkpoint import checkpoint_shape
    from mythril_tpu.laser.batch.explore import replay_wave

    path = str(tmp_path / "wave.npz")
    ex = DeviceCorpusExplorer(
        [BRANCHER],
        lanes_per_contract=8,
        waves=2,
        steps_per_wave=64,
        transaction_count=1,
        checkpoint_path=path,
        pipeline=True,
    )
    out = ex.run()
    # every dispatched wave flushed (pipelining dispatches the warm-up
    # slot too), the writer drained before run() returned, and the
    # LAST flushed frontier replays
    assert out["stats"]["wave_checkpoints"] == out["stats"]["waves"]
    assert checkpoint_shape(path)["lanes"] == 8
    view, _sym, steps = replay_wave(path)
    assert steps > 0
    replayed = set()
    for lane in range(8):
        for pc, taken, _tid in view.journal(lane):
            replayed.add((pc, taken))
    covered = {tuple(b) for b in out["contracts"][0]["covered_branches"]}
    assert replayed <= covered


# -- the service's two pipeline slots ----------------------------------------
def test_service_pipeline_overlaps_waves_from_distinct_jobs():
    from mythril_tpu.service.engine import AnalysisEngine, ServiceConfig
    from mythril_tpu.service.jobs import Job

    engine = AnalysisEngine(
        ServiceConfig(
            stripes=2,
            lanes_per_stripe=4,
            steps_per_wave=64,
            max_waves=3,
            host_walk=False,
            coalesce_wait_s=0.05,
            idle_wait_s=0.02,
            pipeline=True,
        )
    ).start()
    try:
        jobs = [engine.submit(Job(WRITER)), engine.submit(Job(BRANCHER))]
        for job in jobs:
            settled = engine.queue.wait_terminal(job.id, timeout_s=120.0)
            assert settled is not None and settled.state == "done", (
                settled.state if settled else "lost"
            )
        stats = engine.stats()
        pipe = stats["pipeline"]
        assert pipe["enabled"] is True
        assert pipe["overlapped_waves"] >= 1
        assert pipe["wave_overlap_ratio"] > 0
        assert pipe["multi_job_overlaps"] >= 1
        for job in jobs:
            assert job.report["device"]["waves"] >= 1
    finally:
        engine.close()


# -- buffer donation (accelerator-only by default) ---------------------------
def test_explorer_donated_waves_match_undonated(monkeypatch):
    """Off CPU the explorer dispatches the donated kernels (donated
    wave + donated on-device reseed). CPU gates donation off, so the
    donated path is forced here: it must explore exactly like the
    undonated one."""
    kw = dict(
        lanes_per_contract=8,
        waves=3,
        steps_per_wave=64,
        transaction_count=2,
        pipeline=True,
        seed=3,
    )
    plain = DeviceCorpusExplorer([BRANCHER], **kw).run()
    monkeypatch.setattr(
        DeviceCorpusExplorer, "_donation_ok", lambda self: True
    )
    donated = DeviceCorpusExplorer([BRANCHER], **kw).run()
    assert donated["stats"]["waves"] >= 2
    assert _fingerprint(donated["contracts"][0]) == _fingerprint(
        plain["contracts"][0]
    )


def test_service_donated_wave_settles(monkeypatch):
    """A service wave through the donated kernel (forced on CPU) settles
    its job with the device findings."""
    from mythril_tpu.service.engine import AnalysisEngine, ServiceConfig
    from mythril_tpu.service.jobs import Job

    monkeypatch.setattr(AnalysisEngine, "_donation_ok", lambda self: True)
    engine = AnalysisEngine(
        ServiceConfig(
            stripes=2,
            lanes_per_stripe=4,
            steps_per_wave=64,
            max_waves=2,
            host_walk=False,
            coalesce_wait_s=0.05,
            idle_wait_s=0.02,
        )
    ).start()
    try:
        job = engine.submit(Job(KILLABLE))
        settled = engine.queue.wait_terminal(job.id, timeout_s=120.0)
        assert settled is not None and settled.state == "done"
        assert job.report["device"]["waves"] >= 1
        assert job.report["device"]["triggers"]["selfdestruct"] >= 1
    finally:
        engine.close()
