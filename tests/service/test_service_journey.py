"""Per-job journey tracing through the service tier ladder (ISSUE 12,
tier-1 `service` + `observe` markers).

Pins that the three settle paths produce the correct DISTINCT tier
sequences at /v1/jobs/<id>/trace:

    store-hit      admission -> store-hit -> settle
    static-answer  admission -> static-answer -> settle
    full wave      admission -> queued -> lane-grant -> wave -> settle

and that the journey_id round-trips through the routing JSONL (schema
v3), so features ⨝ route ⨝ outcome ⨝ timeline joins offline. The two
admission-tier paths run on engine-less servers (the wave thread does
not exist — settling there PROVES the tier); the full path runs a
real engine. CPU-only."""

from __future__ import annotations

import json
import os
import threading

import pytest

from mythril_tpu import observe
from mythril_tpu.analysis import corpus
from mythril_tpu.analysis.corpusgen import clean_contract
from mythril_tpu.analysis.static import analysis_config_fingerprint
from mythril_tpu.observe.registry import registry
from mythril_tpu.service.client import ServiceClient
from mythril_tpu.service.engine import AnalysisEngine, ServiceConfig
from mythril_tpu.service.jobs import Job
from mythril_tpu.service.server import AnalysisServer
from mythril_tpu.store import close_stores, code_hash_hex, open_store
from mythril_tpu.support.support_args import args as support_args

pytestmark = [pytest.mark.service, pytest.mark.observe]

#: CALLER; SELFDESTRUCT — never banked, never statically answerable
KILLABLE = "33ff"
#: tiny branching writer for the full wave path
WRITER = "6001600055600160015560026000f3"

CFG = dict(
    stripes=2,
    lanes_per_stripe=4,
    steps_per_wave=32,
    max_waves=1,
    queue_capacity=4,
    host_walk=False,
    coalesce_wait_s=0.02,
    idle_wait_s=0.02,
)

ISSUES = [{"address": 1, "swc-id": "110", "title": "banked",
           "contract": "b", "function": "f", "description": "d",
           "severity": "Medium", "min_gas_used": 0, "max_gas_used": 1,
           "sourceMap": None, "tx_sequence": None}]


def trace_of(client: ServiceClient, job_id: str) -> dict:
    return client._request(f"/v1/jobs/{job_id}/trace")


def routing_tail_for(journey_id: str) -> dict:
    for rec in observe.routing_log().tail(64):
        if rec.get("journey_id") == journey_id:
            return rec
    raise AssertionError(
        f"no routing record carries journey_id {journey_id}"
    )


def test_store_hit_journey(tmp_path):
    directory = str(tmp_path / "vstore")
    cfg = ServiceConfig(**CFG)
    open_store(directory).put(
        code_hash_hex(KILLABLE),
        analysis_config_fingerprint(
            transaction_count=cfg.transaction_count,
            create_timeout=cfg.create_timeout,
        ),
        issues=ISSUES,
        provenance={"computed_by": "seeder", "wall_s": 1.0},
    )
    srv = AnalysisServer(
        ServiceConfig(store_dir=directory, **CFG), start_engine=False
    ).start()
    try:
        client = ServiceClient(srv.url)
        job_id = client.submit(KILLABLE)
        job = client.job(job_id)
        assert job["state"] == "done"
        assert job["report"]["journey_id"] == job_id
        doc = trace_of(client, job_id)
        assert doc["journey_id"] == job_id
        assert doc["tiers"] == ["admission", "store-hit", "settle"]
        assert doc["schema_version"] == 1
        assert doc["state"] == "done"
        # the JSONL join key: the service emitted a routing record
        # (v4 since the cross-contract linker added link_* features)
        rec = routing_tail_for(job_id)
        assert rec["schema_version"] == 4
        assert rec["outcome"]["route"] == "store-hit"
    finally:
        srv.close()
        close_stores()


def test_static_answer_journey():
    previous = support_args.static_answer
    support_args.static_answer = True  # the conftest turns it off
    srv = AnalysisServer(
        ServiceConfig(**CFG), start_engine=False
    ).start()
    try:
        client = ServiceClient(srv.url)
        job_id = client.submit(clean_contract(0))
        assert client.job(job_id)["state"] == "done"
        doc = trace_of(client, job_id)
        assert doc["tiers"] == ["admission", "static-answer", "settle"]
        rec = routing_tail_for(job_id)
        assert rec["outcome"]["route"] == "static-answer"
        # the timeline join works offline too: the jsonl line parses
        # back with the same key
        parsed = observe.parse_routing_record(
            json.dumps(rec, sort_keys=True)
        )
        assert parsed["journey_id"] == job_id
        assert observe.assemble_journey(parsed["journey_id"])[
            "tiers"
        ] == doc["tiers"]
    finally:
        srv.close()
        support_args.static_answer = previous


def test_full_wave_journey_and_jsonl_roundtrip(tmp_path):
    observe.configure(out_dir=str(tmp_path))
    srv = AnalysisServer(ServiceConfig(**CFG)).start()
    try:
        client = ServiceClient(srv.url)
        job_id = client.submit(WRITER)
        report = client.report(job_id, wait_s=120.0)
        assert report["state"] == "done", report
        doc = trace_of(client, job_id)
        tiers = doc["tiers"]
        assert tiers[0] == "admission" and tiers[-1] == "settle"
        assert "queued" in tiers and "lane-grant" in tiers
        assert "wave" in tiers
        # the store/static tiers must NOT appear on the full path
        assert "store-hit" not in tiers
        assert "static-answer" not in tiers
        # per-tier dwell covers every tier touched
        assert set(doc["tier_dwell_s"]) == set(tiers)
        # wave events carry their wave index
        waves = [e for e in doc["events"] if e["tier"] == "wave"]
        assert any(e["event"] == "dispatch" for e in waves)
        assert any(e["event"] == "harvest" for e in waves)
        # journey_id rides the on-disk routing JSONL (schema v3)
        path = tmp_path / "routing_features.jsonl"
        assert path.exists()
        records = observe.read_routing_records(str(path))
        match = [r for r in records if r["journey_id"] == job_id]
        assert match, f"no JSONL record for journey {job_id}"
        assert match[0]["outcome"]["route"] in (
            "device-owned", "host-walk"
        )
    finally:
        srv.close()
        observe.configure(out_dir=None)


def test_trace_unknown_job_is_404():
    srv = AnalysisServer(
        ServiceConfig(**CFG), start_engine=False
    ).start()
    try:
        client = ServiceClient(srv.url)
        from mythril_tpu.service.client import ServiceError

        with pytest.raises(ServiceError) as refusal:
            trace_of(client, "0" * 12)
        assert refusal.value.status == 404
    finally:
        srv.close()


def test_healthz_readiness_split_and_draining_reason():
    srv = AnalysisServer(ServiceConfig(**CFG), start_engine=False).start()
    try:
        # honoring OFF: the ready-probe 503 below carries Retry-After
        # (ISSUE 15); the default client would retry-sleep through it
        client = ServiceClient(srv.url, honor_retry_after=False)
        health = client.healthz()
        assert health["ok"] is True
        assert health["state"] in ("ok", "degraded")
        assert health["ready"] is True
        assert health["not_ready_reasons"] == []
        assert isinstance(health["objectives"], list)
        srv.engine.drain()
        health = client.healthz()
        assert health["draining"] is True
        assert health["ready"] is False
        assert "draining" in health["not_ready_reasons"]
        # the readiness PROBE flips to 503 while the payload stays
        from mythril_tpu.service.client import ServiceError

        with pytest.raises(ServiceError) as refusal:
            client._request("/healthz?ready=1")
        assert refusal.value.status == 503
        assert refusal.value.payload["not_ready_reasons"]
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# the host walk on the journey: lock wait, walk, budget cut, phase split
# ---------------------------------------------------------------------------
WALK_CFG = dict(CFG, host_walk=True, host_workers=2, execution_timeout=10)


def writer(value: int) -> str:
    """WRITER with its first stored constant replaced: distinct codes."""
    return "60%02x" % value + WRITER[4:]


def walk_rows(job: Job) -> dict:
    rows = observe.journey_log().events(job.journey_id)
    out = {}
    for row in rows:
        if row["tier"] == "host-walk":
            assert row["event"] not in out, rows  # one walk per job
            out[row["event"]] = row
    return out


def settle(engine: AnalysisEngine, jobs) -> None:
    for job in jobs:
        settled = engine.queue.wait_terminal(job.id, 180.0)
        assert settled is not None and settled.state == "done", job.id


def solve_count() -> int:
    return registry().histogram("mtpu_phase_wall_seconds").labels(
        phase="solve"
    ).count


@pytest.mark.parametrize("cpus", [1, 8], ids=["in-process", "walkers"])
def test_host_walk_journey_lock_wait_walk_and_split(cpus, monkeypatch):
    """Two host workers: with one CPU the walks take turns on the host
    symbolic lock in this process; with CPUs to spare each runs in a
    walker process of its own, side by side."""
    monkeypatch.setattr(corpus, "_effective_cpus", lambda: cpus)
    if cpus > 1:
        # each walk waits inside its walk span until the other has begun
        # its own: walks that took turns would break the barrier
        barrier = threading.Barrier(2, timeout=60.0)
        walk = corpus.analyze_one_payload

        def side_by_side(payload):
            barrier.wait()
            return walk(payload)

        monkeypatch.setattr(corpus, "analyze_one_payload", side_by_side)
    engine = AnalysisEngine(ServiceConfig(**WALK_CFG)).start()
    try:
        jobs = [engine.submit(Job(code)) for code in (KILLABLE, writer(2))]
        settle(engine, jobs)
        walks = [walk_rows(job) for job in jobs]
        for walk in walks:
            start, locked, done = (
                walk[event]["t"] for event in ("start", "locked", "done")
            )
            assert start <= locked <= done
            # wait and walk add up to the whole span, from one clock
            assert (locked - start) + (done - locked) == pytest.approx(
                done - start
            )
            attrs = walk["done"]["attrs"]
            assert attrs["cut"] is False
            assert "cut_budget" not in attrs
            assert attrs["solve_s"] >= 0.0 and attrs["solve_n"] >= 1
            assert {"step_s", "feasibility_s", "concretize_s"} <= set(attrs)
        first, second = sorted(walks, key=lambda w: w["locked"]["t"])
        pids = {w["done"]["attrs"]["pid"] for w in walks}
        if cpus == 1:
            # the lock serializes walks: the later one is granted it only
            # once the earlier one is done
            assert second["locked"]["t"] >= first["done"]["t"]
            assert pids == {os.getpid()}
            assert engine.stats()["host_pool"]["walkers"] == 0
        else:
            # side by side: the later walk begins before the earlier one
            # is done, in another walker process than it
            assert second["locked"]["t"] < first["done"]["t"]
            assert len(pids) == 2 and os.getpid() not in pids
            assert {w["locked"]["attrs"]["walker"] for w in walks} == {0, 1}
            assert engine.stats()["host_pool"]["walkers"] == 2
        # both spans are on the flight recorder for each job
        for job in jobs:
            names = {
                s.name for s in observe.flight_recorder().tail(4096)
                if (s.attrs or {}).get("job") == job.id
            }
            assert {"service.host.lock_wait", "service.host.walk"} <= names
    finally:
        engine.close()


def test_waves_add_nothing_to_a_walks_solver_split():
    engine = AnalysisEngine(ServiceConfig(**WALK_CFG)).start()
    try:
        # device-only jobs alone run no solver query
        before = solve_count()
        settle(engine, [
            engine.submit(Job(writer(v), host_walk=False)) for v in (3, 4)
        ])
        assert solve_count() == before
        # a walk with waves dispatching beside it: every solve in the
        # process is the walk's, and its `done` counts all of them
        before = solve_count()
        walked = engine.submit(Job(writer(5)))
        waves = [
            engine.submit(Job(writer(v), host_walk=False)) for v in (6, 7, 8)
        ]
        settle(engine, [walked] + waves)
        attrs = walk_rows(walked)["done"]["attrs"]
        assert attrs["solve_n"] >= 1
        assert solve_count() - before == attrs["solve_n"]
    finally:
        engine.close()
