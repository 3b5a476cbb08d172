"""Unified telemetry layer suite (mythril_tpu/observe, tier-1
`observe` marker).

Pins the four surfaces the ISSUE-7 tentpole built:
- metrics registry: counter/gauge/histogram semantics, label sets,
  single-lock snapshots + per-run deltas, Prometheus exposition golden;
- structured spans: nesting/ordering under threads, the flight
  recorder's bounds, Perfetto trace-event schema, overlap fraction,
  the automatic dump on an injected mesh degradation;
- solver attribution: per-origin tables with markers;
- routing feature log: JSONL schema golden;
plus the satellites: ExploreStats merge-policy completeness, the
registry-vs-legacy-view equality on a real explorer run, the
registry-backed PhaseProfile's byte-compatible view, and the service
/stats schema_version + /metrics + /trace endpoints."""

import json
import os
import threading
import time

import pytest

from mythril_tpu import observe
from mythril_tpu.observe.registry import (
    SCHEMA_VERSION,
    MetricsRegistry,
    registry,
)
from mythril_tpu.observe.spans import (
    FlightRecorder,
    Span,
    flight_recorder,
    overlap_fraction,
    to_perfetto,
    trace,
)

pytestmark = pytest.mark.observe

#: tiny runtime: a dispatcher with one selector and an INVALID body —
#: enough for the explorer to cover branches and bank a trigger
TINY = (
    "6080604052348015600f57600080fd5b50600436106028576000"
    "3560e01c8063c0406226146028575b600080fd5b60306032565b005b6000fe"
)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------
def test_counter_gauge_histogram_semantics():
    reg = MetricsRegistry()
    c = reg.counter("t_total", "help text")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    c.labels(kind="a").inc(4)
    assert c.labels(kind="a").value == 4
    assert c.value == 3.5  # label-less series unaffected

    g = reg.gauge("t_gauge")
    g.set(7)
    g.set_max(3)
    assert g.value == 7
    g.set_max(11)
    assert g.value == 11

    h = reg.histogram("t_hist", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    child = h.labels()
    assert child.count == 3
    assert abs(child.sum - 5.55) < 1e-9

    with pytest.raises(ValueError):
        reg.gauge("t_total")  # kind conflict


def test_snapshot_and_since_deltas():
    reg = MetricsRegistry()
    c = reg.counter("d_total")
    c.inc(5)
    marker = reg.marker()
    c.inc(2)
    reg.gauge("d_gauge").set(9)
    delta = reg.since(marker)
    assert delta["d_total"][()] == 2
    assert delta["d_gauge"][()] == 9  # gauges report current value
    # unchanged counters drop out of the delta entirely
    c2 = reg.counter("d_idle_total")
    c2.inc(1)
    marker2 = reg.marker()
    assert "d_idle_total" not in reg.since(marker2)


def test_snapshot_is_single_lock_consistent_under_writers():
    """Racing writers always bump two counters together; every
    snapshot must see them EQUAL — the /stats atomicity contract."""
    reg = MetricsRegistry()
    a = reg.counter("pair_a_total")
    b = reg.counter("pair_b_total")
    stop = threading.Event()

    def writer():
        while not stop.is_set():
            with reg._lock:
                a.inc()
                b.inc()

    threads = [threading.Thread(target=writer) for _ in range(3)]
    for t in threads:
        t.start()
    try:
        for _ in range(200):
            snap = reg.snapshot()
            assert snap["pair_a_total"].get((), 0) == snap[
                "pair_b_total"
            ].get((), 0)
    finally:
        stop.set()
        for t in threads:
            t.join()


def test_prometheus_exposition_golden():
    reg = MetricsRegistry()
    reg.counter("g_requests_total", "requests served").labels(
        route="/stats"
    ).inc(3)
    reg.gauge("g_depth", "queue depth").set(2)
    h = reg.histogram("g_wall_seconds", "wall", buckets=(0.5, 2.0))
    h.observe(0.25)
    h.observe(1.0)
    h.observe(9.0)
    assert reg.prometheus_text() == (
        "# HELP g_depth queue depth\n"
        "# TYPE g_depth gauge\n"
        "g_depth 2\n"
        "# HELP g_requests_total requests served\n"
        "# TYPE g_requests_total counter\n"
        'g_requests_total{route="/stats"} 3\n'
        "# HELP g_wall_seconds wall\n"
        "# TYPE g_wall_seconds histogram\n"
        'g_wall_seconds_bucket{le="0.5"} 1\n'
        'g_wall_seconds_bucket{le="2"} 2\n'
        'g_wall_seconds_bucket{le="+Inf"} 3\n'
        "g_wall_seconds_sum 10.25\n"
        "g_wall_seconds_count 3\n"
    )


def test_collector_samples_merge_into_snapshot():
    reg = MetricsRegistry()
    reg.collector(lambda: [("ext_depth", {"q": "main"}, 4)])
    snap = reg.snapshot()
    assert snap["ext_depth"][(("q", "main"),)] == 4


# ---------------------------------------------------------------------------
# per-metric histogram bucket overrides (ISSUE 12)
# ---------------------------------------------------------------------------
def test_histogram_bucket_override_semantics():
    from mythril_tpu.observe.registry import DEFAULT_BUCKETS

    reg = MetricsRegistry()
    # a default-bucket registration followed by an explicit override
    # while the series is still empty: the override wins
    h = reg.histogram("ob_wall_seconds")
    assert h.buckets == DEFAULT_BUCKETS
    h = reg.histogram("ob_wall_seconds", buckets=(0.001, 0.01, 0.1))
    assert h.buckets == (0.001, 0.01, 0.1)
    assert reg.buckets_of("ob_wall_seconds") == (0.001, 0.01, 0.1)
    # a later DEFAULT-bucket re-registration (a generic call site)
    # never clobbers the explicit ladder
    h = reg.histogram("ob_wall_seconds")
    assert h.buckets == (0.001, 0.01, 0.1)
    # once observations exist, a conflicting explicit ladder is
    # ignored — bucket counts are meaningless across a switch
    h.observe(0.05)
    h = reg.histogram("ob_wall_seconds", buckets=(1.0, 2.0))
    assert h.buckets == (0.001, 0.01, 0.1)


def test_job_latency_rebucket_exposition_golden():
    """The re-bucketed job-latency ladder: a ~1.9ms store hit and a
    ~21s cold walk (the BENCH_r06 spectrum) land in DISTINCT buckets
    — the default ladder crushed everything under 5ms into one. The
    exposition is pinned exactly."""
    from mythril_tpu.observe.registry import LATENCY_BUCKETS

    reg = MetricsRegistry()
    h = reg.histogram(
        "jl_latency_seconds", "submit-to-terminal latency",
        buckets=LATENCY_BUCKETS,
    )
    h.observe(0.0019)  # the warm store hit
    h.observe(0.0021)  # a second warm settle
    h.observe(21.0)  # the cold walk
    text = reg.prometheus_text()
    assert text == (
        "# HELP jl_latency_seconds submit-to-terminal latency\n"
        "# TYPE jl_latency_seconds histogram\n"
        'jl_latency_seconds_bucket{le="0.0005"} 0\n'
        'jl_latency_seconds_bucket{le="0.001"} 0\n'
        'jl_latency_seconds_bucket{le="0.002"} 1\n'
        'jl_latency_seconds_bucket{le="0.005"} 2\n'
        'jl_latency_seconds_bucket{le="0.01"} 2\n'
        'jl_latency_seconds_bucket{le="0.025"} 2\n'
        'jl_latency_seconds_bucket{le="0.05"} 2\n'
        'jl_latency_seconds_bucket{le="0.1"} 2\n'
        'jl_latency_seconds_bucket{le="0.25"} 2\n'
        'jl_latency_seconds_bucket{le="0.5"} 2\n'
        'jl_latency_seconds_bucket{le="1"} 2\n'
        'jl_latency_seconds_bucket{le="2.5"} 2\n'
        'jl_latency_seconds_bucket{le="5"} 2\n'
        'jl_latency_seconds_bucket{le="10"} 2\n'
        'jl_latency_seconds_bucket{le="30"} 3\n'
        'jl_latency_seconds_bucket{le="60"} 3\n'
        'jl_latency_seconds_bucket{le="120"} 3\n'
        'jl_latency_seconds_bucket{le="+Inf"} 3\n'
        "jl_latency_seconds_sum 21.004\n"
        "jl_latency_seconds_count 3\n"
    )


def test_service_and_solver_histograms_ride_their_ladders():
    """The two production histograms the satellite re-buckets: the
    service job-latency series and the per-query solver wall."""
    from mythril_tpu.observe.registry import (
        LATENCY_BUCKETS,
        SOLVER_WALL_BUCKETS,
        registry as global_registry,
    )
    from mythril_tpu.service.jobs import Job, JobQueue

    queue = JobQueue(4)
    job = Job(code_hex="6001")
    queue.submit(job)
    queue.settle(job, "done")
    assert global_registry().buckets_of(
        "mtpu_service_job_latency_seconds"
    ) == LATENCY_BUCKETS

    observe.record_query("host-cdcl", "sat", wall_s=0.002)
    assert global_registry().buckets_of(
        "mtpu_solver_query_seconds"
    ) == SOLVER_WALL_BUCKETS


# ---------------------------------------------------------------------------
# spans + flight recorder
# ---------------------------------------------------------------------------
def test_span_nesting_and_ordering_under_threads():
    recorder = flight_recorder()
    base = recorder.recorded
    seen = {}

    def work(tag):
        with trace(f"outer.{tag}"):
            with trace(f"inner.{tag}", step=1):
                time.sleep(0.01)
        seen[tag] = True

    threads = [
        threading.Thread(target=work, args=(i,), name=f"obs-w{i}")
        for i in range(3)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    spans = [
        s
        for s in recorder.tail(2048)
        if s.name.startswith(("outer.", "inner."))
    ]
    assert recorder.recorded - base >= 6
    by_name = {s.name: s for s in spans}
    for i in range(3):
        inner, outer = by_name[f"inner.{i}"], by_name[f"outer.{i}"]
        # nesting: the inner span's parent is ITS thread's outer span
        assert inner.parent == outer.sid
        assert outer.parent is None
        assert inner.tid == outer.tid == f"obs-w{i}"
        # ordering: children open after and close before their parent
        assert outer.t0 <= inner.t0 <= inner.t1 <= outer.t1
        assert inner.attrs == {"step": 1}


def test_trace_disabled_records_nothing():
    recorder = flight_recorder()
    observe.set_enabled(False)
    try:
        base = recorder.recorded
        with trace("never.recorded"):
            pass
        recorder.add("never.recorded.retro", 0.0, 1.0)
        assert recorder.recorded == base
    finally:
        observe.set_enabled(True)


def test_flight_recorder_is_bounded():
    recorder = FlightRecorder(capacity=32)
    for i in range(100):
        recorder.record(Span(i, None, "s", 0.0, 1.0, "t", None, None))
    assert len(recorder) == 32
    assert recorder.dropped == 100 - 32
    assert [s.sid for s in recorder.tail(3)] == [97, 98, 99]


def test_perfetto_trace_event_schema():
    spans = [
        Span(1, None, "wave.device", 10.0, 10.5, "main", "mesh-g0", None),
        Span(2, 1, "wave.harvest", 10.1, 10.2, "main", None, {"serial": 3}),
    ]
    doc = to_perfetto(spans)
    events = doc["traceEvents"]
    complete = [e for e in events if e["ph"] == "X"]
    meta = [e for e in events if e["ph"] == "M"]
    assert len(complete) == 2 and meta, events
    for e in complete:
        # the trace-event contract Perfetto loads: integral µs
        # timestamps/durations, pid/tid tracks, a name
        assert isinstance(e["ts"], int) and e["ts"] >= 0
        assert isinstance(e["dur"], int) and e["dur"] >= 1
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        assert e["name"]
    # the device-group track gets its own labeled thread
    names = {e["args"]["name"] for e in meta}
    assert "mesh-g0" in names and "main" in names
    # json-serializable end to end
    json.dumps(doc)


def test_overlap_fraction():
    def span(t0, t1):
        return Span(0, None, "wave.device", t0, t1, "t", None, None)

    # [0,10] and [5,15]: covered 15s, overlapped 5s
    assert overlap_fraction([span(0, 10), span(5, 15)]) == round(5 / 15, 4)
    # disjoint spans never overlap
    assert overlap_fraction([span(0, 1), span(2, 3)]) == 0.0
    # a lone span has nothing to overlap with
    assert overlap_fraction([span(0, 10)]) == 0.0


def test_flight_dump_on_injected_mesh_degradation(tmp_path):
    """A MESH_GROUP_DEGRADED record auto-dumps the flight recorder
    into the observe directory (the post-mortem timeline)."""
    from mythril_tpu.parallel.topology import FailureDomain

    observe.reset_auto_dumps()
    observe.configure(out_dir=str(tmp_path))
    try:
        with trace("pre.fault"):
            pass
        FailureDomain(0).record_degraded(2, detail="injected by test")
        dumps = [
            f for f in os.listdir(tmp_path)
            if f.startswith("flight-mesh-group-degraded")
        ]
        assert dumps, os.listdir(tmp_path)
        doc = json.loads((tmp_path / dumps[0]).read_text())
        assert doc["traceEvents"]
        # the mesh fault also moved the registry's per-group counters
        assert (
            registry().value(
                "mtpu_mesh_group_faults_total", group="mesh-g0"
            )
            >= 1
        )
    finally:
        observe.configure(out_dir=None)


# ---------------------------------------------------------------------------
# solver attribution
# ---------------------------------------------------------------------------
def test_solver_attribution_table():
    marker = observe.solver_marker()
    observe.record_query("host-cdcl", "sat", 0.25)
    observe.record_query("host-cdcl", "unsat", 0.05)
    observe.record_query("device-portfolio", "sat", 1.5, hop=1)
    table = observe.solver_attribution(marker)
    assert table["host-cdcl"]["queries"] == 2
    assert table["host-cdcl"]["verdicts"] == {"sat": 1, "unsat": 1}
    assert abs(table["host-cdcl"]["wall_s"] - 0.3) < 1e-6
    assert table["device-portfolio"]["escalations"] == 1
    # disabled: nothing records
    observe.set_enabled(False)
    try:
        marker2 = observe.solver_marker()
        observe.record_query("host-cdcl", "sat", 1.0)
        assert observe.solver_attribution(marker2) == {}
    finally:
        observe.set_enabled(True)


def test_check_terms_records_attribution():
    """The real solver funnel tags its verdicts: a trivial UNSAT pair
    through check_terms lands in the host-cdcl row."""
    from mythril_tpu.laser.smt import terms
    from mythril_tpu.laser.smt.solver.solver import check_terms

    x = terms.bv_var("obs_x", 8)
    marker = observe.solver_marker()
    verdict, _model = check_terms(
        [terms.eq(x, terms.bv_const(1, 8)),
         terms.eq(x, terms.bv_const(2, 8))],
        timeout_ms=5000,
    )
    assert verdict == "unsat"
    table = observe.solver_attribution(marker)
    assert table["host-cdcl"]["verdicts"].get("unsat", 0) >= 1


# ---------------------------------------------------------------------------
# routing feature log
# ---------------------------------------------------------------------------
def test_routing_record_jsonl_schema(tmp_path):
    from mythril_tpu.observe.routing import RECORD_KEYS

    observe.configure(out_dir=str(tmp_path))
    try:
        rec = observe.routing_log().record(
            contract="Tiny",
            code_hash="ab" * 32,
            features=observe.routing_features_for(TINY),
            outcome=observe.routing_outcome_for(
                {
                    "name": "Tiny",
                    "issues": [{"swc-id": "110"}],
                    "states": 12,
                    "wall_s": 0.5,
                    "error": None,
                    "complete": True,
                    "owned": True,
                }
            ),
        )
        line = (tmp_path / "routing_features.jsonl").read_text()
        parsed = json.loads(line.strip().splitlines()[-1])
    finally:
        observe.configure(out_dir=None)
    from mythril_tpu.observe.routing import (
        SCHEMA_VERSION as ROUTING_SCHEMA_VERSION,
    )

    assert tuple(sorted(parsed)) == tuple(sorted(RECORD_KEYS))
    assert parsed == json.loads(json.dumps(rec, sort_keys=True))
    # the routing log versions its records independently of the
    # registry schema (v2 added the taint/value-set feature block)
    assert parsed["schema_version"] == ROUTING_SCHEMA_VERSION
    feats = parsed["features"]
    # the cost-model features ROADMAP item 5 trains on
    for key in ("code_bytes", "storage_op_density", "call_op_density"):
        assert key in feats, feats
    out = parsed["outcome"]
    assert out["route"] == "device-owned"
    assert out["issues"] == 1 and out["wall_s"] == 0.5


def test_routing_route_classification():
    assert (
        observe.routing_outcome_for({"skipped": "deadline-expired"})["route"]
        == "skipped"
    )
    assert (
        observe.routing_outcome_for({"owned": True})["route"]
        == "device-owned"
    )
    assert observe.routing_outcome_for({})["route"] == "host-walk"


# ---------------------------------------------------------------------------
# ExploreStats merge policy (the counter-drift satellite)
# ---------------------------------------------------------------------------
def test_merge_policy_covers_every_field():
    from mythril_tpu.laser.batch.explore import MERGE_POLICY, ExploreStats

    fields = set(ExploreStats().as_dict())
    policy = set(MERGE_POLICY)
    # every stat field has an EXPLICIT policy; the only extra policy
    # entry is the optional halt_reason the stats dict may carry
    assert fields - policy == set(), f"unmapped stats: {fields - policy}"
    assert policy - fields == {"halt_reason"}, policy - fields
    assert set(MERGE_POLICY.values()) <= {"sum", "max", "last", "derived"}


def test_merge_stats_semantics():
    from mythril_tpu.laser.batch.explore import merge_stats

    dst = {}
    merge_stats(dst, {
        "waves": 3, "arena_nodes": 10, "wall_s": 5.0,
        "halt_reason": "stop-event", "pipelined": 1,
    })
    merge_stats(dst, {
        "waves": 2, "arena_nodes": 7, "wall_s": 9.0,
        "halt_reason": "deadline-expired", "pipelined": 0,
    })
    assert dst["waves"] == 5  # sum
    assert dst["arena_nodes"] == 10  # max
    assert "wall_s" not in dst  # derived: recomputed by the caller
    assert dst["halt_reason"] == "deadline-expired"  # last
    assert dst["pipelined"] == 1  # max: any pipelined chunk marks it


def test_scheduler_merge_rides_the_policy():
    """The mesh scheduler's fold uses the explicit policy (this is the
    drift regression: a summed high-water mark would exceed the max)."""
    from mythril_tpu.parallel.scheduler import CorpusScheduler

    sched = CorpusScheduler.__new__(CorpusScheduler)
    sched._merged_stats = {}
    sched._merge_stats({"waves": 1, "waves_inflight_max": 2, "spec_pruned_phases": 5})
    sched._merge_stats({"waves": 1, "waves_inflight_max": 2, "spec_pruned_phases": 3})
    assert sched._merged_stats["waves"] == 2
    assert sched._merged_stats["waves_inflight_max"] == 2
    assert sched._merged_stats["spec_pruned_phases"] == 5


# ---------------------------------------------------------------------------
# PhaseProfile: registry-backed view, byte-compatible shape
# ---------------------------------------------------------------------------
def test_phase_profile_view_and_registry_backing():
    from mythril_tpu.support.phase_profile import PhaseProfile

    profile = PhaseProfile()
    profile.reset()
    hist = registry().histogram("mtpu_phase_wall_seconds")
    before = hist.labels(phase="obs_test").count
    with profile.measure("obs_test"):
        pass
    profile.add("obs_test", 0.75, n=2)
    snap = profile.as_dict()
    assert snap["obs_test"]["count"] == 3
    assert snap["obs_test"]["wall_s"] >= 0.75
    assert "obs_test" in str(profile)
    # the registry kept the cumulative series (the /metrics view)...
    assert hist.labels(phase="obs_test").count == before + 3
    # ...while the per-contract view resets to empty
    profile.reset()
    assert profile.as_dict() == {}
    assert hist.labels(phase="obs_test").count == before + 3


def test_phase_profile_measure_follows_a_registry_swap():
    import importlib

    from mythril_tpu.support.phase_profile import PhaseProfile

    registry_module = importlib.import_module("mythril_tpu.observe.registry")

    profile = PhaseProfile()
    with profile.measure("obs_swap"):  # caches the old registry's handle
        pass
    old = registry_module._REGISTRY
    fresh = registry_module.reset_registry()
    try:
        with profile.measure("obs_swap"):
            time.sleep(0.001)
        series = fresh.histogram("mtpu_phase_wall_seconds").labels(
            phase="obs_swap"
        )
        assert series.count == 1
        assert series.sum >= 0.001
        assert fresh.snapshot()["mtpu_phase_wall_seconds"][
            (("phase", "obs_swap"),)
        ]["count"] == 1
    finally:
        registry_module._REGISTRY = old


# ---------------------------------------------------------------------------
# registry-vs-legacy equality on a real explorer run
# ---------------------------------------------------------------------------
def test_explorer_publishes_registry_equal_to_legacy_stats():
    from mythril_tpu.laser.batch.explore import (
        MERGE_POLICY,
        DeviceCorpusExplorer,
    )

    marker = registry().marker()
    explorer = DeviceCorpusExplorer(
        [TINY], lanes_per_contract=8, waves=2, steps_per_wave=64,
        budget_s=30,
    )
    stats = explorer.run()["stats"]
    delta = registry().since(marker)
    assert stats["waves"] >= 1 and stats["device_steps"] > 0
    for field, policy in MERGE_POLICY.items():
        value = stats.get(field)
        if not isinstance(value, (int, float)):
            continue
        if policy == "sum":
            got = delta.get(f"mtpu_explore_{field}_total", {}).get((), 0)
            assert got == pytest.approx(value), (field, got, value)
        elif policy == "max":
            got = registry().value(f"mtpu_explore_{field}_max")
            assert got >= value, (field, got, value)
    # the run left its span trail
    names = {s.name for s in flight_recorder().tail(4096)}
    assert {"explore.run", "wave.dispatch", "wave.device"} <= names


# ---------------------------------------------------------------------------
# service: atomic /stats + /metrics + /trace + drain flush
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def live_server():
    from mythril_tpu.service.engine import ServiceConfig
    from mythril_tpu.service.server import AnalysisServer

    config = ServiceConfig(
        stripes=2, lanes_per_stripe=4, steps_per_wave=64, max_waves=1,
        host_walk=False, coalesce_wait_s=0.01,
    )
    server = AnalysisServer(config).start()
    yield server
    server.close()


def _get(url: str):
    import urllib.request

    with urllib.request.urlopen(url, timeout=30) as resp:
        return resp.headers.get("Content-Type", ""), resp.read()


def test_service_stats_metrics_trace_endpoints(live_server):
    from mythril_tpu.service.client import ServiceClient
    from mythril_tpu.service.engine import STATS_SCHEMA_VERSION

    client = ServiceClient(live_server.url)
    job_id = client.submit(TINY)
    report = client.report(job_id, wait_s=180.0)
    assert report["state"] == "done", report

    stats = client.stats()
    assert stats["schema_version"] == STATS_SCHEMA_VERSION
    assert stats["waves"]["count"] >= 1
    assert stats["observe"]["enabled"] is True

    ctype, body = _get(live_server.url + "/metrics")
    assert ctype.startswith("text/plain")
    text = body.decode()
    assert "# TYPE mtpu_service_waves_total counter" in text
    assert "mtpu_service_admissions_total" in text
    # the engine's series carry its instance label
    eid = live_server.engine._eid
    assert f'mtpu_service_waves_total{{engine="{eid}"}}' in text

    _ctype, body = _get(live_server.url + "/trace?n=64")
    doc = json.loads(body)
    assert doc["schema_version"] == SCHEMA_VERSION
    names = {s["name"] for s in doc["spans"]}
    assert "service.wave.dispatch" in names

    _ctype, body = _get(live_server.url + "/trace?format=perfetto")
    assert json.loads(body)["traceEvents"]


def test_service_drain_flushes_flight_recorder(tmp_path):
    from mythril_tpu.service.engine import AnalysisEngine, ServiceConfig

    engine = AnalysisEngine(
        ServiceConfig(
            stripes=2, lanes_per_stripe=4, checkpoint_dir=str(tmp_path)
        )
    )
    engine.drain()
    dump = engine.flight_dump_path
    assert dump and os.path.exists(dump)
    assert json.loads(open(dump).read()).get("traceEvents") is not None
    assert engine.stats()["observe"]["flight_dump"] == dump
