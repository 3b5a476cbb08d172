"""The trace reduction on a small recorded slice (plain data in the
shape `tracing.read_planes` returns)."""

import pytest

import tracing


def _planes():
    ops = [
        ("fusion.1", 0.0, 1.0),
        ("fusion.2", 0.5, 1.5),  # overlaps the first: busy once
        ("while.3", 2.0, 3.0),
        ("fusion.4", 6.0, 7.0),
    ]
    modules = [
        ("jit__spec_sym_run_impl(7)", 0.0, 1.5),
        ("jit__search_rows(2)", 2.0, 3.0),
        ("jit__spec_sym_run_impl(7)", 6.0, 7.0),
    ]
    # the program's host spans, on two threads: a walk across the gap
    # from 3.0 to 6.0 with a wait for it beside it, and a wait that
    # ends where the first busy stretch begins
    host = [
        {"name": "python3", "events": []},
        {"name": "walker-0", "events": [("service.host.walk", 2.5, 6.5)]},
        {"name": "walker-1", "events": [("service.host.lock_wait", -1.0, 0.0),
                                         ("service.host.lock_wait", 2.8, 6.2)]},
    ]
    return [
        {"name": "/host:CPU", "lines": host},
        {"name": "/device:TPU:0", "lines": [
            {"name": "Steps", "events": []},
            {"name": tracing.OPS_LINE, "events": ops},
            {"name": tracing.MODULES_LINE, "events": modules},
        ]},
    ]


def test_busy_union_and_kernel_time():
    out = tracing.reduce(_planes(), 12.0, {"wave": ["_spec_sym_run_impl"]})
    assert out["chips"] == 1
    assert out["busy_s"] == pytest.approx(3.5)
    assert out["window_s"] == 12.0
    assert out["kernel_s"]["wave"] == pytest.approx(2.5)
    assert out["kernel_events"]["wave"] == 2
    names = [name for name, _ in out["device_ops"]]
    assert names[0] in {"fusion.1", "fusion.2", "while.3", "fusion.4"}
    # the idle tail to the slice's end (7 -> 12), then the gap from 3.0
    # to 6.0 after the search executable, with a walk and its wait open
    assert out["idle_gaps"][0] == [
        "no host span | after jit__spec_sym_run_impl(7)", pytest.approx(5.0)
    ]
    assert out["idle_gaps"][1] == [
        "service.host.lock_wait x1 3.00s + service.host.walk x1 3.00s | after jit__search_rows(2)",
        pytest.approx(3.0),
    ]
    assert sum(g for _n, g in out["idle_gaps"]) == pytest.approx(12.0 - 3.5)


def test_two_chips_average():
    planes = _planes()
    second = dict(planes[1], name="/device:TPU:1")
    out = tracing.reduce(planes + [second], 10.0, {"wave": ["_spec_sym_run_impl"]})
    assert out["chips"] == 2
    assert out["busy_s"] == pytest.approx(3.5)
    assert out["kernel_s"]["wave"] == pytest.approx(2.5)


def test_idle_gaps_labelled_by_host_spans():
    """A span counts for a gap where it is open at some point of it; one
    that ends where the gap begins, or begins where it ends, does not.
    Each name gives its count and the seconds of the gap it covers."""
    planes = _planes()
    planes[0]["lines"].append({"name": "walker-2", "events": [
        ("service.host.walk", 1.0, 1.5),   # ends where the gap from 1.5 begins
        ("service.host.walk", 2.0, 2.9),   # inside a busy stretch
        ("service.host.walk", 7.5, 8.0),   # inside the idle tail
        ("service.host.walk", 12.0, 13.0),  # after the slice
    ]})
    # a second walk in the tail, overlapping the first: counted, and
    # the seconds they cover taken once
    planes[0]["lines"].append({"name": "walker-3", "events": [
        ("service.host.walk", 7.8, 9.0),
    ]})
    out = tracing.reduce(planes, 12.0, {"wave": ["_spec_sym_run_impl"]})
    gaps = dict(out["idle_gaps"])
    assert gaps["service.host.walk x2 1.50s | after jit__spec_sym_run_impl(7)"] == (
        pytest.approx(5.0))
    assert gaps["service.host.lock_wait x1 3.00s + service.host.walk x1 3.00s | after "
                "jit__search_rows(2)"] == pytest.approx(3.0)
    assert gaps["no host span | after jit__spec_sym_run_impl(7)"] == (
        pytest.approx(0.5))
    assert len(gaps) == 3
    assert tracing.host_spans(planes[1:]) == []


def test_read_planes_keeps_named_host_spans(tmp_path):
    """A recorded CPU trace: the host plane keeps the named spans alone,
    their arguments stripped from the name; the rest is dropped."""
    import time

    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    with jax.profiler.TraceAnnotation("service.host.walk", job=3):
        time.sleep(0.05)
    with jax.profiler.TraceAnnotation("service.host.other", job=3):
        time.sleep(0.01)
    jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    spans = tracing.host_spans(tracing.read_planes(path, ["service.host.walk"]))
    assert [name for name, _s, _e in spans] == ["service.host.walk"]
    (_name, start, end), = spans
    assert end - start == pytest.approx(0.05, abs=0.03)
    assert tracing.host_spans(tracing.read_planes(path)) == []


def test_no_device_plane_reads_nothing():
    assert tracing.reduce(_planes()[:1], 10.0, {"wave": ["x"]}) == {}


def test_per_layer_readers_on_a_recorded_slice(monkeypatch, tmp_path):
    """The traced run's reduction end to end: the cell's readers on a
    recorded slice and window counters."""
    import harness
    import run as bench_run

    class Cut:
        error = None
        window_s = 10.0
        out_dir = tmp_path / "trace"
        t0, t1 = 0.0, 10.0

        def xplane(self):
            return tmp_path / "slice.xplane.pb"

    monkeypatch.setattr(tracing, "read_planes", lambda path, keep: _planes())
    journey = [
        {"t": 1.0, "tier": "admission", "event": "submitted"},
        {"t": 1.5, "tier": "lane-grant", "event": "granted"},
        {"t": 2.0, "tier": "host-walk", "event": "start"},
        {"t": 9.0, "tier": "host-walk", "event": "done"},
    ]
    window = {
        "wall_s": 51.0, "device_steps": 5100, "compiles": {"misses": 0},
        "latencies": [8.0, 9.0, 10.0, 30.0], "journeys": [journey],
        "solver": {"host-cdcl": {"queries": 4, "wall_s": 0.1}},
    }
    device = {}
    bench = harness.definition(harness.BENCH.parent)
    metrics, breakdown = bench_run.per_layer(
        bench, "serve-t2.fresh", window, Cut(),
        {"hbm_bytes_per_s": 819e9}, device,
    )
    assert device == {"busy_s": pytest.approx(3.5), "window_s": 10.0}
    value = {k: v["value"] for k, v in metrics.items()}
    assert value["lane_steps_per_s"] == 100.0
    assert value["device_idle_pct"] == pytest.approx(65.0)
    assert value["window_compiles"] == 0.0
    assert value["host_walk_p50_s"] == 7.0
    assert value["queue_wait_p50_s"] == 0.5
    assert value["settle_p50_s"] == 9.5
    assert value["host_cdcl_ms_per_query"] == pytest.approx(25.0)
    assert set(breakdown) == {"device_ops", "idle_gaps"}
    assert [
        "service.host.lock_wait x1 3.00s + service.host.walk x1 3.00s | after jit__search_rows(2)",
        pytest.approx(3.0),
    ] in breakdown["idle_gaps"]
    assert len(breakdown["device_ops"]) <= 10
