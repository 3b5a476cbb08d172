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
    return [
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": []}]},
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
    # to 6.0 after the search executable
    assert out["idle_gaps"][0] == ["after jit__spec_sym_run_impl(7)", pytest.approx(5.0)]
    assert out["idle_gaps"][1] == ["after jit__search_rows(2)", pytest.approx(3.0)]
    assert sum(g for _n, g in out["idle_gaps"]) == pytest.approx(12.0 - 3.5)


def test_two_chips_average():
    planes = _planes()
    second = dict(planes[1], name="/device:TPU:1")
    out = tracing.reduce(planes + [second], 10.0, {"wave": ["_spec_sym_run_impl"]})
    assert out["chips"] == 2
    assert out["busy_s"] == pytest.approx(3.5)
    assert out["kernel_s"]["wave"] == pytest.approx(2.5)


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

    monkeypatch.setattr(tracing, "read_planes", lambda path: _planes())
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
    assert len(breakdown["device_ops"]) <= 10
