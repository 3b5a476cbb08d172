"""`myth observe` operator tooling (observe/opstool.py, tier-1
`observe` marker): the Prometheus text parser, the top/report
renderers, and the bench-record compare gate — including the
acceptance contract that the committed BENCH_r04 -> r06 trajectory
reproduces clean while an injected regression exits the gate dirty."""

from __future__ import annotations

import json
import os

import pytest

from mythril_tpu.observe import opstool

pytestmark = pytest.mark.observe

REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def bench_path(n: int) -> str:
    return os.path.join(REPO, f"BENCH_r{n:02d}.json")


def test_parse_prometheus_families_and_labels():
    text = "\n".join([
        "# HELP mtpu_x_total help",
        "# TYPE mtpu_x_total counter",
        'mtpu_x_total{origin="host-cdcl",verdict="sat"} 3',
        'mtpu_x_total{origin="memo",verdict="sat"} 2',
        "mtpu_health_state 1",
        "junk line without a value",
    ])
    parsed = opstool.parse_prometheus(text)
    assert opstool.family_total(parsed, "mtpu_x_total") == 5
    assert opstool.family_total(
        parsed, "mtpu_x_total", origin="memo"
    ) == 2
    assert opstool.family_total(parsed, "mtpu_health_state") == 1


def test_render_top_shows_health_queue_and_tiers():
    stats = {
        "uptime_s": 12.5,
        "health": {
            "state": "degraded",
            "ready": False,
            "reasons": ["slo-degraded:warm-settle-p95"],
            "not_ready_reasons": ["arena-warming"],
            "objectives": [
                {"objective": "warm-settle-p95", "state": "degraded",
                 "burn_short": 2.5, "burn_long": 1.2},
            ],
        },
        "queue": {"depth": 4, "capacity": 8, "accepted": 30,
                  "rejected_full": 1, "rejected_draining": 0,
                  "jobs": {"done": 25, "failed": 1}},
        "arena": {"lanes": 32, "lanes_busy": 16, "jobs_resident": 2,
                  "max_jobs_resident": 4},
        "waves": {"count": 90, "rate_per_s": 12.0,
                  "warm_wave_s": 0.01, "cold_wave_s": 4.2},
        "store": {"answered": 7},
        "static": {"static_answered": 3},
        "solver": {"loss": {"GATE_DISABLED": 10}},
        "device": {
            "arena": {"occupancy": 0.5},
            "host_rss_bytes": 200 << 20,
            "wave_overlap_frac": 0.4,
            "kernel_cache": {"size": 2, "pinned": 1},
        },
    }
    frame = opstool.render_top(stats)
    assert "DEGRADED" in frame
    assert "arena-warming" in frame
    assert "warm-settle-p95" in frame
    assert "4/8" in frame  # queue bar
    assert "store-hit=7" in frame and "static-answer=3" in frame
    assert "GATE_DISABLED=10" in frame
    assert "overlap=0.4" in frame


def test_render_report_markdown_and_html():
    routing = [
        {"outcome": {"route": "store-hit", "wall_s": 0.002}},
        {"outcome": {"route": "store-hit", "wall_s": 0.003}},
        {"outcome": {"route": "host-walk", "wall_s": 2.5}},
    ]
    journeys = [
        {"journey_id": "abc", "tiers": ["admission", "settle"],
         "wall_s": 0.01},
    ]
    md = opstool.render_report(
        routing_records=routing, journeys=journeys,
    )
    assert "| store-hit | 2 |" in md
    assert "| host-walk | 1 |" in md
    assert "admission -> settle" in md
    html = opstool.render_report(
        routing_records=routing, fmt="html"
    )
    assert html.startswith("<!doctype html>")
    assert "store-hit" in html


def test_compare_reproduces_r04_to_r06_trajectory(tmp_path):
    """The acceptance contract: the committed records gate clean, a
    timed-out round (rc 124, parsed=null) is skipped with a note, and
    the stable-field trajectory is present."""
    timed_out = tmp_path / "BENCH_r05.json"
    timed_out.write_text(json.dumps({"n": 5, "rc": 124, "parsed": None}))
    records = [
        opstool.load_bench_record(bench_path(4)),
        opstool.load_bench_record(str(timed_out)),
        opstool.load_bench_record(bench_path(6)),
    ]
    result = opstool.compare_records(records)
    assert result["labels"] == ["r04", "r06"]
    assert result["skipped"] == ["r05"]
    assert result["regressions"] == []
    traj = result["trajectory"]["scaling_ratio_4x_steps"]
    assert traj == [3.62, 3.81]
    rendered = opstool.render_compare(result)
    assert "r04 -> r06" in rendered
    assert "no regressions on stable fields" in rendered
    # cross-backend fields ride the table but are exempt from gating
    assert "device_verdict_share" in result["exempt_fields"]


def test_compare_full_committed_history_gates_clean():
    import glob

    paths = sorted(glob.glob(os.path.join(REPO, "BENCH_r*.json")))
    assert len(paths) >= 2
    records = [opstool.load_bench_record(p) for p in paths]
    result = opstool.compare_records(records)
    assert result["regressions"] == []


def test_injected_regression_fails_the_gate(tmp_path):
    _label, r06 = opstool.load_bench_record(bench_path(6))
    bad = dict(r06, scaling_ratio_4x_steps=1.0, store_hit_rate=0.1)
    path = tmp_path / "BENCH_bad.json"
    path.write_text(json.dumps({"n": 7, "parsed": bad}))
    records = [
        opstool.load_bench_record(bench_path(6)),
        opstool.load_bench_record(str(path)),
    ]
    result = opstool.compare_records(records)
    fields = {r["field"] for r in result["regressions"]}
    assert "scaling_ratio_4x_steps" in fields
    assert "store_hit_rate" in fields
    rendered = opstool.render_compare(result)
    assert "REGRESSION scaling_ratio_4x_steps" in rendered
    # a lower-is-better regression: warm hits getting slower
    worse = dict(r06, warm_hit_p50_s=0.5)
    path.write_text(json.dumps({"n": 7, "parsed": worse}))
    result = opstool.compare_records([
        opstool.load_bench_record(bench_path(6)),
        opstool.load_bench_record(str(path)),
    ])
    assert {r["field"] for r in result["regressions"]} == {
        "warm_hit_p50_s"
    }


def test_threshold_scale_loosens_the_gate(tmp_path):
    _label, r06 = opstool.load_bench_record(bench_path(6))
    slightly_worse = dict(
        r06, scaling_ratio_4x_steps=r06["scaling_ratio_4x_steps"] * 0.8
    )
    path = tmp_path / "BENCH_meh.json"
    path.write_text(json.dumps({"n": 7, "parsed": slightly_worse}))
    records = [
        opstool.load_bench_record(bench_path(6)),
        opstool.load_bench_record(str(path)),
    ]
    assert opstool.compare_records(records)["regressions"]
    assert not opstool.compare_records(
        records, threshold_scale=2.0
    )["regressions"]


def test_observe_cli_command_registered():
    from mythril_tpu.interfaces.cli import COMMAND_LIST, build_parser

    assert "observe" in COMMAND_LIST
    parser = build_parser()
    args = parser.parse_args(
        ["observe", "compare", "a.json", "b.json", "--fail-on-regression"]
    )
    assert args.command == "observe"
    assert args.observe_mode == "compare"
    assert args.records == ["a.json", "b.json"]
    assert args.fail_on_regression is True
