"""`myth solverlab` replay-lab suite (analysis/solverlab.py; tier-1
`solverlab` marker).

The acceptance bar (ISSUE 8): a corpus captured from the fault-suite
contracts replays offline with 100% host-engine agreement against the
live verdicts, the capture->replay pipeline is deterministic (same
verdicts, same content addresses across captures), sharding partitions
the corpus exactly, filters select by loss reason / origin, and the
CLI surface parses.
"""

import json

import pytest

from mythril_tpu import observe
from mythril_tpu.analysis import solverlab
from mythril_tpu.observe import querylog

pytestmark = pytest.mark.solverlab

#: the pipeline suite's fault-suite fixtures (same shapes, same seeds)
#: — GATED's taken direction needs a solver-derived flip witness, so
#: capturing its exploration yields real flip-frontier queries
GATED = "60003560f81c604214600d57005b600160005500"
BRANCHER = "600035600757005b600160005500"


@pytest.fixture(autouse=True)
def _no_capture_leak():
    querylog.configure_capture(None)
    yield
    querylog.configure_capture(None)


def _capture_fault_suite(out_dir) -> list:
    from mythril_tpu.laser.batch.explore import DeviceCorpusExplorer
    from mythril_tpu.support.model import clear_cache
    from mythril_tpu.support.support_args import args as support_args

    # the get_model memo would swallow repeat queries before they
    # reach check_terms (and so the capture hook); every capture run
    # starts from a cold memo, exactly like a fresh process
    clear_cache()
    # the harness's host-first funnel (conftest): a MythrilAnalyzer built
    # earlier in this process pushes device_first=True into the global
    # args, and a device-won flip carries no loss reason
    saved, support_args.device_first = support_args.device_first, False
    querylog.configure_capture(str(out_dir))
    try:
        ex = DeviceCorpusExplorer(
            [GATED, BRANCHER],
            lanes_per_contract=8,
            waves=3,
            steps_per_wave=64,
            transaction_count=1,
            seed=7,
        )
        ex.run()
    finally:
        querylog.configure_capture(None)
        support_args.device_first = saved
    return querylog.load_corpus(str(out_dir))


def test_fault_suite_replay_agrees_100_percent(tmp_path):
    corpus = _capture_fault_suite(tmp_path / "corpus")
    assert corpus, "the fault-suite exploration captured no queries"
    assert any(a["origin"] == "flip-frontier" for a in corpus)
    report = solverlab.run(str(tmp_path / "corpus"), engines=["host"])
    host = report["replay"]["host"]
    assert host["agreement"]["disagree"] == 0, report["disagreements"]
    assert host["agreement_pct"] == 100.0
    # host-won queries all carry a loss reason; the waterfall shows it
    assert report["loss_waterfall_sat"]
    assert sum(report["loss_waterfall_sat"].values()) == (
        report["live_verdicts"].get("sat", 0)
    )


def test_capture_replay_determinism(tmp_path):
    """Same exploration captured twice -> identical content addresses;
    same corpus replayed twice -> identical verdict tables."""
    first = _capture_fault_suite(tmp_path / "one")
    second = _capture_fault_suite(tmp_path / "two")
    assert {a["sha"] for a in first} == {a["sha"] for a in second}
    r1 = solverlab.replay_corpus(first, engines=["host"])
    r2 = solverlab.replay_corpus(first, engines=["host"])
    assert r1["replay"]["host"]["verdicts"] == r2["replay"]["host"]["verdicts"]
    assert r1["replay"]["host"]["agreement"] == r2["replay"]["host"]["agreement"]


def test_device_engine_replays_the_corpus(tmp_path):
    """The portfolio engine re-solves the captured flip queries on
    (CPU) device: any witness it finds passes the concrete soundness
    gate, and a miss counts as incomplete, never disagreement."""
    corpus = _capture_fault_suite(tmp_path / "corpus")
    report = solverlab.replay_corpus(
        corpus, engines=["device"], candidates=16, steps=64
    )
    device = report["replay"]["device"]
    assert device["agreement"]["disagree"] == 0, report["disagreements"]
    assert sum(device["verdicts"].values()) == len(corpus)


def test_shard_partitions_exactly(tmp_path):
    corpus = [
        {"sha": f"{i:064x}", "verdict": "sat", "origin": "module",
         "program": {"nodes": [], "roots": []}}
        for i in range(17)
    ]
    shards = [
        solverlab.shard_corpus(corpus, solverlab.parse_shard(f"{i}/4"))
        for i in range(4)
    ]
    seen = [a["sha"] for shard in shards for a in shard]
    assert sorted(seen) == sorted(a["sha"] for a in corpus)
    assert solverlab.parse_shard(None) is None
    with pytest.raises(ValueError):
        solverlab.parse_shard("4/4")
    with pytest.raises(ValueError):
        solverlab.parse_shard("nope")


def test_filters_select_by_reason_and_origin(tmp_path):
    corpus_dir = tmp_path / "corpus"
    _capture_fault_suite(corpus_dir)
    everything = querylog.load_corpus(str(corpus_dir))
    reasons = {a["loss_reason"] for a in everything if a["loss_reason"]}
    assert reasons  # host-won queries carry reasons
    reason = sorted(reasons)[0]
    filtered = querylog.load_corpus(str(corpus_dir), reason=reason)
    assert filtered and all(
        a["loss_reason"] == reason for a in filtered
    )
    flips = querylog.load_corpus(str(corpus_dir), origin="flip-frontier")
    assert all(a["origin"] == "flip-frontier" for a in flips)
    none = querylog.load_corpus(str(corpus_dir), origin="no-such-origin")
    assert none == []


def test_report_mode_skips_solving(tmp_path):
    corpus_dir = tmp_path / "corpus"
    _capture_fault_suite(corpus_dir)
    report = solverlab.run(str(corpus_dir), mode="report")
    assert report["mode"] == "report"
    assert "replay" not in report
    assert report["queries"] >= 1
    assert set(report) >= {
        "live_verdicts", "origins", "buckets",
        "loss_waterfall", "loss_waterfall_sat",
    }
    # the text renderer never chokes on a report-mode dict
    text = solverlab.render_text(report)
    assert "loss waterfall" in text


def test_replay_does_not_mutate_the_corpus(tmp_path):
    corpus_dir = tmp_path / "corpus"
    _capture_fault_suite(corpus_dir)
    before = {
        a["sha"]: len(a["observations"])
        for a in querylog.load_corpus(str(corpus_dir))
    }
    solverlab.run(str(corpus_dir), engines=["host"])
    after = {
        a["sha"]: len(a["observations"])
        for a in querylog.load_corpus(str(corpus_dir))
    }
    assert before == after


def test_cli_surface_parses():
    from mythril_tpu.interfaces.cli import build_parser

    parser = build_parser()
    args = parser.parse_args(
        [
            "solverlab", "replay", "--corpus", "/tmp/x",
            "--engines", "host,device", "--filter", "reason=GATE_DISABLED",
            "--shard", "0/2", "--timeout-ms", "5000", "--json", "--strict",
        ]
    )
    assert args.command == "solverlab"
    assert args.mode == "replay"
    assert args.shard == "0/2"
    args = parser.parse_args(["solverlab", "report", "--corpus", "/tmp/x"])
    assert args.mode == "report"
    # the analyze surface grew the capture flag
    args = parser.parse_args(
        ["analyze", "-c", "33ff", "--capture-queries", "/tmp/q"]
    )
    assert args.capture_queries == "/tmp/q"


def test_run_report_is_json_serializable(tmp_path):
    corpus_dir = tmp_path / "corpus"
    _capture_fault_suite(corpus_dir)
    report = solverlab.run(str(corpus_dir), engines=["host"])
    blob = json.dumps(report, sort_keys=True)
    assert json.loads(blob)["replay"]["host"]["agreement_pct"] == 100.0
