"""A whole run of the serve cell with the look for a chip skipped, on the
CPU at a small size, and the timed path broken underneath: an answer
altered where it is produced, a walk that returns its state unchanged,
a walk that drops one finding, or an answer that never comes, makes
`correct` false. The same run unbroken is correct.

The mix is cut to the fixture families whose walks end well inside
their limit on a CPU, so that every report is held to its planted
weaknesses; the engine, its waves and its walks are the cell's own."""

import pytest

import generate
import harness
import run

ROOT = harness.BENCH.parent
#: families whose 8 s walks end in about a second on a CPU
QUICK = ("exceptions.sol", "origin.sol", "suicide.sol", "kinds_of_calls.sol")


@pytest.fixture
def serve(monkeypatch):
    families = [f for f in generate.contracts.fixtures() if f[0] in QUICK]
    monkeypatch.setattr(generate.contracts, "fixtures", lambda: families)
    monkeypatch.setattr(harness, "device_info", lambda chips: {
        "platform": "cpu", "kind": "TPU v5 lite", "count": 1,
    })
    real = harness.load_json

    def load(path):
        data = real(path)
        if path.name == "fresh.json":
            data["clients"] = 2
        return data

    monkeypatch.setattr(harness, "load_json", load)
    from mythril_tpu.analysis import corpus
    from mythril_tpu.store.store import close_stores

    # each run starts from an empty verdict store, as a new process does
    close_stores()
    return corpus


def _run(monkeypatch):
    monkeypatch.chdir(ROOT)
    opts = run.parse(["--workload", "serve-t2.fresh", "--seed", str(2**32 + 9),
                      "--seconds", "6", "--trace", "0"])
    return run.run_cell(opts, ROOT)


def _break(monkeypatch, corpus, change):
    original = corpus.analyze_one_payload

    def broken(payload):
        return change(original(payload))

    monkeypatch.setattr(corpus, "analyze_one_payload", broken)


def test_sound_run_is_correct(serve, monkeypatch):
    result = _run(monkeypatch)
    assert result["attempted"] >= 2
    assert result["compared"]["missed_planted"]["value"] == 0
    assert result["correct"] is True
    assert result["metrics"]["contracts_per_min"]["value"] > 0


def test_altered_answer_is_not_correct(serve, monkeypatch):
    def moved(out):
        for issue in out.get("issues") or []:
            issue["address"] += 1
        return out

    _break(monkeypatch, serve, moved)
    result = _run(monkeypatch)
    assert result["compared"]["unwitnessed_findings"]["value"] >= 1
    assert result["correct"] is False


@pytest.mark.parametrize("keep", [0, -1], ids=["state-unchanged", "one-dropped"])
def test_dropped_findings_are_not_correct(serve, monkeypatch, keep):
    """The walk returns no finding, or all but its last one: every
    finding left is sound, and only completeness sees the fault."""

    def dropped(out):
        out["issues"] = (out.get("issues") or [])[:keep]
        return out

    _break(monkeypatch, serve, dropped)
    result = _run(monkeypatch)
    assert result["compared"]["unwitnessed_findings"]["value"] == 0
    assert result["compared"]["missed_planted"]["value"] >= 1
    assert result["correct"] is False


def test_missing_answer_is_not_correct(serve, monkeypatch):
    def crashed(out):
        return dict(out, issues=[], error="walk crashed")

    _break(monkeypatch, serve, crashed)
    result = _run(monkeypatch)
    assert result["compared"]["failed_answers"]["value"] >= 1
    assert result["correct"] is False


def test_cut_walks_from_spans():
    """Walks run one at a time: each began at the later of its own start
    and the end of the walk before it."""
    serve_engine = harness.load_module(
        harness.BENCH / "systems" / "serve_engine.py", "t_serve_engine"
    )

    def walk(start, done, limit=8):
        return [
            {"t": start, "tier": "host-walk", "event": "start",
             "attrs": {"timeout_s": limit}},
            {"t": done, "tier": "host-walk", "event": "done"},
        ]

    journeys = [
        walk(0.0, 2.0),    # 2 s alone
        walk(0.5, 10.0),   # began at 2.0: 8 s, its whole limit
        walk(1.0, 15.0),   # began at 10.0: 5 s, waited 9 s before
        [],                # answered without a walk
    ]
    reports = [{"host": {}}] * 3 + [{}]
    assert serve_engine.walks_cut(journeys, reports) == [False, True, False, False]
    with pytest.raises(harness.BenchError):
        serve_engine.walks_cut([[]], [{"host": {}}])
