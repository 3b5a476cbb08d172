"""A whole run of the serve cell with the look for a chip skipped, on the
CPU at a small size, and the timed path broken underneath: an answer
altered where it is produced, a walk that returns its state unchanged,
a walk that drops one finding, a walk that says at once that its
budget cut it, or an answer that never comes, makes `correct` false.
The same run unbroken is correct.

The mix is cut to the fixture families whose walks end well inside
their limit on a CPU, so that every report is held to its planted
weaknesses; the engine, its waves and its walks are the cell's own."""

import re

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


def test_early_cut_is_not_excused(serve, monkeypatch, capfd):
    """The walk returns at once, says it was cut by its budget and finds
    nothing: a cut that short is not excused from the planted
    weaknesses, and the run prints it as unbacked."""

    def cut_at_once(payload):
        return {"issues": [], "states": 0, "cut": "execution"}

    monkeypatch.setattr(serve, "analyze_one_payload", cut_at_once)
    result = _run(monkeypatch)
    err = capfd.readouterr().err
    assert result["compared"]["missed_planted"]["value"] >= 1
    assert result["correct"] is False
    said = re.search(r"bench: (\d+) walks cut; cuts_unbacked (\d+)", err)
    assert said is not None
    assert int(said.group(1)) == 0
    assert int(said.group(2)) >= 1


def _walk(start, locked, done, limit=8, **attrs):
    """A walked job's host-walk rows; `locked` None leaves it out, and
    attrs ride on `done`."""
    rows = [{"t": start, "tier": "host-walk", "event": "start",
             "attrs": {"timeout_s": limit}}]
    if locked is not None:
        rows.append({"t": locked, "tier": "host-walk", "event": "locked"})
    rows.append({"t": done, "tier": "host-walk", "event": "done", "attrs": attrs})
    return rows


WALKED = {"host": {}}
CUT_CASES = {
    # one at a time, each begun when the one before it ended: the
    # program and the serial judgment agree
    "serial-agree": (
        [_walk(0.0, 0.0, 2.0, cut=False),
         _walk(0.5, 2.0, 10.0, cut=True),     # 8 s, its whole limit
         _walk(1.0, 10.0, 15.0, cut=False),   # 5 s, after a 9 s wait
         []],                                 # answered without a walk
        [WALKED] * 3 + [{}],
        [False, True, False, False], [False] * 4, [False, True, False, False],
    ),
    # four side by side: the cut one ran 8.4 s from locked, but another
    # ended 0.6 s before it, so the serial judgment misses the cut
    "side-by-side": (
        [_walk(0.0, 0.0, 3.0, cut=False),
         _walk(0.2, 0.2, 8.6, cut=True),
         _walk(0.4, 0.4, 5.0, cut=False),
         _walk(0.6, 0.6, 8.0, cut=False)],
        [WALKED] * 4,
        [False, True, False, False], [False] * 4, [False] * 4,
    ),
    # says cut after 1 s: held to its planted weaknesses, and unbacked
    "cut-too-soon": (
        [_walk(0.0, 0.0, 1.0, cut="execution")],
        [WALKED],
        [False], [True], [False],
    ),
    # ran to its end in 7.3 s of its 8: held to its planted weaknesses,
    # where the serial judgment excused it for passing the share
    "ended-late": (
        [_walk(0.0, 0.0, 7.3, cut=False)],
        [WALKED],
        [False], [False], [True],
    ),
    "no-walk": ([[]], [{}], [False], [False], [False]),
    "done-without-cut": ([_walk(0.0, 0.0, 8.0)], [WALKED], None, None, None),
    "no-locked": ([_walk(0.0, None, 8.0, cut=True)], [WALKED], None, None, None),
    "no-walk-span": ([[]], [WALKED], None, None, None),
}


@pytest.mark.parametrize("case", list(CUT_CASES))
def test_cut_walks_from_spans(case):
    """A walk is cut by the program's `cut` on its journey `done`,
    backed by its own time from `locked`; the serial judgment it
    replaced is kept only to print where the two differ."""
    serve_engine = harness.load_module(
        harness.BENCH / "systems" / "serve_engine.py", "t_serve_engine"
    )
    journeys, reports, cut, unbacked, serial = CUT_CASES[case]
    if cut is None:
        with pytest.raises(harness.BenchError):
            serve_engine.walks_cut(journeys, reports)
        return
    assert serve_engine.walks_cut(journeys, reports) == (cut, unbacked)
    assert serve_engine._serial_cut(journeys, reports) == serial
