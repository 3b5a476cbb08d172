"""End-to-end smoke for the solver query flight recorder + solverlab.

Captures a small corpus from two fault-suite contracts (the gated-flip
shape whose taken direction needs a solver witness, and the killable
shape whose module query concretizes an attacker call), then checks
the ISSUE-8 contracts:

1. **Coverage** — at least one query captured per origin the live run
   produced, and the flip-frontier / module / memo-miss origins all
   appear (explorer flip solving, detection-module queries, engine
   feasibility checks).
2. **Replay fidelity** — `solverlab` host replay reproduces the live
   verdicts 100% (zero disagreements), twice (deterministic).
3. **Loss accounting** — every host-won query carries a non-empty
   loss reason: sum(loss reasons over sat) == cdcl sat verdicts for
   the captured window.
4. **Zero cost off** — with capture disarmed, a fresh analysis adds
   no capture series to the registry and the disabled hook is a
   boolean check (<1% of any real wall).

Usage:
    python tools/solverlab_smoke.py

Exits 0 on success; prints the failing assertion and exits 1.
"""

from __future__ import annotations

import json
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

#: the fault-suite shapes (tests/laser/test_pipeline.py)
KILLABLE = "33ff"
GATED = "60003560f81c604214600d57005b600160005500"


def main() -> int:
    import os

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from mythril_tpu.support.accel import ensure_compile_cache

    # persistent XLA cache: the inverted-funnel leg compiles the
    # batched diversified-search kernel once per shape class
    ensure_compile_cache()

    from mythril_tpu import observe
    from mythril_tpu.analysis import solverlab
    from mythril_tpu.analysis.corpus import analyze_corpus
    from mythril_tpu.laser.batch.explore import DeviceCorpusExplorer
    from mythril_tpu.laser.smt.solver import capture as query_capture
    from mythril_tpu.laser.smt.solver.solver_statistics import (
        SolverStatistics,
    )
    from mythril_tpu.observe import querylog
    from mythril_tpu.support.model import clear_cache

    t_start = time.monotonic()
    corpus_dir = tempfile.mkdtemp(prefix="solverlab-smoke-")
    marker = observe.solver_marker()
    cdcl_base = SolverStatistics().cdcl_sat_count
    clear_cache()
    querylog.configure_capture(corpus_dir)

    # -- live run 1: the explorer's flip-frontier queries through the
    # INVERTED funnel (device-first is the product default: the
    # batched device dispatch answers before any host CDCL sprint) ---
    from mythril_tpu.support.support_args import args as _flags

    assert _flags.device_first, "device-first funnel must be the default"
    explorer = DeviceCorpusExplorer(
        [GATED, KILLABLE],
        lanes_per_contract=8,
        waves=3,
        steps_per_wave=64,
        transaction_count=1,
    )
    explorer.run()
    device_owned = explorer.stats.device_sat + explorer.stats.device_unsat

    # -- live run 2: the host walk's module + memo-miss queries ---------
    results = analyze_corpus(
        [(GATED, "", "gated"), (KILLABLE, "", "killable")],
        transaction_count=1,
        execution_timeout=20,
        create_timeout=10,
        use_device=False,
        processes=1,
    )
    querylog.configure_capture(None)

    corpus = querylog.load_corpus(corpus_dir)
    origins = {}
    for artifact in corpus:
        origins[artifact["origin"]] = origins.get(artifact["origin"], 0) + 1
    losses_sat = observe.loss_reasons(since=marker, verdict="sat")
    cdcl_sats = SolverStatistics().cdcl_sat_count - cdcl_base

    summary = {
        "corpus_dir": corpus_dir,
        "captured": len(corpus),
        "origins": origins,
        "loss_reasons_sat": losses_sat,
        "cdcl_sat_verdicts": cdcl_sats,
        "device_owned_verdicts": device_owned,
        "live_issues": sum(len(r["issues"]) for r in results),
    }
    try:
        # -- 0. the inverted-funnel leg (ISSUE 9): the device-first
        # dispatch must OWN verdicts on the fault-suite corpus — the
        # host sprint is the escalation ladder, not the answer path
        assert device_owned > 0, (
            f"inverted funnel produced zero device-owned verdicts "
            f"(device_sat={explorer.stats.device_sat}, "
            f"device_unsat={explorer.stats.device_unsat}, "
            f"host_sat={explorer.stats.host_sat})"
        )

        # -- 1. per-origin coverage ------------------------------------
        assert corpus, "the live runs captured no queries at all"
        for origin in ("flip-frontier", "module", "memo-miss"):
            assert origins.get(origin, 0) >= 1, (
                f"no query captured for origin {origin!r}: {origins}"
            )
        for artifact in corpus:
            assert artifact["sha"], artifact
            assert artifact["program"]["roots"], artifact["sha"]

        # -- 2. replay fidelity (twice: deterministic) -----------------
        for attempt in (1, 2):
            report = solverlab.run(corpus_dir, mode="replay",
                                   engines=["host"])
            host = report["replay"]["host"]
            summary[f"replay_{attempt}"] = host
            assert host["agreement"]["disagree"] == 0, (
                f"host replay attempt {attempt} disagreed with the live "
                f"verdicts: {report['disagreements']}"
            )
            assert host["agreement_pct"] == 100.0, host

        # -- 3. loss accounting ----------------------------------------
        assert sum(losses_sat.values()) == cdcl_sats, (
            f"host-won losses {losses_sat} (sum "
            f"{sum(losses_sat.values())}) != cdcl sat verdicts "
            f"{cdcl_sats}"
        )
        assert losses_sat, "no host-won query carried a loss reason"
        for artifact in corpus:
            if artifact["verdict"] == "sat" and artifact.get(
                "observations", []
            )[-1].get("engine") == "host-cdcl":
                assert artifact.get("loss_reason"), (
                    f"host-won artifact without a loss reason: "
                    f"{artifact['sha']}"
                )

        # -- 4. capture off = zero cost --------------------------------
        reg_marker = observe.solver_marker()
        clear_cache()
        analyze_corpus(
            [(GATED, "", "gated")],
            transaction_count=1,
            execution_timeout=15,
            create_timeout=10,
            use_device=False,
            processes=1,
        )
        delta = observe.registry().since(reg_marker)
        assert not delta.get("mtpu_solver_captured_queries_total"), (
            "capture-off run still moved the capture counter"
        )
        t_hook = time.monotonic()
        for _ in range(100_000):
            query_capture.capture_active()
        hook_s = time.monotonic() - t_hook
        live_wall = time.monotonic() - t_start
        assert hook_s < 0.01 * live_wall, (
            f"disabled hook cost {hook_s:.3f}s/100k — not <1% of the "
            f"{live_wall:.1f}s live run"
        )
        summary["hook_s_per_100k"] = round(hook_s, 4)
    except AssertionError as why:
        print(
            f"smoke FAILED after {time.monotonic() - t_start:.1f}s: {why}",
            file=sys.stderr,
        )
        print(json.dumps(summary, indent=2, default=str), file=sys.stderr)
        return 1

    print(
        f"smoke OK in {time.monotonic() - t_start:.1f}s: "
        f"{len(corpus)} queries captured ({origins}), inverted funnel "
        f"owned {device_owned} verdicts, host replay agreed 100% "
        f"twice, sat-loss sum {sum(losses_sat.values())} == "
        f"cdcl sats {cdcl_sats}, capture-off added zero series"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
