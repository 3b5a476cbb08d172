#!/usr/bin/env python3
"""Chip smoke: `myth analyze` end to end on a TPU, in this one process.

The quickest proof that the system still starts on the chip. It drives
the CLI's `main` in-process (a child would find the chip held by this
process) at the shipped defaults, and checks the reports against the
committed goldens (tests/testdata/goldens):

1. device check: JAX's devices must be TPUs;
2. audit one contract: suicide.sol.o with --device-prepass always,
   under the golden run's flags (analysis/goldens.py golden_corpus_run);
3. sweep the 13 vendored fixtures at -t 2 as one corpus: the
   overlapped striped device prepass beside the host walk.

With --chips 4 only phase 3 runs, twice: on the default lane-sharded
engine over all four chips, and on the multi-chip corpus scheduler
(`--devices 4`); each must match the goldens with waves on every chip.

Every figure printed before the last line is a smoke figure: one run,
compile included, not a benchmark. The last stdout line is the verdict,
`{"ok": true, "device": {...}}`; any failure exits non-zero without it.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import sys
import threading
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
FIXTURES = REPO / "tests" / "testdata" / "vendored" / "inputs"
GOLDENS = REPO / "tests" / "testdata" / "goldens"
AUDIT_FIXTURE = "suicide.sol.o"

#: analysis/goldens.py golden_corpus_run: the budgets the goldens were
#: made under (reports are deterministic only with deterministic solving)
GOLDEN_FLAGS = [
    "--bin-runtime",
    "--no-onchain-data",
    "-t", "2",
    "--execution-timeout", "120",
    "--create-timeout", "10",
    "--deterministic-solving",
    "-o", "jsonv2",
]

#: degradations that mean the device did not do its part
DEVICE_FAULTS = {
    "prepass-failed",
    "device-dispatch-failed",
    "async-device-fault",
    "wave-abandoned",
    "mesh-group-degraded",
    # the corpus prepass was still inside a device dispatch when the
    # host walk ended: its final outcome never arrived
    "prepass-cut",
}


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def device_check(chips: int) -> dict:
    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    say(f"devices {json.dumps(device)}")
    check(
        device["platform"] == "tpu",
        f"no TPU: JAX found platform {device['platform']!r}",
    )
    check(
        device["count"] >= chips,
        f"{chips} chips wanted, JAX found {device['count']}",
    )
    return device


class CompileClock:
    """Seconds JAX spent in backend compiles (persistent-cache reads
    included) and the cache's hits and misses, from JAX's own
    monitoring events."""

    def __init__(self) -> None:
        import jax

        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def figures(self) -> dict:
        return {
            "compile_s": self.seconds,
            "cache_hits": self.hits,
            "cache_misses": self.misses,
        }


def run_myth(argv: list) -> tuple:
    """`myth <argv>` through the CLI's main in this process ->
    (jsonv2 report, degradation reasons recorded during the run, wall)."""
    from mythril_tpu.interfaces.cli import main
    from mythril_tpu.support import resilience

    marker = resilience.DegradationLog().marker()
    out = io.StringIO()
    saved_argv = sys.argv
    sys.argv = ["myth", *argv]
    t0 = time.perf_counter()
    try:
        with contextlib.redirect_stdout(out):
            try:
                main()
                code = 0
            except SystemExit as why:
                code = why.code or 0
    finally:
        sys.argv = saved_argv
    wall = time.perf_counter() - t0
    text = out.getvalue().strip()
    check(code == 0, f"myth {argv[0]} exited {code}: {text[-2000:]}")
    from mythril_tpu.analysis.corpus import PREPASS_THREAD

    # main returned with no prepass thread left inside a device call
    # (one would abort the interpreter's exit)
    check(
        not any(t.name == PREPASS_THREAD for t in threading.enumerate()),
        f"myth {argv[0]} returned with its device prepass still running",
    )
    report = json.loads(text.splitlines()[-1])[0]
    return report, resilience.DegradationLog().counts_since(marker), wall


def golden_keys(names: list) -> set:
    keys = set()
    for name in names:
        rows = json.loads((GOLDENS / f"{name}.issues.json").read_text())
        keys |= {(r["contract"], r["swc-id"], r["address"]) for r in rows}
    return keys


def issue_rows(report: dict, codes: dict) -> list:
    """jsonv2 issues -> the golden row shape (analysis/goldens.py
    canonical_issues), each tagged with its contract through the
    report's sourceList (bytecode hash per contract)."""
    from mythril_tpu.analysis.goldens import canonical_issues
    from mythril_tpu.ethereum.evmcontract import EVMContract

    by_hash = {
        EVMContract(code=code).bytecode_hash: name
        for name, code in codes.items()
    }
    rows = []
    for issue in report["issues"]:
        address, _, index = issue["locations"][0]["sourceMap"].split(":")
        cases = issue["extra"].get("testCases") or [{}]
        rows.append({
            "contract": by_hash[report["sourceList"][int(index)]],
            "swc-id": issue["swcID"].removeprefix("SWC-"),
            "address": int(address),
            "title": issue["swcTitle"],
            "function": None,
            "tx_sequence": {"steps": cases[0].get("steps") or []},
        })
    return canonical_issues(rows)


def check_report(label: str, report: dict, degradation: dict,
                 codes: dict) -> dict:
    """The report holds every golden issue of its contracts, each extra
    issue carries a concrete transaction sequence, the device stepped,
    and nothing degraded off the device. Returns the prepass stats."""
    rows = issue_rows(report, codes)
    found = {(r["contract"], r["swc-id"], r["address"]) for r in rows}
    goldens = golden_keys(list(codes))
    missing = goldens - found
    check(not missing, f"{label}: golden issues missing: {sorted(missing)}")
    extras = [
        r for r in rows
        if (r["contract"], r["swc-id"], r["address"]) not in goldens
    ]
    for r in extras:
        say(
            f"{label}: extra issue {r['contract']} SWC-{r['swc-id']} "
            f"@{r['address']} tx_steps={r['tx_steps']}"
        )
        check(r["tx_steps"], f"{label}: extra issue without a tx sequence")
    faults = {k: v for k, v in degradation.items() if k in DEVICE_FAULTS}
    check(not faults, f"{label}: device degraded: {faults}")
    if degradation:
        say(f"{label}: degradations (not device faults) {degradation}")
    # a corpus run reports its prepass's final counters; a single
    # contract's own prepass rides its execution info
    meta = report["meta"]
    source = "device_prepass" if "device_prepass" in meta else "execution_info"
    stats = meta.get("device_prepass") or meta.get(
        "mythril_execution_info", {}
    ).get("device_symbolic_prepass")
    check(stats is not None, f"{label}: no device prepass stats in the report")
    say(f"{label}: prepass stats from {source}")
    check(
        stats.get("device_steps", 0) > 0 and stats.get("waves", 0) > 0,
        f"{label}: the device did not step (device_steps="
        f"{stats.get('device_steps')}, waves={stats.get('waves')})",
    )
    say(
        f"{label}: {len(found)} issues ({len(extras)} beyond the goldens), "
        f"all {len(goldens)} golden issues found"
    )
    return stats


def smoke_figures(label: str, report: dict, stats: dict, wall: float,
                  clock) -> None:
    """Figures of this one run (compile counted since process start)."""
    verdicts = {
        origin: row["verdicts"]
        for origin, row in (
            report["meta"].get("solver_attribution") or {}
        ).items()
    }
    figures = {
        "wall_s": wall,
        "device_steps": stats.get("device_steps"),
        "waves": stats.get("waves"),
        "explorer_device_sat": stats.get("device_sat"),
        "explorer_device_unsat": stats.get("device_unsat"),
        "explorer_host_sat": stats.get("host_sat"),
        "solver_verdicts": verdicts,
        **clock.figures(),
    }
    say(f"{label} smoke figures {json.dumps(figures)}")


def audit_one(clock) -> None:
    path = FIXTURES / AUDIT_FIXTURE
    codes = {path.name.removesuffix(".o"): path.read_text().strip()}
    report, degradation, wall = run_myth(
        ["analyze", "-f", str(path), *GOLDEN_FLAGS,
         "--device-prepass", "always"]
    )
    stats = check_report("audit", report, degradation, codes)
    smoke_figures("audit", report, stats, wall, clock)


def sweep(clock, label: str, extra: list) -> dict:
    paths = sorted(FIXTURES.glob("*.sol.o"))
    codes = {p.name.removesuffix(".o"): p.read_text().strip() for p in paths}
    report, degradation, wall = run_myth(
        ["analyze", "-f", *map(str, paths), *GOLDEN_FLAGS, *extra]
    )
    stats = check_report(label, report, degradation, codes)
    smoke_figures(label, report, stats, wall, clock)
    # the corpus prepass's flip frontier went through the device solver
    # funnel, and its verdicts came back before the report was built
    funnel = (report["meta"].get("solver_attribution") or {}).get(
        "device-portfolio"
    )
    check(funnel, f"{label}: no device solver funnel verdicts")
    return stats


def check_mesh(label: str, stats: dict, chips: int, groups: int) -> None:
    """Waves on every chip: the scheduler reports one row per device
    group; the lane-sharded engine is one group whose every wave is
    sharded over all its devices."""
    mesh = stats.get("mesh") or {}
    rows = mesh.get("per_device") or []
    say(f"{label}: mesh {json.dumps(mesh)}")
    # a scheduler row lists its group's devices; the engine row counts
    covered = sum(
        len(r["devices"]) if isinstance(r.get("devices"), list)
        else r.get("devices", 0)
        for r in rows
    )
    check(
        covered == chips,
        f"{label}: {chips} chips wanted in the mesh, rows cover {covered}",
    )
    check(len(rows) == groups, f"{label}: {groups} groups wanted")
    idle = [r["group"] for r in rows if not r.get("waves")]
    check(not idle, f"{label}: device groups without waves: {idle}")


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: only the multi-chip sweep (lane-sharded + --devices 4)",
    )
    opts = parser.parse_args()
    try:
        sys.path.insert(0, str(REPO))
        from mythril_tpu.support.accel import ensure_compile_cache

        say(f"compile cache {ensure_compile_cache()}")
        device = device_check(opts.chips)
        clock = CompileClock()
        if opts.chips == 1:
            audit_one(clock)
            sweep(clock, "sweep", [])
        else:
            stats = sweep(clock, "sweep-lane-sharded", [])
            check_mesh("sweep-lane-sharded", stats, opts.chips, 1)
            stats = sweep(
                clock, "sweep-scheduler", ["--devices", str(opts.chips)]
            )
            check_mesh("sweep-scheduler", stats, opts.chips, opts.chips)
    except Exception as why:  # the smoke's verdict: any failure fails it
        import traceback

        traceback.print_exc()
        say(f"FAILED: {why}")
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
