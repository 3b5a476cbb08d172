"""System under test: the `myth serve` engine
(`service/engine.AnalysisEngine`) in the process that holds the chip,
under a closed loop of clients.

Set-up starts the engine at the cell's configuration, lets its arena
warm-up land, and sends two rounds of one device-only job per fixture
family (drawn apart from the window's stream) so that the engine's
union kernel bucket covers the whole mix, and is compiled, before the
window; then one walked job of each family the mix's `warm_walks`
names, so that the host walk's first use is paid before the window. In the window each client submits the next contract of the
stream and waits for it to settle; it submits no more once the window
has closed. A job submitted inside the window is waited for after it,
up to a minute, and counts with all of its wait.

`contracts_per_min` is every job the window's clients submitted over
the clients' mean busy span: each client is busy from the window's
open until its last job settled, so the rate takes all of the work
and all of its time, and moves by less than one job's worth.
"""

from __future__ import annotations

import bisect
import itertools
import json
import shutil
import statistics
import threading
import time
from typing import Dict, List, Optional, Tuple

import generate
from harness import BenchError, say

#: how long a job submitted inside the window may take to settle
SETTLE_GRACE_S = 60.0
#: the share of its time limit that a walk the program says was cut
#: must have run from `locked` to `done`: a real cut leaves less than
#: the solver's margin of the limit (a query gets at most what is left
#: of it, less 0.5 s), so an 8 s walk cut honestly runs over 6 s
CUT_SHARE = 0.75
#: the program's host spans that label the trace's idle gaps: a walk
#: doing its own work, and a walk waiting to begin it
TRACE_HOST_SPANS = ("service.host.walk", "service.host.lock_wait")


class System:
    def __init__(self, ctx: Dict) -> None:
        self.ctx = ctx
        self.cfg = ctx["config"]["deployment"]
        self.mix = ctx["traffic"]
        self.seed = ctx["seed"]

    def setup(self) -> None:
        from mythril_tpu.service.engine import AnalysisEngine, ServiceConfig
        from mythril_tpu.service.jobs import Job

        cfg = self.cfg
        store = self.ctx["state_dir"] / "store"
        shutil.rmtree(store, ignore_errors=True)
        self.engine = AnalysisEngine(ServiceConfig(
            stripes=cfg["stripes"],
            lanes_per_stripe=cfg["lanes_per_stripe"],
            steps_per_wave=cfg["steps_per_wave"],
            max_waves=cfg["max_waves"],
            host_workers=cfg["host_workers"],
            host_walk=True,
            execution_timeout=cfg["execution_timeout"],
            create_timeout=cfg["create_timeout"],
            transaction_count=cfg["transaction_count"],
            static_answer=True,
            store_dir=str(store),
            store=True,
            router=True,
            arena_warmup=True,
        )).start()
        self.Job = Job
        if not self.engine._warm_done.wait(900):
            raise BenchError("the engine's arena warm-up did not land")
        # two rounds: the first widens the engine's union bucket to the
        # whole mix (each widening compiles on a background thread);
        # the second dispatches on the final bucket once it is warm
        families = len(generate.contracts.fixtures())
        warm = generate.stream(self.mix, self.seed, tag="warm")
        for round_ in range(2):
            jobs = [
                self.engine.submit(Job(next(warm)[0], host_walk=False))
                for _ in range(families)
            ]
            for job in jobs:
                if self.engine.queue.wait_terminal(job.id, 900) is None:
                    raise BenchError("a warm-up job did not settle")
            for thread in list(self.engine._warmup_threads):
                thread.join(900)
            say(f"engine warm-up round {round_}: {len(jobs)} device-only "
                f"jobs settled, kernel warm-ups joined")
        # one walked job per family the mix names, drawn apart from the
        # window's stream: the walk's first use lands here
        for family in self.mix.get("warm_walks", ()):
            walks = generate.stream(self.mix, self.seed, tag=f"warm-walk-{family}")
            code = next((c for c, _, name in itertools.islice(walks, families)
                         if name.startswith(family + "#")), None)
            if code is None:
                raise BenchError(f"no fixture family {family!r} to walk in set-up")
            job = self.engine.submit(Job(code))
            if self.engine.queue.wait_terminal(job.id, 900) is None:
                raise BenchError("a walked warm-up job did not settle")
            say(f"engine warm-up walk of a {family} mutant settled")

    def window(self, seconds: float) -> Dict:
        from mythril_tpu import observe

        engine = self.engine
        source = generate.stream(self.mix, self.seed)
        source_mu = threading.Lock()
        done: List[Dict] = []
        done_mu = threading.Lock()
        solver_mark = observe.solver_marker()
        steps_before = self._device_steps()
        t_open = time.perf_counter()
        t_close = t_open + seconds

        def client(index: int) -> None:
            while time.perf_counter() < t_close:
                with source_mu:
                    code, _creation, name = next(source)
                t = time.perf_counter()
                job = engine.submit(self.Job(code))
                left = t_close + SETTLE_GRACE_S - time.perf_counter()
                settled = engine.queue.wait_terminal(job.id, max(0.0, left))
                t_done = time.perf_counter()
                with done_mu:
                    done.append({
                        "client": index,
                        "family": name.split("#")[0],
                        "code": code,
                        "submit_t": t,
                        "settle_t": t_done if settled is not None else None,
                        "state": settled.state if settled is not None else None,
                        "report": settled.report if settled is not None else None,
                        "journey": observe.journey_log().events(job.journey_id),
                    })

        clients = self.mix["clients"]
        threads = [
            threading.Thread(target=client, args=(i,), name=f"bench-client-{i}")
            for i in range(clients)
        ]
        for th in threads:
            th.start()
        time.sleep(max(0.0, t_close - time.perf_counter()))
        steps_after = self._device_steps()
        window_s = time.perf_counter() - t_open
        for th in threads:
            th.join()
        ok = [d for d in done if d["state"] == "done"]
        failed = [d for d in done if d["state"] != "done"]
        # each client's busy span: the window's open to its last settle
        spans = []
        for i in range(clients):
            ends = [d["settle_t"] for d in done if d["client"] == i]
            if ends and None not in ends:
                spans.append(max(ends) - t_open)
        say(f"{len(done)} jobs submitted in the {window_s} s window, "
            f"{len(ok)} settled, {len(failed)} failed or unsettled; "
            f"client busy spans {spans} s")
        cut = judge_walks(ok)
        return {
            "wall_s": window_s,
            "settled": len(ok),
            "busy_span_s": statistics.mean(spans) if spans else None,
            "attempted": len(done),
            "failed": len(failed),
            "latencies": [d["settle_t"] - d["submit_t"] for d in ok],
            "device_steps": steps_after - steps_before,
            "solver": observe.solver_attribution(solver_mark),
            "journeys": [d["journey"] for d in ok],
            "reports": [
                {"code": bytes.fromhex(d["code"]),
                 "issues": (d["report"] or {}).get("issues") or [],
                 "family": d["family"], "walk_cut": c}
                for c, d in zip(cut, ok)
            ],
        }

    def _device_steps(self) -> int:
        return self.engine.device_steps

    def end_to_end(self, run: Dict) -> Dict:
        if not run["busy_span_s"]:
            return {}
        return {"contracts_per_min": 60.0 * run["settled"] / run["busy_span_s"]}

    def close(self) -> None:
        self.engine.close()


def walks_cut(journeys: List[List[Dict]], reports: List) -> Tuple[List[bool], List[bool]]:
    """Whether each job's host walk was cut by its time limit, by the
    program's own word backed by the walk's own time; and whether the
    program said `cut` of a walk too short for it. One flag per job in
    each list.

    A walked job counts as cut when its journey host-walk `done` says
    `cut`, and its walk time from `locked` (the walk has begun its own
    work) to `done` reaches CUT_SHARE of the limit on its `start`
    (`timeout_s`). A real cut leaves less of the budget than the
    solver's margin, so an honest one runs well past that share. A walk
    that says `cut` in less time is not excused from its planted
    weaknesses, and is flagged unbacked. The judgment holds whether
    walks run one at a time or side by side. A walked job without one
    start, locked and done, or whose done carries no `cut` or whose
    start no limit, is an error. A job answered without a walk is not
    cut."""
    cut, unbacked = [], []
    for events, report in zip(journeys, reports):
        walk = _walk_rows(events, report, ("start", "locked", "done"))
        if walk is None:
            cut.append(False)
            unbacked.append(False)
            continue
        start, locked, done = walk
        said = (done.get("attrs") or {}).get("cut")
        limit = (start.get("attrs") or {}).get("timeout_s")
        if said is None or limit is None:
            raise BenchError("a host walk whose journey says no cut or no limit")
        backed = done["t"] - locked["t"] >= CUT_SHARE * limit
        cut.append(bool(said) and backed)
        unbacked.append(bool(said) and not backed)
    return cut, unbacked


def _walk_rows(events: List[Dict], report, names) -> Optional[tuple]:
    """The job's one host-walk row of each event in `names`, or None
    for a job answered without a walk."""
    rows = [r for r in events if r.get("tier") == "host-walk"]
    if not rows and "host" not in (report or {}):
        return None
    found = []
    for name in names:
        hits = [r for r in rows if r.get("event") == name]
        if len(hits) != 1:
            raise BenchError(f"a walked job without one host-walk {name}")
        found.append(hits[0])
    return tuple(found)


def _serial_cut(journeys: List[List[Dict]], reports: List) -> List[bool]:
    """The judgment `walks_cut` replaced, kept only to print where the
    two differ: walks taken to run one at a time, each begun at the
    later of its own `start` and the `done` of the walk before it, and
    cut when its time from there reaches CUT_SHARE of its limit."""
    walks = [_walk_rows(e, r, ("start", "done")) for e, r in zip(journeys, reports)]
    ends = sorted(w[1]["t"] for w in walks if w is not None)
    out = []
    for w in walks:
        if w is None:
            out.append(False)
            continue
        start, end = w
        before = bisect.bisect_left(ends, end["t"])
        began = max(start["t"], ends[before - 1] if before else start["t"])
        limit = (start.get("attrs") or {}).get("timeout_s")
        out.append(limit is None or end["t"] - began >= CUT_SHARE * limit)
    return out


def judge_walks(jobs: List[Dict]) -> List[bool]:
    """`walks_cut` over settled jobs ({family, journey, report}), with
    what it found printed: the walks cut, those that said `cut` too
    soon (`cuts_unbacked`) and those the serial judgment would have
    judged otherwise (`cut_disagree`, not compared)."""
    journeys = [j["journey"] for j in jobs]
    reports = [j["report"] for j in jobs]
    cut, unbacked = walks_cut(journeys, reports)
    serial = _serial_cut(journeys, reports)

    def walk_s(job):
        t = {r.get("event"): r["t"] for r in job["journey"]
             if r.get("tier") == "host-walk"}
        return round(t["done"] - t["locked"], 3), round(t["done"] - t["start"], 3)

    flagged = [j for j, u in zip(jobs, unbacked) if u]
    differ = [(j, c) for j, c, s in zip(jobs, cut, serial) if c != s]
    say(f"{sum(cut)} walks cut; cuts_unbacked {len(flagged)} "
        + json.dumps([[j["family"], *walk_s(j)] for j in flagged]))
    say(f"cut_disagree {len(differ)} " + json.dumps(
        [[j["family"], "cut" if c else "not cut", *walk_s(j)] for j, c in differ]
    ) + " (family, this judgment, walk s from locked, from start)")
    return cut
