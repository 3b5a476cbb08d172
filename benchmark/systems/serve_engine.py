"""System under test: the `myth serve` engine
(`service/engine.AnalysisEngine`) in the process that holds the chip,
under a closed loop of clients.

Set-up starts the engine at the cell's configuration, lets its arena
warm-up land, and sends two rounds of one device-only job per fixture
family (drawn apart from the window's stream) so that the engine's
union kernel bucket covers the whole mix, and is compiled, before the
window. In the window each client submits the next contract of the
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
import shutil
import statistics
import threading
import time
from typing import Dict, List

import generate
from harness import BenchError, say

#: how long a job submitted inside the window may take to settle
SETTLE_GRACE_S = 60.0
#: a walk that ran this share of its time limit may have been cut by
#: it, or had its last solver queries shortened by it (a query gets at
#: most what is left of the walk's limit)
CUT_SHARE = 0.75


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
        cut = walks_cut([d["journey"] for d in done],
                        [d["report"] for d in done])
        say(f"{len(done)} jobs submitted in the {window_s} s window, "
            f"{len(ok)} settled, {len(failed)} failed or unsettled; "
            f"client busy spans {spans} s; "
            f"{sum(cut)} walks cut")
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
                for c, d in zip(cut, done) if d["state"] == "done"
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


def walks_cut(journeys: List[List[Dict]], reports: List) -> List[bool]:
    """Whether each job's host walk may have been cut by its time limit.
    The engine runs its walks one at a time under the host symbolic lock,
    so a walk began no earlier than its own `start` span and the `done`
    of the walk before it; a walk whose time from there reaches CUT_SHARE
    of its limit (the `timeout_s` of its start) is counted cut. A job
    answered without a walk is not cut."""
    walks = []
    for events, report in zip(journeys, reports):
        rows = [r for r in events if r.get("tier") == "host-walk"]
        if not rows and "host" not in (report or {}):
            walks.append(None)
            continue
        starts = [r for r in rows if r.get("event") == "start"]
        dones = [r for r in rows if r.get("event") == "done"]
        if len(starts) != 1 or len(dones) != 1:
            raise BenchError("a walked job without one host-walk span")
        walks.append((starts[0], dones[0]))
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
