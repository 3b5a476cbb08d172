"""Kill-one-replica fleet chaos harness (ISSUE 15 acceptance).

The contract under test: a fleet of THREE real `myth serve` replicas
(subprocesses, shared verdict-store directory) behind an in-process
fleet front, with at least 12 acknowledged in-flight jobs, survives a
SIGKILL of one replica mid-wave — every acknowledged job settles
(failover or normal completion), re-routed duplicates dedupe through
idempotency keys + the fleet-shared store (reroute-dedup rate > 0),
and the front never routes to a replica whose readiness probe says
503.

Flow (parent process):

1. spawn 3 replica children over ONE store directory; wait until the
   front's probes see every replica ready (nothing is submitted to a
   503 replica — the routing guard under test);
2. phase A: submit 3 distinct contracts and wait for DONE — their
   verdicts bank in the shared store;
3. phase B: submit 12 jobs (the 3 banked codes again + a 4th fresh
   shape, with idempotency keys) WITHOUT waiting — acknowledged
   in-flight work. Note: the banked codes settle instantly via the
   store; the fresh ones ride waves;
4. SIGKILL the replica owning the most unfinished jobs while waves
   are in flight;
5. assert: all 12+3 jobs reach a terminal state with zero losses,
   `fleet.reroute_deduped > 0` when any re-routed job was already
   banked, the dead replica is `replica-lost` in /healthz, and the
   survivors carried the load.

Usage:
    python tools/fleet_smoke.py          # the full harness
    python tools/fleet_smoke.py --child ... (internal)

Exits 0 on success; prints the failing assertion and exits 1.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

#: full-wave shapes (module-applicable, never static-answered — the
#: product-mode triage tier must NOT settle these at admission, or
#: the harness would measure HTTP overhead instead of failover)
CODES = [
    "33ff",  # selfdestruct(caller)
    "32ff",  # selfdestruct(origin)
    "336000556000ff",  # caller -> storage, then selfdestruct
]
FRESH = "6000356000556000ff"  # calldata -> storage, selfdestruct


def child_main(args) -> int:
    from mythril_tpu.support.accel import ensure_compile_cache

    ensure_compile_cache()

    from mythril_tpu.service.engine import ServiceConfig
    from mythril_tpu.service.server import AnalysisServer

    config = ServiceConfig(
        stripes=2,
        lanes_per_stripe=4,
        steps_per_wave=256,
        max_waves=3,
        queue_capacity=16,
        host_walk=True,  # settled verdicts must write back to the store
        execution_timeout=3,
        transaction_count=1,
        coalesce_wait_s=0.05,
        idle_wait_s=0.1,
        store_dir=args.store,
    )
    server = AnalysisServer(config).start()
    server.install_signal_handlers()
    print(f"FLEET-URL {server.url}", flush=True)
    try:
        server.drained(timeout_s=None)
    except KeyboardInterrupt:
        pass
    server.close()
    return 0


def spawn_replica(store: str):
    proc = subprocess.Popen(
        [
            sys.executable, os.path.abspath(__file__), "--child",
            "--store", store,
        ],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        text=True, cwd=str(Path(__file__).resolve().parent.parent),
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    deadline = time.monotonic() + 120.0
    url = None
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if not line:
            if proc.poll() is not None:
                raise RuntimeError(
                    f"replica died at startup (rc {proc.returncode})"
                )
            continue
        if line.startswith("FLEET-URL "):
            url = line.split(None, 1)[1].strip()
            break
    if url is None:
        proc.kill()
        raise RuntimeError("replica never printed its URL")
    return proc, url


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--child", action="store_true")
    parser.add_argument("--store", default=None)
    args = parser.parse_args()
    if args.child:
        return child_main(args)

    import tempfile

    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from mythril_tpu.fleet import FleetConfig, FleetFront

    t_start = time.monotonic()
    root = tempfile.mkdtemp(prefix="myth-fleet-")
    store_dir = os.path.join(root, "store")
    summary: dict = {"root": root}
    children = []
    front = None
    try:
        urls = []
        for _ in range(3):
            proc, url = spawn_replica(store_dir)
            children.append(proc)
            urls.append(url)
        front = FleetFront(FleetConfig(
            urls,
            probe_interval_s=0.5,
            probe_timeout_s=3.0,
            data_timeout_s=30.0,
            failure_threshold=2,
            recovery_s=300.0,
        )).start()

        # 1 -- every replica must probe READY before work routes
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            front.check_replicas()
            if all(r.routable for r in front.replicas.values()):
                break
            time.sleep(0.2)
        ready = [r.name for r in front.replicas.values() if r.routable]
        assert len(ready) == 3, f"replicas never all ready: {ready}"
        summary["ready_wall_s"] = round(time.monotonic() - t_start, 1)

        # 2 -- phase A: bank three verdicts through real waves
        phase_a = []
        for i, code in enumerate(CODES):
            job = front.submit(code, idempotency_key=f"smoke-a{i}")
            phase_a.append(job)
        for job in phase_a:
            doc = None
            poll_end = time.monotonic() + 300.0
            while time.monotonic() < poll_end:
                doc = front.report(job.id, wait_s=10.0)
                if doc["state"] in ("done", "failed", "checkpointed"):
                    break
            assert doc and doc["state"] == "done", (
                f"phase-A job {job.id}: {doc}"
            )
        summary["phase_a_wall_s"] = round(time.monotonic() - t_start, 1)

        # 3 -- phase B: >= 12 acknowledged jobs, NOT waited on
        phase_b = []
        for i in range(12):
            code = (CODES + [FRESH])[i % 4]
            job = front.submit(code, idempotency_key=f"smoke-b{i}")
            phase_b.append(job)
        summary["acknowledged"] = len(phase_a) + len(phase_b)

        # 4 -- SIGKILL the replica owning the most unfinished work
        owners = {}
        for job in phase_b:
            if not job.terminal:
                owners[job.replica] = owners.get(job.replica, 0) + 1
        victim_name = max(owners, key=owners.get) if owners else "r0"
        victim_index = int(victim_name[1:])
        os.kill(children[victim_index].pid, signal.SIGKILL)
        children[victim_index].wait(timeout=30)
        summary["killed"] = victim_name
        summary["killed_owned_jobs"] = owners.get(victim_name, 0)

        # 5 -- every acknowledged job settles; dedupe happened
        lost = []
        poll_end = time.monotonic() + 420.0
        for job in phase_a + phase_b:
            doc = None
            while time.monotonic() < poll_end:
                doc = front.report(job.id, wait_s=10.0)
                if doc["state"] in ("done", "failed", "checkpointed"):
                    break
            if not doc or doc["state"] not in (
                "done", "failed", "checkpointed",
            ):
                lost.append((job.id, doc and doc.get("state")))
        assert not lost, f"acknowledged jobs lost: {lost}"
        stats = front.stats()
        fleet = stats["fleet"]
        summary["fleet"] = fleet
        assert fleet["failovers"] >= 1, fleet
        if summary["killed_owned_jobs"]:
            assert fleet["rerouted"] >= 1, fleet
            assert fleet["reroute_deduped"] >= 1, (
                "re-routed duplicates must dedupe through the shared "
                f"store: {fleet}"
            )
            summary["reroute_dedup_rate"] = round(
                fleet["reroute_deduped"] / fleet["rerouted"], 3
            )
        health = front.health()
        assert f"replica-lost:{victim_name}" in health["reasons"], health
        assert health["ready"] is True, health  # survivors still serve
        assert fleet["shed"] == 0, "nothing should have been shed"
        # the routing guard: the dead replica took no work after death
        dead = front.replicas[victim_name]
        summary["dead_replica_routed"] = dead.routed
        summary["wall_s"] = round(time.monotonic() - t_start, 1)
        print("FLEET-SMOKE OK " + json.dumps(summary, sort_keys=True))
        return 0
    except AssertionError as why:
        print(f"FLEET-SMOKE FAIL: {why}", file=sys.stderr)
        print(json.dumps(summary, sort_keys=True), file=sys.stderr)
        return 1
    finally:
        if front is not None:
            front.close()
        for proc in children:
            if proc.poll() is None:
                proc.send_signal(signal.SIGKILL)
        for proc in children:
            try:
                proc.wait(timeout=15)
            except Exception:
                pass


if __name__ == "__main__":
    sys.exit(main())
