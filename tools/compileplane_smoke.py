"""Zero-cold-start smoke for the persistent AOT compile plane.

The contract under test (ISSUE 17 acceptance): a `myth serve` replica
pointed at a prebaked kernel pack reaches ready WITHOUT compiling the
packed buckets in-process — and keeps doing so after a SIGKILL +
restart, with wave results bit-identical to a packless replica that
paid the compile.

Flow (parent process):

1. child --bake: bake a one-bucket pack for the smoke's dispatch
   shape into a temp dir (the bake wall is the no-pack cold compile);
2. child --serve --pack: spawn a packed replica, measure spawn ->
   ready; assert the pack mounted, readiness cleared, and the
   generic-wave AOT table shows ZERO in-process compiles; settle a
   small contract batch and keep the reports;
3. SIGKILL the packed replica mid-life; restart over the SAME pack;
   assert it is again ready with zero in-process compiles and that
   resubmitting the same contracts yields bit-identical reports;
4. child --serve (no pack): a packless replica pays the in-process
   compile; assert its ready wall exceeds the packed replica's and
   that its reports match the packed ones bit-identically;
5. child --serve --pack with MYTHRIL_NO_AOT=1: the degrade leg — the
   pack is ignored with an ATTRIBUTED reason (`disabled-by-flag`),
   the replica compiles in-process and still serves.

Usage:
    python tools/compileplane_smoke.py          # the full harness
    python tools/compileplane_smoke.py --bake/--serve ... (internal)

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

#: the smoke's dispatch shape — bake and serve MUST agree or the pack
#: cannot cover the service's generic wave bucket
SHAPE = dict(stripes=2, lanes_per_stripe=4, steps_per_wave=64, code_cap=64)

#: tiny full-wave contracts (each < code_cap bytes)
CONTRACTS = [
    "6001600055600060015500",  # storage writer
    "600035600757005b600160005500",  # brancher
    "33ff",  # CALLER; SELFDESTRUCT
]


def child_bake(args) -> int:
    from mythril_tpu.compileplane.pack import bake_service_pack

    manifest = bake_service_pack(args.pack, [None], **SHAPE)
    print(
        "CP-BAKED "
        + json.dumps({
            "artifacts": manifest["artifacts"],
            "wall_s": manifest["baked"][0]["wall_s"],
        }),
        flush=True,
    )
    return 0


def child_serve(args) -> int:
    from mythril_tpu.service.engine import ServiceConfig
    from mythril_tpu.service.server import AnalysisServer

    config = ServiceConfig(
        stripes=SHAPE["stripes"],
        lanes_per_stripe=SHAPE["lanes_per_stripe"],
        steps_per_wave=SHAPE["steps_per_wave"],
        code_cap=SHAPE["code_cap"],
        max_waves=3,
        queue_capacity=16,
        host_walk=True,
        execution_timeout=3,
        transaction_count=1,
        coalesce_wait_s=0.05,
        idle_wait_s=0.1,
        arena_warmup=True,
        kernel_pack=args.pack,
    )
    server = AnalysisServer(config).start()
    server.install_signal_handlers()
    print(f"CP-URL {server.url}", flush=True)
    server.engine._warm_done.wait(timeout=600.0)
    print("CP-READY", flush=True)
    try:
        server.drained(timeout_s=None)
    except KeyboardInterrupt:
        pass
    server.close()
    return 0


def spawn_serve(pack: str | None, env_extra: dict | None = None):
    """Returns (proc, url, ready_wall_s): ready_wall is spawn-to-READY
    — interpreter + jax init + mount/compile, the honest cold start."""
    cmd = [sys.executable, os.path.abspath(__file__), "--serve"]
    if pack:
        cmd += ["--pack", pack]
    # the children run on CPU JAX; the persistent XLA compile cache
    # stays OFF in them: the packless leg must pay the real compile it
    # claims to measure
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    t0 = time.monotonic()
    proc = subprocess.Popen(
        cmd, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        text=True, env=env,
        cwd=str(Path(__file__).resolve().parent.parent),
    )
    url = None
    deadline = time.monotonic() + 300.0
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if not line:
            if proc.poll() is not None:
                raise RuntimeError(
                    f"serve child died at startup (rc {proc.returncode})"
                )
            continue
        if line.startswith("CP-URL "):
            url = line.split(None, 1)[1].strip()
        elif line.startswith("CP-READY"):
            return proc, url, time.monotonic() - t0
    proc.kill()
    raise RuntimeError("serve child never reached ready")


def settle_all(client) -> list:
    """Submit every smoke contract, return its report issue sets (the
    bit-identity payload: title/address/severity per issue)."""
    reports = []
    for i, code in enumerate(CONTRACTS):
        job_id = client.submit(code, idempotency_key=None)
        doc = client.report(job_id, wait_s=240.0)
        assert doc["state"] == "done", f"job {job_id}: {doc['state']}"
        reports.append(sorted(
            (iss.get("title"), iss.get("address"), iss.get("severity"))
            for iss in doc.get("issues") or []
        ))
    return reports


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--bake", action="store_true")
    parser.add_argument("--serve", action="store_true")
    parser.add_argument("--pack", default=None)
    args = parser.parse_args()
    if args.bake:
        return child_bake(args)
    if args.serve:
        return child_serve(args)

    import tempfile

    from mythril_tpu.service.client import ServiceClient

    t_start = time.monotonic()
    root = tempfile.mkdtemp(prefix="myth-cpsmoke-")
    pack_dir = os.path.join(root, "pack")
    summary: dict = {"root": root}

    # -- phase 1: bake ---------------------------------------------------
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--bake",
         "--pack", pack_dir],
        capture_output=True, text=True, timeout=600,
        cwd=str(Path(__file__).resolve().parent.parent),
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert out.returncode == 0, f"bake failed: {out.stderr[-2000:]}"
    baked = json.loads(
        next(l for l in out.stdout.splitlines()
             if l.startswith("CP-BAKED ")).split(None, 1)[1]
    )
    assert baked["artifacts"] >= 1, f"empty pack: {baked}"
    summary["bake"] = baked

    # -- phase 2: packed replica boots ready, zero compiles --------------
    child, url, ready_pack = spawn_serve(pack_dir)
    client = ServiceClient(url, retries=5, backoff_s=0.2)
    try:
        stats = client.stats()
        plane = stats["kernel"]["compileplane"]
        assert plane["pack_mount"]["mounted"] >= 1, plane
        assert plane["pack_mount"]["refused"] == 0, plane
        assert stats["kernel"]["generic_aot"]["compiles"] == 0, (
            "packed replica compiled its generic wave in-process"
        )
        reports_pack = settle_all(client)
        # the served waves rode the pack too: still zero compiles
        stats = client.stats()
        assert stats["kernel"]["generic_aot"]["compiles"] == 0, (
            "a served wave recompiled a packed bucket"
        )
        assert stats["kernel"]["compileplane"]["kernel_pack_hit_rate"] > 0
        summary["ready_pack_s"] = round(ready_pack, 3)
    finally:
        os.kill(child.pid, signal.SIGKILL)
        child.wait(timeout=30)

    # -- phase 3: SIGKILL happened above; restart over the same pack -----
    child2, url2, ready_pack2 = spawn_serve(pack_dir)
    client2 = ServiceClient(url2, retries=5, backoff_s=0.2)
    try:
        stats = client2.stats()
        assert stats["kernel"]["compileplane"]["pack_mount"]["mounted"] >= 1
        assert stats["kernel"]["generic_aot"]["compiles"] == 0
        reports_pack2 = settle_all(client2)
        assert reports_pack2 == reports_pack, (
            f"restart changed results: {reports_pack2} != {reports_pack}"
        )
        summary["ready_pack_restart_s"] = round(ready_pack2, 3)
    finally:
        os.kill(child2.pid, signal.SIGKILL)
        child2.wait(timeout=30)

    # -- phase 4: the packless replica pays the compile ------------------
    child3, url3, ready_no_pack = spawn_serve(None)
    client3 = ServiceClient(url3, retries=5, backoff_s=0.2)
    try:
        stats = client3.stats()
        assert stats["kernel"]["compileplane"] == {"enabled": False}, (
            stats["kernel"]["compileplane"]
        )
        reports_no_pack = settle_all(client3)
        assert reports_no_pack == reports_pack, (
            "pack vs no-pack reports diverge: "
            f"{reports_no_pack} != {reports_pack}"
        )
        summary["ready_no_pack_s"] = round(ready_no_pack, 3)
        cold_best = min(ready_pack, ready_pack2)
        assert cold_best < ready_no_pack, (
            f"pack gave no cold-start win: {cold_best} vs {ready_no_pack}"
        )
    finally:
        os.kill(child3.pid, signal.SIGKILL)
        child3.wait(timeout=30)

    # -- phase 5: MYTHRIL_NO_AOT degrade with attribution ----------------
    child4, url4, ready_no_aot = spawn_serve(
        pack_dir, env_extra={"MYTHRIL_NO_AOT": "1"}
    )
    client4 = ServiceClient(url4, retries=5, backoff_s=0.2)
    try:
        stats = client4.stats()
        plane = stats["kernel"]["compileplane"]
        # nothing mounted, and the refusal is attributed, not silent
        assert plane.get("pack_mount", {}).get("mounted", 0) == 0, plane
        reports_no_aot = settle_all(client4)
        assert reports_no_aot == reports_pack
        plane = client4.stats()["kernel"]["compileplane"]
        assert plane.get("unsupported", {}).get("disabled", 0) >= 1, (
            f"degrade reason not attributed: {plane}"
        )
        summary["ready_no_aot_s"] = round(ready_no_aot, 3)
    finally:
        os.kill(child4.pid, signal.SIGKILL)
        child4.wait(timeout=30)

    summary["wall_s"] = round(time.monotonic() - t_start, 1)
    print(f"compileplane smoke OK: {json.dumps(summary)}")
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except AssertionError as why:
        print(f"compileplane smoke FAILED: {why}", file=sys.stderr)
        sys.exit(1)
