"""Chain-head streaming chaos harness (ISSUE 16 acceptance).

The contract under test: a real `myth watch` subprocess following a
scripted fake chain over TWO real HTTP JSON-RPC endpoints survives
every fault the outside world throws in one run —

1. ~40 blocks with injected deployments (survivor shapes + inert
   ones) stream in while the watcher follows: every deployment on
   the final canonical chain must have a live alert (zero missed);
2. one RPC endpoint dies mid-stream (503 on every call): the death
   breaker opens and the stream continues on the survivor endpoint;
3. the watcher is SIGKILLed mid-stream and restarted with
   `--recover`: the fsync'd cursor replays, the tip block is
   redelivered, and content-derived alert ids absorb the duplicates
   (at-least-once, no double alerts);
4. a 3-block reorg orphans a block carrying a deployment: the
   cursor rolls back to the common ancestor and the orphaned alert
   is RETRACTED while replacements ingest;
5. the alert p50 (block seen -> alert fired) stays under the
   block-time budget.

The fake endpoints are real HTTP servers (stdlib, in-parent threads)
speaking real JSON-RPC to the unmodified hardened client — only the
chain behind them is scripted. No `--front` is mounted: the fleet
handoff is pinned by tests/chainstream (FakeFront) and the fleet's
own harness; this one owns the RPC/cursor/alert fault surface.

Usage:
    python tools/chainstream_smoke.py          # the full harness
    python tools/chainstream_smoke.py --child ... (internal)

Exits 0 on success; prints the failing assertion and exits 1.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import signal
import subprocess
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

#: survivor shapes (module-applicable, never static-answered) and one
#: inert shape the static tier settles at line rate
SURVIVORS = ["33ff", "32ff", "336000556000ff"]
INERT = "00"

BLOCK_GAP_S = 0.06  # scripted block time
ALERT_BUDGET_S = 2.0  # the p50 gate (way under the default 12s)


def _sha(text: str) -> str:
    return "0x" + hashlib.sha256(text.encode()).hexdigest()


def _addr(seed: str) -> str:
    return "0x" + hashlib.sha256(seed.encode()).hexdigest()[:40]


# ---------------------------------------------------------------------------
# the scripted chain + fake endpoints (parent side)
# ---------------------------------------------------------------------------
class ScriptedChain:
    """The canonical chain the fake endpoints serve, under one lock."""

    def __init__(self):
        self.mu = threading.Lock()
        self.blocks = []
        self.codes = {}
        self.receipts = {}
        self.add_block()  # genesis

    def head(self) -> int:
        with self.mu:
            return len(self.blocks) - 1

    def add_block(self, deployments=(), salt="main"):
        with self.mu:
            number = len(self.blocks)
            parent = (
                self.blocks[-1]["hash"] if self.blocks
                else "0x" + "0" * 64
            )
            txs = []
            for i, (address, code_hex) in enumerate(deployments):
                txh = _sha(f"tx:{number}:{i}:{salt}")
                txs.append({"hash": txh, "to": None, "input": "0x"})
                self.receipts[txh] = {
                    "transactionHash": txh,
                    "contractAddress": address,
                }
                self.codes[address.lower()] = "0x" + code_hex
            block = {
                "number": hex(number),
                "hash": _sha(f"block:{number}:{salt}"),
                "parentHash": parent,
                "transactions": txs,
            }
            self.blocks.append(block)
            return block

    def reorg(self, depth: int, salt: str):
        """Orphan the last `depth` blocks; the caller regrows."""
        with self.mu:
            orphaned = self.blocks[-depth:]
            self.blocks = self.blocks[:-depth]
            return orphaned

    def rpc(self, method, params):
        with self.mu:
            if method == "eth_blockNumber":
                return hex(len(self.blocks) - 1)
            if method == "eth_getBlockByNumber":
                number = int(params[0], 16)
                if 0 <= number < len(self.blocks):
                    return self.blocks[number]
                raise LookupError(f"unknown block {number}")
            if method == "eth_getTransactionReceipt":
                receipt = self.receipts.get(params[0])
                if receipt is None:
                    raise LookupError("unknown transaction")
                return receipt
            if method == "eth_getCode":
                return self.codes.get(params[0].lower(), "0x")
        raise LookupError(f"unsupported method {method}")


def make_endpoint(chain: ScriptedChain):
    """One fake execution client: (server, url, down_flag)."""
    down = threading.Event()

    class Handler(BaseHTTPRequestHandler):
        def do_POST(self):  # noqa: N802 (stdlib casing)
            if down.is_set():
                self.send_response(503)
                self.end_headers()
                return
            length = int(self.headers.get("Content-Length") or 0)
            request = json.loads(self.rfile.read(length))
            body = {"jsonrpc": "2.0", "id": request.get("id")}
            try:
                body["result"] = chain.rpc(
                    request["method"], request.get("params") or []
                )
            except LookupError as why:
                body["error"] = {"code": -32001, "message": str(why)}
            payload = json.dumps(body).encode()
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(payload)))
            self.end_headers()
            self.wfile.write(payload)

        def log_message(self, *args):  # quiet
            pass

    server = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    return server, f"http://127.0.0.1:{server.server_port}", down


# ---------------------------------------------------------------------------
# the watcher child (real `myth watch` through the real CLI)
# ---------------------------------------------------------------------------
def child_main(args) -> int:
    from mythril_tpu.interfaces.cli import main as cli_main

    argv = ["myth", "watch", "--state", args.state,
            "--poll-interval", "0.05",
            "--rpc-timeout", "2.0",
            "--start-block", "0",
            "--backfill-batch", "8",
            "--alert-budget", str(ALERT_BUDGET_S)]
    for url in args.rpc:
        argv += ["--rpc", url]
    if args.recover:
        argv.append("--recover")
    sys.argv = argv
    cli_main()
    return 0


def spawn_watcher(state: str, urls, recover=False):
    cmd = [sys.executable, os.path.abspath(__file__), "--child",
           "--state", state]
    for url in urls:
        cmd += ["--rpc", url]
    if recover:
        cmd.append("--recover")
    return subprocess.Popen(
        cmd, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        text=True, cwd=str(Path(__file__).resolve().parent.parent),
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )


def wait_for_tip(state: str, number: int, timeout_s: float = 60.0) -> bool:
    """Parent-side read-only replay of the cursor segments until the
    recorded tip reaches `number`."""
    from mythril_tpu.chainstream import replay_dir

    deadline = time.monotonic() + timeout_s
    cursor_dir = os.path.join(state, "cursor")
    while time.monotonic() < deadline:
        facts = replay_dir(cursor_dir)
        chain = facts["chain"]
        if chain and chain[-1].number >= number:
            return True
        time.sleep(0.1)
    return False


def read_alert_log(state: str):
    """(live_by_codehash_blockhash, retracted_ids, latencies)."""
    fired = {}
    status = {}
    latencies = []
    path = os.path.join(state, "alerts.jsonl")
    with open(path) as fp:
        for line in fp:
            if not line.strip():
                continue
            rec = json.loads(line)
            event = rec.get("event")
            if event == "fired":
                fired[rec["alert_id"]] = rec
                status[rec["alert_id"]] = "fired"
                if rec.get("latency_s") is not None:
                    latencies.append(rec["latency_s"])
            elif event in ("retracted", "superseded"):
                status[rec["alert_id"]] = event
    return fired, status, latencies


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--child", action="store_true")
    parser.add_argument("--state", default=None)
    parser.add_argument("--rpc", action="append", default=[])
    parser.add_argument("--recover", action="store_true")
    args = parser.parse_args()
    if args.child:
        return child_main(args)

    import tempfile

    t_start = time.monotonic()
    root = tempfile.mkdtemp(prefix="myth-chainstream-")
    state = os.path.join(root, "state")
    summary: dict = {"root": root}
    chain = ScriptedChain()
    servers = []
    child = None
    try:
        ep0, url0, down0 = make_endpoint(chain)
        ep1, url1, down1 = make_endpoint(chain)
        servers = [ep0, ep1]
        urls = [url0, url1]

        # phase 1 -- follow ~18 blocks, then SIGKILL mid-stream
        deployed = {}  # address -> code, expected LIVE at the end
        child = spawn_watcher(state, urls)
        for n in range(1, 18):
            if n % 3 == 0:
                code = SURVIVORS[(n // 3) % len(SURVIVORS)]
                address = _addr(f"p1:{n}")
                chain.add_block(deployments=[(address, code)])
                deployed[address] = code
            elif n % 7 == 0:
                chain.add_block(
                    deployments=[(_addr(f"inert:{n}"), INERT)]
                )
            else:
                chain.add_block()
            time.sleep(BLOCK_GAP_S)
        assert wait_for_tip(state, chain.head()), (
            "phase-1 watcher never caught the head"
        )
        os.kill(child.pid, signal.SIGKILL)
        child.wait(timeout=30)
        summary["phase1_head"] = chain.head()
        fired_before, _, _ = read_alert_log(state)
        assert fired_before, "phase 1 fired no alerts"
        summary["phase1_alerts"] = len(fired_before)

        # phase 2 -- restart with --recover; kill an endpoint; reorg
        child = spawn_watcher(state, urls, recover=True)
        for n in range(chain.head() + 1, 34):
            if n % 3 == 0:
                code = SURVIVORS[n % len(SURVIVORS)]
                address = _addr(f"p2:{n}")
                chain.add_block(deployments=[(address, code)])
                deployed[address] = code
            else:
                chain.add_block()
            if n == 24:
                down0.set()  # endpoint 0 dies mid-stream
                summary["endpoint_killed_at"] = n
            time.sleep(BLOCK_GAP_S)
        assert wait_for_tip(state, chain.head()), (
            "stream stalled after the endpoint death"
        )

        # the 3-block reorg: orphan a block CARRYING a deployment
        orphan_addr = _addr("orphan")
        chain.add_block(deployments=[(orphan_addr, SURVIVORS[0])])
        chain.add_block()
        chain.add_block()
        assert wait_for_tip(state, chain.head()), (
            "watcher never saw the pre-reorg blocks"
        )
        # give the tip alert a beat to land in the log, then fork
        time.sleep(0.5)
        chain.reorg(3, salt="fork")
        replacement = _addr("replacement")
        chain.add_block(deployments=[(replacement, SURVIVORS[1])],
                        salt="fork")
        deployed[replacement] = SURVIVORS[1]
        chain.add_block(salt="fork")
        chain.add_block(salt="fork")
        chain.add_block(salt="fork")  # the fork extends past the old head
        assert wait_for_tip(state, chain.head()), (
            "watcher never crossed the reorg"
        )
        time.sleep(0.5)  # let the retraction + replacement alerts land

        child.send_signal(signal.SIGTERM)  # clean drain -> stats JSON
        out, _ = child.communicate(timeout=60)
        stats = json.loads(out.strip().splitlines()[-1])
        summary["final_head"] = chain.head()

        # -- assertions -------------------------------------------------
        fired, status, latencies = read_alert_log(state)
        by_addr = {
            rec["address"]: rec for rec in fired.values()
            if status[rec["alert_id"]] != "retracted"
        }
        missed = [a for a in deployed if a not in by_addr]
        assert not missed, f"missed deployments: {missed}"
        summary["deployments"] = len(deployed)

        orphan_ids = [
            rec["alert_id"] for rec in fired.values()
            if rec["address"] == orphan_addr
        ]
        assert orphan_ids, "the orphaned deployment never alerted"
        assert all(status[i] == "retracted" for i in orphan_ids), (
            f"orphaned alert not retracted: "
            f"{[(i, status[i]) for i in orphan_ids]}"
        )
        assert stats["reorgs"] >= 1, stats
        summary["reorgs"] = stats["reorgs"]
        summary["deepest_reorg"] = stats["deepest_reorg"]

        # recovery: the phase-2 child replayed the phase-1 cursor
        recovered = stats.get("recovered") or {}
        assert recovered.get("records", 0) > 0, recovered
        assert recovered.get("clean_shutdown") in (False, "False"), (
            f"SIGKILL must not look like a clean drain: {recovered}"
        )
        summary["recovered_records"] = recovered["records"]
        summary["redelivered"] = recovered.get("redelivered")
        # no double alerts from the redelivery: one live alert per
        # deployed address
        addresses = [
            rec["address"] for rec in fired.values()
            if rec["address"] in deployed
        ]
        assert len(addresses) == len(set(addresses)), (
            "duplicate alerts for one (code, block) after recovery"
        )

        # the dead endpoint opened its breaker; the stream survived
        pool = stats["pool"]
        dead = [
            ep for ep in pool["endpoints"]
            if ep["transport_failures"] > 0 and not ep["alive"]
        ]
        assert dead, f"no endpoint death registered: {pool}"
        assert pool["up"] >= 1, pool

        # alert latency: p50 under the block-time budget
        assert latencies, "no alert latencies recorded"
        latencies.sort()
        p50 = latencies[len(latencies) // 2]
        assert p50 < ALERT_BUDGET_S, (
            f"alert p50 {p50:.3f}s over the {ALERT_BUDGET_S}s budget"
        )
        summary["alert_p50_s"] = round(p50, 4)
        summary["alerts_fired"] = len(fired)
        summary["wall_s"] = round(time.monotonic() - t_start, 1)
        print("CHAINSTREAM-SMOKE OK " + json.dumps(summary, sort_keys=True))
        return 0
    except AssertionError as why:
        print(f"CHAINSTREAM-SMOKE FAIL: {why}", file=sys.stderr)
        print(json.dumps(summary, sort_keys=True), file=sys.stderr)
        return 1
    finally:
        if child is not None and child.poll() is None:
            child.send_signal(signal.SIGKILL)
            try:
                child.wait(timeout=15)
            except Exception:
                pass
        for server in servers:
            server.shutdown()


if __name__ == "__main__":
    sys.exit(main())
