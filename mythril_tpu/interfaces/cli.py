#!/usr/bin/env python3
"""Command-line interface.

Covers mythril/interfaces/cli.py: the same command tree
(`analyze|disassemble|pro|read-storage|leveldb-search|function-to-hash|
hash-to-address|list-detectors|version|truffle|help`) with the same
flags, defaults and output behavior, so `myth analyze ...` invocations
are drop-in. The implementation is table-driven: every flag lives in a
declarative spec below and the parsers are assembled in loops; command
dispatch is a name -> handler registry.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import signal
import sys
import traceback
from argparse import ArgumentParser, Namespace, RawTextHelpFormatter

from mythril_tpu import __version__ as VERSION
from mythril_tpu import mythx
from mythril_tpu.analysis.module import ModuleLoader
from mythril_tpu.exceptions import (
    AddressNotFoundError,
    CriticalError,
    DeadlineExpiredError,
    DetectorNotFoundError,
    DevicePrepassError,
)
from mythril_tpu.laser.ethereum.transaction.symbolic import ACTORS
from mythril_tpu.mythril import (
    MythrilAnalyzer,
    MythrilConfig,
    MythrilDisassembler,
    MythrilLevelDB,
)
from mythril_tpu.plugin.loader import MythrilPluginLoader

# initialise the extension system at import, as the reference does
_ = MythrilPluginLoader()

log = logging.getLogger(__name__)

ANALYZE_LIST = ("analyze", "a")
DISASSEMBLE_LIST = ("disassemble", "d")
PRO_LIST = ("pro", "p")

COMMAND_LIST = (
    ANALYZE_LIST
    + DISASSEMBLE_LIST
    + PRO_LIST
    + (
        "read-storage",
        "leveldb-search",
        "function-to-hash",
        "hash-to-address",
        "list-detectors",
        "lint",
        "graph",
        "serve",
        "fleet",
        "watch",
        "kernels",
        "submit",
        "solverlab",
        "route",
        "observe",
        "version",
        "truffle",
        "help",
    )
)

LOG_LEVELS = (
    logging.NOTSET,
    logging.CRITICAL,
    logging.ERROR,
    logging.WARNING,
    logging.INFO,
    logging.DEBUG,
)

# ---------------------------------------------------------------------------
# flag specs: (flags tuple, kwargs) rows, grouped by the shared parser
# that carries them
# ---------------------------------------------------------------------------
RUNTIME_INPUT_FLAGS = [
    (
        ("-a", "--address"),
        dict(help="pull contract from the blockchain", metavar="CONTRACT_ADDRESS"),
    ),
    (
        ("--bin-runtime",),
        dict(
            action="store_true",
            help=(
                "Only when -c or -f is used. Consider the input bytecode as "
                "binary runtime code, default being the contract creation "
                "bytecode."
            ),
        ),
    ),
]

CREATION_INPUT_FLAGS = [
    (
        ("-c", "--code"),
        dict(
            help='hex-encoded bytecode string ("6060604052...")',
            metavar="BYTECODE",
        ),
    ),
    (
        ("-f", "--codefile"),
        dict(
            help=(
                "file containing hex-encoded bytecode string; several "
                "files load one contract each, named after the file"
            ),
            metavar="BYTECODEFILE",
            type=argparse.FileType("r"),
            nargs="+",
        ),
    ),
]

OUTPUT_FLAGS = [
    (
        ("-o", "--outform"),
        dict(
            choices=["text", "markdown", "json", "jsonv2"],
            default="text",
            help="report output format",
            metavar="<text/markdown/json/jsonv2>",
        ),
    )
]

RPC_FLAGS = [
    (
        ("--rpc",),
        dict(
            help="custom RPC settings",
            metavar="HOST:PORT / ganache / infura-[network_name]",
            default="infura-mainnet",
        ),
    ),
    (("--rpctls",), dict(type=bool, default=False, help="RPC connection over TLS")),
]

UTILITY_FLAGS = [
    (
        ("--solc-json",),
        dict(
            help=(
                "Json for the optional 'settings' parameter of solc's "
                "standard-json input"
            )
        ),
    ),
    (
        ("--solv",),
        dict(
            help=(
                "specify solidity compiler version. If not present, will try "
                "to install it (Experimental)"
            ),
            metavar="SOLV",
        ),
    ),
]

ANALYZE_COMMAND_FLAGS = [
    (("-g", "--graph"), dict(help="generate a control flow graph")),
    (
        ("-j", "--statespace-json"),
        dict(help="dumps the statespace json", metavar="OUTPUT_FILE"),
    ),
    (
        ("--truffle",),
        dict(
            action="store_true",
            help="analyze a truffle project (run from project dir)",
        ),
    ),
    (("--infura-id",), dict(help="set infura id for onchain analysis")),
]

ANALYZE_OPTION_FLAGS = [
    (
        ("-m", "--modules"),
        dict(
            help="Comma-separated list of security analysis modules",
            metavar="MODULES",
        ),
    ),
    (
        ("--max-depth",),
        dict(
            type=int,
            default=128,
            help="Maximum recursion depth for symbolic execution",
        ),
    ),
    (
        ("--call-depth-limit",),
        dict(
            type=int,
            default=3,
            help="Maximum call depth limit for symbolic execution",
        ),
    ),
    (
        ("--strategy",),
        dict(
            choices=["dfs", "bfs", "naive-random", "weighted-random"],
            default="bfs",
            help="Symbolic execution strategy",
        ),
    ),
    (
        ("-b", "--loop-bound"),
        dict(type=int, default=3, help="Bound loops at n iterations", metavar="N"),
    ),
    (
        ("-t", "--transaction-count"),
        dict(
            type=int,
            default=2,
            help="Maximum number of transactions issued by laser",
        ),
    ),
    (
        ("--execution-timeout",),
        dict(
            type=int,
            default=86400,
            help="The amount of seconds to spend on symbolic execution",
        ),
    ),
    (
        ("--solver-timeout",),
        dict(
            type=int,
            default=10000,
            help=(
                "The maximum amount of time(in milli seconds) the solver "
                "spends for queries from analysis modules"
            ),
        ),
    ),
    (
        ("--create-timeout",),
        dict(
            type=int,
            default=10,
            help="The amount of seconds to spend on the initial contract creation",
        ),
    ),
    (
        ("--parallel-solving",),
        dict(
            action="store_true",
            help="Enable solving solver queries in parallel",
        ),
    ),
    (
        ("--no-onchain-data",),
        dict(
            action="store_true",
            help=(
                "Don't attempt to retrieve contract code, variables and "
                "balances from the blockchain"
            ),
        ),
    ),
    (
        ("--deterministic-solving",),
        dict(
            action="store_true",
            help=(
                "Conflict-budget solver marathons so reports are "
                "reproducible across machines and load (slightly less "
                "complete on hard queries than pure wall budgets)"
            ),
        ),
    ),
    (
        ("--deadline",),
        dict(
            type=float,
            default=None,
            metavar="SECONDS",
            help=(
                "Wall-clock budget for the WHOLE run: solver queries "
                "clamp to it, device waves stop at it, and on expiry "
                "the analysis degrades per --on-timeout instead of "
                "running past the budget"
            ),
        ),
    ),
    (
        ("--on-timeout",),
        dict(
            choices=["partial", "fail"],
            default="partial",
            help=(
                "What an expired --deadline produces: 'partial' emits "
                "the report built so far, marked partial with "
                "per-contract completion status and degradation-reason "
                "counts; 'fail' exits with an error"
            ),
        ),
    ),
    (
        ("--corpus-shard",),
        dict(
            default=None,
            metavar="I/N",
            help=(
                "Analyze only this host's shard of the input contracts "
                "(deterministic content-hash partition; run one myth per "
                "host with I=0..N-1 and merge the reports)"
            ),
        ),
    ),
    (
        ("--sparse-pruning",),
        dict(
            action="store_true",
            help=(
                "Checks for reachability after the end of tx. Recommended "
                "for short execution timeouts < 1 min"
            ),
        ),
    ),
    (
        ("--no-static-prune",),
        dict(
            action="store_true",
            help=(
                "Disable the static bytecode prepass (CFG recovery + "
                "constant dataflow): detection-module pre-screening, "
                "dispatcher-seed masking, and flip-frontier pruning "
                "all switch off — the differential baseline for a "
                "suspected wrong prune"
            ),
        ),
    ),
    (
        ("--store",),
        dict(
            default=None,
            metavar="DIR",
            help=(
                "Cross-run verdict store directory (env "
                "MYTHRIL_STORE_DIR): repeat contracts settle from the "
                "banked (codehash, config-fingerprint) verdict, "
                "near-duplicate forks re-analyze only their changed "
                "selectors, and completed analyses write their "
                "verdicts back"
            ),
        ),
    ),
    (
        ("--no-store",),
        dict(
            action="store_true",
            help=(
                "Disable the verdict store entirely (no lookups, no "
                "incremental re-analysis, no write-back) even when a "
                "directory is configured — the parity-differential "
                "baseline for a suspected stale or wrong cached "
                "verdict"
            ),
        ),
    ),
    (
        ("--no-pipeline",),
        dict(
            action="store_true",
            help=(
                "Disable the pipelined wave engine (double-buffered "
                "async dispatch + donated arena buffers): the device "
                "exploration falls back to the lock-step "
                "dispatch/harvest/solve schedule — the differential "
                "baseline for a suspected pipelining bug"
            ),
        ),
    ),
    (
        ("--no-specialize",),
        dict(
            action="store_true",
            help=(
                "Disable per-contract specialized step kernels "
                "(opcode-set phase pruning + superblock fusion from "
                "the static summary): device waves run the generic "
                "opcode-switch interpreter — the differential "
                "baseline for a suspected specialization bug"
            ),
        ),
    ),
    (
        ("--no-blockjit",),
        dict(
            action="store_true",
            help=(
                "Disable the block-level JIT (whole CFG basic blocks "
                "advanced per kernel iteration): specialized kernels "
                "fall back to PR-6 superblock fusion only — the "
                "differential baseline for a suspected block-lowering "
                "bug (env: MYTHRIL_NO_BLOCKJIT=1)"
            ),
        ),
    ),
    (
        ("--host-first-funnel",),
        dict(
            action="store_true",
            help=(
                "Restore the legacy host-first solver funnel: the "
                "per-query CDCL sprint sees every flip query before "
                "the batched device dispatch. Default is the "
                "device-first funnel (diversified SLS portfolio + "
                "enumeration + cube-and-conquer first, host CDCL as "
                "the escalation ladder) — this flag is the parity "
                "differential baseline for a suspected funnel bug"
            ),
        ),
    ),
    (
        ("--sprint-cap-s",),
        dict(
            type=float,
            default=None,
            metavar="SECONDS",
            help=(
                "Wall cap for the escalation ladder's host-CDCL pass "
                "over one wave's flip survivors (default 5.0, env "
                "MYTHRIL_SPRINT_CAP_S); capped queries are recorded "
                "SPRINT_PREEMPTED with the actual cap in the loss "
                "artifact and retried next wave"
            ),
        ),
    ),
    (
        ("--trace-out",),
        dict(
            default=None,
            metavar="FILE",
            help=(
                "Write the run's structured-span timeline as "
                "Chrome/Perfetto trace JSON (open at "
                "https://ui.perfetto.dev): device waves, host "
                "harvest/solve, kernel compiles, mesh steals — the "
                "flight recorder's full view of where the wall went"
            ),
        ),
    ),
    (
        ("--observe-out",),
        dict(
            default=None,
            metavar="DIR",
            help=(
                "Telemetry output directory: per-contract routing-"
                "feature records (routing_features.jsonl — the "
                "host/device cost-model training set) plus automatic "
                "flight-recorder dumps on mesh/deadline degradations"
            ),
        ),
    ),
    (
        ("--no-observe",),
        dict(
            action="store_true",
            help=(
                "Disable telemetry recording (spans, solver "
                "attribution, routing records): the zero-overhead "
                "differential baseline — issue sets are identical "
                "with and without"
            ),
        ),
    ),
    (
        ("--capture-queries",),
        dict(
            default=None,
            metavar="DIR",
            help=(
                "Solver query flight recorder: serialize every solved "
                "SMT query into DIR as a content-addressed, replayable "
                "artifact (lowered program + shape bucket + origin + "
                "verdict/wall/loss-reason observations). Replay the "
                "corpus offline with `myth solverlab`"
            ),
        ),
    ),
    (
        ("--device-prepass",),
        dict(
            choices=["auto", "always", "never"],
            default="auto",
            help=(
                "Run the accelerator symbolic exploration before the host "
                "walk (auto: on when an accelerator backend is present)"
            ),
        ),
    ),
    (
        ("--device-solving",),
        dict(
            choices=["auto", "always", "never"],
            default="auto",
            help=(
                "Allow the on-chip portfolio to answer solver queries the "
                "CDCL sprint cannot (auto: on with an accelerator backend)"
            ),
        ),
    ),
    (
        ("--device-prepass-budget",),
        dict(
            type=float,
            default=12.0,
            help="Wall-clock seconds the device prepass may spend per contract",
        ),
    ),
    (
        ("--devices",),
        dict(
            type=int,
            default=None,
            metavar="N",
            help=(
                "Shard the corpus over N device groups (multi-chip "
                "corpus scheduler): one wave engine per group, "
                "cross-group work stealing, per-group failure "
                "domains. Default: one lane-sharded engine over all "
                "visible devices"
            ),
        ),
    ),
    (
        ("--device-ownership",),
        dict(
            choices=["auto", "always", "never"],
            default="auto",
            help=(
                "Let the device OWN contracts its exploration covered "
                "end-to-end: issues come from the banked concrete "
                "evidence and the host walk is skipped (auto: on when "
                "an accelerator backend is present)"
            ),
        ),
    ),
    (
        ("--unconstrained-storage",),
        dict(
            action="store_true",
            help=(
                "Default storage value is symbolic, turns off the on-chain "
                "storage loading"
            ),
        ),
    ),
    (("--phrack",), dict(action="store_true", help="Phrack-style call graph")),
    (
        ("--enable-physics",),
        dict(action="store_true", help="enable graph physics simulation"),
    ),
    (
        ("-q", "--query-signature"),
        dict(
            action="store_true",
            help="Lookup function signatures through www.4byte.directory",
        ),
    ),
    (
        ("--enable-iprof",),
        dict(action="store_true", help="enable the instruction profiler"),
    ),
    (
        ("--disable-dependency-pruning",),
        dict(action="store_true", help="Deactivate dependency-based pruning"),
    ),
    (
        ("--enable-coverage-strategy",),
        dict(action="store_true", help="enable coverage based search strategy"),
    ),
    (
        ("--custom-modules-directory",),
        dict(
            help=(
                "designates a separate directory to search for custom "
                "analysis modules"
            ),
            metavar="CUSTOM_MODULES_DIRECTORY",
        ),
    ),
    (
        ("--attacker-address",),
        dict(
            help="Designates a specific attacker address to use during analysis",
            metavar="ATTACKER_ADDRESS",
        ),
    ),
    (
        ("--creator-address",),
        dict(
            help="Designates a specific creator address to use during analysis",
            metavar="CREATOR_ADDRESS",
        ),
    ),
]

SOLIDITY_FILES_ARG = dict(
    nargs="*",
    help=(
        "Inputs file name and contract name. \n"
        "usage: file1.sol:OptionalContractName file2.sol "
        "file3.sol:OptionalContractName"
    ),
)


def _install_flags(parser, rows) -> None:
    for flags, kwargs in rows:
        parser.add_argument(*flags, **kwargs)


def _shared_parser(rows) -> ArgumentParser:
    parser = ArgumentParser(add_help=False)
    _install_flags(parser, rows)
    return parser


# ---------------------------------------------------------------------------
# error output
# ---------------------------------------------------------------------------
def exit_with_error(format_, message, exit_code=None):
    """Print the error in the requested output format and exit.
    `exit_code` defaults to the reference CLI's bare sys.exit() (code
    0); callers for whom the failure is a hard contract pass nonzero."""
    if format_ in ("text", "markdown"):
        log.error(message)
    elif format_ == "json":
        print(json.dumps({"success": False, "error": str(message), "issues": []}))
    else:
        print(
            json.dumps(
                [
                    {
                        "issues": [],
                        "sourceType": "",
                        "sourceFormat": "",
                        "sourceList": [],
                        "meta": {
                            "logs": [
                                {
                                    "level": "error",
                                    "hidden": True,
                                    "msg": str(message),
                                }
                            ]
                        },
                    }
                ]
            )
        )
    sys.exit(exit_code)


# ---------------------------------------------------------------------------
# parser assembly
# ---------------------------------------------------------------------------
def build_parser() -> ArgumentParser:
    rpc = _shared_parser(RPC_FLAGS)
    utilities = _shared_parser(UTILITY_FLAGS)
    runtime_input = _shared_parser(RUNTIME_INPUT_FLAGS)
    creation_input = _shared_parser(CREATION_INPUT_FLAGS)
    output = _shared_parser(OUTPUT_FLAGS)

    parser = argparse.ArgumentParser(
        description="Security analysis of Ethereum smart contracts"
    )
    parser.add_argument("--epic", action="store_true", help=argparse.SUPPRESS)
    parser.add_argument(
        "-v", type=int, help="log level (0-5)", metavar="LOG_LEVEL", default=2
    )

    subparsers = parser.add_subparsers(dest="command", help="Commands")

    analyzer = subparsers.add_parser(
        ANALYZE_LIST[0],
        help="Triggers the analysis of the smart contract",
        parents=[rpc, utilities, creation_input, runtime_input, output],
        aliases=ANALYZE_LIST[1:],
        formatter_class=RawTextHelpFormatter,
    )
    analyzer.add_argument("solidity_files", **SOLIDITY_FILES_ARG)
    _install_flags(analyzer.add_argument_group("commands"), ANALYZE_COMMAND_FLAGS)
    _install_flags(analyzer.add_argument_group("options"), ANALYZE_OPTION_FLAGS)

    disassembler = subparsers.add_parser(
        DISASSEMBLE_LIST[0],
        help="Disassembles the smart contract",
        aliases=DISASSEMBLE_LIST[1:],
        parents=[rpc, utilities, creation_input, runtime_input],
        formatter_class=RawTextHelpFormatter,
    )
    disassembler.add_argument(
        "solidity_files",
        nargs="*",
        help=(
            "Inputs file name and contract name. Currently supports a single "
            "contract\nusage: file1.sol:OptionalContractName"
        ),
    )

    pro = subparsers.add_parser(
        PRO_LIST[0],
        help="Analyzes input with the MythX API (https://mythx.io)",
        aliases=PRO_LIST[1:],
        parents=[utilities, creation_input, output],
        formatter_class=RawTextHelpFormatter,
    )
    pro.add_argument("solidity_files", **SOLIDITY_FILES_ARG)
    pro.add_argument(
        "--full",
        help="Run a full analysis. Default: quick analysis",
        action="store_true",
    )

    subparsers.add_parser(
        "list-detectors",
        parents=[output],
        help="Lists available detection modules",
    )

    read_storage = subparsers.add_parser(
        "read-storage",
        help="Retrieves storage slots from a given address through rpc",
        parents=[rpc],
    )
    read_storage.add_argument(
        "storage_slots",
        help="read state variables from storage index",
        metavar="INDEX,NUM_SLOTS,[array] / mapping,INDEX,[KEY1, KEY2...]",
    )
    read_storage.add_argument("address", help="contract address", metavar="ADDRESS")

    leveldb = subparsers.add_parser(
        "leveldb-search", help="Searches the code fragment in local leveldb"
    )
    leveldb.add_argument("search")
    leveldb.add_argument(
        "--leveldb-dir",
        help="specify leveldb directory for search or direct access operations",
        metavar="LEVELDB_PATH",
    )

    func_to_hash = subparsers.add_parser(
        "function-to-hash", help="Returns the hash signature of the function"
    )
    func_to_hash.add_argument(
        "func_name", help="calculate function signature hash", metavar="SIGNATURE"
    )

    hash_to_addr = subparsers.add_parser(
        "hash-to-address",
        help="converts the hashes in the blockchain to ethereum address",
    )
    hash_to_addr.add_argument(
        "hash", help="Find the address from hash", metavar="FUNCTION_NAME"
    )
    hash_to_addr.add_argument(
        "--leveldb-dir",
        help="specify leveldb directory for search or direct access operations",
        metavar="LEVELDB_PATH",
    )

    lint = subparsers.add_parser(
        "lint",
        help=(
            "Static bytecode analysis only: CFG recovery, constant "
            "dataflow, dead-code/dead-branch findings, and the "
            "detector pre-screen — pure host work, sub-second, no "
            "device initialization"
        ),
        parents=[rpc, utilities, creation_input, runtime_input, output],
        formatter_class=RawTextHelpFormatter,
    )
    lint.add_argument("solidity_files", **SOLIDITY_FILES_ARG)
    lint.add_argument(
        "--fail-on",
        action="append",
        metavar="CHECK",
        default=None,
        help=(
            "exit nonzero when the named lint check fires on any "
            "contract (repeatable) — makes `myth lint` usable as a "
            "CI gate. Checks: unreachable-code, invalid-jump-target, "
            "stack-underflow, dead-branch, inert-function, "
            "tainted-jump-target, tainted-delegatecall-target, "
            "tx-origin-as-auth, unprotected-selfdestruct, "
            "delegatecall-to-upgradeable-target, "
            "proxy-storage-collision, tainted-cross-contract-call-arg, "
            "untrusted-return-data-in-guard"
        ),
    )

    graph = subparsers.add_parser(
        "graph",
        help=(
            "Cross-contract static linker: join every input "
            "contract's call sites into one typed inter-contract call "
            "graph (provenance-annotated edges, proxy pairing, "
            "escape summaries, arena co-location plan) — pure host "
            "work, sub-second, no device initialization. Deployment "
            "addresses ride file/contract names as "
            "'name@0x<40-hex-addr>'"
        ),
        formatter_class=RawTextHelpFormatter,
    )
    graph.add_argument(
        "graph_inputs",
        nargs="+",
        metavar="DIR|FILE",
        help=(
            "directories and/or files of runtime bytecode hex "
            "(.hex/.sol.o/.bin-runtime or raw hex files)"
        ),
    )
    graph.add_argument(
        "--json",
        action="store_true",
        dest="graph_json",
        help="emit the full link-graph JSON payload (schema_version "
        "pinned) instead of the human summary",
    )

    serve = subparsers.add_parser(
        "serve",
        help=(
            "Run the persistent analysis service: a long-lived daemon "
            "that owns the device, serves analysis jobs over HTTP/JSON, "
            "and amortizes XLA compile across requests"
        ),
    )
    serve.add_argument("--host", default="127.0.0.1", help="bind address")
    serve.add_argument(
        "--port", type=int, default=7341, help="listen port (0 = ephemeral)"
    )
    serve.add_argument(
        "--stripes",
        type=int,
        default=4,
        help="arena stripes (max concurrently-resident contracts)",
    )
    serve.add_argument(
        "--lanes-per-stripe",
        type=int,
        default=8,
        help="device lanes per stripe",
    )
    serve.add_argument(
        "--steps-per-wave", type=int, default=256, help="EVM steps per wave"
    )
    serve.add_argument(
        "--max-waves",
        type=int,
        default=2,
        help="device waves per job before the host walk",
    )
    serve.add_argument(
        "--queue-capacity",
        type=int,
        default=64,
        help="admission queue bound (full queue answers 429)",
    )
    serve.add_argument(
        "--host-workers",
        type=int,
        default=1,
        help="host-analysis workers consuming finished stripes; with two "
        "or more (and a CPU left for the engine per extra worker) the "
        "walks run side by side in walker processes, otherwise one at a "
        "time in this process",
    )
    serve.add_argument(
        "--no-host-walk",
        action="store_true",
        help="device-only reports (skip the per-job host walk)",
    )
    serve.add_argument(
        "--execution-timeout",
        type=int,
        default=8,
        help="seconds of host walk per job",
    )
    serve.add_argument(
        "--transaction-count",
        type=int,
        default=2,
        help="attacker transactions the host walk models",
    )
    serve.add_argument(
        "--checkpoint-dir",
        default=None,
        help="where drain checkpoints land (default: a temp dir)",
    )
    serve.add_argument(
        "--no-pipeline",
        action="store_true",
        help=(
            "disable double-buffered wave pipelining (dispatch wave "
            "N+1 while harvesting wave N); lock-step waves instead"
        ),
    )
    serve.add_argument(
        "--no-specialize",
        action="store_true",
        help=(
            "disable contract-specialized step kernels (phase "
            "pruning + superblock fusion); every wave runs the "
            "generic interpreter"
        ),
    )
    serve.add_argument(
        "--no-blockjit",
        action="store_true",
        help=(
            "disable the block-level JIT; specialized kernels keep "
            "superblock fusion only (env: MYTHRIL_NO_BLOCKJIT=1)"
        ),
    )
    serve.add_argument(
        "--no-static-prune",
        action="store_true",
        help=(
            "disable the static layer for the whole service (detector "
            "pre-screen, seed mask, static-answer triage) — the "
            "full-mount parity baseline"
        ),
    )
    serve.add_argument(
        "--no-static-answer",
        action="store_true",
        help=(
            "keep the static prepass but disable ONLY the "
            "static-answer triage tier: provably-clean submissions go "
            "through the full wave/walk path anyway"
        ),
    )
    serve.add_argument(
        "--devices",
        type=int,
        default=1,
        metavar="N",
        help=(
            "split the arena over N device groups: one dispatch/"
            "harvest pair per group, jobs striped over groups at "
            "admission, idle groups steal resident jobs "
            "(/stats mesh.*). Stripes must divide evenly by N"
        ),
    )
    serve.add_argument(
        "--observe-out",
        default=None,
        metavar="DIR",
        help=(
            "telemetry output directory: degradation flight-recorder "
            "dumps land here and the drain's final flush prefers it "
            "over the checkpoint dir (live views: /metrics, /trace)"
        ),
    )
    serve.add_argument(
        "--no-observe",
        action="store_true",
        help="disable span/attribution/routing telemetry recording",
    )
    serve.add_argument(
        "--capture-queries",
        default=None,
        metavar="DIR",
        help=(
            "capture-at-serve: every SMT query the service solves "
            "lands in DIR as a replayable artifact (myth solverlab); "
            "live loss/capture counters at /stats solver.*"
        ),
    )
    serve.add_argument(
        "--store",
        default=None,
        metavar="DIR",
        help=(
            "cross-run verdict store (env MYTHRIL_STORE_DIR): repeat "
            "submissions settle DONE at admission from the banked "
            "(codehash, config-fingerprint) verdict — no queue slot, "
            "no wave — and completed walks write back; share one DIR "
            "across replicas so any of them answers any repeat "
            "(/stats store.*)"
        ),
    )
    serve.add_argument(
        "--no-store",
        action="store_true",
        help=(
            "disable the verdict store tier (no admission lookups, "
            "no write-back) even when a directory is configured"
        ),
    )
    serve.add_argument(
        "--journal",
        default=None,
        metavar="DIR",
        help=(
            "durable job journal: every job transition is an "
            "fsync'd append-only WAL record under DIR, so a "
            "SIGKILL/OOM mid-wave loses zero acknowledged jobs "
            "(restart with --recover to replay)"
        ),
    )
    serve.add_argument(
        "--recover",
        action="store_true",
        help=(
            "replay the --journal DIR at startup: terminal jobs are "
            "adopted as queryable history, non-terminal jobs "
            "re-admitted (deduping through the verdict store), and "
            "jobs in flight at a crash take a quarantine strike"
        ),
    )
    serve.add_argument(
        "--no-breakers",
        action="store_true",
        help=(
            "disable the tier circuit breakers (device dispatch, "
            "device-first solving, kernel compile, store I/O): every "
            "tier re-enters its full retry ladder per job — the "
            "pre-breaker differential baseline"
        ),
    )
    serve.add_argument(
        "--quarantine-strikes",
        type=int,
        default=2,
        metavar="N",
        help=(
            "wave-fault strikes before a codehash is quarantined "
            "(settled FAILED at admission, denylisted for the "
            "process lifetime); one strike short of N the job runs "
            "in a solo wave"
        ),
    )
    serve.add_argument(
        "--no-arena-warmup",
        action="store_true",
        help=(
            "skip the background arena warmup compile at startup: "
            "the service reports ready immediately and the FIRST "
            "request pays the kernel compile (default: warm up off "
            "the serving path; /healthz readiness reports "
            "arena-warming until the compile lands)"
        ),
    )
    serve.add_argument(
        "--health-interval",
        type=float,
        default=2.0,
        metavar="SECONDS",
        help=(
            "cadence of the health/device sampler thread (SLO burn "
            "rates, mtpu_health_state, mtpu_device_* gauges)"
        ),
    )
    serve.add_argument(
        "--kernel-pack",
        default=None,
        metavar="DIR",
        help=(
            "prebaked kernel pack (`myth kernels bake`): mounted "
            "synchronously at boot, before the server binds, so "
            "packed buckets dispatch with ZERO in-process compiles "
            "and /healthz readiness clears without waiting out the "
            "compile clock; share one DIR across replicas"
        ),
    )
    serve.add_argument(
        "--kernel-cache",
        default=None,
        metavar="DIR",
        help=(
            "persistent compile-artifact cache (env "
            "MYTHRIL_KERNEL_CACHE): every kernel compiled in-process "
            "is AOT-exported here and loaded back on the next boot "
            "instead of recompiling; safe to share across replicas "
            "(content-addressed, atomic writes)"
        ),
    )
    serve.add_argument(
        "--no-aot",
        action="store_true",
        help=(
            "disable AOT export/import (env MYTHRIL_NO_AOT=1): every "
            "compile site uses the plain in-process jit path — the "
            "parity-differential baseline for a suspected AOT bug"
        ),
    )
    serve.add_argument(
        "--router",
        default=None,
        metavar="DIR",
        help=(
            "learned tier-ladder router artifacts (`myth route "
            "train`; env MYTHRIL_ROUTER_DIR): admission prices each "
            "job per tier from the routing-log cost model and sends "
            "cheap-predicted work straight to the host walk; a tuned "
            "solver-default artifact (`myth solverlab tune --watch`) "
            "in the same DIR installs too. Absent/refused artifacts "
            "keep today's ladder bit-for-bit"
        ),
    )
    serve.add_argument(
        "--no-router",
        action="store_true",
        help=(
            "disable the learned router tier even when an artifact "
            "directory is configured — the parity baseline"
        ),
    )

    fleet = subparsers.add_parser(
        "fleet",
        help=(
            "Run the federated serving front: health-routed admission "
            "over N `myth serve` replicas with replica-death failover "
            "(idempotency-keyed resubmission dedupes through the "
            "fleet-shared verdict store), drain-time frontier "
            "rebalancing, and 503+Retry-After load shedding when the "
            "whole fleet is saturated"
        ),
    )
    fleet.add_argument(
        "--replica",
        action="append",
        dest="replicas",
        metavar="URL",
        default=None,
        help=(
            "a `myth serve` replica base URL (repeat per replica); "
            "replicas should share one --store directory so any of "
            "them answers any repeat"
        ),
    )
    fleet.add_argument("--host", default="127.0.0.1", help="bind address")
    fleet.add_argument(
        "--port", type=int, default=7340, help="listen port"
    )
    fleet.add_argument(
        "--probe-interval",
        type=float,
        default=1.0,
        metavar="SECONDS",
        help="cadence of the replica health/occupancy probe loop",
    )
    fleet.add_argument(
        "--probe-timeout",
        type=float,
        default=2.0,
        metavar="SECONDS",
        help=(
            "per-probe timeout; a hung probe counts as a failure "
            "toward the replica's death breaker"
        ),
    )
    fleet.add_argument(
        "--failover-threshold",
        type=int,
        default=3,
        metavar="N",
        help=(
            "consecutive failed probes before a replica's death "
            "breaker trips open and its in-flight jobs fail over to "
            "survivors"
        ),
    )
    fleet.add_argument(
        "--recovery-s",
        type=float,
        default=5.0,
        metavar="SECONDS",
        help=(
            "seconds before a dead replica's breaker half-opens (a "
            "restarted replica rejoins after one healthy probe)"
        ),
    )
    fleet.add_argument(
        "--retry-after",
        type=float,
        default=2.0,
        metavar="SECONDS",
        help=(
            "the Retry-After hint on fleet-wide 503 sheds (no "
            "routable replica accepted the submission)"
        ),
    )
    fleet.add_argument(
        "--journal",
        default=None,
        metavar="DIR",
        help=(
            "the front's own durable routing journal (same WAL as "
            "`myth serve --journal`): every routed admission is "
            "fsync'd with its code, idempotency key, and replica "
            "assignment before the 202"
        ),
    )
    fleet.add_argument(
        "--recover",
        action="store_true",
        help=(
            "replay the routing journal at startup: live jobs "
            "re-attach to their replicas, and the first probe sweep "
            "fails over whatever died with the front"
        ),
    )
    fleet.add_argument(
        "--store",
        default=None,
        metavar="DIR",
        help=(
            "the fleet-shared verdict-store directory (informational "
            "— replicas mount it themselves via `myth serve --store`; "
            "surfaced in /fleet/stats so operators can verify the "
            "fleet shares one)"
        ),
    )
    fleet.add_argument(
        "--kernel-pack",
        default=None,
        metavar="DIR",
        help=(
            "the fleet-shared prebaked kernel-pack directory (same "
            "contract as --store: replicas mount it via `myth serve "
            "--kernel-pack`; surfaced in /fleet/stats so operators "
            "can verify every replica boots warm from one pack)"
        ),
    )
    fleet.add_argument(
        "--router",
        default=None,
        metavar="DIR",
        help=(
            "router artifact directory (`myth route train`): replica "
            "choice becomes cost-informed — occupancy times the "
            "replica's measured settle EWMA — instead of raw "
            "least-loaded; absent/refused artifacts keep the "
            "least-loaded order bit-for-bit"
        ),
    )

    kernels = subparsers.add_parser(
        "kernels",
        help=(
            "Kernel-pack tooling over the persistent compile plane: "
            "bake hot specialization buckets into a prebaked pack "
            "ahead of time (bake), preflight-load a pack under this "
            "backend fingerprint (warm), inspect artifacts (ls), and "
            "LRU-trim / drop stale artifacts (gc). A baked pack "
            "mounts at `myth serve --kernel-pack DIR` for "
            "zero-compile cold starts"
        ),
    )
    kernels.add_argument(
        "kernels_mode",
        choices=["bake", "warm", "ls", "gc"],
        metavar="MODE",
        help="bake | warm | ls | gc",
    )
    kernels.add_argument(
        "pack_dir",
        metavar="DIR",
        help="the pack directory (created by bake if missing)",
    )
    kernels.add_argument(
        "--corpus",
        action="append",
        default=None,
        metavar="PATH",
        help=(
            "bake: contract file or directory (hex or raw EVM bytes) "
            "to mine specialization buckets from; repeatable"
        ),
    )
    kernels.add_argument(
        "--routing",
        action="append",
        default=None,
        metavar="FILE",
        help=(
            "bake: routing_features.jsonl from a running service "
            "(--observe-out): rows carrying a phase_bucket feature "
            "contribute their buckets; repeatable"
        ),
    )
    kernels.add_argument(
        "--buckets",
        action="append",
        default=None,
        metavar="FILE",
        help=(
            "bake: explicit bucket-list JSON (a list — or "
            '{"buckets": [...]} — of bucket records as `myth kernels '
            "ls` prints them); repeatable"
        ),
    )
    kernels.add_argument(
        "--stripes",
        type=int,
        default=4,
        help="bake: target arena stripes (match the serve flags)",
    )
    kernels.add_argument(
        "--lanes-per-stripe",
        type=int,
        default=8,
        help="bake: target device lanes per stripe",
    )
    kernels.add_argument(
        "--steps-per-wave",
        type=int,
        default=256,
        help="bake: target EVM steps per wave",
    )
    kernels.add_argument(
        "--code-cap",
        type=int,
        default=2048,
        help="bake: target code-capacity floor (pow2-bucketed)",
    )
    kernels.add_argument(
        "--generic-only",
        action="store_true",
        help=(
            "bake: only the generic interpreter kernel (no bucket "
            "mining) — covers the arena warmup and unspecialized "
            "waves, the minimum useful pack"
        ),
    )
    kernels.add_argument(
        "--capacity",
        type=int,
        default=256,
        help="gc: artifact count to LRU-trim the directory down to",
    )
    kernels.add_argument(
        "--drop-stale",
        action="store_true",
        help=(
            "gc: also unlink artifacts whose fingerprint does not "
            "match this backend (orphaned by a toolchain upgrade)"
        ),
    )
    kernels.add_argument(
        "--json", action="store_true", help="emit machine-readable JSON",
        dest="kernels_json",
    )

    watch = subparsers.add_parser(
        "watch",
        help=(
            "Stream the chain head into the warm service: follow new "
            "blocks over one or more JSON-RPC endpoints (failover + "
            "quorum head tracking), static-triage fresh deployments "
            "and proxy upgrades at line rate, hand survivors to a "
            "`myth fleet`/`myth serve` front under content-derived "
            "idempotency keys, and keep a crash-safe reorg-aware "
            "cursor with a fired/retracted/superseded alert log"
        ),
    )
    watch.add_argument(
        "--rpc",
        action="append",
        dest="rpc_urls",
        metavar="URL",
        default=None,
        help=(
            "an execution-client JSON-RPC endpoint (repeat per "
            "endpoint for failover; one endpoint dying must never "
            "stall the stream)"
        ),
    )
    watch.add_argument(
        "--front",
        default=None,
        metavar="URL",
        help=(
            "a `myth fleet` or `myth serve` base URL; survivors of "
            "the static triage are submitted there (omit for "
            "static-only alerting)"
        ),
    )
    watch.add_argument(
        "--state",
        default="./chainstream",
        metavar="DIR",
        help=(
            "the watcher's durable state: the fsync'd cursor journal "
            "and the append-only alert log live here"
        ),
    )
    watch.add_argument(
        "--recover",
        action="store_true",
        help=(
            "replay the cursor journal and alert log at startup and "
            "resume from the recorded tip (at-least-once: the tip "
            "block is redelivered; content-derived alert ids and "
            "idempotency keys absorb the duplicates)"
        ),
    )
    watch.add_argument(
        "--quorum",
        type=int,
        default=1,
        metavar="N",
        help=(
            "endpoints that must confirm a height before it counts "
            "as the consensus head (a single racing or lying "
            "endpoint cannot move a quorum of 2+)"
        ),
    )
    watch.add_argument(
        "--poll-interval",
        type=float,
        default=2.0,
        metavar="SECONDS",
        help="seconds between chain-head polls",
    )
    watch.add_argument(
        "--rpc-timeout",
        type=float,
        default=5.0,
        metavar="SECONDS",
        help="per-request RPC timeout (every call is bounded)",
    )
    watch.add_argument(
        "--start-block",
        type=int,
        default=None,
        metavar="N",
        help=(
            "first block to ingest on a fresh cursor (default: the "
            "consensus head at startup)"
        ),
    )
    watch.add_argument(
        "--backfill-batch",
        type=int,
        default=16,
        metavar="N",
        help=(
            "max blocks ingested per tick; bounds tick latency so a "
            "deep gap backfills without starving head-following"
        ),
    )
    watch.add_argument(
        "--max-reorg-depth",
        type=int,
        default=64,
        metavar="N",
        help=(
            "cursor tail depth — the deepest reorg resolvable "
            "against recorded hashes; deeper forks force a resync"
        ),
    )
    watch.add_argument(
        "--alert-budget",
        type=float,
        default=12.0,
        metavar="SECONDS",
        help=(
            "the block-time budget: the alert-latency SLO wants the "
            "p50 block-seen-to-alert under this"
        ),
    )
    watch.add_argument(
        "--submit-deadline",
        type=float,
        default=30.0,
        metavar="SECONDS",
        help="per-job wall budget handed to the fleet for survivors",
    )
    watch.add_argument(
        "--ticks",
        type=int,
        default=0,
        metavar="N",
        help="exit after N ticks (0 = run until interrupted)",
    )
    watch.add_argument(
        "--no-fsync",
        action="store_true",
        help=(
            "skip the per-record fsync on the cursor/alert logs "
            "(testing only; crash safety depends on the fsync)"
        ),
    )

    observe_cmd = subparsers.add_parser(
        "observe",
        help=(
            "Operator tooling over the telemetry layer: a live "
            "terminal view of a running service (top), a static "
            "digest from metrics/routing/journey artifacts (report), "
            "and a bench-record trajectory/regression differ "
            "(compare)"
        ),
    )
    observe_cmd.add_argument(
        "observe_mode",
        choices=["top", "report", "compare"],
        metavar="MODE",
        help="top | report | compare",
    )
    observe_cmd.add_argument(
        "records",
        nargs="*",
        metavar="BENCH.json",
        help="compare: two or more BENCH_r*.json records, oldest first",
    )
    observe_cmd.add_argument(
        "--url",
        action="append",
        default=None,
        help=(
            "running `myth serve` (or `myth fleet`) base URL; repeat "
            "for a per-replica fleet view — top renders one "
            "health/occupancy column set per target (default "
            "http://127.0.0.1:7341)"
        ),
    )
    observe_cmd.add_argument(
        "--interval", type=float, default=2.0,
        help="top: seconds between refreshes",
    )
    observe_cmd.add_argument(
        "--count", type=int, default=0,
        help="top: frames to render before exiting (0 = until ^C)",
    )
    observe_cmd.add_argument(
        "--metrics", default=None, metavar="FILE",
        help="report: a saved /metrics snapshot instead of a live URL",
    )
    observe_cmd.add_argument(
        "--routing", default=None, metavar="FILE",
        help="report: a routing_features.jsonl to fold in",
    )
    observe_cmd.add_argument(
        "--tail", type=int, default=5000, metavar="N",
        help=(
            "report: read only the newest N routing records (bounded "
            "backward read — a month-long log folds in without "
            "loading it whole; 0 = the whole file)"
        ),
    )
    observe_cmd.add_argument(
        "--format",
        choices=["markdown", "html"],
        default="markdown",
        dest="report_format",
        help="report: output format",
    )
    observe_cmd.add_argument(
        "--out", default=None, metavar="FILE",
        help="report: write to FILE instead of stdout",
    )
    observe_cmd.add_argument(
        "--fail-on-regression",
        action="store_true",
        help=(
            "compare: exit nonzero when a stable field moves the "
            "wrong way past its threshold between adjacent records"
        ),
    )
    observe_cmd.add_argument(
        "--threshold-scale", type=float, default=1.0,
        help=(
            "compare: multiply every stable field's regression "
            "threshold (loosen or tighten the gate)"
        ),
    )
    observe_cmd.add_argument(
        "--json", action="store_true", help="emit machine-readable JSON"
    )

    solverlab = subparsers.add_parser(
        "solverlab",
        help=(
            "Offline solver replay lab: re-run a corpus captured with "
            "--capture-queries against any engine matrix (host CDCL, "
            "on-chip portfolio, full race funnel) with per-engine "
            "agreement tables and the funnel-loss waterfall"
        ),
    )
    solverlab.add_argument(
        "mode",
        choices=["replay", "report", "tune"],
        nargs="?",
        default="replay",
        help=(
            "replay: re-solve the corpus on the chosen engines; "
            "report: the captured waterfall alone, no solving; "
            "tune: grid/random sweep of the diversified-portfolio "
            "knobs (noise, restart schedule, cube depth, lane split) "
            "over the corpus with a ranked results table — the lab "
            "that derives portfolio.PORTFOLIO_DEFAULTS"
        ),
    )
    solverlab.add_argument(
        "--corpus", required=True, metavar="DIR",
        help="the --capture-queries output directory to load",
    )
    solverlab.add_argument(
        "--engines",
        default="host,device",
        help="comma list of host|device|race (default host,device)",
    )
    solverlab.add_argument(
        "--filter",
        default=None,
        metavar="KEY=VALUE",
        help=(
            "replay only matching artifacts: reason=<LOSS_REASON> or "
            "origin=<flip-frontier|module|memo-miss>"
        ),
    )
    solverlab.add_argument(
        "--shard",
        default=None,
        metavar="I/N",
        help=(
            "replay only this host's content-hash shard (run one "
            "solverlab per host with I=0..N-1 for a mesh replay)"
        ),
    )
    solverlab.add_argument(
        "--timeout-ms", type=int, default=10_000,
        help="per-query budget for the host/race engines",
    )
    solverlab.add_argument(
        "--candidates", type=int, default=64,
        help="portfolio candidates per query (device engine)",
    )
    solverlab.add_argument(
        "--steps", type=int, default=512,
        help="portfolio local-search steps (device engine)",
    )
    solverlab.add_argument(
        "--json", action="store_true", help="emit the report as JSON"
    )
    solverlab.add_argument(
        "--strict",
        action="store_true",
        help="exit 1 when any engine disagrees with a live verdict",
    )
    solverlab.add_argument(
        "--trials", type=int, default=12,
        help="tune mode: random-sweep sample count (default 12)",
    )
    solverlab.add_argument(
        "--sweep",
        choices=["random", "grid"],
        default="random",
        help=(
            "tune mode: 'random' samples --trials grid combinations, "
            "'grid' walks one knob at a time off the committed "
            "defaults"
        ),
    )
    solverlab.add_argument(
        "--tune-seed", type=int, default=1,
        help="tune mode: random-sweep seed (deterministic trials)",
    )
    solverlab.add_argument(
        "--watch",
        action="store_true",
        help=(
            "tune mode: continuous self-tuning — re-sweep whenever "
            "the capture corpus grows, gate the winner by 100%% "
            "host-replay agreement, and promote it as a versioned "
            "tuned-defaults artifact (`myth serve --router DIR` "
            "installs it); the solver half of the data flywheel"
        ),
    )
    solverlab.add_argument(
        "--watch-out",
        default=None,
        metavar="DIR",
        help=(
            "--watch: where tuned-v<N>.json artifacts land "
            "(default: the corpus directory itself)"
        ),
    )
    solverlab.add_argument(
        "--watch-interval", type=float, default=30.0,
        metavar="SECONDS",
        help="--watch: seconds between corpus re-scans",
    )
    solverlab.add_argument(
        "--min-new", type=int, default=8, metavar="N",
        help=(
            "--watch: fresh captured queries required before a "
            "re-sweep (the first sweep always runs)"
        ),
    )
    solverlab.add_argument(
        "--rounds", type=int, default=0, metavar="N",
        help="--watch: exit after N scan rounds (0 = until ^C)",
    )

    route = subparsers.add_parser(
        "route",
        help=(
            "Learned tier-ladder router lab over the routing JSONL: "
            "train a per-tier cost model from accumulated logs into a "
            "versioned router artifact (train), score an artifact's "
            "regret/oracle-agreement against a log (eval), and "
            "explain one contract's routing decision feature-by-"
            "feature (explain). Artifacts mount at `myth serve "
            "--router DIR` and `myth fleet --router DIR`"
        ),
    )
    route.add_argument(
        "route_mode",
        choices=["train", "eval", "explain"],
        metavar="MODE",
        help="train | eval | explain",
    )
    route.add_argument(
        "--log", required=True, metavar="FILE",
        help="the routing_features.jsonl to learn from / score against",
    )
    route.add_argument(
        "--out", default=None, metavar="DIR",
        help="train: where the router-v<N>.json artifact lands",
    )
    route.add_argument(
        "--router", default=None, metavar="DIR",
        help=(
            "eval/explain: the artifact directory to load (default: "
            "env MYTHRIL_ROUTER_DIR)"
        ),
    )
    route.add_argument(
        "--l2", type=float, default=1.0,
        help="train: ridge/logistic L2 strength (default 1.0)",
    )
    route.add_argument(
        "--select", default=None, metavar="NAME|HASH",
        help=(
            "explain: pick the record by contract name or code-hash "
            "prefix (default: the last record in the log)"
        ),
    )
    route.add_argument(
        "--json", action="store_true", help="emit machine-readable JSON"
    )

    submit = subparsers.add_parser(
        "submit",
        parents=[creation_input],
        help="Submit bytecode to a running `myth serve` instance",
    )
    submit.add_argument(
        "--url",
        default="http://127.0.0.1:7341",
        help="service base URL",
    )
    submit.add_argument(
        "--address",
        default=None,
        metavar="ADDRESS",
        help=(
            "submit the DEPLOYED code at this on-chain address "
            "instead of -c/-f bytecode (fetched over --rpc-url via "
            "eth_getCode; rides the same CodeCache/triage/store path "
            "as a pasted payload)"
        ),
    )
    submit.add_argument(
        "--rpc-url",
        default=None,
        metavar="URL",
        help=(
            "execution-client JSON-RPC endpoint for --address "
            "(e.g. http://127.0.0.1:8545)"
        ),
    )
    submit.add_argument(
        "--max-waves", type=int, default=None, help="device waves override"
    )
    submit.add_argument(
        "--deadline",
        type=float,
        default=None,
        help="per-job wall budget the service supervisor enforces",
    )
    submit.add_argument(
        "--no-host-walk",
        action="store_true",
        help="ask for a device-only report",
    )
    submit.add_argument(
        "--idempotency-key",
        default=None,
        metavar="KEY",
        help=(
            "dedupe key for this submission (default: a fresh UUID); "
            "a resubmit with the same key — e.g. after a server "
            "restart — maps to the existing job instead of re-running"
        ),
    )
    submit.add_argument(
        "--no-wait",
        action="store_true",
        help="print the job id and return without waiting for the report",
    )
    submit.add_argument(
        "--wait-s",
        type=float,
        default=120.0,
        help="how long to wait for the report",
    )

    subparsers.add_parser(
        "version", parents=[output], help="Outputs the version"
    )
    subparsers.add_parser("truffle", parents=[analyzer], add_help=False)
    subparsers.add_parser("help", add_help=False)
    return parser


# kept under their historical names (third-party wrappers use them)
def get_rpc_parser() -> ArgumentParser:
    return _shared_parser(RPC_FLAGS)


def get_utilities_parser() -> ArgumentParser:
    return _shared_parser(UTILITY_FLAGS)


def get_runtime_input_parser() -> ArgumentParser:
    return _shared_parser(RUNTIME_INPUT_FLAGS)


def get_creation_input_parser() -> ArgumentParser:
    return _shared_parser(CREATION_INPUT_FLAGS)


def get_output_parser() -> ArgumentParser:
    return _shared_parser(OUTPUT_FLAGS)


def create_analyzer_parser(parser: ArgumentParser):
    parser.add_argument("solidity_files", **SOLIDITY_FILES_ARG)
    _install_flags(parser.add_argument_group("commands"), ANALYZE_COMMAND_FLAGS)
    _install_flags(parser.add_argument_group("options"), ANALYZE_OPTION_FLAGS)


# ---------------------------------------------------------------------------
# argument validation + environment setup
# ---------------------------------------------------------------------------
def validate_args(args: Namespace):
    if args.__dict__.get("v", False):
        if not 0 <= args.v < len(LOG_LEVELS):
            exit_with_error(
                args.outform,
                "Invalid -v value, you can find valid values in usage",
            )
        chosen = LOG_LEVELS[args.v]
        try:
            import coloredlogs

            coloredlogs.install(
                fmt="%(name)s [%(levelname)s]: %(message)s", level=chosen
            )
        except ImportError:
            logging.basicConfig(
                format="%(name)s [%(levelname)s]: %(message)s", level=chosen
            )
        logging.getLogger("mythril_tpu").setLevel(chosen)

    if args.command in DISASSEMBLE_LIST and (
        len(args.solidity_files) > 1 or len(args.codefile or ()) > 1
    ):
        exit_with_error(
            "text", "Only a single arg is supported for using disassemble"
        )
    if args.command in ANALYZE_LIST and args.enable_iprof and args.v < 4:
        exit_with_error(
            args.outform,
            "--enable-iprof must be used with -v LOG_LEVEL where LOG_LEVEL >= 4",
        )


def set_config(args: Namespace):
    config = MythrilConfig()
    opt = args.__dict__.get
    if opt("infura_id"):
        config.set_api_infura_id(args.infura_id)
    if args.command in ANALYZE_LIST and not args.no_onchain_data and not args.rpc:
        config.set_api_from_config_path()
    if opt("rpc") and not opt("no_onchain_data", False):
        config.set_api_rpc(rpc=args.rpc, rpctls=args.rpctls)
    if args.command in ("hash-to-address", "leveldb-search"):
        config.set_api_leveldb(opt("leveldb_dir") or config.leveldb_dir)
    return config


def leveldb_search(config: MythrilConfig, args: Namespace):
    if args.command not in ("hash-to-address", "leveldb-search"):
        return
    searcher = MythrilLevelDB(config.eth_db)
    if args.command == "leveldb-search":
        searcher.search_db(args.search)
    else:
        try:
            searcher.contract_hash_to_address(args.hash)
        except AddressNotFoundError:
            print("Address not found.")
    sys.exit()


def _read_codefile(codefile) -> str:
    with codefile:
        return "".join(line.strip() for line in codefile if line.strip())


def load_code(disassembler: MythrilDisassembler, args: Namespace):
    """Load the analysis target from whichever input flag was given."""
    opt = args.__dict__.get

    if opt("code"):
        blob = args.code
        address, _ = disassembler.load_from_bytecode(
            blob[2:] if blob.startswith("0x") else blob, args.bin_runtime
        )
    elif opt("codefile"):
        for codefile in args.codefile:
            blob = _read_codefile(codefile)
            address, contract = disassembler.load_from_bytecode(
                blob[2:] if blob.startswith("0x") else blob, args.bin_runtime
            )
            if len(args.codefile) > 1:
                # one report over several contracts: tell them apart
                name = os.path.basename(codefile.name)
                contract.name = name.rsplit(".", 1)[0]
    elif opt("address"):
        address, _ = disassembler.load_from_address(args.address)
    elif opt("solidity_files"):
        if (
            args.command in ANALYZE_LIST
            and args.graph
            and len(args.solidity_files) > 1
        ):
            exit_with_error(
                args.outform,
                "Cannot generate call graphs from multiple input files. "
                "Please do it one at a time.",
            )
        address, _ = disassembler.load_from_solidity(args.solidity_files)
    else:
        exit_with_error(
            opt("outform", "text"),
            "No input bytecode. Please provide EVM code via -c BYTECODE, "
            "-a ADDRESS, -f BYTECODE_FILE or <SOLIDITY_FILE>",
        )
    return address


# ---------------------------------------------------------------------------
# command handlers
# ---------------------------------------------------------------------------
def _print_report(report, outform: str) -> None:
    renderers = {
        "json": report.as_json,
        "jsonv2": report.as_swc_standard_format,
        "text": report.as_text,
        "markdown": report.as_markdown,
    }
    print(renderers[outform]())


def _run_read_storage(disassembler, address, args):
    print(
        disassembler.get_state_variable_from_storage(
            address=address,
            params=[p.strip() for p in args.storage_slots.strip().split(",")],
        )
    )


def _run_pro(disassembler, address, args):
    mode = "full" if args.full else "quick"
    _print_report(mythx.analyze(disassembler.contracts, mode), args.outform)


def _run_lint(disassembler, address, args):
    """`myth lint`: the static layer alone — per contract, CFG/prune
    stats plus the pure static findings (schema_version pins the
    payload). `--fail-on CHECK` turns a named check into a CI gate:
    the command exits 1 when it fires anywhere. Never touches the
    device."""
    from mythril_tpu.analysis.static import LINT_CHECKS, summary_for

    fail_on = set(args.fail_on or [])
    unknown_checks = fail_on - LINT_CHECKS
    if unknown_checks:
        exit_with_error(
            args.outform,
            "unknown --fail-on check(s): {} (known: {})".format(
                ", ".join(sorted(unknown_checks)),
                ", ".join(sorted(LINT_CHECKS)),
            ),
            exit_code=2,
        )

    rows = []
    for contract in disassembler.contracts:
        code = contract.code or getattr(contract, "creation_code", "") or ""
        try:
            summary = summary_for(code)
        except Exception as why:
            exit_with_error(
                args.outform,
                f"static analysis failed for {contract.name}: {why}",
                exit_code=1,
            )
        rows.append(summary.lint_dict(name=contract.name))

    fired = sorted(
        {
            finding["check"]
            for row in rows
            for finding in row["findings"]
            if finding["check"] in fail_on
        }
    )

    if args.outform in ("json", "jsonv2"):
        print(json.dumps(rows, sort_keys=True))
        if fired:
            sys.exit(1)
        return
    for row in rows:
        print(f"Static analysis: {row['contract']} ({row['code_hash']})")
        print(
            "  blocks: {blocks} ({reachable_blocks} reachable, "
            "{dead_blocks} dead), instructions: {instructions} "
            "({dead_instructions} dead)".format(**row)
        )
        print(
            "  jumps: {resolved_jumps} resolved / {unresolved_jumps} "
            "unresolved / {invalid_jumps} invalid; dead branch "
            "directions: {dead_directions}".format(**row)
        )
        print(
            "  selectors: {selectors} ({dead_selectors} statically "
            "prunable); prune rate: {prune_rate}".format(**row)
        )
        skipped = row["modules_skipped"]
        print(
            "  detector screen: {} applicable, {} skipped{}".format(
                row["modules_applicable"],
                len(skipped),
                " ({})".format(", ".join(skipped)) if skipped else "",
            )
        )
        taint = row.get("taint") or {}
        if taint and not taint.get("incomplete"):
            print(
                "  taint: density {density}, {n_calls} resolved call "
                "target(s), {n_fp} function fingerprint(s){answer}".format(
                    density=taint.get("density"),
                    n_calls=row.get("resolved_call_target_count", 0),
                    n_fp=row.get("fingerprint_count", 0),
                    answer=(
                        "; statically answerable"
                        if row.get("static_answerable")
                        else ""
                    ),
                )
            )
        if row["findings"]:
            print("  findings:")
            for finding in row["findings"]:
                print(
                    "    - [{check}] {detail}".format(**finding)
                )
        print("  wall: {wall_ms} ms".format(**row))
    if fired:
        print(
            "lint: --fail-on check(s) fired: {}".format(", ".join(fired))
        )
        sys.exit(1)


def _run_disassemble(disassembler, address, args):
    target = disassembler.contracts[0]
    if target.code:
        print("Runtime Disassembly: \n" + target.get_easm())
    if target.creation_code:
        print("Disassembly: \n" + target.get_creation_easm())


def _override_actors(args) -> None:
    for flag, actor in (
        ("attacker_address", "ATTACKER"),
        ("creator_address", "CREATOR"),
    ):
        given = getattr(args, flag)
        if not given:
            continue
        try:
            ACTORS[actor] = given
        except ValueError:
            exit_with_error(
                args.outform, f"{actor.capitalize()} address is invalid"
            )


def _apply_corpus_shard(disassembler, args) -> bool:
    """--corpus-shard I/N: keep only this host's content-hash shard of
    the loaded contracts (analysis/corpus.py corpus_shard). True when
    sharding emptied a previously NON-empty contract list — the only
    case the caller may treat as a clean empty-shard run (an input
    that loaded no contracts at all must still error)."""
    spec = getattr(args, "corpus_shard", None)
    if not spec or not disassembler.contracts:
        return False
    try:
        index_s, count_s = spec.split("/", 1)
        index, count = int(index_s), int(count_s)
    except ValueError:
        exit_with_error(
            args.outform, f"--corpus-shard wants I/N, got {spec!r}"
        )
    from mythril_tpu.analysis.corpus import corpus_shard

    try:
        disassembler.contracts[:] = corpus_shard(
            disassembler.contracts,
            index,
            count,
            identity=lambda c: f"{c.name}:{c.code or ''}",
        )
    except ValueError as why:
        exit_with_error(args.outform, str(why))
    return not disassembler.contracts


def _run_analyze(disassembler, address, args):
    from mythril_tpu import observe

    if getattr(args, "no_observe", False):
        observe.set_enabled(False)
    if getattr(args, "observe_out", None):
        observe.configure(out_dir=args.observe_out)
    if _apply_corpus_shard(disassembler, args):
        # a legitimately empty shard (more hosts than contracts) is a
        # clean no-findings run, not an input error — and it must honor
        # --outform so multi-host merge scripts can parse every host
        from mythril_tpu.analysis.report import Report

        _print_report(Report(), args.outform)
        return
    analyzer = MythrilAnalyzer(
        strategy=args.strategy,
        disassembler=disassembler,
        address=address,
        max_depth=args.max_depth,
        execution_timeout=args.execution_timeout,
        loop_bound=args.loop_bound,
        create_timeout=args.create_timeout,
        enable_iprof=args.enable_iprof,
        disable_dependency_pruning=args.disable_dependency_pruning,
        use_onchain_data=not args.no_onchain_data,
        solver_timeout=args.solver_timeout,
        parallel_solving=args.parallel_solving,
        custom_modules_directory=args.custom_modules_directory or "",
        sparse_pruning=args.sparse_pruning,
        unconstrained_storage=args.unconstrained_storage,
        call_depth_limit=args.call_depth_limit,
        device_prepass=args.device_prepass,
        device_solving=args.device_solving,
        device_prepass_budget=args.device_prepass_budget,
        device_ownership=args.device_ownership,
        deterministic_solving=args.deterministic_solving,
        static_prune=not args.no_static_prune,
        pipeline=not args.no_pipeline,
        specialize=not args.no_specialize,
        blockjit=not args.no_blockjit,
        mesh_devices=args.devices,
        deadline=args.deadline,
        on_timeout=args.on_timeout,
        capture_queries=args.capture_queries,
        device_first=not args.host_first_funnel,
        sprint_cap_s=args.sprint_cap_s,
        store_dir=(
            args.store or os.environ.get("MYTHRIL_STORE_DIR") or None
        ),
        store=not args.no_store,
    )

    if not disassembler.contracts:
        exit_with_error(
            args.outform, "input files do not contain any valid contracts"
        )
    _override_actors(args)

    if args.graph:
        html = analyzer.graph_html(
            contract=analyzer.contracts[0],
            enable_physics=args.enable_physics,
            phrackify=args.phrack,
            transaction_count=args.transaction_count,
        )
        try:
            with open(args.graph, "w") as fp:
                fp.write(html)
        except Exception as e:
            exit_with_error(args.outform, "Error saving graph: " + str(e))
        return

    if args.statespace_json:
        if not analyzer.contracts:
            exit_with_error(
                args.outform, "input files do not contain any valid contracts"
            )
        statespace = analyzer.dump_statespace(contract=analyzer.contracts[0])
        try:
            with open(args.statespace_json, "w") as fp:
                json.dump(statespace, fp)
        except Exception as e:
            exit_with_error(args.outform, "Error saving json: " + str(e))
        return

    try:
        report = analyzer.fire_lasers(
            modules=(
                [m.strip() for m in args.modules.strip().split(",")]
                if args.modules
                else None
            ),
            transaction_count=args.transaction_count,
        )
        _print_report(report, args.outform)
    except DetectorNotFoundError as e:
        exit_with_error(args.outform, format(e))
    except DeadlineExpiredError as e:
        # --on-timeout=fail: the budget is a hard contract, and the
        # exit code says so (scripts gate on it)
        exit_with_error(
            args.outform, "Analysis deadline expired: " + format(e), exit_code=1
        )
    except DevicePrepassError as e:
        # --device-prepass always: a run without the device is a
        # failed run, and the exit code says so
        exit_with_error(args.outform, format(e), exit_code=1)
    except CriticalError as e:
        exit_with_error(args.outform, "Analysis error encountered: " + format(e))
    finally:
        # the span timeline flushes even on a deadline/error exit —
        # a failed run's trace is the one you want to open
        if getattr(args, "trace_out", None):
            try:
                observe.export_trace(args.trace_out)
                log.info("span trace written to %s", args.trace_out)
            except Exception:
                log.warning("trace export failed", exc_info=True)


def execute_command(
    disassembler: MythrilDisassembler,
    address: str,
    parser: ArgumentParser,
    args: Namespace,
):
    if args.command == "read-storage":
        _run_read_storage(disassembler, address, args)
    elif args.command in PRO_LIST:
        _run_pro(disassembler, address, args)
    elif args.command == "lint":
        _run_lint(disassembler, address, args)
    elif args.command in DISASSEMBLE_LIST:
        _run_disassemble(disassembler, address, args)
    elif args.command in ANALYZE_LIST:
        _run_analyze(disassembler, address, args)
    else:
        parser.print_help()


def contract_hash_to_address(args: Namespace):
    """Print the selector for a function signature."""
    print(MythrilDisassembler.hash_for_function_signature(args.func_name))
    sys.exit()


# ---------------------------------------------------------------------------
# top-level dispatch
# ---------------------------------------------------------------------------
def _cmd_version(args: Namespace) -> None:
    if args.outform == "json":
        print(json.dumps({"version_str": VERSION}))
    else:
        print("Mythril-TPU version {}".format(VERSION))
    sys.exit()


def _cmd_list_detectors(args: Namespace) -> None:
    rows = [
        {"classname": type(module).__name__, "title": module.name}
        for module in ModuleLoader().get_detection_modules()
    ]
    if args.outform == "json":
        print(json.dumps(rows))
    else:
        for row in rows:
            print("{}: {}".format(row["classname"], row["title"]))
    sys.exit()


def _cmd_serve(args: Namespace) -> None:
    """`myth serve`: run the persistent analysis service until a
    graceful drain (SIGTERM/SIGINT or POST /v1/drain) completes."""
    from mythril_tpu import observe
    from mythril_tpu.service.engine import ServiceConfig
    from mythril_tpu.service.server import serve_forever

    if args.no_observe:
        observe.set_enabled(False)
    if args.observe_out:
        observe.configure(out_dir=args.observe_out)
    if args.capture_queries:
        observe.configure_capture(args.capture_queries)
    if args.no_static_prune:
        # the process-wide switch: host walks in the service pool read
        # the same flag bag, so the parity baseline really mounts all
        from mythril_tpu.support.support_args import args as support_args

        support_args.static_prune = False
    if args.no_blockjit:
        # the process-wide switch: blockjit_enabled() consumers
        # outside the engine config (CodeCache feeds) read the bag
        from mythril_tpu.support.support_args import args as support_args

        support_args.blockjit = False
    if args.no_breakers:
        # the process-wide switch: the device-solve and kernel-compile
        # breakers sit below the engine config (explore.py,
        # specialize.py, store.py all read the bag)
        from mythril_tpu.support.support_args import args as support_args

        support_args.breakers = False
    if args.no_aot:
        # the process-wide switch: wave_run/SpecializedKernel consult
        # aot_enabled() below the engine config
        from mythril_tpu.support.support_args import args as support_args

        support_args.aot = False
    config = ServiceConfig(
        stripes=args.stripes,
        lanes_per_stripe=args.lanes_per_stripe,
        steps_per_wave=args.steps_per_wave,
        max_waves=args.max_waves,
        queue_capacity=args.queue_capacity,
        host_workers=args.host_workers,
        host_walk=not args.no_host_walk,
        execution_timeout=args.execution_timeout,
        transaction_count=args.transaction_count,
        checkpoint_dir=args.checkpoint_dir,
        pipeline=not args.no_pipeline,
        specialize=not args.no_specialize,
        blockjit=not args.no_blockjit,
        devices=args.devices,
        static_answer=not (
            args.no_static_answer or args.no_static_prune
        ),
        store_dir=(
            args.store or os.environ.get("MYTHRIL_STORE_DIR") or None
        ),
        store=not args.no_store,
        arena_warmup=not args.no_arena_warmup,
        health_interval_s=args.health_interval,
        journal_dir=args.journal,
        recover=args.recover,
        breakers=not args.no_breakers,
        quarantine_strikes=args.quarantine_strikes,
        kernel_pack=args.kernel_pack,
        kernel_cache_dir=(
            args.kernel_cache
            or os.environ.get("MYTHRIL_KERNEL_CACHE")
            or None
        ),
        router_dir=(
            args.router
            or os.environ.get("MYTHRIL_ROUTER_DIR")
            or None
        ),
        router=not args.no_router,
    )
    serve_forever(config, host=args.host, port=args.port)
    sys.exit()


def _cmd_fleet(args: Namespace) -> None:
    """`myth fleet`: run the federated serving front over N `myth
    serve` replicas until interrupted."""
    from mythril_tpu.fleet import FleetConfig, serve_fleet

    if not args.replicas:
        log.error(
            "myth fleet wants at least one --replica URL (a running "
            "`myth serve` instance)"
        )
        sys.exit(2)
    config = FleetConfig(
        replica_urls=args.replicas,
        probe_interval_s=args.probe_interval,
        probe_timeout_s=args.probe_timeout,
        failure_threshold=args.failover_threshold,
        recovery_s=args.recovery_s,
        retry_after_s=args.retry_after,
        journal_dir=args.journal,
        recover=args.recover,
        store_dir=args.store,
        kernel_pack_dir=args.kernel_pack,
        router_dir=args.router,
    )
    serve_fleet(config, host=args.host, port=args.port)
    sys.exit()


def _cmd_kernels(args: Namespace) -> None:
    """`myth kernels bake|warm|ls|gc`: kernel-pack tooling over the
    persistent compile plane (compileplane/pack.py holds the logic)."""
    from mythril_tpu.compileplane import pack as kpack

    def _emit(doc: Dict) -> None:
        print(json.dumps(doc, sort_keys=True, indent=None
                         if args.kernels_json else 2))

    if args.kernels_mode == "bake":
        buckets = (
            [None]
            if args.generic_only
            else kpack.mine_buckets(
                corpus=args.corpus or (),
                routing=args.routing or (),
                bucket_files=args.buckets or (),
            )
        )
        log.info(
            "baking %d bucket(s) for a %dx%d arena",
            len(buckets), args.stripes, args.lanes_per_stripe,
        )

        def _progress(row: Dict) -> None:
            log.info(
                "baked %s donate=%s in %.1fs",
                row["bucket"], row["donate"], row["wall_s"],
            )

        manifest = kpack.bake_service_pack(
            args.pack_dir,
            buckets,
            stripes=args.stripes,
            lanes_per_stripe=args.lanes_per_stripe,
            steps_per_wave=args.steps_per_wave,
            code_cap=args.code_cap,
            progress=_progress,
        )
        _emit(manifest)
    elif args.kernels_mode == "warm":
        report = kpack.verify_pack(args.pack_dir)
        _emit(report)
        if report["refused"] and not report["loadable"]:
            # nothing in the pack loads under this backend: the
            # deploy preflight should fail loudly, not mount a no-op
            sys.exit(1)
    elif args.kernels_mode == "ls":
        _emit(kpack.list_pack(args.pack_dir))
    elif args.kernels_mode == "gc":
        _emit(
            kpack.gc_pack(
                args.pack_dir,
                capacity=args.capacity,
                drop_stale=args.drop_stale,
            )
        )
    sys.exit()


def _cmd_observe(args: Namespace) -> None:
    """`myth observe top|report|compare`: operator tooling over the
    telemetry layer (observe/opstool.py holds the logic)."""
    import time as _time
    import urllib.request

    from mythril_tpu.observe import opstool

    urls = args.url or ["http://127.0.0.1:7341"]

    def _fetch(path: str, parse_json: bool, url: str = None):
        base = (url or urls[0]).rstrip("/")
        with urllib.request.urlopen(base + path,
                                    timeout=10.0) as response:
            body = response.read().decode()
        return json.loads(body) if parse_json else body

    if args.observe_mode == "top":
        frames = 0
        try:
            while True:
                if len(urls) > 1:
                    # the fleet operator view: one row of columns per
                    # replica target; an unreachable target renders
                    # DOWN instead of sinking the whole frame
                    rows = []
                    for url in urls:
                        try:
                            stats = _fetch("/stats", True, url=url)
                            metrics = opstool.parse_prometheus(
                                _fetch("/metrics", False, url=url)
                            )
                        except OSError:
                            stats = metrics = None
                        rows.append((url, stats, metrics))
                    frame = opstool.render_top_multi(rows)
                    if args.json:
                        print(json.dumps(
                            {
                                "targets": {
                                    url: stats
                                    for url, stats, _m in rows
                                }
                            },
                            sort_keys=True,
                        ))
                    else:
                        print("\033[2J\033[H" + frame, flush=True)
                else:
                    stats = _fetch("/stats", True)
                    metrics = opstool.parse_prometheus(
                        _fetch("/metrics", False)
                    )
                    frame = opstool.render_top(stats, metrics)
                    if args.json:
                        print(json.dumps(
                            {"stats": stats}, sort_keys=True
                        ))
                    else:
                        print("\033[2J\033[H" + frame, flush=True)
                frames += 1
                if args.count and frames >= args.count:
                    break
                _time.sleep(max(0.2, args.interval))
        except KeyboardInterrupt:
            pass
        except OSError as why:
            log.error("observe top: %s unreachable: %s", urls[0], why)
            sys.exit(1)
        sys.exit()

    if args.observe_mode == "report":
        metrics = stats = None
        routing_records = journeys = None
        try:
            if args.metrics:
                with open(args.metrics) as fp:
                    metrics = opstool.parse_prometheus(fp.read())
            else:
                metrics = opstool.parse_prometheus(_fetch("/metrics", False))
                stats = _fetch("/stats", True)
        except OSError as why:
            log.error("observe report: no metrics source: %s", why)
            sys.exit(1)
        if args.routing:
            from mythril_tpu.observe.routing import (
                read_records, tail_records,
            )

            try:
                if args.tail and args.tail > 0:
                    routing_records = tail_records(
                        args.routing, args.tail
                    )
                else:
                    routing_records = read_records(args.routing)
            except OSError as why:
                log.error("observe report: %s", why)
                sys.exit(1)
        body = opstool.render_report(
            metrics=metrics,
            routing_records=routing_records,
            journeys=journeys,
            stats=stats,
            fmt=args.report_format,
        )
        if args.out:
            with open(args.out, "w") as fp:
                fp.write(body)
            print(f"observe report written to {args.out}")
        else:
            print(body)
        sys.exit()

    # compare
    if len(args.records) < 2:
        log.error("observe compare wants two or more BENCH_r*.json records")
        sys.exit(2)
    try:
        records = [opstool.load_bench_record(p) for p in args.records]
    except (OSError, ValueError) as why:
        log.error("observe compare: %s", why)
        sys.exit(2)
    result = opstool.compare_records(
        records, threshold_scale=args.threshold_scale
    )
    if args.json:
        print(json.dumps(result, sort_keys=True))
    else:
        print(opstool.render_compare(result))
    if args.fail_on_regression and result["regressions"]:
        sys.exit(1)
    sys.exit()


def _cmd_solverlab(args: Namespace) -> None:
    """`myth solverlab`: replay a captured query corpus offline."""
    from mythril_tpu.analysis import solverlab

    reason = origin = None
    if args.filter:
        try:
            key, value = args.filter.split("=", 1)
        except ValueError:
            log.error("--filter wants KEY=VALUE, got %r", args.filter)
            sys.exit(1)
        if key == "reason":
            reason = value
        elif key == "origin":
            origin = value
        else:
            log.error("--filter key must be reason or origin, got %r", key)
            sys.exit(1)
    engines = [e.strip() for e in args.engines.split(",") if e.strip()]
    try:
        report = solverlab.run(
            args.corpus,
            mode=args.mode,
            engines=engines,
            timeout_ms=args.timeout_ms,
            candidates=args.candidates,
            steps=args.steps,
            reason=reason,
            origin=origin,
            shard=args.shard,
            trials=args.trials,
            sweep=args.sweep,
            tune_seed=args.tune_seed,
            watch=args.watch,
            watch_out=args.watch_out,
            watch_interval_s=args.watch_interval,
            watch_min_new=args.min_new,
            watch_rounds=args.rounds,
        )
    except (OSError, ValueError) as why:
        log.error("solverlab: %s", why)
        sys.exit(1)
    if args.json:
        print(json.dumps(report, sort_keys=True))
    else:
        print(solverlab.render_text(report))
    if args.strict:
        disagreements = sum(
            table["agreement"]["disagree"]
            for table in (report.get("replay") or {}).values()
        )
        sys.exit(1 if disagreements else 0)
    sys.exit()


def _cmd_route(args: Namespace) -> None:
    """`myth route train|eval|explain`: the learned tier-ladder
    router lab (mythril_tpu/routing holds the logic)."""
    from mythril_tpu import routing
    from mythril_tpu.observe.routing import read_records

    try:
        records = read_records(args.log)
    except OSError as why:
        log.error("route: cannot read %s: %s", args.log, why)
        sys.exit(1)

    if args.route_mode == "train":
        if not args.out:
            log.error("route train wants --out DIR for the artifact")
            sys.exit(2)
        try:
            model = routing.train_model(records, lam=args.l2)
        except ValueError as why:
            log.error("route train: %s", why)
            sys.exit(1)
        path = routing.save_router(args.out, model)
        summary = {
            "artifact": path,
            "trained_rows": model["trained_rows"],
            "routes": {
                name: {
                    "n": head["n"],
                    "mean_wall_s": round(head["mean_wall_s"], 4),
                }
                for name, head in model["routes"].items()
            },
        }
        if args.json:
            print(json.dumps(summary, sort_keys=True))
        else:
            print(f"router artifact written to {path}")
            for name, head in sorted(summary["routes"].items()):
                print(
                    f"  {name}: {head['n']} rows, mean wall "
                    f"{head['mean_wall_s']}s"
                )
        sys.exit()

    router = None
    try:
        if args.router:
            router = routing.load_router(args.router)
        else:
            router = routing.configured_router()
    except Exception as why:
        log.error("route: router load failed: %s", why)
        sys.exit(1)
    if router is None:
        log.error(
            "route %s wants a verifying artifact (--router DIR or "
            "MYTHRIL_ROUTER_DIR)", args.route_mode,
        )
        sys.exit(1)

    if args.route_mode == "eval":
        report = routing.evaluate_log(records, router)
        if args.json:
            print(json.dumps(report, sort_keys=True))
        else:
            print(
                f"router-v{report['router_version']} over "
                f"{report['records']} records ({report['scored']} "
                f"scored): regret {report['regret_s']:.3f}s, oracle "
                f"agreement {report['oracle_agreement']:.2f}"
            )
            for name, row in sorted(report["per_route"].items()):
                print(
                    f"  {name}: n={row['n']} regret="
                    f"{row['regret_s']:.3f}s oracle-agrees="
                    f"{row['oracle_agrees']} observed-wall="
                    f"{row['observed_wall_s']:.3f}s"
                )
        sys.exit()

    # explain
    from mythril_tpu.routing.evaluate import find_record

    record = find_record(records, args.select)
    if record is None:
        log.error("route explain: no record matches %r", args.select)
        sys.exit(1)
    report = routing.explain_record(record, router)
    if args.json:
        print(json.dumps(report, sort_keys=True))
    else:
        print(
            f"{report['contract'] or report['code_hash']}: logged "
            f"{report['logged_route']}, router-v"
            f"{report['router_version']} picks {report['chosen_route']}"
        )
        for name, head in sorted(report["expected"].items()):
            print(
                f"  {name}: wall {head['wall_s']:.3f}s p_success "
                f"{head['p_success']:.2f} cost {head['cost']:.3f}"
            )
        for name, rows in sorted(report["attributions"].items()):
            top = ", ".join(
                f"{row['feature']}={row['wall_contribution']:+.3f}"
                for row in rows[:5]
            )
            print(f"  {name} drivers: {top}")
    sys.exit()


def _cmd_watch(args: Namespace) -> None:
    """`myth watch`: stream the chain head into the warm service
    until interrupted (or for --ticks ticks)."""
    from mythril_tpu.chainstream import ChainWatcher, RpcPool, WatchConfig

    if not args.rpc_urls:
        log.error(
            "myth watch wants at least one --rpc URL (an "
            "execution-client JSON-RPC endpoint)"
        )
        sys.exit(2)
    pool = RpcPool.from_urls(
        args.rpc_urls,
        timeout_s=args.rpc_timeout,
        quorum=args.quorum,
    )
    front = None
    if args.front:
        from mythril_tpu.service.client import ServiceClient

        front = ServiceClient(args.front)
    watcher = ChainWatcher(
        pool,
        args.state,
        front=front,
        config=WatchConfig(
            poll_interval_s=args.poll_interval,
            backfill_batch=args.backfill_batch,
            max_reorg_depth=args.max_reorg_depth,
            start_block=args.start_block,
            alert_budget_s=args.alert_budget,
            submit_deadline_s=args.submit_deadline,
            fsync=not args.no_fsync,
        ),
    )
    if args.recover:
        facts = watcher.recover()
        log.info(
            "chainstream recovered: %d record(s), tip %s, "
            "redelivered=%s",
            facts["records"], facts["tip"], facts["redelivered"],
        )

    def _drain(signum, frame):  # noqa: ARG001 (signal signature)
        watcher.stop()

    signal.signal(signal.SIGTERM, _drain)
    signal.signal(signal.SIGINT, _drain)
    try:
        watcher.run_forever(
            max_ticks=args.ticks if args.ticks > 0 else None
        )
    finally:
        watcher.close()
        print(json.dumps(watcher.stats(), sort_keys=True, default=str))
    sys.exit()


def _cmd_submit(args: Namespace) -> None:
    """`myth submit`: send bytecode to a running service, print the
    report (or the job id with --no-wait) as JSON."""
    from mythril_tpu.service.client import ServiceClient, ServiceError

    if args.address:
        # the on-chain entry into the warm path: eth_getCode through
        # the same DynLoader the symbolic engine uses, then the bytes
        # ride the normal submission road (CodeCache, static triage,
        # verdict store) exactly like a pasted payload
        if not args.rpc_url:
            log.error("--address wants --rpc-url RPC_ENDPOINT")
            sys.exit(1)
        from mythril_tpu.ethereum.interface.rpc.client import EthJsonRpc
        from mythril_tpu.ethereum.interface.rpc.exceptions import (
            EthJsonRpcError,
        )
        from mythril_tpu.support.loader import DynLoader

        loader = DynLoader(EthJsonRpc.from_url(args.rpc_url))
        try:
            deployed = loader.deployed_code(args.address)
        except EthJsonRpcError as why:
            log.error("eth_getCode(%s) failed: %s", args.address, why)
            sys.exit(1)
        if deployed is None:
            log.error("no code at %s", args.address)
            sys.exit(1)
        blob = deployed.hex()
    elif args.code:
        blob = args.code
    elif args.codefile:
        if len(args.codefile) > 1:
            log.error("submit takes one bytecode file")
            sys.exit(1)
        blob = _read_codefile(args.codefile[0])
    else:
        log.error(
            "No input bytecode. Provide EVM code via -c BYTECODE, "
            "-f BYTECODE_FILE, or --address ADDRESS --rpc-url URL"
        )
        sys.exit(1)
    client = ServiceClient(args.url)
    try:
        job_id = client.submit(
            blob,
            max_waves=args.max_waves,
            deadline_s=args.deadline,
            host_walk=False if args.no_host_walk else None,
            idempotency_key=args.idempotency_key,
        )
        if args.no_wait:
            print(json.dumps({"job_id": job_id}))
            sys.exit()
        print(json.dumps(client.report(job_id, wait_s=args.wait_s), indent=2))
    except ServiceError as why:
        # backpressure (429 full / 503 draining) and mistakes (400)
        # both land here; the exit code flags the failure for scripts
        print(
            json.dumps({"error": str(why), "status": why.status}),
            file=sys.stderr,
        )
        sys.exit(1)
    sys.exit()


#: file suffixes `myth graph DIR` picks up when walking a directory
#: (explicitly named files are always taken as-is)
_GRAPH_SUFFIXES = (".hex", ".sol.o", ".bin-runtime", ".bin", ".evm", ".code")


def _graph_inputs(paths):
    """Expand `myth graph` positionals into (name, runtime_hex) rows.

    Directories contribute their hex-bearing files (sorted, one
    contract per file); files given directly are taken regardless of
    suffix. The file stem is the contract name — a ``@0x<40 hex>``
    suffix in it declares the deployment address for the link-time
    address book (linkset.address_from_name)."""
    files = []
    for given in paths:
        if os.path.isdir(given):
            for entry in sorted(os.listdir(given)):
                full = os.path.join(given, entry)
                if os.path.isfile(full) and entry.endswith(_GRAPH_SUFFIXES):
                    files.append(full)
        elif os.path.isfile(given):
            files.append(given)
        else:
            log.error("graph input not found: %s", given)
            sys.exit(2)
    rows = []
    for path in files:
        try:
            with open(path) as handle:
                blob = "".join(
                    part for line in handle for part in line.split()
                )
        except OSError as why:
            log.error("cannot read %s: %s", path, why)
            sys.exit(2)
        if blob.startswith("0x"):
            blob = blob[2:]
        if not blob:
            log.warning("graph: %s is empty; skipped", path)
            continue
        try:
            bytes.fromhex(blob)
        except ValueError:
            log.warning("graph: %s is not bytecode hex; skipped", path)
            continue
        name = os.path.basename(path)
        for suffix in _GRAPH_SUFFIXES:
            if name.endswith(suffix):
                name = name[: -len(suffix)]
                break
        rows.append((name, blob))
    return rows


def _cmd_graph(args: Namespace) -> None:
    """`myth graph DIR|FILE... [--json]` — cross-contract static
    linker over runtime bytecode files: per-contract link facts join
    into the typed call graph (provenance-tagged edges, proxy pairs,
    storage-collision diff, escape summaries, linked fingerprints,
    arena co-location plan). Pure host work — the static layer never
    imports jax — so a fixture pair links in well under a second."""
    from mythril_tpu.analysis.static import summary_for
    from mythril_tpu.analysis.static.linkset import LinkSet

    rows = _graph_inputs(args.graph_inputs)
    if not rows:
        log.error("graph: no bytecode inputs")
        sys.exit(2)
    linkset = LinkSet()
    for name, blob in rows:
        try:
            linkset.add(name, bytes.fromhex(blob), summary_for(blob))
        except Exception as why:
            log.warning("graph: link pass skipped %s: %s", name, why)
    if not linkset.nodes:
        log.error("graph: no contract linked")
        sys.exit(1)
    payload = linkset.as_dict()
    if args.graph_json:
        print(json.dumps(payload, indent=2, sort_keys=True))
        sys.exit()

    names = linkset.names
    stats = payload["stats"]
    print(
        "Link graph: {nodes} contract(s), {edges} call site(s) "
        "({edges_resolved} resolved, resolve rate {resolve_rate})".format(
            **stats
        )
    )
    for edge in payload["edges"]:
        target = (
            names.get(edge["callee"], edge["callee"])
            if edge["callee"]
            else (edge["target_address"] or "?")
        )
        print(
            "  {caller} pc {pc} {kind} [{selector}] --{provenance}--> "
            "{target}{mark}".format(
                caller=names.get(edge["caller"], edge["caller"]),
                pc=edge["pc"],
                kind=edge["kind"],
                selector=edge["selector"],
                provenance=edge["provenance"],
                target=target,
                mark="" if edge["resolved"] else " (unresolved)",
            )
        )
    if payload["proxy_pairs"]:
        print("Proxy pairs:")
        for pair in payload["proxy_pairs"]:
            print(
                "  {proxy} --[{kind}{upgrade}]--> {impl}".format(
                    proxy=names.get(pair["proxy"], pair["proxy"]),
                    kind=pair["kind"],
                    upgrade=", upgradeable" if pair["upgradeable"] else "",
                    impl=names.get(
                        pair["implementation"], pair["implementation"]
                    ),
                )
            )
    if payload["collisions"]:
        print("Storage collisions:")
        for row in payload["collisions"]:
            print(
                "  {proxy} / {impl}: slot(s) {slots}".format(
                    proxy=names.get(row["proxy"], row["proxy"]),
                    impl=names.get(
                        row["implementation"], row["implementation"]
                    ),
                    slots=", ".join(row["slots"]),
                )
            )
    if payload["findings"]:
        print("Findings:")
        for finding in payload["findings"]:
            print(
                "  - [{check}] {contract}: {detail}".format(**finding)
            )
    print("Arena co-location plan:")
    for entry, callees in payload["arena_plan"].items():
        print(
            "  {entry}: {callees}".format(
                entry=entry,
                callees=(
                    ", ".join(names.get(ch, ch) for ch in callees)
                    if callees
                    else "(self only)"
                ),
            )
        )
    print(
        "Proxies: {proxies}, pairs: {proxy_pairs}, collisions: "
        "{collisions}, escape widened: {escape_widened}, wall: "
        "{wall_ms} ms".format(**stats)
    )
    sys.exit()


def parse_args_and_execute(parser: ArgumentParser, args: Namespace) -> None:
    if args.epic:
        here = os.path.dirname(os.path.realpath(__file__))
        sys.argv.remove("--epic")
        os.system(" ".join(sys.argv) + " | python3 " + here + "/epic.py")
        sys.exit()

    if args.command not in COMMAND_LIST or args.command is None:
        parser.print_help()
        sys.exit()

    if args.command == "version":
        _cmd_version(args)
    if args.command == "list-detectors":
        _cmd_list_detectors(args)
    if args.command == "serve":
        _cmd_serve(args)
    if args.command == "fleet":
        _cmd_fleet(args)
    if args.command == "watch":
        _cmd_watch(args)
    if args.command == "kernels":
        _cmd_kernels(args)
    if args.command == "submit":
        _cmd_submit(args)
    if args.command == "solverlab":
        _cmd_solverlab(args)
    if args.command == "route":
        _cmd_route(args)
    if args.command == "observe":
        _cmd_observe(args)
    if args.command == "graph":
        _cmd_graph(args)
    if args.command == "help":
        parser.print_help()
        sys.exit()

    validate_args(args)
    try:
        if args.command == "function-to-hash":
            contract_hash_to_address(args)
        config = set_config(args)
        leveldb_search(config, args)

        disassembler = MythrilDisassembler(
            eth=config.eth,
            solc_version=args.__dict__.get("solv"),
            solc_settings_json=args.__dict__.get("solc_json"),
            enable_online_lookup=args.__dict__.get("query_signature"),
        )
        address = load_code(disassembler, args)
        execute_command(
            disassembler=disassembler, address=address, parser=parser, args=args
        )
    except CriticalError as ce:
        exit_with_error(args.__dict__.get("outform", "text"), str(ce))
    except Exception:
        exit_with_error(args.__dict__.get("outform", "text"), traceback.format_exc())


def main() -> None:
    """CLI entry point."""
    from mythril_tpu.support.accel import ensure_compile_cache

    ensure_compile_cache()
    parser = build_parser()
    try:
        parse_args_and_execute(parser=parser, args=parser.parse_args())
    finally:
        corpus = sys.modules.get("mythril_tpu.analysis.corpus")
        if corpus is not None:
            corpus.join_stopped_prepasses()


if __name__ == "__main__":
    main()
