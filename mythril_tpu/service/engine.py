"""The persistent analysis engine: warm device arena + continuous
lane-level batching + overlapped host analysis.

One-shot `myth analyze` pays process startup, XLA compile, and arena
allocation on every invocation; compile alone dwarfs steady-state wave
cost (measured on CPU JAX: ~18s cold vs ~2ms warm for the same wave).
This engine owns the device for its lifetime and amortizes all three:

- **Warm arena** — ONE fixed batch shape (`stripes x lanes_per_stripe`
  lanes, one code-table row per stripe plus a halt row). The jit'd
  `run` kernel keys on that shape, so after the first wave every
  request rides the compiled kernel. Contracts longer than the current
  code capacity re-bucket it (power of two, seeds.code_cap_bucket) —
  the one event that recompiles, counted in /stats.
- **Continuous batching** — the wave loop admits queued jobs into free
  stripes *between waves* and finished jobs release their stripes the
  wave they complete, so concurrent requests coalesce into shared
  dispatches instead of queuing behind a whole corpus drain
  (service/lane_allocator.py holds the packing logic).
- **Code LRU** — disassembled dense code rows cached by code hash:
  resubmitted or popular contracts skip `to_dense`.
- **Host pool** — finished device phases hand off to a host worker
  (analysis/corpus.py pooled mode, outcome injected) so device waves
  and host `fire_lasers` overlap continuously. Host symbolic state is
  process-global, so with the CPUs for it the walks run side by side
  in walker processes (service/walkers.py); otherwise in-process
  workers serialize on HOST_SYMBOLIC_LOCK.
- **Drain** — `drain()` (wired to SIGTERM by the server) finishes the
  in-flight wave, then checkpoints every unfinished job's seeded
  frontier to a replayable npz (laser/batch/checkpoint.py, shape
  metadata included): accepted work is completed or checkpointed,
  never dropped.
"""

from __future__ import annotations

import hashlib
import logging
import os
import tempfile
import threading
import time
from collections import OrderedDict
from concurrent.futures import CancelledError, ThreadPoolExecutor
from typing import Dict, List, Optional, Tuple

import numpy as np

from mythril_tpu import observe
from mythril_tpu.observe import journey
from mythril_tpu.observe.registry import _label_key
from mythril_tpu.observe.spans import flight_recorder, trace
from mythril_tpu.service.jobs import Job, JobQueue, JobState
from mythril_tpu.service.lane_allocator import LaneAllocator
from mythril_tpu.service.walkers import WalkerPool, walker_width

log = logging.getLogger(__name__)

#: /stats payload schema version: smoke tools pin it and the key set
#: it covers. Bump on any shape change. v3 adds the `health` (SLO
#: state machine) and `device` (saturation sampler) blocks. v4 adds
#: `journal` (durable WAL + recovery counters), `breaker` (tier
#: circuit-breaker board), and `quarantine` (poison-job strikes).
STATS_SCHEMA_VERSION = 4

#: engine-instance serial for the registry label (tests run many
#: engines per process; each gets its own series)
_ENGINE_SERIAL = __import__("itertools").count(1)

#: trigger statuses -> report kinds (mirrors explore.TRIGGER_KINDS; a
#: local copy so importing the engine never drags the explorer in)
_TRIGGER_KINDS = {
    4: "assert-violation",  # Status.INVALID
    5: "stack-error",  # Status.ERR_STACK
    6: "invalid-jump",  # Status.ERR_JUMP
    10: "selfdestruct",  # Status.KILLED
}
_DEGRADED_STATUSES = (7, 8)  # ERR_MEM, UNSUPPORTED

DEFAULT_CALLER = 0xDEADBEEFDEADBEEFDEADBEEFDEADBEEFDEADBEEF
DEFAULT_ADDRESS = 0x901D573B8CE8C997DE5F19173C32D966B4FA55FE


class ServiceConfig:
    """Arena + policy knobs. Everything has a serving-shaped default;
    tests shrink the arena, `myth serve` exposes the lot as flags."""

    def __init__(
        self,
        stripes: int = 4,
        lanes_per_stripe: int = 8,
        steps_per_wave: int = 256,
        max_waves: int = 2,
        queue_capacity: int = 64,
        calldata_len: int = 68,
        code_cap: int = 2048,
        code_cache_cap: int = 64,
        host_workers: int = 1,
        host_walk: bool = True,
        execution_timeout: int = 8,
        create_timeout: int = 10,
        transaction_count: int = 2,
        checkpoint_dir: Optional[str] = None,
        coalesce_wait_s: float = 0.05,
        idle_wait_s: float = 0.2,
        pipeline: bool = True,
        devices: int = 1,
        specialize: bool = True,
        specialize_warmup: str = "background",
        blockjit: bool = True,
        static_answer: bool = True,
        store_dir: Optional[str] = None,
        store: bool = True,
        arena_warmup: bool = False,
        health_interval_s: float = 2.0,
        journal_dir: Optional[str] = None,
        recover: bool = False,
        journal_fsync: bool = True,
        breakers: bool = True,
        quarantine_strikes: int = 2,
        kernel_pack: Optional[str] = None,
        kernel_cache_dir: Optional[str] = None,
        router_dir: Optional[str] = None,
        router: bool = True,
    ) -> None:
        self.stripes = stripes
        self.lanes_per_stripe = lanes_per_stripe
        self.steps_per_wave = steps_per_wave
        self.max_waves = max_waves
        self.queue_capacity = queue_capacity
        self.calldata_len = calldata_len
        self.code_cap = code_cap
        self.code_cache_cap = code_cache_cap
        self.host_workers = host_workers
        self.host_walk = host_walk
        self.execution_timeout = execution_timeout
        self.create_timeout = create_timeout
        self.transaction_count = transaction_count
        self.checkpoint_dir = checkpoint_dir
        #: brief admission window before an empty arena's first wave so
        #: near-simultaneous submissions coalesce into one dispatch —
        #: the continuous-batching analogue of a scheduler tick
        self.coalesce_wait_s = coalesce_wait_s
        self.idle_wait_s = idle_wait_s
        #: double-buffered wave pipelining: dispatch wave N+1 (seeded
        #: from the corpora known before wave N's results) before
        #: harvesting wave N, so the host-side harvest/admission work
        #: overlaps device execution — waves from DISTINCT jobs share
        #: the two pipeline slots. `myth serve --no-pipeline` disables.
        self.pipeline = pipeline
        #: `myth serve --devices N`: split the arena into N device
        #: groups, one dispatch/harvest pair per group, jobs striped
        #: over groups at admission and migrated to idle groups live
        #: (/stats mesh.* counters). 1 = the single-arena engine.
        self.devices = max(1, int(devices or 1))
        #: contract-specialized step kernels (specialize.py): waves
        #: dispatch on the engine's monotone union bucket (it widens
        #: as new phase groups arrive, never narrows — residency churn
        #: must not churn compiles), cached per bucket and pinned in
        #: the code LRU. `myth serve --no-specialize` restores the
        #: generic interpreter.
        self.specialize = specialize
        #: block-level JIT (laser/batch/blockjit.py): specialized
        #: kernels advance whole lowered CFG basic blocks per
        #: iteration; per-code block-program rows ride the CodeCache
        #: specialization feed. `myth serve --no-blockjit` keeps the
        #: PR-6 fuse-only kernels.
        self.blockjit = blockjit
        #: the static-answer triage tier at admission: a submission
        #: whose semantic screen (analysis/static taint + sink
        #: predicates) proves NO detection module can fire settles
        #: DONE with an empty issue set before it ever reaches the
        #: queue — no wave, no walk, no lane. Also gated by the
        #: process-wide static flags (`--no-static-prune` restores
        #: full-mount parity).
        self.static_answer = static_answer
        #: cross-run verdict store (mythril_tpu/store, `myth serve
        #: --store DIR`): repeat submissions — same codehash, same
        #: analysis-config fingerprint — settle DONE at admission with
        #: the banked issue set (registry-only admission, no queue
        #: slot, no wave, no walk), and every completed walk writes
        #: its verdict back. `--no-store` (store=False) disables the
        #: tier even with a directory configured.
        self.store_dir = store_dir
        self.store = store
        #: arena warmup (myth serve default ON, tests default OFF):
        #: `start()` launches a background all-halt wave of the real
        #: dispatch shape, so the generic kernel compiles before the
        #: first request and /healthz readiness reports
        #: `arena-warming` until it lands — the warming half of the
        #: readiness/liveness split
        self.arena_warmup = arena_warmup
        #: cadence of the health/device sampler thread the server runs
        self.health_interval_s = health_interval_s
        #: durable job journal (`myth serve --journal DIR`,
        #: service/journal.py): every transition is an fsync'd WAL
        #: record, so a SIGKILL/OOM mid-wave loses zero acknowledged
        #: jobs. `recover` (`--recover`) replays prior segments at
        #: startup: terminal jobs are adopted as history, non-terminal
        #: jobs re-admitted (deduping through the verdict store), and
        #: jobs in flight at the crash marker take a quarantine strike.
        self.journal_dir = journal_dir
        self.recover = recover
        self.journal_fsync = journal_fsync
        #: tier circuit breakers (support/breaker.py, `--no-breakers`):
        #: device dispatch, device-first solving, kernel compile, and
        #: store I/O each trip open on repeated failure and route down
        #: their existing fallback ladder instead of re-failing per
        #: job. ANDed with the process-wide support_args.breakers.
        self.breakers = breakers
        #: poison-job quarantine: a codehash implicated in this many
        #: wave faults (async-fault attribution + crash-implication
        #: strikes at recovery) settles FAILED with
        #: DegradationReason.QUARANTINED at admission for the rest of
        #: the process life; one strike short of that, the job is
        #: isolated to a SOLO wave so a poison contract cannot take
        #: innocent neighbors down with it.
        self.quarantine_strikes = max(1, int(quarantine_strikes))
        #: persistent compile plane (mythril_tpu/compileplane):
        #: `kernel_pack` (`myth serve --kernel-pack DIR`) mounts a
        #: prebaked kernel pack at boot — packed buckets dispatch
        #: AOT-loaded executables with zero in-process compiles;
        #: `kernel_cache_dir` (`--kernel-cache DIR`) adds a read-write
        #: artifact cache every compile writes back into, so the NEXT
        #: replica on this (fleet-shared) directory starts warm.
        self.kernel_pack = kernel_pack
        self.kernel_cache_dir = kernel_cache_dir
        #: learned tier-ladder router (mythril_tpu/routing, `myth
        #: serve --router DIR`): admission prices host-walk vs
        #: device-waves from a trained cost-model artifact and routes
        #: host-cheap submissions straight to the walk pool (no queue
        #: slot, no wave), with in-flight promotion back to the wave
        #: queue on budget overrun. Absent/refused artifact or
        #: `--no-router` (router=False): today's ladder, bit for bit.
        self.router_dir = router_dir
        self.router = router
        #: how a not-yet-compiled bucket is handled: "background"
        #: (default — the wave runs GENERIC while a warmup thread
        #: compiles the bucket off the serving path; no request ever
        #: pays specialized-compile latency) or "sync" (compile on the
        #: dispatching wave — deterministic, used by the tests)
        self.specialize_warmup = specialize_warmup


class CodeCache:
    """LRU of disassembled dense code rows keyed by code hash — the
    warm path for resubmitted contracts (to_dense is a host-side
    linear sweep, cheap once but not free at service request rates).
    The static summary (analysis/static: CFG + dataflow + prune feed)
    and the kernel-specialization feed (laser/batch/specialize.py:
    PhaseSet bucket + per-pc fuse row + a PINNED handle on the
    bucket's compiled kernel) ride in the same LRU entry beside the
    disassembly, so a resubmitted contract skips every sweep AND hits
    an already-compiled contract-specialized kernel.

    Eviction releases the entry's kernel pin: the kernel cache may
    then drop the bucket's XLA executables (unless another resident
    contract still pins the same bucket) — a compiled-kernel slot
    never leaks past its LRU entry."""

    def __init__(
        self, code_cap: int, capacity: int = 64, blockjit: bool = True
    ) -> None:
        self.code_cap = code_cap
        self.capacity = max(1, capacity)
        #: engine-level blockjit gate (ServiceConfig.blockjit) — ANDed
        #: with the process-wide blockjit_enabled() switch
        self.blockjit = blockjit
        self._rows: "OrderedDict[str, list]" = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.static_summaries = 0
        self.kernels_pinned = 0
        self.kernels_released = 0

    @staticmethod
    def code_hash(code: bytes) -> str:
        return hashlib.sha256(code).hexdigest()

    def _release_kernel(self, entry: list) -> None:
        """Drop the entry's pin on its specialization bucket (the
        eviction contract: dense rows and the static summary die with
        the entry by GC; the compiled kernel must be RELEASED so the
        kernel cache can drop its live XLA executables too)."""
        spec = entry[3].get("spec")
        if spec is not None and spec.get("kernel") is not None:
            from mythril_tpu.laser.batch.specialize import kernel_cache

            kernel_cache().release(spec["kernel"])
            spec["kernel"] = None
            self.kernels_released += 1

    def _entry(self, code: bytes) -> list:
        from mythril_tpu.disassembler.asm import to_dense

        key = self.code_hash(code)
        hit = self._rows.get(key)
        if hit is not None:
            self.hits += 1
            self._rows.move_to_end(key)
            return hit
        self.misses += 1
        ops_row = np.zeros((self.code_cap + 33,), dtype=np.uint8)
        ops, jumpdest = to_dense(code, max_len=self.code_cap)
        ops_row[: self.code_cap] = ops
        # slot 3 holds the lazily-built derived feeds: the static
        # summary and the specialization feed (None until a consumer
        # asks for them)
        entry = [
            ops_row, jumpdest, min(len(code), self.code_cap),
            {"summary": None, "summary_tried": False, "spec": None},
        ]
        self._rows[key] = entry
        while len(self._rows) > self.capacity:
            _k, evicted = self._rows.popitem(last=False)
            self._release_kernel(evicted)
            self.evictions += 1
        return entry

    def rows(self, code: bytes) -> Tuple[np.ndarray, np.ndarray, int]:
        """(ops[code_cap+33] u8, jumpdest[code_cap] bool, length)."""
        entry = self._entry(code)
        return entry[0], entry[1], entry[2]

    def static_summary(self, code: bytes):
        """The code's StaticSummary from the same LRU entry, built on
        first request; None when the static layer is off or failed."""
        entry = self._entry(code)
        feeds = entry[3]
        if feeds["summary"] is None and not feeds["summary_tried"]:
            feeds["summary_tried"] = True
            try:
                from mythril_tpu.analysis.static import (
                    static_prune_enabled,
                    summary_for,
                )

                if not static_prune_enabled():
                    return None
                feeds["summary"] = summary_for(code)
                self.static_summaries += 1
            except Exception:
                log.debug("static summary failed", exc_info=True)
                return None
        return feeds["summary"]

    def spec_for(self, code: bytes) -> Optional[Dict]:
        """The code's specialization feed from the same LRU entry:
        {"phases": PhaseSet, "fuse_row": u8[code_cap], "block_row":
        u8[code_cap], "kernel": pinned SpecializedKernel} — built (and
        the kernel compiled lazily on its first wave) once per
        resident code hash, so warm resubmissions dispatch with zero
        compile latency AND zero table-sweep cost (the fuse/block
        rows were previously rebuilt per wave). None when
        specialization is off or the feed build failed."""
        entry = self._entry(code)
        feeds = entry[3]
        if feeds["spec"] is None:
            try:
                from mythril_tpu.laser.batch import blockjit as _bj
                from mythril_tpu.laser.batch import specialize as _spec

                if not _spec.specialize_enabled():
                    return None
                summary = self.static_summary(code)
                blockjit_on = self.blockjit and _bj.blockjit_enabled()
                phases = _spec.phases_for(
                    _spec.signature_for(code, summary),
                    fuse=_spec.fuse_profitable(code, summary),
                    block_depth=(
                        _bj.block_depth_for(code, summary)
                        if blockjit_on
                        else 0
                    ),
                )
                feeds["spec"] = {
                    "phases": phases,
                    "fuse_row": _spec.build_fuse_row(
                        code, self.code_cap, summary
                    ),
                    "block_row": (
                        _bj.build_block_row(code, self.code_cap, summary)
                        if blockjit_on
                        else None
                    ),
                    "kernel": _spec.kernel_cache().acquire(phases),
                }
                self.kernels_pinned += 1
            except Exception:
                log.debug("specialization feed failed", exc_info=True)
                return None
        return feeds["spec"]

    def rebucket(self, code_cap: int) -> None:
        """Grow the capacity (new kernel shape): cached rows are the
        old width, so the cache flushes and rebuilds lazily — kernel
        pins released with their entries."""
        self.code_cap = code_cap
        for entry in self._rows.values():
            self._release_kernel(entry)
        self._rows.clear()

    def stats(self) -> Dict:
        return {
            "size": len(self._rows),
            "capacity": self.capacity,
            "hits": self.hits,
            "misses": self.misses,
            "evictions": self.evictions,
            "static_summaries": self.static_summaries,
            "kernels_pinned": self.kernels_pinned,
            "kernels_released": self.kernels_released,
        }


class _JobTrack:
    """Per-resident-job device bookkeeping: lanes, seeds, coverage,
    trigger bank. Touched only by the wave thread."""

    def __init__(
        self, job: Job, stripes: List[int], lanes: List[int],
        calldata_len: int, static_feed=None, spec_feed=None,
    ) -> None:
        import random

        from mythril_tpu.laser.batch.seeds import dispatcher_seeds

        self.job = job
        self.stripes = stripes
        self.lanes = lanes
        self.code_row = stripes[0]
        self.calldata_len = calldata_len
        # the static prune feed masks inert selectors out of this
        # job's seeding; per-job drop delta kept for the report
        self.static = static_feed
        #: the code's specialization feed (CodeCache.spec_for): the
        #: wave kernel is the union bucket over resident jobs' phases
        self.spec = spec_feed
        before = static_feed.seeds_dropped if static_feed else 0
        self.seeds = dispatcher_seeds(
            job.code.hex(), calldata_len, prune=static_feed
        )
        self.static_seeds_dropped = (
            (static_feed.seeds_dropped - before) if static_feed else 0
        )
        self.corpus: List[bytes] = list(self.seeds)
        self.covered: set = set()
        #: True when a donor replica's frontier seeded this track (the
        #: cross-host rebalance handoff; rides into the job report)
        self.frontier_seeded = False
        self.pc_seen: Optional[np.ndarray] = None
        self.triggers: Dict[str, List[Dict]] = {}
        self.waves_done = 0
        self.stale_waves = 0
        self.degraded_lanes = 0
        self.lane_steps = 0
        self.rng = random.Random(int(job.id, 16))
        if job.frontier:
            try:
                self.seed_frontier(job.frontier)
            except Exception:
                log.warning(
                    "job %s: donor frontier refused; exploring from "
                    "scratch", job.id, exc_info=True,
                )

    def seed_frontier(self, frontier: Dict) -> None:
        """Install a donor replica's exported frontier (the
        explore.py `export_frontier` shape, hex-encoded for the HTTP
        hop) BEFORE this track's first wave: the donor's covered
        branch directions stay covered and its parent inputs lead the
        mutation corpus — the service-tier mirror of
        DeviceCorpusExplorer.seed_frontier, so a rebalanced job
        continues the donor's exploration instead of restarting."""
        self.covered |= {
            (int(pc), bool(taken))
            for pc, taken in frontier.get("covered") or []
        }
        inputs = []
        for data in frontier.get("parent_inputs") or []:
            try:
                inputs.append(
                    bytes.fromhex(data) if isinstance(data, str)
                    else bytes(data)
                )
            except (ValueError, TypeError):
                continue
        if inputs:
            self.corpus = inputs + self.corpus
        self.frontier_seeded = True

    def export_frontier(self) -> Dict:
        """Pack this track's live frontier for a host handoff to
        another replica — the same keys DeviceCorpusExplorer.
        export_frontier packs (explore.py), with byte payloads
        hex-encoded so the doc rides GET /v1/frontier/export."""
        return {
            "code_hex": self.job.code.hex(),
            "covered": [
                [int(pc), bool(taken)]
                for pc, taken in sorted(self.covered)
            ],
            "attempted": [],
            "parent_inputs": [d.hex() for d in self.corpus[-64:]],
            "carries": [],
        }

    def next_inputs(self) -> List[bytes]:
        """One calldata per owned lane: dispatcher seeds first, then
        single-byte mutations of the banked corpus (the explorer's
        mutation-fill idiom, scaled down to a stripe)."""
        out: List[bytes] = []
        if self.waves_done == 0:
            for i in range(len(self.lanes)):
                out.append(self.seeds[i % len(self.seeds)])
            return out
        for _ in self.lanes:
            parent = self.rng.choice(self.corpus)
            mutated = bytearray(parent.ljust(self.calldata_len, b"\x00"))
            mutated[self.rng.randrange(len(mutated))] = self.rng.randrange(256)
            out.append(bytes(mutated))
        return out

    def harvest(
        self, inputs: List[bytes], status, halt_pc, gas_min, gas_max,
        br_pc, br_taken, br_cnt, pc_seen, steps: int, lanes=None,
    ) -> None:
        # `lanes` is the dispatch-time snapshot: under the mesh a job
        # may migrate to another group while its wave is in flight, so
        # the harvest must read the lanes the wave actually ran on
        lanes = self.lanes if lanes is None else lanes
        fresh = 0
        self.waves_done += 1
        self.lane_steps += steps * len(lanes)
        for data, lane in zip(inputs, lanes):
            st = int(status[lane])
            if st in _DEGRADED_STATUSES:
                self.degraded_lanes += 1
            kind = _TRIGGER_KINDS.get(st)
            if kind is not None:
                bucket = self.triggers.setdefault(kind, [])
                pc = int(halt_pc[lane])
                if all(pc != t["pc"] for t in bucket) and len(bucket) < 64:
                    bucket.append(
                        {
                            "pc": pc,
                            "input": data.hex(),
                            "prefix": [],
                            "gas_min": int(gas_min[lane]),
                            "gas_max": int(gas_max[lane]),
                            "call_value": 0,
                            "prefix_values": [],
                        }
                    )
            for k in range(int(br_cnt[lane])):
                edge = (int(br_pc[lane, k]), bool(br_taken[lane, k]))
                if edge not in self.covered:
                    self.covered.add(edge)
                    fresh += 1
            self.corpus.append(data)
        rows = pc_seen[lanes].astype(np.uint32)
        merged = np.bitwise_or.reduce(rows, axis=0)
        if self.pc_seen is None or np.any(merged & ~self.pc_seen):
            fresh += 1
        self.pc_seen = (
            merged if self.pc_seen is None else (self.pc_seen | merged)
        )
        del self.corpus[256:]  # bounded seed bank
        self.stale_waves = 0 if fresh else self.stale_waves + 1

    def outcome(self) -> Dict:
        """The device phase's result in the prepass-outcome shape
        SymExecWrapper injects (explore.py outcome contract): trigger
        witnesses become Issues, covered branch directions pre-empt
        host feasibility queries."""
        from mythril_tpu.laser.batch.explore import ExploreStats

        stats = ExploreStats()
        stats.device_steps = self.lane_steps
        stats.waves = self.waves_done
        stats.branches_covered = len(self.covered)
        stats.lanes_degraded_mem = 0
        return {
            "covered_branches": sorted(self.covered),
            "corpus_size": len(self.corpus),
            "triggers": {k: list(v) for k, v in self.triggers.items()},
            "evidence": [],
            "device_complete": False,
            "completeness_gates": {},
            "degraded_lanes": self.degraded_lanes,
            "stats": stats.as_dict(),
        }

    def covered_pc_bits(self) -> int:
        if self.pc_seen is None:
            return 0
        return int(
            (np.unpackbits(self.pc_seen.view(np.uint8)) != 0).sum()
        )


class AnalysisEngine:
    """Wave loop + admission + host pool behind the HTTP server.

    `start()` spins the wave thread; `submit()` is thread-safe (the
    HTTP layer calls it from handler threads); `drain()` implements the
    SIGTERM contract. The engine also works un-started: submissions
    queue, and a drain checkpoints them — the degenerate case the drain
    tests pin without paying a kernel compile."""

    def __init__(self, config: Optional[ServiceConfig] = None) -> None:
        from mythril_tpu.laser.batch.seeds import code_cap_bucket
        from mythril_tpu.support.resilience import DegradationLog

        self.cfg = config or ServiceConfig()
        self.queue = JobQueue(self.cfg.queue_capacity)
        #: device-group mesh (myth serve --devices N): the arena
        #: splits into per-group stripe blocks, each group runs its
        #: own dispatch/harvest pair, and jobs stripe over the groups
        self.mesh = None
        if self.cfg.devices > 1:
            from mythril_tpu.parallel.topology import discover_topology

            self.mesh = discover_topology(self.cfg.devices)
        self.alloc = LaneAllocator(
            self.cfg.stripes,
            self.cfg.lanes_per_stripe,
            groups=self.mesh.n_groups if self.mesh else 1,
        )
        #: per-device (group) tables (mesh counters live in the
        #: registry — /stats mesh.* reads the snapshot)
        self._group_tables: Dict = {}
        self.code_cap = code_cap_bucket(1, floor=self.cfg.code_cap)
        self.code_cache = CodeCache(
            self.code_cap, self.cfg.code_cache_cap,
            blockjit=self.cfg.blockjit,
        )
        self._tracks: "OrderedDict[str, _JobTrack]" = OrderedDict()
        self._arena_ops: Optional[np.ndarray] = None
        self._arena_jd: Optional[np.ndarray] = None
        self._arena_len: Optional[np.ndarray] = None
        self._arena_fuse: Optional[np.ndarray] = None
        self._arena_block: Optional[np.ndarray] = None
        self._code_table = None
        self._fuse_table = None
        self._block_table = None
        self._group_fuse: Dict = {}
        self._group_block: Dict = {}
        self._table_dirty = True
        self._rebuild_arena_rows()
        self._lock = threading.Lock()
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._pool = ThreadPoolExecutor(
            max_workers=max(1, self.cfg.host_workers),
            thread_name_prefix="myth-serve-host",
        )
        self._host_inflight: Dict[str, Tuple] = {}
        #: walker processes the host walks run in side by side, started
        #: with the engine when `host_workers` and the CPUs allow two or
        #: more (None walks in this process, one at a time)
        self._walkers: Optional[WalkerPool] = None
        # the learned tier-ladder router (mythril_tpu/routing): priced
        # admission + in-flight promotion. None (no artifact, refused
        # artifact, or router=False) keeps today's ladder bit-for-bit.
        self._router = None
        if self.cfg.router:
            try:
                from mythril_tpu.routing import router as _routing_rt
                from mythril_tpu.routing import tuning as _routing_tune

                self._router = (
                    _routing_rt.load_router(self.cfg.router_dir)
                    if self.cfg.router_dir
                    else _routing_rt.configured_router()
                )
                # tuned portfolio-override artifacts ride the same
                # directory: install the newest verifying one
                if self.cfg.router_dir:
                    _routing_tune.maybe_install_tuned(self.cfg.router_dir)
            except Exception:
                self._router = None
                log.debug("router load failed", exc_info=True)
        self._deg_marker = DegradationLog().marker()
        # -- observability: the wave-loop counters are REGISTRY-backed
        # (mtpu_service_* series labeled by engine instance): every
        # mutation goes through the registry's one lock, and stats()
        # reads them all from ONE snapshot — a point-in-time-consistent
        # /stats instead of field-by-field reads racing the wave loop.
        # The legacy attribute names stay as properties below.
        self.started_t = time.monotonic()
        self._eid = f"e{next(_ENGINE_SERIAL)}"
        reg = observe.registry()
        lab = {"engine": self._eid}
        self._c_waves = reg.counter(
            "mtpu_service_waves_total", "device waves dispatched"
        ).labels(**lab)
        self._c_device_steps = reg.counter(
            "mtpu_service_device_steps_total", "lane-steps executed"
        ).labels(**lab)
        self._c_host_completed = reg.counter(
            "mtpu_service_host_completed_total", "host walks finished"
        ).labels(**lab)
        self._c_host_walks = reg.counter(
            "mtpu_serve_host_walks_total",
            "host walks by where they ran: a walker process or in-process",
        )
        self._c_rebuckets = reg.counter(
            "mtpu_service_kernel_rebuckets_total",
            "code-capacity re-buckets (arena recompiles)",
        ).labels(**lab)
        self._c_static_seeds = reg.counter(
            "mtpu_service_static_seeds_dropped_total",
            "dispatcher seeds masked by the static prune",
        ).labels(**lab)
        self._c_static_answered = reg.counter(
            "mtpu_service_static_answered_total",
            "submissions settled by the static-answer triage tier "
            "(no device dispatch, no host walk)",
        ).labels(**lab)
        self._c_store_answered = reg.counter(
            "mtpu_service_store_answered_total",
            "submissions settled by the verdict store at admission "
            "(no queue slot, no wave, no walk)",
        ).labels(**lab)
        self._c_store_writebacks = reg.counter(
            "mtpu_service_store_writebacks_total",
            "completed walks persisted into the verdict store",
        ).labels(**lab)
        self._c_wave_kind = reg.counter(
            "mtpu_service_wave_kind_total",
            "waves by kernel kind (specialized vs generic)",
        )
        self._c_spec_waves = self._c_wave_kind.labels(kind="spec", **lab)
        self._c_generic_waves = self._c_wave_kind.labels(
            kind="generic", **lab
        )
        self._c_fused = reg.counter(
            "mtpu_service_fused_steps_total",
            "instructions advanced by fused substeps",
        ).labels(**lab)
        self._c_blocks = reg.counter(
            "mtpu_service_blockjit_blocks_total",
            "lowered basic blocks entered by block substeps "
            "(block-level JIT)",
        ).labels(**lab)
        self._c_fallbacks = reg.counter(
            "mtpu_service_kernel_fallbacks_total",
            "specialized waves retried on the generic kernel",
        ).labels(**lab)
        self._c_overlapped = reg.counter(
            "mtpu_service_pipeline_overlapped_total",
            "harvests that ran with another wave in flight",
        ).labels(**lab)
        self._c_multi_job = reg.counter(
            "mtpu_service_pipeline_multi_job_total",
            "overlaps whose two slots spanned distinct jobs",
        ).labels(**lab)
        self._g_inflight = reg.gauge(
            "mtpu_service_pipeline_inflight",
            "waves currently in flight past the dispatch slot",
        ).labels(**lab)
        self._c_mesh_steals = reg.counter(
            "mtpu_service_mesh_steals_total",
            "resident-job migrations to idle device groups",
        ).labels(**lab)
        self._c_mesh_rebalance = reg.counter(
            "mtpu_service_mesh_rebalance_bytes_total",
            "bytes re-uploaded by job migrations",
        ).labels(**lab)
        self._c_quarantined = reg.counter(
            "mtpu_quarantined_total",
            "jobs settled FAILED by the poison-job quarantine "
            "(denylisted codehash or strike threshold reached)",
        ).labels(**lab)
        self._c_recovered = reg.counter(
            "mtpu_journal_recovered_jobs_total",
            "non-terminal journaled jobs re-admitted at recovery",
        ).labels(**lab)
        self._c_recovery_deduped = reg.counter(
            "mtpu_journal_recovery_deduped_total",
            "recovered jobs settled instantly through the verdict "
            "store instead of re-running",
        ).labels(**lab)
        self._c_group_waves = reg.counter(
            "mtpu_service_group_waves_total",
            "waves dispatched per device group",
        )
        # materialize every series at 0 so /metrics exposes the full
        # schema from the first scrape (a dashboard must not have to
        # wait for the first wave to learn the series names)
        for child in (
            self._c_waves, self._c_device_steps, self._c_host_completed,
            self._c_rebuckets, self._c_static_seeds,
            self._c_static_answered, self._c_store_answered,
            self._c_store_writebacks, self._c_spec_waves,
            self._c_generic_waves, self._c_fused, self._c_fallbacks,
            self._c_overlapped, self._c_multi_job, self._c_mesh_steals,
            self._c_mesh_rebalance, self._c_quarantined,
            self._c_recovered, self._c_recovery_deduped,
        ):
            child.inc(0)
        self._g_inflight.set(0)
        for gid in range(self.mesh.n_groups if self.mesh else 1):
            self._c_group_waves.labels(
                engine=self._eid, group=str(gid)
            ).inc(0)
        #: the engine's monotone specialization bucket (widens as jobs
        #: with new phase groups arrive; a wider kernel stays sound
        #: for every lane) and the warmups already launched for it
        self._union_phases = None
        self._kernel_warming: set = set()
        self._warmup_threads: List[threading.Thread] = []
        self._first_wave_t: Optional[float] = None
        self._last_wave_t: Optional[float] = None
        self._wave_cold_s: Optional[float] = None
        self._wave_warm_ema_s: Optional[float] = None
        # -- cross-run verdict store (mythril_tpu/store) ---------------
        # one fingerprint per engine: the service's verdict-relevant
        # config is fixed at construction, so repeats hash once
        self.vstore = None
        self._config_fp: Optional[str] = None
        if self.cfg.store:
            try:
                from mythril_tpu.analysis.static.summary import (
                    analysis_config_fingerprint,
                )
                from mythril_tpu.store import configured_store

                self.vstore = configured_store(self.cfg.store_dir)
                if self.vstore is not None:
                    self._config_fp = analysis_config_fingerprint(
                        transaction_count=self.cfg.transaction_count,
                        create_timeout=self.cfg.create_timeout,
                    )
            except Exception:
                log.warning("verdict store unavailable", exc_info=True)
                self.vstore = None
        self._checkpoint_dir: Optional[str] = self.cfg.checkpoint_dir
        self._drained = threading.Event()
        self._draining = False
        #: where the drain's final flight-recorder flush landed (None
        #: until drained; /stats observe.flight_dump mirrors it)
        self.flight_dump_path: Optional[str] = None
        # -- health state machine (observe/slo.py) ---------------------
        # the SLO engine samples the shared registry; the monitor folds
        # objective burn with this engine's lifecycle facts into the
        # ok/degraded/redlined machine /healthz and mtpu_health_state
        # export. Warming is set immediately when arena warmup is off.
        # -- persistent compile plane (mythril_tpu/compileplane) -------
        # mounted SYNCHRONOUSLY, before the health monitor exists and
        # before the server can bind: the boot order the pack
        # readiness contract pins (mount -> serve -> ready). A pack
        # failure degrades to plain in-process compiles — it must
        # never stop the replica from serving.
        self._pack_mounted: Dict = {}
        if self.cfg.kernel_pack or self.cfg.kernel_cache_dir:
            try:
                from mythril_tpu.compileplane.plane import configure_plane

                plane = configure_plane(
                    cache_dir=self.cfg.kernel_cache_dir,
                    pack_dirs=(
                        (self.cfg.kernel_pack,)
                        if self.cfg.kernel_pack
                        else ()
                    ),
                )
                if plane is not None and self.cfg.kernel_pack:
                    self._pack_mounted = plane.mount_packs()
            except Exception:
                log.warning(
                    "kernel pack mount failed; compiling in-process",
                    exc_info=True,
                )
        self._warm_done = threading.Event()
        if not self.cfg.arena_warmup or self._pack_covers_warmup():
            # no warmup configured — or the mounted pack already holds
            # the generic warmup executable for this dispatch shape:
            # a pack-warmed replica is ready as soon as the pack is
            # mounted, it does not wait out a compile clock that will
            # never tick
            self._warm_done.set()
        self.health = observe.HealthMonitor(
            warming_fn=lambda: not self._warm_done.is_set(),
            compiling_fn=lambda: any(
                t.is_alive() for t in self._warmup_threads
            ),
            draining_fn=lambda: self._draining,
            saturation_fn=self._saturation_reasons,
        )
        # the device monitor reads this engine's arena occupancy (the
        # newest engine owns the source; tests run many engines per
        # process and the live serve runs one)
        observe.device_monitor().set_arena_source(self.alloc.occupancy)
        # -- poison-job quarantine ------------------------------------
        # strike counters by codehash (wave-fault attribution +
        # crash-implication at recovery) and the process-lifetime
        # denylist; a clean DONE settle clears a codehash's strikes
        self._strikes: Dict[str, int] = {}
        self._denylist: set = set()
        #: idempotency-key -> job id (seeded from the journal at
        #: recovery): a retried submit with a known key maps to the
        #: existing job instead of double-running
        self._idem: Dict[str, str] = {}
        # -- durable job journal (service/journal.py) -----------------
        self.journal = None
        if self.cfg.journal_dir:
            try:
                from mythril_tpu.service.journal import JobJournal

                self.journal = JobJournal(
                    self.cfg.journal_dir, fsync=self.cfg.journal_fsync
                )
            except OSError:
                log.warning("job journal unavailable", exc_info=True)
        self.queue.journal = self.journal
        if self.journal is not None and self.cfg.recover:
            try:
                self._recover_from_journal()
            except Exception:
                log.exception("journal recovery failed; serving fresh")

    # -- legacy counter names (views over the registry series) ---------
    @property
    def waves_total(self) -> int:
        return int(self._c_waves.value)

    @property
    def device_steps(self) -> int:
        return int(self._c_device_steps.value)

    @property
    def host_completed(self) -> int:
        return int(self._c_host_completed.value)

    @property
    def kernel_rebuckets(self) -> int:
        return int(self._c_rebuckets.value)

    @property
    def static_seeds_dropped(self) -> int:
        return int(self._c_static_seeds.value)

    @property
    def spec_waves(self) -> int:
        return int(self._c_spec_waves.value)

    @property
    def generic_waves(self) -> int:
        return int(self._c_generic_waves.value)

    @property
    def kernel_fused_steps(self) -> int:
        return int(self._c_fused.value)

    @property
    def kernel_fallbacks(self) -> int:
        return int(self._c_fallbacks.value)

    @property
    def pipeline_overlapped(self) -> int:
        return int(self._c_overlapped.value)

    @property
    def pipeline_multi_job(self) -> int:
        return int(self._c_multi_job.value)

    @property
    def _pipeline_inflight(self) -> int:
        return int(self._g_inflight.value)

    @property
    def mesh_steals(self) -> int:
        return int(self._c_mesh_steals.value)

    @property
    def mesh_rebalance_bytes(self) -> int:
        return int(self._c_mesh_rebalance.value)

    # -- lifecycle -----------------------------------------------------
    def _saturation_reasons(self) -> List[str]:
        """Live redline facts for the health monitor: a full admission
        queue means the replica is refusing work RIGHT NOW — the
        federation front should stop routing here before the SLO
        windows even notice."""
        from mythril_tpu.observe import slo

        reasons: List[str] = []
        if self.queue.depth() >= self.queue.capacity:
            reasons.append(slo.REDLINE_QUEUE_SATURATED)
        # open tier breakers (support/breaker.py): the replica is
        # serving through a fallback ladder — enumerated so the
        # federation front can route around it until the half-open
        # probe recovers
        if self.cfg.breakers:
            from mythril_tpu.support import breaker as cb

            if cb.breakers_enabled():
                reasons.extend(cb.open_reasons())
        return reasons

    def _warmup_batch(self):
        """The all-halt batch of the exact dispatch shape — shared by
        the warmup wave and the pack-coverage probe (identical avals
        by construction)."""
        from mythril_tpu.laser.batch.state import make_batch

        n = self.alloc.n_lanes
        return make_batch(
            n,
            code_ids=np.full((n,), self.cfg.stripes, np.int32),
            calldata=[b""] * n,
            caller=DEFAULT_CALLER,
            address=DEFAULT_ADDRESS,
            timestamp=0x5BFA4639,
            number=0x66E393,
            gasprice=0x773594000,
        )

    def _pack_covers_warmup(self) -> bool:
        """Did the pack mount pre-load the generic wave executable for
        THIS engine's dispatch shape? Then mounting WAS the warmup:
        the first wave dispatches an already-resident executable and
        readiness can clear immediately (the `--no-arena-warmup` +
        `--kernel-pack` interaction contract in tests/service)."""
        if not self._pack_mounted.get("mounted"):
            return False
        try:
            from mythril_tpu.compileplane.plane import active_plane
            from mythril_tpu.laser.batch.run import wave_entry_digest

            plane = active_plane()
            if plane is None:
                return False
            digest = wave_entry_digest(
                self._warmup_batch(),
                self._table(),
                max_steps=self.cfg.steps_per_wave,
                track_coverage=True,
                donate=False,
            )
            return plane.preloaded(None, digest)
        except Exception:
            log.debug("pack warmup-coverage probe failed", exc_info=True)
            return False

    def _arena_warmup(self) -> None:
        """Compile the generic wave kernel OFF the serving path: one
        all-halt wave of the exact dispatch shape, so the first real
        request rides a warm executable and readiness truthfully says
        when. With a kernel pack mounted, the wave entry loads from
        the plane instead of compiling — seconds, not minutes.
        Failure still flips readiness — a broken warmup must not
        wedge the replica not-ready forever (the first real wave will
        surface the fault with attribution)."""
        try:
            import jax

            from mythril_tpu.laser.batch.run import wave_run

            batch = self._warmup_batch()
            with trace("service.arena.warmup", track="service"):
                _out, steps = wave_run(
                    batch,
                    self._table(),
                    max_steps=self.cfg.steps_per_wave,
                    track_coverage=True,
                    donate=False,
                )
                jax.block_until_ready(steps)
        except Exception:
            log.warning("arena warmup failed", exc_info=True)

    def _warm_up(self, arena: bool) -> None:
        """Readiness: the arena warm-up wave (when `arena`), and every
        walker's warm-up walk, which runs in its own process alongside
        the arena's compile or cache loads."""
        try:
            if arena:
                self._arena_warmup()
            if self._walkers is not None:
                self._walkers.wait_warm()
        finally:
            self._warm_done.set()

    def start(self) -> "AnalysisEngine":
        if self._thread is None:
            arena = self.cfg.arena_warmup and not self._warm_done.is_set()
            width = walker_width(self.cfg.host_workers)
            if width > 1:
                self._walkers = WalkerPool(width)
                self._warm_done.clear()
            self._thread = threading.Thread(
                target=self._loop, name="myth-serve-waves", daemon=True
            )
            self._thread.start()
            if not self._warm_done.is_set():
                threading.Thread(
                    target=self._warm_up,
                    args=(arena,),
                    name="myth-arena-warmup",
                    daemon=True,
                ).start()
        return self

    def submit(self, job: Job) -> Job:
        """Admit `job` through the tier ladder; returns the CANONICAL
        job — which is an earlier one when the submission carried an
        idempotency key the service has already seen (a client retry
        after a dropped connection or a server restart must map back
        to the same job, never double-run)."""
        from mythril_tpu.support.resilience import inject

        inject("service.admit")
        key = job.idempotency_key
        if key:
            existing = self.queue.get(self._idem.get(key, ""))
            if existing is not None:
                return existing
        observe.journey_event(
            job.journey_id, journey.TIER_ADMISSION, "submitted",
            code_len=len(job.code),
        )
        if key:
            self._idem[key] = job.id
        if self._try_quarantine(job):
            return job
        if self._try_store_hit(job):
            return job
        if self._try_static_answer(job):
            return job
        if self._try_routed_host(job):
            return job
        self.queue.submit(job)  # raises QueueRefusal on backpressure
        self._wake.set()
        return job

    # -- poison-job quarantine -----------------------------------------
    def _strike(self, code_hash: str) -> int:
        """One wave-fault (or crash-implication) strike against a
        codehash; returns the new count."""
        count = self._strikes.get(code_hash, 0) + 1
        self._strikes[code_hash] = count
        return count

    def _is_quarantined(self, code_hash: str) -> bool:
        return (
            code_hash in self._denylist
            or self._strikes.get(code_hash, 0)
            >= self.cfg.quarantine_strikes
        )

    def _is_suspect(self, code_hash: str) -> bool:
        """One strike short of quarantine: the job still runs, but
        ISOLATED to a solo wave — a poison contract must not take
        innocent arena neighbors down with its next fault."""
        return self._strikes.get(code_hash, 0) >= 1

    def _quarantine_job(self, job: Job, code_hash: str) -> None:
        """Settle `job` FAILED with the QUARANTINED degradation and
        denylist its codehash for the process lifetime. The job must
        already be registered in the queue."""
        from mythril_tpu.support.resilience import (
            DegradationLog,
            DegradationReason,
        )

        self._denylist.add(code_hash)
        self._c_quarantined.inc()
        job.degraded.append(DegradationReason.QUARANTINED)
        job.error = (
            job.error
            or "codehash quarantined after repeated wave faults"
        )
        DegradationLog().record(
            DegradationReason.QUARANTINED,
            site="service-quarantine",
            contract=job.id,
            detail=code_hash[:16],
        )
        observe.journey_event(
            job.journey_id, journey.TIER_ADMISSION, "quarantined",
            code_hash=code_hash[:16],
        )
        job.report = {
            "job_id": job.id,
            "journey_id": job.journey_id,
            "code_hash": code_hash,
            "quarantined": True,
            "issues": [],
        }
        self.queue.settle(job, JobState.FAILED)
        self._routing_record(job, route="quarantined")

    def _try_quarantine(self, job: Job) -> bool:
        """The quarantine gate at admission: a denylisted (or
        strike-threshold) codehash settles FAILED instantly —
        registry-only admission, no queue slot, no wave, no chance to
        crash the arena again. False lets the job continue down the
        tier ladder; QueueRefusal propagates when draining."""
        code_hash = CodeCache.code_hash(job.code)
        if not self._is_quarantined(code_hash):
            return False
        self.queue.register(job)  # raises QueueRefusal when draining
        self._quarantine_job(job, code_hash)
        return True

    # -- tier circuit breakers -----------------------------------------
    def _breaker(self, tier: str):
        """The tier's process-wide breaker, or None when the layer is
        off (config knob AND the --no-breakers flag bag switch)."""
        from mythril_tpu.support import breaker as cb

        if not (self.cfg.breakers and cb.breakers_enabled()):
            return None
        return cb.breaker(tier)

    def _breaker_allow(self, tier: str) -> bool:
        br = self._breaker(tier)
        return True if br is None else br.allow()

    def _breaker_record(self, tier: str, ok: bool, detail: str = "") -> None:
        br = self._breaker(tier)
        if br is None:
            return
        if ok:
            br.record_success()
        else:
            br.record_failure(detail)

    # -- journal recovery ----------------------------------------------
    def _recover_from_journal(self) -> None:
        """Replay prior journal segments: adopt terminal jobs as
        queryable history (reports re-attached from the verdict store
        when banked), strike crash-implicated in-flight jobs, re-admit
        everything non-terminal through the normal tier ladder (the
        store dedupes already-computed verdicts in microseconds), then
        compact the old segments away."""
        from mythril_tpu.service.journal import EVENT_SETTLED

        replay = self.journal.replay_prior()
        if not replay.records:
            return
        # crash-implication strikes BEFORE re-admission: a job that
        # was on the device when the process died runs solo this time
        # (and quarantines if it was already striked)
        implicated = replay.crash_implicated()
        for jj in implicated:
            if jj.code_hash:
                self._strike(jj.code_hash)
        log.info(
            "journal recovery: %d records across %d segments, %d jobs "
            "(%d crash-implicated)%s",
            replay.records, len(replay.segments), len(replay.jobs),
            len(implicated),
            "" if replay.clean_shutdown else " — UNCLEAN shutdown",
        )
        for jj in replay.jobs.values():
            if jj.idempotency_key:
                self._idem[jj.idempotency_key] = jj.job_id
            if not jj.terminal:
                continue
            # terminal: adopt as history + re-journal one compact
            # settled line so the NEXT recovery survives compaction
            job = Job(code_hex=jj.code_hex or "00")
            job.id = jj.job_id
            job.journey_id = jj.job_id
            job.idempotency_key = jj.idempotency_key
            job.recovered = True
            job.state = jj.state
            if (
                jj.state == JobState.DONE
                and self.vstore is not None
                and jj.code_hash
            ):
                try:
                    entry = self.vstore.get(jj.code_hash, self._config_fp)
                except Exception:
                    entry = None
                if entry is not None:
                    job.report = {
                        "job_id": job.id,
                        "journey_id": job.journey_id,
                        "code_hash": jj.code_hash,
                        "store_hit": True,
                        "recovered": True,
                        "issues": entry.issues,
                    }
            self.queue.adopt(job)
            self.journal.append(
                EVENT_SETTLED, sync=False, job_id=jj.job_id,
                state=jj.state, code_hash=jj.code_hash,
                key=jj.idempotency_key,
            )
        for jj in replay.nonterminal():
            if not jj.code_hex:
                continue  # never durably admitted: nothing to re-run
            try:
                params = jj.params or {}
                job = Job(
                    code_hex=jj.code_hex,
                    max_waves=params.get("max_waves"),
                    deadline_s=params.get("deadline_s"),
                    host_walk=params.get("host_walk"),
                    lanes=params.get("lanes"),
                    idempotency_key=jj.idempotency_key,
                )
            except ValueError:
                continue
            job.id = jj.job_id
            job.journey_id = jj.job_id
            job.recovered = True
            if jj.idempotency_key:
                self._idem[jj.idempotency_key] = job.id
            try:
                self.submit(job)
            except Exception:
                log.warning(
                    "recovery re-admission refused for job %s",
                    jj.job_id, exc_info=True,
                )
                continue
            self._c_recovered.inc()
            if job.terminal and (job.report or {}).get("store_hit"):
                self._c_recovery_deduped.inc()
        self.journal.compact()

    def _try_store_hit(self, job: Job) -> bool:
        """The verdict-store exact-hit tier at admission (HTTP thread,
        one hash + one file read warm): a submission whose (codehash,
        config fingerprint) is banked settles DONE with the stored
        issue set before it ever reaches the queue — registry-only
        admission, exactly like the static-answer tier below it. False
        keeps the job on the full path; QueueRefusal propagates when
        draining."""
        from mythril_tpu.store import store_enabled

        if self.vstore is None or not store_enabled():
            return False
        try:
            entry = self.vstore.get(
                CodeCache.code_hash(job.code), self._config_fp
            )
        except Exception:
            log.debug("store lookup failed; full path", exc_info=True)
            return False
        if entry is None:
            return False
        self.queue.register(job)  # raises QueueRefusal when draining
        self._c_store_answered.inc()
        observe.journey_event(
            job.journey_id, journey.TIER_STORE_HIT, "banked-verdict",
            issues=len(entry.issues or ()),
        )
        now = time.monotonic()
        job.report = {
            "job_id": job.id,
            "journey_id": job.journey_id,
            "code_hash": entry.code_hash,
            "store_hit": True,
            "issues": entry.issues,
            "store": {
                "config_fingerprint": entry.config_fp,
                "provenance": entry.provenance,
            },
            "timings": {
                "queued_s": 0.0,
                "device_s": 0.0,
                "total_s": round(now - job.created_t, 6),
            },
        }
        self.queue.settle(job, JobState.DONE)
        self._routing_record(job, route="store-hit")
        return True

    def _try_static_answer(self, job: Job) -> bool:
        """The static-answer triage tier at admission (runs on the
        HTTP thread — pure host work, microseconds warm): when the
        semantic screen proves NO detection module can fire on this
        code, the job settles DONE with an empty issue set before it
        ever reaches the queue. False keeps the job on the full
        wave/walk path; QueueRefusal propagates when draining."""
        from mythril_tpu.analysis.static import static_answer_enabled

        if not (self.cfg.static_answer and static_answer_enabled()):
            return False
        try:
            from mythril_tpu.analysis.static import summary_for

            summary = summary_for(job.code)
            if not summary.static_answerable:
                return False
        except Exception:
            log.debug("static triage failed; full path", exc_info=True)
            return False
        self.queue.register(job)  # raises QueueRefusal when draining
        self._c_static_answered.inc()
        observe.journey_event(
            job.journey_id, journey.TIER_STATIC_ANSWER, "screened-clean",
            wall_ms=summary.wall_ms,
        )
        now = time.monotonic()
        job.report = {
            "job_id": job.id,
            "journey_id": job.journey_id,
            "code_hash": CodeCache.code_hash(job.code),
            "static_answered": True,
            "issues": [],
            "static": {
                "modules_applicable": 0,
                "static_answerable": True,
                "wall_ms": summary.wall_ms,
            },
            "timings": {
                "queued_s": 0.0,
                "device_s": 0.0,
                "total_s": round(now - job.created_t, 6),
            },
        }
        self.queue.settle(job, JobState.DONE)
        self._routing_record(job, route="static-answer")
        return True

    def _try_routed_host(self, job: Job) -> bool:
        """The cost-model admission tier (mythril_tpu/routing): when
        the loaded router prices this submission cheaper on the host
        walk than on device waves, dispatch it STRAIGHT to the walk
        pool — registry-only admission, no queue slot, no wave, the
        arena stays free for wave-bound work. The walk runs clamped to
        the decision's predicted budget; an overrun or error promotes
        the job back onto the wave queue in `_finalize` (the routing
        record then settles as promoted-device-waves). False keeps the
        job on today's queue path — which is ALSO the answer whenever
        no router is loaded, the walk pool is saturated, or the model
        has no opinion, so router-off parity is structural."""
        if self._router is None or not self.cfg.host_walk:
            return False
        if job.host_walk is False or job.frontier is not None:
            return False
        # cap direct dispatches at the walk pool's width: past that
        # the queue's wave tier is the better wait anyway
        if len(self._host_inflight) >= max(1, self.cfg.host_workers):
            return False
        try:
            decision = self._router.decide(
                observe.routing_features_for(
                    job.code.hex(),
                    summary=self.code_cache.static_summary(job.code),
                ),
                tiers=["host-walk", "device-waves"],
            )
        except Exception:
            log.debug("route decision failed", exc_info=True)
            return False
        if decision is None or decision.route != "host-walk":
            return False
        self.queue.register(job)  # raises QueueRefusal when draining
        job.routed = "host-walk"
        job.route_budget_s = decision.budget_s()
        pair = decision.expected.get("host-walk")
        observe.journey_event(
            job.journey_id, journey.TIER_ADMISSION, "routed",
            route="host-walk",
            predicted_wall_s=round(pair[0], 4) if pair else None,
            budget_s=round(job.route_budget_s, 4),
        )
        now = time.monotonic()
        job.started_t = now
        job.device_done_t = now  # no device phase: host_s is the wall
        self.queue.mark(job, JobState.ANALYZING)
        # the injected-outcome shape the walk consumes (track.outcome's
        # empty case): a zeroed ExploreStats, no coverage, no triggers
        from mythril_tpu.laser.batch.explore import ExploreStats

        outcome = {
            "covered_branches": [],
            "corpus_size": 0,
            "triggers": {},
            "evidence": [],
            "device_complete": False,
            "completeness_gates": {},
            "degraded_lanes": 0,
            "stats": ExploreStats().as_dict(),
        }
        future = self._pool.submit(self._host_task, job, None, outcome)
        self._host_inflight[job.id] = (future, None, outcome)
        return True

    def _routing_record(self, job: Job, route: Optional[str] = None) -> None:
        """One routing-feature record per settled service job: the
        same features ⨝ route ⨝ outcome row the corpus driver emits,
        carrying the journey_id so the offline trainer joins the
        timeline too. Service traffic is training data — the cost
        model must see the cache economics of real request streams."""
        if not observe.enabled():
            return
        try:
            report = job.report or {}
            result = {
                "issues": report.get("issues") or [],
                "wall_s": (report.get("timings") or {}).get("total_s"),
                "error": job.error,
                "complete": job.error is None,
                "store_hit": route == "store-hit",
                "static_answered": route == "static-answer",
                "quarantined": route == "quarantined",
                # the router's own vocabulary (satellite 2): a routed
                # or promoted job settles as routed-<tier> /
                # promoted-<tier> so decisions feed their training set
                "routed": job.routed if route is None else None,
                "promoted": job.promoted if route is None else None,
            }
            # the store-hit/quarantine tiers settle in microseconds:
            # their records must not pay a CFG recovery for feature
            # columns
            summary = (
                False
                if route in ("store-hit", "quarantined")
                else self.code_cache.static_summary(job.code)
            )
            observe.routing_log().record(
                contract=f"job-{job.id}",
                code_hash=CodeCache.code_hash(job.code),
                features=observe.routing_features_for(
                    job.code.hex(), summary=summary
                ),
                outcome=observe.routing_outcome_for(result),
                journey_id=job.journey_id,
            )
        except Exception:
            log.debug("service routing record failed", exc_info=True)

    @property
    def draining(self) -> bool:
        return self._draining

    def export_frontiers(self) -> Dict:
        """The GET /v1/frontier/export payload: every non-terminal job
        with enough context to re-run on another replica — resident
        jobs carry their live track frontier (covered directions +
        corpus tail), queued jobs pass along whatever donor frontier
        they arrived with. The fleet front resubmits each doc to a
        survivor with the ORIGINAL idempotency key; the frontier seeds
        the survivor's track (Job.frontier) so exploration continues
        where this replica left off. Tracks are owned by the wave
        thread; by the time a front asks (the replica is draining) the
        loop is winding down, and a marginally stale frontier only
        costs re-exploration, never correctness."""
        docs = []
        for job in self.queue.nonterminal():
            track = self._tracks.get(job.id)
            try:
                frontier = (
                    track.export_frontier()
                    if track is not None
                    else dict(
                        job.frontier
                        or {"code_hex": job.code.hex()}
                    )
                )
            except Exception:
                log.warning(
                    "frontier export failed for job %s", job.id,
                    exc_info=True,
                )
                frontier = {"code_hex": job.code.hex()}
            docs.append({
                "job_id": job.id,
                "state": job.state,
                "code": job.code.hex(),
                "idempotency_key": job.idempotency_key,
                "params": {
                    "max_waves": job.max_waves,
                    "deadline_s": (
                        job.deadline.budget_s if job.deadline else None
                    ),
                    "host_walk": job.host_walk,
                    "lanes": job.lanes,
                },
                "frontier": frontier,
            })
        return {
            "schema_version": 1,
            "draining": self._draining,
            "jobs": docs,
        }

    def drain(self, timeout_s: float = 120.0) -> None:
        """The SIGTERM contract: refuse new work, finish the in-flight
        wave and the in-flight host analyses, checkpoint everything
        else to replayable npz. Idempotent."""
        with self._lock:
            if self._draining:
                self._drained.wait(timeout_s)
                return
            self._draining = True
        queued = self.queue.drain_remaining()
        self._stop.set()
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=timeout_s)
            if self._thread.is_alive():
                # a wedged device call: checkpoint from the host-side
                # track state anyway (it is no longer being mutated in
                # any way that matters — the wave will be re-run from
                # the checkpoint) and say so
                log.warning(
                    "drain: wave thread still busy after %.0fs; "
                    "checkpointing resident jobs from the last "
                    "harvested state", timeout_s,
                )
        # resident jobs: their next-wave frontier, seeded exactly as
        # the wave loop would have
        for track in list(self._tracks.values()):
            self._checkpoint_job(track.job, track)
            self.alloc.release(track.stripes)
        self._tracks.clear()
        # never-admitted jobs: their first-wave frontier
        for job in queued:
            self._checkpoint_job(job, None)
        # host pool: running analyses finish, queued ones cancel and
        # fall back to device-only reports (the device phase already
        # completed — its findings are not lost, the walk is skipped)
        self._pool.shutdown(wait=True, cancel_futures=True)
        for job_id, (future, track, outcome) in list(
            self._host_inflight.items()
        ):
            if future.cancelled():
                job = self.queue.get(job_id)
                if job is not None and not job.terminal:
                    job.degraded.append("interrupted")
                    self._finalize(job, track, outcome, host_result=None)
        self._host_inflight.clear()
        # the walks are over: the walker processes exit with the pool
        if self._walkers is not None:
            self._walkers.close()
        # in-flight kernel warmups: an XLA compile racing interpreter
        # teardown aborts the process (std::terminate), so the drain
        # waits them out (bounded — a compile is seconds, and no new
        # warmup launches once draining)
        for thread in self._warmup_threads:
            thread.join(timeout=60.0)
        # the final flight-recorder flush: the drained service leaves
        # its span timeline beside its checkpoints (Perfetto JSON), so
        # a post-mortem sees what the waves were doing at shutdown
        if observe.enabled():
            try:
                dump_dir = observe.out_dir() or self.checkpoint_dir()
                self.flight_dump_path = observe.export_trace(
                    os.path.join(dump_dir, "flight_recorder.trace.json")
                )
            except Exception:
                log.debug("drain flight-recorder flush failed",
                          exc_info=True)
        # release the saturation source if this engine still owns it
        # (tests run many engines; the sampler must not keep reading a
        # drained allocator as "the" arena)
        monitor = observe.device_monitor()
        if monitor._arena_source == self.alloc.occupancy:
            monitor.set_arena_source(None)
        # the journal's clean-shutdown marker: every accepted job is
        # terminal (completed or checkpointed) at this point, so a
        # recovery of this journal re-admits nothing and strikes nobody
        if self.journal is not None:
            self.journal.mark_drain()
            self.journal.close()
        self._drained.set()

    def close(self) -> None:
        self.drain()

    # -- admission + arena ---------------------------------------------
    def _rebuild_arena_rows(self) -> None:
        rows = self.cfg.stripes + 1  # + the halt row idle lanes run
        self._arena_ops = np.zeros((rows, self.code_cap + 33), np.uint8)
        self._arena_jd = np.zeros((rows, self.code_cap), bool)
        self._arena_len = np.zeros((rows,), np.int32)
        # per-row superblock fuse + block-program tables (specialize
        # .py / blockjit.py): the halt row stays all-zero (idle lanes
        # never fuse or block-step)
        self._arena_fuse = np.zeros((rows, self.code_cap), np.uint8)
        self._arena_block = np.zeros((rows, self.code_cap), np.uint8)
        self._table_dirty = True

    def _install_code(self, track: _JobTrack) -> None:
        ops_row, jd_row, length = self.code_cache.rows(track.job.code)
        self._arena_ops[track.code_row] = ops_row
        self._arena_jd[track.code_row] = jd_row
        self._arena_len[track.code_row] = length
        self._arena_fuse[track.code_row] = (
            track.spec["fuse_row"]
            if track.spec is not None
            else 0
        )
        block_row = (
            track.spec.get("block_row") if track.spec is not None else None
        )
        self._arena_block[track.code_row] = (
            block_row if block_row is not None else 0
        )
        self._table_dirty = True

    def _ensure_code_cap(self, code: bytes) -> None:
        from mythril_tpu.laser.batch.seeds import code_cap_bucket

        if len(code) <= self.code_cap:
            return
        self.code_cap = code_cap_bucket(len(code), floor=self.code_cap)
        self._c_rebuckets.inc()
        self.code_cache.rebucket(self.code_cap)
        self._rebuild_arena_rows()
        for resident in self._tracks.values():
            self._install_code(resident)
        log.info(
            "service arena re-bucketed code capacity to %d (recompile)",
            self.code_cap,
        )

    def _admit(self) -> None:
        """Between waves: pull queued jobs into free stripes (striped
        over the device groups least-loaded-first when --devices > 1),
        then rebalance residents onto any group the admissions left
        idle. A SUSPECT job (one quarantine strike — implicated in a
        wave fault or a crash) is only ever admitted into an EMPTY
        arena and blocks co-admissions while resident: its next fault
        must take down nobody else."""
        if any(
            self._is_suspect(CodeCache.code_hash(t.job.code))
            for t in self._tracks.values()
        ):
            return  # a solo wave is in progress; nobody rides along
        free = self.alloc.stripes - self.alloc.occupancy()["stripes_busy"]
        if free <= 0:
            return
        claimed = self.queue.claim(free)
        stop_at: Optional[int] = None
        for idx, job in enumerate(claimed):
            suspect = self._is_suspect(CodeCache.code_hash(job.code))
            if suspect and self._tracks:
                # the suspect waits for an empty arena
                stop_at = idx
                break
            n_stripes = self.alloc.stripes_needed(
                job.lanes or self.cfg.lanes_per_stripe
            )
            if n_stripes > self.alloc.stripes_per_group:
                # a job must fit ONE group: its wave is one dispatch
                n_stripes = self.alloc.stripes_per_group
            granted = self.alloc.allocate(job.id, n_stripes)
            if granted is None:
                stop_at = idx
                break
            self._ensure_code_cap(job.code)
            lanes = [
                lane for s in granted for lane in self.alloc.lanes_of(s)
            ]
            track = _JobTrack(
                job, granted, lanes, self.cfg.calldata_len,
                static_feed=self.code_cache.static_summary(job.code),
                spec_feed=(
                    self.code_cache.spec_for(job.code)
                    if self.cfg.specialize
                    else None
                ),
            )
            self._c_static_seeds.inc(track.static_seeds_dropped)
            self._install_code(track)
            self._tracks[job.id] = track
            observe.journey_event(
                job.journey_id, journey.TIER_LANE_GRANT, "granted",
                stripes=len(granted), lanes=len(lanes),
                group=self.alloc.group_of(granted[0]), solo=suspect,
            )
            if suspect:
                # a solo wave: admit nobody else alongside
                stop_at = idx + 1
                break
        if stop_at is not None:
            # hand unplaced claims back in reverse so the queue keeps
            # its FIFO order (unclaim inserts at the head)
            for job in reversed(claimed[stop_at:]):
                if job.id not in self._tracks:
                    self.queue.unclaim(job)
        if self.mesh is not None:
            self._rebalance()

    def _rebalance(self) -> None:
        """Live mesh balancing: a device group left with NO resident
        job — while another group carries two or more — steals the
        loaded group's newest job at the wave boundary. The move is a
        host handoff (release stripes, re-grant in the idle group,
        reinstall the code row); the job's corpus and coverage ride
        its track untouched, and in-flight waves are safe because
        dispatch records snapshot each job's lanes."""
        occ = self.alloc.occupancy()["groups"]
        idle = [g["group"] for g in occ if g["jobs_resident"] == 0]
        if not idle:
            return
        for target in idle:
            victim_group = max(occ, key=lambda g: g["jobs_resident"])
            if victim_group["jobs_resident"] < 2:
                return
            jobs = self.alloc.jobs_in_group(victim_group["group"])
            track = self._tracks.get(jobs[-1]) if jobs else None
            if track is None:
                return
            old = track.stripes
            granted = self.alloc.allocate(
                track.job.id, len(old), group=target
            )
            if granted is None:
                return
            self.alloc.release(old)
            track.stripes = granted
            track.code_row = granted[0]
            track.lanes = [
                lane
                for s in granted
                for lane in self.alloc.lanes_of(s)
            ]
            self._install_code(track)
            self._c_mesh_steals.inc()
            self._c_mesh_rebalance.inc(
                len(track.job.code)
                + sum(len(c) for c in track.corpus)
            )
            log.info(
                "mesh rebalance: job %s moved group %d -> %d",
                track.job.id,
                victim_group["group"],
                target,
            )
            occ = self.alloc.occupancy()["groups"]

    def _table(self, device=None):
        import jax.numpy as jnp

        from mythril_tpu.laser.batch.state import CodeTable

        if self._table_dirty or self._code_table is None:
            self._code_table = CodeTable(
                jnp.asarray(self._arena_ops),
                jnp.asarray(self._arena_jd),
                jnp.asarray(self._arena_len),
            )
            self._fuse_table = jnp.asarray(self._arena_fuse)
            self._block_table = jnp.asarray(self._arena_block)
            self._table_dirty = False
            self._group_tables.clear()
            self._group_fuse.clear()
            self._group_block.clear()
        if device is None:
            return self._code_table
        # per-group replica: a group's wave must find its table on its
        # OWN device — mixed-device jit inputs are an error, and the
        # replica is what makes the group's arena self-contained
        cached = self._group_tables.get(device)
        if cached is None:
            import jax

            cached = jax.device_put(self._code_table, device)
            self._group_tables[device] = cached
        return cached

    def _fuse(self, device=None):
        """The fuse table matching `_table()` (same dirty lifecycle;
        `_table()` must have been called first this wave)."""
        if device is None:
            return self._fuse_table
        cached = self._group_fuse.get(device)
        if cached is None:
            import jax

            cached = jax.device_put(self._fuse_table, device)
            self._group_fuse[device] = cached
        return cached

    def _block(self, device=None):
        """The block-program table matching `_table()` (same dirty
        lifecycle) — the substep table of a blockjit bucket."""
        if device is None:
            return self._block_table
        cached = self._group_block.get(device)
        if cached is None:
            import jax

            cached = jax.device_put(self._block_table, device)
            self._group_block[device] = cached
        return cached

    def _substep_table(self, phases, device=None):
        """The substep table matching a wave bucket: the block-program
        rows for a blockjit bucket, the superblock fuse rows
        otherwise."""
        if phases is not None and phases.block_depth > 0:
            return self._block(device)
        return self._fuse(device)

    def _donation_ok(self) -> bool:
        """Buffer donation: a seeded wave batch is never read again on
        the host (retries rebuild it from `calldata`), so the device
        reuses its buffers for the output. CPU ignores donation with a
        warning, so it is gated to accelerators."""
        import jax

        return jax.default_backend() != "cpu"

    def _wave_kernel(self, job_ids, batch, table, donate) -> Optional[Tuple]:
        """(kernel, phases) for this wave, or None for a generic wave.

        The bucket is the engine's MONOTONE union over every admitted
        job's phases: residency churn (jobs finishing, new mixes)
        never narrows it, so the compile count is bounded by the phase
        flags, not by residency patterns. A bucket whose executable is
        not yet warm for this dispatch shape is handled per
        `specialize_warmup`: "background" runs THIS wave generic and
        compiles off the serving path; "sync" compiles on the wave.
        Any resident job without a specialization feed makes the wave
        generic (the striped dispatch is one kernel)."""
        if not self.cfg.specialize:
            return None
        from mythril_tpu.laser.batch import specialize as _spec

        if not _spec.specialize_enabled():
            return None
        if not self._breaker_allow("kernel"):
            # the kernel-compile breaker is open: the specialized tier
            # is routed around — every wave runs the (already-warm)
            # generic interpreter until the half-open probe recovers
            return None
        feeds = []
        for jid in job_ids:
            track = self._tracks.get(jid)
            if track is None or track.spec is None:
                return None
            feeds.append(track.spec["phases"])
        if not feeds:
            return None
        if self._union_phases is not None:
            feeds.append(self._union_phases)
        self._union_phases = _spec.union_phases(feeds)
        kernel = _spec.kernel_cache().get(self._union_phases)
        key = kernel.run_key(batch, table, donate)
        if kernel.is_warm(key):
            return kernel, self._union_phases
        if self.cfg.specialize_warmup == "sync":
            return kernel, self._union_phases
        self._warm_kernel_async(kernel, key, batch, table, donate)
        return None

    def _warm_kernel_async(self, kernel, key, batch, table, donate) -> None:
        """Compile the bucket for this dispatch shape OFF the serving
        path: a daemon thread runs the kernel once over a dummy batch
        of the same shape (all lanes halt on the empty halt row after
        one step, so the warmup's execution cost is one step — its
        wall is the compile). At most one warmup per (bucket, shape)."""
        import jax.numpy as jnp

        from mythril_tpu.laser.batch.state import make_batch

        warm_id = (kernel.phases, key)
        with self._lock:
            if self._draining or warm_id in self._kernel_warming:
                return
            self._kernel_warming.add(warm_id)
        n = batch.pc.shape[0]
        fuse = (
            self._block_table
            if kernel.phases.block_depth > 0
            else self._fuse_table
        )
        steps = self.cfg.steps_per_wave
        # Warmup-pin the kernel so a capacity eviction racing this
        # thread cannot drop() executables mid-compile: eviction may
        # still unmap the bucket (counted inflight), but the discard is
        # deferred to release_warmup below — deterministic either way.
        from mythril_tpu.laser.batch import specialize as _spec

        _spec.kernel_cache().pin_warmup(kernel)

        def _warm():
            try:
                dummy = make_batch(
                    n,
                    code_ids=np.full((n,), self.cfg.stripes, np.int32),
                    mem_cap=batch.mem.shape[1],
                    stack_cap=batch.stack.shape[1],
                )
                out = kernel.run(
                    dummy, table, fuse, max_steps=steps,
                    track_coverage=True, donate=donate,
                )
                jnp.asarray(out[1]).block_until_ready()
            except Exception:
                log.debug("kernel warmup failed", exc_info=True)
            finally:
                _spec.kernel_cache().release_warmup(kernel)

        thread = threading.Thread(
            target=_warm, name="myth-kernel-warmup", daemon=True
        )
        self._warmup_threads.append(thread)
        thread.start()

    # -- the wave loop -------------------------------------------------
    def _loop(self) -> None:
        """Pipelined: dispatch wave N+1 (seeded from corpora known
        before wave N's results — the service's mutation seeding never
        needed the in-flight wave's outcome) BEFORE harvesting wave N,
        so the device executes N+1 while the host reads back and
        consumes N and admits new jobs into freed stripes. With
        `pipeline` off, each wave is dispatched and harvested
        lock-step (the old schedule)."""
        inflight: Optional[Dict] = None
        while not self._stop.is_set():
            try:
                nxt = self._dispatch_wave()
            except Exception:
                log.exception("service wave dispatch fault")
                nxt = None
            if inflight is not None:
                if nxt is not None:
                    self._c_overlapped.inc()
                    jobs = set(inflight["wave_inputs"]) | set(
                        nxt["wave_inputs"]
                    )
                    if len(jobs) > 1:
                        # the two pipeline slots hold waves spanning
                        # more than one job
                        self._c_multi_job.inc()
                try:
                    self._harvest_wave(inflight)
                except Exception:
                    log.exception("service wave loop fault; jobs failed")
                inflight = None
                self._g_inflight.set(0)
            if nxt is not None:
                if self.pipeline_enabled:
                    inflight = nxt
                    self._g_inflight.set(1)
                else:
                    try:
                        self._harvest_wave(nxt)
                    except Exception:
                        log.exception("service wave loop fault; jobs failed")
            elif inflight is None:
                self._wake.wait(self.cfg.idle_wait_s)
                self._wake.clear()
        if inflight is not None:
            # the drain contract: the in-flight wave is finished, its
            # jobs harvested, before checkpoints are cut
            try:
                self._harvest_wave(inflight)
            except Exception:
                log.exception("drain harvest of the in-flight wave failed")
            self._g_inflight.set(0)

    @property
    def pipeline_enabled(self) -> bool:
        return bool(getattr(self.cfg, "pipeline", True))

    def _dispatch_wave(self) -> Optional[Dict]:
        """Admit queued jobs, seed every resident job's lanes, and
        dispatch the wave ASYNCHRONOUSLY (no block): returns the
        in-flight record the harvest half consumes. The host-side
        inputs ride the record so a faulted dispatch can be rebuilt
        and retried through the synchronous resilience ladder."""
        from mythril_tpu.laser.batch.run import wave_run
        from mythril_tpu.laser.batch.state import make_batch
        from mythril_tpu.support import resilience

        if not self._tracks and self.queue.depth():
            # the coalesce window: near-simultaneous submissions share
            # the first wave instead of serializing behind it
            time.sleep(self.cfg.coalesce_wait_s)
        self._admit()
        if not self._tracks:
            return None
        if not self._breaker_allow("device"):
            # the device-tier breaker is OPEN: route every resident
            # job's device phase straight down the ladder to the host
            # walk — zero doomed dispatches, zero per-job retry cost.
            # The half-open probe (after recovery_s) re-enters the
            # normal dispatch below and its outcome moves the breaker.
            for track in list(self._tracks.values()):
                del self._tracks[track.job.id]
                self.alloc.release(track.stripes)
                track.job.device_done_t = time.monotonic()
                track.job.degraded.append("breaker-open:device")
                observe.journey_event(
                    track.job.journey_id, journey.TIER_WAVE,
                    "breaker-skip",
                )
                self._dispatch_host(track)
            return None
        halt_row = self.cfg.stripes
        n = self.alloc.n_lanes
        code_ids = np.full((n,), halt_row, np.int32)
        calldata: List[bytes] = [b""] * n
        wave_inputs: Dict[str, List[bytes]] = {}
        for track in self._tracks.values():
            inputs = track.next_inputs()
            wave_inputs[track.job.id] = inputs
            observe.journey_event(
                track.job.journey_id, journey.TIER_WAVE, "dispatch",
                wave=track.waves_done + 1,
            )
            for lane, data in zip(track.lanes, inputs):
                code_ids[lane] = track.code_row
                calldata[lane] = data
        if self.journal is not None:
            # WAL ordering: the intent record lands before the device
            # does anything — a crash during this wave implicates
            # exactly these jobs at recovery
            self.journal.wave_dispatched(list(wave_inputs))
        if self.mesh is not None:
            return self._dispatch_wave_mesh(code_ids, calldata, wave_inputs)
        batch = make_batch(
            n,
            code_ids=code_ids,
            calldata=calldata,
            caller=DEFAULT_CALLER,
            address=DEFAULT_ADDRESS,
            timestamp=0x5BFA4639,
            number=0x66E393,
            gasprice=0x773594000,
        )
        record: Dict = {
            "wave_inputs": wave_inputs,
            "code_ids": code_ids,
            "calldata": calldata,
            "out": None,
            "steps": None,
            "fused": None,
            "blocks": None,
            "spec": False,
            "failed": None,
            "t0": time.perf_counter(),
        }
        try:
            resilience.inject("service.dispatch")
            with trace(
                "service.wave.dispatch", track="service",
                jobs=len(wave_inputs),
            ):
                donate = self._donation_ok()
                table = self._table()
                spec = self._wave_kernel(wave_inputs, batch, table, donate)
                if spec is not None:
                    kernel, _phases = spec
                    record["spec"] = True
                    self._c_spec_waves.inc()
                    (
                        record["out"], record["steps"], record["fused"],
                        record["blocks"],
                    ) = kernel.run(
                        batch,
                        table,
                        self._substep_table(_phases),
                        max_steps=self.cfg.steps_per_wave,
                        track_coverage=True,
                        donate=donate,
                    )
                else:
                    self._c_generic_waves.inc()
                    record["out"], record["steps"] = wave_run(
                        batch,
                        table,
                        max_steps=self.cfg.steps_per_wave,
                        track_coverage=True,
                        donate=donate,
                    )
        except Exception as why:
            if not resilience.is_device_fault(why):
                raise
            record["failed"] = why
        return record

    def _dispatch_wave_mesh(
        self, code_ids, calldata, wave_inputs: Dict
    ) -> Dict:
        """The --devices N dispatch: one wave PER DEVICE GROUP, each
        over its own contiguous lane block with its own table replica,
        launched asynchronously back-to-back so the groups execute
        concurrently. Groups with no resident job skip their dispatch
        entirely (an idle group burns nothing — and is exactly the
        group _rebalance feeds next)."""
        import jax

        from mythril_tpu.laser.batch.run import wave_run
        from mythril_tpu.laser.batch.state import make_batch
        from mythril_tpu.support import resilience

        donate = self._donation_ok()
        record: Dict = {
            "wave_inputs": wave_inputs,
            "code_ids": code_ids,
            "calldata": calldata,
            "lanes_by_job": {
                jid: list(self._tracks[jid].lanes)
                for jid in wave_inputs
                if jid in self._tracks
            },
            "group_by_job": {
                jid: self.alloc.group_of(self._tracks[jid].stripes[0])
                for jid in wave_inputs
                if jid in self._tracks
            },
            "groups": [],
            "t0": time.perf_counter(),
        }
        live_groups = set(record["group_by_job"].values())
        span = self.alloc.lanes_per_group
        for group in self.mesh.groups:
            if group.gid not in live_groups:
                continue
            lo = group.gid * span
            hi = lo + span
            batch = make_batch(
                span,
                code_ids=code_ids[lo:hi],
                calldata=calldata[lo:hi],
                caller=DEFAULT_CALLER,
                address=DEFAULT_ADDRESS,
                timestamp=0x5BFA4639,
                number=0x66E393,
                gasprice=0x773594000,
            )
            device = group.devices[0]
            batch = jax.device_put(batch, device)
            grec = {
                "gid": group.gid,
                "device": device,
                "lo": lo,
                "hi": hi,
                "out": None,
                "steps": None,
                "fused": None,
                "blocks": None,
                "spec": False,
                "failed": None,
            }
            # per-group kernel selection: the union bucket over THIS
            # group's resident jobs only (another group's keccak does
            # not widen this group's kernel)
            group_jobs = [
                jid
                for jid, gid in record["group_by_job"].items()
                if gid == group.gid
            ]
            try:
                resilience.inject("service.dispatch")
                table = self._table(device)
                spec = self._wave_kernel(group_jobs, batch, table, donate)
                if spec is not None:
                    kernel, _phases = spec
                    self._c_spec_waves.inc()
                    grec["spec"] = True
                    (
                        grec["out"], grec["steps"], grec["fused"],
                        grec["blocks"],
                    ) = kernel.run(
                        batch,
                        table,
                        self._substep_table(_phases, device),
                        max_steps=self.cfg.steps_per_wave,
                        track_coverage=True,
                        donate=donate,
                    )
                else:
                    self._c_generic_waves.inc()
                    grec["out"], grec["steps"] = wave_run(
                        batch,
                        table,
                        max_steps=self.cfg.steps_per_wave,
                        track_coverage=True,
                        donate=donate,
                    )
            except Exception as why:
                if not resilience.is_device_fault(why):
                    raise
                grec["failed"] = why
            record["groups"].append(grec)
            self._c_group_waves.labels(engine=self._eid, group=str(group.gid)).inc()
        return record

    def _rebuild_batch(self, record: Dict, lo: int = 0, hi=None):
        from mythril_tpu.laser.batch.state import make_batch

        hi = self.alloc.n_lanes if hi is None else hi
        return make_batch(
            hi - lo,
            code_ids=record["code_ids"][lo:hi],
            calldata=record["calldata"][lo:hi],
            caller=DEFAULT_CALLER,
            address=DEFAULT_ADDRESS,
            timestamp=0x5BFA4639,
            number=0x66E393,
            gasprice=0x773594000,
        )

    def _note_wave_timing(self, wall: float) -> None:
        now = time.monotonic()
        self._c_waves.inc()
        if self._first_wave_t is None:
            self._first_wave_t = now
            self._wave_cold_s = wall
        else:
            ema = self._wave_warm_ema_s
            self._wave_warm_ema_s = (
                wall if ema is None else 0.8 * ema + 0.2 * wall
            )
        self._last_wave_t = now

    def _job_wave_done(self, track: _JobTrack) -> bool:
        """Post-harvest settlement shared by the single-arena and mesh
        paths: deadline expiry, wave cap, staleness."""
        track.job.waves = track.waves_done
        observe.journey_event(
            track.job.journey_id, journey.TIER_WAVE, "harvest",
            wave=track.waves_done,
            covered_branches=len(track.covered),
            stale_waves=track.stale_waves,
        )
        max_waves = track.job.max_waves or self.cfg.max_waves
        expired = (
            track.job.deadline is not None and track.job.deadline.expired
        )
        if expired:
            from mythril_tpu.support.resilience import (
                DegradationLog,
                DegradationReason,
            )

            track.job.degraded.append(DegradationReason.DEADLINE_EXPIRED)
            DegradationLog().record(
                DegradationReason.DEADLINE_EXPIRED,
                site="service-wave",
                contract=track.job.id,
            )
        return bool(
            expired
            or track.waves_done >= max_waves
            or track.stale_waves >= 2
        )

    def _harvest_wave(self, record: Dict) -> None:
        import jax

        from mythril_tpu.laser.batch.run import run_resilient
        from mythril_tpu.support import resilience

        if record.get("groups") is not None:
            return self._harvest_wave_mesh(record)
        try:
            resilience.inject("service.harvest")
            if record["failed"] is not None:
                raise record["failed"]
            # asynchronous XLA faults surface HERE, attributed to the
            # wave in this record, not to whatever the host was doing
            with trace("service.wave.harvest", track="service"):
                jax.block_until_ready(record["steps"])
            # the retrospective device-execution span (dispatch ->
            # readback-ready): the service's Perfetto track
            flight_recorder().add(
                "wave.device",
                record["t0"],
                time.perf_counter(),
                track="service",
                jobs=len(record["wave_inputs"]),
            )
            out, steps = record["out"], record["steps"]
            if record.get("fused") is not None:
                self._c_fused.inc(int(record["fused"]))
            if record.get("blocks") is not None:
                self._c_blocks.inc(int(record["blocks"]))
            self._breaker_record("device", True)
        except Exception as why:
            if not resilience.is_device_fault(why):
                raise
            self._breaker_record("device", False, str(why))
            resilience.DegradationLog().record(
                resilience.DegradationReason.ASYNC_DEVICE_FAULT,
                site="service-wave",
                detail=str(why),
            )
            if record.get("spec"):
                # the retry ladder always re-dispatches GENERIC: a
                # specialized lowering must not be retried into itself
                self._c_fallbacks.inc()
            try:
                out, steps = run_resilient(
                    self._rebuild_batch(record),
                    self._table(),
                    max_steps=self.cfg.steps_per_wave,
                    track_coverage=True,
                )
            except Exception as ladder_why:
                self._fail_wave(ladder_why)
                return
        wave_inputs = record["wave_inputs"]
        self._note_wave_timing(time.perf_counter() - record["t0"])
        status, halt_pc, gas_min, gas_max, br_pc, br_taken, br_cnt, seen = (
            jax.device_get(
                (
                    out.status, out.pc, out.gas_min, out.gas_max,
                    out.br_pc, out.br_taken, out.br_cnt, out.pc_seen,
                )
            )
        )
        steps = int(steps)
        self._c_device_steps.inc(steps * self.alloc.n_lanes)
        finished: List[_JobTrack] = []
        for track in list(self._tracks.values()):
            if track.job.id not in wave_inputs:
                # admitted AFTER this wave dispatched (pipelined): its
                # first wave is the one still in flight
                continue
            track.harvest(
                wave_inputs[track.job.id], status, halt_pc, gas_min,
                gas_max, br_pc, br_taken, br_cnt, seen, steps,
            )
            if self._job_wave_done(track):
                finished.append(track)
        for track in finished:
            del self._tracks[track.job.id]
            self.alloc.release(track.stripes)
            track.job.device_done_t = time.monotonic()
            self._dispatch_host(track)

    def _harvest_wave_mesh(self, record: Dict) -> None:
        """Harvest every group's wave of one mesh dispatch. Each group
        is its own failure domain: a group whose readback faults past
        the resilience ladder fails ONLY the jobs resident in it (the
        DegradationLog attributes the group), while the other groups'
        results harvest normally."""
        import jax

        from mythril_tpu.laser.batch.run import run_resilient
        from mythril_tpu.support import resilience

        n = self.alloc.n_lanes
        fields = None
        steps_by_group: Dict[int, int] = {}
        failed_groups = set()
        for grec in record["groups"]:
            gid = grec["gid"]
            try:
                resilience.inject("service.harvest")
                if grec["failed"] is not None:
                    raise grec["failed"]
                jax.block_until_ready(grec["steps"])
                out, steps = grec["out"], grec["steps"]
                if grec.get("fused") is not None:
                    self._c_fused.inc(int(grec["fused"]))
                if grec.get("blocks") is not None:
                    self._c_blocks.inc(int(grec["blocks"]))
                self._breaker_record("device", True)
            except Exception as why:
                if not resilience.is_device_fault(why):
                    raise
                self._breaker_record("device", False, str(why))
                resilience.DegradationLog().record(
                    resilience.DegradationReason.ASYNC_DEVICE_FAULT,
                    site=f"service-wave/mesh-g{gid}",
                    detail=str(why),
                )
                if grec.get("spec"):
                    self._c_fallbacks.inc()
                try:
                    out, steps = run_resilient(
                        jax.device_put(
                            self._rebuild_batch(
                                record, grec["lo"], grec["hi"]
                            ),
                            grec["device"],
                        ),
                        self._table(grec["device"]),
                        max_steps=self.cfg.steps_per_wave,
                        track_coverage=True,
                    )
                except Exception as ladder_why:
                    self._fail_group_jobs(gid, ladder_why, record)
                    failed_groups.add(gid)
                    continue
            arrays = jax.device_get(
                (
                    out.status, out.pc, out.gas_min, out.gas_max,
                    out.br_pc, out.br_taken, out.br_cnt, out.pc_seen,
                )
            )
            if fields is None:
                fields = [
                    np.zeros((n,) + a.shape[1:], a.dtype) for a in arrays
                ]
            for full, part in zip(fields, arrays):
                full[grec["lo"] : grec["hi"]] = part
            steps_by_group[gid] = int(steps)
            self._c_device_steps.inc(int(steps) * (grec["hi"] - grec["lo"]))
        self._note_wave_timing(time.perf_counter() - record["t0"])
        if fields is None:
            return  # every live group failed; jobs already settled
        status, halt_pc, gas_min, gas_max, br_pc, br_taken, br_cnt, seen = (
            fields
        )
        finished: List[_JobTrack] = []
        for track in list(self._tracks.values()):
            jid = track.job.id
            if jid not in record["wave_inputs"]:
                continue
            gid = record["group_by_job"].get(jid)
            if gid is None or gid in failed_groups:
                continue
            track.harvest(
                record["wave_inputs"][jid], status, halt_pc, gas_min,
                gas_max, br_pc, br_taken, br_cnt, seen,
                steps_by_group.get(gid, 0),
                lanes=record["lanes_by_job"][jid],
            )
            if self._job_wave_done(track):
                finished.append(track)
        for track in finished:
            del self._tracks[track.job.id]
            self.alloc.release(track.stripes)
            track.job.device_done_t = time.monotonic()
            self._dispatch_host(track)

    def _fail_group_jobs(
        self, gid: int, why: Exception, record: Dict
    ) -> None:
        """One device group's wave died past run_resilient's whole
        ladder: fail THAT group's resident jobs, attribute the group,
        and leave every other group — and the service — running."""
        jobs = [
            jid
            for jid, job_gid in record["group_by_job"].items()
            if job_gid == gid and jid in self._tracks
        ]
        self.mesh.group(gid).failure_domain.record_degraded(
            len(jobs), detail=f"service wave failed: {why}"
        )
        for jid in jobs:
            track = self._tracks.pop(jid)
            self.alloc.release(track.stripes)
            track.job.error = f"device wave failed in mesh-g{gid}: {why}"
            self._fail_with_strike(track.job)

    def _fail_wave(self, why: Exception) -> None:
        """A wave died past run_resilient's whole escalation ladder:
        fail the resident jobs with the fault recorded — the service
        itself stays up for the next request."""
        from mythril_tpu.support.resilience import (
            DegradationLog,
            DegradationReason,
        )

        DegradationLog().record(
            DegradationReason.WAVE_ABANDONED,
            site="service-wave",
            detail=str(why),
        )
        for track in list(self._tracks.values()):
            del self._tracks[track.job.id]
            self.alloc.release(track.stripes)
            track.job.error = f"device wave failed: {why}"
            self._fail_with_strike(track.job)

    def _fail_with_strike(self, job: Job) -> None:
        """Settle a wave-faulted job FAILED with quarantine
        attribution: every job resident in the dead wave takes a
        strike (a poison contract and its innocent neighbors are
        indistinguishable HERE — the solo-wave isolation on the next
        submission is what tells them apart: innocents pass their solo
        wave and the strike clears; the poison faults again and
        quarantines)."""
        code_hash = CodeCache.code_hash(job.code)
        strikes = self._strike(code_hash)
        if strikes >= self.cfg.quarantine_strikes:
            self._quarantine_job(job, code_hash)
            return
        self.queue.settle(job, JobState.FAILED)

    # -- host phase ----------------------------------------------------
    def _dispatch_host(self, track: _JobTrack) -> None:
        job = track.job
        outcome = track.outcome()
        host_walk = (
            self.cfg.host_walk if job.host_walk is None else job.host_walk
        )
        if not host_walk:
            self._finalize(job, track, outcome, host_result=None)
            return
        self.queue.mark(job, JobState.ANALYZING)
        future = self._pool.submit(self._host_task, job, track, outcome)
        self._host_inflight[job.id] = (future, track, outcome)

    def _host_task(self, job: Job, track: _JobTrack, outcome: Dict) -> None:
        from mythril_tpu.analysis import corpus
        from mythril_tpu.support.host_lock import HOST_SYMBOLIC_LOCK

        timeout = self.cfg.execution_timeout
        if job.deadline is not None:
            timeout = max(1, min(timeout, int(job.deadline.remaining)))
        if track is None and job.routed and not job.promoted \
                and job.route_budget_s:
            # routed walk: clamp to the decision's budget, so a
            # mis-route pays at most the predicted wall (plus slack)
            # before `_finalize` promotes it onto the wave queue
            timeout = max(1, min(timeout, int(job.route_budget_s + 0.999)))
        payload = (
            job.code.hex(),
            "",
            f"job-{job.id}",
            DEFAULT_ADDRESS,
            "bfs",
            self.cfg.transaction_count,
            timeout,
            self.cfg.create_timeout,
            128,  # max_depth
            3,  # loop_bound
            None,  # modules
            None,  # solver_timeout
            False,  # use_device: the arena is the wave thread's
            outcome,
            None,  # deterministic_solving
        )
        observe.journey_event(
            job.journey_id, journey.TIER_HOST_WALK, "start",
            timeout_s=timeout,
        )
        # host symbolic state (term arena, CDCL session) is
        # process-global: a walk waits for a free walker process, or
        # in-process walks serialize on the lock. The wait is a span of
        # its own and `locked` ends it on the journey; `done` is
        # recorded before the release, so a walk's locked to done holds
        # its own work and nothing else
        walkers = self._walkers
        walker = None
        with trace("service.host.lock_wait", track="service", job=job.id):
            if walkers is None:
                HOST_SYMBOLIC_LOCK.acquire()
            else:
                walker = walkers.acquire()
        try:
            observe.journey_event(
                job.journey_id, journey.TIER_HOST_WALK, "locked",
                **({} if walker is None else {"walker": walker.index}),
            )
            solver_before = observe.solver_marker() if walker is None else None
            try:
                with trace(
                    "service.host.walk", track="service", job=job.id
                ), corpus.walker_bound(walker):
                    result = corpus.analyze_one_payload(payload)
            except CancelledError:
                raise
            except Exception as why:  # analyze_one_payload already catches;
                result = {"issues": [], "states": 0, "error": str(why)}
            self._walk_done(job, result, solver_before)
        finally:
            if walker is None:
                HOST_SYMBOLIC_LOCK.release()
            else:
                walkers.release(walker)
        self._c_host_walks.labels(
            engine=self._eid,
            walker="inproc" if walker is None else "process",
        ).inc()
        self._host_inflight.pop(job.id, None)
        self._c_host_completed.inc()
        self._finalize(job, track, outcome, host_result=result)

    def _walk_done(self, job: Job, result: Dict, solver_before) -> None:
        """The walk's journey events. The solver attribution and the
        phase split are this walk's alone: a walker process hands back
        its walk's registry growth (`telemetry`), folded here into this
        process's registry; an in-process walk holds
        HOST_SYMBOLIC_LOCK, and its delta since `solver_before` is its
        own (device-only waves run no solver or LASER step). The ladder
        hops (device-first vs CDCL) land as one solver-tier event;
        `done` carries the budget cut, the split and the walking pid."""
        grown = result.pop("telemetry", None)
        if grown is not None:
            observe.registry().fold(grown)
        try:
            attribution = (
                observe.solver_attribution(solver_before)
                if grown is None
                else observe.solver_attribution(growth=grown)
            )
            if attribution:
                observe.journey_event(
                    job.journey_id, journey.TIER_SOLVER, "escalations",
                    **{
                        origin: row["queries"]
                        for origin, row in attribution.items()
                    },
                )
        except Exception:
            log.debug("journey solver attribution failed", exc_info=True)
        phases = result.get("phases") or {}

        def phase(name: str, field: str = "wall_s"):
            return phases.get(name, {}).get(field, 0)

        observe.journey_event(
            job.journey_id, journey.TIER_HOST_WALK, "done",
            issues=len(result.get("issues") or ()),
            states=result.get("states", 0),
            cut=bool(result.get("cut")),
            cut_budget=result.get("cut"),
            step_s=phase("step"),
            feasibility_s=phase("feasibility"),
            solve_s=phase("solve"),
            solve_n=phase("solve", "count"),
            concretize_s=phase("concretize"),
            pid=result.get("pid", os.getpid()),
        )

    def _finalize(
        self, job: Job, track: Optional[_JobTrack], outcome: Dict,
        host_result: Optional[Dict],
    ) -> None:
        now = time.monotonic()
        # in-flight promotion (mythril_tpu/routing): a router-dispatched
        # walk that errored or burned its whole clamped budget was
        # mis-routed — instead of settling a truncated result, the job
        # goes to the HEAD of the wave queue for the device tier it
        # was denied. One promotion max (job.promoted latches), and the
        # regret — wall burnt beyond the predicted budget — is counted.
        if (
            track is None
            and job.routed
            and not job.promoted
            and self._router is not None
            and host_result is not None
            and not self.queue.draining
            and (job.deadline is None or job.deadline.remaining > 1.0)
        ):
            wall = now - (job.started_t or job.created_t)
            clamp = int((job.route_budget_s or 0) + 0.999)
            if host_result.get("error") is not None or (
                clamp and wall >= clamp - 0.05
            ):
                job.promoted = "device-waves"
                job.error = None
                self._router.note_promotion("host-walk", "device-waves")
                if job.route_budget_s and wall > job.route_budget_s:
                    self._router.note_regret(wall - job.route_budget_s)
                observe.journey_event(
                    job.journey_id, journey.TIER_ADMISSION, "promoted",
                    route="device-waves", walk_wall_s=round(wall, 4),
                )
                job.device_done_t = None
                self.queue.unclaim(job)
                self._wake.set()
                return
        device_s = (
            (job.device_done_t or now) - (job.started_t or job.created_t)
        )
        report = {
            "job_id": job.id,
            "journey_id": job.journey_id,
            "code_hash": CodeCache.code_hash(job.code),
            "device": {
                "waves": outcome["stats"]["waves"],
                "lane_steps": outcome["stats"]["device_steps"],
                "covered_branches": len(outcome["covered_branches"]),
                "covered_pc_bits": (
                    track.covered_pc_bits() if track is not None else 0
                ),
                "triggers": {
                    kind: len(bucket)
                    for kind, bucket in outcome["triggers"].items()
                },
                "degraded_lanes": outcome["degraded_lanes"],
                "static_pruned_seeds": (
                    track.static_seeds_dropped if track is not None else 0
                ),
            },
            "issues": [],
            "timings": {
                "queued_s": round(
                    (job.started_t or now) - job.created_t, 3
                ),
                "device_s": round(device_s, 3),
            },
        }
        state = JobState.DONE
        if host_result is not None:
            report["issues"] = host_result.get("issues", [])
            report["host"] = {
                "states": host_result.get("states", 0),
                "error": host_result.get("error"),
            }
            report["timings"]["host_s"] = round(
                now - (job.device_done_t or now), 3
            )
            if host_result.get("error"):
                job.error = host_result["error"]
                state = JobState.FAILED
        if job.degraded:
            report["degraded"] = list(job.degraded)
        report["timings"]["total_s"] = round(now - job.created_t, 3)
        job.report = report
        # the routing record lands BEFORE the settle wakes long-poll
        # waiters: a client that sees the terminal state must find the
        # record (and its journey_id) already in the JSONL
        self._routing_record(job)
        self.queue.settle(job, state)
        if state == JobState.DONE:
            # a clean completion clears any quarantine strikes: an
            # innocent job implicated in a shared-wave fault proves
            # itself by passing its solo wave
            self._strikes.pop(CodeCache.code_hash(job.code), None)
            self._store_writeback(job, report, outcome)

    def _store_writeback(
        self, job: Job, report: Dict, outcome: Dict
    ) -> None:
        """Tier 3: a job that completed its host walk cleanly (no
        error, no degradation) banks its verdict + the wave phase's
        evidence for future admissions. Device-only reports (host walk
        off) are NOT banked — the store must never serve a weaker
        verdict than a full analysis would compute."""
        if self.vstore is None or report.get("host") is None:
            return
        if report["host"].get("error") or job.degraded:
            return
        try:
            from mythril_tpu.store import (
                banks_from_outcome,
                provenance,
                static_export,
            )

            summary = self.code_cache.static_summary(job.code)
            self.vstore.put(
                CodeCache.code_hash(job.code),
                self._config_fp,
                issues=report.get("issues") or [],
                static=static_export(summary),
                banks=banks_from_outcome(outcome),
                provenance=provenance(
                    wall_s=report["timings"].get("total_s"),
                    computed_by=f"service:{self._eid}",
                ),
            )
            self._c_store_writebacks.inc()
        except Exception:
            log.debug("store write-back failed for job %s", job.id,
                      exc_info=True)

    # -- drain checkpoints ----------------------------------------------
    def checkpoint_dir(self) -> str:
        if self._checkpoint_dir is None:
            self._checkpoint_dir = tempfile.mkdtemp(prefix="myth-serve-")
        os.makedirs(self._checkpoint_dir, exist_ok=True)
        return self._checkpoint_dir

    def _checkpoint_job(self, job: Job, track: Optional[_JobTrack]) -> None:
        """Flush one unfinished job's seeded frontier to a replayable
        npz: its lanes' next-wave inputs (or first-wave dispatcher
        seeds when it never entered the arena) against its own
        single-contract code table. replay_wave / load_checkpoint
        reconstruct the exact wave the drain cut off."""
        from mythril_tpu.laser.batch.checkpoint import save_checkpoint
        from mythril_tpu.laser.batch.seeds import (
            code_cap_bucket,
            dispatcher_seeds,
        )
        from mythril_tpu.laser.batch.state import make_batch, make_code_table

        try:
            if track is not None:
                n = len(track.lanes)
                inputs = track.next_inputs()
            else:
                n = (
                    self.alloc.stripes_needed(
                        job.lanes or self.cfg.lanes_per_stripe
                    )
                    * self.cfg.lanes_per_stripe
                )
                # same prune feed a wave admission would have used, so
                # the checkpointed frontier replays what the engine
                # would actually have seeded
                seeds = dispatcher_seeds(
                    job.code.hex(), self.cfg.calldata_len,
                    prune=self.code_cache.static_summary(job.code),
                )
                inputs = [seeds[i % len(seeds)] for i in range(n)]
            table = make_code_table(
                [job.code], code_cap=code_cap_bucket(len(job.code))
            )
            batch = make_batch(
                n,
                calldata=inputs,
                caller=DEFAULT_CALLER,
                address=DEFAULT_ADDRESS,
                timestamp=0x5BFA4639,
                number=0x66E393,
                gasprice=0x773594000,
            )
            path = os.path.join(
                self.checkpoint_dir(), f"job-{job.id}.npz"
            )
            save_checkpoint(
                path, batch, table, step=self.cfg.steps_per_wave
            )
            job.checkpoint_path = path
            self.queue.settle(job, JobState.CHECKPOINTED)
        except Exception as why:
            log.exception("drain checkpoint failed for job %s", job.id)
            job.error = f"drain checkpoint failed: {why}"
            self.queue.settle(job, JobState.FAILED)

    # -- introspection --------------------------------------------------
    def _link_stats(self) -> Dict:
        """`static.link.*`: the linker's process-wide counters. Never
        fatal — a missing linker reads as all-zero, not a 500."""
        try:
            from mythril_tpu.analysis.static import link_stat_counts

            return dict(link_stat_counts())
        except Exception:
            return {}

    def _kernel_stats(self) -> Dict:
        """The specialization scorecard (/stats kernel.*): the
        process-wide compile cache (size, hits, misses, compiles in
        flight, compile wall) plus this engine's wave split and fused
        throughput."""
        from mythril_tpu.laser.batch.specialize import (
            kernel_cache_stats,
            specialize_enabled,
        )

        from mythril_tpu.laser.batch.blockjit import blockjit_enabled

        out = {
            "enabled": bool(self.cfg.specialize) and specialize_enabled(),
            "warmup": self.cfg.specialize_warmup,
            "warmups_launched": len(self._kernel_warming),
            "spec_waves": self.spec_waves,
            "generic_waves": self.generic_waves,
            "fused_steps": self.kernel_fused_steps,
            "blockjit": (
                bool(self.cfg.specialize)
                and specialize_enabled()
                and bool(self.cfg.blockjit)
                and blockjit_enabled()
            ),
            "blockjit_blocks": int(self._c_blocks.value),
            "fallbacks": self.kernel_fallbacks,
            "pinned_codes": self.code_cache.kernels_pinned
            - self.code_cache.kernels_released,
        }
        out.update(kernel_cache_stats())
        # the cache's own counters under their /stats names
        out["cache_hits"] = out.pop("hits")
        out["cache_misses"] = out.pop("misses")
        # the compile plane's scorecard (/stats kernel.compileplane.*):
        # pack/cache hit split, AOT load latency, pack mount outcome —
        # the smoke reads generic_aot.compiles to prove a packed boot
        # compiled nothing in-process.
        try:
            from mythril_tpu.compileplane.plane import active_plane
            from mythril_tpu.laser.batch.run import generic_aot_stats

            plane = active_plane()
            out["compileplane"] = (
                dict(plane.stats(), pack_mount=self._pack_mounted)
                if plane is not None
                else {"enabled": False}
            )
            out["generic_aot"] = generic_aot_stats()
        except Exception:
            out["compileplane"] = {"enabled": False}
        return out

    def _breaker_stats(self) -> Dict:
        """`/stats breaker.*`: the tier circuit-breaker board
        (support/breaker.py) — per-tier state/trip/failure counters,
        process-wide (the tiers are shared, not per-engine)."""
        from mythril_tpu.support import breaker as cb

        enabled = bool(self.cfg.breakers) and cb.breakers_enabled()
        return {
            "enabled": enabled,
            "tiers": cb.board_stats() if enabled else {},
        }

    @staticmethod
    def _solver_stats(snap: Dict) -> Dict:
        """`/stats solver.*`: the query flight recorder's live view —
        the loss waterfall (why host-answered queries were not
        device-answered), the host-WON restriction, and the capture
        corpus state (observe/querylog.py). Process-wide series, not
        per-engine: the solver funnel is shared."""
        from mythril_tpu.observe import querylog

        loss: Dict[str, int] = {}
        loss_sat: Dict[str, int] = {}
        for key, value in (snap.get("mtpu_solver_loss_total") or {}).items():
            labels = dict(key)
            reason = labels.get("reason", "?")
            loss[reason] = loss.get(reason, 0) + int(value)
            if labels.get("verdict") == "sat":
                loss_sat[reason] = loss_sat.get(reason, 0) + int(value)
        return {
            "loss": loss,
            "loss_sat": loss_sat,
            "captured_queries": int(
                sum(
                    (
                        snap.get("mtpu_solver_captured_queries_total") or {}
                    ).values()
                )
            ),
            "capture_dir": querylog.capture_dir(),
        }

    def stats(self) -> Dict:
        """The /stats tree. The wave-loop counters all come out of ONE
        registry snapshot (a single lock acquisition), so the numbers
        are point-in-time consistent with each other even while the
        wave thread is mutating them; the queue/arena/cache blocks are
        internally consistent behind their own locks. Pinned by
        `schema_version`."""
        from mythril_tpu.support.resilience import DegradationLog

        now = time.monotonic()
        snap = observe.registry().snapshot()

        def sv(name: str, **labels) -> float:
            return snap.get(name, {}).get(
                _label_key(dict(labels, engine=self._eid)), 0
            )

        waves_total = int(sv("mtpu_service_waves_total"))
        overlapped = int(sv("mtpu_service_pipeline_overlapped_total"))
        span = (
            (self._last_wave_t - self._first_wave_t)
            if waves_total > 1
            else None
        )
        return {
            "schema_version": STATS_SCHEMA_VERSION,
            "uptime_s": round(now - self.started_t, 3),
            "draining": self._draining,
            "queue": {
                "depth": self.queue.depth(),
                "capacity": self.queue.capacity,
                "accepted": self.queue.accepted,
                "rejected_full": self.queue.rejected_full,
                "rejected_draining": self.queue.rejected_draining,
                "jobs": self.queue.jobs_by_state(),
            },
            "arena": self.alloc.occupancy(),
            "waves": {
                "count": waves_total,
                "steps_per_wave": self.cfg.steps_per_wave,
                "device_steps": int(sv("mtpu_service_device_steps_total")),
                "rate_per_s": (
                    round((waves_total - 1) / span, 3) if span else 0.0
                ),
                "cold_wave_s": (
                    round(self._wave_cold_s, 4)
                    if self._wave_cold_s is not None
                    else None
                ),
                "warm_wave_s": (
                    round(self._wave_warm_ema_s, 4)
                    if self._wave_warm_ema_s is not None
                    else None
                ),
            },
            "warm": {
                "code_cap": self.code_cap,
                "kernel_rebuckets": int(
                    sv("mtpu_service_kernel_rebuckets_total")
                ),
                "code_cache": self.code_cache.stats(),
            },
            "pipeline": {
                "enabled": self.pipeline_enabled,
                "inflight": int(sv("mtpu_service_pipeline_inflight")),
                "overlapped_waves": overlapped,
                "multi_job_overlaps": int(
                    sv("mtpu_service_pipeline_multi_job_total")
                ),
                "wave_overlap_ratio": (
                    round(overlapped / waves_total, 3)
                    if waves_total
                    else 0.0
                ),
            },
            "mesh": {
                # the ACTUAL topology, not the requested --devices N (a
                # request past the visible device count clamps)
                "devices": self.mesh.n_devices if self.mesh else 1,
                "groups": self.alloc.groups,
                "steals": int(sv("mtpu_service_mesh_steals_total")),
                "rebalance_bytes": int(
                    sv("mtpu_service_mesh_rebalance_bytes_total")
                ),
                "per_device": [
                    dict(
                        g,
                        waves=int(
                            sv(
                                "mtpu_service_group_waves_total",
                                group=str(g["group"]),
                            )
                        ),
                        devices=(
                            [
                                str(d)
                                for d in self.mesh.group(
                                    g["group"]
                                ).devices
                            ]
                            if self.mesh
                            else None
                        ),
                        faults=(
                            self.mesh.group(
                                g["group"]
                            ).failure_domain.faults
                            if self.mesh
                            else 0
                        ),
                    )
                    for g in self.alloc.occupancy()["groups"]
                ],
            },
            "store": dict(
                (
                    self.vstore.stats()
                    if self.vstore is not None
                    else {
                        "hits": 0,
                        "near_hits": 0,
                        "misses": 0,
                        "writes": 0,
                        "bytes": 0,
                        "evictions": 0,
                        "corrupt": 0,
                    }
                ),
                enabled=self.vstore is not None,
                answered=int(sv("mtpu_service_store_answered_total")),
                writebacks=int(
                    sv("mtpu_service_store_writebacks_total")
                ),
            ),
            "static": {
                "summaries_cached": self.code_cache.static_summaries,
                "seeds_dropped": int(
                    sv("mtpu_service_static_seeds_dropped_total")
                ),
                "static_answered": int(
                    sv("mtpu_service_static_answered_total")
                ),
                "answer_enabled": bool(self.cfg.static_answer),
                # the cross-contract linker's process-wide counters
                # (analysis/static/callgraph.py): nodes/sites linked,
                # provenance resolution, proxy pairing, escape
                # widening — the `static.link.*` rows
                "link": self._link_stats(),
            },
            "journal": dict(
                (
                    self.journal.stats()
                    if self.journal is not None
                    else {"enabled": False}
                ),
                recovered_jobs=int(
                    sv("mtpu_journal_recovered_jobs_total")
                ),
                recovery_deduped=int(
                    sv("mtpu_journal_recovery_deduped_total")
                ),
            ),
            "breaker": self._breaker_stats(),
            "quarantine": {
                "strikes": dict(self._strikes),
                "denylisted": len(self._denylist),
                "strike_threshold": self.cfg.quarantine_strikes,
                "quarantined": int(sv("mtpu_quarantined_total")),
            },
            "kernel": self._kernel_stats(),
            "solver": self._solver_stats(snap),
            "host_pool": {
                "workers": max(1, self.cfg.host_workers),
                # walker processes the walks run in (0: in-process)
                "walkers": (
                    len(self._walkers.walkers) if self._walkers else 0
                ),
                "inflight": len(self._host_inflight),
                "completed": int(sv("mtpu_service_host_completed_total")),
            },
            "observe": {
                "enabled": observe.enabled(),
                "spans_recorded": flight_recorder().recorded,
                "flight_dump": getattr(self, "flight_dump_path", None),
            },
            "health": self.health.healthz_payload(),
            "device": observe.device_monitor().latest(),
            "degradation": DegradationLog().counts_since(self._deg_marker),
        }
