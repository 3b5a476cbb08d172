"""Corpus-parallel analysis: many contracts at once.

The reference analyzes contracts strictly sequentially
(mythril/mythril/mythril_analyzer.py:145-185 — a plain for-loop);
SURVEY.md §2.4 maps that loop onto two axes here:

1. **Device axis** — the parent process (which owns the accelerator)
   runs ONE lane-striped symbolic exploration over the whole corpus
   (laser/batch/explore.py DeviceCorpusExplorer): every contract gets
   a stripe of lanes, each wave advances the entire corpus in one
   jit'd dispatch, and the banked witnesses + branch coverage are
   handed to the host analyses.
2. **Host axis** — the per-contract SymExecWrapper + fire_lasers
   pipeline. Single-process runs get each contract's prepass outcome
   injected (witness issues + coverage-guided pruning); pooled runs
   overlap the prepass with the workers and merge its witnesses into
   the results afterward (workers never touch the device; the chip is
   a parent-process resource).
"""

from __future__ import annotations

import contextlib
import logging
import multiprocessing as mp
import os
import threading
import time
import traceback
from typing import Dict, List, Optional, Tuple

log = logging.getLogger(__name__)


def _engine_mesh_stats(stats: Dict, n_devices: Optional[int]) -> Dict:
    """Mesh observability parity with the scheduler path: the single
    lane-sharded engine is one group with zero steals, and its
    occupancy is the fraction of the run a wave was in flight —
    bench.py and the chip smoke read these fields regardless of which
    path ran. Fills `stats` in place and returns it."""
    wall = stats.get("wall_s") or 0.0
    busy = stats.get("device_busy_s") or 0.0
    stats.setdefault("mesh_devices", n_devices or 1)
    stats.setdefault("mesh_groups", 1)
    stats.setdefault("steal_count", 0)
    stats.setdefault("rebalance_bytes", 0)
    stats.setdefault(
        "mesh",
        {
            "devices": n_devices or 1,
            "groups": 1,
            "steals": 0,
            "stolen_items": 0,
            "rebalance_bytes": 0,
            "per_device": [
                {
                    "group": 0,
                    "devices": n_devices or 1,
                    "waves": stats.get("waves", 0),
                    "device_steps": stats.get("device_steps", 0),
                    "busy_s": round(busy, 3),
                    "occupancy": (
                        round(min(1.0, busy / wall), 3) if wall > 0 else 0.0
                    ),
                    "steals": 0,
                    "faults": stats.get("device_faults", 0),
                }
            ],
        },
    )
    return stats


def _raise_if_prepass_required(why: BaseException) -> None:
    """Under --device-prepass always a failed prepass fails the run
    (auto degrades to the host walk instead)."""
    from mythril_tpu.support.support_args import args

    if getattr(args, "device_prepass", "auto") == "always":
        from mythril_tpu.exceptions import DevicePrepassError

        raise DevicePrepassError(
            f"corpus device prepass failed: {why!r}"
        ) from why


def _host_worker_pool(processes: int):
    """A spawn pool (fresh singletons per worker) whose every worker,
    respawned ones included, is pinned to the CPU before its first task
    (`accel.pin_to_cpu`)."""
    from mythril_tpu.support.accel import pin_to_cpu

    return mp.get_context("spawn").Pool(
        processes=processes, initializer=pin_to_cpu
    )


def _effective_cpus() -> int:
    """CPUs this process may actually run on — the affinity mask, not
    the host count (a container pinned to one core of a 64-core host
    must take the single-core paths)."""
    try:
        return len(os.sched_getaffinity(0))
    except (AttributeError, OSError):
        return mp.cpu_count()


#: corpus size from which the overlapped prepass pays even on a
#: single-core box: the waves are device-bound (GIL released during
#: dispatch + readback — measured ~2.7s of host-side work per ~33s
#: wave at 3328 lanes), so the per-wave contention tax amortizes over
#: many host analyses, where on a small corpus it dominates
OVERLAP_MIN_CORPUS = 32


def resolve_prepass_budget_s(
    n_contracts: int,
    override: Optional[float] = None,
    execution_timeout: Optional[float] = None,
    ownership: bool = False,
) -> float:
    """Default ACTIVE-time budget (waves + flip solving; lock waits
    don't bill) for the striped corpus prepass.

    With `ownership` (the round-5 inversion), the economics change:
    every contract the exploration completes refunds its WHOLE host
    walk (up to execution_timeout each), so the budget scales with the
    walk ceiling — up to half the refundable wall, bounded per corpus
    size. Early exits (per-contract parking, frontier exhaustion,
    coverage plateau) stop the spend well short of the budget on
    corpora that converge, so the bound mostly prices the hopeless
    tail.

    Witness-injection-only mode (ownership off) keeps the old curve:
    small corpora 1s/contract (the selector seeds cover most of what
    wave 1 reaches; every active second contends with overlapped host
    analyses on a small box), large corpora 0.5s/contract capped at
    120s."""
    if override is not None:
        return override
    n = max(1, n_contracts)
    if ownership and execution_timeout:
        return min(0.5 * execution_timeout * n, 30.0 + 5.0 * n, 300.0)
    if n >= OVERLAP_MIN_CORPUS:
        # floored at the small-corpus cap so crossing the threshold
        # never SHRINKS the budget (32 contracts must not explore less
        # than 31)
        return min(120.0, max(30.0, 0.5 * n))
    return min(30.0, 1.0 * n)


def _runnable_rows(
    contracts: List[Tuple[str, str, str]],
) -> List[Tuple[int, str]]:
    """(index, normalized runtime hex) for every contract the device
    prepass can execute — THE filter both the prepass and its budget/
    window sizing must share, or the two silently desync."""
    rows = []
    for idx, (code, _creation, _name) in enumerate(contracts):
        code = code[2:] if code.startswith("0x") else code
        if len(code) >= 8:
            rows.append((idx, code))
    return rows


def corpus_shard(items, shard_index: int, shard_count: int, identity=None):
    """Deterministic multi-host partition of a corpus — the DCN axis of
    SURVEY §2.4's per-contract-loop mapping: contracts are
    embarrassingly parallel across hosts, so scale-out is a stable
    partition + a report merge, with no cross-host traffic during
    analysis (the reference's analog is running its sequential loop on
    a slice of the input list).

    Assignment hashes each item's CONTENT (name + runtime code), not
    its position, so every host computes the same partition no matter
    how its filesystem enumerates the inputs. `identity` maps an item
    to its identity string; the default fits the analyze_corpus row
    shape (code, creation, name).
    """
    import hashlib

    if shard_count < 1:
        raise ValueError(f"shard count must be >= 1, got {shard_count}")
    if not 0 <= shard_index < shard_count:
        raise ValueError(
            f"shard index {shard_index} outside 0..{shard_count - 1}"
        )
    if shard_count == 1:
        return list(items)
    if identity is None:
        identity = lambda row: f"{row[2]}:{row[0]}"  # noqa: E731
    out = []
    for item in items:
        digest = hashlib.sha256(identity(item).encode()).digest()
        if int.from_bytes(digest[:8], "big") % shard_count == shard_index:
            out.append(item)
    return out


def corpus_device_prepass(
    contracts: List[Tuple[str, str, str]],
    budget_s: Optional[float] = None,
    lanes_per_contract: Optional[int] = None,
    address: int = 0x901D573B8CE8C997DE5F19173C32D966B4FA55FE,
    transaction_count: int = 1,
    host_lock=None,
    stop_event=None,
    publish=None,
    lock_wanted=None,
    execution_timeout: Optional[float] = None,
    ownership: bool = False,
    deadline=None,
    checkpoint_path=None,
    mesh_groups: Optional[int] = None,
    selector_masks: Optional[Dict[int, Tuple]] = None,
) -> Dict[int, Dict]:
    """One striped device exploration over the corpus; returns
    {contract_index: single-contract prepass outcome} for injection
    into the per-contract analyses (indexed, not named — corpus rows
    may share names). Empty on any failure — the host pipeline must
    never be blocked by the device.

    `mesh_groups > 1` (or `--devices N` via the global flag bag) runs
    the multi-chip corpus scheduler instead of one lane-sharded
    engine: the corpus shards over N device groups at admission, each
    group runs its own wave engine in its own failure domain, and a
    drained group steals pending contracts/frontiers from the most
    loaded one (parallel/scheduler.py).

    `selector_masks` ({contract index: (unchanged selector bytes,
    entry directions)}, mythril_tpu/store) restricts specific
    contracts' exploration to their CHANGED functions — the verdict
    store's incremental tier. The mesh scheduler path drops the masks
    (pure optimization; sharded index bookkeeping isn't worth the
    coupling there yet)."""
    runnable = _runnable_rows(contracts)
    if not runnable:
        return {}
    if mesh_groups is None:
        from mythril_tpu.support.support_args import args as _flags

        mesh_groups = getattr(_flags, "mesh_devices", None)
    if budget_s is None:
        budget_s = resolve_prepass_budget_s(
            len(runnable),
            execution_timeout=execution_timeout,
            ownership=ownership,
        )
    if lanes_per_contract is None:
        # corpus-sized waves: the symbolic kernel was lane-bound over a
        # remote-chip link used before PR 21 (historical: ~33s/wave at
        # 3328 lanes), so wide stripes at hundreds of contracts would
        # starve the wave count; narrower stripes keep several waves
        # per transaction phase
        lanes_per_contract = 16 if len(runnable) >= 64 else 32
    if mesh_groups is not None and mesh_groups > 1 and len(runnable) > 1:
        # the multi-chip corpus scheduler: one wave engine per device
        # group, admission-time sharding, live work stealing, per-group
        # failure domains — the same outcome contract as the single
        # engine below, plus stats["mesh"] observability
        return _mesh_prepass(
            runnable,
            mesh_groups=mesh_groups,
            budget_s=budget_s,
            lanes_per_contract=lanes_per_contract,
            address=address,
            transaction_count=transaction_count,
            host_lock=host_lock,
            stop_event=stop_event,
            publish=publish,
            lock_wanted=lock_wanted,
            deadline=deadline,
            checkpoint_path=checkpoint_path,
        )
    # multi-chip: when the backend exposes more than one device, the
    # striped wave shards lane-major over the dp mesh (SURVEY §2.4's
    # per-contract-loop axis) — the single-chip path is the mesh path
    # with one device, so `myth analyze`/analyze_corpus pick the mesh
    # up with no extra configuration
    import jax

    n_devices = None
    if len(jax.devices()) > 1:
        # shard_batch requires the mesh size to divide the lane count;
        # shrink to the largest divisor rather than letting a
        # non-dividing device count sink the whole prepass into the
        # broad except below (silent host-only degradation)
        n_lanes = len(runnable) * lanes_per_contract
        n_devices = len(jax.devices())
        while n_devices > 1 and n_lanes % n_devices:
            n_devices -= 1
        if n_devices <= 1:
            n_devices = None
    try:
        from mythril_tpu.laser.batch.explore import DeviceCorpusExplorer

        translate = None
        if publish is not None:

            def translate(ti, outcome):
                # a published outcome carries the mesh rows too: it
                # stands as final when the owner cuts the prepass short
                _engine_mesh_stats(outcome["stats"], n_devices)
                publish(runnable[ti][0], outcome)

        from mythril_tpu.laser.batch.explore import required_calldata_len

        at_scale = len(runnable) >= OVERLAP_MIN_CORPUS
        # translate contract-index masks to track indices (the
        # explorer only sees the runnable rows)
        track_masks = None
        if selector_masks:
            track_masks = {
                ti: selector_masks[idx]
                for ti, (idx, _code) in enumerate(runnable)
                if idx in selector_masks
            }
        explorer = DeviceCorpusExplorer(
            [code for _, code in runnable],
            calldata_len=max(
                required_calldata_len(code) for _, code in runnable
            ),
            # corpus scale runs LEAN-CAP symbolic waves: the
            # [N, mem_cap] memory array dominated per-step wave cost
            # in the historical remote-chip measurements (explore.py
            # cap notes), and the degraded-lane counters report what
            # the lean trade excludes. Small corpora keep the roomy caps — depth per
            # contract matters more than wave cost there.
            mem_cap=4096 if at_scale else 16384,
            storage_cap=64 if at_scale else 128,
            lanes_per_contract=lanes_per_contract,
            # the budget (active time) is the real limiter; the wave
            # cap only backstops a runaway phase. 8 waves starved the
            # ownership gate: frontier closure + poison seeding need
            # however many waves the budget affords.
            waves=48,
            steps_per_wave=512,
            budget_s=budget_s,
            address=address,
            transaction_count=transaction_count,
            n_devices=n_devices,
            host_lock=host_lock,
            stop_event=stop_event,
            publish=translate,
            deadline=deadline,
            checkpoint_path=checkpoint_path,
            selector_masks=track_masks,
        )
        if lock_wanted is not None:
            explorer.lock_wanted = lock_wanted
        from mythril_tpu.observe.spans import trace

        with trace("corpus.prepass", contracts=len(runnable)):
            result = explorer.run()
    except Exception as why:
        from mythril_tpu.support.resilience import (
            DegradationLog,
            DegradationReason,
        )

        DegradationLog().record(
            DegradationReason.PREPASS_FAILED, site="corpus-prepass"
        )
        _raise_if_prepass_required(why)
        log.warning("corpus device prepass failed", exc_info=True)
        return {}
    stats = result["stats"]
    _engine_mesh_stats(stats, n_devices)
    log.info(
        "Corpus device prepass: %d contracts, %d lane-steps over %d waves "
        "in %.1fs, %d branch directions covered",
        len(runnable),
        stats["device_steps"],
        stats["waves"],
        stats["wall_s"],
        stats["branches_covered"],
    )
    outcomes = {}
    for (idx, _code), outcome in zip(runnable, result["contracts"]):
        # the stats block is CORPUS-WIDE (one striped exploration);
        # it rides along on every outcome for observability, marked so
        # consumers don't sum it per contract
        outcome["stats"] = dict(stats, scope="corpus")
        outcomes[idx] = outcome
    return outcomes


def _mesh_prepass(
    runnable,
    mesh_groups: int,
    budget_s: Optional[float],
    lanes_per_contract: int,
    address: int,
    transaction_count: int,
    host_lock,
    stop_event,
    publish,
    lock_wanted,
    deadline,
    checkpoint_path,
) -> Dict[int, Dict]:
    """The multi-chip corpus prepass: shard the runnable rows over
    `mesh_groups` device groups and run one wave engine per group with
    live work stealing (parallel/scheduler.py). Outcome contract
    matches corpus_device_prepass's single-engine path."""
    try:
        from mythril_tpu.parallel.scheduler import CorpusScheduler

        at_scale = len(runnable) >= OVERLAP_MIN_CORPUS
        translate = (
            None
            if publish is None
            else (lambda ti, outcome: publish(runnable[ti][0], outcome))
        )
        scheduler = CorpusScheduler(
            [code for _, code in runnable],
            n_groups=mesh_groups,
            budget_s=budget_s,
            host_lock=host_lock,
            stop_event=stop_event,
            publish=translate,
            lock_wanted=lock_wanted,
            deadline=deadline,
            checkpoint_path=checkpoint_path,
            explorer_kwargs=dict(
                lanes_per_contract=lanes_per_contract,
                mem_cap=4096 if at_scale else 16384,
                storage_cap=64 if at_scale else 128,
                waves=48,
                steps_per_wave=512,
                address=address,
                transaction_count=transaction_count,
            ),
        )
        from mythril_tpu.observe.spans import trace

        with trace(
            "corpus.prepass", contracts=len(runnable), mesh=mesh_groups
        ):
            result = scheduler.run()
    except Exception as why:
        from mythril_tpu.support.resilience import (
            DegradationLog,
            DegradationReason,
        )

        DegradationLog().record(
            DegradationReason.PREPASS_FAILED, site="corpus-mesh-prepass"
        )
        _raise_if_prepass_required(why)
        log.warning("multi-chip corpus prepass failed", exc_info=True)
        return {}
    stats = result["stats"]
    mesh = stats.get("mesh", {})
    log.info(
        "Mesh corpus prepass: %d contracts over %d device group(s), "
        "%d lane-steps / %d waves in %.1fs, %d steal event(s), "
        "%d rebalance byte(s)",
        len(runnable),
        mesh.get("groups", 1),
        stats.get("device_steps", 0),
        stats.get("waves", 0),
        stats.get("wall_s", 0.0),
        mesh.get("steals", 0),
        mesh.get("rebalance_bytes", 0),
    )
    outcomes = {}
    for (idx, _code), outcome in zip(runnable, result["contracts"]):
        outcome["stats"] = dict(stats, scope="corpus")
        outcomes[idx] = outcome
    return outcomes


#: the overlapped prepass thread's name
PREPASS_THREAD = "corpus-prepass"

#: prepass threads a finish() left running past its grace, each already
#: told to stop (join_stopped_prepasses)
_STOPPED_PREPASSES: List = []


def join_stopped_prepasses(timeout: float = 600.0) -> bool:
    """Wait for the prepass threads a finish() left running. A daemon
    thread still inside a device call when the interpreter finalizes
    aborts the process, so the CLI calls this before it returns. Each
    thread stops at its next dispatch boundary; False when one is still
    alive after `timeout` (a hung device call)."""
    deadline = time.monotonic() + timeout
    while _STOPPED_PREPASSES:
        thread = _STOPPED_PREPASSES.pop()
        thread.join(max(0.0, deadline - time.monotonic()))
        if thread.is_alive():
            log.error(
                "corpus device prepass still inside a device call %.0fs "
                "after it was stopped",
                timeout,
            )
            return False
    return True


class OverlappedPrepass:
    """Own the striped device prepass thread beside a sequence of host
    analyses in THIS process.

    The prepass explores the whole corpus on device while the caller
    analyzes contracts one by one; both sides serialize host symbolic
    state on HOST_SYMBOLIC_LOCK (support/host_lock.py). Per-contract
    outcomes are published incrementally after every wave, so analyses
    that start mid-prepass still get witness/coverage injection, and
    `finish()` returns the final outcomes for a post-merge.

    Usage:
        pre = OverlappedPrepass(contracts, address, transaction_count)
        for i, c in enumerate(contracts):
            outcome, device_ok = pre.outcome_for(i)
            with pre.lock:
                ...analyze c with prepass_outcome=outcome, device off
                   unless device_ok...
            pre.yield_lock()
        final = pre.finish()
    """

    def __init__(
        self,
        contracts: List[Tuple[str, str, str]],
        address: int,
        transaction_count: int,
        budget_s: Optional[float] = None,
        execution_timeout: Optional[float] = None,
        ownership: bool = False,
        deadline=None,
        mesh_groups: Optional[int] = None,
        selector_masks: Optional[Dict[int, Tuple]] = None,
    ) -> None:
        import threading

        # the device stack is imported HERE, not first in the worker: a
        # first `import jax` racing between two threads hands one of
        # them a partially initialized module (and the prepass fails)
        import mythril_tpu.laser.batch.explore  # noqa: F401
        from mythril_tpu.support.host_lock import HOST_SYMBOLIC_LOCK

        self.lock = HOST_SYMBOLIC_LOCK
        self._final: Dict[int, Dict] = {}
        self._published: Dict[int, Dict] = {}
        self._stop = threading.Event()
        self._lock_wanted = threading.Event()
        self._deviceless = 0
        self._finished = False
        self._drain_abandoned = False
        #: a --device-prepass always failure, re-raised to the caller
        self._error: Optional[BaseException] = None

        def _work():
            from mythril_tpu.exceptions import DevicePrepassError

            try:
                outcomes = corpus_device_prepass(
                    contracts,
                    budget_s=budget_s,
                    address=address,
                    transaction_count=transaction_count,
                    host_lock=self.lock,
                    stop_event=self._stop,
                    publish=self._published.__setitem__,
                    lock_wanted=self._lock_wanted,
                    execution_timeout=execution_timeout,
                    ownership=ownership,
                    deadline=deadline,
                    mesh_groups=mesh_groups,
                    selector_masks=selector_masks,
                )
            except DevicePrepassError as why:
                self._error = why
                return
            # rebinding, not updating: finish() may already have handed
            # the published outcomes to the caller as final
            self._final = {**self._final, **outcomes}

        self._thread = threading.Thread(
            target=_work, name=PREPASS_THREAD, daemon=True
        )
        self._thread.start()

    @property
    def drain_abandoned(self) -> bool:
        """True once a drain timed out on a hung device call — no
        further outcomes will ever be published."""
        return self._drain_abandoned

    def _done(self) -> bool:
        if self._thread is not None and not self._thread.is_alive():
            self._thread.join()
            self._thread = None
        return self._thread is None

    def drain(self) -> None:
        """Block until the prepass finishes its remaining active
        budget, without stopping it early. While the caller waits here
        the lock stays free, so the drain runs at full speed — this is
        how the analysis loop bounds its overlap window: cheap
        contracts share the core with the prepass, then one drain, and
        the budget-bound heavyweights run uncontended with the FINAL
        outcome. (An active-time budget alone cannot bound the
        prepass's wall span: lock waits don't bill, so a 13s budget
        can stretch across a whole corpus of analyses.) The join is
        bounded AND paid once: a device call hung on a crashed device
        must cost the corpus two minutes total, not two minutes per
        remaining contract — after a timed-out drain every later call
        is a no-op and the analyses continue on partial outcomes."""
        if self._drain_abandoned or self._thread is None:
            return
        self._thread.join(timeout=120)
        if not self._done():
            self._drain_abandoned = True
            log.warning(
                "corpus device prepass drain timed out; continuing on "
                "partial outcomes (later drains skipped)"
            )

    def outcome_for(self, i: int):
        """(outcome to inject for contract i, device allowed).

        While the prepass runs, analyses get the latest PUBLISHED
        partial outcome with the device off — the chip belongs to the
        prepass thread, and an injected outcome bypasses the
        device_prepass mode check anyway. Once it's done, the device
        comes back for everyone: covered contracts get the final
        outcome (which skips their own per-contract prepass), missed
        ones fall back to the normal per-contract device path."""
        if self._error is not None:
            raise self._error
        if self._done():
            return self._final.get(i), True
        self._deviceless += 1
        return self._published.get(i), False

    def yield_lock(self) -> None:
        """Hand the lock to the prepass thread between analyses:
        CPython locks are unfair and a tight loop would reacquire
        within microseconds, rationing the prepass to one reseed per
        contract (lock convoy). Only yields when a flip burst is
        actually waiting — an unconditional sleep would tax every
        analysis of a large corpus for a lock the prepass wants at
        most once per wave."""
        if (
            self._thread is not None
            and self._thread.is_alive()
            and self._lock_wanted.is_set()
        ):
            time.sleep(0.05)

    def finish(self) -> Dict[int, Dict]:
        """Stop the exploration at its next wave boundary and return
        the final per-contract outcomes (empty on prepass failure;
        under --device-prepass always the failure raises here).
        Idempotent — callers invoke it from finally blocks so an
        exception escaping the analysis loop cannot orphan the
        thread."""
        if self._finished:
            return self._final
        self._finished = True
        if self._thread is not None:
            self._stop.set()
            # stop is honored between waves and before every device
            # search dispatch, but not inside one: one corpus wave runs
            # ~30-60s, so 90s means "a wave and slack". A thread still
            # inside a device call past it is left to finish on its own
            # and joined before the process exits. A thread a drain
            # already waited 120s on is known hung — its device call
            # cannot observe the stop event, so another 90s here would
            # break drain()'s "two minutes total" bound.
            self._thread.join(timeout=0.1 if self._drain_abandoned else 90)
            if self._thread.is_alive():
                _STOPPED_PREPASSES.append(self._thread)
            if self._thread.is_alive() and not self._final:
                from mythril_tpu.support import resilience

                # what the last harvested wave published stands: the
                # banked witnesses and coverage are real outcomes
                self._final = dict(self._published)
                resilience.DegradationLog().record(
                    resilience.DegradationReason.PREPASS_CUT,
                    site="corpus-prepass-finish",
                )
                log.warning(
                    "corpus device prepass did not stop within its "
                    "grace period; the outcomes of its last harvested "
                    "wave stand"
                )
            self._thread = None
        if self._error is not None:
            raise self._error
        if not self._final and self._deviceless:
            # the prepass died without outcomes: these analyses ran
            # host-only on at most a partial outcome — say so rather
            # than degrade silently
            log.warning(
                "corpus device prepass produced no outcomes; %d "
                "contract(s) were analyzed without the device",
                self._deviceless,
            )
        return self._final


def _ownership_enabled(use_device: bool) -> bool:
    """Resolve --device-ownership (auto = follow the device axis)."""
    from mythril_tpu.support.support_args import args

    mode = getattr(args, "device_ownership", "auto")
    if mode == "never":
        return False
    if mode == "always":
        return True
    return bool(use_device)


def _outcome_owns(outcome: Optional[Dict]) -> bool:
    """True when a FINAL prepass outcome covered the contract
    end-to-end (explore.py `device_complete`): frontier closed, no
    degraded lanes, no dropped carries. Partial (mid-exploration)
    outcomes never own — UNLESS the explorer froze this contract early
    (`final_for_contract`: all gates green in the last phase, track
    parked, evidence immutable), which is per-contract finality inside
    a still-running corpus exploration."""
    return bool(
        outcome
        and outcome.get("device_complete")
        and (
            outcome.get("final_for_contract")
            or not (outcome.get("stats") or {}).get("partial")
        )
    )


def _maybe_ownable(outcome: Optional[Dict]) -> bool:
    """Could a still-running prepass still hand this contract over?
    False the moment a published outcome shows a hard ownership
    failure (degraded lanes, dropped carries, saturated event bank) —
    those gates only ever get worse, so the host walk should start
    immediately instead of waiting out the prepass."""
    if outcome is None:
        return True  # no information yet
    gates = outcome.get("completeness_gates") or {}
    return (
        gates.get("no_degraded", True)
        and gates.get("no_carry_overflow", True)
        and gates.get("no_event_overflow", True)
    )


def _owned_result(code, creation_code, name, outcome, address) -> Dict:
    """The analysis result for a device-owned contract: issues are
    synthesized from the banked concrete evidence (witness issues +
    evidence issues, analysis/prepass.py / analysis/evidence.py); the
    host walk is SKIPPED — this is the round-5 inversion of the
    reference's per-contract loop (mythril_analyzer.py:145-185)."""
    from mythril_tpu.analysis.prepass import witness_issues
    from mythril_tpu.ethereum.evmcontract import EVMContract

    try:
        contract = EVMContract(
            code=code or "", creation_code=creation_code or "", name=name
        )
        issues = witness_issues(contract, outcome, address)
    except Exception:
        # synthesis failed AFTER the walk was skipped on its promise:
        # None tells the caller to fall back to the host walk
        log.warning("owned-result synthesis failed for %s", name, exc_info=True)
        return None
    stats = dict(outcome.get("stats") or {}, scope="corpus", owned=True)
    return {
        "name": name,
        "issues": [issue.as_dict for issue in issues],
        "states": 0,
        "device_prepass": stats,
        "phases": {},
        "precovered_skips": 0,
        "owned": True,
        "error": None,
    }


def _static_answer_result(name: str, summary, wall_s: float) -> Dict:
    """The result slot for a statically-answered contract: the
    semantic screen (analysis/static taint + sink predicates) proved
    that NO detection module can fire, so the empty issue set IS the
    analysis — no device wave, no host walk, no solver. Same shape as
    an analyzed result so report builders need no special case; the
    `static_answered` flag routes it in the routing feature log and
    the report meta."""
    return {
        "name": name,
        "issues": [],
        "states": 0,
        "device_prepass": None,
        "phases": {},
        "precovered_skips": 0,
        "wall_s": round(wall_s, 6),
        "error": None,
        "static_answered": True,
        "static_analysis": {
            "code_hash": summary.code_hash,
            "static_answerable": True,
            "modules_applicable": 0,
            "wall_ms": summary.wall_ms,
        },
    }


def _static_triage(
    contracts: List[Tuple[str, str, str]],
    skip: Optional[frozenset] = None,
) -> Dict[int, Dict]:
    """{index: static-answer result} for every corpus row the
    semantic screen settles outright. Runs BEFORE the device prepass
    so answered contracts never occupy a lane; any per-contract
    failure simply keeps that contract on the full path. `skip` rows
    (already settled by an earlier tier — the verdict store) are
    never re-examined."""
    from mythril_tpu.analysis.static import summary_for
    from mythril_tpu.observe.registry import registry

    out: Dict[int, Dict] = {}
    counter = registry().counter(
        "mtpu_static_answered_total",
        "contracts settled by the static-answer triage tier",
    )
    for i, (code, creation_code, name) in enumerate(contracts):
        if skip and i in skip:
            continue
        if creation_code:
            # a deploying row executes creation code too — the
            # runtime-only proof does not cover it
            continue
        norm = code[2:] if code.startswith("0x") else code
        if len(norm) < 4:
            continue
        t0 = time.perf_counter()
        try:
            summary = summary_for(norm)
            if summary.static_answerable:
                out[i] = _static_answer_result(
                    name, summary, time.perf_counter() - t0
                )
                counter.inc()
        except Exception:
            log.debug(
                "static triage failed for %s; full path", name,
                exc_info=True,
            )
    if out:
        log.info(
            "Static triage answered %d/%d contract(s) without "
            "dispatch",
            len(out),
            len(contracts),
        )
    return out


def _store_hit_result(name: str, entry, wall_s: float) -> Dict:
    """The result slot for an exact verdict-store hit: the banked
    issue set IS the analysis — no device wave, no host walk, no
    solver. Same shape as an analyzed result; the `store_hit` flag
    routes it in the routing feature log and the report meta."""
    return {
        "name": name,
        "issues": entry.issues,
        "states": 0,
        "device_prepass": None,
        "phases": {},
        "precovered_skips": 0,
        "wall_s": round(wall_s, 6),
        "error": None,
        "store_hit": True,
        "store": {
            "code_hash": entry.code_hash,
            "config_fingerprint": entry.config_fp,
            "provenance": entry.provenance,
        },
    }


def _store_triage(
    contracts: List[Tuple[str, str, str]],
    vstore,
    config_fp: str,
    linkset=None,
) -> Tuple[Dict[int, Dict], Dict[int, object]]:
    """({index: exact-hit result}, {index: IncrementalPlan}) from the
    verdict store (mythril_tpu/store). Runs BEFORE the static triage
    and the device prepass, so hit contracts never occupy a lane and
    incremental contracts explore only their changed selectors. Every
    doubt bails that contract to the full path — a store problem can
    cost speed, never correctness.

    With a corpus `linkset`, an exact codehash hit is additionally
    checked against its stored CALL-GRAPH fingerprints: byte-identical
    code whose resolved callee closure moved (implementation upgrade
    behind an unchanged proxy) is NOT served the stale verdict — it
    downgrades to a linked incremental plan re-analyzing only the
    selectors whose closure changed, or to full analysis when the
    linked diff cannot be trusted (link-unresolved / link-cycle)."""
    answers: Dict[int, Dict] = {}
    plans: Dict[int, object] = {}
    if vstore is None:
        return answers, plans
    from mythril_tpu.analysis.static import (
        static_prune_enabled,
        summary_for,
    )
    from mythril_tpu.store import (
        IncrementalBail,
        code_hash_hex,
        plan_incremental,
        plan_linked_incremental,
    )

    for i, (code, creation_code, name) in enumerate(contracts):
        if creation_code:
            # a deploying row executes creation code too — the
            # runtime-keyed verdict does not cover it
            continue
        norm = code[2:] if code.startswith("0x") else code
        if len(norm) < 8:
            continue
        t0 = time.perf_counter()
        code_hash = code_hash_hex(norm)
        try:
            entry = vstore.get(code_hash, config_fp)
        except Exception:
            log.debug("store lookup failed for %s", name, exc_info=True)
            continue
        if entry is not None:
            if linkset is not None and entry.linked_fingerprints:
                verdict = _linked_hit_verdict(
                    norm, name, entry, linkset, config_fp,
                    plan_linked_incremental, summary_for,
                )
                if verdict == "stale":
                    continue  # full analysis; serving the hit is wrong
                if verdict is not None:
                    plans[i] = verdict
                    continue
            answers[i] = _store_hit_result(
                name, entry, time.perf_counter() - t0
            )
            continue
        if not static_prune_enabled():
            continue  # the diff needs the static layer's fingerprints
        try:
            summary = summary_for(norm, config_fp=config_fp)
            nearest = vstore.nearest(
                config_fp,
                summary.function_fingerprints,
                exclude_code_hash=code_hash,
            )
            if nearest is None:
                continue
            plans[i] = plan_incremental(summary, nearest)
            log.info(
                "Store incremental plan for %s: %d changed / %d "
                "unchanged selector(s), %d banked issue(s)",
                name,
                len(plans[i].changed),
                len(plans[i].unchanged),
                len(plans[i].banked_issues),
            )
        except IncrementalBail as bail:
            log.info(
                "Store incremental bail for %s: %s (full analysis)",
                name,
                bail.reason,
            )
        except Exception:
            log.debug(
                "store incremental planning failed for %s", name,
                exc_info=True,
            )
    if answers:
        log.info(
            "Verdict store settled %d/%d contract(s) at admission",
            len(answers),
            len(contracts),
        )
    return answers, plans


def _linked_hit_verdict(
    norm: str,
    name: str,
    entry,
    linkset,
    config_fp: str,
    plan_linked_incremental,
    summary_for,
):
    """Check an exact store hit against its call-graph fingerprints.
    Returns None (hit stands), an IncrementalPlan (only the selectors
    whose callee closure moved re-run; the rest is banked), or the
    sentinel "stale" (closure moved but the diff cannot be trusted —
    full analysis, never the stale verdict)."""
    from mythril_tpu.store import IncrementalBail

    try:
        summary = summary_for(norm, config_fp=config_fp)
    except Exception:
        log.debug("summary failed for linked hit %s", name, exc_info=True)
        return None
    if summary.code_hash not in linkset.nodes:
        return None  # row not linked: pre-link behavior
    linked_now, problems = linkset.linked_fingerprints(summary.code_hash)
    if linked_now == entry.linked_fingerprints and not problems:
        return None  # closure identical everywhere
    try:
        plan = plan_linked_incremental(
            summary, entry, linked_now, problems
        )
    except IncrementalBail as bail:
        log.info(
            "Linked store hit for %s cannot be diffed: %s "
            "(full analysis)",
            name,
            bail.reason,
        )
        return "stale"
    except Exception:
        log.debug(
            "linked incremental planning failed for %s", name,
            exc_info=True,
        )
        return "stale"
    if plan is None:
        return None
    log.info(
        "Linked store hit for %s: callee closure moved for %d "
        "selector(s); %d banked",
        name,
        len(plan.changed),
        len(plan.unchanged),
    )
    return plan


def _apply_incremental(result: Optional[Dict], plan) -> Optional[Dict]:
    """Fold one incremental plan's banked issues into the fresh
    (changed-selector-restricted) result and flag the route."""
    if result is None or result.get("error"):
        return result
    from mythril_tpu.store import merge_banked_issues

    added = merge_banked_issues(result.setdefault("issues", []), plan.banked_issues)
    result["store_incremental"] = True
    result["store"] = dict(plan.as_dict(), banked_merged=added)
    return result


def _store_writeback(
    results: List[Optional[Dict]],
    contracts: List[Tuple[str, str, str]],
    prepass: Dict[int, Dict],
    vstore,
    config_fp: str,
    linkset=None,
) -> int:
    """Tier 3: persist every COMPLETE full analysis (including
    incremental ones — a fork's merged verdict is a first-class entry
    for the next fork). Store-hit and statically-answered rows are not
    re-written (their verdicts are already cheap or present); partial,
    skipped, and errored rows never are."""
    if vstore is None:
        return 0
    from mythril_tpu.analysis.static import (
        static_prune_enabled,
        summary_for,
    )
    from mythril_tpu.store import (
        banks_from_outcome,
        code_hash_hex,
        provenance,
        static_export,
    )

    written = 0
    for i, (code, creation_code, name) in enumerate(contracts):
        result = results[i] if i < len(results) else None
        if (
            result is None
            or creation_code
            or not result.get("complete")
            or result.get("store_hit")
            or result.get("static_answered")
            or result.get("skipped")
        ):
            continue
        norm = code[2:] if code.startswith("0x") else code
        if len(norm) < 8:
            continue
        summary = None
        if static_prune_enabled():
            try:
                summary = summary_for(norm, config_fp=config_fp)
            except Exception:
                summary = None
        try:
            path = vstore.put(
                code_hash_hex(norm),
                config_fp,
                issues=result.get("issues") or [],
                static=static_export(summary, linkset=linkset),
                banks=banks_from_outcome(prepass.get(i)),
                provenance=provenance(
                    wall_s=result.get("wall_s"),
                    computed_by="corpus",
                    incremental=bool(result.get("store_incremental")),
                ),
            )
            written += bool(path)
        except Exception:
            log.debug("store write-back failed for %s", name,
                      exc_info=True)
    if written:
        log.info("Verdict store banked %d verdict(s)", written)
    return written


def _skipped_result(name: str, reason: str) -> Dict:
    """The result slot for a contract the supervisor never analyzed
    (deadline expiry / SIGTERM): same shape as an analyzed result so
    report builders need no special case, explicitly marked so the
    partial report can say WHICH contracts are missing and why. The
    post-merge still folds in any witnesses the device prepass banked
    for it — a run killed at minute 10 keeps every finding harvested
    so far."""
    from mythril_tpu.support.resilience import (
        DegradationLog,
        DegradationReason,
    )

    DegradationLog().record(
        DegradationReason.CONTRACT_SKIPPED,
        site="corpus",
        detail=reason,
        contract=name,
    )
    return {
        "name": name,
        "issues": [],
        "states": 0,
        "device_prepass": None,
        "phases": {},
        "precovered_skips": 0,
        "error": None,
        "skipped": reason,
    }


def _analyze_one(payload: Tuple) -> Dict:
    """Worker: analyze one contract, return issue dicts (run in a
    spawned process; heavyweight imports stay inside). The result
    carries its own wall (`wall_s`) — the per-contract outcome field
    the routing feature log (observe/routing.py) trains on."""
    t_start = time.perf_counter()
    (
        code,
        creation_code,
        name,
        address,
        strategy,
        transaction_count,
        execution_timeout,
        create_timeout,
        max_depth,
        loop_bound,
        modules,
        solver_timeout,
        use_device,
        prepass_outcome,
        deterministic_solving,
    ) = payload
    args = restore_device_args = restore_deterministic = None
    try:
        from mythril_tpu.analysis.security import fire_lasers
        from mythril_tpu.analysis.symbolic import SymExecWrapper
        from mythril_tpu.ethereum.evmcontract import EVMContract
        from mythril_tpu.support.support_args import args

        if solver_timeout:
            args.solver_timeout = solver_timeout
        if deterministic_solving is not None:
            # threaded through the payload (not toggled by the caller
            # around the whole run) so the flag flip is scoped to this
            # one analysis and restored on every exit path
            restore_deterministic = args.deterministic_solving
            args.deterministic_solving = deterministic_solving
        if not use_device:
            # pooled workers must not contend for the one accelerator;
            # any prepass outcome arrives via the payload (injected) or
            # the post-pool witness merge — device paths stay parent-only.
            # Restored on exit: host-only corpus legs can run in-parent
            # (single process) and must not degrade later analyses in
            # the same process through the shared Args singleton.
            restore_device_args = (args.device_prepass, args.device_solving)
            args.device_prepass = "never"
            args.device_solving = "never"

        from mythril_tpu.observe.spans import trace

        contract = EVMContract(
            code=code or "", creation_code=creation_code or "", name=name
        )
        with trace("contract.analyze", contract=name):
            sym = SymExecWrapper(
                contract,
                address,
                strategy,
                max_depth=max_depth,
                execution_timeout=execution_timeout,
                loop_bound=loop_bound,
                create_timeout=create_timeout,
                transaction_count=transaction_count,
                modules=modules,
                compulsory_statespace=False,
                prepass_outcome=prepass_outcome,
            )
            issues = fire_lasers(sym, modules)
        exploration = getattr(sym, "device_exploration", None)
        from mythril_tpu.support.phase_profile import PhaseProfile

        return {
            "name": name,
            "issues": [issue.as_dict for issue in issues],
            "states": sym.laser.total_states,
            "device_prepass": exploration["stats"] if exploration else None,
            "phases": PhaseProfile().as_dict(),
            "precovered_skips": sym.laser.device_precovered_skips,
            # the budget that cut the walk ("execution" or "create"),
            # None when it ran to its end
            "cut": sym.laser.budget_cut,
            "wall_s": round(time.perf_counter() - t_start, 3),
            "error": None,
        }
    except Exception:
        return {
            "name": name,
            "issues": [],
            "states": 0,
            "wall_s": round(time.perf_counter() - t_start, 3),
            "error": traceback.format_exc(),
        }
    finally:
        if restore_device_args is not None and args is not None:
            args.device_prepass, args.device_solving = restore_device_args
        if restore_deterministic is not None and args is not None:
            args.deterministic_solving = restore_deterministic


#: the walker process (service/walkers.py) that host walks on the
#: calling thread go to; unset, they run in this process
_BOUND = threading.local()


@contextlib.contextmanager
def walker_bound(walker):
    """Send this thread's `analyze_one_payload` calls to `walker` (a
    `service.walkers.Walker`; None keeps them in this process)."""
    _BOUND.walker = walker
    try:
        yield
    finally:
        _BOUND.walker = None


def analyze_one_payload(payload: Tuple) -> Dict:
    """The per-contract pipeline the corpus pool runs, for the analysis
    service (mythril_tpu/service/engine.py): it feeds finished device
    stripes through it, so the payload contract is shared, not
    duplicated. The walk runs in the walker bound to the calling
    thread (`walker_bound`), or else in this process."""
    walker = getattr(_BOUND, "walker", None)
    if walker is None:
        return _analyze_one(payload)
    return walker.walk(payload)


def analyze_corpus(
    contracts: List[Tuple[str, str, str]],
    address: int = 0x901D573B8CE8C997DE5F19173C32D966B4Fa55FE,
    strategy: str = "bfs",
    transaction_count: int = 2,
    execution_timeout: int = 60,
    create_timeout: int = 10,
    max_depth: int = 128,
    loop_bound: int = 3,
    modules: Optional[List[str]] = None,
    solver_timeout: Optional[int] = None,
    processes: Optional[int] = None,
    use_device: Optional[bool] = None,
    device_budget_s: Optional[float] = None,
    deterministic_solving: Optional[bool] = None,
    deadline_s: Optional[float] = None,
    on_timeout: str = "partial",
    devices: Optional[int] = None,
    store_dir: Optional[str] = None,
    store: Optional[bool] = None,
    router_dir: Optional[str] = None,
    router: Optional[bool] = None,
    _flag_scoped: bool = False,
) -> List[Dict]:
    """Analyze `contracts` = [(runtime_code_hex, creation_code_hex,
    name), ...]: one striped device prepass in this process plus the
    per-contract host pipeline — sequential with outcome injection when
    single-process, overlapped with a worker pool (witnesses merged
    afterward) otherwise. Returns one result dict per contract
    ({name, issues, error, device_prepass, phases, complete}).

    Resource exhaustion is an OUTCOME here, not a crash: the supervisor
    (support/resilience.py) is consulted at every contract boundary.
    With `deadline_s` (falling back to the process-global run deadline)
    an expired budget — or a delivered SIGINT/SIGTERM — stops launching
    new work; already-harvested device witnesses still merge into the
    skipped contracts' slots, each result says whether it is
    `complete`, and `on_timeout` picks between the partial result list
    (default) and a DeadlineExpiredError. Signal handling is the
    CALLER's choice: enter `resilience.graceful_shutdown()` around this
    call (the CLI and the fault harness do) to convert SIGINT/SIGTERM
    into the graceful partial-run stop instead of process death."""
    from mythril_tpu.support import resilience

    processes = processes or min(len(contracts), _effective_cpus())
    deadline = (
        resilience.run_deadline()
        if deadline_s is None
        else resilience.Deadline(deadline_s, label="corpus")
    )
    if deterministic_solving is not None and not _flag_scoped:
        # The flag must also govern the PARENT-side device prepass
        # (flip solving + witness banking run in this process, not in
        # _analyze_one), so it is scoped to this call with a restore on
        # every exit path. Spawned workers (fresh processes, default
        # Args) still get it via the payload, hence the parameter is
        # threaded through the recursion too.
        from mythril_tpu.support.support_args import args as _args

        _restore_det = _args.deterministic_solving
        _args.deterministic_solving = deterministic_solving
        try:
            return analyze_corpus(
                contracts,
                address=address,
                strategy=strategy,
                transaction_count=transaction_count,
                execution_timeout=execution_timeout,
                create_timeout=create_timeout,
                max_depth=max_depth,
                loop_bound=loop_bound,
                modules=modules,
                solver_timeout=solver_timeout,
                processes=processes,
                use_device=use_device,
                device_budget_s=device_budget_s,
                deterministic_solving=deterministic_solving,
                deadline_s=deadline_s,
                on_timeout=on_timeout,
                devices=devices,
                store_dir=store_dir,
                store=store,
                router_dir=router_dir,
                router=router,
                _flag_scoped=True,
            )
        finally:
            _args.deterministic_solving = _restore_det
    if use_device is None:
        # the device axis is on whenever an accelerator is present —
        # the PARENT owns the chip, so pooling does not disable it
        from mythril_tpu.support.accel import accelerator_present

        use_device = accelerator_present()

    # tier 1+2 of the verdict store (mythril_tpu/store): exact
    # (codehash, config-fingerprint) hits settle HERE in microseconds
    # with the banked issue set; near-duplicates get an incremental
    # plan that masks their unchanged selectors out of the device
    # exploration and pre-banks the untouched functions' issues
    from mythril_tpu.analysis.static import (
        static_answer_enabled,
        static_prune_enabled,
    )
    from mythril_tpu.analysis.static.summary import (
        analysis_config_fingerprint,
    )

    config_fp = analysis_config_fingerprint(
        modules=modules,
        transaction_count=transaction_count,
        solver_timeout=solver_timeout,
        create_timeout=create_timeout,
    )
    # corpus-mode cross-contract linking (analysis/static/linkset.py),
    # BEFORE the store triage and the prepass: the resolved call graph
    # feeds (a) the linked-fingerprint diff that catches "same proxy
    # bytes, upgraded implementation" exact hits, (b) per-result link
    # meta in the jsonv2 report, (c) routing-log v4 features
    linkset = None
    if static_prune_enabled() and contracts:
        try:
            from mythril_tpu.analysis.static import link_corpus

            linkset = link_corpus(contracts)
            link_stats = linkset.stats()
            log.info(
                "Link pass: %d node(s), %d/%d edge(s) resolved, "
                "%d proxy pair(s) in %.1fms",
                link_stats["nodes"],
                link_stats["edges_resolved"],
                link_stats["edges"],
                link_stats["proxy_pairs"],
                link_stats["wall_ms"],
            )
        except Exception:
            linkset = None
            log.debug("corpus link pass failed", exc_info=True)
    vstore = None
    if store is not False:
        try:
            from mythril_tpu.store import configured_store

            vstore = configured_store(store_dir)
        except Exception:
            log.debug("verdict store unavailable", exc_info=True)
    store_answers, store_plans = _store_triage(
        contracts, vstore, config_fp, linkset=linkset
    )
    selector_masks = {
        i: (plan.mask_selectors, plan.mask_directions)
        for i, plan in store_plans.items()
    } or None

    # the static-answer triage tier: contracts the semantic screen
    # settles are answered HERE (microseconds) and excluded from the
    # device prepass — the prepass sees their rows as non-runnable so
    # the index mapping every consumer shares stays intact
    static_answers: Dict[int, Dict] = (
        _static_triage(contracts, skip=frozenset(store_answers))
        if static_answer_enabled()
        else {}
    )
    prepass_rows = list(contracts)
    for i in list(static_answers) + list(store_answers):
        prepass_rows[i] = ("", contracts[i][1], contracts[i][2])

    # The learned tier-ladder router (mythril_tpu/routing): for every
    # contract the triage tiers did NOT settle, price host-walk vs
    # device-waves from the routing features and keep host-routed rows
    # OUT of the device prepass — the prepass budget scales with the
    # RUNNABLE row count, so cheap contracts the walk converges on in
    # milliseconds stop billing device waves. Router absent / refused
    # / --no-router: the plan stays empty and this whole block is a
    # no-op — today's routes, bit for bit. Mis-routes are repaired
    # in-flight by _promote_overruns below.
    route_plan: Dict[int, str] = {}
    route_decisions: Dict[int, object] = {}
    corpus_router = None
    if router is not False and use_device:
        try:
            from mythril_tpu.routing import router as _routing_rt

            corpus_router = (
                _routing_rt.load_router(router_dir)
                if router_dir
                else _routing_rt.configured_router()
            )
        except Exception:
            corpus_router = None
            log.debug("router load failed", exc_info=True)
    if corpus_router is not None:
        from mythril_tpu import observe as _obs

        for i, (code, _creation, _name) in enumerate(contracts):
            if i in static_answers or i in store_answers:
                continue
            code_norm = code[2:] if code.startswith("0x") else code
            if len(code_norm) < 8:
                continue  # not a runnable prepass row anyway
            try:
                link_meta = None
                if linkset is not None:
                    import hashlib as _hl

                    link_meta = linkset.node_meta(
                        "0x" + _hl.sha256(
                            bytes.fromhex(code_norm)
                        ).hexdigest()
                    )
                decision = corpus_router.decide(
                    _obs.routing_features_for(code, link=link_meta),
                    tiers=["host-walk", "device-waves"],
                )
            except Exception:
                log.debug("route decision failed", exc_info=True)
                continue
            if decision is None:
                continue
            route_plan[i] = decision.route
            route_decisions[i] = decision
            if decision.route == "host-walk":
                prepass_rows[i] = ("", contracts[i][1], contracts[i][2])
        if route_plan:
            log.info(
                "Router v%d: %d host-walk / %d device-waves of %d "
                "routable contract(s)",
                corpus_router.version,
                sum(1 for r in route_plan.values() if r == "host-walk"),
                sum(1 for r in route_plan.values() if r == "device-waves"),
                len(route_plan),
            )

    single_process = processes <= 1 or len(contracts) == 1

    def payload(code, creation_code, name, worker_device, outcome):
        return (
            code,
            creation_code,
            name,
            address,
            strategy,
            transaction_count,
            execution_timeout,
            create_timeout,
            max_depth,
            loop_bound,
            modules,
            solver_timeout,
            worker_device,
            outcome,
            deterministic_solving,
        )

    prepass: Dict[str, Dict] = {}
    if single_process:
        # Sequential hosts: the striped device prepass OVERLAPS the
        # per-contract analyses — a prepass thread runs the waves (pure
        # device work) while the main thread analyzes, and both sides
        # take HOST_SYMBOLIC_LOCK around host symbolic state (the term
        # arena and the incremental CDCL session are process-global —
        # support/host_lock.py). Contracts reached after the prepass
        # lands get its outcome injected (witness issues,
        # coverage-guided pruning); earlier ones pick up their
        # witnesses in the post-merge, same as the pooled path.
        # Overlap needs either a second core or a corpus long enough
        # to amortize the tax: a wave's host-side dispatch/sync work
        # contends with the analyses on a 1-core box (measured: a
        # budget-bound contract analyzed beside a live prepass thread
        # loses ~30% of its explored states on a 13-fixture corpus),
        # but the waves are device-bound (~2.7s of GIL-held work per
        # ~33s wave at corpus sizes), so from OVERLAP_MIN_CORPUS
        # contracts the chip rides along ~free while the CPU
        # analyzes. Below that, single-core hosts — and lone
        # contracts, which have nothing to overlap with — run the
        # prepass FIRST, uncontended, then analyze with the final
        # outcome injected.
        if use_device and len(contracts) > 1 and (
            _effective_cpus() > 1
            or len(_runnable_rows(prepass_rows)) >= OVERLAP_MIN_CORPUS
        ):
            pre = OverlappedPrepass(
                prepass_rows,
                address,
                transaction_count,
                device_budget_s,
                execution_timeout=execution_timeout,
                ownership=_ownership_enabled(use_device),
                deadline=deadline,
                mesh_groups=devices,
                selector_masks=selector_masks,
            )
            # Smallest code first: cheap analyses (which converge well
            # inside their budgets regardless of contention) soak up
            # the prepass's busy window, so the budget-bound
            # heavyweights run after it finishes — on an uncontended
            # core and with the FINAL prepass outcome instead of a
            # partial. Measured on the 13-fixture corpus (1-core box):
            # scheduling the largest contract first instead cost it
            # ~30% of its explored states to prepass-thread contention.
            order = sorted(
                range(len(contracts)), key=lambda i: len(contracts[i][0])
            )
            # Overlap window: cheap analyses share the (single) core
            # with the prepass for about its active budget, then one
            # drain lets it finish uncontended. Past the window every
            # remaining contract runs on a quiet core — measured: a
            # budget-bound contract analyzed beside a live prepass
            # thread loses ~30% of its explored states to contention.
            # Sized from the RUNNABLE count (the same filter
            # corpus_device_prepass applies) so rows with no runtime
            # code don't inflate the contended period. Large corpora
            # get a 2x window: their waves bill active time at nearly
            # wall rate (flip bursts wait for the lock at most once
            # per wave), so by 2x the budget the prepass has finished
            # on its own and the drain is a no-op instead of a
            # main-thread stall on pure device work.
            n_run = max(1, len(_runnable_rows(prepass_rows)))
            overlap_window_s = (
                2.0 if n_run >= OVERLAP_MIN_CORPUS else 1.25
            ) * resolve_prepass_budget_s(
                n_run,
                device_budget_s,
                execution_timeout=execution_timeout,
                ownership=_ownership_enabled(use_device),
            )
            t_overlap = time.perf_counter()
            own = _ownership_enabled(use_device)
            slots: List[Optional[Dict]] = [None] * len(contracts)
            halt_reason: Optional[str] = None
            try:
                # Ownership-aware scheduling: a contract the running
                # prepass may still freeze as final (no hard gate
                # failure published yet) is DEFERRED rather than
                # walked — walking it now would burn its full budget
                # on work the chip is about to hand over. Clearly
                # unownable contracts (degraded, overflowed) walk
                # immediately and soak the overlap window; once the
                # prepass ends (or the window drains it), everything
                # left resolves against final outcomes.
                pending = list(order)
                while pending:
                    progressed = False
                    deferred: List[int] = []
                    for i in pending:
                        # the supervisor boundary: an expired deadline
                        # or a delivered signal stops LAUNCHING work;
                        # everything already harvested keeps flowing
                        # into the partial report below
                        resilience.inject("corpus.contract")
                        if halt_reason is None:
                            halt_reason = resilience.interrupted_reason(
                                deadline
                            )
                        code, creation_code, name = contracts[i]
                        if i in store_answers:
                            # exact store hit: the banked verdict is
                            # the analysis — survives a deadline halt
                            # like the static answers below
                            slots[i] = store_answers[i]
                            progressed = True
                            continue
                        if i in static_answers:
                            # statically answered: the empty issue set
                            # is the analysis — it even survives a
                            # deadline halt (it costs microseconds)
                            slots[i] = static_answers[i]
                            progressed = True
                            continue
                        if halt_reason is not None:
                            slots[i] = _skipped_result(name, halt_reason)
                            progressed = True
                            continue
                        # per-contract, as before the deferral rework:
                        # a long pass over `pending` must still hand
                        # the prepass its uncontended tail past the
                        # overlap window
                        if time.perf_counter() - t_overlap > overlap_window_s:
                            pre.drain()
                        outcome, device_ok = pre.outcome_for(i)
                        if outcome is None and i in store_plans:
                            # no device outcome (yet): the store's
                            # banked coverage for the unchanged
                            # selectors pre-empts walk feasibility
                            # queries instead
                            outcome = store_plans[i].injected_outcome
                        if own and _outcome_owns(outcome):
                            # device-complete contract: evidence IS
                            # the analysis; no walk, no lock, no
                            # solver
                            owned_res = _owned_result(
                                code, creation_code, name, outcome,
                                address,
                            )
                            if owned_res is not None:
                                if i in store_plans:
                                    owned_res = _apply_incremental(
                                        owned_res, store_plans[i]
                                    )
                                slots[i] = owned_res
                                progressed = True
                                continue
                        if (
                            not device_ok
                            and own
                            and _maybe_ownable(outcome)
                            and not pre.drain_abandoned
                        ):
                            # a hung prepass (abandoned drain) will
                            # never publish finality: deferring past it
                            # would spin this loop forever
                            deferred.append(i)
                            continue
                        with pre.lock:
                            slots[i] = _analyze_one(
                                payload(
                                    code,
                                    creation_code,
                                    name,
                                    use_device and device_ok,
                                    outcome,
                                )
                            )
                        if i in store_plans:
                            slots[i] = _apply_incremental(
                                slots[i], store_plans[i]
                            )
                        pre.yield_lock()
                        progressed = True
                    pending = deferred
                    if pending and not progressed:
                        # only deferred work left: let the prepass run
                        # uncontended and poll its published finality
                        time.sleep(1.0)
                results = slots
            finally:
                # an exception (including a caller's alarm/deadline)
                # must not orphan the prepass thread mid-wave: it would
                # keep the chip and the host lock busy under whatever
                # the caller measures next
                prepass = pre.finish()
        else:
            if use_device:
                prepass = corpus_device_prepass(
                    prepass_rows,
                    budget_s=device_budget_s,
                    address=address,
                    transaction_count=transaction_count,
                    execution_timeout=execution_timeout,
                    ownership=_ownership_enabled(use_device),
                    deadline=deadline,
                    stop_event=resilience.shutdown_event(),
                    mesh_groups=devices,
                    selector_masks=selector_masks,
                )
            own = _ownership_enabled(use_device)
            results = []
            halt_reason = None
            for i, (code, creation_code, name) in enumerate(contracts):
                resilience.inject("corpus.contract")
                if halt_reason is None:
                    halt_reason = resilience.interrupted_reason(deadline)
                if i in store_answers:
                    results.append(store_answers[i])
                    continue
                if i in static_answers:
                    results.append(static_answers[i])
                    continue
                if halt_reason is not None:
                    # device-owned evidence survives the halt: synthesis
                    # is cheap (no walk, no solver), so an owned
                    # contract still reports in full
                    owned_res = (
                        _owned_result(
                            code, creation_code, name, prepass[i], address
                        )
                        if own and _outcome_owns(prepass.get(i))
                        else None
                    )
                    results.append(
                        owned_res
                        if owned_res is not None
                        else _skipped_result(name, halt_reason)
                    )
                    continue
                owned_res = (
                    _owned_result(
                        code, creation_code, name, prepass[i], address
                    )
                    if own and _outcome_owns(prepass.get(i))
                    else None
                )
                if owned_res is None:
                    outcome = prepass.get(i)
                    if outcome is None and i in store_plans:
                        outcome = store_plans[i].injected_outcome
                    owned_res = _analyze_one(
                        payload(
                            code,
                            creation_code,
                            name,
                            use_device,
                            outcome,
                        )
                    )
                if i in store_plans:
                    owned_res = _apply_incremental(
                        owned_res, store_plans[i]
                    )
                results.append(owned_res)
    else:
        # pooled hosts: the prepass likewise overlaps the worker pool;
        # witnesses merge in when both finish. Results are collected
        # INCREMENTALLY (imap preserves order) so a deadline or a
        # signal keeps everything finished so far and marks only the
        # tail skipped — map_async's all-or-nothing get() would lose
        # the whole pool on a timeout.
        payloads = [
            payload(
                code,
                creation_code,
                name,
                False,
                (
                    store_plans[i].injected_outcome
                    if i in store_plans
                    else None
                ),
            )
            for i, (code, creation_code, name) in enumerate(contracts)
            if i not in static_answers and i not in store_answers
        ]
        with _host_worker_pool(processes) as pool:
            walked = pool.imap(_analyze_one, payloads)
            if use_device:
                prepass = corpus_device_prepass(
                    prepass_rows,
                    budget_s=device_budget_s,
                    address=address,
                    transaction_count=transaction_count,
                    deadline=deadline,
                    stop_event=resilience.shutdown_event(),
                    mesh_groups=devices,
                    selector_masks=selector_masks,
                )
            results = []
            halt_reason = None
            for i, (code, _creation, name) in enumerate(contracts):
                if i in store_answers:
                    results.append(store_answers[i])
                    continue
                if i in static_answers:
                    results.append(static_answers[i])
                    continue
                if halt_reason is None:
                    halt_reason = resilience.interrupted_reason(deadline)
                if halt_reason is None:
                    try:
                        walked_res = (
                            walked.next()
                            if deadline is None
                            else walked.next(max(0.1, deadline.remaining))
                        )
                        if i in store_plans:
                            walked_res = _apply_incremental(
                                walked_res, store_plans[i]
                            )
                        results.append(walked_res)
                        continue
                    except mp.TimeoutError:
                        halt_reason = (
                            resilience.interrupted_reason(deadline)
                            or "deadline-expired"
                        )
                results.append(_skipped_result(name, halt_reason))
            if halt_reason is not None:
                # in-flight workers past the deadline: stop them now
                pool.terminate()
    if prepass:
        _merge_prepass_witnesses(results, contracts, prepass, address)
    if route_plan:
        _promote_overruns(
            results,
            contracts,
            route_plan,
            route_decisions,
            corpus_router,
            address=address,
            transaction_count=transaction_count,
            execution_timeout=execution_timeout,
            use_device=use_device,
            devices=devices,
            deadline=deadline,
        )
    try:
        # one saturation sample at the run boundary: batch runs get
        # the same mtpu_device_* gauges the serve sampler keeps live
        from mythril_tpu import observe as _observe

        _observe.device_monitor().sample()
    except Exception:
        log.debug("device monitor sample failed", exc_info=True)
    skipped = 0
    for result in results:
        if result is None:
            continue
        # per-contract completion status, first-class in the result
        # (and from there in the json/jsonv2 report meta): a partial
        # run SAYS which contracts it covered
        result["complete"] = (
            not result.get("skipped") and result.get("error") is None
        )
        skipped += bool(result.get("skipped"))
    # tier 3: every completed full analysis becomes a store entry —
    # the write that turns this run's compute into the next run's
    # admission-time answer
    if vstore is not None:
        _store_writeback(
            results, contracts, prepass, vstore, config_fp,
            linkset=linkset,
        )
    if linkset is not None:
        _attach_link_meta(results, contracts, linkset)
    # router decisions feed their own training data (satellite 2):
    # planned rows settle as routed-<tier> / promoted-<tier> in the
    # routing JSONL. Stamped AFTER the store writeback so banked
    # verdicts stay route-free (a store hit replays as store-hit).
    for i, planned in route_plan.items():
        result = results[i] if i < len(results) else None
        if result is None or result.get("skipped") or result.get("promoted"):
            continue
        result["routed"] = planned
    _emit_routing_records(results, contracts, linkset=linkset)
    if skipped and on_timeout == "fail":
        from mythril_tpu.exceptions import DeadlineExpiredError

        raise DeadlineExpiredError(
            f"{skipped}/{len(contracts)} contract(s) unanalyzed at the "
            "deadline (--on-timeout=fail)"
        )
    return results


def _attach_link_meta(
    results: List[Optional[Dict]],
    contracts: List[Tuple[str, str, str]],
    linkset,
) -> None:
    """Per-result cross-contract link facts for the jsonv2 report
    meta (and anyone reading the raw result dicts): the compact node
    block plus the corpus-level stats on every row — consumers of one
    contract's report still see the resolve rate the graph achieved."""
    run_stats = None
    try:
        run_stats = linkset.stats()
    except Exception:
        log.debug("link stats failed", exc_info=True)
    import hashlib as _hashlib

    for (code, _creation, _name), result in zip(contracts, results):
        if result is None:
            continue
        try:
            norm = code[2:] if code.startswith("0x") else code
            code_hash = (
                "0x" + _hashlib.sha256(bytes.fromhex(norm)).hexdigest()
            )
        except ValueError:
            continue
        meta = linkset.node_meta(code_hash)
        if meta is None:
            continue
        result["link"] = meta
        if run_stats is not None:
            result["link_run"] = dict(run_stats)


def _emit_routing_records(
    results: List[Dict],
    contracts: List[Tuple[str, str, str]],
    linkset=None,
) -> None:
    """One routing-feature record per analyzed contract
    (observe/routing.py): static features joined with the route taken
    and the outcome — the JSONL training set ROADMAP item 5's cost
    model needs. Never fatal; a record failure loses one row, not the
    run."""
    from mythril_tpu import observe

    if not observe.enabled():
        return
    import hashlib

    for (code, _creation, name), result in zip(contracts, results):
        if result is None:
            continue
        try:
            code_norm = code[2:] if code.startswith("0x") else code
            try:
                digest = hashlib.sha256(
                    bytes.fromhex(code_norm or "")
                ).hexdigest()
            except ValueError:
                digest = ""
            outcome = observe.routing_outcome_for(result)
            # every record gets a journey skeleton: corpus analyses
            # have no HTTP job id, so the id is minted here and the
            # route lands as the timeline's middle tier — the same
            # features ⨝ route ⨝ outcome ⨝ timeline join key the
            # service emits (observe/journey.py)
            journey_id = observe.new_journey_id()
            observe.journey_event(
                journey_id, "admission", "corpus", contract=name,
            )
            observe.journey_event(
                journey_id, outcome.get("route", "?"), "routed",
                wall_s=outcome.get("wall_s"),
            )
            observe.journey_event(
                journey_id, "settle",
                "done" if not outcome.get("error") else "failed",
                issues=outcome.get("issues"),
            )
            link_meta = None
            if linkset is not None:
                try:
                    link_meta = linkset.node_meta("0x" + digest)
                except Exception:
                    link_meta = None
            observe.routing_log().record(
                contract=name,
                code_hash=digest,
                features=observe.routing_features_for(
                    code_norm, link=link_meta
                ),
                outcome=outcome,
                journey_id=journey_id,
            )
        except Exception:
            log.debug("routing record failed for %s", name, exc_info=True)


def _promote_overruns(
    results: List[Optional[Dict]],
    contracts: List[Tuple[str, str, str]],
    route_plan: Dict[int, str],
    route_decisions: Dict[int, object],
    corpus_router,
    address: int,
    transaction_count: int,
    execution_timeout: int,
    use_device: bool,
    devices: Optional[int],
    deadline,
) -> None:
    """The router's in-flight repair tier: a host-routed contract
    whose walk errored or overran the decision's predicted budget
    (`RouteDecision.budget_s` — slack times the predicted wall) was
    mis-routed, so it gets the device waves it was denied: one small
    prepass over just the overrun rows, witnesses merged in place, the
    result stamped ``promoted`` (the routing record settles as
    ``promoted-device-waves``, its own outcome class, so the trainer
    prices the mis-route). Regret — wall actually burnt beyond the
    budget — feeds mtpu_router_regret_seconds_total."""
    from mythril_tpu.support import resilience

    if not use_device or resilience.interrupted_reason(deadline) is not None:
        return
    overrun: List[int] = []
    for i, planned in route_plan.items():
        if planned != "host-walk":
            continue
        result = results[i] if i < len(results) else None
        if result is None or result.get("skipped"):
            continue
        decision = route_decisions.get(i)
        budget = decision.budget_s() if decision is not None else 0.0
        wall = result.get("wall_s") or 0.0
        if result.get("error") is not None or (budget and wall > budget):
            overrun.append(i)
            if corpus_router is not None and budget and wall > budget:
                corpus_router.note_regret(wall - budget)
    if not overrun:
        return
    promo_rows: List[Tuple[str, str, str]] = [
        (
            contracts[i][0] if i in overrun else "",
            contracts[i][1],
            contracts[i][2],
        )
        for i in range(len(contracts))
    ]
    try:
        promo = corpus_device_prepass(
            promo_rows,
            address=address,
            transaction_count=transaction_count,
            execution_timeout=execution_timeout,
            ownership=False,
            deadline=deadline,
            stop_event=resilience.shutdown_event(),
            mesh_groups=devices,
        )
    except Exception:
        log.debug("promotion prepass failed", exc_info=True)
        return
    _merge_prepass_witnesses(results, contracts, promo, address)
    for i in overrun:
        result = results[i]
        if result is not None:
            result["promoted"] = "device-waves"
            if corpus_router is not None:
                corpus_router.note_promotion("host-walk", "device-waves")


def _merge_prepass_witnesses(
    results: List[Dict],
    contracts: List[Tuple[str, str, str]],
    prepass: Dict[int, Dict],
    address: int,
) -> None:
    """Fold the device prepass's banked witnesses into the pooled
    results: per contract (by position — pool.map preserves order),
    attach the prepass counters and append witness issues for
    locations no host worker reported."""
    from mythril_tpu.analysis.prepass import witness_issues
    from mythril_tpu.ethereum.evmcontract import EVMContract

    for i, (code, _creation, name) in enumerate(contracts):
        outcome = prepass.get(i)
        result = results[i] if i < len(results) else None
        if outcome is None or result is None:
            continue
        if result.get("owned"):
            continue  # issues ARE the witnesses; nothing to merge
        result["device_prepass"] = outcome["stats"]
        try:
            contract = EVMContract(code=code or "", name=name)
            fresh = witness_issues(contract, outcome, address)
        except Exception:
            log.debug("witness merge failed for %s", name, exc_info=True)
            continue
        seen = {(i.get("address"), i.get("swc-id")) for i in result["issues"]}
        extra = [
            issue.as_dict
            for issue in fresh
            if (issue.address, issue.swc_id) not in seen
        ]
        if extra:
            log.info(
                "Device prepass contributed %d issue(s) to %s that the "
                "host walk did not find",
                len(extra),
                name,
            )
            result["issues"].extend(extra)
            outcome["stats"]["witness_issues"] = len(extra)


def mesh_explore_corpus(
    contracts: List[Tuple[str, str, str]],
    n_devices: Optional[int] = None,
    lanes_per_contract: int = 16,
    max_steps: int = 2048,
    calldata_len: int = 68,
    seed: int = 7,
) -> Dict:
    """Corpus exploration sharded over a device mesh (SURVEY §2.4's
    per-contract-loop axis): every contract becomes a stripe of lanes
    with distinct calldata seeds, the whole wave is one lane-sharded
    StateBatch, and the mesh splits it over the dp axis — the batched
    replacement for the reference's sequential per-contract loop.

    Returns {lane_steps, wall_s, lane_steps_per_sec, contracts,
    lanes, coverage} — used by tools/corpus_bench.py --mesh.
    """
    import random
    import time as _time

    import numpy as np

    from mythril_tpu.laser.batch.run import run
    from mythril_tpu.laser.batch.seeds import code_cap_bucket, selector_seeds
    from mythril_tpu.laser.batch.state import make_batch, make_code_table
    from mythril_tpu.parallel import make_mesh, replicate_table, shard_batch

    rng = random.Random(seed)
    codes = []
    seeds_per_code = []
    for runtime_hex, _creation, _name in contracts:
        runtime_hex = runtime_hex[2:] if runtime_hex.startswith("0x") else runtime_hex
        codes.append(bytes.fromhex(runtime_hex))
        seeds_per_code.append(
            selector_seeds(runtime_hex, lanes_per_contract, calldata_len, rng)
        )

    cap = code_cap_bucket(max(len(c) for c in codes))
    table = make_code_table(codes, code_cap=cap)

    mesh = make_mesh(n_devices)
    n_dev = mesh.devices.size
    n_lanes = len(codes) * lanes_per_contract
    pad = (-n_lanes) % n_dev
    code_ids = np.array(
        [i for i in range(len(codes)) for _ in range(lanes_per_contract)]
        + [0] * pad,
        dtype=np.int32,
    )
    calldata = [d for seeds in seeds_per_code for d in seeds]
    calldata += [b"\x00" * calldata_len] * pad

    batch = make_batch(len(code_ids), code_ids=code_ids, calldata=calldata)
    batch = shard_batch(batch, mesh)
    table = replicate_table(table, mesh)

    # warm the jit cache with the SAME static args (max_steps is a
    # static jit argument — a different value compiles a different
    # executable) so the measurement is execution, not compile
    warm, _ = run(batch, table, max_steps=max_steps)
    np.asarray(warm.pc)[:1]

    t0 = _time.perf_counter()
    out, steps = run(batch, table, max_steps=max_steps)
    seen_host = np.asarray(out.pc_seen)  # the device->host sync point
    wall = _time.perf_counter() - t0
    covered = int(
        (np.unpackbits(seen_host.view(np.uint8), axis=-1) != 0).sum()
    )

    lane_steps = int(steps) * len(code_ids)
    return {
        "devices": int(n_dev),
        "contracts": len(codes),
        "lanes": len(code_ids),
        "steps": int(steps),
        "lane_steps": lane_steps,
        "wall_s": round(wall, 3),
        "lane_steps_per_sec": round(lane_steps / wall, 1),
        "covered_pc_bits": covered,
    }
