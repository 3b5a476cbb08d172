"""Device-led symbolic exploration.

The generational frontier loop over the symbolic batch engine
(symbolic.py): the device executes a wave of lanes and *constructs the
path constraints on device* (expression arena); the host decodes only
the frontier branches it wants to flip, solves for a witness (CDCL
sprint first, on-chip portfolio for the queries it can't finish), and
seeds the next wave with the witnesses. Forking at a symbolic JUMPI is
the flip; dead lanes are compacted away simply by not reseeding them.

The engine is corpus-shaped: `DeviceCorpusExplorer` stripes N
contracts across one StateBatch (contract i owns a contiguous block of
lanes) so a whole corpus advances in a single jit'd wave — the batched
replacement for the reference's sequential per-contract loop
(mythril/mythril/mythril_analyzer.py:145-185). `DeviceSymbolicExplorer`
is the single-contract view the per-contract analysis path uses.

Exploration is multi-transaction (reference threat model:
mythril/laser/ethereum/svm.py:189-219 drives `-t` symbolic attacker
transactions): a successful lane whose storage journal gained writes
becomes a *carry* — its journal is the next transaction's start state
(make_batch storage_seed) and its calldata joins the witness prefix.

The reference's frontier pruners map onto the carry step (SURVEY §2.4
"pruners as lane masks"):
- mutation pruner (mutation_pruner.py:22-89): non-mutating zero-value
  end states never become carries — identical drop rule, as a filter;
- dependency pruner: carry dedup by canonicalized journal collapses
  the states whose tx-N writes are indistinguishable to tx N+1;
- call-depth limiter: structurally moot on device — CALL-family
  opcodes hand the lane to the host (UNSUPPORTED), so device lanes
  never nest frames.

Compare analysis/hybrid_fuzz.py, whose flips re-execute the whole path
prefix through the host object engine — here the arena replaces that
host replay, so the per-flip cost is one term decode + one solver
call, and the stepping work all happened on the TPU.
"""

from __future__ import annotations

import logging
import random
import threading
import time
from typing import Dict, List, Optional, Set, Tuple

import numpy as np

from mythril_tpu.exceptions import (
    DeviceDispatchError,
    SolverTimeOutException,
    UnsatError,
)
from mythril_tpu.laser.batch.arena import ArenaView
from mythril_tpu.laser.batch.checkpoint import (
    WaveCheckpointWriter,
    save_checkpoint,
)
from mythril_tpu.laser.batch.state import (
    Status,
    make_batch,
    make_code_table,
    storage_dict_from,
)
from mythril_tpu.laser.batch.symbolic import (
    make_sym_batch,
    reseed_wave,
    reseed_wave_donated,
    sym_run,
    sym_run_donated,
)
from mythril_tpu.laser.smt.solver import capture as query_capture
from mythril_tpu.laser.smt.solver.portfolio import device_solve_batch
from mythril_tpu.laser.smt.solver.solver import lower
from mythril_tpu.observe.querylog import QUERY_ORIGIN_FLIP, query_context
from mythril_tpu.observe.solverstats import ORIGIN_DEVICE, record_query
from mythril_tpu.observe.spans import flight_recorder, trace
from mythril_tpu.support.model import get_model

log = logging.getLogger(__name__)

DEFAULT_CALLER = 0xDEADBEEFDEADBEEFDEADBEEFDEADBEEFDEADBEEF
DEFAULT_ADDRESS = 0x901D573B8CE8C997DE5F19173C32D966B4FA55FE

# the jsonv2 replay block context (analysis/report.py
# REPLAY_BLOCK_CONTEXT): the explorer executes under the SAME concrete
# environment the report claims for its test cases, so a banked
# witness replays by construction — even for asserts gated on
# ADDRESS/TIMESTAMP/NUMBER/BALANCE
REPLAY_ENV = {
    "timestamp": 0x5BFA4639,
    "number": 0x66E393,
    "gasprice": 0x773594000,
    "balance": 0,
}

TRIGGER_KINDS = {
    Status.INVALID: "assert-violation",
    Status.ERR_JUMP: "invalid-jump",
    Status.ERR_STACK: "stack-error",
    Status.KILLED: "selfdestruct",
}

#: event-kind byte -> call mnemonic (symbolic.py EV_*)
CALL_EVENT_KINDS = {4: "CALL", 5: "CALLCODE", 6: "DELEGATECALL", 7: "STATICCALL"}
WRAP_EVENT_OPS = {1: "addition", 2: "subtraction", 3: "multiplication"}
#: env sources the predictable-vars module hooks (DIFFICULTY is a leaf
#: for flippability but the reference module does not report it)
PREDICTABLE_SRCS = ("TIMESTAMP", "NUMBER", "COINBASE", "GASLIMIT", "BLOCKHASH")
GAS_STIPEND = 2300

#: carried next-transaction start states kept per contract per phase
CARRY_CAP = 16

#: the adversarial values poisoned-storage carries seed into observed
#: slots — the concolic stand-in for the host engine's symbolic
#: initial storage ("the contract may be in any prior state"). Two
#: carries per contract: MAX makes guarded reads pass and
#: receiving-side adds wrap (SWC-101); the attacker's address makes
#: storage-held callees resolve to the attacker (SWC-105/107/112 —
#: the reference solves `storage_slot == attacker` the same way).
POISON_VALUE = 2**256 - 1
POISON_ADDR = DEFAULT_CALLER
#: observed-slot cap per contract (per poison carry, many slots)
POISON_SLOTS = 8

#: msg.value seeded on the callvalue-axis carries of contracts whose
#: code reads CALLVALUE — the concolic stand-in for the host's
#: symbolic call value (1 ETH: passes `msg.value > 0` guards, small
#: enough that profit gates stay meaningful)
CALLVALUE_SEED = 10**18


class ExploreStats:
    """Counters proving the device did the stepping."""

    def __init__(self) -> None:
        # lane-steps executed on device, counting only lanes that were
        # still RUNNING at each step (the while_loop's own knowledge);
        # the raw product steps x lanes — which overcounts the halted
        # tail — is kept beside it for the utilization comparison
        self.device_steps = 0
        self.device_steps_raw = 0
        self.waves = 0
        self.transactions = 0  # deepest transaction index reached (1-based)
        self.arena_nodes = 0
        self.forks_tried = 0
        self.forks_feasible = 0
        # flip-witness sources in the DEVICE-FIRST funnel (ISSUE 9):
        # the batched on-chip dispatch answers first (device_sat, and
        # device_unsat for enumeration-owned unsats); the incremental
        # CDCL session is the escalation ladder behind it (host_sat)
        self.device_sat = 0
        self.device_unsat = 0
        #: queries decided by exhaustive enumeration (complete small
        #: spaces — the only device-owned unsat mode)
        self.device_enumerated = 0
        #: queries whose witness came from the cube-and-conquer fan
        self.device_cube_sat = 0
        self.host_sat = 0
        self.branches_covered = 0
        self.carries_banked = 0  # mutating end states promoted to tx N+1
        # device-cap observability: lanes that halted by *degrading* —
        # capacity overflow (ERR_MEM) or an off-device opcode
        # (UNSUPPORTED) — rather than by finishing. These lanes' work
        # falls back to the host engine, so the counters measure how
        # much of the modeled space the lean device caps actually
        # cover on this workload (laser/batch/state.py caps).
        self.lanes_degraded_mem = 0
        self.lanes_degraded_unsupported = 0
        # resilience observability: waves whose dispatch died past the
        # retry ladder (the exploration degraded instead of crashing),
        # and wave checkpoints flushed for resume
        self.device_faults = 0
        self.wave_checkpoints = 0
        # static-prune observability (analysis/static): flip targets
        # the pre-dispatch pass proved dead (never solved), dispatcher
        # seeds dropped for statically-inert functions, and how many
        # contracts carried a static summary at all
        self.static_pruned_flips = 0
        self.static_seeds_dropped = 0
        self.static_summaries = 0
        #: contracts whose semantic screen proved NO detection module
        #: can fire (summary.static_answerable) — the population the
        #: static-answer triage tier settles without any device work
        self.static_answered = 0
        # verdict-store incremental re-analysis (mythril_tpu/store):
        # unchanged-fork selectors whose dispatcher seeds and entry
        # flips this exploration masked — lanes spent only on changed
        # functions
        self.store_masked_selectors = 0
        # -- kernel specialization observability (specialize.py) ------
        #: 1 when the waves ran a contract-specialized kernel
        self.specialized = 0
        #: handler phases the wave kernel elided (union bucket)
        self.spec_pruned_phases = 0
        #: instructions advanced by fused substeps (superblock fusion)
        #: ON TOP of the full-step active count — total instructions
        #: executed is device_steps + spec_fused_steps
        self.spec_fused_steps = 0
        #: wave retries that fell back to the generic kernel (the
        #: resilience ladder never re-dispatches specialized)
        self.spec_fallbacks = 0
        # -- block-level JIT observability (blockjit.py) ---------------
        #: instructions advanced by block substeps ON TOP of the
        #: full-step active count (the blockjit twin of
        #: spec_fused_steps — a wave counts into one or the other,
        #: never both)
        self.blockjit_steps = 0
        #: lowered basic blocks entered through a block head by a
        #: block substep
        self.blockjit_blocks = 0
        #: basic blocks across this exploration's contracts that the
        #: lowering classified NOT lowerable (calls, storage/memory
        #: effects, env reads, unresolved jumps, foreign opcodes) —
        #: those blocks run on the generic per-opcode step, attributed
        #: here, never silently mis-executed
        self.blockjit_fallbacks = 0
        #: this explorer's kernel-cache lookups (process-wide LRU)
        self.kernel_cache_hits = 0
        self.kernel_cache_misses = 0
        #: first-call trace+compile wall of this run's kernel bucket
        self.kernel_compile_s = 0.0
        self.wall_s = 0.0
        # where the prepass wall goes: device wave execution vs host
        # flip solving (the two phases that can dominate)
        self.wave_exec_s = 0.0
        self.flip_solve_s = 0.0
        # -- pipelined wave engine observability ----------------------
        #: 1 when the double-buffered schedule ran this exploration
        self.pipelined = 0
        #: most waves simultaneously in flight (2 = the pipeline)
        self.waves_inflight_max = 0
        #: harvests that ran with another wave executing on device —
        #: the integer the ratio below normalizes (robust to rounding
        #: on tiny workloads)
        self.waves_overlapped = 0
        #: host-side work (evidence consume + flip solving + next-wave
        #: seeding) done WHILE a wave was executing on device
        self.wave_overlap_s = 0.0
        #: host blocked waiting on a wave's readiness (device working,
        #: host idle) / device span from dispatch to readiness
        self.device_wait_s = 0.0
        self.device_busy_s = 0.0
        #: overlap_s / busy_s — the fraction of device execution the
        #: host covered with concurrent work (0 in --no-pipeline runs)
        self.wave_overlap_ratio = 0.0
        #: fraction of the exploration wall with NO wave in flight
        self.device_idle_frac = 0.0
        #: bytes the compacted per-wave readback actually transferred,
        #: and what the full-table transfer would have cost
        self.evidence_bytes = 0
        self.evidence_bytes_full = 0
        self.evidence_bytes_per_wave = 0

    def as_dict(self) -> Dict[str, int]:
        return dict(self.__dict__)


#: Explicit cross-engine merge semantics for EVERY ExploreStats field
#: (plus the optional "halt_reason" the stats dict may carry). The
#: multi-chip scheduler folds per-chunk stats dicts with these rules;
#: before PR 7 it guessed (sum unless listed), and a new counter
#: could silently merge wrong. tests/observe pins that every field has
#: an explicit policy, so adding a stat without deciding its merge is
#: a test failure, not a latent drift.
#:
#:   sum      additive work/byte/fault counters
#:   max      high-water marks and per-run mode flags (1 if ANY chunk
#:            ran pipelined/specialized; the deepest transaction)
#:   derived  ratios recomputed AFTER the merge from merged inputs
#:   last     non-numeric run verdicts (the newest chunk owns them)
MERGE_POLICY: Dict[str, str] = {
    "device_steps": "sum",
    "device_steps_raw": "sum",
    "waves": "sum",
    "transactions": "max",
    "arena_nodes": "max",
    "forks_tried": "sum",
    "forks_feasible": "sum",
    "device_sat": "sum",
    "device_unsat": "sum",
    "device_enumerated": "sum",
    "device_cube_sat": "sum",
    "host_sat": "sum",
    "branches_covered": "sum",
    "carries_banked": "sum",
    "lanes_degraded_mem": "sum",
    "lanes_degraded_unsupported": "sum",
    "device_faults": "sum",
    "wave_checkpoints": "sum",
    "static_pruned_flips": "sum",
    "static_seeds_dropped": "sum",
    "static_summaries": "sum",
    "static_answered": "sum",
    "store_masked_selectors": "sum",
    "specialized": "max",
    "spec_pruned_phases": "max",
    "spec_fused_steps": "sum",
    "spec_fallbacks": "sum",
    "blockjit_steps": "sum",
    "blockjit_blocks": "sum",
    "blockjit_fallbacks": "sum",
    "kernel_cache_hits": "sum",
    "kernel_cache_misses": "sum",
    "kernel_compile_s": "sum",
    "wall_s": "derived",
    "wave_exec_s": "sum",
    "flip_solve_s": "sum",
    "pipelined": "max",
    "waves_inflight_max": "max",
    "waves_overlapped": "sum",
    "wave_overlap_s": "sum",
    "device_wait_s": "sum",
    "device_busy_s": "sum",
    "wave_overlap_ratio": "derived",
    "device_idle_frac": "derived",
    "evidence_bytes": "sum",
    "evidence_bytes_full": "sum",
    "evidence_bytes_per_wave": "derived",
    "halt_reason": "last",
}


def merge_stats(dst: Dict, src: Dict) -> None:
    """Fold one engine's stats dict into `dst` under MERGE_POLICY.
    Unknown numeric keys sum (the policy-pin test keeps the set
    complete for ExploreStats fields); unknown non-numeric keys are
    ignored."""
    for key, value in src.items():
        policy = MERGE_POLICY.get(key)
        if policy == "derived":
            continue
        if policy == "last":
            dst[key] = value
            continue
        if not isinstance(value, (int, float)) or isinstance(value, bool):
            continue
        if policy == "max":
            dst[key] = max(dst.get(key, 0), value)
        else:  # "sum" and unregistered numeric keys
            dst[key] = dst.get(key, 0) + value


def publish_explore_stats(stats: Dict) -> None:
    """Register one finished exploration's counters into the
    process-wide metrics registry (mtpu_explore_*): summing fields
    accumulate as counters, high-water fields as set-max gauges —
    the /metrics view of what ExploreStats reports per run."""
    from mythril_tpu.observe.registry import registry

    reg = registry()
    for key, value in stats.items():
        if not isinstance(value, (int, float)) or isinstance(value, bool):
            continue
        policy = MERGE_POLICY.get(key)
        if policy == "sum":
            reg.counter(
                f"mtpu_explore_{key}_total",
                f"ExploreStats.{key}, accumulated over explorations",
            ).inc(value)
        elif policy == "max":
            reg.gauge(
                f"mtpu_explore_{key}_max",
                f"ExploreStats.{key}, process high-water mark",
            ).set_max(value)
        elif key in ("wave_overlap_ratio", "device_idle_frac"):
            # derived ratios promoted to LIVE gauges (last run wins —
            # a ratio has no meaningful sum): the devicemon sampler
            # additionally recomputes the cumulative view from the
            # summed inputs as mtpu_device_{wave_overlap,idle}_frac
            reg.gauge(
                f"mtpu_explore_{key}",
                f"ExploreStats.{key}, most recent exploration",
            ).set(value)


def required_calldata_len(
    code_hex: str, default: int = 68, cap: int = 480
) -> int:
    """Static scan for the largest PUSH1..PUSH4 immediate that
    directly feeds a CALLDATALOAD, plus a word of margin: contracts
    reading high fixed offsets (hand-rolled dispatchers, packed
    multi-word args) are unreachable past the default 68-byte seed
    window otherwise — their guards could never be covered or flipped
    on device. Bounded by the device calldata envelope."""
    code = bytes.fromhex(code_hex[2:] if code_hex.startswith("0x") else code_hex)
    need = default
    i = 0
    while i < len(code):
        op = code[i]
        if 0x60 <= op <= 0x7F:
            n = op - 0x5F
            if n <= 4 and i + n + 1 < len(code) and code[i + n + 1] == 0x35:
                off = int.from_bytes(code[i + 1 : i + 1 + n], "big")
                if off < cap:
                    need = max(need, off + 36)
            i += 1 + n
        else:
            i += 1
    return min(need, cap)


class _ContractTrack:
    """Per-contract exploration bookkeeping inside the striped batch."""

    def __init__(self, code_hex: str) -> None:
        self.code_hex = code_hex
        #: dispatcher seeds, computed once — selector recovery
        #: disassembles the contract, and doing that per PHASE for a
        #: whole corpus is seconds of GIL time stolen from overlapped
        #: host analyses
        self.selector_seeds: Optional[List[bytes]] = None
        #: static pre-analysis (analysis/static StaticSummary), set by
        #: the explorer when the static prepass is enabled; None means
        #: no pruning and no seed masking for this contract
        self.static = None
        #: the statically-dead branch directions — (jumpi_pc, taken)
        #: pairs the flip loop must never spend a solver attempt on
        self.static_dead: frozenset = frozenset()
        #: this contract's kernel-specialization bucket (step.PhaseSet,
        #: set by the explorer when specialization is on; the wave
        #: kernel is the union over the striped tracks)
        self.phases = None
        self.covered: Set[Tuple[int, bool]] = set()
        self.attempted: Set[Tuple[int, bool]] = set()
        self.corpus: List[Tuple[int, bytes]] = []  # (carry index, calldata)
        #: solver-derived inputs (flip/steer witnesses) — the seeds
        #: worth carrying into the next transaction phase ahead of
        #: mutation filler
        self.flip_corpus: List[bytes] = []
        #: kind -> [{pc, input, prefix, gas_min, gas_max}]; pc is the
        #: faulting instruction (the step kernel pins a halted lane's
        #: pc there), prefix the calldata of the transactions before
        #: the faulting one, gas bounds the lane's accumulated range
        self.triggers: Dict[str, List[Dict]] = {}
        self.exhausted = False  # no flips left last time we looked
        self.parent_inputs: List[bytes] = []  # last phase's distinct inputs
        #: concrete detection evidence, keyed (class, pc[, detail]) —
        #: every record carries the exhibiting lane's replayable input
        #: (analysis/evidence.py turns these into Issues)
        self.evidence: Dict[Tuple, Dict] = {}
        #: property-steering queries already dispatched (pc, kind)
        self.prop_attempted: Set[Tuple[int, int]] = set()
        # -- device-completeness accounting (ownership gate) ----------
        #: lanes of this contract that degraded (ERR_MEM/UNSUPPORTED):
        #: their work fell back to the host, so the device's view of
        #: the contract is partial
        self.degraded = 0
        #: a carry was dropped at CARRY_CAP: some tx-N+1 start state
        #: was never explored
        self.carry_overflow = False
        #: every finished phase ended with the frontier genuinely
        #: closed (exhausted, no retriable candidates) — False the
        #: moment a phase ends on budget/wave-cap instead
        self.frontier_closed = True
        #: never-written slots the device observed SLOADs of
        self.storage_reads: Set[int] = set()
        #: arith sites over opaque operands that never wrapped — each
        #: must be resolved (a wrap witness, or an ANSWERED node-site
        #: steering query at the same pc) or the contract stays
        #: host-owned
        self.opaque_sites: Set[int] = set()
        #: steering queries that got a genuine answer (unsat, or sat
        #: with the wrap then confirmed concretely) — attempts alone
        #: resolve nothing
        self.prop_resolved: Set[Tuple[int, int]] = set()
        #: branch targets whose path condition could not be decoded
        #: (opaque prefix): unflippable — complete only if some
        #: concrete lane covered them anyway
        self.opaque_branches: Set[Tuple[int, bool]] = set()
        #: the per-lane evidence bank overflowed: completeness inputs
        #: (opaque sites, storage reads) may be truncated
        self.event_overflow = False
        #: the synthetic adversarial-storage start states (MAX and
        #: attacker-address variants; grown in place as reads surface)
        self.poison_carries: List[Dict] = []
        #: does the bytecode read msg.value? (byte scan over-approxes
        #: into PUSH data — a harmless extra carry)
        self.uses_callvalue = 0x34 in bytes.fromhex(self.code_hex)
        #: this phase's transaction start states
        self.carries: List[Dict] = [{"journal": {}, "prefix": []}]
        if self.uses_callvalue:
            # the msg.value axis: one value-bearing start state
            self.carries.append(
                {"journal": {}, "prefix": [], "callvalue": CALLVALUE_SEED}
            )
        #: mutating end states collected for the NEXT transaction,
        #: keyed by canonicalized journal (the device mutation pruner)
        self.next_carries: Dict[Tuple, Dict] = {}
        self.idle = False  # no start states left for this phase
        #: contract finished EARLY (all ownership gates green in the
        #: final phase): its evidence is frozen, its lanes stop being
        #: seeded, and the published outcome is final mid-run — the
        #: ownership consumer (analysis/corpus.py) may skip the host
        #: walk without waiting for the whole corpus run to end
        self.parked = False
        self._final_outcome: Optional[Dict] = None

    def device_complete(self) -> bool:
        """True when the striped exploration covered this contract's
        bounded model end-to-end: every phase's frontier closed, no
        lane degraded off-device, no carry dropped, and every opaque
        arith site resolved (wrapped concretely, or steering-checked
        through its node form at the same pc). The ownership gate
        (analysis/corpus.py): a complete contract's issues come from
        the evidence bank alone and the host walk is skipped."""
        return all(self.completeness_gates().values())

    def completeness_gates(self) -> Dict[str, bool]:
        """The ownership conditions, individually — every value must be
        True for device_complete. Exported through outcome() so an
        incomplete contract SAYS which gate kept the host walk."""
        steered = {p for (p, k) in self.prop_resolved if k in (10, 11, 12)}
        unresolved = {
            pc
            for pc in self.opaque_sites
            if ("wrap", pc) not in self.evidence and pc not in steered
        }
        return {
            "steering_resolved": not self._unresolved_steering(),
            "frontier_closed": bool(self.frontier_closed),
            "no_degraded": self.degraded == 0,
            "no_carry_overflow": not self.carry_overflow,
            "no_event_overflow": not self.event_overflow,
            "arith_sites_resolved": not unresolved,
            # every unflippable (opaque-prefix) branch target must have
            # been covered concretely by some lane
            "opaque_branches_covered": self.opaque_branches <= self.covered,
            # an unseeded poisoned state means the storage dimension
            # was never sampled: whatever it would have exhibited is
            # unknown, so the host walk keeps the contract
            "poison_seeded": not self.unseeded_poison(),
        }

    def bank_carry(
        self,
        journal: Dict[int, int],
        prefix: List[bytes],
        parent: Optional[Dict] = None,
    ) -> bool:
        key = tuple(sorted(journal.items()))
        if key in self.next_carries:
            return False
        if len(self.next_carries) >= CARRY_CAP:
            # a DISTINCT mutated end state was dropped: the next
            # transaction's exploration is knowingly partial
            self.carry_overflow = True
            return False
        carry = {"journal": journal, "prefix": prefix}
        if parent:
            if parent.get("base"):
                # descendants of a poisoned start state keep its
                # synthetic initial storage: any witness they produce
                # must declare it
                carry["base"] = parent["base"]
            if parent.get("balance"):
                carry["balance"] = parent["balance"]
            # per-transaction msg.value trail (witness steps + the
            # attacker-profit gate)
            carry["prefix_values"] = parent.get("prefix_values", []) + [
                parent.get("callvalue", 0)
            ]
        self.next_carries[key] = carry
        return True

    def ensure_poison_carries(self) -> None:
        """Create/refresh the adversarial-storage start states from
        the observed never-written reads. Mutated in place: carries
        are referenced by index, and the next wave's make_batch reads
        the journals fresh."""
        if self.parked or not self.storage_reads:
            return
        if not self.poison_carries:
            # MAX and attacker-address variants run VALUE-FREE (a
            # value-bearing start reverts at every non-payable guard);
            # payable contracts get one extra MAX+msg.value combo for
            # the `balances[x] += msg.value` wrap family
            variants = 3 if self.uses_callvalue else 2
            for k in range(variants):
                # every poisoned state also holds a funded contract
                # balance (`send(this.balance)` shapes — the host
                # models balances symbolically; witnesses declare it)
                carry = {
                    "journal": {},
                    "prefix": [],
                    "base": {},
                    "balance": CALLVALUE_SEED,
                }
                if k == 2:
                    carry["callvalue"] = CALLVALUE_SEED
                self.poison_carries.append(carry)
                self.carries.append(carry)
            self._n_uniform_poison = variants
        values = (POISON_VALUE, POISON_ADDR, POISON_VALUE)
        # only the uniform variant carries take the all-slot refresh:
        # per-slot singles (appended below) must keep their lone-slot
        # isolation across waves
        uniform = self.poison_carries[
            : getattr(self, "_n_uniform_poison", len(self.poison_carries))
        ]
        for value, carry in zip(values, uniform):
            for slot in sorted(self.storage_reads)[:POISON_SLOTS]:
                if slot not in carry["journal"]:
                    # a new slot means the poisoned state changed: it
                    # deserves a fresh seeding pass
                    carry["seeded"] = False
                carry["journal"][slot] = value
                carry["base"][slot] = value
        # Per-slot SINGLES: uniform poison blocks guarded paths (a
        # MAX-poisoned `minInvestment` reverts the same function whose
        # poisoned balance would wrap), so each observed slot also gets
        # lone-slot MAX and attacker-address states — the closest
        # concolic analogue of the solver picking per-slot values.
        keys = getattr(self, "_poison_keys", None)
        if keys is None:
            keys = self._poison_keys = set()
        # MAX singles only: the attacker-address dimension rides the
        # all-ADDR variant (callee/owner slots resolve together there),
        # while wrap-guard interplay needs each slot isolated at MAX
        for slot in sorted(self.storage_reads)[:POISON_SLOTS - 2]:
            k = (slot, POISON_VALUE)
            if k in keys or len(self.poison_carries) >= 9:
                continue
            keys.add(k)
            carry = {
                "journal": {slot: POISON_VALUE},
                "prefix": [],
                "base": {slot: POISON_VALUE},
                "balance": CALLVALUE_SEED,
            }
            if self.uses_callvalue:
                carry["callvalue"] = CALLVALUE_SEED
            self.poison_carries.append(carry)
            self.carries.append(carry)

    def unseeded_poison(self) -> List[int]:
        return [
            i
            for i in self.poison_indices()
            if not self.carries[i].get("seeded")
        ]

    def still_exhausted(self) -> bool:
        """True when the last reseed found this frontier exhausted AND
        no lane has covered anything new since that verdict — the
        condition under which an early phase end (budget, wave cap,
        stop) cannot have left live work here."""
        return (
            self.exhausted
            and len(self.covered) == getattr(self, "_exhausted_cov", -1)
        )

    def finalize_if_complete(self) -> bool:
        """Early per-contract finality, checked after every reseed of
        the LAST transaction phase: once this contract's frontier is
        provably closed (idle this phase, or exhausted with stable
        coverage) and every other ownership gate is green, freeze it —
        snapshot the outcome as final, stop seeding its lanes, and
        stop consuming its events. The frozen claim stays sound
        because nothing can mutate the track afterwards; the consumer
        gets ownership ~as soon as the contract converges instead of
        at the end of the whole corpus run."""
        if self.parked:
            return True
        if getattr(self, "_poison_pending_serial", None) is not None:
            # a freshly-seeded poison stripe is scheduled for a wave
            # that has not been HARVESTED yet (under the pipelined
            # schedule that wave may still be two dispatches out) —
            # its results must land before completeness can claim the
            # storage dimension was sampled
            return False
        gates = self.completeness_gates()
        gates["frontier_closed"] = self.idle or self.still_exhausted()
        if not all(gates.values()):
            return False
        self.frontier_closed = True
        self.exhausted = True
        self._exhausted_cov = len(self.covered)
        self.parked = True
        out = self.outcome()
        out["final_for_contract"] = True
        self._final_outcome = out
        return True

    def _unresolved_steering(self) -> bool:
        """A steering query that was dispatched but never got a real
        answer — sprint-capped, lowering-failed, or sat-but-never-
        confirmed-concretely — leaves its property OPEN: the host walk
        must keep the contract."""
        for key in self.prop_attempted:
            pc, k = key
            if key in self.prop_resolved:
                continue
            if k in (10, 11, 12) and ("wrap", pc) in self.evidence:
                continue
            if k in (4, 6):
                mnemonic = {4: "CALL", 6: "DELEGATECALL"}[k]
                rec = self.evidence.get(("call", pc, mnemonic))
                if rec is not None and rec.get("to_attacker"):
                    continue
            return True
        return False

    def result_stored_in_block(self, pc: int) -> bool:
        """Static stand-in for the wrap-usage check when the result is
        term-opaque: does the basic block continuing at `pc` reach one
        of integer.py's promotion sites (SSTORE, RETURN, CALL — or a
        JUMPI, whose in-block condition chain the result feeds) before
        a plain control transfer? Linear byte sweep, PUSH data skipped
        — the `SLOAD ADD ... SSTORE` / `MUL ... GT ... JUMPI` compiler
        shapes this covers have no interior branches."""
        cached = getattr(self, "_stored_memo", None)
        if cached is None:
            cached = self._stored_memo = {}
        hit = cached.get(pc)
        if hit is not None:
            return hit
        code = bytes.fromhex(self.code_hex)
        at = pc
        out = False
        for _ in range(48):
            if at >= len(code):
                break
            op = code[at]
            if op in (0x55, 0xF3, 0xF1, 0x57):
                out = True  # SSTORE / RETURN / CALL / JUMPI use sites
                break
            if op in (0x00, 0x56, 0xFD, 0xFE, 0xFF):
                break  # STOP/JUMP/REVERT/INVALID/SELFDESTRUCT
            at += 1 + (op - 0x5F if 0x60 <= op <= 0x7F else 0)
        cached[pc] = out
        return out

    def poison_indices(self) -> List[int]:
        return [
            i
            for i, c in enumerate(self.carries)
            if any(c is p for p in self.poison_carries)
        ]

    def advance_phase(self) -> bool:
        """Promote the banked carries to the next transaction's start
        states; False when exploration of this contract is over."""
        if self.parked:
            return False  # frozen — state must not be touched
        # inputs that exercised branches last transaction are the best
        # seeds for the next one: a branch direction that was a dead
        # end under empty storage may open under the carried journal,
        # and the global covered-set keeps it off the flip frontier.
        # SOLVER-DERIVED witnesses first (they opened branches nothing
        # else reaches), then the rest latest-first — plain
        # reversed(corpus) buries a wave's flip witnesses behind its
        # own mutation filler and they fall out of the seed window.
        seen = set()
        self.parent_inputs = [
            data
            for data in reversed(self.flip_corpus)
            if not (data in seen or seen.add(data))
        ] + [
            data
            for _, data in reversed(self.corpus)
            if not (data in seen or seen.add(data))
        ]
        self.flip_corpus = []
        # fresh phase, fresh poison: the carried states already hold
        # last phase's written slots; new never-written reads surface
        # their own synthetic start state
        self.poison_carries = []
        self.storage_reads = set()
        self._poison_keys = set()
        self._poison_pending_serial = None
        if not self.next_carries:
            self.idle = True
            # keep a placeholder so the lane stripe stays shape-stable
            self.carries = [{"journal": {}, "prefix": []}]
            return False
        self.carries = list(self.next_carries.values())
        self.next_carries = {}
        self.attempted = set()
        self.exhausted = False
        return True

    @staticmethod
    def _hexify_rec(rec: Dict) -> Dict:
        """Internal records hold raw bytes; the outcome dict carries hex
        strings — including the per-property witnesses (w_unchecked /
        w_profit) banked beside call records."""
        out = dict(
            rec,
            input=rec["input"].hex(),
            prefix=[p.hex() for p in rec["prefix"]],
        )
        for k in ("w_unchecked", "w_profit"):
            w = out.get(k)
            if w is not None:
                out[k] = dict(
                    w,
                    input=w["input"].hex(),
                    prefix=[p.hex() for p in w["prefix"]],
                )
        return out

    def outcome(self) -> Dict:
        return {
            "covered_branches": sorted(self.covered),
            "corpus_size": len(self.corpus),
            "triggers": {
                kind: [
                    dict(
                        t,
                        input=t["input"].hex(),
                        prefix=[p.hex() for p in t["prefix"]],
                    )
                    for t in bucket
                ]
                for kind, bucket in self.triggers.items()
            },
            "evidence": [self._hexify_rec(rec) for rec in self.evidence.values()],
            "device_complete": self.device_complete(),
            "completeness_gates": self.completeness_gates(),
            "degraded_lanes": self.degraded,
        }


class _WavePayload:
    """One wave's host-side seed snapshot: everything needed to (a)
    dispatch it, (b) re-dispatch it cold after a fault, (c) flush its
    checkpoint from a background thread, and (d) consume its results
    — all WITHOUT touching live track state, which later harvests
    mutate in place while this wave is still in flight."""

    __slots__ = (
        "inputs", "flat", "lane_carry", "carries", "storage_seed",
        "callvalues", "balances", "synthetic", "serial",
    )

    def __init__(
        self, inputs, flat, lane_carry, carries, storage_seed,
        callvalues, balances, synthetic, serial,
    ) -> None:
        self.inputs = inputs
        self.flat = flat
        self.lane_carry = lane_carry
        self.carries = carries
        self.storage_seed = storage_seed
        self.callvalues = callvalues
        self.balances = balances
        self.synthetic = synthetic
        self.serial = serial


class _Inflight:
    """A dispatched, not-yet-harvested wave."""

    __slots__ = (
        "payload", "out", "steps", "active", "fused", "blocks",
        "dispatch_t", "failed",
    )

    def __init__(self, payload: _WavePayload) -> None:
        self.payload = payload
        self.out = None
        self.steps = None
        self.active = None
        self.fused = None  # substep lane-steps (specialized waves)
        self.blocks = None  # lowered blocks entered (blockjit waves)
        self.dispatch_t = None
        self.failed = None


class DeviceCorpusExplorer:
    """Explore a corpus of contracts in one lane-striped StateBatch.

    Contract i owns lanes [i*L, (i+1)*L). Every wave advances the whole
    corpus in one jit'd `sym_run`; flips and reseeding happen per
    contract on the host between waves, and carries advance the whole
    corpus one attacker transaction at a time up to `transaction_count`.
    """

    def __init__(
        self,
        codes_hex: List[str],
        calldata_len: int = 68,
        lanes_per_contract: int = 32,
        waves: int = 4,
        steps_per_wave: int = 512,
        portfolio_candidates: int = 64,
        portfolio_steps: int = 1024,
        seed: int = 1,
        budget_s: Optional[float] = None,
        address: int = DEFAULT_ADDRESS,
        n_devices: Optional[int] = None,
        transaction_count: int = 1,
        empty_world: bool = True,
        host_lock=None,
        stop_event=None,
        publish=None,
        mem_cap: int = 16384,
        storage_cap: int = 128,
        deadline=None,
        checkpoint_path=None,
        pipeline: Optional[bool] = None,
        devices=None,
        fault_domain: Optional[str] = None,
        specialize: Optional[bool] = None,
        selector_masks: Optional[Dict[int, Tuple]] = None,
    ) -> None:
        from mythril_tpu.laser.batch.seeds import code_cap_bucket
        from mythril_tpu.support.support_args import args as _flags

        self.tracks = [
            _ContractTrack(c[2:] if c.startswith("0x") else c) for c in codes_hex
        ]
        self.codes = [bytes.fromhex(t.code_hex) for t in self.tracks]
        #: verdict-store incremental masks (mythril_tpu/store/diff.py):
        #: {track index: (frozenset of unchanged selector bytes,
        #: frozenset of their (jumpi_pc, taken) entry directions)} —
        #: those selectors' dispatcher seeds and entry flips are
        #: pruned exactly like statically-dead ones, so this
        #: exploration spends lanes only on a fork's CHANGED functions
        self.selector_masks = dict(selector_masks or {})
        self._attach_static_feeds()
        self.lanes_per_contract = lanes_per_contract
        self.calldata_len = calldata_len
        self.waves = waves
        self.steps_per_wave = steps_per_wave
        self.portfolio_candidates = portfolio_candidates
        self.portfolio_steps = portfolio_steps
        self.budget_s = budget_s
        self.address = address
        self.transaction_count = max(1, transaction_count)
        # False when foreign accounts may carry code (on-chain
        # loading): device lanes then hand CALLs to the host instead
        # of treating them as transfers
        self.empty_world = empty_world
        # Overlapped mode (analysis/corpus.py): waves run in a prepass
        # thread while the main thread analyzes; `host_lock` guards the
        # process-global symbolic state (support/host_lock.py) around
        # flip decode+solve bursts, and the budget switches to ACTIVE
        # time (waves + flip solving) so wall spent blocked on the lock
        # doesn't count against the prepass. `stop_event` lets the
        # owner end the exploration when its own work is done.
        self.host_lock = host_lock
        self.stop_event = stop_event
        #: resilience supervision (support/resilience.py): an expired
        #: `deadline` — or a delivered SIGINT/SIGTERM — reads as a stop
        #: request at every wave/budget boundary, and `checkpoint_path`
        #: flushes each wave's seeded frontier to npz BEFORE dispatch,
        #: so a wave killed mid-flight replays exactly (replay_wave)
        self.deadline = deadline
        self.checkpoint_path = checkpoint_path
        #: double-buffered wave pipelining (--no-pipeline turns it
        #: off): up to two waves in flight, wave N+1 seeded from the
        #: frontier known BEFORE wave N's results, so the host's
        #: evidence consume + flip solving for wave N overlap the
        #: device's execution of wave N+1
        self.pipeline = (
            bool(getattr(_flags, "pipeline", True))
            if pipeline is None
            else bool(pipeline)
        )
        #: background npz flusher (checkpoint.py): the per-wave
        #: seeded-frontier flush serializes off the critical path
        self._ckpt_writer = (
            WaveCheckpointWriter() if checkpoint_path else None
        )
        #: the most recently harvested wave's device buffers — the
        #: next dispatch's donation fodder (arena reuse): None forces
        #: the cold make_batch upload path
        self._carcass = None
        self._donate: Optional[bool] = None
        self._wave_serial = 0
        self._halt_reason = None
        #: set while this explorer wants/holds the host lock — the
        #: overlapped owner only needs to yield between analyses when
        #: a flip burst is actually waiting, not once per contract
        self.lock_wanted = threading.Event()
        # `publish(track_index, outcome_so_far)` after every wave: in
        # overlapped mode the owner consumes partial outcomes for
        # contracts it analyzes before the exploration completes —
        # wave-1 triggers/coverage already pre-empt most of what the
        # final outcome would (dict writes are GIL-atomic; the value is
        # freshly built, never mutated after publication)
        self.publish = publish
        #: device model capacities per lane. The [N, mem_cap] memory
        #: array dominated per-step cost over a remote-chip link used
        #: before PR 21 (historical measurement:
        #: 152 ms/step at 16384/128 vs 39 ms/step at 4096/64, 3328
        #: lanes) — corpus callers pass lean caps and the degraded-lane
        #: counters report what the trade costs
        self.mem_cap = mem_cap
        self.storage_cap = storage_cap
        self.rng = random.Random(seed)
        self.stats = ExploreStats()
        self.stats.pipelined = int(self.pipeline)
        self.stats.static_summaries = sum(
            1 for t in self.tracks if t.static is not None
        )
        self.stats.static_answered = sum(
            1
            for t in self.tracks
            if t.static is not None and t.static.static_answerable
        )
        # selectors actually masked (a mask on a track whose static
        # feed failed never attached, so count from the feeds)
        from mythril_tpu.store.diff import SelectorMaskFeed as _MaskFeed

        self.stats.store_masked_selectors = sum(
            len(t.static.mask_selectors)
            for t in self.tracks
            if isinstance(t.static, _MaskFeed)
        )
        self._phase_allowance: Optional[float] = None

        # bucket the code capacity to powers of two so XLA compiles one
        # kernel per size class, not one per corpus composition
        cap = code_cap_bucket(max((len(c) for c in self.codes), default=1))
        self.code_table = make_code_table(self.codes, code_cap=cap)
        # host copy for the background checkpoint writer: the table
        # never changes, so snapshotting it once keeps the writer from
        # pulling it back over the link every wave
        self._code_table_host = type(self.code_table)(
            *(np.asarray(a) for a in self.code_table)
        )
        self.code_ids = np.repeat(
            np.arange(len(self.codes), dtype=np.int32), lanes_per_contract
        )
        #: multi-chip scheduler attribution (parallel/topology.py):
        #: the failure-domain label qualifies this explorer's fault
        #: injection sites and degradation records, so a fault in one
        #: device group's engine is pinned to THAT group
        self.fault_domain = fault_domain
        self.mesh = None
        if devices is not None:
            # an explicit device set (one scheduler group): the wave
            # pins to these devices via the mesh path — a single-device
            # group is a 1-device mesh, which is how a group's arena
            # replica stays resident on its own chip
            from mythril_tpu.parallel import make_mesh, replicate_table

            self.mesh = make_mesh(devices=devices)
            self.code_table = replicate_table(self.code_table, self.mesh)
        elif n_devices is not None and n_devices > 1:
            from mythril_tpu.parallel import make_mesh, replicate_table

            self.mesh = make_mesh(n_devices)
            self.code_table = replicate_table(self.code_table, self.mesh)

        # -- kernel specialization (specialize.py) ---------------------
        # Per-track opcode signatures (from the static summary when one
        # attached, a linear sweep otherwise) union into ONE wave-kernel
        # bucket: the wave is a single striped dispatch, so the kernel
        # must lower every phase ANY striped contract reaches. The
        # per-pc fuse table rides beside the code table (replicated
        # under a mesh the same way). --no-specialize, or any failure
        # here, falls back to the generic kernel.
        self._kernel = None
        self._fuse_tbl = None
        self.kernel_phases = None
        if specialize is None:
            from mythril_tpu.laser.batch.specialize import specialize_enabled

            specialize = specialize_enabled()
        if specialize:
            try:
                from mythril_tpu.laser.batch import blockjit as _bj
                from mythril_tpu.laser.batch import specialize as _spec

                blockjit_on = _bj.blockjit_enabled()
                for track, code in zip(self.tracks, self.codes):
                    track.phases = _spec.phases_for(
                        _spec.signature_for(code, track.static),
                        fuse=_spec.fuse_profitable(code, track.static),
                        block_depth=(
                            _bj.block_depth_for(code, track.static)
                            if blockjit_on
                            else 0
                        ),
                    )
                self.kernel_phases = _spec.union_phases(
                    [t.phases for t in self.tracks]
                )
                summaries = [t.static for t in self.tracks]
                if self.kernel_phases.block_depth > 0:
                    # the block-program table replaces the fuse table:
                    # its rows carry the fusible marks too, so fusion
                    # rides the block substeps for every lane
                    fuse_np = _bj.build_block_table(
                        self.codes, cap, summaries
                    )
                    self.stats.blockjit_fallbacks = sum(
                        _bj.block_stats(code, static)["blocks_unlowered"]
                        for code, static in zip(self.codes, summaries)
                    )
                else:
                    fuse_np = _spec.build_fuse_table(
                        self.codes, cap, summaries
                    )
                import jax.numpy as jnp

                self._fuse_tbl = jnp.asarray(fuse_np)
                if self.mesh is not None:
                    from mythril_tpu.parallel import replicate_table

                    self._fuse_tbl = replicate_table(
                        self._fuse_tbl, self.mesh
                    )
                cache = _spec.kernel_cache()
                h0, m0 = cache.hits, cache.misses
                self._kernel = cache.acquire(self.kernel_phases)
                self.stats.kernel_cache_hits += cache.hits - h0
                self.stats.kernel_cache_misses += cache.misses - m0
                self.stats.specialized = 1
                self.stats.spec_pruned_phases = len(
                    self.kernel_phases.pruned
                )
            except Exception:
                log.debug(
                    "kernel specialization failed; exploring on the "
                    "generic kernel",
                    exc_info=True,
                )
                self._kernel = None
                self._fuse_tbl = None
                self.kernel_phases = None

    # -- static pre-analysis -------------------------------------------
    def _attach_static_feeds(self) -> None:
        """Run the host-side static pass once per contract (cached by
        code hash) BEFORE any lane is seeded: statically-dead branch
        directions never enter the flip frontier and inert functions
        never get dispatcher seeds. Failure is never fatal — a
        contract without a feed simply explores unpruned."""
        from mythril_tpu.analysis.static import static_prune_enabled

        if not static_prune_enabled():
            return
        from mythril_tpu.analysis.static import summary_for

        for ti, track in enumerate(self.tracks):
            try:
                track.static = summary_for(track.code_hex)
                mask = self.selector_masks.get(ti)
                if mask is not None:
                    # wrap the summary so the unchanged-fork selectors
                    # read as dead to seeding AND to the flip frontier
                    from mythril_tpu.store.diff import SelectorMaskFeed

                    sels, directions = mask
                    track.static = SelectorMaskFeed(
                        track.static, sels, directions
                    )
                track.static_dead = frozenset(
                    track.static.prune_directions()
                )
            except Exception:
                log.debug(
                    "static pre-analysis failed; contract explores "
                    "unpruned",
                    exc_info=True,
                )
                track.static = None
                track.static_dead = frozenset()

    # -- failure-domain attribution ------------------------------------
    def _inject(self, site: str) -> None:
        """Fire the fault-injection hook at `site` — and, when this
        explorer runs inside a scheduler device group, at the
        domain-qualified site too, so a harness can fault ONE group's
        dispatches (`device.dispatch.mesh-g0`) while the other groups'
        engines run clean."""
        from mythril_tpu.support import resilience

        resilience.inject(site)
        if self.fault_domain is not None:
            resilience.inject(f"{site}.{self.fault_domain}")

    def _site(self, site: str) -> str:
        """Degradation-record site, qualified with the failure domain
        so the DegradationLog attributes the group."""
        if self.fault_domain is not None:
            return f"{site}/{self.fault_domain}"
        return site

    # -- supervision ---------------------------------------------------
    def _stop_requested(self) -> bool:
        """One answer for every wave/budget/solve boundary: the owner's
        stop event, a delivered SIGINT/SIGTERM, or an expired deadline
        all read as "finish the current unit of work and wind down with
        partial outcomes". The first trigger is remembered so the final
        stats can say WHY the run ended early."""
        from mythril_tpu.support import resilience

        if self.stop_event is not None and self.stop_event.is_set():
            self._halt_reason = self._halt_reason or "stop-event"
            return True
        reason = resilience.interrupted_reason(self.deadline)
        if reason is not None:
            if self._halt_reason is None:
                self._halt_reason = reason
                resilience.DegradationLog().record(
                    reason, site=self._site("explorer"),
                    detail="exploration wound down at a wave boundary",
                )
            return True
        return False

    # -- frontier handoff (multi-chip work stealing) --------------------
    def export_frontier(self, ci: int) -> Dict:
        """Pack contract ci's live exploration frontier for a host
        handoff to another device group's engine
        (parallel/scheduler.py work stealing): the seeds worth
        re-dispatching (flip witnesses first — solver-derived inputs
        are the expensive part), the covered/attempted sets (so the
        stealing engine never re-solves a flip this one already
        answered), and the banked transaction-start carries with their
        journals. Everything is host-resident after a harvest; the
        stealing side re-uploads it through its own wave seeding path
        (the same width-bucketed slab `reseed_wave` ships), which is
        the device-side unpack."""
        track = self.tracks[ci]
        seen: Set[bytes] = set()
        inputs: List[bytes] = []
        for data in list(reversed(track.flip_corpus)) + [
            d for _, d in reversed(track.corpus)
        ]:
            if data not in seen:
                seen.add(data)
                inputs.append(data)
        carries = []
        for carry in track.carries:
            if any(carry is p for p in track.poison_carries):
                continue  # poison is re-derived from observed reads
            packed = {
                "journal": dict(carry["journal"]),
                "prefix": list(carry["prefix"]),
            }
            for key in ("callvalue", "balance", "prefix_values"):
                if carry.get(key):
                    packed[key] = carry[key]
            if carry.get("base"):
                packed["base"] = dict(carry["base"])
            carries.append(packed)
        return {
            "code_hex": track.code_hex,
            "covered": sorted(track.covered),
            "attempted": sorted(track.attempted),
            "parent_inputs": inputs[:64],
            "carries": carries[:CARRY_CAP],
        }

    def seed_frontier(self, ci: int, frontier: Dict) -> None:
        """Install a stolen frontier (export_frontier's shape) into
        contract ci's track BEFORE run(): the engine continues the
        donor's exploration instead of restarting it — solved flips
        stay blacklisted, covered directions stay off the flip
        frontier, and the donor's carries become this engine's
        transaction-start states."""
        track = self.tracks[ci]
        if track.code_hex != frontier.get("code_hex", track.code_hex):
            raise ValueError(
                "frontier handoff code mismatch for contract "
                f"{ci}: refusing to seed another contract's state"
            )
        track.covered |= {tuple(b) for b in frontier.get("covered", [])}
        track.attempted |= {
            tuple(b) for b in frontier.get("attempted", [])
        }
        track.parent_inputs = [
            bytes(d) for d in frontier.get("parent_inputs", [])
        ]
        carries = frontier.get("carries")
        if carries:
            track.carries = [
                {
                    "journal": dict(c.get("journal", {})),
                    "prefix": [bytes(p) for p in c.get("prefix", [])],
                    **{
                        k: c[k]
                        for k in (
                            "callvalue", "balance", "prefix_values", "base",
                        )
                        if c.get(k)
                    },
                }
                for c in carries[:CARRY_CAP]
            ]

    # -- seeding -------------------------------------------------------
    def _seed_phase_inputs(
        self, offset: int = 0
    ) -> List[List[Tuple[int, bytes]]]:
        """Per contract: (carry index, calldata) pairs — every carry
        crossed with the dispatcher seeds, round-robin to the stripe.

        `offset` continues the same deterministic seed stream `offset`
        stripes further along — the pipelined schedule fills its
        second in-flight slot with the stream's next window (the only
        inputs derivable before any wave has been harvested)."""
        from mythril_tpu.laser.batch.seeds import dispatcher_seeds

        stripes = []
        for track in self.tracks:
            if track.selector_seeds is None:
                # cache only the deterministic part (zero + dispatcher
                # selectors); the random filler below is re-drawn each
                # phase so later transactions don't replay identical
                # calldata. The static feed masks inert selectors out
                # of the wave seeding (drops logged at DEBUG there).
                before = track.static.seeds_dropped if track.static else 0
                track.selector_seeds = dispatcher_seeds(
                    track.code_hex, self.calldata_len, prune=track.static
                )
                if track.static is not None:
                    self.stats.static_seeds_dropped += (
                        track.static.seeds_dropped - before
                    )
            seeds = list(track.parent_inputs) + track.selector_seeds
            while len(seeds) < self.lanes_per_contract:
                seeds.append(
                    bytes(
                        self.rng.randrange(256)
                        for _ in range(self.calldata_len)
                    )
                )
            n_carries = len(track.carries)
            shift = offset * self.lanes_per_contract
            stripes.append(
                [
                    (
                        j % n_carries,
                        seeds[((j + shift) // n_carries) % len(seeds)],
                    )
                    for j in range(self.lanes_per_contract)
                ]
            )
        return stripes

    # -- solving -------------------------------------------------------
    def _sprint_cap_s(self) -> float:
        """The escalation ladder's wall cap for one wave's host-CDCL
        pass (args.sprint_cap_s, seeded from MYTHRIL_SPRINT_CAP_S;
        previously a hardcoded 5.0)."""
        from mythril_tpu.support.support_args import args as _flags

        try:
            return max(0.0, float(getattr(_flags, "sprint_cap_s", 5.0)))
        except (TypeError, ValueError):
            return 5.0

    def _device_first(self) -> bool:
        """Funnel order (ISSUE 9): device-first batched dispatch with
        the CDCL sprint demoted to an escalation ladder, vs the legacy
        host-first order (--host-first-funnel, the parity baseline).
        An OPEN device-solve breaker forces the host-first order too:
        the sprint answers first and the device stage is skipped
        outright (`_device_flips` gates), so a sick accelerator is
        routed around instead of re-failing per wave."""
        from mythril_tpu.support.support_args import args as _flags

        return bool(
            getattr(_flags, "device_first", True)
        ) and self._device_solve_allowed()

    @staticmethod
    def _device_solve_allowed() -> bool:
        """The device-solve tier breaker's verdict (support/breaker
        .py); True when the breaker layer is disabled."""
        from mythril_tpu.support import breaker as _cb

        if not _cb.breakers_enabled():
            return True
        return _cb.breaker(_cb.TIER_DEVICE_SOLVE).allow()

    def _lower_flips(self, batch, indices=None):
        """Lower flip queries for the device stage. MUST run under the
        host lock in overlapped mode (the term arena and `lower` are
        process-global). Returns (lowered queries, their indices into
        `batch`); queries that fail to lower are simply absent — the
        escalation ladder still sees them."""
        if indices is None:
            indices = range(len(batch))
        lowered_batch: List = []
        kept: List[int] = []
        for i in indices:
            try:
                lowered, _ = lower([c.raw for c in batch[i]])
            except Exception as e:
                log.debug("lowering failed: %s", e)
                continue
            lowered_batch.append(lowered)
            kept.append(i)
        return lowered_batch, kept

    def _sprint_flips(self, batch, out, skip=frozenset()):
        """Host-CDCL pass over a wave's flip batch (condition tuples).
        In the device-first funnel this is the ESCALATION ladder: it
        runs after the batched device dispatch and only sees the
        device's UNKNOWN survivors (`skip` holds the device-answered
        indices). MUST run under the host lock in overlapped mode: the
        incremental CDCL session, the term arena, and `lower` are all
        process-global.

        Writes assignments into `out` in place; returns (capped,
        survivors): the index set that never got a REAL attempt
        (wall cap / stop request — retried next wave, and recorded
        SPRINT_PREEMPTED with the actual cap in the loss artifact),
        and the attempted-but-undecided indices (solver timeouts —
        the legacy host-first order hands these to the device)."""
        t0 = time.perf_counter()
        sprint_span = trace(
            "flip.solve.host", track=self.fault_domain, queries=len(batch)
        )
        sprint_span.__enter__()
        survivors: List[int] = []
        capped: set = set()
        # the pass is time-capped as a whole: once hard queries have
        # eaten this much wall, the rest are recorded preempted and
        # retried next wave (device-first: they already had their
        # batched device attempt this wave)
        sprint_cap_s = self._sprint_cap_s()
        stopped = False
        with query_context(QUERY_ORIGIN_FLIP):
            for i, conditions in enumerate(batch):
                if i in skip or out[i] is not None:
                    continue
                # a stop request bounds post-stop lock-held work to the
                # query in flight — the owner may be waiting on a join
                # deadline past which it stops honoring the lock
                # protocol
                if stopped or self._stop_requested():
                    stopped = True
                    capped.add(i)
                    continue
                if time.perf_counter() - t0 > sprint_cap_s:
                    survivors.append(i)
                    capped.add(i)
                    # the loss artifact names the cap that preempted
                    # the query (the tuning knob for the ladder)
                    try:
                        lowered, _ = lower([c.raw for c in conditions])
                        query_capture.capture_flip(
                            lowered,
                            verdict="unknown",
                            wall_s=0.0,
                            engine="host-cdcl",
                            site="sprint_flips",
                            loss_reason="SPRINT_PREEMPTED",
                            detail={"sprint_cap_s": sprint_cap_s},
                        )
                    except Exception:
                        log.debug("sprint-cap capture failed", exc_info=True)
                    continue
                try:
                    model = get_model(
                        tuple(conditions),
                        enforce_execution_time=False,
                        solver_timeout=2000,
                    )
                    self.stats.host_sat += 1
                    out[i] = dict(model.assignment)
                except UnsatError:
                    pass
                except SolverTimeOutException:
                    survivors.append(i)
                except Exception as e:
                    log.debug("CDCL flip solve did not finish: %s", e)
                    survivors.append(i)
        sprint_span.__exit__(None, None, None)
        if stopped:
            # post-stop, undecided queries get no further stage this
            # wave (bounded lock-held work); capped ones stay
            # retriable, timeouts keep their attempt
            survivors = []
        self.stats.flip_solve_s += time.perf_counter() - t0
        return capped, survivors

    def _device_flips(self, out, lowered_batch, kept, device_first=True):
        """The lock-free device stage: ONE batched dispatch for the
        whole wave's flip frontier (device-first funnel) — on a link
        where a dispatch chain costs seconds, the portfolio is only
        affordable at batch granularity, and its cost does not grow
        with query count. The dispatch runs the diversified SLS
        portfolio, exhaustive enumeration of small spaces, and the
        cube-and-conquer fan (portfolio.device_solve_batch); every
        SAT is witness-validated before it counts, and enumeration
        UNSATs are device-OWNED verdicts that never escalate. Holding
        the host lock here would block the owner's analyses on pure
        device work.

        Writes witnesses into `out`; returns (answered, unsat): the
        device-decided index sets (the escalation ladder skips both).
        """
        answered: set = set()
        unsat: set = set()
        if not lowered_batch:
            return answered, unsat
        if not self._device_solve_allowed():
            # breaker open: the whole frontier goes to the escalation
            # ladder (host CDCL) — no doomed device dispatch
            return answered, unsat
        t0 = time.perf_counter()
        n_dev = 1
        devices = None
        if self.mesh is not None:
            devices = list(np.asarray(self.mesh.devices).flat)
            n_dev = len(devices)
        try:
            with trace(
                "flip.solve.device",
                track=self.fault_domain,
                queries=len(lowered_batch),
            ):
                # the legacy (host-first) baseline mirrors the old
                # device stage: full per-query step budget, no cube
                # fan — the parity differential compares funnels, not
                # knob sets
                verdicts = device_solve_batch(
                    lowered_batch,
                    candidates=self.portfolio_candidates,
                    steps=None if device_first else self.portfolio_steps,
                    cube_depth=None if device_first else 0,
                    n_devices=n_dev,
                    devices=devices,
                    should_stop=self._stop_requested,
                )
        except Exception as why:
            from mythril_tpu.support import breaker as _cb
            from mythril_tpu.support import resilience as _res

            if not _res.is_device_fault(why):
                raise
            # a faulted solver dispatch degrades this wave's frontier
            # to the host ladder and feeds the breaker — repeated
            # faults trip it open and later waves skip the stage
            if _cb.breakers_enabled():
                _cb.breaker(_cb.TIER_DEVICE_SOLVE).record_failure(
                    str(why)
                )
            _res.DegradationLog().record(
                _res.DegradationReason.DEVICE_DISPATCH_FAILED,
                site="flip.solve.device",
                detail=str(why),
            )
            return answered, unsat
        from mythril_tpu.support import breaker as _cb

        if _cb.breakers_enabled():
            _cb.breaker(_cb.TIER_DEVICE_SOLVE).record_success()
        from mythril_tpu.laser.smt.solver.solver_statistics import (
            SolverStatistics,
        )

        dt = time.perf_counter() - t0
        per_query = dt / max(1, len(kept))
        for qi, (i, verdict) in enumerate(zip(kept, verdicts)):
            if verdict.status == "sat":
                self.stats.device_sat += 1
                # the process-wide engine scorecard: flip witnesses are
                # device-OWNED sat verdicts (bench device_verdict_share)
                SolverStatistics().device_sat_count += 1
                out[i] = verdict.assignment
                answered.add(i)
            elif verdict.status == "unsat":
                # a complete enumeration exhausted the space: the
                # device owns this unsat — no host escalation
                self.stats.device_unsat += 1
                answered.add(i)
                unsat.add(i)
            if verdict.via == "enum":
                self.stats.device_enumerated += 1
            elif verdict.via == "cube":
                self.stats.device_cube_sat += 1
            # solver attribution: the device is the funnel's FIRST
            # rung now (hop 0); the sprint ladder behind it is hop 1
            record_query(ORIGIN_DEVICE, verdict.status, per_query, hop=0)
            # flight recorder: the batched dispatch bypasses
            # check_terms, so these flip-frontier queries capture here
            query_capture.capture_flip(
                lowered_batch[qi],
                verdict=verdict.status,
                wall_s=per_query,
                hop=0,
                loss_reason=verdict.loss,
                detail={"via": verdict.via} if verdict.via else None,
            )
        self.stats.flip_solve_s += dt
        return answered, unsat

    def _witness_bytes(self, assignment: Dict[str, int]) -> bytes:
        data = bytearray(self.calldata_len)
        for name, value in assignment.items():
            if name.startswith("cd"):
                try:
                    i = int(name[2:])
                except ValueError:
                    continue
                if i < self.calldata_len:
                    data[i] = value & 0xFF
        return bytes(data)

    # -- the wave ------------------------------------------------------
    def _donation_ok(self) -> bool:
        """Buffer donation only where the backend honors it (the CPU
        client warns and ignores donations — noise, no win)."""
        if self._donate is None:
            import jax

            self._donate = jax.default_backend() != "cpu"
        return self._donate

    def _prepare_wave(self, inputs: List[List[Tuple[int, bytes]]]):
        """Snapshot one wave's host-side seed data (a _WavePayload) and
        hand its checkpoint flush to the background writer.

        The snapshot matters: carry journals are mutated in place by
        later harvests (ensure_poison_carries), so both the dispatch
        and the asynchronously-written checkpoint must read copies
        taken at seeding time — the flushed frontier is the one that
        DISPATCHED, whatever the host learned afterwards."""
        flat = [pair for stripe in inputs for pair in stripe]
        L = self.lanes_per_contract
        carries = []
        for lane, (ci, _) in enumerate(flat):
            live = self.tracks[lane // L].carries[ci]
            snap = dict(live)
            snap["journal"] = dict(live["journal"])
            snap["prefix"] = list(live["prefix"])
            if live.get("base"):
                snap["base"] = dict(live["base"])
            if live.get("prefix_values"):
                snap["prefix_values"] = list(live["prefix_values"])
            carries.append(snap)
        payload = _WavePayload(
            inputs=inputs,
            flat=flat,
            lane_carry=[ci for ci, _ in flat],
            carries=carries,
            storage_seed=[c["journal"] for c in carries],
            callvalues=[c.get("callvalue", 0) for c in carries],
            balances=[
                c.get("balance", REPLAY_ENV["balance"]) for c in carries
            ],
            synthetic=np.array([bool(c.get("base")) for c in carries]),
            serial=self._wave_serial,
        )
        self._wave_serial += 1
        if self._ckpt_writer is not None:
            # flush the SEEDED frontier: a wave killed mid-flight
            # (fault, OOM, SIGKILL) leaves its exact inputs on disk,
            # and the engine is deterministic, so replay_wave
            # reproduces the lost wave bit-for-bit. The serialization
            # runs on the writer thread (atomic rename), overlapping
            # the dispatch instead of preceding it.
            path = self.checkpoint_path
            table = self._code_table_host
            steps = self.steps_per_wave

            def _flush(payload=payload):
                env = dict(REPLAY_ENV)
                env["balance"] = payload.balances
                frontier = make_batch(
                    len(payload.flat),
                    code_ids=self.code_ids,
                    calldata=[data for _, data in payload.flat],
                    callvalue=payload.callvalues,
                    caller=DEFAULT_CALLER,
                    address=self.address,
                    mem_cap=self.mem_cap,
                    storage_cap=self.storage_cap,
                    storage_seed=payload.storage_seed,
                    empty_world=self.empty_world,
                    as_numpy=True,
                    **env,
                )
                save_checkpoint(
                    path,
                    frontier,
                    table,
                    step=steps,
                    extra={
                        "synthetic": payload.synthetic.astype(np.uint8)
                    },
                    atomic=True,
                )

            self._ckpt_writer.submit(_flush)
            self.stats.wave_checkpoints += 1
        return payload

    def _cold_sym(self, payload):
        """Full host-side batch build + upload (the first wave, every
        mesh-sharded wave, and the fault-retry path)."""
        env = dict(REPLAY_ENV)
        env["balance"] = payload.balances
        base = make_batch(
            len(payload.flat),
            code_ids=self.code_ids,
            calldata=[data for _, data in payload.flat],
            callvalue=payload.callvalues,
            caller=DEFAULT_CALLER,
            address=self.address,
            mem_cap=self.mem_cap,
            storage_cap=self.storage_cap,
            storage_seed=payload.storage_seed,
            empty_world=self.empty_world,
            **env,
        )
        if self.mesh is not None:
            from mythril_tpu.parallel import shard_batch

            base = shard_batch(base, self.mesh)
        sym = make_sym_batch(base)
        if payload.synthetic.any():
            # poisoned start states are SAMPLES of the host's symbolic
            # initial storage: reads of them must count as opaque so
            # arithmetic over them banks (wrap or opaque-site) events
            # instead of masquerading as path constants
            import jax.numpy as jnp

            seeded = (
                jnp.arange(sym.sval_tid.shape[1])[None, :]
                < base.storage_cnt[:, None]
            )
            sym = sym._replace(
                sval_tid=jnp.where(
                    jnp.asarray(payload.synthetic)[:, None] & seeded,
                    jnp.int32(-1),
                    sym.sval_tid,
                )
            )
        return sym

    def _warm_sym(self, payload):
        """Device-side reseed out of the previous wave's buffers: the
        host uploads only the per-wave seed delta (calldata, values,
        a width-bucketed storage slab) — symbolic.reseed_wave."""
        from mythril_tpu.ops import u256

        n = len(payload.flat)
        limbs = u256.LIMBS
        widest = max((len(j) for j in payload.storage_seed), default=0)
        w = 1
        while w < min(widest, self.storage_cap):
            w <<= 1
        skeys = np.zeros((n, w, limbs), np.uint32)
        svals = np.zeros((n, w, limbs), np.uint32)
        scnt = np.zeros((n,), np.int32)
        for i, journal in enumerate(payload.storage_seed):
            for j, (slot, value) in enumerate(
                list(journal.items())[: self.storage_cap]
            ):
                skeys[i, j] = u256.from_int(slot)
                svals[i, j] = u256.from_int(value)
                scnt[i] = j + 1
        cd_w = 1
        while cd_w < self.calldata_len:
            cd_w <<= 1
        cd = np.zeros((n, cd_w), np.uint8)
        cds = np.zeros((n,), np.int32)
        for i, (_ci, data) in enumerate(payload.flat):
            m = min(len(data), cd_w)
            if m:
                cd[i, :m] = np.frombuffer(bytes(data[:m]), np.uint8)
            cds[i] = len(data)
        cv = np.stack(
            [u256.from_int(int(v)) for v in payload.callvalues]
        ).astype(np.uint32)
        bal = np.stack(
            [u256.from_int(int(v)) for v in payload.balances]
        ).astype(np.uint32)
        reseed = (
            reseed_wave_donated if self._donation_ok() else reseed_wave
        )
        carcass, self._carcass = self._carcass, None
        return reseed(
            carcass,
            self.code_ids,
            cd,
            cds,
            cv,
            bal,
            skeys,
            svals,
            scnt,
            payload.synthetic,
        )

    def _dispatch_wave(self, payload) -> "_Inflight":
        """Seed + dispatch one wave ASYNCHRONOUSLY: the call returns as
        soon as XLA has enqueued the computation, so the caller can
        keep consuming the previous wave while the device runs this
        one. Classified dispatch-time faults are captured on the
        inflight record — harvest retries them through the ladder with
        correct wave attribution."""
        from mythril_tpu.support import resilience

        self._inject("explore.wave")
        fl = _Inflight(payload)
        fl.dispatch_t = time.perf_counter()
        try:
            with trace(
                "wave.dispatch",
                track=self.fault_domain,
                serial=payload.serial,
            ):
                if self._carcass is not None and self.mesh is None:
                    sym = self._warm_sym(payload)
                else:
                    sym = self._cold_sym(payload)
                if self._kernel is not None:
                    # the contract-specialized kernel: pruned phases +
                    # block/superblock substeps (specialize.py,
                    # blockjit.py)
                    fl.out, fl.steps, fl.active, fl.fused, fl.blocks = (
                        self._kernel.sym_run(
                            sym,
                            self.code_table,
                            self._fuse_tbl,
                            max_steps=self.steps_per_wave,
                            donate=self._donation_ok(),
                        )
                    )
                else:
                    runner = (
                        sym_run_donated if self._donation_ok() else sym_run
                    )
                    fl.out, fl.steps, fl.active = runner(
                        sym, self.code_table, max_steps=self.steps_per_wave
                    )
        except Exception as why:
            if not resilience.is_device_fault(why):
                raise
            # the wave never launched: drop the (possibly half-donated)
            # carcass and let harvest re-dispatch cold under the ladder
            self._carcass = None
            fl.failed = why
        return fl

    def _retry_wave(self, fl):
        """The resilience ladder for a wave whose dispatch or readback
        faulted: cold re-dispatch from the retained host payload (the
        donated warm path cannot replay — its input buffers are spent),
        synchronous, attributed to the faulted wave's serial. Retries
        always run the GENERIC kernel — a fault on a specialized
        dispatch must not be retried into the same specialized
        lowering (fallback-to-generic, specialize.py docstring)."""
        import jax

        from mythril_tpu.support import resilience

        if self._kernel is not None:
            self.stats.spec_fallbacks += 1

        def _cold():
            # the ladder's own per-attempt injection point, qualified
            # so a chaos harness keeps faulting ONLY this group's
            # retries (the global `device.dispatch` site fires inside
            # retry_device_dispatch for every group alike)
            if self.fault_domain is not None:
                resilience.inject(f"device.dispatch.{self.fault_domain}")
            sym = self._cold_sym(fl.payload)
            out, steps, active = sym_run(
                sym, self.code_table, max_steps=self.steps_per_wave
            )
            jax.block_until_ready(steps)
            return out, steps, active

        return resilience.retry_device_dispatch(
            _cold,
            label="wave",
            policy=resilience.RetryPolicy(attempts=2, base_delay_s=0.2),
        )

    def _harvest_wave(self, fl) -> ArenaView:
        """Block until the wave's results are ready — the single point
        where asynchronous XLA faults surface, so the fault containment
        lives HERE, attributed to the wave that actually faulted even
        when a newer wave is already in flight — then pull the
        compacted evidence readback (ArenaView)."""
        import jax

        from mythril_tpu.support import resilience

        wait0 = time.perf_counter()
        fused = blocks = None
        with trace(
            "wave.harvest",
            track=self.fault_domain,
            serial=fl.payload.serial,
        ):
            if fl.failed is None:
                try:
                    self._inject("device.dispatch")
                    jax.block_until_ready(fl.steps)
                    out, steps, active = fl.out, fl.steps, fl.active
                    fused = fl.fused
                    blocks = fl.blocks
                except Exception as why:
                    if not resilience.is_device_fault(why):
                        raise
                    resilience.DegradationLog().record(
                        resilience.DegradationReason.ASYNC_DEVICE_FAULT,
                        site=self._site(f"wave#{fl.payload.serial}"),
                        detail=str(why),
                    )
                    self._carcass = None
                    out, steps, active = self._retry_wave(fl)
            else:
                out, steps, active = self._retry_wave(fl)
        now = time.perf_counter()
        self.stats.device_wait_s += now - wait0
        if fl.dispatch_t is not None:
            self.stats.device_busy_s += max(0.0, now - fl.dispatch_t)
            # the retrospective device-execution span: dispatch to
            # readback-ready — the Perfetto track a pipelined run's
            # overlap (and bench's trace_overlap_frac) reads from
            flight_recorder().add(
                "wave.device",
                fl.dispatch_t,
                now,
                track=self.fault_domain or "device",
                serial=fl.payload.serial,
            )
        view = ArenaView(out)
        # the spent output buffers become the next dispatch's donation
        # fodder (everything the host needs is in the view's numpy)
        self._carcass = out if self.mesh is None else None
        self.stats.arena_nodes = max(self.stats.arena_nodes, view.count)
        self.stats.waves += 1
        self.stats.device_steps += int(active)
        if fused is not None:
            # instructions the substeps advanced beyond the full-step
            # active count (specialized waves only) — kept BESIDE
            # device_steps, whose active-lanes-per-full-step semantics
            # the utilization comparison against device_steps_raw
            # pins; total instructions executed is device_steps +
            # spec_fused_steps + blockjit_steps. A blockjit wave's
            # substeps count into blockjit_steps, a fuse-only wave's
            # into spec_fused_steps — one or the other, never both.
            if (
                self.kernel_phases is not None
                and self.kernel_phases.block_depth > 0
            ):
                self.stats.blockjit_steps += int(fused)
                if blocks is not None:
                    self.stats.blockjit_blocks += int(blocks)
            else:
                self.stats.spec_fused_steps += int(fused)
        self.stats.device_steps_raw += int(steps) * len(fl.payload.flat)
        self.stats.evidence_bytes += view.bytes_fetched
        self.stats.evidence_bytes_full += view.bytes_full
        return view

    def _consume_wave(self, view: ArenaView, payload) -> None:
        """Fold one harvested wave into the tracks: triggers, carries,
        coverage, evidence, poison bookkeeping. Pure host work — under
        the pipelined schedule this (plus the reseed's flip solving)
        is exactly what overlaps the next wave's device execution."""
        with trace(
            "wave.consume", track=self.fault_domain, serial=payload.serial
        ):
            return self._consume_wave_inner(view, payload)

    def _consume_wave_inner(self, view: ArenaView, payload) -> None:
        flat = payload.flat
        L = self.lanes_per_contract
        status, halt_pc = view.status, view.halt_pc
        gas_min, gas_max = view.gas_min, view.gas_max
        tables = view.storage_tables()
        self._lane_carry = payload.lane_carry
        self.stats.lanes_degraded_mem += int(
            (status == Status.ERR_MEM).sum()
        )
        self.stats.lanes_degraded_unsupported += int(
            (status == Status.UNSUPPORTED).sum()
        )
        self._pending_props: List[Tuple[int, int, List]] = []
        srcs_memo: Dict[int, set] = {}
        for t in self.tracks:
            # a poison stripe is accounted for once the wave CARRYING
            # it has been harvested (under pipelining that wave may be
            # a later serial than the next one harvested)
            pending = getattr(t, "_poison_pending_serial", None)
            if pending is not None and payload.serial >= pending:
                t._poison_pending_serial = None
        for lane, (ci, data) in enumerate(flat):
            track = self.tracks[lane // L]
            if track.idle or track.parked:
                # parked: the published-final claim stays sound only
                # because nothing (evidence, degradation, carries)
                # mutates a frozen track
                continue
            # the SNAPSHOT carry, not the live one: poison journals
            # are refreshed in place by harvests that may run between
            # this wave's dispatch and its consume (pipelining), and
            # the lane executed against the snapshot
            carry = payload.carries[lane]
            st = int(status[lane])
            if st in (Status.ERR_MEM, Status.UNSUPPORTED):
                track.degraded += 1
            kind = TRIGGER_KINDS.get(st)
            if kind is not None:
                bucket = track.triggers.setdefault(kind, [])
                pc = int(halt_pc[lane])
                # one witness per faulting pc is what a report needs
                if all(pc != t["pc"] for t in bucket) and len(bucket) < 64:
                    trig = {
                        "pc": pc,
                        "input": data,
                        "prefix": list(carry["prefix"]),
                        "gas_min": int(gas_min[lane]),
                        "gas_max": int(gas_max[lane]),
                        "call_value": carry.get("callvalue", 0),
                        "prefix_values": list(
                            carry.get("prefix_values", [])
                        ),
                    }
                    if carry.get("base"):
                        trig["initial_storage"] = {
                            hex(k): hex(v)
                            for k, v in carry["base"].items()
                        }
                    if carry.get("balance"):
                        trig["initial_balance"] = carry["balance"]
                    bucket.append(trig)
            if st in (Status.STOPPED, Status.RETURNED):
                # the device mutation pruner: only end states whose
                # journal gained writes become next-tx start states
                journal = storage_dict_from(tables, lane)
                if journal != carry["journal"]:
                    if track.bank_carry(
                        journal,
                        list(carry["prefix"]) + [data],
                        parent=carry,
                    ):
                        self.stats.carries_banked += 1
            rows = view.journal(lane)
            for pc, taken, _tid in rows:
                track.covered.add((pc, taken))
            self._consume_evidence(
                track,
                view,
                lane,
                data,
                carry,
                st,
                int(gas_min[lane]),
                int(gas_max[lane]),
                rows,
                srcs_memo,
            )
        for track in self.tracks:
            if not track.idle:
                # the concolic symbolic-initial-storage axis: observed
                # never-written reads become adversarial start states
                track.ensure_poison_carries()
        for ci, track in enumerate(self.tracks):
            track.corpus.extend(payload.inputs[ci])

    #: env-source opcode -> the predictable-vars module's operation text
    _ENV_OPERATION = {
        "TIMESTAMP": "The block.timestamp environment variable",
        "NUMBER": "The block.number environment variable",
        "COINBASE": "The block.coinbase environment variable",
        "GASLIMIT": "The block.gaslimit environment variable",
        "BLOCKHASH": "The block hash of a previous block",
    }

    def _consume_evidence(
        self, track, view, lane, data, carry, st, gmin, gmax, rows, srcs_memo
    ) -> None:
        """Fold one lane's banked events + journal provenance into the
        track's evidence map. Everything recorded here was CONCRETELY
        exhibited by the lane — the record's input/prefix replays it —
        so issue synthesis (analysis/evidence.py) needs no solver.

        Calls with a calldata-derived target additionally enqueue a
        STEERING query (path + target == attacker): its witness seeds a
        lane next wave, whose concrete execution then confirms the
        SWC-105/107/112 property the reference modules solve for."""

        def base(extra: Dict) -> Dict:
            rec = {
                "input": data,
                "prefix": list(carry["prefix"]),
                "gas_min": gmin,
                "gas_max": gmax,
                "call_value": carry.get("callvalue", 0),
                "prefix_values": list(carry.get("prefix_values", [])),
            }
            if carry.get("base"):
                # poisoned start state: the witness must declare the
                # synthetic initial storage it assumed
                rec["initial_storage"] = {
                    hex(k): hex(v) for k, v in carry["base"].items()
                }
            if carry.get("balance"):
                rec["initial_balance"] = carry["balance"]
            rec.update(extra)
            return rec

        halted_clean = st in (Status.STOPPED, Status.RETURNED)
        n_branches = int(view.br_cnt[lane])
        if int(view.ev_overflow[lane]):
            track.event_overflow = True
        if int(view.ev_cnt[lane]):
            for ev in view.events(lane):
                pc, k = ev["pc"], ev["kind"]
                if k in WRAP_EVENT_OPS:
                    exact = {
                        1: ev["a"] + ev["b"] >= 2**256,
                        2: ev["a"] < ev["b"],
                        3: ev["a"] * ev["b"] >= 2**256,
                    }[k]
                    key = ("wrap", pc)
                    if not exact:
                        # the device's wrap flag over-approximated (MUL
                        # uses a 128-bit hi check): whether any input
                        # wraps HERE is undecided on device. Mark the
                        # site opaque — ownership is withheld unless a
                        # steering query (kinds 10-12) or a later exact
                        # wrap resolves the same pc
                        track.opaque_sites.add(pc)
                    if exact and key not in track.evidence:
                        # "the wrapped value was USED" (integer.py's
                        # promotion rule): DAG reachability when the
                        # result is a term; for opaque results (taint-
                        # hashed mapping reads) the static in-block
                        # store/return check stands in
                        used = (
                            view.wrap_used(lane, ev["tid"])
                            if ev["tid"] > 0
                            else track.result_stored_in_block(pc)
                        )
                        if used:
                            track.evidence[key] = base(
                                {
                                    "class": "wrap",
                                    "pc": pc,
                                    "op": WRAP_EVENT_OPS[k],
                                }
                            )
                elif k in CALL_EVENT_KINDS:
                    mnemonic = CALL_EVENT_KINDS[k]
                    key = ("call", pc, mnemonic)
                    to_attacker = ev["a"] == DEFAULT_CALLER
                    rec = track.evidence.get(key)
                    if rec is None:
                        rec = track.evidence[key] = base(
                            {
                                "class": "call",
                                "pc": pc,
                                "kind": mnemonic,
                                "gas": ev["gas"],
                                "to_attacker": False,
                                "value_to_attacker": False,
                                "target_tainted": ev["tid"] != 0,
                                "unchecked": False,
                            }
                        )
                    rec["gas"] = max(rec["gas"], ev["gas"])
                    rec["target_tainted"] = rec["target_tainted"] or ev["tid"] != 0
                    if to_attacker and not rec["to_attacker"]:
                        # THIS lane exhibits the attacker-target
                        # property: its input is the witness worth
                        # reporting
                        rec.update(
                            to_attacker=True,
                            input=data,
                            prefix=list(carry["prefix"]),
                            gas_min=gmin,
                            gas_max=gmax,
                        )
                    if to_attacker:
                        # the stipend gate for attacker-targeted issues
                        # must see gas from a lane that ALSO proved the
                        # target — not the max over unrelated lanes
                        rec["attacker_gas"] = max(
                            rec.get("attacker_gas", 0), ev["gas"]
                        )
                    sent = sum(
                        carry.get("prefix_values", [])
                    ) + carry.get("callvalue", 0)
                    if to_attacker and ev["b"] > sent and not rec["value_to_attacker"]:
                        # the attacker PROFITS: receives more than the
                        # whole sequence sent in (ether_thief.py's
                        # balance-increase property). THIS lane's input
                        # replays the profit — bank it beside the shared
                        # record so the synthesized issue's witness
                        # exhibits the property it claims
                        rec["value_to_attacker"] = True
                        # explicit None/0 defaults: the merged issue
                        # dict must not inherit the shared record's
                        # initial_storage/balance when THIS lane ran
                        # without them (the witness would declare a
                        # synthetic start state it never assumed)
                        rec["w_profit"] = dict(
                            {"initial_storage": None, "initial_balance": 0},
                            **base({}),
                        )
                    if (
                        halted_clean
                        and n_branches == ev["aux"]
                        and not rec["unchecked"]
                    ):
                        # the lane ended with NO branch after the call:
                        # nothing ever constrained the return value.
                        # Same rule: the witness is this lane's input
                        rec["unchecked"] = True
                        rec["w_unchecked"] = dict(
                            {"initial_storage": None, "initial_balance": 0},
                            **base({}),
                        )
                    # steering: make a lane send the call to the
                    # attacker (confirms next wave, concretely)
                    if (
                        ev["tid"] > 0
                        and ev["gas"] > GAS_STIPEND
                        and k in (4, 6)
                        and not rec["to_attacker"]
                        and (pc, k) not in track.prop_attempted
                    ):
                        conds = self._steer_conditions(view, lane, ev)
                        if conds is not None:
                            track.prop_attempted.add((pc, k))
                            self._pending_props.append(
                                (lane // self.lanes_per_contract,
                                 self._lane_carry[lane],
                                 conds,
                                 (pc, k))
                            )
                elif k in (10, 11, 12):
                    # tainted arithmetic that has not wrapped on any
                    # lane yet: steer a lane into the wrap (the witness
                    # seeds next wave; the concrete wrap then banks as
                    # kind 1-3 and becomes evidence)
                    if (
                        ("wrap", pc) not in track.evidence
                        and (pc, k) not in track.prop_attempted
                    ):
                        conds = self._steer_wrap_conditions(view, lane, ev)
                        if conds is not None:
                            track.prop_attempted.add((pc, k))
                            self._pending_props.append(
                                (lane // self.lanes_per_contract,
                                 self._lane_carry[lane],
                                 conds,
                                 (pc, k))
                            )
                elif k in (8, 9):
                    access = "SSTORE" if k == 8 else "SLOAD"
                    key = ("state_acc", pc, access)
                    if key not in track.evidence:
                        track.evidence[key] = base(
                            {"class": "state_acc", "pc": pc, "access": access}
                        )
                elif k == 13:
                    track.storage_reads.add(ev["a"])
                elif k == 15:
                    track.opaque_sites.add(pc)
        for pc, taken, tid in rows:
            if tid == 0:
                continue
            srcs = srcs_memo.get(tid)
            if srcs is None:
                srcs = srcs_memo[tid] = view.dag_source_ops(tid)
            if "ORIGIN" in srcs:
                key = ("env", pc, "115")
                if key not in track.evidence:
                    track.evidence[key] = base(
                        {"class": "env", "pc": pc, "swc": "115", "operation": ""}
                    )
            hits = [s for s in PREDICTABLE_SRCS if s in srcs]
            if hits:
                swc = "116" if "TIMESTAMP" in hits else "120"
                key = ("env", pc, swc)
                if key not in track.evidence:
                    track.evidence[key] = base(
                        {
                            "class": "env",
                            "pc": pc,
                            "swc": swc,
                            "operation": self._ENV_OPERATION[hits[0]],
                        }
                    )

    def _steer_conditions(self, view, lane, ev):
        """Path-prefix + (target == attacker) [+ value > 0] for a call
        event — the property the reference's 105/107/112 modules query,
        phrased as a seed-derivation problem."""
        from mythril_tpu.laser.smt import UGT, symbol_factory

        target = view.term(ev["tid"], lane)
        if target is None:
            return None
        path = view.path_condition(lane, ev["aux"] - 1, flip_last=False) or []
        attacker = symbol_factory.BitVecVal(DEFAULT_CALLER, 256)
        conds = path + [target == attacker]
        if ev["kind"] == 4 and ev["vtid"] > 0:
            value = view.term(ev["vtid"], lane)
            if value is not None:
                conds.append(UGT(value, symbol_factory.BitVecVal(0, 256)))
        return conds

    def _steer_wrap_conditions(self, view, lane, ev):
        """Path-prefix + the exact wrap predicate for a tainted arith
        site — the property integer.py solves at transaction end,
        phrased as a seed-derivation problem."""
        from mythril_tpu.laser.smt import UDiv, UGT, ULT, symbol_factory

        operands = view.row_operand_terms(ev["tid"], lane)
        if operands is None:
            return None
        a, b = operands
        path = view.path_condition(lane, ev["aux"] - 1, flip_last=False) or []
        zero = symbol_factory.BitVecVal(0, 256)
        if ev["kind"] == 10:  # ADD wraps iff a + b < a
            wrap = ULT(a + b, a)
        elif ev["kind"] == 11:  # SUB wraps iff a < b
            wrap = ULT(a, b)
        else:  # MUL wraps iff b != 0 and a > MAX // b
            maxw = symbol_factory.BitVecVal(2**256 - 1, 256)
            wrap = UGT(a, UDiv(maxw, b))
            path = path + [b != zero]
        return path + [wrap]

    def _collect_flip_candidates(
        self, view: ArenaView, ci: int
    ) -> List[Tuple[int, List, Tuple[int, bool]]]:
        """Contract ci's un-attempted frontier branches this wave: one
        candidate per lane (the lane's first flippable uncovered
        target), each a (carry index, decoded path condition, target)
        triple. A flip witness stays bound to its source lane's carry —
        the path condition only holds under that start state."""
        track = self.tracks[ci]
        if track.parked:
            return []  # frozen: flags untouched
        if track.idle:
            track.exhausted = True
            return []
        L = self.lanes_per_contract
        candidates: List[Tuple[int, List, Tuple[int, bool]]] = []
        # every lane may contribute one candidate (bounded by the lane
        # count): unsat candidates cost one short CDCL sprint each
        # (time-capped in _sprint_flips) and surplus feasible witnesses
        # still seed lanes, so oversampling loses nothing — while
        # under-sampling would blacklist targets via `attempted`
        # without ever solving them
        for lane in range(ci * L, (ci + 1) * L):
            for k, (pc, taken, tid) in enumerate(view.journal(lane)):
                target = (pc, not taken)
                if tid <= 0:
                    continue  # concrete or opaque condition: nothing to flip
                if target in track.static_dead:
                    # the static pass proved this direction infeasible
                    # (constant condition) or inert (dispatcher entry
                    # of an effect-free function): a solve would be
                    # UNSAT or pure waste — blacklist without spending
                    # the sprint
                    if target not in track.attempted:
                        track.attempted.add(target)
                        self.stats.static_pruned_flips += 1
                    continue
                if target in track.covered or target in track.attempted:
                    continue
                track.attempted.add(target)
                self.stats.forks_tried += 1
                conditions = view.path_condition(lane, k, flip_last=True)
                if conditions is None:
                    # opaque decision upstream: unflippable. Recorded —
                    # the ownership gate demands some concrete lane
                    # cover the target anyway (poison samples usually
                    # do) before the contract can be device-owned.
                    track.opaque_branches.add(target)
                    continue
                candidates.append((self._lane_carry[lane], conditions, target))
                break
        return candidates

    def _reseed(
        self, view: ArenaView
    ) -> Tuple[Optional[List[List[Tuple[int, bytes]]]], int]:
        """(next-wave inputs, pending flip work): per contract, flip
        witnesses topped up with mutations of its corpus. Inputs are
        None when every contract's frontier is exhausted; the count is
        flip witnesses plus sprint-capped candidates still awaiting a
        genuine solve (so the phase loop never concludes exhaustion
        over queries nobody attempted).

        Candidates are collected across the WHOLE corpus first and
        solved as one batch, so hard queries share a single device
        dispatch instead of paying per-query latency. Only the
        host-symbolic stages (term decode + CDCL sprint + lowering)
        hold the host lock; the device dispatch and the track
        bookkeeping are lock-free."""
        from contextlib import nullcontext

        props = getattr(self, "_pending_props", [])
        self._pending_props = []
        guard = self.host_lock if self.host_lock is not None else nullcontext()
        device_first = self._device_first()
        self.lock_wanted.set()
        try:
            with guard:
                per_contract = [
                    self._collect_flip_candidates(view, ci)
                    for ci in range(len(self.tracks))
                ]
                flat = [c for cands in per_contract for c in cands]
                # property-steering queries ride the same funnel batch
                # as the flips (same cost model, same device dispatch)
                batch = [cond for _, cond, _ in flat] + [p[2] for p in props]
                solved: List[Optional[Dict[str, int]]] = [None] * len(batch)
                if device_first:
                    # INVERTED funnel (ISSUE 9): lower the WHOLE
                    # frontier under the lock, so the one batched
                    # device dispatch — whose cost does not grow with
                    # query count — fires first, lock-free
                    lowered_batch, kept = self._lower_flips(batch)
                else:
                    # legacy host-first order (the parity baseline):
                    # the sprint sees everything, the device only its
                    # survivors
                    capped, survivors = self._sprint_flips(batch, solved)
                    lowered_batch, kept = self._lower_flips(
                        batch, indices=survivors
                    )
        finally:
            self.lock_wanted.clear()
        device_unsat: set = set()
        if device_first:
            # once stopping, no new device dispatch: a cold one runs for
            # minutes past the owner's wind-down (the sprint below then
            # records every query preempted)
            answered, device_unsat = (
                (set(), set())
                if self._stop_requested()
                else self._device_flips(solved, lowered_batch, kept)
            )
            # the ESCALATION ladder: host CDCL only sees the device's
            # unknown survivors (and the queries that never lowered)
            self.lock_wanted.set()
            try:
                with guard:
                    capped, _survivors = self._sprint_flips(
                        batch, solved, skip=answered
                    )
            finally:
                self.lock_wanted.clear()
        else:
            _answered, device_unsat = self._device_flips(
                solved, lowered_batch, kept, device_first=False
            )
        # a capped query that the device also failed to answer (or that
        # never compiled) had no genuine attempt; sprint-attempted and
        # device-answered ones (including device-owned unsats) are
        # spoken for
        retriable = {
            i
            for i in capped
            if solved[i] is None and i not in device_unsat and i < len(flat)
        }
        # steering witnesses: calldata that makes a banked call site
        # target the attacker — seeded below, confirmed concretely by
        # the next wave's event bank
        steer: Dict[int, List[Tuple[int, bytes]]] = {}
        for j, (tidx, carry_idx, _conds, key) in enumerate(props):
            assignment = solved[len(flat) + j]
            trk = self.tracks[tidx]
            if assignment is not None:
                witness = self._witness_bytes(assignment)
                steer.setdefault(tidx, []).append((carry_idx, witness))
                trk.flip_corpus.append(witness)
                # sat resolves the property only once a seeded lane
                # CONFIRMS it concretely (wrap/to_attacker evidence —
                # _unresolved_steering checks that side)
            elif len(flat) + j not in capped:
                # a genuine unsat answer closes the property
                trk.prop_resolved.add(key)
            else:
                # sprint-capped: never attempted — lift the mark so a
                # later wave retries instead of leaving it open forever
                trk.prop_attempted.discard(key)

        stripes: List[List[Tuple[int, bytes]]] = []
        track_has_payload: List[bool] = []
        n_flips = 0
        n_retriable = 0
        cursor = 0
        for ci, track in enumerate(self.tracks):
            if track.parked:
                # frozen stripe: shape-stable placeholder lanes (empty
                # calldata halts immediately); harvest ignores them
                stripes.append([(0, b"")] * self.lanes_per_contract)
                track_has_payload.append(False)
                continue
            fresh: List[Tuple[int, bytes]] = list(
                steer.get(ci, [])[: self.lanes_per_contract]
            )
            had_retriable = False
            for carry_idx, _cond, target in per_contract[ci]:
                assignment = solved[cursor]
                if cursor in retriable:
                    # never actually attempted (sprint cap): lift the
                    # blacklist so a later wave gets a real try
                    track.attempted.discard(target)
                    had_retriable = True
                    n_retriable += 1
                cursor += 1
                # every feasible witness seeds a lane (up to the stripe
                # width) — a solved flip discarded here would leave its
                # target blacklisted in `attempted` yet never explored
                if assignment is None or len(fresh) >= self.lanes_per_contract:
                    continue
                self.stats.forks_feasible += 1
                witness = self._witness_bytes(assignment)
                fresh.append((carry_idx, witness))
                track.flip_corpus.append(witness)
            # a frontier with un-attempted (capped) candidates is not
            # exhausted — it just hasn't had its turn with the solver
            track.exhausted = not fresh and not had_retriable
            if track.exhausted:
                # snapshot: if later waves (mutation-filled lanes of a
                # corpus that is still running for OTHER contracts)
                # uncover nothing new here, this frontier may claim
                # closure even when the PHASE ends on budget/wave-cap
                track._exhausted_cov = len(track.covered)
            track_has_payload.append(bool(fresh))
            n_flips += len(fresh)
            # mutation fill — and the poison carries' ONLY seed source:
            # synthetic start states are appended mid-phase, so no flip
            # or phase seed ever points at them; without this rotation
            # a poisoned state would exist but never execute
            poison_idx = track.poison_indices()
            fill_no = 0
            while len(fresh) < self.lanes_per_contract:
                carry_idx, parent = self.rng.choice(track.corpus)
                if poison_idx:
                    rotation = fill_no % (len(poison_idx) + 1)
                    if rotation < len(poison_idx):
                        carry_idx = poison_idx[rotation]
                fill_no += 1
                mutated = bytearray(parent)
                mutated[self.rng.randrange(len(mutated))] = self.rng.randrange(
                    256
                )
                fresh.append((carry_idx, bytes(mutated)))
            stripes.append(fresh[: self.lanes_per_contract])
        pending = n_flips + n_retriable
        # Poison continuation: adversarial-storage carries are created
        # AFTER the wave that observed the reads, so when the flip
        # frontier dries up in that same wave they have never run.
        # Give every unseeded poisoned state one dedicated stripe of
        # dispatcher seeds — the wave that concretely exhibits the
        # storage-dependent wraps/thefts the host finds with symbolic
        # storage.
        n_poison = 0
        for ci, track in enumerate(self.tracks):
            if track.idle or track.parked or track_has_payload[ci]:
                # flip/steer witnesses keep their stripe; the poison
                # pass waits for a drier wave
                continue
            # at most two poisoned states per wave: a full stripe per
            # state beats a sliver of every state
            pend = track.unseeded_poison()[:2]
            if not pend:
                continue
            seeds = list(track.selector_seeds or []) + list(
                track.parent_inputs or []
            )
            if not seeds:
                seeds = [b"\x00" * self.calldata_len]
            stripes[ci] = [
                (
                    pend[j % len(pend)],
                    seeds[(j // len(pend)) % len(seeds)],
                )
                for j in range(self.lanes_per_contract)
            ]
            for i in pend:
                track.carries[i]["seeded"] = True
            # the stripe is SCHEDULED but runs in the wave the NEXT
            # dispatch launches (serial self._wave_serial): finality
            # must wait for that wave's HARVEST (parking now would
            # freeze the track with the poison results discarded —
            # unsound ownership). Tagging the serial — rather than a
            # boolean the next harvest clears — keeps this sound under
            # pipelining, where an older wave is harvested after the
            # poison stripe was scheduled but before it runs.
            track._poison_pending_serial = self._wave_serial
            n_poison += 1
        pending += n_poison
        #: the phase loop must not plateau-break away a wave that
        #: carries freshly-seeded poison stripes
        self._poison_stripes_pending = n_poison
        return (stripes if pending else None), pending

    # -- the phase loop ------------------------------------------------
    def _phase(self, txn: int) -> bool:
        """One attacker transaction's wave loop over the whole corpus;
        False when the wall-clock budget is exhausted. The schedule is
        either lock-step (--no-pipeline: dispatch, harvest, solve,
        repeat) or double-buffered (default: up to two waves in
        flight, host work overlapping device execution)."""
        if self.pipeline:
            return self._phase_pipelined(txn)
        return self._phase_sync(txn)

    def _plateau_break(self, plateaued: bool, n_flips: int) -> bool:
        """Coverage stalled and flips are drying up — but only once
        every poisoned state has had its seeding wave (those open
        value dimensions coverage cannot see); a wave whose stripes
        WERE just poison-seeded must run before the verdict counts."""
        quota = len(self.tracks) * self.lanes_per_contract
        return (
            plateaued
            and n_flips < max(1, quota // 4)
            and not getattr(self, "_poison_stripes_pending", 0)
            and not any(
                t.unseeded_poison() for t in self.tracks if not t.idle
            )
        )

    def _finalize_tracks(self) -> Tuple[List, bool]:
        """Early per-contract finality (last transaction phase only):
        contracts that just closed every ownership gate freeze NOW."""
        newly_parked = [
            t
            for t in self.tracks
            if not t.parked and t.finalize_if_complete()
        ]
        if newly_parked:
            self._publish_partial()
        return newly_parked, all(
            t.parked or t.idle for t in self.tracks
        )

    def _phase_sync(self, txn: int) -> bool:
        inputs = self._seed_phase_inputs()
        for wave_no in range(self.waves):
            if self._stop_requested():
                # honored before DISPATCHING a wave, not only at the
                # budget check — the last-wave break and the phase
                # advance both skip _budget_spent
                return False
            covered_before = sum(len(t.covered) for t in self.tracks)
            w0 = time.perf_counter()
            payload = self._prepare_wave(inputs)
            fl = self._dispatch_wave(payload)
            view = self._harvest_wave(fl)
            self._consume_wave(view, payload)
            self._wave_times.append(time.perf_counter() - w0)
            self.stats.wave_exec_s += self._wave_times[-1]
            if txn == 0 and wave_no == 0:
                # the first wave carries the one-time kernel compile
                # (amortized machine-wide by the persistent cache);
                # the budget governs the steady-state loop after it
                self._t0 = time.perf_counter()
            self._publish_partial()
            if wave_no == self.waves - 1:
                # the wave cap ends the phase with the final wave's
                # results never reseeded: `exhausted` is stale for any
                # track whose coverage moved since its snapshot, so
                # only provably-still-exhausted frontiers stay closed
                for track in self.tracks:
                    if not track.idle and not track.still_exhausted():
                        track.frontier_closed = False
                break  # no next wave to seed; don't waste solver calls
            if self._budget_spent():
                return False
            covered_now = sum(len(t.covered) for t in self.tracks)
            plateaued = wave_no > 0 and covered_now == covered_before
            fresh, n_flips = self._reseed(view)
            if txn == self.transaction_count - 1:
                # early per-contract finality: a contract that just
                # closed all its ownership gates freezes NOW, and the
                # publisher announces it so the analysis loop can skip
                # its host walk without waiting for the corpus run
                _, all_done = self._finalize_tracks()
                if all_done:
                    return True  # everything owned or inert: run over
            if fresh is None:
                break  # every frontier exhausted: the plateau signal
            if self._plateau_break(plateaued, n_flips):
                break
            inputs = fresh
        return True

    def _phase_pipelined(self, txn: int) -> bool:
        """The double-buffered schedule: wave N+1 is seeded from the
        frontier known BEFORE wave N's results and dispatched ahead of
        wave N's consume, so the host's evidence consumption and flip
        solving for wave N overlap the device's execution of wave N+1
        (the flip witnesses land in wave N+2 — one wave later than the
        lock-step schedule, bought back by the extra dispatch slot).

        The in-flight queue holds at most two waves. Harvest order is
        dispatch order; the fault containment in _harvest_wave keeps
        per-wave attribution even when the fault is asynchronous."""
        from collections import deque

        inflight: "deque[_Inflight]" = deque()
        # the warm-up second slot rides free: the lock-step schedule
        # gets `waves` reseed generations, and so does this one
        dispatch_budget = self.waves + 1 if self.waves > 1 else self.waves
        dispatched = 0
        stop_dispatch = False
        finished = True
        harvested = 0

        def _launch(stripes) -> None:
            nonlocal dispatched
            payload = self._prepare_wave(stripes)
            inflight.append(self._dispatch_wave(payload))
            dispatched += 1
            self.stats.waves_inflight_max = max(
                self.stats.waves_inflight_max, len(inflight)
            )

        if self._stop_requested():
            return False
        _launch(self._seed_phase_inputs())
        if dispatch_budget > 1 and not self._stop_requested():
            # the second pipeline slot: the seed stream's next window —
            # the only inputs derivable before any harvest
            _launch(self._seed_phase_inputs(offset=1))

        while inflight:
            fl = inflight.popleft()
            w0 = time.perf_counter()
            view = self._harvest_wave(fl)
            h0 = time.perf_counter()
            overlapping = bool(inflight)  # device busy with wave N+1
            covered_before = sum(len(t.covered) for t in self.tracks)
            self._consume_wave(view, fl.payload)
            self._wave_times.append(time.perf_counter() - w0)
            self.stats.wave_exec_s += self._wave_times[-1]
            harvested += 1
            if txn == 0 and harvested == 1:
                self._t0 = time.perf_counter()
            self._publish_partial()
            covered_now = sum(len(t.covered) for t in self.tracks)

            if not stop_dispatch and self._stop_requested():
                stop_dispatch = True
                finished = False
            if not stop_dispatch and dispatched >= dispatch_budget:
                stop_dispatch = True  # the wave cap: drain what's left
            if not stop_dispatch and self._budget_spent():
                stop_dispatch = True
                finished = False
            if not stop_dispatch:
                plateaued = harvested > 1 and covered_now == covered_before
                fresh, n_flips = self._reseed(view)
                if fresh is not None and not self._plateau_break(
                    plateaued, n_flips
                ):
                    _launch(fresh)
                # an exhausted/plateaued frontier on THIS wave only
                # skips launching from it — a still-in-flight wave may
                # carry flip witnesses that reopen it, and ITS harvest
                # gets its own reseed verdict (the lock-step loop's
                # break maps to the drain running out of launches).
                # Per-contract finality is NOT checked here: every
                # in-flight wave carries live stripes for every
                # unparked track (mutation fill), so parking one now
                # would discard results already executing — the
                # lock-step schedule's mid-phase parking moves to the
                # drain end below, where nothing is in flight.
            if overlapping:
                self.stats.waves_overlapped += 1
                self.stats.wave_overlap_s += time.perf_counter() - h0

        # the phase's last harvested wave(s) were never reseeded: the
        # lock-step final-wave rule applies — only provably-still-
        # exhausted frontiers stay closed
        for track in self.tracks:
            if not track.idle and not track.still_exhausted():
                track.frontier_closed = False
        if txn == self.transaction_count - 1 and finished:
            self._finalize_tracks()
        return finished

    def _publish_partial(self) -> None:
        if self.publish is None:
            return
        for ci, track in enumerate(self.tracks):
            if track.parked:
                # the frozen FINAL outcome — final_for_contract lets
                # the ownership consumer act on it mid-run
                outcome = dict(track._final_outcome)
            else:
                outcome = track.outcome()
            # per-track copy: consumers annotate their stats dict
            # (witness_issues), so sharing one object across contracts
            # would let them clobber each other
            outcome["stats"] = dict(self.stats.as_dict(), partial=True)
            self.publish(ci, outcome)

    def _budget_spent(self) -> bool:
        return self._allowance_spent(self._phase_allowance)

    def _hard_stop(self) -> bool:
        """The +45s slack line past which even a phase's guaranteed
        opening wave is forfeit (billed in the mode's own currency:
        active time when overlapped, wall otherwise)."""
        if self.budget_s is None:
            return False
        if self.host_lock is not None:
            active = self.stats.wave_exec_s + self.stats.flip_solve_s
            return active > self.budget_s + 45
        return time.perf_counter() - self._t_start > self.budget_s + 45

    def _allowance_spent(self, allowance: Optional[float]) -> bool:
        if self._stop_requested():
            return True
        budget_s = allowance if allowance is not None else self.budget_s
        if budget_s is None:
            return False
        # predict the next wave from steady-state waves only — wave 0
        # carries the compile, so until a second wave has run the
        # prediction is optimistic by design (the overshoot is bounded
        # by one wave)
        predicted = (
            min(self._wave_times[1:]) if len(self._wave_times) > 1 else 0.0
        )
        if self.host_lock is not None:
            # overlapped: bill only ACTIVE time — wall spent waiting on
            # the lock is the main thread's analysis time, not ours
            active = self.stats.wave_exec_s + self.stats.flip_solve_s
            if active > budget_s + 45:
                return True
            steady = active - (
                self._wave_times[0] if self._wave_times else 0.0
            )
            return steady + predicted > budget_s
        # hard stop: the whole prepass — compile included — may cost
        # at most one compile allowance (45s, paid at most once per
        # kernel shape per machine thanks to the persistent cache) on
        # top of the steady-state budget; the compile itself cannot be
        # interrupted from here
        if time.perf_counter() - self._t_start > budget_s + 45:
            return True
        elapsed = time.perf_counter() - self._t0
        return elapsed + predicted > budget_s

    def run(self) -> Dict:
        """Phase loop: one wave loop per attacker transaction, carries
        (mutated storage journals + their calldata prefixes) advancing
        between phases. Stops at `transaction_count`, on a corpus-wide
        dead end, or on the wall-clock budget."""
        from mythril_tpu.laser.smt.solver.device_race import DEVICE_BUSY

        DEVICE_BUSY.acquire()
        try:
            with trace(
                "explore.run",
                track=self.fault_domain,
                contracts=len(self.tracks),
            ):
                return self._run_phases()
        finally:
            if self._ckpt_writer is not None:
                # outcomes must never race their own checkpoints; close
                # also retires the worker thread (a later run() would
                # lazily restart it)
                self._ckpt_writer.close()
            if self._kernel is not None:
                # unpin this run's specialization bucket (the kernel
                # cache LRU may now evict it; the jit cache keeps it
                # warm for the next explorer until then)
                from mythril_tpu.laser.batch.specialize import kernel_cache

                kernel_cache().release(self._kernel)
            DEVICE_BUSY.release()

    def _run_phases(self) -> Dict:
        self._t_start = self._t0 = time.perf_counter()
        self._wave_times: List[float] = []
        for txn in range(self.transaction_count):
            if txn >= 2 and self._hard_stop():
                # A spent budget ends the CURRENT phase's wave loop but
                # phase 2 (the `-t 2` threat model) still gets its
                # unconditional opening wave; DEEPER phases only open
                # while inside the hard stop's +45s slack — without
                # this gate a `-t 4` corpus run overshoots by one
                # ~30-60s wave per remaining phase. Checked BEFORE
                # advance_phase(): the break must not first consume the
                # banked carries and wipe the last phase's corpus stats
                # (outcomes would publish corpus_size 0 after a full
                # phase of exploration).
                for track in self.tracks:
                    if track.next_carries:
                        # a banked tx-N+1 start state will never run
                        track.frontier_closed = False
                break
            if txn > 0:
                advanced = [t.advance_phase() for t in self.tracks]
                if not any(advanced):
                    break  # no contract mutated state: tx N+1 is moot
                for track in self.tracks:
                    track.corpus = []
            # Cumulative allowance per transaction phase: phase k may
            # spend at most (k+1)/T of the budget, so phase 1 cannot
            # eat the whole budget before the later transactions — the
            # `-t 2` threat model — ever execute (the last phase's
            # share is the full budget). Without this, a corpus-sized
            # wave bill starves phase 2 exactly when the multi-tx
            # exploration matters most.
            self._phase_allowance = (
                None
                if self.budget_s is None
                else self.budget_s * (txn + 1) / self.transaction_count
            )
            if txn == self.transaction_count - 1:
                # carries dropped during the LAST phase feed no further
                # phase: overflow there must not block completeness
                for track in self.tracks:
                    track._final_phase_overflow_base = track.carry_overflow
            self.stats.transactions = txn + 1
            try:
                with trace(
                    "phase", track=self.fault_domain, txn=txn,
                    contracts=len(self.tracks),
                ):
                    finished = self._phase(txn)
            except DeviceDispatchError as why:
                # a wave died past the retry ladder: the exploration
                # DEGRADES — every live frontier reopens (those
                # contracts go to the host walk), the banked evidence
                # and coverage so far stay valid, and the corpus run
                # continues instead of crashing
                from mythril_tpu.support.resilience import (
                    DegradationLog,
                    DegradationReason,
                )

                DegradationLog().record(
                    DegradationReason.WAVE_ABANDONED,
                    site=self._site("explorer"),
                    detail=str(why),
                )
                self.stats.device_faults += 1
                for track in self.tracks:
                    if not track.idle and not track.still_exhausted():
                        track.frontier_closed = False
                break
            # completeness accounting: a phase that ended on budget or
            # wave cap (or a stop request) leaves live frontiers open —
            # those contracts are NOT device-complete and the ownership
            # gate must send them to the host walk
            stopped = self._stop_requested()
            for track in self.tracks:
                if not track.idle and not track.exhausted:
                    track.frontier_closed = False
                if (
                    (not finished or stopped)
                    and not track.idle
                    and not track.still_exhausted()
                ):
                    # the PHASE ended early (budget/wave-cap/stop), but
                    # a track whose own frontier exhausted — and whose
                    # coverage hasn't moved since — is done regardless
                    # of why the corpus loop stopped; marking every
                    # track open here was the corpus-scale ownership
                    # killer (32-contract bench: 0 owned)
                    track.frontier_closed = False
            # A stop REQUEST (the overlapped owner shutting us down)
            # ends everything now.
            if stopped:
                break

        for track in self.tracks:
            base = getattr(track, "_final_phase_overflow_base", None)
            if base is not None:
                track.carry_overflow = base
        self.stats.branches_covered = sum(len(t.covered) for t in self.tracks)
        self.stats.wall_s = round(time.perf_counter() - self._t_start, 3)
        self.stats.wave_exec_s = round(self.stats.wave_exec_s, 3)
        self.stats.flip_solve_s = round(self.stats.flip_solve_s, 3)
        # pipeline observability: how much of the device's execution
        # span the host covered with concurrent work, how much of the
        # run the device sat idle, and what the compacted readback
        # transferred per wave (bench.py reports all three)
        busy = self.stats.device_busy_s
        wall = self.stats.wall_s
        self.stats.wave_overlap_ratio = (
            round(min(1.0, self.stats.wave_overlap_s / busy), 3)
            if busy > 0
            else 0.0
        )
        self.stats.device_idle_frac = (
            round(max(0.0, min(1.0, 1.0 - busy / wall)), 3)
            if wall > 0
            else 0.0
        )
        self.stats.evidence_bytes_per_wave = (
            int(self.stats.evidence_bytes / self.stats.waves)
            if self.stats.waves
            else 0
        )
        self.stats.device_wait_s = round(self.stats.device_wait_s, 3)
        self.stats.device_busy_s = round(self.stats.device_busy_s, 3)
        self.stats.wave_overlap_s = round(self.stats.wave_overlap_s, 3)
        if self._kernel is not None:
            # the bucket's first-call trace+compile wall (0 once warm)
            self.stats.kernel_compile_s = round(
                self._kernel.compile_s, 3
            )
        stats = self.stats.as_dict()
        if self._halt_reason:
            # WHY the run ended early (deadline-expired / interrupted /
            # stop-event) — consumers mark the outcome partial with a
            # structured reason instead of guessing from counters
            stats["halt_reason"] = self._halt_reason
        # the registry view of this run: the process-wide mtpu_explore_*
        # series /metrics scrapes (the legacy dict above is the per-run
        # view — tests pin the two equal over a run's delta)
        publish_explore_stats(stats)
        return {
            "stats": stats,
            "contracts": [
                dict(t._final_outcome) if t.parked else t.outcome()
                for t in self.tracks
            ],
        }


def replay_wave(path, expect_shape=None):
    """Re-execute a flushed wave checkpoint exactly.

    The explorer writes each wave's SEEDED frontier (StateBatch + code
    table + synthetic-storage mask) to `checkpoint_path` before the
    dispatch, so a run killed mid-wave loses nothing: this function
    reloads the npz, rebuilds the symbolic batch — reapplying the
    synthetic mask the same way the wave dispatch did — and runs the
    wave to the same step budget. The engine is deterministic, so the
    replayed coverage/status/evidence equal the uninterrupted wave's
    (tests/laser/test_resilience.py asserts this bit-for-bit).

    `expect_shape` (checkpoint.arena_shape dict, partial fine) makes a
    checkpoint written under a different arena shape refuse with a
    clear error instead of replaying garbage lanes — the persistent
    service pins its warm arena shape through this.

    Returns (ArenaView, sym_out, steps)."""
    import jax.numpy as jnp

    from mythril_tpu.laser.batch.checkpoint import (
        load_checkpoint,
        load_checkpoint_extra,
    )

    batch, code, wave_steps = load_checkpoint(path, expect_shape=expect_shape)
    if code is None:
        raise ValueError("wave checkpoint carries no code table")
    sym = make_sym_batch(batch)
    synthetic = load_checkpoint_extra(path).get("synthetic")
    if synthetic is not None and synthetic.any():
        seeded = (
            jnp.arange(sym.sval_tid.shape[1])[None, :]
            < jnp.asarray(batch.storage_cnt)[:, None]
        )
        sym = sym._replace(
            sval_tid=jnp.where(
                jnp.asarray(synthetic.astype(bool))[:, None] & seeded,
                jnp.int32(-1),
                sym.sval_tid,
            )
        )
    out, steps, _active = sym_run(sym, code, max_steps=int(wave_steps))
    return ArenaView(out), out, int(steps)


class DeviceSymbolicExplorer(DeviceCorpusExplorer):
    """Explore one contract's intra-transaction paths on device — the
    single-contract view the per-contract analysis path uses."""

    def __init__(
        self,
        code_hex: str,
        calldata_len: int = 68,
        lanes: int = 32,
        waves: int = 4,
        steps_per_wave: int = 2048,
        portfolio_candidates: int = 64,
        portfolio_steps: int = 1024,
        seed: int = 1,
        budget_s: Optional[float] = None,
        address: int = DEFAULT_ADDRESS,
        transaction_count: int = 1,
        empty_world: bool = True,
        pipeline: Optional[bool] = None,
    ) -> None:
        super().__init__(
            [code_hex],
            calldata_len=calldata_len,
            lanes_per_contract=lanes,
            waves=waves,
            steps_per_wave=steps_per_wave,
            portfolio_candidates=portfolio_candidates,
            portfolio_steps=portfolio_steps,
            seed=seed,
            budget_s=budget_s,
            address=address,
            transaction_count=transaction_count,
            empty_world=empty_world,
            pipeline=pipeline,
        )

    # single-contract views over the corpus bookkeeping
    @property
    def covered(self) -> Set[Tuple[int, bool]]:
        return self.tracks[0].covered

    @property
    def corpus(self) -> List[bytes]:
        return [data for _, data in self.tracks[0].corpus]

    @property
    def triggers(self) -> Dict[str, List[Dict]]:
        return self.tracks[0].triggers

    def run(self) -> Dict:
        outcome = super().run()
        single = outcome["contracts"][0]
        single["stats"] = outcome["stats"]
        return single
