"""Facade: per-contract analysis orchestration.

Covers mythril/mythril/mythril_analyzer.py — publishes the run options
to the global `args` bag, drives SymExecWrapper + fire_lasers for each
loaded contract with crash containment (a crashing contract reports
its traceback and salvages the callback issues already found), and
produces the graph/statespace artifacts.
"""

from __future__ import annotations

import logging
import traceback
from typing import List, Optional

from mythril_tpu.analysis.callgraph import generate_graph
from mythril_tpu.analysis.report import Issue, Report
from mythril_tpu.analysis.security import fire_lasers, retrieve_callback_issues
from mythril_tpu.analysis.symbolic import SymExecWrapper
from mythril_tpu.analysis.traceexplore import get_serializable_statespace
from mythril_tpu.ethereum.evmcontract import EVMContract
from mythril_tpu.exceptions import DetectorNotFoundError, DevicePrepassError
from mythril_tpu.laser.execution_info import ExecutionInfo
from mythril_tpu.laser.smt.solver import SolverStatistics
from mythril_tpu.mythril.mythril_disassembler import MythrilDisassembler
from mythril_tpu.support.loader import DynLoader
from mythril_tpu.support.source_support import Source
from mythril_tpu.support.start_time import StartTime
from mythril_tpu.support.support_args import args

log = logging.getLogger(__name__)

CRASH_NOTICE = (
    "Exception occurred, aborting analysis. Please report this "
    "issue to the project's issue tracker.\n"
)


#: analyzer-local options and their defaults
_RUN_DEFAULTS = dict(
    use_onchain_data=True,
    strategy="dfs",
    address=None,
    max_depth=None,
    execution_timeout=None,
    loop_bound=None,
    create_timeout=None,
    disable_dependency_pruning=False,
    custom_modules_directory="",
    # deadline-aware supervision (support/resilience.py): a wall-clock
    # budget for the WHOLE run; on expiry the analyzer stops launching
    # contracts and either reports partial (with per-contract
    # completion status) or fails hard, per on_timeout
    deadline=None,
    on_timeout="partial",
    # solver query flight recorder (observe/querylog.py): when set,
    # every solved SMT query serializes into this directory as a
    # replayable artifact for `myth solverlab`
    capture_queries=None,
)

#: options published to the global `args` bag for the deep layers
_GLOBAL_DEFAULTS = dict(
    sparse_pruning=False,
    parallel_solving=False,
    unconstrained_storage=False,
    call_depth_limit=3,
    device_prepass="auto",
    device_solving="auto",
    device_prepass_budget=12.0,
    device_prepass_lanes=128,
    device_ownership="auto",
    deterministic_solving=False,
    static_prune=True,
    pipeline=True,
    specialize=True,
    # None = leave the flag bag as-is (the CLI always passes the
    # explicit value; programmatic/test constructions keep the
    # harness default — blockjit compiles per bucket, so silently
    # re-enabling it under the test conftest would re-add the compile
    # cost the conftest exists to avoid)
    blockjit=None,
    mesh_devices=None,
    # device-first solver funnel (ISSUE 9): batched device dispatch
    # before the CDCL sprint on the explorer's flip frontier
    # (--host-first-funnel restores the legacy order)
    device_first=True,
    # cross-run verdict store (mythril_tpu/store, --store DIR /
    # --no-store): deep layers and the corpus driver read these from
    # the flag bag
    store_dir=__import__("os").environ.get("MYTHRIL_STORE_DIR") or None,
    store=True,
)


class MythrilAnalyzer:
    """Runs the security analysis over the disassembler's contracts.

    Accepts the reference CLI's full option set as keywords; anything
    in `_RUN_DEFAULTS` configures this analyzer, anything in
    `_GLOBAL_DEFAULTS` (plus enable_iprof / solver_timeout) is pushed
    into the global `args` bag for the deep layers.
    """

    def __init__(
        self,
        disassembler: MythrilDisassembler,
        requires_dynld: bool = False,
        enable_iprof: bool = False,
        solver_timeout: Optional[int] = None,
        **options,
    ):
        self.eth = disassembler.eth
        self.contracts: List[EVMContract] = disassembler.contracts or []
        self.enable_online_lookup = disassembler.enable_online_lookup

        for field, default in _RUN_DEFAULTS.items():
            setattr(self, field, options.pop(field, default))
        for field, default in _GLOBAL_DEFAULTS.items():
            value = options.pop(field, default)
            if value is None and field == "blockjit":
                continue  # None = keep the bag's current value
            setattr(args, field, value)
        # the sprint cap keeps its env-seeded default
        # (MYTHRIL_SPRINT_CAP_S) unless explicitly overridden
        sprint_cap_s = options.pop("sprint_cap_s", None)
        if sprint_cap_s is not None:
            args.sprint_cap_s = float(sprint_cap_s)
        if options:
            raise TypeError(f"unknown analyzer options: {sorted(options)}")

        args.iprof = enable_iprof
        if solver_timeout is not None:
            args.solver_timeout = solver_timeout
        # mirrored into the flag bag for observability (deep layers
        # consult resilience.run_deadline(), set in fire_lasers)
        args.run_deadline_s = self.deadline
        args.on_timeout = self.on_timeout

    # -- shared engine construction ------------------------------------
    def _symbolically_execute(self, contract, **overrides) -> SymExecWrapper:
        options = dict(
            dynloader=DynLoader(self.eth, active=self.use_onchain_data),
            max_depth=self.max_depth,
            execution_timeout=self.execution_timeout,
            create_timeout=self.create_timeout,
            disable_dependency_pruning=self.disable_dependency_pruning,
            custom_modules_directory=self.custom_modules_directory,
        )
        options.update(overrides)
        return SymExecWrapper(
            contract or self.contracts[0], self.address, self.strategy, **options
        )

    # -- artifacts -----------------------------------------------------
    def dump_statespace(self, contract: EVMContract = None) -> dict:
        """Serializable statespace of the contract."""
        sym = self._symbolically_execute(contract, run_analysis_modules=False)
        return get_serializable_statespace(sym)

    def graph_html(
        self,
        contract: EVMContract = None,
        enable_physics: bool = False,
        phrackify: bool = False,
        transaction_count: Optional[int] = None,
    ) -> str:
        """Interactive callgraph HTML."""
        sym = self._symbolically_execute(
            contract,
            transaction_count=transaction_count,
            run_analysis_modules=False,
        )
        return generate_graph(sym, physics=enable_physics, phrackify=phrackify)

    # -- the analysis run ----------------------------------------------
    def _corpus_prepass(self, transaction_count: Optional[int]):
        """The overlapped striped device prepass for multi-contract
        runs (analysis/corpus.py OverlappedPrepass): the chip explores
        the whole corpus while this process analyzes contracts one by
        one. None when there is nothing to overlap (single contract,
        no accelerator, or --device-prepass never)."""
        if len(self.contracts) < 2:
            return None
        mode = getattr(args, "device_prepass", "auto")
        if mode == "never":
            return None
        if mode == "auto":
            from mythril_tpu.support.accel import accelerator_present

            if not accelerator_present():
                return None
        try:
            from mythril_tpu.analysis.corpus import OverlappedPrepass

            from mythril_tpu.support import resilience

            return OverlappedPrepass(
                [
                    (c.code or "", getattr(c, "creation_code", "") or "", c.name)
                    for c in self.contracts
                ],
                self._prepass_address(),
                transaction_count or 2,
                execution_timeout=self.execution_timeout,
                ownership=getattr(args, "device_ownership", "auto") != "never",
                deadline=resilience.run_deadline(),
            )
        except Exception:
            if mode == "always":
                raise
            log.debug("overlapped corpus prepass unavailable", exc_info=True)
            return None

    def _prepass_address(self) -> int:
        address = self.address
        if isinstance(address, str):
            return int(address, 16)
        if isinstance(address, int):
            return address
        from mythril_tpu.laser.batch.explore import DEFAULT_ADDRESS

        return DEFAULT_ADDRESS

    def fire_lasers(
        self,
        modules: Optional[List[str]] = None,
        transaction_count: Optional[int] = None,
    ) -> Report:
        """Analyze every loaded contract; one contract crashing doesn't
        lose the others' findings. With several contracts and an
        accelerator, the striped device prepass overlaps the loop —
        the reference's sequential per-contract for-loop
        (mythril/mythril/mythril_analyzer.py:145-185) becomes the host
        half of a host+device pipeline.

        The run is supervised (support/resilience.py): --deadline
        installs the process-global run deadline every solver query and
        wave loop clamps to, SIGINT/SIGTERM degrade to a graceful stop,
        and an expired budget yields a PARTIAL report — per-contract
        completion status plus degradation-reason counts in the meta —
        or a hard DeadlineExpiredError under --on-timeout=fail."""
        from mythril_tpu.support import resilience

        SolverStatistics().enabled = True
        degradation_marker = resilience.DegradationLog().marker()
        from mythril_tpu import observe

        if self.capture_queries:
            observe.configure_capture(self.capture_queries)
        solver_marker = observe.solver_marker()
        self._journey_ids: List = []
        if self.deadline is not None:
            resilience.set_run_deadline(self.deadline)
        pre = self._corpus_prepass(transaction_count)

        try:
            with resilience.graceful_shutdown():
                (
                    collected,
                    crashes,
                    execution_info,
                    completion,
                ) = self._analyze_contracts(pre, modules, transaction_count)
        finally:
            # an exception escaping the loop (DetectorNotFoundError)
            # must not orphan the prepass thread on the device
            final = pre.finish() if pre is not None else {}
            if self.deadline is not None:
                resilience.clear_run_deadline()
        collected += self._merge_prepass_issues(final, collected)
        for i, status in enumerate(completion):
            outcome = final.get(i)
            if outcome is not None:
                status["device_complete"] = bool(
                    outcome.get("device_complete")
                )

        # prime the source registry for the report
        Source().get_source_from_contracts_list(self.contracts)

        report = self._build_report(collected, crashes, execution_info)
        if final:
            # the corpus prepass's final counters (waves, device steps,
            # per-device mesh rows): a contract's own execution info may
            # carry an outcome published mid-run
            report.meta["device_prepass"] = next(iter(final.values()))[
                "stats"
            ]
        # per-run solver attribution (observe/solverstats.py): which
        # engine answered how many queries at what cost — the jsonv2
        # meta view of ROADMAP item 1's device-vs-host question
        attribution = observe.solver_attribution(solver_marker)
        if attribution:
            report.meta["solver_attribution"] = attribution
        # the flight recorder's loss waterfall: why host-answered
        # queries were not device-answered (all verdicts + the
        # host-WON restriction), plus how many queries the capture
        # corpus banked this run
        losses = observe.loss_reasons(since=solver_marker)
        if losses:
            report.meta["solver_loss_reasons"] = losses
            report.meta["solver_loss_reasons_sat"] = observe.loss_reasons(
                since=solver_marker, verdict="sat"
            )
        if self.capture_queries:
            report.meta["captured_queries"] = observe.captured_total(
                since=solver_marker
            )
        # per-contract tier-ladder journeys (observe/journey.py): the
        # jsonv2 meta carries each contract's timeline skeleton, keyed
        # by the journey_id the routing JSONL also carries — the
        # offline features ⨝ route ⨝ outcome ⨝ timeline join
        journeys = []
        for name, journey_id in self._journey_ids:
            doc = observe.assemble_journey(journey_id)
            if doc is not None:
                doc["contract"] = name
                journeys.append(doc)
        if journeys:
            report.meta["journeys"] = journeys
        reasons = resilience.DegradationLog().counts_since(degradation_marker)
        partial = any(not status["complete"] for status in completion)
        if reasons or partial:
            report.partial = partial
            report.degradation = {
                "reasons": reasons,
                "contracts": completion,
            }
        return report

    def _analyze_contracts(
        self,
        pre,
        modules: Optional[List[str]],
        transaction_count: Optional[int],
    ):
        """The per-contract host loop (crash-contained per contract),
        consulting the resilience supervisor at every contract
        boundary: an expired deadline or a delivered signal marks the
        remaining contracts skipped (partial report) or raises
        (on_timeout=fail) instead of running past the budget."""
        from contextlib import nullcontext

        from mythril_tpu.support import resilience

        collected: List[Issue] = []
        crashes: List[str] = []
        execution_info: Optional[List[ExecutionInfo]] = None
        completion: List[dict] = []
        halt_reason: Optional[str] = None
        for index, contract in enumerate(self.contracts):
            if halt_reason is None:
                halt_reason = resilience.interrupted_reason()
            if halt_reason is not None:
                if self.on_timeout == "fail":
                    from mythril_tpu.exceptions import DeadlineExpiredError

                    raise DeadlineExpiredError(
                        f"{len(self.contracts) - index} contract(s) "
                        f"unanalyzed at the deadline ({halt_reason})"
                    )
                resilience.DegradationLog().record(
                    resilience.DegradationReason.CONTRACT_SKIPPED,
                    site="analyzer",
                    detail=halt_reason,
                    contract=contract.name,
                )
                completion.append(
                    {
                        "contract": contract.name,
                        "complete": False,
                        "skipped": halt_reason,
                    }
                )
                continue
            StartTime()  # fresh discovery-time baseline per contract
            outcome, device_ok = (
                pre.outcome_for(index) if pre is not None else (None, True)
            )
            restore = None
            crashed = False
            if not device_ok:
                # the chip belongs to the prepass thread; the injected
                # (possibly partial) outcome stands in for this
                # contract's own device prepass
                restore = (args.device_prepass, args.device_solving)
                args.device_prepass = "never"
                args.device_solving = "never"
            import time as _time

            from mythril_tpu.observe.spans import trace as _trace

            t_contract = _time.perf_counter()
            try:
                with _trace("contract.analyze", contract=contract.name):
                    with pre.lock if pre is not None else nullcontext():
                        sym = self._symbolically_execute(
                            contract,
                            loop_bound=self.loop_bound,
                            transaction_count=transaction_count,
                            modules=modules,
                            compulsory_statespace=False,
                            prepass_outcome=outcome,
                        )
                        issues = fire_lasers(sym, modules)
                execution_info = sym.execution_info
            except (DetectorNotFoundError, DevicePrepassError):
                raise
            except KeyboardInterrupt:
                log.critical("Keyboard Interrupt")
                issues = retrieve_callback_issues(modules)
            except Exception:
                log.critical(CRASH_NOTICE + traceback.format_exc())
                issues = retrieve_callback_issues(modules)
                crashes.append(traceback.format_exc())
                crashed = True
            finally:
                if restore is not None:
                    args.device_prepass, args.device_solving = restore
            if pre is not None:
                pre.yield_lock()

            for issue in issues:
                issue.add_code_info(contract)
            collected += issues
            completion.append(
                {"contract": contract.name, "complete": not crashed}
            )
            if not crashed:
                self._store_writeback(
                    contract, issues, outcome,
                    _time.perf_counter() - t_contract,
                    modules, transaction_count,
                )
            journey_id = self._routing_record(
                contract, issues, crashed,
                _time.perf_counter() - t_contract,
            )
            if journey_id is not None:
                self._journey_ids.append((contract.name, journey_id))
            log.info("Solver statistics: \n%s", str(SolverStatistics()))
            from mythril_tpu.support.phase_profile import PhaseProfile

            log.info("Host phase profile: \n%s", str(PhaseProfile()))
        return collected, crashes, execution_info, completion

    def _store_writeback(
        self,
        contract,
        issues: List[Issue],
        outcome,
        wall_s: float,
        modules: Optional[List[str]] = None,
        transaction_count: Optional[int] = None,
    ) -> None:
        """Tier 3 of the verdict store on the one-shot CLI path: a
        cleanly-completed contract banks its verdict (keyed on its
        RUNTIME code + the run's config fingerprint) so a later
        `myth serve` / corpus run settles the repeat at admission.
        Deploying analyses are not banked — their verdict covers
        creation code the runtime key doesn't."""
        from mythril_tpu.store import configured_store

        try:
            vstore = configured_store()
        except Exception:
            return
        if vstore is None:
            return
        runtime = (contract.code or "").removeprefix("0x")
        if len(runtime) < 8 or getattr(contract, "creation_code", ""):
            return
        try:
            from mythril_tpu.analysis.static import (
                static_prune_enabled,
                summary_for,
            )
            from mythril_tpu.analysis.static.summary import (
                analysis_config_fingerprint,
            )
            from mythril_tpu.store import (
                banks_from_outcome,
                code_hash_hex,
                provenance,
                static_export,
            )

            config_fp = analysis_config_fingerprint(
                modules=modules,
                transaction_count=transaction_count,
                create_timeout=self.create_timeout,
            )
            summary = None
            if static_prune_enabled():
                summary = summary_for(runtime, config_fp=config_fp)
            vstore.put(
                code_hash_hex(runtime),
                config_fp,
                issues=[issue.as_dict for issue in issues],
                static=static_export(summary),
                banks=banks_from_outcome(outcome),
                provenance=provenance(
                    wall_s=wall_s, computed_by="analyzer"
                ),
            )
        except Exception:
            log.debug("store write-back failed for %s", contract.name,
                      exc_info=True)

    @staticmethod
    def _routing_record(
        contract, issues: List[Issue], crashed: bool, wall_s: float
    ) -> Optional[str]:
        """One routing-feature record per analyzed contract on the CLI
        path (the corpus driver emits its own): static features joined
        with the walk's wall/issue outcome (observe/routing.py), keyed
        by a freshly minted journey_id whose skeleton timeline also
        lands in the journey log — the jsonv2 meta attaches it."""
        from mythril_tpu import observe

        if not observe.enabled():
            return None
        try:
            import hashlib

            code = contract.code or getattr(
                contract, "creation_code", ""
            ) or ""
            code = code[2:] if code.startswith("0x") else code
            try:
                digest = hashlib.sha256(bytes.fromhex(code)).hexdigest()
            except ValueError:
                digest = ""
            outcome = observe.routing_outcome_for(
                {
                    "name": contract.name,
                    "issues": [None] * len(issues),
                    "wall_s": round(wall_s, 3),
                    "error": "crash" if crashed else None,
                    "complete": not crashed,
                }
            )
            journey_id = observe.new_journey_id()
            observe.journey_event(
                journey_id, "admission", "analyze",
                contract=contract.name,
            )
            observe.journey_event(
                journey_id, outcome.get("route", "?"), "routed",
                wall_s=outcome.get("wall_s"),
            )
            observe.journey_event(
                journey_id, "settle",
                "failed" if crashed else "done",
                issues=len(issues),
            )
            observe.routing_log().record(
                contract=contract.name,
                code_hash=digest,
                features=observe.routing_features_for(code),
                outcome=outcome,
                journey_id=journey_id,
            )
            return journey_id
        except Exception:
            log.debug("routing record failed", exc_info=True)
        return None

    def _merge_prepass_issues(
        self, final: dict, collected: List[Issue]
    ) -> List[Issue]:
        """Witness issues the device banked for contracts the host walk
        missed (same dedup rule as the pooled corpus merge: one issue
        per (address, swc-id) PER CONTRACT — two contracts may hold the
        same vulnerability at the same byte offset)."""
        from mythril_tpu.analysis.prepass import witness_issues

        seen = {
            (issue.contract, issue.address, issue.swc_id)
            for issue in collected
        }
        extra: List[Issue] = []
        address = self._prepass_address()
        for i, contract in enumerate(self.contracts):
            outcome = final.get(i)
            if not outcome:
                continue
            try:
                fresh = witness_issues(contract, outcome, address)
            except Exception:
                log.debug("witness merge failed for %s", contract.name,
                          exc_info=True)
                continue
            for issue in fresh:
                if (issue.contract, issue.address, issue.swc_id) in seen:
                    continue
                issue.add_code_info(contract)
                extra.append(issue)
        if extra:
            log.info(
                "Device prepass contributed %d issue(s) the host walk "
                "did not find",
                len(extra),
            )
        return extra

    def _build_report(self, collected, crashes, execution_info) -> Report:

        report = Report(
            contracts=self.contracts,
            exceptions=crashes,
            execution_info=execution_info,
        )
        for issue in collected:
            report.append_issue(issue)
        return report
