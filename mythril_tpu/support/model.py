"""THE solver entry point: cached `get_model`.

Reference parity: mythril/support/model.py:15-48 — every feasibility
check and issue query in the engine funnels through here; results are
memoized (the reference uses an lru_cache of 2**23 over z3 ASTs; here
the key is the tuple of interned term ids, which is exact because
terms are hash-consed), and the per-query timeout is clamped to the
remaining execution time.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Dict, Iterable, Tuple

from mythril_tpu.exceptions import SolverTimeOutException, UnsatError
from mythril_tpu.laser.ethereum.time_handler import SOLVER_MARGIN_MS, time_handler
from mythril_tpu.laser.smt import Bool
from mythril_tpu.laser.smt.model import Model
from mythril_tpu.laser.smt.solver import Optimize, sat, unknown, unsat
from mythril_tpu.support.support_args import args

_CACHE_MAX = 2**20
_cache: "OrderedDict[Tuple, Tuple[str, Model]]" = OrderedDict()


def clear_cache() -> None:
    _cache.clear()


# NOTE (measured, round 3): promoting IndependenceSolver-style bucket
# slicing — with a bucket-level verdict cache — onto this default path
# was prototyped and REVERTED. Nearly every engine query does split
# (typically ~4 components), but the marathon cost concentrates in the
# one hard component, which must be solved regardless, and the
# persistent incremental CDCL session already amortizes the repeated
# easy prefixes (they are sprint-instant). Net effect was pure
# partition/merge overhead: exceptions.sol.o 0.5s -> 1.1s, calls.sol
# 41.8s -> 43.6s at equal budgets. The optional IndependenceSolver
# remains for API parity; don't re-try this without a workload where
# the hard component is itself shared across queries.


def get_model(
    constraints,
    minimize=(),
    maximize=(),
    enforce_execution_time: bool = True,
    solver_timeout: int = None,
) -> Model:
    """Return a model for `constraints` or raise UnsatError.

    minimize/maximize are BitVec objectives (used by
    analysis/solver.get_transaction_sequence to shrink witnesses).
    """
    from mythril_tpu.laser.smt.bool import Bool as BoolType

    norm = []
    for c in constraints:
        if isinstance(c, bool):
            from mythril_tpu.laser.smt import symbol_factory

            c = symbol_factory.Bool(c)
        norm.append(c)

    timeout = solver_timeout or args.solver_timeout
    if enforce_execution_time:
        timeout = min(timeout, time_handler.time_remaining() - SOLVER_MARGIN_MS)
        if timeout <= 0:
            raise SolverTimeOutException("Execution time budget exhausted")

    key = (
        tuple(c.raw._id for c in norm),
        tuple(m.raw._id for m in minimize),
        tuple(m.raw._id for m in maximize),
    )
    hit = _cache.get(key)
    if hit is not None:
        from mythril_tpu.observe.solverstats import ORIGIN_MEMO, record_query

        _cache.move_to_end(key)
        status, model = hit
        # attribution: the memo pre-empted a solve — the table's
        # "memo" row is how many engine queries never reached a solver
        record_query(ORIGIN_MEMO, str(status))
        if status == sat:
            return model
        if status == unsat:
            raise UnsatError("unsat (cached)")
        raise SolverTimeOutException("timeout (cached)")

    s = Optimize(timeout=timeout)
    for c in norm:
        s.add(c)
    for e in minimize:
        s.minimize(e)
    for e in maximize:
        s.maximize(e)
    from mythril_tpu.observe.querylog import query_context
    from mythril_tpu.support.phase_profile import PhaseProfile

    with PhaseProfile().measure("solve"):
        # flight-recorder origin: a bare get_model solve is a memo
        # miss (engine feasibility checks); module/flip-frontier
        # callers already tagged the context and keep their tag
        with query_context("memo-miss", only_if_root=True):
            result = s.check()
    if result == sat:
        model = s.model()
        _store(key, (sat, model))
        return model
    if result == unsat:
        _store(key, (unsat, None))
        raise UnsatError("unsat")
    # unknown: do NOT cache timeouts permanently under a longer budget —
    # but the reference caches too (lru over identical args); keep parity
    _store(key, (unknown, None))
    raise SolverTimeOutException("solver timeout")


def _store(key, value) -> None:
    _cache[key] = value
    if len(_cache) > _CACHE_MAX:
        _cache.popitem(last=False)
