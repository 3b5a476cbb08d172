"""Execution-time budget shared across the engine.

Reference parity: mythril/laser/ethereum/time_handler.py:5-18
(singleton started by LaserEVM.sym_exec; support/model.py clamps every
solver call to the remaining budget so no query outlives the run).
"""

from __future__ import annotations

import time

from mythril_tpu.support.support_utils import Singleton

#: what the solver keeps back of the budget: get_model refuses a query
#: (SolverTimeOutException) once less than this is left
SOLVER_MARGIN_MS = 500


class TimeHandler(object, metaclass=Singleton):
    def __init__(self):
        self.start_time = None
        self.execution_time = None

    def start_execution(self, execution_time_seconds: int) -> None:
        self.start_time = int(time.time() * 1000)
        self.execution_time = execution_time_seconds * 1000

    def time_remaining(self) -> int:
        """Milliseconds left in the budget (large if never started)."""
        if self.start_time is None:
            return 2**31
        return self.execution_time - (int(time.time() * 1000) - self.start_time)

    def solver_budget_spent(self) -> bool:
        """True once get_model refuses every query for the budget."""
        return self.time_remaining() - SOLVER_MARGIN_MS <= 0


time_handler = TimeHandler()
