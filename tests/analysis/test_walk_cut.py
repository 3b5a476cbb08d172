"""A host walk records whether its time budget cut it: LASER sets the
flag where `exec` returns for the budget, and where a feasibility
filter drops states because the solver refused them for the budget.
The per-contract result carries it as `cut`. CPU-only, no device."""

from datetime import datetime, timedelta

from mythril_tpu.analysis.corpus import analyze_one_payload
from mythril_tpu.analysis.corpusgen import loop_contract
from mythril_tpu.laser.ethereum import svm
from mythril_tpu.laser.ethereum.svm import LaserEVM
from mythril_tpu.laser.ethereum.time_handler import SOLVER_MARGIN_MS, time_handler

ADDRESS = 0x901D573B8CE8C997DE5F19173C32D966B4Fa55FE


def walk(code: str, execution_timeout: int) -> dict:
    return analyze_one_payload((
        code, "", "walk", ADDRESS, "bfs", 2, execution_timeout, 10,
        128, 3, None, None, False, None, None,
    ))


class TenSecondsPerReading(datetime):
    """LASER's clock advancing 10 s at every reading: a 1 s budget is
    spent by the first check after the walk starts, on any machine and
    whatever the solver's caches hold (a real walk's length depends on
    both)."""

    last = None

    @classmethod
    def now(cls, tz=None):
        cls.last = (cls.last or datetime.now(tz)) + timedelta(seconds=10)
        return cls.last


def test_a_walk_past_its_budget_is_cut(monkeypatch):
    monkeypatch.setattr(svm, "datetime", TenSecondsPerReading)
    result = walk(loop_contract(), 1)
    assert result["error"] is None
    assert result["cut"] == "execution"


def test_states_the_solver_refused_for_the_budget_cut_the_walk(monkeypatch):
    # the solver's share of the budget is gone: get_model refuses every
    # feasibility check and the filter drops the states, long before
    # exec's own check of a 60 s budget
    monkeypatch.setattr(time_handler, "time_remaining", lambda: SOLVER_MARGIN_MS)
    result = walk(loop_contract(), 60)
    assert result["error"] is None
    assert result["cut"] == "execution"


def test_a_walk_that_ends_is_not_cut():
    result = walk("33ff", 60)  # CALLER; SELFDESTRUCT
    assert result["error"] is None
    assert result["cut"] is None
    assert result["issues"]


def test_spent_budget_names_the_budget():
    laser = LaserEVM(execution_timeout=5, create_timeout=2)
    laser.time = datetime.now() - timedelta(seconds=3)
    assert laser._spent_budget(creating=False) is None
    laser.open_states = [object()]
    assert laser._spent_budget(creating=True) == "create"
    laser.time = datetime.now() - timedelta(seconds=6)
    assert laser._spent_budget(creating=False) == "execution"
    # no budget: never spent
    assert LaserEVM(execution_timeout=0)._spent_budget(creating=False) is None
