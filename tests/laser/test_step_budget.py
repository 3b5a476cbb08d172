"""Step-kernel compilation budget (VERDICT r3 #8).

The remote-chip link used before PR 21 charged a fixed ~ms dispatch
floor per compiled segment inside the jit'd while loop (historical;
docs/roadmap.md "Performance findings"), so the segment census was the
kernel's cost model there: a change
that doubles the fusion count halves corpus wave throughput even if
every op is cheap. The round-3 census existed only as a roadmap note;
this pins it in CI.

Counts are taken on the CPU backend (tests run on the virtual mesh),
whose absolute numbers differ from the TPU compile — the budget is a
REGRESSION tripwire for structural bloat (new unfused segments, phase
conditionals, concat custom-calls), not a cross-backend constant. On
a budget trip: either fuse the regression away or re-measure and bump
the budget in the same commit that justifies it.
"""

import jax
import pytest

from __graft_entry__ import _demo_workload
from mythril_tpu.laser.batch.step import step

#: measured on the CPU backend 2026-07-31: 1097 fusion instructions
#: and 18 conditionals across the compiled step module (the TPU
#: compile of the same kernel measured 75 fusions / 11 conditionals in
#: its while body — backends fuse differently; this budget tracks the
#: CPU number CI can see). ~25% headroom for benign drift.
FUSION_BUDGET = 1370
CONDITIONAL_BUDGET = 24


@pytest.fixture(scope="module")
def step_hlo():
    batch, code = _demo_workload(n_lanes=64)
    return jax.jit(step).lower(batch, code).compile().as_text()


def test_fusion_count_within_budget(step_hlo):
    # "fusion(" appears exactly once per fusion instruction definition
    # (references are bare %fusion.N, no parenthesis)
    n = step_hlo.count("fusion(")
    assert 0 < n <= FUSION_BUDGET, (
        f"step kernel compiles to {n} fusions (budget {FUSION_BUDGET}) — "
        "a segment regression multiplies the per-step dispatch floor"
    )


def test_conditional_count_within_budget(step_hlo):
    n = step_hlo.count(" conditional(")
    assert 0 < n <= CONDITIONAL_BUDGET, (
        f"step kernel compiles to {n} conditionals "
        f"(budget {CONDITIONAL_BUDGET}); phase gates multiply segments"
    )
