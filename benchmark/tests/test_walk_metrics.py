"""The host-walk readers on hand-built journeys: lock wait, walk time,
converged share and solver share, and nothing to read where the program
records no `locked`, `cut` or `solve_s` (an older program)."""

import pytest

import harness


def reader(name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py", f"t_{name}")


def walk(start, locked, done, **attrs):
    """One walked job's journey: admission, the host-walk rows, settle.
    `locked` None leaves the event out; attrs ride on `done`."""
    rows = [{"t": start - 1.0, "tier": "admission", "event": "submitted"},
            {"t": start, "tier": "host-walk", "event": "start",
             "attrs": {"timeout_s": 8}}]
    if locked is not None:
        rows.append({"t": locked, "tier": "host-walk", "event": "locked"})
    rows.append({"t": done, "tier": "host-walk", "event": "done",
                 "attrs": dict(issues=0, states=1, **attrs)})
    rows.append({"t": done + 0.01, "tier": "settle", "event": "done"})
    return rows


def device_only(t):
    return [{"t": t, "tier": "admission", "event": "submitted"},
            {"t": t + 1, "tier": "settle", "event": "done"}]


JOURNEYS = [
    walk(0.0, 0.0, 6.0, cut=False, solve_s=5.0),
    walk(1.0, 6.0, 14.0, cut=True, solve_s=7.0),
    walk(2.0, 14.0, 18.0, cut=False, solve_s=3.0),
    device_only(3.0),
]


def test_lock_wait_median():
    # waits 0, 5, 12: the median is the middle one
    assert reader("host_lock_wait_p50_s").read({"journeys": JOURNEYS}) == 5.0


def test_walk_run_median():
    # held 6, 8, 4
    assert reader("host_walk_run_p50_s").read({"journeys": JOURNEYS}) == 6.0


def test_wait_and_run_add_up_to_the_walk():
    walk_p50 = reader("host_walk_p50_s")
    one = [walk(2.0, 4.5, 9.0, cut=False, solve_s=1.0)]
    wait = reader("host_lock_wait_p50_s").read({"journeys": one})
    run = reader("host_walk_run_p50_s").read({"journeys": one})
    assert wait + run == pytest.approx(walk_p50.read({"journeys": one}))


def test_converged_share_counts_walks_with_a_done():
    # two of three walks ran to their end; the device-only job is no walk
    value = reader("walk_converged_pct").read({"journeys": JOURNEYS})
    assert value == pytest.approx(100.0 * 2 / 3)


def test_solve_share_is_over_time_holding_the_lock():
    # (5 + 7 + 3) / (6 + 8 + 4), not over start-to-done (6 + 13 + 16)
    value = reader("walk_solve_pct").read({"journeys": JOURNEYS})
    assert value == pytest.approx(100.0 * 15 / 18)


def test_nothing_to_read_from_an_older_program():
    # the parent's journeys: start and done only, done without cut or
    # the phase split
    old = [walk(0.0, None, 6.0), walk(1.0, None, 9.0)]
    for name in ("host_lock_wait_p50_s", "host_walk_run_p50_s",
                 "walk_converged_pct", "walk_solve_pct"):
        assert reader(name).read({"journeys": old}) is None, name
    # `locked` recorded but not `cut` / `solve_s`
    half = [walk(0.0, 1.0, 6.0)]
    assert reader("walk_converged_pct").read({"journeys": half}) is None
    assert reader("walk_solve_pct").read({"journeys": half}) is None
    assert reader("host_lock_wait_p50_s").read({"journeys": half}) == 1.0


def test_nothing_to_read_without_walks():
    for name in ("host_lock_wait_p50_s", "host_walk_run_p50_s",
                 "walk_converged_pct", "walk_solve_pct"):
        assert reader(name).read({"journeys": [device_only(0.0)]}) is None
        assert reader(name).read({}) is None
