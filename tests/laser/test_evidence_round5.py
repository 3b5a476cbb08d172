"""Round-5 device evidence + ownership: kernel banks, synthesis,
poisoned storage, and the completeness gate.

Everything here runs on the CPU backend (conftest pins it): the
evidence machinery is backend-agnostic, and tiny hand-assembled
contracts keep the waves fast.
"""

import pytest

from mythril_tpu.analysis.corpus import _outcome_owns, analyze_corpus
from mythril_tpu.analysis.evidence import evidence_issues
from mythril_tpu.analysis.prepass import reset_proven, witness_issues
from mythril_tpu.ethereum.evmcontract import EVMContract
from mythril_tpu.laser.batch.explore import DeviceSymbolicExplorer

ADDR = 0x901D573B8CE8C997DE5F19173C32D966B4FA55FE

#: PUSH1 5; CALLDATALOAD(0); SUB (wraps when cd < 5); SSTORE slot 0;
#: ORIGIN == CALLDATALOAD(0) -> JUMPI; STOP
WRAP_AND_ORIGIN = (
    "6005" "6000" "35" "03" "600055" "32" "600035" "14" "6011" "57" "00"
    "5b00"
)

#: value-bearing CALL to a calldata-derived target:
#: CALL(gas=0xffff, to=cd[0..31], value=1, ...); STOP
CALL_TO_CALLDATA = "6000600060006000600160003561fffff100"

#: arithmetic on INITIAL STORAGE: sload(0) + calldataload(0) stored
#: back — wraps only under a poisoned start state
STORAGE_ADD = "60005460003501600055" + "00"


def explore(code_hex, **kw):
    kw.setdefault("lanes", 8)
    kw.setdefault("waves", 6)
    kw.setdefault("steps_per_wave", 128)
    kw.setdefault("transaction_count", 1)
    ex = DeviceSymbolicExplorer(code_hex, **kw)
    return ex, ex.run()


def test_wrap_event_banked_and_synthesized():
    _, out = explore(WRAP_AND_ORIGIN)
    recs = [r for r in out["evidence"] if r["class"] == "wrap"]
    assert recs and recs[0]["pc"] == 5 and recs[0]["op"] == "subtraction"
    reset_proven()
    issues = evidence_issues(
        EVMContract(code=WRAP_AND_ORIGIN, name="w"), out, ADDR
    )
    wraps = [i for i in issues if i.swc_id == "101"]
    assert wraps and wraps[0].address == 5
    assert wraps[0].title == "Integer Arithmetic Bugs"
    # the witness replays: the banked input IS the transaction
    steps = wraps[0].transaction_sequence["steps"]
    assert steps and int(steps[-1]["input"][2:10] or "0", 16) < 5


def test_origin_provenance_survives_mixed_opacity():
    _, out = explore(WRAP_AND_ORIGIN)
    env = [r for r in out["evidence"] if r["class"] == "env"]
    assert env and env[0]["swc"] == "115" and env[0]["pc"] == 16


def test_call_steering_confirms_attacker_target():
    """Wave 1 banks a tainted-target call; the steering witness seeds
    a lane that concretely calls the attacker with value."""
    _, out = explore(CALL_TO_CALLDATA, waves=4)
    call = [r for r in out["evidence"] if r["class"] == "call"][0]
    assert call["to_attacker"] and call["value_to_attacker"]
    assert call["unchecked"]  # no branch after the call
    reset_proven()
    issues = evidence_issues(
        EVMContract(code=CALL_TO_CALLDATA, name="c"), out, ADDR
    )
    swcs = {i.swc_id for i in issues}
    assert {"104", "105", "107"} <= swcs


def test_poisoned_storage_exhibits_storage_dependent_wrap():
    """sload(0) + cd wraps only under the synthetic MAX start state;
    the witness must DECLARE the poisoned storage it assumed."""
    _, out = explore(STORAGE_ADD, waves=8)
    wraps = [r for r in out["evidence"] if r["class"] == "wrap"]
    assert wraps, "poisoned carry never exhibited the wrap"
    assert wraps[0].get("initial_storage"), "witness must declare poison"
    reset_proven()
    issues = witness_issues(EVMContract(code=STORAGE_ADD, name="p"), out, ADDR)
    w = [i for i in issues if i.swc_id == "101"][0]
    accounts = w.transaction_sequence["initialState"]["accounts"]
    assert "0x0" in accounts[hex(ADDR)]["storage"]


def test_outcome_owns_requires_final_and_complete():
    assert not _outcome_owns(None)
    assert not _outcome_owns({"device_complete": False, "stats": {}})
    assert not _outcome_owns(
        {"device_complete": True, "stats": {"partial": True}}
    )
    assert _outcome_owns({"device_complete": True, "stats": {}})


def test_ownership_end_to_end_matches_host_walk(monkeypatch):
    """analyze_corpus with ownership: the owned result's distinct
    findings equal the host-only walk's on the same contract."""
    from mythril_tpu.support.support_args import args

    # the harness's host-first funnel (conftest), whatever an analyzer
    # built earlier in this process pushed into the global args: the
    # device funnel's time on the CPU (26 s here) would eat the prepass
    # budget
    monkeypatch.setattr(args, "device_first", False)
    rows = [(WRAP_AND_ORIGIN, "", "w")]
    dev = analyze_corpus(
        rows,
        transaction_count=1,
        execution_timeout=30,
        create_timeout=10,
        use_device=True,
        processes=1,
    )
    host = analyze_corpus(
        rows,
        transaction_count=1,
        execution_timeout=30,
        create_timeout=10,
        use_device=False,
        processes=1,
    )
    fp = lambda res: {  # noqa: E731
        (i["swc-id"], i["address"]) for i in res[0]["issues"]
    }
    assert dev[0].get("owned"), "device-complete contract must be owned"
    assert fp(dev) == fp(host)


def test_incomplete_contract_falls_back_to_host_walk():
    """A contract whose device exploration degrades (memory cap) is
    NOT owned: the host walk carries it."""
    from mythril_tpu.analysis.corpusgen import degrader_contract

    # past even the roomy 16384-byte cap, so the demotion happens in
    # every prepass configuration
    rows = [(degrader_contract(0x5000), "", "d")]
    res = analyze_corpus(
        rows,
        transaction_count=1,
        execution_timeout=30,
        create_timeout=10,
        use_device=True,
        device_budget_s=20.0,
        processes=1,
    )
    assert not res[0].get("owned")
    assert {i["swc-id"] for i in res[0]["issues"]} == {"110"}
