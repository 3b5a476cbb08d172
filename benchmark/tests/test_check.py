"""The comparison that decides `correct`, on recorded answers: the
program's findings over the vendored fixtures and three generated
contracts (host walk, 8 s per contract, -t 2)."""

import json
import random
from pathlib import Path

import pytest

import check
import contracts
from reference.evm import NOT_REACHED, REACHED, Tx, reaches
from reference.keccak import keccak256

DATA = Path(__file__).resolve().parent / "data" / "findings.json"


def recorded(walk_cut=False):
    rows = json.loads(DATA.read_text())
    return [
        {"code": bytes.fromhex(r["code"]), "issues": r["issues"],
         "family": r["name"].removesuffix(".o"), "walk_cut": walk_cut}
        for r in rows
    ]


def test_keccak_vectors():
    assert keccak256(b"").hex() == (
        "c5d2460186f7233c927e7db2dcc703c0e500b653ca82273b7bfad8045d85a470"
    )
    assert keccak256(b"abc").hex() == (
        "4e03657aea45a94fc7d47ba826c8d667c0d1e6e33a64a036ec44f58fa12d6c45"
    )
    # the EVM's empty-code hash, and the Transfer event topic (a
    # 31-byte preimage; the rate is 136 bytes, so also one block)
    assert keccak256(b"Transfer(address,address,uint256)").hex() == (
        "ddf252ad1be2c89b69c2b068fc378daa952ba7f163c4a11628f55a4df523b3ef"
    )


def test_recorded_findings_are_witnessed_and_complete():
    result = check.check(recorded())
    assert result["findings_checked"] == 59
    assert result["compared"]["unwitnessed_findings"]["value"] == 0
    # the environments.sol overflow witness reads calldata at a wrapped
    # offset: the replay cannot judge it either way
    assert result["findings_unjudged"] == 1
    assert result["planted_looked_for"] == 24
    assert result["compared"]["missed_planted"]["value"] == 0
    assert check.passed(result)


def test_control_fails():
    """Findings reported without a solved witness (no calldata) come
    out unwitnessed: the control of the comparison."""
    result = check.check(recorded(), witness="none")
    assert result["compared"]["unwitnessed_findings"]["value"] >= 50
    assert not check.passed(result)


def test_altered_answer_fails():
    """A finding whose address is moved where it is produced is not
    reached, and its planted weakness is missed."""
    moved = [
        dict(r, issues=[dict(i, address=i["address"] + 1) for i in r["issues"]])
        for r in recorded()
    ]
    result = check.check(moved)
    assert result["compared"]["unwitnessed_findings"]["value"] >= 55
    assert result["compared"]["missed_planted"]["value"] == 24


@pytest.mark.parametrize("drop", ["all", "first"])
def test_dropped_finding_is_missed(drop):
    """A walk that returns its state unchanged (no findings), or drops
    one finding, misses planted weaknesses: soundness alone would pass."""
    reports = recorded()
    for r in reports:
        r["issues"] = [] if drop == "all" else r["issues"][1:]
    result = check.check(reports)
    assert result["compared"]["unwitnessed_findings"]["value"] == 0
    assert result["compared"]["missed_planted"]["value"] >= (
        24 if drop == "all" else 4
    )
    assert not check.passed(result)


def test_cut_walks_are_counted_apart():
    reports = recorded(walk_cut=True)
    for r in reports:
        r["issues"] = []
    result = check.check(reports)
    assert result["walks_cut"] == len(reports)
    assert result["planted_looked_for"] == 0
    assert check.passed(result)


def test_planted_weaknesses_sit_on_their_instruction_in_every_mutant():
    """Each planted (SWC, address) is an instruction its SWC can sit on,
    in the fixture and in its constant mutants alike."""
    families = dict(contracts.fixtures())
    planted = check.planted()
    assert set(planted) == set(families)
    for family, want in planted.items():
        code = bytes.fromhex(families[family])
        for seed in range(5):
            mutant = contracts.mutate_constants(code, random.Random(seed))
            assert len(mutant) == len(code)
            for swc, address in want:
                assert mutant[address] == code[address]
                assert code[address] in check.SWC_OPCODES[swc]


INVALID = frozenset({0xFE})


def test_unknown_condition_explores_both_directions():
    # TIMESTAMP == 5 ? jump to INVALID : STOP
    code = bytes([0x42, 0x60, 0x05, 0x14, 0x60, 0x09, 0x57, 0x00, 0x00,
                  0x5B, 0xFE])
    assert reaches(code, [Tx(b"", 0, 1, 2)], {}, False, 10, INVALID) == REACHED
    # CALLDATALOAD(0) == 5: fixed by the calldata, so only one direction
    code = bytes([0x60, 0x00, 0x35, 0x60, 0x05, 0x14, 0x60, 0x0B, 0x57,
                  0x00, 0x00, 0x5B, 0xFE])
    five = (5).to_bytes(32, "big")
    assert reaches(code, [Tx(five, 0, 1, 2)], {}, False, 12, INVALID) == REACHED
    assert reaches(code, [Tx(bytes(32), 0, 1, 2)], {}, False, 12, INVALID) == NOT_REACHED
    # the right address on the wrong opcode is not a finding there
    assert reaches(code, [Tx(five, 0, 1, 2)], {}, False, 11, INVALID) == NOT_REACHED


def _store_or_check() -> bytes:
    """if calldata[0..31] == 1 { sstore(0, 1) } else if sload(0) == 1
    { invalid } -- the INVALID is the last byte."""
    head = bytes([0x60, 0x00, 0x35, 0x60, 0x01, 0x14])  # cd0 == 1
    check_part = bytes([0x60, 0x00, 0x54, 0x60, 0x01, 0x14])  # sload(0) == 1
    store = bytes([0x5B, 0x60, 0x01, 0x60, 0x00, 0x55, 0x00])
    # layout: head; PUSH1 store_at; JUMPI; check_part; PUSH1 bad_at;
    # JUMPI; STOP; store; bad: JUMPDEST INVALID
    store_at = len(head) + 3 + len(check_part) + 3 + 1
    bad_at = store_at + len(store)
    code = head + bytes([0x60, store_at, 0x57]) + check_part
    code += bytes([0x60, bad_at, 0x57, 0x00]) + store + bytes([0x5B, 0xFE])
    return code


def test_storage_carries_between_transactions():
    code = _store_or_check()
    target = len(code) - 1
    one = (1).to_bytes(32, "big")
    zero = bytes(32)
    # slot 0 starts unknown (a deployed contract's storage is free)
    assert reaches(code, [Tx(zero, 0, 1, 2)], {}, False, target, INVALID) == REACHED
    # known 0: only after the writing transaction
    assert reaches(code, [Tx(zero, 0, 1, 2)], {0: 0}, False, target, INVALID) == NOT_REACHED
    assert reaches(
        code, [Tx(one, 0, 1, 2), Tx(zero, 0, 1, 2)], {0: 0}, False, target,
        INVALID,
    ) == REACHED
