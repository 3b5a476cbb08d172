"""The comparison that decides a run's `correct`.

Soundness: every finding a completed report carries says "the
instruction at this address of this contract is reachable, with this
SWC weakness, by this transaction sequence". The reference EVM
(benchmark/reference) replays the sequence and checks that the last
transaction can execute that instruction, which has to be one that the
SWC weakness can sit on (SWC_OPCODES: a call for SWC-104, SELFDESTRUCT
for SWC-106, the INVALID byte for SWC-110, ...).

Completeness: each fixture family carries planted weaknesses at fixed
addresses (reference/planted.json), which a constant mutant keeps. A
report whose walk ran to its end has to hold every one of them; a walk
cut by its time limit reports what it found by then, which depends on
speed, and is counted apart.

The control (`witness="none"`) reports each finding without solving its
witness: every transaction's calldata is dropped. It breaks the stated
guarantee that a finding carries a transaction sequence reproducing it,
and must come out unwitnessed.
"""

from __future__ import annotations

import ast
import json
from pathlib import Path
from typing import Dict, List

from reference.evm import NOT_REACHED, UNJUDGED, Tx, reaches

PLANTED = Path(__file__).resolve().parent / "reference" / "planted.json"

CALLS = frozenset({0xF1, 0xF2, 0xF4, 0xFA})
#: the instructions a weakness of each SWC id can sit on; a finding of
#: an id not listed may sit on any instruction
SWC_OPCODES = {
    "101": frozenset({0x01, 0x02, 0x03, 0x0A}),  # ADD MUL SUB EXP
    "104": CALLS,
    "105": frozenset({0xF1, 0xF2}),  # calls that move value
    "106": frozenset({0xFF}),  # SELFDESTRUCT
    "107": CALLS | {0x54, 0x55},  # the call, or state access after it
    "110": frozenset({0xFE}),  # the designated INVALID
    "112": frozenset({0xF4}),  # DELEGATECALL
    "115": frozenset({0x57}),  # the JUMPI on tx.origin
    "116": frozenset({0x57}),  # the JUMPI on a block variable
}


def _hex_bytes(value) -> bytes:
    if isinstance(value, (bytes, bytearray)):
        return bytes(value)
    text = str(value or "")
    text = text[2:] if text.startswith("0x") else text
    return bytes.fromhex(text)


def _int(value, default: int = 0) -> int:
    if value is None or value == "":
        return default
    if isinstance(value, int):
        return value
    return int(str(value), 0)


def _storage(text) -> Dict[int, int]:
    """A witness's initial storage (`{}`, JSON or a Python dict repr)
    -> {slot: value}; slots it leaves out stay unknown."""
    if isinstance(text, dict):
        raw = text
    else:
        raw = None
        for parse in (json.loads, ast.literal_eval):
            try:
                raw = parse(text or "{}")
                break
            except (ValueError, SyntaxError, TypeError):
                continue
        if not isinstance(raw, dict):
            return {}
    out = {}
    for k, v in raw.items():
        try:
            out[_int(k)] = _int(v)
        except (TypeError, ValueError):
            continue
    return out


def finding_reached(code: bytes, issue: Dict, witness: str = "solved") -> str:
    """What the reference EVM says of the finding's transaction
    sequence (evm.REACHED, NOT_REACHED or UNJUDGED).
    `witness="none"` is the control."""
    seq = issue.get("tx_sequence") or {}
    steps = seq.get("steps") or []
    if not steps:
        return NOT_REACHED
    target = steps[-1].get("address") or ""
    if not target:
        return NOT_REACHED  # a creation transaction: runtime-only corpus
    accounts = (seq.get("initialState") or {}).get("accounts") or {}
    account = accounts.get(target.lower()) or accounts.get(target) or {}
    storage = _storage(account.get("storage"))
    txs = []
    for step in steps:
        calldata = _hex_bytes(step.get("input"))
        if witness == "none":
            calldata = b""
        txs.append(Tx(
            calldata, _int(step.get("value")), _int(step.get("origin")),
            _int(step.get("address")),
        ))
    swc = str(issue.get("swc-id") or "").removeprefix("SWC-")
    return reaches(
        code, txs, storage, False, int(issue["address"]), SWC_OPCODES.get(swc)
    )


def planted() -> Dict[str, set]:
    """{family: {(SWC id, address)}} of the planted weaknesses."""
    families = json.loads(PLANTED.read_text())["families"]
    return {f: {(str(swc), int(a)) for swc, a in rows} for f, rows in families.items()}


def check(reports: List[Dict], witness: str = "solved") -> Dict:
    """Replay every finding of the completed reports, each
    {code, issues, family, walk_cut}, and look for the planted
    weaknesses of each report whose walk ran to its end. Returns the
    counts compared and their limits."""
    expected = planted()
    findings = unwitnessed = unjudged = 0
    cut = looked_for = missed = 0
    for report in reports:
        code = report["code"]
        for issue in report["issues"]:
            findings += 1
            said = finding_reached(code, issue, witness)
            unwitnessed += said == NOT_REACHED
            unjudged += said == UNJUDGED
        if report["walk_cut"]:
            cut += 1
            continue
        want = expected.get(report["family"], set())
        found = {
            (str(i.get("swc-id")).removeprefix("SWC-"), int(i["address"]))
            for i in report["issues"]
        }
        looked_for += len(want)
        missed += len(want - found)
    return {
        "findings_checked": findings,
        "findings_unjudged": unjudged,
        "walks_cut": cut,
        "planted_looked_for": looked_for,
        "compared": {
            "unwitnessed_findings": {"value": unwitnessed, "limit": 0},
            "missed_planted": {"value": missed, "limit": 0},
        },
    }


def passed(result: Dict) -> bool:
    return all(
        row["value"] <= row["limit"] for row in result["compared"].values()
    )
