"""Contract shapes the traffic mixes draw from.

Copied from the program's corpus synthesis (mythril_tpu/analysis/
corpusgen.py: `mutate_constants`, `wide_contract`, `loop_contract`) so
that a later change to the program cannot move the yardstick. Each
shape is a pure function of its arguments.
"""

from __future__ import annotations

import random
from pathlib import Path
from typing import List, Tuple

PUSH1, PUSH32 = 0x60, 0x7F
FIXTURES = Path(__file__).resolve().parent / "traffic" / "fixtures"


def _instruction_starts(code: bytes) -> List[int]:
    starts = []
    pc = 0
    while pc < len(code):
        starts.append(pc)
        op = code[pc]
        pc += 1 + (op - PUSH1 + 1 if PUSH1 <= op <= PUSH32 else 0)
    return starts


def _masklike(word: bytes) -> bool:
    extreme = sum(1 for b in word if b in (0x00, 0xFF))
    return extreme >= len(word) - 2 or len(set(word)) <= 2


def mutate_constants(code: bytes, rng: random.Random) -> bytes:
    """A replica with the same instruction skeleton: new dispatcher
    selectors (PUSH4 followed by EQ), new PUSH20 addresses, and new low
    halves of PUSH32 data words. Jump targets are never touched."""
    out = bytearray(code)
    starts = _instruction_starts(code)
    for i, pc in enumerate(starts):
        op = code[pc]
        if not PUSH1 <= op <= PUSH32:
            continue
        width = op - PUSH1 + 1
        arg = bytes(code[pc + 1:pc + 1 + width])
        if len(arg) < width:
            continue
        nxt = code[starts[i + 1]] if i + 1 < len(starts) else None
        if width == 4 and nxt == 0x14:
            out[pc + 1:pc + 5] = rng.randbytes(4)
        elif width == 20:
            out[pc + 1:pc + 21] = rng.randbytes(20)
        elif width == 32 and not _masklike(arg):
            out[pc + 17:pc + 33] = rng.randbytes(16)
    return bytes(out)


def fixtures() -> List[Tuple[str, str]]:
    """[(family, runtime hex)] of the vendored fixtures, by name."""
    out = []
    for f in sorted(FIXTURES.glob("*.sol.o")):
        code = f.read_text().strip()
        code = code[2:] if code.startswith("0x") else code
        out.append((f.name.removesuffix(".o"), code))
    return out


def wide_contract(n_guards: int, seed: int) -> str:
    """`n_guards` independent calldata guards (each a 32-byte word
    against its own constant), an ADD that wraps into a branch
    (SWC-101), an ORIGIN guard (SWC-115), a TIMESTAMP guard (SWC-116)
    and a calldata-guarded SELFDESTRUCT (SWC-106). A sequential walk
    forks about 2^(n_guards+4) ways; branch coverage needs one flip per
    guard direction. `seed` is below 2**31."""
    rng = random.Random(0xBEEF + seed)
    code = bytearray()

    def guard_cd(offset: int, magic: int, body: bytes) -> None:
        code.extend([0x61, (offset >> 8) & 0xFF, offset & 0xFF, 0x35])
        code.extend([0x63])
        code.extend(magic.to_bytes(4, "big"))
        code.extend([0x14, 0x15])
        skip = len(code) + 3 + 1 + len(body)
        code.extend([0x61, (skip >> 8) & 0xFF, skip & 0xFF, 0x57])
        code.extend(body)
        code.extend([0x5B])

    def mark(j: int) -> bytes:
        return bytes([0x60, 0x01, 0x60, j & 0xFF, 0x53])

    def guard_tail(j: int) -> None:
        code.extend([0x14, 0x15])
        skip = len(code) + 3 + 1 + 5
        code.extend([0x61, (skip >> 8) & 0xFF, skip & 0xFF, 0x57])
        code.extend(mark(j))
        code.extend([0x5B])

    for j in range(n_guards):
        guard_cd(4 + 32 * j, 0xFEED0000 + rng.getrandbits(16), mark(j))
    o_w = 4 + 32 * n_guards
    big = (2**256 - (0x10000 + rng.getrandbits(12))) | 1
    code.extend([0x61, (o_w >> 8) & 0xFF, o_w & 0xFF, 0x35, 0x7F])
    code.extend(big.to_bytes(32, "big"))
    code.extend([0x01, 0x60, 0x00])
    guard_tail(7)
    code.extend([0x32, 0x73])
    code.extend((0xAAAA000000000000000000000000000000000000 + seed).to_bytes(20, "big"))
    guard_tail(8)
    code.extend([0x42, 0x63])
    code.extend((0x5C000000 + seed).to_bytes(4, "big"))
    guard_tail(9)
    guard_cd(o_w + 32, 0xDEAD0000 + rng.getrandbits(16), bytes([0x33, 0xFF]))
    code.extend([0x00])
    return bytes(code).hex()


def loop_contract(iterations_cap: int, magic: int) -> str:
    """`n = calldata[0..31] & cap; while (n) { acc += n; n -= 1 };
    storage[0] = acc; if (calldata[32..63] == magic) assert(false)`:
    an attacker-chosen loop count before a guarded SWC-110. The program's
    version compares with the one byte 0xaa; here `magic` is a 4-byte
    constant drawn from the seed, so that no two contracts of a stream
    are alike."""
    loop = 0x0A
    code = bytes(
        [0x60, 0x00, 0x35, 0x60, iterations_cap & 0xFF, 0x16, 0x60, 0x00]
    )
    code += bytes([0x90, 0x90])
    body = bytes(
        [
            0x5B, 0x81, 0x15, 0x60, 0x00, 0x57, 0x81, 0x01, 0x90,
            0x60, 0x01, 0x90, 0x03, 0x90, 0x60, loop, 0x56,
        ]
    )
    exit_at = loop + len(body)
    body = body.replace(bytes([0x60, 0x00, 0x57]), bytes([0x60, exit_at, 0x57]))
    tail = bytes([0x5B, 0x60, 0x00, 0x55])
    fail_at = exit_at + len(tail) + 13
    tail += bytes([0x60, 0x20, 0x35, 0x63]) + (magic & 0xFFFFFFFF).to_bytes(4, "big")
    tail += bytes([0x14, 0x60, fail_at, 0x57, 0x00, 0x5B, 0xFE])
    return (code + body + tail).hex()
