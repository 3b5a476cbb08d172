"""A plain EVM interpreter that replays a finding's transactions.

It shares no code with the system under test. It runs a contract's
runtime bytecode on the calldata, call value and sender that a finding's
transaction sequence states, and answers one question: does some
execution of the last transaction execute the instruction at a given
address?

Values the sequence does not fix are unknown: the block environment,
balances, gas, other accounts' code, the results of external calls,
and storage slots that no transaction has written (the analyser treats
a deployed contract's initial storage as free). An unknown value stays
unknown through arithmetic; a JUMPI on an unknown condition explores
both directions. Everything the transaction fixes (calldata, value,
sender, storage written by earlier transactions, memory) is exact, so
a wrong witness, a wrong address or a path the calldata cannot take
does not reach.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from .keccak import keccak256

M = 1 << 256
MASK = M - 1
SIGN = 1 << 255

#: execution budget of one replay (all paths, all transactions): a
#: witness that needs more than this is reported as not reached
STEP_BUDGET = 400_000
#: a calldata offset no transaction can carry. The analyser models
#: calldata as an unbounded array, so a witness that reads there (an
#: offset that wrapped around 2**256) relies on bytes its rendered
#: calldata cannot hold, and the replay cannot judge it
WRAPPED = 1 << 64
#: live paths kept at once; further forks are dropped
PATH_CAP = 4096
#: storage states carried from one transaction into the next
CARRY_CAP = 64


def _signed(x: int) -> int:
    return x - M if x & SIGN else x


class _Path:
    __slots__ = ("pc", "stack", "mem", "unk", "storage", "wild", "rdata")

    def __init__(self, storage: Dict, wild: bool) -> None:
        self.pc = 0
        self.stack: List[Optional[int]] = []
        self.mem = bytearray()
        #: memory byte i is unknown when unk[i] == 1
        self.unk = bytearray()
        self.storage = storage
        self.wild = wild
        self.rdata: Optional[bytes] = b""

    def fork(self) -> "_Path":
        p = _Path(dict(self.storage), self.wild)
        p.pc = self.pc
        p.stack = list(self.stack)
        p.mem = bytearray(self.mem)
        p.unk = bytearray(self.unk)
        p.rdata = self.rdata
        return p

    def grow(self, end: int) -> None:
        if end > len(self.mem):
            size = (end + 31) // 32 * 32
            self.mem.extend(bytes(size - len(self.mem)))
            self.unk.extend(bytes(size - len(self.unk)))

    def read(self, off: int, size: int) -> Optional[bytes]:
        if size == 0:
            return b""
        self.grow(off + size)
        if any(self.unk[off:off + size]):
            return None
        return bytes(self.mem[off:off + size])

    def write(self, off: int, data: Optional[bytes], size: int) -> None:
        if size == 0:
            return
        self.grow(off + size)
        if data is None:
            self.unk[off:off + size] = b"\x01" * size
        else:
            self.mem[off:off + size] = data
            self.unk[off:off + size] = bytes(size)


class _Lost(Exception):
    """The path depends on an unknown it cannot branch on (a jump
    target, a memory offset): it is dropped, not reached."""


def jumpdests(code: bytes) -> set:
    out = set()
    pc = 0
    while pc < len(code):
        op = code[pc]
        if op == 0x5B:
            out.add(pc)
        pc += 1 + (op - 0x5F if 0x60 <= op <= 0x7F else 0)
    return out


def _binop(op: int, a: int, b: int) -> int:
    if op == 0x01:
        return (a + b) & MASK
    if op == 0x02:
        return (a * b) & MASK
    if op == 0x03:
        return (a - b) & MASK
    if op == 0x04:
        return a // b if b else 0
    if op == 0x05:
        if b == 0:
            return 0
        sa, sb = _signed(a), _signed(b)
        q = abs(sa) // abs(sb)
        return (q if (sa < 0) == (sb < 0) else -q) & MASK
    if op == 0x06:
        return a % b if b else 0
    if op == 0x07:
        if b == 0:
            return 0
        sa, sb = _signed(a), _signed(b)
        r = abs(sa) % abs(sb)
        return (-r if sa < 0 else r) & MASK
    if op == 0x0A:
        return pow(a, b, M)
    if op == 0x0B:
        if a >= 31:
            return b
        bit = 8 * a + 7
        low = b & ((1 << (bit + 1)) - 1)
        return (low | (MASK ^ ((1 << (bit + 1)) - 1))) if b >> bit & 1 else low
    if op == 0x10:
        return int(a < b)
    if op == 0x11:
        return int(a > b)
    if op == 0x12:
        return int(_signed(a) < _signed(b))
    if op == 0x13:
        return int(_signed(a) > _signed(b))
    if op == 0x14:
        return int(a == b)
    if op == 0x16:
        return a & b
    if op == 0x17:
        return a | b
    if op == 0x18:
        return a ^ b
    if op == 0x1A:
        return (b >> (8 * (31 - a))) & 0xFF if a < 32 else 0
    if op == 0x1B:
        return (b << a) & MASK if a < 256 else 0
    if op == 0x1C:
        return b >> a if a < 256 else 0
    if op == 0x1D:
        if a >= 256:
            return MASK if b & SIGN else 0
        return (_signed(b) >> a) & MASK
    raise AssertionError(op)


_BINOPS = {
    0x01, 0x02, 0x03, 0x04, 0x05, 0x06, 0x07, 0x0A, 0x0B, 0x10, 0x11,
    0x12, 0x13, 0x14, 0x16, 0x17, 0x18, 0x1A, 0x1B, 0x1C, 0x1D,
}
#: opcodes that push one value the transaction does not fix
_UNKNOWN_ENV = {
    0x3A, 0x41, 0x42, 0x43, 0x44, 0x45, 0x46, 0x47, 0x48, 0x5A,
}
#: opcode -> (values popped, values pushed unknown)
_UNKNOWN_OPS = {
    0x31: (1, 1),  # BALANCE
    0x3B: (1, 1),  # EXTCODESIZE
    0x3F: (1, 1),  # EXTCODEHASH
    0x40: (1, 1),  # BLOCKHASH
    0xF0: (3, 1),  # CREATE
    0xF5: (4, 1),  # CREATE2
}


class Tx:
    """One transaction of a witness."""

    def __init__(self, calldata: bytes, value: int, caller: int,
                 address: int) -> None:
        self.calldata = calldata
        self.value = value
        self.caller = caller
        self.address = address


class Replay:
    """Replays transactions on one contract's runtime code."""

    def __init__(self, code: bytes) -> None:
        self.code = code
        self.dests = jumpdests(code)
        self.steps = 0
        #: some path read calldata at a wrapped offset
        self.wrapped = False

    def run(self, tx: Tx, storage: Dict, wild: bool,
            target: Optional[Tuple[int, Optional[frozenset]]] = None):
        """Run one transaction from `storage`.

        With `target` = (pc, opcodes or None): True when some path
        executes the instruction at pc (whose byte must be one of
        `opcodes` when given), else False. Without: the storages of the paths that
        ended, as [(storage, wild)], those of reverted paths unchanged.
        """
        ends: List[Tuple[Dict, bool]] = []
        live = [_Path(dict(storage), wild)]
        while live:
            p = live.pop()
            try:
                outcome = self._exec(p, tx, live, target)
            except _Lost:
                continue
            if outcome is True:
                return True
            if target is None and len(ends) < CARRY_CAP:
                if outcome == "ok":
                    ends.append((p.storage, p.wild))
                elif outcome == "revert":
                    ends.append((dict(storage), wild))
            if self.steps > STEP_BUDGET:
                break
        return False if target is not None else ends

    def _exec(self, p: _Path, tx: Tx, live: List[_Path], target):
        code = self.code
        n = len(code)
        st = p.stack
        while True:
            self.steps += 1
            if self.steps > STEP_BUDGET:
                raise _Lost()
            pc = p.pc
            if pc >= n:
                return "ok"
            op = code[pc]
            if target is not None and pc == target[0]:
                if target[1] is None or op in target[1]:
                    return True
            p.pc = pc + 1
            if 0x60 <= op <= 0x7F:
                width = op - 0x5F
                st.append(int.from_bytes(
                    code[pc + 1:pc + 1 + width].ljust(width, b"\0"), "big"))
                p.pc = pc + 1 + width
            elif 0x80 <= op <= 0x8F:
                st.append(st[-(op - 0x7F)])
            elif 0x90 <= op <= 0x9F:
                k = op - 0x8F
                st[-1], st[-1 - k] = st[-1 - k], st[-1]
            elif op in _BINOPS:
                a = st.pop()
                b = st.pop()
                if a is None or b is None:
                    st.append(None)
                else:
                    st.append(_binop(op, a, b))
            elif op == 0x00:
                return "ok"
            elif op == 0x08 or op == 0x09:
                a, b, c = st.pop(), st.pop(), st.pop()
                if a is None or b is None or c is None:
                    st.append(None)
                elif c == 0:
                    st.append(0)
                else:
                    st.append((a + b) % c if op == 0x08 else (a * b) % c)
            elif op == 0x15:
                a = st.pop()
                st.append(None if a is None else int(a == 0))
            elif op == 0x19:
                a = st.pop()
                st.append(None if a is None else a ^ MASK)
            elif op == 0x20:
                off, size = self._known(st.pop()), self._known(st.pop())
                data = p.read(off, size)
                st.append(None if data is None
                          else int.from_bytes(keccak256(data), "big"))
            elif op == 0x30:
                st.append(tx.address)
            elif op == 0x32 or op == 0x33:
                st.append(tx.caller)
            elif op == 0x34:
                st.append(tx.value)
            elif op == 0x35:
                off = st.pop()
                if off is None:
                    st.append(None)
                else:
                    self.wrapped |= off >= WRAPPED
                    word = tx.calldata[off:off + 32] if off < len(tx.calldata) else b""
                    st.append(int.from_bytes(word.ljust(32, b"\0"), "big"))
            elif op == 0x36:
                st.append(len(tx.calldata))
            elif op == 0x37 or op == 0x39:
                dst = self._known(st.pop())
                src, size = st.pop(), self._known(st.pop())
                blob = tx.calldata if op == 0x37 else code
                if src is None:
                    p.write(dst, None, size)
                else:
                    self.wrapped |= op == 0x37 and src >= WRAPPED
                    chunk = blob[src:src + size] if src < len(blob) else b""
                    p.write(dst, chunk.ljust(size, b"\0"), size)
            elif op == 0x38:
                st.append(n)
            elif op == 0x3C:
                st.pop()
                dst = self._known(st.pop())
                st.pop()
                size = self._known(st.pop())
                p.write(dst, None, size)
            elif op == 0x3D:
                st.append(None if p.rdata is None else len(p.rdata))
            elif op == 0x3E:
                dst = self._known(st.pop())
                src, size = st.pop(), self._known(st.pop())
                if p.rdata is None or src is None:
                    p.write(dst, None, size)
                else:
                    if src + size > len(p.rdata):
                        return "revert"
                    p.write(dst, p.rdata[src:src + size], size)
            elif op in _UNKNOWN_ENV:
                st.append(None)
            elif op in _UNKNOWN_OPS:
                pops, _pushes = _UNKNOWN_OPS[op]
                for _ in range(pops):
                    st.pop()
                st.append(None)
            elif op == 0x50:
                st.pop()
            elif op == 0x51:
                off = self._known(st.pop())
                data = p.read(off, 32)
                st.append(None if data is None else int.from_bytes(data, "big"))
            elif op == 0x52:
                off = self._known(st.pop())
                v = st.pop()
                p.write(off, None if v is None else v.to_bytes(32, "big"), 32)
            elif op == 0x53:
                off = self._known(st.pop())
                v = st.pop()
                p.write(off, None if v is None else bytes([v & 0xFF]), 1)
            elif op == 0x54:
                key = st.pop()
                st.append(None if key is None else p.storage.get(key))
            elif op == 0x55:
                key, v = st.pop(), st.pop()
                if key is None:
                    # a write to an unknown slot may have hit any slot
                    p.wild = True
                    p.storage = {}
                else:
                    p.storage[key] = v
            elif op == 0x56:
                dest = self._known(st.pop())
                if dest not in self.dests:
                    return "revert"
                p.pc = dest
            elif op == 0x57:
                dest, cond = st.pop(), st.pop()
                if cond is None:
                    dest = self._known(dest)
                    if dest in self.dests and len(live) < PATH_CAP:
                        taken = p.fork()
                        taken.pc = dest
                        live.append(taken)
                elif cond:
                    dest = self._known(dest)
                    if dest not in self.dests:
                        return "revert"
                    p.pc = dest
            elif op == 0x58:
                st.append(pc)
            elif op == 0x59:
                st.append(len(p.mem))
            elif op == 0x5B:
                pass
            elif 0xA0 <= op <= 0xA4:
                self._known(st.pop())
                self._known(st.pop())
                for _ in range(op - 0xA0):
                    st.pop()
            elif op in (0xF1, 0xF2, 0xF4, 0xFA):
                st.pop()
                st.pop()
                if op in (0xF1, 0xF2):
                    st.pop()
                st.pop()
                st.pop()
                ret_off = self._known(st.pop())
                ret_size = self._known(st.pop())
                p.write(ret_off, None, ret_size)
                p.rdata = None
                st.append(None)
            elif op == 0xF3:
                st.pop()
                st.pop()
                return "ok"
            elif op == 0xFD:
                return "revert"
            elif op == 0xFF:
                st.pop()
                return "ok"
            else:
                # INVALID (0xfe) and undefined opcodes: exceptional halt
                return "revert"

    @staticmethod
    def _known(v: Optional[int]) -> int:
        if v is None or v > 1 << 32:
            raise _Lost()
        return v


#: what a replay says of a witness
REACHED, NOT_REACHED, UNJUDGED = "reached", "not-reached", "unjudged"


def reaches(code: bytes, txs: List[Tx], storage: Dict, wild: bool,
            pc: int, opcodes: Optional[frozenset]) -> str:
    """REACHED when, run in order from `storage`, the last of `txs` can
    execute the instruction at `pc` (one of `opcodes` when given);
    UNJUDGED when it cannot but some path read calldata at a wrapped
    offset; NOT_REACHED otherwise."""
    replay = Replay(code)

    def verdict(hit: bool) -> str:
        if hit:
            return REACHED
        return UNJUDGED if replay.wrapped else NOT_REACHED

    states = [(storage, wild)]
    for tx in txs[:-1]:
        nxt: List[Tuple[Dict, bool]] = []
        for s, w in states:
            nxt.extend(replay.run(tx, s, w))
            if len(nxt) >= CARRY_CAP or replay.steps > STEP_BUDGET:
                break
        states = nxt[:CARRY_CAP]
        if not states:
            return verdict(False)
    for s, w in states:
        if replay.run(txs[-1], s, w, target=(pc, opcodes)):
            return REACHED
        if replay.steps > STEP_BUDGET:
            break
    return verdict(False)
