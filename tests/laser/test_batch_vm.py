"""Conformance tests for the batched concrete interpreter.

Hand-assembled EVM programs (our analog of the reference's VMTests
harness, reference: tests/laser/evm_testsuite/evm_test.py) run through
the jit'd step kernel; storage/stack/memory/status/gas are compared
against hand-computed EVM semantics.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from mythril_tpu.disassembler.asm import assemble, push
from mythril_tpu.laser.batch import (
    Status,
    make_batch,
    make_code_table,
    run,
)
from mythril_tpu.laser.batch.state import mem_bytes, stack_list, storage_dict
from mythril_tpu.support.keccak import keccak256_int

M = 1 << 256


def exec_one(src, calldata=b"", callvalue=0, max_steps=4096):
    code = assemble(src) if not isinstance(src, bytes) else src
    # fixed code_cap so every test reuses one compiled step kernel
    table = make_code_table([code], code_cap=256)
    batch = make_batch(1, calldata=[calldata], callvalue=callvalue)
    out, steps = run(batch, table, max_steps=max_steps)
    return out


def sstore(slot, valsrc):
    """Assemble: SSTORE(slot) = result of valsrc (list of lines)."""
    return valsrc + [push(slot), "SSTORE"]


def test_arithmetic_program():
    src = (
        sstore(0, [push(3), push(4), "ADD"])          # 4+3 = 7
        + sstore(1, [push(3), push(10), "SUB"])        # 10-3 = 7
        + sstore(2, [push(6), push(7), "MUL"])         # 42
        + sstore(3, [push(3), push(100), "DIV"])       # 33
        + sstore(4, [push(7), push(100), "MOD"])       # 2
        + sstore(5, [push(10), push(2), "EXP"])        # 1024
        + sstore(6, [push(5), push(3), push(4), "ADDMOD"])  # (3+4)%5 = 2
        + sstore(7, [push(5), push(3), push(4), "MULMOD"])  # 12%5 = 2
        + ["STOP"]
    )
    out = exec_one(src)
    assert int(out.status[0]) == Status.STOPPED
    assert storage_dict(out, 0) == {0: 7, 1: 7, 2: 42, 3: 33, 4: 2, 5: 1024,
                                    6: 2, 7: 2}


def test_stack_ops_dup_swap():
    # stack: [1, 2, 3]; SWAP2 -> [3, 2, 1]; DUP3 -> [3, 2, 1, 3]
    src = [push(1), push(2), push(3), "SWAP2", "DUP3", "STOP"]
    out = exec_one(src)
    assert stack_list(out, 0) == [3, 2, 1, 3]


def test_comparisons_and_bitwise():
    src = (
        sstore(0, [push(2), push(1), "LT"])  # 1 < 2 -> 1
        + sstore(1, [push(1), push(2), "LT"])  # 2 < 1 -> 0
        + sstore(2, [push(0xF0), push(0x0F), "OR"])
        + sstore(3, [push(1), "NOT"])  # 2^256 - 2
        + sstore(4, [push(0), "ISZERO"])
        + sstore(5, [push(2), push(1), "SHL"])  # 1 << 2 = 4
        + ["STOP"]
    )
    out = exec_one(src)
    got = storage_dict(out, 0)
    assert got[0] == 1 and 1 not in got  # slot1 = 0 filtered as zero
    assert got[2] == 0xFF
    assert got[3] == M - 2
    assert got[4] == 1
    assert got[5] == 4


def test_memory_roundtrip_and_msize():
    src = (
        [push(0xDEADBEEF), push(0x20), "MSTORE"]  # mem[0x20:0x40] = ..beef
        + sstore(0, [push(0x20), "MLOAD"])
        + sstore(1, ["MSIZE"])
        + [push(0xAB), push(0x5F), "MSTORE8"]      # single byte at 0x5f
        + sstore(2, [push(0x40), "MLOAD"])
        + ["STOP"]
    )
    out = exec_one(src)
    got = storage_dict(out, 0)
    assert got[0] == 0xDEADBEEF
    assert got[1] == 0x40
    assert got[2] == 0xAB  # byte at offset 0x5f is the LSB of word at 0x40


def test_jump_loop_sum():
    # sum = 0; i = 10; while i: sum += i; i -= 1;  sstore(0, sum)
    src = [
        push(0),            # sum
        push(10),           # i  -> stack [sum, i]
        "JUMPDEST",         # addr 4: loop head
        "DUP1",
        "ISZERO",
        push(0x15),         # exit
        "JUMPI",
        "DUP1",             # [sum, i, i]
        "SWAP2",            # [i, i, sum]
        "ADD",              # [i, sum+i]
        "SWAP1",            # [sum+i, i]
        push(1),
        "SWAP1",
        "SUB",              # i-1
        push(0x04),
        "JUMP",
        "JUMPDEST",         # addr 0x15: exit
        "POP",
        push(0),
        "SSTORE",
        "STOP",
    ]
    code = assemble(src)
    # verify hand-computed jump targets hold
    assert code[4] == 0x5B and code[0x15] == 0x5B
    out = exec_one(src)
    assert int(out.status[0]) == Status.STOPPED
    assert storage_dict(out, 0) == {0: 55}


def test_calldata_ops():
    cd = bytes.fromhex("a9059cbb") + (0x1234).to_bytes(32, "big")
    src = (
        sstore(0, [push(0), "CALLDATALOAD", push(0xE0), "SHR"])  # selector
        + sstore(1, [push(4), "CALLDATALOAD"])                    # arg
        + sstore(2, ["CALLDATASIZE"])
        # CALLDATACOPY(mem 0, src 4, len 32) then MLOAD(0)
        + sstore(3, [push(32), push(4), push(0), "CALLDATACOPY",
                     push(0), "MLOAD"])
        + ["STOP"]
    )
    out = exec_one(src, calldata=cd)
    got = storage_dict(out, 0)
    assert got[0] == 0xA9059CBB
    assert got[1] == 0x1234
    assert got[2] == 36
    assert got[3] == 0x1234


def test_sha3():
    # keccak256 of 64 zero bytes (fresh memory)
    src = sstore(0, [push(64), push(0), "SHA3"]) + ["STOP"]
    out = exec_one(src)
    assert storage_dict(out, 0)[0] == keccak256_int(bytes(64))


def test_sha3_nonzero_input():
    src = (
        [push(0x0102030405060708), push(0x20), "MSTORE"]
        + sstore(0, [push(0x40), push(0), "SHA3"])
        + ["STOP"]
    )
    out = exec_one(src)
    # mem[0x20:0x40] holds the 32-byte BE word -> hash input is 56 zero
    # bytes followed by the 8 value bytes
    expect = keccak256_int(bytes(56) + (0x0102030405060708).to_bytes(8, "big"))
    assert storage_dict(out, 0)[0] == expect


def test_return_data():
    src = [
        push(0xCAFE), push(0), "MSTORE",
        push(32), push(0), "RETURN",
    ]
    out = exec_one(src)
    assert int(out.status[0]) == Status.RETURNED
    assert int(out.ret_offset[0]) == 0 and int(out.ret_len[0]) == 32
    assert mem_bytes(out, 0, 0, 32) == (0xCAFE).to_bytes(32, "big")


def test_revert_status():
    out = exec_one([push(0), push(0), "REVERT"])
    assert int(out.status[0]) == Status.REVERTED


def test_error_paths():
    # invalid jump destination (into push data)
    out = exec_one([push(1), "JUMP", "STOP"])
    assert int(out.status[0]) == Status.ERR_JUMP
    # stack underflow
    out = exec_one(["ADD", "STOP"])
    assert int(out.status[0]) == Status.ERR_STACK
    # designated invalid opcode
    out = exec_one(bytes([0xFE]))
    assert int(out.status[0]) == Status.INVALID
    # unknown opcode byte
    out = exec_one(bytes([0x21]))
    assert int(out.status[0]) == Status.INVALID
    # unsupported on device -> host takes over
    out = exec_one([push(0)] * 3 + ["CREATE"])
    assert int(out.status[0]) == Status.UNSUPPORTED
    # a CALL to a codeless address executes on device as a transfer
    # (empty-world semantics); STOP after it proves the lane continued
    out = exec_one([push(0)] * 7 + ["CALL", "STOP"])
    assert int(out.status[0]) == Status.STOPPED
    # ... but a self-call needs real code execution -> host takeover
    out = exec_one(
        [push(0)] * 5 + ["ADDRESS"] + [push(0)] + ["CALL"])
    assert int(out.status[0]) == Status.UNSUPPORTED
    # running off the end of code halts like STOP
    out = exec_one([push(1), "POP"])
    assert int(out.status[0]) == Status.STOPPED


def test_env_opcodes():
    src = (
        sstore(0, ["CALLVALUE"])
        + sstore(1, ["CALLER"])
        + sstore(2, ["ADDRESS"])
        + sstore(3, ["TIMESTAMP"])
        + sstore(4, ["NUMBER"])
        + sstore(5, ["CHAINID"])
        + sstore(6, ["CODESIZE"])
        + ["STOP"]
    )
    out = exec_one(src, callvalue=123)
    got = storage_dict(out, 0)
    assert got[0] == 123
    assert got[1] == 0xDEADBEEFDEADBEEF
    assert got[2] == 0xAFFEAFFE
    assert got[3] == 1_600_000_000
    assert got[4] == 10_000_000
    assert got[5] == 1
    assert got[6] == len(assemble(src))


def test_signed_ops_in_program():
    minus2 = M - 2
    src = (
        sstore(0, [push(minus2), push(7), "SDIV"])  # 7 / -2 = -3
        + sstore(1, [push(3), push(minus2), "SMOD"])  # -2 % 3 = -2
        + sstore(2, [push(minus2), push(1), "SLT"])   # 1 < -2 ? 0
        + sstore(3, [push(1), push(minus2), "SLT"])   # -2 < 1 ? 1
        + ["STOP"]
    )
    out = exec_one(src)
    got = storage_dict(out, 0)
    assert got.get(0, 0) == M - 3
    assert got.get(1, 0) == M - 2
    assert 2 not in got
    assert got.get(3, 0) == 1


def test_gas_accounting_simple():
    # PUSH(3) + PUSH(3) + ADD(3) + PUSH(3) + SSTORE(5000..25000) + STOP(0)
    src = [push(1), push(2), "ADD", push(0), "SSTORE", "STOP"]
    out = exec_one(src)
    assert int(out.gas_min[0]) == 3 + 3 + 3 + 3 + 5000
    assert int(out.gas_max[0]) == 3 + 3 + 3 + 3 + 25000


def test_sstore_overwrite_and_sload():
    src = (
        [push(7), push(5), "SSTORE"]
        + [push(9), push(5), "SSTORE"]   # overwrite slot 5
        + sstore(1, [push(5), "SLOAD"])
        + sstore(2, [push(99), "SLOAD"])  # never written -> 0
        + ["STOP"]
    )
    out = exec_one(src)
    got = storage_dict(out, 0)
    assert got[5] == 9 and got[1] == 9 and 2 not in got


def test_heterogeneous_batch():
    """Different contracts + calldata per lane in one batch."""
    prog_a = assemble(sstore(0, [push(2), push(5), "ADD"]) + ["STOP"])
    prog_b = assemble(sstore(0, [push(0), "CALLDATALOAD"]) + ["STOP"])
    prog_c = assemble([push(0), "JUMP"])  # invalid jump
    table = make_code_table([prog_a, prog_b, prog_c], code_cap=256)
    batch = make_batch(
        6,
        code_ids=[0, 1, 2, 0, 1, 2],
        calldata=[b"", (11).to_bytes(32, "big"), b"", b"",
                  (22).to_bytes(32, "big"), b""],
    )
    out, steps = run(batch, table)
    assert storage_dict(out, 0) == {0: 7}
    assert storage_dict(out, 1) == {0: 11}
    assert int(out.status[2]) == Status.ERR_JUMP
    assert storage_dict(out, 3) == {0: 7}
    assert storage_dict(out, 4) == {0: 22}
    assert int(out.status[5]) == Status.ERR_JUMP
    assert [int(s) for s in out.status[:2]] == [Status.STOPPED, Status.STOPPED]


def test_pc_opcode():
    src = [push(0), "POP", "PC"]  # PC at address 3 pushes 3
    out = exec_one(src)
    assert stack_list(out, 0) == [3]


def test_int32_wrap_offset_is_oog():
    """An MSTORE at an offset just below 2**31 must out-of-gas, not wrap
    the int32 end-of-access computation and silently no-op."""
    import jax.numpy as jnp

    from mythril_tpu.laser.batch.run import run
    from mythril_tpu.laser.batch.state import Status, make_batch, make_code_table

    code = (
        bytes.fromhex("6001")                      # PUSH1 1
        + bytes([0x63, 0x7F, 0xFF, 0xFF, 0xE1])    # PUSH4 0x7FFFFFE1
        + bytes.fromhex("5200")                    # MSTORE; STOP
    )
    table = make_code_table([code])
    batch = make_batch(1)._replace(
        gas_budget=jnp.asarray([1000], dtype=jnp.uint32)
    )
    out, _ = run(batch, table, max_steps=16)
    assert int(out.status[0]) == Status.ERR_OOG


def test_extcodesize_and_returndatacopy_device_semantics():
    """EXTCODESIZE answers on device (own size / 0 in an empty world);
    RETURNDATACOPY's zero-length Solidity form is a no-op; everything
    else hands off to the host."""
    import numpy as np

    from mythril_tpu.laser.batch.run import run
    from mythril_tpu.laser.batch.state import (
        Status,
        make_batch,
        make_code_table,
        storage_dict,
    )

    code = bytes([
        0x30, 0x3B, 0x60, 0x00, 0x55,              # EXTCODESIZE(self) -> s0
        0x61, 0xBE, 0xEF, 0x3B, 0x60, 0x01, 0x55,  # EXTCODESIZE(0xbeef) -> s1
        0x60, 0x00, 0x60, 0x00, 0x60, 0x00, 0x3E,  # RETURNDATACOPY(0,0,0)
        0x00,
    ])
    table = make_code_table([code])
    batch = make_batch(
        2, calldata=[b"", b""], empty_world=np.array([1, 0], np.uint8)
    )
    out, _ = run(batch, table, max_steps=32)
    assert int(out.status[0]) == Status.STOPPED
    assert storage_dict(out, 0) == {0: len(code)}  # foreign size 0 filtered
    # a world that may hold foreign code defers the foreign query
    assert int(out.status[1]) == Status.UNSUPPORTED

    # nonzero-length RETURNDATACOPY is an EVM exception -> host decides
    code2 = bytes([0x60, 0x01, 0x60, 0x00, 0x60, 0x00, 0x3E, 0x00])
    out2, _ = run(make_batch(1, calldata=[b""]), make_code_table([code2]),
                  max_steps=8)
    assert int(out2.status[0]) == Status.UNSUPPORTED


def test_einsum_peek_matches_gather_peek(monkeypatch):
    """Accelerators read stack slots, calldata and storage through
    one-hot contractions ("einsum"); CPU takes the gather. Both forms
    are traced here on CPU over a loop of CALLDATALOAD / arithmetic /
    SSTORE / SLOAD and must leave every lane bit-identical."""
    import importlib

    import jax

    from __graft_entry__ import _demo_workload
    from mythril_tpu.laser.batch.run import _run_impl

    # the package re-exports the step FUNCTION under the module's name
    step_mod = importlib.import_module("mythril_tpu.laser.batch.step")

    batch, code = _demo_workload(n_lanes=16)
    outs = {}
    for choice in ("gather", "einsum"):
        monkeypatch.setattr(step_mod, "_PEEK_CHOICE", choice)
        # a fresh jit per choice: the choice is read at trace time
        outs[choice] = jax.jit(lambda b, c: _run_impl(b, c, max_steps=48))(
            batch, code
        )
    for got, want in zip(
        jax.tree.leaves(outs["einsum"]), jax.tree.leaves(outs["gather"])
    ):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
