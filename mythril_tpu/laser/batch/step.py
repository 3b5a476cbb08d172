"""The batched EVM state-transition kernel.

One call executes one instruction on every live lane of a StateBatch —
the lifted form of the reference's `Instruction.evaluate(global_state)`
dispatch (reference: mythril/laser/ethereum/instructions.py:231 and the
per-opcode handlers it selects). Design rules:

- *execute-all-and-mask* for cheap ops: every cheap handler's result is
  computed for all lanes and merged by opcode mask (wide SIMD beats
  branching on TPU);
- *cond-gating* for expensive handlers (division loops, EXP, keccak,
  memory copies, storage journal): `lax.cond(jnp.any(mask), ...)` skips
  the whole phase when no lane needs it this step;
- exactly ONE consolidated stack scatter per step (every opcode writes
  at most one result slot; SWAP's second slot is handled separately),
  because [N, STACK_CAP, 16] scatters dominate bandwidth otherwise.

Unknown opcodes mark the lane INVALID (the reference raises
InvalidInstruction and drops the state, svm.py:254); opcodes outside
the device set (CALL family, CREATE, EXTCODE*) mark UNSUPPORTED so the
host symbolic engine can take the lane over.
"""

from __future__ import annotations

import functools
import os
from typing import NamedTuple, Optional

import numpy as np

import jax.numpy as jnp
from jax import lax

from mythril_tpu.laser.batch.state import (
    CALLDATA_CAP,
    HASH_CAP,
    MEM_CAP,
    STACK_CAP,
    CodeTable,
    StateBatch,
    Status,
)
from mythril_tpu.ops import u256
from mythril_tpu.ops.keccak import keccak_f
from mythril_tpu.support.opcodes import OPCODES

W = u256.LIMBS

# ---------------------------------------------------------------------------
# opcode byte constants
# ---------------------------------------------------------------------------
_B = {name: entry[0] for name, entry in OPCODES.items()}

STOP, ADD, MUL, SUB, DIV, SDIV, MOD, SMOD = (
    _B["STOP"], _B["ADD"], _B["MUL"], _B["SUB"], _B["DIV"], _B["SDIV"],
    _B["MOD"], _B["SMOD"],
)
ADDMOD, MULMOD, EXP, SIGNEXTEND = _B["ADDMOD"], _B["MULMOD"], _B["EXP"], _B["SIGNEXTEND"]
LT, GT, SLT, SGT, EQ, ISZERO = _B["LT"], _B["GT"], _B["SLT"], _B["SGT"], _B["EQ"], _B["ISZERO"]
AND, OR, XOR, NOT, BYTE, SHL, SHR, SAR = (
    _B["AND"], _B["OR"], _B["XOR"], _B["NOT"], _B["BYTE"], _B["SHL"],
    _B["SHR"], _B["SAR"],
)
SHA3 = _B["SHA3"]
ADDRESS, BALANCE, ORIGIN, CALLER, CALLVALUE = (
    _B["ADDRESS"], _B["BALANCE"], _B["ORIGIN"], _B["CALLER"], _B["CALLVALUE"],
)
CALLDATALOAD, CALLDATASIZE, CALLDATACOPY = (
    _B["CALLDATALOAD"], _B["CALLDATASIZE"], _B["CALLDATACOPY"],
)
CODESIZE, CODECOPY, GASPRICE = _B["CODESIZE"], _B["CODECOPY"], _B["GASPRICE"]
RETURNDATASIZE = _B["RETURNDATASIZE"]
BLOCKHASH, COINBASE, TIMESTAMP, NUMBER, DIFFICULTY, GASLIMIT = (
    _B["BLOCKHASH"], _B["COINBASE"], _B["TIMESTAMP"], _B["NUMBER"],
    _B["DIFFICULTY"], _B["GASLIMIT"],
)
CHAINID, SELFBALANCE, BASEFEE = _B["CHAINID"], _B["SELFBALANCE"], _B["BASEFEE"]
POP, MLOAD, MSTORE, MSTORE8, SLOAD, SSTORE = (
    _B["POP"], _B["MLOAD"], _B["MSTORE"], _B["MSTORE8"], _B["SLOAD"], _B["SSTORE"],
)
JUMP, JUMPI, PC, MSIZE, GAS, JUMPDEST = (
    _B["JUMP"], _B["JUMPI"], _B["PC"], _B["MSIZE"], _B["GAS"], _B["JUMPDEST"],
)
RETURN, REVERT, INVALID_OP, SELFDESTRUCT = (
    _B["RETURN"], _B["REVERT"], _B["ASSERT_FAIL"], _B["SUICIDE"],
)
CALL_OP, CALLCODE_OP, DELEGATECALL_OP, STATICCALL_OP = (
    _B["CALL"], _B["CALLCODE"], _B["DELEGATECALL"], _B["STATICCALL"],
)
EXTCODESIZE_OP, EXTCODECOPY_OP, RETURNDATACOPY_OP = (
    _B["EXTCODESIZE"], _B["EXTCODECOPY"], _B["RETURNDATACOPY"],
)

_UNSUPPORTED_NAMES = [
    "CREATE", "CREATE2",
    "EXTCODECOPY", "EXTCODEHASH",
    "BEGINSUB", "RETURNSUB", "JUMPSUB",
]
# CALL/CALLCODE/DELEGATECALL/STATICCALL are conditionally supported:
# in an empty world (no foreign code) they execute as transfers; the
# kernel demotes the remaining cases to UNSUPPORTED per lane. The same
# gating covers EXTCODESIZE (self -> own code length, foreign -> 0)
# and RETURNDATACOPY (the zero-length form Solidity emits after calls;
# nonzero lengths are an EVM exception the host adjudicates).

# ---------------------------------------------------------------------------
# static per-opcode tables (numpy, baked into the jit as constants)
# ---------------------------------------------------------------------------
_VALID = np.zeros(256, dtype=bool)
_POPS = np.zeros(256, dtype=np.int32)
_NET_SP = np.zeros(256, dtype=np.int32)
_GAS_MIN = np.zeros(256, dtype=np.uint32)
_GAS_MAX = np.zeros(256, dtype=np.uint32)
_SUPPORTED = np.zeros(256, dtype=bool)
for _name, (_byte, _pops, _pushes, _gmin, _gmax) in OPCODES.items():
    _VALID[_byte] = True
    _POPS[_byte] = _pops
    _NET_SP[_byte] = _pushes - _pops
    _GAS_MIN[_byte] = _gmin
    _GAS_MAX[_byte] = _gmax
    _SUPPORTED[_byte] = _name not in _UNSUPPORTED_NAMES

# merged per-opcode metadata, one row gather per step:
# [valid, supported, pops, net_sp, gas_min, gas_max]
# (static gas bounds fit int32: the largest table entry is CREATE's
# 32000)
_META = np.stack(
    [
        _VALID.astype(np.int32),
        _SUPPORTED.astype(np.int32),
        _POPS,
        _NET_SP,
        _GAS_MIN.astype(np.int32),
        _GAS_MAX.astype(np.int32),
    ],
    axis=1,
)


# ---------------------------------------------------------------------------
# kernel specialization: trace-time phase switches
# ---------------------------------------------------------------------------
# The generic step kernel lowers EVERY handler phase into the HLO —
# cond-gated phases still pay their branch evaluation each step and
# their compiled footprint always. A PhaseSet prunes whole phases at
# TRACE time from the static layer's reachable-opcode signature
# (laser/batch/specialize.py builds them), so a contract that never
# hashes, never journals storage, never EXPs gets a kernel without
# those phases at all. Phases are grouped coarsely (one flag covers an
# opcode family) so similar contracts land in the same specialization
# bucket and share one compile.


class PhaseSet(NamedTuple):
    """Hashable trace-time phase switches (a static jit argument).

    All-True == the generic kernel. `fuse_depth` > 1 additionally runs
    that many fused-substep micro-iterations per full step (superblock
    fusion, specialize.py). `block_depth` > 0 runs that many
    block-substep micro-iterations instead — the block-level JIT
    (laser/batch/blockjit.py), whose lowered op set is a superset of
    the fusible one, so it subsumes fusion when on. Both are part of
    the specialization-bucket key: a blockjit kernel and a fuse-only
    kernel over the same phase flags are distinct compiles."""

    calls: bool = True
    extcodesize: bool = True
    returndatacopy: bool = True
    arith: bool = True
    cmp: bool = True
    bits: bool = True
    shifts: bool = True
    div: bool = True
    modops: bool = True
    exp: bool = True
    env_block: bool = True
    env_tx: bool = True
    env_info: bool = True
    calldataload: bool = True
    sha3: bool = True
    mload: bool = True
    mstore: bool = True
    mstore8: bool = True
    copy: bool = True
    sload: bool = True
    sstore: bool = True
    logs: bool = True
    selfdestruct: bool = True
    fuse_depth: int = 0
    block_depth: int = 0

    @property
    def pruned(self):
        """Names of the phases this kernel elides."""
        return tuple(
            name for name in PHASE_FLAGS if not getattr(self, name)
        )


#: the boolean phase fields, in declaration order
PHASE_FLAGS = tuple(
    name
    for name in PhaseSet._fields
    if name not in ("fuse_depth", "block_depth")
)

#: phase flag -> the opcode names that phase (and only that phase)
#: handles. Ops in NO group (STOP/RETURN/REVERT/JUMP/JUMPI/JUMPDEST/
#: POP/PC-relative PUSH/DUP/SWAP, ASSERT_FAIL) are structural and
#: always lowered.
PHASE_OPS = {
    "calls": ["CALL", "CALLCODE", "DELEGATECALL", "STATICCALL"],
    "extcodesize": ["EXTCODESIZE"],
    "returndatacopy": ["RETURNDATACOPY"],
    "arith": ["ADD", "SUB", "MUL"],
    "cmp": ["LT", "GT", "SLT", "SGT", "EQ", "ISZERO"],
    "bits": ["AND", "OR", "XOR", "NOT"],
    "shifts": ["BYTE", "SHL", "SHR", "SAR", "SIGNEXTEND"],
    "div": ["DIV", "SDIV", "MOD", "SMOD"],
    "modops": ["ADDMOD", "MULMOD"],
    "exp": ["EXP"],
    "env_block": [
        "TIMESTAMP", "NUMBER", "COINBASE", "DIFFICULTY", "GASLIMIT",
        "CHAINID", "BASEFEE", "BLOCKHASH",
    ],
    "env_tx": [
        "ADDRESS", "CALLER", "ORIGIN", "CALLVALUE", "GASPRICE",
        "SELFBALANCE", "BALANCE",
    ],
    "env_info": [
        "CALLDATASIZE", "CODESIZE", "RETURNDATASIZE", "MSIZE", "PC", "GAS",
    ],
    "calldataload": ["CALLDATALOAD"],
    "sha3": ["SHA3"],
    "mload": ["MLOAD"],
    "mstore": ["MSTORE"],
    "mstore8": ["MSTORE8"],
    "copy": ["CALLDATACOPY", "CODECOPY"],
    "sload": ["SLOAD"],
    "sstore": ["SSTORE"],
    "logs": ["LOG0", "LOG1", "LOG2", "LOG3", "LOG4"],
    "selfdestruct": ["SUICIDE"],
}

#: the generic (nothing pruned, no fusion) kernel
GENERIC_PHASES = PhaseSet()


def _on(phases: Optional[PhaseSet], name: str) -> bool:
    """Trace-time phase switch: None means the generic kernel."""
    return phases is None or getattr(phases, name)


@functools.lru_cache(maxsize=None)
def _unhandled_table(phases: PhaseSet) -> np.ndarray:
    """bool[256]: opcodes whose handler phase this PhaseSet prunes.

    The specialized kernel's safety net: a lane reaching a pruned
    opcode (a wrong or stale signature — reachable sets are
    over-approximations, so this should never fire) degrades to
    UNSUPPORTED and the host re-executes it, exactly like any other
    off-device opcode. Silent mis-execution is impossible by
    construction."""
    table = np.zeros(256, dtype=bool)
    for flag, names in PHASE_OPS.items():
        if not getattr(phases, flag):
            for opname in names:
                table[_B[opname]] = True
    return table


# Stack-peek implementation: "gather" (take_along_axis) or "einsum"
# (one-hot contraction). The limbs-major probe measured the contraction
# at 2/3 the kernel-segment count of the gather, and the full step
# kernel at +18% throughput on the pre-PR-21 remote v5e link (historical) (439k -> 520k
# transitions/s); segment count is the latency unit on
# dispatch-floor-bound links (docs/roadmap.md). On CPU the one-hot
# multiply is pure overhead, so the default is per-backend; the
# MYTHRIL_TPU_PEEK env var pins either implementation.
_PEEK_CHOICE = os.environ.get("MYTHRIL_TPU_PEEK", "auto")


def _peek_einsum() -> bool:
    if _PEEK_CHOICE != "auto":
        return _PEEK_CHOICE == "einsum"
    import jax

    return jax.default_backend() != "cpu"


def _gate(pred, fn, operands):
    """Whole-batch phase gate: `lax.cond` skips the phase when no lane
    triggers it. (Probed once: inlining every phase unconditionally —
    the handlers are mask-correct either way — crashed the TPU worker
    outright on the pre-PR-21 remote v5e link (historical), so the conditionals stay.)"""
    return lax.cond(pred, fn, lambda x: x, operands)


def _m(mask, x, y):
    """Masked select with trailing-dim broadcast."""
    extra = x.ndim - mask.ndim
    return jnp.where(mask.reshape(mask.shape + (1,) * extra), x, y)


def _word_to_i32(a):
    """u256 word -> (int32 value, overflow mask). Values >= 2**31 overflow."""
    lo = a[..., 0] + (a[..., 1] << 16)
    big = jnp.any(a[..., 2:] != 0, axis=-1) | (lo >= jnp.uint32(1 << 31))
    return lo.astype(jnp.int32), big


def _addr160(word):
    """Truncate a u256 word mod 2**160 (10 of 16 limbs) — the EVM's
    rule for every address-valued operand."""
    return jnp.concatenate(
        [word[:, :10], jnp.zeros_like(word[:, 10:])], axis=1
    )


def _mem_gas(words):
    w = words.astype(jnp.uint32)
    return 3 * w + (w * w) // 512


def step(batch: StateBatch, code: CodeTable,
         track_coverage: bool = True,
         phases: Optional[PhaseSet] = None) -> StateBatch:
    n = batch.pc.shape[0]
    # capacities are carried by the batch's array shapes, so callers
    # size them per workload (make_batch mem_cap=/calldata_cap=/...)
    mem_cap = batch.mem.shape[1]
    stack_cap = batch.stack.shape[1]
    cd_cap = batch.calldata.shape[1]
    lanes = jnp.arange(n)

    # ---- fetch -----------------------------------------------------------
    code_len = code.length[batch.code_id]
    oob = batch.pc >= code_len  # running off the code ends the tx
    pc_safe = jnp.clip(batch.pc, 0, code.ops.shape[1] - 33)
    # one 33-byte window gather serves BOTH the opcode fetch (byte 0)
    # and the PUSH payload (bytes 1..32) — two separate code-table
    # gathers are two kernel segments
    code_win = code.ops[
        batch.code_id[:, None], pc_safe[:, None] + jnp.arange(33)[None, :]
    ]
    op = code_win[:, 0].astype(jnp.int32)

    active = batch.active
    halt_oob = active & oob
    live = active & ~oob

    # one gather against the merged [256, 6] metadata table instead of
    # six separate [256] lookups — each unfused gather is a kernel
    # segment on this platform
    meta = jnp.asarray(_META)[op]
    valid = meta[:, 0] != 0
    supported = meta[:, 1] != 0
    pops = meta[:, 2]
    net_sp = meta[:, 3]
    underflow = batch.sp < pops
    over_cap = batch.sp + net_sp > stack_cap
    if stack_cap >= 1024:
        # the model holds the full EVM stack: the genuine stack-limit
        # exception fires at the EVM's 1024, not at a roomier model
        # cap (reference: StackOverflowException)
        overflow = batch.sp + net_sp > 1024
        cap_degrade = jnp.zeros_like(over_cap)
    else:
        # the model cap is BELOW the EVM's 1024: a lane that outgrows
        # it proves nothing about real EVM behavior — degrade to the
        # host engine (UNSUPPORTED -> takeover) instead of reporting a
        # stack error the contract may never have
        overflow = jnp.zeros_like(over_cap)
        cap_degrade = over_cap

    is_invalid_op = live & (~valid | (op == INVALID_OP))
    is_unsupported = live & valid & ~supported & (op != INVALID_OP)
    is_unsupported = is_unsupported | (
        live & valid & supported & ~underflow & cap_degrade
    )
    if phases is not None and phases.pruned:
        # the specialization safety net: an opcode whose handler phase
        # this kernel pruned degrades to UNSUPPORTED (host takeover),
        # leaving the lane AT the instruction — never silently
        # mis-executed. A sound signature makes this dead code.
        unhandled = jnp.asarray(_unhandled_table(phases))[op]
        is_unsupported = is_unsupported | (
            live & valid & supported & ~underflow & ~cap_degrade
            & unhandled
        )
    stack_err = live & valid & supported & (underflow | overflow)
    ex = (
        live & valid & supported & ~stack_err & ~cap_degrade
        & (op != INVALID_OP)
    )  # executing
    if phases is not None and phases.pruned:
        ex = ex & ~unhandled

    # ---- operands --------------------------------------------------------
    # one gather for every slot any phase peeks (a/b/c + DUP/SWAP
    # depths): unfused gathers dominate step latency on this platform.
    # (The CALL-family memory-window operands gather separately inside
    # the lax.cond'd call branch — widening THIS gather taxes every
    # step, measured at ~5% of the headline throughput.)
    dup_n_pre = (op - 0x80).astype(jnp.int32)
    swap_n_pre = (op - 0x8F).astype(jnp.int32)
    peek_ks = jnp.stack(
        [jnp.zeros_like(op), jnp.ones_like(op), 2 * jnp.ones_like(op),
         dup_n_pre, swap_n_pre], axis=1)  # [n, 5]
    peek_idx = jnp.clip(batch.sp[:, None] - 1 - peek_ks, 0, stack_cap - 1)
    if _peek_einsum():
        # one-hot contraction instead of a gather: a per-lane [5,S]x[S,W]
        # reduction the vector/matrix units take directly, measured to
        # compile to fewer kernel segments (tools/limbs_major_probe.py)
        onehot = (
            peek_idx[:, :, None] == jnp.arange(stack_cap)[None, None, :]
        ).astype(batch.stack.dtype)
        peeked = jnp.einsum("nks,nsw->nkw", onehot, batch.stack)
    else:
        peeked = jnp.take_along_axis(
            batch.stack, peek_idx[:, :, None].astype(jnp.int32), axis=1)
    a, b, c = peeked[:, 0], peeked[:, 1], peeked[:, 2]
    dup_val, swap_deep_val = peeked[:, 3], peeked[:, 4]

    status = batch.status
    status = jnp.where(halt_oob, Status.STOPPED, status)
    status = jnp.where(is_invalid_op, Status.INVALID, status)
    status = jnp.where(is_unsupported, Status.UNSUPPORTED, status)
    status = jnp.where(stack_err, Status.ERR_STACK, status)

    # result accumulation: one stack slot per opcode. Pop-then-push ops
    # write at sp-pops; DUP writes the new top (sp); SWAP writes sp-1.
    res_val = jnp.zeros((n, W), jnp.uint32)
    res_mask = jnp.zeros((n,), bool)
    is_dup = (op >= 0x80) & (op <= 0x8F)
    is_swap = (op >= 0x90) & (op <= 0x9F)
    res_idx = jnp.where(
        is_dup, batch.sp, jnp.where(is_swap, batch.sp - 1, batch.sp - pops))
    res_idx = jnp.clip(res_idx, 0, stack_cap - 1)

    mem = batch.mem
    msize = batch.msize_words
    gas_dyn_min = jnp.zeros((n,), jnp.uint32)
    gas_dyn_max = jnp.zeros((n,), jnp.uint32)
    skeys, svals, scnt = batch.storage_keys, batch.storage_vals, batch.storage_cnt
    ret_offset, ret_len = batch.ret_offset, batch.ret_len

    def put(res_val, res_mask, mask, val):
        return _m(mask, val, res_val), res_mask | mask

    # ---- external calls in a codeless world ------------------------------
    # A call to an account without code is a plain ether transfer plus
    # a success push (reference: instructions.py:1929-1940). In the
    # analyze world only the target contract carries code, so the four
    # CALL ops execute on device: balance check -> transfer -> push.
    # Lanes whose callee might carry code — self-calls, precompiles, or
    # batches built from multi-account fixtures (empty_world=0) —
    # degrade to UNSUPPORTED mid-step; the host resumes at the call.
    # EXTCODESIZE: own address -> code length; any other address in an
    # empty world -> 0 (precompiles carry no code either). Outside the
    # empty world a foreign size is unknowable on device.
    if _on(phases, "extcodesize"):
        extsz = ex & (op == EXTCODESIZE_OP)
        extsz_self = u256.eq(_addr160(a), batch.address)
        extsz_ok = extsz & ((batch.empty_world != 0) | extsz_self)
        status = jnp.where(extsz & ~extsz_ok, Status.UNSUPPORTED, status)
        extsz_word = jnp.zeros((n, W), jnp.uint32)
        extsz_word = extsz_word.at[:, 0].set(
            jnp.where(extsz_self, code_len, 0).astype(jnp.uint32)
        )
        res_val, res_mask = put(res_val, res_mask, extsz_ok, extsz_word)

    # RETURNDATACOPY: device lanes always have an empty return buffer
    # (calls that would fill one hand off to the host), so the
    # (dest, 0, 0) form Solidity emits is a no-op; any other operands
    # are an out-of-bounds read the host adjudicates exactly.
    if _on(phases, "returndatacopy"):
        rdc = ex & (op == RETURNDATACOPY_OP)
        rdc_ok = rdc & u256.is_zero(b) & u256.is_zero(c)
        status = jnp.where(rdc & ~rdc_ok, Status.UNSUPPORTED, status)

    is_call_fam = (
        (op == CALL_OP) | (op == CALLCODE_OP)
        | (op == DELEGATECALL_OP) | (op == STATICCALL_OP)
    )
    call_any = ex & is_call_fam
    balance = batch.balance

    def do_calls(args):
        res_val, res_mask, status, balance, msize, g_min, g_max = args
        callee = _addr160(b)
        callee_precompile = (
            jnp.all(callee[:, 1:] == 0, axis=-1)
            & (callee[:, 0] >= 1)
            & (callee[:, 0] <= 9)
        )
        # window operands sit at depth 3..6 for CALL/CALLCODE (after
        # gas,to,value) and 2..5 for DELEGATECALL/STATICCALL
        win0 = jnp.where(
            (op == CALL_OP) | (op == CALLCODE_OP), 3, 2
        ).astype(jnp.int32)
        win_ks = win0[:, None] + jnp.arange(4)[None, :]
        win_idx = jnp.clip(
            batch.sp[:, None] - 1 - win_ks, 0, stack_cap - 1
        )
        windows = jnp.take_along_axis(
            batch.stack, win_idx[:, :, None].astype(jnp.int32), axis=1
        )
        # in/out memory windows: the call expands memory over both even
        # with a codeless callee. Degenerate windows (non-i32 offsets,
        # >1MB expansion — where quadratic gas would overflow) go to host.
        in_off_i, in_off_big = _word_to_i32(windows[:, 0])
        in_len_i, in_len_big = _word_to_i32(windows[:, 1])
        ret_off_i, ret_off_big = _word_to_i32(windows[:, 2])
        ret_len_i, ret_len_big = _word_to_i32(windows[:, 3])

        def _win_words(off_i, len_i):
            return jnp.where(len_i > 0, (off_i + len_i + 31) // 32, 0)

        want_words = jnp.maximum(
            _win_words(in_off_i, in_len_i), _win_words(ret_off_i, ret_len_i)
        )
        win_bad = (
            in_len_big
            | ret_len_big
            | ((in_len_i > 0) & in_off_big)
            | ((ret_len_i > 0) & ret_off_big)
            | (want_words > (1 << 15))
        )
        runnable = (
            (batch.empty_world != 0)
            & ~u256.eq(callee, batch.address)
            & ~callee_precompile
            & ~win_bad
        )
        degrade = call_any & ~runnable
        status = jnp.where(degrade, Status.UNSUPPORTED, status)
        call_exec = call_any & runnable
        # the transferred value: third stack word for CALL/CALLCODE only
        carries_value = (op == CALL_OP) | (op == CALLCODE_OP)
        call_value = _m(call_exec & carries_value, c, jnp.zeros_like(c))
        can_pay = ~u256.ult(balance, call_value)
        res_val, res_mask = put(
            res_val, res_mask, call_exec, u256.bool_to_word(can_pay)
        )
        # only an outgoing CALL moves ether (CALLCODE pays itself)
        outgoing = call_exec & (op == CALL_OP) & can_pay
        balance = _m(outgoing, u256.sub(balance, call_value), balance)
        # memory growth + its exact quadratic gas (words capped above,
        # so the uint32 arithmetic cannot overflow)
        new_msize = jnp.maximum(msize, want_words.astype(jnp.int32))
        mem_gas = jnp.where(
            call_exec, _mem_gas(new_msize) - _mem_gas(msize), 0
        ).astype(jnp.uint32)
        return (
            res_val,
            res_mask,
            status,
            balance,
            jnp.where(call_exec, new_msize, msize),
            g_min + mem_gas,
            g_max + mem_gas,
        )

    if _on(phases, "calls"):
        (res_val, res_mask, status, balance, msize, gas_dyn_min,
         gas_dyn_max) = (
            _gate(
                jnp.any(call_any),
                do_calls,
                (res_val, res_mask, status, balance, msize, gas_dyn_min,
                 gas_dyn_max),
            )
        )

    # ---- cheap binary arithmetic / compares / bitwise --------------------
    # entries are included per phase group: pruning a group the
    # contract never reaches drops its compute AND its share of the
    # per-step mask-merge from the lowered HLO
    cheap_bin = {}
    if _on(phases, "arith"):
        cheap_bin.update({
            ADD: u256.add(a, b),
            SUB: u256.sub(a, b),
            MUL: u256.mul(a, b),
        })
    if _on(phases, "bits"):
        cheap_bin.update({AND: a & b, OR: a | b, XOR: a ^ b})
    if _on(phases, "cmp"):
        cheap_bin.update({
            LT: u256.bool_to_word(u256.ult(a, b)),
            GT: u256.bool_to_word(u256.ult(b, a)),
            SLT: u256.bool_to_word(u256.slt(a, b)),
            SGT: u256.bool_to_word(u256.slt(b, a)),
            EQ: u256.bool_to_word(u256.eq(a, b)),
        })
    if _on(phases, "shifts"):
        cheap_bin.update({
            BYTE: u256.byte_op(a, b),
            SHL: u256.shl(b, u256.shift_amount(a)),
            SHR: u256.lshr(b, u256.shift_amount(a)),
            SAR: u256.ashr(b, u256.shift_amount(a)),
            SIGNEXTEND: u256.signextend(a, b),
        })
    for byte_, val in cheap_bin.items():
        res_val, res_mask = put(res_val, res_mask, ex & (op == byte_), val)

    # unary
    if _on(phases, "cmp"):
        res_val, res_mask = put(
            res_val, res_mask, ex & (op == ISZERO),
            u256.bool_to_word(u256.is_zero(a)))
    if _on(phases, "bits"):
        res_val, res_mask = put(
            res_val, res_mask, ex & (op == NOT), u256.bit_not(a))

    # ---- expensive arithmetic (gated) ------------------------------------
    if _on(phases, "div"):
        div_mask = ex & (
            (op == DIV) | (op == SDIV) | (op == MOD) | (op == SMOD)
        )

        def do_div(args):
            res_val, res_mask = args
            q, r = u256.udivmod(a, b)
            qs = u256.sdiv(a, b)
            rs = u256.srem(a, b)
            val = _m(op == DIV, q, _m(op == SDIV, qs, _m(op == MOD, r, rs)))
            return put(res_val, res_mask, div_mask, val)

        res_val, res_mask = _gate(
            jnp.any(div_mask), do_div, (res_val, res_mask))

    if _on(phases, "modops"):
        modmask = ex & ((op == ADDMOD) | (op == MULMOD))

        def do_modops(args):
            res_val, res_mask = args
            am = u256.addmod(a, b, c)
            mm = u256.mulmod(a, b, c)
            return put(res_val, res_mask, modmask, _m(op == ADDMOD, am, mm))

        res_val, res_mask = _gate(
            jnp.any(modmask), do_modops, (res_val, res_mask))

    if _on(phases, "exp"):
        exp_mask = ex & (op == EXP)

        def do_exp(args):
            res_val, res_mask, g_min, g_max = args
            res_val, res_mask = put(
                res_val, res_mask, exp_mask, u256.exp(a, b))
            # dynamic gas: priced per byte of exponent (b)
            high_limb = jnp.max(
                jnp.where(
                    b != 0, jnp.arange(1, W + 1, dtype=jnp.int32)[None, :], 0
                ),
                axis=-1)  # 1-based index of highest nonzero limb, 0 if b == 0
            top_limb = jnp.take_along_axis(
                b, jnp.clip(high_limb - 1, 0, W - 1)[:, None], axis=-1)[:, 0]
            exp_bytes = jnp.where(
                high_limb > 0, 2 * high_limb - (top_limb < 256), 0
            ).astype(jnp.uint32)
            exp_bytes = jnp.where(exp_mask, exp_bytes, 0)
            # 10/byte is the Frontier/Homestead price (the true minimum
            # across forks); 50/byte (EIP-160) bounds the maximum
            return (res_val, res_mask, g_min + 10 * exp_bytes,
                    g_max + 50 * exp_bytes)

        res_val, res_mask, gas_dyn_min, gas_dyn_max = _gate(
            jnp.any(exp_mask), do_exp,
            (res_val, res_mask, gas_dyn_min, gas_dyn_max))

    # ---- environment / block pushes --------------------------------------
    zero_w = jnp.zeros((n, W), jnp.uint32)
    budget = batch.gas_budget
    # GAS pushes the gas remaining AFTER its own charge (2): exact when
    # the accumulated minimum is exact, which the concolic lane keeps
    # for the static+memory costs preceding a GAS read (the gas0/gas1
    # VMTests pin this value through an SSTORE). gas_left also feeds
    # the memory-expansion OOG check, so it is computed unconditionally.
    gas_left = budget - jnp.minimum(batch.gas_min + 2, budget)

    env_pushes = {}
    if _on(phases, "env_tx"):
        env_pushes.update({
            ADDRESS: batch.address,
            CALLER: batch.caller,
            ORIGIN: batch.origin,
            CALLVALUE: batch.callvalue,
            GASPRICE: batch.gasprice,
            SELFBALANCE: batch.balance,
        })
    if _on(phases, "env_block"):
        env_pushes.update({
            TIMESTAMP: batch.timestamp,
            NUMBER: batch.number,
            COINBASE: batch.coinbase,
            DIFFICULTY: batch.difficulty,
            GASLIMIT: batch.gaslimit,
            CHAINID: batch.chainid,
            BASEFEE: batch.basefee,
        })
    if _on(phases, "env_info"):
        gas_word = jnp.zeros((n, W), jnp.uint32)
        gas_word = gas_word.at[:, 0].set(gas_left & 0xFFFF)
        gas_word = gas_word.at[:, 1].set(gas_left >> 16)
        msize_word = jnp.zeros((n, W), jnp.uint32)
        msize_bytes = (msize * 32).astype(jnp.uint32)
        msize_word = msize_word.at[:, 0].set(msize_bytes & 0xFFFF)
        msize_word = msize_word.at[:, 1].set(msize_bytes >> 16)
        pc_word = jnp.zeros((n, W), jnp.uint32)
        pc_word = pc_word.at[:, 0].set(batch.pc.astype(jnp.uint32) & 0xFFFF)
        pc_word = pc_word.at[:, 1].set(batch.pc.astype(jnp.uint32) >> 16)
        cds_word = jnp.zeros((n, W), jnp.uint32)
        cds_word = cds_word.at[:, 0].set(
            batch.calldatasize.astype(jnp.uint32))
        csize_word = jnp.zeros((n, W), jnp.uint32)
        csize_word = csize_word.at[:, 0].set(code_len.astype(jnp.uint32))
        env_pushes.update({
            CALLDATASIZE: cds_word,
            CODESIZE: csize_word,
            RETURNDATASIZE: zero_w,
            MSIZE: msize_word,
            PC: pc_word,
            GAS: gas_word,
        })
    for byte_, val in env_pushes.items():
        res_val, res_mask = put(res_val, res_mask, ex & (op == byte_), val)

    if _on(phases, "env_tx"):
        # BALANCE: own account -> balance, anything else -> 0 (no world
        # state on device; the symbolic engine handles foreign accounts)
        bal_mask = ex & (op == BALANCE)
        res_val, res_mask = put(
            res_val, res_mask, bal_mask,
            _m(u256.eq(a, batch.address), batch.balance, zero_w))
    if _on(phases, "env_block"):
        # BLOCKHASH: zero (reference returns a symbol; concolic tests
        # skip it)
        res_val, res_mask = put(
            res_val, res_mask, ex & (op == BLOCKHASH), zero_w)

    # top-of-stack as an i32 offset: CALLDATALOAD's operand, and the
    # memory/hash/log/halt phases' window base — computed once for all
    off_i, off_big = _word_to_i32(a)

    # ---- CALLDATALOAD ----------------------------------------------------
    if _on(phases, "calldataload"):
        cdl_mask = ex & (op == CALLDATALOAD)
        cd_idx = jnp.clip(off_i[:, None], 0, cd_cap) + jnp.arange(32)[None, :]
        cd_in = (cd_idx < batch.calldatasize[:, None]) & (cd_idx < cd_cap)
        if _peek_einsum():
            # same contraction trick as the stack peek: the 32-byte
            # window read becomes a one-hot [n,32,C]x[n,C] reduction
            cd_onehot = (
                jnp.clip(cd_idx, 0, cd_cap - 1)[:, :, None]
                == jnp.arange(cd_cap)[None, None, :]
            ).astype(batch.calldata.dtype)
            cd_bytes = jnp.einsum("nkc,nc->nk", cd_onehot, batch.calldata)
        else:
            cd_bytes = jnp.take_along_axis(
                batch.calldata, jnp.clip(cd_idx, 0, cd_cap - 1), axis=1)
        cd_bytes = jnp.where(cd_in, cd_bytes, 0).astype(jnp.uint32)
        cd_word = u256.bytes_to_word(cd_bytes)
        res_val, res_mask = put(
            res_val, res_mask, cdl_mask, _m(off_big, zero_w, cd_word))

    # ---- PUSHn -----------------------------------------------------------
    push_mask = ex & (op >= 0x60) & (op <= 0x7F)
    push_n = (op - 0x5F).astype(jnp.int32)
    pbytes = code_win[:, 1:].astype(jnp.uint32)  # rides the fetch window
    pword = u256.bytes_to_word(pbytes)
    shift = (8 * (32 - push_n)).astype(jnp.uint32)
    pword = u256.lshr(pword, shift)
    res_val, res_mask = put(res_val, res_mask, push_mask, pword)

    # ---- DUP / SWAP ------------------------------------------------------
    dup_mask = ex & (op >= 0x80) & (op <= 0x8F)
    dup_n = dup_n_pre
    res_val, res_mask = put(res_val, res_mask, dup_mask, dup_val)

    swap_mask = ex & (op >= 0x90) & (op <= 0x9F)
    swap_n = swap_n_pre
    # top goes to the deep slot via the fused second write below; deep
    # value goes to the top through the consolidated result write
    res_val, res_mask = put(res_val, res_mask, swap_mask, swap_deep_val)

    BIGOFF = jnp.int32(1 << 29)  # stands in for any offset/len >= 2**31

    def expand(mask, off_i32, nbytes, msize, gmin, gmax, status,
               over_status=Status.ERR_MEM):
        """Memory expansion accounting + capacity check.

        Zero-length accesses never expand memory (EVM semantics), so
        huge offsets with len 0 are fine. Accesses past mem_cap whose
        true expansion gas provably exceeds the lane's remaining budget
        halt with ERR_OOG — the genuine EVM outcome — instead of the
        model-capacity status; the gas is estimated in float32 (w up to
        2**25 words keeps the estimate within ~1 part in 2**23, and the
        fixtures in this regime have order-of-magnitude margins)."""
        # clamp before adding: offsets just below 2**31 would wrap the
        # int32 sum and dodge the capacity check entirely
        off_c = jnp.minimum(off_i32, BIGOFF)
        nb = jnp.minimum(
            jnp.broadcast_to(jnp.asarray(nbytes, jnp.int32), mask.shape), BIGOFF
        )
        end = off_c + nb
        nz = mask & (nb > 0)
        over = nz & (end > mem_cap)
        wf = ((end + 31) // 32).astype(jnp.float32)
        # EVM charges the delta above the already-paid size, not the
        # absolute cost of the new size
        est = (3.0 * wf + wf * wf / 512.0) - _mem_gas(msize).astype(jnp.float32)
        budget_left = gas_left.astype(jnp.float32)
        oog = over & (est > budget_left)
        bad = over & ~oog
        grow_mask = nz & ~over
        new_words = jnp.where(grow_mask, (end + 31) // 32, 0)
        grow = jnp.maximum(new_words, msize)
        delta = (_mem_gas(grow) - _mem_gas(msize)).astype(jnp.uint32)
        gmin = gmin + jnp.where(grow_mask, delta, 0)
        gmax = gmax + jnp.where(grow_mask, delta, 0)
        msize = jnp.where(grow_mask, grow, msize)
        status = jnp.where(oog, Status.ERR_OOG, status)
        status = jnp.where(bad, over_status, status)
        return msize, gmin, gmax, status, mask & ~over

    # ---- SHA3 (gated) ----------------------------------------------------
    sha_mask = ex & (op == SHA3) if _on(phases, "sha3") else None
    if sha_mask is not None:
        len_i, len_big = _word_to_i32(b)
        sha_off = jnp.where(off_big, BIGOFF, off_i)
        sha_len = jnp.where(len_big, BIGOFF, len_i)
        # charge memory expansion over the hashed range first (reference:
        # sha3_ extends via mem_extend before hashing) — unaffordable huge
        # ranges OOG; affordable-but-over-cap goes back to the host engine
        msize, gas_dyn_min, gas_dyn_max, status, sha_exp_ok = expand(
            sha_mask, sha_off, sha_len, msize, gas_dyn_min, gas_dyn_max,
            status, over_status=Status.UNSUPPORTED)
        sha_toobig = sha_exp_ok & (sha_len > HASH_CAP)
        sha_ok = sha_exp_ok & ~sha_toobig

    def do_sha3(args):
        res_val, res_mask = args
        from mythril_tpu.laser.batch.state import SHA_MAX_BLOCKS, SHA_RATE

        # per-lane padded length in rate blocks (>=1); lanes absorb
        # their own number of blocks and the digest is captured when
        # each lane's last block has been permuted
        n_blocks = (len_i + 1 + SHA_RATE - 1) // SHA_RATE
        last_pad = n_blocks * SHA_RATE - 1  # absolute 0x80 position

        def absorb(blk, lo, hi):
            pos = blk * SHA_RATE + jnp.arange(SHA_RATE)[None, :]
            block_idx = jnp.clip(off_i, 0, mem_cap)[:, None] + pos
            inb = (pos < len_i[:, None]) & (block_idx < mem_cap)
            raw = jnp.take_along_axis(
                mem, jnp.clip(block_idx, 0, mem_cap - 1), axis=1)
            raw = jnp.where(inb, raw, 0).astype(jnp.uint32)
            # multi-rate padding: 0x01 at len, 0x80 at the final byte
            raw = raw | jnp.where(pos == len_i[:, None], 0x01, 0)
            raw = raw | jnp.where(pos == last_pad[:, None], 0x80, 0)
            lanes8 = raw.reshape(n, 17, 8)
            blo = (lanes8[..., 0] | (lanes8[..., 1] << 8)
                   | (lanes8[..., 2] << 16) | (lanes8[..., 3] << 24))
            bhi = (lanes8[..., 4] | (lanes8[..., 5] << 8)
                   | (lanes8[..., 6] << 16) | (lanes8[..., 7] << 24))
            active_blk = (blk < n_blocks)[:, None]
            lo = jnp.where(
                active_blk, lo.at[:, :17].set(lo[:, :17] ^ blo), lo)
            hi = jnp.where(
                active_blk, hi.at[:, :17].set(hi[:, :17] ^ bhi), hi)
            plo, phi = keccak_f(lo, hi)
            return (jnp.where(active_blk, plo, lo),
                    jnp.where(active_blk, phi, hi))

        # block 0 always runs; later blocks are whole-batch gated so
        # the dominant single-block case (mapping slots) pays for one
        # permutation, and the final state is captured per lane
        lo = jnp.zeros((n, 25), jnp.uint32)
        hi = jnp.zeros((n, 25), jnp.uint32)
        lo, hi = absorb(0, lo, hi)
        flo, fhi = lo, hi
        for blk in range(1, SHA_MAX_BLOCKS):
            lo, hi = _gate(
                jnp.any(sha_ok & (n_blocks > blk)),
                lambda args, blk=blk: absorb(blk, *args),
                (lo, hi),
            )
            done_now = (n_blocks == blk + 1)[:, None]
            flo = jnp.where(done_now, lo, flo)
            fhi = jnp.where(done_now, hi, fhi)

        by = []
        for lane_i in range(4):
            for half, arr in ((0, flo), (1, fhi)):
                for j in range(4):
                    by.append((arr[:, lane_i] >> (8 * j)) & 0xFF)
        digest = jnp.stack(by, axis=-1)  # [n, 32] bytes, LE lanes
        word = u256.bytes_to_word(digest)
        return put(res_val, res_mask, sha_ok, word)

    if sha_mask is not None:
        res_val, res_mask = _gate(
            jnp.any(sha_mask), do_sha3, (res_val, res_mask))
        # affordable inputs beyond the device hash cap go to the host
        status = jnp.where(sha_toobig, Status.UNSUPPORTED, status)
        sha_words = jnp.where(
            sha_ok, (len_i + 31) // 32, 0).astype(jnp.uint32)
        gas_dyn_min = gas_dyn_min + 6 * sha_words
        gas_dyn_max = gas_dyn_max + 6 * sha_words

    # ---- memory ----------------------------------------------------------
    if _on(phases, "mload"):
        mload_mask = ex & (op == MLOAD)
        msize, gas_dyn_min, gas_dyn_max, status, mload_ok = expand(
            mload_mask, jnp.where(off_big, BIGOFF, off_i), 32,
            msize, gas_dyn_min, gas_dyn_max, status)

        def do_mload(args):
            res_val, res_mask = args
            idx = (
                jnp.clip(off_i, 0, mem_cap - 32)[:, None]
                + jnp.arange(32)[None, :]
            )
            byts = jnp.take_along_axis(mem, idx, axis=1).astype(jnp.uint32)
            return put(res_val, res_mask, mload_ok, u256.bytes_to_word(byts))

        res_val, res_mask = _gate(
            jnp.any(mload_ok), do_mload, (res_val, res_mask))

    if _on(phases, "mstore"):
        mstore_mask = ex & (op == MSTORE)
        msize, gas_dyn_min, gas_dyn_max, status, mstore_ok = expand(
            mstore_mask, jnp.where(off_big, BIGOFF, off_i), 32,
            msize, gas_dyn_min, gas_dyn_max, status)

        def do_mstore(mem):
            j = jnp.arange(mem_cap)[None, :]
            rel = j - off_i[:, None]
            inw = (rel >= 0) & (rel < 32) & mstore_ok[:, None]
            wbytes = u256.word_to_bytes(b)  # [n, 32]
            src = jnp.take_along_axis(
                wbytes, jnp.clip(rel, 0, 31).astype(jnp.int32), axis=1)
            return jnp.where(inw, src, mem)

        mem = _gate(jnp.any(mstore_ok), do_mstore, mem)

    if _on(phases, "mstore8"):
        m8_mask = ex & (op == MSTORE8)
        msize, gas_dyn_min, gas_dyn_max, status, m8_ok = expand(
            m8_mask, jnp.where(off_big, BIGOFF, off_i), 1,
            msize, gas_dyn_min, gas_dyn_max, status)

        def do_mstore8(mem):
            j = jnp.arange(mem_cap)[None, :]
            hit = (j == off_i[:, None]) & m8_ok[:, None]
            return jnp.where(
                hit, (b[:, 0] & 0xFF).astype(jnp.uint8)[:, None], mem)

        mem = _gate(jnp.any(m8_ok), do_mstore8, mem)

    # ---- CALLDATACOPY / CODECOPY (gated) ---------------------------------
    if _on(phases, "copy"):
        copy_mask = ex & ((op == CALLDATACOPY) | (op == CODECOPY))
        dst_i, dst_big = _word_to_i32(a)
        src_i, src_big = _word_to_i32(b)
        cplen_i, cplen_big = _word_to_i32(c)
        # a huge source offset is legal: reads past the data are zeros
        src_i = jnp.where(src_big, BIGOFF, src_i)
        msize, gas_dyn_min, gas_dyn_max, status, copy_ok = expand(
            copy_mask,
            jnp.where(dst_big, BIGOFF, dst_i),
            jnp.where(cplen_big, BIGOFF, cplen_i),
            msize, gas_dyn_min, gas_dyn_max, status)
        copy_words = jnp.where(
            copy_ok, (cplen_i + 31) // 32, 0).astype(jnp.uint32)
        gas_dyn_min = gas_dyn_min + 3 * copy_words
        gas_dyn_max = gas_dyn_max + 3 * copy_words

        def do_copy(mem):
            j = jnp.arange(mem_cap)[None, :]
            rel = j - dst_i[:, None]
            inw = (rel >= 0) & (rel < cplen_i[:, None]) & copy_ok[:, None]
            sidx = src_i[:, None] + rel
            # calldata source
            cd_ok = (
                (sidx >= 0) & (sidx < batch.calldatasize[:, None])
                & (sidx < cd_cap)
            )
            from_cd = jnp.take_along_axis(
                batch.calldata, jnp.clip(sidx, 0, cd_cap - 1), axis=1)
            from_cd = jnp.where(cd_ok, from_cd, 0)
            # code source
            co_ok = (sidx >= 0) & (sidx < code_len[:, None])
            from_co = code.ops[
                batch.code_id[:, None],
                jnp.clip(sidx, 0, code.ops.shape[1] - 1)]
            from_co = jnp.where(co_ok, from_co, 0)
            src = jnp.where((op == CALLDATACOPY)[:, None], from_cd, from_co)
            return jnp.where(inw, src, mem)

        mem = _gate(jnp.any(copy_ok), do_copy, mem)

    # ---- storage (gated) -------------------------------------------------
    if _on(phases, "sload"):
        sload_mask = ex & (op == SLOAD)

        def do_sload(args):
            res_val, res_mask = args
            s_cap = skeys.shape[1]
            hit = jnp.all(skeys == a[:, None, :], axis=-1)  # [n, S]
            hit = hit & (jnp.arange(s_cap)[None, :] < scnt[:, None])
            any_hit = jnp.any(hit, axis=-1)
            last = jnp.argmax(
                jnp.where(hit, jnp.arange(s_cap)[None, :] + 1, 0), axis=-1)
            if _peek_einsum():
                # one-hot contraction instead of a gather (same trick
                # as the stack peek)
                oh = (
                    jnp.arange(s_cap)[None, :] == last[:, None]
                ).astype(svals.dtype)
                val = jnp.einsum("ns,nsw->nw", oh, svals)
            else:
                val = jnp.take_along_axis(
                    svals, last[:, None, None], axis=1)[:, 0, :]
            val = _m(any_hit, val, jnp.zeros_like(val))
            return put(res_val, res_mask, sload_mask, val)

        res_val, res_mask = _gate(
            jnp.any(sload_mask), do_sload, (res_val, res_mask))

    if _on(phases, "sstore"):
        sstore_mask = ex & (op == SSTORE)

        def do_sstore(args):
            skeys, svals, scnt, status = args
            s_cap = skeys.shape[1]
            hit = jnp.all(skeys == a[:, None, :], axis=-1)
            hit = hit & (jnp.arange(s_cap)[None, :] < scnt[:, None])
            any_hit = jnp.any(hit, axis=-1)
            last = jnp.argmax(
                jnp.where(hit, jnp.arange(s_cap)[None, :] + 1, 0), axis=-1)
            slot = jnp.where(any_hit, last, scnt)
            full = sstore_mask & ~any_hit & (scnt >= s_cap)
            write = sstore_mask & ~full
            oh = (jnp.arange(s_cap)[None, :] == slot[:, None]) & write[:, None]
            skeys = jnp.where(oh[:, :, None], a[:, None, :], skeys)
            svals = jnp.where(oh[:, :, None], b[:, None, :], svals)
            scnt = jnp.where(write & ~any_hit, scnt + 1, scnt)
            status = jnp.where(full, Status.ERR_MEM, status)
            return skeys, svals, scnt, status

        skeys, svals, scnt, status = _gate(
            jnp.any(sstore_mask), do_sstore, (skeys, svals, scnt, status))

    # ---- LOGn: pure pops (topics + data range) ---------------------------
    if _on(phases, "logs"):
        log_mask = ex & (op >= 0xA0) & (op <= 0xA4)
        log_len_i, log_len_big = _word_to_i32(b)
        msize, gas_dyn_min, gas_dyn_max, status, log_ok = expand(
            log_mask,
            jnp.where(off_big, BIGOFF, off_i),
            jnp.where(log_len_big, BIGOFF, log_len_i),
            msize, gas_dyn_min, gas_dyn_max, status)
        gas_dyn_min = gas_dyn_min + jnp.where(
            log_ok, 8 * log_len_i.astype(jnp.uint32), 0)
        gas_dyn_max = gas_dyn_max + jnp.where(
            log_ok, 8 * log_len_i.astype(jnp.uint32), 0)

    # ---- halts -----------------------------------------------------------
    stop_mask = ex & (op == STOP)
    status = jnp.where(stop_mask, Status.STOPPED, status)
    if _on(phases, "selfdestruct"):
        kill_mask = ex & (op == SELFDESTRUCT)
        status = jnp.where(kill_mask, Status.KILLED, status)

    retrev_mask = ex & ((op == RETURN) | (op == REVERT))
    rr_len_i, rr_len_big = _word_to_i32(b)
    msize, gas_dyn_min, gas_dyn_max, status, rr_ok = expand(
        retrev_mask,
        jnp.where(off_big, BIGOFF, off_i),
        jnp.where(rr_len_big, BIGOFF, rr_len_i),
        msize, gas_dyn_min, gas_dyn_max, status)
    ret_offset = jnp.where(rr_ok, off_i, ret_offset)
    ret_len = jnp.where(rr_ok, rr_len_i, ret_len)
    status = jnp.where(
        rr_ok, jnp.where(op == RETURN, Status.RETURNED, Status.REVERTED), status)

    # ---- jumps + pc ------------------------------------------------------
    jump_mask = ex & (op == JUMP)
    jumpi_mask = ex & (op == JUMPI)
    dest_i, dest_big = _word_to_i32(a)
    taken = jumpi_mask & ~u256.is_zero(b)
    do_jump = jump_mask | taken
    dest_ok = (
        ~dest_big
        & (dest_i < code_len)
        & (dest_i < code.jumpdest.shape[1])
        & code.jumpdest[batch.code_id, jnp.clip(dest_i, 0, code.jumpdest.shape[1] - 1)]
    )
    status = jnp.where(do_jump & ~dest_ok, Status.ERR_JUMP, status)

    push_len = jnp.where(push_mask, push_n, 0)
    pc_next = batch.pc + 1 + push_len
    pc_new = jnp.where(do_jump & dest_ok, dest_i, pc_next)
    still_running = status == Status.RUNNING
    pc_new = jnp.where(ex & still_running, pc_new, batch.pc)

    # ---- consolidated stack/sp write ------------------------------------
    # an op that degraded mid-step (capacity -> UNSUPPORTED/ERR_MEM)
    # must leave the lane exactly AT the instruction: no sp delta, no
    # static gas — the host engine re-executes it on takeover
    interrupted = ex & (
        (status == Status.UNSUPPORTED) | (status == Status.ERR_MEM)
    )
    effective = ex & ~interrupted
    # one fused pass over the stack: result slot + SWAP's deep slot
    slot_ids = jnp.arange(stack_cap)[None, :]
    oh_res = (slot_ids == res_idx[:, None]) & (res_mask & effective)[:, None]
    swap_idx = jnp.clip(batch.sp - 1 - swap_n, 0, stack_cap - 1)
    oh_swap = (slot_ids == swap_idx[:, None]) & (swap_mask & ~interrupted)[:, None]
    stack = jnp.where(
        oh_res[:, :, None], res_val[:, None, :],
        jnp.where(oh_swap[:, :, None], a[:, None, :], batch.stack))
    sp = jnp.where(effective, batch.sp + net_sp, batch.sp)

    # ---- gas (static bounds ride the merged metadata gather) -------------
    gas_min = (batch.gas_min
               + jnp.where(effective, meta[:, 4].astype(jnp.uint32), 0)
               + gas_dyn_min)
    gas_max = (batch.gas_max
               + jnp.where(effective, meta[:, 5].astype(jnp.uint32), 0)
               + gas_dyn_max)
    # out-of-gas: even the minimum-cost path exceeded this lane's budget
    # (reference: OutOfGasException via check_gas, machine_state.py:83-264)
    oog = active & (gas_min > batch.gas_budget) & (status != Status.UNSUPPORTED)
    status = jnp.where(oog, Status.ERR_OOG, status)

    # concolic branch journal: record each JUMPI decision in order
    # (saturates at BRANCH_CAP; the hybrid fuzzer reads it host-side)
    br_cap = batch.br_pc.shape[1]
    br_slot = jnp.clip(batch.br_cnt, 0, br_cap - 1)
    record = jumpi_mask & (batch.br_cnt < br_cap)
    slot_hit = (
        jnp.arange(br_cap)[None, :] == br_slot[:, None]
    ) & record[:, None]
    br_pc = jnp.where(slot_hit, batch.pc[:, None], batch.br_pc)
    br_taken = jnp.where(
        slot_hit, taken.astype(jnp.uint8)[:, None], batch.br_taken
    )
    br_cnt = batch.br_cnt + record.astype(jnp.int32)

    # coverage bitmap: mark this step's pc for every executing lane
    # (the fuzz/explore loops read it; conformance and the throughput
    # path turn it off — it is a whole extra pass per step)
    if track_coverage:
        word_idx = jnp.clip(batch.pc // 32, 0, batch.pc_seen.shape[1] - 1)
        bit = (jnp.uint32(1) << (batch.pc % 32).astype(jnp.uint32))
        seen_words = jnp.take_along_axis(
            batch.pc_seen, word_idx[:, None], axis=1)[:, 0]
        seen_words = jnp.where(ex, seen_words | bit, seen_words)
        pc_seen = jnp.where(
            jnp.arange(batch.pc_seen.shape[1])[None, :] == word_idx[:, None],
            seen_words[:, None],
            batch.pc_seen,
        )
    else:
        pc_seen = batch.pc_seen

    return batch._replace(
        pc=pc_new,
        pc_seen=pc_seen,
        br_pc=br_pc,
        br_taken=br_taken,
        br_cnt=br_cnt,
        stack=stack,
        sp=sp,
        balance=balance,
        mem=mem,
        msize_words=msize,
        storage_keys=skeys,
        storage_vals=svals,
        storage_cnt=scnt,
        status=status,
        gas_min=gas_min,
        gas_max=gas_max,
        ret_offset=ret_offset,
        ret_len=ret_len,
    )
