"""StateBatch: the EVM machine state as a structure of arrays.

The reference keeps one Python object graph per path state
(reference: mythril/laser/ethereum/state/global_state.py,
machine_state.py, memory.py, account.py) and copies it on every
instruction (the #1 CPU cost per SURVEY §3.2). Here a *batch* of N
machine states is one pytree of fixed-shape arrays; "copying" a state
is free (functional updates), and forking a path is a lane copy.

Shapes (N = lanes):
  pc            i32[N]
  stack         u32[N, STACK_CAP, 16]   (256-bit words as 16x16-bit limbs)
  sp            i32[N]                  (next free slot)
  mem           u8[N, MEM_CAP]
  msize_words   i32[N]                  (EVM memory size in 32-byte words)
  storage_*     bounded key/value journal per lane
  status        i32[N]                  (Status enum)
  gas_min/max   u32[N]                  (accumulated bounds, reference:
                                         machine_state.py min_gas_used)
plus per-lane environment words (caller, callvalue, calldata, block ctx).
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

import jax.numpy as jnp

from mythril_tpu.ops import u256

STACK_CAP = 128  # configurable; EVM max is 1024, real contracts stay shallow
MEM_CAP = 4096  # bytes of modelled memory per lane
STORAGE_CAP = 64  # journal entries per lane
CALLDATA_CAP = 512  # bytes of calldata per lane
SHA_RATE = 136  # keccak-256 rate in bytes
SHA_MAX_BLOCKS = 8  # absorption blocks unrolled in the step kernel
HASH_CAP = SHA_MAX_BLOCKS * SHA_RATE - 1  # 1087 B of SHA3 input on device
PC_BITMAP_WORDS = 768  # coverage bitmap words (EVM max code size 24576 / 32)
BRANCH_CAP = 64  # recorded JUMPI decisions per lane (concolic journal)


class Status:
    RUNNING = 0
    STOPPED = 1
    RETURNED = 2
    REVERTED = 3
    INVALID = 4  # ASSERT_FAIL / designated invalid opcode
    ERR_STACK = 5  # under/overflow
    ERR_JUMP = 6  # invalid jump destination
    ERR_MEM = 7  # memory model capacity exceeded
    UNSUPPORTED = 8  # opcode outside the device set -> host takes over
    ERR_OOG = 9  # minimum gas bound exceeded the lane's gas budget
    KILLED = 10  # SELFDESTRUCT executed (a successful halt that the
    #              explorer banks as SWC-106 evidence)

    HALTED = (STOPPED, RETURNED, REVERTED, INVALID, ERR_STACK, ERR_JUMP,
              ERR_MEM, UNSUPPORTED, ERR_OOG, KILLED)


class CodeTable(NamedTuple):
    """Shared contract store: lanes reference rows by code_id."""

    ops: jnp.ndarray  # u8[C, CODE_CAP + 33] (zero-padded for PUSH reads)
    jumpdest: jnp.ndarray  # bool[C, CODE_CAP]
    length: jnp.ndarray  # i32[C]


class StateBatch(NamedTuple):
    code_id: jnp.ndarray
    pc: jnp.ndarray
    stack: jnp.ndarray
    sp: jnp.ndarray
    mem: jnp.ndarray
    msize_words: jnp.ndarray
    storage_keys: jnp.ndarray
    storage_vals: jnp.ndarray
    storage_cnt: jnp.ndarray
    status: jnp.ndarray
    gas_min: jnp.ndarray
    gas_max: jnp.ndarray
    gas_budget: jnp.ndarray  # u32[N]; lane OOGs when gas_min exceeds it
    ret_offset: jnp.ndarray
    ret_len: jnp.ndarray
    pc_seen: jnp.ndarray  # u32[N, PC_BITMAP_WORDS] executed-pc bitmap (coverage)
    br_pc: jnp.ndarray  # i32[N, BRANCH_CAP] JUMPI pcs in execution order
    br_taken: jnp.ndarray  # u8[N, BRANCH_CAP] 1 = branch taken
    br_cnt: jnp.ndarray  # i32[N] journal length (saturates at BRANCH_CAP)
    # environment (reference: laser/ethereum/state/environment.py)
    address: jnp.ndarray  # u32[N,16]
    caller: jnp.ndarray
    origin: jnp.ndarray
    callvalue: jnp.ndarray
    gasprice: jnp.ndarray
    balance: jnp.ndarray  # active account balance
    calldata: jnp.ndarray  # u8[N, CALLDATA_CAP]
    calldatasize: jnp.ndarray  # i32[N]
    # block context
    timestamp: jnp.ndarray
    number: jnp.ndarray
    coinbase: jnp.ndarray
    difficulty: jnp.ndarray
    gaslimit: jnp.ndarray
    chainid: jnp.ndarray
    basefee: jnp.ndarray
    # world model: 1 = no foreign account carries code, so CALL-family
    # ops to non-self, non-precompile addresses execute on device as
    # plain transfers (the analyze world); 0 = calls hand off to host
    empty_world: jnp.ndarray  # u8[N]

    @property
    def n_lanes(self) -> int:
        return self.pc.shape[0]

    @property
    def active(self):
        return self.status == Status.RUNNING


def make_code_table(codes, code_cap: int = None) -> CodeTable:
    """Build a CodeTable from a list of bytecode byte strings."""
    from mythril_tpu.disassembler.asm import to_dense

    code_cap = code_cap or max((len(c) for c in codes), default=1)
    ops = np.zeros((len(codes), code_cap + 33), dtype=np.uint8)
    jd = np.zeros((len(codes), code_cap), dtype=bool)
    length = np.zeros((len(codes),), dtype=np.int32)
    for i, code in enumerate(codes):
        o, j = to_dense(code, max_len=code_cap)
        ops[i, :code_cap] = o
        jd[i] = j
        length[i] = min(len(code), code_cap)
    return CodeTable(jnp.asarray(ops), jnp.asarray(jd), jnp.asarray(length))


def _word_rows(n, value: int = 0):
    return np.broadcast_to(np.asarray(u256.from_int(value)), (n, u256.LIMBS))


def make_batch(
    n: int,
    code_ids=None,
    calldata=None,
    callvalue=0,
    caller: int = 0xDEADBEEFDEADBEEF,
    address: int = 0xAFFEAFFE,
    balance: int = 10**18,
    timestamp: int = 1_600_000_000,
    number: int = 10_000_000,
    chainid: int = 1,
    gasprice: int = 10,
    gas_budget: int = 8_000_000,
    mem_cap: int = MEM_CAP,
    calldata_cap: int = CALLDATA_CAP,
    storage_cap: int = STORAGE_CAP,
    stack_cap: int = STACK_CAP,
    storage_seed=None,
    empty_world=True,
    as_numpy=False,
) -> StateBatch:
    """Fresh batch at pc=0 with empty stacks and zeroed memory.

    Capacities are per-batch: the step kernel reads them off the array
    shapes, so mainnet-shaped workloads pass e.g. mem_cap=24576 while
    the default stays lean for throughput runs.

    `storage_seed` pre-loads per-lane storage journals — one
    {slot: value} dict (or None) per lane — the mechanism a
    multi-transaction exploration uses to carry tx N's writes into
    tx N+1's start state. `callvalue` accepts a scalar or one int per
    lane (the explorer's msg.value axis).

    `as_numpy` skips the device upload and returns a StateBatch of
    host numpy arrays — the background wave-checkpoint writer builds
    its npz frontier this way without ever touching the device."""
    code_ids = (
        np.zeros((n,), np.int32)
        if code_ids is None
        else np.asarray(code_ids, np.int32)
    )
    cd = np.zeros((n, calldata_cap), dtype=np.uint8)
    cds = np.zeros((n,), dtype=np.int32)
    if calldata is not None:
        for i, data in enumerate(calldata):
            m = min(len(data), calldata_cap)
            cd[i, :m] = np.frombuffer(bytes(data[:m]), dtype=np.uint8)
            cds[i] = len(data)
    skeys = np.zeros((n, storage_cap, u256.LIMBS), dtype=np.uint32)
    svals = np.zeros((n, storage_cap, u256.LIMBS), dtype=np.uint32)
    scnt = np.zeros((n,), dtype=np.int32)
    if storage_seed is not None:
        for i, journal in enumerate(storage_seed):
            for j, (slot, value) in enumerate(
                list((journal or {}).items())[:storage_cap]
            ):
                skeys[i, j] = u256.from_int(slot)
                svals[i, j] = u256.from_int(value)
                scnt[i] = j + 1
    batch = StateBatch(
        code_id=code_ids,
        pc=np.zeros((n,), np.int32),
        stack=np.zeros((n, stack_cap, u256.LIMBS), np.uint32),
        sp=np.zeros((n,), np.int32),
        mem=np.zeros((n, mem_cap), np.uint8),
        msize_words=np.zeros((n,), np.int32),
        storage_keys=skeys,
        storage_vals=svals,
        storage_cnt=scnt,
        status=np.zeros((n,), np.int32),
        gas_min=np.zeros((n,), np.uint32),
        gas_max=np.zeros((n,), np.uint32),
        gas_budget=np.full((n,), gas_budget, np.uint32),
        ret_offset=np.zeros((n,), np.int32),
        ret_len=np.zeros((n,), np.int32),
        pc_seen=np.zeros((n, PC_BITMAP_WORDS), np.uint32),
        br_pc=np.full((n, BRANCH_CAP), -1, np.int32),
        br_taken=np.zeros((n, BRANCH_CAP), np.uint8),
        br_cnt=np.zeros((n,), np.int32),
        address=_word_rows(n, address),
        caller=_word_rows(n, caller),
        origin=_word_rows(n, caller),
        callvalue=(
            _word_rows(n, callvalue)
            if np.isscalar(callvalue)
            else np.stack([u256.from_int(int(v)) for v in callvalue])
        ),
        balance=(
            _word_rows(n, balance)
            if np.isscalar(balance)
            else np.stack([u256.from_int(int(v)) for v in balance])
        ),
        gasprice=_word_rows(n, gasprice),
        calldata=cd,
        calldatasize=cds,
        timestamp=_word_rows(n, timestamp),
        number=_word_rows(n, number),
        coinbase=_word_rows(n, 0),
        difficulty=_word_rows(n, 0x0BAD),
        gaslimit=_word_rows(n, 8_000_000),
        chainid=_word_rows(n, chainid),
        basefee=_word_rows(n, 7),
        empty_world=(
            np.full((n,), int(bool(empty_world)), np.uint8)
            if np.isscalar(empty_world) or isinstance(empty_world, bool)
            else np.asarray(empty_world, np.uint8)
        ),
    )
    if as_numpy:
        return batch
    # one upload per field; broadcast views are materialized by jax
    return StateBatch(*(jnp.asarray(a) for a in batch))


def storage_dict_from(tables, lane: int) -> dict:
    """One lane's storage journal (latest write wins) out of a bulk
    (keys, vals, cnt) host read. Bulk callers must fetch the three
    journal arrays in ONE transfer (e.g. jax.device_get) — indexing a
    jax array per lane issues a separate device gather + transfer each
    time (~0.4s/lane over a remote-chip link used before PR 21,
    historical; measured to dominate striped wave cost)."""
    keys, vals, cnt = tables
    out = {}
    for i in range(int(cnt[lane])):
        out[u256.to_int(keys[lane, i])] = u256.to_int(vals[lane, i])
    return {k: v for k, v in out.items() if v != 0}


def storage_dict(batch: StateBatch, lane: int) -> dict:
    """Host-side view of one lane's storage journal (single-lane
    convenience; bulk callers use storage_dict_from)."""
    tables = (
        np.asarray(batch.storage_keys[lane])[None],
        np.asarray(batch.storage_vals[lane])[None],
        np.asarray([batch.storage_cnt[lane]]),
    )
    return storage_dict_from(tables, 0)


def stack_list(batch: StateBatch, lane: int) -> list:
    """Host-side view of one lane's stack (bottom to top)."""
    sp = int(batch.sp[lane])
    return [u256.to_int(np.asarray(batch.stack[lane, i])) for i in range(sp)]


def mem_bytes(batch: StateBatch, lane: int, offset: int, length: int) -> bytes:
    return bytes(np.asarray(batch.mem[lane, offset : offset + length]).tolist())
