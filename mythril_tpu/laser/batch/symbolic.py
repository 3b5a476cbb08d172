"""Device-side symbolic lanes: taint tracking + the expression arena.

This is the round's centerpiece (SURVEY §7.1 step 4): symbolic values
live ON DEVICE as node ids into an append-only expression arena. Every
lane's stack slot, memory byte, storage journal entry and JUMPI
decision carries a term id alongside its concrete value; ops whose
operands are symbolic append one arena node per lane per step (dynamic
compaction via cumsum ranks). The host never re-executes a path to
learn its constraints — it decodes the arena (see arena.py), which IS
the symbolic execution transcript.

Term-id convention:
    0   concrete (the value is just the value)
    > 0 arena row + 1 (a well-formed symbolic expression)
    < 0 opaque: symbolic but outside the device expression language
        (keccak preimages, tainted addresses, arena overflow) — sound
        to execute concretely, not available for branch flipping.
        Opaque ids carry PROVENANCE bits so detection evidence
        survives opacity: -(1 + bits) with bit 1 = derived from
        tx.origin (SWC-115 source) and bit 2 = derived from a
        predictable block attribute (TIMESTAMP/NUMBER/COINBASE/
        DIFFICULTY/GASLIMIT/BLOCKHASH — SWC-116 sources). -1 is the
        generic opaque; a JUMPI whose journal tid is -2/-4 decided on
        tx.origin, -3/-4 on a predictable var.

Evidence banks (round 5 — the device owns detection, the host
verifies): beside the arena, every lane banks the concrete EVENTS the
detection layer needs, so issues can be synthesized from device
evidence instead of host solver walks (analysis/evidence.py):

- wrap events: ADD/SUB/MUL whose concrete execution wrapped (with both
  operand values banked for exact host-side confirmation and the
  result's term id for DAG usage tracking) — SWC-101 witnesses;
- call events: CALL-family sites with target/value term ids + concrete
  values and the branch-journal depth at call time — SWC-104/105/107/
  112 witnesses;
- the RETURN window, so "wrapped value escapes via RETURN" usage
  checks can read the final memory taints.

`sym_step` wraps the concrete `step` kernel: values advance exactly as
in the concrete engine (the concolic semantics pinned by VMTests), and
the taint pass runs beside it on the same decoded instruction.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from mythril_tpu.laser.batch.state import (
    CALLDATA_CAP,
    MEM_CAP,
    STACK_CAP,
    STORAGE_CAP,
    CodeTable,
    StateBatch,
    Status,
    make_batch,
)
from mythril_tpu.laser.batch.step import PhaseSet, _on, _word_to_i32, step
from mythril_tpu.ops import u256
from mythril_tpu.support.opcodes import OPCODES

W = u256.LIMBS
OPAQUE = np.int32(-1)  # numpy: importing must not initialize a backend

#: arena rows per batch (shared by all lanes of a wave)
ARENA_CAP = 32768

#: banked detection events per lane (one wrap/call/arith site each; a
#: report needs one witness per faulting pc, and surplus lanes cover
#: the overflow)
EVENT_CAP = 12

#: event kinds (ev_kind values)
EV_WRAP_ADD = 1
EV_WRAP_SUB = 2
EV_WRAP_MUL = 3
EV_CALL = 4
EV_CALLCODE = 5
EV_DELEGATECALL = 6
EV_STATICCALL = 7
EV_SSTORE_AFTER_CALL = 8
EV_SLOAD_AFTER_CALL = 9
#: tainted arithmetic that did NOT wrap on this lane — a steering
#: target: the explorer solves path + wrap-condition and seeds a lane
#: that wraps concretely (explore.py `_steer_wrap_conditions`)
EV_SITE_ADD = 10
EV_SITE_SUB = 11
EV_SITE_MUL = 12
#: SLOAD of a never-written slot (concrete key in ev_a): the observed
#: key feeds the poisoned-storage carry — the concolic equivalent of
#: the host engine's symbolic initial storage (explore.py)
EV_SLOAD_MISS = 13
#: arithmetic over OPAQUE operands that did not wrap: unverifiable by
#: steering (no decodable terms), so an unresolved site of this kind
#: blocks device-completeness — the host walk keeps those contracts
EV_SITE_OPAQUE = 15

_B = {name: entry[0] for name, entry in OPCODES.items()}

#: ops compiled to arena nodes when an operand is symbolic, with arity 2
NODE_BINOPS = [
    "ADD", "SUB", "MUL", "DIV", "SDIV", "MOD", "SMOD", "EXP", "SIGNEXTEND",
    "LT", "GT", "SLT", "SGT", "EQ", "AND", "OR", "XOR", "BYTE", "SHL",
    "SHR", "SAR",
]
#: unary node ops
NODE_UNOPS = ["ISZERO", "NOT"]
#: ternary ops degrade to opaque when tainted
TERNARY_OPS = ["ADDMOD", "MULMOD"]
#: empty-world calls: the concrete push is exact, but a tainted
#: gas/callee/value makes the outcome path-dependent -> opaque
CALL_OPS = ["CALL", "CALLCODE", "DELEGATECALL", "STATICCALL"]

_IS_BIN = np.zeros(256, bool)
for _n in NODE_BINOPS:
    _IS_BIN[_B[_n]] = True
_IS_UN = np.zeros(256, bool)
for _n in NODE_UNOPS:
    _IS_UN[_B[_n]] = True
_IS_TER = np.zeros(256, bool)
for _n in TERNARY_OPS:
    _IS_TER[_B[_n]] = True
_IS_CALL = np.zeros(256, bool)
for _n in CALL_OPS:
    _IS_CALL[_B[_n]] = True

_POPS = np.zeros(256, np.int32)
_PUSHES = np.zeros(256, np.int32)
_VALID = np.zeros(256, bool)
for _name, (_byte, _pops, _pushes, _gmin, _gmax) in OPCODES.items():
    _POPS[_byte] = _pops
    _PUSHES[_byte] = _pushes
    _VALID[_byte] = True

# merged per-opcode shadow metadata, one gather per step (each unfused
# gather is a kernel segment — see step.py _META):
# [pops, pushes, valid, is_bin, is_un, is_ter, is_call,
#  call_kind, is_env_leaf, call_has_value]

CALLDATALOAD = _B["CALLDATALOAD"]
CALLDATACOPY = _B["CALLDATACOPY"]
CODECOPY = _B["CODECOPY"]
SHA3 = _B["SHA3"]
MLOAD, MSTORE, MSTORE8 = _B["MLOAD"], _B["MSTORE"], _B["MSTORE8"]
SLOAD, SSTORE = _B["SLOAD"], _B["SSTORE"]
JUMPI = _B["JUMPI"]
CALL_B, SELFBALANCE_B = _B["CALL"], _B["SELFBALANCE"]
EXTCODESIZE_B = _B["EXTCODESIZE"]
ADD_B, SUB_B, MUL_B = _B["ADD"], _B["SUB"], _B["MUL"]
RETURN_B = _B["RETURN"]
ORIGIN_B = _B["ORIGIN"]
BLOCKHASH_B = _B["BLOCKHASH"]
#: push-only environment sources that become ARENA LEAF NODES: the
#: leaf decodes to the wave's pinned concrete value (REPLAY_ENV), so
#: env-guarded branches stay flippable with REPLAYABLE witnesses
#: (the solver sees cd == <pinned value>), while detection provenance
#: (SWC-115 origin / SWC-116 predictable vars) reads the leaf ops out
#: of the DAG closure. BLOCKHASH (pops 1) stays provenance-opaque.
ENV_LEAF_OPS = [
    "ORIGIN", "TIMESTAMP", "NUMBER", "COINBASE", "DIFFICULTY", "GASLIMIT",
]
_IS_ENV_LEAF = np.zeros(256, bool)
for _n in ENV_LEAF_OPS:
    _IS_ENV_LEAF[_B[_n]] = True
#: CALL-family byte -> event kind (0 = not a call)
_CALL_KIND = np.zeros(256, np.int32)
_CALL_KIND[_B["CALL"]] = EV_CALL
_CALL_KIND[_B["CALLCODE"]] = EV_CALLCODE
_CALL_KIND[_B["DELEGATECALL"]] = EV_DELEGATECALL
_CALL_KIND[_B["STATICCALL"]] = EV_STATICCALL
#: calls that carry a value operand (stack slot 3)
_CALL_HAS_VALUE = np.zeros(256, bool)
_CALL_HAS_VALUE[_B["CALL"]] = True
_CALL_HAS_VALUE[_B["CALLCODE"]] = True

_SYM_META = np.stack(
    [
        _POPS,
        _PUSHES,
        _VALID.astype(np.int32),
        _IS_BIN.astype(np.int32),
        _IS_UN.astype(np.int32),
        _IS_TER.astype(np.int32),
        _IS_CALL.astype(np.int32),
        _CALL_KIND,
        _IS_ENV_LEAF.astype(np.int32),
        _CALL_HAS_VALUE.astype(np.int32),
    ],
    axis=1,
)


class SymBatch(NamedTuple):
    """A StateBatch plus the symbolic shadow state."""

    base: StateBatch
    stack_tid: jnp.ndarray  # i32[N, STACK_CAP]
    mem_tid: jnp.ndarray  # i32[N, MEM_CAP]
    skey_tid: jnp.ndarray  # i32[N, STORAGE_CAP]
    sval_tid: jnp.ndarray  # i32[N, STORAGE_CAP]
    br_tid: jnp.ndarray  # i32[N, BRANCH_CAP] condition term per decision
    balance_tid: jnp.ndarray  # i32[N]; 0 or OPAQUE (tainted transfers)
    # per-lane detection-evidence banks (see module docstring)
    ev_pc: jnp.ndarray  # i32[N, EVENT_CAP]
    ev_kind: jnp.ndarray  # i32[N, EVENT_CAP] EV_* kind
    ev_tid: jnp.ndarray  # i32[N, EVENT_CAP] wrap result / call target tid
    ev_vtid: jnp.ndarray  # i32[N, EVENT_CAP] call value tid (wraps: 0)
    ev_a: jnp.ndarray  # u32[N, EVENT_CAP, W] operand a / call target value
    ev_b: jnp.ndarray  # u32[N, EVENT_CAP, W] operand b / call value
    ev_aux: jnp.ndarray  # i32[N, EVENT_CAP] br_cnt at a call site
    ev_gas: jnp.ndarray  # u32[N, EVENT_CAP] call gas operand, saturated
    ev_cnt: jnp.ndarray  # i32[N]
    ev_overflow: jnp.ndarray  # i32[N] a distinct event was DROPPED
    call_seen: jnp.ndarray  # i32[N] lane executed a gas-forwarding call
    ret_off: jnp.ndarray  # i32[N] RETURN window offset (-1: none)
    ret_len: jnp.ndarray  # i32[N]
    # the shared expression arena
    ar_op: jnp.ndarray  # i32[ARENA_CAP]
    ar_a: jnp.ndarray  # i32[ARENA_CAP] operand-a term id (0 = concrete)
    ar_b: jnp.ndarray  # i32[ARENA_CAP]
    ar_va: jnp.ndarray  # u32[ARENA_CAP, W] operand-a concrete value
    ar_vb: jnp.ndarray  # u32[ARENA_CAP, W]
    ar_count: jnp.ndarray  # i32 scalar


def make_sym_batch(base: StateBatch) -> SymBatch:
    n = base.pc.shape[0]
    return SymBatch(
        base=base,
        stack_tid=jnp.zeros((n, base.stack.shape[1]), jnp.int32),
        mem_tid=jnp.zeros((n, base.mem.shape[1]), jnp.int32),
        skey_tid=jnp.zeros((n, base.storage_keys.shape[1]), jnp.int32),
        sval_tid=jnp.zeros((n, base.storage_keys.shape[1]), jnp.int32),
        br_tid=jnp.zeros((n, base.br_pc.shape[1]), jnp.int32),
        balance_tid=jnp.zeros((n,), jnp.int32),
        ev_pc=jnp.zeros((n, EVENT_CAP), jnp.int32),
        ev_kind=jnp.zeros((n, EVENT_CAP), jnp.int32),
        ev_tid=jnp.zeros((n, EVENT_CAP), jnp.int32),
        ev_vtid=jnp.zeros((n, EVENT_CAP), jnp.int32),
        ev_a=jnp.zeros((n, EVENT_CAP, W), jnp.uint32),
        ev_b=jnp.zeros((n, EVENT_CAP, W), jnp.uint32),
        ev_aux=jnp.zeros((n, EVENT_CAP), jnp.int32),
        ev_gas=jnp.zeros((n, EVENT_CAP), jnp.uint32),
        ev_cnt=jnp.zeros((n,), jnp.int32),
        ev_overflow=jnp.zeros((n,), jnp.int32),
        call_seen=jnp.zeros((n,), jnp.int32),
        ret_off=jnp.full((n,), -1, jnp.int32),
        ret_len=jnp.full((n,), -1, jnp.int32),
        ar_op=jnp.zeros((ARENA_CAP,), jnp.int32),
        ar_a=jnp.zeros((ARENA_CAP,), jnp.int32),
        ar_b=jnp.zeros((ARENA_CAP,), jnp.int32),
        ar_va=jnp.zeros((ARENA_CAP, W), jnp.uint32),
        ar_vb=jnp.zeros((ARENA_CAP, W), jnp.uint32),
        ar_count=jnp.int32(0),
    )


def _scatter2(tids, idx, val, mask):
    hit = (jnp.arange(tids.shape[1])[None, :] == idx[:, None]) & mask[:, None]
    return jnp.where(hit, val[:, None], tids)


@functools.lru_cache(maxsize=None)
def _env_leaf_table(names) -> np.ndarray:
    """bool[256] of the env-leaf ops a specialized kernel keeps."""
    table = np.zeros(256, dtype=bool)
    for name in names:
        table[_B[name]] = True
    return table


def _kept_env_leaves(phases):
    """ENV_LEAF_OPS restricted to the phases this kernel lowers
    (ORIGIN rides env_tx, the block attributes ride env_block)."""
    return tuple(
        name
        for name in ENV_LEAF_OPS
        if _on(phases, "env_tx" if name == "ORIGIN" else "env_block")
    )


def sym_step(symb: SymBatch, code: CodeTable, phases=None) -> SymBatch:
    """One instruction on every lane, with the symbolic shadow pass.

    `phases` (step.PhaseSet, a static jit argument) prunes handler
    phases from BOTH the concrete kernel and this shadow pass at trace
    time — the specialization layer (specialize.py) derives it from
    the static summary's reachable-opcode signature. None = generic."""
    pre = symb.base
    n = pre.pc.shape[0]
    mem_cap = pre.mem.shape[1]
    stack_cap = pre.stack.shape[1]

    # --- decode this step's instruction (mirrors step's fetch) --------
    code_len = code.length[pre.code_id]
    oob = pre.pc >= code_len
    pc_safe = jnp.clip(pre.pc, 0, code.ops.shape[1] - 33)
    op = code.ops[pre.code_id, pc_safe].astype(jnp.int32)
    meta = jnp.asarray(_SYM_META)[op]
    pops = meta[:, 0]
    pushes = meta[:, 1]
    net_sp = pushes - pops
    live = pre.active & ~oob
    ex = (
        live
        & (meta[:, 2] != 0)
        & (pre.sp >= pops)
        & (pre.sp + net_sp <= stack_cap)
    )

    # one consolidated peek each for the value stack (3 slots) and the
    # shadow stack (those plus the DUP/SWAP depths) — separate per-slot
    # gathers are separate kernel segments
    dup_n = (op - 0x80).astype(jnp.int32)
    swap_n = (op - 0x8F).astype(jnp.int32)
    peek_ks = jnp.stack(
        [jnp.zeros_like(op), jnp.ones_like(op), 2 * jnp.ones_like(op),
         dup_n, swap_n], axis=1)  # [n, 5]
    peek_idx = jnp.clip(
        pre.sp[:, None] - 1 - peek_ks, 0, stack_cap - 1
    ).astype(jnp.int32)
    vals = jnp.take_along_axis(
        pre.stack, peek_idx[:, :3, None], axis=1)
    a_val, b_val, c_val = vals[:, 0], vals[:, 1], vals[:, 2]
    tids = jnp.take_along_axis(symb.stack_tid, peek_idx, axis=1)
    a_tid, b_tid, c_tid = tids[:, 0], tids[:, 1], tids[:, 2]
    dup_tid, swap_deep_tid = tids[:, 3], tids[:, 4]

    # --- run the concrete kernel --------------------------------------
    post = step(pre, code, phases=phases)
    # A lane the kernel demoted mid-step (capacity / conditional
    # support -> UNSUPPORTED/ERR_MEM) executed nothing: the host will
    # re-run the instruction from the untouched concrete state, so
    # neither the shadow nor the evidence banks may record it.
    executed = (post.status != Status.UNSUPPORTED) & (
        post.status != Status.ERR_MEM
    )

    # --- classify the symbolic effect ---------------------------------
    is_bin = meta[:, 3] != 0
    is_un = meta[:, 4] != 0
    is_ter = meta[:, 5] != 0
    is_cdl = op == CALLDATALOAD

    bin_sym = ex & is_bin & ((a_tid != 0) | (b_tid != 0))
    un_sym = ex & is_un & (a_tid != 0)
    cdl_clean = ex & is_cdl & (a_tid == 0)

    # opaque results: operand already opaque, ternary taint, tainted
    # calldata offsets, tainted keccak windows
    bin_ok = (a_tid >= 0) & (b_tid >= 0)
    un_ok = a_tid >= 0
    # taint-involved binops ALWAYS get a row, opaque operand or not:
    # the row is undecodable as a term (flip/steer decode returns
    # None), but it preserves the dataflow DAG — provenance scans
    # (origin/predictable sources) and usage tracking keep working
    # through mixed opaque/symbolic expressions
    mk_node = bin_sym | (un_sym & un_ok) | cdl_clean
    # environment leaves (see ENV_LEAF_OPS): a row whose decode is the
    # pinned concrete value; operands forced to 0 below. A specialized
    # kernel keeps only the leaves whose env phase it lowers.
    kept_leaves = _kept_env_leaves(phases)
    if len(kept_leaves) == len(ENV_LEAF_OPS):
        mk_env = ex & (meta[:, 8] != 0)
    elif kept_leaves:
        mk_env = ex & jnp.asarray(_env_leaf_table(kept_leaves))[op]
    else:
        mk_env = jnp.zeros_like(ex)
    env_val = jnp.zeros_like(a_val)
    for _env_name in kept_leaves:
        env_val = jnp.where(
            (op == _B[_env_name])[:, None],
            getattr(pre, _env_name.lower()),
            env_val,
        )
    tainted_top3 = (a_tid != 0) | (b_tid != 0) | (c_tid != 0)
    is_callf = meta[:, 6] != 0
    # a call's success push depends on its operands AND on the balance,
    # which an earlier tainted transfer may have made path-dependent.
    # Phase-pruned terms drop out of the merge at trace time (their
    # ops degrade to UNSUPPORTED in the concrete kernel and never
    # execute).
    opaque_terms = [un_sym & ~un_ok]
    if _on(phases, "modops"):
        # (binops over opaque operands now make rows — see mk_node)
        opaque_terms.append(ex & is_ter & tainted_top3)
    if _on(phases, "calldataload"):
        opaque_terms.append(ex & is_cdl & (a_tid != 0))
    if _on(phases, "calls"):
        opaque_terms.append(
            ex & is_callf & (tainted_top3 | (symb.balance_tid != 0))
        )
    if _on(phases, "extcodesize"):
        opaque_terms.append(ex & (op == EXTCODESIZE_B) & (a_tid != 0))
    mk_opaque = functools.reduce(jnp.logical_or, opaque_terms)
    # (RETURNDATACOPY's zero-length gate needs no shadow case: a
    # tainted length's OTHER branch is an exceptional halt — a dead
    # end that yields no witnesses — so not deriving inputs for it
    # costs completeness nothing the trigger bank would keep.)
    # an outgoing CALL of a tainted value taints the balance itself
    if _on(phases, "calls"):
        balance_tid = jnp.where(
            ex & (op == CALL_B) & ((c_tid != 0) | (symb.balance_tid != 0)),
            OPAQUE,
            symb.balance_tid,
        )
    else:
        balance_tid = symb.balance_tid

    # --- memory taints -------------------------------------------------
    # A tainted (symbolic) offset makes the access location itself
    # path-dependent; the concolic shadow then degrades to opaque —
    # the concrete window is what the kernel actually touched, so
    # poisoning it keeps later reads honest.
    off_i, off_big = _word_to_i32(a_val)
    off_sym = a_tid != 0
    mem_tid = symb.mem_tid
    j = jnp.arange(mem_cap)[None, :]
    rel = j - off_i[:, None]

    # MLOAD: uniform 32-byte window of one tid propagates; mixed or
    # symbolically-addressed reads are opaque
    mload_prop = None
    if _on(phases, "mload"):
        mload_m = ex & (op == MLOAD) & ~off_big
        widx = (
            jnp.clip(off_i, 0, mem_cap - 32)[:, None]
            + jnp.arange(32)[None, :]
        )
        wtids = jnp.take_along_axis(mem_tid, widx, axis=1)
        w_first = wtids[:, 0]
        w_uniform = jnp.all(wtids == w_first[:, None], axis=1)
        w_any = jnp.any(wtids != 0, axis=1)
        mload_prop = mload_m & w_uniform & ~off_sym
        mload_opq = mload_m & ((~w_uniform & w_any) | (off_sym & w_any))
        mk_opaque = mk_opaque | mload_opq | (ex & (op == MLOAD) & off_big)

    # MSTORE writes the value tid over its window (opaque when the
    # destination is symbolic); MSTORE8 degrades per byte
    if _on(phases, "mstore"):
        mstore_m = ex & (op == MSTORE) & ~off_big
        inw32 = (rel >= 0) & (rel < 32) & mstore_m[:, None]
        st_tid = jnp.where(off_sym & (b_tid != 0), OPAQUE, b_tid)
        mem_tid = jnp.where(inw32, st_tid[:, None], mem_tid)
    if _on(phases, "mstore8"):
        m8_m = ex & (op == MSTORE8) & ~off_big
        m8_tid = jnp.where(b_tid != 0, OPAQUE, 0)
        mem_tid = jnp.where(
            (rel == 0) & m8_m[:, None], m8_tid[:, None], mem_tid)

    # CALLDATACOPY makes the window opaque bytes (byte-granular
    # calldata expressions stay host-side); CODECOPY writes concrete
    # code bytes, which must also CLEAR stale taint over the window
    if _on(phases, "copy"):
        cplen_i, _ = _word_to_i32(c_val)
        ccopy_m = ex & (op == CALLDATACOPY) & ~off_big
        inc = (rel >= 0) & (rel < cplen_i[:, None]) & ccopy_m[:, None]
        mem_tid = jnp.where(inc, OPAQUE, mem_tid)
        codecopy_m = ex & (op == CODECOPY) & ~off_big
        incc = (rel >= 0) & (rel < cplen_i[:, None]) & codecopy_m[:, None]
        mem_tid = jnp.where(incc, 0, mem_tid)

    # SHA3 of a tainted window (or tainted bounds) -> opaque digest
    if _on(phases, "sha3"):
        sha_m = ex & (op == SHA3) & ~off_big
        len_i, _ = _word_to_i32(b_val)
        insh = (rel >= 0) & (rel < len_i[:, None])
        sha_tainted = sha_m & (
            jnp.any(jnp.where(insh, mem_tid != 0, False), axis=1)
            | off_sym
            | (b_tid != 0)
        )
        mk_opaque = mk_opaque | sha_tainted

    # --- storage taints ------------------------------------------------
    skey_tid, sval_tid = symb.skey_tid, symb.sval_tid
    sload_m = ex & (op == SLOAD)
    sstore_m = ex & (op == SSTORE)
    any_hit = None
    if _on(phases, "sload") or _on(phases, "sstore"):
        s_cap = pre.storage_keys.shape[1]
        hit = jnp.all(pre.storage_keys == a_val[:, None, :], axis=-1)
        hit = hit & (jnp.arange(s_cap)[None, :] < pre.storage_cnt[:, None])
        any_hit = jnp.any(hit, axis=-1)
        last = jnp.argmax(
            jnp.where(hit, jnp.arange(s_cap)[None, :] + 1, 0), axis=-1)
        stored_tid = jnp.take_along_axis(sval_tid, last[:, None], axis=1)[:, 0]
        # a MISS reads initial storage, which the host models as
        # symbolic: the concrete 0 is just this lane's SAMPLE of it, so
        # the result is opaque — arithmetic over it must bank (wrap or
        # opaque-site) events instead of posing as a path constant
        sload_tid = jnp.where(any_hit, stored_tid, OPAQUE)
        sload_tid = jnp.where(a_tid != 0, OPAQUE, sload_tid)
    if _on(phases, "sstore"):
        # SSTORE: mirror the slot choice and record the value/key tids
        slot = jnp.where(
            any_hit, last, jnp.clip(pre.storage_cnt, 0, s_cap - 1))
        sval_tid = _scatter2(sval_tid, slot, b_tid, sstore_m)
        skey_tid = _scatter2(skey_tid, slot, a_tid, sstore_m)

    # --- arena append --------------------------------------------------
    mk_row = mk_node | mk_env
    ranks = jnp.cumsum(mk_row.astype(jnp.int32)) - mk_row.astype(jnp.int32)
    rows = symb.ar_count + ranks
    ok = mk_row & (rows < ARENA_CAP)
    dump = jnp.where(ok, rows, ARENA_CAP + 1)  # OOB rows are dropped

    ar_op = symb.ar_op.at[dump].set(op, mode="drop")
    ar_a = symb.ar_a.at[dump].set(jnp.where(mk_env, 0, a_tid), mode="drop")
    ar_b = symb.ar_b.at[dump].set(jnp.where(mk_env, 0, b_tid), mode="drop")
    ar_va = symb.ar_va.at[dump].set(
        jnp.where(mk_env[:, None], env_val, a_val), mode="drop"
    )
    ar_vb = symb.ar_vb.at[dump].set(
        jnp.where(mk_env[:, None], jnp.zeros_like(b_val), b_val), mode="drop"
    )
    ar_count = jnp.minimum(
        symb.ar_count + jnp.sum(mk_row.astype(jnp.int32)), ARENA_CAP
    )

    node_tid = (rows + 1).astype(jnp.int32)
    overflowed = mk_row & ~ok

    # --- result tid ----------------------------------------------------
    res_tid = jnp.zeros((n,), jnp.int32)
    res_tid = jnp.where(mk_row, node_tid, res_tid)
    res_tid = jnp.where(mk_opaque | overflowed, OPAQUE, res_tid)
    # binop results are nodes even over opaque operands (see mk_node);
    # unary results of opaque operands PRESERVE the operand's
    # provenance bits (-(1 + bits), term-id convention) so BLOCKHASH-
    # derived dependence survives ISZERO/NOT chains
    neg_bits_a = jnp.where(a_tid < 0, jnp.clip(-a_tid - 1, 0, 3), 0)
    res_tid = jnp.where(un_sym & ~un_ok, -(1 + neg_bits_a), res_tid)
    if _on(phases, "env_block"):
        # BLOCKHASH: predictable-var provenance without a leaf (its
        # result value is block-state we do not model as a constant)
        res_tid = jnp.where(
            ex & (op == BLOCKHASH_B), jnp.int32(-3), res_tid)
    if mload_prop is not None:
        res_tid = jnp.where(mload_prop, w_first, res_tid)
    if _on(phases, "sload"):
        res_tid = jnp.where(sload_m, sload_tid, res_tid)
    if _on(phases, "env_tx") and _on(phases, "calls"):
        # SELFBALANCE reads the (possibly tainted) balance; with calls
        # pruned the balance can never become tainted at all
        res_tid = jnp.where(
            ex & (op == SELFBALANCE_B) & (balance_tid != 0), OPAQUE, res_tid
        )

    # DUP/SWAP move tids with their values (depths pre-gathered in the
    # consolidated peek)
    is_dup = (op >= 0x80) & (op <= 0x8F)
    is_swap = (op >= 0x90) & (op <= 0x9F)
    res_tid = jnp.where(ex & is_dup, dup_tid, res_tid)
    res_tid = jnp.where(ex & is_swap, swap_deep_tid, res_tid)

    # --- stack tid write (mirrors the consolidated stack write) --------
    res_idx = jnp.where(
        is_dup, pre.sp, jnp.where(is_swap, pre.sp - 1, pre.sp - pops)
    )
    res_idx = jnp.clip(res_idx, 0, stack_cap - 1)
    writes = ex & executed & (pushes > 0)
    stack_tid = _scatter2(symb.stack_tid, res_idx, res_tid, writes)
    # SWAP's second slot: the old top's tid sinks to the deep position
    stack_tid = _scatter2(
        stack_tid,
        jnp.clip(pre.sp - 1 - swap_n, 0, stack_cap - 1),
        a_tid,
        ex & is_swap,
    )

    # --- branch journal tids -------------------------------------------
    br_cap = pre.br_pc.shape[1]
    record = ex & (op == JUMPI) & (pre.br_cnt < br_cap)
    br_slot = jnp.clip(pre.br_cnt, 0, br_cap - 1)
    slot_hit = (jnp.arange(br_cap)[None, :] == br_slot[:, None]) & record[:, None]
    br_tid = jnp.where(slot_hit, b_tid[:, None], symb.br_tid)

    # --- evidence banks ------------------------------------------------
    # Wrap events: the concrete execution actually wrapped, which IS a
    # sat proof of the module's overflow predicate on this lane's path.
    # ADD/SUB checks are exact; MUL banks a cheap over-approximation
    # (overflow is impossible when both operands fit 128 bits) and the
    # host confirms exactly from the banked operand values — an extra
    # banked event costs a slot, never a false issue. Only node-backed
    # results bank (ev_tid must support DAG usage tracking).
    _false = jnp.zeros((n,), bool)
    if _on(phases, "arith"):
        wrap_add = (op == ADD_B) & u256.ult(u256.bit_not(a_val), b_val)
        wrap_sub = (op == SUB_B) & u256.ult(a_val, b_val)
        hi_a = jnp.any(a_val[:, W // 2 :] != 0, axis=-1)
        hi_b = jnp.any(b_val[:, W // 2 :] != 0, axis=-1)
        nz_a = jnp.any(a_val != 0, axis=-1)
        nz_b = jnp.any(b_val != 0, axis=-1)
        wrap_mul = (op == MUL_B) & (hi_a | hi_b) & nz_a & nz_b
        arith_exec = (
            ((op == ADD_B) | (op == SUB_B) | (op == MUL_B)) & ex & executed
        )
        # A concrete wrap banks REGARDLESS of term-ness: arithmetic over
        # taint-hashed mapping reads is opaque in the expression language
        # (the `balances[to] += x` shape), but the wrap still concretely
        # happened and the lane's input replays it. ev_tid is the result
        # node when one exists (DAG usage tracking) and 0 otherwise (the
        # consumer falls back to a static used-check).
        wrap_evt = (wrap_add | wrap_sub | wrap_mul) & arith_exec
        # sites WITHOUT a concrete wrap bank as steering targets — those
        # need decodable operand terms, so they stay node-gated; opaque-
        # operand sites bank as EV_SITE_OPAQUE (completeness gate)
        no_wrap = ~(wrap_add | wrap_sub | wrap_mul)
        # steering sites need DECODABLE operand terms (both non-opaque)
        site_evt = arith_exec & bin_sym & bin_ok & ok & no_wrap
        opaque_site = arith_exec & no_wrap & ((a_tid < 0) | (b_tid < 0))
        wrap_kind = jnp.where(
            op == ADD_B,
            EV_WRAP_ADD,
            jnp.where(op == SUB_B, EV_WRAP_SUB, EV_WRAP_MUL),
        ).astype(jnp.int32)
        wrap_kind = jnp.where(site_evt, wrap_kind + 9, wrap_kind)
        wrap_kind = jnp.where(opaque_site, EV_SITE_OPAQUE, wrap_kind)
    else:
        wrap_evt = site_evt = opaque_site = _false
        wrap_kind = jnp.zeros((n,), jnp.int32)

    # Call events: every executed CALL-family site, with target/value
    # term ids + concrete values, the gas operand (saturated to 32
    # bits — detection only compares against the 2300 stipend), and
    # the branch-journal depth at call time (analysis/evidence.py
    # classifies SWC-104/105/107/112).
    if _on(phases, "calls"):
        call_kind = meta[:, 7]
        has_value = meta[:, 9] != 0
        call_evt = ex & executed & (call_kind != 0)
        gas32 = (
            a_val[:, 0].astype(jnp.uint32)
            | (a_val[:, 1].astype(jnp.uint32) << 16)
        )
        gas_sat = jnp.where(
            jnp.any(a_val[:, 2:] != 0, axis=-1), jnp.uint32(0xFFFFFFFF),
            gas32,
        )
        # state access AFTER a gas-forwarding call (reentrancy surface,
        # state_change_external_calls.py): the flag arms on the call,
        # the SSTORE/SLOAD event banks the access site
        forwarding = call_evt & (gas_sat > 2300)
        state_acc = ex & executed & (symb.call_seen != 0) & (
            (op == SSTORE) | (op == SLOAD)
        )
        call_seen = jnp.where(
            forwarding, jnp.int32(1), symb.call_seen
        )
    else:
        call_kind = jnp.zeros((n,), jnp.int32)
        has_value = _false
        call_evt = state_acc = _false
        gas_sat = jnp.zeros((n,), jnp.uint32)
        call_seen = symb.call_seen
    # SLOAD of a never-written slot: the observed CONCRETE key value
    # is what the poisoned-storage carry will seed. The key may be
    # taint-derived (mapping slots hash calldata) — the value is still
    # the one this lane's replayable input reaches, which is all the
    # poison mechanism needs.
    if _on(phases, "sload"):
        sload_miss = ex & executed & sload_m & ~any_hit
    else:
        sload_miss = _false

    evt = wrap_evt | site_evt | opaque_site | call_evt | state_acc | sload_miss
    kind = jnp.where(call_evt, call_kind, wrap_kind)
    kind = jnp.where(
        state_acc & (op == SSTORE), EV_SSTORE_AFTER_CALL, kind
    )
    kind = jnp.where(state_acc & (op == SLOAD), EV_SLOAD_AFTER_CALL, kind)
    # an after-call SLOAD outranks the miss hint (one event per step)
    kind = jnp.where(sload_miss & ~state_acc, EV_SLOAD_MISS, kind)
    ev_tid_new = jnp.where(mk_node & ok, node_tid, 0)
    ev_tid_new = jnp.where(call_evt, b_tid, ev_tid_new)
    ev_tid_new = jnp.where(state_acc | sload_miss, 0, ev_tid_new)
    ev_vtid_new = jnp.where(call_evt & has_value, c_tid, 0)
    a_field = jnp.where(call_evt[:, None], b_val, a_val)
    b_field = jnp.where(
        call_evt[:, None],
        jnp.where(has_value[:, None], c_val, jnp.zeros_like(c_val)),
        b_val,
    )
    # one witness per (pc, kind) per lane: loops would otherwise fill
    # the bank with duplicates of the first wrapping site
    seen = jnp.any(
        (symb.ev_pc == pre.pc[:, None])
        & (symb.ev_kind == kind[:, None])
        & (jnp.arange(EVENT_CAP)[None, :] < symb.ev_cnt[:, None]),
        axis=1,
    )
    bank = evt & ~seen & (symb.ev_cnt < EVENT_CAP)
    # a DISTINCT event hitting a full bank is LOST evidence: the
    # consumer's completeness inputs are truncated, so the lane flags
    # it and the ownership gate sends the contract to the host walk
    ev_overflow = jnp.where(
        evt & ~seen & (symb.ev_cnt >= EVENT_CAP),
        jnp.int32(1),
        symb.ev_overflow,
    )
    ev_hit = (
        jnp.arange(EVENT_CAP)[None, :]
        == jnp.clip(symb.ev_cnt, 0, EVENT_CAP - 1)[:, None]
    ) & bank[:, None]
    ev_pc = jnp.where(ev_hit, pre.pc[:, None], symb.ev_pc)
    ev_kind = jnp.where(ev_hit, kind[:, None], symb.ev_kind)
    ev_tid = jnp.where(ev_hit, ev_tid_new[:, None], symb.ev_tid)
    ev_vtid = jnp.where(ev_hit, ev_vtid_new[:, None], symb.ev_vtid)
    ev_a = jnp.where(ev_hit[:, :, None], a_field[:, None, :], symb.ev_a)
    ev_b = jnp.where(ev_hit[:, :, None], b_field[:, None, :], symb.ev_b)
    ev_aux = jnp.where(ev_hit, pre.br_cnt[:, None], symb.ev_aux)
    ev_gas = jnp.where(ev_hit, gas_sat[:, None], symb.ev_gas)
    ev_cnt = symb.ev_cnt + bank.astype(jnp.int32)

    # RETURN window (final memory taints + this window = "the wrapped
    # value escapes via RETURN" usage evidence)
    ret_m = ex & executed & (op == RETURN_B)
    len_ret, len_big = _word_to_i32(b_val)
    ret_known = ret_m & ~off_big & ~len_big
    ret_off = jnp.where(ret_known, off_i, jnp.where(ret_m, -1, symb.ret_off))
    ret_len = jnp.where(ret_known, len_ret, jnp.where(ret_m, -1, symb.ret_len))

    return SymBatch(
        base=post,
        stack_tid=stack_tid,
        mem_tid=mem_tid,
        skey_tid=skey_tid,
        sval_tid=sval_tid,
        br_tid=br_tid,
        balance_tid=balance_tid,
        ev_pc=ev_pc,
        ev_kind=ev_kind,
        ev_tid=ev_tid,
        ev_vtid=ev_vtid,
        ev_a=ev_a,
        ev_b=ev_b,
        ev_aux=ev_aux,
        ev_gas=ev_gas,
        ev_cnt=ev_cnt,
        ev_overflow=ev_overflow,
        call_seen=call_seen,
        ret_off=ret_off,
        ret_len=ret_len,
        ar_op=ar_op,
        ar_a=ar_a,
        ar_b=ar_b,
        ar_va=ar_va,
        ar_vb=ar_vb,
        ar_count=ar_count,
    )


def _sym_run_impl(symb: SymBatch, code: CodeTable, max_steps: int = 2048,
                  phases=None):
    """Run every lane to halt (or budget) with the symbolic shadow.

    Returns (out, steps, active_lane_steps): `steps` is the raw loop
    trip count, `active_lane_steps` counts only lanes that were still
    RUNNING when each step executed — the honest per-wave work metric
    (most lanes halt long before the wave's step budget, so
    steps * n_lanes overcounts by the halted tail).

    `phases` (static) prunes handler phases at trace time — the
    specialization layer's loop (specialize.py) additionally
    interleaves fused substeps; THIS loop is the generic/pruned-only
    schedule."""

    def cond(carry):
        s, i, _active = carry
        return (i < max_steps) & jnp.any(s.base.status == Status.RUNNING)

    def body(carry):
        s, i, active = carry
        active = active + jnp.sum(
            (s.base.status == Status.RUNNING).astype(jnp.int32)
        )
        return sym_step(s, code, phases=phases), i + 1, active

    out, steps, active = lax.while_loop(
        cond, body, (symb, jnp.int32(0), jnp.int32(0))
    )
    return out, steps, active


sym_run = functools.partial(
    jax.jit, static_argnames=("max_steps", "phases"))(
    _sym_run_impl
)
#: donated variant for the pipelined wave engine (explore.py): the
#: seeded input SymBatch is consumed by the dispatch, so XLA reuses its
#: buffers for the output instead of allocating a second arena-sized
#: footprint per in-flight wave. Only safe when the caller never reads
#: the input again (the explorer's dispatch path guarantees this);
#: gated off on backends without donation support (CPU).
sym_run_donated = functools.partial(
    jax.jit, static_argnames=("max_steps", "phases"), donate_argnums=(0,)
)(_sym_run_impl)


def _reseed_wave_impl(
    symb: SymBatch,
    code_ids,
    calldata,
    calldatasize,
    callvalue,
    balance,
    skeys,
    svals,
    scnt,
    synthetic,
):
    """Build the NEXT wave's seeded SymBatch on device out of the
    PREVIOUS wave's (donated) buffers.

    This is the arena-reuse half of the pipelined wave engine: the
    big constant-shaped state (stack, memory, coverage bitmap, shadow
    tids, the expression arena) is re-zeroed in place on device, the
    environment words (block context, caller, address, gas budget,
    empty_world) are carried over untouched — they are identical every
    wave of an exploration — and the host uploads only the per-wave
    seed delta: calldata, call values, balances, and a compact
    storage-journal slab (`skeys`/`svals` are [N, w, LIMBS] with w the
    power-of-two bucket of the widest journal, not the full
    storage_cap table `make_batch` would rebuild).

    `synthetic` marks lanes whose seeded journal is an adversarial
    SAMPLE of symbolic initial storage: their seeded value tids become
    opaque, exactly as the explorer's make_batch path masks them."""
    base = symb.base
    n = base.pc.shape[0]
    s_cap = base.storage_keys.shape[1]

    storage_keys = jnp.zeros_like(base.storage_keys)
    storage_vals = jnp.zeros_like(base.storage_vals)
    storage_keys = storage_keys.at[:, : skeys.shape[1]].set(skeys)
    storage_vals = storage_vals.at[:, : svals.shape[1]].set(svals)
    cd = jnp.zeros_like(base.calldata).at[:, : calldata.shape[1]].set(calldata)

    new_base = base._replace(
        code_id=code_ids,
        pc=jnp.zeros_like(base.pc),
        stack=jnp.zeros_like(base.stack),
        sp=jnp.zeros_like(base.sp),
        mem=jnp.zeros_like(base.mem),
        msize_words=jnp.zeros_like(base.msize_words),
        storage_keys=storage_keys,
        storage_vals=storage_vals,
        storage_cnt=scnt,
        status=jnp.zeros_like(base.status),
        gas_min=jnp.zeros_like(base.gas_min),
        gas_max=jnp.zeros_like(base.gas_max),
        ret_offset=jnp.zeros_like(base.ret_offset),
        ret_len=jnp.zeros_like(base.ret_len),
        pc_seen=jnp.zeros_like(base.pc_seen),
        br_pc=jnp.full_like(base.br_pc, -1),
        br_taken=jnp.zeros_like(base.br_taken),
        br_cnt=jnp.zeros_like(base.br_cnt),
        callvalue=callvalue,
        balance=balance,
        calldata=cd,
        calldatasize=calldatasize,
    )
    seeded = jnp.arange(s_cap)[None, :] < scnt[:, None]
    sval_tid = jnp.where(
        synthetic[:, None] & seeded,
        jnp.int32(-1),
        jnp.zeros_like(symb.sval_tid),
    )
    return SymBatch(
        base=new_base,
        stack_tid=jnp.zeros_like(symb.stack_tid),
        mem_tid=jnp.zeros_like(symb.mem_tid),
        skey_tid=jnp.zeros_like(symb.skey_tid),
        sval_tid=sval_tid,
        br_tid=jnp.zeros_like(symb.br_tid),
        balance_tid=jnp.zeros_like(symb.balance_tid),
        ev_pc=jnp.zeros_like(symb.ev_pc),
        ev_kind=jnp.zeros_like(symb.ev_kind),
        ev_tid=jnp.zeros_like(symb.ev_tid),
        ev_vtid=jnp.zeros_like(symb.ev_vtid),
        ev_a=jnp.zeros_like(symb.ev_a),
        ev_b=jnp.zeros_like(symb.ev_b),
        ev_aux=jnp.zeros_like(symb.ev_aux),
        ev_gas=jnp.zeros_like(symb.ev_gas),
        ev_cnt=jnp.zeros_like(symb.ev_cnt),
        ev_overflow=jnp.zeros_like(symb.ev_overflow),
        call_seen=jnp.zeros_like(symb.call_seen),
        ret_off=jnp.full_like(symb.ret_off, -1),
        ret_len=jnp.full_like(symb.ret_len, -1),
        ar_op=jnp.zeros_like(symb.ar_op),
        ar_a=jnp.zeros_like(symb.ar_a),
        ar_b=jnp.zeros_like(symb.ar_b),
        ar_va=jnp.zeros_like(symb.ar_va),
        ar_vb=jnp.zeros_like(symb.ar_vb),
        ar_count=jnp.int32(0),
    )


reseed_wave = jax.jit(_reseed_wave_impl)
#: donated variant: the spent wave's output buffers become the next
#: wave's input buffers — device memory for the exploration stays flat
#: at ~pipeline-depth arenas regardless of wave count.
reseed_wave_donated = jax.jit(_reseed_wave_impl, donate_argnums=(0,))
