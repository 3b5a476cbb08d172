"""On-chip portfolio search: SMT queries as TPU tensor programs.

This is the north-star solver component (SURVEY.md §7.1): a lowered
constraint set (bit-vector ops only — arrays/UF are gone after
preprocess.lower) compiles to a flat tensor program over 16-bit limbs
and is interpreted on device for K candidate assignments at once; a
stochastic local search mutates candidates toward satisfying every
constraint root. A found witness is decoded host-side and re-verified
by the model soundness gate, so SAT answers are certain; *absence* of
a witness proves nothing — the native CDCL solver remains the
completeness oracle. The reference's counterpart is z3's
`parallel.enable` thread pool (mythril/laser/smt/solver/__init__.py:8).

Signed operations are compiled away with sign-bit constants:
`slt(a,b) = ult(a^s, b^s)`, `sext_w0(x) = (x^s) - s`, `ashr` ORs a
sign-fill mask — so the interpreter needs only unsigned primitives
from ops/u256. Shapes are bucketed (nodes/consts/roots padded to size
classes) so XLA compiles one interpreter per bucket, not per query.
"""

from __future__ import annotations

from collections import namedtuple
from contextlib import contextmanager
from typing import Dict, List, Optional, Tuple

import numpy as np

from mythril_tpu.laser.smt import terms
from mythril_tpu.laser.smt.terms import Term

LIMB_BITS = 16
LIMB_MASK = 0xFFFF

#: Diversified-portfolio knobs — the replay-derived defaults committed
#: from `myth solverlab tune` sweeps over the captured fault-suite
#: corpus (ISSUE 9; re-run the tune against a fresh capture before
#: changing them by hand). Search-shape knobs are trace-time constants:
#: `portfolio_overrides` invalidates the kernel cache around a sweep.
PORTFOLIO_DEFAULTS: Dict[str, float] = {
    # WalkSAT-style noise: the probability a lane accepts a WORSENING
    # move, swept linearly across the candidate axis (lane 0 is a pure
    # hill climber, the last lane a near-random walker)
    "noise_lo": 0.02,
    "noise_hi": 0.40,
    # fraction of lanes restricted to greedy local moves (bit flip /
    # increment / decrement); the rest draw from the full move mix
    # (randomize limb, zero limb, constant injection)
    "greedy_frac": 0.5,
    # Luby restart unit, in search steps: a lane stalled for
    # luby(i) * restart_base steps reseeds with fresh randomness
    "restart_base": 24,
    # fraction of initial candidates polarity-seeded from the
    # program's own constant pool — dispatcher selectors, actor
    # addresses, and banked storage values from the static summary /
    # carries land in the pool via the path conditions, so these lanes
    # start at the constants the query is actually about
    "seeded_frac": 0.25,
    # cube-and-conquer split depth for hard queries: 2^depth cubes
    # pinned on the top-impact variables (soft-score gradient ranking)
    "cube_depth": 3,
    # exhaustive-enumeration cap: a COMPLETE program whose total
    # variable space fits 2^enum_bits is enumerated outright — the
    # only mode where the device owns unsat verdicts
    "enum_bits": 14,
    # chunked enumeration extends the complete range by this many cube
    # bits (2^cube chunks of 2^enum_bits candidates each)
    "enum_cube_bits": 4,
    # candidates per enumeration chunk (2^bits): bounds the [N, K, L]
    # eval footprint and the XLA shape-class count
    "enum_chunk_bits": 12,
    # the device-FIRST wave dispatch's step budget (the batched flip
    # funnel); escalation survivors and race queries get the caller's
    # full step budget
    "first_pass_steps": 192,
    # grace window (ms) the check_terms funnel gives an in-flight race
    # to claim a verdict the host just found — the escalation
    # threshold the mtpu_solver_race_margin_seconds histogram tunes
    "race_grace_ms": 150,
}


#: pristine copy of the COMMITTED defaults, for reset_tuned_defaults
#: (the self-tuning flywheel swaps the live dict, tests swap it back)
_FACTORY_DEFAULTS: Dict[str, float] = dict(PORTFOLIO_DEFAULTS)

#: version of the installed tuned-override artifact (0 = committed
#: defaults) — exported as the mtpu_router_tuned_version gauge
_TUNED_VERSION = 0


def install_tuned_defaults(knobs: Dict[str, float], version: int) -> None:
    """Apply a tuned-v<N>.json override artifact (routing/tuning.py)
    as the process defaults. Same trace-time-constant discipline as
    `portfolio_overrides`: the kernel cache is dropped so the swap
    recompiles rather than mismatches — kernel keys derive from the
    knob values, so a stale kernel can never serve tuned traffic."""
    global _TUNED_VERSION
    unknown = set(knobs) - set(PORTFOLIO_DEFAULTS)
    if unknown:
        raise ValueError(f"unknown portfolio knobs: {sorted(unknown)}")
    PORTFOLIO_DEFAULTS.update(knobs)
    _TUNED_VERSION = int(version)
    _eval_cache.clear()
    try:
        from mythril_tpu.observe.registry import registry

        registry().gauge(
            "mtpu_router_tuned_version",
            "version of the installed tuned portfolio-override artifact "
            "(0 = committed defaults)",
        ).set(_TUNED_VERSION)
    except Exception:
        pass


def reset_tuned_defaults() -> None:
    """Back to the committed defaults (test isolation)."""
    global _TUNED_VERSION
    PORTFOLIO_DEFAULTS.clear()
    PORTFOLIO_DEFAULTS.update(_FACTORY_DEFAULTS)
    _TUNED_VERSION = 0
    _eval_cache.clear()


def tuned_version() -> int:
    return _TUNED_VERSION


@contextmanager
def portfolio_overrides(**knobs):
    """Temporarily override PORTFOLIO_DEFAULTS (`myth solverlab tune`
    sweeps one trial per override set). The strategy knobs are baked
    into the jitted search at trace time, so the kernel cache is
    dropped on entry AND exit — replay-lab cost, never paid live."""
    unknown = set(knobs) - set(PORTFOLIO_DEFAULTS)
    if unknown:
        raise ValueError(f"unknown portfolio knobs: {sorted(unknown)}")
    saved = dict(PORTFOLIO_DEFAULTS)
    PORTFOLIO_DEFAULTS.update(knobs)
    _eval_cache.clear()
    try:
        yield
    finally:
        PORTFOLIO_DEFAULTS.clear()
        PORTFOLIO_DEFAULTS.update(saved)
        _eval_cache.clear()


#: One query's device verdict (device_solve_batch): status is
#: "sat" (validated witness in `assignment`), "unsat" (complete
#: enumeration exhausted the space — device-owned), or "unknown"
#: (`loss` names the reason in the querylog taxonomy). `via` records
#: the deciding mode: "sls", "enum", "cube", or None.
DeviceVerdict = namedtuple("DeviceVerdict", "status assignment loss via")

OPS = [
    "const",    # 0: const_pool[imm0]
    "var",      # 1: X[imm0]
    "add", "sub", "mul", "udiv", "urem",            # 2-6
    "bvand", "bvor", "bvxor", "bvnot",              # 7-10
    "shl", "lshr",                                   # 11-12
    "ashr",     # 13: imm0 = signbit const idx, imm1 = allones const idx
    "concat",   # 14: (a << imm0) | b   (imm0 = width(b))
    "extract",  # 15: a >> imm0, masked to node width
    "zext",     # 16: identity (mask handles it)
    "sext",     # 17: (a ^ pool[imm0]) - pool[imm0]
    "ite",      # 18: bool(a) ? b : c
    "eq",       # 19
    "ult",      # 20
    "ule",      # 21
    "slt",      # 22: ult(a^pool[imm0], b^pool[imm0])
    "sle",      # 23: ule(a^pool[imm0], b^pool[imm0])
    "band", "bor", "bnot", "bxor", "implies",        # 24-28
]
OP_INDEX = {name: i for i, name in enumerate(OPS)}

# the term layer names bitwise BV ops without the bv prefix
_OP_ALIASES = {"and": "bvand", "or": "bvor", "xor": "bvxor", "not": "bvnot"}


class Program:
    """A compiled constraint set: flat node arrays + metadata."""

    def __init__(self, opcodes, args, imms, widths, const_pool, var_slots,
                 roots, roots_mask, limbs, n_real_nodes):
        self.opcodes = opcodes          # [N] int32
        self.args = args                # [N, 3] int32 node indices
        self.imms = imms                # [N, 2] int32 immediates
        self.widths = widths            # [N] int32
        self.const_pool = const_pool    # [C, L] uint32 limbs
        self.var_slots = var_slots      # slot -> (name, width)
        self.roots = roots              # [R] int32 node indices
        self.roots_mask = roots_mask    # [R] bool (False = padding)
        self.limbs = limbs
        self.n_real_nodes = n_real_nodes
        #: the constraint terms this program was compiled FROM — the
        #: set every device witness is concretely validated against
        #: before a sat verdict counts (validate_witness)
        self.source: List[Term] = []
        #: REAL constant-pool rows (the pool array is padded to a
        #: bucket): polarity seeding and the constant-injection move
        #: draw only from these
        self.n_consts = 1
        #: False when segmentation dropped constraints outside the
        #: device language: still sound for SAT search (the validation
        #: gate covers the kept subset and callers re-check the full
        #: set), NEVER eligible for enumeration-unsat
        self.complete = True


def _bucket(n: int, lo: int = 64) -> int:
    size = lo
    while size < n:
        size *= 2
    return size


#: widened shape-bucket lattice (ISSUE 9 coverage widening): 128 limbs
#: = 2048-bit nodes. Wide concat chains (keccak preimages, packed
#: calldata) used to be BUCKET_OVERFLOW losses at the old 64-limb cap.
DEFAULT_MAX_LIMBS = 128


def compile_program(
    lowered: List[Term], max_limbs: int = DEFAULT_MAX_LIMBS
) -> Optional[Program]:
    """Flatten the constraint DAG into tensor-program arrays; None when
    an op falls outside the device language or widths exceed the cap."""
    return compile_program_ex(lowered, max_limbs)[0]


def _power_of_two(t: Term) -> bool:
    return t.op == "const" and t.args[0] > 0 and not t.args[0] & (t.args[0] - 1)


def compile_program_ex(
    lowered: List[Term], max_limbs: int = DEFAULT_MAX_LIMBS
) -> Tuple[Optional[Program], Optional[str]]:
    """`compile_program` with the failure EXPLAINED: (program, None) on
    success, (None, loss_reason) on a bail — the reason strings are the
    flight recorder's taxonomy (observe/querylog.py): QUERY_TRIVIAL
    (nothing to search), BUCKET_OVERFLOW (widths past the limb cap),
    LOWERING_UNSUPPORTED (op outside the device language)."""
    order: List[Term] = []
    index: Dict[int, int] = {}

    for root in lowered:
        stack = [(root, False)]
        while stack:
            node, expanded = stack.pop()
            if node._id in index:
                continue
            if expanded:
                if node._id not in index:
                    index[node._id] = len(order)
                    order.append(node)
                continue
            stack.append((node, True))
            for a in node.args:
                if isinstance(a, Term) and a._id not in index:
                    stack.append((a, False))

    if not order:
        return None, "QUERY_TRIVIAL"
    max_width = max((t.width or 1) for t in order)
    L = max(16, _bucket((max_width + LIMB_BITS - 1) // LIMB_BITS, 16))
    if L > max_limbs:
        return None, "BUCKET_OVERFLOW"

    n = len(order)
    opcodes = np.zeros(n, dtype=np.int32)
    args = np.zeros((n, 3), dtype=np.int32)
    imms = np.zeros((n, 2), dtype=np.int32)
    widths = np.ones(n, dtype=np.int32)
    const_pool: List[int] = []
    const_index: Dict[int, int] = {}
    var_slots: List[Tuple[str, int]] = []
    var_index: Dict[Tuple[str, int], int] = {}

    def intern_const(value: int) -> int:
        got = const_index.get(value)
        if got is None:
            got = const_index[value] = len(const_pool)
            const_pool.append(value)
        return got

    def var_slot(key: Tuple[str, int]) -> int:
        got = var_index.get(key)
        if got is None:
            got = var_index[key] = len(var_slots)
            var_slots.append(key)
        return got

    for i, t in enumerate(order):
        op = t.op
        w = t.width or 1
        widths[i] = w
        if op == "const":
            opcodes[i] = OP_INDEX["const"]
            imms[i, 0] = intern_const(t.args[0])
        elif op in ("true", "false"):
            opcodes[i] = OP_INDEX["const"]
            imms[i, 0] = intern_const(1 if op == "true" else 0)
        elif op == "var":
            opcodes[i] = OP_INDEX["var"]
            imms[i, 0] = var_slot((t.args[0], w))
        elif op == "bvar":
            opcodes[i] = OP_INDEX["var"]
            imms[i, 0] = var_slot((t.args[0], 1))
        elif op == "extract":
            hi, lo, a = t.args
            opcodes[i] = OP_INDEX["extract"]
            args[i, 0] = index[a._id]
            imms[i, 0] = lo
        elif op == "zext":
            opcodes[i] = OP_INDEX["zext"]
            args[i, 0] = index[t.args[0]._id]
        elif op == "sext":
            a = t.args[0]
            opcodes[i] = OP_INDEX["sext"]
            args[i, 0] = index[a._id]
            imms[i, 0] = intern_const(1 << (a.width - 1))
        elif op == "concat":
            a, b = t.args
            opcodes[i] = OP_INDEX["concat"]
            args[i, 0] = index[a._id]
            args[i, 1] = index[b._id]
            imms[i, 0] = b.width
        elif op in ("slt", "sle"):
            a, b = t.args
            opcodes[i] = OP_INDEX[op]
            args[i, 0] = index[a._id]
            args[i, 1] = index[b._id]
            imms[i, 0] = intern_const(1 << (a.width - 1))
        elif op == "ashr":
            a, sh = t.args
            opcodes[i] = OP_INDEX["ashr"]
            args[i, 0] = index[a._id]
            args[i, 1] = index[sh._id]
            imms[i, 0] = intern_const(1 << (w - 1))
            imms[i, 1] = intern_const((1 << w) - 1)
        elif op == "udiv" and _power_of_two(t.args[1]):
            # a / 2^k is a >> k: the selector shift every dispatcher
            # query carries, without the 256-step long division
            a, d = t.args
            opcodes[i] = OP_INDEX["extract"]
            args[i, 0] = index[a._id]
            imms[i, 0] = d.args[0].bit_length() - 1
        elif op == "ite":
            c, a, b = t.args
            opcodes[i] = OP_INDEX["ite"]
            args[i, 0] = index[c._id]
            args[i, 1] = index[a._id]
            args[i, 2] = index[b._id]
        elif op in _OP_ALIASES or op in OP_INDEX:
            opcodes[i] = OP_INDEX[_OP_ALIASES.get(op, op)]
            for k, a in enumerate(t.args[:3]):
                if isinstance(a, Term):
                    args[i, k] = index[a._id]
        else:
            return None, "LOWERING_UNSUPPORTED"

    roots = [index[c._id] for c in lowered]

    n_pad = _bucket(n)
    def pad(arr, shape, fill=0):
        out = np.full(shape, fill, dtype=arr.dtype)
        out[: arr.shape[0]] = arr
        return out

    c_pad = _bucket(max(1, len(const_pool)), 16)
    pool = np.zeros((c_pad, L), dtype=np.uint32)
    for k, value in enumerate(const_pool):
        for j in range(L):
            pool[k, j] = (value >> (LIMB_BITS * j)) & LIMB_MASK

    r_pad = _bucket(max(1, len(roots)), 16)
    roots_arr = np.zeros(r_pad, dtype=np.int32)
    roots_arr[: len(roots)] = roots
    roots_mask = np.zeros(r_pad, dtype=bool)
    roots_mask[: len(roots)] = True

    prog = Program(
        pad(opcodes, (n_pad,)),
        pad(args, (n_pad, 3)),
        pad(imms, (n_pad, 2)),
        pad(widths, (n_pad,), fill=1),
        pool,
        var_slots,
        roots_arr,
        roots_mask,
        L,
        n,
    )
    prog.source = list(lowered)
    prog.n_consts = max(1, len(const_pool))
    return prog, None


#: ops the compile loop above can lower (everything it special-cases
#: plus the direct OPS table and the bitwise aliases)
_DEVICE_OPS = (
    set(OPS)
    | set(_OP_ALIASES)
    | {"true", "false", "var", "bvar", "const"}
)


def _constraint_supported(root: Term, max_limbs: int) -> bool:
    """Whole-DAG device-language check for ONE constraint: every op
    lowerable, every node width inside the limb cap."""
    width_cap = max_limbs * LIMB_BITS
    seen = set()
    stack = [root]
    while stack:
        t = stack.pop()
        if t._id in seen:
            continue
        seen.add(t._id)
        if t.op not in _DEVICE_OPS or (t.width or 1) > width_cap:
            return False
        for a in t.args:
            if isinstance(a, Term):
                stack.append(a)
    return True


def compile_program_relaxed(
    lowered: List[Term], max_limbs: int = DEFAULT_MAX_LIMBS
) -> Tuple[Optional[Program], int, Optional[str]]:
    """`compile_program_ex` with SEGMENTATION (ISSUE 9 coverage
    widening): when the full set will not lower, constraints outside
    the device language (or past the limb cap) are dropped and the
    supported remainder compiles as an INCOMPLETE program — sound for
    SAT search because every witness is validated before it counts
    (and, on the flip path, concretely executed), never eligible for
    enumeration-unsat. Returns (program, n_dropped, loss_reason);
    a non-None program with n_dropped > 0 is the segmented form."""
    prog, loss = compile_program_ex(lowered, max_limbs)
    if prog is not None:
        return prog, 0, None
    kept = [c for c in lowered if _constraint_supported(c, max_limbs)]
    n_dropped = len(lowered) - len(kept)
    if not kept or n_dropped == 0:
        # nothing lowerable, or the bail was not per-constraint (e.g.
        # an empty order): segmentation cannot help
        return None, n_dropped, loss
    prog, seg_loss = compile_program_ex(kept, max_limbs)
    if prog is None:
        return None, n_dropped, seg_loss or loss
    prog.complete = False
    return prog, n_dropped, None


def bucket_key(prog: Program) -> Dict[str, int]:
    """The XLA shape bucket a compiled program lands in — the grouping
    key the capture artifacts and `myth solverlab` report engines by
    (one interpreter compiles per distinct bucket, not per query)."""
    return {
        "nodes": int(prog.opcodes.shape[0]),
        "consts": int(prog.const_pool.shape[0]),
        "roots": int(prog.roots.shape[0]),
        "vars": int(_bucket(max(1, len(prog.var_slots)), 4)),
        "limbs": int(prog.limbs),
    }


# ---------------------------------------------------------------------------
# device interpreter + local search
# ---------------------------------------------------------------------------

_eval_cache: Dict[Tuple[int, int], object] = {}


def _get_search_fn(K: int, L: int, steps: int):
    """The jit'd evaluate-and-search kernel for (K candidates, L limbs,
    steps); cached per shape bucket."""
    key = (K, L, steps)
    got = _eval_cache.get(key)
    if got is not None:
        return got

    import jax
    import jax.numpy as jnp

    from mythril_tpu.ops import u256

    def width_mask(width):
        k = jnp.arange(L, dtype=jnp.int32)
        bits = jnp.clip(width - k * LIMB_BITS, 0, LIMB_BITS)
        # shift amount capped below the lane width (shift-by-16 on a
        # 16-bit mask is what the full-limb branch handles)
        partial = (jnp.uint32(1) << jnp.minimum(bits, 15).astype(jnp.uint32)) - 1
        return jnp.where(bits >= LIMB_BITS, jnp.uint32(LIMB_MASK), partial)

    def bcast_amount(amount):
        """Broadcast a traced scalar shift amount to the batch shape
        (u256 shift ops take one uint32 amount per batch element)."""
        return jnp.full((K,), amount, dtype=jnp.uint32)

    def to_bool(x):
        return x[:, 0] != 0

    FULL = jnp.int32(1 << 10)  # soft-score scale per constraint

    def from_bool(hard, soft=None):
        """Bool word: limb0 = 0/1 truth, limb1 = soft score [0, FULL]
        (the local-search gradient; hard-only ops score 0 or FULL)."""
        hard_u = hard.astype(jnp.uint32)
        soft_u = (
            (hard_u * FULL.astype(jnp.uint32))
            if soft is None
            else soft.astype(jnp.uint32)
        )
        return (
            jnp.zeros((K, L), dtype=jnp.uint32)
            .at[:, 0].set(hard_u)
            .at[:, 1].set(soft_u)
        )

    def soft_of(x):
        return x[:, 1].astype(jnp.int32)

    def popcount_bits(x):
        return jax.lax.population_count(x).sum(axis=-1).astype(jnp.int32)

    def eval_program(opcodes, args, imms, widths, pool, X, n_nodes):
        N = opcodes.shape[0]
        values = jnp.zeros((N, K, L), dtype=jnp.uint32)

        def body(i, values):
            op = opcodes[i]
            a = values[args[i, 0]]
            b = values[args[i, 1]]
            c = values[args[i, 2]]
            imm0 = imms[i, 0]
            imm1 = imms[i, 1]
            w = widths[i]
            k0 = jnp.broadcast_to(pool[imm0], (K, L))
            k1 = jnp.broadcast_to(pool[imm1], (K, L))

            def soft_eq(x, y, width):
                # bit-level hamming credit: fully-equal -> FULL
                diff = popcount_bits(u256.bit_xor(x, y))
                width = jnp.maximum(width, 1)
                return ((width - jnp.minimum(diff, width)) * FULL) // width

            arg_w = widths[args[i, 0]]

            branches = [
                lambda: k0,                                       # const
                lambda: X[imm0],                                  # var
                lambda: u256.add(a, b),
                lambda: u256.sub(a, b),
                lambda: u256.mul(a, b),
                lambda: u256.udiv(a, b),
                lambda: u256.urem(a, b),
                lambda: u256.bit_and(a, b),
                lambda: u256.bit_or(a, b),
                lambda: u256.bit_xor(a, b),
                lambda: u256.bit_not(a),
                lambda: u256.shl(a, u256.shift_amount(b)),
                lambda: u256.lshr(a, u256.shift_amount(b)),
                # ashr at node width: lshr | sign-fill
                # (k0 = signbit const, k1 = allones-at-width const)
                lambda: u256.bit_or(
                    u256.lshr(a, u256.shift_amount(b)),
                    jnp.where(
                        to_bool_word(u256.bit_and(a, k0))[:, None],
                        u256.bit_and(
                            u256.bit_not(
                                u256.lshr(k1, u256.shift_amount(b))
                            ),
                            k1,
                        ),
                        jnp.zeros((K, L), dtype=jnp.uint32),
                    ),
                ),
                lambda: u256.bit_or(
                    u256.shl(a, bcast_amount(imm0)), b
                ),                                                # concat
                lambda: u256.lshr(a, bcast_amount(imm0)),         # extract
                lambda: a,                                        # zext
                lambda: u256.sub(u256.bit_xor(a, k0), k0),        # sext
                lambda: jnp.where(to_bool(a)[:, None], b, c),     # ite
                lambda: from_bool(u256.eq(a, b), soft_eq(a, b, arg_w)),
                lambda: from_bool(u256.ult(a, b)),
                lambda: from_bool(u256.ule(a, b)),
                lambda: from_bool(
                    u256.ult(u256.bit_xor(a, k0), u256.bit_xor(b, k0))
                ),                                                # slt
                lambda: from_bool(
                    u256.ule(u256.bit_xor(a, k0), u256.bit_xor(b, k0))
                ),                                                # sle
                lambda: from_bool(
                    jnp.logical_and(to_bool(a), to_bool(b)),
                    jnp.minimum(soft_of(a), soft_of(b)),
                ),                                                # band
                lambda: from_bool(
                    jnp.logical_or(to_bool(a), to_bool(b)),
                    jnp.maximum(soft_of(a), soft_of(b)),
                ),                                                # bor
                lambda: from_bool(
                    jnp.logical_not(to_bool(a)), FULL - soft_of(a)
                ),                                                # bnot
                lambda: from_bool(jnp.logical_xor(to_bool(a), to_bool(b))),
                lambda: from_bool(
                    jnp.logical_or(jnp.logical_not(to_bool(a)), to_bool(b)),
                    jnp.maximum(FULL - soft_of(a), soft_of(b)),
                ),                                                # implies
            ]
            out = jax.lax.switch(op, branches)
            mask = width_mask(w)
            # bool nodes (width 1) keep limb1: it carries the soft score
            mask = jnp.where(
                w == 1, mask.at[1].set(jnp.uint32(LIMB_MASK)), mask
            )
            out = out & jnp.broadcast_to(mask, (K, L))
            return values.at[i].set(out)

        # the real nodes only: the bucket's padding nodes feed no root
        return jax.lax.fori_loop(0, n_nodes, body, values)

    def to_bool_word(x):
        """Truthiness of a plain word value (non-bool nodes)."""
        return jnp.logical_not(u256.is_zero(x))

    def score(opcodes, args, imms, widths, pool, roots, roots_mask, X):
        # nodes are in post-order over the roots: the last real node is
        # the highest root
        n_nodes = jnp.max(jnp.where(roots_mask, roots, 0)) + 1
        values = eval_program(opcodes, args, imms, widths, pool, X, n_nodes)
        rv = values[roots]  # [R, K, L]
        hard = (rv[..., 0] != 0) | ~roots_mask[:, None]
        soft = jnp.where(
            roots_mask[:, None], rv[..., 1].astype(jnp.int32), 0
        )
        return hard.all(axis=0), soft.sum(axis=0)  # [K] solved, [K] score

    # heterogeneous lane strategy constants (PORTFOLIO_DEFAULTS),
    # baked at trace time — portfolio_overrides invalidates the cache
    NOISE_LO = float(PORTFOLIO_DEFAULTS["noise_lo"])
    NOISE_HI = float(PORTFOLIO_DEFAULTS["noise_hi"])
    GREEDY_FRAC = float(PORTFOLIO_DEFAULTS["greedy_frac"])
    RESTART_BASE = int(PORTFOLIO_DEFAULTS["restart_base"])
    SEEDED_FRAC = float(PORTFOLIO_DEFAULTS["seeded_frac"])

    def search(opcodes, args, imms, widths, pool, roots, roots_mask,
               var_widths, n_vars, n_consts, seed):
        # n_vars = the query's REAL var count: batched dispatch pads
        # var_widths to a shared bucket, and mutating width-1 dummy
        # slots would waste most of the step budget on a small query.
        # n_consts likewise bounds the REAL constant-pool rows so the
        # polarity/injection draws never land on zero padding.
        V = var_widths.shape[0]
        key = jax.random.PRNGKey(seed)
        k1, k2, kseed = jax.random.split(key, 3)
        # candidate pool: zeros, small values, random
        X = jax.random.randint(
            k1, (V, K, L), 0, 1 << LIMB_BITS, dtype=jnp.uint32
        )
        X = X.at[:, 0, :].set(0)                       # all-zero candidate
        X = X.at[:, 1, :].set(0)
        X = X.at[:, 1, 0].set(1)                       # all-one candidate
        P = pool.shape[0]
        n_consts = jnp.maximum(n_consts, 1)
        # polarity seeding: a band of candidates starts from the
        # program's OWN constants (dispatcher selectors, actor
        # addresses, banked storage values — the static summary's and
        # carries' imprint on the path conditions). The band CYCLES
        # the real pool rows per variable, so every constant is
        # guaranteed a seeded lane once S >= n_consts — wide
        # equalities solve at step 0
        S = max(0, min(K - 2, int(K * SEEDED_FRAC)))
        if S:
            cidx0 = (
                jnp.arange(S)[None, :] + jnp.arange(V)[:, None]
            ) % n_consts
            X = X.at[:, 2 : 2 + S, :].set(pool[cidx0])
        vmask = jax.vmap(width_mask)(var_widths)       # [V, L]
        X = X & vmask[:, None, :]

        solved0, score0 = score(
            opcodes, args, imms, widths, pool, roots, roots_mask, X
        )

        limb_caps = jnp.maximum((var_widths + LIMB_BITS - 1) // LIMB_BITS, 1)

        # the DIVERSIFIED lane strategies: WalkSAT-style noise swept
        # across the candidate axis (lane 0 pure hill climber, the
        # last a near-random walker) and a greedy/random move-mix
        # split — no two lane groups search the same basin the same
        # way, so a wave's candidates cover strategy space, not just
        # seed space
        lane = jnp.arange(K)
        noise = NOISE_LO + (NOISE_HI - NOISE_LO) * (
            lane.astype(jnp.float32) / max(K - 1, 1)
        )
        greedy = lane < max(1, int(K * GREEDY_FRAC))
        greedy_kinds = jnp.array([0, 3, 4], dtype=jnp.int32)

        def body(state):
            X, cur_score, best_score, key, it, _, stall, lub_u, lub_v = state
            key, kv, kk, kp, kb, kc, kn = jax.random.split(key, 7)
            v = jax.random.randint(kv, (K,), 0, jnp.maximum(n_vars, 1))
            # greedy lanes draw only local moves (bit flip, inc, dec);
            # diverse lanes keep the full mix incl. randomize/zero/
            # constant injection (the greedy draw reuses kind_full's
            # entropy — one fewer threefry per step)
            kind_full = jax.random.randint(kk, (K,), 0, 6)
            kind_greedy = greedy_kinds[kind_full % 3]
            kind = jnp.where(greedy, kind_greedy, kind_full)
            # only mutate limbs inside the var's width
            limb = jax.random.randint(kp, (K,), 0, L) % limb_caps[v]
            bits = jax.random.randint(
                kb, (K,), 0, 1 << LIMB_BITS, dtype=jnp.uint32
            )
            cand = jnp.arange(K)
            cur = X[v, cand, limb]
            flipped = jnp.where(
                kind == 0, cur ^ (jnp.uint32(1) << (bits & 15)),  # bit flip
                jnp.where(kind == 1, bits,                 # randomize limb
                          0),                              # zero limb
            ).astype(jnp.uint32)
            Xp = X.at[v, cand, limb].set(flipped)
            # whole-var moves: 3/4 increment / decrement jump over the
            # carry-chain local minima single bit flips get stuck in;
            # 5 injects a program constant (equalities against wide
            # literals — actor addresses, selectors — solve in one move)
            rows = X[v, cand, :]                           # [K, L]
            one = jnp.zeros((K, L), dtype=jnp.uint32).at[:, 0].set(1)
            stepped = jnp.where(
                (kind == 3)[:, None],
                u256.add(rows, one),
                u256.sub(rows, one),
            )
            cidx = jax.random.randint(kc, (K,), 0, max(P, 1)) % n_consts
            injected = pool[cidx]                          # [K, L]
            whole = jnp.where((kind == 5)[:, None], injected, stepped)
            Xp = jnp.where(
                (kind >= 3)[None, :, None],
                X.at[v, cand, :].set(whole),
                Xp,
            )
            Xp = Xp & vmask[:, None, :]
            solved, new_score = score(
                opcodes, args, imms, widths, pool, roots, roots_mask, Xp
            )
            # greedy accept OR the lane's WalkSAT noise: a worsening
            # move is taken with probability noise[k] — the diverse
            # lanes trade hill-climbing discipline for basin escape.
            # A solving move is always taken.
            accept = (
                (new_score >= cur_score)
                | (jax.random.uniform(kn, (K,)) < noise)
                | solved
            )
            X = jnp.where(accept[None, :, None], Xp, X)
            cur_score = jnp.where(accept, new_score, cur_score)
            improved = new_score > best_score
            best_score = jnp.maximum(best_score, new_score)
            stall = jnp.where(improved | solved, 0, stall + 1)
            # Luby-schedule restarts: a lane stalled past its current
            # budget reseeds with fresh pseudo-random state and
            # advances its Luby counters — nonconverged lanes get
            # diverse restarts instead of grinding one basin for the
            # whole step budget. The reseed is a cheap multiplicative
            # mix of the step's draw (per-lane, per-limb) XORed over
            # every variable — decorrelating without paying a full
            # (V, K, L) threefry each iteration.
            budget = lub_v * RESTART_BASE
            restart = (stall >= budget) & jnp.logical_not(solved)
            mix = (
                (bits * jnp.uint32(0x9E3779B9))[:, None]
                ^ (
                    jnp.arange(L, dtype=jnp.uint32)
                    + jnp.uint32(1)
                )[None, :]
                * jnp.uint32(0x85EBCA6B)
            )  # [K, L]
            Xf = (X ^ mix[None, :, :]) & vmask[:, None, :]
            X = jnp.where(restart[None, :, None], Xf, X)
            # force the next move's acceptance on restarted lanes: the
            # fresh point's true score is learned on the next eval
            cur_score = jnp.where(
                restart, jnp.int32(-(1 << 30)), cur_score
            )
            stall = jnp.where(restart, 0, stall)
            # O(1) Luby advance: (u & -u) == v -> (u+1, 1), else (u, 2v)
            last = (lub_u & (-lub_u)) == lub_v
            lub_u = jnp.where(
                restart & last, lub_u + 1, lub_u
            )
            lub_v = jnp.where(
                restart, jnp.where(last, 1, lub_v * 2), lub_v
            )
            return (
                X, cur_score, best_score, key, it + 1, solved.any(),
                stall, lub_u, lub_v,
            )

        def cond(state):
            it, done = state[4], state[5]
            return jnp.logical_and(it < steps, jnp.logical_not(done))

        zeros_k = jnp.zeros((K,), dtype=jnp.int32)
        ones_k = jnp.ones((K,), dtype=jnp.int32)
        state = jax.lax.while_loop(
            cond,
            body,
            (
                X, score0, score0, k2, jnp.int32(0), solved0.any(),
                zeros_k, ones_k, ones_k,
            ),
        )
        X = state[0]
        solved, final_score = score(
            opcodes, args, imms, widths, pool, roots, roots_mask, X
        )
        # a solved lane always beats the best soft score: noisy lanes
        # may sit above an unsolved-but-sweet basin
        winner = jnp.argmax(
            final_score + jnp.where(solved, jnp.int32(1 << 30), 0)
        )
        return solved[winner], X[:, winner, :]

    import jax as _jax

    fn = _jax.jit(search)
    fn.score = _jax.jit(score)
    fn.raw = search  # unjitted form, for the batched dispatch
    _eval_cache[key] = fn
    return fn


def debug_eval(prog: Program, assignment: Dict[str, int], candidates: int = 2):
    """Evaluate a compiled program under one host assignment; returns
    (solved, soft_score) — a test/debug window into the interpreter."""
    import jax.numpy as jnp

    K = candidates
    L = prog.limbs
    X = np.zeros((len(prog.var_slots), K, L), dtype=np.uint32)
    for slot, (name, _w) in enumerate(prog.var_slots):
        value = assignment.get(name, 0)
        for j in range(L):
            X[slot, :, j] = (value >> (LIMB_BITS * j)) & LIMB_MASK
    fn = _get_search_fn(K, L, 1)
    solved, score = fn.score(
        jnp.asarray(prog.opcodes),
        jnp.asarray(prog.args),
        jnp.asarray(prog.imms),
        jnp.asarray(prog.widths),
        jnp.asarray(prog.const_pool),
        jnp.asarray(prog.roots),
        jnp.asarray(prog.roots_mask),
        jnp.asarray(X),
    )
    return bool(solved[0]), int(score[0])


def _program_args(prog: Program):
    import jax.numpy as jnp

    var_widths = np.array(
        [w for _, w in prog.var_slots], dtype=np.int32
    )
    return (
        jnp.asarray(prog.opcodes),
        jnp.asarray(prog.args),
        jnp.asarray(prog.imms),
        jnp.asarray(prog.widths),
        jnp.asarray(prog.const_pool),
        jnp.asarray(prog.roots),
        jnp.asarray(prog.roots_mask),
        jnp.asarray(var_widths),
    )


def _decode_assignment(
    prog: Program, winner, limbs: Optional[int] = None
) -> Dict[str, int]:
    assignment: Dict[str, int] = {}
    for slot, (name, _w) in enumerate(prog.var_slots):
        value = 0
        for j in range(limbs or prog.limbs):
            value |= int(winner[slot, j]) << (LIMB_BITS * j)
        assignment[name] = value
    return assignment


def _stacked_args(live: List[Tuple[int, Program]], Q: int):
    """The batched search kernel's arguments for `live` programs, Q
    rows -> (args, L): every other stacked axis padded to one shared
    bucket."""
    import jax.numpy as jnp

    # One shared shape bucket: every stacked axis padded to the max
    # bucket over the batch, so the batched kernel compiles once per
    # (Q, N, C, R, V, L, K, steps) class rather than once per query.
    N = max(p.opcodes.shape[0] for _, p in live)
    C = max(p.const_pool.shape[0] for _, p in live)
    R = max(p.roots.shape[0] for _, p in live)
    V = _bucket(max(len(p.var_slots) for _, p in live), 4)
    L = max(p.limbs for _, p in live)

    def stack(getter, shape, dtype, fill=0):
        arr = np.full((Q,) + shape, fill, dtype=dtype)
        for qi, (_, p) in enumerate(live):
            src = getter(p)
            arr[qi][tuple(slice(0, s) for s in src.shape)] = src
        # Q-padding rows repeat the first program (their results are
        # ignored) so the kernel never sees degenerate zero programs.
        for qi in range(len(live), Q):
            src = getter(live[0][1])
            arr[qi][tuple(slice(0, s) for s in src.shape)] = src
        return jnp.asarray(arr)

    def widen_pool(p: Program):
        # const pools narrower than the bucket's limb count re-expand
        # from the original values' limbs: higher limbs are zero by
        # construction (values fit the program's own width cap)
        if p.const_pool.shape[1] == L:
            return p.const_pool
        wide = np.zeros((p.const_pool.shape[0], L), dtype=np.uint32)
        wide[:, : p.const_pool.shape[1]] = p.const_pool
        return wide

    args = (
        stack(lambda p: p.opcodes, (N,), np.int32),
        stack(lambda p: p.args, (N, 3), np.int32),
        stack(lambda p: p.imms, (N, 2), np.int32),
        stack(lambda p: p.widths, (N,), np.int32, fill=1),
        stack(widen_pool, (C, L), np.uint32),
        stack(lambda p: p.roots, (R,), np.int32),
        stack(lambda p: p.roots_mask, (R,), bool),
        stack(
            lambda p: np.array([w for _, w in p.var_slots], dtype=np.int32),
            (V,),
            np.int32,
            fill=1,
        ),
        # each query's REAL var count, so the search never mutates its
        # padding slots
        jnp.asarray(
            [len(p.var_slots) for _, p in live]
            + [len(live[0][1].var_slots)] * (Q - len(live)),
            dtype=jnp.int32,
        ),
        # ... and its REAL const count, so polarity seeding and the
        # injection move never draw zero padding rows
        jnp.asarray(
            [getattr(p, "n_consts", 1) for _, p in live]
            + [getattr(live[0][1], "n_consts", 1)] * (Q - len(live)),
            dtype=jnp.int32,
        ),
    )

    return args, L


def _search_rows(search):
    """The batched search over stacked queries: one query after another
    (lax.map), each row's search skipped when its `live` flag is off.

    Not vmap: a vmapped query's opcodes are batched, so the
    interpreter's `lax.switch` evaluates EVERY branch at every node and
    selects — including the 256-step long division of udiv/urem at all
    of a program's nodes, on every one of its up to `steps` evaluations.
    On one v5e chip that made a 12-query flip-funnel dispatch run for
    minutes (PR 21); mapped, the opcode stays a scalar, the switch runs
    only the taken branch, and each query's while-loop ends when it
    solves, not when the slowest query of the batch does."""
    import jax
    import jax.numpy as jnp

    def one(row):
        *args, seed, live = row
        V, L = args[7].shape[0], args[4].shape[-1]
        return jax.lax.cond(
            live,
            lambda: search(*args, seed),
            lambda: (jnp.bool_(False), jnp.zeros((V, L), jnp.uint32)),
        )

    return lambda *stacked: jax.lax.map(one, stacked)


#: queries per batched search dispatch: every dispatch stacks exactly
#: this many rows (one compiled shape), and the owner's stop is checked
#: between dispatches
SEARCH_ROWS = 16


def _sls_batch(
    live: List[Tuple[int, Program]],
    candidates: int = 64,
    steps: int = 512,
    seed: int = 7,
    n_devices: int = 1,
    devices=None,
    should_stop=None,
) -> Dict[int, Dict[str, int]]:
    """Batched diversified SLS over many compiled programs: they stack
    on a leading axis, SEARCH_ROWS per dispatch, every other stacked
    axis padded to the max bucket over the batch, and each dispatch
    searches its rows in turn (`_search_rows`), K heterogeneous
    candidates each. With n_devices > 1 the rows shard over the
    devices (pmap over row chunks); an explicit `devices` list pins
    the shards to a scheduler group's own chips. `should_stop()` is
    checked before every dispatch; the rows not yet dispatched stay
    unsolved. Returns {live index: raw assignment} for solved entries
    (decoded, NOT yet validated)."""
    out: Dict[int, Dict[str, int]] = {}
    if not live:
        return out
    if len(live) == 1:
        i, prog = live[0]
        asn = device_check(
            prog.source, candidates, steps, seed,
            n_devices=n_devices, prog=prog,
        )
        if asn is not None:
            out[i] = asn
        return out

    import jax
    import jax.numpy as jnp

    Q = SEARCH_ROWS
    # largest power-of-two device count that divides Q, clamped to the
    # devices that actually exist
    pool = list(devices) if devices else list(jax.devices())
    D = 1
    avail = min(n_devices, len(pool), Q)
    while D * 2 <= avail:
        D *= 2
    for start in range(0, len(live), Q):
        if should_stop is not None and should_stop():
            break
        rows = live[start : start + Q]
        args, L = _stacked_args(rows, Q)
        fn = _get_search_fn(candidates, L, steps)
        args += (
            jnp.arange(seed + start, seed + start + Q, dtype=jnp.int32),
            jnp.arange(Q) < len(rows),  # padding rows skip their search
        )
        if D > 1:
            pkey = (
                "pmap-rows", candidates, L, steps, D,
                tuple(str(d) for d in pool[:D]),
            )
            pfn = _eval_cache.get(pkey)
            if pfn is None:
                pfn = jax.pmap(_search_rows(fn.raw), devices=pool[:D])
                _eval_cache[pkey] = pfn
            chunk = lambda a: a.reshape((D, Q // D) + a.shape[1:])
            solved, winners = pfn(*(chunk(a) for a in args))
            solved = np.asarray(solved).reshape(Q)
            winners = np.asarray(winners).reshape((Q,) + winners.shape[2:])
        else:
            vkey = ("rows", candidates, L, steps)
            vfn = _eval_cache.get(vkey)
            if vfn is None:
                vfn = jax.jit(_search_rows(fn.raw))
                _eval_cache[vkey] = vfn
            solved, winners = vfn(*args)
            solved = np.asarray(solved)
            winners = np.asarray(winners)
        for qi, (i, p) in enumerate(rows):
            if bool(solved[qi]):
                out[i] = _decode_assignment(p, winners[qi], limbs=L)
    return out


def validate_witness(prog: Program, assignment: Dict[str, int]) -> bool:
    """Host-side concrete validation: the decoded device model must
    satisfy every constraint the program was compiled FROM. A
    corrupted device model (transfer fault, decode bug, an
    interpreter divergence) fails here and is discarded — a device
    SAT never counts unvalidated. For segmented programs this covers
    the kept subset (the full set is re-checked by the caller's
    soundness gate or by concrete execution of the witness)."""
    from mythril_tpu.laser.smt.evalterm import eval_term

    try:
        return all(eval_term(c, assignment) for c in prog.source)
    except Exception:
        return False


# ---------------------------------------------------------------------------
# cube-and-conquer + exhaustive enumeration
# ---------------------------------------------------------------------------


def rank_impact_vars(
    prog: Program, probes: int = 16, seed: int = 11
) -> List[int]:
    """Variable slots ranked by estimated soft-score GRADIENT: over a
    probe batch of random assignments, the mean |Δ soft score| of
    re-randomizing ONE variable — the same gradient signal the SLS
    accept rule climbs. Hard queries cube on the top of this ranking
    (a high-gradient variable partitions the score landscape most)."""
    import jax.numpy as jnp

    V = len(prog.var_slots)
    if V == 0:
        return []
    if V > 64 or prog.n_real_nodes > 512:
        # gradient probing costs one program eval per var; past this
        # var count — or on programs big enough that each eval is
        # itself expensive — fall back to reference counting
        return _occurrence_rank(prog)
    rng = np.random.RandomState(seed)
    L = prog.limbs
    K = probes
    fn = _get_search_fn(K, L, 1)
    base_args = _program_args(prog)[:7]

    def rand_rows(n):
        return rng.randint(0, 1 << LIMB_BITS, size=(n, K, L)).astype(
            np.uint32
        )

    X = rand_rows(V)
    # clamp to var widths
    for v, (_n, w) in enumerate(prog.var_slots):
        for j in range(L):
            bits = max(0, min(LIMB_BITS, w - j * LIMB_BITS))
            X[v, :, j] &= (1 << bits) - 1
    _, base = fn.score(*base_args, jnp.asarray(X))
    base = np.asarray(base, dtype=np.int64)
    impact = np.zeros(V, dtype=np.float64)
    for v in range(V):
        X2 = X.copy()
        row = rand_rows(1)[0]
        w = prog.var_slots[v][1]
        for j in range(L):
            bits = max(0, min(LIMB_BITS, w - j * LIMB_BITS))
            row[:, j] &= (1 << bits) - 1
        X2[v] = row
        _, s2 = fn.score(*base_args, jnp.asarray(X2))
        impact[v] = np.abs(np.asarray(s2, dtype=np.int64) - base).mean()
    return list(np.argsort(-impact, kind="stable"))


def _occurrence_rank(prog: Program) -> List[int]:
    """Cheap fallback ranking: how often each var slot is referenced
    (via its var node) by other nodes."""
    opcodes = np.asarray(prog.opcodes)
    arg_idx = np.asarray(prog.args)
    imms = np.asarray(prog.imms)
    var_op = OP_INDEX["var"]
    n = prog.n_real_nodes
    node_slot = np.full(opcodes.shape[0], -1, dtype=np.int64)
    var_nodes = opcodes[:n] == var_op
    node_slot[:n][var_nodes] = imms[:n, 0][var_nodes]
    counts = np.zeros(len(prog.var_slots), dtype=np.int64)
    for k in range(3):
        ref = node_slot[arg_idx[:n, k]]
        for s in ref[ref >= 0]:
            counts[s] += 1
    return list(np.argsort(-counts, kind="stable"))


def cube_queries(
    lowered: List[Term],
    prog: Program,
    depth: Optional[int] = None,
    ranked: Optional[List[int]] = None,
) -> List[List[Term]]:
    """Split a hard query into 2^depth CUBE queries: the top-impact
    variables' low bits pinned to every combination via extra
    equality roots. The cubes PARTITION the original search space —
    any cube witness is an original witness, and the union of the
    cubes' spaces is exactly the original's (the merge direction the
    solverperf roundtrip test pins). Returns [] when the program has
    no rankable variables."""
    if depth is None:
        depth = int(PORTFOLIO_DEFAULTS["cube_depth"])
    if depth <= 0 or not prog.var_slots:
        return []
    if ranked is None:
        ranked = rank_impact_vars(prog)
    # pin bits round-robin over the ranked variables (bit 0 of the
    # top-impact var, bit 0 of the next, ... then bit 1 of the top
    # var, ...) until `depth` bits — so a two-variable query still
    # splits 2^depth ways
    pins: List[Tuple[str, int, int]] = []  # (name, width, bit index)
    bit_round = 0
    while len(pins) < depth:
        took = False
        for slot in ranked:
            if len(pins) >= depth:
                break
            name, w = prog.var_slots[slot]
            if bit_round < w:
                pins.append((name, w, bit_round))
                took = True
        if not took:
            break  # every variable's bits are exhausted
        bit_round += 1
    if not pins:
        return []
    depth = len(pins)
    out: List[List[Term]] = []
    for m in range(1 << depth):
        extra: List[Term] = []
        for b, (name, w, bit_idx) in enumerate(pins):
            bit = (m >> b) & 1
            var = terms.bv_var(name, w)
            if w == 1:
                extra.append(terms.eq(var, terms.bv_const(bit, 1)))
            else:
                extra.append(
                    terms.eq(
                        terms.extract(bit_idx, bit_idx, var),
                        terms.bv_const(bit, 1),
                    )
                )
        out.append(list(lowered) + extra)
    return out


def enum_space_bits(prog: Program) -> int:
    """Total bits across the program's variable slots — the size of
    the exhaustive search space (2^bits assignments)."""
    return sum(w for _, w in prog.var_slots)


def device_enumerate(
    prog: Program,
    enum_bits: Optional[int] = None,
    cube_bits: Optional[int] = None,
    n_devices: int = 1,
) -> Tuple[str, Optional[Dict[str, int]]]:
    """COMPLETE check by exhaustive enumeration: every assignment of a
    small variable space is evaluated on device, in cube-sized chunks
    — the index space is cut on the top-impact variables' bits (each
    chunk one cube), chunks fan across the batch and, with
    n_devices > 1, across a mesh group. A found witness is sat; an
    EXHAUSTED space is a device-owned unsat verdict — the portfolio's
    only complete mode. Segmented (incomplete) programs and spaces
    past enum_bits + cube_bits return ("unknown", None).
    """
    if enum_bits is None:
        enum_bits = int(PORTFOLIO_DEFAULTS["enum_bits"])
    if cube_bits is None:
        cube_bits = int(PORTFOLIO_DEFAULTS["enum_cube_bits"])
    B = enum_space_bits(prog)
    if (
        not prog.var_slots
        or not getattr(prog, "complete", True)
        or B == 0
        or B > enum_bits + cube_bits
    ):
        return "unknown", None

    import jax
    import jax.numpy as jnp

    # bit layout: top-impact vars take the HIGH bits, so the chunk
    # index enumerates cubes over exactly the variables a split-based
    # solver would branch on first
    ranked = _occurrence_rank(prog)
    offsets: Dict[int, int] = {}
    top = B
    for slot in ranked:
        w = prog.var_slots[slot][1]
        top -= w
        offsets[slot] = top
    # chunk size bucketed to ONE shape class per limb count: tiny
    # spaces pad up (duplicate assignments are harmless), large spaces
    # split into 2^(B - chunk_bits) cube chunks
    chunk_bits = min(B, int(PORTFOLIO_DEFAULTS["enum_chunk_bits"]))
    K = max(1 << chunk_bits, 1024)
    n_chunks = 1 << (B - chunk_bits)
    space = 1 << B
    L = prog.limbs
    V = len(prog.var_slots)
    fn = _get_search_fn(K, L, 1)
    base_args = _program_args(prog)[:7]

    def chunk_X(ci: int) -> np.ndarray:
        idx = (
            (ci << chunk_bits) + np.arange(K, dtype=np.uint64)
        ) % np.uint64(space)
        X = np.zeros((V, K, L), dtype=np.uint32)
        for v, (_name, w) in enumerate(prog.var_slots):
            vals = (idx >> np.uint64(offsets[v])) & np.uint64(
                (1 << w) - 1
            )
            for j in range((w + LIMB_BITS - 1) // LIMB_BITS):
                X[v, :, j] = (
                    (vals >> np.uint64(LIMB_BITS * j))
                    & np.uint64(LIMB_MASK)
                ).astype(np.uint32)
        return X

    # dispatch every cube chunk before blocking on any: with
    # n_devices > 1 the chunks round-robin over the mesh group's
    # devices (the computation follows its committed input), so the
    # cube fan genuinely runs the lattice in parallel
    pool = jax.devices()
    D = min(max(1, n_devices), len(pool), n_chunks)
    pending = []
    for ci in range(n_chunks):
        xin = jnp.asarray(chunk_X(ci))
        if D > 1:
            xin = jax.device_put(xin, pool[ci % D])
        pending.append((ci, fn.score(*base_args, xin)))
    for ci, (solved, _score) in pending:
        solved = np.asarray(solved)
        if solved.any():
            k = int(np.argmax(solved))
            return "sat", _decode_assignment(prog, chunk_X(ci)[:, k, :])
    return "unsat", None


def device_solve_batch(
    queries: List[List[Term]],
    candidates: int = 64,
    steps: Optional[int] = None,
    seed: int = 7,
    n_devices: int = 1,
    devices=None,
    cube_depth: Optional[int] = None,
    should_stop=None,
) -> List[DeviceVerdict]:
    """The device-FIRST solving funnel for a batch of independent
    queries (ISSUE 9): the accelerator attacks the whole batch before
    any host CDCL sees a single query, and returns a TYPED verdict
    per position so callers escalate only genuine unknowns.

    Stages, all device-side:

    1. compile — segmented (`compile_program_relaxed`) so partial
       device-language coverage still searches; uncompilable queries
       come back unknown with the compile loss.
    2. enumerate — complete programs over small variable spaces are
       exhaustively evaluated in cube-sized chunks: sat witnesses AND
       device-owned unsat-within-bucket verdicts.
    3. diversified SLS — one batched dispatch of the heterogeneous
       batched portfolio over everything else.
    4. cube-and-conquer — SLS survivors split into 2^depth cubes on
       their top-impact (soft-score gradient) variables; the cube fan
       rides a second batched dispatch, sharded over `devices`.

    Every sat is host-validated (`validate_witness`) before it
    counts; a corrupted device model degrades to unknown with
    WITNESS_INVALID, never to a wrong verdict.

    `should_stop()`, checked before every search dispatch (stages 3
    and 4, SEARCH_ROWS queries each) and every cube split, lets an
    owner winding down skip the work not yet started: a dispatch
    cannot be interrupted once it runs. Skipped queries stay unknown.
    """
    from mythril_tpu.observe import querylog

    if not queries:
        return []
    if steps is None:
        steps = int(PORTFOLIO_DEFAULTS["first_pass_steps"])
    if cube_depth is None:
        cube_depth = int(PORTFOLIO_DEFAULTS["cube_depth"])

    out: List[DeviceVerdict] = [
        DeviceVerdict("unknown", None, querylog.LOSS_SLS_NONCONVERGED, None)
        for _ in queries
    ]
    progs: List[Optional[Program]] = [None] * len(queries)
    sls_live: List[Tuple[int, Program]] = []
    for i, q in enumerate(queries):
        prog, _dropped, loss = compile_program_relaxed(q)
        if prog is None or not prog.var_slots:
            out[i] = DeviceVerdict(
                "unknown",
                None,
                loss or querylog.LOSS_QUERY_TRIVIAL,
                None,
            )
            continue
        progs[i] = prog
        # stage 2: complete small spaces enumerate outright — the
        # device owns unsat here, not just sat
        verdict, asn = device_enumerate(prog, n_devices=n_devices)
        if verdict == "sat":
            if validate_witness(prog, asn):
                out[i] = DeviceVerdict("sat", asn, None, "enum")
            else:
                out[i] = DeviceVerdict(
                    "unknown", None, querylog.LOSS_WITNESS_INVALID, "enum"
                )
            continue
        if verdict == "unsat":
            out[i] = DeviceVerdict("unsat", None, None, "enum")
            continue
        sls_live.append((i, prog))

    stopped = should_stop or (lambda: False)
    # stage 3: batched diversified-SLS dispatches over the remainder
    found = _sls_batch(
        sls_live, candidates, steps, seed,
        n_devices=n_devices, devices=devices, should_stop=stopped,
    )
    survivors: List[Tuple[int, Program]] = []
    for i, prog in sls_live:
        asn = found.get(i)
        if asn is None:
            survivors.append((i, prog))
        elif validate_witness(prog, asn):
            out[i] = DeviceVerdict("sat", asn, None, "sls")
        else:
            out[i] = DeviceVerdict(
                "unknown", None, querylog.LOSS_WITNESS_INVALID, "sls"
            )

    # stage 4: cube-and-conquer the survivors — 2^depth pinned-bit
    # cubes per query, fanned in more batched dispatches
    if cube_depth > 0 and survivors:
        cube_live: List[Tuple[int, Program]] = []
        parents: List[int] = []
        for i, prog in survivors:
            if stopped():
                return out
            for cq in cube_queries(prog.source, prog, depth=cube_depth):
                cprog = compile_program(cq)
                if cprog is None or not cprog.var_slots:
                    continue
                cprog.complete = prog.complete
                cube_live.append((len(parents), cprog))
                parents.append(i)
        cfound = _sls_batch(
            cube_live, candidates, steps, seed + 7919,
            n_devices=n_devices, devices=devices, should_stop=stopped,
        )
        for ci, cprog in cube_live:
            i = parents[ci]
            if out[i].status == "sat":
                continue
            asn = cfound.get(ci)
            if asn is not None and validate_witness(cprog, asn):
                out[i] = DeviceVerdict("sat", asn, None, "cube")
    return out


def device_check_batch(
    queries: List[List[Term]],
    candidates: int = 64,
    steps: int = 512,
    seed: int = 7,
    n_devices: int = 1,
) -> List[Optional[Dict[str, int]]]:
    """Solve MANY independent queries in ONE device dispatch (the
    assignment-only legacy surface over `device_solve_batch`).

    Returns one Optional assignment per query, position-aligned.
    Queries that fall outside the device language come back None
    (which, as always, proves nothing — use `device_solve_batch` for
    the typed verdicts, including device-owned unsat)."""
    verdicts = device_solve_batch(
        queries,
        candidates=candidates,
        steps=steps,
        seed=seed,
        n_devices=n_devices,
    )
    return [v.assignment if v.status == "sat" else None for v in verdicts]


def device_check(
    lowered: List[Term],
    candidates: int = 64,
    steps: int = 512,
    seed: int = 7,
    n_devices: int = 1,
    prog: Optional[Program] = None,
) -> Optional[Dict[str, int]]:
    """Try to find a witness for `lowered` on device. Returns a
    {var_name: value} assignment, or None (which proves nothing).

    With n_devices > 1 the search runs as a true portfolio: one
    independent replica per device (pmap over seeds), any replica's
    witness wins — the multi-chip scaling axis for hard queries.
    Callers that already compiled `lowered` pass `prog` to skip the
    recompile (device_check_batch's single-survivor fallback).
    """
    if prog is None:
        prog = compile_program(lowered)
    if prog is None or not prog.var_slots:
        return None

    import jax
    import jax.numpy as jnp

    fn = _get_search_fn(candidates, prog.limbs, steps)
    prog_args = _program_args(prog)

    n_vars = len(prog.var_slots)
    n_consts = getattr(prog, "n_consts", 1)
    n_devices = min(n_devices, len(jax.devices()))
    if n_devices > 1:
        pkey = ("pmap", candidates, prog.limbs, steps, n_devices)
        replicated = _eval_cache.get(pkey)
        if replicated is None:
            # in_axes: program arrays broadcast, seeds split per device
            replicated = jax.pmap(
                fn,
                devices=jax.devices()[:n_devices],
                in_axes=(None,) * 10 + (0,),
            )
            _eval_cache[pkey] = replicated
        seeds = jnp.arange(seed, seed + n_devices, dtype=jnp.int32)
        solved_all, winners = replicated(
            *prog_args, n_vars, n_consts, seeds
        )
        solved_all = np.asarray(solved_all)
        if not solved_all.any():
            return None
        winner = np.asarray(winners)[int(np.argmax(solved_all))]
    else:
        solved, winner = fn(*prog_args, n_vars, n_consts, seed)
        if not bool(solved):
            return None
        winner = np.asarray(winner)  # [V, L]

    return _decode_assignment(prog, winner)
