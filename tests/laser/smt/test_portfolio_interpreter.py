"""Device-interpreter differential: the portfolio solver's tensor
program must agree with the host evaluator op by op.

compile_program + debug_eval evaluate a constraint under a forced
assignment on device (CPU backend here; identical lowering on TPU);
solved/score must match host evaluation for every sampled input —
including the compiled signed rewrites (slt/sle via sign-bit xor,
sext via xor-sub, ashr via sign-fill masks).
"""

import random

import pytest

from mythril_tpu.laser.smt import terms
from mythril_tpu.laser.smt.evalterm import eval_term
from mythril_tpu.laser.smt.solver.portfolio import compile_program, debug_eval

W = 32
EDGES = [0, 1, 2, (1 << W) - 1, (1 << W) - 2, 1 << (W - 1), (1 << (W - 1)) - 1, 0xDEADBEEF % (1 << W)]
RNG = random.Random(99)
SAMPLES = [(x, y) for x in EDGES for y in EDGES[:4]] + [
    (RNG.getrandbits(W), RNG.getrandbits(W)) for _ in range(24)
]

OPS = {
    "add": terms.add,
    "sub": terms.sub,
    "mul": terms.mul,
    "udiv": terms.udiv,
    # lowered to a shift (compile_program): the selector pattern
    "udiv-pow2": lambda a, b: terms.add(
        terms.udiv(a, terms.bv_const(1 << 24, W)), b
    ),
    "udiv-1": lambda a, b: terms.add(
        terms.udiv(a, terms.bv_const(1, W)), b
    ),
    "urem": terms.urem,
    "and": terms.bvand,
    "or": terms.bvor,
    "xor": terms.bvxor,
    "shl": terms.shl,
    "lshr": terms.lshr,
    "ashr": terms.ashr,
    "concat-extract": lambda a, b: terms.extract(
        W, 1, terms.concat(a, b)
    ),
    "sext": lambda a, b: terms.add(
        terms.sext(terms.extract(7, 0, a), W - 8), b
    ),
    "ite(slt)": lambda a, b: terms.ite(
        terms.slt(a, b), terms.add(a, b), terms.bvxor(a, b)
    ),
    "ule-word": lambda a, b: terms.ite(
        terms.ule(a, b), terms.bv_const(1, W), terms.bv_const(2, W)
    ),
    "sle-word": lambda a, b: terms.ite(
        terms.sle(a, b), terms.bv_const(1, W), terms.bv_const(2, W)
    ),
}


@pytest.mark.parametrize("name", sorted(OPS))
def test_device_op_matches_host(name):
    build = OPS[name]
    x_t = terms.bv_var(f"dp_{name}_x", W)
    y_t = terms.bv_var(f"dp_{name}_y", W)
    expr = build(x_t, y_t)

    for xv, yv in SAMPLES:
        asn = {x_t.args[0]: xv, y_t.args[0]: yv}
        want = eval_term(expr, asn)
        # constraint "expr == want" must be satisfied under the forced
        # assignment; "expr == want+1" must not
        prog_eq = compile_program([terms.eq(expr, terms.bv_const(want, W))])
        assert prog_eq is not None, name
        solved, _ = debug_eval(prog_eq, asn)
        assert solved, f"{name}({xv},{yv}): device disagrees with host ({want})"

        wrong = (want + 1) % (1 << W)
        prog_ne = compile_program([terms.eq(expr, terms.bv_const(wrong, W))])
        solved_wrong, _ = debug_eval(prog_ne, asn)
        assert not solved_wrong, f"{name}({xv},{yv}): device accepts wrong value"


def test_udiv_by_power_of_two_compiles_to_a_shift():
    from mythril_tpu.laser.smt.solver.portfolio import OP_INDEX

    x = terms.bv_var("dp_pow2_x", 256)
    sel = terms.udiv(x, terms.bv_const(1 << 224, 256))
    prog = compile_program([terms.eq(sel, terms.bv_const(0xA9059CBB, 256))])
    ops = set(prog.opcodes[: prog.n_real_nodes].tolist())
    assert OP_INDEX["udiv"] not in ops and OP_INDEX["extract"] in ops
    solved, _ = debug_eval(prog, {"dp_pow2_x": 0xA9059CBB << 224 | 5})
    assert solved
