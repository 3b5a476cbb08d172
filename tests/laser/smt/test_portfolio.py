"""On-chip portfolio solver tests (CPU backend; the compiled program
and local search run identically on TPU)."""

import pytest

from mythril_tpu.laser.smt import ULT, symbol_factory
from mythril_tpu.laser.smt.evalterm import eval_term
from mythril_tpu.laser.smt.solver.portfolio import (
    compile_program,
    debug_eval,
    device_check,
)
from mythril_tpu.laser.smt.solver.solver import lower


def bv(name, width=256):
    return symbol_factory.BitVecSym(name, width)


def lowered(*constraints):
    out, _ = lower([c.raw for c in constraints])
    return out


def test_interpreter_matches_host_eval():
    x, y = bv("px", 64), bv("py", 64)
    cons = lowered(x + y == 100, ULT(x, y), x * 2 == y - 10)
    prog = compile_program(cons)
    assert prog is not None
    # x=30, y=70: 30+70=100, 30<70, 60 == 60
    solved, _ = debug_eval(prog, {"px": 30, "py": 70})
    assert solved
    solved_bad, _ = debug_eval(prog, {"px": 31, "py": 69})
    assert not solved_bad


def test_soft_score_gradient():
    x = bv("gx", 64)
    prog = compile_program(lowered(x + 5 == 12))
    _, perfect = debug_eval(prog, {"gx": 7})
    _, close = debug_eval(prog, {"gx": 6})  # 11 vs 12: 3 bits differ
    # 0xAAAA..AA + 5 differs from 12 in ~half of all 64 bits
    _, far = debug_eval(prog, {"gx": 0xAAAA_AAAA_AAAA_AAAA})
    assert perfect > close > far


def test_search_finds_linear_witness():
    x = bv("sx", 64)
    cons = lowered(x + 5 == 12)
    asn = device_check(cons, candidates=64, steps=4096)
    assert asn is not None
    assert all(eval_term(c, asn) for c in cons)


def test_search_finds_multi_constraint_witness():
    y = bv("sy", 32)
    cons = lowered(y * 3 == 21, ULT(y, 100))
    asn = device_check(cons, candidates=64, steps=4096)
    assert asn is not None
    assert all(eval_term(c, asn) for c in cons)


def test_witness_never_lies():
    """device_check output must always satisfy the constraints (run a
    few shapes; None is acceptable, a wrong witness is not)."""
    a, b = bv("wa", 64), bv("wb", 64)
    for cons in [
        lowered(a - b == 3, ULT(b, 1000)),
        lowered((a & 0xFF) == 0x42),
        lowered(a == b, ULT(a, 10)),
    ]:
        asn = device_check(cons, candidates=32, steps=1024)
        if asn is not None:
            assert all(eval_term(c, asn) for c in cons)


def test_unsupported_ops_return_none():
    from mythril_tpu.laser.smt import terms

    # a raw select is outside the device language (lower() normally
    # removes arrays; feed one directly)
    arr = terms.array_var("A", 256, 256)
    sel = terms.select(arr, terms.bv_var("i", 256))
    cons = [terms.eq(sel, terms.bv_const(5, 256))]
    assert compile_program(cons) is None

# slow tier: ~30 s of full-budget portfolio grinding per test on a
# 1-core host; the multichip suite keeps a fast batched-solve pin
@pytest.mark.slow
def test_batched_dispatch_alignment():
    """device_check_batch answers each query independently in one
    dispatch: results are position-aligned, every returned witness
    satisfies ITS OWN query, and device-language dropouts come back
    None without disturbing their neighbours."""
    from mythril_tpu.laser.smt import terms
    from mythril_tpu.laser.smt.solver.portfolio import device_check_batch

    x, y, z = bv("bx", 64), bv("by", 32), bv("bz", 16)
    queries = [
        lowered(x + 5 == 12),
        lowered(y * 3 == 21, ULT(y, 100)),
        # outside the device language: raw select survives lowering here
        # because it is injected directly
        [
            terms.eq(
                terms.select(
                    terms.array_var("B", 256, 256), terms.bv_var("i", 256)
                ),
                terms.bv_const(5, 256),
            )
        ],
        lowered((z & 0xFF) == 0x42),
    ]
    out = device_check_batch(queries, candidates=64, steps=4096)
    assert len(out) == len(queries)
    assert out[2] is None
    for q, asn in zip(queries, out):
        if asn is None:
            continue
        assert all(eval_term(c, asn) for c in q)
    # the easy linear queries must actually be solved, not skipped
    assert out[0] is not None and out[1] is not None and out[3] is not None


def test_batched_matches_single():
    """A query solved through the batch decodes to a witness exactly as
    valid as the per-query path's."""
    from mythril_tpu.laser.smt.solver.portfolio import device_check_batch

    a, b = bv("ma", 64), bv("mb", 64)
    cons = lowered(a - b == 3, ULT(b, 1000))
    single = device_check(cons, candidates=64, steps=4096)
    batched = device_check_batch([cons, cons], candidates=64, steps=4096)
    for asn in [single] + list(batched):
        if asn is not None:
            assert all(eval_term(c, asn) for c in cons)


@pytest.mark.slow
def test_batched_dispatch_sharded_over_devices():
    """The query axis shards over a device mesh (pmap of the batched
    search): same aligned answers, each device solving its chunk."""
    import jax

    from mythril_tpu.laser.smt.solver.portfolio import device_check_batch

    if jax.device_count() < 2:
        pytest.skip("needs a multi-device mesh")
    qs = [bv(f"sh{i}", 32) for i in range(4)]
    queries = [lowered(q * 3 == 21 + 3 * i) for i, q in enumerate(qs)]
    out = device_check_batch(
        queries, candidates=32, steps=2048, n_devices=jax.device_count()
    )
    assert len(out) == len(queries)
    solved = 0
    for q, asn in zip(queries, out):
        if asn is not None:
            assert all(eval_term(c, asn) for c in q)
            solved += 1
    assert solved >= 1
