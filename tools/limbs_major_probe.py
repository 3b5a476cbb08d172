"""Limbs-major layout probe: the three hot step-kernel phases in both
data layouts, measured by wall clock AND compiled-segment count.

Motivation (docs/roadmap.md, historical): the remote-chip link used
before PR 21 paid a fixed ~ms-scale
cost per unfused kernel segment inside compiled loops, so segment
count — not FLOPs — sets the step kernel's throughput there, while on
clean hardware the same kernels are bandwidth-bound. The candidate
layout change moves 256-bit words from lanes-major [N, S, W] (W=16
limbs in the 128-wide vector minor: 1/8 utilization) to limbs-major
[W, S, N] (lanes in the vector minor: full utilization, and the stack
peek becomes a one-hot contraction the MXU can take).

Phases probed:
  peek     read the lane-indexed top-of-stack word
  scatter  consolidated one-hot stack write (the step kernel's single
           fused write pass)
  mul      u256 schoolbook multiply

Run:  python tools/limbs_major_probe.py  (TPU when available)
Prints one JSON line per (phase, layout) with per-iteration wall and
the compiled HLO fusion count.
"""

from __future__ import annotations

import json
import sys
import time
from functools import partial
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402

from mythril_tpu.ops import u256  # noqa: E402

N = 4096  # lanes
S = 64  # stack slots
W = u256.LIMBS  # 16-bit limbs per word
ITERS = 64  # loop iterations inside one compiled program
LIMB_MASK = (1 << u256.LIMB_BITS) - 1


# -- lanes-major (the current step-kernel layout) -----------------------
def peek_nm(stack, sp):
    idx = jnp.clip(sp - 1, 0, S - 1)
    return jnp.take_along_axis(
        stack, idx[:, None, None].astype(jnp.int32), axis=1
    )[:, 0, :]


def scatter_nm(stack, sp, val):
    hit = jnp.arange(S)[None, :] == jnp.clip(sp - 1, 0, S - 1)[:, None]
    return jnp.where(hit[:, :, None], val[:, None, :], stack)


def mul_nm(a, b):
    return u256.mul(a, b)


# -- limbs-major [W, S, N] / [W, N] -------------------------------------
def peek_wm(stack, sp):
    onehot = (
        jnp.arange(S)[:, None] == jnp.clip(sp - 1, 0, S - 1)[None, :]
    ).astype(stack.dtype)
    # one-hot contraction over the stack axis: an [S]x[S,N] reduction
    # per limb plane — the shape a systolic array takes directly
    return jnp.einsum("wsn,sn->wn", stack, onehot)


def scatter_wm(stack, sp, val):
    hit = jnp.arange(S)[:, None] == jnp.clip(sp - 1, 0, S - 1)[None, :]
    return jnp.where(hit[None, :, :], val[:, None, :], stack)


def mul_wm(a, b):
    """Schoolbook multiply on limbs-major [W, N] operands — the same
    partial-product and sequential carry-ripple structure as
    u256._schoolbook/_carry so the layouts compare op-for-op."""
    lo = [jnp.zeros((N,), jnp.uint32) for _ in range(W)]
    hi = [jnp.zeros((N,), jnp.uint32) for _ in range(W)]
    for i in range(W):
        for j in range(W - i):
            p = a[i] * b[j]
            k = i + j
            lo[k] = lo[k] + (p & LIMB_MASK)
            hi[k] = hi[k] + (p >> u256.LIMB_BITS)
    sums = [lo[0]] + [lo[k] + hi[k - 1] for k in range(1, W)]
    carry = jnp.zeros((N,), jnp.uint32)
    final = []
    for k in range(W):
        t = sums[k] + carry
        final.append(t & LIMB_MASK)
        carry = t >> u256.LIMB_BITS
    return jnp.stack(final, axis=0)


# -- measurement --------------------------------------------------------
def _loop(phase_fn, state):
    """ITERS dependent applications of the phase inside one program."""

    def body(_, carry):
        return phase_fn(carry)

    return lax.fori_loop(0, ITERS, body, state)


def measure(name, phase_fn, state):
    fn = jax.jit(partial(_loop, phase_fn))
    lowered = fn.lower(state)
    compiled = lowered.compile()
    hlo = compiled.as_text()
    fusions = hlo.count(" fusion(") + hlo.count(" fusion.")
    out = fn(state)  # warm
    jax.tree.map(np.asarray, out)
    t0 = time.perf_counter()
    out = fn(state)
    jax.tree.map(np.asarray, out)  # readback forces completion
    wall = time.perf_counter() - t0
    print(
        json.dumps(
            {
                "phase": name,
                "per_iter_ms": round(1000 * wall / ITERS, 3),
                "hlo_fusions": fusions,
                "lanes": N,
                "iters": ITERS,
                "backend": jax.default_backend(),
            }
        )
    )


def main() -> None:
    rng = np.random.RandomState(7)
    stack_nm = jnp.asarray(
        rng.randint(0, 1 << 16, size=(N, S, W)).astype(np.uint32)
    )
    stack_wm = jnp.transpose(stack_nm, (2, 1, 0))
    sp = jnp.asarray(rng.randint(1, S, size=(N,)).astype(np.int32))
    a_nm = jnp.asarray(rng.randint(0, 1 << 16, size=(N, W)).astype(np.uint32))
    b_nm = jnp.asarray(rng.randint(0, 1 << 16, size=(N, W)).astype(np.uint32))
    a_wm, b_wm = a_nm.T, b_nm.T

    # correctness cross-checks between layouts
    np.testing.assert_array_equal(
        np.asarray(peek_nm(stack_nm, sp)), np.asarray(peek_wm(stack_wm, sp)).T
    )
    np.testing.assert_array_equal(
        np.asarray(scatter_nm(stack_nm, sp, a_nm)),
        np.asarray(scatter_wm(stack_wm, sp, a_wm)).transpose(2, 1, 0),
    )
    np.testing.assert_array_equal(
        np.asarray(mul_nm(a_nm, b_nm)), np.asarray(mul_wm(a_wm, b_wm)).T
    )
    # adversarial carry check: all-0xFFFF operands ripple the full width
    worst = jnp.full((N, W), 0xFFFF, jnp.uint32)
    np.testing.assert_array_equal(
        np.asarray(mul_nm(worst, worst)),
        np.asarray(mul_wm(worst.T, worst.T)).T,
    )

    # peek/mul feed their output back via a rotate so the loop has a
    # real data dependency; scatter feeds the stack through
    measure(
        "peek/lanes-major",
        lambda st: (st[0], jnp.roll(peek_nm(st[0], st[1])[:, 0].astype(jnp.int32) % S + 1, 1)),
        (stack_nm, sp),
    )
    measure(
        "peek/limbs-major",
        lambda st: (st[0], jnp.roll(peek_wm(st[0], st[1])[0].astype(jnp.int32) % S + 1, 1)),
        (stack_wm, sp),
    )
    measure(
        "scatter/lanes-major",
        lambda st: (scatter_nm(st[0], st[1], st[0][:, 0]), st[1] + 1),
        (stack_nm, sp),
    )
    measure(
        "scatter/limbs-major",
        lambda st: (scatter_wm(st[0], st[1], st[0][:, 0]), st[1] + 1),
        (stack_wm, sp),
    )
    measure("mul/lanes-major", lambda ab: (mul_nm(ab[0], ab[1]), ab[0]), (a_nm, b_nm))
    measure("mul/limbs-major", lambda ab: (mul_wm(ab[0], ab[1]), ab[0]), (a_wm, b_wm))


if __name__ == "__main__":
    main()
