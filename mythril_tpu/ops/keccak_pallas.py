"""Pallas TPU kernel for the keccak-f[1600] permutation.

The whole 1600-bit state stays in VMEM for all 24 rounds: the batch
lives on the 128-wide lane axis ([25, N] layout, one block per grid
step), rounds and rotations are static Python so the round constants
fold into the instruction stream. Measured on TPU v5e the kernel runs
at parity with the XLA fori_loop path (both ~0.02 ms at N=4096 —
keccak-f is pure VPU work XLA already schedules well); it is kept,
bit-exact-tested, as the substrate for fused stages the XLA path
cannot express (absorb+permute pipelines over paged memory).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from mythril_tpu.ops.keccak import _RC_INT, _ROT

BLOCK = 512  # batch lanes per grid step (multiple of the 128-lane tile)


def _rol(lo, hi, n):
    n %= 64
    if n == 0:
        return lo, hi
    if n == 32:
        return hi, lo
    if n < 32:
        return (
            (lo << n) | (hi >> (32 - n)),
            (hi << n) | (lo >> (32 - n)),
        )
    n -= 32
    return (
        (hi << n) | (lo >> (32 - n)),
        (lo << n) | (hi >> (32 - n)),
    )


def _kernel(lo_ref, hi_ref, out_lo_ref, out_hi_ref):
    lo = [lo_ref[i, :] for i in range(25)]
    hi = [hi_ref[i, :] for i in range(25)]

    for rnd in range(24):
        # theta
        clo = [lo[x] ^ lo[x + 5] ^ lo[x + 10] ^ lo[x + 15] ^ lo[x + 20]
               for x in range(5)]
        chi = [hi[x] ^ hi[x + 5] ^ hi[x + 10] ^ hi[x + 15] ^ hi[x + 20]
               for x in range(5)]
        dlo, dhi = [], []
        for x in range(5):
            rl, rh = _rol(clo[(x + 1) % 5], chi[(x + 1) % 5], 1)
            dlo.append(clo[(x + 4) % 5] ^ rl)
            dhi.append(chi[(x + 4) % 5] ^ rh)
        alo = [lo[i] ^ dlo[i % 5] for i in range(25)]
        ahi = [hi[i] ^ dhi[i % 5] for i in range(25)]
        # rho + pi
        blo, bhi = [None] * 25, [None] * 25
        for x in range(5):
            for y in range(5):
                rl, rh = _rol(alo[x + 5 * y], ahi[x + 5 * y], _ROT[x][y])
                blo[y + 5 * ((2 * x + 3 * y) % 5)] = rl
                bhi[y + 5 * ((2 * x + 3 * y) % 5)] = rh
        # chi
        lo, hi = [], []
        for i in range(25):
            x, y = i % 5, i // 5
            i1, i2 = (x + 1) % 5 + 5 * y, (x + 2) % 5 + 5 * y
            lo.append(blo[i] ^ ((~blo[i1]) & blo[i2]))
            hi.append(bhi[i] ^ ((~bhi[i1]) & bhi[i2]))
        # iota: static round constants fold into the instruction stream
        lo[0] = lo[0] ^ np.uint32(_RC_INT[rnd] & 0xFFFFFFFF)
        hi[0] = hi[0] ^ np.uint32(_RC_INT[rnd] >> 32)

    for i in range(25):
        out_lo_ref[i, :] = lo[i]
        out_hi_ref[i, :] = hi[i]


def _keccak_f_blocks(lo_t, hi_t, interpret):
    """lo_t/hi_t: [25, M] uint32 with M a multiple of BLOCK."""
    from jax.experimental import pallas as pl

    m = lo_t.shape[1]
    grid = (m // BLOCK,)
    spec = pl.BlockSpec((25, BLOCK), lambda i: (0, i))
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[spec, spec],
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct(lo_t.shape, jnp.uint32),
            jax.ShapeDtypeStruct(hi_t.shape, jnp.uint32),
        ],
        interpret=interpret,
    )(lo_t, hi_t)


@functools.partial(jax.jit, static_argnames=("interpret",))
def keccak_f_pallas(lo, hi, interpret: bool = False):
    """keccak-f[1600] on [..., 25] uint32 lane pairs via the pallas
    kernel. Shape-compatible with ops.keccak.keccak_f. `interpret` runs
    the Pallas interpreter instead of the Mosaic kernel: CPU callers
    (tests) ask for it; it is never inferred from the backend, so a
    compile for a described chip compiles the real kernel."""
    batch_shape = lo.shape[:-1]
    n = int(np.prod(batch_shape)) if batch_shape else 1
    m = ((n + BLOCK - 1) // BLOCK) * BLOCK

    lo_t = jnp.zeros((25, m), dtype=jnp.uint32)
    hi_t = jnp.zeros((25, m), dtype=jnp.uint32)
    lo_t = lo_t.at[:, :n].set(lo.reshape(n, 25).T)
    hi_t = hi_t.at[:, :n].set(hi.reshape(n, 25).T)

    out_lo, out_hi = _keccak_f_blocks(lo_t, hi_t, interpret)
    out_lo = out_lo[:, :n].T.reshape(batch_shape + (25,))
    out_hi = out_hi[:, :n].T.reshape(batch_shape + (25,))
    return out_lo, out_hi
