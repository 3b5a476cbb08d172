"""Pallas probe: in-place per-lane stack-slot write vs the one-hot merge.

The step kernel's consolidated stack write (laser/batch/step.py
"consolidated stack/sp write") rewrites the whole [N, S, W] stack
through a one-hot jnp.where every step — the #1 bandwidth term of the
step at big N (SURVEY §7.1 reserves Pallas for exactly this
scatter/compaction class). The Pallas candidate updates ONLY each
lane's written row: a (N,)-grid kernel with scalar-prefetched slot
indices driving the output index_map, stack buffer aliased in-place,
so the bytes touched drop from N*S*W to N*W (128x at S=128).

Run on the real chip:  python tools/pallas_stack_probe.py [N]
Prints per-iteration wall for both implementations over a 64-step
chained scan (forced readback — block_until_ready lies on this link)
plus a correctness check, and is the measured basis for the roadmap's
verdict on the Pallas stack path.

MEASURED VERDICT (2026-08-01, v5e over the pre-PR-21 remote-chip link — kept for the
record; see docs/roadmap.md "Pallas stack scatter"): the BlockSpec
route is a dead end on TPU. Mosaic requires the last two block dims
divisible by (8, 128) (doubled sublanes for 16-bit dtypes) unless
equal to the array dims, so a [1, 1, W] per-lane block — the whole
point of the in-place design — cannot be expressed; the smallest
legal block already spans 8 stack slots, and slot indices differ per
lane within any multi-lane block. A hand-rolled HBM DMA kernel
remains possible but unmotivated: the one-hot merge measured here
(44-81 ms/iter at [16384,128,16]) is dominated by the scan carrying a
fresh stack copy per iteration — inside the real jit'd while loop the
carried state is donated/aliased and the merge fuses with adjacent
passes (the ENTIRE 75-fusion step runs at ~26 ms/step), so there is
no 40+ ms standalone write to reclaim.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

N = int(sys.argv[1]) if len(sys.argv) > 1 else 16384
S, W = 128, 16
ITERS = 64


def baseline_write(stack, res_idx, res_val, mask):
    """The step kernel's one-hot merge (full-array rewrite)."""
    slot_ids = jnp.arange(S)[None, :]
    oh = (slot_ids == res_idx[:, None]) & mask[:, None]
    return jnp.where(oh[:, :, None], res_val[:, None, :], stack)


def make_pallas_write():
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(idx_ref, mask_ref, val_ref, stack_in_ref, out_ref):
        lane = pl.program_id(0)

        @pl.when(mask_ref[lane] != 0)
        def _():
            out_ref[0, 0, :] = val_ref[0, :]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # res_idx, mask
        grid=(N,),
        in_specs=[
            # the lane's fresh value: one [1, W] row
            pl.BlockSpec((1, W), lambda lane, idx, msk: (lane, 0)),
            # the stack row this lane writes (aliased to the output):
            # scalar-prefetched slot index drives the block placement
            pl.BlockSpec(
                (1, 1, W), lambda lane, idx, msk: (lane, idx[lane], 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, W), lambda lane, idx, msk: (lane, idx[lane], 0)
        ),
    )

    fn = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, S, W), jnp.uint16),
        input_output_aliases={3: 0},  # stack buffer updated in place
    )

    def write(stack, res_idx, res_val, mask):
        return fn(res_idx, mask.astype(jnp.int32), res_val, stack)

    return write


def timed(write_fn, label):
    key = jax.random.PRNGKey(0)
    stack = jnp.zeros((N, S, W), jnp.uint16)
    idx = jax.random.randint(key, (N,), 0, S, dtype=jnp.int32)
    val = jax.random.randint(key, (N, W), 0, 1 << 16).astype(jnp.uint16)
    mask = (jnp.arange(N) % 4) != 0

    @jax.jit
    def loop(stack):
        def body(st, i):
            # chain: rotate the written value so iterations can't fuse
            st = write_fn(st, (idx + i) % S, val + i.astype(jnp.uint16), mask)
            return st, ()

        st, _ = lax.scan(body, stack, jnp.arange(ITERS, dtype=jnp.int32))
        return st

    out = loop(stack)
    _ = np.asarray(out).sum()  # warm + force
    t0 = time.perf_counter()
    out = loop(stack)
    _ = np.asarray(out).sum()
    dt = time.perf_counter() - t0
    print(
        f"{label}: {dt:.3f}s for {ITERS} iters at N={N} "
        f"({dt / ITERS * 1000:.2f} ms/iter)"
    )
    return np.asarray(out)


def main():
    ref = timed(baseline_write, "one-hot merge ")
    try:
        pallas_write = make_pallas_write()
        got = timed(pallas_write, "pallas in-place")
    except Exception as why:
        print(f"pallas path failed: {why!r}")
        return
    if np.array_equal(ref, got):
        print("correctness: pallas output == baseline output")
    else:
        diff = (ref != got).sum()
        print(f"MISMATCH: {diff} differing elements")


if __name__ == "__main__":
    main()
