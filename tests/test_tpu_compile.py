"""The main path's kernels compile for a described TPU v5e chip.

No chip is attached: the TPU compiler installed beside JAX compiles for
a `v5e:2x2` topology it is only told about, and refuses here what the
chip's compiler would refuse there (tiling, fast-memory, device-memory
limits) at no chip time. Nothing runs, so these tests say nothing about
results or speed.

The topology is described inside a module fixture, never at import: one
process at a time may load the TPU library, and every xdist worker
imports this file. Lane counts are the shipped ones or fixed small
ones: measured on this compiler, compile time does not move with the
lane axis (32 vs 512 lanes: 21.7 s vs 21.2 s for the concrete kernel),
so the small widths check the same programs.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

#: the symbolic wave caps analysis/corpus.py gives a small corpus
EXPLORER_MEM_CAP = 16384
EXPLORER_STORAGE_CAP = 128
EXPLORER_STEPS_PER_WAVE = 512


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("no TPU compiler (libtpu) is installed")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # with libtpu installed a topology that cannot be described is a
    # broken installation: fail, never skip
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2"
    )
    # a described-chip compile is written to the persistent cache but
    # cannot be read back without a chip: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree,
    )


def test_concrete_wave_kernel_at_serve_arena(one_chip):
    """`run` at the arena shape `myth serve` boots with (its default
    4 stripes x 8 lanes, 256 steps per wave)."""
    from mythril_tpu.compileplane.pack import _arena_for, service_shape
    from mythril_tpu.laser.batch.run import run

    shape = service_shape(stripes=4, lanes_per_stripe=8, steps_per_wave=256)
    batch, table, _ = _arena_for(shape)
    compiled = run.lower(
        _on(one_chip, batch),
        _on(one_chip, table),
        max_steps=shape["steps_per_wave"],
        track_coverage=True,
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes > 0


def test_symbolic_wave_kernel_at_explorer_caps(one_chip):
    """`sym_run`, the DeviceCorpusExplorer's wave, at the caps a small
    corpus runs with (128 lanes)."""
    from mythril_tpu.laser.batch.seeds import code_cap_bucket
    from mythril_tpu.laser.batch.state import make_batch, make_code_table
    from mythril_tpu.laser.batch.symbolic import make_sym_batch, sym_run

    lanes = 128
    base = make_batch(
        lanes,
        code_ids=np.zeros(lanes, np.int32),
        calldata=[b""] * lanes,
        mem_cap=EXPLORER_MEM_CAP,
        storage_cap=EXPLORER_STORAGE_CAP,
    )
    table = make_code_table([b"\x00"], code_cap=code_cap_bucket(1))
    compiled = sym_run.lower(
        _on(one_chip, make_sym_batch(base)),
        _on(one_chip, table),
        max_steps=EXPLORER_STEPS_PER_WAVE,
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes > 0


def test_pallas_keccak_compiles_to_mosaic(one_chip):
    from mythril_tpu.ops.keccak_pallas import keccak_f_pallas

    x = jax.ShapeDtypeStruct((4096, 25), jnp.uint32, sharding=one_chip)
    compiled = keccak_f_pallas.lower(x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_portfolio_batch_search_kernel(one_chip):
    """The batched search `device_solve_batch` dispatches, at the
    default candidate count and first-pass step budget. Every dispatch
    stacks SEARCH_ROWS queries, so this one shape is the only one the
    funnel compiles per program bucket (there is no larger row bucket
    to compile)."""
    from mythril_tpu.laser.smt import symbol_factory
    from mythril_tpu.laser.smt.solver import portfolio
    from mythril_tpu.laser.smt.solver.solver import lower

    live = []
    for i in range(2):
        y = symbol_factory.BitVecSym(f"tpu_compile_y{i}", 256)
        cons, _ = lower([(y * 3 == 21 + 3 * i).raw])
        live.append((i, portfolio.compile_program(cons)))
    rows = portfolio.SEARCH_ROWS
    args, limbs = portfolio._stacked_args(live, rows)
    search = portfolio._get_search_fn(
        64, limbs, int(portfolio.PORTFOLIO_DEFAULTS["first_pass_steps"])
    )
    args += (
        jnp.arange(7, 7 + rows, dtype=jnp.int32),
        jnp.arange(rows) < len(live),
    )
    compiled = (
        jax.jit(portfolio._search_rows(search.raw))
        .lower(*_on(one_chip, args))
        .compile()
    )
    assert compiled.memory_analysis().temp_size_in_bytes > 0
