"""Test configuration.

All tests run on a virtual 8-device CPU mesh so multi-chip sharding
logic (mythril_tpu.parallel) is exercised without TPU hardware, per the
reference's "test chain interaction without a chain" strategy
(reference: tests/__init__.py + mocked RPC in tests/mythril/).
XLA_FLAGS must be set before the first backend init.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# persistent XLA compile cache (the program's own location rule):
# amortize keccak/divmod compiles across runs
from mythril_tpu.support.accel import ensure_compile_cache  # noqa: E402

ensure_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

# Kernel specialization is OFF by default under the test harness: the
# product default is on, but every distinct (specialization bucket x
# arena shape) pays a fresh XLA compile, and the many small contract
# combinations across the suite would not fit tier-1's 10-minute
# window on a 1-core host. The dedicated suite
# (tests/laser/test_specialize.py, `-m specialize`) re-enables it and
# pins the specialized-vs-generic differentials.
from mythril_tpu.support.support_args import args as _support_args  # noqa: E402

_support_args.specialize = False

# The block-level JIT rides the specialize flag (no specialized
# kernel, no block substeps) but is ALSO off explicitly: the blockjit
# suite (tests/laser/test_blockjit.py, `-m blockjit`) re-enables both
# and pins the blockjit-vs-generic differentials; product/bench
# default is on.
_support_args.blockjit = False

# The device-first solver funnel is likewise OFF by default under the
# test harness: the product default is on, but the batched diversified
# SLS dispatch pays a fresh XLA compile per stacked shape bucket, and
# running it for EVERY wave's flip frontier across the whole suite
# would not fit tier-1's window on 1 CPU core. The dedicated suite
# (tests/laser/test_solverperf.py, `-m solverperf`) re-enables it and
# pins the inverted-vs-legacy funnel differentials.
_support_args.device_first = False

# The static-answer TRIAGE TIER is OFF by default under the test
# harness (the product default is on): many suites pin wave/walk
# mechanics on tiny synthetic contracts that are provably clean, and
# triage would answer those jobs before the machinery under test ever
# runs. The semantic detector SCREEN itself stays ON (it rides
# static_prune) — its soundness is pinned by the module positive
# fixtures across the whole suite. The dedicated taint suite
# (tests/analysis/test_static_taint.py, `-m taint`) and the service
# triage test re-enable the tier and pin its behavior.
_support_args.static_answer = False


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running golden analyses (run explicitly with -m slow)"
    )
    config.addinivalue_line(
        "markers",
        "faults: deterministic fault-injection suite (resilience harness; "
        "fast — runs in tier-1, selectable with -m faults)",
    )
    config.addinivalue_line(
        "markers",
        "service: persistent analysis service suite (myth serve; CPU-only, "
        "fast — runs in tier-1, selectable with -m service)",
    )
    config.addinivalue_line(
        "markers",
        "static: static bytecode analysis suite (analysis/static: CFG "
        "recovery, dataflow, prune feed, detector screen; host-only, "
        "fast — runs in tier-1, selectable with -m static)",
    )
    config.addinivalue_line(
        "markers",
        "pipeline: pipelined wave engine suite (double-buffered async "
        "dispatch, device-side evidence compaction, donated arena "
        "reseed; CPU-only, fast — runs in tier-1, selectable with "
        "-m pipeline)",
    )
    config.addinivalue_line(
        "markers",
        "multichip: multi-chip mesh suite (sharded step, corpus "
        "scheduler with work stealing, per-group failure domains, "
        "mesh service) on the 8 simulated host devices this conftest "
        "forces — runs in tier-1, selectable with -m multichip",
    )
    config.addinivalue_line(
        "markers",
        "specialize: kernel-specialization suite (per-contract step "
        "kernels: phase pruning, superblock fusion, compile cache, "
        "CodeCache kernel eviction; CPU-only — runs in tier-1, "
        "selectable with -m specialize)",
    )
    config.addinivalue_line(
        "markers",
        "blockjit: block-level JIT suite (laser/batch/blockjit.py: "
        "block-summary goldens, block-program tables, blockjit-vs-"
        "generic differentials, mid-block OOG replay, kernel-cache "
        "block-key pin/evict, --no-blockjit parity; CPU-only — runs "
        "in tier-1, selectable with -m blockjit)",
    )
    config.addinivalue_line(
        "markers",
        "observe: unified telemetry suite (mythril_tpu/observe: "
        "metrics registry + Prometheus exposition, structured spans + "
        "Perfetto export + flight recorder, solver attribution, "
        "routing feature log, stats-merge policy; CPU-only — runs in "
        "tier-1, selectable with -m observe)",
    )
    config.addinivalue_line(
        "markers",
        "solverlab: solver query flight recorder + replay lab suite "
        "(observe/querylog capture artifacts + loss-reason taxonomy, "
        "solver funnel classification, myth solverlab replay "
        "agreement; CPU-only — runs in tier-1, selectable with "
        "-m solverlab)",
    )
    config.addinivalue_line(
        "markers",
        "solverperf: device-first solver funnel suite (inverted-vs-"
        "legacy parity differential, deterministic heterogeneous lane "
        "seeding, cube-split/merge + exhausted-cube unsat, witness "
        "validation, sprint-cap knob, race-margin histogram; "
        "CPU-only — runs in tier-1, selectable with -m solverperf)",
    )
    config.addinivalue_line(
        "markers",
        "store: cross-run verdict store suite (mythril_tpu/store: "
        "content-addressed entries + config fingerprints, exact-hit "
        "settle at corpus/service admission, fingerprint-diff "
        "incremental re-analysis differential, corrupt-entry refusal, "
        "concurrent writers, --no-store parity; CPU-only — runs in "
        "tier-1, selectable with -m store)",
    )
    config.addinivalue_line(
        "markers",
        "chaos: crash-safe serving suite (durable job journal append/"
        "replay, recovery re-admission with store dedupe, poison-job "
        "quarantine strike escalation, tier circuit-breaker "
        "transitions and ladder fallback, journal-fault degradation; "
        "CPU-only — runs in tier-1, selectable with -m chaos; the "
        "subprocess SIGKILL harness is tools/chaos_smoke.py via "
        "[testenv:chaos])",
    )
    config.addinivalue_line(
        "markers",
        "fleet: federated serving suite (mythril_tpu/fleet: health-"
        "routed admission over N replicas, replica-death failover "
        "with idempotency-keyed reroute dedupe through the shared "
        "verdict store, drain-time frontier handoff, fleet-wide load "
        "shedding with Retry-After, front journal recovery; CPU-only, "
        "engine-less servers — runs in tier-1, selectable with "
        "-m fleet; the subprocess kill-one-replica harness is "
        "tools/fleet_smoke.py via [testenv:fleet])",
    )
    config.addinivalue_line(
        "markers",
        "chainstream: reorg-safe chain-head streaming suite "
        "(mythril_tpu/chainstream: multi-endpoint RPC failover with "
        "death breakers + quorum heads, crash-safe cursor journal "
        "with reorg rollback, line-rate static triage, "
        "fired/retracted/superseded alert log, fleet survivor "
        "handoff with content-derived idempotency keys; scripted "
        "in-process fake chain, no network — runs in tier-1, "
        "selectable with -m chainstream; the subprocess "
        "SIGKILL+reorg harness is tools/chainstream_smoke.py via "
        "[testenv:chainstream])",
    )
    config.addinivalue_line(
        "markers",
        "compileplane: persistent AOT compile plane suite "
        "(mythril_tpu/compileplane: artifact-cache roundtrip + "
        "checksum/fingerprint/schema refusal, bake->fresh-plane load "
        "bit-identical differential, MYTHRIL_NO_AOT fallback parity, "
        "concurrent writers, LRU eviction, TIER_COMPILEPLANE breaker "
        "fallback, pack-warmed service boot ordering; CPU-only — runs "
        "in tier-1, selectable with -m compileplane; the subprocess "
        "SIGKILL+restart harness is tools/compileplane_smoke.py via "
        "[testenv:compileplane])",
    )
    config.addinivalue_line(
        "markers",
        "taint: taint & value-set static layer suite (attacker-taint "
        "fixpoint goldens, semantic screen soundness sweep over every "
        "module positive fixture, static-answer triage differential, "
        "taint lint checks, routing schema back-compat; host-only, "
        "fast — runs in tier-1, selectable with -m taint)",
    )
    config.addinivalue_line(
        "markers",
        "linker: cross-contract static linker suite (analysis/static/"
        "callgraph + linkset: call-site provenance goldens, SCC escape "
        "widening, proxy pairing + storage-collision diff, the linked-"
        "fingerprint store-invalidation differential, `myth graph` "
        "JSON golden, the four link lint checks, routing v3->v4 "
        "back-compat; host-only, fast — runs in tier-1, selectable "
        "with -m linker)",
    )
    config.addinivalue_line(
        "markers",
        "router: learned tier-ladder router + solver self-tuning suite "
        "(mythril_tpu/routing: artifact roundtrip/refusal/fallback, "
        "train->eval determinism golden, routed service admission + "
        "router-off parity + in-flight promotion-on-overrun, the "
        "tuned-overrides replay-agreement gate and tune --watch loop, "
        "cost-informed fleet replica choice differential; host-only, "
        "fast — runs in tier-1, selectable with -m router)",
    )


def pytest_collection_modifyitems(config, items):
    import pytest as _pytest

    if config.getoption("-m"):
        return
    skip_slow = _pytest.mark.skip(reason="slow golden analysis; use -m slow")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip_slow)
