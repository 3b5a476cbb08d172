"""Benchmark: honest batched-interpreter throughput + the
time-to-convergence corpus A/B.

Emission is HEADLINE-FIRST and incremental: the complete one-line JSON
record prints as soon as the headline phases (static prune,
transitions, ONE convergence pair) finish — inside
`MYTHRIL_BENCH_HEADLINE_S` (default 8 min) — and prints again after
every refinement (second pair, default path, hard solve). The LAST
parseable line is the record; a capture window that closes mid-refine
still holds a complete artifact (the round-5 rc:124/parsed:null fix,
hardened).

The record carries three measurement groups:

1. `state_transitions_per_sec` (the `value` field): one state
   transition = one EVM instruction applied to one path state — the
   unit of work of the reference's `execute_state` hot loop
   (mythril/laser/ethereum/svm.py:303). A single jit'd step advances
   every lane of a StateBatch at once on the TPU.

   Honesty rules (round-2): timing stops only after a forced
   device->host readback, and the measurement must scale ~linearly
   with step count (a dispatch-only "measurement" would not).

2. The **convergence corpus A/B** (round-5 headline). The round-4
   equal-budget design measured the timeout, not the engine (both
   legs' walls pinned at budget x contracts; issues tied —
   BASELINE.md round-4 reconciliation). This one measures WALL TO
   FIXPOINT: `CONV_CONTRACTS` contracts (analysis/corpusgen.py
   `synth_bench_corpus` — fixture constant-mutants plus deep-loop and
   cap-degrading shapes) analyzed at `-t 2` under a budget high
   enough that both legs CONVERGE, so a faster engine finishes
   sooner instead of exploring more states inside the same wall.

   Device leg: the round-5 inversion — one striped device exploration
   owns every contract it covers end-to-end (issues synthesized from
   banked concrete evidence, host walk skipped;
   --device-ownership/analysis/corpus.py), the host walking only the
   remainder with witness injection + solve pre-emption. Host leg:
   the same analyzer, chip off. Interleaved x `CONV_PAIRS`, medians,
   spread-gated. Explicit `criteria` fields state the round's
   pass/fail thresholds so the record cannot blur: speedup
   (host_wall/device_wall) >= 2.0 with distinct-finding parity.

3. The default single-contract path with its prepass/solver counters.

Baseline: the reference cannot run in this image (z3 is absent — its
entire solving surface is z3, mythril/laser/smt/solver/solver.py), and
it publishes no numbers (BASELINE.md). The normative proxy, recorded
in BASELINE.md, is therefore this repo's own host-only leg — the same
analyzer with the accelerator disabled. `vs_baseline` is the measured
median host-only wall over the median device wall on the convergence
A/B: the speedup the chip delivers over the proxy, not a constant.
"""

from __future__ import annotations

import json
import os
import signal
import statistics
import sys
import time

#: Internal wall-clock budget for the WHOLE bench process (both
#: attempts share it), sized below the harness's 870 s capture window:
#: the bench must ALWAYS emit its one parseable JSON line with whatever
#: phases completed, instead of being killed by the outer `timeout`
#: (BENCH_r05.json's rc:124/parsed:null failure mode). Phases that
#: don't fit the remaining budget are skipped and say so in the record.
BENCH_BUDGET_S = float(os.environ.get("MYTHRIL_BENCH_BUDGET_S", "780"))
_BENCH_T0 = time.monotonic()

#: The HEADLINE deadline: the record must be printed (complete, with
#: transitions + one convergence pair) by this wall mark even when the
#: full budget would allow more — the capture window must never close
#: on a bench that has measured everything but printed nothing
#: (BENCH_r05's rc:124/parsed:null failure mode). Later phases REFINE
#: the record and print it again; the last line supersedes.
HEADLINE_DEADLINE_S = float(
    os.environ.get("MYTHRIL_BENCH_HEADLINE_S", "480")
)


def _budget_left() -> float:
    return BENCH_BUDGET_S - (time.monotonic() - _BENCH_T0)


def _headline_left() -> float:
    return HEADLINE_DEADLINE_S - (time.monotonic() - _BENCH_T0)


# sizes are env-tunable so the tier-1 smoke (tests/test_bench_smoke.py)
# can drive the REAL emission path at toy scale
N_LANES = int(os.environ.get("MYTHRIL_BENCH_LANES", "16384"))
N_STEPS = int(os.environ.get("MYTHRIL_BENCH_STEPS", "256"))
CONV_CONTRACTS = int(os.environ.get("MYTHRIL_BENCH_CONTRACTS", "32"))
CONV_PAIRS = int(os.environ.get("MYTHRIL_BENCH_PAIRS", "2"))
#: per-contract ceiling, NOT the expected wall: contracts converge
#: (walk reaches fixpoint) well under it; the ceiling only bounds
#: pathological mutants
CONV_EXEC_TIMEOUT_S = 90
#: the device leg's exploration allowance — this IS the chip carrying
#: the workload, so it is sized for coverage, not minimized
CONV_DEVICE_BUDGET_S = 180.0
SPREAD_GATE = 0.35
#: covers the configured worst case (every contract at the ceiling +
#: the device exploration allowance) — the deadline guards HANGS, it
#: must not fire on a merely pathological corpus
LEG_DEADLINE_S = CONV_CONTRACTS * CONV_EXEC_TIMEOUT_S + 600
SPEEDUP_TARGET = 2.0


def _timed_run(batch, code, max_steps: int) -> float:
    """Run the batched interpreter and return wall seconds measured
    through a forced host readback (the only sync this platform
    honors)."""
    import numpy as np

    from mythril_tpu.laser.batch.run import run

    t0 = time.perf_counter()
    out, steps = run(batch, code, max_steps=max_steps)
    # np.asarray forces device execution AND the device->host copy;
    # summing both fields makes the readback depend on the full result.
    sync = int(np.asarray(out.pc).sum())
    n_live = int((np.asarray(out.status) == 0).sum())
    dt = time.perf_counter() - t0
    assert sync >= 0  # keep the readback live
    assert int(steps) == max_steps, f"early halt at {int(steps)}/{max_steps}"
    # the demo contract loops forever; a dead lane means transitions
    # would overcount masked no-op work
    assert n_live == out.pc.shape[0], f"lanes died: {n_live}/{out.pc.shape[0]}"
    return dt


#: v5e(lite) headline numbers for the roofline denominators
HBM_BYTES_PER_S = 819e9
PEAK_BF16_FLOPS = 197e12


def _roofline(batch, code, rate: float) -> dict:
    """Bytes-per-step / roofline accounting for the step kernel.

    The batched interpreter is integer vector work — the MXU (the
    FLOPs headline) is idle by design, so MFU is ~0 and the honest
    utilization axis is HBM: a functional step reads and writes the
    whole StateBatch (XLA fuses/elides some of it, so this is an upper
    bound on demanded traffic) plus the two code-table gathers. The
    interesting diagnosis is which side of the roofline the measured
    rate lands on: demanded-bytes x steps/s far under the HBM ceiling
    means the kernel is DISPATCH/latency-bound, not bandwidth-bound —
    macro-stepping (unroll) is then the lever, not layout."""
    state_bytes = sum(
        getattr(a, "nbytes", 0) for a in batch
    )
    gather_bytes = N_LANES * (33 + 6 * 4)  # code window + opcode metadata
    bytes_per_step = 2 * state_bytes + gather_bytes
    steps_per_sec = rate / N_LANES
    demanded = bytes_per_step * steps_per_sec
    return {
        "state_bytes_per_lane": int(state_bytes // N_LANES),
        "bytes_per_step": int(bytes_per_step),
        "batch_steps_per_sec": round(steps_per_sec, 2),
        "hbm_demand_gbps": round(demanded / 1e9, 2),
        "hbm_utilization_pct": round(100 * demanded / HBM_BYTES_PER_S, 2),
        "mfu_pct": 0.0,  # integer kernel: no MXU FLOPs by design
        "roofline_bound": (
            "bandwidth" if demanded > 0.5 * HBM_BYTES_PER_S else "dispatch"
        ),
    }


def bench_transitions() -> dict:
    import jax

    from __graft_entry__ import _demo_workload

    batch, code = _demo_workload(N_LANES)

    # Warmup at both step counts so neither timed call includes compile.
    _timed_run(batch, code, N_STEPS)
    _timed_run(batch, code, N_STEPS // 4)

    dt_full = _timed_run(batch, code, N_STEPS)
    dt_quarter = _timed_run(batch, code, N_STEPS // 4)

    # Linearity gate: 4x the steps must cost >=2x the wall time (slack
    # for fixed dispatch/readback overhead). A lazy "finish" fails this.
    # The upper bound catches the opposite failure: a transient device
    # stall during the full run (observed once, over the pre-PR-21
    # remote-chip link: ratio 19.4, recorded
    # rate understated 5x) — raise so the __main__ retry reruns clean.
    ratio = dt_full / max(dt_quarter, 1e-9)
    if ratio < 2.0:
        raise RuntimeError(
            f"non-linear scaling (t({N_STEPS})={dt_full:.3f}s vs "
            f"t({N_STEPS // 4})={dt_quarter:.3f}s, ratio {ratio:.2f}) — "
            "the timer is not observing execution"
        )
    if ratio > 8.0:
        raise RuntimeError(
            f"full run stalled (ratio {ratio:.2f} for 4x steps) — "
            "transient device/link interference; retrying gives an "
            "honest number instead of an understated one"
        )

    transitions = N_LANES * N_STEPS
    rate = transitions / dt_full
    print(
        f"bench: {transitions} transitions in {dt_full:.3f}s "
        f"(quarter-run {dt_quarter:.3f}s, ratio {ratio:.2f}) on "
        f"{jax.devices()[0]}",
        file=sys.stderr,
    )
    out = {"rate": rate, "wall_s": dt_full, "scaling_ratio": ratio}
    out.update(_roofline(batch, code, rate))
    return out


def bench_specialize_ab(dev: dict) -> dict:
    """Generic-vs-specialized step-throughput A/B (ISSUE 6): the SAME
    demo workload timed on the generic interpreter (the transitions
    half above, `dev["rate"]`) and on its contract-specialized kernel
    (laser/batch/specialize.py: phase pruning + superblock fusion).
    The specialized leg's transition count includes the instructions
    the fused substeps advanced — both legs count executed EVM
    instructions per second."""
    import numpy as np

    import jax.numpy as jnp

    from __graft_entry__ import _demo_workload
    from mythril_tpu.laser.batch import specialize as spec_mod

    batch, code = _demo_workload(N_LANES)
    length = int(np.asarray(code.length)[0])
    raw = bytes(np.asarray(code.ops)[0, :length].tolist())
    # the production kernel-selection path: pruning from the signature,
    # fusion only where the superblock profile profits
    phases = spec_mod.phases_for(
        spec_mod.signature_for(raw), fuse=spec_mod.fuse_profitable(raw)
    )
    fuse = jnp.asarray(
        spec_mod.build_fuse_table([raw], code.ops.shape[1] - 33)
    )
    kern = spec_mod.kernel_cache().get(phases)

    def timed(max_steps: int):
        t0 = time.perf_counter()
        out, steps, fused, _blocks = kern.run(
            batch, code, fuse, max_steps=max_steps
        )
        sync = int(np.asarray(out.pc).sum())  # forced readback
        n_fused = int(fused)
        dt = time.perf_counter() - t0
        assert sync >= 0
        return dt, int(steps), n_fused

    timed(N_STEPS)  # warmup: the one specialized-kernel compile
    dt, steps, n_fused = timed(N_STEPS)
    assert steps == N_STEPS, f"early halt at {steps}/{N_STEPS}"
    # the demo contract loops forever, so every lane executes every
    # full step; the fused substeps add on top
    transitions = N_LANES * steps + n_fused
    spec_rate = transitions / dt
    out = {
        "specialized_step_rate": round(spec_rate, 1),
        "specialized_wall_s": round(dt, 3),
        "specialized_fused_steps": n_fused,
        "spec_pruned_phases": len(phases.pruned),
    }
    if dev.get("rate"):
        out["generic_step_rate"] = round(dev["rate"], 1)
        out["specialize_speedup"] = round(spec_rate / dev["rate"], 3)
    print(f"bench: specialize A/B {out}", file=sys.stderr)
    return out


#: the blockjit A/B's own lane count: the SPEEDUP is a ratio of two
#: legs at the same shape, so it does not need the headline's 16k
#: lanes — a smaller shape keeps both compiles + runs inside the leg
#: deadline on a 1-core host
BJ_LANES = int(os.environ.get("MYTHRIL_BENCH_BJ_LANES", "2048"))


def _blockjit_workload(n_lanes: int):
    """The block-JIT A/B workload: an arithmetic/compare/bitwise loop
    body — the straight-line chains PR-6 fusion cannot advance (every
    ALU op breaks a PUSH/DUP/SWAP run) but block lowering can. One
    CFG block per pass: JUMPDEST; (MUL, ADD, XOR, DUP/EQ/POP mix);
    JUMP — the dominant compiled-Solidity shape for hashing/math-heavy
    function bodies."""
    import numpy as np

    from mythril_tpu.laser.batch.state import make_batch, make_code_table

    body = bytes([
        0x60, 0x01,        # PUSH1 1 (seed)
        0x5B,              # 2: JUMPDEST  — loop head
        0x60, 0x03, 0x02,  # PUSH1 3; MUL
        0x60, 0x07, 0x01,  # PUSH1 7; ADD
        0x60, 0x55, 0x18,  # PUSH1 0x55; XOR
        0x80, 0x60, 0x2A,  # DUP1; PUSH1 42
        0x10, 0x50,        # LT; POP
        0x80, 0x19, 0x16,  # DUP1; NOT; AND
        0x60, 0x02, 0x56,  # PUSH1 2; JUMP
    ])
    code = make_code_table([body])
    rng = np.random.default_rng(1)
    calldata = [rng.integers(0, 256, 36, dtype=np.uint8).tobytes()
                for _ in range(n_lanes)]
    batch = make_batch(n_lanes, calldata=calldata)
    return batch, code, body


def bench_blockjit_ab() -> dict:
    """Specialized-vs-blockjit step-throughput A/B (ISSUE 13): the
    SAME ALU-dense workload timed on the PR-6 specialized kernel
    (phase pruning + superblock fusion, block_depth=0) and on the
    block-JIT kernel (whole lowered CFG blocks per iteration). Both
    legs count executed EVM instructions per second (full steps x
    lanes + substep-advanced instructions), so the speedup is the
    honest blocks-vs-stack-shuffles ratio the acceptance gates on."""
    import numpy as np

    import jax.numpy as jnp

    from mythril_tpu.laser.batch import blockjit as bj_mod
    from mythril_tpu.laser.batch import specialize as spec_mod

    batch, code, raw = _blockjit_workload(BJ_LANES)
    cap = code.ops.shape[1] - 33
    signature = spec_mod.signature_for(raw)
    fuse_on = spec_mod.fuse_profitable(raw)
    spec_phases = spec_mod.phases_for(signature, fuse=fuse_on)
    depth = bj_mod.block_depth_for(raw)
    bj_phases = spec_mod.phases_for(
        signature, fuse=fuse_on, block_depth=depth
    )
    fuse_tbl = jnp.asarray(spec_mod.build_fuse_table([raw], cap))
    block_tbl = jnp.asarray(bj_mod.build_block_table([raw], cap))
    bstats = bj_mod.block_stats(raw)

    def timed(phases, tbl, max_steps: int):
        kern = spec_mod.kernel_cache().get(phases)
        t0 = time.perf_counter()
        out, steps, subs, blocks = kern.run(
            batch, code, tbl, max_steps=max_steps
        )
        sync = int(np.asarray(out.pc).sum())  # forced readback
        n_subs, n_blocks = int(subs), int(blocks)
        dt = time.perf_counter() - t0
        assert sync >= 0
        return dt, int(steps), n_subs, n_blocks

    # warmup both compiles, then time
    timed(spec_phases, fuse_tbl, N_STEPS)
    timed(bj_phases, block_tbl, N_STEPS)
    s_dt, s_steps, s_subs, _ = timed(spec_phases, fuse_tbl, N_STEPS)
    b_dt, b_steps, b_subs, b_blocks = timed(bj_phases, block_tbl, N_STEPS)
    spec_rate = (BJ_LANES * s_steps + s_subs) / s_dt
    bj_rate = (BJ_LANES * b_steps + b_subs) / b_dt
    out = {
        "blockjit_step_rate": round(bj_rate, 1),
        "blockjit_wall_s": round(b_dt, 3),
        "blockjit_substep_steps": b_subs,
        "blockjit_block_rate": round(b_blocks / b_dt, 1),
        "blockjit_speedup": round(bj_rate / spec_rate, 3),
        "blockjit_depth": depth,
        "blockjit_fallback_blocks": bstats["blocks_unlowered"],
        "blockjit_lowered_density": bstats["lowered_density"],
        "spec_leg_step_rate": round(spec_rate, 1),
    }
    print(f"bench: blockjit A/B {out}", file=sys.stderr)
    return out


def bench_static_prune() -> dict:
    """The static layer (analysis/static) over the benchmark corpus:
    pure host work, no device — measures what fraction of the corpus's
    statically-decidable units (branch directions, dispatcher
    selectors, basic blocks) the pre-dispatch pass proves dead, i.e.
    lanes/flips/modules the arena never wastes. Runs first: it is
    milliseconds and must not be skippable by budget exhaustion."""
    from mythril_tpu.analysis.corpusgen import synth_bench_corpus
    from mythril_tpu.analysis.static import summary_for

    contracts = synth_bench_corpus(CONV_CONTRACTS)
    t0 = time.perf_counter()
    pruned = total = dead_selectors = dead_directions = 0
    mounted_semantic = mounted_opcode = registered = 0
    static_answerable = 0
    taint_wall_ms = 0.0
    for code, _creation, _name in contracts:
        summary = summary_for(code)
        pruned += summary.prune_units
        total += summary.total_units
        dead_selectors += len(summary.dead_selectors)
        dead_directions += len(summary.prune_directions())
        # the semantic-vs-opcode screen A/B (the strictly-reduces
        # acceptance reads both rates) + the triage-tier population
        sem_app, sem_skip = summary.applicable_modules()
        opc_app, _opc_skip = summary.applicable_modules(semantic=False)
        mounted_semantic += len(sem_app)
        mounted_opcode += len(opc_app)
        registered += len(sem_app) + len(sem_skip)
        static_answerable += bool(summary.static_answerable)
        if summary.taint is not None:
            taint_wall_ms += summary.taint.wall_ms
    return {
        "static_prune_rate": round(pruned / total, 4) if total else 0.0,
        "static_dead_selectors": dead_selectors,
        "static_dead_directions": dead_directions,
        "screen_mount_rate_opcode": (
            round(mounted_opcode / registered, 4) if registered else 0.0
        ),
        "screen_mount_rate_semantic": (
            round(mounted_semantic / registered, 4) if registered else 0.0
        ),
        "static_answer_rate": (
            round(static_answerable / len(contracts), 4)
            if contracts
            else 0.0
        ),
        "static_taint_wall_s": round(taint_wall_ms / 1e3, 3),
        "static_wall_s": round(time.perf_counter() - t0, 3),
        **bench_static_link(contracts),
    }


def bench_static_link(contracts) -> dict:
    """The cross-contract linker leg (analysis/static/linkset): link
    the bench corpus plus the known-positive fixture families and
    report resolution quality. Headline fields:

    - `link_resolve_rate`: resolved / total call-site edges (the
      planted fixtures all resolve, organic corpus edges may not);
    - `proxy_detect_rate`: detected proxies / planted proxies (2x
      EIP-1967 + 2x EIP-1167 here — must be 1.0);
    - `callgraph_fingerprint_hit_rate`: selectors that got a linked
      fingerprint / all selectors (the rest carry link-unresolved /
      link-cycle problems and can never serve a linked store hit);
    - `static_link_wall_s`: the whole corpus-level link pass (the
      admission-path budget: sub-second).
    """
    from mythril_tpu.analysis.corpusgen import (
        cross_call_pair,
        minimal_proxy,
        proxy_pair,
    )
    from mythril_tpu.analysis.static import link_corpus

    planted_proxies = 4
    rows = list(contracts)
    for k in range(2):
        rows.extend(proxy_pair(seed=k, collide=bool(k % 2)))
        rows.extend(minimal_proxy(seed=k))
    rows.extend(cross_call_pair(seed=0))
    t0 = time.perf_counter()
    linkset = link_corpus(rows)
    stats = linkset.stats()
    data = linkset.resolve()
    fps = sum(len(v) for v in data["linked_fingerprints"].values())
    problems = sum(len(v) for v in data["link_problems"].values())
    return {
        "link_resolve_rate": stats["resolve_rate"],
        "proxy_detect_rate": (
            round(stats["proxies"] / planted_proxies, 4)
        ),
        "callgraph_fingerprint_hit_rate": (
            round(fps / (fps + problems), 4) if fps + problems else 1.0
        ),
        "link_proxy_pairs": stats["proxy_pairs"],
        "link_collisions": stats["collisions"],
        "static_link_wall_s": round(time.perf_counter() - t0, 3),
    }


class _Deadline(Exception):
    pass


def _with_deadline(fn, seconds: int):
    """Run fn() under a SIGALRM deadline; raises _Deadline."""

    def _alarm(signum, frame):
        raise _Deadline()

    previous = signal.signal(signal.SIGALRM, _alarm)
    signal.alarm(seconds)
    try:
        return fn()
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, previous)


def _corpus_leg(contracts, use_device, deadline_s=None):
    """One A/B leg. Legs share one process, so the query memo is
    cleared each time — without the reset the second leg would ride
    the first leg's solves.

    `deadline_s` bounds the leg INSIDE the analyzer (the supervisor is
    consulted at every contract boundary, emitting a partial result
    list) — the BENCH_r05 fix: the SIGALRM wrapper alone can be
    swallowed by per-contract error containment, which let a host leg
    run 691s past its alarm and the outer timeout kill the process
    with no JSON emitted (rc:124/parsed:null)."""
    from mythril_tpu import observe
    from mythril_tpu.analysis.corpus import analyze_corpus
    from mythril_tpu.support.model import clear_cache
    from mythril_tpu.laser.smt.solver.solver_statistics import (
        SolverStatistics,
    )

    stats = SolverStatistics()
    stats.enabled = True
    clear_cache()
    d0 = stats.device_sat_count
    solver_marker = observe.solver_marker()
    t0 = time.perf_counter()
    results = analyze_corpus(
        contracts,
        transaction_count=2,
        execution_timeout=CONV_EXEC_TIMEOUT_S,
        create_timeout=10,
        use_device=use_device,
        device_budget_s=CONV_DEVICE_BUDGET_S if use_device is None else None,
        processes=1,
        deadline_s=deadline_s,
        on_timeout="partial",
        # multi-chip: with >1 visible device the device leg runs the
        # mesh corpus scheduler (one wave engine per device group,
        # work stealing) — the single-chip leg is unchanged
        devices=_bench_devices() if use_device is None else None,
    )
    wall = time.perf_counter() - t0
    prepass = max(
        ((r.get("device_prepass") or {}) for r in results),
        key=lambda s: s.get("device_steps", 0),
    )
    # distinct findings: the criteria metric. The reference re-reports
    # some classes per end-state (ExternalCalls dedupe=False), so raw
    # counts measure duplication, not coverage.
    distinct = len(
        {
            (r["name"], i["swc-id"], i["address"])
            for r in results
            for i in r["issues"]
        }
    )
    # span-derived device overlap for THIS leg: only wave.device spans
    # that closed after the leg started count
    leg_spans = [
        s
        for s in observe.flight_recorder().tail(8192)
        if s.t1 >= t0
    ]
    return {
        "wall_s": round(wall, 1),
        "issues": sum(len(r["issues"]) for r in results),
        "distinct_issues": distinct,
        "states": sum(r.get("states", 0) for r in results),
        "errors": sum(1 for r in results if r["error"]),
        "owned": sum(1 for r in results if r.get("owned")),
        "device_sat": stats.device_sat_count - d0,
        "solver_attribution": observe.solver_attribution(solver_marker),
        "trace_overlap_frac": observe.overlap_fraction(
            leg_spans, name="wave.device"
        ),
        "prepass": prepass or None,
    }


def _spread(values) -> float:
    med = statistics.median(values)
    return (max(values) - min(values)) / med if med else 0.0


def _bench_devices():
    """Device-group count for the mesh scheduler: the visible device
    count when there is more than one chip, else None (single
    engine)."""
    try:
        import jax

        n = len(jax.devices())
        return n if n > 1 else None
    except Exception:
        return None


class _ConvAB:
    """Incremental device/host time-to-convergence A/B: pairs
    accumulate one at a time and summarize() re-aggregates after every
    pair, so main() can print a COMPLETE record after the first pair
    (headline-first) and refine it while budget remains."""

    def __init__(self):
        from mythril_tpu.analysis.corpusgen import synth_bench_corpus

        self.contracts = synth_bench_corpus(CONV_CONTRACTS)
        self.device_legs = []
        self.host_legs = []

    def _leg_deadline(self, cap=None) -> int:
        # each leg promises only the wall the bench budget still holds
        # (minus slack for the later bench halves); a leg that cannot
        # fit raises _Deadline NOW so the record says "deadline"
        # instead of the outer timeout killing the process mid-leg
        room = _budget_left() - 90
        if cap is not None:
            room = min(room, cap)
        room = int(min(LEG_DEADLINE_S, room))
        if room < 30:
            raise _Deadline()
        return room

    def warmup(self) -> None:
        """Warm the wave kernels at the legs' exact shapes (one
        untimed wave): jit tracing + compile are once-per-machine
        costs (persistent compile cache), not per-corpus costs, and
        the first device leg must not carry them into the median."""
        try:
            from mythril_tpu.analysis.corpus import corpus_device_prepass

            # budget 0: each phase still opens its one unconditional
            # wave, through the SAME sizing rules (lanes/caps/mesh)
            # the timed legs resolve
            _with_deadline(
                lambda: corpus_device_prepass(
                    self.contracts,
                    budget_s=0.0,
                    mesh_groups=_bench_devices(),
                ),
                min(240, self._leg_deadline()),
            )
            print("bench: corpus wave kernels warmed", file=sys.stderr)
        except _Deadline:
            raise
        except Exception as e:
            print(f"bench: corpus warmup skipped: {e!r}", file=sys.stderr)

    def run_pair(self, headline: bool = False) -> None:
        """One interleaved device+host pair. A headline pair is
        additionally bounded by the headline window so the FIRST
        record prints inside the capture window no matter what the
        corpus does."""
        import logging

        logging.disable(logging.WARNING)
        try:
            for use_device, bucket in (
                (None, self.device_legs),
                (False, self.host_legs),
            ):
                cap = None
                if headline:
                    legs_left = 2 if use_device is None else 1
                    cap = max(30, int((_headline_left() - 30) / legs_left))
                room = self._leg_deadline(cap)
                bucket.append(
                    _with_deadline(
                        lambda room=room, ud=use_device: _corpus_leg(
                            self.contracts, ud,
                            deadline_s=max(30, room - 30),
                        ),
                        room,
                    )
                )
        finally:
            logging.disable(logging.NOTSET)
        pair = len(self.host_legs)
        print(
            f"bench: conv pair {pair}/{CONV_PAIRS}: device "
            f"{self.device_legs[-1]['wall_s']}s/"
            f"{self.device_legs[-1]['distinct_issues']} findings "
            f"({self.device_legs[-1]['owned']} owned) vs host "
            f"{self.host_legs[-1]['wall_s']}s/"
            f"{self.host_legs[-1]['distinct_issues']} findings",
            file=sys.stderr,
        )

    def summarize(self, strict: bool = True) -> dict:
        """Aggregate whatever pairs have run: medians + spreads +
        explicit criteria (the same record shape at every refinement).
        With `strict` and >1 pair, a spread-gate violation raises so
        __main__'s retry reruns the measurement."""
        device_legs, host_legs = self.device_legs, self.host_legs
        if not device_legs or not host_legs:
            return {}
        d_walls = [leg["wall_s"] for leg in device_legs]
        h_walls = [leg["wall_s"] for leg in host_legs]
        d_spread, h_spread = _spread(d_walls), _spread(h_walls)
        spread_rejected = (
            len(d_walls) > 1 and max(d_spread, h_spread) > SPREAD_GATE
        )
        if spread_rejected and strict:
            raise RuntimeError(
                f"convergence A/B spread gate: device {d_spread:.2f} / "
                f"host {h_spread:.2f} exceeds {SPREAD_GATE} — the regime "
                "is too noisy to record"
            )

        # the prepass counters of the median device leg (the recorded one)
        median_leg = device_legs[
            d_walls.index(sorted(d_walls)[len(d_walls) // 2])
        ]
        d_wall = statistics.median(d_walls)
        h_wall = statistics.median(h_walls)
        d_found = int(
            statistics.median([leg["distinct_issues"] for leg in device_legs])
        )
        h_found = int(
            statistics.median([leg["distinct_issues"] for leg in host_legs])
        )
        speedup = round(h_wall / d_wall, 3) if d_wall else None
        out = {
            "corpus_contracts": len(self.contracts),
            "spread_rejected": spread_rejected,
            "corpus_pairs": len(host_legs),
            "corpus_exec_timeout_s": CONV_EXEC_TIMEOUT_S,
            "corpus_wall_s": d_wall,
            "corpus_wall_spread": round(d_spread, 3),
            "corpus_issues": d_found,
            "corpus_issues_raw": int(
                statistics.median([leg["issues"] for leg in device_legs])
            ),
            "corpus_owned_contracts": int(
                statistics.median([leg["owned"] for leg in device_legs])
            ),
            "corpus_errors": max(leg["errors"] for leg in device_legs),
            "host_only_wall_s": h_wall,
            "host_only_wall_spread": round(h_spread, 3),
            "host_only_issues": h_found,
            "host_only_issues_raw": int(
                statistics.median([leg["issues"] for leg in host_legs])
            ),
            "contracts_per_sec": round(len(self.contracts) / d_wall, 3)
            if d_wall
            else None,
            "device_sat_verdicts_corpus": sum(
                leg["device_sat"] for leg in device_legs
            ),
            "corpus_walls_device": d_walls,
            "corpus_walls_host": h_walls,
            # the round's pass/fail thresholds, stated in the artifact so
            # narrative and record cannot diverge (round-4 lesson)
            "criteria": {
                "speedup_def": "median host_only_wall_s / corpus_wall_s",
                "speedup_target": SPEEDUP_TARGET,
                "speedup_measured": speedup,
                "speedup_pass": bool(
                    speedup is not None and speedup >= SPEEDUP_TARGET
                ),
                "findings_def": "median distinct (contract, swc, address)",
                "findings_device": d_found,
                "findings_host": h_found,
                "findings_parity_pass": d_found >= h_found,
            },
        }
        # per-origin solver attribution + span-derived wave overlap of
        # the recorded (median) device leg — the ISSUE-7 observability
        # fields (ROADMAP item 1 reads solver_attribution to see which
        # engine owns the verdicts)
        out["solver_attribution"] = median_leg.get(
            "solver_attribution"
        ) or {}
        out["trace_overlap_frac"] = median_leg.get(
            "trace_overlap_frac", 0.0
        )
        prepass = median_leg.get("prepass") or {}
        for k, v in prepass.items():
            if k not in ("scope", "partial", "mesh"):
                out[f"prepass_{k}"] = v
        # the pipelined-wave-engine headline metrics, promoted out of the
        # prepass_* namespace (ISSUE 4 acceptance: bench reports them)
        for alias in (
            "wave_overlap_ratio",
            "device_idle_frac",
            "evidence_bytes_per_wave",
            "waves_overlapped",
            "pipelined",
        ):
            if f"prepass_{alias}" in out:
                out[alias] = out[f"prepass_{alias}"]
        if out.get("prepass_evidence_bytes_full") and out.get(
            "prepass_evidence_bytes"
        ):
            out["evidence_compaction_ratio"] = round(
                out["prepass_evidence_bytes_full"]
                / max(1, out["prepass_evidence_bytes"]),
                2,
            )
        # mesh scheduler observability (ISSUE 5 acceptance: the bench
        # reports mesh_devices / steal_count / per-device occupancy)
        mesh = prepass.get("mesh") or {}
        out["mesh_devices"] = prepass.get(
            "mesh_devices", mesh.get("devices", 1)
        )
        out["mesh_groups"] = prepass.get("mesh_groups", mesh.get("groups", 1))
        out["steal_count"] = prepass.get("steal_count", mesh.get("steals", 0))
        out["rebalance_bytes"] = prepass.get(
            "rebalance_bytes", mesh.get("rebalance_bytes", 0)
        )
        out["mesh_occupancy"] = [
            {
                "group": g.get("group"),
                "occupancy": g.get("occupancy"),
                "waves": g.get("waves"),
                "steals": g.get("steals", 0),
            }
            for g in mesh.get("per_device", [])
        ]
        return out

def bench_hard_solve(budget_s: int = 300) -> dict:
    """The solver-race half (VERDICT r4 item 3): BEC-guard-shaped
    queries — `x*y/y != x (y != 0)`, the SWC-101 multiplication+
    division circuit — posed through the public Solver surface twice:

    - host leg: device solving OFF (pure incremental CDCL);
    - race leg: device solving ON — the CDCL marathon races the
      on-chip portfolio (laser/smt/solver/device_race.py), first
      answer wins, witnesses validated/extended before being believed.

    Each leg gets a fresh blast session (reset_blast_session) so the
    comparison is cold-for-cold. Reports per-leg walls plus the race
    scorecard (device_sat_verdicts_hard, race_wins/race_losses) —
    the counters the round-4 verdict asked to see in the artifact."""
    import random

    from mythril_tpu.support.support_args import args as _args
    from mythril_tpu.laser.smt import terms
    from mythril_tpu.laser.smt.solver.solver import (
        check_terms,
        reset_blast_session,
    )
    from mythril_tpu.laser.smt.solver.solver_statistics import (
        SolverStatistics,
    )

    rng = random.Random(41)
    W = 256

    def queries():
        out = []
        for k in range(3):
            x = terms.bv_var(f"hs_x{k}", W)
            y = terms.bv_var(f"hs_y{k}", W)
            q = terms.udiv(terms.mul(x, y), y)
            out.append(
                [
                    terms.bnot(terms.eq(q, x)),
                    terms.bnot(terms.eq(y, terms.bv_const(0, W))),
                    terms.ult(
                        terms.bv_const(rng.getrandbits(64), W), x
                    ),
                ]
            )
        return out

    stats = SolverStatistics()
    stats.enabled = True
    legs = {}
    restore = _args.device_solving
    # one materialization: both legs must solve the SAME instances
    # (terms are interned process-wide and survive the session reset)
    qs = queries()
    try:
        for leg, mode in (("host", "never"), ("race", "always")):
            _args.device_solving = mode
            reset_blast_session()
            d0, w0, l0 = (
                stats.device_sat_count, stats.race_wins, stats.race_losses,
            )
            walls = []
            sats = 0
            for cs in qs:

                def one(cs=cs):
                    t0 = time.perf_counter()
                    verdict, _model = check_terms(cs, timeout_ms=30_000)
                    return verdict, time.perf_counter() - t0

                try:
                    verdict, dt = _with_deadline(one, budget_s)
                except _Deadline:
                    verdict, dt = "deadline", float(budget_s)
                walls.append(round(dt, 1))
                sats += verdict == "sat"
            legs[leg] = {
                "walls": walls,
                "wall_s": round(sum(walls), 1),
                "sat": sats,
                "device_sat": stats.device_sat_count - d0,
                "race_wins": stats.race_wins - w0,
                "race_losses": stats.race_losses - l0,
            }
            print(f"bench: hard-solve {leg} leg {legs[leg]}", file=sys.stderr)
    finally:
        _args.device_solving = restore
        reset_blast_session()
    out = {
        "hard_solve_host_wall_s": legs["host"]["wall_s"],
        "hard_solve_race_wall_s": legs["race"]["wall_s"],
        "hard_solve_host_walls": legs["host"]["walls"],
        "hard_solve_race_walls": legs["race"]["walls"],
        "hard_solve_host_sat": legs["host"]["sat"],
        "hard_solve_race_sat": legs["race"]["sat"],
        "device_sat_verdicts_hard": legs["race"]["device_sat"],
        "race_wins": legs["race"]["race_wins"],
        "race_losses": legs["race"]["race_losses"],
    }
    if legs["race"]["wall_s"]:
        out["hard_solve_speedup"] = round(
            legs["host"]["wall_s"] / legs["race"]["wall_s"], 3
        )
    return out


def bench_device_default_path(budget_s: int = 210) -> dict:
    """The default `myth analyze` path with the device engaged: one
    reference contract analyzed single-process, reporting how much
    stepping/solving the TPU did. Runs last, under a deadline: the
    device kernels' first-compile cost must never sink the earlier
    metrics."""
    from mythril_tpu.analysis.goldens import GOLDEN_FIXTURES

    target = GOLDEN_FIXTURES / "exceptions.sol.o"
    if not target.exists():
        return {}

    import logging

    logging.disable(logging.WARNING)
    try:
        from mythril_tpu.analysis.corpus import analyze_corpus
        from mythril_tpu.laser.smt.solver.solver_statistics import (
            SolverStatistics,
        )

        stats = SolverStatistics()
        stats.enabled = True
        d0, c0 = stats.device_sat_count, stats.cdcl_sat_count
        t0 = time.perf_counter()

        def run():
            return analyze_corpus(
                [(target.read_text().strip(), "", target.stem)],
                transaction_count=2,
                execution_timeout=30,
                create_timeout=10,
                processes=1,
            )

        results = _with_deadline(run, budget_s)
        out = {
            "default_path_wall_s": round(time.perf_counter() - t0, 1),
            "default_path_issues": len(results[0]["issues"]),
            "device_sat_verdicts": stats.device_sat_count - d0,
            "cdcl_sat_verdicts": stats.cdcl_sat_count - c0,
        }
        for k, v in (results[0].get("device_prepass") or {}).items():
            out[f"default_prepass_{k}"] = v
    except _Deadline:
        print("bench: default-path half hit its deadline", file=sys.stderr)
        return {"default_path": "deadline"}
    except Exception as e:
        print(f"bench: default-path half skipped: {e!r}", file=sys.stderr)
        return {"default_path": "skipped"}
    finally:
        logging.disable(logging.NOTSET)
    print(f"bench: default path {out}", file=sys.stderr)
    return out


def bench_store(budget_s: int = 150) -> dict:
    """The duplicate-heavy verdict-store leg (mythril_tpu/store): a
    COLD corpus of base contracts analyzes host-only with write-back,
    then a WARM corpus of exact duplicates plus one-selector forks
    runs against the same store directory. At real traffic most
    submissions are the warm shape — the leg measures what the store
    refunds: `store_hit_rate` (exact settles / warm corpus),
    `incremental_rate` (fingerprint-diff re-analyses), and
    `warm_hit_p50_s` (median settle wall of an exact hit — the
    admission-tier latency a repeat job pays instead of a full
    pipeline)."""
    import statistics
    import tempfile

    from mythril_tpu.analysis.corpus import analyze_corpus
    from mythril_tpu.analysis.corpusgen import (
        deadweight_contract,
        fork_contract,
    )

    store_dir = tempfile.mkdtemp(prefix="myth-bench-store-")
    bases = [
        (fork_contract(0, 0), "", "storebase#0"),
        (fork_contract(1, 0), "", "storebase#1"),
        (deadweight_contract(0), "", "storebase#2"),
    ]
    leg_deadline = max(30.0, budget_s * 0.45)
    t0 = time.monotonic()
    analyze_corpus(
        bases,
        execution_timeout=8,
        processes=1,
        use_device=False,
        store_dir=store_dir,
        deadline_s=leg_deadline,
    )
    cold_wall = time.monotonic() - t0
    # warm traffic: every base resubmitted byte-for-byte, plus a fork
    # of base#0 whose SECOND function is untouched (one-selector
    # mutation — the incremental tier's population)
    warm_corpus = [
        (code, "", f"{name}#dupe") for code, _c, name in bases
    ] + [(fork_contract(0, 1), "", "storefork#0")]
    t1 = time.monotonic()
    warm = analyze_corpus(
        warm_corpus,
        execution_timeout=8,
        processes=1,
        use_device=False,
        store_dir=store_dir,
        deadline_s=leg_deadline,
    )
    warm_wall = time.monotonic() - t1
    hits = [r for r in warm if r and r.get("store_hit")]
    incrementals = [
        r for r in warm if r and r.get("store_incremental")
    ]
    out = {
        "store_hit_rate": round(len(hits) / len(warm_corpus), 3),
        "incremental_rate": round(
            len(incrementals) / len(warm_corpus), 3
        ),
        "warm_hit_p50_s": (
            round(
                statistics.median(
                    [r.get("wall_s") or 0.0 for r in hits]
                ),
                6,
            )
            if hits
            else None
        ),
        "store_cold_wall_s": round(cold_wall, 3),
        "store_warm_wall_s": round(warm_wall, 3),
    }
    print(f"bench: store leg {out}", file=sys.stderr)
    return out


def bench_journal(rounds: int = 48) -> dict:
    """Durable-journal (WAL) overhead on the warm admission tier
    (ISSUE 14): an engine-less service settling static-answer
    submissions — the fastest settle path the service has, so the
    per-record WAL cost shows up at its worst — with the journal on
    vs off. `journal_overhead_frac` = (p50_on - p50_off) / p50_off
    over that path; instant-tier settle records are written unsynced
    by design, so this measures the buffered-write cost (the fsync'd
    full-path figure is gated in tools/chaos_smoke.py against the
    warm wave p50). `journal_admit_p50_s` is the durable (fsync'd)
    admission record alone — the latency a queued submission pays for
    the crash-safety guarantee."""
    import statistics
    import tempfile

    from mythril_tpu.analysis.corpusgen import clean_contract
    from mythril_tpu.service.engine import AnalysisEngine, ServiceConfig
    from mythril_tpu.service.jobs import Job

    def leg(journal_dir):
        engine = AnalysisEngine(ServiceConfig(
            stripes=2, lanes_per_stripe=2, host_walk=False,
            queue_capacity=rounds * 2 + 8, journal_dir=journal_dir,
        ))
        walls = []
        for i in range(rounds):
            t0 = time.perf_counter()
            engine.submit(Job(clean_contract(i % 8)))
            walls.append(time.perf_counter() - t0)
        admits = []
        for _ in range(rounds // 2):
            t0 = time.perf_counter()
            engine.submit(Job("33ff"))  # queue path: fsync'd admit
            admits.append(time.perf_counter() - t0)
        # drop the first rounds (summary-cache warmup) per leg
        return (
            statistics.median(walls[8:]),
            statistics.median(admits),
        )

    p50_off, admit_off = leg(None)
    with tempfile.TemporaryDirectory(prefix="myth-bench-wal-") as jd:
        p50_on, admit_on = leg(jd)
    out = {
        "journal_overhead_frac": (
            round(max(0.0, (p50_on - p50_off)) / p50_off, 4)
            if p50_off
            else None
        ),
        "journal_warm_p50_off_s": round(p50_off, 6),
        "journal_warm_p50_on_s": round(p50_on, 6),
        "journal_admit_p50_s": round(admit_on, 6),
    }
    print(f"bench: journal leg {out}", file=sys.stderr)
    return out


def bench_fleet(jobs_per_leg: int = 6) -> dict:
    """Federated serving leg (ISSUE 15): in-process `myth serve`
    replicas behind a FleetFront.

    - `fleet_throughput_scale`: wall for the same full-wave job mix
      through a 1-replica front vs a 2-replica front (>= ~1 says the
      front stripes instead of serializing; true scaling needs real
      parallel hardware — on a 1-core CPU container the two engines
      time-slice, so the gate threshold is loose);
    - `fleet_failover_p50_s`: p50 of death-detection -> settle for
      jobs re-routed off a SIGKILLed-equivalent replica whose
      verdicts were already banked in the fleet-shared store — the
      reroute-after-restart-settles-in-microseconds claim, measured;
    - `fleet_reroute_dedup_rate`: deduped / rerouted on that leg.
    """
    import statistics
    import tempfile

    from mythril_tpu.fleet import FleetConfig, FleetFront
    from mythril_tpu.service.engine import ServiceConfig
    from mythril_tpu.service.server import AnalysisServer

    # module-applicable shapes (never static-answered: the jobs must
    # genuinely ride waves, or the legs only measure HTTP overhead)
    codes = [
        "33ff",  # selfdestruct(caller)
        "32ff",  # selfdestruct(origin)
        "336000556000ff",  # caller -> storage, then selfdestruct
    ]
    cfg = dict(
        stripes=2, lanes_per_stripe=4, steps_per_wave=128, max_waves=2,
        queue_capacity=32, host_walk=False, coalesce_wait_s=0.02,
        idle_wait_s=0.05,
    )
    fleet_kw = dict(
        probe_interval_s=0.2, failure_threshold=2, recovery_s=300.0
    )

    def throughput(n_replicas: int) -> float:
        servers = [
            AnalysisServer(ServiceConfig(**cfg)).start()
            for _ in range(n_replicas)
        ]
        front = FleetFront(
            FleetConfig([s.url for s in servers], **fleet_kw)
        ).start()
        try:
            # warm the wave kernel off the clock (shared compile cache
            # across replicas/legs: identical arena shape)
            warm = front.submit(codes[0], idempotency_key="fl-warm")
            front.report(warm.id, wait_s=240.0)
            t0 = time.perf_counter()
            batch = [
                front.submit(
                    codes[i % len(codes)],
                    idempotency_key=f"fl-tp{n_replicas}-{i}",
                )
                for i in range(jobs_per_leg)
            ]
            for job in batch:
                doc = front.report(job.id, wait_s=240.0)
                assert doc["state"] == "done", doc
            return time.perf_counter() - t0
        finally:
            front.close()
            for s in servers:
                s.close()

    t1 = throughput(1)
    t2 = throughput(2)

    # -- failover leg: banked verdicts re-route in microseconds -------
    # host_walk=True here: only a completed host walk writes its
    # verdict back to the shared store, and the banked verdict is what
    # the re-route dedupes through
    store_dir = tempfile.mkdtemp(prefix="myth-bench-fleet-")
    fo_cfg = dict(cfg, host_walk=True)
    victim = AnalysisServer(
        ServiceConfig(store_dir=store_dir, **fo_cfg)
    ).start()
    survivor = AnalysisServer(
        ServiceConfig(store_dir=store_dir, **fo_cfg)
    ).start()
    front = FleetFront(
        FleetConfig([victim.url, survivor.url], **fleet_kw)
    ).start()
    try:
        batch = []
        for i in range(jobs_per_leg):
            job = front.submit(
                codes[i % len(codes)], idempotency_key=f"fl-fo{i}"
            )
            batch.append(job)
        # wait until every job settled ON ITS REPLICA (polling the
        # replicas directly: the front still believes them in-flight,
        # which is exactly the crash window)
        server_of = {"r0": victim, "r1": survivor}
        deadline = time.monotonic() + 240.0
        for job in batch:
            client = server_of[job.replica].engine.queue
            while time.monotonic() < deadline:
                remote = client.get(job.remote_id)
                if remote is not None and remote.terminal:
                    break
                time.sleep(0.02)
        kill_t = time.monotonic()
        victim._httpd.shutdown()
        victim._httpd.server_close()
        while front.failovers == 0 and time.monotonic() - kill_t < 30:
            front.check_replicas()
        walls = []
        for job in batch:
            doc = front.report(job.id, wait_s=60.0)
            assert doc["state"] == "done", doc
            if job.rerouted and job.finished_t is not None and (
                job.failover_t is not None
            ):
                walls.append(job.finished_t - job.failover_t)
        fleet = front.stats()["fleet"]
        out = {
            "fleet_throughput_scale": (
                round(t1 / t2, 3) if t2 else None
            ),
            "fleet_throughput_1r_wall_s": round(t1, 3),
            "fleet_throughput_2r_wall_s": round(t2, 3),
            "fleet_failover_p50_s": (
                round(statistics.median(walls), 6) if walls else None
            ),
            "fleet_reroute_dedup_rate": (
                round(fleet["reroute_deduped"] / fleet["rerouted"], 3)
                if fleet["rerouted"]
                else None
            ),
            "fleet_rerouted_jobs": fleet["rerouted"],
        }
    finally:
        front.close()
        survivor.close()
        try:
            victim.engine._draining = True
            victim.engine._drained.set()
            victim.close()
        except Exception:
            pass
    print(f"bench: fleet leg {out}", file=sys.stderr)
    return out


def bench_chainstream(blocks: int = 30, per_block: int = 2) -> dict:
    """Chain-head streaming leg (ISSUE 16): a ChainWatcher over an
    in-process scripted chain (fake clients under the REAL
    RpcEndpoint/RpcPool/cursor/triage machinery; no network, no
    front — the fleet handoff is the fleet leg's problem).

    - `ingest_static_rate`: distinct contracts static-triaged per
      second on the ingest path (line-rate triage under a burst of
      `blocks * per_block` fresh deployments);
    - `alert_p50_s`: p50 block-seen -> alert-fired (gated: the SLO
      story wants it far under any real block time);
    - `head_lag_blocks_max`: deepest backlog observed while draining
      the burst with a bounded per-tick backfill batch;
    - `reorg_recovery_s`: wall for a 3-block reorg to resolve —
      rollback + retraction + canonical re-ingest to the new head.
    """
    import hashlib as _hashlib
    import statistics
    import tempfile

    from mythril_tpu.chainstream import ChainWatcher, RpcEndpoint, RpcPool
    from mythril_tpu.chainstream import WatchConfig
    from mythril_tpu.ethereum.interface.rpc.exceptions import (
        RpcErrorResponse,
    )

    def _sha(text):
        return "0x" + _hashlib.sha256(text.encode()).hexdigest()

    class _Chain:
        def __init__(self):
            self.blocks, self.codes, self.receipts = [], {}, {}
            self.add_block()

        def add_block(self, deployments=(), salt="main"):
            number = len(self.blocks)
            parent = (
                self.blocks[-1]["hash"] if self.blocks
                else "0x" + "0" * 64
            )
            txs = []
            for i, (address, code_hex) in enumerate(deployments):
                txh = _sha(f"tx:{number}:{i}:{salt}")
                txs.append({"hash": txh, "to": None, "input": "0x"})
                self.receipts[txh] = {"contractAddress": address}
                self.codes[address.lower()] = "0x" + code_hex
            self.blocks.append({
                "number": hex(number),
                "hash": _sha(f"block:{number}:{salt}"),
                "parentHash": parent,
                "transactions": txs,
            })

    class _Client:
        def __init__(self, chain):
            self.chain = chain

        def eth_blockNumber(self, timeout_s=None):
            return len(self.chain.blocks) - 1

        def eth_getBlockByNumber(self, block, tx_objects=True,
                                 timeout_s=None):
            number = block if isinstance(block, int) else int(block, 16)
            if 0 <= number < len(self.chain.blocks):
                return self.chain.blocks[number]
            raise RpcErrorResponse(-32001, "unknown block")

        def eth_getTransactionReceipt(self, tx_hash, timeout_s=None):
            return self.chain.receipts[tx_hash]

        def eth_getCode(self, address, default_block="latest",
                        timeout_s=None):
            return self.chain.codes.get(address.lower(), "0x")

    chain = _Chain()
    pool = RpcPool([RpcEndpoint("e0", _Client(chain), retries=0)])
    state = tempfile.mkdtemp(prefix="myth-bench-stream-")
    watcher = ChainWatcher(
        pool, state,
        config=WatchConfig(start_block=0, backfill_batch=8),
    )
    watcher.tick()  # genesis + static-layer warmup off the clock

    # -- ingest burst: every deployment a DISTINCT bytecode ------------
    n_contracts = 0
    for b in range(blocks):
        deployments = []
        for j in range(per_block):
            i = b * per_block + j
            # PUSH1 i PUSH1 0 SSTORE CALLER SELFDESTRUCT — distinct
            # code hash per contract, module-applicable (survivor)
            code = f"60{i % 256:02x}60005533ff"
            deployments.append((_sha(f"bench-dep:{i}")[:42], code))
            n_contracts += 1
        chain.add_block(deployments=deployments)
    lag_max = 0
    t0 = time.perf_counter()
    while watcher.head_lag() != 0 or watcher.head != len(chain.blocks) - 1:
        watcher.tick()
        lag_max = max(lag_max, watcher.head_lag() or 0)
    ingest_wall = time.perf_counter() - t0
    latencies = sorted(
        a.latency_s for a in watcher.alerts.alerts()
        if a.latency_s is not None
    )

    # -- 3-block reorg recovery ----------------------------------------
    chain.blocks = chain.blocks[:-3]
    for _ in range(4):  # the fork wins by one
        chain.add_block(salt="fork")
    t0 = time.perf_counter()
    while (
        watcher.cursor.tip() is None
        or watcher.cursor.tip().block_hash != chain.blocks[-1]["hash"]
    ):
        watcher.tick()
    reorg_wall = time.perf_counter() - t0
    watcher.close()
    out = {
        "ingest_static_rate": (
            round(n_contracts / ingest_wall, 1) if ingest_wall else None
        ),
        "alert_p50_s": (
            round(statistics.median(latencies), 6) if latencies else None
        ),
        "head_lag_blocks_max": lag_max,
        "reorg_recovery_s": round(reorg_wall, 6),
        "chainstream_reorgs": watcher.reorgs,
    }
    print(f"bench: chainstream leg {out}", file=sys.stderr)
    return out


def bench_compileplane() -> dict:
    """Zero-cold-start compile plane leg (ISSUE 17): bake a one-bucket
    kernel pack for a tiny dispatch shape, then measure both boot
    paths on the SAME arena avals —

    - `cold_ready_no_pack_s`: the in-process compile a packless
      replica pays before its first wave (the bake's own compile wall,
      which IS that compile);
    - `cold_ready_pack_s`: mount the pack + run the first wave off the
      deserialized AOT executable, zero in-process compiles;
    - `kernel_pack_hit_rate` (gated): pack hits over pack-consulting
      lookups — 1.0 on this leg, a drop means the load path broke;
    - `aot_load_p50_s`: p50 artifact deserialize wall.
    """
    import shutil
    import tempfile

    import jax

    from mythril_tpu.compileplane.pack import (
        _arena_for,
        bake_service_pack,
        service_shape,
    )
    from mythril_tpu.compileplane.plane import configure_plane, reset_plane
    from mythril_tpu.laser.batch.run import (
        clear_aot_generic,
        generic_aot_stats,
        wave_run,
    )

    shape_args = dict(
        stripes=2, lanes_per_stripe=2, steps_per_wave=32, code_cap=32
    )
    shape = service_shape(**shape_args)
    pack_dir = tempfile.mkdtemp(prefix="myth-bench-pack-")
    reset_plane()
    clear_aot_generic()
    try:
        manifest = bake_service_pack(pack_dir, [None], **shape_args)
        cold_no_pack = manifest["baked"][0]["wall_s"]

        # a "fresh replica": no plane, no AOT table, no jit caches
        reset_plane()
        clear_aot_generic()
        jax.clear_caches()
        plane = configure_plane(pack_dirs=(pack_dir,))
        t0 = time.perf_counter()
        mounted = plane.mount_packs()
        batch, table, _substep = _arena_for(shape)
        out_state = wave_run(
            batch,
            table,
            max_steps=shape["steps_per_wave"],
            track_coverage=True,
            donate=False,
        )
        jax.block_until_ready(out_state[1])
        cold_pack = time.perf_counter() - t0
        stats = plane.stats()
        out = {
            "cold_ready_no_pack_s": round(cold_no_pack, 3),
            "cold_ready_pack_s": round(cold_pack, 3),
            "compileplane_speedup": (
                round(cold_no_pack / cold_pack, 2) if cold_pack else None
            ),
            "kernel_pack_hit_rate": stats["kernel_pack_hit_rate"],
            "aot_load_p50_s": stats["aot_load_p50_s"],
            "compileplane_artifacts": manifest["artifacts"],
            "compileplane_mounted": mounted["mounted"],
            "compileplane_inproc_compiles": generic_aot_stats()["compiles"],
        }
    finally:
        reset_plane()
        clear_aot_generic()
        shutil.rmtree(pack_dir, ignore_errors=True)
    print(f"bench: compileplane leg {out}", file=sys.stderr)
    return out


def bench_router() -> dict:
    """Routed-vs-uniform tier-ladder A/B (ISSUE 19), the data
    flywheel demo in one leg:

    1. UNIFORM leg: today's ladder over a mixed synthetic corpus
       (every contract pays the device prepass), routing records
       accumulating in-process;
    2. train a router artifact FROM THAT LEG'S OWN records (`myth
       route train` under the hood) — the flywheel's first turn;
    3. ROUTED leg: the same corpus with the artifact mounted —
       cheap-predicted contracts skip straight to the host walk, the
       device budget concentrates on the rest, overruns promote.

    Headline fields: `routed_speedup` (gated — uniform wall over
    routed wall, must stay > 1), `routing_regret` (model-priced
    seconds the uniform leg burnt on mispriced routes),
    `router_artifact_version`."""
    import shutil
    import tempfile

    from mythril_tpu import observe, routing
    from mythril_tpu.analysis.corpus import analyze_corpus
    from mythril_tpu.analysis.corpusgen import synth_bench_corpus
    from mythril_tpu.support.model import clear_cache

    contracts = synth_bench_corpus(max(8, min(CONV_CONTRACTS, 16)))

    def _leg(router_dir=None, router_on=None):
        clear_cache()
        t0 = time.perf_counter()
        results = analyze_corpus(
            contracts,
            transaction_count=1,
            execution_timeout=8,
            create_timeout=10,
            use_device=True,  # the ladder under test, CPU backend or not
            processes=1,
            deadline_s=max(60, min(240, int(_budget_left() - 60))),
            on_timeout="partial",
            router_dir=router_dir,
            router=router_on,
        )
        return time.perf_counter() - t0, results

    log = observe.routing_log()
    log.clear()
    uniform_wall, _uniform = _leg(router_on=False)
    records = log.tail(4096)
    artifact_dir = tempfile.mkdtemp(prefix="myth-bench-router-")
    try:
        model = routing.train_model(records)  # ValueError when starved
        routing.save_router(artifact_dir, model)
        router = routing.load_router(artifact_dir)
        if router is None:
            raise RuntimeError("freshly saved router artifact refused")
        regret = None
        try:
            regret = routing.evaluate_log(records, router)["regret_s"]
        except Exception:
            pass
        log.clear()
        routed_wall, routed = _leg(router_dir=artifact_dir, router_on=True)
        out = {
            "router_uniform_wall_s": round(uniform_wall, 2),
            "router_routed_wall_s": round(routed_wall, 2),
            "routed_speedup": (
                round(uniform_wall / routed_wall, 3)
                if routed_wall else None
            ),
            "routing_regret": (
                round(regret, 3) if regret is not None else None
            ),
            "router_artifact_version": router.version,
            "router_trained_rows": model["trained_rows"],
            "router_routed_contracts": sum(
                1 for r in routed if r.get("routed")
            ),
            "router_promoted_contracts": sum(
                1 for r in routed if r.get("promoted")
            ),
        }
    finally:
        shutil.rmtree(artifact_dir, ignore_errors=True)
    print(f"bench: router leg {out}", file=sys.stderr)
    return out


def _emit(record: dict, stage: str) -> None:
    """Print the one-line JSON record NOW. Called after the headline
    phases (transitions + one convergence pair) and again after every
    refinement: a capture that closes at any point past the headline
    emit still holds a complete, parseable record — the last printed
    line supersedes earlier ones."""
    record["bench_emit"] = stage
    record["bench_wall_s"] = round(time.monotonic() - _BENCH_T0, 1)
    _device_saturation_fields(record)
    # tier circuit-breaker scorecard (ISSUE 14): cumulative trips
    # across every tier at emit time — a healthy run reports 0
    try:
        from mythril_tpu.support.breaker import trips_total

        record["breaker_trips"] = trips_total()
    except Exception:
        pass
    print(json.dumps(record), flush=True)


def _device_saturation_fields(record: dict) -> None:
    """The devicemon sample at emit time (ISSUE 12): how full the
    hardware was when this record closed — device memory where the
    backend reports it, process RSS everywhere, cumulative wave
    overlap/idle fractions."""
    try:
        from mythril_tpu import observe

        sample = observe.device_monitor().sample()
    except Exception as e:
        print(f"bench: device sample failed: {e!r}", file=sys.stderr)
        return
    record["device_host_rss_bytes"] = sample.get("host_rss_bytes")
    record["device_mem_bytes_in_use"] = sum(
        row.get("bytes_in_use") or 0
        for row in (sample.get("memory") or {}).values()
    ) or None
    record["device_wave_overlap_frac"] = sample.get("wave_overlap_frac")
    record["device_idle_frac"] = sample.get("idle_frac")


#: run-scoped markers for the solver flight-recorder fields: every
#: _emit reports the loss waterfall / capture count / cdcl-sat total
#: over the SAME window (main() start), so
#: sum(solver_loss_reasons.values()) == cdcl_sat_verdicts holds on
#: every printed record
_SOLVER_RUN_MARKER = None
_CDCL_SAT_BASE = 0
_DEVICE_SAT_BASE = 0


def _mark_solver_run() -> None:
    global _SOLVER_RUN_MARKER, _CDCL_SAT_BASE, _DEVICE_SAT_BASE
    from mythril_tpu import observe
    from mythril_tpu.laser.smt.solver.solver_statistics import (
        SolverStatistics,
    )

    _SOLVER_RUN_MARKER = observe.solver_marker()
    _CDCL_SAT_BASE = SolverStatistics().cdcl_sat_count
    _DEVICE_SAT_BASE = SolverStatistics().device_sat_count


def _solver_flight_fields(record: dict) -> None:
    """The flight-recorder scorecard (ISSUE 8): host-won loss reasons,
    captured-corpus size, and the matching run-scoped cdcl-sat count."""
    if _SOLVER_RUN_MARKER is None:
        return
    try:
        from mythril_tpu import observe
        from mythril_tpu.laser.smt.solver.solver_statistics import (
            SolverStatistics,
        )

        record["solver_loss_reasons"] = observe.loss_reasons(
            since=_SOLVER_RUN_MARKER, verdict="sat"
        )
        record["solver_loss_reasons_all"] = observe.loss_reasons(
            since=_SOLVER_RUN_MARKER
        )
        record["captured_queries"] = observe.captured_total(
            since=_SOLVER_RUN_MARKER
        )
        cdcl_sats = SolverStatistics().cdcl_sat_count - _CDCL_SAT_BASE
        device_sats = (
            SolverStatistics().device_sat_count - _DEVICE_SAT_BASE
        )
        record["cdcl_sat_verdicts"] = cdcl_sats
        record["device_sat_verdicts"] = device_sats
        # the ISSUE-9 acceptance headline: what fraction of this run's
        # SAT verdicts the accelerator OWNED (device-first funnel
        # target: > 0.5, up from 0.0 in BENCH_r02-r04)
        total_sats = cdcl_sats + device_sats
        record["device_verdict_share"] = (
            round(device_sats / total_sats, 3) if total_sats else 0.0
        )
    except Exception as e:
        print(f"bench: solver flight fields failed: {e!r}", file=sys.stderr)


def _refresh_headline(record: dict, dev: dict) -> None:
    """(Re)derive the cross-phase headline fields from the phase data
    currently in the record."""
    _solver_flight_fields(record)
    record["value"] = round(dev["rate"], 1) if "rate" in dev else None
    vs_baseline = None
    if record.get("corpus_wall_s") and record.get("host_only_wall_s"):
        vs_baseline = round(
            record["host_only_wall_s"] / record["corpus_wall_s"], 3
        )
    record["vs_baseline"] = vs_baseline
    # kernel-specialization scorecard: the process-wide compile-cache
    # counters at emit time (covers the A/B leg AND the corpus legs'
    # in-process explorers)
    try:
        from mythril_tpu.laser.batch.specialize import kernel_cache_stats

        ks = kernel_cache_stats()
        record["kernel_cache_hits"] = ks["hits"]
        record["kernel_cache_misses"] = ks["misses"]
        record["kernel_buckets"] = ks["size"]
        record["kernel_compile_s"] = ks["compile_s"]
    except Exception:
        pass


def main(final_attempt: bool = False) -> None:
    record = {
        "metric": "state_transitions_per_sec",
        "value": None,
        "unit": "states/sec",
        # measured: median host-only(proxy baseline, see BASELINE.md)
        # wall over median device wall on the corpus A/B
        "vs_baseline": None,
        "vs_baseline_def": "host_only_wall_s / corpus_wall_s (measured)",
        "n_lanes": N_LANES,
        "n_steps": N_STEPS,
        "bench_budget_s": BENCH_BUDGET_S,
        "headline_deadline_s": HEADLINE_DEADLINE_S,
        # mesh defaults so the fields exist even when the corpus half
        # never runs (budget-skipped records stay schema-complete)
        "mesh_devices": 1,
        "steal_count": 0,
        # telemetry defaults (ISSUE 7): populated by the corpus legs
        "solver_attribution": {},
        "trace_overlap_frac": 0.0,
        # flight-recorder defaults (ISSUE 8): refreshed at every emit
        "solver_loss_reasons": {},
        "captured_queries": 0,
        "cdcl_sat_verdicts": 0,
        # device-first funnel scorecard (ISSUE 9): refreshed at every
        # emit — device_sat / (device_sat + cdcl_sat) over the run
        "device_sat_verdicts": 0,
        "device_verdict_share": 0.0,
        # verdict-store scorecard (ISSUE 11): the duplicate-heavy leg
        # fills these; None = the leg never ran
        "store_hit_rate": None,
        "incremental_rate": None,
        "warm_hit_p50_s": None,
        # crash-safety scorecard (ISSUE 14): journal WAL overhead on
        # the warm admission tier + cumulative breaker trips
        # (refreshed at every emit; a healthy run reports 0 trips)
        "journal_overhead_frac": None,
        "breaker_trips": 0,
        # federated-serving scorecard (ISSUE 15): the fleet leg fills
        # these; None = the leg never ran
        "fleet_throughput_scale": None,
        "fleet_failover_p50_s": None,
        "fleet_reroute_dedup_rate": None,
        # chain-head streaming scorecard (ISSUE 16): the chainstream
        # leg fills these; None = the leg never ran
        "alert_p50_s": None,
        "head_lag_blocks_max": None,
        "reorg_recovery_s": None,
        "ingest_static_rate": None,
        # compile-plane scorecard (ISSUE 17): the compileplane leg
        # fills these; None = the leg never ran
        "cold_ready_no_pack_s": None,
        "cold_ready_pack_s": None,
        "kernel_pack_hit_rate": None,
        "aot_load_p50_s": None,
        # learned-router scorecard (ISSUE 19): the router A/B leg
        # fills these; None = the leg never ran (the compare gate
        # skips absent/None fields)
        "routed_speedup": None,
        "routing_regret": None,
        "router_artifact_version": None,
    }
    _mark_solver_run()
    capture_dir = os.environ.get("MYTHRIL_BENCH_CAPTURE_DIR")
    if capture_dir:
        # leave a hard-query corpus behind for solverlab tuning
        # (ROADMAP item 1): every query this bench solves becomes a
        # replayable artifact
        try:
            from mythril_tpu import observe as _observe

            _observe.configure_capture(capture_dir)
            record["capture_dir"] = capture_dir
        except Exception as e:
            print(f"bench: query capture unavailable: {e!r}", file=sys.stderr)
    if os.environ.get("MYTHRIL_BENCH_NO_OBSERVE"):
        # the telemetry-overhead differential leg: spans/attribution/
        # routing recording off, record fields stay at their defaults
        from mythril_tpu import observe

        observe.set_enabled(False)

    try:
        record.update(bench_static_prune())
        print("bench: static prune done", file=sys.stderr)
    except Exception as e:
        print(f"bench: static-prune half failed: {e!r}", file=sys.stderr)
        record["static_prune_rate"] = None
        record["static_answer_rate"] = None
        record["screen_mount_rate_opcode"] = None
        record["screen_mount_rate_semantic"] = None
        record["link_resolve_rate"] = None
        record["proxy_detect_rate"] = None
        record["callgraph_fingerprint_hit_rate"] = None
        record["static_link_wall_s"] = None

    try:
        record.update(bench_journal())
        print("bench: journal leg done", file=sys.stderr)
    except Exception as e:
        print(f"bench: journal leg failed: {e!r}", file=sys.stderr)

    try:
        record.update(
            _with_deadline(bench_chainstream, 120)
        )
        print("bench: chainstream leg done", file=sys.stderr)
    except _Deadline:
        print("bench: chainstream leg hit its deadline", file=sys.stderr)
    except Exception as e:
        print(f"bench: chainstream leg failed: {e!r}", file=sys.stderr)

    # the compile-plane leg runs EARLY (it clears the jit caches to
    # simulate a fresh replica — later legs recompile their own shapes
    # regardless, earlier ones must not have theirs dropped mid-use)
    if _budget_left() > 240 and not os.environ.get(
        "MYTHRIL_BENCH_NO_COMPILEPLANE"
    ):
        try:
            record.update(
                _with_deadline(bench_compileplane, 180)
            )
            print("bench: compileplane leg done", file=sys.stderr)
        except _Deadline:
            print("bench: compileplane leg hit its deadline", file=sys.stderr)
        except Exception as e:
            print(f"bench: compileplane leg failed: {e!r}", file=sys.stderr)

    # the routed-vs-uniform tier-ladder A/B (ISSUE 19): two corpus
    # passes on a trimmed corpus + an in-process train step between
    if _budget_left() > 300 and not os.environ.get(
        "MYTHRIL_BENCH_NO_ROUTER"
    ):
        try:
            record.update(
                _with_deadline(
                    bench_router,
                    max(120, min(600, int(_budget_left() - 120))),
                )
            )
            print("bench: router leg done", file=sys.stderr)
        except _Deadline:
            print("bench: router leg hit its deadline", file=sys.stderr)
        except Exception as e:
            print(f"bench: router leg failed: {e!r}", file=sys.stderr)

    if _budget_left() > 240 and not os.environ.get(
        "MYTHRIL_BENCH_NO_FLEET"
    ):
        try:
            record.update(
                _with_deadline(
                    bench_fleet,
                    max(60, min(300, int(_budget_left() - 120))),
                )
            )
            print("bench: fleet leg done", file=sys.stderr)
        except _Deadline:
            print("bench: fleet leg hit the budget", file=sys.stderr)
        except Exception as e:
            print(f"bench: fleet leg failed: {e!r}", file=sys.stderr)

    dev = {}
    try:
        dev = _with_deadline(
            bench_transitions, max(30, min(240, int(_budget_left() - 60)))
        )
    except _Deadline:
        print("bench: transitions half hit the budget", file=sys.stderr)
        dev = {"transitions": "deadline"}
    except Exception:
        if not final_attempt:
            raise  # linearity-gate rejection: let __main__ retry
        import traceback as _tb

        print(
            f"bench: transitions half failed: {_tb.format_exc()}",
            file=sys.stderr,
        )
        dev = {"transitions": "failed"}
    if "transitions" in dev:
        record["transitions"] = dev["transitions"]
    record["scaling_ratio_4x_steps"] = (
        round(dev["scaling_ratio"], 2) if "scaling_ratio" in dev else None
    )
    for k in (
        "state_bytes_per_lane", "bytes_per_step", "batch_steps_per_sec",
        "hbm_demand_gbps", "hbm_utilization_pct", "mfu_pct",
        "roofline_bound",
    ):
        if k in dev:
            record[k] = dev[k]

    # -- generic-vs-specialized step-throughput A/B -------------------
    if "rate" not in dev or _budget_left() < 120:
        record["specialize_ab"] = (
            "budget-skipped" if "rate" in dev else "no-generic-leg"
        )
        print("bench: specialize A/B skipped", file=sys.stderr)
    else:
        try:
            record.update(
                _with_deadline(
                    lambda: bench_specialize_ab(dev),
                    max(30, min(180, int(_budget_left() - 60))),
                )
            )
        except _Deadline:
            record["specialize_ab"] = "deadline"
            print("bench: specialize A/B hit its deadline", file=sys.stderr)
        except Exception as e:
            record["specialize_ab"] = "failed"
            print(f"bench: specialize A/B failed: {e!r}", file=sys.stderr)

    # -- specialized-vs-blockjit step-throughput A/B (ISSUE 13) -------
    if _budget_left() < 120:
        record["blockjit_ab"] = "budget-skipped"
        print("bench: blockjit A/B skipped", file=sys.stderr)
    else:
        try:
            record.update(
                _with_deadline(
                    bench_blockjit_ab,
                    max(30, min(300, int(_budget_left() - 60))),
                )
            )
        except _Deadline:
            record["blockjit_ab"] = "deadline"
            print("bench: blockjit A/B hit its deadline", file=sys.stderr)
        except Exception as e:
            record["blockjit_ab"] = "failed"
            print(f"bench: blockjit A/B failed: {e!r}", file=sys.stderr)

    # -- headline convergence pair (bounded by the headline window) ---
    conv = None
    if CONV_PAIRS < 1:
        record["corpus"] = "disabled"
    elif _budget_left() < 120 or _headline_left() < 60:
        record["corpus"] = "budget-skipped"
        print("bench: corpus half skipped (budget spent)", file=sys.stderr)
    else:
        try:
            conv = _ConvAB()
            if not conv.contracts:
                record["corpus"] = "empty"
                conv = None
            else:
                conv.warmup()
                conv.run_pair(headline=True)
                record.update(conv.summarize(strict=False))
        except _Deadline:
            print("bench: a corpus leg hit its deadline", file=sys.stderr)
            record["corpus"] = "deadline"
        except Exception as e:
            # the corpus half must not sink the device metric: any
            # other bug is recorded as a skip, the JSON still prints
            print(f"bench: corpus half failed: {e!r}", file=sys.stderr)
            record["corpus"] = "failed"
            conv = None

    _refresh_headline(record, dev)
    _emit(record, "headline")  # <-- the capture-window guarantee

    # -- refinement: the remaining pairs, then the cheap halves -------
    spread_error = None
    while (
        conv is not None
        and len(conv.host_legs) < CONV_PAIRS
        and _budget_left() >= 120
    ):
        try:
            conv.run_pair()
            record.update(conv.summarize(strict=not final_attempt))
        except _Deadline:
            print("bench: a corpus leg hit its deadline", file=sys.stderr)
            break
        except RuntimeError as why:
            # spread-gate rejection: finish the record (the headline
            # line already stands), then let __main__ retry the whole
            # measurement unless this IS the retry
            record.update(conv.summarize(strict=False))
            spread_error = why
            break

    # -- duplicate-heavy verdict-store leg ----------------------------
    if _budget_left() < 90:
        record.setdefault("store", "budget-skipped")
        print("bench: store leg skipped (budget spent)", file=sys.stderr)
    else:
        try:
            record.update(
                _with_deadline(
                    lambda: bench_store(
                        budget_s=max(45, min(150, int(_budget_left() - 60)))
                    ),
                    max(60, min(180, int(_budget_left() - 45))),
                )
            )
        except _Deadline:
            record["store"] = "deadline"
            print("bench: store leg hit its deadline", file=sys.stderr)
        except Exception as e:
            record["store"] = "failed"
            print(f"bench: store leg failed: {e!r}", file=sys.stderr)

    if _budget_left() < 60:
        record.setdefault("default_path", "budget-skipped")
        print(
            "bench: default-path half skipped (budget spent)",
            file=sys.stderr,
        )
    else:
        try:
            record.update(
                bench_device_default_path(
                    budget_s=max(30, min(210, int(_budget_left() - 45)))
                )
            )
        except Exception as e:
            print(
                f"bench: default-path half failed: {e!r}", file=sys.stderr
            )
    if _budget_left() < 45:
        record.setdefault("hard_solve", "budget-skipped")
        print(
            "bench: hard-solve half skipped (budget spent)", file=sys.stderr
        )
    else:
        try:
            record.update(
                bench_hard_solve(
                    budget_s=max(20, min(300, int(_budget_left() - 15)))
                )
            )
        except Exception as e:
            print(f"bench: hard-solve half failed: {e!r}", file=sys.stderr)

    trace_out = os.environ.get("MYTHRIL_BENCH_TRACE_OUT")
    if trace_out:
        # the run's Perfetto timeline beside the record: a pipelined
        # multi-device corpus leg renders its overlapped waves
        try:
            from mythril_tpu import observe

            observe.export_trace(trace_out)
            record["trace_out"] = trace_out
            print(f"bench: span trace written to {trace_out}",
                  file=sys.stderr)
        except Exception as e:
            print(f"bench: trace export failed: {e!r}", file=sys.stderr)

    _refresh_headline(record, dev)
    _emit(record, "final")
    if spread_error is not None and not final_attempt:
        raise spread_error  # __main__ reruns; this record already printed


if __name__ == "__main__":
    from mythril_tpu.support.accel import ensure_compile_cache

    ensure_compile_cache()
    # One retry shields the round's metric from transient device
    # hiccups and from a spread-gate rejection. Only runtime/IO errors
    # retry; deterministic bugs propagate.
    try:
        main()
    except (RuntimeError, OSError) as e:
        print(f"bench: first attempt failed ({e!r}); retrying", file=sys.stderr)
        time.sleep(5)
        main(final_attempt=True)
