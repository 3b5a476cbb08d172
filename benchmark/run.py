#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process holds.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. Everything a cell needs is found by name
in BENCHMARK.json: its configuration (benchmark/configs/), the system
under test that configuration names (benchmark/systems/), its traffic mix
(benchmark/traffic/) and, with --trace 1, its per-layer metric readers
(benchmark/metrics/). Set-up (imports, JAX, the compile cache, warm-up)
ends when the window opens; the window lasts --seconds; the answers
the window produced are then checked against the reference EVM
(benchmark/check.py). The last line of stdout is the result; the last
lines of stderr are the numbers compared, each beside its limit.
Without a TPU, or with fewer chips than the cell asks for, the run
exits 1 and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent

def parse(argv):
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return parser.parse_args(argv)


def run_cell(opts, root: Path) -> dict:
    """One run of one cell -> the result line (a dict)."""
    import harness
    from harness import BenchError, say

    bench = harness.definition(root)
    cell = harness.find(bench["workloads"], opts.workload, "workload")
    config = harness.find(bench["configs"], cell["config"], "config")
    cfg = harness.load_json(root / config["file"])
    traffic = harness.load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    if not (root / "mythril_tpu").is_dir():
        raise BenchError(f"no program under test in {root}")
    sys.path.insert(0, str(root))
    say(f"compile cache {harness.use_compile_cache(root)}")
    device = harness.device_info(cell["chips"])
    peaks = harness.peaks_for(device["kind"])
    clock = harness.CompileClock()
    state_dir = root / ".bench_state" / opts.workload
    state_dir.mkdir(parents=True, exist_ok=True)
    ctx = {"config": cfg, "traffic": traffic, "seed": opts.seed,
           "root": root, "state_dir": state_dir, "workload": opts.workload}
    module = harness.load_module(
        BENCH / "systems" / f"{cfg['system']}.py", f"bench_system_{cfg['system']}"
    )
    system = module.System(ctx)
    system.setup()
    setup_s = time.perf_counter() - T_START
    setup_compiles = clock.mark()
    say(f"set-up {setup_s} s, compiles {setup_compiles}")

    cut = None
    if opts.trace:
        from tracing import Slice

        # where the configuration puts its slice: a share of the window
        # in, and a length that the window bounds
        where = cfg["trace_slice"]
        start = where["start_share"] * opts.seconds
        cut = Slice(
            state_dir / "trace",
            start_s=start,
            length_s=min(where["seconds"], opts.seconds - start),
        )
        cut.begin()
    mark = clock.mark()
    say("window opens")
    run = system.window(opts.seconds)
    if cut is not None:
        cut.end()
    run["compiles"] = clock.since(mark)
    say(f"window compiles {run['compiles']}")
    device["memory_peak_bytes"] = harness.memory_peak_bytes(cell["chips"])
    close = getattr(system, "close", None)
    if close is not None:
        close()

    result = {
        "correct": None,
        "attempted": run["attempted"],
        "failed": run["failed"],
        "metrics": {},
        "device": device,
    }
    if opts.trace:
        result["metrics"], breakdown = per_layer(
            bench, opts.workload, run, cut, peaks, device,
            host_spans=getattr(module, "TRACE_HOST_SPANS", ()),
        )
        if breakdown:
            result["breakdown"] = breakdown
    else:
        e2e = system.end_to_end(run)
        e2e["setup_s"] = setup_s
        for m in harness.metrics_for(bench, opts.workload, trace=False):
            value = e2e.get(m["name"])
            if value is None:
                raise BenchError(f"no reading for {m['name']}")
            result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}

    import check

    t = time.perf_counter()
    verdict = check.check(run["reports"])
    verdict["compared"]["failed_answers"] = {"value": run["failed"], "limit": 0}
    say(f"checked {verdict['findings_checked']} findings "
        f"({verdict['findings_unjudged']} unjudged: wrapped calldata) and "
        f"{verdict['planted_looked_for']} planted weaknesses in "
        f"{len(run['reports']) - verdict['walks_cut']} reports whose walk "
        f"ran to its end ({verdict['walks_cut']} cut) in "
        f"{time.perf_counter() - t} s")
    control = check.check(run["reports"], witness="none")
    say("control (witnesses not solved): " + json.dumps(
        {k: v["value"] for k, v in control["compared"].items()}
    ))
    result["correct"] = check.passed(verdict)
    result["compared"] = verdict["compared"]
    return result


def per_layer(bench, workload, run, cut, peaks, device, host_spans=()):
    """The cell's per-layer metrics from its readers, and the trace's
    breakdown, its idle gaps labelled by the system's `host_spans`;
    fills `device` with the slice's busy and window seconds."""
    import harness
    import tracing
    from harness import BenchError, say

    if cut.error is not None:
        raise BenchError(f"the profiler failed: {cut.error!r}")
    path = cut.xplane()
    if path is None or cut.window_s is None:
        raise BenchError("the profiler wrote no trace")
    kernels = harness.load_json(BENCH / "kernels.json")
    planes = tracing.read_planes(path, host_spans)
    reduced = tracing.reduce(planes, cut.window_s, kernels)
    shutil.rmtree(cut.out_dir, ignore_errors=True)
    if not reduced or reduced["busy_s"] <= 0:
        raise BenchError("no device operation in the traced slice")
    say(f"trace: {json.dumps({k: reduced[k] for k in ('busy_s', 'window_s', 'kernel_s', 'kernel_events', 'executables')})}")
    run["trace"] = reduced
    run["peaks"] = peaks
    device["busy_s"] = reduced["busy_s"]
    device["window_s"] = reduced["window_s"]
    metrics = {}
    for m in harness.metrics_for(bench, workload, trace=True):
        reader = harness.load_module(
            BENCH / "metrics" / f"{m['name']}.py", f"bench_metric_{m['name']}"
        )
        value = reader.read(run)
        if value is None:
            say(f"{m['name']}: nothing to read")
            continue
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    breakdown = {
        "device_ops": reduced["device_ops"],
        "idle_gaps": reduced["idle_gaps"],
    }
    return metrics, breakdown


def emit(result: dict) -> None:
    """The compared numbers as the last lines of stderr, then the
    result as the last line of stdout."""
    for name, row in result["compared"].items():
        print(f"compared {name} {row['value']} limit {row['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    opts = parse(argv)
    sys.path.insert(0, str(BENCH))
    from harness import BenchError, say

    try:
        result = run_cell(opts, Path.cwd().resolve())
    except BenchError as why:
        say(f"no result: {why}")
        return 1
    emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
