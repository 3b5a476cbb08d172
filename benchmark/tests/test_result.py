"""The result line and the refusals: no program, no TPU."""

import io
import json
import os
import shutil
import subprocess
import sys
from contextlib import redirect_stderr, redirect_stdout
from pathlib import Path

import harness
import run

ROOT = harness.BENCH.parent


def test_last_lines_schema():
    result = {
        "correct": True, "attempted": 3, "failed": 0,
        "metrics": {"contracts_per_min": {"value": 12.5, "unit": "contracts/min"}},
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                   "memory_peak_bytes": 1},
        "compared": {"unwitnessed_findings": {"value": 0, "limit": 0}},
    }
    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        run.emit(result)
    line = json.loads(out.getvalue().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert list(line)[-1] == "compared"
    assert err.getvalue().splitlines()[-1] == (
        "compared unwitnessed_findings 0 limit 0"
    )


def _run(cwd: Path, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "serve-t2.fresh",
         "--seed", str(2**33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_no_program_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(harness.BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    done = _run(tmp_path)
    assert done.returncode != 0
    assert not done.stdout.strip()
    assert "no program under test" in done.stderr


def test_no_tpu_no_result():
    done = _run(ROOT)
    assert done.returncode != 0
    assert not done.stdout.strip()
    assert "no TPU" in done.stderr


def test_every_declared_metric_has_its_file():
    bench = harness.definition(ROOT)
    for m in bench["per_layer"]:
        assert (harness.BENCH / "metrics" / f"{m['name']}.py").is_file()
    for cell in bench["workloads"]:
        assert (harness.BENCH / "traffic" / f"{cell['traffic']}.json").is_file()
    for config in bench["configs"]:
        cfg = harness.load_json(ROOT / config["file"])
        assert (harness.BENCH / "systems" / f"{cfg['system']}.py").is_file()
        assert cfg["reduced"] == config["reduced"]
        for key in cfg["reduced"]:
            assert cfg["source_values"][key] != cfg["deployment"][key]
