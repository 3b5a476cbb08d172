"""A later change adds a configuration, a traffic mix and a per-layer
metric as new files and new entries, and edits no file the benchmark
already has."""

import hashlib
import json
import shutil
import sys

import harness

ROOT = harness.BENCH.parent


def _digests(root):
    return {
        p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
        for p in (root / "benchmark").rglob("*")
        if p.is_file() and "__pycache__" not in p.parts
    }


def test_new_files_only(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(harness.BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(tmp_path)
    bench_dir = tmp_path / "benchmark"
    (bench_dir / "configs" / "serve-t9.json").write_text(json.dumps({
        "name": "serve-t9", "system": "serve_engine",
        "source_values": {}, "deployment": {}, "reduced": [],
    }))
    (bench_dir / "traffic" / "tiny.json").write_text(json.dumps({
        "kind": "closed_loop", "clients": 2,
        "parts": [{"shape": "wide", "count": 2, "guards": [6]},
                  {"shape": "fixture_mutant", "count": 1}],
    }))
    (bench_dir / "metrics" / "dummy_count.py").write_text(
        "def read(run):\n    return run.get('dummy')\n"
    )
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "serve-t9", "source": "x",
                             "file": "benchmark/configs/serve-t9.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "serve-t9.tiny", "config": "serve-t9",
                               "traffic": "tiny", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "dummy_count", "unit": "n", "better": "lower",
                               "source": "program_counter", "layer": "corpus",
                               "moves": "contracts_per_min",
                               "workloads": ["serve-t9.tiny"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    # every file that was there is unchanged
    after = _digests(tmp_path)
    assert {k: after[k] for k in before} == before

    # the harness finds the new pieces by name
    defined = harness.definition(tmp_path)
    cell = harness.find(defined["workloads"], "serve-t9.tiny", "workload")
    config = harness.find(defined["configs"], cell["config"], "config")
    assert harness.load_json(tmp_path / config["file"])["system"] == "serve_engine"
    names = [m["name"] for m in harness.metrics_for(defined, "serve-t9.tiny", True)]
    assert "dummy_count" in names and "settle_p95_s" not in names
    reader = harness.load_module(bench_dir / "metrics" / "dummy_count.py", "t_dummy")
    assert reader.read({"dummy": 3}) == 3
    sys.path.insert(0, str(bench_dir))
    try:
        import generate

        mix = harness.load_json(bench_dir / "traffic" / "tiny.json")
        assert len(generate.corpus(mix, 5, 0)) == 3
    finally:
        sys.path.remove(str(bench_dir))
