"""Configurations, traffic mixes and per-layer metrics are found by
name: a new cell with its own mix and metric is files and entries
alone, with no edit to a file that is there."""
import hashlib
import json
import shutil

import pytest

from bench import core

from conftest import ROOT

DUMMY_METRIC = '''"""Tokens the engine produced in the window (a test's own metric)."""


def read(run):
    base, end = run.system.window
    return float(sum(1 for _, a, b, _ in run.system.calls
                     if base <= a and b <= end))
'''


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "bench").rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


@pytest.fixture
def checkout(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


def test_files_are_found_by_name():
    spec = core.load_benchmark()
    for w in spec["workloads"]:
        assert hasattr(core.config_builder(w["config"]), "build")
        assert core.traffic_params(w["traffic"])["kind"]
    for m in spec["per_layer"]:
        assert callable(core.metric_reader(m["name"]))
    with pytest.raises(core.BenchError, match="missing file"):
        core.metric_reader("no_such_metric.serve")


def test_new_cell_mix_and_metric_need_no_edit(checkout, tiny_run):
    before = _digests(checkout)
    solo = json.loads((checkout / "bench/traffic/solo-long.json").read_text())
    short = dict(solo, prompt_buckets=[[512, 1.0]], output={"fixed": 8})
    (checkout / "bench/traffic/solo-short.json").write_text(json.dumps(short))
    (checkout / "bench/metrics/engine_tokens.serve.py").write_text(
        DUMMY_METRIC)
    spec = json.loads((checkout / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "qwen15-4b.solo-short",
                              "config": "qwen15-4b-serve",
                              "traffic": "solo-short", "chips": 1,
                              "why": "a test's own cell"})
    spec["per_layer"].append({"name": "engine_tokens.serve", "unit": "tokens",
                              "better": "higher", "source": "host_clock",
                              "layer": "model step", "moves": "itl_p95_ms",
                              "workloads": ["qwen15-4b.solo-short"]})
    for m in spec["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("qwen15-4b.solo-short")
    (checkout / "BENCHMARK.json").write_text(json.dumps(spec))

    _, out = tiny_run("qwen15-4b.solo-short", trace=True, root=checkout)
    assert out["correct"] is True
    assert out["metrics"]["engine_tokens.serve"]["value"] > 0
    assert out["metrics"]["engine_tokens.serve"]["unit"] == "tokens"
    after = _digests(checkout)
    assert {k: v for k, v in after.items() if k in before} == before
