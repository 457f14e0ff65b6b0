"""The result line has the contract's keys, and a run with no chip
prints no result."""
import json
import os
import shutil
import subprocess
import sys

from conftest import ROOT

KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def test_untraced_line_has_exactly_the_contract_keys(tiny_run):
    line, out = tiny_run("qwen15-4b.solo-long")
    assert list(out) == KEYS + ["compared"]     # ``compared`` comes last
    assert set(out["metrics"]) == {"ttft_p95_ms", "itl_p95_ms", "setup_s"}
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(out["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    for c in out["compared"].values():
        assert set(c) == {"value", "limit"}
    assert "\n" not in line


def test_traced_line_adds_device_time_and_breakdown(tiny_run):
    _, out = tiny_run("qwen15-4b.solo-long", trace=True)
    assert list(out) == KEYS + ["breakdown", "compared"]
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    # per-layer metrics only; those read from the chip's trace are left
    # out on the CPU, where there is no device plane
    assert "fabric_host_ms_per_token.serve" in out["metrics"]
    assert "setup_s" not in out["metrics"]
    assert "decode_step_ms.serve" not in out["metrics"]


def _run(cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen15-4b.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_fails_with_a_clear_error():
    out = _run(ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    """A directory with BENCHMARK.json and the benchmark's paths only:
    the system under test is missing, so no result."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in spec["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
