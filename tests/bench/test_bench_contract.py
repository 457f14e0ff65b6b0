"""BENCHMARK.json keeps to the benchmark's contract, and every name in
it finds its files."""
import json
import re

import pytest

from bench import core

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def spec():
    text = (core.ROOT / "BENCHMARK.json").read_text()
    assert len(text.encode()) <= 64 * 1024
    return json.loads(text)


def _line(text):
    return isinstance(text, str) and 1 <= len(text) <= 200 \
        and "\n" not in text and "\t" not in text


def test_top_level_keys(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(spec["paths"]) <= 16
    for p in spec["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (core.ROOT / p).is_dir()
    assert 1 <= len(spec["command"]) <= 32
    assert all(_line(w) for w in spec["command"])
    assert (core.ROOT / spec["command"][1]).is_file()
    assert isinstance(spec["run_seconds"], int) and 1 <= spec["run_seconds"] <= 51
    n = len(spec["workloads"])
    assert (2 + 14 * 24) * (spec["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200, "the longest check, with 24 cells, must fit"
    assert n <= 24


def test_configs(spec):
    used = {w["config"] for w in spec["workloads"]}
    names = [c["name"] for c in spec["configs"]]
    assert len(set(names)) == len(names) and set(names) == used
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith(tuple(p + "/" for p in spec["paths"]))
        params = core.load_json(core.ROOT / c["file"])
        assert params["name"] == c["name"] and params["reduced"] == c["reduced"]
        assert (core.BENCH / "configs" / f"{c['name']}.py").is_file()
        assert len(c["reduced"]) <= 16
    assert len({c["file"] for c in spec["configs"]}) == len(spec["configs"])


def test_workloads(spec):
    pairs = set()
    four = 0
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        core.traffic_params(w["traffic"])
        four += w["chips"] == 4
    assert four <= max(1, len(spec["workloads"]) // 2)
    assert len({w["name"] for w in spec["workloads"]}) == len(pairs)


def test_metrics(spec):
    e2e, layers = spec["end_to_end"], spec["per_layer"]
    names = [m["name"] for m in e2e + layers]
    assert len(set(names)) == len(names)
    assert "setup_s" in names
    cells = {w["name"] for w in spec["workloads"]}
    for m in e2e:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", cells)) <= cells
    layer_names = set()
    for m in layers:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in SOURCES and _line(m["layer"])
        assert m["moves"] in {e["name"] for e in e2e}
        moved = next(e for e in e2e if e["name"] == m["moves"])
        for cell in m.get("workloads", cells):
            assert cell in moved.get("workloads", cells), (m["name"], cell)
        assert (core.BENCH / "metrics" / f"{m['name']}.py").is_file()
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
        layer_names.add(m["layer"])
    for m in e2e + layers:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    # every cell reports setup_s, another end-to-end and a per-layer metric
    for cell in cells:
        assert len(core.metrics_for(e2e, cell)) >= 2
        assert core.metrics_for(layers, cell)


def test_peaks_are_keyed_by_device_kind():
    p = core.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert p["ici_send_bytes_per_s"] * 8 == p["ici_bits_per_s"]
    with pytest.raises(core.BenchError):
        core.peaks("cpu")
