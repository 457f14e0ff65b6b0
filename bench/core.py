"""What every cell shares: finding a cell's files by name, the
statistics of a run, the table of peaks and the result line.

A cell names a configuration and a traffic mix; each per-layer metric
names itself. The files are found by those names:

  bench/configs/<config>.json   the configuration as it is run
  bench/configs/<config>.py     builds the system under test from it
  bench/traffic/<traffic>.json  the mix, read by ``bench.traffic``
  bench/metrics/<metric>.py     ``read(run)`` -> a number or None

so a later cell, mix or metric is added as files, with no edit here.
"""
from __future__ import annotations

import importlib.util
import json
import math
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class BenchError(RuntimeError):
    """A cell cannot run: missing files, no chip, an unknown device."""


def load_json(path: Path) -> dict:
    try:
        return json.loads(Path(path).read_text())
    except FileNotFoundError:
        raise BenchError(f"missing file {path}") from None


def load_module(path: Path, name: str):
    path = Path(path)
    if not path.is_file():
        raise BenchError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _safe(name: str) -> str:
    return "".join(c if c.isalnum() else "_" for c in name)


def load_benchmark(root: Path = ROOT) -> dict:
    return load_json(Path(root) / "BENCHMARK.json")


def workload(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise BenchError(f"no workload {name!r} in BENCHMARK.json; have "
                     f"{[w['name'] for w in spec['workloads']]}")


def config_entry(spec: dict, name: str) -> dict:
    for c in spec["configs"]:
        if c["name"] == name:
            return c
    raise BenchError(f"no config {name!r} in BENCHMARK.json")


def config_params(spec: dict, name: str, root: Path = ROOT) -> dict:
    return load_json(Path(root) / config_entry(spec, name)["file"])


def config_builder(name: str, base: Path = BENCH):
    """The module that builds configuration ``name``'s system."""
    return load_module(Path(base) / "configs" / f"{name}.py",
                       f"bench_config_{_safe(name)}")


def traffic_params(name: str, base: Path = BENCH) -> dict:
    return load_json(Path(base) / "traffic" / f"{name}.json")


def metric_reader(name: str, base: Path = BENCH) -> Callable:
    return load_module(Path(base) / "metrics" / f"{name}.py",
                       f"bench_metric_{_safe(name)}").read


def metrics_for(entries: Sequence[dict], cell: str) -> List[dict]:
    """The metrics a cell reports: those that list it, and those with
    no ``workloads`` key at all."""
    return [m for m in entries if cell in m.get("workloads", [cell])]


# --- statistics -----------------------------------------------------------

def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile over every sample: the smallest value
    with at least ``q`` percent of the samples at or below it."""
    if not values:
        raise ValueError("percentile of no samples")
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return float(ordered[rank - 1])


def rate(work: float, window_s: float) -> float:
    """Work over the whole window."""
    if window_s <= 0:
        raise ValueError(f"window of {window_s} s")
    return work / window_s


# --- peaks ----------------------------------------------------------------

def peaks(device_kind: str, base: Path = BENCH) -> dict:
    table = load_json(Path(base) / "peaks.json")["devices"]
    if device_kind not in table:
        raise BenchError(f"device kind {device_kind!r} is not in "
                         f"bench/peaks.json ({sorted(table)})")
    return table[device_kind]


# --- the result line ------------------------------------------------------

def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: Dict[str, dict], device: dict,
                compared: Dict[str, dict],
                breakdown: Optional[dict] = None) -> str:
    """The last line of standard output. ``compared`` (each number the
    correctness check compared, beside its limit) comes last."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = compared
    return json.dumps(out)
