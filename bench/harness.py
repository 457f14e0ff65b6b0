"""One run of one cell: build the system, warm it up, measure the
window, check the result against the plain reference, and read the
metrics. ``bench/run.py`` is its command line."""
from __future__ import annotations

import sys
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Optional

from bench import core
from bench.trace import TracedWindow, TraceSummary

ROOT = core.ROOT
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


@dataclass
class Run:
    """What a per-layer metric's reader sees: the system after its
    window, the profiled stretch and its reduction, and the chip's
    peaks."""
    system: object
    tracer: TracedWindow
    trace: Optional[TraceSummary]
    peak: dict


def log(*lines: str) -> None:
    for line in lines:
        print(line, file=sys.stderr, flush=True)


def _merge(base: dict, extra: dict) -> dict:
    out = dict(base)
    for k, v in extra.items():
        out[k] = (_merge(out[k], v) if isinstance(v, dict)
                  and isinstance(out.get(k), dict) else v)
    return out


def _devices(chips: int, require_tpu: bool):
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise core.BenchError(f"no TPU: JAX runs on {devices[0].platform}")
    if len(devices) < chips:
        raise core.BenchError(f"the cell needs {chips} chips, JAX finds "
                              f"{len(devices)} {devices[0].platform} device(s)")
    return devices[:chips]


def _memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def judge(attempted: int, failed: int, compared: dict) -> bool:
    """A run is correct when it attempted something, nothing failed,
    and every number compared lies within its limit."""
    return (attempted > 0 and failed == 0
            and all(c["value"] <= c["limit"] for c in compared.values()))


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             root: Path = ROOT, require_tpu: bool = True,
             overrides: Optional[dict] = None, peak: Optional[dict] = None,
             fault: Optional[Callable] = None,
             keep_trace: Optional[Path] = None, compile_cache: bool = True,
             t_start: Optional[float] = None) -> str:
    """One run of cell ``name``; returns the result line. ``overrides``
    (config keys), ``peak``, ``fault`` (called on the built system, to
    break the timed path), ``require_tpu=False`` and
    ``compile_cache=False`` are for the tests, which run small copies
    on the CPU."""
    import jax
    from repro.launch.compile_cache import use_compile_cache

    t_start = time.perf_counter() if t_start is None else t_start
    spec = core.load_benchmark(root)
    cell = core.workload(spec, name)
    params = core.config_params(spec, cell["config"], root)
    if overrides:
        params = _merge(params, overrides)
    bench_dir = Path(root) / "bench"
    traffic = core.traffic_params(cell["traffic"], bench_dir)
    cache = None
    if compile_cache:
        cache = use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = _devices(int(cell["chips"]), require_tpu)
    peak = peak or core.peaks(devices[0].device_kind, bench_dir)
    log(f"cell {name}: seed {seed}, {seconds} s, trace {int(trace)}, "
        f"{len(devices)} x {devices[0].device_kind}, compile cache {cache}")

    t_build = time.perf_counter()
    system = core.config_builder(cell["config"], bench_dir).build(
        params, traffic, seed)
    if fault is not None:
        fault(system)
    t_warm = time.perf_counter()
    system.warmup(traffic)
    t_open = time.perf_counter()
    setup_s = t_open - t_start
    log(f"setup: {setup_s:.3f} s (imports and start {t_build - t_start:.3f}"
        f", build {t_warm - t_build:.3f}, warm-up {t_open - t_warm:.3f})")

    compiles = []

    def on_event(event, duration, **kw):
        if event in COMPILE_EVENTS:
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    tracer = TracedWindow(trace, float(traffic.get("trace_s", 4.0)),
                          keep=keep_trace)
    try:
        system.run(traffic, seed, seconds, tracer)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    e2e = system.end_to_end()
    log(*system.notes(),
        f"compilations in the window: {len(compiles)}")
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": _memory_peak(devices)}
    t_check = time.perf_counter()
    compared = system.check(seed)
    log(f"check: {time.perf_counter() - t_check:.3f} s")
    attempted, failed = system.attempted(), system.failed()
    correct = judge(attempted, failed, compared)

    metrics, breakdown = {}, None
    if trace:
        t_read = time.perf_counter()
        summary = tracer.summary()
        log(f"trace reduction: {time.perf_counter() - t_read:.3f} s")
        if summary is not None:
            device["busy_s"], device["window_s"] = (summary.busy_s,
                                                    summary.window_s)
            breakdown = summary.breakdown()
            log("trace layout (plane | line | events):",
                *(f"  {row}" for row in summary.layout))
        run = Run(system, tracer, summary, peak)
        for m in core.metrics_for(spec["per_layer"], name):
            value = core.metric_reader(m["name"], bench_dir)(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e["setup_s"] = setup_s
        for m in core.metrics_for(spec["end_to_end"], name):
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    log(f"attempted {attempted}, failed {failed}, correct {correct}",
        *(f"compared: {k} {c['value']!r} limit {c['limit']!r}"
          for k, c in compared.items()))
    return core.result_line(correct=correct, attempted=attempted,
                            failed=failed, metrics=metrics, device=device,
                            compared=compared, breakdown=breakdown)
