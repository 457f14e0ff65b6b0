#!/usr/bin/env python3
"""Find the knee of an open-loop serving cell: the highest offered rate
the system sustains without a growing backlog. Run once, when a cell's
rate is set (its mix then offers 0.8 x the knee); not part of a
benchmark run.

  python3 bench/sweep.py --workload qwen15-4b.chat --rates 0.3,0.4,0.5 \\
      --samples 3 --seconds 51

Each rate runs the cell's mix at that rate for one window per schedule
sample (sample j draws its arrivals and sizes from the mix's
``schedule_seed`` + j), all in one process. A backlog that grows shows
as time to first token rising from the first half of the window to the
second, and as requests due in the window still waiting for their
first token at its close; the line for each rate and sample gives
both, the tails, and the tokens per second completed. One JSON object
per line goes to standard output.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import statistics
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import core  # noqa: E402
from bench.trace import TracedWindow  # noqa: E402


def sweep(name: str, rates, seconds: float, seed: int, samples: int = 1,
          *, root: Path = ROOT, overrides=None):
    from bench import harness
    spec = core.load_benchmark(root)
    cell = core.workload(spec, name)
    params = core.config_params(spec, cell["config"], root)
    if overrides:
        params = harness._merge(params, overrides)
    builder = core.config_builder(cell["config"], Path(root) / "bench")
    base = core.traffic_params(cell["traffic"], Path(root) / "bench")
    for rate in rates:
        for j in range(samples):
            schedule_seed = int(base["schedule_seed"]) + j
            traffic = dict(base, rate_per_s=rate,
                           schedule_seed=schedule_seed)
            system = builder.build(params, traffic, seed)
            system.warmup(traffic)
            system.run(traffic, seed, seconds, TracedWindow(False, 0.0))
            out = {"rate_per_s": rate, "schedule_seed": schedule_seed,
                   **_halves(system), **system.end_to_end()}
            system.free()
            del system      # the next run's weights need its memory
            gc.collect()
            yield out


def _halves(system) -> dict:
    """Median time to first token of the requests due in each half of
    the window: a growing backlog shows as the second above the first."""
    start, end = system.window
    half = start + (end - start) / 2
    ok = [s for s in system.counted() if s.ok]
    out = {"requests": len(ok), "failed": system.failed(),
           "waiting_at_close": sum(1 for s in ok if s.times[0] >= end),
           "offered_tokens_per_s": sum(s.req.output_len
                                       for s in system.counted())
           / (end - start)}
    for name, part in (("first", [s for s in ok if s.due < half]),
                       ("second", [s for s in ok if s.due >= half])):
        ttft = [s.times[0] - s.due for s in part]
        out[f"ttft_p50_{name}_half_s"] = (statistics.median(ttft)
                                           if ttft else None)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests per second")
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--samples", type=int, default=1,
                    help="schedule samples per rate")
    args = ap.parse_args(argv)
    import jax
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2
    rates = [float(r) for r in args.rates.split(",")]
    for out in sweep(args.workload, rates, args.seconds, args.seed,
                     args.samples):
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
