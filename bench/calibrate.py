#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from; not part of a
benchmark run.

  python3 bench/calibrate.py --workload qwen15-4b.chat --seeds 12 \\
      --control-seeds 3 --seconds 15

For each seed, in one process: build the cell's system, warm it up,
run a short window at the cell's own load, and check it as a run does
(the lower reading is the largest over the seeds). For the first
``--control-seeds`` seeds of a served model it checks the same run
again with the control in the program's place (``float8_control``;
the upper reading is the smallest), and says whether the harness
judges it correct. For an exchange it plants each fault the cell can
have and reads the check. One JSON object per reading goes to
standard output.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path
from typing import Callable, Dict, Iterator, Optional

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import core  # noqa: E402
from bench.trace import TracedWindow  # noqa: E402

FIRST_SEED = 90001


def float8_control(system) -> None:
    """The control in the program's place: the check reads, at each
    position of the sampled prompts and served tokens, the gap of the
    token that the reference in float8 (the step below the bf16 served)
    puts first, where it read the served token's."""
    system.reference_gap = system.control_gap


def _exchange_faults() -> Dict[str, Callable]:
    """Faults planted under an exchange's timed path."""
    def left_out(system):
        """The exchange between chips left out: each chip keeps its
        own payload."""
        transport = system.transport
        deliver = system.device_deliver

        def no_exchange(messages):
            out = deliver(messages)
            transport.outputs = tuple(transport.bufs)
            return out
        system.device_deliver = no_exchange

    def altered(system):
        """An answer altered where it is produced: one byte flipped."""
        transport = system.transport
        deliver = system.device_deliver

        def flip(messages):
            import jax
            out = deliver(messages)
            first = transport.outputs[0]
            mask = np.zeros(first.shape, np.uint8)
            mask[0, 0] = 1
            transport.outputs = ((first ^ jax.device_put(mask, first.sharding),)
                                 + tuple(transport.outputs[1:]))
            return out
        system.device_deliver = flip

    return {"exchange_left_out": left_out, "answer_altered": altered}


def readings(name: str, seeds: int, control_seeds: int, seconds: float, *,
             root: Path = ROOT, overrides: Optional[dict] = None,
             first_seed: int = FIRST_SEED) -> Iterator[dict]:
    """Yield one reading per seed (and per control or fault)."""
    from bench import harness
    spec = core.load_benchmark(root)
    cell = core.workload(spec, name)
    params = core.config_params(spec, cell["config"], root)
    if overrides:
        params = harness._merge(params, overrides)
    traffic = core.traffic_params(cell["traffic"], Path(root) / "bench")
    builder = core.config_builder(cell["config"], Path(root) / "bench")
    faults = {"none": None}
    if traffic["kind"] == "exchange_closed":
        faults.update(_exchange_faults())
    for i in range(seeds):
        seed = first_seed + i
        for fault_name, fault in faults.items():
            if fault is not None and i >= control_seeds:
                continue
            t0 = time.perf_counter()
            system = builder.build(params, traffic, seed)
            if fault is not None:
                fault(system)
            system.warmup(traffic)
            system.run(traffic, seed, seconds, TracedWindow(False, 0.0))
            out = {"workload": name, "seed": seed, "fault": fault_name,
                   "attempted": system.attempted(),
                   "failed": system.failed()}
            out["compared"] = system.check(seed)
            out["correct"] = harness.judge(out["attempted"], out["failed"],
                                           out["compared"])
            if traffic["kind"] != "exchange_closed" and i < control_seeds:
                float8_control(system)
                compared = system.check(seed)
                out["control_gap"] = compared["max_logit_gap"]["value"]
                out["control_correct"] = harness.judge(
                    out["attempted"], out["failed"], compared)
            out["seconds"] = time.perf_counter() - t0
            yield out
            del system
            gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--first-seed", type=int, default=FIRST_SEED)
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import use_compile_cache
    import jax
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    for r in readings(args.workload, args.seeds, args.control_seeds,
                      args.seconds, first_seed=args.first_seed):
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
