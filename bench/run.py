#!/usr/bin/env python3
"""Run one cell of the chip benchmark once, on the chips of this machine.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, an entry of ``workloads`` in ``BENCHMARK.json``, names a
configuration and a traffic mix; their files are found by those names
(``bench/core.py``). A run builds the system from the seed, warms up
every shape the mix uses (all of it timed as ``setup_s``), measures for
``--seconds``, then checks what the timed path produced against the
configuration's plain reference (``bench/harness.py``). With
``--trace 0`` it reports the cell's end-to-end metrics; with
``--trace 1`` it profiles a few seconds at the end of the window and
reports the cell's per-layer metrics, each read by
``bench/metrics/<metric>.py``.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``compared``: each number the check compared,
beside its limit); the compared numbers are also the last lines of
standard error. With no TPU, or fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import core, harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", type=Path, default=None,
                    help="copy the profile (.xplane.pb) to this directory")
    args = ap.parse_args(argv)
    try:
        line = harness.run_cell(args.workload, args.seed, args.seconds,
                                bool(args.trace), keep_trace=args.keep_trace,
                                t_start=T_START)
    except core.BenchError as e:
        harness.log(f"bench: {e}")
        return 2
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
