#!/usr/bin/env python3
"""Reduce a kept profile's program spans: the host spans the serving
program marks itself (``serve.*`` and ``rpc.*``, from
``repro.rpc.tracing.host_span``), as ``bench/trace.py`` reduces the
benchmark's own ``bench.*`` spans, over the same traced stretch
(``bench.traced``) and with the same helpers:

- ``idle_by_program``: the device's idle time, by the innermost program
  span open on the host meanwhile (``outside program spans`` for the
  rest), as a mean over the devices; it sums to the idle time;
- ``program_counts``: how many spans of each name start in the stretch;
- ``self_s``: each name's host time in the stretch less that of the
  program spans nested in it;
- ``host_s``: every host span's durations (``bench.*`` too) in the
  stretch.

The benchmark deletes its profile after reducing it; keep one with
``bench/run.py --trace 1 --keep-trace DIR``, then

  python3 bench/program_trace.py DIR/*.xplane.pb [more profiles, .gz too]

prints one JSON object a profile.
"""
from __future__ import annotations

import gzip
import json
import shutil
import sys
import tempfile
from collections import defaultdict
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional, Sequence

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import trace as tr  # noqa: E402

PREFIXES = ("serve.", "rpc.")
OUTSIDE = "outside program spans"
DECODE = "serve.decode"


def span_name(event_name: str) -> str:
    """``serve.decode#request=3#`` -> ``serve.decode``: a profiler may
    append a span's metadata to its name."""
    return event_name.split("#", 1)[0]


def idle_by_span(busy: Sequence[tr.Interval],
                 spans: Dict[str, List[tr.Interval]], lo: float,
                 hi: float) -> Dict[str, float]:
    """One device's idle time in ``[lo, hi)``, whose busy intervals
    are ``busy`` (sorted, disjoint), by the innermost of ``spans`` open
    on the host during it."""
    out = tr.attribute(tr._gaps(busy, lo, hi), tr.innermost(spans))
    if "outside bench spans" in out:
        out[OUTSIDE] = out.pop("outside bench spans")
    return dict(out)


@dataclass
class ProgramSpans:
    """One traced stretch's program spans, in seconds."""
    window_s: float
    busy_s: float
    idle_by_program: Dict[str, float]
    program_counts: Dict[str, int]
    self_s: Dict[str, float]
    host_s: Dict[str, List[float]]

    def decode_host_idle_s(self) -> Optional[float]:
        """Device idle under ``serve.decode`` and its parts, per decode
        call."""
        n = self.program_counts.get(DECODE, 0)
        idle = sum(v for k, v in self.idle_by_program.items()
                   if k == DECODE or k.startswith(DECODE + "."))
        return idle / n if n else None


def summarize(xplane: Path) -> ProgramSpans:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(xplane))
    host: Dict[str, List[tr.Interval]] = defaultdict(list)
    devices: Dict[int, List[tr.Interval]] = defaultdict(list)
    for plane in data.planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m is not None and line.name == tr.OPS_LINE:
                devices[int(m.group(1))].extend(
                    (a, b) for _, a, b in tr._events(line))
            elif m is None and plane.name.startswith("/host:"):
                for name, a, b in tr._events(line):
                    name = span_name(name)
                    if name.startswith(PREFIXES + ("bench.",)):
                        host[name].append((a, b))
    if not host.get(tr.WINDOW_SPAN):
        raise ValueError(f"{xplane}: no {tr.WINDOW_SPAN} span")
    lo, hi = host[tr.WINDOW_SPAN][0]
    program = {k: v for k, v in host.items() if k.startswith(PREFIXES)}
    n = max(1, len(devices))
    busy = 0.0
    idle: Dict[str, float] = defaultdict(float)
    for ops in devices.values():
        ivs = tr.union(tr.clip(ops, lo, hi))
        busy += tr.total(ivs) / 1e9 / n
        for name, ns in idle_by_span(ivs, program, lo, hi).items():
            idle[name] += ns / 1e9 / n
    counts: Dict[str, int] = defaultdict(int)
    for name, ivs in program.items():
        counts[name] = sum(1 for a, _ in ivs if lo <= a < hi)
    own: Dict[str, float] = defaultdict(float)
    for name, a, b, s in tr.self_times(
            [(k, a, b) for k, ivs in program.items() for a, b in ivs]):
        if lo <= a < hi:
            own[name] += s / 1e9
    durations = {k: [(b - a) / 1e9 for a, b in ivs if lo <= a < hi]
                 for k, ivs in host.items() if k != tr.WINDOW_SPAN}
    return ProgramSpans(window_s=(hi - lo) / 1e9, busy_s=busy,
                        idle_by_program=dict(idle),
                        program_counts=dict(counts), self_s=dict(own),
                        host_s=durations)


def read(path: Path) -> ProgramSpans:
    """:func:`summarize` of a ``.xplane.pb`` or ``.xplane.pb.gz``."""
    if path.suffix != ".gz":
        return summarize(path)
    tmp = Path(tempfile.mkdtemp(prefix="program_trace_"))
    try:
        dst = tmp / path.stem
        with gzip.open(path, "rb") as a, open(dst, "wb") as b:
            shutil.copyfileobj(a, b)
        return summarize(dst)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def report(p: ProgramSpans) -> dict:
    ms = {k: v * 1e3 for k, v in p.idle_by_program.items()}
    decode = p.decode_host_idle_s()
    return {
        "window_s": p.window_s, "busy_s": p.busy_s,
        "idle_ms": (p.window_s - p.busy_s) * 1e3,
        "idle_by_program_ms": dict(sorted(ms.items(),
                                          key=lambda kv: -kv[1])),
        "program_counts": p.program_counts,
        "self_ms": {k: v * 1e3 for k, v in sorted(p.self_s.items())},
        "decode_host_idle_ms": None if decode is None else decode * 1e3,
        "mean_ms": {k: sum(v) / len(v) * 1e3
                    for k, v in sorted(p.host_s.items()) if v},
    }


def main(argv=None) -> int:
    paths = [Path(a) for a in (sys.argv[1:] if argv is None else argv)]
    if not paths:
        print(__doc__, file=sys.stderr)
        return 2
    for path in paths:
        print(json.dumps({"profile": str(path), **report(read(path))}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
