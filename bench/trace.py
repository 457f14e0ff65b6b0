"""Profile part of a window on the chip, and reduce the profile to the
numbers the per-layer metrics read.

The benchmark marks what the host is doing with
``jax.profiler.TraceAnnotation`` spans named ``bench.*`` (the traced
window itself is ``bench.traced``). The reduction reads the profiler's
``.xplane.pb`` with ``jax.profiler.ProfileData``:

- device planes (``/device:TPU:<n>``): busy time is the union of the
  intervals of the events on the ``XLA Ops`` line, clipped to the
  traced window; each compiled program's device time comes from the
  ``XLA Modules`` line, under the program's name (``jit_decode``);
- the host plane: the ``bench.*`` spans, which name what the host was
  doing during each device idle gap.
"""
from __future__ import annotations

import re
import shutil
import tempfile
import time
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.traced"
COLLECTIVE = re.compile(r"collective-permute|all-to-all|all-gather|"
                        r"all-reduce|reduce-scatter|ppermute")

Interval = Tuple[float, float]


def module_name(event_name: str) -> str:
    """``jit_decode(12)`` -> ``jit_decode``."""
    return re.sub(r"\(.*\)$", "", event_name).strip()


def op_name(event_name: str) -> str:
    """An op event is named by its HLO text, ``%fusion.57 = (...)
    fusion(...)``: keep ``fusion.57``."""
    return event_name.split(" = ", 1)[0].lstrip("%").strip()


def self_times(events: Sequence[Tuple[str, float, float]]
               ) -> List[Tuple[str, float, float, float]]:
    """``(name, start, end, self time)`` of each event: its duration
    less that of the events nested in it (a ``while`` op holds its
    body's ops)."""
    out: List[list] = []
    stack: List[int] = []
    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and out[stack[-1]][2] <= a:
            stack.pop()
        if stack:
            parent = out[stack[-1]]
            parent[3] -= min(b, parent[2]) - a
        out.append([name, a, b, b - a])
        stack.append(len(out) - 1)
    return [tuple(e) for e in out]


def enclosing(starts: Sequence[float],
              spans: Sequence[Tuple[str, float, float]]) -> List[str]:
    """For each start time (in order), the name of the span that holds
    it, or ``?``."""
    spans = sorted(spans, key=lambda s: s[1])
    out, j = [], 0
    for a in starts:
        while j + 1 < len(spans) and spans[j + 1][1] <= a:
            j += 1
        hit = spans and spans[j][1] <= a < spans[j][2]
        out.append(spans[j][0] if hit else "?")
    return out


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def total(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


@dataclass
class TraceSummary:
    """One traced window, in seconds."""
    window_s: float
    devices: int
    busy_s: float                       # mean over the devices
    collective_s: float                 # mean, union of collective ops
    modules: Dict[str, List[float]]     # program -> device durations
    ops: Dict[str, float]               # op -> device time, mean
    idle_by_host: Dict[str, float]      # bench span -> idle time, mean
    layout: List[str] = field(default_factory=list)

    def module_times(self, name: str) -> List[float]:
        """Device durations of every run of program ``name`` per chip
        (programs spanning several chips count once per chip)."""
        return self.modules.get(name, [])

    def breakdown(self, n: int = 10) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:n]
        gaps = sorted(self.idle_by_host.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def _events(line):
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def summarize(xplane: Path) -> TraceSummary:
    """Reduce one ``.xplane.pb`` to a :class:`TraceSummary`."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(xplane))
    layout: List[str] = []
    host: Dict[str, List[Interval]] = defaultdict(list)
    devices: Dict[int, dict] = {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            evs = _events(line)
            layout.append(f"{plane.name} | {line.name} | {len(evs)}")
            if m is not None:
                dev = devices.setdefault(int(m.group(1)),
                                         {"ops": [], "modules": []})
                if line.name == OPS_LINE:
                    dev["ops"].extend(evs)
                elif line.name == MODULES_LINE:
                    dev["modules"].extend(evs)
            elif plane.name.startswith("/host:"):
                for name, a, b in evs:
                    if name.startswith("bench."):
                        host[name].append((a, b))
    if not host.get(WINDOW_SPAN):
        raise ValueError(f"{xplane}: no {WINDOW_SPAN} span")
    lo, hi = host[WINDOW_SPAN][0]
    window_s = (hi - lo) / 1e9
    n = max(1, len(devices))
    busy = coll = 0.0
    modules: Dict[str, List[float]] = defaultdict(list)
    ops: Dict[str, float] = defaultdict(float)
    idle: Dict[str, float] = defaultdict(float)
    spans = {k: v for k, v in host.items() if k != WINDOW_SPAN}
    segments = innermost(spans)
    for dev in devices.values():
        ivs = union(clip([(a, b) for _, a, b in dev["ops"]], lo, hi))
        busy += total(ivs) / 1e9
        coll += total(union(clip([(a, b) for name, a, b in dev["ops"]
                                  if COLLECTIVE.search(name)],
                                 lo, hi))) / 1e9
        inside = [e for e in self_times(dev["ops"]) if lo <= e[1] < hi]
        owners = enclosing([e[1] for e in inside], [(module_name(m), a, b)
                                    for m, a, b in dev["modules"]])
        for (name, _, _, own), owner in zip(inside, owners):
            ops[f"{owner}/{op_name(name)}"] += own / 1e9 / n
        for name, a, b in dev["modules"]:
            if a >= lo and b <= hi:
                modules[module_name(name)].append((b - a) / 1e9)
        for name, ns in attribute(_gaps(ivs, lo, hi), segments).items():
            idle[name] += ns / 1e9 / n
    return TraceSummary(window_s=window_s, devices=len(devices),
                        busy_s=busy / n, collective_s=coll / n,
                        modules=dict(modules), ops=dict(ops),
                        idle_by_host=dict(idle), layout=layout)


def _gaps(busy: Sequence[Interval], lo: float, hi: float
          ) -> List[Interval]:
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def innermost(spans: Dict[str, List[Interval]]
              ) -> List[Tuple[float, float, str]]:
    """The host timeline as segments, each named by the innermost
    ``bench.*`` span open during it (spans nest: they come from one
    thread)."""
    edges = sorted({t for ivs in spans.values() for iv in ivs for t in iv})
    flat = sorted((a, b, name) for name, ivs in spans.items()
                  for a, b in ivs)
    out, active, i = [], [], 0
    for a, b in zip(edges, edges[1:]):
        while i < len(flat) and flat[i][0] <= a:
            active.append(flat[i])
            i += 1
        active = [s for s in active if s[1] > a]
        if active:
            s = min(active, key=lambda s: s[1] - s[0])
            out.append((a, b, s[2]))
    return out


def attribute(gaps: Sequence[Interval],
              segments: Sequence[Tuple[float, float, str]]
              ) -> Dict[str, float]:
    """Idle time (ns) by the host activity that overlaps it."""
    out: Dict[str, float] = defaultdict(float)
    j = 0
    for a, b in gaps:
        covered = 0.0
        while j < len(segments) and segments[j][1] <= a:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < b:
            sa, sb, name = segments[k]
            overlap = min(b, sb) - max(a, sa)
            if overlap > 0:
                out[name] += overlap
                covered += overlap
            k += 1
        if b - a > covered:
            out["outside bench spans"] += b - a - covered
    return out


class TracedWindow:
    """Profiles one stretch of a window: ``start`` and ``end_stretch``
    bracket it with the ``bench.traced`` span, to which the reduction
    is clipped. ``stop`` ends the profile; a loop calls it once its
    window has closed, since collecting the profile holds the host for
    seconds. The profile goes to a temporary directory that
    :meth:`summary` reads and then deletes, unless ``keep`` names a
    directory to copy it to."""

    def __init__(self, enabled: bool, length_s: float,
                 keep: Optional[Path] = None):
        self.enabled, self.length_s, self.keep = enabled, length_s, keep
        #: host-clock time at which the loop starts the profile
        self.at = float("inf")
        #: host-clock bounds of the profiled stretch
        self.t_start = self.t_stop = 0.0
        self.started = self.ended = self.stopped = False
        self._dir: Optional[Path] = None
        self._span = None

    def due(self, now: float) -> bool:
        return self.enabled and not self.started and now >= self.at

    def start(self) -> None:
        import jax
        self._dir = Path(tempfile.mkdtemp(prefix="bench_trace_"))
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # bench.* spans come from
        options.enable_hlo_proto = False    # TraceAnnotation alone
        jax.profiler.start_trace(str(self._dir), profiler_options=options)
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()
        self.started = True
        self.t_start = time.perf_counter()

    def end_stretch(self) -> None:
        if not self.started or self.ended:
            return
        self.t_stop = time.perf_counter()
        self._span.__exit__(None, None, None)
        self.ended = True

    def stop(self) -> None:
        import jax
        if not self.started or self.stopped:
            return
        self.end_stretch()
        jax.profiler.stop_trace()
        self.stopped = True

    def covers(self, t0: float, t1: float) -> bool:
        """Whether a host interval lies inside the profiled stretch."""
        return self.stopped and self.t_start <= t0 and t1 <= self.t_stop

    def summary(self) -> Optional[TraceSummary]:
        if not self.stopped:
            return None
        try:
            found = sorted(self._dir.rglob("*.xplane.pb"))
            if not found:
                raise ValueError(f"no .xplane.pb under {self._dir}")
            if self.keep is not None:
                self.keep.mkdir(parents=True, exist_ok=True)
                shutil.copy(found[0], self.keep / found[0].name)
            return summarize(found[0])
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)


def span(name: str):
    """A host span on the profiler's clock (no cost when not tracing
    beyond a context manager)."""
    import jax
    return jax.profiler.TraceAnnotation(name)

