"""Requests the scheduler advances per step: tokens produced over
``ServeScheduler.counters["steps"]``, both counted over the window
from the benchmark's wrapper on ``step``. Moves ``itl_p95_ms``: each
step decodes its running requests one device call at a time."""


def read(run):
    base, end = run.system.window
    made = [n for t, n in run.system.steps if base <= t < end and n > 0]
    return sum(made) / len(made) if made else None
