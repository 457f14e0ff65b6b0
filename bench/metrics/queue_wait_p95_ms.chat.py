"""How long a request waits for a slot: the 95th percentile (nearest
rank) over the window's requests of ``Request.admitted_s -
Request.submitted_s``, the scheduler's own stamps (its clock is the
host clock on the loopback fabric). Moves ``ttft_p95_ms``: a request
that arrives while every slot is taken waits here before its prefill.
A program without the stamps reports nothing."""
from bench import core
from bench.serve import _digest


def read(run):
    s = run.system
    waits = []
    for x in s.counted():
        req = s.engine_requests.get(_digest(x.prompt))
        t0 = getattr(req, "submitted_s", None)
        t1 = getattr(req, "admitted_s", None)
        if t0 is not None and t1 is not None:
            waits.append(t1 - t0)
    return core.percentile(waits, 95) * 1e3 if waits else None
