"""Host time the RPC fabric takes per streamed token: time inside
``RpcFabric.flush`` less time inside the engine's ``scheduler_prefill``
and ``scheduler_decode`` (the device calls and their host syncs), over
the window, per token chunk the client received in it."""


def _inside(intervals, lo, hi):
    return sum(max(0.0, min(b, hi) - max(a, lo)) for a, b in intervals)


def read(run):
    s = run.system
    base, end = s.window
    flush = _inside(s.flushes, base, end)
    engine = _inside([(a, b) for _, a, b, _ in s.calls], base, end)
    tokens = sum(1 for x in s.served for t in x.times if base <= t < end)
    return (flush - engine) / tokens * 1e3 if tokens else None
