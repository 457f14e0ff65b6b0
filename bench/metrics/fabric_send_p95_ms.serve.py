"""How long a streamed token spends in the RPC fabric: the 95th
percentile (nearest rank), over every chunk of the window's finished
requests, of the time the client received it (``Served.times``) less
the time the scheduler handed it to the stream pump
(``Request.sent_s``, stamped where ``stream_tokens`` yields it). Both
are host-clock readings on the loopback fabric. Moves ``itl_p95_ms``.
A program without the stamps reports nothing."""
from bench import core
from bench.serve import _digest


def read(run):
    s = run.system
    lags = []
    for x in s.counted():
        req = s.engine_requests.get(_digest(x.prompt))
        sent = getattr(req, "sent_s", None)
        if not x.ok or not sent or len(sent) != len(x.times):
            continue
        lags.extend(b - a for a, b in zip(sent, x.times))
    return core.percentile(lags, 95) * 1e3 if lags else None
