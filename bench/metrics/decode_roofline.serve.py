"""The decode step's share of its roofline: the least time the chip
could take for a call (``bench.costs.roofline_s`` of the algorithm's
operations and bytes: every weight once, and the keys and values of the
positions held) over the device time of ``jit_decode``, both as means
over the traced stretch."""
from bench.costs import roofline_s


def read(run):
    times = run.trace.module_times("jit_decode") if run.trace else []
    held = [n for kind, a, b, n in run.system.calls
            if kind == "decode" and run.tracer.covers(a, b)]
    if not times or not held:
        return None
    c = run.system.costs
    bound = sum(roofline_s(c.decode_flops(h), c.decode_bytes(h), run.peak)
                for h in held) / len(held)
    return 100.0 * bound / (sum(times) / len(times))
