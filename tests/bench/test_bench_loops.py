"""The loops: an open loop sends each request when it is due and keeps
the fabric going until the next one; a closed loop sends the next
request when the last completes. Small copies on the CPU."""
import pytest

from bench import core, harness
from bench.trace import TracedWindow

from conftest import TINY_QWEN


def _system(cell, **traffic_changes):
    spec = core.load_benchmark()
    w = core.workload(spec, cell)
    params = harness._merge(core.config_params(spec, w["config"]), TINY_QWEN)
    traffic = dict(core.traffic_params(w["traffic"]), **traffic_changes)
    system = core.config_builder(w["config"]).build(params, traffic, 11)
    system.warmup(traffic)
    return system, traffic


@pytest.mark.parametrize("trace", [False, True])
def test_open_loop_serves_each_request_when_due(trace):
    # short prompts and answers keep the small copy far below its capacity
    system, traffic = _system("qwen15-4b.chat", rate_per_s=2.0, lead_s=1.0,
                              prompt_buckets=[[512, 1.0]],
                              output={"fixed": 8}, trace_at_s=0.5)
    # a disabled tracer must not hold the loop back from serving: a stretch
    # of 2 s of the 4 s window would otherwise wait for the close
    tracer = TracedWindow(trace, 2.0)
    system.run(traffic, 11, 4.0, tracer)
    counted = system.counted()
    assert counted and all(s.ok for s in counted)
    assert tracer.stopped is trace
    if trace:           # the stretch the mix places, and no longer
        assert tracer.t_start - system.window[0] >= 0.5
        assert tracer.t_stop - tracer.t_start < 2.5
    if not trace:       # starting the profiler stalls the loop itself
        # a small model on the CPU answers in milliseconds: no request
        # may wait for the window to close
        assert max(s.times[0] - s.due for s in counted) < 1.0
        assert max(system.lateness) < 0.5


def test_open_loop_sends_what_fell_due_during_an_overrun():
    """A flush that overruns the close: the requests that fell due in
    the window meanwhile are still sent, late, and counted."""
    import time

    from bench import traffic as T
    system, traffic = _system("qwen15-4b.chat", rate_per_s=4.0, lead_s=0.5,
                              prompt_buckets=[[512, 1.0]],
                              output={"fixed": 4}, trace_at_s=0.0)
    flush = system.fabric.flush
    stalled = []

    def overrun(until_s=None):
        out = flush(until_s=until_s)
        base, end = system.window
        if not stalled and time.perf_counter() > base + 1.0:
            stalled.append(True)
            time.sleep(end - time.perf_counter() + 0.5)
        return out
    system.fabric.flush = overrun
    system.run(traffic, 11, 2.0, TracedWindow(False, 0.0))
    due = [r for r in T.serve_schedule(traffic, 2.0) if r.due_s >= 0]
    assert stalled and len(due) >= 4
    assert len(system.counted()) == len(due)
    assert all(s.ok for s in system.counted())


def test_closed_loop_keeps_one_request_in_flight():
    system, traffic = _system("qwen15-4b.solo-long")
    system.run(traffic, 11, 2.0, TracedWindow(False, 0.0))
    served = sorted(system.served, key=lambda s: s.submitted)
    assert len(served) >= 2 and all(s.ok for s in served)
    for a, b in zip(served, served[1:]):
        assert b.submitted >= a.times[-1]      # sent after the last ended
