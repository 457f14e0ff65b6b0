"""The arithmetic of the end-to-end metrics: a rate is work over the
whole window, a tail is the tail of every sample."""
from types import SimpleNamespace

import numpy as np
import pytest

from bench import core
from bench import traffic as T
from bench.exchange import ExchangeSystem
from bench.serve import Served, ServeSystem


def test_percentile_is_nearest_rank_over_all_samples():
    values = list(range(1, 101))
    assert core.percentile(values, 95) == 95
    assert core.percentile(values, 50) == 50
    assert core.percentile([3.0], 95) == 3.0
    # 28 requests: p95 is the 27th smallest, the second largest
    assert core.percentile(list(range(28)), 95) == 26
    with pytest.raises(ValueError):
        core.percentile([], 95)


def test_rate_is_work_over_the_window():
    assert core.rate(120, 2.0) == 60.0
    with pytest.raises(ValueError):
        core.rate(1, 0.0)


def _served(index, due, times, n_out):
    chunk = [np.array([index], "<i4").view(np.uint8)]
    handle = SimpleNamespace(done=True, error=None, call_id=index,
                             chunks=[chunk] * len(times))
    s = Served(T.ServeRequest(index, due, 8, n_out), None, due, handle)
    s.times = list(times)
    return s


def _system(window, served):
    system = ServeSystem.__new__(ServeSystem)
    system.window, system.served, system.lateness = window, served, []
    return system


def test_serve_tails_cover_every_request_due_in_the_window():
    served = [
        _served(0, -1.0, [0.5, 0.6], 2),          # due before: not counted
        _served(1, 0.0, [0.1, 0.2, 0.4], 3),      # ttft 0.1, gaps .1 .2
        _served(2, 1.0, [1.3, 1.4], 2),           # ttft 0.3, gap .1
        _served(3, 9.5, [10.5, 10.9], 2),         # drained after close
        _served(4, 10.0, [10.2], 1),              # due at close: not counted
    ]
    e2e = _system((0.0, 10.0), served).end_to_end()
    assert e2e["ttft_p95_ms"] == pytest.approx(1000.0)     # request 3
    assert e2e["itl_p95_ms"] == pytest.approx(400.0)       # request 3's
    # tokens received inside the window, from every request
    assert e2e["tokens_per_s"] == pytest.approx(7 / 10.0)


def test_serve_failures_count_against_attempts():
    bad = _served(1, 1.0, [1.2], 2)            # one chunk of two
    system = _system((0.0, 10.0), [_served(0, 0.0, [0.1, 0.2], 2), bad])
    assert system.attempted() == 2 and system.failed() == 1
    assert system.end_to_end()["ttft_p95_ms"] == pytest.approx(100.0)


def test_exchange_rate_is_rpcs_over_the_whole_window():
    system = ExchangeSystem.__new__(ExchangeSystem)
    system.rpcs_per_exchange = 12
    system.exchanges = [(0.0, 0.004), (0.004, 0.009), (0.009, 0.012)]
    system.window = (0.0, 0.012)
    assert system.end_to_end()["rpcs_per_s"] == pytest.approx(36 / 0.012)
    assert system.attempted() == 36
