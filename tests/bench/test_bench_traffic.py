"""The generator: every seed gets the same sizes and gaps, in an order
of its own, and the same seed gives the same inputs."""
from collections import Counter

import numpy as np
import pytest

from bench import core
from bench import traffic as T


@pytest.fixture(scope="module")
def mixes():
    return {n: core.traffic_params(n) for n in ("chat", "solo-long",
                                                 "fc-skew")}


def test_open_schedule_is_the_same_work_for_every_seed(mixes):
    """Arrivals and sizes come from the mix's own schedule seed: a run's
    seed changes the prompt tokens, not the work."""
    a = T.serve_schedule(mixes["chat"], 51)
    assert a == T.serve_schedule(mixes["chat"], 51)
    assert [r.due_s for r in a] == sorted(r.due_s for r in a)
    assert -mixes["chat"]["lead_s"] <= a[0].due_s and a[-1].due_s < 51
    other = dict(mixes["chat"], schedule_seed=mixes["chat"]["schedule_seed"]
                 + 1)
    assert T.serve_schedule(other, 51) != a


def test_open_schedule_rate_and_lengths(mixes):
    p = mixes["chat"]
    sched = T.serve_schedule(p, 2000)
    in_window = [r for r in sched if r.due_s >= 0]
    assert abs(len(in_window) / 2000 - p["rate_per_s"]) < 0.1 * p["rate_per_s"]
    assert {r.prompt_len for r in sched} == {512, 1024, 2048}
    assert all(32 <= r.output_len <= 226 for r in sched)
    # around the published medians: 1020 prompt and 129 output tokens
    assert np.median([r.prompt_len for r in sched]) == 1024
    assert abs(np.median([r.output_len for r in sched]) - 129) <= 5


def test_open_schedule_lead_holds_arrivals(mixes):
    """The window opens on a loaded system: the schedule's first
    arrivals fall inside the lead, and the traced stretch lies inside
    the window."""
    p = mixes["chat"]
    sched = T.serve_schedule(p, 51)
    assert any(r.due_s < 0 for r in sched)
    assert 0 <= p["trace_at_s"] and p["trace_at_s"] + p["trace_s"] <= 51


def test_closed_requests_repeat_and_cycle(mixes):
    p = mixes["solo-long"]
    a, b = T.closed_request(p, 3), T.closed_request(p, 3 + T.CYCLE)
    assert (a.prompt_len, a.output_len) == (b.prompt_len, b.output_len)
    r = T.closed_request(p, 3)
    assert (r.prompt_len, r.output_len, r.due_s) == (2048, 16, None)
    assert T.prompt_lengths(p) == [2048] and T.max_output(p) == 16


def test_prompt_tokens_repeat_and_stay_in_vocabulary():
    a = T.prompt_tokens(2**31 + 3, 4, 256, 151936)
    assert a.shape == (1, 256) and a.dtype == np.int32
    np.testing.assert_array_equal(a, T.prompt_tokens(2**31 + 3, 4, 256,
                                                     151936))
    assert not np.array_equal(a, T.prompt_tokens(2**31 + 3, 5, 256, 151936))
    assert 0 <= a.min() and a.max() < 151936


def test_payload_is_the_skew_scheme(mixes):
    sizes = T.payload_sizes(mixes["fc-skew"])
    assert Counter(sizes) == {1048576: 6, 10240: 3, 10: 1}
    with pytest.raises(ValueError):
        T.payload_sizes(mixes["chat"])
