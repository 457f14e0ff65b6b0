"""The program's own spans and stamps, as the benchmark reads them: the
reduction of ``serve.*``/``rpc.*`` host spans against the device's idle
time (on intervals, on a profile recorded on the chip and on a CPU
run), and the readers of the scheduler's request stamps."""
import gzip
import shutil
from types import SimpleNamespace

import numpy as np
import pytest

from bench import core
from bench import program_trace as pt
from bench import trace as tr
from bench.serve import _digest

from conftest import ROOT

DATA = ROOT / "tests" / "bench" / "data"
BENCH = ROOT / "bench"


def test_idle_goes_to_the_innermost_program_span():
    spans = {"rpc.pump": [(0, 100)], "serve.step": [(5, 95)],
             "serve.decode": [(10, 40), (60, 90)],
             "serve.decode.fetch": [(30, 40), (80, 90)]}
    busy = [(12, 28), (41, 58), (62, 78), (91, 99)]
    idle = pt.idle_by_span(busy, spans, 0, 110)
    assert idle == {"rpc.pump": 6, "serve.step": 9, "serve.decode": 8,
                    "serve.decode.fetch": 20, pt.OUTSIDE: 10}
    assert sum(idle.values()) == tr.total(tr._gaps(busy, 0, 110))
    p = pt.ProgramSpans(window_s=110, busy_s=57, idle_by_program=idle,
                        program_counts={"serve.decode": 2}, self_s={},
                        host_s={})
    assert p.decode_host_idle_s() == 14      # (8 + 20) / 2 calls
    p.program_counts = {}
    assert p.decode_host_idle_s() is None


def test_span_names_drop_profiler_metadata():
    assert pt.span_name("serve.decode#request=3,position=9#") == \
        "serve.decode"
    assert pt.span_name("rpc.pump") == "rpc.pump"


def test_recorded_profile_splits_decode_idle_by_program_span(tmp_path):
    """A short stretch of ``qwen15-4b.solo-long`` on one v5e with the
    program's spans: every idle nanosecond lies under a program span or
    outside them all, the host sync that fetches each token holds the
    most, and the ``bench.*`` reduction reads the same idle time."""
    src = DATA / "solo-long-spans.xplane.pb.gz"
    if not src.exists():
        pytest.fail(f"missing recorded profile {src}")
    p = pt.read(src)
    assert sum(p.idle_by_program.values()) == pytest.approx(
        p.window_s - p.busy_s)
    assert max(p.idle_by_program, key=p.idle_by_program.get) == \
        "serve.decode.fetch"
    calls = p.program_counts["serve.decode"]
    assert calls >= 5
    for part in ("key", "launch", "sample", "fetch"):
        assert p.program_counts[f"serve.decode.{part}"] == calls
    assert p.decode_host_idle_s() > 0
    dst = tmp_path / "solo-long-spans.xplane.pb"
    with gzip.open(src, "rb") as a, open(dst, "wb") as b:
        shutil.copyfileobj(a, b)
    s = tr.summarize(dst)
    assert s.window_s == pytest.approx(p.window_s)
    assert sum(s.idle_by_host.values()) == pytest.approx(
        sum(p.idle_by_program.values()))
    assert len(s.module_times("jit_decode")) in (calls - 1, calls,
                                                 calls + 1)


def test_cpu_profile_holds_the_program_spans(tiny_run, tmp_path):
    """A traced CPU run of a small copy: the kept profile holds the
    program's spans, nested as they run on the serving thread, and the
    line reports the fabric's send latency."""
    _, out = tiny_run("qwen15-4b.solo-long", trace=True,
                      keep_trace=tmp_path)
    assert out["correct"]
    assert out["metrics"]["fabric_send_p95_ms.serve"]["value"] > 0
    (profile,) = tmp_path.glob("*.xplane.pb")
    p = pt.read(profile)
    n = p.program_counts
    assert n["serve.decode"] > 0 and n["rpc.pump"] > 0
    for part in ("key", "launch", "sample", "fetch"):
        assert n[f"serve.decode.{part}"] == n["serve.decode"]
    # each decode call lies inside a step, which lies inside a pump
    assert n["serve.step"] <= n["rpc.pump"]
    assert n["serve.decode"] + n.get("serve.prefill", 0) >= n["serve.step"]
    assert p.idle_by_program == {}          # no device plane on the CPU


def _stamped(prompt, submitted, admitted, sent):
    return SimpleNamespace(prompts=prompt, submitted_s=submitted,
                           admitted_s=admitted, sent_s=sent)


def _run(served, requests):
    system = SimpleNamespace(
        counted=lambda: served,
        engine_requests={_digest(r.prompts): r for r in requests})
    return SimpleNamespace(system=system, trace=None, tracer=None)


def _served(i, times, ok=True):
    prompt = np.full((1, 4), i, np.int32)
    return SimpleNamespace(prompt=prompt, times=times, ok=ok)


def test_queue_wait_reads_admission_less_submission():
    served = [_served(i, [1.0]) for i in range(20)]
    reqs = [_stamped(s.prompt, 10.0, 10.0 + 0.001 * i, [])
            for i, s in enumerate(served)]
    read = core.metric_reader("queue_wait_p95_ms.chat", BENCH)
    # nearest rank: the 19th of 20 waits, 18 ms
    assert read(_run(served, reqs)) == pytest.approx(18.0)
    # a program without the stamps reports nothing, and does not raise
    bare = [SimpleNamespace(prompts=s.prompt) for s in served]
    assert read(_run(served, bare)) is None


def test_fabric_send_reads_receipt_less_handover():
    served = [_served(0, [1.002, 2.001]), _served(1, [3.0, 4.004]),
              _served(2, [5.0], ok=False)]
    reqs = [_stamped(served[0].prompt, 0, 0, [1.0, 2.0]),
            _stamped(served[1].prompt, 0, 0, [2.999, 4.0]),
            _stamped(served[2].prompt, 0, 0, [4.0])]
    read = core.metric_reader("fabric_send_p95_ms.serve", BENCH)
    # four chunks of finished requests: 2, 1, 1 and 4 ms
    assert read(_run(served, reqs)) == pytest.approx(4.0)
    bare = [SimpleNamespace(prompts=s.prompt) for s in served]
    assert read(_run(served, bare)) is None
