"""The reduction from a profile to busy time, idle share and device
time per program: on intervals, and on profiles recorded on the chip
(``data/*.xplane.pb.gz``)."""
import gzip
import shutil

import pytest

from bench import trace as tr

from conftest import ROOT

DATA = ROOT / "tests" / "bench" / "data"


def test_union_clip_and_gaps():
    ivs = tr.union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert ivs == [(0, 3), (5, 9)]
    assert tr.clip(ivs, 2, 6) == [(2, 3), (5, 6)]
    assert tr.total(ivs) == 7
    assert tr._gaps(ivs, -1, 10) == [(-1, 0), (3, 5), (9, 10)]


def test_self_time_subtracts_nested_ops():
    out = tr.self_times([("while", 0, 10), ("a", 1, 3), ("b", 4, 8),
                         ("c", 5, 6), ("d", 11, 12)])
    own = {name: s for name, _, _, s in out}
    assert own == {"while": 4, "a": 2, "b": 3, "c": 1, "d": 1}


def test_idle_gaps_go_to_the_innermost_host_span():
    spans = {"bench.flush": [(0, 100)], "bench.decode": [(10, 40), (60, 90)],
             "bench.submit": [(100, 110)]}
    segs = tr.innermost(spans)
    assert segs == [(0, 10, "bench.flush"), (10, 40, "bench.decode"),
                    (40, 60, "bench.flush"), (60, 90, "bench.decode"),
                    (90, 100, "bench.flush"), (100, 110, "bench.submit")]
    idle = tr.attribute([(35, 65), (105, 120)], segs)
    assert idle == {"bench.decode": 10, "bench.flush": 20,
                    "bench.submit": 5, "outside bench spans": 10}


def test_names():
    assert tr.module_name("jit_decode(1234)") == "jit_decode"
    assert tr.op_name("%fusion.57 = (bf16[6912]) fusion(...)") == "fusion.57"
    assert tr.enclosing([1, 5, 12], [("m", 0, 6), ("n", 10, 11)]) == \
        ["m", "m", "?"]


def _summary(name, tmp_path):
    src = DATA / f"{name}.xplane.pb.gz"
    if not src.exists():
        pytest.fail(f"missing recorded profile {src}")
    dst = tmp_path / f"{name}.xplane.pb"
    with gzip.open(src, "rb") as a, open(dst, "wb") as b:
        shutil.copyfileobj(a, b)
    return tr.summarize(dst)


def test_recorded_serve_profile(tmp_path):
    """A 0.28 s stretch of ``qwen15-4b.solo-long`` on one v5e: twelve
    decode calls of about 20 ms each."""
    s = _summary("solo-long", tmp_path)
    assert s.devices == 1
    assert s.window_s == pytest.approx(0.28095315)
    assert s.busy_s == pytest.approx(0.242375383)
    decode = s.module_times("jit_decode")
    assert len(decode) == 12
    assert sum(decode) == pytest.approx(0.24222312)
    assert s.module_times("jit_prefill") == []
    idle = 1 - s.busy_s / s.window_s
    assert 0.1 < idle < 0.2
    # every idle nanosecond is attributed to what the host was doing
    assert sum(s.idle_by_host.values()) == pytest.approx(s.window_s
                                                         - s.busy_s)
    assert max(s.idle_by_host, key=s.idle_by_host.get) == "bench.decode"
    ops = s.breakdown()["device_ops"]
    assert len(ops) == 10 and ops[0][0].startswith("jit_decode/")
    assert sum(s.ops.values()) == pytest.approx(s.busy_s, rel=0.02)
