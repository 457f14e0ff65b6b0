"""Host spans inside the serve path (``rpc.tracing.host_span``) and the
scheduler's request stamps: what ``serve --trace`` exports, how the
spans nest on the serving thread, and that every stamp is monotone."""
import numpy as np
import pytest

from repro import rpc
from repro.rpc import tracing
from repro.rpc.tracing import host_span

DECODE_PARTS = ["serve.decode.key", "serve.decode.launch",
                "serve.decode.sample", "serve.decode.fetch"]
PREFILL_PARTS = ["serve.prefill.launch", "serve.prefill.key",
                 "serve.prefill.sample", "serve.prefill.fetch"]


def test_host_span_without_tracer_or_profiler_does_nothing():
    import jax  # noqa: F401  (the profiler exists, but is not recording)
    s = host_span("serve.decode", None, request=1)
    assert s is tracing._NO_SPAN
    with s as inner:
        inner.set(running=2)


def test_host_spans_nest_in_the_innermost_open_one():
    t = rpc.Tracer()
    with host_span("rpc.pump", t, call=7):
        with host_span("serve.step", t) as step:
            step.set(running=1, admitted=0)
    with host_span("rpc.deliver", t, messages=2):
        pass
    a, b, c = t.spans()
    assert [s.category for s in (a, b, c)] == ["host"] * 3
    assert b.parent_id == a.span_id and c.parent_id is None
    assert a.attrs == {"call": 7} and b.attrs == {"running": 1,
                                                  "admitted": 0}
    assert a.trace_id == b.trace_id == c.trace_id == 0    # no call 7
    assert all(s.closed for s in (a, b, c)) and not t._host


def test_host_spans_stop_at_the_span_cap():
    t = rpc.Tracer(max_spans=1)
    with host_span("rpc.pump", t):
        with host_span("serve.step", t):
            pass
    assert [s.name for s in t.spans()] == ["rpc.pump"]
    assert t.dropped == 0 and not t._host


@pytest.fixture(scope="module")
def eng():
    import jax
    from repro.configs import get_reduced_config
    from repro.models import init_params
    from repro.parallel import NO_MESH
    from repro.serve.engine import ServeConfig, ServeEngine
    cfg = get_reduced_config("qwen3-8b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    return ServeEngine(NO_MESH, cfg, params,
                       ServeConfig(max_seq=64, max_new_tokens=4))


def _prompts(eng_, plen, seed):
    return np.random.default_rng(seed).integers(
        0, eng_.acfg.model.vocab_size, (1, plen), dtype=np.int32)


def _serve(eng_, prompts, tracer=None, **kw):
    """Stream every prompt over a loopback fabric at once; returns the
    scheduler's requests, in submit order."""
    from repro.serve.engine import serve_stub
    fab, ch = eng_.serve_loopback(tracer=tracer, **kw)
    sched = eng_.schedulers[0]
    reqs, submit = [], sched.submit

    def recorded(p, mnt=None):
        reqs.append(submit(p, mnt))
        return reqs[-1]

    sched.submit = recorded
    stub = serve_stub(ch)
    handles = [stub.generate_stream((p, 0)) for p in prompts]
    fab.flush()
    for h in handles:
        assert h.done and h.error is None, h.error
    return reqs


def test_serve_trace_nests_spans_on_the_serving_thread(eng):
    tracer = rpc.Tracer()
    reqs = _serve(eng, [_prompts(eng, 8, 1), _prompts(eng, 6, 2)],
                  tracer=tracer, max_batch=4)
    spans = tracer.spans()
    by_id = {s.span_id: s for s in spans}
    host = [s for s in spans if s.category == "host"]
    assert host and all(s.closed for s in host)

    def parent(s):
        return by_id[s.parent_id].name if s.parent_id else None

    # only the flush loop's phases open at the top
    assert {s.name for s in host if s.parent_id is None} == \
        {"rpc.pump", "rpc.deliver", "rpc.complete"}
    trace_of = {r.attrs["call_id"]: r.trace_id for r in tracer.calls()}
    ids = {r.id for r in reqs}
    for s in host:
        if s.parent_id:
            p = by_id[s.parent_id]
            assert p.start_s <= s.start_s <= s.end_s <= p.end_s
        if s.name == "rpc.pump":
            assert s.trace_id == trace_of[s.attrs["call"]]
        if s.name == "serve.step":
            assert parent(s) == "rpc.pump"
            assert set(s.attrs) == {"running", "admitted"}
        if s.name in ("serve.decode", "serve.prefill"):
            assert parent(s) == "serve.step"
            assert s.attrs["request"] in ids
            # the call the request serves, whichever pump drove the step
            assert s.trace_id == trace_of[s.attrs["call"]]
            parts = [c.name for c in s.children]
            assert parts == (DECODE_PARTS if s.name == "serve.decode"
                             else PREFILL_PARTS)
            assert {c.trace_id for c in s.children} == {s.trace_id}
    decodes = [s for s in host if s.name == "serve.decode"]
    assert len(decodes) == 2 * 3            # 4 tokens: prefill + 3 decodes
    assert sorted(s.attrs["position"] for s in decodes
                  if s.attrs["request"] == reqs[0].id) == [9, 10, 11]
    assert sum(s.attrs["admitted"] for s in host
               if s.name == "serve.step") == 2
    events = tracer.chrome_events()
    assert {e["name"] for e in events if e.get("tid") == tracing.HOST_TRACK
            and e["ph"] == "X"} >= {"rpc.pump", "serve.step",
                                    "serve.decode.fetch"}


def _monotone(xs):
    return all(a <= b for a, b in zip(xs, xs[1:]))


def test_stamps_of_a_lone_stream_are_monotone(eng):
    (req,) = _serve(eng, [_prompts(eng, 8, 3)])
    chain = [req.submitted_s, req.admitted_s]
    for made, sent in zip(req.made_s, req.sent_s):
        chain += [made, sent]
    assert len(req.made_s) == len(req.sent_s) == 4
    assert _monotone(chain), chain


def test_stamps_of_interleaved_streams_across_preemption(eng):
    """Two streams on a budget that preempts one: each token is stamped
    once, made before it is sent; the re-derived tokens are not."""
    reqs = _serve(eng, [_prompts(eng, 8, 5), _prompts(eng, 8, 6)],
                  max_batch=4, kv_blocks=21, block_size=1)
    assert eng.schedulers[0].counters["preempted"] >= 1
    for r in reqs:
        assert len(r.made_s) == len(r.sent_s) == len(r.tokens) == 4
        assert r.submitted_s <= r.admitted_s <= r.made_s[0]
        assert _monotone(r.made_s) and _monotone(r.sent_s)
        assert all(m <= s for m, s in zip(r.made_s, r.sent_s))
