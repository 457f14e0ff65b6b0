"""Continuous-batching serve scheduler: admission, KV-block budget
preemption/requeue, exactly-once per-request streams, rpc integration
(interleaved generate_stream pumps, metrics gauges, phase spans)."""
import numpy as np
import pytest

from _hypothesis_support import given, settings, st
from repro.serve.scheduler import (CANCELLED, FINISHED, PREEMPTED,
                                   Request, ServeScheduler,
                                   blocks_per_seq)


class FakeEngine:
    """Deterministic stand-in for ServeEngine's scheduler ops: token t
    of a request is a pure function of its prompt and t, and rebuild
    recomputes exactly the state decode left — so the scheduler's
    exactly-once / byte-identity contracts are testable without jax."""

    class _Cfg:
        max_seq = 64
        max_new_tokens = 4

    def __init__(self):
        self.cfg = self._Cfg()
        self.prefills = self.decodes = self.rebuilds = 0

    def _tok(self, req, t):
        base = int(req.prompts.sum()) % 997
        return np.full(req.rows, base + 7 * t, dtype=np.int32)

    def scheduler_prefill(self, req):
        self.prefills += 1
        req.runtime = ("state", 0)
        return self._tok(req, 0)

    def scheduler_decode(self, req):
        self.decodes += 1
        assert req.runtime == ("state", len(req.tokens) - 1), \
            "decode must resume from the rebuilt state"
        req.runtime = ("state", len(req.tokens))
        return self._tok(req, len(req.tokens))

    def scheduler_rebuild(self, req):
        self.rebuilds += 1
        assert req.runtime is None, "rebuild implies dropped state"
        req.runtime = ("state", len(req.tokens) - 1)


def _expected(req):
    base = int(req.prompts.sum()) % 997
    return [np.full(req.rows, base + 7 * t, dtype=np.int32)
            for t in range(req.max_new_tokens)]


def _prompts(rows, plen, fill):
    return np.full((rows, plen), fill, dtype=np.int32)


# ---------------------------------------------------------------------------
# block accounting
# ---------------------------------------------------------------------------

def test_blocks_per_seq():
    assert blocks_per_seq(1, 0) == 1
    assert blocks_per_seq(16, 0) == 1
    assert blocks_per_seq(16, 1) == 2
    assert blocks_per_seq(8, 4, block_size=4) == 3
    assert blocks_per_seq(8, 0, block_size=1) == 8
    prev = 0
    for g in range(40):          # monotone, never shrinks with growth
        cur = blocks_per_seq(5, g, block_size=4)
        assert cur >= prev
        prev = cur


def test_request_blocks_scale_with_rows():
    req = Request(1, _prompts(3, 8, 1), 4)
    assert req.blocks(block_size=4) == 3 * blocks_per_seq(8, 0,
                                                          block_size=4)
    req.tokens.append(np.zeros(3, np.int32))
    assert req.blocks(block_size=1, extra=2) == 3 * (8 + 1 + 2)


# ---------------------------------------------------------------------------
# scheduler core (fake engine)
# ---------------------------------------------------------------------------

def test_single_request_runs_to_completion():
    eng = FakeEngine()
    sched = ServeScheduler(eng, max_batch=2)
    req = sched.submit(_prompts(2, 8, 3), 4)
    out = sched.run(req)
    exp = np.stack(_expected(req), axis=1)
    assert np.array_equal(out, exp)
    assert req.finished and req.runtime is None
    assert eng.prefills == 1 and eng.decodes == 3
    assert sched.counters["finished"] == 1
    assert not sched.running and not sched.waiting


def test_submit_rejects_over_max_seq():
    sched = ServeScheduler(FakeEngine())
    with pytest.raises(AssertionError):
        sched.submit(_prompts(1, 62, 1), 4)      # 62 + 4 > max_seq 64


def test_max_batch_caps_concurrency_and_third_joins_midflight():
    eng = FakeEngine()
    sched = ServeScheduler(eng, max_batch=2)
    reqs = [sched.submit(_prompts(1, 4, i + 1), 4) for i in range(3)]
    sched.step()
    assert len(sched.running) == 2 and len(sched.waiting) == 1
    outs = [sched.run(r) for r in reqs]
    for req, out in zip(reqs, outs):
        assert np.array_equal(out, np.stack(_expected(req), axis=1))
    assert sched.counters["peak_running"] == 2
    assert sched.counters["finished"] == 3
    # the third request joined the shared loop, not a fresh batch
    assert sched.counters["admitted"] == 3


def test_kv_budget_preempts_and_requeues_until_all_finish():
    """The acceptance shape: a budget that fits both requests at
    admission but not through decode growth — the newest is preempted
    (state dropped), requeued, rebuilt, and still completes with
    exactly the tokens it would have produced alone."""
    eng = FakeEngine()
    # per-seq blocks at block_size=1: prompt 8 + generated; two
    # requests outgrow 21 blocks after their first decode step
    sched = ServeScheduler(eng, max_batch=4, kv_blocks=21, block_size=1)
    r1 = sched.submit(_prompts(1, 8, 1), 4)
    r2 = sched.submit(_prompts(1, 8, 2), 4)
    while not (r1.finished and r2.finished):
        sched.step()
    for req in (r1, r2):
        got = np.stack(req.tokens, axis=1)
        assert np.array_equal(got, np.stack(_expected(req), axis=1))
        assert len(req.tokens) == 4          # exactly once, no dupes
    assert sched.counters["preempted"] >= 1
    # each preempted request is rebuilt once, on its re-admission
    assert sched.counters["preempted"] == eng.rebuilds
    assert eng.rebuilds >= 1
    assert sched.used_blocks() == 0 and not sched.waiting


def test_lone_over_budget_request_still_runs():
    eng = FakeEngine()
    sched = ServeScheduler(eng, max_batch=2, kv_blocks=2, block_size=1)
    req = sched.submit(_prompts(1, 8, 5), 3)    # needs >> 2 blocks
    out = sched.run(req)
    assert out.shape == (1, 3)
    assert sched.counters["preempted"] == 0     # never self-preempts


def test_stream_tokens_exactly_once_across_preemption():
    eng = FakeEngine()
    sched = ServeScheduler(eng, max_batch=4, kv_blocks=21, block_size=1)
    r1 = sched.submit(_prompts(1, 8, 1), 4)
    r2 = sched.submit(_prompts(1, 8, 2), 4)
    s1, s2 = sched.stream_tokens(r1), sched.stream_tokens(r2)
    got1, got2 = [], []
    done1 = done2 = False
    while not (done1 and done2):     # alternate consumers
        if not done1:
            tok = next(s1, None)
            done1 = tok is None
            if tok is not None:
                got1.append(tok)
        if not done2:
            tok = next(s2, None)
            done2 = tok is None
            if tok is not None:
                got2.append(tok)
    assert sched.counters["preempted"] >= 1
    for req, got in ((r1, got1), (r2, got2)):
        assert len(got) == 4
        for a, b in zip(got, _expected(req)):
            assert np.array_equal(a, b)


def test_closing_stream_cancels_request():
    eng = FakeEngine()
    sched = ServeScheduler(eng, max_batch=2)
    req = sched.submit(_prompts(1, 4, 1), 4)
    stream = sched.stream_tokens(req)
    next(stream)
    stream.close()                   # consumer gone mid-decode
    assert req.state == CANCELLED and req.runtime is None
    assert not sched.running and sched.counters["cancelled"] == 1
    # a cancelled request never blocks later traffic
    other = sched.submit(_prompts(1, 4, 2), 2)
    assert np.array_equal(sched.run(other),
                          np.stack(_expected(other), axis=1))


def test_stats_shape():
    sched = ServeScheduler(FakeEngine(), max_batch=2, kv_blocks=9)
    st_ = sched.stats()
    for key in ("submitted", "admitted", "finished", "preempted",
                "cancelled", "steps", "peak_running",
                "peak_waiting", "running", "waiting", "used_blocks",
                "kv_blocks"):
        assert key in st_, key
    assert st_["kv_blocks"] == 9


@given(data=st.data())
@settings(max_examples=50, deadline=None)
def test_arrival_and_consumption_order_never_change_tokens(data):
    """The tentpole property: whatever the arrival schedule, the
    consumer interleaving, the batch cap, or the KV budget (with its
    preemptions), every request's stream is exactly its solo token
    sequence — continuous batching never leaks one request's schedule
    into another's output."""
    n = data.draw(st.integers(1, 4), label="n_requests")
    specs = [(data.draw(st.integers(1, 3)), data.draw(st.integers(1, 6)),
              data.draw(st.integers(1, 5))) for _ in range(n)]
    eng = FakeEngine()
    sched = ServeScheduler(
        eng,
        max_batch=data.draw(st.integers(1, 3), label="max_batch"),
        kv_blocks=data.draw(st.one_of(st.none(), st.integers(6, 60)),
                            label="kv_blocks"),
        block_size=data.draw(st.integers(1, 4), label="block_size"))
    pending = list(range(n))
    active, results, reqs = {}, {}, {}
    while pending or active:
        submit = pending and (not active
                              or data.draw(st.booleans(), label="submit"))
        if submit:
            i = pending.pop(0)
            rows, plen, mnt = specs[i]
            req = sched.submit(_prompts(rows, plen, i + 1), mnt)
            reqs[i] = req
            active[i] = sched.stream_tokens(req)
            results[i] = []
        else:
            i = data.draw(st.sampled_from(sorted(active)), label="pull")
            tok = next(active[i], None)
            if tok is None:
                del active[i]
            else:
                results[i].append(tok)
    for i, req in reqs.items():
        exp = _expected(req)
        assert len(results[i]) == len(exp)
        for a, b in zip(results[i], exp):
            assert np.array_equal(a, b)
    assert not sched.running and not sched.waiting
    assert sched.counters["finished"] == n
    assert sched.counters["preempted"] == eng.rebuilds


# ---------------------------------------------------------------------------
# over the rpc fabric (real engine, reduced config)
# ---------------------------------------------------------------------------

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


def _rng_prompts(eng_, rows, plen, seed):
    vocab = eng_.acfg.model.vocab_size
    return np.random.default_rng(seed).integers(
        0, vocab, (rows, plen), dtype=np.int32)


def test_staggered_join_matches_solo_generate(eng):
    """A request submitted while another is mid-decode joins the shared
    step; both streams stay byte-identical to their solo runs."""
    sched = eng.make_scheduler(max_batch=4)
    p1 = _rng_prompts(eng, 2, 8, 1)
    p2 = _rng_prompts(eng, 2, 8, 2)
    solo1, solo2 = eng.generate(p1), eng.generate(p2)
    r1 = sched.submit(p1)
    s1 = sched.stream_tokens(r1)
    got1 = [next(s1), next(s1)]          # two tokens decoded already
    r2 = sched.submit(p2)                # late joiner
    s2 = sched.stream_tokens(r2)
    got2 = list(s2)
    got1 += list(s1)
    assert np.array_equal(np.stack(got1, axis=1), solo1)
    assert np.array_equal(np.stack(got2, axis=1), solo2)
    assert sched.counters["peak_running"] == 2
    assert sched.counters["admitted"] == 2 and not sched.running


def test_concurrent_streams_over_rpc_interleave_and_match(eng):
    """Two generate_stream calls on one endpoint: chunks come from the
    shared decode step (pumped, so queue depth sees both in flight) and
    each client's reassembled block equals the solo run."""
    from repro import rpc as rpclib
    from repro.serve.engine import decode_token_chunk, serve_stub
    metrics = rpclib.MetricsInterceptor()
    fab = rpclib.RpcFabric(rpclib.make_transport("loopback", 2),
                           server_interceptors=[metrics])
    eng.attach(fab.add_server(0), max_batch=4)
    stub = serve_stub(fab.channel(1, 0))
    p1 = _rng_prompts(eng, 2, 8, 3)
    p2 = _rng_prompts(eng, 2, 8, 4)
    h1 = stub.generate_stream((p1, 0))
    h2 = stub.generate_stream((p2, 0))
    fab.flush()
    out1 = np.stack([decode_token_chunk(c) for c in h1.result()], axis=1)
    out2 = np.stack([decode_token_chunk(c) for c in h2.result()], axis=1)
    assert np.array_equal(out1, eng.generate(p1))
    assert np.array_equal(out2, eng.generate(p2))
    snap = metrics.snapshot(gauges=True)
    # both calls were open at once server-side...
    assert snap["server:Serve/generate_stream"]["queue_peak"] >= 2
    # ...and the endpoint scheduler really ran them as one batch
    sched_stats = snap["serve:scheduler@0"]
    assert sched_stats["peak_running"] == 2
    assert sched_stats["finished"] == 2


def test_kv_exhaustion_over_rpc_preempts_requeues_and_traces(eng):
    """KV budget for one-and-a-bit sequences, two streaming calls: the
    newest is preempted + requeued (visible in the metrics gauges) yet
    both clients get byte-identical results, and the scheduler's
    waiting/prefill/decode/preempted phases land in the Chrome trace."""
    from repro import rpc as rpclib
    from repro.serve.engine import decode_token_chunk, serve_stub
    metrics = rpclib.MetricsInterceptor()
    tracer = rpclib.Tracer()
    fab = rpclib.RpcFabric(rpclib.make_transport("loopback", 2),
                           server_interceptors=[metrics], tracer=tracer)
    eng.attach(fab.add_server(0), max_batch=4, kv_blocks=21,
               block_size=1)
    stub = serve_stub(fab.channel(1, 0))
    p1 = _rng_prompts(eng, 1, 8, 5)
    p2 = _rng_prompts(eng, 1, 8, 6)
    h1 = stub.generate_stream((p1, 0))
    h2 = stub.generate_stream((p2, 0))
    fab.flush()
    out1 = np.stack([decode_token_chunk(c) for c in h1.result()], axis=1)
    out2 = np.stack([decode_token_chunk(c) for c in h2.result()], axis=1)
    assert np.array_equal(out1, eng.generate(p1))
    assert np.array_equal(out2, eng.generate(p2))
    gauges = metrics.snapshot(gauges=True)["serve:scheduler@0"]
    assert gauges["preempted"] >= 1
    # a preempted request is admitted again when it resumes
    assert gauges["admitted"] == gauges["submitted"] + gauges["preempted"]
    assert gauges["finished"] == 2
    names = {e["name"] for e in tracer.chrome_events()}
    for phase in ("waiting", "prefill", "decode", "preempted"):
        assert phase in names, (phase, sorted(names))


def test_rebuild_after_preemption_gives_the_dropped_states(eng,
                                                          monkeypatch):
    """A preempted request's state, rebuilt by replaying its prefill and
    decode steps, is byte for byte the state it held when it was
    dropped: the caches written in place, their indices, the last token
    and the key."""
    import jax
    held, checked = {}, []

    def on_host(runtime):
        return [np.asarray(a).tobytes() for a in jax.tree.leaves(runtime)]

    def keeping(op):
        def run(req):
            out = op(req)
            held[req.id] = on_host(req.runtime)
            return out
        return run

    rebuild = eng.scheduler_rebuild

    def checked_rebuild(req):
        dropped = held[req.id]
        rebuild(req)
        checked.append(on_host(req.runtime) == dropped)

    monkeypatch.setattr(eng, "scheduler_prefill",
                        keeping(eng.scheduler_prefill))
    monkeypatch.setattr(eng, "scheduler_decode",
                        keeping(eng.scheduler_decode))
    monkeypatch.setattr(eng, "scheduler_rebuild", checked_rebuild)
    sched = eng.make_scheduler(max_batch=4, kv_blocks=21, block_size=1)
    reqs = [sched.submit(_rng_prompts(eng, 1, 8, 8 + i), 4)
            for i in range(2)]
    while not all(r.finished for r in reqs):
        sched.step()
    assert sched.counters["preempted"] >= 1
    assert checked and all(checked)


def test_unary_over_rpc_shares_the_endpoint_scheduler(eng):
    from repro import rpc as rpclib
    from repro.serve.engine import serve_stub
    fab = rpclib.RpcFabric(rpclib.make_transport("loopback", 2))
    sched = eng.attach(fab.add_server(0), max_batch=4)
    stub = serve_stub(fab.channel(1, 0))
    p = _rng_prompts(eng, 2, 8, 7)
    out = stub.generate((p, 0)).result()
    assert np.array_equal(out, eng.generate(p))
    assert sched.counters["finished"] == 1


def test_scheduler_least_loaded_steers_to_idle_shard(eng):
    """The scheduler-aware dispatch policy reads each endpoint's live
    scheduler gauge (running + waiting), so a shard decoding requests
    another client submitted loses ties the client's own outstanding
    book would never see."""
    from repro import rpc as rpclib
    from repro.serve.engine import ShardedServeStub
    metrics = rpclib.MetricsInterceptor()
    fab = rpclib.RpcFabric(rpclib.make_transport("loopback", 3),
                           server_interceptors=[metrics])
    sched0 = eng.attach(fab.add_server(0), max_batch=1)
    eng.attach(fab.add_server(1), max_batch=1)
    stub = ShardedServeStub(fab, 2, (0, 1),
                            policy="scheduler_least_loaded")
    assert stub._pick() == 0                     # all idle: first shard
    # another client's work lands in shard 0's scheduler: one request
    # decoding, one queued behind max_batch=1 -> load 2
    r1 = sched0.submit(_rng_prompts(eng, 1, 8, 11))
    r2 = sched0.submit(_rng_prompts(eng, 1, 8, 12))
    sched0.step()
    assert stub._shard_queue_depth(0) == 2
    assert stub._shard_queue_depth(1) == 0
    p = _rng_prompts(eng, 1, 8, 13)
    h = stub.generate(p, 0)
    assert len(stub._inflight[1]) == 1           # steered off shard 0
    fab.flush()
    assert np.array_equal(h.result(), eng.generate(p))
    for r in (r1, r2):
        assert np.array_equal(sched0.run(r),
                              eng.generate(r.prompts))


# ---------------------------------------------------------------------------
# admission policy (sjf)
# ---------------------------------------------------------------------------

class _Clock:
    """Minimal server stand-in: gives the scheduler a controllable
    clock (bind() only reads .clock/.tracer/.endpoint)."""
    endpoint = 0
    tracer = None

    def __init__(self):
        self.t = 0.0

    def clock(self):
        return self.t


def _admission_order(sched, reqs):
    order, seen = [], set()
    while not all(r.finished for r in reqs):
        sched.step()
        for r in sched.running:
            if r.id not in seen:
                seen.add(r.id)
                order.append(r.id)
    return order


def test_unknown_policy_rejected():
    with pytest.raises(ValueError, match="unknown scheduler policy"):
        ServeScheduler(FakeEngine(), policy="lifo")


def test_sjf_admits_shortest_first_with_fifo_tiebreak():
    # one slot: admission order is fully observable. Two plen-2
    # requests tie -> earlier submit id wins; the plen-8 goes last.
    eng = FakeEngine()
    sched = ServeScheduler(eng, max_batch=1, policy="sjf")
    long_ = sched.submit(_prompts(1, 8, 3))
    a = sched.submit(_prompts(1, 2, 5))
    b = sched.submit(_prompts(1, 2, 9))
    assert _admission_order(sched, [long_, a, b]) \
        == [a.id, b.id, long_.id]
    assert sched.stats()["policy"] == "sjf"
    # fifo baseline: same prompts admit in arrival order
    fifo = ServeScheduler(FakeEngine(), max_batch=1, policy="fifo")
    r1 = fifo.submit(_prompts(1, 8, 3))
    r2 = fifo.submit(_prompts(1, 2, 5))
    r3 = fifo.submit(_prompts(1, 2, 9))
    assert _admission_order(fifo, [r1, r2, r3]) \
        == [r1.id, r2.id, r3.id]


def test_sjf_preempted_resumes_before_shorter_fresh_request():
    # block_size=1 makes the budget arithmetic transparent: two plen-4
    # requests outgrow kv_blocks=12 at generated=2, evicting the
    # newest (r2). A fresh plen-1 request then joins the queue — but
    # r2's recompute debt wins: it resumes (rebuild, not prefill)
    # ahead of the shorter newcomer, and every stream still delivers
    # its exact token sequence.
    eng = FakeEngine()
    sched = ServeScheduler(eng, max_batch=2, kv_blocks=12,
                           block_size=1, policy="sjf")
    r1 = sched.submit(_prompts(1, 4, 1))
    r2 = sched.submit(_prompts(1, 4, 2))
    for _ in range(3):
        sched.step()
    assert r2.state == PREEMPTED and r2 in sched.waiting
    short = sched.submit(_prompts(1, 1, 7))
    while not r2.state == "running":
        sched.step()
    assert sched.running[0] is r2        # resumed ahead of `short`
    assert eng.rebuilds == 1
    for r in (r1, r2, short):
        sched.run(r)
        assert all(np.array_equal(t, e)
                   for t, e in zip(r.tokens, _expected(r)))


def test_sjf_starvation_age_restores_fifo_priority():
    # a long prompt parked past starvation_age_s regains strict FIFO
    # priority over fresh short prompts
    clock = _Clock()
    sched = ServeScheduler(FakeEngine(), max_batch=1, policy="sjf",
                           starvation_age_s=1.0).bind(clock)
    long_ = sched.submit(_prompts(1, 8, 3))
    short1 = sched.submit(_prompts(1, 2, 5))
    while not short1.finished:           # sjf favors short1 first
        sched.step()
    assert not long_.finished
    clock.t = 2.0                        # long_ now starved (age 2.0)
    short2 = sched.submit(_prompts(1, 2, 9))
    sched.step()
    assert sched.running[0] is long_     # fifo escape hatch fired
    assert not short2.finished
    # control: without the escape hatch, short2 would have won
    ctrl = ServeScheduler(FakeEngine(), max_batch=1,
                          policy="sjf").bind(_Clock())
    c_long = ctrl.submit(_prompts(1, 8, 3))
    c_short = ctrl.submit(_prompts(1, 2, 5))
    ctrl.step()
    assert ctrl.running[0] is c_short and c_long in ctrl.waiting
