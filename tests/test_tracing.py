"""Distributed tracing over the fabric: span trees on the modeled
clock, the phase-partition invariant, trace-id propagation in the frame
header, Chrome trace-event export, the bench_comm phase breakdown /
--trace / schema-3 JSON surface, and the perf-baseline telemetry
round trip. Ends with the acceptance scenario: a cluster-transport
serve run under faults whose retried, failed-over server-stream call
shows stall -> fault -> backoff -> re-route -> delivery as nested
spans in the exported Chrome JSON."""
import io
import json

import numpy as np
import pytest

from repro import rpc
from repro.rpc.framing import decode, encode
from repro.rpc.tracing import PHASES

SIZES = [2048, 256]


def _bufs(sizes, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 255, s, dtype=np.uint8) for s in sizes]


def _sim_fabric(tracer, n=2, **kw):
    fab = rpc.RpcFabric(rpc.make_transport("simulated", n,
                                           network="eth40g"),
                        tracer=tracer, **kw)
    fab.add_server(1).register("echo", lambda bufs: bufs)
    return fab


def _assert_partition(root, rel_tol=1e-9):
    """The tracing invariant: a closed call's phases are a contiguous
    non-overlapping partition of [start, end] summing to the
    end-to-end latency."""
    phases = sorted((s for s in root.phase_spans() if s.closed),
                    key=lambda s: (s.start_s, s.span_id))
    assert phases, "closed call must have phase spans"
    assert phases[0].start_s == root.start_s
    assert phases[-1].end_s == root.end_s
    for a, b in zip(phases, phases[1:]):
        assert a.end_s == b.start_s        # contiguous, no overlap
    total = sum(s.duration_s for s in phases)
    assert total == pytest.approx(root.duration_s, rel=rel_tol, abs=0.0)


# ---------------------------------------------------------------------------
# span tree + phases, unary
# ---------------------------------------------------------------------------

def test_unary_span_tree_and_exact_phase_partition():
    tracer = rpc.Tracer()
    fab = _sim_fabric(tracer)
    c = fab.channel(0, 1).call("echo", _bufs(SIZES))
    fab.flush()
    assert c.error is None
    (root,) = tracer.calls()
    assert root.closed and root.name == "echo"
    assert root.attrs["outcome"] == "replied"
    assert root.attrs["attempts"] == 1
    assert len(root.attempt_spans()) == 1
    _assert_partition(root)
    by_phase = {s.name for s in root.phase_spans()}
    # simulated unary: queued, on the wire, served, reply in flight
    assert {"queue", "wire", "server", "reply"} <= by_phase
    # wire record spans (request + reply) on the sender tracks
    wires = [s for s in root.walk() if s.category == "wire"]
    assert {w.attrs["reply"] for w in wires} == {False, True}
    # the handler span landed on the SERVER endpoint's track
    handlers = [s for s in root.walk() if s.category == "server"
                and s.name.startswith("handler")]
    assert handlers and all(h.endpoint == 1 for h in handlers)
    # one trace id spans all of it, and live state was reclaimed
    assert {s.trace_id for s in root.walk()} == {root.trace_id}
    assert not tracer._by_call and not tracer._by_trace


def test_trace_id_rides_the_frame_header():
    tracer = rpc.Tracer()
    fab = _sim_fabric(tracer)
    ch = fab.channel(0, 1)
    c = ch.call("echo", _bufs(SIZES))
    ctx = fab.context(c.call_id)
    assert ctx.trace_id == tracer.calls()[0].trace_id > 0
    fab.flush()
    # the header word round-trips the id through encode/decode, and
    # replies inherit it (how the reply wire span found its call)
    f = rpc.make_frame(7, "echo", _bufs(SIZES))
    f = f.__class__(**{**f.__dict__, "trace_id": 41})
    assert decode(encode(f)).trace_id == 41
    assert f.reply([np.zeros(1, np.uint8)]).trace_id == 41


def test_credit_stall_phase_recorded():
    """With a one-message window the second call queues behind the
    first's credit — the stall is its own phase, and the partition
    still holds."""
    tracer = rpc.Tracer()
    fab = _sim_fabric(tracer, window_msgs=1)
    ch = fab.channel(0, 1)
    c1 = ch.call("echo", _bufs(SIZES))
    c2 = ch.call("echo", _bufs(SIZES, seed=1))
    fab.flush()
    assert c1.error is None and c2.error is None
    roots = tracer.calls()
    assert len(roots) == 2
    stalled = [r for r in roots
               if any(s.name == "credit_stall" for s in r.phase_spans())]
    assert stalled, "window_msgs=1 must stall the second call"
    for r in roots:
        _assert_partition(r)


def test_phase_breakdown_sums_to_end_to_end():
    tracer = rpc.Tracer()
    fab = _sim_fabric(tracer)
    ch = fab.channel(0, 1)
    for i in range(5):
        ch.call("echo", _bufs(SIZES, seed=i))
    fab.flush()
    bd = tracer.phase_breakdown()
    assert set(bd) == {"echo"}
    row = bd["echo"]
    assert row["calls"] == 5
    assert set(row["phases"]) == set(PHASES)
    total = sum(row["phases"].values())
    assert abs(total - row["end_to_end_s"]) \
        <= 0.01 * row["end_to_end_s"]       # the 1% acceptance bound
    assert row["end_to_end_s"] > 0


def test_tracer_span_cap_stops_tracking():
    """At the cap, NEW calls stop being tracked (dropped counts);
    already-tracked calls still close their trees."""
    tracer = rpc.Tracer(max_spans=4)
    fab = _sim_fabric(tracer)
    ch = fab.channel(0, 1)
    for i in range(6):
        ch.call("echo", _bufs([64]))
    fab.flush()
    assert len(tracer.calls()) == 2         # cap hit after two starts
    assert tracer.dropped == 4
    for root in tracer.calls():
        assert root.closed
        _assert_partition(root)
    tracer.clear()
    assert tracer.spans() == [] and tracer.dropped == 0


# ---------------------------------------------------------------------------
# Chrome export
# ---------------------------------------------------------------------------

def test_chrome_export_shape(tmp_path):
    tracer = rpc.Tracer()
    fab = _sim_fabric(tracer)
    fab.channel(0, 1).call("echo", _bufs(SIZES))
    fab.flush()
    out = tmp_path / "trace.json"
    tracer.export_chrome(str(out))
    doc = json.loads(out.read_text())
    ev = doc["traceEvents"]
    meta = [e for e in ev if e["ph"] == "M"]
    # process name + one named track per endpoint that recorded spans,
    # and one for the flush loop's host spans
    assert {m["name"] for m in meta} == {"process_name", "thread_name"}
    assert {m["tid"] for m in meta if m["name"] == "thread_name"} \
        == {0, 1, rpc.tracing.HOST_TRACK}
    xs = [e for e in ev if e["ph"] == "X"]
    assert xs
    for e in xs:
        assert e["dur"] >= 0 and e["pid"] == 0
        if e["cat"] == "host":
            # a flight's delivery serves no single call: untraced (0)
            assert e["tid"] == rpc.tracing.HOST_TRACK
            assert e["name"] in ("rpc.deliver", "rpc.complete")
            assert e["args"]["trace_id"] == 0
        else:
            assert e["args"]["trace_id"] >= 1
    assert {e["cat"] for e in xs} >= {"call", "attempt", "phase",
                                      "wire", "server", "host"}
    # file-like export produces the same document
    buf = io.StringIO()
    tracer.export_chrome(buf)
    assert json.loads(buf.getvalue()) == doc


# ---------------------------------------------------------------------------
# bench_comm surface: phases in --json, --trace, schema, baseline
# ---------------------------------------------------------------------------

def _bench_json(tmp_path, *extra):
    from repro.launch import bench_comm
    out = tmp_path / "rows.json"
    bench_comm.main(["--benchmark", "incast", "--transport", "simulated",
                     "--network", "eth40g", "--num-workers", "3",
                     "--json", str(out), *extra])
    return json.loads(out.read_text())


def test_bench_comm_json_schema_and_phase_breakdown(tmp_path, capsys):
    doc = _bench_json(tmp_path)
    assert set(doc) == {"schema", "rows"}      # versioned envelope
    assert doc["schema"] == 3
    (row,) = doc["rows"]
    phases = row["rpc_phases"]["Incast/push_fetch"]
    assert phases["calls"] > 0
    total = sum(phases["phases"].values())
    assert abs(total - phases["end_to_end_s"]) \
        <= 0.01 * phases["end_to_end_s"]
    assert "phase breakdown" in capsys.readouterr().out


def test_bench_comm_trace_flag_writes_chrome_json(tmp_path):
    trace = tmp_path / "out.json"
    _bench_json(tmp_path, "--trace", str(trace))
    doc = json.loads(trace.read_text())
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert any(e["cat"] == "call" for e in xs)


def test_bench_comm_trace_flag_validation(capsys):
    from repro.launch import bench_comm
    with pytest.raises(SystemExit):
        bench_comm.main(["--benchmark", "p2p_latency",
                         "--trace", "x.json"])
    assert "fabric benchmark" in capsys.readouterr().err
    with pytest.raises(SystemExit):
        bench_comm.main(["--sweep", "scheme", "--benchmark", "incast",
                         "--transport", "simulated",
                         "--trace", "x.json"])
    assert "single run" in capsys.readouterr().err
    # --baseline/--check-baseline run no benchmark, so combining them
    # with --trace used to silently write no trace file; now rejected
    for flag in ("--baseline", "--check-baseline"):
        with pytest.raises(SystemExit):
            bench_comm.main(["--benchmark", "incast", "--transport",
                             "simulated", "--trace", "x.json",
                             flag, "b.json"])
        assert "without running a benchmark" in capsys.readouterr().err


def test_baseline_collect_check_and_drift(tmp_path, capsys):
    from repro.core import bench
    from repro.launch import bench_comm
    base = tmp_path / "base.json"
    bench_comm.main(["--baseline", str(base)])
    doc = json.loads(base.read_text())
    assert doc["schema"] == bench.BASELINE_SCHEMA
    assert set(doc["families"]) == {
        "p2p_latency", "p2p_bandwidth", "ps_throughput",
        "fully_connected", "ring", "incast",
        "allreduce_ring", "allreduce_tree", "allreduce_rsag",
        "train_step_ps", "train_step_allreduce"}
    for fam in doc["families"].values():
        assert fam["round_time_s"] > 0 and fam["throughput"] > 0
    assert doc["train_crossover"]["allreduce_wins_from"] is not None
    # clean check: the numbers are deterministic, zero drift
    bench_comm.main(["--check-baseline", str(base)])
    assert "baseline OK" in capsys.readouterr().out
    # a tampered family trips the gate with exit code 1
    doc["families"]["ring"]["throughput"] *= 1.05
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(doc))
    with pytest.raises(SystemExit) as ei:
        bench_comm.main(["--check-baseline", str(bad)])
    assert ei.value.code == 1
    assert "BASELINE DRIFT: ring.throughput" in capsys.readouterr().out
    # a tightened tolerance is honored end to end
    problems = bench.check_baseline(doc, rel_tol=0.10)
    assert problems == []


def test_committed_baseline_matches_fresh_run():
    """The checked-in benchmarks/BENCH_fabric.json must diff clean —
    the same gate CI runs."""
    import pathlib

    from repro.core import bench
    path = pathlib.Path(__file__).resolve().parents[1] \
        / "benchmarks" / "BENCH_fabric.json"
    doc = json.loads(path.read_text())
    assert bench.check_baseline(doc, rel_tol=0.01) == []


# ---------------------------------------------------------------------------
# acceptance: cluster serve under faults — one server-stream call whose
# trace shows stall -> fault -> backoff -> re-route -> delivery
# ---------------------------------------------------------------------------

def _stream_handlers(name, exhaust_once):
    from repro.serve.engine import _i32_buf, decode_generate_request

    def generate_stream(bufs):
        if exhaust_once.pop(name, None):
            raise rpc.ResourceExhausted(f"{name} overloaded")
        prompts, mnt = decode_generate_request(bufs)
        return [[_i32_buf(np.full(prompts.shape[0], int(name[-1]),
                                  np.int32))]
                for _ in range(max(mnt, 1))]

    return {"generate_stream": generate_stream,
            "generate": lambda bufs: bufs}


def test_acceptance_failed_over_stream_trace(tmp_path):
    from repro.serve.engine import SERVE_SERVICE, ShardedServeStub
    cluster = rpc.ClusterSpec(endpoints=(
        rpc.EndpointSpec("ps0", job="ps"),
        rpc.EndpointSpec("ps1", job="ps"),
        rpc.EndpointSpec("worker0")))
    tracer = rpc.Tracer()
    retry = rpc.RetryInterceptor(max_attempts=4, backoff_s=1e-3)
    transport = rpc.make_transport("cluster", cluster=cluster)
    # the call's FIRST frame on worker0 -> ps0 is lost to a link fault
    transport = rpc.make_transport("fault", inner=transport, seed=7,
                                   fault_rate=1.0, max_faults=1,
                                   links=[(2, 0)])
    fab = rpc.RpcFabric(transport, client_interceptors=[retry],
                        window_msgs=1, tracer=tracer)
    exhaust_once = {"ps0": True}   # ps0 sheds the retried attempt once
    for name in ("ps0", "ps1"):
        fab.add_server(name).add_service(
            SERVE_SERVICE, _stream_handlers(name, exhaust_once))
    stub = ShardedServeStub(fab, "worker0", ("ps0", "ps1"))
    prompts = np.zeros((2, 4), np.int32)
    call = stub.generate_stream(prompts, 3)    # round robin -> ps0
    fab.flush()
    assert call.done and call.error is None, call.error

    (root,) = tracer.calls()
    assert root.closed and root.attrs["outcome"] == "stream_end"
    # attempt 1 -> ps0 (lost to the link fault), attempt 2 -> ps0
    # (shed: resource exhausted), attempt 3 re-routed -> ps1
    attempts = root.attempt_spans()
    assert [a.attrs["dst"] for a in attempts] == ["ps0", "ps0", "ps1"]
    assert root.attrs["attempts"] == 3
    # the fault is on attempt 1's subtree, as an instant span
    (fault,) = [s for s in root.walk() if s.category == "fault"]
    assert fault.parent_id == attempts[0].span_id
    assert fault.name == "link_fault worker0->ps0"
    # backoff was paid on the fabric clock between attempts
    backoffs = [s for s in root.phase_spans() if s.name == "backoff"]
    assert backoffs and all(s.duration_s > 0 for s in backoffs)
    # the one-message window stalled the multi-chunk stream somewhere
    assert any(s.name == "credit_stall" for s in root.phase_spans())
    # delivery: reply-direction wire spans from the failover target
    reply_wires = [s for s in root.walk() if s.category == "wire"
                   and s.attrs["reply"] and s.endpoint == 1]
    assert reply_wires, "delivered chunks must trace from ps1"
    # the handler ran on ps1's track, attributed cross-endpoint via
    # the propagated trace id
    handlers = [s for s in root.walk() if s.category == "server"
                and s.name.startswith("handler")]
    assert any(h.endpoint == 1 for h in handlers)
    assert {s.trace_id for s in root.walk()} == {root.trace_id}
    _assert_partition(root)

    # ... and the whole causal chain survives Chrome export
    out = tmp_path / "acceptance.json"
    tracer.export_chrome(str(out))
    ev = json.loads(out.read_text())["traceEvents"]
    names = [e["name"] for e in ev if e["ph"] == "X"]
    for needed in ("attempt 1", "attempt 2", "attempt 3", "backoff",
                   "credit_stall", "link_fault worker0->ps0"):
        assert needed in names, needed
    tracks = {e["args"]["name"] for e in ev
              if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"endpoint ps1", "endpoint worker0"} <= tracks
