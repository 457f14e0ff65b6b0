"""A served model as its users reach it: ``Serve/generate_stream`` over
a loopback ``RpcFabric`` into the endpoint's ``ServeScheduler``, as
``repro.launch.serve`` serves it, greedy, one prompt per request.

The benchmark times each layer from its own wrappers and leaves the
program as it is: the fabric's ``flush`` in the loops below, the
engine's ``scheduler_prefill``/``scheduler_decode`` and the
scheduler's ``step`` wrapped on their instances, and every token chunk
stamped as the client receives it (a client interceptor). Each wrapper
also opens a ``bench.*`` host span for the profiler.
"""
from __future__ import annotations

import hashlib
import time
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from bench import core
from bench import traffic as T
from bench.trace import TracedWindow, span

#: served tokens the reference checks per run, at the least
SAMPLE_TOKENS = 256
#: warm-up requests generate this many tokens (prefill + decode)
WARM_TOKENS = 2
#: a closed loop hands the fabric back after each flush iteration
QUANTUM_S = 1e-3
#: the notes give the requests running per step in bins this long, so
#: that a mix can place its traced stretch where the slots are full
BIN_S = 3.0


@dataclass
class Served:
    """One request as the client saw it."""
    req: T.ServeRequest
    prompt: np.ndarray
    due: float               # host clock; the submit time in a closed loop
    handle: object
    submitted: float = 0.0
    times: List[float] = field(default_factory=list)

    @property
    def tokens(self) -> List[int]:
        return [int(np.ascontiguousarray(c[0]).view("<i4")[0])
                for c in self.handle.chunks]

    @property
    def ok(self) -> bool:
        return (self.handle.done and self.handle.error is None
                and len(self.handle.chunks) == self.req.output_len)


def _digest(prompts: np.ndarray) -> bytes:
    return hashlib.blake2b(np.ascontiguousarray(prompts).tobytes(),
                           digest_size=16).digest()


class ServeSystem:
    """One engine on one chip behind the loopback fabric.

    ``reference_gap(samples)`` is the configuration's plain reference:
    given ``[(prompt, served tokens)]`` it returns the widest gap by
    which a served token's logit lies below the reference's best;
    ``control_gap(samples)`` is the same reading for the reference in
    the precision below the served one (``bench/calibrate.py`` only).
    ``costs`` computes operations and bytes from shapes."""

    def __init__(self, engine, *, max_batch: int, vocab: int,
                 reference_gap: Callable, control_gap: Callable, costs,
                 limit: float):
        from repro.rpc.interceptors import ClientInterceptor
        from repro.serve.engine import serve_stub

        class ChunkClock(ClientInterceptor):
            def __init__(self):
                self.times: Dict[int, List[float]] = defaultdict(list)

            def on_event(self, ctx, event):
                if event.kind == "stream_chunk":
                    self.times[ctx.call_id].append(time.perf_counter())

        self.engine, self.vocab, self.costs = engine, vocab, costs
        self.reference_gap, self.control_gap = reference_gap, control_gap
        self.limit = limit
        self.fabric, channel = engine.serve_loopback(max_batch=max_batch)
        self.scheduler = engine.schedulers[0]
        self.stub = serve_stub(channel)
        self.clock = ChunkClock()
        self.fabric.client_interceptors.append(self.clock)
        self.served: List[Served] = []
        #: (kind, t0, t1, positions): "prefill" with the prompt length,
        #: "decode" with the positions the step attends to
        self.calls: List[Tuple[str, float, float, int]] = []
        self.steps: List[Tuple[float, int]] = []          # (t, tokens)
        self.flushes: List[Tuple[float, float]] = []
        self.engine_requests: Dict[bytes, object] = {}
        self.window = (0.0, 0.0)
        self.lateness: List[float] = []
        self._instrument()

    # -- wrappers ----------------------------------------------------------
    def _instrument(self) -> None:
        eng, sched = self.engine, self.scheduler
        prefill, decode = eng.scheduler_prefill, eng.scheduler_decode
        step, submit = sched.step, sched.submit
        calls, steps = self.calls, self.steps

        def timed_prefill(req):
            t0 = time.perf_counter()
            with span("bench.prefill"):
                tok = prefill(req)
            calls.append(("prefill", t0, time.perf_counter(),
                          req.prompt_len))
            return tok

        def timed_decode(req):
            held = req.prompt_len + len(req.tokens)
            t0 = time.perf_counter()
            with span("bench.decode"):
                tok = decode(req)
            calls.append(("decode", t0, time.perf_counter(), held))
            return tok

        def counted_step():
            n = step()
            steps.append((time.perf_counter(), n))
            return n

        def recorded_submit(prompts, max_new_tokens=None):
            req = submit(prompts, max_new_tokens)
            self.engine_requests[_digest(prompts)] = req
            return req

        eng.scheduler_prefill, eng.scheduler_decode = (timed_prefill,
                                                       timed_decode)
        sched.step, sched.submit = counted_step, recorded_submit

    # -- the loops ---------------------------------------------------------
    def _submit(self, req: T.ServeRequest, seed: int,
                due: Optional[float]) -> Served:
        prompt = T.prompt_tokens(seed, req.index, req.prompt_len, self.vocab)
        now = time.perf_counter()
        with span("bench.submit"):
            handle = self.stub.generate_stream((prompt, req.output_len))
        s = Served(req, prompt, now if due is None else due, handle, now)
        self.served.append(s)
        return s

    def _flush(self, until: Optional[float]) -> None:
        t0 = time.perf_counter()
        with span("bench.flush"):
            self.fabric.flush(until_s=until)
        self.flushes.append((t0, time.perf_counter()))

    def warmup(self, p: dict) -> None:
        """Compile every shape this mix uses: one request per prompt
        length, long enough to run the decode step too."""
        for i, plen in enumerate(T.prompt_lengths(p)):
            self._submit(T.ServeRequest(-1 - i, None, plen, WARM_TOKENS),
                         seed=0, due=None)
            self._flush(None)
        bad = [s for s in self.served if not s.ok]
        if bad:
            raise core.BenchError(f"warm-up request failed: "
                                  f"{bad[0].handle.error}")
        self.served.clear()

    def run(self, p: dict, seed: int, seconds: float,
            tracer: TracedWindow) -> None:
        if p["kind"] == "serve_open":
            self._run_open(p, seed, seconds, tracer)
        elif p["kind"] == "serve_closed":
            self._run_closed(p, seed, seconds, tracer)
        else:
            raise core.BenchError(f"a served model cannot run {p['kind']}")
        t0 = time.perf_counter()
        with span("bench.drain"):
            self.fabric.flush()
        self.flushes.append((t0, time.perf_counter()))
        for s in self.served:
            s.times = list(self.clock.times.get(s.handle.call_id, ()))

    def _run_open(self, p, seed, seconds, tracer) -> None:
        sched = T.serve_schedule(p, seconds)
        base = time.perf_counter() + float(p["lead_s"])
        end = base + seconds
        self.window = (base, end)
        dues = [base + r.due_s for r in sched]
        # the mix places its traced stretch where its schedule keeps the
        # slots full
        tracer.at = base + float(p["trace_at_s"])
        stop = tracer.at + tracer.length_s
        i = 0
        while True:
            now = time.perf_counter()
            if tracer.due(now):
                tracer.start()
            if tracer.started and now >= stop:
                tracer.end_stretch()
            # a flush can overrun its deadline: whatever fell due
            # meanwhile is sent now, late, also past the close
            while i < len(sched) and dues[i] <= now:
                s = self._submit(sched[i], seed, dues[i])
                if base <= dues[i] < end:
                    self.lateness.append(s.submitted - dues[i])
                i += 1
            if now >= end:
                break
            until = min(dues[i] if i < len(sched) else end, end)
            if tracer.enabled and not tracer.ended:
                until = min(until, stop if tracer.started else tracer.at)
            self._flush(until)
            wait = until - time.perf_counter()
            if wait > 0:
                with span("bench.idle"):
                    time.sleep(wait)
        tracer.stop()

    def _run_closed(self, p, seed, seconds, tracer) -> None:
        base = time.perf_counter()
        end = base + seconds
        self.window = (base, end)
        tracer.at = end - tracer.length_s
        k = 0
        clients = []
        for _ in range(int(p["clients"])):
            clients.append(self._submit(T.closed_request(p, k), seed,
                                        None))
            k += 1
        while True:
            now = time.perf_counter()
            if tracer.due(now):
                tracer.start()
            if now >= end:
                break
            self._flush(min(end, now + QUANTUM_S))
            for j, s in enumerate(clients):
                if s.handle.done and time.perf_counter() < end:
                    clients[j] = self._submit(T.closed_request(p, k),
                                              seed, None)
                    k += 1
        tracer.stop()

    # -- what the run measured ---------------------------------------------
    def counted(self) -> List[Served]:
        base, end = self.window
        return [s for s in self.served if base <= s.due < end]

    def end_to_end(self) -> Dict[str, float]:
        base, end = self.window
        ok = [s for s in self.counted() if s.ok]
        ttft = [s.times[0] - s.due for s in ok]
        itl = [b - a for s in ok for a, b in zip(s.times, s.times[1:])]
        received = sum(1 for s in self.served for t in s.times
                       if base <= t < end)
        out = {"tokens_per_s": core.rate(received, end - base)}
        if ttft:
            out["ttft_p95_ms"] = core.percentile(ttft, 95) * 1e3
        if itl:
            out["itl_p95_ms"] = core.percentile(itl, 95) * 1e3
        return out

    def attempted(self) -> int:
        return len(self.counted())

    def failed(self) -> int:
        return sum(1 for s in self.counted() if not s.ok)

    def notes(self) -> List[str]:
        ok = [s for s in self.counted() if s.ok]
        gaps = sum(max(0, len(s.times) - 1) for s in ok)
        lines = [f"samples: {len(ok)} requests for ttft_p95_ms, "
                 f"{gaps} token gaps for itl_p95_ms"]
        base, end = self.window
        bins = [[n for t, n in self.steps
                 if n > 0 and base + k <= t < base + k + BIN_S]
                for k in np.arange(0.0, end - base, BIN_S)]
        lines.append(f"running per step, by {BIN_S:g} s of the window: "
                     + " ".join(f"{np.mean(b):.2f}" if b else "-"
                                for b in bins))
        if self.lateness:
            lines.append(
                f"generator lateness: max {max(self.lateness) * 1e3:.3f} ms"
                f", mean {np.mean(self.lateness) * 1e3:.3f} ms over "
                f"{len(self.lateness)} arrivals")
        return lines

    # -- correctness -------------------------------------------------------
    def sample(self, seed: int) -> List[Served]:
        """Finished requests for the reference, drawn from the seed: the
        longest, then others until ``SAMPLE_TOKENS`` served tokens."""
        ok = sorted((s for s in self.counted() if s.ok),
                    key=lambda s: s.req.index)
        if not ok:
            return []
        longest = max(ok, key=lambda s: s.req.prompt_len + s.req.output_len)
        rest = [s for s in ok if s is not longest]
        order = np.random.default_rng([seed % 2**64, 1]).permutation(
            len(rest))
        picked, tokens = [longest], longest.req.output_len
        for i in order:
            if tokens >= SAMPLE_TOKENS:
                break
            picked.append(rest[i])
            tokens += rest[i].req.output_len
        return picked

    def check(self, seed: int) -> Dict[str, dict]:
        """Every counted request's chunks against what the engine
        produced (the fabric), then a sample's served tokens against
        the plain reference (prefill, then decode through the cache)."""
        mismatched = 0
        for s in self.counted():
            req = self.engine_requests.get(_digest(s.prompt))
            made = [int(t[0]) for t in req.tokens] if req is not None else []
            if s.tokens != made:
                mismatched += 1
        picked = self.sample(seed)
        self.free()
        # with nothing finished to compare, the gap reads as huge
        gap = (self.reference_gap([(s.prompt[0], s.tokens) for s in picked])
               if picked else 1e9)
        return {"max_logit_gap": {"value": gap, "limit": self.limit},
                "fabric_token_mismatches": {"value": mismatched,
                                            "limit": 0}}

    def free(self) -> None:
        """Drop the program's state before the reference runs (finished
        requests hold no cache); the weights stay, the reference reads
        them."""
        self.fabric = self.stub = self.scheduler = None
        self.engine.schedulers.clear()
