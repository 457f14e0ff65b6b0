"""The fully connected exchange as the fabric drives it across chips:
each endpoint sends one payload to every other through
``Exchange/exchange`` stubs, and ``RpcFabric.flush`` hands the flight
to the collective transport, which edge-colours it into rounds of
``ppermute`` over ICI.

The inputs are made here, on the chips, from the seed, and handed to
the transport in place of its own. The benchmark wraps the transport's
``deliver`` on its instance to time it and to keep the outputs of a
sample of exchanges, drawn from the seed, for the reference.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List, Tuple

import numpy as np

from bench import core
from bench import traffic as T
from bench.trace import TracedWindow, span

#: an exchange in the window is kept for the check with this chance
SAMPLE_P = 1.0 / 64
#: at most this many exchanges are kept (each holds one payload per chip)
SAMPLE_MAX = 32
WARM_EXCHANGES = 3
AXIS = "net"


def padded(size: int, align: int) -> int:
    return max(align, -(-size // align) * align)


class ExchangeSystem:
    """``n`` endpoints, one per chip, behind one ``RpcFabric``.

    ``expected(rows)`` is the configuration's plain reference: what
    each chip holds after one exchange, from the inputs alone."""

    def __init__(self, *, n: int, sizes: List[int], align: int,
                 wire_mode: str, seed: int, expected: Callable):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro import rpc
        from repro.core.payload import PayloadSpec, classify

        self.sizes, self.wire_mode = list(sizes), wire_mode
        mesh = jax.make_mesh((n,), (AXIS,), devices=jax.devices()[:n])
        spec = PayloadSpec(sizes=tuple(sizes), scheme="bench",
                           categories=tuple(classify(s) for s in sizes))
        transport = rpc.make_transport(
            "collective", n, mesh=mesh, spec=spec,
            serialized=(wire_mode == "serialized"))
        widths = [padded(s, align) for s in sizes]

        def make_rows(key):
            return tuple(jax.random.bits(jax.random.fold_in(key, j),
                                         (n, w), jnp.uint8)
                         for j, w in enumerate(widths))

        key = jax.random.fold_in(jax.random.PRNGKey(seed % 2**32),
                                 (seed >> 32) % 2**32)
        transport.bufs = list(jax.jit(
            make_rows, out_shardings=NamedSharding(mesh, P(AXIS)))(key))
        self.expected = expected([np.asarray(b) for b in transport.bufs])
        self.transport = transport
        self.fabric = rpc.RpcFabric(transport)
        self._exchange = rpc.fully_connected_exchange
        self.bytes_sent_per_chip = (n - 1) * sum(widths)
        self.rpcs_per_exchange = n * (n - 1)
        #: (t0, t1) of every exchange and of every deliver
        self.exchanges: List[Tuple[float, float]] = []
        self.delivers: List[Tuple[float, float]] = []
        self.kept: List[tuple] = []
        self.window = (0.0, 0.0)
        self._keep = False
        self._instrument()

    def _instrument(self) -> None:
        transport = self.transport
        #: the transport's own deliver, which the timed one calls
        self.device_deliver = transport.deliver

        def timed_deliver(messages):
            t0 = time.perf_counter()
            with span("bench.deliver"):
                out = self.device_deliver(messages)
            self.delivers.append((t0, time.perf_counter()))
            if self._keep:
                self.kept.append(tuple(transport.outputs))
            return out

        transport.deliver = timed_deliver

    def exchange(self) -> None:
        t0 = time.perf_counter()
        with span("bench.exchange"):
            self._exchange(self.fabric, self.sizes, wire_mode=self.wire_mode)
        self.exchanges.append((t0, time.perf_counter()))

    def warmup(self, p: dict) -> None:
        if T.payload_sizes(p) != self.sizes:
            raise core.BenchError("the mix's payload differs from the one "
                                  "this system was built for")
        for _ in range(WARM_EXCHANGES):
            self.exchange()
        self.exchanges.clear()
        self.delivers.clear()

    def run(self, p: dict, seed: int, seconds: float,
            tracer: TracedWindow) -> None:
        if p["kind"] != "exchange_closed":
            raise core.BenchError(f"an exchange cannot run {p['kind']}")
        rng = np.random.default_rng([seed % 2**64, 2])
        base = time.perf_counter()
        end = base + seconds
        tracer.at = end - tracer.length_s
        first = True
        while True:
            now = time.perf_counter()
            if tracer.due(now):
                tracer.start()
            if now >= end:
                break
            self._keep = len(self.kept) < SAMPLE_MAX and (
                first or rng.random() < SAMPLE_P)
            first = False
            self.exchange()
        self._keep = False
        tracer.stop()
        self.window = (base, self.exchanges[-1][1])

    # -- what the run measured ---------------------------------------------
    def end_to_end(self) -> Dict[str, float]:
        base, end = self.window
        rpcs = self.rpcs_per_exchange * len(self.exchanges)
        return {"rpcs_per_s": core.rate(rpcs, end - base)}

    def attempted(self) -> int:
        return self.rpcs_per_exchange * len(self.exchanges)

    def failed(self) -> int:
        return 0

    def notes(self) -> List[str]:
        return [f"samples: {len(self.exchanges)} exchanges of "
                f"{self.rpcs_per_exchange} RPCs; {len(self.kept)} kept for "
                f"the check"]

    def check(self, seed: int) -> Dict[str, dict]:
        """Each chip's shard of every kept exchange, byte for byte,
        against the replay of the schedule over the inputs."""
        wrong = 0
        for outs in self.kept:
            for got, want in zip(outs, self.expected):
                wrong += int(np.count_nonzero(np.asarray(got) != want))
        if not self.kept:       # nothing arrived to check: all of it wrong
            wrong = sum(int(e.size) for e in self.expected)
        return {"mismatched_bytes": {"value": wrong, "limit": 0}}
