"""Collective transport: lowers RPC flights onto the ``ppermute``
schedules of ``repro.core.channels`` and measures them on real devices.

Endpoint *i* is device *i* on the 1-D ``net`` mesh. A flight is edge-
colored into rounds (unique src/dst — precisely ppermute's contract) and
compiled to one jitted program per distinct round pattern: serialized
frames move as one packed collective per round, non-serialized frames as
one collective per iovec buffer. Frames must be homogeneous across the
flight (one PayloadSpec), which is what the benchmark families generate
— the datapath is SPMD, so per-endpoint python handlers don't run here
(service semantics are exchange/echo, as in the paper's benchmarks).
"""
from __future__ import annotations

import time
from typing import Callable, Dict, Sequence, Tuple

import jax

from repro.core import channels as ch
from repro.core.payload import PayloadSpec
from repro.rpc.transport import (Delivery, Message, Transport,
                                 schedule_rounds)


class CollectiveTransport(Transport):

    dispatches = False

    def __init__(self, mesh, spec: PayloadSpec, *, serialized: bool = False,
                 n_endpoints: int = 0, seed: int = 0):
        self.mesh = mesh
        n_dev = mesh.shape[ch.AXIS]
        self.n_endpoints = n_endpoints or n_dev
        assert self.n_endpoints <= n_dev, (self.n_endpoints, n_dev)
        self.spec = spec
        self.serialized = serialized
        self.bufs = ch.device_payload(mesh, spec, seed=seed)
        #: the device arrays the last flight's program returned
        self.outputs = None
        self._fns: Dict[Tuple[Tuple[Tuple[int, int], ...], ...],
                        Callable] = {}

    def _fn(self, perms: Tuple[Tuple[Tuple[int, int], ...], ...]):
        if perms not in self._fns:
            self._fns[perms] = ch.permute_rounds_fn(
                self.mesh, self.spec.n_buffers,
                [list(p) for p in perms], serialized=self.serialized)
        return self._fns[perms]

    def deliver(self, messages: Sequence[Message]) -> Delivery:
        for m in messages:
            assert m.frame.sizes == self.spec.sizes, \
                "collective transport needs homogeneous frames (one spec)"
            assert m.src < self.n_endpoints and m.dst < self.n_endpoints
        rounds = schedule_rounds(messages)
        perms = tuple(tuple((m.src, m.dst) for m in rnd) for rnd in rounds)
        fn = self._fn(perms)
        t0 = time.perf_counter()
        self.outputs = jax.block_until_ready(fn(*self.bufs))
        elapsed = time.perf_counter() - t0
        return Delivery(list(messages), elapsed, len(rounds),
                        modeled=False)
