"""Communication channels: the TPU/JAX realization of the paper's gRPC
primitives (DESIGN.md §2).

 - P2P echo / one-way send  -> ``jax.lax.ppermute`` on a 1-D device axis
 - PS pull (variable fetch) -> multicast ppermute PS -> every worker
 - PS push (tensor update)  -> worker -> every PS (multicast ppermute)

Payloads are lists of uint8 buffers (iovec analogue), shape (N, size)
sharded over the ``net`` axis so each device owns one row.
Non-serialized mode issues one collective per buffer (scatter/gather
semantics); serialized mode packs all buffers into one contiguous
transfer first (repro.core.serialization).

These channels run for real on host devices (benchmarks force
``--xla_force_host_platform_device_count``) — wall-clock numbers are
host-platform, the *relative* trends + the netmodel give the projection
(EXPERIMENTS.md §Comm).
"""
from __future__ import annotations

import functools
from typing import Callable, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import serialization as ser
from repro.core.payload import PayloadSpec, materialize

AXIS = "net"


def make_net_mesh(n_devices: int = 0) -> Mesh:
    devs = jax.devices()
    n = n_devices or len(devs)
    assert n <= len(devs), (n, len(devs))
    return jax.make_mesh((n,), (AXIS,), devices=devs[:n])


def host_payload(spec: PayloadSpec, n: int, *, seed: int = 0
                 ) -> List[np.ndarray]:
    """One payload row per endpoint: list of (n, size) uint8. Row i is
    drawn from ``seed + i``, so the bytes a device receives name their
    sender."""
    rows = [materialize(spec, seed=seed + i, tpu_align=True)
            for i in range(n)]
    return [np.stack([r[j] for r in rows]) for j in range(spec.n_buffers)]


def device_payload(mesh: Mesh, spec: PayloadSpec, *, seed: int = 0
                   ) -> List[jax.Array]:
    """:func:`host_payload` placed one row per device of the mesh."""
    sharding = NamedSharding(mesh, P(AXIS))
    return [jax.device_put(b, sharding)
            for b in host_payload(spec, mesh.shape[AXIS], seed=seed)]


# ---------------------------------------------------------------------------
# P2P
# ---------------------------------------------------------------------------

def _shmap(mesh, fn, n_in):
    return jax.shard_map(fn, mesh=mesh, in_specs=tuple([P(AXIS)] * n_in),
                         out_specs=P(AXIS), check_vma=False)


def permute_rounds_fn(mesh: Mesh, n_buffers: int,
                      rounds: Sequence[Sequence[Tuple[int, int]]],
                      serialized: bool = False) -> Callable:
    """Run a sequence of ppermute rounds over one payload: the common
    lowering every channel (and the rpc collective transport) compiles
    to. One collective per buffer per round (non-serialized) or
    pack -> one collective per round -> unpack (serialized)."""
    rounds = [list(r) for r in rounds]

    def go(*bufs):
        if serialized:
            packed, meta = ser.pack(bufs)
            for perm in rounds:
                packed = jax.lax.ppermute(packed, AXIS, perm)
            return tuple(ser.unpack(packed, meta))
        out = []
        for b in bufs:
            for perm in rounds:
                b = jax.lax.ppermute(b, AXIS, perm)
            out.append(b)
        return tuple(out)

    return jax.jit(_shmap(mesh, go, n_buffers))


def p2p_echo_fn(mesh: Mesh, n_buffers: int, src: int = 0, dst: int = 1,
                serialized: bool = False) -> Callable:
    """Round trip src -> dst -> src."""
    return permute_rounds_fn(mesh, n_buffers, [[(src, dst)], [(dst, src)]],
                             serialized=serialized)


def p2p_send_fn(mesh: Mesh, n_buffers: int, src: int = 0, dst: int = 1,
                serialized: bool = False) -> Callable:
    """One-way payload + 64-byte ack back (bandwidth benchmark)."""
    fwd, bwd = [(src, dst)], [(dst, src)]

    def send(*bufs):
        if serialized:
            packed, meta = ser.pack(bufs)
            packed = jax.lax.ppermute(packed, AXIS, fwd)
            ack = jax.lax.ppermute(packed[..., :64], AXIS, bwd)
            return (packed, ack)
        out = [jax.lax.ppermute(b, AXIS, fwd) for b in bufs]
        ack = jax.lax.ppermute(out[0][..., :64], AXIS, bwd)
        return tuple(out) + (ack,)

    return jax.jit(_shmap(mesh, send, n_buffers))


# ---------------------------------------------------------------------------
# Parameter-server round
# ---------------------------------------------------------------------------

def bipartite_schedule(srcs: Sequence[int], dsts: Sequence[int]
                       ) -> List[List[Tuple[int, int]]]:
    """Edge-color K_{|srcs|,|dsts|}: a minimal sequence of ppermute rounds
    (each with unique sources AND destinations) covering every (src, dst)
    pair exactly once. Rounds = max(|srcs|, |dsts|)."""
    m, n = len(srcs), len(dsts)
    rounds = []
    if m <= n:
        for r in range(n):
            rounds.append([(srcs[i], dsts[(i + r) % n]) for i in range(m)])
    else:
        for r in range(m):
            rounds.append([(srcs[(j + r) % m], dsts[j]) for j in range(n)])
    return rounds


def ps_round_fn(mesh: Mesh, n_buffers: int, n_ps: int, n_workers: int,
                serialized: bool = False) -> Callable:
    """One PS round on devices [0..n_ps) = PS, [n_ps..n_ps+n_workers) =
    workers.

    pull: every PS sends its variable shard to every worker (the
          rendezvous'd tensor-fetch response), n_ps x n_workers messages
    push: every worker sends its update to every PS, n_workers x n_ps
          messages

    ppermute requires unique sources and destinations per collective, so
    the all-pairs exchange is scheduled as a round-robin edge coloring —
    which also matches the per-NIC serialization the netmodel assumes.
    """
    ps_ids = list(range(n_ps))
    w_ids = list(range(n_ps, n_ps + n_workers))
    assert n_ps + n_workers <= mesh.shape[AXIS]
    rounds = bipartite_schedule(ps_ids, w_ids) \
        + bipartite_schedule(w_ids, ps_ids)
    return permute_rounds_fn(mesh, n_buffers, rounds,
                             serialized=serialized)


def rpcs_per_round(n_ps: int, n_workers: int) -> int:
    """The paper counts one RPC per worker x PS interaction per round."""
    return n_ps * n_workers


# ---------------------------------------------------------------------------
# Fully-connected exchange (paper §2 process architecture: every worker
# talks to every other worker)
# ---------------------------------------------------------------------------

def all_to_all_schedule(n: int) -> List[List[Tuple[int, int]]]:
    """Round-robin schedule of the complete digraph K_n: n-1 rounds of
    shift-by-r permutations, each with unique sources and destinations,
    covering every ordered (src, dst) pair with src != dst exactly
    once."""
    assert n >= 2, n
    return [[(i, (i + r) % n) for i in range(n)] for r in range(1, n)]


def fully_connected_fn(mesh: Mesh, n_buffers: int, n_workers: int,
                       serialized: bool = False) -> Callable:
    """One full exchange: every endpoint sends the payload to every
    other endpoint (n_workers * (n_workers - 1) RPCs)."""
    return permute_rounds_fn(mesh, n_buffers,
                             all_to_all_schedule(n_workers),
                             serialized=serialized)


def fc_rpcs_per_round(n_workers: int) -> int:
    return n_workers * (n_workers - 1)


# ---------------------------------------------------------------------------
# Ring / incast streaming families (the rpc fabric's two stream-shaped
# traffic patterns; the rpc collective transport recovers these exact
# rounds from its greedy edge coloring)
# ---------------------------------------------------------------------------

def ring_schedule(n: int, n_chunks: int = 1
                  ) -> List[List[Tuple[int, int]]]:
    """Rotation schedule for a chunked ring stream: ``n_chunks`` rounds
    of the successor permutation i -> (i+1) % n. Every round is a full
    permutation (unique sources AND destinations), so a ring moves one
    chunk per worker per round regardless of n — including n == 2,
    where the round degenerates to the swap (0,1),(1,0)."""
    assert n >= 2, n
    assert n_chunks >= 1, n_chunks
    perm = [(i, (i + 1) % n) for i in range(n)]
    return [list(perm) for _ in range(n_chunks)]


def incast_schedule(n_workers: int, *, server: int = 0,
                    n_chunks: int = 1) -> List[List[Tuple[int, int]]]:
    """Serialized incast rounds: workers 1..n_workers each stream
    ``n_chunks`` chunks into one server endpoint. A single destination
    admits one message per round (the ppermute / single-port
    constraint), so the schedule is n_workers * n_chunks singleton
    rounds, chunk-major. ``n_workers == 1`` degenerates to a plain
    chunked P2P send."""
    assert n_workers >= 1, n_workers
    assert n_chunks >= 1, n_chunks
    workers = [w for w in range(n_workers + 1) if w != server][:n_workers]
    return [[(w, server)] for _ in range(n_chunks) for w in workers]


def ring_fn(mesh: Mesh, n_buffers: int, n_workers: int, *,
            n_chunks: int = 1, serialized: bool = False) -> Callable:
    """One chunked ring pass: every worker streams to its successor."""
    return permute_rounds_fn(mesh, n_buffers,
                             ring_schedule(n_workers, n_chunks),
                             serialized=serialized)


def ring_rpcs_per_round(n_workers: int, n_chunks: int = 1) -> int:
    return n_workers * n_chunks


def incast_rpcs_per_round(n_workers: int, n_chunks: int = 1) -> int:
    return n_workers * n_chunks


# ---------------------------------------------------------------------------
# Collective channels (the SPMD-native PS: FSDP pull/push, DESIGN §3.1)
# ---------------------------------------------------------------------------

def fsdp_pull_push_fn(mesh: Mesh, n_buffers: int) -> Callable:
    """all_gather (pull the full variable from its PS shards) followed by
    psum_scatter (push: reduce updates back onto the shards). This is the
    exact primitive pair GSPMD emits for our fsdp/ps_mode training; the
    suite measures it with model-free payloads."""

    def step(*bufs):
        outs = []
        for b in bufs:
            full = jax.lax.all_gather(b, AXIS, axis=0, tiled=True)
            upd = full.astype(jnp.float32) * 1.000001
            outs.append(jax.lax.psum_scatter(upd, AXIS, scatter_dimension=0,
                                             tiled=True).astype(b.dtype))
        return tuple(outs)

    return jax.jit(_shmap(mesh, step, n_buffers))
