"""Builds ``tfgrpc-ici-4ep``: four endpoints of the RPC fabric, one per
chip, exchanging over ICI through the collective transport. The plain
reference beside this file (``tfgrpc-ici-4ep_ref.py``) replays the
schedule over the inputs."""
from __future__ import annotations

from pathlib import Path

from bench import core
from bench import traffic as T
from bench.exchange import ExchangeSystem

REFERENCE = Path(__file__).with_name("tfgrpc-ici-4ep_ref.py")


def build(c: dict, traffic: dict, seed: int) -> ExchangeSystem:
    ref = core.load_module(REFERENCE, "bench_ref_tfgrpc_ici_4ep")
    n = int(c["endpoints"])
    wire_mode = traffic.get("wire_mode", c["wire_mode"])
    if traffic["pattern"] != "fully_connected":
        raise core.BenchError(f"{c['name']} runs the fully connected "
                              f"exchange, not {traffic['pattern']}")
    return ExchangeSystem(
        n=n, sizes=T.payload_sizes(traffic), align=int(c["align_bytes"]),
        wire_mode=wire_mode, seed=seed,
        expected=lambda rows: ref.fully_connected(rows, n))
