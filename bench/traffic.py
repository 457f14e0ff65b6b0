"""The one generator every traffic mix goes through.

A mix is a JSON file of parameters under ``bench/traffic``. Its
``kind`` names the loop that drives it:

  serve_open       independent users: Poisson arrivals at ``rate_per_s``,
                   sent on schedule whether or not earlier ones finished
  serve_closed     ``clients`` clients, each sending its next request
                   when its last one completes
  exchange_closed  back-to-back exchanges of one payload

Sizes and arrivals come from the mix's own ``schedule_seed``, so every
run's ``--seed`` gets the same sizes and arrivals in the same order; the
run's seed draws the prompt tokens (and the configuration's weights).
Runs of different seeds then do the same work. With a few tens of
requests in a window, any other order moves a burst into or out of it:
rotating the order by the seed changed ``itl_p95_ms`` threefold between
seeds of one set.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Tuple

import numpy as np

#: requests drawn per cycle of a closed loop; request k has size k % CYCLE
CYCLE = 256


@dataclass(frozen=True)
class ServeRequest:
    index: int
    due_s: Optional[float]   # from window open; None in a closed loop
    prompt_len: int
    output_len: int


def _draw_sizes(p: dict, rng: np.random.Generator, n: int
                ) -> List[Tuple[int, int]]:
    lengths = [int(b[0]) for b in p["prompt_buckets"]]
    shares = np.array([float(b[1]) for b in p["prompt_buckets"]])
    prompts = rng.choice(lengths, size=n, p=shares / shares.sum())
    out = p["output"]
    if "fixed" in out:
        outputs = np.full(n, int(out["fixed"]))
    else:
        lo, hi = out["uniform"]
        outputs = rng.integers(int(lo), int(hi) + 1, size=n)
    return [(int(a), int(b)) for a, b in zip(prompts, outputs)]


def serve_schedule(p: dict, seconds: float) -> List[ServeRequest]:
    """Every request an open loop sends, in due order: arrivals from
    ``lead_s`` before the window opens (so it opens on a loaded
    system) until it closes."""
    if p["kind"] != "serve_open":
        raise ValueError(f"serve_schedule needs serve_open, got {p['kind']}")
    rng = np.random.default_rng(int(p["schedule_seed"]))
    lead, mean_gap = float(p["lead_s"]), 1.0 / float(p["rate_per_s"])
    gaps: List[float] = []
    total = 0.0
    while total < lead + seconds:
        gaps.append(float(rng.exponential(mean_gap)))
        total += gaps[-1]
    out, t = [], -lead
    for i, (gap, (plen, olen)) in enumerate(
            zip(gaps, _draw_sizes(p, rng, len(gaps)))):
        t += gap
        if t >= seconds:
            break
        out.append(ServeRequest(i, t, plen, olen))
    return out


def closed_request(p: dict, index: int) -> ServeRequest:
    """Request ``index`` of a closed loop (all clients share one
    sequence)."""
    if p["kind"] != "serve_closed":
        raise ValueError(f"closed_request needs serve_closed, got {p['kind']}")
    sizes = _draw_sizes(p, np.random.default_rng(int(p["schedule_seed"])),
                        CYCLE)
    plen, olen = sizes[index % CYCLE]
    return ServeRequest(index, None, plen, olen)


def prompt_lengths(p: dict) -> List[int]:
    """Every prompt length a mix can send: the shapes to warm up."""
    return sorted({int(b[0]) for b in p["prompt_buckets"]})


def max_output(p: dict) -> int:
    out = p["output"]
    return int(out["fixed"]) if "fixed" in out else int(out["uniform"][1])


def prompt_tokens(seed: int, index: int, length: int, vocab: int
                  ) -> np.ndarray:
    """Request ``index``'s prompt: (1, length) int32 ids in [0, vocab)."""
    rng = np.random.default_rng([seed % 2**64, index % 2**64])
    return rng.integers(0, vocab, size=(1, length), dtype=np.int32)


def payload_sizes(p: dict) -> List[int]:
    """An exchange mix's iovec sizes, in the order they are sent."""
    if p["kind"] != "exchange_closed":
        raise ValueError(f"payload_sizes needs exchange_closed, "
                         f"got {p['kind']}")
    return [int(s) for s in p["iovec_bytes"]]
