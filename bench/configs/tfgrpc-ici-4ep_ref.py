"""Plain reference for ``tfgrpc-ici-4ep``: what each endpoint holds
after one fully connected exchange, in numpy, from the inputs alone.
It imports nothing of the program.

The exchange sends every endpoint's payload to every other in n - 1
rounds; round r moves endpoint i's buffer to endpoint (i + r) % n, and
each round carries forward what the one before delivered. A round is
``ppermute`` semantics: row s goes to row d for every (s, d) pair, and a
row that receives nothing becomes zeros.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np


def shift_rounds(n: int) -> List[List[Tuple[int, int]]]:
    return [[(i, (i + r) % n) for i in range(n)] for r in range(1, n)]


def route(rows: np.ndarray, rounds: Sequence[Sequence[Tuple[int, int]]]
          ) -> np.ndarray:
    for perm in rounds:
        nxt = np.zeros_like(rows)
        for s, d in perm:
            nxt[d] = rows[s]
        rows = nxt
    return rows


def fully_connected(rows: Sequence[np.ndarray], n: int) -> List[np.ndarray]:
    """``rows``: one (n, bytes) array per iovec buffer, row i on chip i."""
    return [route(b, shift_rounds(n)) for b in rows]
