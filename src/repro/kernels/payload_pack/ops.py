"""jit'd wrappers with lane-alignment padding + interpret fallback."""
from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.payload_pack.payload_pack import (LANE, pack_kernel,
                                                     unpack_kernel)


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def pack(bufs: Sequence[jax.Array], *, interpret=None
         ) -> Tuple[jax.Array, Tuple[int, ...]]:
    """Returns (packed uint8, original sizes). Pads each buffer to the
    128-byte lane width; the metadata keeps true sizes for unpack."""
    interpret = _interpret_default() if interpret is None else interpret
    sizes = tuple(int(b.shape[-1]) for b in bufs)
    rows = [jnp.pad(b.reshape(-1), (0, (-b.shape[-1]) % LANE))
            .reshape(-1, LANE) for b in bufs]
    return pack_kernel(rows, interpret=interpret).reshape(-1), sizes


def unpack(packed: jax.Array, sizes: Sequence[int], *, interpret=None
           ) -> List[jax.Array]:
    interpret = _interpret_default() if interpret is None else interpret
    rows = [-(-s // LANE) for s in sizes]
    outs = unpack_kernel(packed.reshape(-1, LANE), rows,
                         interpret=interpret)
    return [o.reshape(-1)[:s] for o, s in zip(outs, sizes)]
