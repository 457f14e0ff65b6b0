"""Payload pack/unpack for TPU (Pallas) — the paper's "serialized mode".

Coalesces N iovec buffers into one contiguous transfer buffer and
splits it back. On gRPC this is protobuf serialization (a host copy); on
TPU it is the HBM copy you pay to turn N small collectives into one —
the trade the serialized/non-serialized benchmark modes measure.

Layout: every buffer is a whole number of 128-byte rows, viewed as a
``(rows, 128)`` uint8 array, and buffer j starts at row ``offs[j]`` of
the packed array. The chip lays uint8 data out in tiles of 32 rows
(``T(8,128)(4,1)``: four rows packed into each 32-bit sublane) and
copies between HBM and VMEM in whole tiles, so a buffer that starts
mid-tile cannot be moved with a direct DMA. Instead both kernels walk their output in blocks of
``block`` rows, which the pipeline streams between HBM and VMEM, and
shift rows inside VMEM by a static amount: the offset of output block b
within buffer j is ``b * block - offs[j]``, whose residue modulo
``block`` is known when the kernel is traced. Each output block reads
two consecutive input blocks (A, B) and takes rows ``[r, block)`` of A
then ``[0, r)`` of B. Buffer offsets and sizes are static, so every
slice in the kernel is static; only the block index is dynamic. VMEM
holds a few blocks per buffer, never a whole buffer.
"""
from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128
BLOCK_ROWS = 1024        # 128 KiB of uint8 per pipelined block
_VMEM_BUDGET = 8 << 20   # bytes of double-buffered pack inputs


def _offsets(rows: Sequence[int]) -> List[int]:
    offs, acc = [], 0
    for r in rows:
        offs.append(acc)
        acc += r
    return offs + [acc]


def _block_rows(total: int, n_inputs: int, block: int) -> int:
    """Rows per block: ``block`` (a multiple of the 32-row uint8 tile),
    shrunk so that two double-buffered blocks per input stay in the
    VMEM budget, or the whole array when it is smaller."""
    cap = _VMEM_BUDGET // (4 * max(n_inputs, 1) * LANE)
    block = max(32, min(block, cap) // 32 * 32)
    return total if total <= block else block


def _copy_rows(o_ref, a_ref, b_ref, *, lo: int, hi: int, src: int,
               a_start: int, b_start: int, block_in: int) -> None:
    """o_ref[lo:hi] = rows [src, src + hi - lo) of the input, where the
    loaded input blocks A and B begin at input rows ``a_start`` and
    ``b_start``. All bounds are static."""
    while lo < hi:
        for ref, start in ((a_ref, a_start), (b_ref, b_start)):
            if start <= src < start + block_in:
                n = min(hi - lo, start + block_in - src)
                o_ref[lo:lo + n, :] = ref[src - start:src - start + n, :]
                break
        else:
            raise AssertionError((src, a_start, b_start, block_in))
        lo, src = lo + n, src + n


def _pack_kernel(*refs, rows: Tuple[int, ...], block: int,
                 blocks_in: Tuple[int, ...]):
    """refs = (A_0, B_0, A_1, B_1, ..., o_ref). Output block b gets the
    rows of every buffer that overlaps it."""
    o_ref = refs[-1]
    b = pl.program_id(0)
    offs = _offsets(rows)
    for j, n in enumerate(rows):
        a_ref, b_ref = refs[2 * j], refs[2 * j + 1]
        bj = blocks_in[j]
        lo_blk, hi_blk = offs[j] // block, (offs[j] + n - 1) // block

        def at_block(bs: int, j=j, n=n, a_ref=a_ref, b_ref=b_ref,
                     bj=bj) -> None:
            # first or last block of buffer j: everything static
            lo = max(offs[j] - bs * block, 0)
            hi = min(offs[j] + n - bs * block, block)
            start = bs * block - offs[j]
            _copy_rows(o_ref, a_ref, b_ref, lo=lo, hi=hi, src=start + lo,
                       a_start=_in_block(start, bj, n, 0) * bj,
                       b_start=_in_block(start, bj, n, 1) * bj,
                       block_in=bj)

        for bs in sorted({lo_blk, hi_blk}):
            pl.when(b == bs)(functools.partial(at_block, bs))
        if hi_blk - lo_blk > 1:
            # interior blocks: a whole output block, shifted by the
            # static residue r; the buffer spans > 2 blocks so bj == block
            r = (-offs[j]) % block

            @pl.when((b > lo_blk) & (b < hi_blk))
            def _interior(a_ref=a_ref, b_ref=b_ref, r=r):
                o_ref[0:block - r, :] = a_ref[r:block, :]
                if r:
                    o_ref[block - r:block, :] = b_ref[0:r, :]


def _in_block(start, block_in: int, n: int, which: int):
    """Index of input block A (``which`` 0) or B (1) of an ``n``-row
    buffer, loaded when the output block begins at input row ``start``
    (negative before the buffer): blocks ``start // block_in`` and the
    next, clamped to the buffer. Python ints in the kernel's static
    cases, traced in the index map."""
    last = -(-n // block_in) - 1
    q = start // block_in + which
    if isinstance(q, int):
        return min(max(q, 0), last)
    return jnp.clip(q, 0, last)


def pack_kernel(bufs: Sequence[jax.Array], *, block: int = BLOCK_ROWS,
                interpret: bool = False) -> jax.Array:
    """bufs: list of (rows_i, 128) uint8. Returns (sum rows, 128) uint8,
    buffer i at rows [sum(rows[:i]), sum(rows[:i+1]))."""
    rows = tuple(int(b.shape[0]) for b in bufs)
    for b in bufs:
        assert b.ndim == 2 and b.shape[1] == LANE, b.shape
    total = sum(rows)
    block = _block_rows(total, len(bufs), block)
    blocks_in = tuple(min(block, n) for n in rows)
    offs = _offsets(rows)
    in_specs, operands = [], []
    for j, (buf, n) in enumerate(zip(bufs, rows)):
        bj = blocks_in[j]
        for which in (0, 1):
            def index(b, j=j, n=n, bj=bj, which=which):
                return _in_block(b * block - offs[j], bj, n, which), 0
            in_specs.append(pl.BlockSpec((bj, LANE), index))
            operands.append(buf)
    kernel = functools.partial(_pack_kernel, rows=rows, block=block,
                               blocks_in=blocks_in)
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(total, block),),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block, LANE), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((total, LANE), jnp.uint8),
        interpret=interpret,
    )(*operands)


def _unpack_kernel(a_ref, b_ref, o_ref, *, s: int, block: int,
                   out_rows: int, first: Tuple[int, int]):
    if out_rows < block:
        # the whole buffer is one output block: rows [s', s' + n) of
        # the packed blocks `first`, all static
        start = first[0] * block
        _copy_rows(o_ref, a_ref, b_ref, lo=0, hi=out_rows, src=s,
                   a_start=start, b_start=first[1] * block,
                   block_in=block)
        return
    o_ref[0:block - s, :] = a_ref[s:block, :]
    if s:
        o_ref[block - s:block, :] = b_ref[0:s, :]


def _unpack_one(packed: jax.Array, off: int, n: int, block: int,
                interpret: bool) -> jax.Array:
    total = packed.shape[0]
    last = -(-total // block) - 1
    p, s = divmod(off, block)
    out_block = min(block, n)
    kernel = functools.partial(
        _unpack_kernel, s=off if n < block else s, block=block,
        out_rows=n if n < block else block,
        first=(min(p, last), min(p + 1, last)))
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(n, out_block),),
        in_specs=[
            pl.BlockSpec((block, LANE),
                         lambda k: (jnp.minimum(k + p, last), 0)),
            pl.BlockSpec((block, LANE),
                         lambda k: (jnp.minimum(k + p + 1, last), 0)),
        ],
        out_specs=pl.BlockSpec((out_block, LANE), lambda k: (k, 0)),
        out_shape=jax.ShapeDtypeStruct((n, LANE), jnp.uint8),
        interpret=interpret,
    )(packed, packed)


def unpack_kernel(packed: jax.Array, rows: Sequence[int], *,
                  block: int = BLOCK_ROWS,
                  interpret: bool = False) -> List[jax.Array]:
    """packed: (sum rows, 128) uint8 -> list of (rows_i, 128) uint8, one
    pipelined copy per buffer."""
    rows = tuple(int(r) for r in rows)
    total = int(packed.shape[0])
    assert sum(rows) == total, (rows, packed.shape)
    block = _block_rows(total, 1, block)
    offs = _offsets(rows)
    return [_unpack_one(packed, offs[j], n, block, interpret)
            for j, n in enumerate(rows)]
