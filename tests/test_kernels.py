"""Pallas kernel sweeps vs pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_support import given, settings, st

from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.payload_pack import pack, pack_ref, unpack
from repro.kernels.rwkv6_scan import rwkv6_ref, rwkv6_scan

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# flash attention: shape x dtype x flavor sweep
# ---------------------------------------------------------------------------

FA_CASES = [
    # B, Sq, H, KV, dh, causal, window, softcap
    (2, 128, 4, 2, 64, True, None, None),
    (1, 256, 4, 4, 64, True, 64, None),
    (2, 128, 8, 2, 32, True, None, 50.0),
    (1, 192, 4, 1, 128, True, None, None),     # MQA, non-pow2 seq
    (2, 64, 4, 2, 64, False, None, None),      # bidirectional (encoder)
    (1, 320, 6, 2, 64, True, 128, 30.0),       # window + softcap
]


@pytest.mark.parametrize("case", FA_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(case, dtype):
    B, Sq, H, KV, dh, causal, window, cap = case
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Sq, H, dh), dtype)
    k = jax.random.normal(ks[1], (B, Sq, KV, dh), dtype)
    v = jax.random.normal(ks[2], (B, Sq, KV, dh), dtype)
    out = flash_attention(q, k, v, causal, window, cap)
    ref = attention_ref(q, k, v, causal=causal, window=window, softcap=cap,
                        scale=1.0 / dh ** 0.5)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


def test_flash_attention_grad_matches_ref():
    B, S, H, KV, dh = 1, 64, 4, 2, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, dh))
    k = jax.random.normal(ks[1], (B, S, KV, dh))
    v = jax.random.normal(ks[2], (B, S, KV, dh))

    def f_kernel(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, None, None) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(attention_ref(q, k, v, causal=True, window=None,
                                     softcap=None,
                                     scale=1 / dh ** 0.5) ** 2)

    gk = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# rwkv6 scan
# ---------------------------------------------------------------------------

RWKV_CASES = [
    (4, 128, 64, 32, True), (2, 64, 32, 16, False),
    (3, 96, 64, 32, True), (1, 250, 64, 64, True),
]


@pytest.mark.parametrize("case", RWKV_CASES)
def test_rwkv6_scan_sweep(case):
    BH, S, hs, chunk, with_u = case
    ks = jax.random.split(KEY, 6)
    r = jax.random.normal(ks[0], (BH, S, hs))
    k = jax.random.normal(ks[1], (BH, S, hs)) * 0.5
    v = jax.random.normal(ks[2], (BH, S, hs))
    lw = -jnp.exp(jax.random.normal(ks[3], (BH, S, hs)) - 1.0)
    s0 = jax.random.normal(ks[4], (BH, hs, hs)) * 0.1
    u = jax.random.normal(ks[5], (BH, hs)) * 0.5 if with_u else None
    y, sT = rwkv6_scan(r, k, v, lw, s0, u, chunk=chunk)
    yr, sTr = rwkv6_ref(r, k, v, lw, s0, u)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(sT), np.asarray(sTr),
                               atol=1e-4, rtol=1e-4)


def test_rwkv6_strong_decay_stability():
    """Strong decays (log_w << 0) must not overflow the chunked form."""
    BH, S, hs = 2, 64, 32
    r = jnp.ones((BH, S, hs))
    k = jnp.ones((BH, S, hs))
    v = jnp.ones((BH, S, hs))
    lw = jnp.full((BH, S, hs), -30.0)  # near-total decay per step
    s0 = jnp.zeros((BH, hs, hs))
    y, sT = rwkv6_scan(r, k, v, lw, s0, None, chunk=16)
    assert bool(jnp.all(jnp.isfinite(y))) and bool(jnp.all(jnp.isfinite(sT)))


# ---------------------------------------------------------------------------
# payload pack
# ---------------------------------------------------------------------------

@given(sizes=st.lists(st.integers(1, 4096), min_size=1, max_size=8),
       seed=st.integers(0, 999))
@settings(max_examples=25, deadline=None)
def test_pack_roundtrip_property(sizes, seed):
    rng = np.random.default_rng(seed)
    bufs = [jnp.asarray(rng.integers(0, 255, s, dtype=np.uint8))
            for s in sizes]
    packed, meta = pack(bufs)
    outs = unpack(packed, meta)
    for a, b in zip(bufs, outs):
        assert bool(jnp.array_equal(a, b))


def test_pack_matches_ref_when_aligned():
    rng = np.random.default_rng(0)
    bufs = [jnp.asarray(rng.integers(0, 255, s, dtype=np.uint8))
            for s in (128, 512, 1024, 128)]
    packed, _ = pack(bufs)
    assert bool(jnp.array_equal(packed, pack_ref(bufs)))


@pytest.mark.parametrize("rows", [
    (1, 70, 33, 100, 2),      # header row, then buffers across seams
    (1, 31, 96, 7, 300),      # buffers inside, across and over blocks
    (3, 200, 1, 1, 97),
    (32, 32, 32),             # every buffer tile-aligned
])
def test_pack_kernel_multi_block_matches_concat(rows):
    """Small blocks put buffers across block seams and give long
    buffers interior blocks: the pipelined paths that real payloads
    take, at interpret-mode sizes."""
    from repro.kernels.payload_pack.payload_pack import (pack_kernel,
                                                         unpack_kernel)
    rng = np.random.default_rng(sum(rows))
    bufs = [rng.integers(0, 255, (r, 128), dtype=np.uint8) for r in rows]
    packed = pack_kernel([jnp.asarray(b) for b in bufs], block=32,
                         interpret=True)
    assert np.array_equal(np.asarray(packed), np.concatenate(bufs))
    outs = unpack_kernel(packed, rows, block=32, interpret=True)
    for a, b in zip(bufs, outs):
        assert np.array_equal(a, np.asarray(b))
