"""Attention: GQA / qk-norm / sliding-window / logit-softcap, with a
q-blocked memory-bounded path for long sequences and a decode path that
reads a (possibly sequence-sharded) KV cache.

The jnp implementation here is the *compile/dry-run* path (and the
oracle for the Pallas flash kernel in ``repro.kernels.flash_attention``);
on real TPU the kernel replaces the inner block computation.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import AttentionConfig, ModelConfig
from repro.models.layers import apply_rope, dense_init, rms_norm_simple

NEG_INF = -1e30

# Cost-analysis hook (launch/roofline.py): scans under-count in XLA cost
# analysis, so segment lowerings unroll the q-block loop by raising the
# effective block size to the full sequence.
FORCE_UNROLL_Q = False


def init_attention(key, cfg: ModelConfig, att: AttentionConfig,
                   dtype) -> dict:
    d = cfg.d_model
    hq, hkv = att.n_heads * att.d_head, att.n_kv_heads * att.d_head
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, hq, dtype),
        "wk": dense_init(ks[1], d, hkv, dtype),
        "wv": dense_init(ks[2], d, hkv, dtype),
        "wo": dense_init(ks[3], hq, d, dtype),
    }
    if att.qkv_bias:
        p["bq"] = jnp.zeros((hq,), dtype)
        p["bk"] = jnp.zeros((hkv,), dtype)
        p["bv"] = jnp.zeros((hkv,), dtype)
    if att.qk_norm:
        p["q_norm"] = jnp.ones((att.d_head,), dtype)
        p["k_norm"] = jnp.ones((att.d_head,), dtype)
    return p


class KVCache(NamedTuple):
    # (B, n_kv * d_head, S_cache): the slots minor-most, as the decode
    # step's attention reads them, so the default layout is the one its
    # loop works in, and heads folded so a TPU tiles it unpadded (n_kv 20
    # would pad to 24 as a dim of its own); stacked over periods as a
    # decode state
    k: jax.Array
    v: jax.Array
    # the *global* write cursor (tokens seen so far), traced scalar
    index: jax.Array


def _qkv(p: dict, att: AttentionConfig, x: jax.Array, positions: jax.Array):
    """x: (B,S,d) -> q (B,S,H,dh), k/v (B,S,KV,dh); RoPE applied."""
    B, S, _ = x.shape
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if att.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, att.n_heads, att.d_head)
    k = k.reshape(B, S, att.n_kv_heads, att.d_head)
    v = v.reshape(B, S, att.n_kv_heads, att.d_head)
    if att.qk_norm:
        q = rms_norm_simple(q, p["q_norm"])
        k = rms_norm_simple(k, p["k_norm"])
    if att.use_rope:
        q = apply_rope(q, positions, att.rope_theta)
        k = apply_rope(k, positions, att.rope_theta)
    return q, k, v


def repeat_kv(h: jax.Array, n_rep: int) -> jax.Array:
    """(B,S,KV,dh) -> (B,S,KV*n_rep,dh); broadcast, not materialized copy."""
    if n_rep == 1:
        return h
    B, S, KV, dh = h.shape
    h = jnp.broadcast_to(h[:, :, :, None, :], (B, S, KV, n_rep, dh))
    return h.reshape(B, S, KV * n_rep, dh)


def _mask_bias(q_pos: jax.Array, kv_pos: jax.Array, *, causal: bool,
               window: Optional[int], kv_valid: Optional[jax.Array]):
    """Additive mask (…,Sq,Skv) in fp32. q_pos (Sq,), kv_pos (Skv,)."""
    ok = jnp.ones((q_pos.shape[0], kv_pos.shape[0]), bool)
    if causal:
        ok &= q_pos[:, None] >= kv_pos[None, :]
    if window is not None:
        ok &= (q_pos[:, None] - kv_pos[None, :]) < window
    bias = jnp.where(ok, 0.0, NEG_INF)
    if kv_valid is not None:  # (B,Skv) -> (B,1,Sq,Skv) broadcastable
        bias = bias[None, :, :] + jnp.where(kv_valid, 0.0, NEG_INF)[:, None, :]
    return bias


def sdpa(q, k, v, bias, *, softcap_val: Optional[float]) -> jax.Array:
    """q (B,Sq,H,dh), k/v (B,Skv,H,dh), bias broadcastable to (B,H,Sq,Skv)."""
    dh = q.shape[-1]
    scale = 1.0 / jnp.sqrt(dh).astype(jnp.float32)
    scores = jnp.einsum("bqhd,bshd->bhqs", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if softcap_val is not None:
        scores = jnp.tanh(scores / softcap_val) * softcap_val
    scores = scores + bias
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqs,bshd->bqhd", probs.astype(v.dtype), v)
    return out


def attention_forward(p: dict, att: AttentionConfig, x: jax.Array,
                      positions: jax.Array, *, window: Optional[int],
                      causal: bool, block_q: int = 1024,
                      return_kv: bool = False):
    """Full-sequence (train / prefill) attention, q-blocked when long."""
    B, S, d = x.shape
    if FORCE_UNROLL_Q:
        block_q = S
    q, k, v = _qkv(p, att, x, positions)
    n_rep = att.n_heads // att.n_kv_heads
    kf, vf = repeat_kv(k, n_rep), repeat_kv(v, n_rep)
    kv_pos = positions

    if S <= block_q:
        bias = _mask_bias(positions, kv_pos, causal=causal, window=window,
                          kv_valid=None)
        out = sdpa(q, kf, vf, bias[None, None], softcap_val=att.logit_softcap)
    else:
        assert S % block_q == 0, (S, block_q)
        nb = S // block_q
        qb = q.reshape(B, nb, block_q, att.n_heads, att.d_head)
        qb = jnp.moveaxis(qb, 1, 0)              # (nb, B, bq, H, dh)
        pb = positions.reshape(nb, block_q)

        def body(_, blk):
            qi, pi = blk
            bias = _mask_bias(pi, kv_pos, causal=causal, window=window,
                              kv_valid=None)
            return None, sdpa(qi, kf, vf, bias[None, None],
                              softcap_val=att.logit_softcap)

        _, ob = jax.lax.scan(body, None, (qb, pb))
        out = jnp.moveaxis(ob, 0, 1).reshape(B, S, att.n_heads, att.d_head)

    out = out.reshape(B, S, att.n_heads * att.d_head) @ p["wo"]
    if return_kv:
        return out, (k, v)
    return out


def attention_forward_flash(p: dict, att: AttentionConfig, x: jax.Array,
                            positions: jax.Array, *, window: Optional[int],
                            causal: bool, return_kv: bool = False):
    """attention_forward, but the inner softmax-attention runs in the
    Pallas flash kernel (real-TPU path; interpret-mode on CPU)."""
    from repro.kernels.flash_attention import flash_attention
    B, S, d = x.shape
    q, k, v = _qkv(p, att, x, positions)
    out = flash_attention(q, k, v, causal, window, att.logit_softcap)
    out = out.reshape(B, S, att.n_heads * att.d_head) @ p["wo"]
    if return_kv:
        return out, (k, v)
    return out


def attention_decode(p: dict, att: AttentionConfig, x: jax.Array,
                     cache: KVCache, *, window: Optional[int],
                     layer: Optional[jax.Array] = None
                     ) -> tuple[jax.Array, KVCache]:
    """One-token decode. x: (B,1,d); cache k/v: (B,KV*dh,Sc), or, with
    ``layer``, the stack (L,B,KV*dh,Sc) of every period's cache, of
    which this layer's is ``layer``.

    The new token's K/V is written with one ``dynamic_update_slice`` at
    ``(layer, 0, 0, slot)`` and the layer read back by index, so a stack
    carried through the period scan (and donated to the step) is updated
    in place: no copy of a layer's cache, let alone of the stack. A
    single layer's cache is a stack of one.

    For windowed layers the cache is a ring buffer of size >= window; for
    full layers Sc is the max context. ``cache.index`` is the global
    token position of the incoming token.
    """
    B, S1, d = x.shape
    assert S1 == 1
    stacked = layer is not None
    ks, vs = (cache.k, cache.v) if stacked else (cache.k[None], cache.v[None])
    li = layer if stacked else 0
    Sc = ks.shape[3]
    KV, dh = att.n_kv_heads, att.d_head
    pos = jnp.full((1,), cache.index, jnp.int32)
    q, k_new, v_new = _qkv(p, att, x, pos)

    slot = cache.index % Sc  # ring-buffer slot (== index when Sc >= context)
    at = (li, 0, 0, slot)
    ks = jax.lax.dynamic_update_slice(
        ks, k_new.reshape(1, B, KV * dh, 1).astype(ks.dtype), at)
    vs = jax.lax.dynamic_update_slice(
        vs, v_new.reshape(1, B, KV * dh, 1).astype(vs.dtype), at)
    k = jax.lax.dynamic_index_in_dim(ks, li, 0, keepdims=False)
    v = jax.lax.dynamic_index_in_dim(vs, li, 0, keepdims=False)
    k, v = k.reshape(B, KV, dh, Sc), v.reshape(B, KV, dh, Sc)

    # Position of every cache slot, reconstructed from the ring layout:
    # the most recent position p <= index with p % Sc == slot.
    slots = jnp.arange(Sc, dtype=jnp.int32)
    slot_pos = cache.index - jnp.mod(cache.index - slots, Sc)
    valid = slot_pos >= 0
    if window is not None:
        valid &= (cache.index - slot_pos) < window

    # Grouped-layout attention: q reshaped (B, KV, G, dh), K/V NEVER
    # repeated to H heads. With the cache sequence-sharded (flash-
    # decoding layout) the softmax/out reductions over Sc psum only
    # (B,KV,G)-sized partials — materializing repeated KV instead forces
    # GSPMD into a full cache all-gather per token (§Perf hypothesis B1).
    G = att.n_heads // att.n_kv_heads
    qg = q.reshape(B, att.n_kv_heads, G, att.d_head)
    scale = 1.0 / jnp.sqrt(att.d_head).astype(jnp.float32)
    # K/V stay in cache dtype (bf16); accumulate in fp32 — upcasting the
    # cache would double the HBM traffic of the token's cache scan (§Perf
    # hypothesis B2).
    s = jnp.einsum("bkgd,bkds->bkgs", qg.astype(k.dtype), k,
                   preferred_element_type=jnp.float32) * scale
    if att.logit_softcap is not None:
        s = jnp.tanh(s / att.logit_softcap) * att.logit_softcap
    s = s + jnp.where(valid, 0.0, NEG_INF)[None, None, None, :]
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - jax.lax.stop_gradient(m))
    probs = e / jnp.sum(e, axis=-1, keepdims=True)
    out = jnp.einsum("bkgs,bkds->bkgd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32).astype(v.dtype)
    out = out.reshape(B, 1, att.n_heads * att.d_head) @ p["wo"]
    if not stacked:
        ks, vs = ks[0], vs[0]
    return out, KVCache(k=ks, v=vs, index=cache.index + 1)


def init_cache(att: AttentionConfig, batch: int, max_seq: int,
               window: Optional[int], dtype) -> KVCache:
    Sc = min(max_seq, window) if window is not None else max_seq
    shape = (batch, att.n_kv_heads * att.d_head, Sc)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                   index=jnp.zeros((), jnp.int32))
