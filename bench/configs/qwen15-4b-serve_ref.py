"""Plain reference for ``qwen15-4b-serve``: the Qwen2 decoder's forward
pass written from the published description, in float32 with every
matrix product at HIGHEST precision, one layer at a time so that it
fits beside the bf16 weights. It imports nothing of the program.

Per layer: x += Wo . attn(rope(Wq h + bq), rope(Wk h + bk), Wv h + bv),
h = rmsnorm(x) * norm1 (causal softmax over every earlier position,
scale 1/sqrt(head size), RoPE on the two halves of each head); then
x += Wdown (silu(Wgate h2) * (Wup h2)), h2 = rmsnorm(x) * norm2. The
logits are rmsnorm(x) * final_norm . lm_head.

``quant=True`` is the control: the same pass with both operands of
every linear layer and of the head rounded to float8 (e4m3, scaled per
row of activations and per column of weights), the step below the
bf16 the configuration serves in.
"""
from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import numpy as np

BATCH = 4             # sequences per call
ALIGN = 128           # sequences are padded at the end to a multiple


def _jax():
    import jax
    import jax.numpy as jnp
    return jax, jnp


def _q8(x, axis):
    jax, jnp = _jax()
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(a, b, quant):
    jax, jnp = _jax()
    if quant:
        a, b = _q8(a, -1), _q8(b, 0)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(x, scale, eps):
    _, jnp = _jax()
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) \
        * scale


def _rope(x, theta):
    """x: (n, S, heads, dh); rotate the two halves of each head."""
    _, jnp = _jax()
    S, dh = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.lru_cache(maxsize=None)
def _layer_fn(heads: int, kv: int, eps: float, theta: float, quant: bool):
    jax, jnp = _jax()

    def layer(x, w):
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        n, S, d = x.shape
        dh = d // heads
        h = _rms(x, w["norm1"], eps)
        q = (_mm(h, w["wq"], quant) + w["bq"]).reshape(n, S, heads, dh)
        k = (_mm(h, w["wk"], quant) + w["bk"]).reshape(n, S, kv, dh)
        v = (_mm(h, w["wv"], quant) + w["bv"]).reshape(n, S, kv, dh)
        q, k = _rope(q, theta), _rope(k, theta)
        k = jnp.repeat(k, heads // kv, axis=2)
        v = jnp.repeat(v, heads // kv, axis=2)
        s = jnp.einsum("nqhd,nkhd->nhqk", q, k,
                       precision=jax.lax.Precision.HIGHEST) / np.sqrt(dh)
        causal = jnp.tril(jnp.ones((S, S), bool))
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("nhqk,nkhd->nqhd", p, v,
                       precision=jax.lax.Precision.HIGHEST)
        x = x + _mm(o.reshape(n, S, heads * dh), w["wo"], quant)
        h2 = _rms(x, w["norm2"], eps)
        g = _mm(h2, w["w_gate"], quant)
        u = _mm(h2, w["w_up"], quant)
        return x + _mm(jax.nn.silu(g) * u, w["w_down"], quant)

    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _head_fn(eps: float, quant: bool):
    jax, jnp = _jax()

    def head(h, final_norm, lm_head):
        h = _rms(h, final_norm.astype(jnp.float32), eps)
        return _mm(h, lm_head.astype(jnp.float32), quant)

    return jax.jit(head)


def logits(weights: dict, c: dict,
           samples: Sequence[Tuple[np.ndarray, List[int]]],
           quant: bool = False) -> list:
    """For each ``(prompt, served)``: the logits (n, vocab) at the
    positions that chose served[0..n-1], teacher-forced on the served
    tokens."""
    jax, jnp = _jax()
    heads, kv = int(c["num_attention_heads"]), int(c["num_key_value_heads"])
    eps, theta = float(c["rms_norm_eps"]), float(c["rope_theta"])
    seqs = [np.concatenate([np.asarray(p, np.int32),
                            np.asarray(t[:-1], np.int32)]) for p, t in samples]
    S = -(-max(len(s) for s in seqs) // ALIGN) * ALIGN
    tokens = np.zeros((len(seqs), S), np.int32)
    for i, s in enumerate(seqs):
        tokens[i, :len(s)] = s
    batches = [jnp.take(weights["embed"], jnp.asarray(tokens[i:i + BATCH]),
                        axis=0).astype(jnp.float32)
               for i in range(0, len(seqs), BATCH)]
    layer = _layer_fn(heads, kv, eps, theta, quant)
    lw = weights["layers"]
    for li in range(int(c["num_hidden_layers"])):
        w = {k: v[li] for k, v in lw.items()}
        batches = [layer(x, w) for x in batches]
    head = _head_fn(eps, quant)
    out = []
    for i, (p, t) in enumerate(samples):
        x = batches[i // BATCH][i % BATCH]
        rows = x[len(p) - 1: len(p) - 1 + len(t)]
        out.append(head(rows, weights["final_norm"], weights["lm_head"]))
    return out


def gaps(ref_rows, chosen) -> np.ndarray:
    """By how much each chosen token's reference logit lies below the
    reference's best at that position."""
    _, jnp = _jax()
    r = jnp.asarray(ref_rows)
    c = jnp.asarray(np.asarray(chosen, np.int32))
    return np.asarray(jnp.max(r, -1) - jnp.take_along_axis(
        r, c[:, None], axis=-1)[:, 0])


def max_gap(weights: dict, c: dict,
            samples: Sequence[Tuple[np.ndarray, List[int]]]) -> float:
    """The widest gap over every served token of the samples."""
    rows = logits(weights, c, samples)
    return float(max(gaps(r, t).max() for r, (_, t) in zip(rows, samples)))


def control_gap(weights: dict, c: dict,
                samples: Sequence[Tuple[np.ndarray, List[int]]]) -> float:
    """The control: at each position of the same prompts and tokens, the
    gap of the token that the float8 pass puts first."""
    _, jnp = _jax()
    ref = logits(weights, c, samples)
    low = logits(weights, c, samples, quant=True)
    return float(max(gaps(r, np.asarray(jnp.argmax(q, -1))).max()
                     for r, q in zip(ref, low)))
