"""Unified model: dense / GQA / MoE / RWKV6 / Mamba-hybrid / encoder-only.

Layers are grouped into *periods* (the repeating ``layer_pattern`` of the
config — e.g. jamba's 8-layer Mamba/attention block, gemma2's local/global
pair) and the model scans over stacked period parameters, so the HLO holds
ONE period body regardless of depth. Each period position has its own
parameter subtree ("pos0", "pos1", …) because layer kinds differ inside a
period.

Modes:
  train   — full-sequence forward, loss; no state
  prefill — full-sequence forward; returns per-layer states (KV/SSM)
  decode  — single token with per-layer states
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as LP

from repro.configs.base import ArchConfig, ModelConfig
from repro.models import attention as attn_lib
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.attention import KVCache
from repro.models.layers import (apply_ffn, apply_norm, dense_init, init_ffn,
                                 init_norm, softcap, truncated_normal)
from repro.parallel.sharding import NO_MESH, ParallelCtx

Params = Dict[str, Any]


def _dtype(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16}[name]


def cast_floats(tree, dtype):
    """Cast fp32 leaves to the compute dtype (mixed precision: fp32
    masters live in the optimizer; compute, activations and therefore
    every weight all-gather / grad reduce-scatter move `dtype` bytes —
    without this, jnp promotion silently runs the whole model in fp32
    (§Perf hypothesis A4)."""
    return jax.tree.map(
        lambda a: a.astype(dtype)
        if hasattr(a, "dtype") and a.dtype == jnp.float32 else a, tree)


def cast_params_for_compute(ctx: ParallelCtx, acfg: ArchConfig,
                            params: Params, dtype) -> Params:
    """cast_floats + re-pin every leaf to its own sharding. The
    constraint keeps GSPMD from hoisting the FSDP weight all-gather
    ABOVE the convert (observed on XLA:CPU SPMD: gathers move fp32 bytes
    without it — 2x wire; §Perf hypothesis A6)."""
    params = cast_floats(params, dtype)
    if ctx.mesh is None:
        return params
    from repro.parallel.sharding import logical_to_physical
    specs = logical_to_physical(ctx, param_logical_axes(acfg))
    return jax.tree.map(
        lambda a, sp: jax.lax.with_sharding_constraint(
            a, jax.NamedSharding(ctx.mesh, sp)), params, specs)


# =========================================================================
# Init
# =========================================================================

def _init_position(key, cfg: ModelConfig, pos: int, dtype) -> Params:
    kind = cfg.layer_pattern[pos]
    ks = jax.random.split(key, 4)
    p: Params = {"norm1": init_norm(cfg, cfg.d_model, dtype),
                 "norm2": init_norm(cfg, cfg.d_model, dtype)}
    if kind == "attn":
        p["mixer"] = attn_lib.init_attention(ks[0], cfg, cfg.attention, dtype)
    elif kind == "mamba":
        p["mixer"] = ssm_lib.init_mamba(ks[0], cfg, cfg.ssm, dtype)
    elif kind == "rwkv":
        p["mixer"] = ssm_lib.init_rwkv6(ks[0], cfg, cfg.ssm, dtype)
    else:
        raise ValueError(kind)
    if kind == "rwkv":
        pass  # channel-mix lives inside the rwkv param set
    elif cfg.moe_at(pos):
        p["ffn"] = moe_lib.init_moe(ks[1], cfg, cfg.moe, dtype)
    else:
        p["ffn"] = init_ffn(ks[1], cfg, cfg.d_model, cfg.d_ff, dtype)
    return p


def init_params(key, acfg: ArchConfig) -> Params:
    cfg = acfg.model
    dtype = _dtype(acfg.train.param_dtype)
    k_embed, k_blocks, k_head = jax.random.split(key, 3)

    def init_period(k):
        pks = jax.random.split(k, cfg.pattern_period)
        return {f"pos{i}": _init_position(pks[i], cfg, i, dtype)
                for i in range(cfg.pattern_period)}

    period_keys = jax.random.split(k_blocks, cfg.n_periods)
    blocks = jax.vmap(init_period)(period_keys)

    params: Params = {
        "embed": truncated_normal(k_embed, (cfg.vocab_size, cfg.d_model),
                                  0.02, dtype),
        "blocks": blocks,
        "final_norm": init_norm(cfg, cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(k_head, cfg.d_model, cfg.vocab_size,
                                       dtype)
    return params


# ---------------- logical sharding of every parameter ---------------------

def param_logical_axes(acfg: ArchConfig) -> Params:
    """Pytree matching init_params' structure; leaves are PartitionSpecs
    of *logical* axis names (see parallel.sharding.logical_to_physical)."""
    cfg = acfg.model

    def norm_axes(_cfg):
        return ({"scale": LP(None)} if _cfg.norm == "rmsnorm"
                else {"scale": LP(None), "bias": LP(None)})

    def pos_axes(pos: int) -> Params:
        kind = cfg.layer_pattern[pos]
        p: Params = {"norm1": norm_axes(cfg), "norm2": norm_axes(cfg)}
        if kind == "attn":
            att = cfg.attention
            m = {"wq": LP("fsdp", "heads"), "wk": LP("fsdp", "heads"),
                 "wv": LP("fsdp", "heads"), "wo": LP("heads", "fsdp")}
            if att.qkv_bias:
                m.update({"bq": LP("heads"), "bk": LP("heads"),
                          "bv": LP("heads")})
            if att.qk_norm:
                m.update({"q_norm": LP(None), "k_norm": LP(None)})
            p["mixer"] = m
        elif kind == "mamba":
            p["mixer"] = {
                "z_proj": LP("fsdp", "heads"),
                "x_proj": LP("fsdp", "heads"),
                "bc_proj": LP("fsdp", None),
                "dt_proj": LP("fsdp", None),
                "conv_w": LP(None, "heads"),
                "conv_b": LP("heads"),
                "conv_w_bc": LP(None, None),
                "conv_b_bc": LP(None), "a_log": LP(None),
                "d_skip": LP(None), "dt_bias": LP(None),
                "norm": LP("heads"), "out_proj": LP("heads", "fsdp")}
        elif kind == "rwkv":
            p["mixer"] = {
                "mu_w": LP(None), "mu_r": LP(None), "mu_k": LP(None),
                "mu_v": LP(None), "mu_g": LP(None),
                "w0": LP(None, None), "w_lora_a": LP("fsdp", None),
                "w_lora_b": LP(None, None), "u": LP(None, None),
                "wr": LP("fsdp", "heads"), "wk": LP("fsdp", "heads"),
                "wv": LP("fsdp", "heads"), "wg": LP("fsdp", "heads"),
                "wo": LP("heads", "fsdp"), "ln_x": LP(None),
                "mu_k_cm": LP(None), "mu_r_cm": LP(None),
                "wk_cm": LP("fsdp", "d_ff"), "wv_cm": LP("d_ff", "fsdp"),
                "wr_cm": LP("fsdp", "heads")}
        if kind != "rwkv":
            if cfg.moe_at(pos):
                es = (acfg.parallel.expert_sharding
                      or cfg.moe.expert_sharding)
                p["ffn"] = moe_lib.moe_param_logical_axes(es)
                if cfg.ffn_activation not in ("swiglu", "geglu"):
                    p["ffn"] = {k: v for k, v in p["ffn"].items()
                                if k != "w_gate"}
            else:
                f = {"w_up": LP("fsdp", "d_ff"), "w_down": LP("d_ff", "fsdp")}
                if cfg.ffn_activation in ("swiglu", "geglu"):
                    f["w_gate"] = LP("fsdp", "d_ff")
                p["ffn"] = f
        return p

    # stacked: prepend the layers axis to every leaf
    def stack(tree):
        return jax.tree.map(lambda lp: LP("layers", *lp), tree)

    # tiny vocabs (hubert's 504-label codebook) cannot shard over the
    # 16-way model axis — and gain nothing from it; replicate instead.
    vocab_ax = "vocab" if (cfg.vocab_size % 16 == 0
                           and cfg.vocab_size >= 4096) else None
    axes: Params = {
        "embed": LP(vocab_ax, "fsdp"),
        "blocks": stack({f"pos{i}": pos_axes(i)
                         for i in range(cfg.pattern_period)}),
        "final_norm": norm_axes(cfg),
    }
    if not cfg.tie_embeddings:
        axes["lm_head"] = LP("fsdp", vocab_ax)
    return axes


# =========================================================================
# Layer application
# =========================================================================

def _apply_position(ctx: ParallelCtx, cfg: ModelConfig, pos: int, p: Params,
                    x: jax.Array, state: Optional[Params], mode: str,
                    positions: jax.Array, compute_dtype,
                    max_seq: Optional[int] = None,
                    use_flash: bool = False, use_rwkv_k: bool = False,
                    layer: Optional[jax.Array] = None
                    ) -> Tuple[jax.Array, Optional[Params], jax.Array]:
    """One layer. Returns (x, new_state, aux_loss). In decode, an
    attention state whose K/V are stacked over periods comes with the
    ``layer`` it belongs to (``attention_decode``)."""
    kind = cfg.layer_pattern[pos]
    window = cfg.window_at(pos)
    aux = jnp.zeros((), jnp.float32)
    mixer_state = state["mixer"] if state is not None else None

    h = apply_norm(cfg, p["norm1"], x)
    new_state: Optional[Params] = None
    if kind == "attn":
        att = cfg.attention
        fwd = (attn_lib.attention_forward_flash if use_flash
               else attn_lib.attention_forward)
        if mode == "decode":
            h, cache = attn_lib.attention_decode(p["mixer"], att, h,
                                                 mixer_state, window=window,
                                                 layer=layer)
            new_state = {"mixer": cache}
        elif mode == "prefill":
            h, kv = fwd(p["mixer"], att, h, positions, window=window,
                        causal=att.causal, return_kv=True)
            new_state = {"mixer": _cache_from_prefill(kv, window, max_seq)}
        else:
            h = fwd(p["mixer"], att, h, positions, window=window,
                    causal=att.causal)
    elif kind == "mamba":
        if mode == "decode":
            h, s = ssm_lib.mamba_step(cfg, cfg.ssm, p["mixer"], h,
                                      mixer_state)
        else:
            h, s = ssm_lib.mamba_forward(cfg, cfg.ssm, p["mixer"], h,
                                         mixer_state)
        new_state = {"mixer": s} if mode != "train" else None
    elif kind == "rwkv":
        if mode == "decode":
            h, s = ssm_lib.rwkv6_time_mix_step(cfg, cfg.ssm, p["mixer"], h,
                                               mixer_state)
        else:
            h, s = ssm_lib.rwkv6_time_mix(cfg, cfg.ssm, p["mixer"], h,
                                          mixer_state,
                                          use_kernel=use_rwkv_k)
        new_state = {"mixer": s} if mode != "train" else None
    x = x + h.astype(x.dtype)
    x = _constrain_act(ctx, x)

    h2 = apply_norm(cfg, p["norm2"], x)
    if kind == "rwkv":
        cm_prev = state.get("shift_cm") if state is not None else None
        cm_state = ({"shift_cm": cm_prev} if cm_prev is not None else None)
        h2, cm_new = ssm_lib.rwkv6_channel_mix(p["mixer"], h2, cm_state)
        if new_state is not None:
            new_state["shift_cm"] = cm_new
    elif cfg.moe_at(pos):
        # decode is dropless (serving must not drop a live token's experts)
        h2, aux = moe_lib.apply_moe(ctx, cfg, cfg.moe, p["ffn"], h2,
                                    dropless=(mode == "decode"))
    else:
        h2 = apply_ffn(cfg, p["ffn"], h2)
    x = x + h2.astype(x.dtype)
    x = _constrain_act(ctx, x)
    return x, new_state, aux


def _constrain_act(ctx: ParallelCtx, x: jax.Array) -> jax.Array:
    """Activations: batch over (pod, data) when divisible, else replicated
    (single-stream decode)."""
    if x.shape[0] % max(ctx.n_batch_shards, 1) == 0:
        return ctx.constrain(x, "batch", None, None)
    return ctx.constrain(x, None, None, None)


def _cache_from_prefill(kv, window: Optional[int],
                        max_seq: int) -> KVCache:
    """Lay prefill K/V out as a ring buffer of Sc slots (slot = pos % Sc),
    as the decode cache holds them: (B, KV*dh, Sc)."""
    k, v = kv
    B, S, KV, dh = k.shape
    k = k.reshape(B, S, KV * dh).swapaxes(1, 2)
    v = v.reshape(B, S, KV * dh).swapaxes(1, 2)
    Sc = min(max_seq, window) if window is not None else max_seq
    if Sc < S:      # windowed: keep the last Sc positions, ring layout
        k = jnp.roll(k[..., -Sc:], S % Sc, axis=2)
        v = jnp.roll(v[..., -Sc:], S % Sc, axis=2)
    elif Sc > S:    # room to grow: unwritten slots are masked by position
        pad = ((0, 0), (0, 0), (0, Sc - S))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    return KVCache(k=k, v=v, index=jnp.asarray(S, jnp.int32))


# =========================================================================
# Full model
# =========================================================================

def _embed_in(ctx, cfg, params, tokens, embeds, compute_dtype):
    if cfg.frontend is not None:
        assert embeds is not None, f"{cfg.name} needs frontend embeds"
        x = embeds.astype(compute_dtype)
    else:
        x = jnp.take(params["embed"], tokens, axis=0).astype(compute_dtype)
    return _constrain_act(ctx, x)


def _take_kv(state: Params) -> Tuple[Optional[Tuple], Params]:
    """A position's decode state -> (its K/V stacks, or None where it
    holds no KV cache, and the state without them). The stacks ride the
    period scan's carry, each layer writing its token in place; every
    other leaf (cache indices, recurrent states) stays per period in the
    scan's xs/ys."""
    m = state["mixer"]
    if not isinstance(m, KVCache):
        return None, state
    return (m.k, m.v), {**state, "mixer": m._replace(k=None, v=None)}


def _put_kv(kv: Tuple, state: Params) -> Params:
    """Inverse of ``_take_kv``."""
    k, v = kv
    return {**state, "mixer": state["mixer"]._replace(k=k, v=v)}


def _scan_periods(ctx, acfg, params, x, states, mode, positions,
                  compute_dtype, max_seq=None):
    cfg = acfg.model
    aux0 = jnp.zeros((), jnp.float32)
    kv = {}
    if mode == "decode":
        taken = {n: _take_kv(st) for n, st in states.items()}
        kv = {n: t[0] for n, t in taken.items() if t[0] is not None}
        states = {n: t[1] for n, t in taken.items()}

    def period_body(x, per_params, per_states, kv, layer):
        new_states = {} if mode != "train" else None
        kv = dict(kv)
        aux_sum = jnp.zeros((), jnp.float32)
        for i in range(cfg.pattern_period):
            name = f"pos{i}"
            st = per_states[name] if per_states is not None else None
            if name in kv:
                st = _put_kv(kv[name], st)
            x, ns, aux = _apply_position(ctx, cfg, i, per_params[name],
                                         x, st, mode, positions,
                                         compute_dtype, max_seq,
                                         acfg.train.use_flash_kernel,
                                         acfg.train.use_rwkv_kernel,
                                         layer)
            aux_sum = aux_sum + aux
            if name in kv:
                kv[name], ns = _take_kv(ns)
            if new_states is not None:
                new_states[name] = ns
        return x, new_states, aux_sum, kv

    use_remat = (mode == "train" and acfg.train.remat)
    if use_remat:
        policy = (jax.checkpoint_policies.nothing_saveable
                  if acfg.train.remat_policy == "nothing_saveable"
                  else jax.checkpoint_policies.dots_saveable)
        period_body = jax.checkpoint(period_body, policy=policy,
                                     static_argnums=())

    if acfg.train.scan_layers and cfg.n_periods > 1:
        def scan_body(carry, xs):
            x, aux, kv = carry
            per_params, per_states, layer = xs
            x, ns, aux_p, kv = period_body(x, per_params, per_states, kv,
                                           layer)
            return (x, aux + aux_p, kv), ns

        xs = (params["blocks"], states if mode != "train" else None,
              jnp.arange(cfg.n_periods, dtype=jnp.int32))
        (x, aux, kv), new_states = jax.lax.scan(scan_body, (x, aux0, kv),
                                                xs)
    else:
        aux = aux0
        new_states_list = []
        for li in range(cfg.n_periods):
            per_params = jax.tree.map(lambda a, li=li: a[li],
                                      params["blocks"])
            per_states = (jax.tree.map(lambda a, li=li: a[li], states)
                          if states is not None else None)
            x, ns, aux_p, kv = period_body(x, per_params, per_states, kv,
                                           li)
            aux = aux + aux_p
            new_states_list.append(ns)
        new_states = (jax.tree.map(lambda *xs: jnp.stack(xs),
                                   *new_states_list)
                      if mode != "train" else None)
    if mode == "decode":
        new_states = {n: _put_kv(kv[n], st) if n in kv else st
                      for n, st in new_states.items()}
    return x, new_states, aux


def forward(ctx: ParallelCtx, acfg: ArchConfig, params: Params, *,
            tokens: Optional[jax.Array] = None,
            embeds: Optional[jax.Array] = None,
            states: Optional[Params] = None,
            mode: str = "train",
            max_seq: Optional[int] = None
            ) -> Tuple[jax.Array, Optional[Params], jax.Array]:
    """Returns (hidden (B,S,d) after final norm, new_states, aux_loss).

    ``max_seq``: prefill only — KV-cache slot count to allocate (defaults
    to the prefill length itself, i.e. no room to decode further).
    """
    cfg = acfg.model
    compute_dtype = _dtype(acfg.train.compute_dtype)
    if compute_dtype != jnp.float32:
        params = cast_params_for_compute(ctx, acfg, params, compute_dtype)
    B, S = (tokens.shape if tokens is not None else embeds.shape[:2])
    if mode == "prefill" and max_seq is None:
        max_seq = S
    x = _embed_in(ctx, cfg, params, tokens, embeds, compute_dtype)
    if mode == "decode":
        positions = None  # attention reads positions from its cache index
    else:
        positions = jnp.arange(S, dtype=jnp.int32)
    x, new_states, aux = _scan_periods(ctx, acfg, params, x, states, mode,
                                       positions, compute_dtype, max_seq)
    x = apply_norm(cfg, params["final_norm"], x)
    return x, new_states, aux


def logits_fn(ctx: ParallelCtx, acfg: ArchConfig, params: Params,
              hidden: jax.Array) -> jax.Array:
    cfg = acfg.model
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = hidden @ head.astype(hidden.dtype)
    logits = softcap(logits, cfg.final_logit_softcap)
    return ctx.constrain(logits, "batch", None, "vocab")


def loss_fn(ctx: ParallelCtx, acfg: ArchConfig, params: Params,
            hidden: jax.Array, labels: jax.Array,
            chunk: int = 512) -> jax.Array:
    """Chunked (over seq) cross-entropy so (B,S,V) logits never fully
    materialize. labels: (B,S) int32, -1 = masked out."""
    cfg = acfg.model
    B, S, d = hidden.shape
    chunk = min(chunk, S)
    assert S % chunk == 0
    nb = S // chunk
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])

    hb = jnp.moveaxis(hidden.reshape(B, nb, chunk, d), 1, 0)
    lb = jnp.moveaxis(labels.reshape(B, nb, chunk), 1, 0)

    def body(carry, blk):
        h, y = blk
        logits = softcap(h @ head.astype(h.dtype), cfg.final_logit_softcap)
        logits = ctx.constrain(logits, "batch", None, "vocab")
        logits = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        onehot = jax.nn.one_hot(jnp.maximum(y, 0), cfg.vocab_size,
                                dtype=jnp.float32)
        true_logit = jnp.sum(logits * onehot, axis=-1)
        mask = (y >= 0).astype(jnp.float32)
        nll = (lse - true_logit) * mask
        return (carry[0] + jnp.sum(nll), carry[1] + jnp.sum(mask)), None

    (tot, cnt), _ = jax.lax.scan(body, (jnp.zeros(()), jnp.zeros(())),
                                 (hb, lb))
    return tot / jnp.maximum(cnt, 1.0)


# =========================================================================
# State init (decode)
# =========================================================================

def init_states(ctx: ParallelCtx, acfg: ArchConfig, batch: int,
                max_seq: int) -> Params:
    """Fresh per-layer states, stacked over periods."""
    cfg = acfg.model
    cache_dtype = _dtype(acfg.train.compute_dtype)

    def one_position(pos: int):
        kind = cfg.layer_pattern[pos]
        window = cfg.window_at(pos)
        if kind == "attn":
            return {"mixer": attn_lib.init_cache(cfg.attention, batch,
                                                 max_seq, window,
                                                 cache_dtype)}
        if kind == "mamba":
            return {"mixer": ssm_lib.init_mamba_state(cfg, cfg.ssm, batch)}
        if kind == "rwkv":
            s = ssm_lib.init_rwkv_state(cfg, cfg.ssm, batch)
            return {"mixer": {"S": s["S"], "shift_tm": s["shift_tm"]},
                    "shift_cm": s["shift_cm"]}
        raise ValueError(kind)

    def stack_periods(tree):
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.n_periods,) + a.shape), tree)

    return stack_periods({f"pos{i}": one_position(i)
                          for i in range(cfg.pattern_period)})


def state_logical_axes(acfg: ArchConfig, batch: int) -> Params:
    """Logical axes for decode states (mirrors init_states).

    KV caches shard their SEQUENCE dim (flash-decoding style): over
    'model' for batched decode (batch rides (pod, data)), over the whole
    mesh for single-stream long-context decode. KV heads stay replicated
    — n_kv_heads rarely divides the model axis and jit in_shardings must
    divide exactly."""
    cfg = acfg.model
    single = batch == 1
    b_ax = None if single else "batch"
    s_ax = "kv_seq_all" if single else "kv_seq"

    def one_position(pos: int):
        kind = cfg.layer_pattern[pos]
        if kind == "attn":
            return {"mixer": KVCache(
                k=LP("layers", b_ax, None, s_ax),
                v=LP("layers", b_ax, None, s_ax),
                index=LP("layers"))}
        if kind == "mamba":
            return {"mixer": {"h": LP("layers", b_ax, "heads", None, None),
                              "conv": LP("layers", b_ax, None, "heads"),
                              "conv_bc": LP("layers", b_ax, None, None)}}
        if kind == "rwkv":
            return {"mixer": {"S": LP("layers", b_ax, "heads", None, None),
                              "shift_tm": LP("layers", b_ax, None)},
                    "shift_cm": LP("layers", b_ax, None)}
        raise ValueError(kind)

    return {f"pos{i}": one_position(i) for i in range(cfg.pattern_period)}
