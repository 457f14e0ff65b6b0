"""Mixture-of-Experts FFN: top-k routing, sort-based capacity dispatch.

Dispatch is the MegaBlocks-style sort formulation (no O(T*E*C) one-hot
dispatch tensor — that is infeasible at 384 experts): flatten the (token,
expert) assignments, argsort by expert, compute position-within-expert
from exclusive-cumsum bincounts, scatter into an (E, C, d) buffer, run
three batched expert GEMMs, gather back, combine with gate weights.
Overflowing tokens beyond capacity C = ceil(T*k/E * cf) are dropped
(standard capacity-factor semantics).

Sharding: the block runs under shard_map over (batch_axes..., model):
 - 'tp': experts replicated on E, tensor-parallel on d_ff (compute split
   over d_ff); combined token output psums over the model axis.
 - 'ep': experts sharded over the model axis (compute split over E);
   every model shard routes the (replicated-over-model) local tokens to
   its resident experts; combined token output psums over the model axis.
Both psum T*d per block. FSDP-sharded expert weights are all-gathered on
entry (the parameter-server "pull"); AD transposes that gather into a
reduce-scatter of the gradients (the "push") — see DESIGN.md §3.1.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, MoEConfig
from repro.models.layers import activation, dense_init
from repro.parallel.sharding import ParallelCtx


def init_moe(key, cfg: ModelConfig, moe: MoEConfig, dtype) -> dict:
    d, f, E = cfg.d_model, moe.d_ff_expert, moe.num_experts
    ks = jax.random.split(key, 4)
    glu = cfg.ffn_activation in ("swiglu", "geglu")
    p = {"router": dense_init(ks[0], d, E, jnp.float32),
         "w_up": dense_init(ks[1], E * d, f, dtype).reshape(E, d, f),
         "w_down": dense_init(ks[2], E * f, d, dtype).reshape(E, f, d)}
    if glu:
        p["w_gate"] = dense_init(ks[3], E * d, f, dtype).reshape(E, d, f)
    return p


def moe_param_logical_axes(ctx_es: str) -> dict:
    e = "expert" if ctx_es == "ep" else None
    ff = None if ctx_es == "ep" else "d_ff"
    return {"router": P(None, None),
            "w_up": P(e, "fsdp", ff),
            "w_gate": P(e, "fsdp", ff),
            "w_down": P(e, ff, "fsdp")}


def _capacity(moe: MoEConfig, n_tokens: int, dropless: bool) -> int:
    if dropless:
        return n_tokens  # max per-expert load is n_tokens (top-k distinct)
    c = int(n_tokens * moe.top_k * moe.capacity_factor / moe.num_experts)
    c = max(4, -(-c // 4) * 4)     # >=4, multiple of 4
    return min(c, n_tokens)


def _dispatch_indices(expert_idx: jax.Array, n_experts: int,
                      capacity: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """expert_idx: (A,) flat assignments. Returns (sort order, destination
    row in the (E*C) buffer for each sorted assignment, keep mask)."""
    order = jnp.argsort(expert_idx, stable=True)
    sorted_e = expert_idx[order]
    counts = jnp.bincount(expert_idx, length=n_experts)
    start = jnp.cumsum(counts) - counts                  # exclusive cumsum
    pos_in_e = jnp.arange(expert_idx.shape[0]) - start[sorted_e]
    keep = pos_in_e < capacity
    dest = jnp.where(keep, sorted_e * capacity + pos_in_e,
                     n_experts * capacity)               # overflow row
    return order, dest, keep


def _expert_ffn(cfg: ModelConfig, p: dict, buf: jax.Array) -> jax.Array:
    """buf: (E, C, d) -> (E, C, d) through the per-expert FFN."""
    glu = cfg.ffn_activation in ("swiglu", "geglu")
    act = "silu" if cfg.ffn_activation == "swiglu" else (
        "gelu" if cfg.ffn_activation == "geglu" else cfg.ffn_activation)
    up = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    if glu:
        gate = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])
        inner = activation(act, gate) * up
    else:
        inner = activation(act, up)
    return jnp.einsum("ecf,efd->ecd", inner, p["w_down"])


def _moe_local(cfg: ModelConfig, moe: MoEConfig, p: dict, x: jax.Array,
               *, n_local_experts: int, expert_offset: jax.Array,
               psum_axis: Optional[str], es: str,
               batch_axes: Tuple[str, ...],
               dropless: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Per-shard MoE over local tokens x: (T, d). Returns (out, aux_loss)."""
    T, d = x.shape
    E, k = moe.num_experts, moe.top_k
    C = _capacity(moe, T, dropless)

    logits = (x.astype(jnp.float32) @ p["router"])       # (T, E)
    gates = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(gates, k)               # (T, k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)

    flat_e = top_i.reshape(-1)                           # (T*k,)
    flat_t = jnp.arange(T * k) // k
    flat_w = top_w.reshape(-1)

    if es == "ep":
        # keep only assignments for this shard's resident experts
        rel = flat_e - expert_offset
        in_range = (rel >= 0) & (rel < n_local_experts)
        eff_e = jnp.where(in_range, rel, n_local_experts)  # park out-of-range
        order, dest, keep = _dispatch_indices(eff_e, n_local_experts + 1, C)
        keep &= (eff_e[order] < n_local_experts)
        dest = jnp.where(keep, dest, n_local_experts * C)
    else:
        order, dest, keep = _dispatch_indices(flat_e, E, C)
        n_local_experts = E

    tok_sorted = flat_t[order]
    w_sorted = flat_w[order] * keep

    buf = jnp.zeros((n_local_experts * C + 1, d), x.dtype)
    buf = buf.at[dest].add(jnp.where(keep[:, None], x[tok_sorted], 0))
    buf = buf[:n_local_experts * C].reshape(n_local_experts, C, d)

    out_buf = _expert_ffn(cfg, p, buf).reshape(n_local_experts * C, d)
    out_buf = jnp.concatenate([out_buf, jnp.zeros((1, d), out_buf.dtype)])
    y_sorted = out_buf[dest] * w_sorted[:, None].astype(out_buf.dtype)
    y = jnp.zeros((T, d), x.dtype).at[tok_sorted].add(y_sorted.astype(x.dtype))

    if psum_axis is not None:
        y = jax.lax.psum(y, psum_axis)

    # Switch-style load-balance aux loss (local estimate, pmean'd).
    frac = jnp.zeros((E,), jnp.float32).at[flat_e].add(1.0) / (T * k)
    importance = jnp.mean(gates, axis=0)
    aux = E * jnp.sum(frac * importance) * moe.aux_loss_weight
    if batch_axes:
        aux = jax.lax.pmean(aux, batch_axes)
    if psum_axis is not None:
        aux = jax.lax.pmean(aux, psum_axis)
    return y, aux


def apply_moe(ctx: ParallelCtx, cfg: ModelConfig, moe: MoEConfig, p: dict,
              x: jax.Array, *, dropless: bool = False
              ) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (out (B,S,d), aux scalar)."""
    B, S, d = x.shape
    es = ctx.expert_sharding
    if ctx.mesh is None:
        out, aux = _moe_local(cfg, moe, p, x.reshape(B * S, d),
                              n_local_experts=moe.num_experts,
                              expert_offset=jnp.zeros((), jnp.int32),
                              psum_axis=None, es="tp", batch_axes=(),
                              dropless=dropless)
        return out.reshape(B, S, d), aux

    mx = ctx.model_axis
    la = moe_param_logical_axes(es)
    # shard_map requires exact divisibility on the batch dim; single-stream
    # decode (B < n_batch_shards) runs the token replicated instead.
    b_ax = ctx.axis("batch") if B % max(ctx.n_batch_shards, 1) == 0 else None
    batch_axes = ctx.batch_axes if b_ax is not None else ()
    in_specs = (P(b_ax, None, None),
                {k2: ctx.spec(*la[k2]) for k2 in p})
    out_specs = (P(b_ax, None, None), P())

    @functools.partial(jax.shard_map, mesh=ctx.mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    def sharded(xl, pl):
        Bl, Sl, _ = xl.shape
        if ctx.fsdp:  # PS pull: all-gather weight shards over the data axes
            for k2, axes in la.items():
                if k2 in pl and "fsdp" in axes:
                    dim = list(axes).index("fsdp")
                    pl[k2] = jax.lax.all_gather(
                        pl[k2], ctx.batch_axes, axis=dim, tiled=True)
        if es == "ep":
            n_local = moe.num_experts // ctx.n_model_shards
            off = jax.lax.axis_index(mx) * n_local
        else:
            n_local = moe.num_experts
            off = jnp.zeros((), jnp.int32)
        y, aux = _moe_local(cfg, moe, pl, xl.reshape(Bl * Sl, d),
                            n_local_experts=n_local, expert_offset=off,
                            psum_axis=mx, es=es, batch_axes=ctx.batch_axes,
                            dropless=dropless)
        return y.reshape(Bl, Sl, d), aux

    return sharded(x, p)
