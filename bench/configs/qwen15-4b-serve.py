"""Builds ``qwen15-4b-serve``: qwen1.5-4b at its published widths and
depth, random bf16 weights made on the device from the seed, served
through the RPC fabric as ``repro.launch.serve`` serves it.

The weights are the benchmark's own (``make_weights``, one jitted call
in the type they are served in); the program gets them rearranged into
its parameter tree, and the plain reference beside this file
(``qwen15-4b-serve_ref.py``) reads the same arrays.
"""
from __future__ import annotations

import dataclasses
import functools
from pathlib import Path

from bench import core
from bench.costs import DenseDecoder
from bench.serve import ServeSystem

REFERENCE = Path(__file__).with_name("qwen15-4b-serve_ref.py")


def reference():
    return core.load_module(REFERENCE, "bench_ref_qwen15_4b_serve")


def make_weights(c: dict, seed: int):
    """Random weights on the device, in bf16, in one jitted call."""
    import jax
    import jax.numpy as jnp

    L, d, f, V = (int(c["num_hidden_layers"]), int(c["hidden_size"]),
                  int(c["intermediate_size"]), int(c["vocab_size"]))
    heads, kv = int(c["num_attention_heads"]), int(c["num_key_value_heads"])
    dh = d // heads
    hq, hkv = heads * dh, kv * dh
    bf16 = jnp.bfloat16
    shapes = {
        "wq": ((L, d, hq), d ** -0.5), "wk": ((L, d, hkv), d ** -0.5),
        "wv": ((L, d, hkv), d ** -0.5), "wo": ((L, hq, d), hq ** -0.5),
        "bq": ((L, hq), 0.02), "bk": ((L, hkv), 0.02), "bv": ((L, hkv), 0.02),
        "w_gate": ((L, d, f), d ** -0.5), "w_up": ((L, d, f), d ** -0.5),
        "w_down": ((L, f, d), f ** -0.5),
    }

    def make(key):
        keys = iter(jax.random.split(key, len(shapes) + 6))
        normal = lambda shape, scale: (
            jax.random.normal(next(keys), shape, jnp.float32) * scale
        ).astype(bf16)
        layers = {k: normal(shape, scale)
                  for k, (shape, scale) in shapes.items()}
        layers["norm1"] = 1 + normal((L, d), 0.1)
        layers["norm2"] = 1 + normal((L, d), 0.1)
        return {"embed": normal((V, d), 0.02),
                "lm_head": normal((d, V), d ** -0.5),
                "final_norm": 1 + normal((d,), 0.1),
                "layers": layers}

    key = jax.random.fold_in(jax.random.PRNGKey(seed % 2**32),
                             (seed >> 32) % 2**32)
    return jax.jit(make)(key)


def program_params(w: dict) -> dict:
    """The same arrays in the program's parameter tree (no copy)."""
    lw = w["layers"]
    return {
        "embed": w["embed"], "lm_head": w["lm_head"],
        "final_norm": {"scale": w["final_norm"]},
        "blocks": {"pos0": {
            "norm1": {"scale": lw["norm1"]}, "norm2": {"scale": lw["norm2"]},
            "mixer": {k: lw[k] for k in ("wq", "wk", "wv", "wo",
                                         "bq", "bk", "bv")},
            "ffn": {k: lw[k] for k in ("w_gate", "w_up", "w_down")}}},
    }


def arch(c: dict):
    """The program's ArchConfig for this configuration file."""
    from repro.configs import get_config
    from repro.serve.engine import serving_config
    base = get_config("qwen1.5-4b")
    heads = int(c["num_attention_heads"])
    att = dataclasses.replace(
        base.model.attention, n_heads=heads,
        n_kv_heads=int(c["num_key_value_heads"]),
        d_head=int(c["hidden_size"]) // heads, qkv_bias=True,
        rope_theta=float(c["rope_theta"]))
    model = dataclasses.replace(
        base.model, num_layers=int(c["num_hidden_layers"]),
        d_model=int(c["hidden_size"]), d_ff=int(c["intermediate_size"]),
        vocab_size=int(c["vocab_size"]), attention=att,
        tie_embeddings=bool(c["tie_word_embeddings"]),
        max_position_embeddings=int(c["max_position_embeddings"]))
    train = dataclasses.replace(base.train,
                                compute_dtype=c["serving"]["dtype"])
    return serving_config(base.replace(model=model, train=train))


def build(c: dict, traffic: dict, seed: int) -> ServeSystem:
    import jax

    from repro.parallel.sharding import make_ctx
    from repro.serve.engine import ServeConfig, ServeEngine
    from bench import traffic as T

    serving = c["serving"]
    acfg = arch(c)
    weights = jax.block_until_ready(make_weights(c, seed))
    engine = ServeEngine(
        make_ctx(acfg, None), acfg, program_params(weights),
        ServeConfig(max_seq=int(serving["max_seq"]),
                    max_new_tokens=T.max_output(traffic),
                    temperature=0.0, seed=0))
    ref = reference()
    return ServeSystem(
        engine, max_batch=int(serving["max_batch"]),
        vocab=int(c["vocab_size"]),
        reference_gap=functools.partial(ref.max_gap, weights, c),
        control_gap=functools.partial(ref.control_gap, weights, c),
        costs=DenseDecoder.from_config(c),
        limit=float(c["correctness"]["max_logit_gap"]))
