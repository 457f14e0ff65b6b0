"""Compiles for a described TPU v5e: the kernels and programs of the main
paths at real widths, checked by the chip's own compiler with no chip
attached. Interpret-mode tests cannot see what these catch: slices not
aligned to the chip's tiling, kernels that need more VMEM than it has,
and programs that do not fit its HBM.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and the
test workers all import this file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.configs import get_config
from repro.core import channels as ch
from repro.kernels.flash_attention import flash_attention
from repro.kernels.payload_pack import pack, unpack
from repro.kernels.rwkv6_scan import rwkv6_scan

#: one v5e chip's HBM (Google Cloud documentation, "TPU v5e")
V5E_HBM_BYTES = 16 * 10**9
#: the paper's Large payload: ten 1 MiB iovec buffers
LARGE = [1 << 20] * 10


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("direction", ["pack", "unpack"])
def test_payload_pack_compiles_at_paper_large(one_chip, direction):
    # framing packs a 128-byte header in front of the buffers, so every
    # buffer starts one row past a tile boundary
    sizes = [128] + LARGE
    if direction == "pack":
        args = [_sds((s,), jnp.uint8, one_chip) for s in sizes]
        fn = jax.jit(lambda *b: pack(b, interpret=False)[0])
    else:
        args = [_sds((sum(sizes),), jnp.uint8, one_chip)]
        fn = jax.jit(lambda p: unpack(p, sizes, interpret=False))
    assert _has_kernel(fn.lower(*args).compile())


@pytest.mark.parametrize("arch,seq", [("qwen1.5-4b", 2048),
                                      ("qwen3-8b", 4096)])
def test_flash_attention_compiles_at_arch_widths(one_chip, arch, seq):
    att = get_config(arch).model.attention
    q = _sds((1, seq, att.n_heads, att.d_head), jnp.bfloat16, one_chip)
    kv = _sds((1, seq, att.n_kv_heads, att.d_head), jnp.bfloat16, one_chip)
    fn = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, True, None, None, None, 128, 128, False))
    assert _has_kernel(fn.lower(q, kv, kv).compile())


def test_rwkv6_scan_compiles_at_rwkv6_widths(one_chip):
    m = get_config("rwkv6-1.6b").model
    hs = m.ssm.head_size
    bh, seq = m.d_model // hs, 2048
    x = _sds((bh, seq, hs), jnp.float32, one_chip)
    fn = jax.jit(lambda r, k, v, w, s0, u: rwkv6_scan(
        r, k, v, w, s0, u, interpret=False))
    compiled = fn.lower(x, x, x, x, _sds((bh, hs, hs), jnp.float32, one_chip),
                        _sds((bh, hs), jnp.float32, one_chip)).compile()
    assert _has_kernel(compiled)


def test_qwen15_4b_prefill_fits_one_chip(one_chip):
    from repro.launch.steps import make_prefill_step
    from repro.models import init_params
    from repro.parallel import NO_MESH
    from repro.serve.engine import serving_config
    acfg = serving_config(get_config("qwen1.5-4b"))
    shapes = jax.eval_shape(functools.partial(init_params, acfg=acfg),
                            jax.random.PRNGKey(0))
    floats = [s.dtype for s in jax.tree.leaves(shapes)
              if jnp.issubdtype(s.dtype, jnp.floating)]
    assert set(floats) == {jnp.dtype(jnp.bfloat16)}
    params = jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip), shapes)
    tokens = _sds((4, 128), jnp.int32, one_chip)
    step = make_prefill_step(NO_MESH, acfg, max_seq=128 + 16 + 8)
    mem = step.lower(params, {"tokens": tokens}).compile().memory_analysis()
    assert mem.argument_size_in_bytes < V5E_HBM_BYTES
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < V5E_HBM_BYTES


@pytest.mark.parametrize("serialized", [False, True])
@pytest.mark.parametrize("channel", ["ps_1x3", "fully_connected"])
def test_channels_compile_on_four_chips(topo, channel, serialized):
    mesh = Mesh(np.array(topo.devices), (ch.AXIS,))
    if channel == "ps_1x3":
        fn = ch.ps_round_fn(mesh, len(LARGE), 1, 3, serialized=serialized)
    else:
        fn = ch.fully_connected_fn(mesh, len(LARGE), 4,
                                   serialized=serialized)
    rows = NamedSharding(mesh, P(ch.AXIS))
    args = [_sds((4, s), jnp.uint8, rows) for s in LARGE]
    text = fn.lower(*args).compile().as_text()
    assert "collective-permute" in text
