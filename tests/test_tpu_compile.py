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
import re

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


#: what ``bench/configs/qwen15-4b-serve.json`` serves: one row, 2304 slots
QWEN_MAX_SEQ = 2304
#: one layer's K (or V) cache at that size: 20 heads x 128 x 2304 slots
QWEN_SLICE = QWEN_MAX_SEQ * 20 * 128
#: what HLO text prints of an instruction outside a fused computation
_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.+?) ([a-z][\w\-]*)\(")
_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]\{([^}]*)\}")


def _scheduled_ops(text):
    """(name, opcode, [(elements, in HBM)]) of every instruction that is
    not inside a fused computation. A layout with a memory space
    (``S(1)``) lives on the chip, not in HBM."""
    fused = set(re.findall(r"calls=%([\w.\-]+)", text))
    comp, ops = None, []
    for line in text.splitlines():
        if line and not line.startswith(" "):
            m = re.match(r"(?:ENTRY )?%([\w.\-]+)", line)
            comp = m.group(1) if m else None
            continue
        m = _INSTR.match(line)
        if m is None or comp in fused:
            continue
        outs = [(int(np.prod([int(d) for d in dims.split(",") if d])),
                 "S(" not in layout)
                for _, dims, layout in _SHAPE.findall(m.group(2))]
        ops.append((m.group(1), m.group(3), outs))
    return ops


@pytest.fixture(scope="module")
def qwen_serving(one_chip):
    """qwen1.5-4b's decode step as the serve engine runs it (bf16,
    B = 1, 2304 slots), compiled for one v5e, and its prefill."""
    from repro.launch.steps import make_decode_step, make_prefill_step
    from repro.models import init_params, init_states
    from repro.parallel import NO_MESH
    from repro.serve.engine import serving_config
    acfg = serving_config(get_config("qwen1.5-4b"))
    on_chip = lambda s: _sds(s.shape, s.dtype, one_chip)
    params = jax.tree.map(on_chip, jax.eval_shape(
        functools.partial(init_params, acfg=acfg), jax.random.PRNGKey(0)))
    states = jax.tree.map(on_chip, jax.eval_shape(
        lambda: init_states(NO_MESH, acfg, 1, QWEN_MAX_SEQ)))
    decode = make_decode_step(NO_MESH, acfg, 1).lower(
        params, states, _sds((1, 1), jnp.int32, one_chip), None).compile()
    prefill = make_prefill_step(NO_MESH, acfg, max_seq=QWEN_MAX_SEQ)
    return decode, prefill, params


def test_qwen15_4b_decode_updates_its_cache_in_place(qwen_serving,
                                                     one_chip):
    """No copy of a layer's cache or more, and nothing of that size
    written to HBM but the in-place update of each stacked cache; no
    temporary of a cache's size; the caches unpadded, in the layout the
    prefill emits them in, the same in and out."""
    decode, prefill, params = qwen_serving
    bulky = [(name, op, outs) for name, op, outs in
             _scheduled_ops(decode.as_text())
             if any(n >= QWEN_SLICE for n, _ in outs)
             and op not in ("parameter", "get-tuple-element", "bitcast",
                            "tuple", "while")]
    assert not [b for b in bulky if b[0].startswith("copy")], bulky
    in_hbm = [b for b in bulky if any(hbm for _, hbm in b[2])]
    assert [op for _, op, _ in in_hbm] == ["dynamic-update-slice"] * 2, \
        in_hbm                                          # K and V
    assert decode.memory_analysis().temp_size_in_bytes < 64 << 20

    formats = decode.input_formats[0][1]
    assert decode.output_formats[0] == formats
    mixer = formats["pos0"]["mixer"]
    cache_bytes = 0
    for f in (mixer.k, mixer.v):
        arg = _sds((40, 1, 2560, QWEN_MAX_SEQ), jnp.bfloat16, one_chip)
        ident = jax.jit(lambda a: a, in_shardings=f, out_shardings=f)
        cache_bytes += ident.lower(arg).compile().memory_analysis() \
            .argument_size_in_bytes
    assert cache_bytes == 2 * 471_859_200        # 2 x 40 x 2304 x 2560 x 2 B

    tokens = _sds((1, 2048), jnp.int32, one_chip)
    emitted = prefill.lower(params, {"tokens": tokens}).compile()
    assert emitted.output_formats[0] == formats


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
