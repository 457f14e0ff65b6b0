"""End-to-end behaviour tests for the system: serve engine generation,
bench-suite wiring, sharding rule coherence, config registry."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import (SHAPES, cells, get_config, get_reduced_config,
                           get_shape, list_archs)
from repro.models import forward, init_params
from repro.models.model import param_logical_axes, state_logical_axes
from repro.parallel import NO_MESH
from repro.serve.engine import ServeConfig, ServeEngine


def test_registry_covers_assignment():
    assert len(list_archs()) == 10
    assert len(SHAPES) == 4
    runnable = cells()
    allc = cells(include_skipped=True)
    assert len(allc) == 40            # the assigned 10x4 grid
    assert len(runnable) == 33        # documented skips (DESIGN.md §4)
    skips = [(a, s, r) for a, s, r in allc if r is not None]
    assert all(r for _, _, r in skips)
    # encoder-only: both decode shapes skipped
    hub = {s for a, s, r in skips if a == "hubert-xlarge"}
    assert hub == {"decode_32k", "long_500k"}


def test_param_counts_match_published_sizes():
    expected = {  # billions, +-12%
        "hubert-xlarge": 0.96, "mixtral-8x7b": 46.7,
        "kimi-k2-1t-a32b": 1041.0, "qwen1.5-4b": 4.0,
        "nemotron-4-15b": 15.0, "qwen3-8b": 8.2, "gemma2-9b": 9.2,
        "internvl2-76b": 70.0, "rwkv6-1.6b": 1.6,
        "jamba-1.5-large-398b": 398.0,
    }
    for arch, want in expected.items():
        got = get_config(arch).model.num_params() / 1e9
        assert abs(got - want) / want < 0.12, (arch, got, want)


def test_moe_active_params():
    k2 = get_config("kimi-k2-1t-a32b").model
    assert 25 < k2.num_active_params() / 1e9 < 40  # ~32B active


def test_logical_axes_match_param_tree():
    for arch in list_archs():
        cfg = get_reduced_config(arch)
        params = init_params(jax.random.PRNGKey(0), cfg)
        axes = param_logical_axes(cfg)
        ps, pdef = jax.tree.flatten(params)
        axs, adef = jax.tree.flatten(axes)
        assert pdef == adef, arch
        for p, a in zip(ps, axs):
            assert p.ndim == len(a), (arch, p.shape, a)


def test_state_axes_match_state_tree():
    for arch in list_archs():
        cfg = get_reduced_config(arch)
        if cfg.model.is_encoder:
            continue
        from repro.models import init_states
        st = init_states(NO_MESH, cfg, batch=2, max_seq=32)
        axes = state_logical_axes(cfg, batch=2)
        sdef = jax.tree.structure(st)
        adef = jax.tree.structure(axes)
        assert sdef == adef, arch


@pytest.mark.parametrize("arch", ["qwen3-8b", "rwkv6-1.6b",
                                  "mixtral-8x7b"])
def test_serve_engine_generates(arch):
    cfg = get_reduced_config(arch)
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(NO_MESH, cfg, params,
                      ServeConfig(max_seq=64, max_new_tokens=4))
    prompts = np.random.default_rng(0).integers(
        0, cfg.model.vocab_size, (2, 8), dtype=np.int32)
    out = eng.generate(prompts)
    assert out.shape == (2, 4)
    assert (out >= 0).all() and (out < cfg.model.vocab_size).all()


def test_serve_greedy_matches_forward():
    cfg = get_reduced_config("qwen3-8b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(NO_MESH, cfg, params,
                      ServeConfig(max_seq=64, max_new_tokens=1))
    prompts = np.random.default_rng(1).integers(
        0, cfg.model.vocab_size, (2, 8), dtype=np.int32)
    out = eng.generate(prompts)
    from repro.models import logits_fn
    h, _, _ = forward(NO_MESH, cfg, params, tokens=jnp.asarray(prompts),
                      mode="train")
    ref = np.asarray(jnp.argmax(
        logits_fn(NO_MESH, cfg, params, h)[:, -1], axis=-1))
    assert (out[:, 0] == ref).all()


def test_serve_rejects_encoder():
    cfg = get_reduced_config("hubert-xlarge")
    params = init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(AssertionError):
        ServeEngine(NO_MESH, cfg, params)


def test_encoder_is_bidirectional():
    """hubert must see future frames (encoder), causal LMs must not."""
    cfg = get_reduced_config("hubert-xlarge")
    params = init_params(jax.random.PRNGKey(0), cfg)
    key = jax.random.PRNGKey(2)
    e1 = jax.random.normal(key, (1, 16, cfg.model.d_model))
    e2 = e1.at[:, -1].set(-e1[:, -1])  # change only the LAST frame
    h1, _, _ = forward(NO_MESH, cfg, params, embeds=e1, mode="train")
    h2, _, _ = forward(NO_MESH, cfg, params, embeds=e2, mode="train")
    # position 0 output must change for an encoder
    assert float(jnp.max(jnp.abs(h1[:, 0] - h2[:, 0]))) > 1e-6

    cfgc = get_reduced_config("qwen3-8b")
    pc = init_params(jax.random.PRNGKey(0), cfgc)
    t1 = jax.random.randint(key, (1, 16), 0, cfgc.model.vocab_size)
    t2 = t1.at[:, -1].set((t1[:, -1] + 1) % cfgc.model.vocab_size)
    c1, _, _ = forward(NO_MESH, cfgc, pc, tokens=t1, mode="train")
    c2, _, _ = forward(NO_MESH, cfgc, pc, tokens=t2, mode="train")
    np.testing.assert_allclose(np.asarray(c1[:, :-1]),
                               np.asarray(c2[:, :-1]), atol=1e-6)


def test_sliding_window_actually_limits_context():
    import repro.configs.base as base
    cfg = get_reduced_config("mixtral-8x7b")
    att = dataclasses.replace(cfg.model.attention, sliding_window=4)
    m = dataclasses.replace(cfg.model, moe=None, attention=att,
                            family="dense")
    cfg = cfg.replace(model=m)
    params = init_params(jax.random.PRNGKey(0), cfg)
    t1 = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 0,
                            cfg.model.vocab_size)
    t2 = t1.at[:, 0].set((t1[:, 0] + 1) % cfg.model.vocab_size)
    h1, _, _ = forward(NO_MESH, cfg, params, tokens=t1, mode="train")
    h2, _, _ = forward(NO_MESH, cfg, params, tokens=t2, mode="train")
    # with window 4 and 2 layers, position 15 cannot see position 0
    np.testing.assert_allclose(np.asarray(h1[:, -1]),
                               np.asarray(h2[:, -1]), atol=1e-6)
    del base


def test_serving_weights_in_compute_dtype():
    """Full-width serving holds bf16 weights (a float32 copy of
    qwen1.5-4b does not fit one chip); reduced configs stay float32."""
    from repro.serve.engine import serving_config
    full = serving_config(get_config("qwen1.5-4b"))
    assert full.train.param_dtype == full.train.compute_dtype == "bfloat16"
    shapes = jax.eval_shape(lambda k: init_params(k, full),
                            jax.random.PRNGKey(0))
    nbytes = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(shapes))
    assert nbytes < 8.5e9 and {s.dtype for s in jax.tree.leaves(shapes)} \
        == {jnp.dtype(jnp.bfloat16)}
    small = get_reduced_config("qwen1.5-4b")
    assert serving_config(small) == small


def test_build_engine_serves_rpc_equal_to_direct():
    """The serve CLI's engine: tokens served over the loopback fabric
    equal the engine's direct path, request by request."""
    from repro.serve.engine import (build_engine, rpc_generate_stream,
                                    serve_stub)
    cfg = get_reduced_config("qwen1.5-4b")
    eng = build_engine(cfg, ServeConfig(max_seq=24, max_new_tokens=4))
    _, channel = eng.serve_loopback()
    rng = np.random.default_rng(0)
    for _ in range(2):
        prompts = rng.integers(0, cfg.model.vocab_size, (2, 8),
                               dtype=np.int32)
        direct = eng.generate(prompts)
        assert np.array_equal(rpc_generate_stream(channel, prompts), direct)
        assert np.array_equal(
            serve_stub(channel).generate((prompts, 0)).result(), direct)


@pytest.mark.parametrize("benchmark", ["ps_throughput", "fully_connected"])
def test_bench_names_the_chips_it_needs(benchmark):
    """One endpoint more than this process has devices: the error says
    how many chips the benchmark needs and what it found."""
    from repro.configs.tfgrpc_bench import BenchConfig
    from repro.core import bench
    have = len(jax.devices())
    need = have + 1
    cfg = BenchConfig(benchmark=benchmark, transport="collective",
                      num_ps=1, num_workers=have, iovec_count=2,
                      categories=("small",))
    if benchmark == "fully_connected":
        cfg = dataclasses.replace(cfg, num_workers=need)
    with pytest.raises(RuntimeError) as e:
        bench.run(cfg)
    msg = str(e.value)
    assert f"needs {need} chips" in msg
    assert f"found {have} {jax.devices()[0].platform} device(s)" in msg
    assert "xla_force_host_platform_device_count" not in msg


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_location(monkeypatch, tmp_path, env_dir):
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / env_dir))
    try:
        got = compile_cache.use_compile_cache()
        if env_dir is None:
            root = compile_cache.CHECKOUT_ROOT
            assert (root / "src" / "repro").is_dir()
            assert got == str(root / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        else:
            assert got == str(tmp_path / env_dir)
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
