"""Per-architecture smoke tests (deliverable f): reduced same-family
config, one forward/train step on CPU, output shapes + finite values."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_reduced_config, list_archs
from repro.data.pipeline import DataConfig, device_batch, host_batch
from repro.launch import steps as steps_lib
from repro.models import forward, init_params, logits_fn
from repro.optim import optimizer as O
from repro.parallel import NO_MESH

B, S = 2, 32


def _batch(cfg, seed=0):
    key = jax.random.PRNGKey(seed)
    m = cfg.model
    out = {"labels": jax.random.randint(key, (B, S), 0, m.vocab_size)}
    if m.frontend:
        out["embeds"] = jax.random.normal(key, (B, S, m.d_model))
    else:
        out["tokens"] = jax.random.randint(key, (B, S), 0, m.vocab_size)
    return out


@pytest.mark.parametrize("arch", list_archs())
def test_forward_shapes_and_finite(arch):
    cfg = get_reduced_config(arch)
    params = init_params(jax.random.PRNGKey(0), cfg)
    b = _batch(cfg)
    h, states, aux = forward(NO_MESH, cfg, params,
                             tokens=b.get("tokens"),
                             embeds=b.get("embeds"), mode="train")
    assert h.shape == (B, S, cfg.model.d_model)
    assert bool(jnp.all(jnp.isfinite(h)))
    logits = logits_fn(NO_MESH, cfg, params, h)
    assert logits.shape == (B, S, cfg.model.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))


@pytest.mark.parametrize("arch", list_archs())
def test_one_train_step(arch):
    cfg = get_reduced_config(arch)
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt = O.init_opt_state(cfg.train, params)
    step = steps_lib.make_train_step(NO_MESH, cfg, donate=False)
    p2, o2, metrics = step(params, opt, _batch(cfg))
    assert bool(jnp.isfinite(metrics["loss"]))
    # params actually changed
    changed = jax.tree.map(
        lambda a, b2: bool(jnp.any(a != b2)), params, p2)
    assert any(jax.tree.leaves(changed))


def _check_prefill_decode(cfg, prompt: int, steps: int, max_seq: int):
    """Prefill ``prompt`` tokens, decode ``steps`` more one at a time,
    and hold each step's logits to the full forward's at that position."""
    m = cfg.model
    key = jax.random.PRNGKey(1)
    params = init_params(key, cfg)
    n = prompt + steps
    if m.frontend:
        emb = jax.random.normal(key, (B, n, m.d_model))
        fk = dict(embeds=emb)
        pk = dict(embeds=emb[:, :prompt])
        dks = [dict(embeds=emb[:, prompt + i:prompt + i + 1])
               for i in range(steps)]
    else:
        toks = jax.random.randint(key, (B, n), 0, m.vocab_size)
        fk = dict(tokens=toks)
        pk = dict(tokens=toks[:, :prompt])
        dks = [dict(tokens=toks[:, prompt + i:prompt + i + 1])
               for i in range(steps)]
    h_full, _, _ = forward(NO_MESH, cfg, params, mode="train", **fk)
    _, states, _ = forward(NO_MESH, cfg, params, mode="prefill",
                           max_seq=max_seq, **pk)
    for i in range(steps):
        ref = logits_fn(NO_MESH, cfg, params, h_full)[:, prompt + i]
        h_dec, states, _ = forward(NO_MESH, cfg, params, mode="decode",
                                   states=states, **dks[i])
        got = logits_fn(NO_MESH, cfg, params, h_dec)[:, 0]
        err = float(jnp.max(jnp.abs(ref - got))
                    / (jnp.max(jnp.abs(ref)) + 1e-9))
        assert err < 2e-3, (cfg.model.name, i, err)


@pytest.mark.parametrize("arch", [a for a in list_archs()
                                  if not get_reduced_config(a).model
                                  .is_encoder])
def test_prefill_decode_consistency(arch):
    _check_prefill_decode(get_reduced_config(arch), S, 2, S + 4)


@pytest.mark.parametrize("scan_layers", [True, False])
def test_decode_past_ring_wrap(scan_layers):
    """gemma2's local layers hold a ring of ``window`` slots: a 60-token
    prompt and 8 decoded tokens wrap it, in the period scan and in the
    unrolled loop alike (two periods, so each carries a stack of two)."""
    cfg = get_reduced_config("gemma2-9b", n_layers=4)
    assert cfg.model.n_periods == 2
    window = cfg.model.window_at(0)
    assert window is not None and 60 < window < 60 + 8
    cfg = cfg.replace(train=dataclasses.replace(cfg.train,
                                                scan_layers=scan_layers))
    _check_prefill_decode(cfg, 60, 8, 72)


@pytest.mark.parametrize("arch", ["qwen3-8b", "rwkv6-1.6b"])
def test_data_pipeline_determinism(arch):
    cfg = get_reduced_config(arch)
    shape = dataclasses.replace(
        __import__("repro.configs", fromlist=["get_shape"]).get_shape(
            "train_4k"), seq_len=16, global_batch=2)
    a = host_batch(cfg, shape, 3, DataConfig(seed=9))
    b = host_batch(cfg, shape, 3, DataConfig(seed=9))
    c = host_batch(cfg, shape, 4, DataConfig(seed=9))
    for k in a:
        assert (a[k] == b[k]).all()
    assert any((a[k] != c[k]).any() for k in a)
