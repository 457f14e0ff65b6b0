#!/usr/bin/env python3
"""Smoke run of the system's main paths on a TPU, in one process.

  python chip_smoke.py             # one chip: serving + the kernels
  python chip_smoke.py --chips 4   # four chips: the paper's channels

One chip: serves qwen1.5-4b at its published widths (random bf16
weights from seed 0) over the loopback RPC fabric, exactly as
``repro.launch.serve`` does, and checks the served tokens against the
engine's direct path; then runs the payload_pack, flash_attention and
rwkv6_scan kernels compiled for the chip at real widths against their
references. Four chips: the P2P, PS and fully-connected benchmarks of
``repro.core.bench`` at the paper's Medium and Large payloads, with what
arrived on each chip checked against the benchmark's round schedule.

Times printed here are labelled smoke timings: one short run, not a
benchmark. The last line of standard output is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every phase passed.
With no TPU, or outside a checkout of the repository, the script exits
non-zero before any phase runs.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch.compile_cache import use_compile_cache  # noqa: E402

ARCH = "qwen1.5-4b"
BATCH, PROMPT_LEN, NEW_TOKENS, REQUESTS, SEED = 4, 128, 16, 3, 0
#: the paper's Large payload (Table 1): ten 1 MiB iovec buffers
LARGE_MB = 10


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def timing(label: str, seconds: float) -> None:
    print(f"smoke timing (one run, not a benchmark): {label}: "
          f"{seconds * 1e3:.1f} ms", flush=True)


def compiled(fn, *args):
    """``fn`` jitted and compiled for ``args``; fails unless the program
    holds a Mosaic kernel, so no kernel phase can run interpreted."""
    exe = jax.jit(fn).lower(*args).compile()
    check("tpu_custom_call" in exe.as_text(),
          f"{getattr(fn, '__name__', fn)}: no compiled kernel")
    return exe


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def serve_phase() -> None:
    from repro.configs import get_config
    from repro.serve.engine import (ServeConfig, build_engine,
                                    rpc_generate_stream)
    acfg = get_config(ARCH)
    t0 = time.perf_counter()
    engine = build_engine(acfg, ServeConfig(
        max_seq=PROMPT_LEN + NEW_TOKENS + 8, max_new_tokens=NEW_TOKENS,
        temperature=0.0, seed=SEED), seed=SEED)
    jax.block_until_ready(engine.params)
    timing(f"{ARCH} weight init (jit compile included)",
           time.perf_counter() - t0)
    dtypes = {str(a.dtype) for a in jax.tree.leaves(engine.params)}
    check(dtypes == {acfg.train.compute_dtype},
          f"serving weights are {dtypes}, not {acfg.train.compute_dtype}")
    _, channel = engine.serve_loopback()
    rng = np.random.default_rng(SEED)
    vocab = acfg.model.vocab_size
    prompts = [rng.integers(0, vocab, (BATCH, PROMPT_LEN), dtype=np.int32)
               for _ in range(REQUESTS)]
    served = []
    for i, p in enumerate(prompts):
        t0 = time.perf_counter()
        served.append(rpc_generate_stream(channel, p))
        timing(f"request {i} [rpc/stream] batch={BATCH} "
               f"prompt={PROMPT_LEN} new={NEW_TOKENS}"
               + (" (includes prefill + decode compile)" if i == 0 else ""),
               time.perf_counter() - t0)
    for i, p in enumerate(prompts):
        t0 = time.perf_counter()
        direct = engine.generate(p)
        timing(f"request {i} [direct]", time.perf_counter() - t0)
        out = served[i]
        check(out.shape == (BATCH, NEW_TOKENS), f"request {i}: {out.shape}")
        check(bool(((out >= 0) & (out < vocab)).all()),
              f"request {i}: token out of vocabulary")
        check(np.array_equal(out, direct),
              f"request {i}: rpc tokens {out[0].tolist()} != direct "
              f"{direct[0].tolist()}")
        print(f"request {i}: rpc == direct, sample={out[0][:8].tolist()}",
              flush=True)


def pack_phase() -> None:
    from repro.kernels.payload_pack import pack, unpack
    from repro.rpc import framing
    rng = np.random.default_rng(SEED)
    bufs = [rng.integers(0, 256, 1 << 20, dtype=np.uint8)
            for _ in range(LARGE_MB)]
    frame = framing.make_frame(1, "exchange", bufs, serialized=True)
    parts = [framing.header_bytes(frame)] + bufs
    sizes = [p.size for p in parts]
    dev = [jnp.asarray(p) for p in parts]
    pack_exe = compiled(lambda *b: pack(b, interpret=False)[0], *dev)
    wire_np = framing.encode(frame, backend="numpy")[0]
    t0 = time.perf_counter()
    packed = jax.block_until_ready(pack_exe(*dev))
    timing(f"payload_pack pack {LARGE_MB} x 1 MiB", time.perf_counter() - t0)
    check(np.array_equal(np.asarray(packed), wire_np),
          "payload_pack: packed bytes differ from the numpy framing path")
    unpack_exe = compiled(lambda p: unpack(p, sizes, interpret=False),
                          packed)
    t0 = time.perf_counter()
    outs = jax.block_until_ready(unpack_exe(packed))
    timing(f"payload_pack unpack {LARGE_MB} x 1 MiB",
           time.perf_counter() - t0)
    for a, b in zip(parts, outs):
        check(np.array_equal(a, np.asarray(b)),
              "payload_pack: unpacked buffer differs")
    # the fabric's own kernel backend, end to end
    wire_k = framing.encode(frame, backend="kernel")
    check(np.array_equal(wire_k[0], wire_np),
          "framing backend=kernel differs from backend=numpy")
    got = framing.decode(wire_k, backend="kernel").bufs
    check(all(np.array_equal(a, b) for a, b in zip(bufs, got)),
          "framing decode backend=kernel differs")
    print(f"payload_pack: {LARGE_MB} x 1 MiB byte-identical to numpy "
          f"framing", flush=True)


def attention_phase() -> None:
    from repro.configs import get_config
    from repro.kernels.flash_attention import attention_ref, flash_attention
    att = get_config(ARCH).model.attention
    seq = 2048
    ks = jax.random.split(jax.random.PRNGKey(SEED), 3)
    q = jax.random.normal(ks[0], (1, seq, att.n_heads, att.d_head),
                          jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, seq, att.n_kv_heads, att.d_head),
                          jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, seq, att.n_kv_heads, att.d_head),
                          jnp.bfloat16)
    exe = compiled(lambda q, k, v: flash_attention(
        q, k, v, True, None, None, None, 128, 128, False), q, k, v)
    t0 = time.perf_counter()
    out = jax.block_until_ready(exe(q, k, v))
    timing(f"flash_attention S={seq} H={att.n_heads} d={att.d_head}",
           time.perf_counter() - t0)
    with jax.default_matmul_precision("float32"):
        ref = attention_ref(q, k, v, causal=True, window=None,
                            softcap=None, scale=att.d_head ** -0.5)
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    err = float(np.max(np.abs(out - ref)))
    print(f"flash_attention: max |kernel - ref| = {err:.3g}", flush=True)
    check(np.allclose(out, ref, atol=2e-2, rtol=2e-2),
          f"flash_attention differs from its reference by {err:.3g}")


def rwkv_phase() -> None:
    from repro.configs import get_config
    from repro.kernels.rwkv6_scan import rwkv6_ref, rwkv6_scan
    m = get_config("rwkv6-1.6b").model
    hs = m.ssm.head_size
    bh, seq = m.d_model // hs, 2048
    ks = jax.random.split(jax.random.PRNGKey(SEED), 6)
    r = jax.random.normal(ks[0], (bh, seq, hs))
    k = jax.random.normal(ks[1], (bh, seq, hs)) * 0.5
    v = jax.random.normal(ks[2], (bh, seq, hs))
    lw = -jnp.exp(jax.random.normal(ks[3], (bh, seq, hs)) - 1.0)
    s0 = jax.random.normal(ks[4], (bh, hs, hs)) * 0.1
    u = jax.random.normal(ks[5], (bh, hs)) * 0.5
    exe = compiled(lambda *a: rwkv6_scan(*a, interpret=False),
                   r, k, v, lw, s0, u)
    t0 = time.perf_counter()
    y, s_t = jax.block_until_ready(exe(r, k, v, lw, s0, u))
    timing(f"rwkv6_scan {bh} heads x {hs}, S={seq}",
           time.perf_counter() - t0)
    with jax.default_matmul_precision("float32"):
        y_ref, s_ref = rwkv6_ref(r, k, v, lw, s0, u)
    for name, got, want in (("y", y, y_ref), ("state", s_t, s_ref)):
        got, want = np.asarray(got), np.asarray(want)
        rel = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
        print(f"rwkv6_scan {name}: max |kernel - ref| / max |ref| = "
              f"{rel:.3g}", flush=True)
        check(np.isfinite(got).all() and rel < 5e-2,
              f"rwkv6_scan {name} differs from its reference ({rel:.3g})")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def _route(rows: np.ndarray, rounds) -> np.ndarray:
    """ppermute semantics in numpy: each round moves row s to row d for
    every (s, d) pair; a row that receives nothing becomes zeros."""
    for perm in rounds:
        nxt = np.zeros_like(rows)
        for s, d in perm:
            nxt[d] = rows[s]
        rows = nxt
    return rows


def _expected(cfg, spec, n: int):
    """What each chip should hold after one iteration, from the same
    round lists the channels compile."""
    from repro.core import channels as ch
    rows = ch.host_payload(spec, n, seed=cfg.seed)
    serialized = cfg.mode == "serialized"
    if cfg.benchmark == "p2p_bandwidth":
        fwd, bwd = [[(0, 1)]], [[(1, 0)]]
        if serialized:
            sent = _route(np.concatenate(rows, axis=1), fwd)
            return [sent, _route(sent[:, :64], bwd)]
        sent = [_route(b, fwd) for b in rows]
        return sent + [_route(sent[0][:, :64], bwd)]
    if cfg.benchmark == "p2p_latency":
        rounds = [[(0, 1)], [(1, 0)]]
    elif cfg.benchmark == "ps_throughput":
        ps = list(range(cfg.num_ps))
        workers = list(range(cfg.num_ps, cfg.num_ps + cfg.num_workers))
        rounds = (ch.bipartite_schedule(ps, workers)
                  + ch.bipartite_schedule(workers, ps))
    else:
        rounds = ch.all_to_all_schedule(cfg.num_workers)
    return [_route(b, rounds) for b in rows]


def _check_placed(name: str, outputs, expected, n: int) -> None:
    check(len(outputs) == len(expected),
          f"{name}: {len(outputs)} outputs, expected {len(expected)}")
    for i, (out, want) in enumerate(zip(outputs, expected)):
        shards = out.addressable_shards
        devices = {s.device for s in shards}
        check(len(devices) == n,
              f"{name} output {i} spans {len(devices)} devices, not {n}")
        for s in shards:
            check(np.array_equal(np.asarray(s.data), want[s.index]),
                  f"{name} output {i}: rows {s.index} on {s.device} differ "
                  f"from the schedule")


def channels_phase(n: int) -> None:
    from repro.configs.tfgrpc_bench import BenchConfig
    from repro.core import bench
    from repro.core.payload import generate_spec
    runs = []
    for payload in ("medium", "large"):
        for mode in ("non_serialized", "serialized"):
            base = BenchConfig(categories=(payload,), iovec_count=10,
                               mode=mode, warmup_s=0.2, duration_s=0.5,
                               seed=SEED)
            runs += [
                dataclasses.replace(base, benchmark="p2p_latency"),
                dataclasses.replace(base, benchmark="p2p_bandwidth"),
                # the paper's §4.5 runs 2 PS x 3 workers (five
                # endpoints); four chips hold 1 PS x 3 workers
                dataclasses.replace(base, benchmark="ps_throughput",
                                    num_ps=1, num_workers=3),
                dataclasses.replace(base, benchmark="fully_connected",
                                    num_workers=n, transport="collective"),
            ]
    for cfg in runs:
        name = f"{cfg.benchmark}/{cfg.categories[0]}/{cfg.mode}"
        st = bench.run(cfg)
        _check_placed(name, st.outputs,
                      _expected(cfg, generate_spec(cfg), n), n)
        derived = ", ".join(f"{k}={v:.6g}" for k, v in st.derived.items())
        print(f"{name}: arrived as scheduled on {n} chips; {derived}",
              flush=True)
        timing(f"{name} mean iteration ({st.n_iters} iterations)",
               st.mean_s)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serving + kernels; 4: the channels across "
                         "chips, and nothing else")
    args = ap.parse_args()
    use_compile_cache()
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"device: {device}", flush=True)
    check(jax.default_backend() == "tpu",
          f"no TPU: JAX runs on {jax.default_backend()}")
    check(len(devices) >= args.chips,
          f"--chips {args.chips} needs {args.chips} chips, found "
          f"{len(devices)}")
    if args.chips == 4:
        channels_phase(4)
    else:
        for phase in (serve_phase, pack_phase, attention_phase,
                      rwkv_phase):
            t0 = time.perf_counter()
            phase()
            print(f"phase {phase.__name__}: passed "
                  f"({time.perf_counter() - t0:.1f} s)", flush=True)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
