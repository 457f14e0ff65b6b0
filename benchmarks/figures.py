"""One benchmark per paper table/figure (deliverable d).

Each ``fig*`` function returns a list of CSV rows
(name, us_per_call, derived...). Two kinds of numbers appear:
  measured_*  — real wall-clock on host devices (the container's
                "cluster"; relative trends)
  model_*     — alpha-beta projections for the paper's clusters
                (calibrated in core.netmodel; the reproduction numbers)
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from repro.configs.tfgrpc_bench import BenchConfig, PS_THROUGHPUT_CONFIG
from repro.core import bench as bench_lib
from repro.core.netmodel import CLUSTER_A, CLUSTER_B, NETWORKS
from repro.core.payload import PayloadSpec, generate_spec

FAST = dict(warmup_s=0.15, duration_s=0.4)

Row = Dict[str, object]


def _row(name: str, us: float, **derived) -> Row:
    return {"name": name, "us_per_call": us, **derived}


def fig7_p2p_latency_serialized() -> List[Row]:
    """Fig 7: 64KB serialized payload latency across Cluster A networks;
    claim: serialization overhead is constant across networks."""
    spec = generate_spec(BenchConfig(
        scheme="uniform", iovec_count=4, categories=("medium",),
        medium_bytes=16 * 1024))  # 4 x 16KB = 64KB payload
    rows = []
    for net in CLUSTER_A:
        n = NETWORKS[net]
        ser = n.rtt(spec, serialized=True)
        raw = n.rtt(spec, serialized=False)
        rows.append(_row(f"fig7/model/{net}", ser * 1e6,
                         serialization_overhead_us=(ser - raw) * 1e6))
    st = bench_lib.p2p_latency(BenchConfig(
        mode="serialized", scheme="uniform", iovec_count=4,
        categories=("medium",), medium_bytes=16 * 1024, **FAST))
    rows.append(_row("fig7/measured/host", st.mean_s * 1e6,
                     iters=st.n_iters))
    return rows


def fig8_9_p2p_latency(cluster: str) -> List[Row]:
    """Figs 8/9: non-serialized P2P latency, three payload schemes."""
    nets = CLUSTER_A if cluster == "A" else CLUSTER_B
    rows = []
    for scheme in ("uniform", "random", "skew"):
        spec = generate_spec(BenchConfig(scheme=scheme))
        for net in nets:
            rows.append(_row(f"fig{'8' if cluster == 'A' else '9'}/model/"
                             f"{scheme}/{net}",
                             NETWORKS[net].rtt(spec) * 1e6,
                             payload_bytes=spec.total_bytes))
        st = bench_lib.p2p_latency(BenchConfig(scheme=scheme, **FAST))
        rows.append(_row(f"fig{'8' if cluster == 'A' else '9'}/measured/"
                         f"{scheme}/host", st.mean_s * 1e6))
    return rows


def fig10_latency_vs_iovec_count() -> List[Row]:
    """Fig 10: Large-only payloads, iovec count 2..10, IPoIB vs RDMA."""
    rows = []
    for count in range(2, 11, 2):
        cfg = BenchConfig(scheme="uniform", iovec_count=count,
                          categories=("large",))
        spec = generate_spec(cfg)
        for net in ("ipoib_edr", "rdma_edr"):
            rows.append(_row(f"fig10/model/{net}/iovec{count}",
                             NETWORKS[net].rtt(spec) * 1e6,
                             payload_mb=spec.total_bytes / 1e6))
        st = bench_lib.p2p_latency(BenchConfig(
            scheme="uniform", iovec_count=count, categories=("large",),
            **FAST))
        rows.append(_row(f"fig10/measured/host/iovec{count}",
                         st.mean_s * 1e6))
    return rows


def fig11_12_bandwidth(cluster: str) -> List[Row]:
    nets = CLUSTER_A if cluster == "A" else CLUSTER_B
    fig = "11" if cluster == "A" else "12"
    rows = []
    for scheme in ("uniform", "random", "skew"):
        spec = generate_spec(BenchConfig(scheme=scheme))
        for net in nets:
            bw = NETWORKS[net].bandwidth(spec)
            rows.append(_row(f"fig{fig}/model/{scheme}/{net}",
                             spec.total_bytes / (bw * 1e6) * 1e6,
                             MBps=bw))
        st = bench_lib.p2p_bandwidth(BenchConfig(scheme=scheme, **FAST))
        rows.append(_row(f"fig{fig}/measured/{scheme}/host",
                         st.mean_s * 1e6, MBps=st.derived["MBps"]))
    return rows


def fig13_14_ps_throughput(cluster: str) -> List[Row]:
    nets = CLUSTER_A if cluster == "A" else CLUSTER_B
    fig = "13" if cluster == "A" else "14"
    rows = []
    for scheme in ("uniform", "random", "skew"):
        spec = generate_spec(BenchConfig(scheme=scheme))
        for net in nets:
            tp = NETWORKS[net].ps_throughput(spec, 2, 3)
            rows.append(_row(f"fig{fig}/model/{scheme}/{net}",
                             1e6 / tp, rpcs_per_s=tp))
        cfg = BenchConfig(benchmark="ps_throughput", num_ps=2,
                          num_workers=3, scheme=scheme, **FAST)
        st = bench_lib.ps_throughput(cfg)
        rows.append(_row(f"fig{fig}/measured/{scheme}/host",
                         st.mean_s * 1e6,
                         rpcs_per_s=st.derived["rpcs_per_s"]))
    return rows


def paper_claims() -> List[Row]:
    """The headline ratios vs the paper's reported numbers."""
    from repro.core.netmodel import paper_ratio_report
    rows = []
    for k, v in paper_ratio_report().items():
        rows.append(_row(f"claims/{k}", 0.0, target=v["target"],
                         model=round(v["model"], 3),
                         rel_err=round(v["rel_err"], 3)))
    return rows


def arch_payload_ps() -> List[Row]:
    """Framework tie-in: PS-throughput with payloads derived from the
    assigned architectures' parameter histograms (core.payload.from_arch)
    — what a PS round for each model family actually looks like."""
    from repro.configs import get_config
    from repro.core.payload import from_arch
    rows = []
    for arch in ("qwen3-8b", "mixtral-8x7b", "kimi-k2-1t-a32b",
                 "rwkv6-1.6b"):
        spec = from_arch(get_config(arch))
        for net in ("rdma_edr", "tpu_ici", "tpu_dcn"):
            tp = NETWORKS[net].ps_throughput(spec, 2, 3)
            rows.append(_row(f"arch_ps/{arch}/{net}", 1e6 / tp,
                             rpcs_per_s=tp,
                             payload_mb=spec.total_bytes / 1e6))
    return rows


def fsdp_primitive() -> List[Row]:
    """The SPMD-native PS (all_gather + psum_scatter), measured on host
    devices — the primitive pair our fsdp/ps_mode training emits."""
    import jax
    from repro.core import channels as ch
    mesh = ch.make_net_mesh()
    spec = generate_spec(BenchConfig())
    bufs = ch.device_payload(mesh, spec)
    fn = ch.fsdp_pull_push_fn(mesh, spec.n_buffers)
    times, _ = bench_lib._timed_loop(fn, bufs, 0.15, 0.4)
    ici = NETWORKS["tpu_ici"]
    n = mesh.shape[ch.AXIS]
    per_dev = spec.total_bytes
    model_s = 2 * per_dev * (n - 1) / n / ici.beta_Bps
    return [_row("fsdp_pull_push/measured/host",
                 float(np.mean(times)) * 1e6, devices=n),
            _row("fsdp_pull_push/model/tpu_ici", model_s * 1e6,
                 payload_bytes=per_dev)]


def extension_dcn_channel() -> List[Row]:
    """Beyond-paper (the paper's future work asks for other channels):
    cross-POD P2P — the DCN hop of the multi-pod mesh. Measured on host
    devices split into two 'pods'; projected for ICI vs DCN vs the
    paper's best NIC."""
    import jax
    from repro.core import channels as ch
    mesh = ch.make_net_mesh()
    n = mesh.shape[ch.AXIS]
    spec = generate_spec(BenchConfig(scheme="skew"))
    bufs = ch.device_payload(mesh, spec)
    rows = []
    # intra-"pod" (neighbors 0->1) vs cross-"pod" (0 -> n/2)
    for name, dst in (("intra_pod", 1), ("cross_pod", n // 2)):
        fn = ch.p2p_echo_fn(mesh, spec.n_buffers, src=0, dst=dst)
        times, _ = bench_lib._timed_loop(fn, bufs, 0.15, 0.4)
        rows.append(_row(f"ext_dcn/measured/{name}",
                         float(np.mean(times)) * 1e6))
    for net in ("tpu_ici", "tpu_dcn", "rdma_edr"):
        rows.append(_row(f"ext_dcn/model/{net}",
                         NETWORKS[net].rtt(spec) * 1e6,
                         payload_mb=spec.total_bytes / 1e6))
    return rows


def extension_grad_compression() -> List[Row]:
    """Beyond-paper: DP gradient compression with error feedback —
    convergence cost of shrinking the PS 'push' payload 2x (bf16) / 4x
    (int8 numerics). 30 real train steps on a reduced qwen3."""
    import dataclasses
    import jax
    from repro.configs import get_reduced_config, get_shape
    from repro.data.pipeline import device_batch, host_batch
    from repro.launch import steps as steps_lib
    from repro.models import init_params
    from repro.optim import optimizer as O
    from repro.parallel import NO_MESH

    shape = dataclasses.replace(get_shape("train_4k"), seq_len=64,
                                global_batch=4)
    rows = []
    for comp in (None, "bf16", "int8"):
        cfg = get_reduced_config("qwen3-8b", n_layers=2)
        cfg = cfg.replace(train=dataclasses.replace(
            cfg.train, grad_compression=comp, learning_rate=3e-3))
        params = init_params(jax.random.PRNGKey(0), cfg)
        opt = O.init_opt_state(cfg.train, params)
        step = steps_lib.make_train_step(NO_MESH, cfg, donate=False)
        loss = None
        for i in range(30):
            b = device_batch(NO_MESH, host_batch(cfg, shape, i))
            params, opt, m = step(params, opt, b)
            loss = float(m["loss"])
        wire = {None: 1.0, "bf16": 0.5, "int8": 0.25}[comp]
        rows.append(_row(f"ext_compress/{comp or 'fp32'}", 0.0,
                         final_loss=round(loss, 4),
                         push_wire_fraction=wire))
    return rows


ALL_FIGURES = {
    "fig7": fig7_p2p_latency_serialized,
    "fig8_clusterA": lambda: fig8_9_p2p_latency("A"),
    "fig9_clusterB": lambda: fig8_9_p2p_latency("B"),
    "fig10": fig10_latency_vs_iovec_count,
    "fig11_clusterA": lambda: fig11_12_bandwidth("A"),
    "fig12_clusterB": lambda: fig11_12_bandwidth("B"),
    "fig13_clusterA": lambda: fig13_14_ps_throughput("A"),
    "fig14_clusterB": lambda: fig13_14_ps_throughput("B"),
    "paper_claims": paper_claims,
    "arch_payload_ps": arch_payload_ps,
    "fsdp_primitive": fsdp_primitive,
    "extension_dcn": extension_dcn_channel,
    "extension_compression": extension_grad_compression,
}
