"""Pallas TPU kernels (checked against their references with
interpret=True on CPU, and compiled for a described TPU v5e by
tests/test_tpu_compile.py):

  flash_attention — fused GQA attention (causal/SWA/softcap), the
                    transformer hot spot
  rwkv6_scan      — chunked data-dependent-decay WKV recurrence
  payload_pack    — iovec coalescing (the paper's serialized mode)
"""
