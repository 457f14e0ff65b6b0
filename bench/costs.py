"""Operations and bytes a step needs, from shapes alone.

These are the algorithm's counts, not what the compiled program
happens to do: attention counts the positions a query attends to
(causal), and a decode step reads the weights once plus the keys and
values of the positions held, not the whole ``max_seq`` cache. A
program that does less work than this cannot show a roofline share
above 100%.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class DenseDecoder:
    """A dense GQA decoder (Qwen2-style) from a configuration file."""
    layers: int
    d_model: int
    d_ff: int
    heads: int
    kv_heads: int
    d_head: int
    vocab: int
    weight_bytes: int = 2          # bf16 weights
    cache_bytes: int = 2           # bf16 keys and values

    @classmethod
    def from_config(cls, c: dict) -> "DenseDecoder":
        heads = int(c["num_attention_heads"])
        return cls(layers=int(c["num_hidden_layers"]),
                   d_model=int(c["hidden_size"]),
                   d_ff=int(c["intermediate_size"]),
                   heads=heads, kv_heads=int(c["num_key_value_heads"]),
                   d_head=int(c["hidden_size"]) // heads,
                   vocab=int(c["vocab_size"]))

    @property
    def layer_params(self) -> int:
        """Matrix parameters of one layer (biases and norms are
        negligible here)."""
        d, hq = self.d_model, self.heads * self.d_head
        hkv = self.kv_heads * self.d_head
        return d * hq + 2 * d * hkv + hq * d + 3 * d * self.d_ff

    @property
    def head_params(self) -> int:
        return self.d_model * self.vocab

    def attention_flops(self, queries: int, first_position: int) -> int:
        """Q.K and P.V for ``queries`` consecutive positions starting at
        ``first_position``, each attending to every earlier position
        and itself, over all layers."""
        attended = queries * first_position + queries * (queries + 1) // 2
        return 4 * self.heads * self.d_head * attended * self.layers

    def prefill_flops(self, prompt_len: int) -> int:
        """A prompt through every layer, and the head at its last
        position only (the step samples one token)."""
        return (2 * self.layers * self.layer_params * prompt_len
                + 2 * self.head_params
                + self.attention_flops(prompt_len, 0))

    def decode_flops(self, held: int) -> int:
        """One token attending to ``held`` positions (itself included)."""
        return (2 * (self.layers * self.layer_params + self.head_params)
                + self.attention_flops(1, held - 1))

    def decode_bytes(self, held: int) -> int:
        """Every weight once, and the keys and values of ``held``
        positions."""
        weights = (self.layers * self.layer_params + self.head_params) \
            * self.weight_bytes
        kv = 2 * self.layers * self.kv_heads * self.d_head * held \
            * self.cache_bytes
        return weights + kv


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of operations
    over peak FLOP/s and bytes over peak bytes/s."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
