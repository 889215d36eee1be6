"""Operations and bytes that a step of a dense decoder needs, from shapes.

These count what the algorithm needs, not what a program happens to
do: causal attention over the live context only, the vocabulary as
published (not padded), weights read once per call.  A roofline share
built on them is the least time the chip could take over the time the
program took, so it cannot pass 100% unless a count here is wrong.

All sizes come from a configuration file's ``config`` (Hugging Face
keys).  Weights and cache are bf16 (2 bytes) as served.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterable, Tuple


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    tied: bool
    itemsize: int = 2

    @classmethod
    def of(cls, config: Dict[str, Any]) -> "Dims":
        c = config["config"] if "config" in config else config
        heads = int(c["num_attention_heads"])
        return cls(layers=int(c["num_hidden_layers"]),
                   d=int(c["hidden_size"]), heads=heads,
                   kv_heads=int(c.get("num_key_value_heads", heads)),
                   head_dim=int(c.get("head_dim")
                                or c["hidden_size"] // heads),
                   ffn=int(c["intermediate_size"]),
                   vocab=int(c["vocab_size"]),
                   tied=bool(c.get("tie_word_embeddings", False)))

    # -- weights -------------------------------------------------------
    @property
    def layer_matmul_params(self) -> int:
        d, hd = self.d, self.head_dim
        attn = d * self.heads * hd * 2 + d * self.kv_heads * hd * 2
        return attn + 3 * d * self.ffn

    @property
    def matmul_params(self) -> int:
        """Multiply-accumulate weights per token: every layer's
        projections and the vocabulary head."""
        return self.layers * self.layer_matmul_params + self.d * self.vocab

    @property
    def weight_bytes(self) -> int:
        """Weights one call reads: the projections, the head, and the
        norm scales (two per layer and the final one)."""
        norms = (2 * self.layers + 1) * self.d
        return (self.matmul_params + norms) * self.itemsize

    @property
    def kv_bytes_per_token(self) -> int:
        return self.layers * 2 * self.kv_heads * self.head_dim * \
            self.itemsize

    # -- one call --------------------------------------------------------
    def attention_flops(self, query_pos: int) -> int:
        """Score and value products of one query that sees
        ``query_pos`` keys (itself included), over all layers."""
        return self.layers * 4 * self.heads * self.head_dim * query_pos

    def prefill(self, prompt_len: int) -> Tuple[float, float]:
        """(flops, bytes) of prefilling one prompt of ``prompt_len``
        tokens: every position through the layers, causal attention, the
        head at the last position only; reads the weights and the
        prompt's embedding rows, writes the prompt's K/V."""
        s = prompt_len
        layer = self.layers * self.layer_matmul_params
        flops = 2 * s * layer + 2 * self.d * self.vocab
        flops += self.layers * 2 * self.heads * self.head_dim * s * (s + 1)
        nbytes = self.weight_bytes + s * self.d * self.itemsize \
            + s * self.kv_bytes_per_token
        return float(flops), float(nbytes)

    def decode(self, contexts: Iterable[int]) -> Tuple[float, float]:
        """(flops, bytes) of one decode step over active rows whose new
        token sits at position ``c - 1`` (``c`` keys including itself):
        reads the weights once, each row's live K/V, writes one token of
        K/V per row."""
        ctx = [int(c) for c in contexts]
        n = len(ctx)
        flops = 2 * n * self.matmul_params + sum(
            self.attention_flops(c) for c in ctx)
        nbytes = self.weight_bytes + n * self.d * self.itemsize \
            + (sum(ctx) + n) * self.kv_bytes_per_token
        return float(flops), float(nbytes)


def least_seconds(flops: float, nbytes: float,
                  peaks: Dict[str, Any]) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / float(peaks["bf16_flops_per_s"]),
               nbytes / float(peaks["hbm_bytes_per_s"]))
