"""Operations and bytes a served step needs, from the configuration's shapes.

These are the yardstick's own counts, worked out from the published sizes in
``bench/configs/<config>.json``, never from what the program happens to
compute: a program that does more work than this (padding, a gather over
empty slots, a float32 copy of the cache) reads as a lower share.
"""

from __future__ import annotations

from dataclasses import dataclass

BF16 = 2  # bytes


@dataclass(frozen=True)
class Shapes:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int

    @classmethod
    def of(cls, cfg: dict) -> "Shapes":
        return cls(
            layers=cfg["num_hidden_layers"],
            d=cfg["hidden_size"],
            heads=cfg["num_attention_heads"],
            kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg["head_dim"],
            ff=cfg["intermediate_size"],
            vocab=cfg["vocab_size"],
        )

    @property
    def layer_params(self) -> int:
        """Matmul weights of one layer (q, k, v, o, gate, up, down)."""
        attn = self.d * self.head_dim * (2 * self.heads + 2 * self.kv_heads)
        return attn + 3 * self.d * self.ff

    @property
    def head_params(self) -> int:
        """The output head (the embedding table where it is tied); an untied
        model's table is only gathered from, a row a token."""
        return self.d * self.vocab

    @property
    def kv_bytes_per_token(self) -> int:
        """K and V of one token over all layers, bf16."""
        return 2 * self.layers * self.kv_heads * self.head_dim * BF16


def token_flops(s: Shapes, keys: int) -> float:
    """One token through every layer, attending over ``keys`` positions
    (itself included): 2 per multiply-add of the projections and the FFN,
    4 * heads * head_dim per key for scores and values."""
    return s.layers * (2.0 * s.layer_params + 4.0 * s.heads * s.head_dim * keys)


def logits_flops(s: Shapes) -> float:
    """One row of logits through the output head."""
    return 2.0 * s.head_params


def prefill_flops(s: Shapes, start: int, end: int) -> float:
    """Prompt positions ``[start, end)``, each attending over its causal
    prefix: the sum of ``token_flops`` without a per-token loop."""
    n = end - start
    keys = (end * (end + 1) - start * (start + 1)) // 2
    return s.layers * (2.0 * s.layer_params * n + 4.0 * s.heads * s.head_dim * keys)


def decode_step_bytes(s: Shapes, keys: list[int]) -> float:
    """Least HBM traffic of one decode step: every weight once (the output
    head's table, not the rows an untied embedding gathers) and the K/V of
    each slot's live context: ``keys - 1`` positions read and the new one
    written."""
    weights = (s.layers * s.layer_params + s.head_params) * BF16
    return weights + s.kv_bytes_per_token * sum(keys)


def decode_attn_least_s(s: Shapes, keys: list[int], peak: dict, chips: int = 1) -> float:
    """Least time of one decode step's attention at the chips' peaks: the
    decoding slots' live K/V (every layer, bf16) over HBM bandwidth, or
    4 * heads * head_dim FLOPs per attended key over the bf16 peak,
    whichever is longer."""
    n = sum(keys)
    by_bytes = s.kv_bytes_per_token * n / (chips * peak["hbm_bytes_per_s"])
    by_flops = s.layers * 4.0 * s.heads * s.head_dim * n / (chips * peak["bf16_flops_per_s"])
    return max(by_bytes, by_flops)
