"""Operations and bytes a dense decoder's tokens need, from its shapes.

These count the work the model requires, not what a given program does:
no padding, no positions beyond a request's own, no logits for prompt
positions other than the last, and K/V counted once per KV head.  A program
that does more work reads a lower share of the peak; none can read more
than 100% of it.

``d`` is a ``bench.modelcfg.Dims``.  One multiply-add is two operations.
"""
from __future__ import annotations

from typing import Mapping

KV_BYTES = 2                # bfloat16 K and V rows


def matmul_flops(d) -> int:
    """Projections and MLP of every layer, for one token."""
    D, H, KV, hd, F = d.d_model, d.heads, d.kv_heads, d.head_dim, d.d_ff
    per_layer = D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * F
    return 2 * d.layers * per_layer


def attention_flops(d, keys: int) -> int:
    """Scores and weighted values of every layer, for one query that
    attends ``keys`` positions (itself included)."""
    return 4 * d.layers * d.heads * d.head_dim * keys


def head_flops(d) -> int:
    """Logits over the vocabulary, for one position."""
    return 2 * d.d_model * d.vocab


def prefill_flops(d, prompt_len: int) -> int:
    """A whole prompt: every token through every layer, causal attention,
    and logits at its last position only."""
    P = prompt_len
    return (P * matmul_flops(d) + 4 * d.layers * d.heads * d.head_dim
            * P * (P + 1) // 2 + head_flops(d))


def decode_flops(d, keys: int) -> int:
    """One generated token whose query attends ``keys`` cache rows."""
    return matmul_flops(d) + attention_flops(d, keys) + head_flops(d)


def kv_bytes(d, rows: int) -> int:
    """K and V of ``rows`` cache rows over every layer, once per KV head."""
    return rows * d.layers * 2 * d.kv_heads * d.head_dim * KV_BYTES


def decode_weight_bytes(d, stored: Mapping[str, int], active: int) -> int:
    """Weight bytes one decode step over ``active`` requests must read:
    every stored byte of every layer tensor and of the head, and the
    ``active`` embedding rows it gathers.  ``stored`` maps tensor names to
    the bytes they are stored in (the entropy-coded payload of quantized
    tensors, four bytes an element of float32 ones); a tied head reads the
    whole embedding."""
    total = sum(b for name, b in stored.items()
                if name.startswith("layers/") or name in ("final_norm",
                                                          "lm_head"))
    if d.tie:
        total += stored["embed"]
    else:
        total += active * stored["embed"] // d.vocab
    return total
