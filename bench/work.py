"""Operations and bytes a decoder's tokens need, from its shapes.

These count the work the model requires, not what a given program does:
no padding, no positions beyond a request's own, no logits for prompt
positions other than the last, and K/V counted once per KV head.  A program
that does more work reads a lower share of the peak; none can read more
than 100% of it.

``d`` is a ``bench.modelcfg.Dims``.  One multiply-add is two operations.

The counts are a dense block's.  A configuration whose block is another
brings its own ``matmul_flops(d)`` and ``decode_weight_bytes(d, stored,
active)`` in its adapter (``bench/modelcfg.py``); the functions here take
that adapter as ``adapter`` and use its function of the same name where it
has one.  The sparse-expert counts at the end are the benchmark's
definition of such a block's work, for an adapter to name.
"""
from __future__ import annotations

from fractions import Fraction
from typing import Mapping

KV_BYTES = 2                # bfloat16 K and V rows


def _attention_projections(d) -> int:
    """Multiply-adds of one layer's Q, K, V and output projections."""
    D, H, KV, hd = d.d_model, d.heads, d.kv_heads, d.head_dim
    return D * H * hd + 2 * D * KV * hd + H * hd * D


def matmul_flops(d, adapter=None) -> int:
    """Projections and MLP of every layer, for one token."""
    own = getattr(adapter, "matmul_flops", None)
    if own is not None:
        return own(d)
    return 2 * d.layers * (_attention_projections(d) + 3 * d.d_model * d.d_ff)


def attention_flops(d, keys: int) -> int:
    """Scores and weighted values of every layer, for one query that
    attends ``keys`` positions (itself included)."""
    return 4 * d.layers * d.heads * d.head_dim * keys


def head_flops(d) -> int:
    """Logits over the vocabulary, for one position."""
    return 2 * d.d_model * d.vocab


def prefill_flops(d, prompt_len: int, adapter=None) -> int:
    """A whole prompt: every token through every layer, causal attention,
    and logits at its last position only."""
    P = prompt_len
    return (P * matmul_flops(d, adapter) + 4 * d.layers * d.heads * d.head_dim
            * P * (P + 1) // 2 + head_flops(d))


def decode_flops(d, keys: int, adapter=None) -> int:
    """One generated token whose query attends ``keys`` cache rows."""
    return matmul_flops(d, adapter) + attention_flops(d, keys) + head_flops(d)


def kv_bytes(d, rows: int) -> int:
    """K and V of ``rows`` cache rows over every layer, once per KV head."""
    return rows * d.layers * 2 * d.kv_heads * d.head_dim * KV_BYTES


def decode_weight_bytes(d, stored: Mapping[str, int], active: int,
                        adapter=None) -> int:
    """Weight bytes one decode step over ``active`` requests must read:
    every stored byte of every layer tensor and of the head, and the
    ``active`` embedding rows it gathers.  ``stored`` maps tensor names to
    the bytes they are stored in (the entropy-coded payload of quantized
    tensors, four bytes an element of float32 ones); a tied head reads the
    whole embedding."""
    own = getattr(adapter, "decode_weight_bytes", None)
    if own is not None:
        return own(d, stored, active)
    total = sum(b for name, b in stored.items()
                if name.startswith("layers/") or name in ("final_norm",
                                                          "lm_head"))
    if d.tie:
        total += stored["embed"]
    else:
        total += active * stored["embed"] // d.vocab
    return total


# ------------------------------------------------------------ sparse experts
#
# A sparse-expert block puts a router and routed expert MLPs, and where the
# model has one a shared expert, in place of the dense MLP.  Its sizes are
# ``Dims.family_sizes``:
#   experts       routed experts of the published model: the router's width
#   experts_held  routed experts this chip holds (its share of expert
#                 parallelism); the program computes their part alone
#   top_k         routed experts each token goes to
#   expert_d_ff   one routed expert's width
#   shared_d_ff   the shared expert's width (0: none)
#   shared_gate   outputs of the shared expert's sigmoid gate over the
#                 hidden state (0: ungated)

# the program's names for the stacked weights of the routed experts held
EXPERT_TENSORS = ("layers/moe/w_gate", "layers/moe/w_up", "layers/moe/w_down")


def sparse_expert_matmul_flops(d) -> int:
    """Every layer, for one token: the attention projections, the router
    over every published expert, ``top_k`` routed expert MLPs weighted by
    the share of experts held here (``top_k * experts_held / experts``,
    rounded down to a whole multiply-add), the shared expert and its gate."""
    D = d.d_model
    E, held, k = d.size("experts"), d.size("experts_held"), d.size("top_k")
    routed = 3 * D * d.size("expert_d_ff") * k * held // E
    shared = 3 * D * d.size("shared_d_ff") + D * d.size("shared_gate")
    return 2 * d.layers * (_attention_projections(d) + D * E + routed
                           + shared)


def expected_experts_reached(d, tokens: int) -> Fraction:
    """The share of the held experts that ``tokens`` tokens reach, expected
    under uniform routing: each token picks ``top_k`` of ``experts``, so a
    given expert is missed by all of them with probability
    ``(1 - top_k / experts) ** tokens``.  The harness cannot see where the
    router sends each token (the program reports no routing), so the bytes
    a step must read are counted at this expectation; a router that sends
    tokens unevenly reaches fewer experts and reads fewer bytes."""
    return 1 - (1 - Fraction(d.size("top_k"), d.size("experts"))) ** tokens


def sparse_expert_decode_weight_bytes(d, stored: Mapping[str, int],
                                      active: int) -> int:
    """Weight bytes one decode step over ``active`` requests must read: the
    stored bytes of the held experts (``EXPERT_TENSORS``, which hold
    ``experts_held`` experts) that ``active`` tokens are expected to reach
    (``expected_experts_reached``, rounded down to a whole byte), and every
    other tensor as ``decode_weight_bytes`` counts it."""
    experts = sum(stored[name] for name in EXPERT_TENSORS)
    rest = {n: b for n, b in stored.items() if n not in EXPERT_TENSORS}
    return (decode_weight_bytes(d, rest, active)
            + int(experts * expected_experts_reached(d, active)))
