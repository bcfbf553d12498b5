"""Process-wide sharding-hints context.

Models are mesh-agnostic; the launch layer installs hints so memory-critical
*activation* tensors (attention q/k/v and scores at 32k+) receive explicit
``with_sharding_constraint``s instead of relying on GSPMD propagation alone.
Attention (``layers.gqa_attention``) takes one of two forms, chosen from
the hints by :func:`kv_heads_cover_model`:

* grouped — q viewed as (B, S, KV, G, hd) against K/V as stored,
  (B, T, KV, hd): taken with no hints, under exact serving, or when the
  model axis divides the KV head count.  The KV axis carries the model
  sharding.
* replicated — K/V broadcast up to all H query heads (G = 1): taken only
  where the model axis is wider than the KV heads can cover, so that one
  merged H axis shards over it.

Either way every constraint is a plain PartitionSpec over the head axis
that attention keeps (KV or H):

* q (B, S, heads, [G,] hd) and k/v (B, T, heads, hd): batch axes on B,
  model axis on the heads; for decode the KV-time dim T instead carries the
  cache's sequence sharding (``kv_seq_axes`` — "model" when the arch's KV
  head count cannot cover the model axis, the data axes for single-request
  long-context).
* scores (B, heads, [G,] Sq, T): batch on B, model on the heads when free,
  cache sharding on T (GSPMD emits the partial-softmax psum —
  flash-decoding's combine).

Install with :func:`set_hints` before tracing; smoke tests leave it unset and
models run constraint-free on one device.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ShardingHints:
    mesh: object
    batch_axes: Tuple[str, ...] = ("data",)   # () when batch is unsharded
    model_axis: Optional[str] = "model"
    kv_seq_axes: Tuple[str, ...] = ()         # cache T-dim sharding (decode)
    seq_sp: bool = True                       # sequence-parallel layer carries
    feature_axes: Tuple[str, ...] = ()        # weight-stationary decode: the
    #   FSDP axes ride the activation FEATURE dim, forcing partial-dot + tiny
    #   psum instead of weight all-gathers (EXPERIMENTS.md §Perf H1)
    exact_tp: bool = False                    # bit-identical sharded serving:
    #   weights stay sharded at REST (per-device HBM divided along output
    #   channels) and are constrained replicated at their USE site — an
    #   all-gather, pure data movement — so every compute op runs with
    #   reference shapes and rounds exactly like the single-device engine.
    #   Activation constraints are skipped entirely: partitioning activation
    #   rows changes XLA's emitted reduction loops (fusion/row-count
    #   dependent accumulation order, measured at ~1 ulp per rms_norm), which
    #   is what breaks greedy-token identity under classic sharded-compute TP.


_HINTS: list = [None]


def set_hints(h: Optional[ShardingHints]) -> None:
    _HINTS[0] = h


def get_hints() -> Optional[ShardingHints]:
    return _HINTS[0]


def _fits(dim: int, mesh, axes: Tuple[str, ...]) -> bool:
    if not axes:
        return False
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return dim % n == 0


def constrain_replicated(x: jax.Array) -> jax.Array:
    """Exact sharded serving: gather a HBM-sharded WEIGHT to every device at
    its use site.  The all-gather is pure data movement — the consuming op
    then reads a full-shape buffer exactly like the single-device program
    reads the parameter buffer, so its emitted kernel (and therefore its
    rounding) is identical.  No-op unless ``exact_tp`` hints are installed.
    """
    h = get_hints()
    if h is None or not h.exact_tp:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(h.mesh, P(*([None] * x.ndim))))


def kv_heads_cover_model(n_kv: int) -> bool:
    """Whether attention may keep K/V at their own ``n_kv`` heads (the
    grouped form): no hints, exact serving, no model axis, or a model axis
    that divides ``n_kv``.  False only where the model axis is wider than
    the KV heads can cover, so K/V must be replicated up to every query
    head for the head axis to shard."""
    h = get_hints()
    if h is None or h.exact_tp or not h.model_axis:
        return True
    return _fits(n_kv, h.mesh, (h.model_axis,))


def constrain_heads(x: jax.Array, *, is_cache_side: bool = False) -> jax.Array:
    """Constrain (B, T, heads, ...): batch/B, model/heads, cache sharding on
    T; trailing dims (the query group, hd) stay unsharded."""
    h = get_hints()
    if h is None or h.exact_tp:     # exact serving: no activation constraints
        return x
    B, T, N = x.shape[:3]
    batch = h.batch_axes if _fits(B, h.mesh, h.batch_axes) else None
    seq = h.kv_seq_axes if (is_cache_side
                            and _fits(T, h.mesh, h.kv_seq_axes)) else None
    heads = None
    m = h.model_axis
    if m and _fits(N, h.mesh, (m,)) and (seq is None or m not in seq):
        heads = m
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(h.mesh, P(batch, seq, heads,
                                   *([None] * (x.ndim - 3)))))


def constrain_scores(s: jax.Array) -> jax.Array:
    """Constrain (B, heads, ..., Sq, T) attention scores."""
    h = get_hints()
    if h is None or h.exact_tp:     # exact serving: no activation constraints
        return s
    B, N, T = s.shape[0], s.shape[1], s.shape[-1]
    batch = h.batch_axes if _fits(B, h.mesh, h.batch_axes) else None
    seq = h.kv_seq_axes if _fits(T, h.mesh, h.kv_seq_axes) else None
    heads = None
    m = h.model_axis
    if m and _fits(N, h.mesh, (m,)) and (seq is None or m not in seq):
        heads = m
    return jax.lax.with_sharding_constraint(
        s, NamedSharding(h.mesh, P(batch, heads, *([None] * (s.ndim - 3)),
                                   seq)))


def constrain_activation(x: jax.Array) -> jax.Array:
    """Constrain a (B, S, D) activation at a layer boundary.

    Batch shards over the batch axes; the SEQUENCE dim additionally shards
    over the model axis (sequence parallelism, Korthikanti et al.): the saved
    scan carry — the dominant remat-memory term — shrinks by |model|, and the
    TP all-reduce after each row-parallel matmul becomes an equal-byte
    reduce-scatter + all-gather pair.  Skipped automatically when S doesn't
    divide (decode steps).
    """
    h = get_hints()
    if h is None or h.exact_tp or x.ndim < 3:
        return x
    if h.feature_axes:
        if not _fits(x.shape[-1], h.mesh, h.feature_axes):
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(h.mesh, P(*([None] * (x.ndim - 1)),
                                       h.feature_axes)))
    batch = h.batch_axes if _fits(x.shape[0], h.mesh, h.batch_axes) else None
    m = h.model_axis
    seq = m if (h.seq_sp and m and _fits(x.shape[1], h.mesh, (m,))) else None
    if batch is None and seq is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(h.mesh, P(batch, seq, *([None] * (x.ndim - 2)))))
