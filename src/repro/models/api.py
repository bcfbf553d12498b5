"""Uniform model API: ``build(cfg)`` returns the family module; every family exposes

    schema(cfg) -> {name: Spec}
    init(cfg, key) -> params
    loss_fn(cfg, params, batch, *, unroll, ...) -> scalar
    prefill(cfg, params, tokens_or_batch, *, max_len, ...) -> (logits, cache)
    decode_step(cfg, params, token, cache, pos, *, ...) -> (logits, cache)
    init_cache(cfg, batch, max_len) -> cache pytree
    cache_specs(cfg) -> logical axes for cache leaves

Attention-cache families (dense, moe) additionally expose the cached
layer step (``kv.py``'s layouts, one layer scan — see ``dense.py``):

    decode_step(cfg, params, token, cache, pos, table=None, *, ...)
    prefill_chunk(cfg, params, tokens, cache, pos, table=None, *, ...)
        — ``pos`` scalar (lockstep) or (B,) per-slot (continuous
        batching); ``table`` routes through a paged block pool
    init_kv_pool(cfg, n_blocks, block_size, kv_bits) -> paged pool
    embed / head / prefill_layer / cached_layer
        — the per-layer pieces compressed-resident serving jits

``has_cached_step(cfg)`` reports whether a family has it (recurrent
caches — ssm/hybrid conv+state, encdec cross-attention — need a
family-specific slot layout and are not wired up yet).
"""
from __future__ import annotations

import types
from typing import Dict, Tuple

import jax

from repro.configs.base import ArchConfig


def build(cfg: ArchConfig) -> types.ModuleType:
    from . import dense, encdec, hybrid, mamba2, moe
    return {
        "dense": dense,
        "moe": moe,
        "ssm": mamba2,
        "hybrid": hybrid,
        "encdec": encdec,
    }[cfg.family]


def has_cached_step(cfg: ArchConfig) -> bool:
    """True when the family has the cached layer step, which is what
    continuous batching, the paged KV pool and compressed-resident serving
    (fused or not) all run."""
    return hasattr(build(cfg), "cached_layer")


def cache_specs(cfg: ArchConfig, **kw) -> Dict[str, Tuple]:
    """Family ``cache_specs`` with kwarg filtering: callers pass the full
    option set (``layout="slot"``, ``kv_bits=8``, ...) and families that do
    not take an option simply don't see it — the sharding layer can resolve
    any family's cache without per-family dispatch."""
    import inspect
    fn = build(cfg).cache_specs
    accepted = inspect.signature(fn).parameters
    return fn(cfg, **{k: v for k, v in kw.items() if k in accepted})


def param_shapes(cfg: ArchConfig) -> Dict[str, Tuple[int, ...]]:
    return {n: s.shape for n, s in build(cfg).schema(cfg).items()}


def param_specs(cfg: ArchConfig) -> Dict[str, Tuple]:
    return {n: s.axes for n, s in build(cfg).schema(cfg).items()}


def param_shape_structs(cfg: ArchConfig, dtype=None) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for every parameter (dry-run: no allocation)."""
    import jax.numpy as jnp
    sch = build(cfg).schema(cfg)
    return {n: jax.ShapeDtypeStruct(s.shape, dtype or s.dtype) for n, s in sch.items()}
