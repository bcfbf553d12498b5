"""Paged, compressible KV cache for continuous batching (docs/KV_CACHE.md).

The subsystem splits along the device/host line:

* :mod:`repro.models` owns the device side — ``init_kv_pool`` block pools
  (``models/kv.py``) and the one cached step, ``decode_step`` /
  ``prefill_chunk``, which given a block table scatters and gathers K/V
  through it (``api.has_cached_step`` gates families);
* :class:`BlockKVManager` (here) owns the host side — block tables, free
  lists, prefix-chain refcounts, LRU eviction;
* :class:`ColdBlockStore` entropy-codes evicted shared blocks to host
  bytes via the ``core.codecs`` registry.

Policy comes in as :class:`repro.core.spec.KVCompressionSpec` (the
``--kv-spec`` grammar).  ``kv_pool_bytes`` sizes a pool without allocating
it — the peak-HBM breakdowns in ``launch/serve.py`` and
``benchmarks/resident_serving.py`` use it.
"""
from __future__ import annotations

import math

import jax

from repro.configs.base import ArchConfig
from repro.core.spec import KVCompressionSpec
from repro.models import api
from .blocks import BlockKVManager
from .cold import ColdBlockStore


def kv_pool_bytes(cfg: ArchConfig, n_blocks: int, block_size: int,
                  bits: int = 16) -> int:
    """Bytes of a paged KV pool, via ``eval_shape`` (nothing allocated)."""
    shapes = jax.eval_shape(
        lambda: api.build(cfg).init_kv_pool(cfg, n_blocks, block_size, bits))
    return sum(math.prod(leaf.shape) * leaf.dtype.itemsize
               for leaf in jax.tree.leaves(shapes))


def kv_cache_bytes(cfg: ArchConfig, n_slots: int, max_len: int) -> int:
    """Bytes of the PR 2 slotted cache — the dense reference budget."""
    shapes = jax.eval_shape(
        lambda: api.build(cfg).init_cache(cfg, n_slots, max_len))
    return sum(math.prod(leaf.shape) * leaf.dtype.itemsize
               for leaf in jax.tree.leaves(shapes))


__all__ = ["BlockKVManager", "ColdBlockStore", "KVCompressionSpec",
           "kv_pool_bytes", "kv_cache_bytes"]
