"""KV cache layouts: the one place that knows how K/V rows are kept.

Every layout is a dict of layer-stacked leaves, and the leaves alone say
which layout it is:

* **slot stack, bf16** — ``{"k", "v"}``, each (L, B, T, KV, hd): one
  contiguous ``max_len`` row per lockstep batch row or serving slot
  (:func:`init_cache`);
* **slot stack, int8** — adds ``k_scale`` / ``v_scale`` (L, B, T, KV, 1):
  symmetric per (token, head) (:func:`quantize_kv`);
* **paged pool** — (L, NB, BS, KV, ·) fixed-size blocks, routed through a
  (B, MB) block table (:func:`init_kv_pool`, docs/KV_CACHE.md): bf16
  ``{"k", "v"}``, or uint8 symbols at 8 or 4 bits (nibble-packed along
  head_dim) with ``*_scale`` / ``*_zero`` per (token, head), asymmetric
  (:func:`kv_quantize`).

:func:`store` writes one step's rows into one layer of the stack and
:func:`load` reads that layer back as K and V, dequantized to bf16 where
the leaves are quantized.  The cached layer step
(``dense.cached_layer``) calls exactly these two, so every layout rides
the same layer scan, and a scan that carries the stack updates it in
place.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig

Leaves = Dict[str, jax.Array]


# ------------------------------------------------------------------ layouts

def init_cache(cfg: ArchConfig, batch: int, max_len: int, dtype=jnp.bfloat16,
               kv_bits: int = 16) -> Leaves:
    """The slot stack: bf16 (``dtype``), or int8 with scales at ``kv_bits=8``."""
    L, KV, hd = cfg.n_layers, cfg.n_kv_heads, cfg.hd
    if kv_bits == 8:
        return {
            "k": jnp.zeros((L, batch, max_len, KV, hd), jnp.int8),
            "v": jnp.zeros((L, batch, max_len, KV, hd), jnp.int8),
            "k_scale": jnp.zeros((L, batch, max_len, KV, 1), jnp.bfloat16),
            "v_scale": jnp.zeros((L, batch, max_len, KV, 1), jnp.bfloat16),
        }
    return {
        "k": jnp.zeros((L, batch, max_len, KV, hd), dtype),
        "v": jnp.zeros((L, batch, max_len, KV, hd), dtype),
    }


def cache_specs(cfg: ArchConfig, kv_bits: int = 16, layout: str = "batch"
                ) -> Dict[str, Tuple[Optional[str], ...]]:
    """Logical axes for the slot stack's leaves.  ``layout="slot"`` names
    axis 1 "slot" instead of "batch": the slotted cache of continuous
    batching is the same memory, but slots are rows of a resident pool
    (requests come and go within them) rather than rows of one lockstep
    request batch, and the sharding rules resolve the two independently."""
    b = "slot" if layout == "slot" else "batch"
    s = {
        "k": ("layers", b, "kv_seq", "kv", None),
        "v": ("layers", b, "kv_seq", "kv", None),
    }
    if kv_bits == 8:
        s["k_scale"] = ("layers", b, "kv_seq", "kv", None)
        s["v_scale"] = ("layers", b, "kv_seq", "kv", None)
    return s


def init_kv_pool(cfg: ArchConfig, n_blocks: int, block_size: int,
                 kv_bits: int = 16) -> Leaves:
    """Preallocate a paged block pool (block id 0 is the trash block)."""
    L, KV, hd = cfg.n_layers, cfg.n_kv_heads, cfg.hd
    if kv_bits == 16:
        return {
            "k": jnp.zeros((L, n_blocks, block_size, KV, hd), jnp.bfloat16),
            "v": jnp.zeros((L, n_blocks, block_size, KV, hd), jnp.bfloat16),
        }
    assert kv_bits in (8, 4), kv_bits
    if kv_bits == 4 and hd % 2:
        raise ValueError(f"kv_bits=4 nibble-packs head_dim pairs; "
                         f"hd={hd} is odd")
    hs = hd if kv_bits == 8 else hd // 2
    pool = {
        "k": jnp.zeros((L, n_blocks, block_size, KV, hs), jnp.uint8),
        "v": jnp.zeros((L, n_blocks, block_size, KV, hs), jnp.uint8),
    }
    for side in ("k", "v"):
        pool[f"{side}_scale"] = jnp.zeros((L, n_blocks, block_size, KV, 1),
                                          jnp.bfloat16)
        pool[f"{side}_zero"] = jnp.zeros((L, n_blocks, block_size, KV, 1),
                                         jnp.bfloat16)
    return pool


# --------------------------------------------------------------- quantizers

def quantize_kv(k: jax.Array):
    """EntroLLM-grid int8 KV quantization for the slot stack: per (token,
    head) symmetric scale over head_dim — the cache read is the
    decode-phase HBM bound at serving batch sizes, so halving its bytes is
    the paper's bandwidth insight applied to the cache (beyond-paper,
    EXPERIMENTS.md §Perf H3)."""
    s = jnp.max(jnp.abs(k.astype(jnp.float32)), axis=-1, keepdims=True) / 127.0
    s = jnp.maximum(s, 1e-8)
    q = jnp.clip(jnp.round(k.astype(jnp.float32) / s), -127, 127
                 ).astype(jnp.int8)
    return q, s.astype(jnp.bfloat16)


def dequantize_kv(q: jax.Array, s: jax.Array) -> jax.Array:
    return q.astype(jnp.bfloat16) * s.astype(jnp.bfloat16)


def kv_quantize(x: jax.Array, bits: int):
    """Asymmetric per-(token, head) KV quantization for the paged pool — the
    jnp twin of :func:`repro.core.quant.quantize` with
    ``Scheme.ASYMMETRIC`` at per-channel granularity over head_dim, applied
    in-graph so blocks quantize as they are written.

    x: (..., hd) -> (q uint8 (..., hd) [or (..., hd/2) nibble-packed at
    bits=4], scale bf16 (..., 1), zero bf16 (..., 1)).  The grid spans
    [min, max] per (token, head) vector: KV activations are not
    zero-centered (unlike weights), so the asymmetric grid halves the error
    of a symmetric one at the same width.
    """
    assert bits in (8, 4), bits
    xf = x.astype(jnp.float32)
    lo = jnp.min(xf, axis=-1, keepdims=True)
    hi = jnp.max(xf, axis=-1, keepdims=True)
    qmax = (1 << bits) - 1
    scale = (hi - lo) / qmax
    scale = jnp.where(scale == 0.0, 1.0, scale)      # constant vector guard
    q = jnp.clip(jnp.round((xf - lo) / scale), 0, qmax).astype(jnp.uint8)
    if bits == 4:
        q = q[..., 0::2] | (q[..., 1::2] << 4)       # nibble-pack along hd
    return q, scale.astype(jnp.bfloat16), lo.astype(jnp.bfloat16)


def kv_dequantize(q: jax.Array, scale: jax.Array, zero: jax.Array,
                  bits: int) -> jax.Array:
    """Inverse of :func:`kv_quantize`: uint8 symbols -> bf16 K/V rows."""
    assert bits in (8, 4), bits
    if bits == 4:
        q = jnp.stack([q & 0xF, q >> 4], axis=-1
                      ).reshape(q.shape[:-1] + (q.shape[-1] * 2,))
    return q.astype(jnp.bfloat16) * scale.astype(jnp.bfloat16) \
        + zero.astype(jnp.bfloat16)


def _pool_bits(leaves: Leaves, hd: int) -> int:
    """8 or 4: a 4-bit pool packs two head_dim symbols per byte."""
    return 8 if leaves["k"].shape[-1] == hd else 4


def _encode(leaves: Leaves, k: jax.Array, v: jax.Array) -> Leaves:
    """The step's K and V (B, S, KV, hd) as rows of the leaves' format."""
    if "k_zero" in leaves:
        bits, rows = _pool_bits(leaves, k.shape[-1]), {}
        for side, x in (("k", k), ("v", v)):
            rows[side], rows[f"{side}_scale"], rows[f"{side}_zero"] = \
                kv_quantize(x, bits)
        return rows
    if "k_scale" in leaves:
        (kq, ks), (vq, vs) = quantize_kv(k), quantize_kv(v)
        return {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
    return {"k": k, "v": v}


def _decode(c: Leaves, hd: int) -> Tuple[jax.Array, jax.Array]:
    """One layer's leaves -> K and V, dequantized where quantized."""
    if "k_zero" in c:
        bits = _pool_bits(c, hd)
        return tuple(kv_dequantize(c[s], c[f"{s}_scale"], c[f"{s}_zero"],
                                   bits) for s in ("k", "v"))
    if "k_scale" in c:
        return dequantize_kv(c["k"], c["k_scale"]), \
            dequantize_kv(c["v"], c["v_scale"])
    return c["k"], c["v"]


# ------------------------------------------------------------------ routing

def write_kv_rows(cache: jax.Array, rows: jax.Array, layer: jax.Array,
                  pos: jax.Array) -> jax.Array:
    """Write one step's rows (B, S, KV, ·) into layer ``layer`` of a
    slot-stack leaf (L, B, T, KV, ·): row b lands at ``[pos_b, pos_b + S)``,
    ``pos`` scalar (lockstep) or ``(B,)`` (slots).

    One scatter of B windows whose start indices clamp the way
    ``dynamic_update_slice`` clamps them, so every row lands exactly where
    ``layers.update_kv_cache`` puts it on the layer's own slice.  Nothing
    else of the stack is touched.
    """
    B = rows.shape[0]
    idx = jnp.stack([
        jnp.broadcast_to(jnp.asarray(layer, jnp.int32), (B,)),
        jnp.arange(B, dtype=jnp.int32),
        jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,)),
    ], axis=-1)                                          # (B, 3)
    dnums = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(1, 2, 3), inserted_window_dims=(0, 1),
        scatter_dims_to_operand_dims=(0, 1, 2))
    return jax.lax.scatter(cache, idx, rows.astype(cache.dtype), dnums,
                           indices_are_sorted=True, unique_indices=True,
                           mode=jax.lax.GatherScatterMode.CLIP)


def scatter_blocks(pool_leaf: jax.Array, bt: jax.Array, positions: jax.Array,
                   val: jax.Array, layer: jax.Array) -> jax.Array:
    """Write per-request rows into layer ``layer`` of a pool leaf
    (L, NB, BS, ...) through the block table.

    bt: (B, MB); positions: (B, S) global token positions; val: (B, S, ...).
    Row (b, s) lands at block ``bt[b, positions[b, s] // BS]``, offset
    ``positions[b, s] % BS``.  Duplicate targets only arise from
    trash-block writes (several idle lanes aiming at block 0), where any
    winner is equally garbage.
    """
    L, NB, BS = pool_leaf.shape[:3]
    blk = jnp.take_along_axis(bt, positions // BS, axis=1)      # (B, S)
    idx = (blk * BS + positions % BS).reshape(-1)               # (B*S,)
    flat = pool_leaf.reshape((L, NB * BS) + pool_leaf.shape[3:])
    flat = flat.at[layer, idx].set(
        val.reshape((-1,) + val.shape[2:]).astype(pool_leaf.dtype))
    return flat.reshape(pool_leaf.shape)


def gather_blocks(pool_leaf: jax.Array, bt: jax.Array,
                  layer: jax.Array) -> jax.Array:
    """Gather per-request sequences out of layer ``layer`` of a pool leaf.

    pool_leaf: (L, NB, BS, ...); bt: (B, MB) int32 block table.  Returns
    (B, MB * BS, ...) — each request's blocks concatenated in table order,
    ready to stand in for the slot stack's (B, T, ...) layer (positions
    >= kv_len are masked by attention exactly like slot padding).
    """
    g = jnp.take(jax.lax.dynamic_index_in_dim(pool_leaf, layer, 0,
                                              keepdims=False), bt, axis=0)
    return g.reshape((bt.shape[0], -1) + pool_leaf.shape[3:])


# --------------------------------------------------------------------- seam

def store(leaves: Leaves, k: jax.Array, v: jax.Array, layer: jax.Array,
          pos: jax.Array, table: Optional[jax.Array] = None) -> Leaves:
    """Write the step's K and V (B, S, KV, hd) into layer ``layer``, at
    rows ``[pos_b, pos_b + S)`` of each batch row.

    Without ``table`` the leaves are the slot stack and ``pos`` is scalar
    (lockstep) or (B,) (slots).  With the (B, MB) block ``table`` they are
    the paged pool and ``pos`` is (B,); the caller guarantees the table's
    entries for those rows are allocated.  Quantized leaves quantize the
    rows first.  Returns the updated leaves; nothing else of them changes.
    """
    rows = _encode(leaves, k, v)
    if table is None:
        return {n: write_kv_rows(a, rows[n], layer, pos)
                for n, a in leaves.items()}
    positions = jnp.asarray(pos)[:, None] + jnp.arange(k.shape[1])
    return {n: scatter_blocks(a, table, positions, rows[n], layer)
            for n, a in leaves.items()}


def load(leaves: Leaves, layer: jax.Array, hd: int,
         table: Optional[jax.Array] = None) -> Tuple[jax.Array, jax.Array]:
    """Layer ``layer``'s K and V, (B, T, KV, hd) — the slot stack's rows,
    or the pool's blocks gathered through ``table`` — as stored, or
    dequantized to bf16 where the leaves are quantized.  ``hd`` tells a
    4-bit pool (two symbols a byte) from an 8-bit one."""
    if table is None:
        c = {n: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)
             for n, a in leaves.items()}
    else:
        c = {n: gather_blocks(a, table, layer) for n, a in leaves.items()}
    return _decode(c, hd)
