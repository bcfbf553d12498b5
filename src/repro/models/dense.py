"""Dense decoder-only transformer (chameleon / stablelm / command-r+ / glm4 / qwen3).

Layers are stacked on a leading axis and iterated with ``lax.scan`` whose ``unroll``
degree is a lowering knob: smoke tests keep it rolled (fast compile), the dry-run
unrolls fully so ``cost_analysis`` counts every layer.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.obs import metrics as obs_metrics
from .layers import (QT, Schema, Spec, gather_blocks, gqa_attention,
                     init_params, kv_dequantize, kv_quantize, matmul, rms_norm,
                     rope, scatter_blocks, softmax_xent, swiglu, take_rows,
                     update_kv_cache, write_kv_rows)


def schema(cfg: ArchConfig) -> Schema:
    L, D, H, KV, hd, F = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                          cfg.hd, cfg.d_ff)
    Vp = cfg.padded_vocab()
    resid = 0.02 / (2 * L) ** 0.5    # residual-branch init scaling
    s: Schema = {
        "embed": Spec((Vp, D), ("vocab", "embed"), 0.02),
        "final_norm": Spec((D,), (None,), "ones", jnp.float32),
        "layers/attn_norm": Spec((L, D), ("layers", None), "ones", jnp.float32),
        "layers/wq": Spec((L, D, H * hd), ("layers", "embed", "heads")),
        "layers/wk": Spec((L, D, KV * hd), ("layers", "embed", "kv")),
        "layers/wv": Spec((L, D, KV * hd), ("layers", "embed", "kv")),
        "layers/wo": Spec((L, H * hd, D), ("layers", "heads", "embed"), resid),
        "layers/mlp_norm": Spec((L, D), ("layers", None), "ones", jnp.float32),
        "layers/w_gate": Spec((L, D, F), ("layers", "embed", "mlp")),
        "layers/w_up": Spec((L, D, F), ("layers", "embed", "mlp")),
        "layers/w_down": Spec((L, F, D), ("layers", "mlp", "embed"), resid),
    }
    if not cfg.tie_embeddings:
        s["lm_head"] = Spec((D, Vp), ("embed", "vocab"), 0.02)
    if cfg.qkv_bias:
        s["layers/q_bias"] = Spec((L, H * hd), ("layers", "heads"), "zeros", jnp.float32)
        s["layers/k_bias"] = Spec((L, KV * hd), ("layers", "kv"), "zeros", jnp.float32)
        s["layers/v_bias"] = Spec((L, KV * hd), ("layers", "kv"), "zeros", jnp.float32)
    if cfg.qk_norm:
        s["layers/q_norm"] = Spec((L, hd), ("layers", None), "ones", jnp.float32)
        s["layers/k_norm"] = Spec((L, hd), ("layers", None), "ones", jnp.float32)
    return s


def init(cfg: ArchConfig, key: jax.Array) -> Dict[str, jax.Array]:
    return init_params(schema(cfg), key)


def _layer_stack(params: Dict[str, Any]) -> Dict[str, Any]:
    return {k.split("/", 1)[1]: v for k, v in params.items() if k.startswith("layers/")}


def quantize_kv(k: jax.Array):
    """EntroLLM-grid int8 KV quantization: per (token, head) symmetric scale
    over head_dim — the cache read is the decode-phase HBM bound at serving
    batch sizes, so halving its bytes is the paper's bandwidth insight
    applied to the cache (beyond-paper, EXPERIMENTS.md §Perf H3)."""
    s = jnp.max(jnp.abs(k.astype(jnp.float32)), axis=-1, keepdims=True) / 127.0
    s = jnp.maximum(s, 1e-8)
    q = jnp.clip(jnp.round(k.astype(jnp.float32) / s), -127, 127
                 ).astype(jnp.int8)
    return q, s.astype(jnp.bfloat16)


def dequantize_kv(q: jax.Array, s: jax.Array) -> jax.Array:
    return q.astype(jnp.bfloat16) * s.astype(jnp.bfloat16)


def _qkv(cfg: ArchConfig, lp: Dict[str, Any], x: jax.Array, positions):
    """Q, K and V of the attention sub-block, as every attention path takes
    them: pre-norm, projection, bias (``cfg.qkv_bias``), per-head qk-norm
    (``cfg.qk_norm``), and rotary in ``cfg.rotary``'s form.  x: (B, S, D)
    -> q (B, S, H, hd), k and v (B, S, KV, hd).  The counter ``attn.rope``
    counts each trace by its rotary form and bias."""
    B, S, _ = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)

    def project(w, b, n):
        t = matmul(h, lp[w])
        if cfg.qkv_bias:
            t = t + lp[b].astype(t.dtype)
        return t.reshape(B, S, n, hd)

    q, k, v = project("wq", "q_bias", H), project("wk", "k_bias", KV), \
        project("wv", "v_bias", KV)
    if cfg.qk_norm:
        q = rms_norm(q, lp["q_norm"], cfg.norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.norm_eps)
    obs_metrics.counter("attn.rope").inc(rotary=cfg.rotary,
                                         qkv_bias=str(int(cfg.qkv_bias)))
    q = rope(q, positions, cfg.rope_theta, cfg.rope_dim, cfg.rope_pairs)
    k = rope(k, positions, cfg.rope_theta, cfg.rope_dim, cfg.rope_pairs)
    return q, k, v


def _attn(cfg: ArchConfig, lp: Dict[str, Any], x: jax.Array, *, positions,
          cache: Optional[Tuple] = None, pos=None, layer=None, q_block: int = 0,
          unroll: int = 1):
    """Attention sub-block; returns (out, new_cache).

    ``cache`` is (k, v) bf16 or (k, v, k_scale, v_scale) for the int8 cache.
    ``pos`` is the cache write offset — scalar (lockstep batch) or ``(B,)``
    (slot batch, one independent position per row).  With a cache present,
    ``S`` may exceed 1: the chunk is written at ``[pos, pos + S)`` and
    attended causally against the whole cache (chunked prefill).

    Without ``layer`` the cache leaves are one layer's (B, T, KV, ·).  With
    ``layer`` they are the whole layer stack (L, B, T, KV, ·): the step's
    rows are written at layer ``layer`` first and that layer is read back
    after, so a scan that carries the stack updates it in place.
    """
    B, S, D = x.shape
    H, hd = cfg.n_heads, cfg.hd
    q, k, v = _qkv(cfg, lp, x, positions)
    if cache is None:
        attn = gqa_attention(q, k, v, causal=True, q_block=q_block, unroll=unroll)
        new_cache = (k, v)
    else:
        if len(cache) == 4:
            (kq, ks), (vq, vs) = quantize_kv(k), quantize_kv(v)
            rows = (kq, vq, ks, vs)
        else:
            rows = (k, v)
        if layer is None:
            new_cache = update_kv_cache(cache[0], cache[1], rows[0], rows[1], pos)
            if len(rows) == 4:
                new_cache += update_kv_cache(cache[2], cache[3], rows[2],
                                             rows[3], pos)
            c = new_cache
        else:
            new_cache = tuple(write_kv_rows(cl, r, layer, pos)
                              for cl, r in zip(cache, rows))
            c = tuple(jax.lax.dynamic_index_in_dim(cl, layer, 0, keepdims=False)
                      for cl in new_cache)
        ck, cv = c[:2] if len(c) == 2 else (dequantize_kv(c[0], c[2]),
                                            dequantize_kv(c[1], c[3]))
        attn = gqa_attention(q, ck, cv, causal=S > 1, q_offset=pos,
                             kv_len=pos + S)
    out = matmul(attn.reshape(B, S, H * hd), lp["wo"])
    return out, new_cache


def _block(cfg: ArchConfig, lp, x, *, positions, cache=None, pos=None,
           layer=None, q_block=0, unroll=1):
    attn_out, new_cache = _attn(cfg, lp, x, positions=positions, cache=cache, pos=pos,
                                layer=layer, q_block=q_block, unroll=unroll)
    x = x + attn_out
    h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    x = x + swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])
    return x, new_cache


def forward(cfg: ArchConfig, params, tokens, *, unroll: int = 1, q_block: int = 0,
            remat: bool = False, collect_cache: bool = False):
    """Full-sequence forward.  Returns (hidden, cache|None)."""
    from repro.distributed.ctx import constrain_activation
    B, S = tokens.shape
    x = constrain_activation(take_rows(params["embed"], tokens))
    positions = jnp.arange(S)
    stack = _layer_stack(params)

    def body(x, lp):
        x, kv = _block(cfg, lp, x, positions=positions, q_block=q_block, unroll=unroll)
        return constrain_activation(x), kv if collect_cache else None

    fn = jax.checkpoint(body) if remat else body
    x, caches = jax.lax.scan(fn, x, stack, unroll=unroll)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, caches


def logits_fn(cfg: ArchConfig, params, x: jax.Array) -> jax.Array:
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    if cfg.tie_embeddings:
        from .layers import deq
        return matmul(x, deq(head).T)
    return matmul(x, head)


def loss_fn(cfg: ArchConfig, params, batch, *, unroll: int = 1, q_block: int = 0,
            remat: bool = True) -> jax.Array:
    tokens = batch["tokens"]
    inp, labels = tokens[:, :-1], tokens[:, 1:]
    x, _ = forward(cfg, params, inp, unroll=unroll, q_block=q_block, remat=remat)
    return softmax_xent(logits_fn(cfg, params, x), labels, cfg.vocab)


# ------------------------------------------------------------------------- serving

def init_cache(cfg: ArchConfig, batch: int, max_len: int, dtype=jnp.bfloat16,
               kv_bits: int = 16):
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
    """Logical axes for the cache leaves.  ``layout="slot"`` names axis 1
    "slot" instead of "batch": the slotted cache of continuous batching is
    the same memory, but slots are rows of a resident pool (requests come
    and go within them) rather than rows of one lockstep request batch, and
    the sharding rules resolve the two independently."""
    b = "slot" if layout == "slot" else "batch"
    s = {
        "k": ("layers", b, "kv_seq", "kv", None),
        "v": ("layers", b, "kv_seq", "kv", None),
    }
    if kv_bits == 8:
        s["k_scale"] = ("layers", b, "kv_seq", "kv", None)
        s["v_scale"] = ("layers", b, "kv_seq", "kv", None)
    return s


def prefill(cfg: ArchConfig, params, tokens, *, max_len: Optional[int] = None,
            unroll: int = 1, q_block: int = 0):
    """Run the prompt; return (last-position logits, cache padded to max_len)."""
    B, S = tokens.shape
    max_len = max_len or S
    x, caches = forward(cfg, params, tokens, unroll=unroll, q_block=q_block,
                        collect_cache=True)
    k, v = caches
    pad = [(0, 0), (0, 0), (0, max_len - S), (0, 0), (0, 0)]
    cache = {"k": jnp.pad(k, pad), "v": jnp.pad(v, pad)}
    logits = logits_fn(cfg, params, x[:, -1:, :])
    return logits, cache


def _cache_keys(cache) -> Tuple[str, ...]:
    return ("k", "v", "k_scale", "v_scale") if "k_scale" in cache \
        else ("k", "v")


def _cached_layers(cfg: ArchConfig, params, tokens, cache, pos, unroll):
    """Embed, the layer scan of `decode_step` and `prefill_chunk`, the head.

    The layer-stacked cache rides in the scan's carry next to ``x``; the
    weight stack and the layer index are the scanned inputs.  Each layer
    writes only its step rows into the carried stack and reads its own layer
    back, so XLA updates the donated cache in place.  (Scanned as input and
    returned as output, the stack would be copied whole, cut out and written
    back whole every step.)"""
    from repro.distributed.ctx import constrain_activation
    x = constrain_activation(take_rows(params["embed"], tokens))
    positions = jnp.asarray(pos)[..., None] + jnp.arange(tokens.shape[1])
    keys = _cache_keys(cache)

    def body(carry, xs):
        x, c = carry
        lp, l = xs
        x, c = _block(cfg, lp, x, positions=positions, cache=c, pos=pos,
                      layer=l)
        return (constrain_activation(x), c), None

    layers = jnp.arange(cache["k"].shape[0])
    (x, out), _ = jax.lax.scan(body, (x, tuple(cache[k] for k in keys)),
                               (_layer_stack(params), layers), unroll=unroll)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return logits_fn(cfg, params, x), dict(zip(keys, out))


def decode_step(cfg: ArchConfig, params, token, cache, pos, *, unroll: int = 1):
    """One generation step.  token: (B, 1) int32; pos: scalar position shared by
    the whole batch (lockstep) or (B,) per-slot positions (continuous batch)."""
    return _cached_layers(cfg, params, token, cache, pos, unroll)


# ------------------------------------------------- compressed-resident serving
#
# Per-layer weight-slot entry points (docs/SERVING.md §"Compressed-resident
# serving"): the same math as `prefill` / `decode_step` / `prefill_chunk`,
# but one layer at a time with the layer's weights passed as a slot dict
# (the keys `_layer_stack` would produce) instead of sliced from the stacked
# params by `lax.scan`.  The driver in `serving.engine.ServeSteps` loops the
# layers in execution order, so entropy-decoding layer l+1 can overlap layer
# l's compute.  Each function mirrors one scan iteration of its whole-tree
# twin op for op — that is the bit-identity contract
# `tests/test_resident_serving.py` pins.


def embed_step(cfg: ArchConfig, params, tokens):
    """Token embedding against the resident globals (the pre-loop line of
    `forward` / `decode_step`).  tokens: (B, S) int32."""
    from repro.distributed.ctx import constrain_activation
    return constrain_activation(take_rows(params["embed"], tokens))


def head_step(cfg: ArchConfig, params, x, *, last_only: bool = False):
    """Final norm + logits (the post-loop lines of the step functions).
    ``last_only`` reproduces `prefill`'s last-position slice."""
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if last_only:
        x = x[:, -1:, :]
    return logits_fn(cfg, params, x)


def resident_prefill_block(cfg: ArchConfig, lp, x, *, positions,
                           q_block: int = 0, unroll: int = 1):
    """One `forward`-collect-cache scan iteration: full causal attention over
    the prompt, returning the layer's (k, v) for the caller to write into
    the zero-padded cache at its layer row."""
    from repro.distributed.ctx import constrain_activation
    x, kv = _block(cfg, lp, x, positions=positions, q_block=q_block,
                   unroll=unroll)
    return constrain_activation(x), kv


def resident_block(cfg: ArchConfig, lp, x, cache, l, pos):
    """One `decode_step` / `prefill_chunk` scan iteration against the
    layer-stacked cache: slice layer ``l``'s rows, run the block, write them
    back.  ``pos`` follows the step functions' contract (scalar lockstep or
    (B,) per-slot); S comes from ``x``, so the same callable serves decode
    (S=1) and chunked prefill.

    ``lp`` values may be dense arrays, QT/QT4 triples, or — under
    ``CompressedResidentWeights(fused=True)`` — FusedQT payload handles:
    every weight reaches ``layers.matmul``, whose dispatch decodes fused
    handles inside the matmul instead of reading a prefetched dense tile.
    The handle's static geometry is layer-invariant, so this block still
    traces once for all layers."""
    from repro.distributed.ctx import constrain_activation
    S = x.shape[1]
    positions = jnp.asarray(pos)[..., None] + jnp.arange(S)   # (S,) or (B, S)
    keys = _cache_keys(cache)
    c = tuple(jax.lax.dynamic_index_in_dim(cache[k], l, 0, keepdims=False)
              for k in keys)
    x, c = _block(cfg, lp, x, positions=positions, cache=c, pos=pos)
    out = dict(cache)
    for k, ci in zip(keys, c):
        out[k] = jax.lax.dynamic_update_index_in_dim(cache[k], ci, l, 0)
    return constrain_activation(x), out


def prefill_chunk(cfg: ArchConfig, params, tokens, cache, pos, *,
                  unroll: int = 1):
    """Chunked prefill: write one prompt chunk into an existing slotted cache.

    tokens: (B, S) int32 chunk; cache: ``init_cache``-layout pytree; pos: (B,)
    int32 per-slot write offsets, or a scalar one for every row (the chunk
    occupies cache rows ``[pos, pos + S)``; the caller guarantees
    ``pos + S <= max_len``).
    Returns (logits for every chunk position (B, S, V), cache) — the caller
    picks the logit at the request's true last prompt position, so ragged
    prompts ride in fixed-shape chunks (pad tokens land in the cache but stay
    masked forever because ``kv_len`` never reaches them).

    This is the admission path of continuous batching: a new request prefills
    chunk by chunk through ONE compiled shape while the decode batch keeps
    stepping between chunks, then the filled cache rows are spliced into a
    free slot.
    """
    return _cached_layers(cfg, params, tokens, cache, pos, unroll)


# ------------------------------------------------------------- paged KV cache
#
# Block-pool twins of the slot-batch step functions (docs/KV_CACHE.md).  The
# cache is a pool of fixed-size blocks, (L, n_blocks, block_size, KV, ·) per
# leaf, and every request's sequence is routed through a (B, max_blocks)
# block table: attention scatters the step's K/V into the table's blocks and
# gathers the logical sequence back out (``layers.gather_blocks`` /
# ``scatter_blocks``).  With dense bf16 blocks the gathered sequence holds
# bitwise the same live rows as the slot cache, so greedy decode is
# bit-identical to ``decode_step`` / ``prefill_chunk`` (the drift contract);
# quantized pools (``kv_bits`` 8/4) trade bounded greedy drift for 1.8-3.2x
# more tokens per HBM byte.  ``pos`` is always the (B,) per-slot vector —
# paged serving is a continuous-batching feature.


def init_kv_pool(cfg: ArchConfig, n_blocks: int, block_size: int,
                 kv_bits: int = 16):
    """Preallocate a paged KV block pool (block id 0 is the trash block)."""
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


def _pool_meta(cfg: ArchConfig, pool) -> Tuple[Tuple[str, ...], int]:
    """(leaf order, kv_bits) — both static at trace time from pool shapes."""
    if "k_scale" not in pool:
        return ("k", "v"), 16
    bits = 8 if pool["k"].shape[-1] == cfg.hd else 4
    return ("k", "k_scale", "k_zero", "v", "v_scale", "v_zero"), bits


def _paged_attn(cfg: ArchConfig, lp: Dict[str, Any], x: jax.Array, *,
                pc: Dict[str, jax.Array], bt: jax.Array, pos: jax.Array):
    """Attention against one layer's block-pool slice ``pc``.

    Mirrors :func:`_attn`'s cached path op for op on the compute side — the
    only difference is where K/V rows live: ``scatter_blocks`` replaces
    ``update_kv_cache`` and ``gather_blocks`` materializes the (B, MB*BS)
    logical sequence the same ``gqa_attention`` masks by ``kv_len``.
    Quantized pools quantize the step's K/V per (token, head) before the
    scatter and dequantize the gathered sequence in-graph.
    """
    B, S, D = x.shape
    H, hd = cfg.n_heads, cfg.hd
    positions = jnp.asarray(pos)[:, None] + jnp.arange(S)     # (B, S)
    q, k, v = _qkv(cfg, lp, x, positions)
    _, bits = _pool_meta(cfg, pc)
    if bits == 16:
        new = {"k": scatter_blocks(pc["k"], bt, positions, k),
               "v": scatter_blocks(pc["v"], bt, positions, v)}
        ck = gather_blocks(new["k"], bt)
        cv = gather_blocks(new["v"], bt)
    else:
        new = {}
        for side, step_val in (("k", k), ("v", v)):
            sq, ss, sz = kv_quantize(step_val, bits)
            new[side] = scatter_blocks(pc[side], bt, positions, sq)
            new[f"{side}_scale"] = scatter_blocks(pc[f"{side}_scale"], bt,
                                                  positions, ss)
            new[f"{side}_zero"] = scatter_blocks(pc[f"{side}_zero"], bt,
                                                 positions, sz)
        ck = kv_dequantize(gather_blocks(new["k"], bt),
                           gather_blocks(new["k_scale"], bt),
                           gather_blocks(new["k_zero"], bt), bits)
        cv = kv_dequantize(gather_blocks(new["v"], bt),
                           gather_blocks(new["v_scale"], bt),
                           gather_blocks(new["v_zero"], bt), bits)
    attn = gqa_attention(q, ck, cv, causal=S > 1, q_offset=pos,
                         kv_len=jnp.asarray(pos) + S)
    out = matmul(attn.reshape(B, S, H * hd), lp["wo"])
    return out, new


def _paged_block(cfg: ArchConfig, lp, x, *, pc, bt, pos):
    attn_out, new = _paged_attn(cfg, lp, x, pc=pc, bt=bt, pos=pos)
    x = x + attn_out
    h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    x = x + swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])
    return x, new


def paged_decode_step(cfg: ArchConfig, params, token, pool, bt, pos, *,
                      unroll: int = 1):
    """One fused generation step over a paged slot batch.

    token: (B, 1) int32; pool: ``init_kv_pool`` pytree; bt: (B, MB) int32
    block table (trash rows for inactive lanes); pos: (B,) per-slot kv_len.
    """
    from repro.distributed.ctx import constrain_activation
    x = constrain_activation(take_rows(params["embed"], token))
    stack = _layer_stack(params)
    keys, _ = _pool_meta(cfg, pool)

    def body(x, xs):
        lp, *pc = xs
        x, new = _paged_block(cfg, lp, x, pc=dict(zip(keys, pc)), bt=bt,
                              pos=pos)
        return constrain_activation(x), tuple(new[k] for k in keys)

    x, out = jax.lax.scan(body, x, (stack, *[pool[k] for k in keys]),
                          unroll=unroll)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return logits_fn(cfg, params, x), dict(zip(keys, out))


def paged_prefill_chunk(cfg: ArchConfig, params, tokens, pool, bt, pos, *,
                        unroll: int = 1):
    """Chunked prefill through the block table (paged ``prefill_chunk``).

    tokens: (B, S) chunk; pos: (B,) chunk start offsets.  The chunk's rows
    land in the blocks ``bt`` names for positions [pos, pos + S); the caller
    guarantees those table entries are allocated (admission preallocates the
    whole request — see serving/kvcache/blocks.py).
    """
    from repro.distributed.ctx import constrain_activation
    x = constrain_activation(take_rows(params["embed"], tokens))
    stack = _layer_stack(params)
    keys, _ = _pool_meta(cfg, pool)

    def body(x, xs):
        lp, *pc = xs
        x, new = _paged_block(cfg, lp, x, pc=dict(zip(keys, pc)), bt=bt,
                              pos=pos)
        return constrain_activation(x), tuple(new[k] for k in keys)

    x, out = jax.lax.scan(body, x, (stack, *[pool[k] for k in keys]),
                          unroll=unroll)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return logits_fn(cfg, params, x), dict(zip(keys, out))
