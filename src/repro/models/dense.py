"""Dense decoder-only transformer (chameleon / stablelm / command-r+ / glm4 / qwen3).

Layers are stacked on a leading axis and iterated with ``lax.scan`` whose ``unroll``
degree is a lowering knob: smoke tests keep it rolled (fast compile), the dry-run
unrolls fully so ``cost_analysis`` counts every layer.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.obs import metrics as obs_metrics
from . import kv
from .layers import (Schema, Spec, gqa_attention, init_params, matmul,
                     rms_norm, rope, softmax_xent, swiglu, take_rows)


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
          cache: Optional[Dict[str, jax.Array]] = None, pos=None, layer=None,
          table=None, q_block: int = 0, unroll: int = 1):
    """Attention sub-block; returns (out, cache).

    Without ``cache``: causal attention over the whole sequence, returning
    this layer's (k, v) (`forward`).  With ``cache`` — the layer-stacked
    leaves of any :mod:`kv` layout — the step's rows are stored at layer
    ``layer`` and that layer is read back (``kv.store`` / ``kv.load``), so
    a scan that carries the stack updates it in place.  ``pos`` is the
    write offset, scalar (lockstep batch) or ``(B,)`` (slot batch, one
    position per row); ``S`` may exceed 1: the chunk is written at
    ``[pos, pos + S)`` and attended causally against the cache (chunked
    prefill).  ``table`` is the paged pool's (B, MB) block table.
    """
    B, S, D = x.shape
    H, hd = cfg.n_heads, cfg.hd
    q, k, v = _qkv(cfg, lp, x, positions)
    if cache is None:
        attn = gqa_attention(q, k, v, causal=True, q_block=q_block, unroll=unroll)
        cache = (k, v)
    else:
        cache = kv.store(cache, k, v, layer, pos, table)
        ck, cv = kv.load(cache, layer, hd, table)
        attn = gqa_attention(q, ck, cv, causal=S > 1, q_offset=pos,
                             kv_len=pos + S)
    out = matmul(attn.reshape(B, S, H * hd), lp["wo"])
    return out, cache


def _mlp(cfg: ArchConfig, lp, h):
    """The dense family's feed-forward, as ``(y, aux)`` like every
    family's MLP (the dense one has no auxiliary loss)."""
    return swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"]), None


def _block(cfg: ArchConfig, lp, x, *, mlp=_mlp, **attn_kw):
    """One decoder layer: attention, then the family's ``mlp``.
    Returns (x, cache, aux)."""
    attn_out, cache = _attn(cfg, lp, x, **attn_kw)
    x = x + attn_out
    h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    y, aux = mlp(cfg, lp, h)
    return x + y, cache, aux


# ------------------------------------------------------------- step pieces
#
# Embed, head and the two per-layer bodies are the functions `forward` and
# the cached step call, and the only ones: compressed-resident serving
# (docs/SERVING.md §"Compressed-resident serving") jits these same
# functions and loops the layers in Python, so its output is the
# whole-tree step's by construction.  A family other than dense passes its
# own ``mlp``.

def embed(cfg: ArchConfig, params, tokens):
    """Token embedding.  tokens: (B, S) int32 -> (B, S, D)."""
    from repro.distributed.ctx import constrain_activation
    return constrain_activation(take_rows(params["embed"], tokens))


def head(cfg: ArchConfig, params, x, *, last_only: bool = False):
    """Final norm and logits; ``last_only`` keeps the last position, as
    `prefill` does."""
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if last_only:
        x = x[:, -1:, :]
    return logits_fn(cfg, params, x)


def prefill_layer(cfg: ArchConfig, lp, x, positions, *, mlp=_mlp,
                  q_block: int = 0, unroll: int = 1):
    """`forward`'s per-layer body: causal attention over the whole
    sequence.  Returns (x, (k, v), aux)."""
    from repro.distributed.ctx import constrain_activation
    x, kv_rows, aux = _block(cfg, lp, x, positions=positions, mlp=mlp,
                             q_block=q_block, unroll=unroll)
    return constrain_activation(x), kv_rows, aux


def step_positions(pos, S: int):
    """Positions of a cached step's S tokens: (S,) for a scalar ``pos``,
    (B, S) for per-slot ``(B,)`` positions."""
    return jnp.asarray(pos)[..., None] + jnp.arange(S)


def cached_layer(cfg: ArchConfig, lp, x, cache, layer, pos, positions,
                 table=None, *, mlp=_mlp):
    """The cached step's per-layer body: store the step's rows into layer
    ``layer`` of the stacked ``cache`` and attend against it.  S comes
    from ``x``, so one body serves decode (S=1) and chunked prefill.
    Returns (x, cache).

    ``lp`` values may be dense arrays, QT/QT4 triples, or — under
    ``CompressedResidentWeights(fused=True)`` — FusedQT payload handles:
    every weight reaches ``layers.matmul``, whose dispatch decodes fused
    handles inside the matmul."""
    from repro.distributed.ctx import constrain_activation
    x, cache, _ = _block(cfg, lp, x, positions=positions, cache=cache,
                         pos=pos, layer=layer, table=table, mlp=mlp)
    return constrain_activation(x), cache


def forward(cfg: ArchConfig, params, tokens, *, unroll: int = 1, q_block: int = 0,
            remat: bool = False, collect_cache: bool = False):
    """Full-sequence forward.  Returns (hidden, cache|None)."""
    B, S = tokens.shape
    x = embed(cfg, params, tokens)
    positions = jnp.arange(S)
    stack = _layer_stack(params)

    def body(x, lp):
        x, kv_rows, _ = prefill_layer(cfg, lp, x, positions, q_block=q_block,
                                      unroll=unroll)
        return x, kv_rows if collect_cache else None

    fn = jax.checkpoint(body) if remat else body
    x, caches = jax.lax.scan(fn, x, stack, unroll=unroll)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, caches


def logits_fn(cfg: ArchConfig, params, x: jax.Array) -> jax.Array:
    w = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    if cfg.tie_embeddings:
        from .layers import deq
        return matmul(x, deq(w).T)
    return matmul(x, w)


def loss_fn(cfg: ArchConfig, params, batch, *, unroll: int = 1, q_block: int = 0,
            remat: bool = True) -> jax.Array:
    tokens = batch["tokens"]
    inp, labels = tokens[:, :-1], tokens[:, 1:]
    x, _ = forward(cfg, params, inp, unroll=unroll, q_block=q_block, remat=remat)
    return softmax_xent(logits_fn(cfg, params, x), labels, cfg.vocab)


# ------------------------------------------------------------------------- serving

init_cache = kv.init_cache
cache_specs = kv.cache_specs
init_kv_pool = kv.init_kv_pool


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


def _cached_layers(cfg: ArchConfig, params, tokens, cache, pos, table, unroll,
                   mlp):
    """Embed, the layer scan of `decode_step` and `prefill_chunk`, the head.

    The layer-stacked cache rides in the scan's carry next to ``x``; the
    weight stack and the layer index are the scanned inputs.  Each layer
    writes only its step rows into the carried stack and reads its own layer
    back, so XLA updates the donated cache in place.  (Scanned as input and
    returned as output, the stack would be copied whole, cut out and written
    back whole every step.)"""
    x = embed(cfg, params, tokens)
    positions = step_positions(pos, tokens.shape[1])

    def body(carry, xs):
        x, c = carry
        lp, l = xs
        return cached_layer(cfg, lp, x, c, l, pos, positions, table,
                            mlp=mlp), None

    layers = jnp.arange(cache["k"].shape[0])
    (x, cache), _ = jax.lax.scan(body, (x, cache),
                                 (_layer_stack(params), layers), unroll=unroll)
    return head(cfg, params, x), cache


def decode_step(cfg: ArchConfig, params, token, cache, pos, table=None, *,
                unroll: int = 1, mlp=_mlp):
    """One generation step.  token: (B, 1) int32; cache: any :mod:`kv`
    layout; pos: scalar position shared by the whole batch (lockstep) or
    (B,) per-slot positions (continuous batch).  With the paged pool,
    ``table`` is the (B, MB) block table (trash rows for inactive lanes)
    and ``pos`` the (B,) per-slot kv_len."""
    return _cached_layers(cfg, params, token, cache, pos, table, unroll, mlp)


def prefill_chunk(cfg: ArchConfig, params, tokens, cache, pos, table=None, *,
                  unroll: int = 1, mlp=_mlp):
    """Chunked prefill: write one prompt chunk into an existing cache.

    tokens: (B, S) int32 chunk; cache, pos and ``table`` as in
    :func:`decode_step` (the chunk occupies cache rows ``[pos, pos + S)``;
    the caller guarantees ``pos + S <= max_len``, and with a block table
    that those entries are allocated — see serving/kvcache/blocks.py).
    Returns (logits for every chunk position (B, S, V), cache) — the caller
    picks the logit at the request's true last prompt position, so ragged
    prompts ride in fixed-shape chunks (pad tokens land in the cache but stay
    masked forever because ``kv_len`` never reaches them).

    This is the admission path of continuous batching: a new request prefills
    chunk by chunk through ONE compiled shape while the decode batch keeps
    stepping between chunks, then the filled cache rows are spliced into a
    free slot.
    """
    return _cached_layers(cfg, params, tokens, cache, pos, table, unroll, mlp)
