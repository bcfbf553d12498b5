"""Shared model-building blocks (pure JAX, functional, schema-driven).

Parameters live in a flat ``{name: array}`` dict; a parallel schema maps each name to
``(shape, logical_axes, init)``.  Logical axes (e.g. ``"vocab"``, "heads", "mlp",
"expert") are resolved to mesh axes by ``repro.distributed.sharding`` — models know
nothing about meshes.

Weight tensors may be plain arrays OR :class:`QT` triples (quantized weight + scale +
zero) — ``matmul``/``take`` dequantize on the fly, which XLA fuses into the consuming
dot, keeping integer bytes on the HBM path (the EntroLLM serving mode).  When the
``repro.kernels`` Pallas path is enabled, ``matmul`` routes to the fused dequant-matmul
kernel instead.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.fused_decode_matmul import FusedQT, fused_decode_matmul
from repro.obs import metrics as obs_metrics

# --------------------------------------------------------------------------- schema

Axes = Tuple[Optional[str], ...]


@dataclasses.dataclass(frozen=True)
class Spec:
    shape: Tuple[int, ...]
    axes: Axes                      # logical axis names, len == len(shape)
    init: Any = 0.02                # float std | "zeros" | "ones" | "a_log" | "dt_bias"
    dtype: Any = jnp.bfloat16       # norms/ssm-sensitive params use f32

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


Schema = Dict[str, Spec]


def init_param(key: jax.Array, spec: Spec) -> jax.Array:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    if spec.init == "a_log":        # mamba2: A in [-16, -1] via log
        u = jax.random.uniform(key, spec.shape, jnp.float32, 1.0, 16.0)
        return jnp.log(u).astype(spec.dtype)
    if spec.init == "dt_bias":      # softplus^-1 of dt in [1e-3, 1e-1]
        u = jax.random.uniform(key, spec.shape, jnp.float32, 1e-3, 1e-1)
        return jnp.log(jnp.expm1(u)).astype(spec.dtype)
    std = float(spec.init)
    return (std * jax.random.normal(key, spec.shape, jnp.float32)).astype(spec.dtype)


def init_params(schema: Schema, key: jax.Array) -> Dict[str, jax.Array]:
    names = sorted(schema)
    keys = jax.random.split(key, len(names))
    return {n: init_param(k, schema[n]) for n, k in zip(names, keys)}


# ----------------------------------------------------------------- quantized weights

class QT(NamedTuple):
    """Quantized weight triple; leaves integer bytes on the HBM path."""
    q: jax.Array        # uint8 symbols
    scale: jax.Array    # f32 broadcastable
    zero: jax.Array     # f32 broadcastable


class QT4(NamedTuple):
    """int4 weights packed two-per-byte along the LAST axis (see
    kernels.ops.pack_nibbles): q[..., j] holds symbol 2j in the low nibble and
    symbol 2j+1 in the high nibble.  Unpacking is shifts + interleave — cheap,
    fusable, and halves the HBM bytes of the uint8 path again."""
    q: jax.Array        # uint8, last dim = N/2
    scale: jax.Array
    zero: jax.Array


def _unpack4(q: jax.Array) -> jax.Array:
    lo = q & jnp.uint8(0x0F)
    hi = q >> jnp.uint8(4)
    return jnp.stack([lo, hi], axis=-1).reshape(*q.shape[:-1], q.shape[-1] * 2)


def pack_qt(q: np.ndarray, scale: np.ndarray, zero: np.ndarray, *,
            bits: int, pack_int4: bool = True) -> "QT | QT4":
    """Host ``(q, scale, zero)`` symbols -> the serving-resident triple.

    The ONE packing rule both weight loaders share (whole-model
    ``load_params_from_compressed`` and the per-layer compressed-resident
    decode): 4-bit symbols with an even last dim pack nibble pairs into
    :class:`QT4` (0.5 bytes/param resident), everything else stays a
    :class:`QT` of uint8 symbols.  Packing a full stacked tensor and then
    slicing a layer is byte-identical to packing the layer's slice, which is
    what keeps the two residency modes interchangeable.
    """
    q = np.asarray(q)
    if bits == 4 and pack_int4 and q.shape[-1] % 2 == 0:
        packed = (q[..., 0::2] | (q[..., 1::2] << 4)).astype(np.uint8)
        return QT4(packed, np.asarray(scale), np.asarray(zero))
    return QT(q, np.asarray(scale), np.asarray(zero))


class QTG(NamedTuple):
    """Quantized weight with a gradient path to the bf16 master (training's
    compressed-FSDP-gather mode): forward computes from the uint8 symbols
    (the master is dead code, so only integer bytes cross the FSDP
    all-gather); backward is a straight-through estimator into the master."""
    q: jax.Array        # uint8 symbols (packed nibbles when bits == 4)
    scale: jax.Array
    zero: jax.Array
    master: jax.Array   # bf16 FSDP-sharded master weight (grad target)
    # static marker for 4-bit packing rides in scale's trailing dim (see deq)


@jax.custom_vjp
def _ste_deq(master, q, scale, zero):
    # packed-nibble detection is static: packed q has half the master's
    # trailing dim
    sym = _unpack4(q) if q.shape[-1] != master.shape[-1] else q
    return (sym.astype(jnp.bfloat16) * scale.astype(jnp.bfloat16)
            + zero.astype(jnp.bfloat16))


def _ste_deq_fwd(master, q, scale, zero):
    return _ste_deq(master, q, scale, zero), None


def _ste_deq_bwd(_, g):
    # straight-through: full gradient to the master weight
    return g.astype(jnp.bfloat16), None, None, None


_ste_deq.defvjp(_ste_deq_fwd, _ste_deq_bwd)


def gather_weight(w: Any) -> Any:
    """Exact sharded serving: all-gather a HBM-sharded weight (or each part
    of a QT/QT4/QTG triple) at its use site.  Identity unless ``exact_tp``
    serving hints are installed (training / single-device paths unchanged)."""
    from repro.distributed.ctx import constrain_replicated, get_hints
    h = get_hints()
    if h is None or not h.exact_tp:
        return w
    if isinstance(w, (QT, QT4, QTG)):
        return type(w)(*(constrain_replicated(p) for p in w))
    return constrain_replicated(w)


def deq(w: Any, dtype=jnp.bfloat16) -> jax.Array:
    w = gather_weight(w)
    if isinstance(w, QT):
        return w.q.astype(dtype) * w.scale.astype(dtype) + w.zero.astype(dtype)
    if isinstance(w, QT4):
        return (_unpack4(w.q).astype(dtype) * w.scale.astype(dtype)
                + w.zero.astype(dtype))
    if isinstance(w, QTG):
        return _ste_deq(w.master, w.q, w.scale, w.zero).astype(dtype)
    return w.astype(dtype) if w.dtype != dtype else w


def round_bf16(w: jax.Array) -> jax.Array:
    """``w`` rounded to bf16's grid (round-to-nearest-even, exactly as
    ``w.astype(bf16)``) and kept float32."""
    return jax.lax.reduce_precision(w, exponent_bits=8, mantissa_bits=7)


def _rounds_weight(x: jax.Array, w: Any) -> bool:
    """Whether ``matmul`` rounds ``w`` to bf16 itself when lowered for the
    TPU: a plain float32 weight against float32 or bf16 activations under
    the default matmul precision, where the TPU dot takes both operands in
    bf16 anyway."""
    return (not isinstance(w, (QT, QT4, QTG, FusedQT))
            and w.dtype == jnp.float32
            and x.dtype in (jnp.float32, jnp.bfloat16)
            and jax.config.jax_default_matmul_precision is None)


def matmul(x: jax.Array, w: Any, dim_nums: Optional[str] = None) -> jax.Array:
    """x @ w with on-the-fly dequantization (fused by XLA into the dot).

    Under exact-TP serving hints ``deq`` all-gathers the HBM-sharded weight
    first, so the dot reads a full-shape buffer and rounds exactly like the
    single-device program (sharded residency, replicated compute).

    A :class:`~repro.kernels.fused_decode_matmul.FusedQT` weight routes to
    the fused entropy-decode→dequant→matmul kernel — the weight never
    exists densely; the handle's jit path runs the exact ``deq`` ops after
    an in-graph decode, so it stays bit-identical to a QT slot.

    A float32 weight is rounded to bf16 here, inside the dot's fusion, when
    the program is lowered for the TPU, whose default-precision dot takes
    it in bf16 anyway: left to the dot (or to ``deq``'s cast for bf16
    activations), the rounding becomes a bf16 copy of the whole
    layer-stacked weight, written before the layer scan on every call.
    The dot accumulates in f32 and its output keeps ``x``'s dtype, as
    before.  Every other platform keeps the plain dot, which does not
    round.  The counter ``matmul.weight`` (label
    ``operand=rounded_f32|as_given``) counts each trace's choice.
    """
    def dot(x, wd):
        return x @ wd if dim_nums is None else jnp.einsum(dim_nums, x, wd)

    rounds = _rounds_weight(x, w)
    obs_metrics.counter("matmul.weight").inc(
        operand="rounded_f32" if rounds else "as_given")
    if isinstance(w, FusedQT):
        assert dim_nums is None, "FusedQT weights support plain x @ w only"
        return fused_decode_matmul(x, w)
    if not rounds:
        return dot(x, deq(w, x.dtype))
    return jax.lax.platform_dependent(
        x, gather_weight(w),
        tpu=lambda x, w: dot(x, round_bf16(w)).astype(x.dtype),
        default=lambda x, w: dot(x, w.astype(x.dtype)))


def take_rows(w: Any, idx: jax.Array) -> jax.Array:
    """Embedding lookup honoring quantized tables (dequantize only gathered rows)."""
    w = gather_weight(w)
    if isinstance(w, QTG):
        rows = jnp.take(w.q, idx, axis=0)
        master_rows = jnp.take(w.master, idx, axis=0)
        scale = w.scale if w.scale.shape[0] == 1 \
            else jnp.take(w.scale, idx, axis=0)
        zero = w.zero if w.zero.shape[0] == 1 \
            else jnp.take(w.zero, idx, axis=0)
        return _ste_deq(master_rows, rows, scale, zero)
    if isinstance(w, QT4):
        rows = _unpack4(jnp.take(w.q, idx, axis=0))
        scale = w.scale if w.scale.ndim == 0 or w.scale.shape[0] == 1 \
            else jnp.take(w.scale, idx, axis=0)
        zero = w.zero if w.zero.ndim == 0 or w.zero.shape[0] == 1 \
            else jnp.take(w.zero, idx, axis=0)
        return rows.astype(jnp.bfloat16) * scale.astype(jnp.bfloat16) \
            + zero.astype(jnp.bfloat16)
    if isinstance(w, QT):
        rows = jnp.take(w.q, idx, axis=0)
        scale = w.scale if w.scale.ndim == 0 or w.scale.shape[0] == 1 \
            else jnp.take(w.scale, idx, axis=0)
        zero = w.zero if w.zero.ndim == 0 or w.zero.shape[0] == 1 \
            else jnp.take(w.zero, idx, axis=0)
        return rows.astype(jnp.bfloat16) * scale.astype(jnp.bfloat16) \
            + zero.astype(jnp.bfloat16)
    return jnp.take(w, idx, axis=0)


# ------------------------------------------------------------------------ primitives

def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale.astype(x.dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float,
         rot_dim: Optional[int] = None, pairs: str = "half") -> jax.Array:
    """Rotary embedding.  x: (..., S, n, hd); positions: (..., S) int32.

    The first ``rot_dim`` dims of each head (default: all of it) rotate at
    frequencies ``theta ** (-2i / rot_dim)``; the rest pass through.
    ``pairs="half"`` rotates ``(x[i], x[i + rot_dim/2])`` (rotate-half);
    ``"interleaved"`` rotates ``(x[2i], x[2i+1])`` (GLM, GPT-J)."""
    hd = x.shape[-1]
    rot = hd if rot_dim is None else rot_dim
    half = rot // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs          # (..., S, half)
    cos = jnp.cos(angles)[..., None, :]                                 # (..., S, 1, half)
    sin = jnp.sin(angles)[..., None, :]
    if pairs == "interleaved":
        xr = x[..., :rot].reshape(x.shape[:-1] + (half, 2))
        x1, x2 = xr[..., 0], xr[..., 1]
    else:
        x1, x2 = x[..., :half], x[..., half:rot]
    r1 = x1 * cos.astype(x.dtype) - x2 * sin.astype(x.dtype)
    r2 = x2 * cos.astype(x.dtype) + x1 * sin.astype(x.dtype)
    if pairs == "interleaved":
        out = jnp.stack([r1, r2], axis=-1).reshape(x.shape[:-1] + (rot,))
    else:
        out = jnp.concatenate([r1, r2], axis=-1)
    if rot < hd:
        out = jnp.concatenate([out, x[..., rot:]], axis=-1)
    return out


def swiglu(x: jax.Array, w_gate: Any, w_up: Any, w_down: Any) -> jax.Array:
    g = matmul(x, w_gate)
    u = matmul(x, w_up)
    return matmul(jax.nn.silu(g) * u, w_down)


# -------------------------------------------------------------------------- attention

NEG_INF = -1e9


def gqa_attention(
    q: jax.Array,              # (B, S, H, hd)
    k: jax.Array,              # (B, T, KV, hd)
    v: jax.Array,              # (B, T, KV, hd)
    *,
    causal: bool,
    q_offset: Any = 0,         # global position of q[0]: scalar, or (B,) per-slot
    kv_len: Optional[jax.Array] = None,   # valid cache length: scalar, or (B,) per-slot
    q_block: int = 0,          # 0 = single block; else scan over q blocks
    unroll: int = 1,
) -> jax.Array:
    """Grouped-query attention with optional q-block chunking.

    ``q_offset`` and ``kv_len`` accept either scalars (lockstep batch: every
    row at the same position) or ``(B,)`` arrays (slot batch: each row is an
    independent request with its own cache length — the continuous-batching
    serving mode).  Per-slot offsets disable q-block chunking (the block scan
    would need ragged bases); callers pass ``q_block=0`` on that path.

    Head grouping: ``q`` is viewed as ``(B, S, KV, G, hd)`` and the scores
    and weighted values are contracted against K/V exactly as stored,
    ``(B, T, KV, hd)``, so no tensor with H heads over the T cache rows is
    ever built (decode reads the cache once per KV head).  That grouped form
    is taken whenever the installed sharding hints allow it
    (:func:`repro.distributed.ctx.kv_heads_cover_model`): no hints, exact
    serving, or a model axis that divides KV — the KV axis then carries the
    model sharding.  Only where the model axis is wider than the KV heads
    can cover are K/V broadcast up to all H heads first (MaxText-style "KV
    replication", G = 1 per head), so that the merged H axis shards over the
    model axis and each chip materializes only its own H/|model| slice.  The
    counter ``attn.form`` (label ``form=grouped|replicated``) counts each
    trace's choice.

    Chunking over the query axis bounds the live (Qb x T) score tensor — the
    memory-realistic lowering used by the dry-run for long-sequence prefill
    (the softmax over T is exact per block; no online accumulation needed).
    """
    from repro.distributed.ctx import (constrain_heads, constrain_scores,
                                       kv_heads_cover_model)
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = hd ** -0.5
    replicate = G > 1 and not kv_heads_cover_model(KV)
    obs_metrics.counter("attn.form").inc(
        form="replicated" if replicate else "grouped")
    if replicate:
        k = jnp.broadcast_to(k[:, :, :, None, :], (B, T, KV, G, hd)
                             ).reshape(B, T, H, hd)
        v = jnp.broadcast_to(v[:, :, :, None, :], (B, T, KV, G, hd)
                             ).reshape(B, T, H, hd)
        KV, G = H, 1
    q = constrain_heads(q.reshape(B, S, KV, G, hd))
    k = constrain_heads(k, is_cache_side=True)
    v = constrain_heads(v, is_cache_side=True)

    def block(qb: jax.Array, qpos: jax.Array) -> jax.Array:
        # qb: (B, Sb, KV, G, hd); qpos: (Sb,) or (B, Sb) global positions
        # bf16 operands + f32 accumulation (MXU-style): keeps the KV-cache
        # read at 2 bytes/element — an f32 cast before the dot doubles the
        # cache wire/HBM traffic (EXPERIMENTS.md §Perf H1 iteration 2)
        s = jnp.einsum("bskgh,btkh->bkgst", (qb * scale).astype(jnp.bfloat16),
                       k.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
        s = constrain_scores(s)                       # (B, KV, G, Sq, T)
        tpos = jnp.arange(T)
        mask = jnp.ones(qpos.shape + (T,), bool)      # (Sb, T) or (B, Sb, T)
        if causal:
            mask &= tpos <= qpos[..., None]
        if kv_len is not None:
            kl = jnp.asarray(kv_len)
            # scalar broadcasts; (B,) reshapes to (B, 1, 1) against (B, Sb, T)
            if kl.ndim == 1:
                mask = mask & (tpos < kl[:, None, None])
            else:
                mask &= tpos < kl
        while mask.ndim < 3:                          # -> (B|1, Sb, T)
            mask = mask[None]
        s = jnp.where(mask[:, None, None], s, NEG_INF)  # (B|1, 1, 1, Sq, T)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgst,btkh->bskgh", p.astype(v.dtype), v)
        return o.reshape(qb.shape[:2] + (H, hd))

    qpos0 = jnp.asarray(q_offset)[..., None] + jnp.arange(S)  # (S,) or (B, S)
    if q_block <= 0 or q_block >= S or qpos0.ndim > 1:
        return block(q, qpos0)

    assert S % q_block == 0, (S, q_block)
    nb = S // q_block
    qb = q.reshape(B, nb, q_block, KV, G, hd)

    def body(_, qi):
        qblk, base = qi
        return None, block(qblk, base + jnp.arange(q_block))

    bases = q_offset + jnp.arange(nb) * q_block
    _, outs = jax.lax.scan(body, None, (jnp.moveaxis(qb, 1, 0), bases), unroll=unroll)
    return jnp.moveaxis(outs, 0, 1).reshape(B, S, H, hd)


def update_kv_cache(cache_k: jax.Array, cache_v: jax.Array, k: jax.Array, v: jax.Array,
                    pos: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Write step-k/v (B, S, KV, hd) into preallocated (B, T, KV, hd) caches.

    ``pos`` is the write offset along T: a scalar writes every batch row at
    the same position (lockstep decode), a ``(B,)`` array writes each row at
    its own position (slot batch — every slot tracks an independent
    ``kv_len``, so a freshly admitted request and a request 100 tokens deep
    share one fused cache update).
    """
    if jnp.ndim(pos) == 1:
        def row(c, x, p):
            return jax.lax.dynamic_update_slice(c, x.astype(c.dtype), (p, 0, 0))
        return jax.vmap(row)(cache_k, k, pos), jax.vmap(row)(cache_v, v, pos)
    ck = jax.lax.dynamic_update_slice(cache_k, k.astype(cache_k.dtype), (0, pos, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache_v, v.astype(cache_v.dtype), (0, pos, 0, 0))
    return ck, cv


# ---------------------------------------------------------------------- loss helpers

def softmax_xent(logits: jax.Array, labels: jax.Array, vocab: int) -> jax.Array:
    """Mean token cross-entropy; labels >= vocab (padding) are masked out."""
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(
        logits.astype(jnp.float32), labels[..., None].clip(0, logits.shape[-1] - 1),
        axis=-1)[..., 0]
    mask = (labels >= 0) & (labels < vocab)
    return jnp.sum((lse - ll) * mask) / jnp.maximum(jnp.sum(mask), 1)
