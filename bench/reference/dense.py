"""Plain float32 reference of a dense decoder-only transformer, and the
seeded weights the benchmark serves.

The block follows the published description of Qwen3 and GLM-4: pre-norm
RMSNorm, grouped-query attention with rotary embeddings (rotate-half over
the whole head), per-head q/k RMSNorm where the configuration sets
``qk_norm``, a SwiGLU MLP, a final RMSNorm and a tied or separate head.
Departures of the served block from a published model are listed in the
configuration file under ``assumed``; this module computes what the file
states.

Everything here is ``jax.numpy`` in float32 with every matrix product at
``Precision.HIGHEST``; the attention runs in blocks of queries so a
9k-token sequence fits next to the weights.  The weights are the seed's,
put on the configuration's 8-bit grid by the quantizer the configuration
names and dequantized exactly, in float32: how the program rounds them for
its compute is the program's, and the comparison in ``bench/check.py``
measures it.  ``control=True`` rounds both operands of every product to
float8 (e4m3, one scale per operand, as fp8 inference does): the precision
step below the configuration's bfloat16, used to show that the comparison
can fail.
"""
from __future__ import annotations

import functools
import math
import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
NORM = "norm"               # norm weights: uniform in [0.8, 1.2], float32
Q_BLOCK = 512               # queries per attention block


def weight_specs(d) -> Dict[str, Tuple[Tuple[int, ...], object]]:
    """``name -> (shape, init)``; init is a normal's std or ``NORM``.

    Projections into a residual branch get std ``fan_in ** -0.5`` so every
    layer moves the residual stream by about its own size: with the small
    residual init of a training run, random layers would add almost
    nothing and a tied head would echo the input token, which no
    comparison of served tokens could tell from a broken layer.  Names and
    layouts are those the served model takes (layers stacked on axis 0,
    ``x @ w`` orientation)."""
    L, D, H, KV, hd, F, V = (d.layers, d.d_model, d.heads, d.kv_heads,
                             d.head_dim, d.d_ff, d.vocab)
    s = {
        "embed": ((V, D), 0.02),
        "final_norm": ((D,), NORM),
        "layers/attn_norm": ((L, D), NORM),
        "layers/wq": ((L, D, H * hd), D ** -0.5),
        "layers/wk": ((L, D, KV * hd), D ** -0.5),
        "layers/wv": ((L, D, KV * hd), D ** -0.5),
        "layers/wo": ((L, H * hd, D), (H * hd) ** -0.5),
        "layers/mlp_norm": ((L, D), NORM),
        "layers/w_gate": ((L, D, F), D ** -0.5),
        "layers/w_up": ((L, D, F), D ** -0.5),
        "layers/w_down": ((L, F, D), F ** -0.5),
    }
    if not d.tie:
        s["lm_head"] = ((D, V), 0.02)
    if d.qk_norm:
        s["layers/q_norm"] = ((L, hd), NORM)
        s["layers/k_norm"] = ((L, hd), NORM)
    return s


def seed_key(seed: int) -> jax.Array:
    """A raw threefry key from every bit of ``seed`` (``PRNGKey`` keeps
    only the low 32 bits of a larger seed)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jnp.asarray(words, jnp.uint32)


def _divisor(n: int, cap: int) -> int:
    cap = max(1, min(n, cap))
    return max(b for b in range(1, cap + 1) if n % b == 0)


def _normal_rows(key, shape, std):
    """bf16 normal draws made in row blocks, so the float32 temporary is
    one block and not the whole tensor."""
    cols = shape[-1]
    rows = math.prod(shape[:-1])
    block = _divisor(rows, (1 << 22) // cols)
    keys = jax.random.split(key, rows // block)
    out = jax.lax.map(lambda k: (std * jax.random.normal(
        k, (block, cols), jnp.float32)).astype(jnp.bfloat16), keys)
    return out.reshape(shape)


def _generate(specs, key):
    out = {}
    for name, (shape, init) in specs:
        k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
        if init == NORM:
            out[name] = jax.random.uniform(k, shape, jnp.float32, 0.8, 1.2)
        else:
            out[name] = _normal_rows(k, shape, float(init))
    return out


def make_weights(d, seed: int) -> Dict[str, jax.Array]:
    """Every weight from ``seed``, on the device, in one jitted call:
    bfloat16 matrices, float32 norm weights."""
    specs = tuple(sorted(weight_specs(d).items()))
    return jax.jit(functools.partial(_generate, specs))(seed_key(seed))


# ------------------------------------------------------------ quantization

@functools.partial(jax.jit, static_argnames="bits")
def quantize_on_grid(w: jax.Array, bits: int) -> jax.Array:
    """The EntroLLM grid: one (scale, zero) per index of axis 0;
    symmetric-unsigned when the whole tensor has one sign, asymmetric (zero
    at the channel minimum) otherwise.  Returns ``q * scale + zero`` in
    float32, the weight the grid stands for."""
    qmax = float((1 << bits) - 1)
    red = tuple(range(1, w.ndim))
    lo = jnp.min(w, axis=red, keepdims=True)
    hi = jnp.max(w, axis=red, keepdims=True)
    one_sign = jnp.max(w) * jnp.min(w) >= 0.0
    absmax = jnp.where(jnp.abs(hi) >= jnp.abs(lo), hi, lo)
    sym_scale = jnp.where(absmax == 0.0, 1.0, absmax / qmax)
    asym_scale = (hi - lo) / qmax
    asym_scale = jnp.where(asym_scale == 0.0, 1.0, asym_scale)
    scale = jnp.where(one_sign, sym_scale, asym_scale)
    zero = jnp.where(one_sign, 0.0, lo)
    q = jnp.clip(jnp.round((w - zero) / scale), 0.0, qmax)
    return q * scale + zero


def reference_weights(d, seed: int, bits: int) -> Dict[str, jax.Array]:
    """The served weights as the reference sees them: regenerated from the
    seed, matrices on the ``bits``-bit grid and dequantized exactly, norm
    weights as drawn; all float32 arrays."""
    out = {}
    for name, w in make_weights(d, seed).items():
        w = w.astype(jnp.float32)
        out[name] = w if NORM in name else quantize_on_grid(w, bits)
    return out


# ------------------------------------------------------------------ forward

def _fp8(t):
    """Round to float8 e4m3 under one per-tensor scale; back in float32."""
    s = jnp.maximum(jnp.max(jnp.abs(t)), 1e-30) / 448.0
    return (t / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _dot(spec, a, b, control):
    if control:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x: (S, n, hd); rotate-half over the whole head."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, control):
    """Causal attention; q: (S, H, hd), k/v: (S, KV, hd), S a multiple of
    ``Q_BLOCK``.  Query heads h use KV head h // (H / KV)."""
    S, H, hd = q.shape
    g = H // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    kpos = jnp.arange(S)

    def block(args):
        qb, q0 = args
        s = _dot("qhd,khd->hqk", qb * hd ** -0.5, k, control)
        qpos = q0 + jnp.arange(qb.shape[0])
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _dot("hqk,khd->qhd", p, v, control)

    nb = S // Q_BLOCK
    out = jax.lax.map(block, (q.reshape(nb, Q_BLOCK, H, hd),
                              jnp.arange(nb) * Q_BLOCK))
    return out.reshape(S, H, hd)


@functools.partial(jax.jit, static_argnames=("d", "control"))
def _layer(x, lp, *, d, control):
    S = x.shape[0]
    H, KV, hd = d.heads, d.kv_heads, d.head_dim
    pos = jnp.arange(S)
    h = _rms(x, lp["attn_norm"], d.eps)
    q = _dot("sd,dn->sn", h, lp["wq"], control).reshape(S, H, hd)
    k = _dot("sd,dn->sn", h, lp["wk"], control).reshape(S, KV, hd)
    v = _dot("sd,dn->sn", h, lp["wv"], control).reshape(S, KV, hd)
    if d.qk_norm:
        q = _rms(q, lp["q_norm"], d.eps)
        k = _rms(k, lp["k_norm"], d.eps)
    q = _rope(q, pos, d.rope_theta)
    k = _rope(k, pos, d.rope_theta)
    a = _attention(q, k, v, control).reshape(S, H * hd)
    x = x + _dot("sn,nd->sd", a, lp["wo"], control)
    h = _rms(x, lp["mlp_norm"], d.eps)
    m = jax.nn.silu(_dot("sd,df->sf", h, lp["w_gate"], control)) \
        * _dot("sd,df->sf", h, lp["w_up"], control)
    return x + _dot("sf,fd->sd", m, lp["w_down"], control)


@functools.partial(jax.jit, static_argnames=("d", "control"))
def _head(x, idx, final_norm, head, *, d, control):
    x = _rms(x[idx], final_norm, d.eps)
    # a tied head is the (vocab, d_model) embedding itself
    return _dot("nd,vd->nv" if d.tie else "nd,dv->nv", x, head, control)


def logits_at(d, w: Dict[str, jax.Array], tokens: np.ndarray,
              rows: np.ndarray, *, control: bool = False) -> jax.Array:
    """Float32 logits ``(len(rows), vocab)`` at positions ``rows`` of one
    sequence ``tokens``.  Pad ``tokens`` at the end to a fixed length: the
    attention is causal, so padding changes no earlier position."""
    if len(tokens) % Q_BLOCK:
        raise ValueError(f"sequence length {len(tokens)} is not a multiple "
                         f"of {Q_BLOCK}")
    x = w["embed"][jnp.asarray(tokens, jnp.int32)]
    for layer in range(d.layers):
        lp = {k.split("/", 1)[1]: v[layer] for k, v in w.items()
              if k.startswith("layers/")}
        x = _layer(x, lp, d=d, control=control)
    head = w["embed"] if d.tie else w["lm_head"]
    return _head(x, jnp.asarray(rows, jnp.int32), w["final_norm"], head,
                 d=d, control=control)
