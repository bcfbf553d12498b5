"""Plain float32 reference of GLM-4's block (THUDM/glm-4-9b-chat), and the
seeded weights the benchmark serves.

The block is ``dense.py``'s (pre-norm RMSNorm, grouped-query attention, a
SwiGLU MLP, a final RMSNorm and a separate output head) with GLM-4's own
attention, from its published ``modeling_chatglm.py``:

- Q, K and V carry a bias (``add_qkv_bias``); the output projection and the
  MLP have none (``add_bias_linear`` false);
- the rotary embedding rotates interleaved pairs ``(x[2i], x[2i+1])`` of
  the first half of each head only (64 of ``kv_channels`` 128) at
  frequencies ``base ** (-2i / 64)``, ``base = 10000 * rope_ratio``; the
  other half passes through unrotated;
- RMSNorm's epsilon is the file's ``layernorm_epsilon`` (1.5625e-7).

Departures from the published model, each also in the configuration file:
the weights are random from the seed (biases normal with std
``BIAS_STD``), the depth is cut, and the vocabulary is one chip's slice of
a vocabulary-parallel deployment (ids, logits and sampling are over the
slice).

The biases are drawn as small as the embedding.  V's bias passes the
attention unaveraged, so a larger one drowns the token signal and the
layers answer nearly one token whatever the input: at std 0.5, 2-5
distinct greedy picks over 512 positions of random tokens, against
232-317 at 0.02 and 294-358 with no bias (width 512, two seeds, on the
CPU).  Served tokens could then not tell a broken layer, and on one TPU
v5e the float8 control passed the cell's check on two seeds of six.

Float32 throughout, every matrix product at ``Precision.HIGHEST``; the
attention runs in blocks of ``Q_BLOCK`` queries.  Matrices are put on the
configuration's 8-bit grid and dequantized exactly; norm weights and
biases stay as drawn, as the program's compression keeps them (float32,
not quantized).  ``control=True`` rounds both operands of every product to
float8, as in ``dense.py``.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import dense

Q_BLOCK = dense.Q_BLOCK
NORM = dense.NORM
BIAS_STD = 0.02             # Q/K/V biases: normal, the embedding's std
ROPE_FRACTION = 0.5         # share of each head that rotates
BIASES = ("layers/q_bias", "layers/k_bias", "layers/v_bias")


def weight_specs(d) -> Dict[str, Tuple[Tuple[int, ...], object]]:
    """``dense.weight_specs`` and the Q/K/V biases, ``(layers, width)``."""
    s = dense.weight_specs(d)
    L, H, KV, hd = d.layers, d.heads, d.kv_heads, d.head_dim
    s["layers/q_bias"] = ((L, H * hd), BIAS_STD)
    s["layers/k_bias"] = ((L, KV * hd), BIAS_STD)
    s["layers/v_bias"] = ((L, KV * hd), BIAS_STD)
    return s


def make_weights(d, seed: int) -> Dict[str, jax.Array]:
    """Every weight from ``seed``, on the device, in one jitted call:
    bfloat16 matrices and biases, float32 norm weights."""
    specs = tuple(sorted(weight_specs(d).items()))
    return jax.jit(functools.partial(dense._generate, specs))(
        dense.seed_key(seed))


def reference_weights(d, seed: int, bits: int) -> Dict[str, jax.Array]:
    """The served weights as the reference sees them: matrices on the
    ``bits``-bit grid and dequantized exactly, norm weights and biases as
    drawn; all float32 arrays."""
    out = {}
    for name, w in make_weights(d, seed).items():
        w = w.astype(jnp.float32)
        keep = NORM in name or name in BIASES
        out[name] = w if keep else dense.quantize_on_grid(w, bits)
    return out


def _rope(x, pos, theta):
    """x: (S, n, hd); interleaved pairs over the first ROPE_FRACTION of
    each head, the rest unrotated."""
    rot = int(x.shape[-1] * ROPE_FRACTION)
    half = rot // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    pairs = x[..., :rot].reshape(x.shape[:-1] + (half, 2))
    x0, x1 = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin], axis=-1)
    return jnp.concatenate([out.reshape(x.shape[:-1] + (rot,)),
                            x[..., rot:]], axis=-1)


@functools.partial(jax.jit, static_argnames=("d", "control"))
def _layer(x, lp, *, d, control):
    S = x.shape[0]
    H, KV, hd = d.heads, d.kv_heads, d.head_dim
    pos = jnp.arange(S)
    h = dense._rms(x, lp["attn_norm"], d.eps)
    q = (dense._dot("sd,dn->sn", h, lp["wq"], control) + lp["q_bias"]
         ).reshape(S, H, hd)
    k = (dense._dot("sd,dn->sn", h, lp["wk"], control) + lp["k_bias"]
         ).reshape(S, KV, hd)
    v = (dense._dot("sd,dn->sn", h, lp["wv"], control) + lp["v_bias"]
         ).reshape(S, KV, hd)
    q = _rope(q, pos, d.rope_theta)
    k = _rope(k, pos, d.rope_theta)
    a = dense._attention(q, k, v, control).reshape(S, H * hd)
    x = x + dense._dot("sn,nd->sd", a, lp["wo"], control)
    h = dense._rms(x, lp["mlp_norm"], d.eps)
    m = jax.nn.silu(dense._dot("sd,df->sf", h, lp["w_gate"], control)) \
        * dense._dot("sd,df->sf", h, lp["w_up"], control)
    return x + dense._dot("sf,fd->sd", m, lp["w_down"], control)


def logits_at(d, w: Dict[str, jax.Array], tokens: np.ndarray,
              rows: np.ndarray, *, control: bool = False) -> jax.Array:
    """Float32 logits ``(len(rows), vocab)`` at positions ``rows`` of one
    sequence ``tokens``, padded at the end to a multiple of ``Q_BLOCK``."""
    if len(tokens) % Q_BLOCK:
        raise ValueError(f"sequence length {len(tokens)} is not a multiple "
                         f"of {Q_BLOCK}")
    if d.tie:
        raise ValueError("GLM-4's output layer is separate from its "
                         "embedding (tie_word_embeddings false)")
    x = w["embed"][jnp.asarray(tokens, jnp.int32)]
    for layer in range(d.layers):
        lp = {k.split("/", 1)[1]: v[layer] for k, v in w.items()
              if k.startswith("layers/")}
        x = _layer(x, lp, d=d, control=control)
    return dense._head(x, jnp.asarray(rows, jnp.int32), w["final_norm"],
                       w["lm_head"], d=d, control=control)
