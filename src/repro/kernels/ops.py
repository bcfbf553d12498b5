"""Jitted public wrappers around the Pallas kernels + packing utilities.

The wrappers run the compiled kernel unless the caller names interpret mode
(``interpret=True``); a kernel the compiler refuses raises its error.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .dequant_matmul import dequant_matmul as _dequant_matmul_pallas
from .huffman_decode import decode_streams_pallas


def pack_nibbles(q: np.ndarray) -> np.ndarray:
    """(K, N) uint8 symbols < 16 -> (K//2, N) packed bytes (even k low nibble)."""
    assert q.shape[0] % 2 == 0, q.shape
    lo = q[0::2]
    hi = q[1::2]
    return (lo | (hi << 4)).astype(np.uint8)


def unpack_nibbles(p: np.ndarray) -> np.ndarray:
    K2, N = p.shape
    out = np.empty((K2 * 2, N), np.uint8)
    out[0::2] = p & 0x0F
    out[1::2] = p >> 4
    return out


def dequant_matmul(x: jax.Array, wq: jax.Array, scale, zero, *,
                   int4: bool = False, interpret: bool = False,
                   block: Tuple[int, int, int] = (128, 128, 512)) -> jax.Array:
    """Fused dequant matmul with automatic padding to block multiples."""
    bm, bn, bk = block
    M, K = x.shape
    N = wq.shape[1]
    Mp, Np, Kp = (-(-M // bm) * bm, -(-N // bn) * bn, -(-K // bk) * bk)
    xpad = jnp.pad(x, ((0, Mp - M), (0, Kp - K))) if (Mp, Kp) != (M, K) else x
    if int4:
        # packed rows: K/2 bytes along axis 0; pad at the end keeps alignment
        wpad = jnp.pad(wq, ((0, (Kp - K) // 2), (0, Np - N))) \
            if (Kp, Np) != (K, N) else wq
    else:
        wpad = jnp.pad(wq, ((0, Kp - K), (0, Np - N))) if (Kp, Np) != (K, N) else wq
    if jnp.size(scale) > 1:
        scale = jnp.pad(jnp.asarray(scale, jnp.float32).reshape(-1), (0, Np - N))
        zero = jnp.pad(jnp.asarray(zero, jnp.float32).reshape(-1), (0, Np - N))
    out = _dequant_matmul_pallas(xpad, wpad, scale, zero, bm=bm, bn=bn, bk=bk,
                                 int4=int4, interpret=interpret)
    return out[:M, :N]


def huffman_decode(mat: jax.Array, counts: jax.Array, lut_sym: jax.Array,
                   lut_len: jax.Array, *, max_len: int, max_count: int,
                   interpret: bool = False) -> jax.Array:
    """Multi-stream Huffman decode (see kernels.huffman_decode)."""
    return decode_streams_pallas(mat, counts, lut_sym, lut_len,
                                 max_len=max_len, max_count=max_count,
                                 interpret=interpret)
