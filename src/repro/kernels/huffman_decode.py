"""Pallas TPU kernel: multi-stream LUT-based canonical Huffman decode.

TPU adaptation of the paper's thread-parallel decoder (§III-C).  The paper
assigns one CPU thread per encoded segment; here a *vector lane* takes that
role: a block of ``LANES`` streams advances in lock-step, one symbol per
iteration, via a gather into the canonical-code lookup table.

VMEM budget per program instance (defaults):
  * LUT: 2 x 2^12 x 4 B               =  32 KiB
  * stream block: LANES x stream_bytes = 128 x B bytes (B <= 64 KiB -> 8 MiB max;
    segment sizing keeps B ~ 10 KiB for 64k-symbol uint4 segments -> ~1.3 MiB)
  * output block: LANES x max_count x 4 B

The bit-window arithmetic matches ``core.bitstream.decode_serial`` exactly:
MSB-first within bytes, 32-bit sliding window, ``max_len``-bit peek.

The decode loop is sequential in symbols (inherent to Huffman) but the kernel
is embarrassingly parallel across stream blocks — grid dim 0 — which is how
the paper's "coarse-grained parallelism over tensors" maps onto a TPU core's
grid + lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128     # streams per program instance (one VREG row of lanes)


def _decode_kernel(mat_ref, counts_ref, lut_sym_ref, lut_len_ref, out_ref, *,
                   max_len: int, max_count: int):
    """One grid step: decode LANES streams, max_count symbols each."""
    d = mat_ref[...].astype(jnp.uint32)           # (LANES, B) stream bytes
    counts = counts_ref[...]                      # (LANES,)
    lut_sym = lut_sym_ref[...]                    # (2^max_len,)
    lut_len = lut_len_ref[...]
    mask = jnp.uint32((1 << max_len) - 1)
    lanes = jnp.arange(d.shape[0])

    def step(k, carry):
        bitpos, out = carry
        byte = (bitpos >> 3).astype(jnp.int32)
        # 32-bit window starting at byte (guard bytes make byte+3 in-bounds)
        w = (
            (d[lanes, byte] << 24)
            | (d[lanes, byte + 1] << 16)
            | (d[lanes, byte + 2] << 8)
            | d[lanes, byte + 3]
        )
        shift = (32 - max_len - (bitpos & 7)).astype(jnp.uint32)
        peek = ((w >> shift) & mask).astype(jnp.int32)
        sym = lut_sym[peek]
        ln = lut_len[peek]
        active = k < counts
        out = out.at[:, k].set(jnp.where(active, sym, 0))
        bitpos = jnp.where(active, bitpos + ln, bitpos)
        return bitpos, out

    bitpos0 = jnp.zeros((d.shape[0],), jnp.int32)
    out0 = jnp.zeros((d.shape[0], max_count), jnp.int32)
    _, out = jax.lax.fori_loop(0, max_count, step, (bitpos0, out0))
    out_ref[...] = out


@functools.partial(jax.jit,
                   static_argnames=("max_len", "max_count", "interpret"))
def decode_streams_pallas(mat: jax.Array, counts: jax.Array, lut_sym: jax.Array,
                          lut_len: jax.Array, *, max_len: int, max_count: int,
                          interpret: bool = False) -> jax.Array:
    """mat: (S, B) uint8 guard-padded streams (S % LANES == 0 after padding);
    counts: (S,) int32.  Returns (S, max_count) int32 symbols.
    """
    S, B = mat.shape
    Sp = -(-S // LANES) * LANES
    if Sp != S:
        mat = jnp.pad(mat, ((0, Sp - S), (0, 0)))
        counts = jnp.pad(counts, (0, Sp - S))
    # LUT block shape follows the array (the raw codec passes a 2^bits-entry
    # identity LUT through this same kernel; peek masking uses max_len)
    lut_size = lut_sym.shape[0]

    kernel = functools.partial(_decode_kernel, max_len=max_len,
                               max_count=max_count)
    out = pl.pallas_call(
        kernel,
        grid=(Sp // LANES,),
        in_specs=[
            pl.BlockSpec((LANES, B), lambda i: (i, 0)),          # stream block
            pl.BlockSpec((LANES,), lambda i: (i,)),              # counts
            pl.BlockSpec((lut_size,), lambda i: (0,)),           # LUT resident
            pl.BlockSpec((lut_size,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((LANES, max_count), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Sp, max_count), jnp.int32),
        interpret=interpret,
    )(mat, counts.astype(jnp.int32), lut_sym.astype(jnp.int32),
      lut_len.astype(jnp.int32))
    return out[:S]
