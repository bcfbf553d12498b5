"""Pallas TPU kernel: multi-stream tANS (FSE-style) decode.

The ``tans`` twin of :mod:`repro.kernels.huffman_decode` — same lock-step
lane-per-segment structure (a block of ``LANES`` streams advances one symbol
per iteration), with the carried per-lane ANS state replacing the Huffman
window peek as the table index.  VMEM holds three ``2^table_log`` int32
tables (48 KiB at the default ``table_log=12``) instead of Huffman's two.

Loop body per lane (matches ``core.bitstream.decode_serial_tans`` exactly):

    sym   = tab_sym[state]
    nb    = tab_bits[state]                       # 0..table_log fresh bits
    fresh = top nb bits of the table_log-bit window at bitpos
    state = tab_base[state] + fresh;  bitpos += nb

Streams begin with a 16-bit initial-state header
(``bitstream.TANS_STATE_HEADER_BITS``); guard bytes make the 32-bit window
load always in-bounds.  The kernel is embarrassingly parallel across stream
blocks (grid dim 0), exactly like the Huffman kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.bitstream import TANS_STATE_HEADER_BITS

from .huffman_decode import LANES


def _tans_kernel(mat_ref, counts_ref, sym_ref, bits_ref, base_ref, out_ref, *,
                 table_log: int, max_count: int):
    """One grid step: decode LANES tANS streams, max_count symbols each."""
    d = mat_ref[...].astype(jnp.uint32)           # (LANES, B) stream bytes
    counts = counts_ref[...]                      # (LANES,)
    tab_sym = sym_ref[...]                        # (2^table_log,)
    tab_bits = bits_ref[...]
    tab_base = base_ref[...]
    mask = jnp.uint32((1 << table_log) - 1)
    lanes = jnp.arange(d.shape[0])

    def step(k, carry):
        st, bitpos, out = carry
        sym = tab_sym[st]
        nb = tab_bits[st]
        byte = (bitpos >> 3).astype(jnp.int32)
        w = (
            (d[lanes, byte] << 24)
            | (d[lanes, byte + 1] << 16)
            | (d[lanes, byte + 2] << 8)
            | d[lanes, byte + 3]
        )
        shift = (32 - table_log - (bitpos & 7)).astype(jnp.uint32)
        peek = (w >> shift) & mask
        fresh = (peek >> (table_log - nb).astype(jnp.uint32)).astype(jnp.int32)
        active = k < counts
        out = out.at[:, k].set(jnp.where(active, sym, 0))
        st = jnp.where(active, tab_base[st] + fresh, st)
        bitpos = jnp.where(active, bitpos + nb, bitpos)
        return st, bitpos, out

    st0 = ((d[:, 0] << 8) | d[:, 1]).astype(jnp.int32)
    bitpos0 = jnp.full((d.shape[0],), TANS_STATE_HEADER_BITS, jnp.int32)
    out0 = jnp.zeros((d.shape[0], max_count), jnp.int32)
    _, _, out = jax.lax.fori_loop(0, max_count, step, (st0, bitpos0, out0))
    out_ref[...] = out


@functools.partial(jax.jit,
                   static_argnames=("table_log", "max_count", "interpret"))
def decode_streams_tans_pallas(mat: jax.Array, counts: jax.Array,
                               tab_sym: jax.Array, tab_bits: jax.Array,
                               tab_base: jax.Array, *, table_log: int,
                               max_count: int,
                               interpret: bool = False) -> jax.Array:
    """mat: (S, B) uint8 guard-padded tANS streams (headers included);
    counts: (S,) int32.  Returns (S, max_count) int32 symbols.
    """
    S, B = mat.shape
    Sp = -(-S // LANES) * LANES
    if Sp != S:
        mat = jnp.pad(mat, ((0, Sp - S), (0, 0)))
        counts = jnp.pad(counts, (0, Sp - S))
    tab_size = tab_sym.shape[0]

    kernel = functools.partial(_tans_kernel, table_log=table_log,
                               max_count=max_count)
    out = pl.pallas_call(
        kernel,
        grid=(Sp // LANES,),
        in_specs=[
            pl.BlockSpec((LANES, B), lambda i: (i, 0)),          # stream block
            pl.BlockSpec((LANES,), lambda i: (i,)),              # counts
            pl.BlockSpec((tab_size,), lambda i: (0,)),           # tables resident
            pl.BlockSpec((tab_size,), lambda i: (0,)),
            pl.BlockSpec((tab_size,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((LANES, max_count), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Sp, max_count), jnp.int32),
        interpret=interpret,
    )(mat, counts.astype(jnp.int32), tab_sym.astype(jnp.int32),
      tab_bits.astype(jnp.int32), tab_base.astype(jnp.int32))
    return out[:S]
