"""Fused entropy-decode → dequantize → matmul (the end of the HBM round trip).

Compressed-resident serving (``serving/resident.py``) decodes each layer's
QT triples into a dense double-buffered slot before its matmuls, so the
dense weights still transit HBM once per layer.  This module removes that
round trip: a :class:`FusedQT` handle keeps one tensor's layer slice as the
*packed lane matrix* of its encoded segments (plus the codec's decode
tables and the layer's scale/zero), and ``fused_decode_matmul(x, fq)``
decodes weight tiles straight into the matmul's K-loop — on TPU inside a
Pallas kernel's VMEM scratch, elsewhere through the jit lock-step decoders.

Geometry (the tile-alignment contract ``core.scheduler.fused_tile_reason``
checks): the layer slice is (K, N) symbols stored row-major as S uniform
segments of ``seg`` symbols each, with ``seg % N == 0`` — so every lane
boundary coincides with a matmul K-tile boundary and a decoded lane block
``(lanes, seg)`` reshapes losslessly to ``(lanes * seg/N, N)``.  Containers
are written with fixed segment budgets, so real stacked tensors satisfy
this whenever ``K*N % seg == 0`` (ragged tails fall back to the unfused
per-layer decode path).

Implementations (``FusedQT.impl``, picked by the decode-backend rule):

* ``"jax"`` — in-graph :func:`repro.core.decode_jax.decode_streams_jax` /
  ``decode_streams_tans_jax`` followed by the *exact* ops of
  ``models.layers.deq`` + ``matmul`` (bf16 dequant, same dot).  Decoded
  symbols are exact integers, so this path is **bit-identical** to the
  unfused QT slot on any host — the property the differential harness
  (``tests/differential/``) asserts end to end.
* ``"pallas"`` — one kernel: grid over K-tiles, each program decodes its
  lane block with the lock-step loop (prefix or tANS), dequantizes in
  bf16 inside VMEM, and accumulates into an f32 scratch.  Compiled-only,
  and not yet lowered by Mosaic on v5e (``tests/test_tpu_compile.py``), so
  :func:`default_fused_impl` never picks it today.
* ``"pallas-interpret"`` — the same kernel interpreted (CPU differential
  testing only; never auto-picked).

The numpy oracle every implementation is checked against is
:func:`repro.kernels.ref.fused_decode_matmul_ref`.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128             # lane cap per program instance (one VREG row)
FUSED_IMPLS = ("pallas", "jax", "pallas-interpret")


def lanes_per_tile(n_lanes: int, cap: int = LANES) -> int:
    """Largest divisor of ``n_lanes`` not exceeding ``cap`` — the per-program
    lane-block height (divisor, so the grid tiles the lanes exactly)."""
    for c in range(min(n_lanes, cap), 0, -1):
        if n_lanes % c == 0:
            return c
    return 1


@jax.tree_util.register_pytree_node_class
class FusedQT:
    """A compressed weight handle the matmul can consume directly.

    Children (traced): ``mat`` — the (S, B) uint8 guard-padded lane matrix
    of the layer slice's segments; ``tabs`` — the codec's decode-table
    arrays (prefix: lut_sym, lut_len; tans: tab_sym, tab_bits, tab_base);
    ``scale``/``zero`` — the layer's dequant affine (broadcastable against
    (K, N), exactly what the unfused QT slot carries).

    Aux (static, shapes the kernel): ``family`` ("prefix"/"tans"),
    ``tbits`` (peek_bits / table_log), ``seg`` symbols per lane, the dense
    (K, N) geometry, the quantizer ``bits`` (provenance only — symbols
    decode to uint8 regardless), and ``impl``.

    Registered as a pytree so handles flow through jitted serving blocks
    like any weight leaf; the static aux is identical across layers of one
    tensor, so the per-layer block functions retrace once, not per layer.
    """

    def __init__(self, mat, tabs, scale, zero, *, family: str, tbits: int,
                 seg: int, K: int, N: int, bits: int, impl: str):
        self.mat = mat
        self.tabs = tuple(tabs)
        self.scale = scale
        self.zero = zero
        self.family = family
        self.tbits = int(tbits)
        self.seg = int(seg)
        self.K = int(K)
        self.N = int(N)
        self.bits = int(bits)
        self.impl = impl

    @property
    def shape(self) -> Tuple[int, int]:
        return (self.K, self.N)

    def tree_flatten(self):
        return ((self.mat, self.tabs, self.scale, self.zero),
                (self.family, self.tbits, self.seg, self.K, self.N,
                 self.bits, self.impl))

    @classmethod
    def tree_unflatten(cls, aux, children):
        mat, tabs, scale, zero = children
        family, tbits, seg, K, N, bits, impl = aux
        return cls(mat, tabs, scale, zero, family=family, tbits=tbits,
                   seg=seg, K=K, N=N, bits=bits, impl=impl)

    def __repr__(self):
        return (f"FusedQT({self.family}{self.bits}, K={self.K}, N={self.N}, "
                f"seg={self.seg}, lanes={self.mat.shape[0]}, "
                f"impl={self.impl!r})")


def default_fused_impl() -> str:
    """The decode-backend registry's auto rule applied to the fused path:
    the compiled Pallas kernel only where Mosaic lowers it
    (``decode_backends.PALLAS_DECODE_LOWERS``, on a TPU), the jit path
    everywhere else."""
    from repro.core.decode_backends import pallas_auto_eligible
    return "pallas" if pallas_auto_eligible() else "jax"


def build_fused_qt(table, mat, scale, zero, *, seg_symbols: int, K: int,
                   N: int, bits: int, impl: Optional[str] = None) -> FusedQT:
    """Build a :class:`FusedQT` from a codec table + packed lane matrix.

    ``mat`` rows are the layer slice's segments in symbol order, each
    holding exactly ``seg_symbols`` symbols (uniform — the tile-alignment
    contract), guard-padded as by ``bitstream.pack_streams``.
    """
    mat = jnp.asarray(mat, jnp.uint8)
    S = mat.shape[0]
    if S * seg_symbols != K * N:
        raise ValueError(
            f"lane matrix holds {S} x {seg_symbols} symbols; dense geometry "
            f"needs {K} x {N}")
    if seg_symbols % N:
        raise ValueError(
            f"segment of {seg_symbols} symbols does not tile rows of {N}")
    a = table.decode_arrays()
    if table.kernel == "prefix":
        tabs = (jnp.asarray(a["lut_sym"], jnp.int32),
                jnp.asarray(a["lut_len"], jnp.int32))
        tbits = int(table.peek_bits)
    elif table.kernel == "tans":
        tabs = (jnp.asarray(a["tab_sym"], jnp.int32),
                jnp.asarray(a["tab_bits"], jnp.int32),
                jnp.asarray(a["tab_base"], jnp.int32))
        tbits = int(table.table_log)
    else:
        raise ValueError(f"unknown kernel family {table.kernel!r}")
    if impl is None:
        impl = default_fused_impl()
    if impl not in FUSED_IMPLS:
        raise ValueError(f"unknown fused impl {impl!r}; one of {FUSED_IMPLS}")
    return FusedQT(mat, tabs, jnp.asarray(scale), jnp.asarray(zero),
                   family=table.kernel, tbits=tbits, seg=int(seg_symbols),
                   K=int(K), N=int(N), bits=int(bits), impl=impl)


# ------------------------------------------------------------------ jax impl

def _decode_lanes_jax(fq: FusedQT) -> jax.Array:
    """In-graph decode of the full lane matrix -> (K, N) uint8 symbols."""
    from repro.core.decode_jax import (decode_streams_jax,
                                       decode_streams_tans_jax)
    S = fq.mat.shape[0]
    counts = jnp.full((S,), fq.seg, jnp.int32)
    if fq.family == "prefix":
        dec = decode_streams_jax(fq.mat, counts, fq.tabs[0], fq.tabs[1],
                                 max_len=fq.tbits, max_count=fq.seg)
    else:
        dec = decode_streams_tans_jax(fq.mat, counts, fq.tabs[0], fq.tabs[1],
                                      fq.tabs[2], table_log=fq.tbits,
                                      max_count=fq.seg)
    return dec.reshape(fq.K, fq.N).astype(jnp.uint8)


def _fused_jax(x: jax.Array, fq: FusedQT) -> jax.Array:
    # the exact op sequence of layers.deq(QT, x.dtype) + layers.matmul —
    # decoded symbols are exact integers, so this is bit-identical to the
    # unfused slot path (the differential harness's core claim)
    q = _decode_lanes_jax(fq)
    dt = x.dtype
    wd = q.astype(dt) * fq.scale.astype(dt) + fq.zero.astype(dt)
    return x @ wd


# --------------------------------------------------------------- pallas impl

def _fused_prefix_kernel(x_ref, mat_ref, sym_ref, len_ref, scale_ref,
                         zero_ref, o_ref, acc_ref, *, seg: int, max_len: int,
                         n_k: int):
    """One K-tile: decode the lane block, dequantize in VMEM, accumulate."""
    k = pl.program_id(0)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    d = mat_ref[...].astype(jnp.uint32)            # (lpt, B) stream bytes
    lut_sym = sym_ref[...]
    lut_len = len_ref[...]
    mask = jnp.uint32((1 << max_len) - 1)
    lanes = jnp.arange(d.shape[0])

    def step(t, carry):
        bitpos, out = carry
        byte = (bitpos >> 3).astype(jnp.int32)
        w = (
            (d[lanes, byte] << 24)
            | (d[lanes, byte + 1] << 16)
            | (d[lanes, byte + 2] << 8)
            | d[lanes, byte + 3]
        )
        shift = (32 - max_len - (bitpos & 7)).astype(jnp.uint32)
        peek = ((w >> shift) & mask).astype(jnp.int32)
        # uniform lane counts == seg: every lane is active every step
        out = out.at[:, t].set(lut_sym[peek])
        return bitpos + lut_len[peek], out

    bitpos0 = jnp.zeros((d.shape[0],), jnp.int32)
    out0 = jnp.zeros((d.shape[0], seg), jnp.int32)
    _, syms = jax.lax.fori_loop(0, seg, step, (bitpos0, out0))
    _deq_accumulate(x_ref, syms, scale_ref, zero_ref, acc_ref)

    @pl.when(k == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _fused_tans_kernel(x_ref, mat_ref, sym_ref, bits_ref, base_ref, scale_ref,
                       zero_ref, o_ref, acc_ref, *, seg: int, table_log: int,
                       n_k: int):
    from repro.core.bitstream import TANS_STATE_HEADER_BITS
    k = pl.program_id(0)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    d = mat_ref[...].astype(jnp.uint32)
    tab_sym = sym_ref[...]
    tab_bits = bits_ref[...]
    tab_base = base_ref[...]
    mask = jnp.uint32((1 << table_log) - 1)
    lanes = jnp.arange(d.shape[0])

    def step(t, carry):
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
        out = out.at[:, t].set(sym)
        return tab_base[st] + fresh, bitpos + nb, out

    st0 = ((d[:, 0] << 8) | d[:, 1]).astype(jnp.int32)
    bitpos0 = jnp.full((d.shape[0],), TANS_STATE_HEADER_BITS, jnp.int32)
    out0 = jnp.zeros((d.shape[0], seg), jnp.int32)
    _, _, syms = jax.lax.fori_loop(0, seg, step, (st0, bitpos0, out0))
    _deq_accumulate(x_ref, syms, scale_ref, zero_ref, acc_ref)

    @pl.when(k == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _deq_accumulate(x_ref, syms, scale_ref, zero_ref, acc_ref):
    """Shared tail: (lpt, seg) symbols -> (bk, N) bf16 weights -> MXU.

    Dequant happens in bf16 — the serving contract of ``layers.deq`` (the
    unfused slot path this kernel replaces), unlike ``dequant_matmul``'s
    f32 grid: the fused path's comparison target is the QT slot, not the
    f32 oracle, so it mirrors the slot's arithmetic.
    """
    N = acc_ref.shape[1]
    lpt, seg = syms.shape
    q = syms.reshape(lpt * (seg // N), N)          # row-major: (bk, N)
    w = (q.astype(jnp.bfloat16) * scale_ref[...].astype(jnp.bfloat16)
         + zero_ref[...].astype(jnp.bfloat16))
    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.bfloat16), w,
                            preferred_element_type=jnp.float32)


def _fused_pallas(x: jax.Array, fq: FusedQT, *, interpret: bool) -> jax.Array:
    lead = x.shape[:-1]
    x2 = x.reshape(-1, fq.K)
    M = x2.shape[0]
    Mp = -(-M // 8) * 8                      # sublane-align the batch rows
    if Mp != M:
        x2 = jnp.pad(x2, ((0, Mp - M), (0, 0)))
    S, B = fq.mat.shape
    lpt = lanes_per_tile(S)
    bk = lpt * (fq.seg // fq.N)              # K rows decoded per program
    n_k = S // lpt

    scale2 = jnp.broadcast_to(
        jnp.asarray(fq.scale, jnp.float32).reshape(1, -1),
        (1, fq.N) if jnp.size(fq.scale) > 1 else (1, 1))
    zero2 = jnp.broadcast_to(
        jnp.asarray(fq.zero, jnp.float32).reshape(1, -1),
        (1, fq.N) if jnp.size(fq.zero) > 1 else (1, 1))
    sn = scale2.shape[1]

    if fq.family == "prefix":
        kernel = functools.partial(_fused_prefix_kernel, seg=fq.seg,
                                   max_len=fq.tbits, n_k=n_k)
    else:
        kernel = functools.partial(_fused_tans_kernel, seg=fq.seg,
                                   table_log=fq.tbits, n_k=n_k)
    tab_specs = [pl.BlockSpec((t.shape[0],), lambda k: (0,))
                 for t in fq.tabs]
    out = pl.pallas_call(
        kernel,
        grid=(n_k,),
        in_specs=[
            pl.BlockSpec((Mp, bk), lambda k: (0, k)),       # x K-slab
            pl.BlockSpec((lpt, B), lambda k: (k, 0)),       # lane block
            *tab_specs,                                     # tables resident
            pl.BlockSpec((1, sn), lambda k: (0, 0)),
            pl.BlockSpec((1, sn), lambda k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((Mp, fq.N), lambda k: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((Mp, fq.N), x.dtype),
        scratch_shapes=[pltpu.VMEM((Mp, fq.N), jnp.float32)],
        interpret=interpret,
    )(x2, fq.mat, *fq.tabs, scale2, zero2)
    return out[:M].reshape(*lead, fq.N)


# ------------------------------------------------------------------ dispatch

def fused_decode_matmul(x: jax.Array, fq: FusedQT) -> jax.Array:
    """``x @ dequant(decode(fq))`` without materializing the dense weight in
    HBM.  ``x``: (..., K); returns (..., N) in ``x.dtype``."""
    if fq.impl == "jax":
        return _fused_jax(x, fq)
    if fq.impl == "pallas":
        return _fused_pallas(x, fq, interpret=False)
    if fq.impl == "pallas-interpret":
        return _fused_pallas(x, fq, interpret=True)
    raise ValueError(f"unknown fused impl {fq.impl!r}; one of {FUSED_IMPLS}")
