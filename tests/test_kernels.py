"""Per-kernel allclose sweeps: Pallas (interpret=True) vs the ref.py oracle,
across shapes and scale/zero layouts (deliverable c)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.bitstream import decode_streams, encode_symbols, pack_streams
from repro.core.entropy import HuffmanTable
from repro.kernels import ops, ref


@pytest.mark.parametrize("M,K,N", [
    (8, 128, 64), (64, 384, 200), (128, 512, 128), (1, 1024, 96), (33, 257, 65),
])
@pytest.mark.parametrize("per_channel", [True, False])
def test_dequant_matmul_int8(M, K, N, per_channel):
    rng = np.random.default_rng(M * 1000 + K + N)
    x = jnp.asarray(rng.normal(size=(M, K)), jnp.bfloat16)
    wq = jnp.asarray(rng.integers(0, 256, size=(K, N)), jnp.uint8)
    if per_channel:
        scale = rng.uniform(1e-3, 1e-2, size=(N,)).astype(np.float32)
        zero = rng.uniform(-1, 0, size=(N,)).astype(np.float32)
    else:
        scale, zero = np.float32(0.005), np.float32(-0.6)
    out = ops.dequant_matmul(x, wq, scale, zero, interpret=True)
    want = ref.dequant_matmul_ref(x, wq, scale, zero)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=1e-2,
                               rtol=1e-2)


@pytest.mark.parametrize("M,K,N", [(16, 256, 128), (8, 130, 48)])
def test_dequant_matmul_int4(M, K, N):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(M, K)), jnp.bfloat16)
    q4 = rng.integers(0, 16, size=(K, N)).astype(np.uint8)
    packed = jnp.asarray(ops.pack_nibbles(q4))
    scale = rng.uniform(0.01, 0.1, size=(N,)).astype(np.float32)
    zero = np.zeros(N, np.float32)
    out = ops.dequant_matmul(x, packed, scale, zero, int4=True,
                             interpret=True)
    want = ref.dequant_matmul_ref(x, packed, scale, zero, int4=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=1e-2,
                               rtol=1e-2)


def test_pack_unpack_nibbles_roundtrip():
    rng = np.random.default_rng(1)
    q = rng.integers(0, 16, size=(64, 33)).astype(np.uint8)
    assert (ops.unpack_nibbles(ops.pack_nibbles(q)) == q).all()


def test_dequant_matmul_equals_float_matmul():
    """Quantize a real matrix, then kernel(x, q) ~= x @ w_dequant."""
    from repro.core import quant
    rng = np.random.default_rng(2)
    w = rng.normal(0, 0.05, size=(256, 128)).astype(np.float32)
    qt = quant.quantize(w, 8)
    x = jnp.asarray(rng.normal(size=(16, 256)), jnp.bfloat16)
    out = ops.dequant_matmul(x, jnp.asarray(qt.q),
                             qt.scale.reshape(-1), qt.zero.reshape(-1),
                             interpret=True)
    want = np.asarray(x, np.float32) @ quant.dequantize(qt)
    np.testing.assert_allclose(np.asarray(out, np.float32), want,
                               atol=0.15, rtol=0.05)


@pytest.mark.parametrize("n_streams,max_len", [(1, 12), (7, 12), (130, 12),
                                               (16, 10)])
def test_huffman_decode_kernel_vs_host(n_streams, max_len):
    rng = np.random.default_rng(n_streams)
    freqs = rng.integers(1, 2000, size=256)
    table = HuffmanTable(freqs, max_len=max_len)
    streams, counts = [], []
    for _ in range(n_streams):
        n = int(rng.integers(10, 500))
        syms = rng.integers(0, 256, size=n).astype(np.uint8)
        s, _ = encode_symbols(syms, table.codes, table.lengths)
        streams.append(s)
        counts.append(n)
    mat, _ = pack_streams(streams)
    counts = np.array(counts, np.int64)
    host = decode_streams(mat, counts, table.lut_sym, table.lut_len, max_len)
    kern = ops.huffman_decode(
        jnp.asarray(mat), jnp.asarray(counts, jnp.int32),
        jnp.asarray(table.lut_sym), jnp.asarray(table.lut_len),
        max_len=max_len, max_count=int(counts.max()), interpret=True)
    assert (np.asarray(kern) == host).all()


def test_huffman_decode_kernel_roundtrip_identity():
    """encode -> pallas decode == original symbols, skewed histogram."""
    rng = np.random.default_rng(9)
    # peaky (trained-LLM-like) distribution
    syms = np.clip(rng.normal(128, 12, size=5000), 0, 255).astype(np.uint8)
    freqs = np.bincount(syms, minlength=256) + 0
    table = HuffmanTable(np.maximum(freqs, 0), max_len=12)
    chunks = np.array_split(syms, 5)
    streams = [encode_symbols(c, table.codes, table.lengths)[0]
               for c in chunks]
    mat, _ = pack_streams(streams)
    counts = np.array([len(c) for c in chunks], np.int64)
    out = ops.huffman_decode(
        jnp.asarray(mat), jnp.asarray(counts, jnp.int32),
        jnp.asarray(table.lut_sym), jnp.asarray(table.lut_len),
        max_len=12, max_count=int(counts.max()), interpret=True)
    got = np.concatenate([np.asarray(out)[i, :c] for i, c in enumerate(counts)])
    assert (got == syms).all()
