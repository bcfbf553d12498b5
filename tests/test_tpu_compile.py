"""Compile the serving path's device programs for a described TPU v5e.

No chip is needed: the TPU compiler that ships with jax compiles against a
described ``v5e:2x2`` topology, so these tests catch what only the chip's
compiler refuses (lowering, tiling, memory) at no chip time.  Shapes are
the ones ``chip_smoke.py`` drives at Qwen3-1.7B's published widths:
4096-symbol segments, 1024 of them per decode call.

The jit decoders, the jit fused impl and a full-width cached decode block
must compile.  The four Pallas decode kernels do not lower in Mosaic today
(its gather lowering refuses their per-lane LUT reads); each refusal is a
strict xfail, so a kernel that starts to lower fails here until
``decode_backends.PALLAS_DECODE_LOWERS`` is flipped with it.

The topology is described inside a fixture, never at import time: only one
process may load the TPU library, and every test worker imports this file.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

SEG = 4096          # symbols per segment (the compressed-resident spec)
LANES = 1024        # segments per decode call (chip_smoke's chunk budget)
WIDTH = 4096        # bytes per stream after pow2 bucketing
LUT = 4096          # 2**12 entries: max code length / tANS table_log 12


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler can be described here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _streams(sharding, lanes=LANES):
    return (_sds(sharding, (lanes, WIDTH), jnp.uint8),
            _sds(sharding, (lanes,), jnp.int32))


def _tables(sharding, n):
    return tuple(_sds(sharding, (LUT,), jnp.int32) for _ in range(n))


def _fused_qt(sharding, family, impl, K=2048, N=2048):
    from repro.kernels.fused_decode_matmul import FusedQT
    mat = _sds(sharding, (K * N // SEG, WIDTH), jnp.uint8)
    scale = _sds(sharding, (1, 1), jnp.float32)
    return FusedQT(mat, _tables(sharding, 2 if family == "prefix" else 3),
                   scale, scale, family=family, tbits=12, seg=SEG, K=K, N=N,
                   bits=8, impl=impl)


def test_jit_prefix_decoder_compiles(one_chip):
    from repro.core.decode_jax import decode_streams_jax
    c = jax.jit(decode_streams_jax, static_argnames=("max_len", "max_count")
                ).lower(*_streams(one_chip), *_tables(one_chip, 2),
                        max_len=12, max_count=SEG).compile()
    assert c.memory_analysis().output_size_in_bytes == LANES * SEG * 4


def test_jit_tans_decoder_compiles(one_chip):
    from repro.core.decode_jax import decode_streams_tans_jax
    c = jax.jit(decode_streams_tans_jax,
                static_argnames=("table_log", "max_count")
                ).lower(*_streams(one_chip), *_tables(one_chip, 3),
                        table_log=12, max_count=SEG).compile()
    assert c.memory_analysis().output_size_in_bytes == LANES * SEG * 4


def test_jit_fused_impl_compiles(one_chip):
    from repro.kernels.fused_decode_matmul import fused_decode_matmul
    x = _sds(one_chip, (4, 1, 2048), jnp.bfloat16)
    c = jax.jit(fused_decode_matmul).lower(
        x, _fused_qt(one_chip, "prefix", "jax")).compile()
    assert c.memory_analysis().output_size_in_bytes == 4 * 2048 * 2


def test_qwen3_cached_decode_block_compiles(one_chip):
    """One full-width Qwen3-1.7B layer against the slot cache, QT weights
    resident (compressed residency's per-layer block)."""
    from repro.configs import registry
    from repro.models import api, dense
    from repro.models.layers import QT
    cfg = registry.published(registry.get("qwen3-1.7b"), 4)
    B, T = 8, 176                         # chip_smoke phase B's slot cache
    shapes = jax.eval_shape(functools.partial(api.build(cfg).init, cfg),
                            jax.random.PRNGKey(0))
    lp = {}
    for name, s in shapes.items():
        if not name.startswith("layers/"):
            continue
        short, sh = name.split("/", 1)[1], s.shape[1:]
        if len(sh) == 2:                  # matrices stay 8-bit symbols
            f32 = _sds(one_chip, (1, 1), jnp.float32)
            lp[short] = QT(_sds(one_chip, sh, jnp.uint8), f32, f32)
        else:
            lp[short] = _sds(one_chip, sh, s.dtype)
    cache = jax.tree.map(lambda s: _sds(one_chip, s.shape, s.dtype),
                         jax.eval_shape(lambda: dense.init_cache(cfg, B, T)))
    assert lp["wq"].q.shape == (2048, 2048) and lp["w_up"].q.shape == (
        2048, 6144)
    # jitted as ServeSteps jits it for compressed residency: the stacked
    # cache donated
    c = jax.jit(functools.partial(dense.cached_layer, cfg),
                donate_argnums=(2,)).lower(
        lp, _sds(one_chip, (B, 1, cfg.d_model), jnp.bfloat16), cache,
        _sds(one_chip, (), jnp.int32), _sds(one_chip, (B,), jnp.int32),
        _sds(one_chip, (B, 1), jnp.int32)).compile()
    mem = c.memory_analysis()
    assert mem.output_size_in_bytes > 0
    # attention reads K/V as stored: no temporary as large as one layer's
    # K cache (the per-head widening held two of them)
    assert mem.temp_size_in_bytes < B * T * cfg.n_kv_heads * cfg.hd * 2


def test_qwen3_decode_step_updates_donated_cache_in_place(one_chip):
    """The whole-tree decode step at the ``qwen3-1.7b.chat`` cell's shapes
    (published widths, 4 layers, 32 slots, 1536 cache rows, float32
    weights), jitted with the cache donated as the engine jits it: the
    layer scan carries the stacked cache and writes only the step's rows,
    so no temporary holds a copy of the stack."""
    from repro.configs import registry
    from repro.models import dense
    cfg = registry.published(registry.get("qwen3-1.7b"), 4)
    B, T = 32, 1536

    def f32(s):                       # the cell serves float32 weights
        floating = jnp.issubdtype(s.dtype, jnp.floating)
        return _sds(one_chip, s.shape, jnp.float32 if floating else s.dtype)

    params = jax.tree.map(f32, jax.eval_shape(
        functools.partial(dense.init, cfg), jax.random.PRNGKey(0)))
    cache = jax.tree.map(lambda s: _sds(one_chip, s.shape, s.dtype),
                         jax.eval_shape(lambda: dense.init_cache(cfg, B, T)))
    c = jax.jit(functools.partial(dense.decode_step, cfg),
                donate_argnums=(2,)).lower(
        params, _sds(one_chip, (B, 1), jnp.int32), cache,
        _sds(one_chip, (B,), jnp.int32)).compile()
    stack = cache["k"].shape
    assert stack == (4, B, T, cfg.n_kv_heads, cfg.hd)
    kv_bytes = 2 * 2 * int(np.prod(stack))              # K and V, bf16
    assert c.memory_analysis().temp_size_in_bytes < kv_bytes
    # no copy of the stack, nor a buffer allocated to hold one
    whole = re.escape("bf16[%s]" % ",".join(map(str, stack)))
    bulk = re.findall(r"%\S+ = " + whole + r"\{[^}]*\} (?:copy|custom-call)\(",
                      c.as_text())
    assert not bulk, bulk


@pytest.mark.parametrize("program,B,S", [("decode_step", 32, 1),
                                         ("prefill_chunk", 1, 256)])
def test_qwen3_step_reads_float32_weights_in_the_dot(one_chip, program, B, S):
    """The ``qwen3-1.7b.chat`` cell's two programs (the decode step over 32
    slots and the one-slot 256-token prefill chunk, float32 weights, 1536
    cache rows): each layer's dot rounds its float32 weight to bf16 inside
    its own fusion, so no bf16 copy of a layer-stacked weight is written
    before the scan, and no temporary is as large as one layer's bf16 MLP
    weights."""
    from repro.configs import registry
    from repro.models import dense
    cfg = registry.published(registry.get("qwen3-1.7b"), 4)
    T = 1536

    def f32(s):
        floating = jnp.issubdtype(s.dtype, jnp.floating)
        return _sds(one_chip, s.shape, jnp.float32 if floating else s.dtype)

    params = jax.tree.map(f32, jax.eval_shape(
        functools.partial(dense.init, cfg), jax.random.PRNGKey(0)))
    cache = jax.tree.map(lambda s: _sds(one_chip, s.shape, s.dtype),
                         jax.eval_shape(lambda: dense.init_cache(cfg, B, T)))
    c = jax.jit(functools.partial(getattr(dense, program), cfg),
                donate_argnums=(2,)).lower(
        params, _sds(one_chip, (B, S), jnp.int32), cache,
        _sds(one_chip, (B,), jnp.int32)).compile()
    stacks = {v.shape for k, v in params.items()
              if k.startswith("layers/") and v.ndim == 3}
    assert (4, cfg.d_model, cfg.d_ff) in stacks
    copies = [m for shape in stacks for m in re.findall(
        r"%\S+ = " + re.escape("bf16[%s]" % ",".join(map(str, shape)))
        + r"\{", c.as_text())]
    assert not copies, copies
    mlp_bf16 = 3 * cfg.d_model * cfg.d_ff * 2
    assert c.memory_analysis().temp_size_in_bytes < mlp_bf16


# Mosaic's gather lowering refuses the per-lane LUT reads: an AssertionError
# for the prefix kernels, "Only 2D gather is supported" for the tANS ones.
_REFUSED = pytest.mark.xfail(strict=True,
                             raises=(AssertionError, NotImplementedError),
                             reason="Mosaic cannot lower per-lane LUT gathers")


@_REFUSED
def test_pallas_huffman_decode_lowers(one_chip):
    from repro.kernels.huffman_decode import decode_streams_pallas
    jax.jit(decode_streams_pallas,
            static_argnames=("max_len", "max_count", "interpret")).lower(
        *_streams(one_chip, 128), *_tables(one_chip, 2), max_len=12,
        max_count=SEG).compile()


@_REFUSED
def test_pallas_tans_decode_lowers(one_chip):
    from repro.kernels.ans_decode import decode_streams_tans_pallas
    jax.jit(decode_streams_tans_pallas,
            static_argnames=("table_log", "max_count", "interpret")).lower(
        *_streams(one_chip, 128), *_tables(one_chip, 3), table_log=12,
        max_count=SEG).compile()


@_REFUSED
@pytest.mark.parametrize("family", ["prefix", "tans"])
def test_pallas_fused_kernel_lowers(one_chip, family):
    from repro.kernels.fused_decode_matmul import fused_decode_matmul
    x = _sds(one_chip, (8, 2048), jnp.bfloat16)
    jax.jit(fused_decode_matmul).lower(
        x, _fused_qt(one_chip, family, "pallas")).compile()

