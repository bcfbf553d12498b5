"""Compressed-resident serving: per-layer decode in execution order.

Load-bearing properties:

* **Bit-identity** — greedy tokens from the compressed-resident engine
  (weights stay entropy-coded; each layer's QT triples materialize just
  before its matmuls) must equal the dense-resident engine bit for bit, for
  both attention-cache families (dense, moe), through both front ends
  (lockstep ``Engine.generate`` and the continuous-batching scheduler), and
  for mixed 4/8-bit rans+huffman containers.
* **Bounded residency** — peak resident weight bytes (compressed payload +
  decode tables + globals/carve-outs + the double-buffered layer slot pair
  + the int32 decode scratch) stay strictly below the dense bf16 footprint.
* **Plan correctness** — the execution-order plan partitions every stacked
  tensor's symbols exactly into per-layer spans, and the per-layer decode
  reproduces the whole-model loader's stacked QT slices byte for byte.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import registry
from repro.core.quant import Granularity
from repro.core.scheduler import iter_seg_runs, plan_execution, tensor_segments
from repro.core.spec import CompressionSpec, spec_from_legacy
from repro.core.store import CompressedModel
from repro.models import api
from repro.models.layers import QT, QT4
from repro.serving import engine as serving_engine
from repro.serving.batching import ContinuousEngine
from repro.serving.resident import CompressedResidentWeights

MAX_LEN = 40
SEGMENT = 1024          # segments per layer slice >> 1 (per-layer lanes)
CHUNK = 64 * 1024


def _cfg(family: str):
    if family == "dense":
        return registry.reduced(registry.get("qwen3-1.7b"))
    cfg = registry.reduced(registry.get("qwen2-moe-a2.7b"))
    # small expert FFN keeps the per-layer numpy decode fast on CPU, and a
    # generous capacity_factor keeps GShard token-dropping out of the
    # picture (see moe.prefill_chunk)
    return dataclasses.replace(
        cfg, d_model=64, n_heads=2, n_kv_heads=2, d_ff=64,
        moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))


def _build(cfg, spec=None):
    params = api.build(cfg).init(cfg, jax.random.PRNGKey(0))
    host = {k: np.asarray(v, np.float32) for k, v in params.items()}
    if spec is None:
        spec = spec_from_legacy(8, Granularity.PER_CHANNEL,
                                segment_symbols=SEGMENT)
    return CompressedModel.compress(host, spec=spec)


@pytest.fixture(scope="module", params=["dense", "moe"])
def harness(request):
    cfg = _cfg(request.param)
    cm = _build(cfg)
    qparams = serving_engine.load_params_from_compressed(cm, quantized=True)
    weights = CompressedResidentWeights(cm, cfg, chunk_symbols=CHUNK)
    sc = serving_engine.ServeConfig(max_len=MAX_LEN)
    return cfg, cm, qparams, weights, sc


def _prompt(cfg, batch, length, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab, (batch, length)).astype(np.int32)


# ------------------------------------------------------------- plan level

def test_execution_plan_partitions_symbols(harness):
    cfg, cm, _, weights, _ = harness
    plan = weights.plan
    assert len(plan) == cfg.n_layers
    per_tensor = {n: 0 for n in weights._hosted}
    for steps in plan:
        seen = set()
        for step in steps:
            for sp in step.spans:
                assert sp.tensor not in seen     # one span per tensor/layer
                seen.add(sp.tensor)
                assert sp.count == cm.tensors[sp.tensor].n_symbols \
                    // cfg.n_layers
                assert sp.trim >= 0
                assert sum(s.count for s in sp.segs) >= sp.trim + sp.count
                per_tensor[sp.tensor] += sp.count
        assert seen == set(weights._hosted)
    for n, total in per_tensor.items():
        assert total == cm.tensors[n].n_symbols


def test_iter_seg_runs_respects_budget(harness):
    _, cm, _, weights, _ = harness
    name = weights._hosted[0]
    segs = tensor_segments(cm, name)
    runs = list(iter_seg_runs(segs, 2 * SEGMENT))
    assert [s.index for r in runs for s in r] == [s.index for s in segs]
    for r in runs:
        assert len(r) == 1 or sum(s.count for s in r) <= 2 * SEGMENT
    assert list(iter_seg_runs(segs, None)) == [segs]


def test_layer_slots_match_stacked_loader(harness):
    """The per-layer decode must reproduce the whole-model loader's stacked
    QT slices byte for byte — symbols, scale, zero, and QT4 packing."""
    cfg, _, qparams, weights, _ = harness
    for l in (0, cfg.n_layers - 1):
        slot = weights.get(l)
        for name in weights._hosted:
            short = name.split("/", 1)[1]
            stacked, got = qparams[name], slot[short]
            assert type(got) is type(stacked)
            np.testing.assert_array_equal(np.asarray(got.q),
                                          np.asarray(stacked.q[l]))
            np.testing.assert_array_equal(np.asarray(got.scale),
                                          np.asarray(stacked.scale[l]))
            np.testing.assert_array_equal(np.asarray(got.zero),
                                          np.asarray(stacked.zero[l]))
        for name, w in weights.stacked.items():
            short = name.split("/", 1)[1]
            np.testing.assert_array_equal(np.asarray(slot[short]),
                                          np.asarray(qparams[name][l]))


# ----------------------------------------------------------- engine level

def test_lockstep_greedy_bit_identity(harness):
    cfg, _, qparams, weights, sc = harness
    dense_eng = serving_engine.Engine(cfg, qparams, sc)
    comp_eng = serving_engine.Engine(cfg, weights, sc, resident="compressed")
    prompt = _prompt(cfg, 2, 8)
    ref = np.asarray(dense_eng.generate(prompt, 6))
    out = np.asarray(comp_eng.generate(prompt, 6))
    np.testing.assert_array_equal(ref, out)


def test_continuous_batching_bit_identity(harness):
    cfg, _, qparams, weights, sc = harness
    comp = ContinuousEngine(cfg, weights, sc, n_slots=3, prefill_chunk=8,
                            resident="compressed")
    ref = ContinuousEngine(cfg, qparams, sc, n_slots=3, prefill_chunk=8)
    for eng in (comp, ref):
        for i in range(3):
            eng.submit(_prompt(cfg, 1, 5 + i, seed=i)[0], 5)
        eng.run()
    assert [r.output for r in comp.finished] \
        == [r.output for r in ref.finished]
    assert all(len(r.output) == 5 for r in comp.finished)


def test_peak_resident_bytes_below_dense_bf16(harness):
    """The acceptance invariant: everything the compressed mode keeps
    resident (payload + tables + qmeta + globals + carve-outs + the
    double-buffered slot pair + decode scratch) < the dense bf16 footprint,
    and the accounting is internally consistent."""
    _, _, _, weights, _ = harness
    b = weights.resident_bytes()
    peak = weights.peak_resident_bytes()
    assert peak == (b["payload"] + b["tables"] + b["qmeta"] + b["globals"]
                    + b["stacked"] + b["scratch"] + 2 * b["layer_slot"])
    assert peak < weights.dense_bf16_bytes()
    # and the payload really is the dominant resident term, not the slots
    assert 2 * b["layer_slot"] < weights.dense_resident_bytes()


# ------------------------------------------------------- mixed containers

def test_mixed_rans4_huffman8_bit_identity():
    """A v2 container mixing 4-bit rans (QT4-packed slots) and 8-bit
    huffman tensors serves bit-identically through per-layer decode."""
    cfg = _cfg("dense")
    spec = CompressionSpec.parse(
        f"defaults:segment_symbols={SEGMENT};"
        f"layers/*w_*:bits=4,codec=rans",
        default_granularity=Granularity.PER_CHANNEL)
    cm = _build(cfg, spec=spec)
    assert sorted(cm.tables) == ["huffman8", "rans4"]
    qparams = serving_engine.load_params_from_compressed(cm, quantized=True)
    weights = CompressedResidentWeights(cm, cfg, chunk_symbols=CHUNK)
    slot = weights.get(0)
    kinds = {type(slot[n.split("/", 1)[1]]) for n in weights._hosted}
    assert kinds == {QT, QT4}          # both families host per-layer slots
    sc = serving_engine.ServeConfig(max_len=MAX_LEN)
    dense_eng = serving_engine.Engine(cfg, qparams, sc)
    comp_eng = serving_engine.Engine(cfg, weights, sc, resident="compressed")
    prompt = _prompt(cfg, 1, 7)
    ref = np.asarray(dense_eng.generate(prompt, 5))
    out = np.asarray(comp_eng.generate(prompt, 5))
    np.testing.assert_array_equal(ref, out)
    assert weights.peak_resident_bytes() < weights.dense_bf16_bytes()


# ------------------------------------------------------------- guardrails

def test_resident_mode_guardrails():
    sc = serving_engine.ServeConfig(max_len=MAX_LEN)
    with pytest.raises(ValueError, match="resident"):
        serving_engine.ServeSteps(_cfg("dense"), sc, resident="bogus")
    ssm = registry.reduced(registry.get("mamba2-370m"))
    assert not api.has_cached_step(ssm)
    with pytest.raises(NotImplementedError, match="per-layer"):
        serving_engine.ServeSteps(ssm, sc, resident="compressed")


def test_decode_into_preallocated_buffer():
    """The decode-into-buffer entry point: same symbols, caller's buffer."""
    from repro.core.bitstream import decode_streams, pack_streams
    from repro.core.codecs import get_codec
    rng = np.random.default_rng(0)
    sym = rng.integers(0, 256, 4096).astype(np.uint8)
    freqs = np.bincount(sym, minlength=256).astype(np.int64)
    table = get_codec("huffman").build(freqs, 8, max_code_len=12)
    streams, counts = [], []
    for i in range(0, 4096, 1024):
        s, _ = table.encode(sym[i:i + 1024])
        streams.append(s)
        counts.append(1024)
    mat, _ = pack_streams(streams)
    counts = np.asarray(counts, np.int64)
    a = table.decode_arrays()
    ref = decode_streams(mat, counts, a["lut_sym"], a["lut_len"],
                         table.peek_bits)
    buf = np.full((8, 2048), -1, np.int32)      # oversize on purpose
    got = decode_streams(mat, counts, a["lut_sym"], a["lut_len"],
                         table.peek_bits, out=buf)
    np.testing.assert_array_equal(ref, got)
    np.testing.assert_array_equal(buf[:4, :1024], ref)
    assert got.base is buf                      # genuinely in place
    with pytest.raises(ValueError, match="too small"):
        decode_streams(mat, counts, a["lut_sym"], a["lut_len"],
                       table.peek_bits, out=np.zeros((2, 8), np.int32))
    # the device-returning (jax) backend honors the same contract: copies
    # into the caller's buffer, and rejects undersized ones identically
    from repro.core.decode_backends import get_backend
    jb = get_backend("jax")
    buf2 = np.full((8, 2048), -1, np.int32)
    got2 = jb.decode_table(table, mat, counts, out=buf2)
    np.testing.assert_array_equal(ref, got2)
    np.testing.assert_array_equal(buf2[:4, :1024], ref)
    with pytest.raises(ValueError, match="too small"):
        jb.decode_table(table, mat, counts, out=np.zeros((2, 8), np.int32))
