"""GLM-4's block (``ArchConfig(family="glm4")``): bias on Q/K/V, interleaved
rotary over the first half of each head, RMSNorm epsilon 1.5625e-7, served
by the dense family.

A seeded tiny GLM (16 query heads per KV head, a 16-dim head whose first 8
dims rotate, an FFN width of 208 = 13 * 16) is served through
``ContinuousEngine`` (chunked prefill, then slot decode) and its logits are
compared with the benchmark's plain float32 reference,
``bench/reference/glm4.py``, loaded by path.  The paged and the
compressed-resident paths are compared with the slot path, the rotary
with a complex-number formula, and a configuration without the new fields
with the dense block's equations as they were before GLM-4's traits.
"""
import dataclasses
import importlib
import importlib.util
import sys
from pathlib import Path
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.base import ArchConfig
from repro.core.quant import Granularity
from repro.core.spec import KVCompressionSpec, spec_from_legacy
from repro.core.store import CompressedModel
from repro.models import api, dense, layers
from repro.obs import metrics as obs_metrics
from repro.serving import engine as serving_engine
from repro.serving.batching import ContinuousEngine
from repro.serving.batching import engine as batching_engine
from repro.serving.resident import CompressedResidentWeights

REF_DIR = Path(__file__).resolve().parents[1] / "bench" / "reference"
SEED = 2 ** 33 + 15
PROMPT_LEN, GEN, CHUNK, MAX_LEN = 37, 12, 16, 64

# rope_theta 10,000 (GLM-4-9B-chat's is 5e6): at these few positions a
# base of 5e6 would leave all but the first rotary pair nearly unturned
CFG = ArchConfig(name="glm4-tiny", family="glm4", n_layers=2, d_model=128,
                 n_heads=16, n_kv_heads=1, d_ff=208, vocab=500, head_dim=16,
                 rope_theta=10_000.0)

# Served logits against the reference, in absolute terms; the logits
# spread about 0.22 (embedding and head std 0.02 over 128 dims).  The
# program's weights and activations are float32 here, as the reference's
# are, but its attention takes bfloat16 operands by design (q, k, the
# probabilities, and the bfloat16 K/V cache; ``layers.gqa_attention``):
# that moves these logits by 1.2e-3 in a full forward and 3.7e-3 through
# the engine, and by 5e-7 once the attention is float32.  Each GLM-4 trait
# taken away moves them by 0.28 or more, past 10 times this tolerance.
LOGIT_TOL = 1e-2


class Dims(NamedTuple):
    """The sizes ``bench/reference/glm4.py`` reads (``bench/modelcfg.Dims``)."""
    family: str
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    eps: float
    tie: bool
    qk_norm: bool


DIMS = Dims("glm4", CFG.n_layers, CFG.d_model, CFG.n_heads, CFG.n_kv_heads,
            CFG.hd, CFG.d_ff, CFG.vocab, CFG.rope_theta, 1.5625e-7, False,
            False)


@pytest.fixture(scope="module")
def ref():
    """``bench/reference/glm4.py``, imported from its path as a member of
    its package (it imports ``dense.py`` beside it)."""
    name = "glm4_test_reference"
    spec = importlib.util.spec_from_file_location(
        name, REF_DIR / "__init__.py", submodule_search_locations=[str(REF_DIR)])
    pkg = importlib.util.module_from_spec(spec)
    sys.modules[name] = pkg
    spec.loader.exec_module(pkg)
    return importlib.import_module(f"{name}.glm4")


@pytest.fixture(scope="module")
def weights(ref):
    """``(reference weights, program float32 params, host copy)``: the
    reference's seeded weights on the 8-bit grid, zero-padded to the
    program's vocabulary rows."""
    w = ref.reference_weights(DIMS, SEED, 8)
    host = {}
    for name, shape in api.param_shapes(CFG).items():
        a = np.asarray(w[name], np.float32)
        host[name] = np.pad(a, [(0, s - h) for h, s in zip(a.shape, shape)])
    return w, {k: jnp.asarray(v) for k, v in host.items()}, host


def _prompt():
    return np.random.default_rng(SEED).integers(
        0, CFG.vocab, PROMPT_LEN).astype(np.int32)


def _serve(monkeypatch, params, cfg=CFG, **engine_kw):
    """One greedy request through a one-slot ``ContinuousEngine``; returns
    ``(output tokens, logits (GEN, vocab) that produced them)``."""
    seen = []
    real_first, real_slots = batching_engine.sample, batching_engine._sample_slots

    def first(logits, *a, **k):
        seen.append(np.asarray(logits, np.float32)[0, -1])
        return real_first(logits, *a, **k)

    def slots(logits, *a, **k):
        seen.append(np.asarray(logits, np.float32)[0, -1])
        return real_slots(logits, *a, **k)

    monkeypatch.setattr(batching_engine, "sample", first)
    monkeypatch.setattr(batching_engine, "_sample_slots", slots)
    ce = ContinuousEngine(cfg, params, serving_engine.ServeConfig(
        max_len=MAX_LEN), n_slots=1, prefill_chunk=CHUNK, **engine_kw)
    req = ce.submit(_prompt(), GEN)
    ce.run()
    assert len(req.output) == GEN
    return list(req.output), np.stack(seen[:GEN])[:, :cfg.vocab]


def _reference_logits(ref, w, out):
    toks = np.zeros(ref.Q_BLOCK, np.int32)
    seq = np.concatenate([_prompt(), np.asarray(out[:-1], np.int32)])
    toks[:len(seq)] = seq
    rows = np.arange(PROMPT_LEN - 1, PROMPT_LEN - 1 + GEN)
    return np.asarray(ref.logits_at(DIMS, w, toks, rows))


def test_glm4_resolves_to_the_dense_block_with_its_traits():
    assert CFG.family == "dense" and api.build(CFG) is dense
    assert (CFG.qkv_bias, CFG.rotary, CFG.rope_dim, CFG.rope_pairs,
            CFG.norm_eps) == (True, "partial_interleaved", 8, "interleaved",
                              1.5625e-7)
    pub = registry.get("glm4-9b")
    assert (pub.family, pub.n_layers, pub.d_model, pub.n_heads, pub.n_kv_heads,
            pub.hd, pub.d_ff, pub.vocab) == \
        ("dense", 40, 4096, 32, 2, 128, 13696, 151552)
    assert (pub.qkv_bias, pub.rope_dim, pub.rope_pairs, pub.norm_eps) == \
        (True, 64, "interleaved", 1.5625e-7)
    assert not pub.tie_embeddings and pub.rope_theta == 5e6
    assert pub.source == "https://huggingface.co/THUDM/glm-4-9b-chat"
    shapes = api.param_shapes(pub)
    assert shapes["layers/q_bias"] == (40, 4096)
    assert shapes["layers/k_bias"] == shapes["layers/v_bias"] == (40, 256)
    red = registry.reduced(pub)
    assert (red.qkv_bias, red.rotary, red.rope_dim, red.norm_eps) == \
        (True, "partial_interleaved", red.hd // 2, 1.5625e-7)



def test_family_traits_fill_defaults_and_refuse_a_conflict():
    """A trait fills a field left at its default and agrees with an explicit
    equal value; an explicit value that differs raises."""
    kw = dict(name="t", n_layers=1, d_model=64, n_heads=4, n_kv_heads=1,
              d_ff=128, vocab=64, head_dim=16)
    same = ArchConfig(family="glm4", norm_eps=1.5625e-7, qkv_bias=True, **kw)
    assert same == ArchConfig(family="glm4", **kw)
    assert dataclasses.replace(same, n_layers=2).rotary == "partial_interleaved"
    for wrong in (dict(norm_eps=1e-5), dict(rotary="full")):
        with pytest.raises(ValueError, match="glm4"):
            ArchConfig(family="glm4", **wrong, **kw)
    with pytest.raises(ValueError, match="rotary"):
        ArchConfig(family="dense", rotary="partial", **kw)

def test_served_logits_match_the_reference(ref, weights, monkeypatch):
    w, params, _ = weights
    out, got = _serve(monkeypatch, params)
    want = _reference_logits(ref, w, out)
    err = np.max(np.abs(got - want))
    assert err <= LOGIT_TOL, err
    # greedy picks the reference's best token, or one whose reference logit
    # is within 2 * err of it: each logit moved by at most err
    gap = want.max(-1) - want[np.arange(GEN), out]
    assert np.max(gap) <= 2 * err, (gap, err)


@pytest.mark.parametrize("change", [
    dict(qkv_bias=False), dict(rotary="full"), dict(norm_eps=1e-2)])
def test_tolerance_fails_without_a_glm4_trait(ref, weights, change):
    """The comparison can fail: one trait taken away moves the logits far
    past LOGIT_TOL (a large epsilon stands in for a wrong one)."""
    w, params, _ = weights
    cfg = dataclasses.replace(CFG, **change)
    toks = np.zeros((1, ref.Q_BLOCK), np.int32)
    toks[0, :PROMPT_LEN] = _prompt()
    x, _ = dense.forward(cfg, params, jnp.asarray(toks[:, :PROMPT_LEN]))
    got = np.asarray(dense.logits_fn(cfg, params, x))[0, :, :CFG.vocab]
    want = np.asarray(ref.logits_at(DIMS, w, toks[0],
                                    np.arange(PROMPT_LEN)))
    assert np.max(np.abs(got - want)) > 10 * LOGIT_TOL


def _slot_and_other(path, weights):
    """``(params of the slot path, params and engine options of the path
    compared with it)``; the compressed-resident path is compared with the
    slot path on the same container's QT weights."""
    _, params, host = weights
    if path == "paged":
        return params, params, dict(kv_spec=KVCompressionSpec(block_size=8))
    cm = CompressedModel.compress(host, spec=spec_from_legacy(
        8, Granularity.PER_CHANNEL, segment_symbols=1024))
    # the biases stay float32, as the norms do (the name key "bias")
    assert not {"layers/q_bias", "layers/k_bias", "layers/v_bias"} & \
        set(cm.tensors)
    qparams = serving_engine.load_params_from_compressed(cm, quantized=True)
    return qparams, CompressedResidentWeights(cm, CFG, chunk_symbols=65536), \
        dict(resident="compressed")


@pytest.mark.parametrize("path", ["paged", "resident"])
def test_path_matches_the_slot_path(weights, monkeypatch, path):
    """Paged bf16 blocks and compressed residency serve bit-identical
    logits to the slot cache (their contracts, docs/KV_CACHE.md and
    docs/SERVING.md)."""
    slot_params, params, kw = _slot_and_other(path, weights)
    out, got = _serve(monkeypatch, params, **kw)
    want_out, want = _serve(monkeypatch, slot_params)
    assert out == want_out
    np.testing.assert_array_equal(got, want)


def _complex_rope(x, pos, theta, rot, pairs):
    """Rotary as complex multiplication: pair (a, b) is a + ib, turned by
    pos * theta ** (-2i / rot)."""
    half = rot // 2
    if pairs == "interleaved":
        a, b = x[..., 0:rot:2], x[..., 1:rot:2]
    else:
        a, b = x[..., :half], x[..., half:rot]
    ang = pos[:, None] * theta ** (-2.0 * np.arange(half) / rot)
    z = (a + 1j * b) * np.exp(1j * ang)[:, None, :]
    out = x.copy()
    if pairs == "interleaved":
        out[..., 0:rot:2], out[..., 1:rot:2] = z.real, z.imag
    else:
        out[..., :half], out[..., half:rot] = z.real, z.imag
    return out


@pytest.mark.parametrize("rot,pairs", [(8, "interleaved"), (16, "interleaved"),
                                       (8, "half"), (16, "half")])
def test_rope_matches_complex_formula(rot, pairs):
    rng = np.random.default_rng(rot)
    x = rng.normal(size=(7, 3, 16)).astype(np.float32)
    pos = np.arange(100, 107)
    got = np.asarray(layers.rope(jnp.asarray(x), jnp.asarray(pos), 500.0,
                                 rot, pairs))
    want = _complex_rope(x.astype(np.float64), pos, 500.0, rot, pairs)
    # float32 angles of positions ~100: about 1e-5 of a turn
    np.testing.assert_allclose(got, want, atol=2e-4)
    np.testing.assert_array_equal(got[..., rot:], x[..., rot:])


def _rope_before(x, positions, theta):
    """``layers.rope`` as it was before partial rotary (rotate-half over the
    whole head)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([
        x1 * cos.astype(x.dtype) - x2 * sin.astype(x.dtype),
        x2 * cos.astype(x.dtype) + x1 * sin.astype(x.dtype)], axis=-1)


def _qkv_before(cfg, lp, x, positions):
    """The dense block's Q/K/V equations before GLM-4's traits."""
    B, S, _ = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    h = layers.rms_norm(x, lp["attn_norm"])
    q = layers.matmul(h, lp["wq"]).reshape(B, S, H, hd)
    k = layers.matmul(h, lp["wk"]).reshape(B, S, KV, hd)
    v = layers.matmul(h, lp["wv"]).reshape(B, S, KV, hd)
    if cfg.qk_norm:
        q = layers.rms_norm(q, lp["q_norm"])
        k = layers.rms_norm(k, lp["k_norm"])
    return (_rope_before(q, positions, cfg.rope_theta),
            _rope_before(k, positions, cfg.rope_theta), v)


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "stablelm-12b"])
def test_config_without_new_fields_keeps_its_equations(arch):
    """Bit-identical Q/K/V and rotary for a configuration that leaves the
    new fields at their defaults, under jit as the engine runs them."""
    cfg = registry.reduced(registry.get(arch))
    assert (cfg.qkv_bias, cfg.rotary, cfg.rope_dim, cfg.rope_pairs,
            cfg.norm_eps) == (False, "full", cfg.hd, "half", 1e-6)
    params = api.build(cfg).init(cfg, jax.random.PRNGKey(3))
    lp = {k: v[0] for k, v in dense._layer_stack(params).items()}
    assert not {"q_bias", "k_bias", "v_bias"} & set(lp)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 5, cfg.d_model),
                          jnp.bfloat16)
    positions = jnp.arange(7, 12)
    got = jax.jit(lambda lp, x: dense._qkv(cfg, lp, x, positions))(lp, x)
    want = jax.jit(lambda lp, x: _qkv_before(cfg, lp, x, positions))(lp, x)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))


@pytest.mark.parametrize("cfg,labels", [
    (registry.reduced(registry.get("qwen3-1.7b")),
     dict(rotary="full", qkv_bias="0")),
    (CFG, dict(rotary="partial_interleaved", qkv_bias="1"))])
def test_attn_rope_counts_each_trace(cfg, labels):
    params = api.build(cfg).init(cfg, jax.random.PRNGKey(0))
    lp = {k: v[0] for k, v in dense._layer_stack(params).items()}
    x = jnp.zeros((1, 4, cfg.d_model), jnp.float32)
    before = obs_metrics.counter("attn.rope").value(**labels)
    jax.jit(lambda lp, x: dense._qkv(cfg, lp, x, jnp.arange(4)))(lp, x)
    assert obs_metrics.counter("attn.rope").value(**labels) == before + 1
