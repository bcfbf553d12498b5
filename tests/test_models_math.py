"""Numerical correctness of the model-math building blocks."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.distributed import ctx
from repro.launch import mesh as mesh_lib
from repro.models import api, dense, layers
from repro.models.kv import dequantize_kv, quantize_kv
from repro.models.layers import gqa_attention, rms_norm, rope, softmax_xent
from repro.models.mamba2 import _causal_conv, ssd_chunked, ssd_step
from repro.obs import metrics as obs_metrics


def _naive_attention(q, k, v, causal, kv_len=None):
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    kf = np.repeat(np.asarray(k, np.float32), G, axis=2)
    vf = np.repeat(np.asarray(v, np.float32), G, axis=2)
    qf = np.asarray(q, np.float32)
    out = np.zeros((B, S, H, hd), np.float32)
    for b in range(B):
        for h in range(H):
            s = qf[b, :, h] @ kf[b, :, h].T / np.sqrt(hd)
            mask = np.ones((S, T), bool)
            if causal:
                mask &= np.tril(np.ones((S, T), bool))
            if kv_len is not None:
                mask[:, int(np.broadcast_to(kv_len, (B,))[b]):] = False
            s = np.where(mask, s, -1e9)
            p = np.exp(s - s.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            out[b, :, h] = p @ vf[b, :, h]
    return out


@pytest.mark.parametrize("S,H,KV,q_block", [(16, 4, 2, 0), (32, 4, 4, 8),
                                            (32, 8, 2, 16)])
def test_gqa_attention_matches_naive(S, H, KV, q_block):
    rng = np.random.default_rng(S + H)
    B, hd = 2, 16
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    out = gqa_attention(q, k, v, causal=True, q_block=q_block)
    want = _naive_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32), want, atol=5e-2)


def test_gqa_attention_decode_with_cache_mask():
    rng = np.random.default_rng(0)
    B, T, H, KV, hd = 2, 16, 4, 2, 8
    q = jnp.asarray(rng.normal(size=(B, 1, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, KV, hd)), jnp.float32)
    out = gqa_attention(q, k, v, causal=False, kv_len=jnp.int32(10))
    want = _naive_attention(q, k, v, causal=False, kv_len=10)
    np.testing.assert_allclose(np.asarray(out, np.float32), want, atol=5e-2)


@pytest.mark.parametrize("cache", ["bf16", "int8"])
@pytest.mark.parametrize("G", [1, 2, 4, 8])
def test_gqa_attention_decode_per_slot_matches_naive(G, cache):
    # one decode step of a slot batch: S = 1, every slot at its own length
    rng = np.random.default_rng(G)
    B, T, KV, hd = 3, 24, 2, 16
    H = KV * G
    kv_len = jnp.asarray([1, 13, 24], jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, 1, H, hd)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(B, T, KV, hd)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(B, T, KV, hd)), jnp.bfloat16)
    if cache == "int8":
        k, v = dequantize_kv(*quantize_kv(k)), dequantize_kv(*quantize_kv(v))
    out = gqa_attention(q, k, v, causal=False, q_offset=kv_len - 1,
                        kv_len=kv_len)
    assert out.shape == (B, 1, H, hd)
    want = _naive_attention(q, k, v, causal=False, kv_len=np.asarray(kv_len))
    np.testing.assert_allclose(np.asarray(out, np.float32), want, atol=5e-2)


def _walk_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk_eqns(sub)


def test_decode_step_never_widens_kv_to_query_heads():
    cfg = registry.reduced(registry.get("qwen3-1.7b"))
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    assert (H, KV) == (4, 2)
    B, T = 3, 40
    params = jax.eval_shape(lambda: dense.init(cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: dense.init_cache(cfg, B, T))
    token = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((B,), jnp.int32)
    closed = jax.make_jaxpr(
        lambda p, t, c, s: dense.decode_step(cfg, p, t, c, s))(
            params, token, cache, pos)
    widened = [e.outvars[0].aval.shape for e in _walk_eqns(closed.jaxpr)
               if e.primitive.name == "broadcast_in_dim"
               and T in e.outvars[0].aval.shape
               and np.prod(e.outvars[0].aval.shape) >= B * T * H * hd]
    assert not widened, widened


def _form_count(form):
    return obs_metrics.counter("attn.form").value(form=form)


@pytest.mark.parametrize("KV,form", [(2, "replicated"), (4, "grouped")])
def test_attn_form_follows_sharding_hints(KV, form):
    B, S, T, H, hd = 2, 1, 16, 8, 16
    rng = np.random.default_rng(KV)
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, KV, hd)), jnp.float32)

    def attend(q, k, v):     # a fresh function each time: traced anew
        return jax.jit(lambda q, k, v: gqa_attention(
            q, k, v, causal=False, kv_len=jnp.int32(11)))(q, k, v)

    before = _form_count("grouped")
    attend(q, k, v)
    assert _form_count("grouped") == before + 1      # no hints: grouped

    # model axis of 4: divides KV = 4, wider than KV = 2 can cover
    mesh = mesh_lib.make_serve_mesh(2, 4)
    counts = {f: _form_count(f) for f in ("grouped", "replicated")}
    ctx.set_hints(ctx.ShardingHints(mesh=mesh, batch_axes=("data",)))
    try:
        out = attend(q, k, v)
    finally:
        ctx.set_hints(None)
    assert _form_count(form) == counts[form] + 1
    other = "grouped" if form == "replicated" else "replicated"
    assert _form_count(other) == counts[other]
    want = _naive_attention(q, k, v, causal=False, kv_len=11)
    np.testing.assert_allclose(np.asarray(out, np.float32), want, atol=5e-2)


# weight kind -> (activation dtype, weight dtype or packing, matmul.weight)
WEIGHTS = {
    "f32": (jnp.float32, jnp.float32, "rounded_f32"),
    "f32-bf16-act": (jnp.bfloat16, jnp.float32, "rounded_f32"),
    "bf16": (jnp.bfloat16, jnp.bfloat16, "as_given"),
    "bf16-f32-act": (jnp.float32, jnp.bfloat16, "as_given"),
    "qt": (jnp.bfloat16, "qt", "as_given"),
    "fused": (jnp.bfloat16, "fused", "as_given"),
}


def _weight_case(kind):
    """(x, w as matmul takes it, the dense weight today's dot reads)."""
    xdt, wdt, _ = WEIGHTS[kind]
    if wdt in ("qt", "fused"):
        from differential import qt_cases
        from repro.kernels.fused_decode_matmul import build_fused_qt
        c = qt_cases.fused_case(bits=8, codec="huffman", K=8, N=16, seg=32)
        qt = layers.pack_qt(c.sym, c.scale, c.zero, bits=8)
        qt = type(qt)(*(jnp.asarray(p) for p in qt))
        w = qt if wdt == "qt" else build_fused_qt(
            c.table, c.mat, c.scale, c.zero, seg_symbols=c.seg, K=c.K,
            N=c.N, bits=c.bits, impl="jax")
        return c.x.astype(xdt), w, qt
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(3, 64)), xdt)
    w = jnp.asarray(rng.normal(0.0, 0.05, size=(64, 48)), wdt)
    return x, w, w


def _weight_count(operand):
    return obs_metrics.counter("matmul.weight").value(operand=operand)


@pytest.mark.parametrize("kind", sorted(WEIGHTS))
def test_matmul_keeps_todays_dot_off_the_tpu(kind):
    """On the CPU ``matmul`` is today's ``x @ deq(w, x.dtype)`` to the bit,
    for every weight kind, and ``matmul.weight`` counts each trace by
    whether the TPU program rounds the weight itself."""
    x, w, dense_w = _weight_case(kind)
    label = WEIGHTS[kind][2]
    want = jax.jit(lambda x, w: x @ layers.deq(w, x.dtype))(x, dense_w)
    before = {op: _weight_count(op) for op in ("rounded_f32", "as_given")}
    got = jax.jit(lambda x, w: layers.matmul(x, w))(x, w)
    assert got.dtype == want.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    np.testing.assert_array_equal(np.asarray(layers.matmul(x, w), np.float32),
                                  np.asarray(want, np.float32))
    assert _weight_count(label) == before[label] + 2     # traced twice
    other = "as_given" if label == "rounded_f32" else "rounded_f32"
    assert _weight_count(other) == before[other]


def test_round_bf16_is_the_bf16_cast():
    """The TPU branch's weight is the float32 weight on bf16's grid, exactly
    as a cast to bf16 rounds it: nearest, ties to even, subnormals and
    infinities included."""
    rng = np.random.default_rng(11)
    one = np.float32(1.0)
    edge = np.array([0.0, -0.0, one + 2.0 ** -8, one + 3 * 2.0 ** -8,
                     -(one + 2.0 ** -8), 1e-40, -3e-39, 3.4e38,
                     np.finfo(np.float32).max, np.inf, -np.inf], np.float32)
    w = np.concatenate([rng.normal(0.0, 0.05, 4096).astype(np.float32),
                        rng.normal(0.0, 1e3, 1024).astype(np.float32),
                        rng.integers(0, 2 ** 32, 1024, dtype=np.uint32
                                     ).view(np.float32), edge])
    w = w[np.isfinite(w) | np.isinf(w)]
    want = np.asarray(jnp.asarray(w).astype(jnp.bfloat16).astype(jnp.float32))
    for got in (layers.round_bf16(jnp.asarray(w)),
                jax.jit(layers.round_bf16)(jnp.asarray(w))):
        assert got.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(got).view(np.uint32),
                                      want.view(np.uint32))


@pytest.mark.parametrize("kind", ["f32", "f32-bf16-act", "bf16", "qt"])
def test_matmul_rounds_float32_weights_only_for_the_tpu(kind):
    """Lowered for the TPU, a float32 weight reaches its dot through
    ``reduce_precision`` and is never cast to bf16; lowered for the CPU, or
    under a non-default matmul precision, the program is today's."""
    x, w, _ = _weight_case(kind)
    rounds = WEIGHTS[kind][2] == "rounded_f32"
    f = jax.jit(lambda x, w: layers.matmul(x, w))

    def text(platform):
        return f.trace(x, w).lower(lowering_platforms=(platform,)).as_text()

    w_bf16 = "tensor<%sxbf16>" % "x".join(map(str, (64, 48)))
    tpu, cpu = text("tpu"), text("cpu")
    assert ("reduce_precision" in tpu) == rounds
    assert "reduce_precision" not in cpu
    if rounds:
        assert w_bf16 not in tpu
        assert (w_bf16 in cpu) == (x.dtype == jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        assert "reduce_precision" not in text("tpu")


def test_rope_preserves_norm_and_relativity():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(1, 8, 2, 16)), jnp.float32)
    pos = jnp.arange(8)
    y = rope(x, pos, 10_000.0)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(y), axis=-1),
        np.linalg.norm(np.asarray(x), axis=-1), rtol=1e-5)
    # dot(q_i, k_j) depends only on i - j
    q = jnp.asarray(rng.normal(size=(1, 16, 1, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 16, 1, 16)), jnp.float32)
    qr = np.asarray(rope(q, jnp.arange(16), 1e4))
    kr = np.asarray(rope(k, jnp.arange(16), 1e4))
    d1 = (qr[0, 5, 0] * kr[0, 3, 0]).sum()
    q2 = np.asarray(rope(q, jnp.arange(16) + 7, 1e4))
    k2 = np.asarray(rope(k, jnp.arange(16) + 7, 1e4))
    d2 = (q2[0, 5, 0] * k2[0, 3, 0]).sum()
    np.testing.assert_allclose(d1, d2, rtol=1e-4, atol=1e-5)


def test_rms_norm_unit_rms():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(0, 10, size=(4, 32)), jnp.float32)
    y = rms_norm(x, jnp.ones(32))
    rms = np.sqrt((np.asarray(y) ** 2).mean(-1))
    np.testing.assert_allclose(rms, 1.0, rtol=1e-3)


def test_softmax_xent_masks_out_of_vocab():
    logits = jnp.zeros((1, 4, 8))
    labels = jnp.asarray([[1, 2, 100, -1]])     # 2 valid, 2 masked
    loss = softmax_xent(logits, labels, vocab=8)
    np.testing.assert_allclose(float(loss), np.log(8), rtol=1e-5)


@pytest.mark.parametrize("S,chunk", [(64, 16), (96, 32), (50, 32)])
def test_ssd_chunked_matches_stepwise(S, chunk):
    rng = np.random.default_rng(S)
    B, H, P, N = 2, 3, 8, 16
    x = jnp.asarray(rng.normal(size=(B, S, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, size=(B, S, H)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 4.0, size=(H,)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32)
    h0 = jnp.asarray(rng.normal(size=(B, H, P, N)), jnp.float32)
    y, h = ssd_chunked(x, dt, A, Bm, Cm, chunk=chunk, h0=h0)
    hh = h0
    ys = []
    for t in range(S):
        yt, hh = ssd_step(x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t], hh)
        ys.append(yt)
    np.testing.assert_allclose(np.asarray(y), np.asarray(jnp.stack(ys, 1)),
                               atol=5e-5)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hh), atol=5e-5)


def test_causal_conv_matches_numpy_and_streams():
    rng = np.random.default_rng(3)
    B, S, C, K = 2, 20, 6, 4
    x = jnp.asarray(rng.normal(size=(B, S, C)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(K, C)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(C,)), jnp.float32)
    y, state = _causal_conv(x, w, b)
    # numpy oracle
    xp = np.concatenate([np.zeros((B, K - 1, C)), np.asarray(x)], axis=1)
    want = np.zeros((B, S, C))
    for k in range(K):
        want += xp[:, k: k + S] * np.asarray(w)[k]
    want = want + np.asarray(b)
    want = want / (1 + np.exp(-want))           # silu
    np.testing.assert_allclose(np.asarray(y), want, atol=1e-5)
    # streaming: feed the tail one token at a time with carried state
    y2, st = _causal_conv(x[:, :10], w, b)
    outs = [y2]
    for t in range(10, S):
        yt, st = _causal_conv(x[:, t:t + 1], w, b, state=st)
        outs.append(yt)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, 1)),
                               np.asarray(y), atol=1e-5)


def test_moe_dispatch_matches_dense_ffn_when_experts_identical():
    """With identical experts + top-1 and ample capacity, MoE == dense MLP."""
    from repro.configs.base import MoEConfig
    from repro.models.moe import moe_mlp
    rng = np.random.default_rng(4)
    B, S, D, F, E = 2, 8, 16, 32, 4
    x = jnp.asarray(rng.normal(size=(B, S, D)), jnp.bfloat16)
    w_gate1 = rng.normal(0, 0.2, size=(D, F)).astype(np.float32)
    w_up1 = rng.normal(0, 0.2, size=(D, F)).astype(np.float32)
    w_down1 = rng.normal(0, 0.2, size=(F, D)).astype(np.float32)
    wts = {
        "router": jnp.asarray(rng.normal(size=(D, E)), jnp.float32),
        "w_gate": jnp.asarray(np.tile(w_gate1, (E, 1, 1)), jnp.bfloat16),
        "w_up": jnp.asarray(np.tile(w_up1, (E, 1, 1)), jnp.bfloat16),
        "w_down": jnp.asarray(np.tile(w_down1, (E, 1, 1)), jnp.bfloat16),
    }
    mcfg = MoEConfig(num_experts=E, top_k=1, capacity_factor=8.0)
    y, aux = moe_mlp(x, wts, mcfg, E)
    xd = np.asarray(x, np.float32)
    h = xd @ w_gate1
    u = xd @ w_up1
    want = (h / (1 + np.exp(-h)) * u) @ w_down1
    np.testing.assert_allclose(np.asarray(y, np.float32), want, atol=0.1,
                               rtol=0.1)
    assert np.isfinite(float(aux))


# cached-step families, each at its registry's reduced size
FAMILIES = {"dense": "qwen3-1.7b", "moe": "qwen2-moe-a2.7b"}
# (cache layout, position form): the paged pool serves continuous batching
# only, so it takes per-slot positions
LAYOUTS = [("slot16", "scalar"), ("slot16", "slots"), ("slot8", "scalar"),
           ("slot8", "slots"), ("paged16", "slots")]


def _fill(cache, seed):
    keys = jax.random.split(jax.random.PRNGKey(seed), len(cache))
    return {name: jax.random.randint(r, a.shape, -127, 128, jnp.int8)
            if a.dtype == jnp.int8
            else jax.random.normal(r, a.shape).astype(a.dtype)
            for r, (name, a) in zip(keys, cache.items())}


def _layer_by_layer(cfg, params, tokens, cache, pos, *table):
    """The same step driven one layer at a time through the per-layer
    pieces compressed-resident serving jits (`ServeSteps`)."""
    mod = api.build(cfg)
    x = jax.jit(functools.partial(mod.embed, cfg))(params, tokens)
    positions = mod.step_positions(pos, tokens.shape[1])
    layer = jax.jit(functools.partial(mod.cached_layer, cfg))
    stack = dense._layer_stack(params)
    for l in range(cfg.n_layers):
        lp = {k: v[l] for k, v in stack.items()}
        x, cache = layer(lp, x, cache, jnp.int32(l), pos, positions, *table)
    return jax.jit(functools.partial(mod.head, cfg))(params, x), cache


@pytest.mark.parametrize("family", sorted(FAMILIES))
@pytest.mark.parametrize("layout,pos_form", LAYOUTS)
@pytest.mark.parametrize("S", [1, 4])
def test_cached_step_writes_only_its_rows_and_matches_layer_by_layer(
        S, layout, pos_form, family):
    """`decode_step` (S=1) and `prefill_chunk` (S=4) carry the layer-stacked
    cache through their scan: the returned cache differs from the one passed
    in only at rows [pos_b, pos_b + S) of each layer and slot, and logits and
    cache are bitwise those of the step driven layer by layer.  The paged
    pool holds the slot stack's rows in shuffled blocks: the step changes
    only the blocks its table names for those rows, and its logits are
    bitwise the slot stack's."""
    cfg = registry.reduced(registry.get(FAMILIES[family]))
    mod = api.build(cfg)
    params = mod.init(cfg, jax.random.PRNGKey(0))
    B, T, BS = 3, 24, 4
    cache = _fill(mod.init_cache(cfg, B, T,
                                 kv_bits=8 if layout == "slot8" else 16), 1)
    pos = jnp.int32(5) if pos_form == "scalar" \
        else jnp.array([0, 9, T - S], jnp.int32)
    tokens = jnp.arange(1, B * S + 1, dtype=jnp.int32).reshape(B, S)
    step = mod.decode_step if S == 1 else mod.prefill_chunk
    run = jax.jit(lambda p, t, c, s, *bt: step(cfg, p, t, c, s, *bt))
    logits, out = run(params, tokens, cache, pos)
    starts = np.broadcast_to(np.asarray(pos), (B,))
    written = np.zeros((B, T), bool)                   # (B, T) rows
    for b, p in enumerate(starts):
        written[b, p:p + S] = True
    before, table = cache, ()

    if layout == "paged16":
        slot_logits, slot_out = logits, out
        MB = T // BS
        bt = 1 + np.random.default_rng(2).permutation(B * MB).reshape(B, MB)
        pool = _fill(mod.init_kv_pool(cfg, 1 + B * MB, BS), 3)
        pool = {n: a.at[:, bt].set(cache[n].reshape(
                    cache[n].shape[:2] + (MB, BS) + cache[n].shape[3:]))
                for n, a in pool.items()}
        before, table = pool, (jnp.asarray(bt, jnp.int32),)
        logits, out = run(params, tokens, pool, pos, *table)
        np.testing.assert_array_equal(np.asarray(logits),
                                      np.asarray(slot_logits))
        for k in pool:       # read back through the table: the slot rows
            np.testing.assert_array_equal(
                np.asarray(out[k])[:, bt].reshape(slot_out[k].shape),
                np.asarray(slot_out[k]), err_msg=k)
        rows, written = written, np.zeros(pool["k"].shape[1:3], bool)
        for b, t in zip(*np.nonzero(rows)):          # -> (NB, BS) slots
            written[bt[b, t // BS], t % BS] = True

    want_logits, want = _layer_by_layer(cfg, params, tokens, before, pos,
                                        *table)
    assert logits.shape == (B, S, cfg.padded_vocab())
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(want_logits))
    for k in before:
        got, prev = np.asarray(out[k]), np.asarray(before[k])
        np.testing.assert_array_equal(got, np.asarray(want[k]), err_msg=k)
        changed = (got != prev).any(axis=(3, 4))     # (L, B, T) / (L, NB, BS)
        assert not (changed & ~written).any(), k
        assert changed[:, written].any(), k
