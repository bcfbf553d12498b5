"""End-to-end integration: train-to-convergence, fault tolerance, the
compress -> parallel-decode -> serve path, gradient compression."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.models import api


def _train(arch="qwen3-1.7b", steps=15, q8=False, grad_compress=False, mb=1):
    from repro.data.pipeline import DataConfig, SyntheticSource
    from repro.training import optimizer as opt, train_loop
    cfg = registry.reduced(registry.get(arch))
    mod = api.build(cfg)
    params = mod.init(cfg, jax.random.PRNGKey(0))
    tc = train_loop.TrainConfig(
        opt=opt.AdamWConfig(
            schedule=opt.Schedule(base_lr=1e-3, warmup_steps=2,
                                  total_steps=steps),
            quantized_state=q8),
        microbatches=mb, grad_compress=grad_compress)
    state = opt.init_state(tc.opt, params)
    src = SyntheticSource(DataConfig(vocab=cfg.vocab, seq_len=64,
                                     global_batch=8, seed=0))
    return cfg, train_loop.train(cfg, tc, params, state, iter(src), steps)


def test_training_reduces_loss():
    _, (params, state, info) = _train()
    losses = [h["loss"] for h in info["history"]]
    assert losses[-1] < losses[0] - 0.05


def test_training_q8_matches_fp32_trajectory():
    """EntroLLM-quantized optimizer state trains as well as fp32 moments."""
    _, (_, _, info32) = _train(q8=False)
    _, (_, _, info8) = _train(q8=True)
    l32 = info32["history"][-1]["loss"]
    l8 = info8["history"][-1]["loss"]
    assert abs(l32 - l8) < 0.15


def test_training_with_grad_compression_converges():
    _, (_, _, info) = _train(grad_compress=True)
    losses = [h["loss"] for h in info["history"]]
    assert losses[-1] < losses[0] - 0.05


def test_microbatched_equals_single_batch_grads():
    """Grad accumulation is numerically consistent with the fused batch."""
    from repro.training import optimizer as opt, train_loop
    cfg = registry.reduced(registry.get("glm4-9b"))
    mod = api.build(cfg)
    params = mod.init(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 65), 0,
                                          cfg.vocab)}
    outs = {}
    for mb in (1, 2):
        tc = train_loop.TrainConfig(opt=opt.AdamWConfig(), microbatches=mb)
        state = opt.init_state(tc.opt, params)
        step = jax.jit(train_loop.make_train_step(cfg, tc))
        p2, _, m = step(params, state, batch)
        outs[mb] = (p2, float(m["loss"]))
    assert abs(outs[1][1] - outs[2][1]) < 0.02
    for k in params:
        np.testing.assert_allclose(
            np.asarray(outs[1][0][k], np.float32),
            np.asarray(outs[2][0][k], np.float32), atol=5e-3)


# ------------------------------------------------------------- fault tolerance

def test_checkpoint_restart_resumes_exactly():
    """Kill-and-restart: restored (params, opt, step) continue bit-identically
    (data stream is a pure function of step index)."""
    from repro.checkpoint.checkpointer import Checkpointer, CheckpointConfig
    from repro.data.pipeline import DataConfig, SyntheticSource
    from repro.training import optimizer as opt, train_loop
    cfg = registry.reduced(registry.get("qwen3-1.7b"))
    mod = api.build(cfg)
    tc = train_loop.TrainConfig(opt=opt.AdamWConfig(
        schedule=opt.Schedule(base_lr=1e-3, warmup_steps=2, total_steps=20)))
    src = SyntheticSource(DataConfig(vocab=cfg.vocab, seq_len=64,
                                     global_batch=8, seed=0))
    step = jax.jit(train_loop.make_train_step(cfg, tc))

    # uninterrupted 6-step run
    p = mod.init(cfg, jax.random.PRNGKey(0))
    s = opt.init_state(tc.opt, p)
    for i in range(6):
        p, s, _ = step(p, s, src.batch(i))
    ref = {k: np.asarray(v, np.float32) for k, v in p.items()}

    # run 3 steps, checkpoint, "crash", restore, run 3 more
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(CheckpointConfig(root=d))
        p2 = mod.init(cfg, jax.random.PRNGKey(0))
        s2 = opt.init_state(tc.opt, p2)
        for i in range(3):
            p2, s2, _ = step(p2, s2, src.batch(i))
        ck.save(3, (p2, s2))
        del p2, s2
        start, (p3, s3) = ck.restore(like=(mod.init(cfg, jax.random.PRNGKey(0)),
                                           opt.init_state(tc.opt, mod.init(
                                               cfg, jax.random.PRNGKey(0)))))
        assert start == 3
        for i in range(3, 6):
            p3, s3, _ = step(p3, s3, src.batch(i))
    for k in ref:
        np.testing.assert_allclose(ref[k], np.asarray(p3[k], np.float32),
                                   atol=1e-5)


def test_nan_watchdog_rolls_back():
    from repro.checkpoint.checkpointer import Checkpointer, CheckpointConfig
    from repro.distributed.fault_tolerance import NanWatchdog
    from repro.training import optimizer as opt
    cfg = registry.reduced(registry.get("qwen3-1.7b"))
    mod = api.build(cfg)
    params = mod.init(cfg, jax.random.PRNGKey(0))
    state = opt.init_state(opt.AdamWConfig(), params)
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(CheckpointConfig(root=d))
        ck.save(1, (params, state))
        wd = NanWatchdog(ck, (params, state))
        out = wd(5, params, state, {"loss": float("nan"), "grad_norm": 1.0})
        assert out is not None          # rollback triggered
        assert wd.rollbacks == [5]
        out2 = wd(6, params, state, {"loss": 2.0, "grad_norm": 1.0})
        assert out2 is None


def test_straggler_watchdog_and_rebalance():
    from repro.distributed.fault_tolerance import (StepTimeWatchdog,
                                                   suggest_rebalance)
    wd = StepTimeWatchdog(threshold=2.0)
    for i in range(10):
        assert wd.observe(i, 0.1) is None
    assert wd.observe(10, 0.5) == 10          # 5x median -> flagged
    assign = suggest_rebalance({0: 1.0, 1: 5.0, 2: 1.2, 3: 0.9}, hosts=2)
    assert set(assign) == {0, 1, 2, 3}
    loads = [sum(t for s, t in {0: 1.0, 1: 5.0, 2: 1.2, 3: 0.9}.items()
                 if assign[s] == h) for h in range(2)]
    assert max(loads) <= 5.1                  # LPT keeps the big shard alone


def test_elastic_reshard_restore():
    """Restore a checkpoint onto a different device layout (1-dev host mesh)."""
    from repro.checkpoint.checkpointer import Checkpointer, CheckpointConfig
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(CheckpointConfig(root=d))
        ck.save(7, tree)
        shard = {"w": NamedSharding(mesh, P("data", None))}
        step, out = ck.restore(like=tree, shardings=shard)
    assert step == 7
    assert out["w"].sharding.is_equivalent_to(shard["w"], 2)
    np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(tree["w"]))


# ----------------------------------------------------- compress -> serve path

def test_compress_serve_equivalence():
    """QT-resident serving must produce the same logits as serving the densely
    dequantized weights (the quantized model IS the served model)."""
    from repro.core.quant import Granularity
    from repro.core.store import CompressedModel
    from repro.serving import engine
    cfg = registry.reduced(registry.get("glm4-9b"))
    mod = api.build(cfg)
    params = mod.init(cfg, jax.random.PRNGKey(0))
    host = {k: np.asarray(v, np.float32) for k, v in params.items()}
    cm = CompressedModel.compress(host, bits=8,
                                  granularity=Granularity.PER_CHANNEL)

    qt_params = engine.load_params_from_compressed(cm, quantized=True)
    dense_params = engine.load_params_from_compressed(cm, quantized=False)

    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0, cfg.vocab)
    lq, _ = jax.jit(lambda p, t: mod.prefill(cfg, p, t, max_len=16))(
        qt_params, toks)
    ld, _ = jax.jit(lambda p, t: mod.prefill(cfg, p, t, max_len=16))(
        dense_params, toks)
    np.testing.assert_allclose(np.asarray(lq, np.float32),
                               np.asarray(ld, np.float32), atol=0.2, rtol=0.1)


def test_compression_stats_sane():
    from repro.core.store import CompressedModel
    rng = np.random.default_rng(0)
    # peaky trained-like weights -> entropy clearly below 8 bits
    params = {"w": (rng.standard_t(4, size=(64, 4096)) * 0.02).astype(np.float32)}
    cm = CompressedModel.compress(params, bits=8)
    st = cm.stats()
    assert st.effective_bits < 7.0
    assert st.entropy_bits <= st.effective_bits <= st.entropy_bits + 1.0
    assert st.reduction_vs_quant > 0.1


def test_entro_checkpoint_roundtrip_bounded_error():
    from repro.checkpoint.checkpointer import Checkpointer, CheckpointConfig
    rng = np.random.default_rng(0)
    tree = {"w": jnp.asarray(rng.normal(0, 0.02, (64, 512)), jnp.float32)}
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(CheckpointConfig(root=d, compress="entro"))
        ck.save(1, tree)
        _, out = ck.restore(like=tree)
    err = np.abs(np.asarray(out["w"]) - np.asarray(tree["w"])).max()
    assert err < 0.02 * 256 / 255 / 2 + 1e-5   # half quantization step


def test_entro_checkpoint_spec_patterns_match_tree_paths():
    """entro_spec rules match the pytree key path (leaf names carry it), so a
    carve-out like '*/mu/*:fp32' actually protects the optimizer moments."""
    from repro.checkpoint.checkpointer import Checkpointer, CheckpointConfig
    from repro.core.store import CompressedModel
    rng = np.random.default_rng(1)
    tree = {"params": {"wq": jnp.asarray(rng.normal(0, 0.02, (64, 256)),
                                         jnp.float32)},
            "opt": {"mu": {"wq": jnp.asarray(rng.normal(0, 0.001, (64, 256)),
                                             jnp.float32)}}}
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(CheckpointConfig(
            root=d, compress="entro",
            entro_spec="*/mu/*:fp32; */params/*:bits=8,codec=rans"))
        ck.save(1, tree)
        step_dir = os.path.join(d, "step_000000001")
        cm = CompressedModel.load(os.path.join(step_dir,
                                               "shard_00000_entro.npz"))
        # the fp32 carve-out fired for the moment leaf: exact round-trip
        assert any("opt/mu/wq" in n for n in cm.unquantized), cm.unquantized
        assert any("params/wq" in n for n in cm.qmeta), list(cm.qmeta)
        _, out = ck.restore(like=tree)
    assert np.array_equal(np.asarray(out["opt"]["mu"]["wq"]),
                          np.asarray(tree["opt"]["mu"]["wq"]))


def test_ef_gradient_compression_unbiased():
    from repro.distributed import grad_compress as gc
    rng = np.random.default_rng(0)
    g = {"w": jnp.asarray(rng.normal(0, 0.01, (4096,)), jnp.float32)}
    res = None
    acc = jnp.zeros(4096)
    for _ in range(30):
        c, res = gc.ef_compress(g, res)
        acc = acc + c["w"]
    assert float(jnp.abs(acc / 30 - g["w"]).max()) < 1e-4
    ratio = gc.wire_bytes(g, compressed=True) / gc.wire_bytes(g, compressed=False)
    assert ratio < 0.3


def test_int8_kv_cache_matches_bf16():
    """H3 optimization: int8 KV cache decode matches bf16-cache decode."""
    from repro.models import kv
    cfg = registry.reduced(registry.get("qwen3-1.7b"))
    mod = api.build(cfg)
    params = mod.init(cfg, jax.random.PRNGKey(0))
    B, S = 2, 24
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
    logits, c16 = jax.jit(lambda p, t: mod.prefill(cfg, p, t, max_len=S + 4))(
        params, toks)
    kq, ks = kv.quantize_kv(c16["k"])
    vq, vs = kv.quantize_kv(c16["v"])
    c8 = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
    step = jax.jit(lambda p, t, c: mod.decode_step(cfg, p, t, c, S))
    l16, _ = step(params, tok, c16)
    l8, n8 = step(params, tok, c8)
    a, b = np.asarray(l16, np.float32), np.asarray(l8, np.float32)
    assert np.abs(a - b).max() < 0.5
    assert (a.argmax(-1) == b.argmax(-1)).all()
    assert n8["k"].dtype == jnp.int8          # cache stays quantized


def test_ste_compressed_gather_training_converges():
    """H2 machinery: QTG straight-through training at 8/4-bit weight gathers
    tracks the fp32 loss trajectory."""
    finals = {}
    for bits in (0, 8, 4):
        from repro.data.pipeline import DataConfig, SyntheticSource
        from repro.training import optimizer as opt, train_loop
        cfg = registry.reduced(registry.get("qwen3-1.7b"))
        mod = api.build(cfg)
        params = mod.init(cfg, jax.random.PRNGKey(0))
        tc = train_loop.TrainConfig(
            opt=opt.AdamWConfig(schedule=opt.Schedule(
                base_lr=1e-3, warmup_steps=2, total_steps=12)),
            q8_gather=bits)
        state = opt.init_state(tc.opt, params)
        src = SyntheticSource(DataConfig(vocab=cfg.vocab, seq_len=64,
                                         global_batch=8, seed=0))
        _, _, info = train_loop.train(cfg, tc, params, state, iter(src), 12)
        finals[bits] = info["history"][-1]["loss"]
        assert finals[bits] < info["history"][0]["loss"] - 0.05
    assert abs(finals[8] - finals[0]) < 0.1
    assert abs(finals[4] - finals[0]) < 0.2


def test_int4_packed_serving_matches_unpacked():
    """4-bit containers load as packed QT4 (0.5 B/param resident) and serve
    the same logits as the unpacked QT path."""
    from repro.core.quant import Granularity
    from repro.core.store import CompressedModel
    from repro.serving import engine
    from repro.models.layers import QT4
    cfg = registry.reduced(registry.get("glm4-9b"))
    mod = api.build(cfg)
    params = mod.init(cfg, jax.random.PRNGKey(0))
    host = {k: np.asarray(v, np.float32) for k, v in params.items()}
    cm = CompressedModel.compress(host, bits=4,
                                  granularity=Granularity.PER_CHANNEL)
    packed = engine.load_params_from_compressed(cm, quantized=True)
    unpacked = engine.load_params_from_compressed(cm, quantized=True,
                                                  pack_int4=False)
    assert any(isinstance(v, QT4) for v in packed.values())
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0, cfg.vocab)
    lp, _ = jax.jit(lambda p, t: mod.prefill(cfg, p, t, max_len=16))(packed, toks)
    lu, _ = jax.jit(lambda p, t: mod.prefill(cfg, p, t, max_len=16))(unpacked, toks)
    np.testing.assert_allclose(np.asarray(lp, np.float32),
                               np.asarray(lu, np.float32), atol=1e-2)
