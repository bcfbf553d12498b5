"""Run the serving system's main path once on a TPU, and check what it says.

Compress -> decode -> serve Qwen3-1.7B at every published width (d_model
2048, 16 heads / 8 KV heads, head_dim 128, d_ff 6144, vocab 151,936), depth
cut to 4 of 28 layers, seeded random weights, through the launcher's own
steps (``repro.launch.serve``) in one process.  One container, compressed
once with the launcher's 8-bit Huffman spec, serves every phase:

  A. dense-resident streamed load through the ``auto`` decoder backend;
     every quantized tensor's decoded symbols must equal the host
     quantizer's symbols exactly.
  B. continuous batching (``--batch-slots``): 8 slots, 16 seeded Poisson
     requests, prompts up to 128 tokens, up to 32 generated; every request
     must finish with every token id inside the vocabulary.
  C. compressed-resident fused path (``--resident compressed --fused``),
     lockstep batch 4, prompt 64, gen 16; its prefill logits must agree
     with phase A's dense-resident engine on the same prompts within
     ``LOGIT_TOL_SHARE``.

``--chips 4`` runs only the fleet and what it is compared with: 4 replicas
from one container, one per device, under the threaded drive; every
request's tokens must equal those of a single engine on device 0.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips, fleet only

Before any work it exits non-zero unless JAX's first device is a TPU.  The
tokens/s lines are smoke figures (compiles included), not a benchmark.  On
success the last line of standard output is one JSON object naming the
device.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "qwen3-1.7b"
LAYERS = 4                      # one whole layer period is 1; 4 of 28 kept
BASE_ARGV = ["--arch", ARCH, "--published-layers", str(LAYERS)]
# one decode call per 1024 segments of 4096 symbols: at full width the
# launcher's defaults (512k symbols dense, 64k compressed-resident) would
# split each tensor into hundreds of lane-starved calls
CHUNK_ARGV = ["--chunk-symbols", str(1 << 22)]
TRAFFIC_ARGV = ["--batch-slots", "8", "--traffic", "16", "--prompt-len", "128",
                "--gen", "32"]
# Phase C tolerance on max |fused - dense| logit (prefill and the first
# decode step), as a share of the dense logits' largest magnitude.  Both paths run the same bf16 ops on the
# same decoded symbols (the jit fused impl replays layers.deq exactly), so on
# the CPU they agree bit for bit; on the chip XLA compiles the whole-model
# scan and the per-layer programs with different fusions, which moves bf16
# rounding points (2^-8 relative each) through 4 residual layers and a
# 2048-deep lm-head dot.  That stays within a few percent of the logit
# scale; a wrong weight, layer or cache row moves logits by their own size.
LOGIT_TOL_SHARE = 0.05


class CompileLog:
    """XLA compile time and persistent-cache hits, from JAX's own
    monitoring events (the compile event spans the cache lookup, so a warm
    persistent cache shows as less compile time)."""

    def __init__(self):
        self.secs = 0.0
        self.n = 0
        self.first = None
        self.cache_hits = 0

    def on_duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.n += 1
            if self.first is None:
                self.first = secs

    def on_event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def require_tpu(chips: int):
    """Fail before any work unless JAX's first device is a TPU (and there
    are ``chips`` of them); print the device on an early line."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX's first device is {d.platform!r} "
                         f"({d.device_kind}), not a TPU; nothing was run")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} TPU "
                         f"devices, JAX sees {len(devs)}")
    print(f"device: {d.device_kind} x{len(devs)} (platform {d.platform})",
          flush=True)
    return devs


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {msg}")


def phase_a_symbols(cm, host, params) -> None:
    """Every quantized tensor's resident symbols == the host quantizer's."""
    import numpy as np
    from repro.core import quant
    from repro.models.layers import QT
    n_sym = 0
    for name in cm.qmeta:
        pol = cm.spec.resolve(name, host[name])
        qt = pol.qt if pol.qt is not None else quant.quantize(
            host[name], pol.bits, pol.granularity, group=pol.group,
            scheme=pol.scheme, name=name)
        got = params[name]
        check(isinstance(got, QT), f"{name} is resident as "
              f"{type(got).__name__}, not a QT symbol triple")
        same = bool((qt.q == np.asarray(got.q)).all())
        check(same, f"{name}: decoded symbols differ from the host "
              f"quantizer's")
        n_sym += qt.q.size
    print(f"phase A ok: decoded symbols equal the host quantizer's for "
          f"{len(cm.qmeta)}/{len(cm.qmeta)} quantized tensors "
          f"({n_sym} symbols)", flush=True)


def finished_in_order(reqs, n: int, cfg, what: str):
    """All ``n`` requests finished their budget with in-vocabulary ids."""
    check(len(reqs) == n, f"{what}: {len(reqs)}/{n} requests finished")
    for r in reqs:
        check(r.finish_reason == "length"
              and len(r.output) == r.max_new_tokens,
              f"{what}: request {r.rid} ended {r.finish_reason!r} after "
              f"{len(r.output)}/{r.max_new_tokens} tokens")
        check(all(0 <= t < cfg.vocab for t in r.output),
              f"{what}: request {r.rid} emitted an id outside "
              f"[0, {cfg.vocab})")
    return sorted(reqs, key=lambda r: r.rid)


def phase_b(serve, cfg, params, load_metrics) -> None:
    args = serve.parse_args(BASE_ARGV + TRAFFIC_ARGV)
    sc, plen = serve.serve_config(cfg, args)
    t0 = time.perf_counter()
    ce = serve.serve_continuous(cfg, params, sc, args, load_metrics,
                                kv_prefix_len=plen)
    span = time.perf_counter() - t0
    fin = finished_in_order(ce.finished, args.traffic, cfg, "phase B")
    toks = sum(len(r.output) for r in fin)
    print(f"phase B ok: {len(fin)}/{args.traffic} requests finished, {toks} "
          f"tokens, every id in [0, {cfg.vocab}); smoke figure "
          f"{toks / span:.1f} tok/s (compiles included, not a benchmark)",
          flush=True)


def phase_c(serve, cfg, cm, dense_params) -> None:
    import jax.numpy as jnp
    import numpy as np
    from repro.serving import engine
    args = serve.parse_args(BASE_ARGV + CHUNK_ARGV + [
        "--resident", "compressed", "--fused", "--batch", "4",
        "--prompt-len", "64", "--gen", "16"])
    params, load_metrics = serve.load(cm, cfg, args)
    impls = sorted({fq.impl for slots in params._fused_slots
                    for fq in slots.values()})
    print(f"phase C: fused impl {impls}; fused {sorted(params._fused)}, "
          f"fallback {sorted(params.fused_fallback)}", flush=True)
    sc, _ = serve.serve_config(cfg, args)
    t0 = time.perf_counter()
    eng, prompt, out = serve.serve_lockstep(cfg, params, sc, args,
                                            load_metrics)
    span = time.perf_counter() - t0
    dense = engine.Engine(cfg, dense_params, sc)
    want = dense.generate(prompt, args.gen)
    got_logits, got_cache = eng.steps.prefill_fn(params, prompt)
    want_logits, want_cache = dense.steps.prefill_fn(dense_params, prompt)
    # one decode step from each engine's own prefill cache, fed the same
    # token, so the cached path is held to the same tolerance
    tok = jnp.argmax(want_logits[:, -1, :cfg.vocab], axis=-1
                     ).astype(jnp.int32)[:, None]
    pos = jnp.int32(prompt.shape[1])
    got_step, _ = eng.steps.decode_fn(params, tok, got_cache, pos)
    want_step, _ = dense.steps.decode_fn(dense_params, tok, want_cache, pos)
    for what, g, w in (("prefill", got_logits, want_logits),
                       ("first decode step", got_step, want_step)):
        got = np.asarray(g[..., :cfg.vocab], np.float32)
        ref = np.asarray(w[..., :cfg.vocab], np.float32)
        diff = float(np.abs(got - ref).max())
        scale = float(np.abs(ref).max())
        print(f"phase C: {what} logits max |fused - dense| {diff} against "
              f"tolerance {LOGIT_TOL_SHARE} x max |dense| {scale} = "
              f"{LOGIT_TOL_SHARE * scale}", flush=True)
        check(np.isfinite(got).all(), f"phase C: non-finite {what} logits")
        check(diff <= LOGIT_TOL_SHARE * scale,
              f"phase C: fused {what} logits outside tolerance")
    agree = np.asarray(out) == np.asarray(want)
    first = [int(np.argmin(row)) if not row.all() else None for row in agree]
    print(f"phase C: greedy tokens agree on {int(agree.sum())}/{agree.size} "
          f"(first differing step per row: {first})", flush=True)
    toks = int(out.size)
    print(f"phase C ok: smoke figure {toks / span:.1f} tok/s (compiles "
          f"included, not a benchmark)", flush=True)


def fleet(serve, cfg, params, load_metrics, devices) -> None:
    """4 replicas (one per device, threaded) vs a single engine."""
    import jax
    ref_args = serve.parse_args(BASE_ARGV + TRAFFIC_ARGV)
    args = serve.parse_args(BASE_ARGV + TRAFFIC_ARGV + ["--replicas", "4"])
    sc, plen = serve.serve_config(cfg, args)
    ce = serve.serve_continuous(cfg, params, sc, ref_args, load_metrics,
                                kv_prefix_len=plen)
    ref = finished_in_order(ce.finished, args.traffic, cfg, "single engine")
    fd = serve.serve_fleet(cfg, params, sc, args, load_metrics,
                           kv_prefix_len=plen, threaded=True)
    got = finished_in_order(fd.finished, args.traffic, cfg, "fleet")
    placed = [h.device for h in fd.replicas]
    check(len({d.id for d in placed}) == 4
          and set(placed) <= set(devices[:4]),
          f"fleet replicas not one per device: {placed}")
    for h in fd.replicas:
        on = {d for leaf in jax.tree.leaves(h.engine.params)
              for d in leaf.devices()}
        check(on == {h.device}, f"replica r{h.idx} weights on {on}, "
              f"not {h.device}")
    for a, b in zip(ref, got):
        check(a.output == b.output, f"fleet request {b.rid} tokens differ "
              f"from the single engine's")
    print(f"fleet ok: replicas on {[str(d) for d in placed]}; "
          f"{len(got)}/{args.traffic} requests, tokens identical to the "
          f"single engine on {devices[0]}", flush=True)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: run only the fleet path across four chips")
    opts = p.parse_args(argv)
    return run(opts.chips, require_tpu(opts.chips))


def run(chips: int, devices) -> int:
    """Every phase for ``chips`` (1: A, B, C; 4: the fleet), after the
    device check."""
    import jax
    from jax import monitoring
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch import serve
    from repro.launch.compile_cache import setup_compile_cache
    print(f"compile cache: {setup_compile_cache()}", flush=True)
    compiles = CompileLog()
    monitoring.register_event_duration_secs_listener(compiles.on_duration)
    monitoring.register_event_listener(compiles.on_event)

    t_start = time.perf_counter()
    load_args = serve.parse_args(BASE_ARGV + CHUNK_ARGV)
    cfg = serve.model_config(load_args)
    t0 = time.perf_counter()
    host = serve.build(cfg)
    t_init = time.perf_counter() - t0
    # one container for every phase: the launcher's 8-bit Huffman spec as
    # it stands for --resident compressed (4096-symbol segments, so phase C
    # gets many-laned per-layer decodes); dense residency decodes it too
    spec = serve.parse_args(BASE_ARGV + ["--resident", "compressed"]
                            ).compress_spec_parsed
    t0 = time.perf_counter()
    cm = serve.compress(host, spec)
    t_comp = time.perf_counter() - t0
    t0 = time.perf_counter()
    params, load_metrics = serve.load(cm, cfg, load_args)
    t_load = time.perf_counter() - t0
    print(f"set-up: init {t_init:.1f}s, compress {t_comp:.1f}s, load "
          f"{t_load:.1f}s (decoder {load_metrics['decode_backend']}), first "
          f"compile {compiles.first or 0.0:.2f}s", flush=True)

    if chips == 4:
        fleet(serve, cfg, params, load_metrics, devices)
    else:
        phase_a_symbols(cm, host, params)
        del host
        phase_b(serve, cfg, params, load_metrics)
        phase_c(serve, cfg, cm, params)

    stats = devices[0].memory_stats() or {}
    print(f"compile: {compiles.secs:.1f}s in {compiles.n} XLA compiles, "
          f"{compiles.cache_hits} persistent-cache hits; wall "
          f"{time.perf_counter() - t_start:.1f}s; device 0 peak_bytes_in_use "
          f"{stats.get('peak_bytes_in_use', 'not reported')}", flush=True)
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
