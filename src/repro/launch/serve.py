"""Serving launcher: EntroLLM end-to-end on this host.

Pipeline: init weights -> mixed-quantize + entropy-encode into the
compressed container (``--codec`` picks the coder; ``--compress-spec`` sets
per-tensor bits / codec / fp32 rules — see :mod:`repro.core.spec`) ->
*streaming* parallel decode (chunked, double-buffered prefetch through a
named decoder backend) -> serve with quantized (QT) weights resident,
dequant fused into matmuls.

``--resident compressed`` skips the load-time decode entirely: the
entropy-coded container stays resident and each layer is decoded just
before its matmuls, double-buffered against the previous layer's compute
(the paper's §IV serving scenario; docs/SERVING.md §"Compressed-resident
serving").  Greedy outputs are bit-identical to the default
``--resident dense`` engine; the launcher reports peak resident weight
bytes for both so the bandwidth-vs-compute tradeoff is visible.

Two serving modes:

* lockstep (default) — one fixed-shape batch through ``Engine.generate``:

      PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b \
          --bits 8 --batch 4 --prompt-len 32 --gen 16

* continuous batching (``--batch-slots N``) — a slot-batched
  ``ContinuousEngine`` serves ``--traffic R`` independently-arriving
  synthetic requests (Poisson replay; ragged prompts and gen lengths),
  reporting queue wait / TTFT / latency percentiles:

      PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b \
          --bits 8 --batch-slots 8 --traffic 16 --gen 16

By default the launcher serves the registry's reduced smoke config
(d_model 128, vocab 512).  ``--published-layers N`` serves the published
config instead, every width as published and depth cut to N whole layers
(``chip_smoke.py`` drives Qwen3-1.7B this way on one chip):

      PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b \
          --published-layers 4 --batch 4 --prompt-len 64 --gen 16

``main`` runs the steps ``model_config`` -> ``build`` -> ``compress`` ->
``load`` -> ``serve_*``, which ``chip_smoke.py`` calls one by one to serve
several phases from one container.

``--production`` lowers the full-config serve_step on the production mesh
instead (same path as the dry-run decode cells).
"""
import argparse
import os
import sys
import time


def parse_args(argv=None) -> argparse.Namespace:
    """Parse and validate the launcher's flags.  Every incompatible flag
    combination fails here, before any expensive work; the parsed
    ``compress_spec`` / ``kv_spec`` / ``mesh_dims`` / ``disaggregate_split``
    ride on the returned namespace."""
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", required=True)
    p.add_argument("--published-layers", type=int, default=0, metavar="N",
                   help="serve the registry's published config at every "
                        "published width, cut to N whole layers (default 0: "
                        "the reduced smoke config, d_model 128, vocab 512)")
    p.add_argument("--bits", type=int, default=8,
                   help="uniform quantization bit-width, 1..8 (subsumed by "
                        "--compress-spec)")
    p.add_argument("--codec", default="huffman",
                   help="entropy codec for the whole model (huffman / rans / "
                        "raw); subsumed by --compress-spec")
    p.add_argument("--compress-spec", default=None, metavar="SPEC",
                   help="per-tensor compression rules, e.g. "
                        "'*norm*:fp32;layers/*:bits=4,codec=rans;*:bits=8' "
                        "(see repro.core.spec); overrides --bits/--codec")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=32)
    p.add_argument("--gen", type=int, default=16)
    p.add_argument("--no-quantized-serving", action="store_true",
                   help="dequantize to dense fp32 at load (baseline mode)")
    p.add_argument("--resident", choices=("dense", "compressed"),
                   default="dense",
                   help="weight residency: 'dense' decodes the container "
                        "into HBM-resident QT params at load; 'compressed' "
                        "keeps the entropy-coded payload resident and "
                        "decodes each layer just before its matmuls "
                        "(bit-identical greedy outputs; see docs/SERVING.md)")
    p.add_argument("--fused", action="store_true",
                   help="with --resident compressed: hand tile-aligned "
                        "tensors to the fused decode→dequant→matmul kernel "
                        "as payload handles (weights never materialize "
                        "densely in HBM); incompatible tensors fall back "
                        "per-tensor to the per-layer decode path")
    p.add_argument("--fused-impl", default=None,
                   choices=("pallas", "jax", "pallas-interpret"),
                   help="fused kernel implementation override (default: "
                        "capability pick — compiled Pallas where it probes, "
                        "the jit in-graph decode elsewhere)")
    p.add_argument("--decode-backend", default=None,
                   help="decoder backend name (numpy / jax / pallas / "
                        "pallas-interpret); default: capability auto-pick")
    p.add_argument("--chunk-symbols", type=int, default=None,
                   help="streaming decode chunk budget in symbols "
                        "(default: scheduler per-layer budget)")
    p.add_argument("--no-stream", action="store_true",
                   help="monolithic decode_all load (pre-streaming path)")
    p.add_argument("--batch-slots", type=int, default=0, metavar="N",
                   help="serve with an N-slot continuous-batching engine "
                        "instead of one lockstep batch (0 = lockstep)")
    p.add_argument("--max-queue", type=int, default=64,
                   help="admission-queue bound for --batch-slots")
    p.add_argument("--prefill-chunk", type=int, default=16,
                   help="chunked-prefill step for --batch-slots")
    p.add_argument("--traffic", type=int, default=0, metavar="R",
                   help="with --batch-slots: replay R synthetic Poisson "
                        "arrivals (ragged prompts/gen) instead of one "
                        "uniform request wave")
    p.add_argument("--kv-spec", default=None, metavar="SPEC",
                   help="with --batch-slots: serve through the paged KV "
                        "block pool under this compression policy, e.g. "
                        "'bits=4,block=16,codec=rans,sharing' (see "
                        "repro.core.spec.KVCompressionSpec; bits=16 keeps "
                        "dense bf16 blocks, bit-identical to the slot pool; "
                        "docs/KV_CACHE.md)")
    p.add_argument("--kv-block", type=int, default=0, metavar="B",
                   help="override the paged KV block size (tokens per "
                        "block); implies --kv-spec when given alone")
    p.add_argument("--prefix-sharing", action="store_true",
                   help="share identical prompt-prefix KV blocks across "
                        "requests (copy-on-write publish of full prompt "
                        "blocks; implies --kv-spec when given alone, and "
                        "makes --traffic replay shared system prompts)")
    p.add_argument("--replicas", type=int, default=0, metavar="N",
                   help="with --batch-slots: serve through a fleet of N "
                        "data-parallel continuous-batching replicas behind "
                        "a request router (one shared weight tree, lockstep "
                        "drive; docs/FLEET.md)")
    p.add_argument("--router", default="round-robin",
                   choices=("round-robin", "least-loaded"),
                   help="fleet placement policy for --replicas "
                        "(docs/FLEET.md)")
    p.add_argument("--disaggregate", default=None, metavar="P:D",
                   help="with --replicas: split the fleet into P prefill "
                        "replicas + D decode replicas (P+D = N); finished "
                        "prompt KV ships prefill->decode as entropy-coded "
                        "block payloads, so requires --kv-spec")
    p.add_argument("--mesh", default=None, metavar="DxM",
                   help="serve on a (data, model) device mesh, e.g. 2x4: "
                        "weights tensor-parallel over model (QT q/scale/zero "
                        "sharded consistently along output channels), KV "
                        "cache batch/slot-sharded over data, placement at "
                        "load-stream time; needs data*model local devices "
                        "(CPU hosts: XLA_FLAGS=--xla_force_host_platform_"
                        "device_count=N)")
    p.add_argument("--trace-out", default=None, metavar="FILE",
                   help="write a Chrome/Perfetto trace_event JSON of the "
                        "serve (load + prefill + decode spans; open in "
                        "ui.perfetto.dev or chrome://tracing, analyze with "
                        "benchmarks/overlap_report.py; docs/OBSERVABILITY.md)")
    p.add_argument("--metrics-out", default=None, metavar="FILE",
                   help="write the metrics-registry snapshot as JSON lines "
                        "(counters/gauges/histograms + per-request "
                        "lifecycles; docs/OBSERVABILITY.md has the catalog)")
    p.add_argument("--trace-sync", action="store_true",
                   help="fence (block_until_ready) inside spans so durations "
                        "measure device compute, not jax async dispatch — "
                        "perturbs pipelining, so timings are faithful but "
                        "throughput is not; outputs stay bit-identical")
    p.add_argument("--production", action="store_true")
    p.add_argument("--shape", default="decode_32k")
    p.add_argument("--multi-pod", action="store_true")
    args = p.parse_args(argv)

    args.mesh_dims = None
    if args.mesh:
        from repro.launch.mesh import parse_mesh_spec
        try:
            args.mesh_dims = parse_mesh_spec(args.mesh)
        except ValueError as e:
            p.error(f"--mesh: {e}")

    if args.published_layers:
        from repro.configs import registry
        try:
            registry.published(registry.get(args.arch), args.published_layers)
        except (KeyError, ValueError) as e:
            p.error(f"--published-layers: {e}")

    if args.resident == "compressed":
        # same upfront-validation contract as the other flags: incompatible
        # mode combinations fail here with the documented alternative
        if args.mesh:
            p.error("--resident compressed is single-device (per-layer "
                    "decode targets the bandwidth-bound single-accelerator "
                    "regime); drop --mesh or use --resident dense")
        if args.no_quantized_serving:
            p.error("--resident compressed always serves QT weights "
                    "(the fused-dequant path hosts the per-layer slots); "
                    "drop --no-quantized-serving")
        if args.no_stream:
            p.error("--no-stream only applies to the load-time decode of "
                    "--resident dense")
    elif args.fused or args.fused_impl:
        p.error("--fused/--fused-impl require --resident compressed (the "
                "fused kernel consumes the entropy-coded payload handles "
                "that mode keeps resident)")

    # paged KV: parse + validate the policy upfront (same contract as
    # --compress-spec); the paged pool rides dense residency, single device
    kv_spec = args.kv_spec_parsed = None
    if args.kv_spec is not None or args.kv_block or args.prefix_sharing:
        if args.batch_slots <= 0:
            p.error("--kv-spec/--kv-block/--prefix-sharing require "
                    "--batch-slots (the paged KV cache is a "
                    "continuous-batching feature; docs/KV_CACHE.md)")
        if args.resident != "dense":
            p.error("paged KV (--kv-spec) needs --resident dense: paged "
                    "x compressed residency is not enabled yet")
        if args.mesh:
            p.error("paged KV (--kv-spec) is single-device today; drop "
                    "--mesh")
        from repro.core.spec import KVCompressionSpec
        overrides = {}
        if args.kv_block:
            overrides["block_size"] = args.kv_block
        if args.prefix_sharing:
            overrides["sharing"] = True
        try:
            kv_spec = KVCompressionSpec.parse(args.kv_spec or "", **overrides)
        except (ValueError, KeyError) as e:
            p.error(f"bad --kv-spec: {e}")
        args.kv_spec_parsed = kv_spec
        if kv_spec.sharing and args.prefill_chunk % kv_spec.block_size:
            p.error(f"--prefix-sharing needs --prefill-chunk divisible by "
                    f"the KV block size (chunk {args.prefill_chunk}, block "
                    f"{kv_spec.block_size}): the prefix-skip boundary must "
                    f"be a chunk boundary")

    # fleet flags: same upfront-validation contract (docs/FLEET.md); the
    # parsed P:D split rides on args so _serve_fleet sees a tuple, not text
    args.disaggregate_split = None
    if args.disaggregate and args.replicas <= 0:
        p.error("--disaggregate requires --replicas")
    if args.replicas:
        if args.replicas < 1:
            p.error(f"--replicas must be >= 1, got {args.replicas}")
        if args.batch_slots <= 0:
            p.error("--replicas requires --batch-slots (fleet replicas are "
                    "continuous-batching engines; docs/FLEET.md)")
        if args.mesh:
            p.error("--replicas is data parallelism over single-device "
                    "engines; the mesh layer shards ONE engine — drop "
                    "--mesh or serve a single replica")
        if args.resident != "dense":
            p.error("--replicas needs --resident dense: the per-layer "
                    "compressed-resident drivers are single-engine today")
        if args.disaggregate:
            try:
                n_pre, n_dec = (int(x) for x in args.disaggregate.split(":"))
            except ValueError:
                p.error(f"bad --disaggregate {args.disaggregate!r}: "
                        f"want P:D, e.g. 1:1")
            if n_pre < 1 or n_dec < 1:
                p.error("--disaggregate needs at least one prefill and one "
                        "decode replica")
            if n_pre + n_dec != args.replicas:
                p.error(f"--disaggregate {args.disaggregate} must sum to "
                        f"--replicas ({args.replicas})")
            if kv_spec is None:
                p.error("--disaggregate requires --kv-spec: the prefill->"
                        "decode handoff ships paged KV blocks entropy-coded "
                        "on the wire (docs/FLEET.md)")
            args.disaggregate_split = (n_pre, n_dec)

    # validate the backend against the registry BEFORE any expensive work, so
    # a typo fails with the list of choices, not a deep KeyError mid-load
    if args.decode_backend is not None and args.decode_backend != "auto":
        from repro.core.decode_backends import (available_backends,
                                                backend_names)
        if args.decode_backend not in backend_names():
            p.error(f"unknown decoder backend {args.decode_backend!r}; "
                    f"registered: {backend_names()}, "
                    f"available on this host: {available_backends()}")
        if args.decode_backend not in available_backends():
            p.error(f"decoder backend {args.decode_backend!r} is not "
                    f"available on this host; available: "
                    f"{available_backends()}")

    # same contract for the encode side: spec / codec names fail upfront
    # against the codec registry, not deep inside compress()
    from repro.core.codecs import codec_names
    from repro.core.quant import Granularity
    from repro.core.spec import CompressionSpec, spec_from_legacy
    if args.compress_spec is not None:
        try:
            # same PER_CHANNEL default as the --bits path: serving scale
            # shapes assume per-leading-index (s, z) on layer-stacked tensors
            compress_spec = CompressionSpec.parse(
                args.compress_spec,
                default_granularity=Granularity.PER_CHANNEL)
        except (ValueError, KeyError) as e:
            p.error(f"bad --compress-spec: {e}")
    else:
        if args.codec not in codec_names():
            p.error(f"unknown codec {args.codec!r}; "
                    f"registered: {codec_names()}")
        if not 1 <= args.bits <= 8:
            p.error(f"--bits must be in [1, 8], got {args.bits}")
        # PER_CHANNEL = one (s, z) per leading index — for layer-stacked
        # tensors that is exactly the paper's per-LAYER mixed scheme (Alg. 1
        # line 5), and scanned layers need the leading scale dim to match
        legacy_kw = {}
        if args.resident == "compressed":
            # per-layer decode parallelism is chunk/segment lanes, so the
            # storage-default 64k segments would lane-starve small layers;
            # finer segments keep every layer many-laned (SERVING.md
            # §"Tuning: segments, chunks, lanes").  An explicit
            # --compress-spec (defaults:segment_symbols=...) overrides.
            legacy_kw["segment_symbols"] = 4096
        compress_spec = spec_from_legacy(args.bits, Granularity.PER_CHANNEL,
                                         codec=args.codec, **legacy_kw)
    args.compress_spec_parsed = compress_spec
    return args


def main(argv=None):
    args = parse_args(argv)
    if args.production:
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
        from repro.launch import dryrun
        d = dryrun.run_cell(args.arch, args.shape, multi_pod=args.multi_pod)
        return 0 if "error" not in d else 1

    from repro.launch.compile_cache import setup_compile_cache
    from repro.obs import trace as obs_trace
    setup_compile_cache()
    if args.trace_out or args.trace_sync:
        obs_trace.enable(sync=args.trace_sync)

    cfg = model_config(args)
    mesh = rules = None
    if args.mesh_dims is not None:
        from repro.launch import mesh as mesh_lib
        from repro.serving import engine
        try:
            mesh = mesh_lib.make_serve_mesh(*args.mesh_dims)
        except ValueError as e:
            print(f"serve: error: {e}", file=sys.stderr)
            return 2
        rules = engine.serve_mesh_rules(cfg, mesh)
    cm = compress(build(cfg), args.compress_spec_parsed)
    serve_params, load_metrics = load(cm, cfg, args, mesh=mesh)
    sc, kv_prefix_len = serve_config(cfg, args)

    rc = 0
    if args.batch_slots > 0 and args.replicas > 0:
        fd = serve_fleet(cfg, serve_params, sc, args, load_metrics,
                         kv_prefix_len=kv_prefix_len)
        rc = 0 if fd.finished else 1
    elif args.batch_slots > 0:
        ce = serve_continuous(cfg, serve_params, sc, args, load_metrics,
                              mesh=mesh, rules=rules,
                              kv_prefix_len=kv_prefix_len)
        rc = 0 if ce.finished else 1
    else:
        serve_lockstep(cfg, serve_params, sc, args, load_metrics, mesh=mesh,
                       rules=rules)
    _write_obs(args)
    return rc


def model_config(args):
    """The config ``args`` serves: the reduced smoke variant by default,
    the published widths cut to ``--published-layers`` layers otherwise
    (the cut is printed)."""
    from repro.configs import registry
    base = registry.get(args.arch)
    if not args.published_layers:
        return registry.reduced(base)
    cfg = registry.published(base, args.published_layers)
    print(f"{base.name} at published widths: {cfg.n_layers} of "
          f"{base.n_layers} layers kept (d_model {cfg.d_model}, "
          f"{cfg.n_heads} heads / {cfg.n_kv_heads} KV heads, head_dim "
          f"{cfg.hd}, d_ff {cfg.d_ff}, vocab {cfg.vocab})")
    return cfg


def build(cfg, seed: int = 0):
    """Seeded random weights for ``cfg``: a host float32 copy of every
    parameter, the input :func:`compress` takes."""
    import jax
    import numpy as np
    from repro.models import api
    params = api.build(cfg).init(cfg, jax.random.PRNGKey(seed))
    return {k: np.asarray(v, np.float32) for k, v in params.items()}


def compress(host, spec):
    """Quantize + entropy-code ``host`` under ``spec``; prints the stats."""
    from repro.core.store import CompressedModel
    t0 = time.perf_counter()
    cm = CompressedModel.compress(host, spec=spec)
    t_comp = time.perf_counter() - t0
    st = cm.stats()
    print(f"compressed {st.param_count/1e6:.1f}M params: "
          f"{st.bits:.3g}b quant -> {st.effective_bits:.2f} effective bits "
          f"(entropy {st.entropy_bits:.2f}); "
          f"{st.reduction_vs_quant*100:.1f}% below quantized, "
          f"{st.reduction_vs_fp16*100:.1f}% below fp16  [{t_comp:.1f}s]")
    for g in st.groups:
        print(f"  [{g.table_id}] {g.param_count/1e6:.2f}M params: "
              f"{g.bits}b {g.codec} -> {g.effective_bits:.2f} achieved bits "
              f"(bound {g.entropy_bits:.2f}, {g.shannon_ratio:.3f}x)")
    return cm


def load(cm, cfg, args, mesh=None):
    """Load ``cm`` into serving weights the way ``args`` asks (dense or
    compressed residency, streamed or monolithic, optionally placed on
    ``mesh``); prints the decoder pick and the load.  Returns
    ``(serve_params, load_metrics)``."""
    from repro.core.decode_backends import auto_pick_reason
    from repro.serving import engine
    if args.decode_backend in (None, "auto"):
        print(f"decoder backend auto: {auto_pick_reason()}")
    load_metrics = {}
    load_kw = {}
    if args.chunk_symbols is not None:      # absent flag -> scheduler default
        load_kw["chunk_symbols"] = args.chunk_symbols
    if args.resident == "compressed":
        from repro.serving.resident import CompressedResidentWeights
        # absent --chunk-symbols: a tighter budget than the storage-default
        # 512k — the int32 scratch is part of the resident peak, and on the
        # reduced configs this launcher serves, the storage default alone
        # would push peak past the dense bf16 footprint (SERVING.md
        # §"Tuning: segments, chunks, lanes"; explicit flag overrides)
        load_kw.setdefault("chunk_symbols", 64 * 1024)
        t0 = time.perf_counter()
        serve_params = CompressedResidentWeights(
            cm, cfg, backend=args.decode_backend, fused=args.fused,
            fused_impl=args.fused_impl, **load_kw)
        load_metrics["decode_load_s"] = time.perf_counter() - t0
        load_metrics["decode_backend"] = serve_params.backend.name
        if args.fused:
            impls = sorted({fq.impl for slots in serve_params._fused_slots
                            for fq in slots.values()})
            if args.fused_impl is None:
                from repro.kernels.fused_decode_matmul import \
                    default_fused_impl
                print(f"  fused impl auto: {default_fused_impl()} (pallas "
                      f"only on a TPU where Mosaic lowers the fused kernel, "
                      f"decode_backends.PALLAS_DECODE_LOWERS)")
            print(f"  fused decode→dequant→matmul: "
                  f"{len(serve_params._fused)} tensors "
                  f"{sorted(serve_params._fused)} via {impls or ['-']}; "
                  f"{len(serve_params.fused_fallback)} fall back "
                  f"{serve_params.fused_fallback or ''}")
        rb = serve_params.resident_bytes()
        peak = serve_params.peak_resident_bytes()
        print(f"compressed-resident load [{load_metrics['decode_backend']}]: "
              f"{load_metrics['decode_load_s']:.2f}s (globals + carve-outs "
              f"decoded; {len(serve_params.plan)} layers stay entropy-coded)")
        print(f"  peak resident weights {peak/2**20:.2f} MiB "
              f"(payload {rb['payload']/2**20:.2f} + tables/qmeta "
              f"{(rb['tables']+rb['qmeta'])/2**20:.2f} + globals "
              f"{(rb['globals']+rb['stacked'])/2**20:.2f} + 2x layer slot "
              f"{rb['layer_slot']/2**20:.2f} + scratch "
              f"{rb['scratch']/2**20:.2f}) vs dense-resident QT "
              f"{serve_params.dense_resident_bytes()/2**20:.2f} MiB, "
              f"dense bf16 {serve_params.dense_bf16_bytes()/2**20:.2f} MiB")
    else:
        if mesh is not None:
            # default placer profile: per-tensor output-channel TP (exact
            # numerics); `rules` only steers cache/batch placement in engines
            load_kw["placer"] = engine.make_param_placer(cfg, mesh)
        serve_params = engine.load_params_from_compressed(
            cm, quantized=not args.no_quantized_serving,
            backend=args.decode_backend, stream=not args.no_stream,
            metrics=load_metrics, **load_kw)
        print(f"{'streamed' if not args.no_stream else 'monolithic'} decode + "
              f"load [{load_metrics['decode_backend']}]: "
              f"{load_metrics['decode_load_s']:.2f}s "
              f"(first weight resident after "
              f"{load_metrics['time_to_first_weight_s']*1e3:.0f}ms; "
              f"quantized residency: {not args.no_quantized_serving})")
    if mesh is not None:
        pb = engine.per_device_bytes(serve_params)
        lo, hi = min(pb.values()), max(pb.values())
        print(f"mesh {args.mesh_dims[0]}x{args.mesh_dims[1]} (data x model): "
              f"weights placed over {len(pb)} devices, "
              f"{lo/2**20:.1f}-{hi/2**20:.1f} MiB/device "
              f"({sum(pb.values())/2**20:.1f} MiB total)")
    return serve_params, load_metrics


def serve_config(cfg, args):
    """The ServeConfig ``args`` needs, and the shared-prefix length its
    traffic uses; prints the KV-cache footprint."""
    from repro.models import api
    from repro.serving import engine
    kv_spec = args.kv_spec_parsed
    # slot mode pads prompts to a prefill-chunk multiple, so its cache needs
    # that much headroom; the lockstep path keeps the exact footprint.
    # Prefix-shared traffic prepends a block-aligned system prompt, so the
    # prompt budget grows to cover prefix + at least one unique token.
    kv_prefix_len = 0
    if kv_spec is not None and kv_spec.sharing and args.traffic > 0:
        b = kv_spec.block_size
        kv_prefix_len = max(b, args.prompt_len // (2 * b) * b)
    prompt_budget = max(args.prompt_len, kv_prefix_len + 1)
    headroom = max(args.prefill_chunk, 0) if args.batch_slots > 0 else 0
    sc = engine.ServeConfig(max_len=prompt_budget + args.gen + headroom)

    # true serving peak is weights + KV — surface the KV term the weight
    # breakdowns above leave out (paged pool bytes print with the manager's
    # own numbers inside _serve_continuous)
    if kv_spec is None and hasattr(api.build(cfg), "init_cache"):
        from repro.serving.kvcache import kv_cache_bytes
        kv_rows = args.batch_slots if args.batch_slots > 0 else args.batch
        kvb = kv_cache_bytes(cfg, kv_rows, sc.max_len)
        print(f"  KV cache {kvb/2**20:.2f} MiB resident "
              f"({kv_rows} x {sc.max_len} bf16 rows) — true serving peak = "
              f"weights + KV")
    return sc, kv_prefix_len


def serve_lockstep(cfg, serve_params, sc, args, load_metrics, mesh=None,
                   rules=None):
    """Lockstep mode: one seeded batch through ``Engine.generate``.
    Returns ``(engine, prompt, tokens)``."""
    import jax.numpy as jnp
    import numpy as np
    from repro.serving import engine
    rng = np.random.default_rng(0)
    eng = engine.Engine(cfg, serve_params, sc, mesh=mesh, rules=rules,
                        resident=args.resident)
    if cfg.family == "encdec":
        prompt = {
            "tokens": jnp.asarray(rng.integers(0, cfg.vocab,
                                               (args.batch, args.prompt_len)),
                                  jnp.int32),
            "src_embeds": jnp.asarray(rng.normal(
                0, 1, (args.batch, args.prompt_len, cfg.d_model)),
                jnp.bfloat16),
        }
    else:
        prompt = jnp.asarray(rng.integers(0, cfg.vocab,
                                          (args.batch, args.prompt_len)),
                             jnp.int32)
    out, metrics = eng.generate(prompt, args.gen, echo_metrics=True)
    ttft = load_metrics["decode_load_s"] + metrics["ttft_s"]
    print(f"generated {out.shape} tokens: prefill {metrics['prefill_s']:.2f}s, "
          f"decode {metrics['decode_s']:.2f}s "
          f"({metrics['decode_tok_per_s']:.1f} decode tok/s, "
          f"{metrics['e2e_tok_per_s']:.1f} e2e tok/s); "
          f"time-to-first-token incl. weight load: {ttft:.2f}s")
    return eng, prompt, out


def _write_obs(args):
    """Export the trace / metrics-registry snapshot the serve recorded."""
    from repro.obs import metrics as obs_metrics
    from repro.obs import trace as obs_trace
    if args.trace_out or args.trace_sync:
        tracer = obs_trace.disable()
        if args.trace_out and tracer is not None:
            n = tracer.save(args.trace_out)
            print(f"trace: {n} events -> {args.trace_out} "
                  f"(open in ui.perfetto.dev; "
                  f"benchmarks/overlap_report.py analyzes it)")
    if args.metrics_out:
        n = obs_metrics.default_registry().write_jsonl(args.metrics_out)
        print(f"metrics: {n} rows -> {args.metrics_out}")


def serve_continuous(cfg, serve_params, sc, args, load_metrics, mesh=None,
                     rules=None, kv_prefix_len=0):
    """--batch-slots path: slot-batched serving of independent requests.
    Returns the :class:`ContinuousEngine` after it drained (its
    ``finished`` requests are the result)."""
    import numpy as np
    from repro.obs.metrics import percentile
    from repro.serving.batching import (ContinuousEngine, QueueFullError,
                                        poisson_trace, replay)

    kv_spec = args.kv_spec_parsed
    rng = np.random.default_rng(0)
    ce = ContinuousEngine(cfg, serve_params, sc, n_slots=args.batch_slots,
                          max_queue=args.max_queue,
                          prefill_chunk=args.prefill_chunk,
                          mesh=mesh, rules=rules, resident=args.resident,
                          kv_spec=kv_spec)
    if kv_spec is not None:
        print(f"  paged KV [{kv_spec.describe()}]: pool "
              f"{ce.slots.pool_bytes/2**20:.2f} MiB resident "
              f"({ce.slots.n_blocks} x {kv_spec.block_size}-token blocks) — "
              f"true serving peak = weights + KV")
    n = args.traffic if args.traffic > 0 else args.batch
    shed = 0
    t0 = time.monotonic()
    if args.traffic > 0:        # Poisson replay: ragged prompts + gen lengths
        prefix_kw = {}
        if kv_spec is not None and kv_spec.sharing:
            # shared system prompts exercise prefix sharing: 2 distinct
            # block-aligned prefixes, ragged unique suffixes
            prefix_kw = dict(prefix_pool=2, prefix_len=kv_prefix_len)
        trace = poisson_trace(n, rate_per_s=100.0, prompt_max=args.prompt_len,
                              gen_max=args.gen, vocab=cfg.vocab, seed=0,
                              **prefix_kw)
        _, shed, _ = replay(ce, trace, shed_on_full=True)
    else:                       # one wave of uniform requests
        for _ in range(n):
            prompt = rng.integers(0, cfg.vocab, (args.prompt_len,)
                                  ).astype(np.int32)
            while True:
                try:
                    ce.submit(prompt, args.gen)
                    break
                except QueueFullError:   # drain some work, then re-offer
                    ce.step()
        ce.run()
    span = time.monotonic() - t0
    fin = ce.finished
    if not fin:
        print(f"continuous batching: no requests completed "
              f"({shed} shed by backpressure)")
        return ce
    toks = sum(len(r.output) for r in fin)
    lat = [r.latency_s for r in fin]
    ttft = [r.ttft_s for r in fin]
    wait = [r.queue_wait_s for r in fin]
    print(f"continuous batching [{args.batch_slots} slots, queue bound "
          f"{args.max_queue}]: {len(fin)}/{n} requests"
          + (f" ({shed} shed by backpressure)" if shed else "")
          + f", {toks} tok in "
          f"{span:.2f}s = {toks/max(span, 1e-9):.1f} tok/s aggregate")
    print(f"  ttft p50 {percentile(ttft, 50)*1e3:.0f}ms "
          f"(+{load_metrics['decode_load_s']:.2f}s "
          f"weight load) | latency p50 {percentile(lat, 50)*1e3:.0f}ms "
          f"p99 {percentile(lat, 99)*1e3:.0f}ms | "
          f"{ce.n_decode_steps} fused decode steps")
    print(f"  queue wait [admitted] p50 {percentile(wait, 50)*1e3:.0f}ms "
          f"p99 {percentile(wait, 99)*1e3:.0f}ms over {len(fin)} requests"
          + (f"; {shed} shed before admission" if shed else ""))
    if kv_spec is not None:
        st = ce.slots.stats()
        print(f"  paged KV: prefix hit rate {st['prefix_hit_rate']*100:.0f}% "
              f"({st['shared_hits']} hits / {st['shared_misses']} misses), "
              f"{st['blocks_free']}/{st['blocks_total']} blocks free, cold "
              f"tier {st['cold_bytes']/2**10:.1f} KiB "
              f"({st['cold_evictions']} evictions, {st['cold_restores']} "
              f"restores, {st['dropped_evictions']} dropped)")
    return ce


def serve_fleet(cfg, serve_params, sc, args, load_metrics, kv_prefix_len=0,
                threaded=False):
    """--replicas path: DP fleet of continuous engines behind the router,
    one replica per local device (replicas wrap around when there are more
    replicas than devices).  Returns the :class:`FleetDriver` after it
    drained.

    The launcher drives in lockstep (docs/FLEET.md §"Drive modes") —
    deterministic and per-request bit-identical to a single engine;
    ``threaded=True`` gives each replica its own stepping thread.
    """
    import jax
    from repro.obs.metrics import percentile
    from repro.serving.batching import poisson_trace, replay_fleet
    from repro.serving.fleet import FleetDriver

    kv_spec = args.kv_spec_parsed
    split = args.disaggregate_split
    fd = FleetDriver(cfg, serve_params, sc, n_replicas=args.replicas,
                     policy=args.router, n_slots=args.batch_slots,
                     max_queue=args.max_queue,
                     prefill_chunk=args.prefill_chunk,
                     kv_spec=kv_spec, disaggregate=split,
                     devices=jax.local_devices()[:args.replicas])
    wb = fd.weight_bytes()
    topo = (f"{split[0]} prefill + {split[1]} decode, disaggregated"
            if split else f"{args.replicas}x data-parallel")
    print(f"fleet [{topo}; router {args.router}]: "
          f"{wb['copies']} weight cop{'y' if wb['copies'] == 1 else 'ies'} "
          f"resident ({wb['total_bytes']/2**20:.2f} MiB, "
          f"mode {wb['mode']})")
    print("  replica devices: " + ", ".join(
        f"r{h.idx} {h.device}" for h in fd.replicas))
    n = args.traffic if args.traffic > 0 else args.batch
    prefix_kw = {}
    if kv_spec is not None and kv_spec.sharing:
        prefix_kw = dict(prefix_pool=2, prefix_len=kv_prefix_len)
    trace = poisson_trace(n, rate_per_s=100.0, prompt_max=args.prompt_len,
                          gen_max=args.gen, vocab=cfg.vocab, seed=0,
                          **prefix_kw)
    t0 = time.monotonic()
    replay_fleet(fd, trace, shed_on_full=True, threaded=threaded)
    span = time.monotonic() - t0
    fin = fd.finished
    n_shed = len(fd.shed)
    if not fin:
        print(f"fleet: no requests completed ({n_shed} shed)")
        return fd
    toks = sum(len(r.output) for r in fin)
    ttft = [r.ttft_s for r in fin]
    lat = [r.latency_s for r in fin]
    per_replica = ", ".join(
        f"r{h.idx}[{h.state.name.lower()}] "
        f"{sum(len(r.output) for r in h.engine.finished)} tok"
        for h in fd.replicas)
    print(f"fleet serve: {len(fin)}/{n} requests"
          + (f" ({n_shed} shed)" if n_shed else "")
          + f", {toks} tok in {span:.2f}s = "
          f"{toks/max(span, 1e-9):.1f} tok/s aggregate")
    print(f"  per replica: {per_replica}")
    print(f"  ttft p50 {percentile(ttft, 50)*1e3:.0f}ms "
          f"p99 {percentile(ttft, 99)*1e3:.0f}ms "
          f"(+{load_metrics['decode_load_s']:.2f}s weight load) | "
          f"latency p50 {percentile(lat, 50)*1e3:.0f}ms "
          f"p99 {percentile(lat, 99)*1e3:.0f}ms | {fd.n_steps} fleet steps")
    if fd.handoff is not None:
        print(f"  handoff: {fd.handoff.n_handoffs} prefill->decode "
              f"payloads, {fd.handoff.bytes_on_wire/2**10:.1f} KiB "
              f"entropy-coded on the wire")
    return fd


if __name__ == "__main__":
    sys.exit(main())
