"""Batched serving engine with the EntroLLM weight path.

Pipeline (paper Alg. 1 EDGE DEVICE OPERATIONS, pod-scale):

  1. **Load**: the engine takes a :class:`core.store.CompressedModel`
     (entropy-coded container).  Weights are parallel-decoded ONCE per engine
     start — the analogue of the paper's once-per-sequence decode, amortized
     over every request the engine ever serves.  The default load path
     *streams*: the :class:`~repro.core.scheduler.DecodeScheduler` feeds
     fixed-budget chunks (embedding first) through a pluggable decoder
     backend with double-buffered prefetch, so host memory stays bounded and
     the first weights are resident long before the last chunk decodes
     (``time_to_first_weight_s`` in the load metrics).
  2. **Residency**: decoded weights stay *quantized* (uint8 symbols + scale +
     zero as :class:`models.layers.QT` triples) in HBM; dequantization fuses
     into each consuming matmul.  HBM traffic per decode step is 1 byte/param
     (uint8) or 0.5 (packed uint4) instead of 2 (bf16) — the bandwidth saving
     the paper measures on Jetson, realized on the TPU memory roofline.
  3. **Serve**: `prefill` then repeated `decode_step`, both jitted with the
     serve shardings; sampling is greedy or temperature-categorical.

``serve_step`` (single decode step) is the function the dry-run lowers for
decode-shape roofline cells.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.spec import quantizable_shape as _quantizable_shape
from repro.core.store import _DEFAULT_CHUNK, CompressedModel
from repro.models import api
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

# historical ad-hoc metric-dict keys -> canonical registry gauge names (the
# deprecated read-through surface Engine.generate keeps alive; the catalog
# lives in docs/OBSERVABILITY.md)
_LEGACY_GENERATE_KEYS = {
    "prefill_s": "serve.prefill_s",
    "decode_s": "serve.decode_s",
    "ttft_s": "serve.ttft_s",
    "decode_tok_per_s": "serve.decode_tok_per_s",
    "e2e_tok_per_s": "serve.e2e_tok_per_s",
    "tok_per_s": "serve.decode_tok_per_s",     # legacy alias of the alias
}


def _fence(x: Any) -> None:
    """Block on ``x`` when the active tracer asked for fenced spans
    (``--trace-sync``): JAX dispatch is asynchronous, so without a fence a
    span around a jitted call measures dispatch, not compute.  No-op (and
    no device sync) in every other configuration — tracing stays a pure
    observer of the async pipeline by default."""
    if obs_trace.sync_enabled():
        jax.block_until_ready(x)


@dataclasses.dataclass
class ServeConfig:
    max_len: int = 2048
    temperature: float = 0.0           # 0 => greedy
    unroll: int = 1
    q_block: int = 0
    quantized_weights: bool = True     # keep QT triples in HBM (EntroLLM mode)


def serve_mesh_rules(cfg: ArchConfig, mesh) -> "Any":
    """The default rule profile for the multi-device serving path: serve
    rules (weights TP over model + FSDP over data, cache batch/slot over
    data) with the KV-head divisibility adjustment."""
    from repro.distributed import sharding as shd
    return shd.arch_rules(cfg, mesh, shd.serve_rules(mesh))


def make_param_placer(cfg: ArchConfig, mesh, rules=None) -> Callable:
    """``(name, host_value) -> placed device value`` for the streaming load.

    Each decoded tensor is ``jax.device_put`` onto the serve mesh the moment
    it leaves the decoder — placement overlaps the prefetch-decode of the
    next chunk exactly like the single-device transfer did, so sharded
    serving keeps the bounded-host-memory property of the streaming loader.
    QT/QT4 triples get consistent q/scale/zero shardings
    (:func:`repro.distributed.sharding.leaf_shardings`); names the schema
    does not know replicate.

    Default layout (``rules=None``): per-tensor output-channel TP
    (:func:`repro.distributed.sharding.serve_tp_table`) for the families the
    exact-TP serving constraints cover (dense, moe) — the bit-identical
    profile the multi-device suite asserts; other families keep weights
    replicated (batch/cache still shard over data).  Pass an explicit
    ``rules`` profile to override both.
    """
    from repro.distributed import sharding as shd
    from jax.sharding import NamedSharding, PartitionSpec as P
    axes = api.param_specs(cfg)
    exact_tp = rules is None and cfg.family in ("dense", "moe")
    replicate_all = shd.Rules({})
    rep = NamedSharding(mesh, P())

    def place(name: str, val: Any) -> Any:
        if name in axes:
            r = (shd.serve_tp_table(cfg, mesh, axes[name]) if exact_tp
                 else (rules if rules is not None else replicate_all))
            sh = shd.leaf_shardings(axes[name], val, r, mesh)
        else:
            sh = jax.tree.map(lambda _: rep, val)
        return jax.device_put(val, sh)

    return place


def per_device_bytes(tree) -> Dict[str, int]:
    """Resident bytes per device for a placed pytree (the sharded-serving
    analogue of the paper's weight-footprint accounting)."""
    out: Dict[str, int] = {}
    for leaf in jax.tree.leaves(tree):
        for sh in getattr(leaf, "addressable_shards", ()):
            key = str(sh.device)
            out[key] = out.get(key, 0) + sh.data.nbytes
    return out


def load_params_from_compressed(model: CompressedModel, *,
                                quantized: bool = True,
                                pack_int4: bool = True,
                                backend: Optional[str] = None,
                                chunk_symbols: Optional[int] = _DEFAULT_CHUNK,
                                stream: bool = True,
                                placer: Optional[Callable] = None,
                                metrics: Optional[dict] = None) -> Dict[str, Any]:
    """Decode the container into serving weights, streaming by default.

    quantized=True  -> {name: QT(q, scale, zero)} + fp32 leftovers (EntroLLM
                       path); 4-bit containers pack nibble pairs into QT4
                       (0.5 bytes/param resident) unless ``pack_int4=False``
    quantized=False -> dense fp32 weights (baseline path)

    ``stream=True`` consumes :meth:`CompressedModel.iter_decode` chunk by
    chunk: host memory stays bounded by the scheduler's chunk budget
    (``chunk_symbols``; ``None`` = one monolithic chunk, same convention as
    the scheduler), the embedding is scheduled first, and each tensor's
    device transfer overlaps the prefetch-decode of the next chunk.
    ``stream=False`` recovers the monolithic ``decode_all`` batch.
    ``backend`` is a decoder-registry name (``numpy`` / ``jax`` / ``pallas``
    / ``pallas-interpret``; None = auto) and is honored on both paths.

    ``placer`` overrides how a decoded host tensor becomes a device tensor:
    ``(name, host_value) -> device value`` — :func:`make_param_placer` builds
    the multi-device one (``jax.device_put`` with the serve-rule shardings at
    load-stream time); the default is a plain single-device transfer.

    When a ``metrics`` dict is passed it is filled with
    ``time_to_first_weight_s`` (start -> first decoded tensor resident),
    ``decode_load_s`` (total), and the resolved ``decode_backend`` name.
    """
    from repro.core.decode_backends import get_backend
    from repro.models.layers import pack_qt
    t0 = time.perf_counter()
    ttfw: Optional[float] = None
    resolved = get_backend(backend)
    place = placer if placer is not None else \
        (lambda _name, v: jax.tree.map(jnp.asarray, v))

    if stream:
        kw = dict(backend=resolved, first=("embed",),
                  chunk_symbols=chunk_symbols)
        pairs = (model.iter_dequantize(**kw) if not quantized
                 else model.iter_quantized_weights(**kw))
    elif quantized:
        pairs = iter(model.quantized_weights(backend=resolved).items())
    else:
        pairs = iter(model.dequantize_all(backend=resolved).items())

    out: Dict[str, Any] = {}
    with obs_trace.span("load.stream", cat="load", backend=resolved.name,
                        stream=stream, quantized=quantized):
        if quantized:
            for k, v in model.unquantized.items():
                out[k] = place(k, v)
        for name, val in pairs:
            if quantized and name in model.qmeta:
                q, scale, zero = val
                bits = model.qmeta[name]["bits"]
                if (not _quantizable_shape(name, model.tensors[name].shape)
                        or model.qmeta[name]["granularity"] == "per_group"):
                    # Two cases the fused dequant-matmul path cannot host, so
                    # dequantize at load instead of packing a QT struct:
                    # * norm scales / biases / sensitive params (quantized
                    #   via an explicit spec rule) — model layers consume
                    #   plain arrays;
                    # * per-group quantization — the (…, D/group, 1) scale
                    #   does not broadcast against the (…, D) weight in the
                    #   kernels.
                    out[name] = place(name, model._dequantize_one(name, q))
                else:
                    out[name] = place(name, pack_qt(q, scale, zero, bits=bits,
                                                    pack_int4=pack_int4))
            else:
                out[name] = place(name, val)
            if ttfw is None:
                jax.block_until_ready(jax.tree.leaves(out[name]))
                ttfw = time.perf_counter() - t0
        jax.block_until_ready(jax.tree.leaves(out))
    load_s = time.perf_counter() - t0
    # registry is canonical (stable names); the caller's dict keeps the
    # historical keys as a deprecated alias surface
    obs_metrics.gauge("load.decode_load_s").set(load_s)
    obs_metrics.gauge("load.time_to_first_weight_s").set(
        ttfw if ttfw is not None else 0.0)
    obs_metrics.counter("load.decodes").inc(backend=resolved.name)
    if metrics is not None:
        metrics["time_to_first_weight_s"] = ttfw if ttfw is not None else 0.0
        metrics["decode_load_s"] = load_s
        metrics["decode_backend"] = resolved.name
    return out


def sample(logits: jax.Array, key: jax.Array, temperature: float,
           vocab: Optional[int] = None) -> jax.Array:
    """Greedy or temperature-categorical pick from the last position.
    ``vocab`` drops the padded-vocabulary columns (``cfg.padded_vocab()``
    rounds the lm head up), so no id outside the vocabulary is emitted."""
    last = logits[:, -1, :vocab]
    if temperature <= 0.0:
        return jnp.argmax(last, axis=-1).astype(jnp.int32)
    return jax.random.categorical(key, last / temperature).astype(jnp.int32)


class ServeSteps:
    """Jitted per-architecture step functions — the ONE set of compiled
    closures every serving front end drives.

    :class:`Engine` (lockstep single batch) and
    :class:`repro.serving.batching.ContinuousEngine` (slot batch) are both
    thin clients of this object: prefill, decode, and (for attention-cache
    families) chunked prefill are jitted here once, so the two engines can
    never drift numerically and a model warm in one is warm in the other.
    ``decode_fn`` accepts ``pos`` as a scalar (lockstep) or a ``(B,)`` array
    (per-slot ragged positions) — same callable, two traced shapes.
    ``decode_fn`` and ``prefill_chunk_fn`` take the paged pool's block
    table as an optional fifth argument (docs/KV_CACHE.md).

    Multi-device: pass ``mesh`` (and optionally ``rules``) and the steps
    carry the serve sharding profile — engines call :meth:`cache_shardings`
    to pin their KV cache (lockstep batch layout or the continuous-batching
    slot pool) onto the mesh; params arrive already placed by the streaming
    loader (:func:`make_param_placer`), and GSPMD propagates the
    tensor-parallel layout through the jitted steps from the operand
    shardings alone.

    Residency: ``resident="dense"`` (default) jits the whole-tree step
    functions — ``params`` is the decoded pytree and every layer's weights
    are in HBM for the scan to slice.  ``resident="compressed"`` swaps the
    step callables for per-layer *drivers*: ``params`` must then be a
    :class:`repro.serving.resident.CompressedResidentWeights`, and each step
    loops the layers in execution order, materializing layer ``l``'s QT
    triples just before its block (the next layer's entropy decode runs on a
    worker thread underneath the asynchronously dispatched compute).  The
    drivers keep the step-function signatures, so :class:`Engine` and
    :class:`~repro.serving.batching.ContinuousEngine` drive either mode
    unchanged — and greedy decode is bit-identical between the two (the
    per-layer loops jit the very functions the scans call; see
    docs/SERVING.md §"Compressed-resident serving").  Compressed residency
    is single-device today (``mesh`` must stay None): per-layer decode
    targets the bandwidth-bound single-accelerator regime the paper
    measures, while multi-device hosts shard *decoded* weights
    (ARCHITECTURE.md §6).
    """

    def __init__(self, cfg: ArchConfig, sc: ServeConfig,
                 *, shardings: Optional[dict] = None,
                 mesh=None, rules=None, resident: str = "dense"):
        if resident not in ("dense", "compressed"):
            raise ValueError(f"resident must be 'dense' or 'compressed', "
                             f"got {resident!r}")
        self.cfg = cfg
        self.sc = sc
        self.mod = api.build(cfg)
        self.resident = resident
        self.mesh = mesh
        self.rules = None
        self._cache_shardings_memo: dict = {}
        if resident == "compressed":
            if mesh is not None:
                raise NotImplementedError(
                    "compressed-resident serving is single-device (see "
                    "docs/SERVING.md §\"Which mode when\"); drop mesh= or "
                    "use resident='dense'")
            if not api.has_cached_step(cfg):
                raise NotImplementedError(
                    f"family {cfg.family!r} does not implement the per-layer "
                    f"weight-slot contract (embed / cached_layer); "
                    f"supported today: dense, moe")
            self._build_resident_steps()
            return
        if mesh is not None:
            self.rules = rules if rules is not None \
                else serve_mesh_rules(cfg, mesh)

        kw = {}
        if shardings:
            kw["in_shardings"] = shardings.get("in")
            kw["out_shardings"] = shardings.get("out")

        scoped = self._scoped_tracer()

        def _prefill(params, prompt):
            return self.mod.prefill(cfg, params, prompt, max_len=sc.max_len,
                                    unroll=sc.unroll, q_block=sc.q_block)

        def _decode(params, token, cache, pos, *table):
            # table: the paged pool's block table, for the families whose
            # decode_step takes one
            return self.mod.decode_step(cfg, params, token, cache, pos, *table,
                                        unroll=sc.unroll)

        self.prefill_fn = jax.jit(scoped(_prefill), **kw)
        self.decode_fn = jax.jit(scoped(_decode), donate_argnums=(2,))
        self.prefill_chunk_fn = None
        if api.has_cached_step(cfg):
            def _chunk(params, tokens, cache, pos, table=None):
                return self.mod.prefill_chunk(cfg, params, tokens, cache, pos,
                                              table, unroll=sc.unroll)

            self.prefill_chunk_fn = jax.jit(scoped(_chunk), donate_argnums=(2,))

    # ------------------------------------------------- compressed residency
    def _build_resident_steps(self) -> None:
        """Per-layer jitted pieces + Python drivers (compressed residency).

        The family's own step pieces, jitted one by one: embed, head (and
        its last-position form for prefill), `forward`'s per-layer body, the
        cached per-layer body shared by decode and chunked prefill, and the
        prefill cache write (``kv.store`` at row 0).  One trace of the
        cached body serves every layer (``l`` is a traced scalar) and every
        front end (S comes from the token shape).  The loops below stitch
        them together around the weight store's prefetch/get double buffer.
        """
        from repro.models import kv
        cfg, sc, mod = self.cfg, self.sc, self.mod

        def _pblock(lp, x, positions):
            x, kv_rows, _aux = mod.prefill_layer(
                cfg, lp, x, positions, q_block=sc.q_block, unroll=sc.unroll)
            return x, kv_rows

        self._embed_fn = jax.jit(partial(mod.embed, cfg))
        self._head_fn = jax.jit(partial(mod.head, cfg))
        self._head_last_fn = jax.jit(partial(mod.head, cfg, last_only=True))
        self._pblock_fn = jax.jit(_pblock)
        self._rblock_fn = jax.jit(partial(mod.cached_layer, cfg),
                                  donate_argnums=(2,))
        self._write_fn = jax.jit(kv.store, donate_argnums=(0,))
        self.prefill_fn = self._resident_prefill
        self.decode_fn = self._resident_step
        self.prefill_chunk_fn = self._resident_step

    def _resident_prefill(self, weights, prompt):
        """Per-layer form of the jitted whole-tree ``prefill``: full causal
        attention per layer, each layer's (k, v) written into the
        zero-padded cache row as it is produced."""
        B, S = prompt.shape
        x = self._embed_fn(weights.globals, prompt)
        positions = jnp.arange(S)
        cache = self.mod.init_cache(self.cfg, B, self.sc.max_len)
        weights.prefetch(0)
        for l in range(weights.n_layers):
            with obs_trace.span("serve.layer", layer=l, phase="prefill"):
                lp = weights.get(l)
                weights.prefetch((l + 1) % weights.n_layers)
                x, (k, v) = self._pblock_fn(lp, x, positions)
                cache = self._write_fn(cache, k, v, jnp.int32(l),
                                       jnp.int32(0))
                _fence(x)
        return self._head_last_fn(weights.globals, x), cache

    def _resident_step(self, weights, tokens, cache, pos, table=None):
        """Per-layer form of ``decode_step`` AND ``prefill_chunk`` (the cached
        layer body reads S from the token shape).

        The overlap: ``get(l)`` returns layer l's slot (usually already
        decoded by the worker), ``prefetch(l+1)`` kicks off the next
        layer's entropy decode, and the jitted block dispatches
        asynchronously — so layer l+1 decodes on the worker thread while
        layer l's matmuls run.  The wrap-around prefetch primes layer 0 for
        the next step.
        """
        x = self._embed_fn(weights.globals, tokens)
        positions = self.mod.step_positions(pos, tokens.shape[1])
        weights.prefetch(0)
        for l in range(weights.n_layers):
            with obs_trace.span("serve.layer", layer=l, phase="step"):
                lp = weights.get(l)
                weights.prefetch((l + 1) % weights.n_layers)
                x, cache = self._rblock_fn(lp, x, cache, jnp.int32(l), pos,
                                           positions, table)
                _fence(x)
        return self._head_fn(weights.globals, x), cache

    def _scoped_tracer(self) -> Callable:
        """Identity on one device.  With a mesh: wrap each step body so its
        TRACE runs under the ambient mesh + exact-TP sharding hints — the
        model's ``constrain_replicated``/``constrain_heads`` hooks fire only
        inside these closures, and the process-global hints are restored
        afterwards so co-resident training/lowering traces never see them."""
        if self.mesh is None:
            return lambda fn: fn
        from repro.distributed.ctx import ShardingHints, get_hints, set_hints
        # exact profile: weights gathered at use (layers.gather_weight), NO
        # activation constraints — every compute op keeps reference shapes,
        # which is what makes sharded greedy decode bit-identical
        hints = ShardingHints(
            mesh=self.mesh, batch_axes=(), model_axis=None,
            kv_seq_axes=(), seq_sp=False, exact_tp=True)

        def scoped(fn):
            def run(*args):
                prev = get_hints()
                set_hints(hints)
                try:
                    # no ambient-mesh context needed: every constraint the
                    # hints drive builds an explicit NamedSharding from
                    # hints.mesh
                    return fn(*args)
                finally:
                    set_hints(prev)
            return run

        return scoped

    def cache_shardings(self, batch: int, *, layout: str = "batch",
                        **cache_kw) -> Optional[dict]:
        """NamedShardings for this config's cache pytree on the serve mesh
        (None when the steps are single-device).  Memoized — resolution runs
        an eval_shape trace of the cache, and ``Engine.generate`` asks once
        per call on the serving hot path."""
        if self.mesh is None:
            return None
        key = (batch, layout, tuple(sorted(cache_kw.items())))
        if key not in self._cache_shardings_memo:
            from repro.distributed import sharding as shd
            self._cache_shardings_memo[key] = shd.cache_shardings(
                self.cfg, self.mesh, self.rules, batch, self.sc.max_len,
                layout=layout, **cache_kw)
        return self._cache_shardings_memo[key]


class Engine:
    """Lockstep serving: one fixed-shape batch per ``generate`` call.

    A thin single-request-batch client of :class:`ServeSteps` — for
    concurrent, independently-arriving requests use
    :class:`repro.serving.batching.ContinuousEngine`, which drives the same
    step functions with a slot batch.

    ``resident="compressed"`` serves straight from the entropy-coded
    container: pass a :class:`repro.serving.resident.
    CompressedResidentWeights` as ``params`` (docs/SERVING.md
    §"Compressed-resident serving").
    """

    def __init__(self, cfg: ArchConfig, params: Dict[str, Any], sc: ServeConfig,
                 *, shardings: Optional[dict] = None,
                 mesh=None, rules=None,
                 steps: Optional[ServeSteps] = None,
                 resident: str = "dense"):
        self.cfg = cfg
        self.params = params
        self.sc = sc
        self.steps = steps if steps is not None else \
            ServeSteps(cfg, sc, shardings=shardings, mesh=mesh, rules=rules,
                       resident=resident)
        self.mod = self.steps.mod
        self.prefill_fn = self.steps.prefill_fn      # backwards-compat aliases
        self.decode_fn = self.steps.decode_fn

    def generate(self, prompt, steps: int, *, key: Optional[jax.Array] = None,
                 echo_metrics: bool = False):
        """prompt: (B, S) int32 tokens — or the batch dict for encdec."""
        key = key if key is not None else jax.random.PRNGKey(0)
        t0 = time.perf_counter()
        with obs_trace.span("serve.prefill"):
            logits, cache = self.prefill_fn(self.params, prompt)
            logits.block_until_ready()
        t_prefill = time.perf_counter() - t0

        if isinstance(prompt, dict):
            S = prompt["tokens"].shape[1]
            B = prompt["tokens"].shape[0]
        else:
            B, S = prompt.shape
        if self.steps.mesh is not None:
            # pin the cache layout once per generate: propagation out of
            # prefill is free to pick any layout, the decode loop then runs
            # against the deterministic serve-rule shardings
            cache = jax.device_put(cache, self.steps.cache_shardings(B))
        toks = []
        # one fresh split per sampled token, including token 0 — sampling the
        # first token from the parent key and then re-splitting that same key
        # in the loop would correlate token 0 with token 1
        key, sub = jax.random.split(key)
        tok = sample(logits, sub, self.sc.temperature, self.cfg.vocab)[:, None]
        tok.block_until_ready()
        t_first_token = time.perf_counter() - t0
        toks.append(tok)
        t1 = time.perf_counter()
        step_hist = obs_metrics.histogram("serve.decode_step_s")
        self.last_step_times: list = []
        for i in range(steps - 1):
            ts = time.perf_counter()
            with obs_trace.span("serve.decode_step", step=i):
                key, sub = jax.random.split(key)
                logits, cache = self.decode_fn(self.params, tok, cache,
                                               jnp.int32(S + i))
                tok = sample(logits, sub, self.sc.temperature,
                             self.cfg.vocab)[:, None]
                toks.append(tok)
                _fence(tok)
            # without --trace-sync each step time is host dispatch (plus any
            # resident decode waits), not device compute — the loop-level
            # t_decode below is fenced and authoritative either way
            dt = time.perf_counter() - ts
            self.last_step_times.append(dt)
            step_hist.observe(dt)
        out = jnp.concatenate(toks, axis=1)
        out.block_until_ready()
        t_decode = time.perf_counter() - t1
        # t_decode covers the steps-1 loop tokens only (token 0 rides on
        # the prefill timing), so the two rates are reported separately
        # instead of pretending one number covers both
        decode_tps = B * max(steps - 1, 1) / max(t_decode, 1e-9)
        e2e_tps = B * steps / max(time.perf_counter() - t0, 1e-9)
        obs_metrics.gauge("serve.prefill_s").set(t_prefill)
        obs_metrics.gauge("serve.decode_s").set(t_decode)
        obs_metrics.gauge("serve.ttft_s").set(t_first_token)
        obs_metrics.gauge("serve.decode_tok_per_s").set(decode_tps)
        obs_metrics.gauge("serve.e2e_tok_per_s").set(e2e_tps)
        obs_metrics.counter("serve.tokens").inc(B * steps)
        if echo_metrics:
            return out, obs_metrics.LegacyMetricsView(
                obs_metrics.default_registry(), _LEGACY_GENERATE_KEYS)
        return out


def make_serve_step(cfg: ArchConfig, sc: ServeConfig) -> Callable:
    """The decode-shape dry-run target: one token against a full KV cache."""
    mod = api.build(cfg)

    def serve_step(params, token, cache, pos):
        return mod.decode_step(cfg, params, token, cache, pos, unroll=sc.unroll)

    return serve_step


def make_prefill_step(cfg: ArchConfig, sc: ServeConfig) -> Callable:
    mod = api.build(cfg)

    def prefill_step(params, prompt):
        return mod.prefill(cfg, params, prompt, max_len=sc.max_len,
                           unroll=sc.unroll, q_block=sc.q_block)

    return prefill_step
