"""Continuous-batching engine: slot-batched decode with mid-flight admission.

The scheduler loop (one ``step()``):

  1. **Admit** — if the admission pipeline is idle and a slot is free, the
     next queued request reserves the slot and starts chunk-prefilling
     through the shared ``ServeSteps.prefill_chunk_fn`` (fixed ``(1, chunk)``
     shape — ONE compile serves every prompt length) into a scratch cache.
     Under load the prefill advances at most ``admit_chunks_per_step``
     chunks per scheduler step (default 4), fused decode steps running in
     between — so in-flight requests pay a bounded slice of prefill latency
     per generated token, never a whole queued prompt; when nothing is
     decoding there is no lane to stall and the prefill drains to completion
     immediately.  On the last chunk the scratch
     rows are spliced into the reserved slot and the request's first token is
     sampled from the logit at its true last prompt position.  Because the
     compressed-weight load streams (PR 1), admission can start as soon as
     the embedding + early layers are resident — prefill of the first
     requests overlaps the tail of the weight decode.
  2. **Decode** — ONE fused ``decode_fn`` call advances every slot: ``pos``
     is the ``(B,)`` per-slot ``kv_len`` vector, so a request 3 tokens deep
     and one 300 tokens deep share the same jitted step (ragged attention via
     per-slot ``kv_len`` masking in ``models/layers.py``).
  3. **Detach** — slots whose request hit EOS or ``max_new_tokens`` are
     released (and their cache rows compacted) without stalling the batch;
     the freed slot is eligible for admission on the next step.

Inactive slots still ride through the fused step (their lane computes a
garbage token that is never read, and their row-0 cache write lands in freed
memory that the next ``insert`` overwrites) — wasted lanes are the price of a
single compiled shape, and they convert into admitted requests on the very
next step.

Numerics: the engine drives the SAME jitted step functions as the lockstep
:class:`~repro.serving.engine.Engine`, and per-slot masking makes each lane
independent of its neighbors, so a request's greedy tokens are bit-identical
whether it runs alone through ``Engine.generate`` or packed in a slot batch
(asserted by ``tests/test_continuous_batching.py`` and the traffic
benchmark).  One carve-out: MoE dispatch capacity is shared across the
batch, so bit-identity additionally needs ``capacity_factor >= num_experts /
top_k`` (no token ever drops) — ``__init__`` warns when a config falls
short.
"""
from __future__ import annotations

import time
from functools import partial
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.spec import KVCompressionSpec
from repro.models import api
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from ..engine import ServeConfig, ServeSteps, _fence, sample
from ..kvcache import BlockKVManager
from .queue import RequestQueue
from .request import Request, RequestState, SamplingParams
from .slots import SlotBatchManager


@partial(jax.jit, static_argnames="vocab")
def _sample_slots(logits, keys, temps, vocab):
    """Per-slot sampling with per-request PRNG streams.

    logits: (B, 1, V); keys: (B, 2) uint32 (one stream per slot, split fresh
    every step); temps: (B,) f32 — greedy lanes (temp <= 0) ignore their key;
    columns from ``vocab`` on (the padded vocabulary) are never sampled.
    Returns (tokens (B,), advanced keys (B, 2)).
    """
    last = logits[:, -1, :vocab]
    greedy = jnp.argmax(last, axis=-1).astype(jnp.int32)
    ks = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
    cat = jax.vmap(
        lambda k, l, t: jax.random.categorical(k, l / jnp.maximum(t, 1e-6))
    )(ks[:, 1], last, temps).astype(jnp.int32)
    return jnp.where(temps > 0, cat, greedy), ks[:, 0]


class ContinuousEngine:
    """Serve concurrent, independently-arriving requests over one slot batch.

    Families must have the cached layer step (``api.has_cached_step``):
    dense and moe today; recurrent caches (ssm/hybrid/encdec) need
    family-specific slot state and raise.

    ``resident="compressed"`` serves the slot batch straight from the
    entropy-coded container — ``params`` must then be a
    :class:`repro.serving.resident.CompressedResidentWeights`, and the
    per-layer drivers replace the jitted whole-tree steps with identical
    numerics (docs/SERVING.md §"Compressed-resident serving").
    """

    def __init__(self, cfg: ArchConfig, params: Dict[str, Any],
                 sc: ServeConfig, *, n_slots: int = 8, max_queue: int = 64,
                 prefill_chunk: int = 32, admit_chunks_per_step: int = 4,
                 mesh=None, rules=None,
                 steps: Optional[ServeSteps] = None,
                 resident: str = "dense",
                 kv_spec: Optional[KVCompressionSpec] = None,
                 kv_blocks: Optional[int] = None,
                 handoff_sink=None):
        if not api.has_cached_step(cfg):
            raise NotImplementedError(
                f"family {cfg.family!r} does not implement the slot-batch "
                f"cache contract (prefill_chunk + per-slot decode positions);"
                f" supported today: dense, moe")
        if prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got {prefill_chunk}")
        if admit_chunks_per_step < 1:
            raise ValueError(f"admit_chunks_per_step must be >= 1, "
                             f"got {admit_chunks_per_step}")
        if cfg.moe is not None and \
                cfg.moe.capacity_factor * cfg.moe.top_k < cfg.moe.num_experts:
            # GShard capacity is shared across the batch, so a token that
            # routes fine solo can be DROPPED when packed with busy neighbors
            # — packing-dependent outputs.  cf >= E/top_k admits the worst
            # case (every token on one expert) and restores bit-identity.
            import warnings
            warnings.warn(
                f"{cfg.name}: moe capacity_factor={cfg.moe.capacity_factor} "
                f"< num_experts/top_k = "
                f"{cfg.moe.num_experts / cfg.moe.top_k:.2f}; expert overflow "
                f"under slot batching can drop tokens a solo run would keep, "
                f"so outputs may depend on batch packing (raise "
                f"capacity_factor to >= num_experts/top_k for bit-identical "
                f"serving)", stacklevel=2)
        if handoff_sink is not None and kv_spec is None:
            raise ValueError(
                "handoff_sink needs the paged KV cache (kv_spec): the "
                "disaggregated handoff ships block payloads, and only "
                "BlockKVManager implements export_blocks (docs/FLEET.md)")
        self.cfg = cfg
        self.params = params
        self.sc = sc
        # disaggregated prefill replicas: called as sink(engine, slot, req)
        # right after a request's prefill completes and its first token is
        # sampled; the sink must export_request() the slot (docs/FLEET.md)
        self.handoff_sink = handoff_sink
        self.steps = steps if steps is not None else \
            ServeSteps(cfg, sc, mesh=mesh, rules=rules, resident=resident)
        self.paged = kv_spec is not None
        if self.paged:
            # paged KV: the same step functions, given the block table
            # (docs/KV_CACHE.md)
            if self.steps.resident != "dense":
                raise NotImplementedError(
                    "paged KV needs the dense-residency whole-tree steps; "
                    "serve with resident='dense' (docs/KV_CACHE.md)")
            if self.steps.mesh is not None:
                raise NotImplementedError(
                    "paged KV is single-device today: the block table is a "
                    "host-side gather index with no sharding rule yet")
            self.slots: Any = BlockKVManager(
                cfg, n_slots, sc.max_len, spec=kv_spec, n_blocks=kv_blocks,
                prefill_chunk=prefill_chunk)
        else:
            self.slots = SlotBatchManager(cfg, n_slots, sc.max_len)
        if not self.paged and self.steps.mesh is not None:
            # the resident slot pool lives sharded on the serve mesh ("slot"
            # resolves like lockstep batch rows — serve_rules); the donating
            # _splice/_zero_slot helpers then keep that placement step over
            # step, and scratch prefill caches (batch 1, unshardable) splice
            # in through GSPMD without ever re-laying-out the pool
            self.slots.cache = jax.device_put(
                self.slots.cache,
                self.steps.cache_shardings(n_slots, layout="slot"))
        self.queue = RequestQueue(max_queue)
        self.prefill_chunk = prefill_chunk
        self.admit_chunks_per_step = admit_chunks_per_step
        self.finished: List[Request] = []
        self.n_decode_steps = 0
        self._prefilling: Optional[dict] = None   # in-flight admission state
        # per-slot device-step state (host mirrors; tiny, synced every step)
        self._tokens = np.zeros((n_slots,), np.int32)
        self._keys = np.zeros((n_slots, 2), np.uint32)
        self._temps = np.zeros((n_slots,), np.float32)

    # --------------------------------------------------------------- submit
    def submit(self, prompt, max_new_tokens: int, *,
               sampling: SamplingParams = SamplingParams(),
               eos_id: Optional[int] = None,
               deadline_s: Optional[float] = None) -> Request:
        """Queue one request (raises ``QueueFullError`` under backpressure)."""
        req = Request(prompt=np.asarray(prompt), max_new_tokens=max_new_tokens,
                      sampling=sampling, eos_id=eos_id, deadline_s=deadline_s)
        return self.submit_request(req)

    def submit_request(self, req: Request) -> Request:
        """External-admission hook: queue a pre-built :class:`Request`.

        The fleet router (``serving/fleet/router.py``) builds one Request at
        the fleet boundary and dispatches it to a replica through this seam,
        so rid / timestamps / sampling state stay with the same object across
        redrives.  Raises ``QueueFullError`` under backpressure and
        ``ValueError`` when the request cannot fit ``max_len`` on any step."""
        P = req.prompt_len
        chunks = -(-P // self.prefill_chunk) * self.prefill_chunk
        need = max(P + req.max_new_tokens, chunks)
        if need > self.sc.max_len:
            raise ValueError(
                f"request {req.rid} needs {need} cache rows (prompt {P} + "
                f"{req.max_new_tokens} new, prefill padded to {chunks}) but "
                f"max_len is {self.sc.max_len}")
        return self.queue.submit(req)

    # ------------------------------------------------------------ admission
    def _start_prefill(self, req: Request) -> None:
        """Reserve a slot and set up the chunked-prefill pipeline state."""
        with obs_trace.span("serve.admit", rid=req.rid):
            req.state = RequestState.PREFILLING
            req.t_admitted = time.monotonic()
            obs_metrics.histogram("queue.wait_s").observe(
                req.queue_wait_s or 0.0, outcome="admitted")
            P, chunk = req.prompt_len, self.prefill_chunk
            padded = -(-P // chunk) * chunk
            toks = np.zeros((1, padded), np.int32)
            toks[0, :P] = req.prompt
            if self.paged:
                # block admission: claim table entries (consuming shared
                # prefix blocks) and start prefill AFTER the shared region —
                # the block manager guarantees the final chunk (position
                # P-1) always runs
                got = self.slots.alloc(req)
                assert got is not None, "admission past can_admit"
                slot, skip = got
                self._prefilling = dict(req=req, slot=slot, toks=toks,
                                        c0=skip, last=None, scratch=None)
                return
            slot = self.slots.alloc(req)
            assert slot is not None, "admission with no free slot"
            self._prefilling = dict(
                req=req, slot=slot, toks=toks, c0=0, last=None,
                scratch=self.steps.mod.init_cache(self.cfg, 1,
                                                  self.sc.max_len))

    def _advance_prefill(self) -> None:
        """Run ONE prefill chunk; on the last chunk, splice the scratch rows
        into the reserved slot and sample the request's first token."""
        st = self._prefilling
        req, chunk = st["req"], self.prefill_chunk
        P, c0 = req.prompt_len, st["c0"]
        with obs_trace.span("serve.admit_chunk", rid=req.rid, c0=c0):
            toks = jnp.asarray(st["toks"][:, c0:c0 + chunk])
            pos = jnp.full((1,), c0, jnp.int32)
            if self.paged:
                bt = jnp.asarray(self.slots.table_rows([st["slot"]]))
                logits, self.slots.pool = self.steps.prefill_chunk_fn(
                    self.params, toks, self.slots.pool, pos, bt)
            else:
                logits, st["scratch"] = self.steps.prefill_chunk_fn(
                    self.params, toks, st["scratch"], pos)
            _fence(logits)
        if c0 <= P - 1 < c0 + chunk:
            st["last"] = logits[:, P - 1 - c0][:, None]     # (1, 1, V)
        st["c0"] = c0 + chunk
        if st["c0"] < st["toks"].shape[1]:
            return
        self._prefilling = None
        slot = st["slot"]
        with obs_trace.span("serve.first_token", rid=req.rid):
            if self.paged:
                self.slots.insert(slot, P)  # prefill wrote the pool in place
            else:
                self.slots.insert(slot, st["scratch"], P)
            key, sub = jax.random.split(jax.random.PRNGKey(req.sampling.seed))
            tok = int(sample(st["last"], sub, req.sampling.temperature,
                             self.cfg.vocab)[0])
            req.t_first_token = time.monotonic()
            obs_metrics.histogram("request.ttft_s").observe(req.ttft_s or 0.0)
            req.state = RequestState.DECODING
            req.output.append(tok)
            self._tokens[slot] = tok
            kd = key if key.dtype == jnp.uint32 else jax.random.key_data(key)
            self._keys[slot] = np.asarray(kd, np.uint32)
            self._temps[slot] = req.sampling.temperature
        if self._hit_stop(req, tok):
            self._detach(slot, req, tok)
        elif self.handoff_sink is not None:
            # disaggregated prefill replica: the request never decodes here —
            # the sink exports the slot's KV blocks + sampling lane and the
            # decode side continues from the exact same state
            self.handoff_sink(self, slot, req)

    def _decoding(self) -> List[int]:
        return [s for s, r in enumerate(self.slots.requests)
                if r is not None and r.state is RequestState.DECODING]

    # ------------------------------------------------------------- stepping
    def step(self) -> bool:
        """One scheduler iteration: advance admission by at most
        ``admit_chunks_per_step`` prefill chunks (to completion while nothing
        is decoding), then one fused decode step over every slot.  Returns
        False when idle (nothing queued, nothing prefilling, nothing
        decoding)."""
        with obs_trace.span("serve.step", step=self.n_decode_steps):
            return self._step_inner()

    def _step_inner(self) -> bool:
        progressed = False
        chunks = 0
        while True:
            if self._prefilling is None and self.slots.n_free:
                if self.paged:
                    # peek-then-plan: commit the pop only once the block
                    # manager can cover the head's whole allocation
                    head = self.queue.peek()
                    if head is not None and self.slots.can_admit(head):
                        self._start_prefill(self.queue.pop())
                else:
                    req = self.queue.pop()
                    if req is not None:
                        self._start_prefill(req)
            if self._prefilling is None:
                break
            self._advance_prefill()
            chunks += 1
            progressed = True
            if self._decoding() and chunks >= self.admit_chunks_per_step:
                break       # a batch is running: bounded stall, move on

        active = self._decoding()
        if not active:
            return progressed

        with obs_trace.span("serve.decode_dispatch", active=len(active)):
            pos = jnp.asarray(self.slots.kv_len)
            tok = jnp.asarray(self._tokens[:, None])
            if self.paged:
                bt = jnp.asarray(self.slots.decode_tables())
                logits, self.slots.pool = self.steps.decode_fn(
                    self.params, tok, self.slots.pool, pos, bt)
            else:
                logits, self.slots.cache = self.steps.decode_fn(
                    self.params, tok, self.slots.cache, pos)
            new_tok, new_keys = _sample_slots(logits, jnp.asarray(self._keys),
                                              jnp.asarray(self._temps),
                                              self.cfg.vocab)
        with obs_trace.span("serve.decode_sync"):
            new_tok = np.asarray(new_tok)
            self._keys = np.array(new_keys)  # copy: host mirror stays writable
        with obs_trace.span("serve.commit"):
            self.n_decode_steps += 1
            obs_metrics.counter("serve.tokens").inc(len(active))
            for s in active:
                self.slots.kv_len[s] += 1
                req = self.slots.requests[s]
                t = int(new_tok[s])
                req.output.append(t)
                self._tokens[s] = t
                if self._hit_stop(req, t):
                    self._detach(s, req, t)
        return True

    def run(self) -> List[Request]:
        """Drain queue + slots to completion; returns finished requests."""
        n0 = len(self.finished)
        while self.step():
            pass
        return self.finished[n0:]

    @property
    def has_work(self) -> bool:
        return bool(len(self.queue)) or self._prefilling is not None \
            or bool(self.slots.active)

    # ------------------------------------------------- fleet seams (export)
    def export_request(self, slot: int):
        """Detach ``slot``'s request mid-flight for a KV handoff.

        Returns ``(req, kv_len, blocks, lane)``: the request object, its
        committed KV length, the raw per-block pool leaves
        (``BlockKVManager.export_blocks``), and the sampling lane state
        ``(token, key, temp)`` a peer engine needs to continue decode from
        the exact device state this engine would have used.  The slot is
        released.  Paged engines only."""
        assert self.paged, "export_request needs the paged KV cache"
        req = self.slots.requests[slot]
        assert req is not None, f"export of free slot {slot}"
        kv_len = int(self.slots.kv_len[slot])
        blocks = self.slots.export_blocks(slot)
        lane = (int(self._tokens[slot]),
                np.array(self._keys[slot]),
                float(self._temps[slot]))
        self.slots.release(slot)
        self._tokens[slot] = 0
        self._keys[slot] = 0
        self._temps[slot] = 0.0
        return req, kv_len, blocks, lane

    def can_adopt(self, req: Request, kv_len: int, n_blocks: int) -> bool:
        """Probe for ``adopt_request`` (peek-then-adopt, like can_admit)."""
        assert self.paged, "can_adopt needs the paged KV cache"
        return self.slots.can_import(req, kv_len, n_blocks)

    def adopt_request(self, req: Request, kv_len: int, blocks, lane) -> bool:
        """Admit an externally prefilled request: install its KV blocks and
        sampling lane, then decode it like any local request.  Returns False
        (nothing changed) when no slot or not enough pool blocks are free —
        the handoff coordinator retries on a later pump.  Paged engines
        only."""
        assert self.paged, "adopt_request needs the paged KV cache"
        slot = self.slots.import_blocks(req, kv_len, blocks)
        if slot is None:
            return False
        tok, key, temp = lane
        req.state = RequestState.DECODING
        self._tokens[slot] = tok
        self._keys[slot] = np.asarray(key, np.uint32)
        self._temps[slot] = temp
        return True

    def evacuate(self) -> List[Request]:
        """Strip every unfinished request off the engine, oldest first.

        The failed-replica redrive path: the fleet driver marks a replica
        FAILED, evacuates it, resets each request (``Request.requeue``) and
        re-enqueues them at the fleet intake — nothing is lost, nothing is
        duplicated.  Queued, mid-prefill, and decoding requests are all
        harvested; the engine is left empty but serviceable."""
        out: List[Request] = []
        while True:
            r = self.queue.pop()    # lazy-expires overdue heads in passing
            if r is None:
                break
            out.append(r)
        # a mid-prefill request already occupies its reserved slot
        # (alloc registered it in slots.requests), so the slot sweep below
        # harvests it; only the pipeline state needs clearing here
        self._prefilling = None
        for s, r in enumerate(list(self.slots.requests)):
            if r is not None:
                self.slots.release(s)
                self._tokens[s] = 0
                self._keys[s] = 0
                self._temps[s] = 0.0
                out.append(r)
        out.sort(key=lambda r: (r.t_arrival if r.t_arrival is not None
                                else float("inf"), r.rid))
        return out

    # -------------------------------------------------------------- private
    @staticmethod
    def _hit_stop(req: Request, tok: int) -> bool:
        return (req.eos_id is not None and tok == req.eos_id) \
            or len(req.output) >= req.max_new_tokens

    def _detach(self, slot: int, req: Request, tok: int) -> None:
        req.finish_reason = "eos" \
            if (req.eos_id is not None and tok == req.eos_id) else "length"
        req.state = RequestState.FINISHED
        req.t_finished = time.monotonic()
        self.slots.release(slot)
        self._tokens[slot] = 0
        self._keys[slot] = 0
        self._temps[slot] = 0.0
        self.finished.append(req)
        obs_metrics.histogram("request.latency_s").observe(req.latency_s or 0.0)
        obs_metrics.counter("requests.finished").inc(reason=req.finish_reason)
        # lifecycle record built from the Request's own monotonic stamps
        # (same clock Lifecycle uses), so the chain is exact, not re-measured
        lc = obs_metrics.lifecycle(req.rid, outcome=req.finish_reason,
                                   tokens=len(req.output))
        for name, t in (("queued", req.t_arrival),
                        ("admitted", req.t_admitted),
                        ("first_token", req.t_first_token),
                        ("done", req.t_finished)):
            if t is not None:
                lc.event(name, t)
